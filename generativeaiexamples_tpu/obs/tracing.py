"""OpenTelemetry tracing spine, gated by ``ENABLE_TRACING``.

Parity with the reference's tracing modules:
- chain-server side extracts W3C traceparent from incoming request headers
  and wraps handlers in spans (reference: common/tracing.py:51-69);
- client side injects the current context into outgoing headers
  (reference: frontend/frontend/tracing.py:47-63).

When tracing is disabled (the default) every helper degrades to a no-op —
zero overhead, no SDK initialization, same as the reference's
``if not enabled`` fallthrough wrappers.

Enablement is evaluated PER CALL, not frozen at import: ``enabled()``
reads the env each time unless ``set_enabled()`` installed an override —
so config-file-driven ``tracing.enabled`` and tests toggling tracing
work without a module reimport, and ``enabled()`` / ``inject_context`` /
``event_span`` / ``instrumented`` all agree on the same check.
"""

from __future__ import annotations

import functools
import os
import time
from contextlib import contextmanager
from typing import Any, Optional

from . import metrics as _metrics

_enabled_override: Optional[bool] = None
_tracer = None


def enabled() -> bool:
    if _enabled_override is not None:
        return _enabled_override
    return os.environ.get("ENABLE_TRACING", "").lower() in ("1", "true",
                                                            "yes")


def set_enabled(value: Optional[bool]) -> None:
    """Force tracing on/off at runtime (config-file wiring, tests);
    ``None`` restores the ``ENABLE_TRACING`` env check."""
    global _enabled_override
    _enabled_override = value


def _get_tracer():
    """Lazy tracer init (service name 'chain-server' like the reference,
    common/tracing.py:32-48; OTLP endpoint from the standard env var).
    Returns None whenever tracing is off — a tracer initialized by an
    earlier enablement does not leak spans after set_enabled(False)."""
    global _tracer
    if not enabled():
        return None
    if _tracer is None:
        from opentelemetry import trace
        try:
            from opentelemetry.sdk.resources import Resource
            from opentelemetry.sdk.trace import TracerProvider
            from opentelemetry.sdk.trace.export import (BatchSpanProcessor,
                                                        ConsoleSpanExporter)

            service = os.environ.get("OTEL_SERVICE_NAME", "chain-server")
            provider = TracerProvider(
                resource=Resource.create({"service.name": service}))
            endpoint = os.environ.get("OTEL_EXPORTER_OTLP_ENDPOINT")
            if endpoint:
                try:
                    from opentelemetry.exporter.otlp.proto.grpc \
                        .trace_exporter import OTLPSpanExporter
                    provider.add_span_processor(BatchSpanProcessor(
                        OTLPSpanExporter(endpoint=endpoint)))
                except ImportError:
                    provider.add_span_processor(
                        BatchSpanProcessor(ConsoleSpanExporter()))
            trace.set_tracer_provider(provider)
        except ImportError:
            # api-only install: the global provider yields non-recording
            # spans — tracing stays wired but exports nothing.
            pass
        _tracer = trace.get_tracer("generativeaiexamples_tpu")
    return _tracer


@contextmanager
def server_span(name: str, headers: Optional[dict] = None,
                attributes: Optional[dict] = None):
    """Span with remote parent extracted from W3C headers
    (reference: common/tracing.py:56-58)."""
    tracer = _get_tracer()
    if tracer is None:
        yield None
        return
    from opentelemetry import trace
    from opentelemetry.propagate import extract
    ctx = extract(dict(headers or {}))
    with tracer.start_as_current_span(
            name, context=ctx, kind=trace.SpanKind.SERVER,
            attributes=attributes or {}) as span:
        yield span


def inject_context(headers: Optional[dict] = None) -> dict:
    """Inject current trace context into outgoing headers
    (reference: frontend/tracing.py:47-63)."""
    headers = dict(headers or {})
    if enabled():
        from opentelemetry.propagate import inject
        inject(headers)
    return headers


def instrumented(name: str):
    """Decorator for aiohttp handlers: wraps in a server span carrying the
    request's W3C context (reference: common/tracing.py:51-69
    ``instrumentation_wrapper``). No-op (identity passthrough of the
    handler's own behavior) when tracing is off."""
    def deco(handler):
        @functools.wraps(handler)
        async def wrapper(request, *args: Any, **kwargs: Any):
            if not enabled():
                return await handler(request, *args, **kwargs)
            with server_span(name, headers=request.headers,
                             attributes={"http.route": str(request.rel_url)}):
                return await handler(request, *args, **kwargs)
        return wrapper
    return deco


def record_stage(name: str, seconds: float) -> None:
    """Report one stage duration to the bound request timeline
    (obs/flight.py) and the labeled ``engine_stage_seconds`` histogram
    (obs/metrics.py observe_stage): the per-stage breakdown exists in
    production scrapes and /debug/requests with nothing installed."""
    from .flight import record_current_stage
    record_current_stage(name, seconds)
    _metrics.observe_stage(name, seconds)


_annotation = None


def _load_annotation():
    """jax.profiler.TraceAnnotation, imported at first use: the chain
    server's handlers import this module without needing JAX."""
    global _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    return _annotation


class phase:
    """One host phase of the engine, as a span on the profiler's clock.

    ``with phase("loop_dispatch", round_id=7): ...`` enters a
    ``jax.profiler.TraceAnnotation`` (a TraceMe: with no profiler
    session it costs an activity check; with one, the span lands on the
    host plane of the same ``.xplane.pb`` as the device plane, its
    keyword arguments as the event's stats) and on exit hands the
    elapsed time to :func:`record_stage` under the same name — a span's
    name IS its ``engine_stage_seconds`` stage. ``seconds`` holds the
    elapsed time after exit, ``t0`` and ``t1`` the ``time.monotonic()``
    instants it began and ended at (a program's launch and readback
    stamps are these reads, not a second pair). ``record=False`` keeps the span and skips
    the stage record (set it inside the block too: a phase that turned
    out to do no work stays out of the histogram). Takes no lock,
    allocates nothing but itself, reads no environment."""

    __slots__ = ("name", "record", "seconds", "t0", "_ann")

    def __init__(self, name: str, record: bool = True, **args: Any):
        self.name = name
        self.record = record
        self.seconds = 0.0
        self._ann = (_annotation or _load_annotation())(name, **args)

    def __enter__(self) -> "phase":
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    @property
    def t1(self) -> float:
        """The instant the phase ended (after exit)."""
        return self.t0 + self.seconds

    def __exit__(self, *exc) -> None:
        self.seconds = time.monotonic() - self.t0
        self._ann.__exit__(*exc)
        if self.record:
            record_stage(self.name, self.seconds)


@contextmanager
def event_span(kind: str, **attributes: Any):
    """Child span for pipeline events — the first-party replacement for the
    reference's LlamaIndex callback→OTel bridge
    (reference: tools/observability/llamaindex/opentelemetry_callback.py:
    84-197 maps QUERY/RETRIEVE/EMBEDDING/SYNTHESIZE/LLM events to spans).
    Chains call this directly around retrieve/embed/generate stages.
    The wall time is always reported through record_stage — stage
    histograms and flight timelines see every span site even with
    tracing off."""
    t0 = time.monotonic()
    try:
        tracer = _get_tracer()
        if tracer is None:
            yield None
            return
        clean = {k: v for k, v in attributes.items()
                 if isinstance(v, (str, int, float, bool))}
        with tracer.start_as_current_span(kind, attributes=clean) as span:
            yield span
    finally:
        record_stage(kind, time.monotonic() - t0)
