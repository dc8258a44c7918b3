"""One persistent XLA compile cache for every entry point.

A cold start compiles the whole prefill-bucket x decode-rung ladder;
the cache turns every later start into disk reads. Its directory is
part of each entry's key, so it must not move: it is either the one the
operator placed from outside (``JAX_COMPILATION_CACHE_DIR``, which JAX
reads itself — then no directory is set in code) or ONE fixed path
inside the checkout. Never a path derived from a model name, a
checkpoint hash, a pid or a temp name: a directory that changes never
hits.

The key includes the programs' metadata
(``jax_compilation_cache_include_metadata_in_key``): the stage scopes of
``models/llama.py`` and the kernel names are HLO metadata, and an
executable loaded from an entry that another checkout wrote would
carry THAT checkout's scope names into a profile. The price is a cold
first start after an edit that moves the traced lines.

The module also keeps the program's own BUILD LOG: what JAX traced,
lowered, compiled and loaded from the cache in this process, summed from
``jax.monitoring`` events (``build_log``; the engine mirrors it as
``engine_programs_built`` and friends). A set-up that got slower shows
here by phase, not only as one end-to-end number.
"""

from __future__ import annotations

import os
import threading

from .logging import get_logger

logger = get_logger(__name__)

# <checkout>/.jax_cache (git-ignored).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in
    force ("" = none). Call before the first engine or encoder compiles.

    XLA:CPU results encode the build host's exact machine features, so a
    persistent CPU cache poisons runs on any other host: on a CPU
    backend nothing is enabled here (an operator who sets
    ``JAX_COMPILATION_CACHE_DIR`` anyway has asked JAX for it directly).
    """
    import jax

    install_build_log()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not placed and jax.default_backend() == "cpu":
        return ""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if placed:
        logger.info("compile cache: %s (JAX_COMPILATION_CACHE_DIR)", placed)
        return placed
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    logger.info("compile cache: %s", DEFAULT_DIR)
    return DEFAULT_DIR


# ------------------------------------------------------------- build log

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_log_lock = threading.Lock()
_log = {"programs_built": 0, "program_trace_s": 0.0, "program_lower_s": 0.0,
        "program_compile_s": 0.0, "program_cache_load_s": 0.0,
        "program_cache_hits": 0}
_installed = False


def _on_duration(event: str, duration: float, **_kw) -> None:
    with _log_lock:
        if event == _TRACE:
            _log["program_trace_s"] += duration
        elif event == _LOWER:
            _log["program_lower_s"] += duration
        elif event == _COMPILE:
            # one per program built, whether XLA compiled it or the
            # cache held it; a hit's retrieval lies inside this interval
            # and is moved to program_cache_load_s below
            _log["programs_built"] += 1
            _log["program_compile_s"] += duration
        elif event == _CACHE_LOAD:
            _log["program_cache_load_s"] += duration
            _log["program_compile_s"] -= duration


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        with _log_lock:
            _log["program_cache_hits"] += 1


def install_build_log() -> None:
    """Register the listeners, once a process (JAX keeps no way to take
    one listener off again). Called by ``enable_compile_cache`` and by
    every ``Engine``; programs built before the first call are not
    counted."""
    global _installed
    with _log_lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def build_log() -> dict:
    """Process totals since ``install_build_log``: programs built
    (compiled or loaded), seconds tracing, lowering, compiling and
    loading from the cache, and cache hits."""
    with _log_lock:
        return dict(_log)
