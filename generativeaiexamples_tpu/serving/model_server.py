"""Model-server orchestrator: topology, checkpoint, engine, HTTP serving.

Parity with the reference's model_server package (reference:
llm-inference-server/model_server/):
- device discovery — ``jax.devices()`` replaces the nvidia-smi probe
  (reference: model_server/model.py:111-138);
- TP×PP = world-size defaulting and validation
  (reference: model_server/__init__.py:103-110);
- checkpoint format sniffing (reference: model.py:147-173);
- content-hash gated rebuild — here the hash keys the converted-weight
  cache, replacing the ``trt-w{ws}-cc{cc}`` engine cache (reference:
  model.py:33-62, 140-145); compiled programs live in the one
  persistent XLA cache (utils/compile_cache.py), whose own keys already
  cover program geometry and topology;
- then serve — one process, no mpirun: XLA collectives over ICI replace
  the per-rank Triton processes (reference: server.py:78-101).
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from aiohttp import web

from ..obs import metrics as obs_metrics
from ..utils.compile_cache import enable_compile_cache
from ..utils.errors import ConfigError
from ..utils.logging import get_logger

logger = get_logger(__name__)

MODEL_TYPES = ("llama", "codellama", "gptnext", "mixtral", "dev")

_TYPE_DEFAULT_NAME = {
    "llama": "llama-2-7b-chat",
    "codellama": "codellama-13b-instruct",
    # Real GPT-Next architecture (layernorm1p + squared-ReLU MLP), not a
    # llama alias: reference serves Nemotron as its second ensemble
    # (ensemble_models/gptnext/, conversion via nemo.py:35-65).
    "gptnext": "nemotron-8b-chat",
    "mixtral": "mixtral-8x7b-instruct",
    "dev": "llama-tiny",
}


def fast_hash_dir(path: str, workers: int = 8) -> str:
    """Parallel content hash of a model directory.

    Parity with the reference's parallel-sha1 dir hash that gates engine
    rebuilds (reference: model_server/model.py:33-62 ``_fast_hash_dir``).
    """
    files = []
    for root, _, names in os.walk(path):
        for n in sorted(names):
            files.append(os.path.join(root, n))
    files.sort()

    def hash_one(p: str) -> str:
        h = hashlib.sha1()
        with open(p, "rb") as f:
            for block in iter(lambda: f.read(1 << 20), b""):
                h.update(block)
        return h.hexdigest()

    top = hashlib.sha1()
    with ThreadPoolExecutor(max_workers=workers) as pool:
        for p, digest in zip(files, pool.map(hash_one, files)):
            top.update(os.path.relpath(p, path).encode())
            top.update(digest.encode())
    return top.hexdigest()


def resolve_azureml_model_dir(model_path: str = "") -> str:
    """AzureML managed-endpoint accommodation: when AZUREML_MODEL_DIR is
    set and no explicit --model-path was given, the checkpoint lives one
    level under it ($AZUREML_MODEL_DIR/<model_name>) — resolve to that
    directory (reference: model_server/__init__.py:36-69 ``_azureml``,
    which symlinks the same layout into /model; no symlinks needed here,
    the importers take the path directly)."""
    if model_path:
        return model_path
    aml = os.environ.get("AZUREML_MODEL_DIR", "")
    if not aml:
        return model_path
    aml = os.path.abspath(aml)
    # MLflow-registered models put files (MLmodel, conda.yaml, .amlignore)
    # next to the model folder — only a directory can be the checkpoint
    entries = [n for n in sorted(os.listdir(aml))
               if os.path.isdir(os.path.join(aml, n))
               and not n.startswith(".")] if os.path.isdir(aml) else []
    if not entries:
        raise ConfigError(
            f"AZUREML_MODEL_DIR={aml} contains no model directory: "
            "AzureML folder structure not recognized")
    resolved = os.path.join(aml, entries[0])
    logger.info("AzureML detected: model dir %s", resolved)
    return resolved


def resolve_topology(world_size: int = 0, tp: int = 0, pp: int = 1,
                     available: Optional[int] = None) -> tuple[int, int, int]:
    """(world, tp, pp) with the reference's defaulting rules
    (reference: model_server/__init__.py:103-110: tp defaults to world/pp,
    and TP·PP must equal world size).

    ``pp > 1`` is a validated SERVING rejection (the Engine would refuse
    the mesh anyway — engine/engine.py topology validation — but failing
    here is milliseconds into startup, before any checkpoint
    conversion): decode dispatches all layers as one program per round,
    so pipeline stages would idle 1/pp of every round. Rationale:
    docs/api-reference.md, "Pipeline-parallel serving"."""
    import jax
    if pp > 1:
        raise ConfigError(
            f"serving requires pp == 1 (got pp={pp}): decode runs all "
            f"layers as one fused program per round; shard serving over "
            f"tp/sp instead — pp is training-only (docs/api-reference.md, "
            f"'Pipeline-parallel serving')")
    if available is None:
        available = len(jax.devices())
    world = world_size or available
    if world > available:
        raise ConfigError(
            f"world size {world} exceeds available devices {available}")
    tp = tp or max(1, world // pp)
    if tp * pp != world:
        raise ConfigError(
            f"tensor parallelism ({tp}) x pipeline parallelism ({pp}) "
            f"must equal world size ({world})")
    return world, tp, pp


def build_services(model_type: str = "dev", model_name: str = "",
                   model_path: str = "", embedder_path: str = "",
                   world_size: int = 0, tp: int = 0, pp: int = 1,
                   max_input_length: int = 3000, max_output_length: int = 512,
                   max_slots: int = 8, dtype: str = "bfloat16",
                   quantization: str = "", with_embedder: bool = True,
                   seed: int = 0, max_prefill_bucket: Optional[int] = None,
                   page_size: int = 0, kv_quant: str = "",
                   prefix_cache: bool = True):
    """Create (engine, embed_service, model_name) per the CLI/config."""
    import jax
    import jax.numpy as jnp

    from ..embed.encoder import get_embedder
    from ..engine.engine import Engine, EngineConfig
    from ..models import llama
    from ..models.configs import get_model_config
    from ..models.import_hf import detect_checkpoint_format, load_checkpoint
    from ..models.tokenizer import ByteTokenizer, get_tokenizer
    from ..parallel.mesh import MeshPlan, make_mesh

    if model_type not in MODEL_TYPES:
        raise ConfigError(
            f"unknown model type {model_type!r}; known: {MODEL_TYPES}")
    model_name = model_name or _TYPE_DEFAULT_NAME[model_type]
    cfg = get_model_config(model_name)
    model_path = resolve_azureml_model_dir(model_path)

    # Engine geometry validates in EngineConfig.__post_init__ — construct
    # it BEFORE checkpoint hashing/conversion so a bad flag fails in
    # milliseconds, not after minutes of weight import.
    engine_cfg = EngineConfig(
        max_slots=max_slots, max_input_length=max_input_length,
        max_output_length=max_output_length, dtype=dtype, seed=seed,
        max_prefill_bucket=max_prefill_bucket,
        page_size=page_size or EngineConfig.page_size, kv_quant=kv_quant,
        prefix_cache=prefix_cache)

    world, tp, pp = resolve_topology(world_size, tp, pp)
    mesh = make_mesh(MeshPlan(tp=tp, pp=pp), jax.devices()[:world]) \
        if world > 1 else None
    identity = base_identity = f"{model_name}-{dtype}-{quantization or 'raw'}"
    hashed = False
    if model_path and not os.environ.get("GAIE_SKIP_HASH"):
        # Weight-content hash in the converted-weight cache identity —
        # the rebuild gate the reference applies to its engine cache
        # (model.py:230-241): a renamed/edited checkpoint must not
        # masquerade as the old one. GAIE_SKIP_HASH=1 skips the startup
        # hash cost (and with it the weight cache).
        digest = fast_hash_dir(model_path)[:12]
        logger.info("checkpoint hash %s", digest)
        identity += f"-{digest}"
        hashed = True
    enable_compile_cache()

    if model_type == "dev":
        # Random-init tiny model: air-gapped dev/e2e mode (the 'fake
        # engine' SURVEY.md §4 notes the reference never shipped).
        if dtype == "bfloat16":
            dtype = "float32"  # tiny dev model runs anywhere, incl CPU
        params = llama.init_params(cfg, jax.random.key(seed),
                                   dtype=jnp.dtype(dtype))
        tokenizer = ByteTokenizer()
    else:
        if not model_path:
            raise ConfigError(f"--model-path is required for {model_type}")
        tokenizer = get_tokenizer(model_path)

        def convert():
            fmt = detect_checkpoint_format(model_path)
            logger.info("model format: %s", fmt)
            p = load_checkpoint(model_path, cfg, dtype=jnp.dtype(dtype))
            if quantization:
                from ..ops.quant import quantize_params
                p = quantize_params(p, mode=quantization)
            return p

        # Converted-weight cache keyed by name+dtype+quant+content
        # hash: restarts skip torch parsing + key mapping +
        # quantization (SURVEY §5, the reference's engine-cache role,
        # model.py:230-246). The cache is only trusted when the identity
        # CARRIES the content hash — under GAIE_SKIP_HASH an updated
        # checkpoint at the same path would silently serve stale weight
        # bytes. Old-hash siblings are pruned on save (a converted 7B
        # tree is multi-GB).
        from ..models import weight_cache
        if hashed:
            params, from_cache = weight_cache.cached_or_convert(
                identity, convert, prune_prefix=base_identity + "-")
            if from_cache:
                logger.info("converted weights served from cache "
                            "(GAIE_WEIGHT_CACHE=0 disables)")
        else:
            if weight_cache.enabled():
                logger.info("weight cache skipped: no content hash "
                            "(GAIE_SKIP_HASH set or no model path)")
            params = convert()

    if quantization and model_type == "dev":
        from ..ops.quant import quantize_params
        params = quantize_params(params, mode=quantization)

    # dtype may have been resolved above (dev mode downgrades bfloat16 to
    # float32 so the tiny model runs anywhere, incl CPU)
    engine_cfg = dataclasses.replace(engine_cfg, dtype=dtype)
    # Embedder BEFORE the engine: the auto-sized KV pool claims what the
    # device reports free, so everything else resident must already be.
    embed_service = None
    if with_embedder:
        if embedder_path:
            embed_service = get_embedder("tpu-jax", "e5-large-v2",
                                         checkpoint_path=embedder_path)
        elif model_type == "dev":
            embed_service = get_embedder("tpu-jax", "encoder-tiny")
    engine = Engine(params, cfg, tokenizer, engine_cfg, mesh=mesh)
    # Allocate-and-verify before serving: worst-case prefill/insert/round
    # transients run (and compile) once, so a pool the headroom model
    # oversized fails here — loudly, stats["pool_shrinks"] — instead of
    # mid-request.
    engine.prewarm()
    return engine, embed_service, model_name


def create_server_app(engine, embed_service=None,
                      model_name: str = "model") -> web.Application:
    """One app serving both API surfaces + health/metrics."""
    from .openai_api import add_openai_routes
    from .triton_shim import add_triton_routes

    app = web.Application()

    async def health(request: web.Request) -> web.Response:
        return web.json_response(
            {"status": "ok", "model": model_name,
             "engine": dict(engine.stats)})

    def _mirror_engine_stats() -> None:
        obs_metrics.record_engine_stats(engine.stats)

    async def metrics_endpoint(request: web.Request) -> web.Response:
        # Scrape-time engine snapshot (same contract as the chain
        # server's /metrics): every numeric Engine.stats() key mirrors
        # as an engine_* gauge, so both server surfaces expose the
        # doc-checked gauge table — including the round-telemetry and
        # cost-drift counters — plus the process resource gauges.
        try:
            _mirror_engine_stats()
        except Exception:  # noqa: BLE001 — metrics must never 500
            logger.debug("engine stats unavailable", exc_info=True)
        obs_metrics.record_process_stats()
        return web.Response(text=obs_metrics.REGISTRY.render_prometheus(),
                            content_type="text/plain")

    async def debug_requests(request: web.Request) -> web.Response:
        # Per-request flight recorder (obs/flight.py): in-flight + last-N
        # completed timelines for every request this engine served —
        # the OpenAI/Triton/gRPC surfaces all stamp X-Request-ID (or a
        # minted cmpl- id) onto their engine submissions.
        from ..obs import flight as obs_flight
        return obs_flight.debug_requests_response(request)

    async def debug_rounds(request: web.Request) -> web.Response:
        # Engine-level round telemetry (obs/rounds.py): per-round
        # plan + execution records and rolling aggregates — the
        # engine's side of the story /debug/requests tells per request.
        from ..obs import rounds as obs_rounds
        return obs_rounds.debug_rounds_response(
            request, getattr(engine, "rounds", None))

    # Retained telemetry: history ring + alert engine + incident
    # black-box, same wiring as the chain server (one unit, inert when
    # HISTORY_INTERVAL_S=0). Engine stats and process gauges are
    # mirrored into every history sample so alerts see them between
    # scrapes.
    from ..obs import alerts as obs_alerts
    from ..obs import history as obs_history
    from ..obs import incidents as obs_incidents

    obs_stack = obs_incidents.ObservabilityStack(
        "model",
        pre_sample=[_mirror_engine_stats,
                    obs_metrics.record_process_stats],
        flight=engine.flight, rounds=engine.rounds)

    async def _obs_start(_app) -> None:
        obs_stack.start()

    async def _obs_stop(_app) -> None:
        obs_stack.stop()

    app.on_startup.append(_obs_start)
    app.on_cleanup.append(_obs_stop)

    async def debug_history(request: web.Request) -> web.Response:
        return obs_history.debug_history_response(request,
                                                  obs_stack.history)

    async def debug_alerts(request: web.Request) -> web.Response:
        return obs_alerts.debug_alerts_response(request, obs_stack.alerts)

    async def debug_incidents(request: web.Request) -> web.Response:
        return obs_incidents.debug_incidents_response(request, obs_stack)

    async def control_incident(request: web.Request) -> web.Response:
        return await obs_incidents.control_incident_response(request,
                                                             obs_stack)

    # On-demand device profiling (SURVEY §5: the jax.profiler endpoint on
    # the serving engine — the role nsys would play on the reference's
    # stack). POST /profiler/start {"dir": ...} -> trace capture begins;
    # POST /profiler/stop -> trace written for TensorBoard/XProf.
    profiler_state = {"dir": None}

    # Profiler start/stop run OFF the event loop with a bound: a wedged
    # jax.profiler (seen hanging in stop_trace on some CPU builds) must
    # cost the caller a 504, not freeze every other endpoint on this
    # server's single event loop forever.
    profiler_timeout_s = float(os.environ.get("PROFILER_TIMEOUT_S", "120"))

    async def profiler_start(request: web.Request) -> web.Response:
        import asyncio
        import jax
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001 — empty body is fine
            body = {}
        # No awaits between the conflict check and the claim: concurrent
        # starts must 409, not race into a double start_trace.
        if profiler_state["dir"]:
            raise web.HTTPConflict(text="profiler already running")
        trace_dir = body.get("dir") or os.path.join(
            "/tmp", "generativeaiexamples_tpu", "profile")
        profiler_state["dir"] = trace_dir
        try:
            os.makedirs(trace_dir, exist_ok=True)
            await asyncio.wait_for(
                asyncio.get_running_loop().run_in_executor(
                    None, jax.profiler.start_trace, trace_dir),
                timeout=profiler_timeout_s)
        except asyncio.TimeoutError:
            # The executor thread may still complete the start later —
            # KEEP the claim, or the state would desync (jax tracing
            # while this server believes it is not). A later
            # /profiler/stop clears it either way.
            raise web.HTTPGatewayTimeout(
                text=f"profiler start exceeded {profiler_timeout_s}s; "
                     f"trace state unknown — POST /profiler/stop to "
                     f"clean up")
        except Exception:
            profiler_state["dir"] = None
            raise
        return web.json_response({"status": "tracing", "dir": trace_dir})

    async def profiler_stop(request: web.Request) -> web.Response:
        import asyncio
        import jax
        if not profiler_state["dir"]:
            raise web.HTTPConflict(text="profiler not running")
        try:
            await asyncio.wait_for(
                asyncio.get_running_loop().run_in_executor(
                    None, jax.profiler.stop_trace),
                timeout=profiler_timeout_s)
        except asyncio.TimeoutError:
            # Keep the claim: the stop may still land on its executor
            # thread, and the operator can retry — clearing it here
            # would let a new start_trace race the wedged stop.
            raise web.HTTPGatewayTimeout(
                text=f"profiler stop exceeded {profiler_timeout_s}s; "
                     f"retry to attempt cleanup")
        except Exception as exc:  # noqa: BLE001 — e.g. "no profile running"
            # jax says there is nothing to stop (a timed-out start that
            # never engaged): reconcile our claim with reality.
            profiler_state["dir"] = None
            raise web.HTTPConflict(
                text=f"profiler stop failed: {exc}") from exc
        trace_dir, profiler_state["dir"] = profiler_state["dir"], None
        return web.json_response({"status": "written", "dir": trace_dir})

    # One score at a time: each request materializes a dense full-length
    # KV cache NEXT TO the engine's deliberately-HBM-filling pool, so
    # unbounded concurrency would be a self-inflicted OOM. An asyncio
    # semaphore (not a threading one inside the executor): waiters queue
    # on the event loop instead of each pinning a shared-executor thread
    # that the generation endpoints also need.
    import asyncio as _asyncio
    score_gate = _asyncio.Semaphore(1)
    # Client-controlled chunk sizes each compile a fresh per-chunk
    # program; an allowlist bounds the trace/compile surface (and caps
    # the single-pass path's activation memory).
    SCORE_CHUNKS = (64, 128, 256, 512, 1024, 2048, 4096, 8192)

    async def score(request: web.Request) -> web.Response:
        """Long-document scoring: per-token NLL / perplexity far beyond
        the engine's serving window (models/llama.py score — chunked
        cached forward on one chip, ring-attention apply_sp on an sp
        mesh). The long-context surface the reference stack has no
        equivalent of."""
        import asyncio

        import jax.numpy as jnp
        import numpy as np

        try:
            body = await request.json()
            if not isinstance(body, dict):
                raise ValueError("body must be a JSON object")
        except Exception as exc:  # noqa: BLE001 — malformed JSON -> 400
            raise web.HTTPBadRequest(text=f"invalid JSON: {exc}") from exc
        # Default sized for a 7B-class model sharing the chip with the
        # serving pool (~2 GB of dense bf16 KV at 32k); raise it on
        # chips with headroom or dedicated scoring servers.
        max_score = int(os.environ.get("GAIE_MAX_SCORE_TOKENS", "32768"))
        loop = asyncio.get_running_loop()
        try:
            chunk = int(body.get("chunk", 2048))
            if chunk not in SCORE_CHUNKS:
                raise ValueError(f"chunk must be one of {SCORE_CHUNKS}")
            if "tokens" in body:
                ids = [int(t) for t in body["tokens"]]
            elif body.get("text"):
                text = str(body["text"])
                # a sentencepiece token covers >= 1 byte, so a byte bound
                # rejects hopeless documents before paying tokenization
                if len(text.encode("utf-8", "ignore")) > max_score * 16:
                    raise web.HTTPRequestEntityTooLarge(
                        max_size=max_score * 16,
                        actual_size=len(text))
                # tokenize OFF the event loop: pure-Python BPE over a
                # large document takes seconds and would freeze every
                # in-flight SSE stream
                ids = await loop.run_in_executor(
                    None, engine.tokenizer.encode, text)
            else:
                raise ValueError("'text' or 'tokens' is required")
            if len(ids) < 2:
                raise ValueError("scoring needs at least 2 tokens")
        except (ValueError, TypeError) as exc:
            raise web.HTTPUnprocessableEntity(text=str(exc)) from exc
        if len(ids) > max_score:
            raise web.HTTPRequestEntityTooLarge(
                max_size=max_score, actual_size=len(ids))
        from ..models import llama as _llama

        def run():
            tokens = jnp.asarray(np.asarray(ids, np.int32)[None, :])
            nll = _llama.score(engine.params, engine.model_cfg, tokens,
                               mesh=engine.mesh, chunk=chunk)
            return np.asarray(nll[0], np.float64)

        try:
            async with score_gate:
                nll = await loop.run_in_executor(None, run)
        except Exception as exc:  # noqa: BLE001 — device OOM must not 500
            if "RESOURCE_EXHAUSTED" in str(exc):
                raise web.HTTPServiceUnavailable(
                    text="scoring cache does not fit next to the serving "
                         "pool; lower the document length or "
                         "GAIE_MAX_SCORE_TOKENS") from exc
            raise
        mean = float(nll.mean())
        out = {"model": model_name, "tokens": len(ids),
               "mean_nll": round(mean, 6),
               "perplexity": round(float(np.exp(mean)), 4)}
        if body.get("per_token"):
            out["nll"] = [round(float(x), 6) for x in nll]
        return web.json_response(out)

    app.router.add_get("/health", health)
    app.router.add_get("/metrics", metrics_endpoint)
    app.router.add_get("/debug/requests", debug_requests)
    app.router.add_get("/debug/rounds", debug_rounds)
    app.router.add_get("/debug/history", debug_history)
    app.router.add_get("/debug/alerts", debug_alerts)
    app.router.add_get("/debug/incidents", debug_incidents)
    app.router.add_post("/control/incident", control_incident)
    app.router.add_post("/v1/score", score)
    app.router.add_post("/profiler/start", profiler_start)
    app.router.add_post("/profiler/stop", profiler_stop)
    add_openai_routes(app, engine, model_name, embed_service=embed_service,
                      max_output=engine.cfg.max_output_length)
    add_triton_routes(app, engine, model_name,
                      max_output=engine.cfg.max_output_length)
    from .jobs_api import add_jobs_routes
    add_jobs_routes(app, engine, model_name,
                    max_output=engine.cfg.max_output_length)
    return app


def main(argv: Optional[list[str]] = None) -> None:
    """CLI parity with ``python -m model_server TYPE ...``
    (reference: model_server/__main__.py:33-135)."""
    parser = argparse.ArgumentParser(
        description="TPU-native LLM inference server")
    parser.add_argument("model_type", choices=MODEL_TYPES)
    parser.add_argument("--model-name", default="")
    parser.add_argument("--model-path", default=os.environ.get("MODEL_PATH", ""))
    parser.add_argument("--embedder-path", default="")
    parser.add_argument("--world-size", type=int, default=0,
                        help="devices to use (default: all local)")
    parser.add_argument("--tensor-parallelism", type=int, default=0)
    parser.add_argument("--pipeline-parallelism", type=int, default=1)
    parser.add_argument("--quantization", default="",
                        choices=["", "int8", "int4", "int4_awq"])
    parser.add_argument("--kv-quant", default="", choices=["", "int8"],
                        help="KV-cache quantization: int8 pool pages + "
                             "per-row scales (~2x pages at fixed HBM)")
    parser.add_argument("--max-input-length", type=int, default=3000)
    parser.add_argument("--max-prefill-bucket", type=int, default=0,
                        help="cap the one-shot prefill bucket; longer "
                             "prompts stream through the paged pool in "
                             "chunks (long-context serving). Must be a "
                             "multiple of --page-size. 0 = off")
    parser.add_argument("--page-size", type=int, default=0,
                        help="KV pool page size in tokens (0 = default "
                             "128); prefill buckets are page multiples")
    parser.add_argument("--max-output-length", type=int, default=512)
    parser.add_argument("--max-batch-size", type=int, default=8)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--no-embedder", action="store_true")
    parser.add_argument("--no-prefix-cache", action="store_true",
                        help="disable shared-prefix KV page reuse across "
                             "requests (engine/prefix_cache.py); on by "
                             "default — repeat-turn chat prefills only "
                             "the new suffix")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--grpc-port", type=int, default=8001,
                        help="gRPC LLMService port (0 disables); the "
                             "reference's Triton serves gRPC on 8001")
    # Multi-host DCN (reference launches one Triton per rank under mpirun,
    # server.py:78-101; here every host runs this same CLI and JAX wires
    # them over DCN).
    parser.add_argument("--coordinator", default="",
                        help="host:port of process 0 for multi-host DCN")
    parser.add_argument("--num-processes", type=int, default=0)
    parser.add_argument("--process-id", type=int, default=-1)
    args = parser.parse_args(argv)

    from ..parallel.mesh import maybe_init_distributed
    if maybe_init_distributed(args.coordinator, args.num_processes,
                              args.process_id):
        logger.info("jax.distributed initialized (multi-host DCN)")

    # Pid file under the run dir (GAIE_RUN_DIR, default under /tmp) —
    # launcher scripts should read this instead of `echo $! > server.pid`
    # littering whatever directory they were started from.
    from ..utils.logging import write_pid_file
    pid_path = write_pid_file(f"model-server-{args.port}")
    if pid_path:
        logger.info("pid file: %s", pid_path)

    engine, embed_service, model_name = build_services(
        model_type=args.model_type, model_name=args.model_name,
        model_path=args.model_path, embedder_path=args.embedder_path,
        world_size=args.world_size, tp=args.tensor_parallelism,
        pp=args.pipeline_parallelism, quantization=args.quantization,
        max_input_length=args.max_input_length,
        max_output_length=args.max_output_length,
        max_slots=args.max_batch_size, dtype=args.dtype,
        with_embedder=not args.no_embedder,
        max_prefill_bucket=args.max_prefill_bucket or None,
        page_size=args.page_size, kv_quant=args.kv_quant,
        prefix_cache=not args.no_prefix_cache)
    engine.start()
    grpc_server = None  # keep the reference: grpc.Server stops when GC'd
    if args.grpc_port:
        from .grpc_server import serve_grpc
        grpc_server = serve_grpc(engine, model_name, embed_service,
                                 max_output=engine.cfg.max_output_length,
                                 host=args.host, port=args.grpc_port)
    logger.info("serving %s on %s:%d", model_name, args.host, args.port)
    try:
        web.run_app(create_server_app(engine, embed_service, model_name),
                    host=args.host, port=args.port)
    finally:
        if grpc_server is not None:
            grpc_server.stop(grace=1.0)


if __name__ == "__main__":
    main()
