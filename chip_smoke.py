"""Chip smoke: the serving path, once, on a real TPU — or a non-zero exit.

    python chip_smoke.py [--seed N]     # one chip: device, kernels,
                                        # engine, serve
    python chip_smoke.py --chips 4      # four-chip host: device, then the
                                        # tp=4 engine against the tp=1
                                        # engine; no other phase

One process holds the chip from first to last (no child needs the
device). Everything is built from committed files and ``--seed``: random
weights at published widths, all 32 layers, the README quickstart's
int8 deployment. Each phase prints one JSON object; the LAST stdout line
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
N}}`` with the device as JAX reports it. Any phase that fails ends the
run with a non-zero exit and ``"ok": false`` — there is no smaller model,
no other quantization, no CPU.

TTFT and tok/s lines are informational (they carry the device kind);
they are not claims.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

MODEL = "llama-2-7b-chat"
QUANT = "int8"
SLOTS = 8
PAGE = 128
TIME_LIMIT_S = 1150     # the contract allows 1200, compilation included

# Stated tolerances on max|got - want| / max|want|. Both sides compute
# from bf16 operands (eps 2^-8) with f32 accumulation and round the
# result to bf16 once, so they differ by a few bf16 ulps of the largest
# value; the int8-KV kernel folds scales around the dots in another
# order than dequantize-then-attend.
TOL = {
    "paged_attention_bf16": 2e-2,
    "paged_attention_int8kv": 3e-2,
    "int4_matmul_per_channel": 2e-2,
    "int4_matmul_grouped": 2e-2,
    "decode_step_kernel_vs_gather": 5e-2,   # 2 layers compound it
}
# tp=4 vs tp=1 first-decode-step logits, same normalization: the same
# math with row-parallel partial sums reduced across chips in another
# order.
TOL_TP_LOGITS = 3e-2


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# --------------------------------------------------------------- device


def device_info() -> dict:
    """The device as JAX reports it — the contract's last-line keys."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def phase_device(device: dict, chips: int) -> str:
    """Fail at once unless JAX runs on a TPU with enough chips; returns
    the compile-cache directory in force."""
    import importlib.metadata as md

    import jax

    from generativeaiexamples_tpu.utils.compile_cache import (
        enable_compile_cache)

    check(device["platform"] == "tpu",
          f"no TPU: JAX reports platform {device['platform']!r}")
    check(device["count"] >= chips,
          f"--chips {chips} needs {chips} devices")
    cache_dir = enable_compile_cache()
    emit({"phase": "device", "ok": True, **device,
          "bytes_limit": jax.devices()[0].memory_stats()["bytes_limit"],
          "jax": jax.__version__, "jaxlib": md.version("jaxlib"),
          "libtpu": md.version("libtpu"),
          "compile_cache_dir": cache_dir,
          "compile_cache_from_env": bool(
              os.environ.get("JAX_COMPILATION_CACHE_DIR"))})
    return cache_dir


# -------------------------------------------------------------- kernels


def phase_kernels(seed: int) -> None:
    """Both Pallas kernels and the kernel-path decode step, COMPILED on
    the device (never interpreted), against their plain references at
    llama-2-7b shapes."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.models.configs import get_model_config
    from generativeaiexamples_tpu.ops import quant
    from generativeaiexamples_tpu.ops.int4_matmul import int4_matmul
    from generativeaiexamples_tpu.ops.kv_quant import (dequantize_rows,
                                                       quantize_rows)
    from generativeaiexamples_tpu.ops.paged_attention import (
        paged_attention_decode, paged_attention_decode_reference)

    cfg = get_model_config(MODEL)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    errs: dict = {}
    key = jax.random.key(seed)

    def maxerr(got, want) -> float:
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return float(jnp.max(jnp.abs(got - want))
                     / jnp.max(jnp.abs(want)))

    # --- paged attention: B slots, ragged lengths, shuffled page table
    B, W, L = SLOTS, 8, 2
    N = B * W + 1
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    pool_k = jax.random.normal(ks[1], (L, N, KV, PAGE, hd), jnp.bfloat16)
    pool_v = jax.random.normal(ks[2], (L, N, KV, PAGE, hd), jnp.bfloat16)
    cur_k = jax.random.normal(ks[3], (B, KV, hd), jnp.bfloat16)
    cur_v = jax.random.normal(ks[4], (B, KV, hd), jnp.bfloat16)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(
        rng.permutation(np.arange(1, N)).reshape(B, W).astype(np.int32))
    lengths_np = rng.integers(1, W * PAGE - 1, size=B).astype(np.int32)
    lengths_np[0] = 0                      # a fresh slot streams nothing
    lengths_np[1] = W * PAGE - 1           # a full window
    lengths = jnp.asarray(lengths_np)
    wp = jnp.take_along_axis(table, (lengths // PAGE)[:, None], 1)[:, 0]
    off = lengths % PAGE
    layer = jnp.ones((1,), jnp.int32)      # read/write layer 1 of 2

    attn, nk, nv = jax.jit(paged_attention_decode)(
        q, pool_k, pool_v, table, lengths, cur_k, cur_v, wp, off, layer)
    ref = paged_attention_decode_reference(
        q, pool_k[1], pool_v[1], table, lengths, cur_k, cur_v)
    errs["paged_attention_bf16"] = maxerr(attn, ref)
    check(bool(jnp.all(nk[1, wp, :, off] == cur_k)
               & jnp.all(nv[1, wp, :, off] == cur_v)),
          "paged attention: appended K/V row did not land in the pool")
    check(bool(jnp.all(nk[0] == pool_k[0])),
          "paged attention: wrote outside the requested layer")

    # --- int8-KV variant against the dequantized pool
    qk, sk = quantize_rows(pool_k)
    qv, sv = quantize_rows(pool_v)
    attn8, *_ = jax.jit(
        lambda *a: paged_attention_decode(*a[:10], pool_ks=a[10],
                                          pool_vs=a[11]))(
        q, qk, qv, table, lengths, cur_k, cur_v, wp, off, layer, sk, sv)
    ref8 = paged_attention_decode_reference(
        q, dequantize_rows(qk[1], sk[1]), dequantize_rows(qv[1], sv[1]),
        table, lengths, cur_k, cur_v)
    errs["paged_attention_int8kv"] = maxerr(attn8, ref8)

    # --- int4 matmul against the XLA unpack path (ops/quant.py)
    D, F = cfg.hidden_size, cfg.intermediate_size
    x = jax.random.normal(ks[5], (B, D), jnp.bfloat16)
    w = jax.random.normal(ks[6], (D, F), jnp.float32) * D ** -0.5
    w4 = quant.quantize_tensor(w, bits=4)
    got = int4_matmul(x, w4["q4"], w4["scale"])
    want = x @ quant.dequantize(w4, jnp.bfloat16)
    errs["int4_matmul_per_channel"] = maxerr(got, want)
    w4g = quant.quantize_tensor_grouped(w, group_size=128)
    got = int4_matmul(x, w4g["q4"], w4g["gscale"])
    want = x @ quant.dequantize(w4g, jnp.bfloat16)
    errs["int4_matmul_grouped"] = maxerr(got, want)
    check(quant._use_int4_kernel(w4) and quant._use_int4_kernel(w4g),
          "int4 kernel gate is off on this device")

    # --- 2-layer decode step at 7B width: kernel path vs jnp gather
    cfg2 = dataclasses.replace(cfg, num_layers=2)
    params = jax.jit(lambda k: quant.quantize_params(
        llama.init_params(cfg2, k, dtype=jnp.bfloat16), QUANT))(ks[7])
    cache = {"k": pool_k, "v": pool_v}
    tok = jnp.asarray(rng.integers(3, cfg.vocab_size, (B, 1)), jnp.int32)
    step = jax.jit(llama.apply_decode_paged,
                   static_argnames=("cfg", "use_kernel"))
    args = (tok, lengths[:, None], cache, table, lengths + 1, wp, off)
    logit_k, cache_k = step(params, cfg2, *args, use_kernel=True)
    logit_g, cache_g = step(params, cfg2, *args, use_kernel=False)
    errs["decode_step_kernel_vs_gather"] = maxerr(logit_k, logit_g)
    check(bool(jnp.all(jnp.isfinite(logit_k))), "decode step: logits "
          "not finite")
    # appended rows only: the kernel's 8-row tile write may leave
    # garbage in the dead rows past a fresh page's first row
    check(maxerr(cache_k["k"][:, wp, :, off],
                 cache_g["k"][:, wp, :, off]) < TOL[
                     "decode_step_kernel_vs_gather"],
          "decode step: kernel and gather paths appended different K rows")

    failed = {k: v for k, v in errs.items() if not v <= TOL[k]}
    emit({"phase": "kernels", "ok": not failed, "max_rel_err": errs,
          "tolerance": TOL, "shapes": {
              "slots": B, "heads": H, "kv_heads": KV, "head_dim": hd,
              "page": PAGE, "window_pages": W, "matmul": [D, F]}})
    check(not failed, f"kernel error above tolerance: {failed}")


# --------------------------------------------------------------- engine


def make_tokenizer(vocab_size: int):
    """The vendored 32k sentencepiece model (llama-2 vocab geometry,
    realistic English compression); fails if it does not fit."""
    from generativeaiexamples_tpu.models.sentencepiece import (
        SentencePieceTokenizer)
    tok = SentencePieceTokenizer(os.path.join(
        REPO, "generativeaiexamples_tpu", "assets", "tokenizer_32k.model"))
    check(tok.vocab_size <= vocab_size, "tokenizer larger than the model")
    return tok


def make_params(cfg, seed: int, mesh=None):
    """Random int8 weights at published widths, made on the device in
    one program (so the bf16 tree never has to exist whole)."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops.quant import quantize_params

    def make(key):
        return quantize_params(
            llama.init_params(cfg, key, dtype=jnp.bfloat16), QUANT)

    params = jax.jit(make)(jax.random.key(seed))
    jax.block_until_ready(params)
    return params


def make_embedder(seed: int):
    """On-device encoder at e5-large-v2 geometry, weights from the seed."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.embed.encoder import EmbeddingService
    from generativeaiexamples_tpu.models import encoder
    from generativeaiexamples_tpu.models.configs import E5_LARGE_V2
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer

    params = jax.jit(lambda key: encoder.init_params(
        E5_LARGE_V2, key, dtype=jnp.bfloat16))(jax.random.key(seed + 1))
    jax.block_until_ready(params)
    return EmbeddingService(params, E5_LARGE_V2, ByteTokenizer())


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def engine_report(engine) -> dict:
    """What a healthy engine build looks like; asserted by the caller."""
    stats = engine.stats
    return {
        "kernel_path": bool(engine._use_kernel),
        "fused_tail": bool(engine._fused_tail),
        "tail": ("fused_tp" if engine.programs.tail.kind == "sharded"
                 else "fused" if engine._fused_tail else "materialized"),
        "downgrades": stats["downgrades"],
        "pool_shrinks": stats["pool_shrinks"],
        "pool_pages": engine._n_pages - 1,
        "pool_source": ("pinned" if engine.cfg.kv_pool_tokens != "auto"
                        else "full_capacity_cpu"
                        if engine._devices()[0].platform == "cpu"
                        else "memory_stats"),
        "cost_model_source": engine._sched._static_cost.source,
        "round_budget_tokens": stats["sched_round_budget_tokens"],
    }


def check_engine(report: dict, tail: str) -> None:
    check(report["kernel_path"], "Pallas kernel path is not armed")
    check(report["tail"] == tail, f"tail is {report['tail']}, not {tail}")
    check(report["downgrades"] == 0, "engine reports feature downgrades")
    check(report["pool_shrinks"] == 0, "prewarm had to shrink the pool")
    check(report["pool_source"] == "memory_stats",
          "pool was not sized from memory_stats")
    check(not report["cost_model_source"].startswith("PROFILE_"),
          "scheduler primed from a committed PROFILE artifact "
          f"({report['cost_model_source']}) that never saw this device")


def phase_engine(seed: int, cache_dir: str):
    """llama-2-7b-chat, all layers, int8 weights, bf16 KV, 8 slots, the
    model server's default limits — built the way ``build_services``
    builds it (embedder first, auto-sized pool, prewarm)."""
    import jax

    from generativeaiexamples_tpu.engine import Engine, EngineConfig
    from generativeaiexamples_tpu.models.configs import get_model_config

    cfg = get_model_config(MODEL)
    dev = jax.devices()[0]
    t0 = time.monotonic()
    embedder = make_embedder(seed)
    params = make_params(cfg, seed)
    t_weights = time.monotonic() - t0
    free_before = (dev.memory_stats()["bytes_limit"]
                   - dev.memory_stats()["bytes_in_use"])
    entries0 = cache_entries(cache_dir)
    t0 = time.monotonic()
    engine = Engine(params, cfg, make_tokenizer(cfg.vocab_size),
                    EngineConfig(max_slots=SLOTS, dtype="bfloat16",
                                 seed=seed))
    t_build = time.monotonic() - t0
    t0 = time.monotonic()
    engine.prewarm()
    t_prewarm = time.monotonic() - t0
    stats = dev.memory_stats()
    report = engine_report(engine)
    emit({"phase": "engine", "ok": True, "model": MODEL,
          "layers": cfg.num_layers, "quant": QUANT, "kv": "bfloat16",
          "slots": SLOTS,
          "max_input_length": engine.cfg.max_input_length,
          "prefill_buckets": list(engine._buckets), **report,
          "free_hbm_before_pool": free_before,
          "headroom_bytes": engine._headroom_bytes(),
          "bytes_limit": stats["bytes_limit"],
          "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
          "weights_s": round(t_weights, 2), "build_s": round(t_build, 2),
          "prewarm_s": round(t_prewarm, 2),
          "compile_cache_entries_before": entries0,
          "compile_cache_entries_written":
              cache_entries(cache_dir) - entries0})
    check(cfg.num_layers == 32, "depth was cut")
    check_engine(report, tail="fused")
    return engine, embedder


# ---------------------------------------------------------------- serve


class _Served:
    """An aiohttp app on 127.0.0.1:0 in a background thread of THIS
    process."""

    def __init__(self, app):
        import asyncio
        import threading

        from aiohttp import web

        self._loop = asyncio.new_event_loop()
        self._runner = web.AppRunner(app, shutdown_timeout=0.5)
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self._loop)

            async def boot():
                await self._runner.setup()
                site = web.TCPSite(self._runner, "127.0.0.1", 0)
                await site.start()
            self._loop.run_until_complete(boot())
            started.set()
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        check(started.wait(60), "HTTP server failed to boot")
        self.url = f"http://127.0.0.1:{self._runner.addresses[0][1]}"

    def close(self) -> None:
        import asyncio
        asyncio.run_coroutine_threadsafe(
            self._runner.cleanup(), self._loop).result(timeout=30)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10)


def check_gauge_zero(metrics_text: str, name: str) -> None:
    """The scraped Prometheus text carries ``name`` with value 0 (a
    gauge the scrape failed to mirror would be absent, not 0)."""
    check(any(ln in (f"{name} 0", f"{name} 0.0")
              for ln in metrics_text.splitlines()),
          f"/metrics lacks '{name} 0'")


def sse_frames(resp, t0: float):
    """(seconds since ``t0`` at arrival, payload) per SSE frame."""
    for raw in resp.iter_lines():
        if raw.startswith(b"data:"):
            yield time.monotonic() - t0, raw[len(b"data:"):].strip()


def phase_serve(engine, embedder, model_name: str, device_kind: str,
                doc_path: str, prompt_tokens: int = 512,
                out_tokens: int = 64, rag_tokens: int = 32) -> dict:
    """The two HTTP surfaces over one engine, as a user would call them:
    the model server's OpenAI routes, then the chain server (developer
    RAG example, in-process EngineLLM, on-device encoder, ``exact-tpu``
    store). Returns the printed report; raises on any failed check."""
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    import requests

    from generativeaiexamples_tpu.chains.examples.developer_rag import (
        QAChatbot)
    from generativeaiexamples_tpu.chains.llm import EngineLLM
    from generativeaiexamples_tpu.chains.server import create_app
    from generativeaiexamples_tpu.serving.model_server import (
        create_server_app)
    from generativeaiexamples_tpu.utils.app_config import AppConfig
    from generativeaiexamples_tpu.utils.configuration import from_dict

    out: dict = {"phase": "serve", "device_kind": device_kind}
    with open(doc_path) as f:
        doc_text = f.read()

    # ~prompt_tokens tokens of real text per request; the leading words
    # differ so the four prompts share no cached prefix block.
    tok = engine.tokenizer
    words = (doc_text.split() * (1 + prompt_tokens * 2
                                 // max(1, len(doc_text.split()))))

    def prompt(i: int) -> str:
        lo, hi = 1, len(words)
        while lo < hi:              # longest word prefix that fits
            mid = (lo + hi + 1) // 2
            text = f"Request {i}. " + " ".join(words[:mid])
            if len(tok.encode(text)) <= prompt_tokens:
                lo = mid
            else:
                hi = mid - 1
        return f"Request {i}. " + " ".join(words[:lo])

    # ----- model server
    server = _Served(create_server_app(engine, embedder, model_name))
    try:
        health = requests.get(f"{server.url}/health", timeout=30)
        check(health.status_code == 200
              and health.json()["status"] == "ok", "/health not ok")

        def complete(i: int) -> dict:
            t0 = time.monotonic()
            r = requests.post(f"{server.url}/v1/completions", json={
                "model": model_name, "prompt": prompt(i),
                "max_tokens": out_tokens, "top_k": 1,
                "temperature": 0.0}, timeout=600)
            check(r.status_code == 200,
                  f"/v1/completions -> {r.status_code}: {r.text[:200]}")
            return {**r.json(), "_seconds": time.monotonic() - t0}

        with ThreadPoolExecutor(4) as pool:
            done = list(pool.map(complete, range(4)))
        for body in done:
            choice, usage = body["choices"][0], body["usage"]
            check(choice["finish_reason"] in ("length", "stop"),
                  f"finish_reason {choice['finish_reason']!r}")
            check(1 <= usage["completion_tokens"] <= out_tokens,
                  f"completion_tokens {usage['completion_tokens']}")
            if choice["finish_reason"] == "length":
                check(usage["completion_tokens"] == out_tokens,
                      "finished by length short of max_tokens")
            check(prompt_tokens * 0.8 <= usage["prompt_tokens"]
                  <= prompt_tokens, f"prompt_tokens "
                  f"{usage['prompt_tokens']} not ~{prompt_tokens}")
            check(choice["text"] != "", "empty completion text")
        out["completions"] = [
            {"prompt_tokens": b["usage"]["prompt_tokens"],
             "completion_tokens": b["usage"]["completion_tokens"],
             "finish_reason": b["choices"][0]["finish_reason"],
             "seconds": round(b["_seconds"], 3)} for b in done]

        t0 = time.monotonic()
        # SAMPLED, as an OpenAI client's default request is: the one
        # request here that runs the sampling (not argmax) fused tail
        with requests.post(f"{server.url}/v1/chat/completions", json={
                "model": model_name, "stream": True,
                "max_tokens": out_tokens, "temperature": 0.7,
                "top_p": 0.9, "seed": 1,
                "messages": [{"role": "user",
                              "content": "What is a paged KV cache?"}]},
                stream=True, timeout=600) as r:
            check(r.status_code == 200, f"chat stream -> {r.status_code}")
            frames = list(sse_frames(r, t0))
        check(frames and frames[-1][1] == b"[DONE]",
              "chat stream did not end with [DONE]")
        chunks = [json.loads(p) for _, p in frames[:-1]]
        finish = chunks[-1]["choices"][0]["finish_reason"]
        deltas = [c["choices"][0]["delta"]["content"] for c in chunks[:-1]]
        check(finish in ("length", "stop"), f"chat finish {finish!r}")
        check("".join(deltas) != "", "empty chat stream")
        t_first, t_last = frames[0][0], frames[-1][0]
        out["chat_stream"] = {
            "chunks": len(deltas), "finish_reason": finish,
            # informational, not claims
            "ttft_ms": round(t_first * 1e3, 1),
            "chunks_per_s": (round((len(deltas) - 1)
                                   / (t_last - t_first), 1)
                             if t_last > t_first else None)}

        metrics = requests.get(f"{server.url}/metrics", timeout=30)
        check(metrics.status_code == 200, "/metrics not 200")
        check_gauge_zero(metrics.text, "engine_downgrades")
        check_gauge_zero(metrics.text, "engine_pool_shrinks")
    finally:
        server.close()

    # ----- chain server
    cfg = from_dict(AppConfig, {
        "vector_store": {"name": "exact-tpu"},
        # a cold server compiles inside its first requests
        "serving": {"request_timeout_s": 600.0}})
    example = QAChatbot(llm=EngineLLM(engine), embedder=embedder,
                        config=cfg)
    with tempfile.TemporaryDirectory() as upload_dir:
        chain = _Served(create_app(example, upload_dir, config=cfg))
        try:
            with open(doc_path, "rb") as f:
                r = requests.post(
                    f"{chain.url}/uploadDocument",
                    files={"file": (os.path.basename(doc_path), f)},
                    timeout=600)
            check(r.status_code == 200,
                  f"/uploadDocument -> {r.status_code}: {r.text[:200]}")
            check(example._fused_ready, "fused on-device RAG admission "
                  "did not arm (see the logged corpus-sync failure)")

            r = requests.post(f"{chain.url}/documentSearch", json={
                "content": "How are the tests run?", "num_docs": 4},
                timeout=600)
            check(r.status_code == 200, f"/documentSearch -> "
                  f"{r.status_code}: {r.text[:200]}")
            hits = r.json()
            check(1 <= len(hits) <= 4 and all(
                h["content"] and h["source"] == os.path.basename(doc_path)
                for h in hits), f"/documentSearch returned {hits!r:.200}")

            t0 = time.monotonic()
            with requests.post(f"{chain.url}/generate", json={
                    "question": "How are the tests run?",
                    "use_knowledge_base": True,
                    "num_tokens": rag_tokens},
                    stream=True, timeout=600) as r:
                check(r.status_code == 200,
                      f"/generate -> {r.status_code}")
                first = None
                body = b""
                for piece in r.iter_content(chunk_size=None):
                    if piece and first is None:
                        first = time.monotonic() - t0
                    body += piece
            text = body.decode("utf-8", "replace")
            check(text.strip() != "", "/generate streamed nothing")
            check("[error]" not in text and "[notice]" not in text,
                  f"/generate stream carries a failure frame: {text!r:.200}")
            out["rag"] = {"chunks_indexed": len(example.index._docs),
                          "search_hits": len(hits),
                          "answer_bytes": len(body),
                          "ttft_ms": round(first * 1e3, 1)}

            metrics = requests.get(f"{chain.url}/metrics", timeout=30)
            check(metrics.status_code == 200, "chain /metrics not 200")
            check_gauge_zero(metrics.text, "engine_downgrades")
        finally:
            chain.close()

    stats = engine.stats
    check(stats["downgrades"] == 0 and stats["pool_shrinks"] == 0,
          "engine degraded while serving")
    check(engine._fatal is None, f"engine fatal: {engine._fatal!r}")
    out.update(ok=True, requests=stats["requests"],
               tokens_generated=stats["tokens_generated"],
               prefills=stats["prefills"])
    emit(out)
    return out


# ------------------------------------------------------------ four chips


def phase_tp4(seed: int, cache_dir: str) -> None:
    """The tp=4 engine against the tp=1 engine on the same prompts:
    first-decode-step logits, the agreeing greedy prefix, and — because
    code that never ran on more than one chip may put everything on the
    first — each device's share of the sharded bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.engine import (Engine, EngineConfig,
                                                 SamplingParams)
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.models.configs import get_model_config
    from generativeaiexamples_tpu.parallel.mesh import MeshPlan, make_mesh

    cfg = get_model_config(MODEL)
    devs = jax.devices()[:4]
    tok = make_tokenizer(cfg.vocab_size)
    with open(os.path.join(REPO, "README.md")) as f:
        words = f.read().split()
    prompts = [tok.encode(" ".join(words[i * 40:i * 40 + 300]))[:384]
               for i in range(3)]
    sp = SamplingParams(max_tokens=32, top_k=1, ignore_eos=True)
    # limits sized to the comparison: both pools then take full capacity
    ecfg = EngineConfig(max_slots=4, max_input_length=1024,
                        max_output_length=128,
                        prefill_buckets=(512, 1024), seed=seed)
    params = make_params(cfg, seed)      # on device 0

    def first_decode_logits(p, mesh):
        """Prefill two pages of prompt 0 into a small paged pool, then
        ONE kernel-path decode step (under ``shard_map`` on the mesh):
        its (V,) logits."""
        from jax.sharding import NamedSharding

        from generativeaiexamples_tpu.parallel.sharding import (
            paged_kv_cache_spec)
        S, L = 2 * PAGE, cfg.num_layers
        nb = S // PAGE
        ids = jnp.asarray(prompts[0][:S], jnp.int32)[None, :]
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        logits, dense = jax.jit(llama.apply, static_argnums=(1,))(
            p, cfg, ids, pos, llama.init_kv_cache(cfg, 1, S, jnp.bfloat16),
            kv_valid_len=jnp.asarray([S], jnp.int32))
        nxt = jnp.argmax(logits[0, -1]).astype(jnp.int32)

        def to_pool(x):   # (L,1,S,KV,hd) -> pages 1..nb of (L,N,KV,page,hd)
            pages = x.reshape(L, nb, PAGE, cfg.num_kv_heads,
                              cfg.head_dim).swapaxes(2, 3)
            return jnp.zeros((L, nb + 2) + pages.shape[2:],
                             x.dtype).at[:, 1:nb + 1].set(pages)
        pool = {"k": to_pool(dense["k"]), "v": to_pool(dense["v"])}
        if mesh is not None:
            spec = paged_kv_cache_spec(cfg, mesh)
            pool = {k: jax.device_put(v, NamedSharding(mesh, spec[k]))
                    for k, v in pool.items()}
        i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
        out, _ = jax.jit(lambda p, tok, pool: llama.apply_decode_paged(
            p, cfg, tok, i32(S)[None], pool,
            jnp.arange(1, nb + 2, dtype=jnp.int32)[None], i32(S + 1),
            i32(nb + 1), i32(0), use_kernel=True, mesh=mesh))(
            p, nxt[None, None], pool)
        return np.asarray(out[0, 0].astype(jnp.float32))

    def run(mesh):
        engine = Engine(params, cfg, tok, ecfg, mesh=mesh)
        t0 = time.monotonic()
        engine.prewarm()
        t_prewarm = time.monotonic() - t0
        engine.start()
        try:
            streams = [engine.submit(p, sp) for p in prompts]
            for s in streams:
                s.text()
            check(all(s.finish_reason == "length" for s in streams),
                  "a stream did not run to length")
            tokens = [list(s.token_ids) for s in streams]
        finally:
            engine.stop()
        return engine, tokens, t_prewarm

    eng1, toks1, pw1 = run(None)
    rep1 = engine_report(eng1)
    check_engine(rep1, tail="fused")
    logits1 = first_decode_logits(eng1.params, None)
    pool1 = sum(int(v.nbytes) for v in eng1._state["cache"].values())
    eng1._state = None      # free device 0's tp=1 pool before the mesh

    mesh = make_mesh(MeshPlan(tp=4), devs)
    eng4, toks4, pw4 = run(mesh)
    rep4 = engine_report(eng4)
    check_engine(rep4, tail="fused_tp")
    logits4 = first_decode_logits(eng4.params, mesh)

    err = float(np.max(np.abs(logits1 - logits4))
                / np.max(np.abs(logits1)))
    agree = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b),
                  min(len(x), len(y))) for x, y in zip(toks1, toks4)]
    # per-device bytes of everything that is sharded over tp
    def shares(tree):
        per = {d.id: 0 for d in devs}
        total = 0
        for leaf in jax.tree.leaves(tree):
            if len(leaf.sharding.device_set) < 4 or \
                    leaf.sharding.is_fully_replicated:
                continue
            total += leaf.nbytes
            for sh in leaf.addressable_shards:
                per[sh.device.id] += sh.data.nbytes
        return total, per
    p_total, p_per = shares(eng4.params)
    c_total, c_per = shares(eng4._state["cache"])
    # informational (device 0 also still holds the tp=1 parameters)
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in devs}
    emit({"phase": "tp4", "ok": True, "model": MODEL,
          "layers": cfg.num_layers, "quant": QUANT,
          "mesh": {k: int(v) for k, v in mesh.shape.items() if v > 1},
          "tp1": {**rep1, "prewarm_s": round(pw1, 2),
                  "pool_bytes": pool1},
          "tp4": {**rep4, "prewarm_s": round(pw4, 2)},
          "first_decode_step_logits_max_rel_err": err,
          "tolerance": TOL_TP_LOGITS,
          "logits_scale": float(np.max(np.abs(logits1))),
          "agreeing_greedy_prefix": agree, "generated": 32,
          "sharded_param_bytes": p_total, "param_bytes_per_device": p_per,
          "sharded_pool_bytes": c_total, "pool_bytes_per_device": c_per,
          "bytes_in_use_per_device": in_use,
          "compile_cache_entries": cache_entries(cache_dir)})
    check(np.all(np.isfinite(logits4)), "tp=4 logits not finite")
    check(err <= TOL_TP_LOGITS, f"tp=4 vs tp=1 logits differ by {err}")
    # Random weights leave near-ties that reduction order may flip, so
    # the prefix lengths are reported; only total disagreement fails.
    check(max(agree) >= 1, "tp=4 and tp=1 agree on no first token")
    check(p_total > 0 and c_total > 0, "nothing is sharded over tp")
    for total, per in ((p_total, p_per), (c_total, c_per)):
        for dev_id, n in per.items():
            check(abs(n - total / 4) <= 0.02 * total,
                  f"device {dev_id} holds {n} of {total} sharded bytes, "
                  f"not a quarter")


# ----------------------------------------------------------------- main


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = parser.parse_args(argv)

    # A hung phase must end the run, not hold the chip: dump every
    # thread's stack and exit non-zero before the time limit.
    faulthandler.dump_traceback_later(TIME_LIMIT_S, exit=True)
    t_start = time.monotonic()
    device = {"platform": None, "kind": None, "count": 0}
    engine = None
    try:
        device = device_info()
        cache_dir = phase_device(device, args.chips)
        if args.chips == 4:
            phase_tp4(args.seed, cache_dir)
        else:
            phase_kernels(args.seed)
            engine, embedder = phase_engine(args.seed, cache_dir)
            phase_serve(engine, embedder, MODEL, device["kind"],
                        os.path.join(REPO, "README.md"))
    except BaseException:  # noqa: BLE001 — reported, then exit non-zero
        traceback.print_exc()
        emit({"ok": False, "device": device,
              "seconds": round(time.monotonic() - t_start, 1)})
        return 1
    finally:
        if engine is not None:
            engine.stop()
    emit({"phase": "done", "seconds": round(time.monotonic() - t_start, 1)})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
