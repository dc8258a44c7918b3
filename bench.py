"""End-to-end serving benchmark (run on real TPU hardware by the driver).

Measures the canonical QA-chatbot serving path (BASELINE.json north star:
<200 ms p50 TTFT for the llama-2-7b chatbot; the reference publishes no
numbers of its own — BASELINE.md):

1. Engine: p50/p99 time-to-first-token and aggregate decode throughput
   through the real continuous-batching engine (paged KV, multi-step decode
   rounds, dispatch-ahead).
2. HBM roofline: achieved bytes/s during steady decode vs the chip's peak
   memory bandwidth — the number that exposes scheduler overhead.
3. E2E chatbot: TTFT through the chain server over HTTP (retrieve -> embed
   query on-device -> prompt template -> engine prefill -> first SSE chunk),
   i.e. the reference's POST /generate hot path (common/server.py:121-142).
4. Multi-turn chat: warm-turn (shared-prefix KV cache hit) engine TTFT vs
   the cold start, over a conversation with a shared system prompt and
   growing history (run_chat_bench).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "ms", "vs_baseline": N, ...}
``vs_baseline`` = baseline_ms / measured_ms (>1 ⇒ beating the target).

Env knobs: BENCH_MODEL (default llama-2-7b-chat), BENCH_QUANT (int8 default
— 7B bf16 + KV + embedder does not fit 16 GB HBM; the reference quotes
30 GB for 7B fp16 and ships int4-AWQ for small-memory parts,
docs/rag/support_matrix.md:4-12 — none|int8|int4 to override),
BENCH_PROMPT_LEN, BENCH_OUTPUT_LEN, BENCH_REQUESTS, BENCH_SLOTS,
BENCH_STEPS_PER_ROUND, BENCH_DISPATCH_DEPTH, BENCH_SKIP_E2E,
BENCH_AUTOSCALE (=1 runs the diurnal-trace autoscale scenario —
docs/autoscaling.md; BENCH_AUTOSCALE_REPLICAS/SECONDS/TRACE/MIN/
TOKENS/INTERVAL_S/DEADLINE_MS refine it),
BENCH_SKIP_CHAT, BENCH_CHAT_TURNS, BENCH_CHAT_SYSTEM (multi-turn chat
scenario: warm shared-prefix TTFT vs cold, engine prefix cache);
BENCH_MODEL_PATH points at a real checkpoint dir (weights + tokenizer
loaded via the import pipeline instead of random init);
BENCH_MESH=tp=1,tp=2 runs the multi-chip serving sweep (one tp-sharded
engine per mesh rung — decode tok/s + TTFT vs chips, topology-matched
round budgets; ';' separates rungs whose spec itself has commas;
BENCH_MESH_SLOTS/BENCH_MESH_REQUESTS size it).
BENCH_SLOTS_SWEEP=8,16,32,64 additionally runs the slots-ladder
capacity sweep (one engine per rung, schema-validated ``capacity``
section — per-rung TTFT/throughput/HBM roofline).

No degradation ladder: the requested model/quant builds and runs, or the
bench raises. The default scenarios (engine, chat, e2e) are fatal on
failure; the flag-gated scenarios still degrade to null (ROADMAP S1).
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import threading
import time
from typing import Optional, Sequence

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

TTFT_BASELINE_MS = 200.0

# Single-sourced roofline denominator (utils/hbm.py) — profile_decode
# reads the same table, so both artifacts agree per hardware.
from generativeaiexamples_tpu.utils.hbm import peak_bw as _peak_bw  # noqa: E402


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def build_embedder():
    """Real on-device encoder (e5-large-v2 geometry, random init — identical
    compute cost to real weights). Built BEFORE the engine so the auto-sized
    KV pool accounts for its memory."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.embed.encoder import EmbeddingService
    from generativeaiexamples_tpu.models import encoder
    from generativeaiexamples_tpu.models.configs import E5_LARGE_V2
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer

    params = jax.jit(
        lambda key: encoder.init_params(E5_LARGE_V2, key, dtype=jnp.bfloat16)
    )(jax.random.key(1))
    jax.block_until_ready(params)
    return EmbeddingService(params, E5_LARGE_V2, ByteTokenizer())


def bench_tokenizer(vocab_size: int):
    """The vendored 32k sentencepiece model (tools/train_tokenizer.py) —
    llama-2 vocab geometry with realistic English compression, so e2e
    prompts tokenize to hundreds of tokens, not the ~1k byte-level ones
    that distorted the round-3 number (VERDICT r3 weak #4)."""
    from generativeaiexamples_tpu.models.sentencepiece import (
        SentencePieceTokenizer)
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "generativeaiexamples_tpu", "assets",
                        "tokenizer_32k.model")
    if os.path.exists(path):
        tok = SentencePieceTokenizer(path)
        if tok.vocab_size <= vocab_size:
            return tok
    return ByteTokenizer()


def build_engine(model_name: str, slots: int, prompt_len: int, out_len: int,
                 quant: str):
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.engine import Engine, EngineConfig
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.models.configs import get_model_config
    from generativeaiexamples_tpu.ops.quant import quantize_params

    cfg = get_model_config(model_name)

    # BENCH_MODEL_PATH: bench against REAL weights + the checkpoint's own
    # tokenizer (VERDICT r3 weak #4 — random init is compute-identical,
    # but only a real checkpoint exercises import + generation quality).
    # Default remains random init so the driver's bench needs no model
    # download.
    ckpt = os.environ.get("BENCH_MODEL_PATH", "")
    if ckpt:
        from generativeaiexamples_tpu.models.import_hf import (
            load_checkpoint)
        from generativeaiexamples_tpu.models.tokenizer import get_tokenizer
        params = load_checkpoint(ckpt, cfg, dtype=jnp.bfloat16)
        if quant != "none":
            params = quantize_params(params, quant)
        params = jax.device_put(params)
        tokenizer = get_tokenizer(ckpt)
    else:
        def make(key):
            params = llama.init_params(cfg, key, dtype=jnp.bfloat16)
            if quant != "none":
                params = quantize_params(params, quant)
            return params

        params = jax.jit(make)(jax.random.key(0))
        tokenizer = bench_tokenizer(cfg.vocab_size)
    jax.block_until_ready(params)

    # Engine limits sized to the measured geometry (plus slack for the e2e
    # chatbot's templated prompts, which run ~1k byte-tokens) — a
    # 3072-token ceiling would force a prefill bucket + page tables the
    # bench never exercises and eat the KV pool's HBM budget (round-2 OOM,
    # VERDICT weak #1). BENCH_MAX_INPUT shrinks the ceiling further for
    # capacity sweeps (engine-only, prompt_len known): the prefill
    # headroom reserve is 3x the largest bucket's dense KV
    # (~0.5 MB/token on 7B), so every bucket rung not needed by the
    # measured geometry costs real pool pages.
    max_in = int(os.environ.get("BENCH_MAX_INPUT", "0")) \
        or max(2048, prompt_len)
    max_out = max(128, out_len)
    # One-shot buckets cap at 1024 (the e2e chatbot's templated prompts
    # run ~1k byte-tokens): the prefill headroom reserve scales with the
    # LARGEST bucket, so a 2048 one-shot rung costs ~1.5 GB of pool
    # pages; rare longer prompts stream through the chunked
    # paged-prefill admission instead.
    bucket_cap = min(1024, max_in)
    buckets = tuple(b for b in (512, bucket_cap) if b <= bucket_cap)
    # BENCH_KV_POOL_TOKENS pins the pool for capacity-tuned rungs
    # (default: auto-sized from the device's memory_stats)
    pool_tokens = os.environ.get("BENCH_KV_POOL_TOKENS", "")
    ecfg = EngineConfig(
        max_slots=slots, max_input_length=max_in, max_output_length=max_out,
        prefill_buckets=buckets, dtype="bfloat16",
        kv_pool_tokens=int(pool_tokens) if pool_tokens else "auto",
        max_prefill_bucket=bucket_cap if max_in > bucket_cap else None,
        kv_quant=os.environ.get("BENCH_KV_QUANT", ""),
        steps_per_round=int(os.environ.get("BENCH_STEPS_PER_ROUND", "16")),
        dispatch_depth=int(os.environ.get("BENCH_DISPATCH_DEPTH", "2")),
        # BENCH_SPEC=1: speculative decoding (prompt-lookup drafting +
        # batched verification, engine/spec_decode.py). The chat and
        # open-loop scenarios then grow a ``spec`` block with the run's
        # acceptance rate and tokens-per-step multiplier.
        spec_decode=os.environ.get("BENCH_SPEC", "") not in ("", "0"))
    engine = Engine(params, cfg, tokenizer, ecfg)
    # Allocate-and-verify: serves the worst-case request once, so a
    # mis-sized pool fails (or loudly shrinks) here, not mid-measurement.
    engine.prewarm()
    return engine, cfg


def run_engine_bench(engine, prompt_len: int, out_len: int, n_requests: int,
                     slots: int):
    from generativeaiexamples_tpu.engine import SamplingParams

    prompt_ids = list(range(3, 3 + 250)) * (prompt_len // 250 + 1)
    prompt_ids = prompt_ids[:prompt_len]
    sp = SamplingParams(max_tokens=out_len, top_k=1, ignore_eos=True)

    # Warmup: compile prefill/insert/decode-round for this geometry —
    # including every right-sized tail round (steps ladder, powers of two
    # up to steps_per_round) — so the measured phases never hit a compile.
    engine.start()
    engine.submit(prompt_ids, SamplingParams(max_tokens=out_len, top_k=1,
                                             ignore_eos=True)).text()
    steps = engine.cfg.steps_per_round
    ladder = []
    s = 1
    while s < steps:
        ladder.append(s)
        s *= 2
    for s in ladder:  # max_tokens=s+1 -> a final round of exactly s steps
        engine.submit(prompt_ids, SamplingParams(
            max_tokens=s + 1, top_k=1, ignore_eos=True)).text()

    # TTFT: sequential requests against an idle engine (the reference's
    # single-user chat scenario). Each request's LEADING tokens are
    # unique (two varied tokens -> 15625 distinct first blocks, residues
    # 4..128 disjoint from the decode-window loop's 130..254 below) so
    # the prefix cache never matches — this metric stays the COLD-start
    # TTFT it always was (r05-comparable); warm-turn TTFT is measured by
    # the chat scenario (run_chat_bench) next to it.
    ttfts = []
    for i in range(n_requests):
        stream = engine.submit(
            [4 + (i % 125), 4 + ((i // 125) % 125)] + prompt_ids[2:],
            SamplingParams(max_tokens=2, top_k=1, ignore_eos=True))
        stream.text()
        ttfts.append(stream.ttft_ms)
    ttfts.sort()
    p50 = ttfts[len(ttfts) // 2]
    p99 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))]

    # Throughput: steady-state decode rate with every slot mid-generation,
    # sampled from engine stats between first-token-everywhere and the
    # first completion — serialized admission prefills and the drain tail
    # would otherwise pollute the number (r3 under-reported ~2x).
    long_sp = SamplingParams(max_tokens=out_len * 2, top_k=1,
                             ignore_eos=True)
    # distinct first tokens, in a residue range (130..254) disjoint from
    # the TTFT loop's (4..128): every slot's prefill stays cold however
    # large BENCH_REQUESTS/BENCH_SLOTS get, so the steady-decode window
    # measures the same work as previous rounds
    streams = [engine.submit(
        [130 + (j % 125), 4 + ((j // 125) % 125)] + prompt_ids[2:],
        long_sp) for j in range(slots)]
    deadline = time.monotonic() + 300
    while any(s.first_token_time is None for s in streams) \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    t0 = time.monotonic()
    tok0 = engine.stats["tokens_generated"]
    t_last, tok_last = t0, tok0
    while not any(s.finish_time for s in streams) \
            and time.monotonic() < deadline:
        time.sleep(0.05)
        t_last, tok_last = time.monotonic(), engine.stats["tokens_generated"]
    for s in streams:
        s.cancel()
    total = 0
    for s in streams:
        s.text()
        total += len(s.token_ids)
    if tok_last - tok0 >= slots * engine.cfg.steps_per_round \
            and t_last > t0:
        tput = (tok_last - tok0) / (t_last - t0)
    else:  # degenerate window: fall back to wall-clock over everything
        tput = total / max(time.monotonic() - t0, 1e-6)
    return p50, p99, tput, time.monotonic() - t0


def spec_snapshot(before: dict, after: dict):
    """Speculative-decoding delta between two engine.stats snapshots:
    the scenario's drafted/accepted counts, acceptance rate, and the
    tokens-per-model-step multiplier over its verify rounds. None when
    the window saw no verify round (spec off, or nothing draftable) —
    scenarios publish ``spec: null`` rather than a block of zeros."""
    rounds = int(after.get("spec_verify_rounds", 0)
                 - before.get("spec_verify_rounds", 0))
    if rounds <= 0:
        return None
    drafted = int(after.get("spec_draft_tokens", 0)
                  - before.get("spec_draft_tokens", 0))
    accepted = int(after.get("spec_accepted_tokens", 0)
                   - before.get("spec_accepted_tokens", 0))
    tokens = int(after.get("spec_verify_tokens", 0)
                 - before.get("spec_verify_tokens", 0))
    slot_steps = int(after.get("spec_verify_slot_steps", 0)
                     - before.get("spec_verify_slot_steps", 0))
    return {
        "draft_tokens": drafted,
        "accepted_tokens": accepted,
        "verify_rounds": rounds,
        "acceptance_rate": round(accepted / drafted, 4) if drafted else 0.0,
        "tokens_per_step": (round(tokens / slot_steps, 4) if slot_steps
                            else 0.0),
    }


def run_chat_bench(engine, n_turns: int = 6, system_len: int = 512,
                   user_len: int = 64, reply_len: int = 32,
                   warmup: bool = True):
    """Multi-turn chat scenario: the prefix-cache workload.

    Every turn's prompt is the shared system prompt + the FULL prior
    conversation + a new user message — exactly the traffic shape where
    recomputing prefill is pure waste. Turn 1 is the cold start (empty
    cache for this conversation); turns 2+ hit the cached prefix and
    prefill only the new suffix. Reports warm-turn TTFT next to the
    cold number plus the engine's prefix-cache counters for the run
    (``prefix_cache_hit_tokens`` asserts prefill actually started at
    the first uncached token rather than the TTFT delta being noise).

    ``warmup`` runs a throwaway conversation with DIFFERENT content
    first: same shapes, so every suffix-chunk program is compiled
    before measurement, but different block hashes, so the measured
    turn 1 stays genuinely cold.
    """
    import statistics

    from generativeaiexamples_tpu.engine import SamplingParams

    vocab = getattr(engine.model_cfg, "vocab_size", 32000)
    span = min(vocab - 4, 250)

    def ids(seed: int, n: int) -> list:
        return [(seed * 131 + 7 * i) % span + 4 for i in range(n)]

    sp = SamplingParams(max_tokens=reply_len, top_k=1, ignore_eos=True)
    max_prompt = engine.cfg.max_input_length

    def run_convo(tag: int):
        history = ids(tag, system_len)
        cold, warm = None, []
        for t in range(n_turns):
            prompt = history + ids(tag * 1009 + t + 1, user_len)
            if len(prompt) >= max_prompt:
                break
            stream = engine.submit(prompt, sp)
            stream.text()
            if t == 0:
                cold = stream.ttft_ms
            else:
                warm.append(stream.ttft_ms)
            history = prompt + stream.token_ids
        return cold, warm

    engine.start()
    if warmup:
        run_convo(tag=7919)
    before = engine.stats
    cold, warm = run_convo(tag=1)
    after = engine.stats
    hit = int(after.get("prefix_cache_hit_tokens", 0)
              - before.get("prefix_cache_hit_tokens", 0))
    lookup = int(after.get("prefix_cache_lookup_tokens", 0)
                 - before.get("prefix_cache_lookup_tokens", 0))
    return {
        "turns": 1 + len(warm),
        "system_prompt_tokens": system_len,
        "cold_ttft_ms": round(cold, 2) if cold else None,
        "warm_p50_ttft_ms": (round(statistics.median(warm), 2)
                             if warm else None),
        "warm_min_ttft_ms": round(min(warm), 2) if warm else None,
        "warm_ttfts_ms": [round(w, 2) for w in warm],
        "prefix_cache_hit_tokens": hit,
        "prefix_cache_hit_rate": (round(hit / lookup, 3) if lookup
                                  else 0.0),
        "prefix_cache_evicted_pages": int(
            after.get("prefix_cache_evicted_pages", 0)
            - before.get("prefix_cache_evicted_pages", 0)),
        # Speculative decoding over the measured conversation (null
        # when spec is off / nothing was draftable): chat replies
        # copying spans of the history are prompt-lookup's best case,
        # so this is the headline tokens-per-step scenario.
        "spec": spec_snapshot(before, after),
    }


def run_openloop_bench(engine, *, rates, duration_s=10.0, slo_ttft_ms=500.0,
                       deadline_ms=2000.0, prompt_median=256,
                       prompt_sigma=0.6, out_len=32, seed=0):
    """Open-loop Poisson-arrival scenario: SLO attainment and goodput
    under OFFERED load, the production-shaped metric the closed-loop
    p50 scenarios cannot produce (a closed loop self-throttles to the
    engine's pace; millions of users do not).

    Per swept rate in ``rates`` (requests/sec): arrivals follow a
    Poisson process (exponential inter-arrival times), prompt lengths a
    LOGNORMAL mix around ``prompt_median`` (the chat-traffic shape: many
    short, a heavy tail of long — exactly what the token-budget
    scheduler interleaves), and every request carries a deadline of
    ``deadline_ms``. Submission never waits for completions — overload
    shows up as shed 429s, ``deadline_queue`` drops, and blown TTFTs
    instead of a silently stretched run.

    Headline per rate: **slo_attainment** (fraction of OFFERED requests
    whose first token beat ``slo_ttft_ms`` AND whose generation finished
    normally before its deadline) and **goodput_tokens_per_sec** (tokens
    from SLO-met requests only, over the rate's wall window — work that
    arrived too late to matter does not count).

    Deterministic per ``seed``; leading prompt tokens are unique per
    request so every admission is a cold prefill (warm-path TTFT is the
    chat scenario's metric, not this one's).
    """
    import numpy as _np

    from generativeaiexamples_tpu.engine import SamplingParams
    from generativeaiexamples_tpu.utils.errors import SchedulerFullError

    max_in = engine.cfg.max_input_length
    sp = SamplingParams(max_tokens=out_len, top_k=1, ignore_eos=True)
    out = {
        "arrival_rps_sweep": [float(r) for r in rates],
        "duration_s": float(duration_s),
        "slo_ttft_ms": float(slo_ttft_ms),
        "deadline_ms": float(deadline_ms) if deadline_ms else None,
        "prompt_len_median": int(prompt_median),
        "prompt_len_sigma": float(prompt_sigma),
        "output_len": int(out_len),
        "rates": [],
        "spec": None,   # filled from the stats delta after the sweep
    }
    engine.start()
    spec_before = engine.stats
    uid = 0   # unique per submission ACROSS rates — see prompt below
    for rate in rates:
        rng = _np.random.RandomState(seed)
        n = max(1, int(rate * duration_s))
        gaps = rng.exponential(1.0 / rate, size=n)
        lens = _np.clip(rng.lognormal(_np.log(prompt_median), prompt_sigma,
                                      size=n).astype(int), 4, max_in)
        streams, shed = [], 0
        t_start = time.monotonic()
        next_t = t_start
        for i in range(n):
            next_t += gaps[i]
            delay = next_t - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            # The 3-token head is unique per submission across the WHOLE
            # sweep (125^3 ≈ 1.9M, far past any realistic rps×duration),
            # not just within one rate: prefix-cache block hashes chain
            # from block 0, so differing heads keep every admission a
            # cold prefill — identical prompts would let a later rate
            # ride an earlier rate's warm pages and measure warm TTFTs
            # against the first rate's cold ones.
            prompt = [4 + (uid % 125), 130 + ((uid // 125) % 125),
                      4 + ((uid // 15625) % 125)] \
                + [3 + (j % 251) for j in range(int(lens[i]) - 3)]
            uid += 1
            deadline_t = (time.monotonic() + deadline_ms / 1e3
                          if deadline_ms else None)
            try:
                streams.append(engine.submit(prompt, sp,
                                             deadline_t=deadline_t))
            except SchedulerFullError:
                shed += 1   # open loop: the 429 IS the datapoint
        # Drain: every accepted stream terminates on its own (deadline
        # enforcement guarantees it); .text() just joins them.
        for s in streams:
            try:
                s.text()
            except Exception:  # noqa: BLE001 — errored streams counted below
                pass
        elapsed = time.monotonic() - t_start
        offered = n
        deadline_drops = sum(1 for s in streams
                             if s.finish_reason == "deadline_queue")
        completed = sum(1 for s in streams
                        if s.finish_reason in ("eos", "length", "stop"))
        met = [s for s in streams
               if s.finish_reason in ("eos", "length", "stop")
               and s.ttft_ms is not None and s.ttft_ms <= slo_ttft_ms]
        good_tokens = sum(len(s.token_ids) for s in met)
        ttfts = sorted(s.ttft_ms for s in streams if s.ttft_ms is not None)
        out["rates"].append({
            "arrival_rps": float(rate),
            "offered": offered,
            "completed": completed,
            "shed": shed,
            "deadline_drops": deadline_drops,
            "slo_attainment": round(len(met) / offered, 4),
            "goodput_tokens_per_sec": round(good_tokens / elapsed, 1),
            "ttft_p50_ms": (round(ttfts[len(ttfts) // 2], 2)
                            if ttfts else None),
            "ttft_p99_ms": (round(
                ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))], 2)
                if ttfts else None),
            "tokens_total": sum(len(s.token_ids) for s in streams),
        })
    # Speculative decoding over the whole sweep (null when spec is off):
    # open-loop prompts are cold/unique, so acceptance here reflects
    # generated-token self-repetition, not warm prompt copying — the
    # pessimistic bound next to the chat scenario's optimistic one.
    out["spec"] = spec_snapshot(spec_before, engine.stats)
    return out


def serve_apps(apps: list):
    """Serve N aiohttp apps on one background event loop, each on an
    ephemeral port. Returns (urls, stop_fn). Shared by the fleet
    scenario (N chain replicas + the router in one process) and its
    tier-1 smoke test."""
    from aiohttp import web

    loop = asyncio.new_event_loop()
    box: dict = {"ports": []}
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            for app in apps:
                runner = web.AppRunner(app)
                await runner.setup()
                site = web.TCPSite(runner, "127.0.0.1", 0)
                await site.start()
                box["ports"].append(runner.addresses[0][1])
        loop.run_until_complete(boot())
        started.set()
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    if not started.wait(60):
        raise RuntimeError("fleet servers failed to boot")

    def stop():
        loop.call_soon_threadsafe(loop.stop)

    return [f"http://127.0.0.1:{p}" for p in box["ports"]], stop


def _sweep_pool_geometry(prompt_len: int, out_len: int,
                         engine_overrides: dict,
                         env_override: str = "") -> tuple[int, int]:
    """Per-rung pool sizing shared by the capacity and multichip sweeps:
    every slot holds its full decode window (prompt + 2x output, rounded
    UP to the engine's power-of-two window rung — the jnp fallback path
    gathers the bucketed window, not the exact page count) so
    ``decode_window_steady`` holds by construction on both kernel and
    fallback paths. Returns ``(page, per_slot_tokens)``;
    ``env_override`` names an env var whose per-slot token count wins
    (the capacity sweep's ``BENCH_SWEEP_KV_POOL_TOKENS``)."""
    page = int(engine_overrides.get("page_size", 128))
    need_pages = -(-(prompt_len + 2 * out_len + 2) // page)
    win_pages = 1
    while win_pages < need_pages:
        win_pages *= 2
    per_slot = win_pages * page
    if env_override:
        per_slot = int(os.environ.get(env_override, "0")) or per_slot
    return page, per_slot


def _sweep_engine_kw(slots: int, prompt_len: int, out_len: int,
                     page: int, per_slot: int, kv_quant: str,
                     steps_per_round: int, engine_overrides: dict,
                     **extra) -> dict:
    """One sweep rung's EngineConfig kwargs: production defaults, with
    ``engine_overrides`` (tests: tiny page/bucket geometry) winning over
    everything except the rung's slot count."""
    kw = dict(
        max_slots=slots, max_input_length=max(2048, prompt_len + 8),
        max_output_length=max(128, 2 * out_len),
        prefill_buckets=(512, 1024), dtype="bfloat16",
        kv_pool_tokens=slots * per_slot + page,
        kv_quant=kv_quant, steps_per_round=steps_per_round,
        dispatch_depth=int(os.environ.get("BENCH_DISPATCH_DEPTH", "2")),
        **extra)
    kw.update(engine_overrides)
    kw["max_slots"] = slots
    return kw


def run_capacity_sweep(params, model_cfg, tokenizer, rungs, *,
                       prompt_len: int, out_len: int, n_requests: int,
                       kv_quant: str = "", steps_per_round: int = 16,
                       **engine_overrides):
    """Slots-ladder capacity sweep (``BENCH_SLOTS_SWEEP=8,16,32,64``):
    one engine per slot rung over SHARED params, each run through the
    closed-loop TTFT + steady-decode measurement and the HBM roofline —
    the BENCH_SWEEP_r05-style capacity table as one automated,
    schema-validated ``capacity`` section instead of N hand-rolled
    single-rung bench invocations.

    Each rung's pool is sized to hold every slot's full decode window
    (prompt + 2x output, rounded UP to the engine's power-of-two window
    rung — the jnp fallback path gathers the bucketed window, not the
    exact page count) so ``decode_window_steady`` holds by construction
    on both kernel and fallback paths and the per-rung roofline number
    is comparable across the ladder; ``BENCH_SWEEP_KV_POOL_TOKENS``
    overrides (per-slot tokens) for HBM-constrained sweeps."""
    from generativeaiexamples_tpu.engine import Engine, EngineConfig

    page, per_slot = _sweep_pool_geometry(
        prompt_len, out_len, engine_overrides,
        env_override="BENCH_SWEEP_KV_POOL_TOKENS")
    out = []
    for slots in rungs:
        kw = _sweep_engine_kw(slots, prompt_len, out_len, page, per_slot,
                              kv_quant, steps_per_round, engine_overrides)
        engine = Engine(params, model_cfg, tokenizer, EngineConfig(**kw))
        try:
            engine.prewarm()
            p50, p99, tput, _ = run_engine_bench(
                engine, prompt_len, out_len, n_requests, slots)
            achieved, util, steady = hbm_utilization(
                engine, model_cfg, tput, slots, prompt_len, out_len)
            stats = engine.stats
            rows = int(stats.get("sampler_rows_sampled", 0))
            skipped = int(stats.get("sampler_rows_skipped", 0))
            out.append({
                "slots": slots,
                "engine_p50_ttft_ms": round(p50, 2),
                "engine_p99_ttft_ms": round(p99, 2),
                "decode_tokens_per_sec": round(tput, 1),
                "tokens_per_sec_per_slot": round(tput / slots, 1),
                "hbm_bw_achieved_gbps": round(achieved / 1e9, 1),
                "hbm_bw_util": round(util, 3),
                "decode_window_steady": steady,
                # Fused-tail occupancy: fraction of unembed/sampler rows
                # the active-slot compaction skipped (partial occupancy
                # during ramp-up/drain — proves the tail is sized to
                # occupancy, not max_slots).
                "sampler_rows_skipped_frac": round(
                    skipped / max(1, rows + skipped), 3),
            })
        finally:
            engine.stop()
        import gc
        gc.collect()
    return {
        "slots_sweep": list(rungs),
        "prompt_len": prompt_len,
        "output_len": out_len,
        "requests_per_rung": n_requests,
        "kv_pool_tokens_per_slot": per_slot,
        "rungs": out,
    }


def parse_mesh_rung(spec: str) -> tuple[str, dict, int]:
    """``"tp=2"`` (or ``"tp=2,sp=2"``) -> (canonical label, axis dict,
    device count). ``"tp=1"`` is the single-chip rung (no mesh). Typo'd
    axes fail loudly (``parallel.mesh.parse_mesh_spec``) — they would
    otherwise abort the sweep mid-ladder or, worse, silently measure a
    single-chip rung under a mesh-looking label."""
    from generativeaiexamples_tpu.engine.scheduler import topology_key
    from generativeaiexamples_tpu.parallel.mesh import parse_mesh_spec
    axes = parse_mesh_spec(spec)
    devices = 1
    for v in axes.values():
        devices *= v
    return topology_key(axes), axes, devices


def split_mesh_rungs(env: str) -> list[str]:
    """``BENCH_MESH`` -> rung specs. ``;`` always separates rungs (the
    unambiguous form for multi-axis meshes). Without one, a comma
    starts a NEW rung only when its axis already appears in the rung
    being built — a mesh never repeats an axis — so ``tp=1,tp=2,tp=4``
    is three rungs while ``tp=2,sp=2`` stays one 4-device mesh."""
    if ";" in env:
        return [m.strip() for m in env.split(";") if m.strip()]
    rungs: list[str] = []
    current: list[str] = []
    seen: set = set()
    for part in (p.strip() for p in env.split(",") if p.strip()):
        axis = part.partition("=")[0].strip()
        if axis in seen:
            rungs.append(",".join(current))
            current, seen = [], set()
        current.append(part)
        seen.add(axis)
    if current:
        rungs.append(",".join(current))
    return rungs


def run_multichip_sweep(params, model_cfg, tokenizer, rungs, *,
                        prompt_len: int, out_len: int, n_requests: int,
                        slots: int = 8, kv_quant: str = "",
                        steps_per_round: int = 16, spec: bool = False,
                        **engine_overrides):
    """Multi-chip serving sweep (``BENCH_MESH=tp=1,tp=2,...``): one
    ENGINE per mesh rung over shared (re-sharded) params, each run
    through the closed-loop TTFT + steady-decode measurement — the
    proof rung that decode tokens/s scales and TTFT drops with chips,
    now that the WHOLE decode hot path (fused sharded sampler tail,
    speculative verify, topology-priced round budget) runs tp-sharded
    instead of falling back. Each rung records the round budget the
    engine derived BEFORE any traffic plus the cost row it came from
    (``cost_source``/``cost_topology``) — the observable trail from
    ``tools/profile_decode.py --mesh`` artifact to first-round
    scheduling. On CPU, tier-1 drives this over the virtual 8-device
    host platform (tests/test_bench_multichip.py)."""
    import jax

    from generativeaiexamples_tpu.engine import Engine, EngineConfig
    from generativeaiexamples_tpu.parallel import MeshPlan, make_mesh

    page, per_slot = _sweep_pool_geometry(prompt_len, out_len,
                                          engine_overrides)
    out = []
    # Parse every rung spec BEFORE building any engine: a typo'd rung
    # must fail the sweep upfront, not abort mid-ladder after paying for
    # (and then discarding) the rungs already measured.
    parsed = [parse_mesh_rung(str(r)) for r in rungs]
    for label, axes, devices in parsed:
        if devices > jax.local_device_count():
            sys.stderr.write(
                f"bench: mesh rung {label} needs {devices} devices, "
                f"have {jax.local_device_count()}; skipping\n")
            continue
        mesh = None
        if devices > 1:
            mesh = make_mesh(MeshPlan(**axes), jax.devices()[:devices])
        kw = _sweep_engine_kw(slots, prompt_len, out_len, page, per_slot,
                              kv_quant, steps_per_round, engine_overrides,
                              spec_decode=spec)
        engine = Engine(params, model_cfg, tokenizer,
                        EngineConfig(**kw), mesh=mesh)
        try:
            # Budget BEFORE traffic: the acceptance-relevant fact is the
            # topology-matched PRIOR the first rounds plan under, not
            # whatever the online calibrator converges to mid-run.
            stats0 = engine.stats
            cost = engine._sched._static_cost
            engine.prewarm()
            p50, p99, tput, _ = run_engine_bench(
                engine, prompt_len, out_len, n_requests, slots)
            stats = engine.stats
            out.append({
                "mesh": label,
                "devices": devices,
                "engine_p50_ttft_ms": round(p50, 2),
                "engine_p99_ttft_ms": round(p99, 2),
                "decode_tokens_per_sec": round(tput, 1),
                "tokens_per_sec_per_device": round(tput / devices, 1),
                # The first-seconds scheduling contract: the budget the
                # engine derived from the topology-matched cost row at
                # build time, and which artifact/row supplied it.
                "sched_round_budget_tokens": int(
                    stats0["sched_round_budget_tokens"]),
                "cost_source": cost.source,
                "cost_topology": cost.topology,
                # Which tail actually served: the whole point of the
                # sweep is that a mesh rung reads "fused_tp", not
                # "materialized".
                "tail": ("fused_tp" if engine.programs.tail.kind == "sharded"
                         else "fused" if engine._fused_tail
                         else "materialized"),
                "engine_downgrades": int(stats["downgrades"]),
                "spec": spec_snapshot({}, stats),
            })
        finally:
            engine.stop()
        import gc
        gc.collect()
    if not out:
        return None
    return {
        "mesh_sweep": [label for label, _, _ in parsed],
        "prompt_len": prompt_len,
        "output_len": out_len,
        "requests_per_rung": n_requests,
        "slots": slots,
        "rungs": out,
    }


def build_fleet_engines(params, model_cfg, tokenizer, n: int,
                        host_pool_tokens: int = 0,
                        roles: Sequence[str] = (),
                        max_input_length: int = 2048,
                        steps_per_round: int | None = None):
    """N small replica engines over SHARED params (read-only on device —
    weights are never duplicated) with explicit, modest KV pools
    (``BENCH_FLEET_KV_POOL_TOKENS``, default 4096 tokens each): the main
    bench engine's auto-sized pool still holds its HBM, so auto-sizing
    here would starve; prewarm's shrink-on-OOM absorbs the rest.
    ``host_pool_tokens`` > 0 enables the host KV tier on every replica
    (the cross-replica transfer arm needs it to land fetched pages).
    ``roles`` assigns each replica a disaggregation role
    (docs/disaggregation.md) — empty means all-unified."""
    import dataclasses

    from generativeaiexamples_tpu.engine import Engine, EngineConfig

    pool = int(os.environ.get("BENCH_FLEET_KV_POOL_TOKENS", "4096"))
    slots = int(os.environ.get("BENCH_FLEET_SLOTS", "4"))
    ecfg = EngineConfig(
        max_slots=slots, max_input_length=max_input_length,
        max_output_length=128,
        prefill_buckets=(512, 1024), dtype="bfloat16",
        kv_pool_tokens=pool,
        kv_quant=os.environ.get("BENCH_KV_QUANT", ""),
        steps_per_round=(int(os.environ.get("BENCH_STEPS_PER_ROUND", "16"))
                         if steps_per_round is None else steps_per_round),
        dispatch_depth=int(os.environ.get("BENCH_DISPATCH_DEPTH", "2")),
        kv_host_pool_tokens=max(0, int(host_pool_tokens)))
    # Mask the env overrides for the build: KV_HOST_POOL_TOKENS /
    # ENGINE_ROLE beat the config fields inside Engine, and the fleet
    # arms' tier + role settings must come from the arm matrix, not from
    # whatever the operator pinned for the MAIN measured engine.
    saved = os.environ.pop("KV_HOST_POOL_TOKENS", None)
    saved_role = os.environ.pop("ENGINE_ROLE", None)
    try:
        engines = [Engine(params, model_cfg, tokenizer,
                          dataclasses.replace(
                              ecfg, role=(roles[i] if i < len(roles)
                                          else "unified")))
                   for i in range(n)]
    finally:
        if saved is not None:
            os.environ["KV_HOST_POOL_TOKENS"] = saved
        if saved_role is not None:
            os.environ["ENGINE_ROLE"] = saved_role
    for e in engines:
        e.prewarm()
    return engines


def run_fleet_bench(engines, *, sessions=6, turns=4, session_rps=2.0,
                    system_chars=1200, user_chars=120, num_tokens=16,
                    slo_ttft_ms=2000.0, seed=0,
                    policies=("round_robin", "affinity"),
                    transfer_arm=False,
                    heartbeat_s=0.5):
    """Multi-replica scenario: open-loop Poisson session load through the
    FLEET ROUTER over N in-process chain-server replicas (docs/router.md).

    The workload is the cross-replica version of the chat scenario:
    ``sessions`` multi-turn conversations arrive as a Poisson process at
    ``session_rps``; each session carries a session-unique system prompt
    and a growing history (the shared-prefix traffic shape), runs its
    turns sequentially (a real chat user), and every turn goes through
    the router's ``/generate``. Run once per placement policy —
    ``round_robin`` (the baseline: affinity and load ignored) and
    ``affinity`` (prefix-affinity + load + health) — with
    policy-unique content so no run rides another's warm KV pages.

    Headline per policy: **prefix_hit_rate** (cross-replica: summed
    engine prefix-cache hit/lookup deltas across ALL replicas — the
    number affinity routing exists to move) and **slo_attainment**
    (turns whose first byte beat ``slo_ttft_ms``). Affinity keeps a
    session's turns on the replica holding its prefix pages; round-robin
    re-prefills the whole history on a cold sibling every hop — that
    delta is the fleet-level warm-TTFT story.

    ``transfer_arm`` grows a third arm (``affinity_transfer``): affinity
    placement with the router's cross-replica KV-page transfer enabled
    (``X-KV-Transfer-From`` donor hints; docs/kv-tiering.md) — a
    placement miss then FETCHES the prefix pages from the sibling
    instead of re-prefilling, so the arm's aggregate prefix-hit rate
    should beat affinity-only. Requires the replicas built with the
    host KV tier on (``build_fleet_engines(host_pool_tokens=...)``).
    """
    import statistics

    import numpy as _np
    import requests

    from generativeaiexamples_tpu.chains.examples.developer_rag import (
        QAChatbot)
    from generativeaiexamples_tpu.chains.llm import EngineLLM
    from generativeaiexamples_tpu.chains.server import create_app
    from generativeaiexamples_tpu.embed.encoder import HashEmbedder
    from generativeaiexamples_tpu.obs import metrics as obs_metrics
    from generativeaiexamples_tpu.router.server import create_router_app
    from generativeaiexamples_tpu.utils.app_config import AppConfig
    from generativeaiexamples_tpu.utils.configuration import from_dict

    cfg = from_dict(AppConfig, {
        "llm": {"model_engine": "tpu-jax"},
        "embeddings": {"model_engine": "hash", "dimensions": 32},
    })
    for eng in engines:
        eng.start()
    apps = [create_app(QAChatbot(llm=EngineLLM(eng),
                                 embedder=HashEmbedder(dim=32),
                                 config=cfg, fused_rag=False), config=cfg)
            for eng in engines]

    def words(tag: str, n_chars: int) -> str:
        # Deterministic filler, unique per tag: the prompt content is
        # what the affinity sketch and the engine prefix cache both key
        # on, so cross-session/cross-policy uniqueness is load-bearing.
        # blake2b, not hash() — PYTHONHASHSEED would break determinism.
        import hashlib
        h = int.from_bytes(hashlib.blake2b(
            tag.encode(), digest_size=4).digest(), "little")
        rng = _np.random.RandomState(h)
        toks = []
        total = 0
        while total < n_chars:
            w = "".join(chr(97 + c) for c in rng.randint(0, 26, size=5))
            toks.append(w)
            total += 6
        return " ".join(toks)[:n_chars]

    def one_policy(policy: str, replica_urls: list[str],
                   kv_transfer: bool = False,
                   label: Optional[str] = None) -> dict:
        label = label or policy
        router_app = create_router_app(
            [(f"r{i}", u) for i, u in enumerate(replica_urls)],
            policy=policy, heartbeat_s=heartbeat_s,
            kv_transfer=kv_transfer, run_heartbeat=True)
        (router_url,), stop_router = serve_apps([router_app])
        snap0 = obs_metrics.REGISTRY.snapshot()
        before = [dict(e.stats) for e in engines]
        results: list[dict] = []
        res_lock = threading.Lock()

        def run_session(i: int, start_delay: float):
            time.sleep(max(0.0, start_delay))
            tag = f"{label}-{seed}-{i}"
            system = f"[session {tag}] " + words(tag, system_chars)
            history = ""
            for t in range(turns):
                question = words(f"{tag}-turn{t}", user_chars)
                t0 = time.monotonic()
                row = {"session": i, "turn": t, "ok": False,
                       "ttft_ms": None}
                try:
                    with requests.post(
                            f"{router_url}/generate",
                            json={"question": question,
                                  "context": system + history,
                                  "use_knowledge_base": False,
                                  "num_tokens": num_tokens},
                            stream=True, timeout=300) as resp:
                        if resp.status_code == 200:
                            it = resp.iter_content(chunk_size=1)
                            body = b""
                            for b in it:
                                body = b
                                row["ttft_ms"] = \
                                    (time.monotonic() - t0) * 1e3
                                break
                            for b in it:
                                body += b
                            answer = body.decode("utf-8", errors="replace")
                            row["ok"] = "[error]" not in answer
                            row["replica"] = resp.headers.get(
                                "X-Routed-Replica", "")
                            history += (f"\nUser: {question}"
                                        f"\nAssistant: {answer}")
                        else:
                            row["status"] = resp.status_code
                except requests.RequestException as exc:
                    row["error"] = str(exc)
                with res_lock:
                    results.append(row)

        rng = _np.random.RandomState(seed)
        delays = _np.cumsum(rng.exponential(1.0 / session_rps,
                                            size=sessions))
        threads = [threading.Thread(target=run_session, args=(i, delays[i]),
                                    daemon=True)
                   for i in range(sessions)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        # Fleet-observability block (docs/observability.md): the per-
        # replica SLO attainment + capacity headroom the router's
        # /debug/fleet spine computed over THIS arm's traffic —
        # schema-validated before it lands in the artifact, so a
        # contract drift fails the bench, not the dashboard.
        fleet_obs = None
        try:
            from generativeaiexamples_tpu.router import fleet as _rfleet
            snap = requests.get(f"{router_url}/debug/fleet",
                                timeout=30).json()
            errs = _rfleet.validate_fleet_snapshot(snap)
            if errs:
                raise ValueError("; ".join(errs))
            fleet_obs = {
                "slo_attainment": snap["fleet"]["slo_attainment"],
                "window_requests": snap["fleet"]["window_requests"],
                "ttft_p50_ms": snap["fleet"]["ttft_p50_ms"],
                "error_rate": snap["fleet"]["error_rate"],
                "headroom_tokens_per_sec":
                    snap["fleet"]["headroom_tokens_per_sec"],
                "capacity_tokens_per_sec":
                    snap["fleet"]["capacity_tokens_per_sec"],
                "replicas": [
                    {"name": row["name"],
                     "slo_attainment": row["slo"]["attainment"],
                     "window_requests": row["slo"]["requests"],
                     "headroom_tokens_per_sec":
                         row["headroom_tokens_per_sec"]}
                    for row in snap["replicas"]],
            }
        except Exception as exc:  # noqa: BLE001 — observability block
            sys.stderr.write(f"bench: fleet_obs capture failed: {exc}\n")
        stop_router()

        snap1 = obs_metrics.REGISTRY.snapshot()
        after = [dict(e.stats) for e in engines]

        def _delta(key: str) -> float:
            return snap1.get(key, 0.0) - snap0.get(key, 0.0)

        hit = sum(a.get("prefix_cache_hit_tokens", 0)
                  - b.get("prefix_cache_hit_tokens", 0)
                  for a, b in zip(after, before))
        lookup = sum(a.get("prefix_cache_lookup_tokens", 0)
                     - b.get("prefix_cache_lookup_tokens", 0)
                     for a, b in zip(after, before))
        ok_rows = [r for r in results if r["ok"]]
        ttfts = sorted(r["ttft_ms"] for r in ok_rows
                       if r["ttft_ms"] is not None)
        warm = sorted(r["ttft_ms"] for r in ok_rows
                      if r["turn"] > 0 and r["ttft_ms"] is not None)
        cold = sorted(r["ttft_ms"] for r in ok_rows
                      if r["turn"] == 0 and r["ttft_ms"] is not None)
        met = [r for r in ok_rows
               if r["ttft_ms"] is not None and r["ttft_ms"] <= slo_ttft_ms]
        placed = {f"r{i}": int(_delta(
            f'router_placed_total{{replica="r{i}"}}'))
            for i in range(len(replica_urls))}
        transfer_pages = sum(
            a.get("kv_tier_transfer_pages", 0)
            - b.get("kv_tier_transfer_pages", 0)
            for a, b in zip(after, before))
        return {
            "policy": label,
            "offered_turns": sessions * turns,
            "completed": len(ok_rows),
            "errors": len(results) - len(ok_rows),
            "slo_attainment": round(len(met) / max(1, sessions * turns), 4),
            "ttft_p50_ms": (round(statistics.median(ttfts), 2)
                            if ttfts else None),
            "ttft_p99_ms": (round(
                ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))], 2)
                if ttfts else None),
            "cold_ttft_p50_ms": (round(statistics.median(cold), 2)
                                 if cold else None),
            "warm_ttft_p50_ms": (round(statistics.median(warm), 2)
                                 if warm else None),
            "prefix_hit_tokens": int(hit),
            "prefix_hit_rate": round(hit / lookup, 4) if lookup else 0.0,
            "placed": placed,
            "affinity_hit_placements": int(_delta("router_affinity_hits")),
            "retries_connect": int(_delta(
                'router_retries_total{reason="connect"}')),
            "kv_transfer": bool(kv_transfer),
            "kv_transfer_pages": int(transfer_pages),
        }, fleet_obs

    arms = [(policy, False, policy) for policy in policies]
    if transfer_arm:
        arms.append(("affinity", True, "affinity_transfer"))
    replica_urls, stop_replicas = serve_apps(apps)
    fleet_obs = None
    try:
        policy_rows = []
        for policy, kv_transfer, label in arms:
            for eng in engines:
                try:
                    # Fresh caches per policy: a later policy must not
                    # ride (or fight eviction with) an earlier one's
                    # pages. Content is policy-unique anyway; this keeps
                    # pool pressure comparable too.
                    eng.reset()
                except Exception:  # noqa: BLE001 — comparability only
                    pass
            row, obs = one_policy(policy, replica_urls,
                                  kv_transfer=kv_transfer, label=label)
            policy_rows.append(row)
            # Keep the LAST arm's snapshot (each arm runs its own
            # router; later arms see the same fleet under the most
            # production-like policy).
            fleet_obs = obs if obs is not None else fleet_obs
    finally:
        stop_replicas()
    return {
        "replicas": len(engines),
        "sessions": int(sessions),
        "turns_per_session": int(turns),
        "session_rps": float(session_rps),
        "slo_ttft_ms": float(slo_ttft_ms),
        "num_tokens": int(num_tokens),
        "policies": policy_rows,
        "fleet_obs": fleet_obs,
    }


def run_disagg_bench(params, model_cfg, tokenizer, *,
                     replicas=2, requests=24, rps=4.0,
                     long_frac=0.4, long_chars=4600, short_chars=400,
                     num_tokens=16, seed=0, heartbeat_s=0.5,
                     max_input_length=4096):
    """Disaggregated prefill/decode vs unified at EQUAL chips
    (docs/disaggregation.md): two arms over an adversarial long/short
    prompt mix.

    - ``unified``: ``replicas`` unified replicas — long prompts chunk-
      prefill on whichever replica serves them, stealing round budget
      from every short request decoding there (head-of-line TTFT).
    - ``disagg``: the SAME chip count split 1 prefill +
      ``replicas - 1`` decode — long prompts run their prefill on the
      prefill replica and arrive at the decode replica as a pushed
      near-full prefix hit, so decode rounds never absorb long-prefill
      work.

    Long prompts are sized past the router's
    ``ROUTER_DISAGG_MIN_PROMPT_BYTES`` gate; short ones under it. Per
    arm: TTFT p50/p99 (and long/short split), decode goodput
    (fleet-summed ``tokens_generated`` over the traffic wall-clock),
    and the handoff accounting (router handoffs/fallbacks, engine
    export/shed counters). The headline claim — disagg beats unified on
    BOTH ttft_p50_ms and decode_goodput — is gated round-over-round by
    ``tools/perf_diff.py`` (``disagg.*@<arm>``)."""
    import statistics

    import numpy as _np
    import requests as _rq

    from generativeaiexamples_tpu.chains.examples.developer_rag import (
        QAChatbot)
    from generativeaiexamples_tpu.chains.llm import EngineLLM
    from generativeaiexamples_tpu.chains.server import create_app
    from generativeaiexamples_tpu.embed.encoder import HashEmbedder
    from generativeaiexamples_tpu.obs import metrics as obs_metrics
    from generativeaiexamples_tpu.router.server import create_router_app
    from generativeaiexamples_tpu.utils.app_config import AppConfig
    from generativeaiexamples_tpu.utils.configuration import from_dict

    cfg = from_dict(AppConfig, {
        "llm": {"model_engine": "tpu-jax"},
        "embeddings": {"model_engine": "hash", "dimensions": 32},
    })
    pool = int(os.environ.get("BENCH_FLEET_KV_POOL_TOKENS", "4096"))

    def words(tag: str, n_chars: int) -> str:
        import hashlib
        h = int.from_bytes(hashlib.blake2b(
            tag.encode(), digest_size=4).digest(), "little")
        rng = _np.random.RandomState(h)
        toks = []
        total = 0
        while total < n_chars:
            w = "".join(chr(97 + c) for c in rng.randint(0, 26, size=5))
            toks.append(w)
            total += 6
        return " ".join(toks)[:n_chars]

    # The adversarial mix, shaped once and shared by both arms (content
    # is arm-tagged below so no arm rides the other's warm pages).
    rng = _np.random.RandomState(seed)
    kinds = ["long" if rng.random_sample() < long_frac else "short"
             for _ in range(requests)]
    delays = _np.cumsum(rng.exponential(1.0 / rps, size=requests))

    def one_arm(label: str, roles: list[str]) -> dict:
        engines = build_fleet_engines(
            params, model_cfg, tokenizer, replicas,
            host_pool_tokens=pool * 4, roles=roles,
            max_input_length=max_input_length)
        for eng in engines:
            eng.start()
        try:
            apps = [create_app(QAChatbot(llm=EngineLLM(eng),
                                         embedder=HashEmbedder(dim=32),
                                         config=cfg, fused_rag=False),
                               config=cfg)
                    for eng in engines]
            replica_urls, stop_replicas = serve_apps(apps)
            router_app = create_router_app(
                [(f"r{i}", u) for i, u in enumerate(replica_urls)],
                policy="affinity", heartbeat_s=heartbeat_s,
                kv_transfer=True, run_heartbeat=True)
            (router_url,), stop_router = serve_apps([router_app])
            # Sync the role/capacity view before traffic: placement must
            # already know who is prefill when the first long prompt
            # lands.
            _rq.post(f"{router_url}/control/heartbeat", timeout=30)
            snap0 = obs_metrics.REGISTRY.snapshot()
            before = [dict(e.stats) for e in engines]
            results: list[dict] = []
            res_lock = threading.Lock()

            def run_request(i: int, start_delay: float):
                time.sleep(max(0.0, start_delay))
                kind = kinds[i]
                tag = f"disagg-{label}-{seed}-{i}"
                n_chars = long_chars if kind == "long" else short_chars
                t0 = time.monotonic()
                row = {"i": i, "kind": kind, "ok": False, "ttft_ms": None}
                try:
                    with _rq.post(
                            f"{router_url}/generate",
                            json={"question": words(f"{tag}-q", 80),
                                  "context": words(tag, n_chars),
                                  "use_knowledge_base": False,
                                  "num_tokens": num_tokens},
                            stream=True, timeout=300) as resp:
                        if resp.status_code == 200:
                            it = resp.iter_content(chunk_size=1)
                            body = b""
                            for b in it:
                                body = b
                                row["ttft_ms"] = \
                                    (time.monotonic() - t0) * 1e3
                                break
                            for b in it:
                                body += b
                            answer = body.decode("utf-8",
                                                 errors="replace")
                            row["ok"] = "[error]" not in answer
                        else:
                            row["status"] = resp.status_code
                except _rq.RequestException as exc:
                    row["error"] = str(exc)
                with res_lock:
                    results.append(row)

            t_traffic = time.monotonic()
            threads = [threading.Thread(target=run_request,
                                        args=(i, delays[i]), daemon=True)
                       for i in range(requests)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=600)
            elapsed = max(1e-3, time.monotonic() - t_traffic)
            stop_router()
            stop_replicas()
            snap1 = obs_metrics.REGISTRY.snapshot()
            after = [dict(e.stats) for e in engines]
        finally:
            for eng in engines:
                try:
                    eng.stop()
                except Exception:  # noqa: BLE001
                    pass

        def _delta(key: str) -> float:
            return snap1.get(key, 0.0) - snap0.get(key, 0.0)

        def _stat(key: str) -> int:
            return int(sum(a.get(key, 0) - b.get(key, 0)
                           for a, b in zip(after, before)))

        ok_rows = [r for r in results if r["ok"]]
        ttfts = sorted(r["ttft_ms"] for r in ok_rows
                       if r["ttft_ms"] is not None)

        def _p50(kind: Optional[str] = None):
            xs = sorted(r["ttft_ms"] for r in ok_rows
                        if r["ttft_ms"] is not None
                        and (kind is None or r["kind"] == kind))
            return round(statistics.median(xs), 2) if xs else None

        role_counts: dict[str, int] = {}
        for role in (roles or ["unified"] * replicas):
            role_counts[role] = role_counts.get(role, 0) + 1
        fallbacks = int(sum(
            _delta(f'router_disagg_fallbacks_total{{reason="{r}"}}')
            for r in ("prefill_error", "prefill_timeout", "no_pages")))
        return {
            "arm": label,
            "roles": role_counts,
            "offered": int(requests),
            "completed": len(ok_rows),
            "errors": len(results) - len(ok_rows),
            "ttft_p50_ms": _p50(),
            "ttft_p99_ms": (round(
                ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))], 2)
                if ttfts else None),
            "long_ttft_p50_ms": _p50("long"),
            "short_ttft_p50_ms": _p50("short"),
            "tokens_generated": _stat("tokens_generated"),
            "decode_goodput": round(
                _stat("tokens_generated") / elapsed, 1),
            "handoffs": int(_delta("router_disagg_handoffs_total")),
            "fallbacks": fallbacks,
            "kv_export_pages": _stat("kv_tier_export_pages"),
            "kv_export_shed": _stat("kv_export_shed"),
            "kv_transfer_pages": _stat("kv_tier_transfer_pages"),
        }

    arms = [
        one_arm("unified", ["unified"] * replicas),
        one_arm("disagg", ["prefill"] + ["decode"] * (replicas - 1)),
    ]
    return {
        "replicas": int(replicas),
        "requests": int(requests),
        "rps": float(rps),
        "long_frac": float(long_frac),
        "long_chars": int(long_chars),
        "short_chars": int(short_chars),
        "num_tokens": int(num_tokens),
        "arms": arms,
    }


def run_failover_bench(params, model_cfg, tokenizer, *,
                       replicas=3, requests=16, rps=3.0,
                       num_tokens=32, seed=0, heartbeat_s=0.3,
                       max_input_length=2048):
    """Mid-stream replica loss under open-loop load, transcript-replay
    resume on vs off (docs/robustness.md): two arms over the SAME
    traffic shape and the SAME scripted kill.

    Each arm serves ``replicas`` unified replicas behind the router,
    every replica on its own killable server. Mid-run a designated
    victim request starts streaming, its routed replica is read off
    ``X-Routed-Replica``, and that server is torn down with the victim
    (plus any open-loop streams it was serving) mid-stream.

    - ``resume_on``: router resume budget 1 — the router re-places the
      severed streams on a sibling and replays the transcript; the
      headline ``completed_no_error_rate`` should hold at 1.0.
    - ``resume_off``: budget 0 — every severed stream gets the classic
      in-band error frame; the same rate quantifies the client-visible
      blast radius resume removes.

    Per arm: completed/error accounting, resume outcome counters
    (``router_resume_total`` deltas), and the latency the resumed
    streams paid over their unresumed peers (p50 duration delta from
    the router's flight recorder). Gated round-over-round by
    ``tools/perf_diff.py`` (``failover.*@<arm>``)."""
    import statistics

    import numpy as _np
    import requests as _rq
    from aiohttp import web

    from generativeaiexamples_tpu.chains.examples.developer_rag import (
        QAChatbot)
    from generativeaiexamples_tpu.chains.llm import EngineLLM
    from generativeaiexamples_tpu.chains.server import create_app
    from generativeaiexamples_tpu.embed.encoder import HashEmbedder
    from generativeaiexamples_tpu.obs import metrics as obs_metrics
    from generativeaiexamples_tpu.router.server import create_router_app
    from generativeaiexamples_tpu.utils import faults
    from generativeaiexamples_tpu.utils.app_config import AppConfig
    from generativeaiexamples_tpu.utils.configuration import from_dict

    cfg = from_dict(AppConfig, {
        "llm": {"model_engine": "tpu-jax"},
        "embeddings": {"model_engine": "hash", "dimensions": 32},
    })
    pool = int(os.environ.get("BENCH_FLEET_KV_POOL_TOKENS", "4096"))

    def words(tag: str, n_chars: int) -> str:
        import hashlib
        h = int.from_bytes(hashlib.blake2b(
            tag.encode(), digest_size=4).digest(), "little")
        rng = _np.random.RandomState(h)
        toks = []
        total = 0
        while total < n_chars:
            w = "".join(chr(97 + c) for c in rng.randint(0, 26, size=5))
            toks.append(w)
            total += 6
        return " ".join(toks)[:n_chars]

    def serve_one(app):
        """One replica on its OWN loop + thread so it can be torn down
        mid-arm without taking the rest of the fleet with it (the shared
        ``serve_apps`` helper only offers a global stop)."""
        loop = asyncio.new_event_loop()
        box: dict = {}
        started = threading.Event()

        def run():
            asyncio.set_event_loop(loop)

            async def boot():
                runner = web.AppRunner(app)
                await runner.setup()
                # shutdown_timeout on the SITE: cleanup() grants
                # in-flight handlers 0.2 s, then force-closes their
                # connections — the wire shape of a pod dying.
                site = web.TCPSite(runner, "127.0.0.1", 0,
                                   shutdown_timeout=0.2)
                await site.start()
                box["port"] = runner.addresses[0][1]
                box["runner"] = runner
            loop.run_until_complete(boot())
            started.set()
            loop.run_forever()

        threading.Thread(target=run, daemon=True).start()
        if not started.wait(60):
            raise RuntimeError("failover replica server failed to boot")
        done = threading.Event()

        def kill():
            if done.is_set():
                return
            done.set()
            fut = asyncio.run_coroutine_threadsafe(
                box["runner"].cleanup(), loop)
            try:
                fut.result(timeout=30)
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
            finally:
                loop.call_soon_threadsafe(loop.stop)

        return f"http://127.0.0.1:{box['port']}", kill

    rng = _np.random.RandomState(seed)
    delays = _np.cumsum(rng.exponential(1.0 / rps, size=requests))

    _RESUME_FAIL = ("no_replica", "rejected", "connect_fail",
                    "overflow", "budget_exhausted")

    # Small decode rounds (4 tokens each, vs the throughput-oriented
    # 16): the scripted kill lands DURING decode only if decode spans
    # several rounds — a 16-step round drains a whole short completion
    # in ~2 dispatches, finishing the upstream stream before the killed
    # server's shutdown grace (0.2 s + 0.2 s cancel) expires, and the
    # teardown then has nothing to sever. The fleet is shared by both
    # arms: the scripted kill tears down a replica's HTTP SERVER, not
    # its engine, so the second arm re-serves the same engines behind
    # fresh servers (and skips a second round of pool allocation +
    # compile warm-up).
    fleet = build_fleet_engines(
        params, model_cfg, tokenizer, replicas,
        host_pool_tokens=pool * 4,
        max_input_length=max_input_length,
        steps_per_round=4)
    for eng in fleet:
        eng.start()

    def one_arm(label: str, resume_attempts: int) -> dict:
        engines = fleet
        kills: list = []
        try:
            apps = [create_app(QAChatbot(llm=EngineLLM(eng),
                                         embedder=HashEmbedder(dim=32),
                                         config=cfg, fused_rag=False),
                               config=cfg)
                    for eng in engines]
            served = [serve_one(app) for app in apps]
            replica_urls = [u for u, _ in served]
            kills = [k for _, k in served]
            router_app = create_router_app(
                [(f"r{i}", u) for i, u in enumerate(replica_urls)],
                policy="affinity", heartbeat_s=heartbeat_s,
                resume_attempts=resume_attempts, run_heartbeat=True)
            (router_url,), stop_router = serve_apps([router_app])
            _rq.post(f"{router_url}/control/heartbeat", timeout=30)
            # Warm every replica (compile prefill/decode) so the
            # scripted kill lands on a stream that is actually
            # emitting tokens, not one stuck behind compilation.
            for i, u in enumerate(replica_urls):
                _rq.post(f"{u}/generate",
                         json={"question": words(f"fw-{label}-{i}", 40),
                               "context": words(f"fwc-{label}-{i}", 200),
                               "use_knowledge_base": False,
                               "num_tokens": 4}, timeout=300)
            snap0 = obs_metrics.REGISTRY.snapshot()
            before = [dict(e.stats) for e in engines]
            results: list[dict] = []
            res_lock = threading.Lock()
            first_byte = [threading.Event() for _ in range(requests)]

            def run_request(i: int, start_delay: float):
                time.sleep(max(0.0, start_delay))
                tag = f"failover-{label}-{seed}-{i}"
                t0 = time.monotonic()
                row = {"i": i, "ok": False, "error_frame": False,
                       "ttft_ms": None}
                try:
                    with _rq.post(
                            f"{router_url}/generate",
                            json={"question": words(f"{tag}-q", 40),
                                  "context": words(tag, 200),
                                  "use_knowledge_base": False,
                                  "num_tokens": num_tokens},
                            stream=True, timeout=300) as resp:
                        if resp.status_code == 200:
                            it = resp.iter_content(chunk_size=1)
                            body = b""
                            for b in it:
                                body = b
                                row["ttft_ms"] = \
                                    (time.monotonic() - t0) * 1e3
                                first_byte[i].set()
                                break
                            for b in it:
                                body += b
                            answer = body.decode("utf-8",
                                                 errors="replace")
                            row["error_frame"] = "[error]" in answer
                            row["ok"] = not row["error_frame"]
                        else:
                            row["status"] = resp.status_code
                except _rq.RequestException as exc:
                    row["error"] = str(exc)
                finally:
                    first_byte[i].set()
                with res_lock:
                    results.append(row)

            t_traffic = time.monotonic()
            threads = [threading.Thread(target=run_request,
                                        args=(i, delays[i]), daemon=True)
                       for i in range(requests)]
            for th in threads:
                th.start()
            # The scripted kill severs only streams PAST their first
            # byte (a loss in the pre-first-byte phase is a 502, not a
            # resumable mid-stream loss, and would muddy the arm
            # comparison), so wait for every open-loop stream's first
            # byte before starting the victim.
            for ev in first_byte:
                ev.wait(timeout=300)

            # The victim stream, from the main thread: its routed
            # replica is severed right after its first byte, while it
            # (and any open-loop neighbour still streaming there) is
            # mid-stream. A dispatch-delay fault stretches each decode
            # round past the killed server's shutdown grace for just
            # this window (0.15 s/round x ~12 rounds of runway vs 0.4 s
            # of grace), and is lifted right after the kill so the
            # resume leg re-prefills at full speed.
            killed_replica = None
            vrow = {"i": -1, "ok": False, "error_frame": False,
                    "ttft_ms": None, "victim": True}
            vt0 = time.monotonic()
            faults.set_plan("engine.dispatch=delay:0.15")
            try:
                with _rq.post(
                        f"{router_url}/generate",
                        json={"question": words(f"fv-{label}-q", 40),
                              "context": words(f"fv-{label}", 200),
                              "use_knowledge_base": False,
                              "num_tokens": num_tokens},
                        headers={"X-Request-ID": f"fv-{label}"},
                        stream=True, timeout=300) as resp:
                    if resp.status_code == 200:
                        it = resp.iter_content(chunk_size=1)
                        body = b""
                        for b in it:
                            body = b
                            vrow["ttft_ms"] = \
                                (time.monotonic() - vt0) * 1e3
                            break
                        killed_replica = resp.headers.get(
                            "X-Routed-Replica")
                        if killed_replica is not None:
                            kills[int(killed_replica[1:])]()
                        faults.clear()
                        for b in it:
                            body += b
                        answer = body.decode("utf-8", errors="replace")
                        vrow["error_frame"] = "[error]" in answer
                        vrow["ok"] = not vrow["error_frame"]
                    else:
                        vrow["status"] = resp.status_code
            except _rq.RequestException as exc:
                vrow["error"] = str(exc)
            finally:
                faults.clear()
            with res_lock:
                results.append(vrow)

            for th in threads:
                th.join(timeout=600)
            # Resumed-vs-unresumed durations from the router's flight
            # recorder (completed ring), read before teardown.
            resumed_ms: list[float] = []
            plain_ms: list[float] = []
            try:
                debug = _rq.get(f"{router_url}/debug/requests",
                                timeout=30).json()
                for tl_row in debug.get("completed", []):
                    meta = tl_row.get("meta", {})
                    dur = meta.get("duration_ms")
                    if meta.get("outcome") != "ok" or dur is None:
                        continue
                    if meta.get("resumed"):
                        resumed_ms.append(float(dur))
                    else:
                        plain_ms.append(float(dur))
            except (_rq.RequestException, ValueError):
                pass
            stop_router()
            snap1 = obs_metrics.REGISTRY.snapshot()
            after = [dict(e.stats) for e in engines]
        finally:
            for kill in kills:
                try:
                    kill()
                except Exception:  # noqa: BLE001
                    pass

        def _delta(key: str) -> float:
            return snap1.get(key, 0.0) - snap0.get(key, 0.0)

        def _stat(key: str) -> int:
            return int(sum(a.get(key, 0) - b.get(key, 0)
                           for a, b in zip(after, before)))

        ok_rows = [r for r in results if r["ok"]]
        ttfts = sorted(r["ttft_ms"] for r in ok_rows
                       if r["ttft_ms"] is not None)
        offered = len(results)
        resumed_p50 = (round(statistics.median(resumed_ms), 2)
                       if resumed_ms else None)
        plain_p50 = (round(statistics.median(plain_ms), 2)
                     if plain_ms else None)
        return {
            "arm": label,
            "resume_attempts": int(resume_attempts),
            "offered": offered,
            "completed": len(ok_rows),
            "errors": offered - len(ok_rows),
            "error_frames": sum(1 for r in results if r["error_frame"]),
            "completed_no_error_rate": round(
                len(ok_rows) / max(1, offered), 4),
            "killed_replica": killed_replica,
            "resumes_ok": int(_delta(
                'router_resume_total{outcome="ok"}')),
            "resumes_failed": int(sum(_delta(
                f'router_resume_total{{outcome="{o}"}}')
                for o in _RESUME_FAIL)),
            "resume_replay_tokens": int(_delta(
                "router_resume_replay_tokens")),
            "resumed_p50_ms": resumed_p50,
            "unresumed_p50_ms": plain_p50,
            "resumed_added_p50_ms": (
                round(max(0.0, resumed_p50 - plain_p50), 2)
                if resumed_p50 is not None and plain_p50 is not None
                else None),
            "ttft_p50_ms": (round(statistics.median(ttfts), 2)
                            if ttfts else None),
            "tokens_generated": _stat("tokens_generated"),
        }

    try:
        arms = [
            one_arm("resume_on", 1),
            one_arm("resume_off", 0),
        ]
    finally:
        for eng in fleet:
            try:
                eng.stop()
            except Exception:  # noqa: BLE001
                pass
    return {
        "replicas": int(replicas),
        "requests": int(requests),
        "rps": float(rps),
        "num_tokens": int(num_tokens),
        "arms": arms,
    }


def parse_trace(spec: str) -> list[tuple[float, float]]:
    """``frac:rps,frac:rps,...`` — the diurnal arrival trace shape
    (fractions of the run's duration; they need not sum to 1, they are
    normalized). Example: ``0.3:1,0.3:6,0.4:1`` is a quiet-burst-quiet
    day compressed into one run."""
    phases = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        frac, _, rps = entry.partition(":")
        phases.append((float(frac), float(rps)))
    if not phases:
        raise ValueError(f"empty trace spec {spec!r}")
    total = sum(f for f, _ in phases)
    return [(f / total, r) for f, r in phases]


def run_autoscale_bench(engines, *, duration_s=12.0,
                        trace=((0.3, 1.0), (0.3, 6.0), (0.4, 1.0)),
                        slo_ttft_ms=2000.0, deadline_ms=None,
                        num_tokens=8, min_replicas=1, interval_s=0.3,
                        heartbeat_s=0.25, seed=0, prompt_chars=400):
    """Autoscale scenario (``BENCH_AUTOSCALE=1``): a diurnal/bursty
    open-loop arrival trace through the fleet router, run twice —
    **autoscaled** (start at ``min_replicas``; the SLO-driven controller
    activates parked replicas on leading indicators and drains them
    back when the burst passes, docs/autoscaling.md) vs **static** (a
    fixed fleet sized to the autoscaled arm's AVERAGE replica count, so
    both arms spend the same replica-minutes and the delta is purely
    WHEN the capacity existed).

    Headline per arm: **slo_attainment** (offered requests that
    completed ok with TTFT under ``slo_ttft_ms``) and **replica_minutes**
    (the integral of active replica count over the run — the bill). On
    a bursty trace the autoscaled arm should beat the equal-average
    static baseline: capacity concentrated under the burst attains more
    than capacity spread evenly.

    ``engines`` is the FULL fleet (the autoscale ceiling); arrivals are
    Poisson within each trace phase, every request unique-content (cold
    prefill — TTFT differences measure capacity, not cache luck).
    """
    import statistics

    import numpy as _np
    import requests

    from generativeaiexamples_tpu.chains.examples.developer_rag import (
        QAChatbot)
    from generativeaiexamples_tpu.chains.llm import EngineLLM
    from generativeaiexamples_tpu.chains.server import create_app
    from generativeaiexamples_tpu.embed.encoder import HashEmbedder
    from generativeaiexamples_tpu.router import autoscale as _rauto
    from generativeaiexamples_tpu.router.server import create_router_app
    from generativeaiexamples_tpu.router.table import ReplicaTable
    from generativeaiexamples_tpu.utils.app_config import AppConfig
    from generativeaiexamples_tpu.utils.configuration import from_dict

    trace = [(float(f), float(r)) for f, r in trace]
    cfg = from_dict(AppConfig, {
        "llm": {"model_engine": "tpu-jax"},
        "embeddings": {"model_engine": "hash", "dimensions": 32},
    })
    for eng in engines:
        eng.start()
    apps = [create_app(QAChatbot(llm=EngineLLM(eng),
                                 embedder=HashEmbedder(dim=32),
                                 config=cfg, fused_rag=False), config=cfg)
            for eng in engines]
    replica_urls, stop_replicas = serve_apps(apps)
    names = [f"r{i}" for i in range(len(engines))]
    pairs = list(zip(names, replica_urls))
    max_replicas = len(engines)
    min_replicas = max(1, min(int(min_replicas), max_replicas))

    def arrivals(label: str) -> list[tuple[float, str]]:
        """(t_offset, unique_prompt) per offered request."""
        rng = _np.random.RandomState(seed)
        out = []
        t0 = 0.0
        uid = 0
        for frac, rps in trace:
            span = duration_s * frac
            t = t0
            while True:
                t += float(rng.exponential(1.0 / max(1e-6, rps)))
                if t >= t0 + span:
                    break
                out.append((t, f"[{label}-{seed}-{uid}] "
                               + "q" * max(1, prompt_chars)))
                uid += 1
            t0 += span
        return out

    def one_arm(label: str, initial: int,
                autoscaled: bool) -> dict:
        table = ReplicaTable(policy="affinity")

        def factory(router):
            executor = _rauto.LocalExecutor(
                router, pairs[initial:], drain_wait_s=15.0)
            policy = _rauto.AutoscalePolicy(
                min_replicas=min_replicas, max_replicas=max_replicas,
                interval_s=interval_s, up_cooldown_s=2 * interval_s,
                down_cooldown_s=4 * interval_s, down_stable_ticks=3,
                drain_wait_s=15.0)
            return _rauto.AutoscaleController(
                router, policy=policy, executor=executor,
                surge=router.surge, slo_ttft_ms=slo_ttft_ms)

        router_app = create_router_app(
            pairs[:initial], table=table, heartbeat_s=heartbeat_s,
            run_heartbeat=True,
            autoscale_factory=factory if autoscaled else None,
            run_autoscale=autoscaled)
        (router_url,), stop_router = serve_apps([router_app])
        rows: list[dict] = []
        rows_lock = threading.Lock()

        def fire(prompt: str):
            t0 = time.monotonic()
            row = {"ok": False, "status": None, "ttft_ms": None}
            headers = {}
            if deadline_ms:
                headers["X-Deadline-Ms"] = str(int(deadline_ms))
            try:
                with requests.post(
                        f"{router_url}/generate",
                        json={"question": prompt, "context": "",
                              "use_knowledge_base": False,
                              "num_tokens": num_tokens},
                        headers=headers, stream=True,
                        timeout=120) as resp:
                    row["status"] = resp.status_code
                    if resp.status_code == 200:
                        body = b""
                        it = resp.iter_content(chunk_size=1)
                        for b in it:
                            body = b
                            row["ttft_ms"] = (time.monotonic() - t0) * 1e3
                            break
                        for b in it:
                            body += b
                        text = body.decode("utf-8", errors="replace")
                        row["ok"] = "[error]" not in text
            except requests.RequestException as exc:
                row["error"] = str(exc)
            with rows_lock:
                rows.append(row)

        # Replica-count sampler: the replica_minutes integral. Samples
        # the TABLE (members, draining included — a draining replica
        # still holds its resources until its streams finish).
        samples: list[int] = []
        stop_sampling = threading.Event()

        def sample():
            while not stop_sampling.wait(0.05):
                samples.append(len(table.replicas()))

        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()
        plan = arrivals(label)
        threads = []
        t_start = time.monotonic()
        for t_off, prompt in plan:
            delay = t_start + t_off - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=fire, args=(prompt,),
                                  daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=180)
        elapsed = time.monotonic() - t_start
        stop_sampling.set()
        sampler.join(timeout=5)
        autoscale_snap = None
        if autoscaled:
            try:
                snap = requests.get(f"{router_url}/debug/autoscale",
                                    timeout=30).json()
                errs = _rauto.validate_autoscale_snapshot(snap)
                if errs:
                    raise ValueError("; ".join(errs))
                autoscale_snap = snap
            except Exception as exc:  # noqa: BLE001 — evidence block
                sys.stderr.write(
                    f"bench: autoscale snapshot capture failed: {exc}\n")
        stop_router()
        avg_replicas = (sum(samples) / len(samples)) if samples \
            else float(initial)
        replica_minutes = avg_replicas * elapsed / 60.0
        offered = len(plan)
        ok_rows = [r for r in rows if r["ok"]]
        met = [r for r in ok_rows
               if r["ttft_ms"] is not None
               and r["ttft_ms"] <= slo_ttft_ms]
        ttfts = sorted(r["ttft_ms"] for r in ok_rows
                       if r["ttft_ms"] is not None)
        totals = (autoscale_snap or {}).get("decisions_total", {})
        surge = (autoscale_snap or {}).get("surge", {})
        return {
            "policy": label,
            "replicas_static": None if autoscaled else initial,
            "offered": offered,
            "completed": len(ok_rows),
            "shed": sum(1 for r in rows if r["status"] == 429),
            "errors": sum(1 for r in rows
                          if not r["ok"] and r["status"] != 429),
            "slo_attainment": round(len(met) / max(1, offered), 4),
            "ttft_p50_ms": (round(statistics.median(ttfts), 2)
                            if ttfts else None),
            "replica_minutes": round(replica_minutes, 4),
            "avg_replicas": round(avg_replicas, 3),
            "peak_replicas": max(samples) if samples else initial,
            "scale_ups": int(totals.get("scale_up", 0)),
            "scale_downs": int(totals.get("scale_down", 0)),
            "surge_rejections": int(sum(
                (surge.get("rejected") or {}).values())),
            "decisions": int(sum(totals.values())),
        }

    def reset_engines():
        for eng in engines:
            try:
                eng.reset()
            except Exception:  # noqa: BLE001 — comparability only
                pass
        # The autoscaled arm's scale-downs DRAINED parked replicas —
        # app-level DrainState the engine reset cannot see. The static
        # arm's fleet must start with admission open everywhere, or its
        # "N replicas" silently run as fewer and the headline
        # comparison measures drain debris instead of capacity timing.
        for url in replica_urls:
            try:
                requests.post(f"{url}/control/undrain", timeout=10)
            except requests.RequestException:
                pass

    # Mask the env switch for the arm matrix: the AUTOSCALED arm gets
    # its controller from the explicit factory, and the STATIC arm must
    # not grow one from a stray ROUTER_AUTOSCALE in the environment.
    saved_env = os.environ.pop("ROUTER_AUTOSCALE", None)
    try:
        auto_row = one_arm("autoscaled", min_replicas, autoscaled=True)
        # Equal-average static baseline: the same replica-minutes budget
        # spread evenly — the honest comparison (a static fleet at max
        # would trivially win attainment by spending more).
        static_n = min(max_replicas,
                       max(min_replicas,
                           int(round(auto_row["avg_replicas"]))))
        reset_engines()
        static_row = one_arm("static", static_n, autoscaled=False)
    finally:
        if saved_env is not None:
            os.environ["ROUTER_AUTOSCALE"] = saved_env
        stop_replicas()
    return {
        "duration_s": float(duration_s),
        "trace": [[f, r] for f, r in trace],
        "slo_ttft_ms": float(slo_ttft_ms),
        "deadline_ms": float(deadline_ms) if deadline_ms else None,
        "num_tokens": int(num_tokens),
        "min_replicas": int(min_replicas),
        "max_replicas": int(max_replicas),
        "interval_s": float(interval_s),
        "policies": [auto_row, static_row],
    }


def run_kv_pressure_bench(params, model_cfg, tokenizer, *,
                          ratios=(1, 2, 4), pool_tokens=None,
                          host_pool_tokens=None, turns=3,
                          user_len=32, reply_len=8, seed=0,
                          **engine_overrides):
    """KV-pressure scenario (``BENCH_KV_PRESSURE=1,2,4``): multi-turn
    chat with a warm working set N× the device KV pool, tiering OFF vs
    ON — the capacity-miss traffic shape the host tier exists for.

    Per ratio N, ``sessions ≈ N × pool / session_prefix`` conversations
    interleave their turns (turn-major order), so by the time a
    session's next turn arrives its prefix pages have been evicted by
    the other sessions. With tiering off every such turn re-prefills
    the whole history; with tiering on the eviction offloaded the pages
    to host RAM and admission restores them (priced H2D). Headline per
    arm: **warm_p50_ttft_ms** and **kv_restore_hit_rate** (restoring
    admissions / prefix lookups) — on hardware the ON arm's warm TTFT
    must beat OFF at N≥2 (tools/perf_diff.py does not gate this section
    yet; the acceptance run reads it directly).

    Fresh engine per arm over SHARED params; ``engine_overrides`` let
    the tier-1 CPU smoke shrink the geometry. The ``KV_HOST_POOL_TOKENS``
    env var is masked for the duration — the arm matrix IS the knob
    here."""
    import statistics

    from generativeaiexamples_tpu.engine import (Engine, EngineConfig,
                                                 SamplingParams)

    if pool_tokens is None:
        pool_tokens = int(os.environ.get("BENCH_KV_PRESSURE_POOL", "")
                          or 2048)
    pool_tokens = int(pool_tokens)
    page = int(engine_overrides.get("page_size", 128))
    # None = derive; an explicit value (including a caller's 0) is kept
    host_tokens = int((max(ratios) + 1) * pool_tokens
                      if host_pool_tokens is None else host_pool_tokens)
    system_len = max(2 * page, pool_tokens // 4)
    vocab = getattr(model_cfg, "vocab_size", 32000)
    span = min(vocab - 4, 250)

    def ids(tag: int, n: int) -> list:
        return [(tag * 131 + 7 * i) % span + 4 for i in range(n)]

    saved_env = os.environ.pop("KV_HOST_POOL_TOKENS", None)
    sp = SamplingParams(max_tokens=reply_len, top_k=1, ignore_eos=True)
    arms = []
    try:
        for ratio in ratios:
            sessions = max(2, round(ratio * pool_tokens / system_len))
            for tiering in (False, True):
                kw = dict(
                    max_slots=2,
                    max_input_length=system_len + turns
                    * (user_len + reply_len) + 2 * page,
                    max_output_length=max(16, 2 * reply_len),
                    prefill_buckets=(512, 1024), dtype="bfloat16",
                    kv_pool_tokens=pool_tokens,
                    steps_per_round=int(os.environ.get(
                        "BENCH_STEPS_PER_ROUND", "16")),
                    kv_host_pool_tokens=host_tokens if tiering else 0)
                kw.update(engine_overrides)
                engine = Engine(params, model_cfg, tokenizer,
                                EngineConfig(**kw))
                try:
                    engine.start()
                    before = engine.stats
                    histories = {
                        s: ids(seed * 7919 + ratio * 100 + s
                               + (10_000 if tiering else 0), system_len)
                        for s in range(sessions)}
                    cold, warm = [], []
                    for t in range(turns):
                        for s in range(sessions):
                            prompt = histories[s] + ids(
                                (ratio * 131 + s) * 1009 + t + 1,
                                user_len)
                            stream = engine.submit(prompt, sp)
                            stream.text()
                            (cold if t == 0 else warm).append(
                                stream.ttft_ms)
                            histories[s] = prompt + stream.token_ids
                    after = engine.stats

                    def delta(key):
                        return after.get(key, 0) - before.get(key, 0)

                    lookups = delta("prefix_cache_lookups")
                    hit = delta("prefix_cache_hit_tokens")
                    lookup_toks = delta("prefix_cache_lookup_tokens")
                    arms.append({
                        "ratio": int(ratio),
                        "tiering": bool(tiering),
                        "sessions": int(sessions),
                        "cold_p50_ttft_ms": round(
                            statistics.median(cold), 2) if cold else None,
                        "warm_p50_ttft_ms": round(
                            statistics.median(warm), 2) if warm else None,
                        "kv_restore_hit_rate": round(
                            delta("kv_tier_restore_hits")
                            / max(1, lookups), 4),
                        "kv_tier_offload_pages": int(
                            delta("kv_tier_offload_pages")),
                        "kv_tier_restore_pages": int(
                            delta("kv_tier_restore_pages")),
                        "kv_restore_skipped_cost": int(
                            delta("kv_restore_skipped_cost")),
                        "prefix_hit_rate": round(
                            hit / lookup_toks, 4) if lookup_toks else 0.0,
                    })
                finally:
                    engine.stop()
                import gc
                gc.collect()
    finally:
        if saved_env is not None:
            os.environ["KV_HOST_POOL_TOKENS"] = saved_env
    return {
        "pool_tokens": int(pool_tokens),
        "host_pool_tokens": int(host_tokens),
        "ratios": [int(r) for r in ratios],
        "turns": int(turns),
        "arms": arms,
    }


def pipeline_snapshot(stats: dict) -> dict:
    """Overlapped harvest/dispatch pipeline summary from engine.stats:
    how long the harvest worker blocked per round/first readback — time
    that runs CONCURRENTLY with admission+dispatch on the scheduler
    thread since round 6, where it used to serialize the loop (the r5
    ``loop_hround`` ~285 ms block). Published in the bench JSON so the
    overlap is driver-verifiable: harvest_wait_ms_per_round staying at
    ~round duration while TTFT drops is the signature of overlap (the
    wait didn't shrink, it moved off the token path)."""
    rounds = int(stats.get("harvest_rounds", 0))
    firsts = int(stats.get("first_readbacks", 0))
    return {
        "harvest_rounds": rounds,
        "harvest_wait_ms_per_round": round(
            float(stats.get("harvest_wait_ms", 0.0)) / max(1, rounds), 2),
        "first_readback_ms_avg": round(
            float(stats.get("first_readback_ms", 0.0)) / max(1, firsts), 2),
        # High-water mark, NOT the live gauge: this snapshot is taken
        # after the scenarios drained, when the instantaneous depth is
        # trivially 0 — the peak is what proves dispatch ran ahead of
        # harvest during the run.
        "dispatch_depth_peak": int(stats.get("dispatch_depth_peak", 0)),
    }


def rounds_snapshot(engine) -> dict:
    """Round-level attribution for the bench JSON, sourced from the
    engine's ROUND RECORDER (obs/rounds.py) instead of ad-hoc bench
    timers: the same per-round records /debug/rounds serves, aggregated
    over the ring. Complements pipeline_snapshot (which reads the
    engine's cumulative stage counters): this is the per-round
    distribution — device time per round, tokens per round, interleave
    share, live bandwidth estimate, and how far measured rounds drifted
    from the step-cost model. Scoped to THIS engine's records — the
    recorder is process-global, and a degraded-rung or sweep engine's
    rounds must not pollute the measured engine's block."""
    agg = engine.rounds.snapshot(
        limit=0, engine_tag=engine.engine_tag)["aggregates"]
    stats = engine.stats
    return {
        "rounds_completed": int(stats.get("rounds_completed", 0)),
        "window_rounds": int(agg.get("rounds_completed", 0)),
        "avg_round_ms": float(agg.get("avg_round_ms", 0.0)),
        "avg_device_ms": float(agg.get("avg_device_ms", 0.0)),
        "p50_device_ms": float(agg.get("p50_device_ms", 0.0)),
        "tokens_per_sec": float(agg.get("tokens_per_sec", 0.0)),
        "interleaved_share": float(agg.get("interleaved_share", 0.0)),
        "avg_bw_util": float(agg.get("avg_bw_util", 0.0)),
        "drift_ratio": float(stats.get("sched_cost_drift_ratio", 0.0)),
        "budget_recalibrations": int(
            stats.get("sched_budget_recalibrations", 0)),
    }


def run_obs_overhead_bench(params, model_cfg, tokenizer, *,
                           prompt_len: int, out_len: int,
                           n_requests: int = 8, slots: int = 4,
                           interval_s: float = 0.05,
                           kv_quant: str = "", steps_per_round: int = 16,
                           **engine_overrides):
    """Observability-overhead scenario (``BENCH_OBS_OVERHEAD=1``): the
    same closed-loop decode measurement twice — once with the
    retained-telemetry layer DISARMED (``HISTORY_INTERVAL_S=0``
    semantics: no sampler thread, no alert ticks) and once ARMED with
    the history sampler at ``interval_s`` (far tighter than the 5 s
    production default, to give the overhead a chance to show) plus the
    full default chain-tier alert rule set ticking on every sample.
    The acceptance bar in docs/observability.md: armed costs < 1 %
    decode tok/s."""
    from generativeaiexamples_tpu.engine import Engine, EngineConfig
    from generativeaiexamples_tpu.obs import alerts as obs_alerts
    from generativeaiexamples_tpu.obs import history as obs_history
    from generativeaiexamples_tpu.obs import metrics as obs_metrics

    page, per_slot = _sweep_pool_geometry(prompt_len, out_len,
                                          engine_overrides)
    kw = _sweep_engine_kw(slots, prompt_len, out_len, page, per_slot,
                          kv_quant, steps_per_round, engine_overrides)
    rules = obs_alerts.default_rules("chain")
    arms = {}
    armed_samples = 0
    for armed in (False, True):
        engine = Engine(params, model_cfg, tokenizer, EngineConfig(**kw))
        history = None
        try:
            engine.prewarm()
            # Same wiring the chain server's ObservabilityStack uses:
            # engine stats mirrored into every sample, alert engine
            # ticking as a sampler subscriber. The disarmed arm builds
            # nothing at all — the HISTORY_INTERVAL_S=0 deployment.
            if armed:
                history = obs_history.MetricHistory(
                    window_s=60.0, interval_s=interval_s,
                    pre_sample=[lambda e=engine:
                                obs_metrics.record_engine_stats(e.stats),
                                obs_metrics.record_process_stats])
                obs_alerts.AlertEngine(history, rules=rules).attach()
                history.start()
            _, _, tput, _ = run_engine_bench(
                engine, prompt_len, out_len, n_requests, slots)
            arms["armed" if armed else "disarmed"] = tput
        finally:
            if history is not None:
                armed_samples = history.samples
                history.stop()
            engine.stop()
        import gc
        gc.collect()
    armed_tps = arms.get("armed", 0.0)
    disarmed_tps = arms.get("disarmed", 0.0)
    overhead = ((disarmed_tps - armed_tps) / disarmed_tps * 100.0
                if disarmed_tps > 0 else 0.0)
    return {
        "history_interval_s": interval_s,
        "history_window_s": 60.0,
        "alert_rules": len(rules),
        "rounds_per_arm": n_requests,
        "armed_tokens_per_sec": round(armed_tps, 1),
        "disarmed_tokens_per_sec": round(disarmed_tps, 1),
        "armed_samples": armed_samples,
        "overhead_pct": round(overhead, 3),
    }


def assemble_result(*, kind, model, headline, engine_p50, engine_p99, tput,
                    achieved_bw, bw_util, bw_steady, chat, e2e_p50,
                    e2e_dist, e2e_breakdown, pipeline, quant, kv_quant,
                    weights, prompt_len, out_len, slots, steps_per_round,
                    kv_pool_pages, device, rtt_ms, n_devices,
                    bench_seconds, e2e_tps_p50=None, openloop=None,
                    fleet=None, capacity=None, rounds=None,
                    kv_pressure=None, autoscale=None,
                    multichip=None, disagg=None, failover=None,
                    obs_overhead=None) -> dict:
    """The bench's single output contract. Every field name here is
    pinned by tools/bench_schema.json (validated at emit time AND by the
    tier-1 suite, tests/test_bench_schema.py) so a rename fails fast
    instead of silently breaking the round-over-round perf trajectory."""
    return {
        "metric": f"{kind}_p50_ttft_ms_{model.replace('-', '_')}",
        "value": round(headline, 2),
        "unit": "ms",
        "vs_baseline": round(TTFT_BASELINE_MS / headline, 3),
        "engine_p50_ttft_ms": round(engine_p50, 2),
        "engine_p99_ttft_ms": round(engine_p99, 2),
        "decode_tokens_per_sec": round(tput, 1),
        "hbm_bw_achieved_gbps": round(achieved_bw / 1e9, 1),
        "hbm_bw_util": round(bw_util, 3),
        # False = slots exceeded the pool's page capacity; tput and the
        # roofline number caught re-admission churn and are unreliable
        "decode_window_steady": bw_steady,
        # Multi-turn scenario: cold vs warm (shared-prefix) engine TTFT
        "chat": chat,
        "e2e_chat_ttft_ms": round(e2e_p50, 2) if e2e_p50 else None,
        "e2e_chat_p99_ttft_ms": e2e_dist["p99"] if e2e_dist else None,
        "e2e_ttft_dist_ms": e2e_dist,
        "e2e_breakdown_ms": e2e_breakdown,
        # Exact median of per-request tokens/sec (flight-timeline
        # generated/duration, warmup excluded) — the per-request
        # distribution the old last-write-wins gauge could not represent
        # under concurrency; live scrapes get the same distribution as
        # the chain_generate_tokens_per_second histogram
        "e2e_tokens_per_second_p50": e2e_tps_p50,
        # Harvest/dispatch overlap: the readback wait now runs on the
        # harvest worker, concurrent with dispatch (pipeline_snapshot)
        "engine_pipeline": pipeline,
        # Round telemetry (obs/rounds.py): per-round attribution from
        # the engine's round recorder — device ms per round, interleave
        # share, live bandwidth estimate, model-vs-measured drift
        "engine_rounds": rounds,
        # Open-loop Poisson-arrival scenario (BENCH_ARRIVAL_RPS sweep):
        # SLO attainment + goodput under offered load — null when the
        # sweep is not requested (closed-loop-only runs keep their
        # existing shape)
        "openloop": openloop,
        # Multi-replica fleet scenario (BENCH_REPLICAS >= 2): Poisson
        # session load through the router over N in-process replicas,
        # affinity placement vs a round-robin baseline — cross-replica
        # prefix_hit_rate and SLO attainment per policy. Null when the
        # fleet is not requested.
        "fleet": fleet,
        # Slots-ladder capacity sweep (BENCH_SLOTS_SWEEP): per-rung
        # TTFT/throughput/HBM-roofline — the BENCH_SWEEP_rNN table as
        # one validated section. Null when the sweep is not requested.
        "capacity": capacity,
        # Multi-chip serving sweep (BENCH_MESH=tp=1,tp=2,...): one
        # tp-sharded engine per mesh rung — decode tok/s and p50 TTFT
        # vs chips, plus the topology-matched round budget each rung's
        # scheduler started from. Null when the sweep is not requested.
        "multichip": multichip,
        # KV-pressure scenario (BENCH_KV_PRESSURE): multi-turn chat at
        # working sets N× the KV pool, host tiering off vs on — warm
        # TTFT + restore hit rate per arm. Null when not requested.
        "kv_pressure": kv_pressure,
        # Autoscale scenario (BENCH_AUTOSCALE=1): diurnal/bursty arrival
        # trace through the router, SLO-driven autoscaling vs an
        # equal-average static fleet — slo_attainment + replica_minutes
        # per arm (docs/autoscaling.md). Null when not requested.
        "autoscale": autoscale,
        # Disaggregation scenario (BENCH_DISAGG=1): prefill/decode chip
        # pools vs a unified fleet at equal chips over an adversarial
        # long/short prompt mix — TTFT p50 + decode goodput per arm
        # (docs/disaggregation.md). Null when not requested.
        "disagg": disagg,
        # Failover scenario (BENCH_FAILOVER=1): scripted mid-stream
        # replica kill under open-loop load, transcript-replay resume
        # on vs off — completed-without-client-visible-error rate and
        # the latency resumed streams paid (docs/robustness.md). Null
        # when not requested.
        "failover": failover,
        # Observability-overhead scenario (BENCH_OBS_OVERHEAD=1): the
        # same decode workload with the retained-telemetry layer armed
        # (history sampler + alert engine ticking) vs disarmed
        # (HISTORY_INTERVAL_S=0) — decode tok/s each way and the
        # percentage the armed layer costs (docs/observability.md).
        # Null when not requested.
        "obs_overhead": obs_overhead,
        "quantization": quant,
        "kv_quant": kv_quant,
        "weights": weights,
        "prompt_len": prompt_len,
        "output_len": out_len,
        "slots": slots,
        "steps_per_round": steps_per_round,
        "kv_pool_pages": kv_pool_pages,
        "device": device,
        "dispatch_rtt_ms": rtt_ms,
        "n_devices": n_devices,
        "bench_seconds": bench_seconds,
    }


def hbm_utilization(engine, model_cfg, tput: float, slots: int,
                    prompt_len: int, out_len: int
                    ) -> tuple[float, float, bool]:
    """Achieved HBM bytes/s during steady decode vs the chip's peak.

    Per decode step the device must read every weight byte once plus the
    live KV window (gathered pages) — the memory-bound decode roofline
    (VERDICT.md weak #1 made this regression invisible; now it's printed)."""
    import jax

    param_bytes = tree_bytes(engine.params)
    dt_size = 2  # bfloat16
    page = engine.cfg.page_size
    if engine._use_kernel:
        # The Pallas kernel streams each slot's LIVE pages (dynamic
        # per-slot loop bound); average context over the measured window
        # is prompt + half the generation.
        win_pages = -(-(prompt_len + out_len) // page)
    else:
        # jnp fallback gathers the bucketed window for every slot
        win_pages = engine._window_for(-(-(prompt_len + out_len + 1) // page))
    kv_read = (model_cfg.num_layers * slots * win_pages * page
               * model_cfg.num_kv_heads * model_cfg.head_dim * 2 * dt_size)
    steps_per_sec = tput / slots
    achieved = (param_bytes + kv_read) * steps_per_sec
    dev = jax.local_devices()[0]
    # No HBM roofline on a CPU: the share reads 0.0 there, never a
    # share of some chip's peak.
    util = 0.0 if dev.platform == "cpu" else achieved / _peak_bw(dev)
    # The model presumes every slot decodes every step. That only holds
    # when the pool can hold all slots' windows at once; past that,
    # admission staggers, the measured window catches re-admission churn,
    # and BOTH tput and this roofline number are unreliable (observed:
    # util "1.9" at BENCH_SLOTS=32 on a 53-page pool). steady=False
    # marks such a run in the output rather than printing a confident lie.
    steady = slots * win_pages <= engine._n_pages - 1
    return achieved, util, steady


def run_e2e_bench(engine, embedder, n_requests: int):
    """p50 TTFT of the full QA-chatbot path through the chain server,
    plus a per-stage latency breakdown (embed / retrieve / template /
    prefill / first chunk) read from each request's FLIGHT-RECORDER
    timeline (obs/flight.py): the bench sends an X-Request-ID per
    request and looks its completed timeline up afterwards — the same
    path an operator debugging one slow production request takes via
    /debug/requests, so the bench exercises (and validates) the
    recorder itself instead of a process-global stage hook.
    Process-GLOBAL pipeline stages
    (harvest wait per round, loop phases) are not per-request facts and
    therefore no longer appear in this breakdown — they live in the
    artifact's ``engine_pipeline`` block (pipeline_snapshot)."""
    import statistics
    import tempfile
    import uuid

    import requests
    from aiohttp import web

    from generativeaiexamples_tpu.chains.examples.developer_rag import QAChatbot
    from generativeaiexamples_tpu.chains.llm import EngineLLM
    from generativeaiexamples_tpu.chains.server import create_app
    from generativeaiexamples_tpu.obs import flight
    from generativeaiexamples_tpu.utils.app_config import AppConfig
    from generativeaiexamples_tpu.utils.configuration import from_dict

    cfg = from_dict(AppConfig, {
        "text_splitter": {"chunk_size": 100, "chunk_overlap": 20}})
    ex = QAChatbot(llm=EngineLLM(engine), embedder=embedder, config=cfg)
    docs = [
        "The MXU is a 128x128 systolic array that performs matrix multiplies "
        "in bfloat16 with float32 accumulation.",
        "TPU chips in a slice communicate over ICI links; XLA compiles "
        "collectives like all-reduce directly into the program.",
        "Paged KV caching shares a pool of fixed-size pages between decode "
        "slots, so cache capacity is sized to HBM instead of batch size.",
        "Continuous batching admits new requests into the decode batch "
        "between steps without recompiling the program.",
    ]
    with tempfile.TemporaryDirectory() as td:
        for i, d in enumerate(docs):
            p = os.path.join(td, f"doc{i}.txt")
            with open(p, "w") as f:
                f.write(d)
            ex.ingest_docs(p, f"doc{i}.txt")

    app = create_app(ex)
    loop = asyncio.new_event_loop()
    port_holder: dict = {}
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)

        async def boot():
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port_holder["port"] = site._server.sockets[0].getsockname()[1]
        loop.run_until_complete(boot())
        started.set()
        loop.run_forever()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    started.wait(timeout=30)
    url = f"http://127.0.0.1:{port_holder['port']}/generate"

    all_stages: list = []
    raw_tps: list = []

    def one_ttft(seq: int) -> float:
        # num_tokens bounds the overestimate: with random weights the
        # detokenizer often withholds everything until the final flush
        # (no valid UTF-8), so first-byte time degenerates to completion
        # time. Real checkpoints stream normally.
        #
        # The question varies per request: on the host (non-fused) RAG
        # path every request submits the templated prompt through
        # engine.submit, and an identical question would make request
        # 2+ a full-cover prefix-cache hit — the headline e2e number
        # must stay the COLD TTFT it was in r05 (warm TTFT is the chat
        # scenario's job). The shared system/context prefix still
        # matching is the production-realistic part and is reported by
        # the engine's hit counters, not hidden.
        rid = f"bench-{seq}-{uuid.uuid4().hex[:8]}"
        t0 = time.monotonic()
        with requests.post(url, json={
                "question": f"(case {seq}) What does the MXU do and "
                            f"how big is it?",
                "use_knowledge_base": True, "num_tokens": 16},
                headers={"X-Request-ID": rid},
                stream=True, timeout=300) as resp:
            resp.raise_for_status()
            # First byte, or EOF for a zero-visible-token generation
            # (random-weight greedy decode can hit eos immediately) —
            # either way the retrieve->embed->prefill path completed.
            tail = b""
            # ONE iter_content generator for first-byte + drain: a second
            # generator on a partially-consumed chunked stream terminates
            # it early (observed: 1-byte bodies while the engine kept
            # generating — which also poisoned the next request's TTFT
            # with the orphaned decode round).
            it = resp.iter_content(chunk_size=1)
            for b in it:
                tail = b
                break
            dt = (time.monotonic() - t0) * 1e3
            # Drain the rest: a sequential chat user reads the full
            # answer before asking again.
            for b in it:
                tail += b
            # The server degrades failures into the stream (reference
            # semantics) — a bench that timed the error banner's first
            # byte would report fiction.
            if b"[error]" in tail:
                raise RuntimeError(
                    f"e2e generation failed in-stream: {tail[:200]!r}")
        # The per-stage breakdown comes from this request's flight
        # timeline — chain stages (embedding/retrieve/templating/llm)
        # and engine stages (admit/first readback/ttft) on one record,
        # keyed by the X-Request-ID sent above. The timeline's
        # generated/duration also give the request's TRUE tokens/sec
        # (exact, unlike the bucket-edge-quantized histogram p50, and
        # warmup-free since the warmup's rid is never looked up here).
        tl = flight.RECORDER.find(rid)
        # The chain worker's finally completes the timeline (stamping
        # duration_ms) moments after the HTTP body drains — wait for it.
        deadline = time.monotonic() + 5
        while tl is not None and not tl.done \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        all_stages.append(tl.stage_durations() if tl is not None else {})
        meta = tl.meta if tl is not None else {}
        if meta.get("generated") and meta.get("duration_ms"):
            raw_tps.append(meta["generated"] / (meta["duration_ms"] / 1e3))
        return dt

    one_ttft(seq=0)  # warmup: compiles the e2e prompt geometry
    all_stages.clear()
    raw_tps.clear()
    raw = [one_ttft(seq=1 + i) for i in range(n_requests)]
    loop.call_soon_threadsafe(loop.stop)
    ttfts = sorted(raw)
    p50 = ttfts[len(ttfts) // 2]
    # Tail + spread: the target is only credible if it holds beyond the
    # median of one jittery batch — publish p99, min/max, and per-batch
    # medians (3 groups in arrival order), so a noisy run is visible in
    # the artifact itself.
    p99 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))]
    nb = max(1, len(raw) // 3)
    batches = [sorted(raw[i:i + nb]) for i in range(0, len(raw), nb)]
    batch_p50s = [round(b[len(b) // 2], 2) for b in batches if b]
    dist = {"p99": round(p99, 2), "min": round(ttfts[0], 2),
            "max": round(ttfts[-1], 2), "batch_p50s": batch_p50s,
            "samples": len(raw)}
    breakdown = {}
    for key in sorted({k for s in all_stages for k in s}):
        vals = [s[key] * 1e3 for s in all_stages if key in s]
        if vals:
            breakdown[key] = round(statistics.median(vals), 2)
    tps_p50 = round(statistics.median(raw_tps), 1) if raw_tps else None
    return p50, dist, breakdown, tps_p50


def main() -> None:
    model = os.environ.get("BENCH_MODEL", "llama-2-7b-chat")
    quant = os.environ.get("BENCH_QUANT", "int8")
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN", "512"))
    out_len = int(os.environ.get("BENCH_OUTPUT_LEN", "64"))
    # 24 samples: a p50 over 8 requests wobbles between runs; 24
    # tightens the estimator without materially lengthening the bench.
    n_requests = int(os.environ.get("BENCH_REQUESTS", "24"))
    # Slot-count choice: 8 is the README quickstart's deployment;
    # throughput deployments raise BENCH_SLOTS/max_slots (which is
    # faster on the current chip: not measured). Sweeps past the pool's
    # page capacity (slots * window > kv_pool_pages) make the
    # steady-state window unreliable — re-admission churn inflates the
    # token counter past the HBM roofline; see hbm_utilization's
    # live-slot clamp.
    slots = int(os.environ.get("BENCH_SLOTS", "8"))

    t_start = time.monotonic()
    skip_e2e = bool(os.environ.get("BENCH_SKIP_E2E"))

    # Dispatch round-trip floor: a bare jit(x+1) dispatch + scalar
    # readback — the fixed host<->device cost every first token pays at
    # least once. Published so the headline number is interpretable.
    def measure_rtt() -> float:
        import jax
        import jax.numpy as jnp
        import statistics

        f = jax.jit(lambda x: x + 1)
        x = jnp.ones((8,))
        float(f(x)[0])  # compile + warm
        samples = []
        for _ in range(5):
            t0 = time.monotonic()
            float(f(x)[0])
            samples.append((time.monotonic() - t0) * 1e3)
        return statistics.median(samples)

    from generativeaiexamples_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    rtt_ms = round(measure_rtt(), 1)
    # Embedder first (and only once): the engine's auto-sized KV pool
    # must account for its memory.
    embedder = None if skip_e2e else build_embedder()

    # One build that raises: no smaller model, no other quantization —
    # a run that lost its model must not exit 0 under another's name.
    engine, model_cfg = build_engine(model, slots, prompt_len, out_len,
                                     quant)
    try:
        p50, p99, tput, _ = run_engine_bench(engine, prompt_len, out_len,
                                             n_requests, slots)
        achieved_bw, bw_util, bw_steady = hbm_utilization(
            engine, model_cfg, tput, slots, prompt_len, out_len)
        # Multi-turn chat: warm-turn (shared-prefix) TTFT next to the
        # cold-start number above. A default scenario: failure is fatal.
        chat = None
        if not os.environ.get("BENCH_SKIP_CHAT"):
            chat = run_chat_bench(
                engine,
                n_turns=int(os.environ.get("BENCH_CHAT_TURNS", "6")),
                system_len=int(os.environ.get("BENCH_CHAT_SYSTEM", "512")))
        e2e_p50, e2e_dist, e2e_breakdown = None, None, None
        e2e_tps_p50 = None
        if not skip_e2e:
            e2e_p50, e2e_dist, e2e_breakdown, e2e_tps_p50 = \
                run_e2e_bench(engine, embedder, max(3, n_requests))
        # Open-loop goodput sweep: only when BENCH_ARRIVAL_RPS names the
        # offered rates (comma-separated requests/sec). Runs LAST — its
        # overload shedding would pollute the closed-loop numbers above.
        openloop = None
        rps_env = os.environ.get("BENCH_ARRIVAL_RPS", "")
        if rps_env:
            try:
                openloop = run_openloop_bench(
                    engine,
                    rates=[float(r) for r in rps_env.split(",") if r],
                    duration_s=float(os.environ.get(
                        "BENCH_OPENLOOP_SECONDS", "10")),
                    slo_ttft_ms=float(os.environ.get(
                        "BENCH_SLO_TTFT_MS", "500")),
                    deadline_ms=float(os.environ.get(
                        "BENCH_OPENLOOP_DEADLINE_MS", "2000")),
                    prompt_median=int(os.environ.get(
                        "BENCH_OPENLOOP_PROMPT_MEDIAN",
                        str(min(256, prompt_len)))),
                    prompt_sigma=float(os.environ.get(
                        "BENCH_OPENLOOP_PROMPT_SIGMA", "0.6")),
                    out_len=int(os.environ.get(
                        "BENCH_OPENLOOP_OUT", str(min(32, out_len)))),
                    seed=int(os.environ.get("BENCH_SEED", "0")))
            except Exception as exc:  # noqa: BLE001
                sys.stderr.write(f"bench: open-loop scenario failed: "
                                 f"{exc}\n")
        # Cumulative over every scenario above — the overlap summary is
        # about pipeline behavior, not one workload's magnitude.
        pipeline = pipeline_snapshot(engine.stats)
        rounds = rounds_snapshot(engine)
    finally:
        engine.stop()

    # Capacity sweep (BENCH_SLOTS_SWEEP=8,16,32,64): per-rung engines
    # over the measured model's params, run with the main engine STOPPED
    # (its auto-sized pool released is not possible — params stay held —
    # so rung pools are sized explicitly). Degrades to capacity=null.
    capacity = None
    sweep_env = os.environ.get("BENCH_SLOTS_SWEEP", "")
    if sweep_env:
        try:
            capacity = run_capacity_sweep(
                engine.params, model_cfg, engine.tokenizer,
                [int(s) for s in sweep_env.split(",") if s],
                prompt_len=prompt_len, out_len=out_len,
                n_requests=int(os.environ.get("BENCH_SWEEP_REQUESTS",
                                              "8")),
                kv_quant=engine.cfg.kv_quant,
                steps_per_round=engine.cfg.steps_per_round)
        except Exception as exc:  # noqa: BLE001
            sys.stderr.write(f"bench: capacity sweep failed: {exc}\n")

    # Multi-chip serving sweep (BENCH_MESH=tp=1,tp=2,...): one engine
    # per mesh rung over the measured params (re-sharded per rung),
    # main engine stopped. Degrades to multichip=null.
    multichip = None
    mesh_env = os.environ.get("BENCH_MESH", "")
    if mesh_env:
        try:
            multichip = run_multichip_sweep(
                engine.params, model_cfg, engine.tokenizer,
                split_mesh_rungs(mesh_env),
                prompt_len=prompt_len, out_len=out_len,
                n_requests=int(os.environ.get("BENCH_MESH_REQUESTS",
                                              "8")),
                slots=int(os.environ.get("BENCH_MESH_SLOTS",
                                         str(slots))),
                kv_quant=engine.cfg.kv_quant,
                steps_per_round=engine.cfg.steps_per_round,
                spec=os.environ.get("BENCH_SPEC", "") not in ("", "0"))
        except Exception as exc:  # noqa: BLE001
            sys.stderr.write(f"bench: multichip sweep failed: {exc}\n")

    # KV-pressure scenario (BENCH_KV_PRESSURE=1,2,4): working sets N×
    # the pool, tiering off vs on. Fresh small engines over the
    # measured params, main engine stopped. Degrades to null.
    kv_pressure = None
    kvp_env = os.environ.get("BENCH_KV_PRESSURE", "")
    if kvp_env:
        try:
            kv_pressure = run_kv_pressure_bench(
                engine.params, model_cfg, engine.tokenizer,
                ratios=[int(r) for r in kvp_env.split(",") if r],
                turns=int(os.environ.get("BENCH_KV_PRESSURE_TURNS", "3")),
                seed=int(os.environ.get("BENCH_SEED", "0")))
        except Exception as exc:  # noqa: BLE001
            sys.stderr.write(f"bench: kv-pressure scenario failed: "
                             f"{exc}\n")

    # Fleet scenario (BENCH_REPLICAS >= 2): the router over N fresh
    # in-process replicas sharing the measured model's params. Runs with
    # the main engine STOPPED (its pool idle) and explicit small replica
    # pools; prewarm's shrink-on-OOM absorbs tight-HBM hosts. Degrades
    # to fleet=null, never aborts the bench. BENCH_FLEET_TRANSFER=0
    # drops the transfer-enabled arm (on by default: the cross-replica
    # prefix-hit headline needs it).
    fleet = None
    n_rep = int(os.environ.get("BENCH_REPLICAS", "0") or 0)
    if n_rep >= 2:
        transfer_arm = os.environ.get("BENCH_FLEET_TRANSFER", "1") \
            not in ("", "0", "false", "off")
        fleet_engines = []
        try:
            hp_env = os.environ.get("BENCH_FLEET_HOST_POOL_TOKENS", "")
            if hp_env != "":
                host_pool = int(hp_env)   # explicit 0 means tier-less
            elif transfer_arm:
                host_pool = int(os.environ.get(
                    "BENCH_FLEET_KV_POOL_TOKENS", "4096")) * 4
            else:
                host_pool = 0
            fleet_engines = build_fleet_engines(
                engine.params, model_cfg, engine.tokenizer, n_rep,
                host_pool_tokens=host_pool)
            fleet = run_fleet_bench(
                fleet_engines,
                sessions=int(os.environ.get("BENCH_FLEET_SESSIONS", "6")),
                turns=int(os.environ.get("BENCH_FLEET_TURNS", "4")),
                session_rps=float(os.environ.get(
                    "BENCH_FLEET_SESSION_RPS", "2")),
                slo_ttft_ms=float(os.environ.get(
                    "BENCH_SLO_TTFT_MS", "2000")),
                transfer_arm=transfer_arm,
                seed=int(os.environ.get("BENCH_SEED", "0")))
        except Exception as exc:  # noqa: BLE001
            sys.stderr.write(f"bench: fleet scenario failed: {exc}\n")
        finally:
            for e in fleet_engines:
                try:
                    e.stop()
                except Exception:  # noqa: BLE001
                    pass

    # Autoscale scenario (BENCH_AUTOSCALE=1): the diurnal trace through
    # the router, autoscaled vs equal-average static. Fresh small
    # replica engines over the measured params (the full fleet is the
    # autoscale ceiling), main engine stopped. Degrades to null.
    autoscale = None
    if os.environ.get("BENCH_AUTOSCALE", "") not in ("", "0"):
        as_engines = []
        try:
            n_as = int(os.environ.get("BENCH_AUTOSCALE_REPLICAS", "")
                       or max(3, n_rep))
            as_engines = build_fleet_engines(
                engine.params, model_cfg, engine.tokenizer, n_as)
            autoscale = run_autoscale_bench(
                as_engines,
                duration_s=float(os.environ.get(
                    "BENCH_AUTOSCALE_SECONDS", "12")),
                trace=parse_trace(os.environ.get(
                    "BENCH_AUTOSCALE_TRACE", "0.3:1,0.3:6,0.4:1")),
                slo_ttft_ms=float(os.environ.get(
                    "BENCH_SLO_TTFT_MS", "2000")),
                deadline_ms=float(os.environ.get(
                    "BENCH_AUTOSCALE_DEADLINE_MS", "0")) or None,
                num_tokens=int(os.environ.get(
                    "BENCH_AUTOSCALE_TOKENS", "8")),
                min_replicas=int(os.environ.get(
                    "BENCH_AUTOSCALE_MIN", "1")),
                interval_s=float(os.environ.get(
                    "BENCH_AUTOSCALE_INTERVAL_S", "0.3")),
                seed=int(os.environ.get("BENCH_SEED", "0")))
        except Exception as exc:  # noqa: BLE001
            sys.stderr.write(f"bench: autoscale scenario failed: "
                             f"{exc}\n")
        finally:
            for e in as_engines:
                try:
                    e.stop()
                except Exception:  # noqa: BLE001
                    pass

    # Disaggregation scenario (BENCH_DISAGG=1): 1 prefill + N-1 decode
    # replicas vs N unified at equal chips, adversarial long/short mix
    # (docs/disaggregation.md). Per-arm engines are built and stopped
    # inside the scenario (the role matrix differs per arm). Degrades
    # to null.
    disagg = None
    if os.environ.get("BENCH_DISAGG", "") not in ("", "0"):
        try:
            disagg = run_disagg_bench(
                engine.params, model_cfg, engine.tokenizer,
                replicas=int(os.environ.get(
                    "BENCH_DISAGG_REPLICAS", "2")),
                requests=int(os.environ.get(
                    "BENCH_DISAGG_REQUESTS", "24")),
                rps=float(os.environ.get("BENCH_DISAGG_RPS", "4")),
                long_frac=float(os.environ.get(
                    "BENCH_DISAGG_LONG_FRAC", "0.4")),
                long_chars=int(os.environ.get(
                    "BENCH_DISAGG_LONG_CHARS", "4600")),
                short_chars=int(os.environ.get(
                    "BENCH_DISAGG_SHORT_CHARS", "400")),
                num_tokens=int(os.environ.get(
                    "BENCH_DISAGG_TOKENS", "16")),
                seed=int(os.environ.get("BENCH_SEED", "0")))
        except Exception as exc:  # noqa: BLE001
            sys.stderr.write(f"bench: disagg scenario failed: {exc}\n")

    # Failover scenario (BENCH_FAILOVER=1): scripted mid-stream replica
    # kill under open-loop load, resume-on vs resume-off arms
    # (docs/robustness.md). Per-arm fleets are built and torn down
    # inside the scenario (a killed replica server can't be reused).
    # Degrades to null.
    failover = None
    if os.environ.get("BENCH_FAILOVER", "") not in ("", "0"):
        try:
            failover = run_failover_bench(
                engine.params, model_cfg, engine.tokenizer,
                replicas=int(os.environ.get(
                    "BENCH_FAILOVER_REPLICAS", "3")),
                requests=int(os.environ.get(
                    "BENCH_FAILOVER_REQUESTS", "16")),
                rps=float(os.environ.get("BENCH_FAILOVER_RPS", "3")),
                num_tokens=int(os.environ.get(
                    "BENCH_FAILOVER_TOKENS", "32")),
                seed=int(os.environ.get("BENCH_SEED", "0")))
        except Exception as exc:  # noqa: BLE001
            sys.stderr.write(f"bench: failover scenario failed: {exc}\n")

    # Observability-overhead scenario (BENCH_OBS_OVERHEAD=1): decode
    # tok/s with the retained-telemetry layer armed vs disarmed
    # (docs/observability.md's < 1 % acceptance bar). Fresh small
    # engines over the measured params, main engine stopped. Degrades
    # to null.
    obs_overhead = None
    if os.environ.get("BENCH_OBS_OVERHEAD", "") not in ("", "0"):
        try:
            obs_overhead = run_obs_overhead_bench(
                engine.params, model_cfg, engine.tokenizer,
                prompt_len=prompt_len, out_len=out_len,
                n_requests=int(os.environ.get(
                    "BENCH_OBS_REQUESTS", "8")),
                slots=int(os.environ.get("BENCH_OBS_SLOTS", "4")),
                interval_s=float(os.environ.get(
                    "BENCH_OBS_INTERVAL_S", "0.05")),
                kv_quant=engine.cfg.kv_quant,
                steps_per_round=engine.cfg.steps_per_round)
        except Exception as exc:  # noqa: BLE001
            sys.stderr.write(f"bench: obs-overhead scenario failed: "
                             f"{exc}\n")

    import jax
    # Headline = the full QA-chatbot path (BASELINE.json's north star is
    # the *chatbot* TTFT, not the engine-only number — VERDICT r3 weak
    # #1); engine-only TTFT degrades to headline only when e2e is off.
    result = assemble_result(
        kind="e2e_chat" if e2e_p50 else "engine",
        model=model,
        headline=e2e_p50 if e2e_p50 else p50,
        engine_p50=p50, engine_p99=p99, tput=tput,
        achieved_bw=achieved_bw, bw_util=bw_util, bw_steady=bw_steady,
        chat=chat, e2e_p50=e2e_p50, e2e_dist=e2e_dist,
        e2e_breakdown=e2e_breakdown, e2e_tps_p50=e2e_tps_p50,
        pipeline=pipeline, openloop=openloop, fleet=fleet,
        capacity=capacity, rounds=rounds, kv_pressure=kv_pressure,
        autoscale=autoscale, multichip=multichip, disagg=disagg,
        failover=failover, obs_overhead=obs_overhead,
        quant=quant, kv_quant=engine.cfg.kv_quant or None,
        weights=("real" if os.environ.get("BENCH_MODEL_PATH")
                 else "random-init"),
        prompt_len=prompt_len, out_len=out_len, slots=slots,
        steps_per_round=engine.cfg.steps_per_round,
        kv_pool_pages=engine._n_pages - 1,
        device=str(jax.local_devices()[0].device_kind),
        rtt_ms=rtt_ms, n_devices=jax.local_device_count(),
        bench_seconds=round(time.monotonic() - t_start, 1))
    # Fail fast on schema drift: a renamed field aborts the bench with a
    # precise message instead of silently breaking the perf trajectory
    # (the same validation runs on CPU in tests/test_bench_schema.py).
    from tools.check_bench_schema import validate_result
    validate_result(result)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
