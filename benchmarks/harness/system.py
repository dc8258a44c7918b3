"""The system under test, built from a configuration file: weights from
the seed (on the device, one program), the engine as a deployment
builds it, the checks that decide ``correct``, and the warm-up.

Copied in idea from ``chip_smoke.py`` (``make_params``,
``engine_report``, ``check_engine``, ``device_info``) and kept here so a
later change to that script cannot move the yardstick. From the package
this module takes only what is measured: ``Engine``, ``EngineConfig``,
``SamplingParams``, the paged model programs of ``llama`` and its weight
initialiser, ``quantize_params``, the tokenizer, the encoder's
parameters and ``enable_compile_cache``. What the paged programs are
held to is the benchmark's own: ``benchmarks/references/``.
"""

from __future__ import annotations

import os
import time

from .spec import REPO

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CheckFailed(AssertionError):
    """A condition of ``correct`` does not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


# ---------------------------------------------------------------- device


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no such accounting, as the CPU does)."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def setup_jax() -> str:
    """The compile cache at its fixed place (``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), every program kept in it however fast
    it compiled, so that a second run finds them all."""
    import jax

    from generativeaiexamples_tpu.utils.compile_cache import (
        enable_compile_cache)
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileLog:
    """Every program JAX builds (compiled, or loaded from the persistent
    cache), with the instant and the function's name."""

    def __init__(self):
        self.events: list = []       # (monotonic t, fun_name, seconds)
        self.cache_hits = 0

    def install(self) -> "CompileLog":
        from jax import monitoring

        def on_duration(event, duration, **kw):
            if event == BACKEND_COMPILE_EVENT:
                self.events.append((time.monotonic(),
                                    str(kw.get("fun_name", "?")),
                                    float(duration)))

        def on_event(event, **kw):
            if event == CACHE_HIT_EVENT:
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        return self

    def between(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] <= t1]

    def last_t(self) -> float:
        return self.events[-1][0] if self.events else 0.0


# --------------------------------------------------------------- weights


def model_config(config: dict):
    from generativeaiexamples_tpu.models.configs import LlamaConfig
    return LlamaConfig(**config["model"])


def make_params(cfg, quant: str, seed: int):
    """Random weights at the configuration's sizes, made on the device
    in ONE jitted program, in the type they are served in."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops.quant import quantize_params

    def make(key):
        params = llama.init_params(cfg, key, dtype=jnp.bfloat16)
        return quantize_params(params, quant) if quant else params

    params = jax.jit(make)(jax.random.key(seed))
    jax.block_until_ready(params)
    return params


def make_encoder_params(seed: int):
    """The e5-large-v2 encoder's weights, resident beside the model as
    the model server keeps them (no cell calls it; it holds memory)."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import encoder
    from generativeaiexamples_tpu.models.configs import E5_LARGE_V2
    params = jax.jit(lambda key: encoder.init_params(
        E5_LARGE_V2, key, dtype=jnp.bfloat16))(jax.random.key(seed + 1))
    jax.block_until_ready(params)
    return params


def make_tokenizer(vocab_size: int):
    from generativeaiexamples_tpu.models.sentencepiece import (
        SentencePieceTokenizer)
    tok = SentencePieceTokenizer(os.path.join(
        REPO, "generativeaiexamples_tpu", "assets", "tokenizer_32k.model"))
    if tok.vocab_size > vocab_size:        # tiny rehearsal vocabularies
        from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
        tok = ByteTokenizer()
    check(tok.vocab_size <= vocab_size, "tokenizer larger than the model")
    return tok


# ---------------------------------------------------------- logits check


def load_reference(config: dict):
    """The configuration's plain reference, found by name like a reader:
    ``"reference": "<name>"`` is ``benchmarks/references/<name>.py``."""
    import importlib
    return importlib.import_module(
        f"benchmarks.references.{config['reference']}")


def logits_check(params, cfg, config: dict, seed: int,
                 kv_quantized: bool = False, forward=None) -> dict:
    """The paged path against the configuration's plain reference, on
    the cell's weights as they are stored.

    For ``prompts`` seeded prompts (a whole number of pages, one
    sequence at a time): the logits of ``apply_prefill_paged`` at the
    last ``positions`` prompt positions, and the logits of
    ``decode_steps`` teacher-forced steps of ``apply_decode_paged``
    through the pool (the Pallas kernel where the engine arms it) —
    prefill and decoding through the cache — against ONE float32 pass
    without a cache: ``benchmarks/references/<name>.py`` ``forward``
    over prompt + chain (``forward`` overrides it: the tests put a
    broken one there). The greedy chain is the reference's own, a
    forward a step over one buffer of prompt + ``decode_steps`` ids
    (causal: what follows a position does not move it). The error of
    one position is max |paged - reference| over max |reference|.

    Held, each at the configuration's own number: the MEDIAN prompt
    position and the median decode step to ``median_tolerance``, and the
    share of positions over ``tolerance`` to ``max_share_over`` (0 holds
    the maximum). A dense model holds every position. With random
    weights a sparse-expert router has near-ties, and the few bf16 ulps
    by which the program's stream differs from float32 flip a top-2
    choice (or which assignment a full expert drops) at some positions,
    which moves THAT position's logits by a third of their scale; a
    precision fault moves every position. ``kv_quantized`` runs the
    paged side over an int8 pool where bf16 is stated — the fault
    ``check_sensitivity.py`` injects to show whether these numbers
    catch one. Returns the reference logits too, for the engine's own
    tokens to be held against."""
    import statistics

    import jax
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.models import llama

    if forward is None:
        forward = load_reference(config).forward
    lc = config["logits_check"]
    page = int(config["engine"].get("page_size", 128))
    S = int(lc.get("prompt_pages", 2)) * page
    n_pos = min(S, int(lc.get("positions", 64)))
    n_dec = int(lc.get("decode_steps", 4))
    nb = -(-(S + n_dec) // page)
    use_kernel = llama.use_paged_kernel(cfg, page)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    # the model group as the file has it, and which tokens the paged
    # side routes together (a reference under a capacity asks): the
    # prompt as one chunk, then each decode step alone
    model = dict(config["model"], routed_together=[S] + [1] * n_dec)

    def reference(ids_np):
        """Prefill logits, the greedy chain and each step's logits."""
        buf = np.concatenate([ids_np, np.zeros(n_dec, ids_np.dtype)])
        prefill = forward(params, model, buf[None],
                          np.arange(S - n_pos, S))
        nxt = int(jnp.argmax(prefill[-1]))
        toks, steps = [], []
        for i in range(n_dec):
            toks.append(nxt)
            buf[S + i] = nxt
            steps.append(forward(params, model, buf[None],
                                 np.arange(S + i, S + i + 1))[0])
            nxt = int(jnp.argmax(steps[-1]))
        return prefill, jnp.asarray(toks, jnp.int32), jnp.stack(steps)

    @jax.jit
    def paged(p, ids, toks):
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        pool = llama.init_paged_kv_cache(cfg, nb + 1, page, jnp.bfloat16,
                                         quantized=kv_quantized)
        table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
        hidden, pool = llama.apply_prefill_paged(
            p, cfg, ids, pos, pool, table, i32(S), jnp.int32(0))
        prefill = llama.unembed(p, cfg, hidden[:, S - n_pos:S])[0]
        steps = []
        for i in range(n_dec):
            at = S + i
            step, pool = llama.apply_decode_paged(
                p, cfg, toks[i][None, None], i32(at)[None], pool, table,
                i32(at + 1), i32(1 + at // page), i32(at % page),
                use_kernel=use_kernel)
            steps.append(step[0, 0])
        return prefill.astype(jnp.float32), \
            jnp.stack(steps).astype(jnp.float32)

    def errs(got, want) -> list:
        e = jnp.max(jnp.abs(got - want), axis=-1) \
            / jnp.max(jnp.abs(want), axis=-1)
        return [float(x) for x in e]

    rng = np.random.default_rng([int(seed), 7])
    tol, median_tol = float(lc["tolerance"]), float(lc["median_tolerance"])
    prefill_errs: list = []
    decode_errs: list = []
    prompts = []
    for _ in range(int(lc["prompts"])):
        ids_np = rng.integers(3, cfg.vocab_size, size=S)
        ref_pre, toks, ref_steps = reference(ids_np)
        got_pre, got_steps = paged(
            params, jnp.asarray(ids_np, jnp.int32)[None, :], toks)
        check(bool(jnp.all(jnp.isfinite(got_pre))
                   & jnp.all(jnp.isfinite(got_steps))),
              "paged path: logits not finite")
        prefill_errs += errs(got_pre, ref_pre)
        decode_errs += errs(got_steps, ref_steps)
        # the logits each generated token is drawn from: the last prompt
        # position, then each teacher-forced step
        prompts.append({
            "ids": ids_np.tolist(),
            "ref_tokens": [int(t) for t in toks],
            "ref_logits": np.concatenate(
                [np.asarray(ref_pre[-1:]), np.asarray(ref_steps)])})
    every = sorted(prefill_errs + decode_errs)
    out = {"reference": config["reference"], "prompt_tokens": S,
           "kernel_path": bool(use_kernel),
           "positions": len(prefill_errs), "decode_steps": len(decode_errs),
           "prefill_median_rel_err": statistics.median(prefill_errs),
           "decode_median_rel_err": statistics.median(decode_errs),
           "decode_rel_errs": decode_errs,      # the steps through the pool
           "rel_err_p75_p90": [every[int(0.75 * len(every))],
                               every[int(0.90 * len(every))]],
           "max_rel_err": every[-1],
           "share_over_tolerance": sum(e > tol for e in every) / len(every),
           "tolerance": tol, "median_tolerance": median_tol,
           "max_share_over": float(lc["max_share_over"]),
           "prompts": prompts}
    faults = []
    for what in ("prefill", "decode"):
        got = out[f"{what}_median_rel_err"]
        if got > median_tol:
            faults.append(f"{what} logits differ from the reference by "
                          f"{got:.4f} of their scale at the median "
                          f"position (> {median_tol})")
    if out["share_over_tolerance"] > float(lc["max_share_over"]):
        faults.append(f"{out['share_over_tolerance']:.3f} of the compared "
                      f"positions lie over {tol} (largest "
                      f"{out['max_rel_err']:.4f}; at most "
                      f"{lc['max_share_over']} may)")
    if faults:
        exc = CheckFailed("; ".join(faults))
        exc.readings = {k: v for k, v in out.items() if k != "prompts"}
        raise exc
    return out


def engine_tokens_check(engine, ref: dict, config: dict) -> dict:
    """The engine's own programs (its prefill, fused tail and decode
    rounds): each check prompt served alone and greedily for as many
    tokens as the reference has logits. A token is held against the
    reference logits it was drawn from for as long as the engine follows
    the reference's own greedy chain: its reference logit must lie
    within the tolerance of the largest (random weights leave near-ties
    that rounding may flip, so it need not BE the argmax). At least
    ``min_token_agreement`` of the compared tokens must (a routing flip,
    see ``logits_check``, costs one token; a broken program costs all)."""
    import numpy as np

    from generativeaiexamples_tpu.engine import SamplingParams
    lc = config["logits_check"]
    tol = float(lc["tolerance"])
    compared = ok = 0
    worst = 0.0
    for p in ref["prompts"]:
        n = len(p["ref_logits"])
        s = engine.submit(p["ids"], SamplingParams(
            max_tokens=n, top_k=1, ignore_eos=True))
        wait_done([s], 300.0)
        check(s.finish_reason == "length" and len(s.token_ids) == n,
              f"engine check request ended {s.finish_reason!r} with "
              f"{len(s.token_ids)} tokens")
        chain = [None] + p["ref_tokens"]     # token i follows chain[:i + 1]
        for i, tok in enumerate(s.token_ids):
            logits = p["ref_logits"][i]
            gap = float(np.max(logits) - logits[tok]) \
                / float(np.max(np.abs(logits)))
            compared += 1
            ok += gap <= tol
            worst = max(worst, gap)
            if i + 1 < n and tok != chain[i + 1]:
                break              # the reference followed another token
    need = float(lc["min_token_agreement"])
    check(compared >= 2 and ok >= need * compared,
          f"only {ok} of {compared} engine tokens have a reference logit "
          f"within {tol} of the largest")
    return {"compared": compared, "within_tolerance": ok, "worst_gap": worst}


# ---------------------------------------------------------------- engine


def build_engine(params, cfg, config: dict, seed: int):
    from generativeaiexamples_tpu.engine import Engine, EngineConfig
    from generativeaiexamples_tpu.obs.rounds import RoundRecorder
    ecfg = dict(config["engine"])
    if "prefill_buckets" in ecfg:
        ecfg["prefill_buckets"] = tuple(ecfg["prefill_buckets"])
    engine = Engine(params, cfg, make_tokenizer(cfg.vocab_size),
                    EngineConfig(seed=seed % (2 ** 31), **ecfg))
    # a round recorder of the benchmark's own, sized for a whole window:
    # the process-wide ring is bounded at 512 rounds
    engine.rounds = RoundRecorder(cap=1 << 16)
    return engine


def engine_report(engine) -> dict:
    stats = engine.stats
    return {
        "kernel_path": bool(engine._use_kernel),
        "fused_tail": bool(engine._fused_tail),
        "downgrades": int(stats["downgrades"]),
        "pool_shrinks": int(stats["pool_shrinks"]),
        "pool_pages": int(engine._n_pages - 1),
        "prefill_buckets": list(engine._buckets),
        "cost_model_source": engine._sched._static_cost.source,
        "round_budget_tokens": int(stats["sched_round_budget_tokens"]),
        "fatal": None if engine._fatal is None else repr(engine._fatal),
    }


def engine_programs(engine) -> list:
    """The step programs the engine has built so far, by their shape
    keys — printed (never measured) so that a program first used inside
    a window can be named."""
    return sorted(
        [f"round{k}" for k in engine._round_fns]
        + [f"chunk{k}" for k in engine._chunk_fns], key=str)


def check_engine(report: dict, config: dict) -> None:
    if config.get("require_kernel_path", True):
        check(report["kernel_path"], "Pallas kernel path is not armed")
        check(report["fused_tail"], "fused sampling tail is not armed")
    check(report["downgrades"] == 0, "engine reports feature downgrades")
    check(report["pool_shrinks"] == 0, "the KV pool had to shrink")
    check(report["fatal"] is None, f"engine fatal: {report['fatal']}")


# --------------------------------------------------------------- warm-up


def wait_done(streams: list, timeout_s: float) -> bool:
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        if all(s.finish_reason is not None for s in streams):
            return True
        time.sleep(0.002)
    return False


def wait_first_token(stream, timeout_s: float) -> bool:
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        if stream.first_token_time is not None \
                or stream.finish_reason is not None:
            return True
        time.sleep(0.001)
    return False


def _ladder(top: int) -> list:
    out, w = [], 1
    while w < top:
        out.append(w)
        w *= 2
    return out + [top]


def chunk_window(p: int, o: int, page: int, max_cache_len: int,
                 largest_bucket: int) -> int:
    """The window a (prompt, output) request's chunk programs are built
    for: the larger of its extent's rung on the power-of-two page ladder
    and its prompt's pages plus one largest bucket."""
    pmax = -(-max_cache_len // page)
    extent_pages = -(-(p + o) // page)
    rung = next((r for r in _ladder(pmax) if extent_pages <= r), pmax)
    return max(rung, -(-p // page) + largest_bucket // page)


def prefill_classes(prompt_lens: list, output_lens: list, page: int,
                    max_cache_len: int, largest_bucket: int) -> tuple:
    """For every distinct chunk-program window the mix can reach, the
    (prompt, output) pair with its longest prompt, and (second list)
    the one with its shortest. A chunk program is compiled per window
    (and per chunk bucket, ``chunk_window``); where there are several
    buckets the two ends of a window's prompts leave last chunks of
    different buckets."""
    lo: dict = {}
    hi: dict = {}
    for p in prompt_lens:
        for o in output_lens:
            window = chunk_window(p, o, page, max_cache_len, largest_bucket)
            if window not in hi or p > hi[window][0]:
                hi[window] = (p, o)
            if window not in lo or p < lo[window][0]:
                lo[window] = (p, o)
    return [hi[w] for w in sorted(hi)], [
        lo[w] for w in sorted(lo) if lo[w][0] != hi[w][0]]


def warm_up(engine, cell, make_sampling, gen_factory, log, *,
            timeout_s: float = 900.0) -> dict:
    """Build every program the cell's traffic can reach, counted as
    set-up: (1) each chunk-program window alone (full grants), beside a
    decoding request (grants cut by the decode's cost) and as a pair
    (grants shared: small chunks, so every prompt meets the first,
    middle and final chunk programs); (2) each decode-round variant
    (steps 8/4/2/1, one row and several), verified on the round records;
    (3) the cell's own mix at its own concurrency (the window seed + 1)
    until no program has been built and the scheduler's round budget has
    not moved for ``quiet_s``. ``make_sampling(max_tokens, seed)``
    builds the cell's sampling parameters."""
    wl, mix = cell.workload, cell.mix
    page = engine.cfg.page_size
    t_start = time.monotonic()
    deadline = t_start + timeout_s
    gen = gen_factory()
    prompts, outputs = gen.sizes()
    ids = gen.random_ids

    def left() -> float:
        return max(1.0, deadline - time.monotonic())

    def first_token_then_cancel(sizes: list) -> None:
        streams = [engine.submit(ids(p), make_sampling(o, 1 + i))
                   for i, (p, o) in enumerate(sizes)]
        for s in streams:
            wait_first_token(s, left())
            s.cancel()
        wait_done(streams, left())

    def decoder():
        s = engine.submit(ids(16), make_sampling(
            engine.cfg.max_output_length, 1))
        wait_first_token(s, left())
        return s

    report: dict = {"uncovered": []}
    probe = make_sampling(1, 1)
    greedy = probe.top_k == 1 or probe.temperature <= 0

    def built() -> set:
        return set(engine._chunk_fns) | set(engine._round_fns)

    # (1) chunk-program windows: alone (full grants), beside a decoding
    # request (grants cut by the decode's cost), then several copies at
    # once (shared grants of one page, so even a short prompt meets the
    # first-chunk and final-chunk programs); verified on the engine's
    # table of built programs and tried again with more copies
    classes, short_ends = prefill_classes(
        prompts, outputs, page, engine.cfg.max_cache_len,
        max(engine._buckets))
    if len(engine._buckets) > 1:
        classes = classes + short_ends
    report["prefill_classes"] = len(classes)
    n0 = len(log.events)
    for size in classes:
        first_token_then_cancel([size])
    blocker = decoder()
    for size in classes:
        first_token_then_cancel([size])
    report["built_alone_and_beside_decode"] = len(log.events) - n0
    n0 = len(log.events)
    for size in classes:
        first_token_then_cancel([size] * 4)

    def missing(size) -> list:
        w = chunk_window(*size, page, engine.cfg.max_cache_len,
                         max(engine._buckets))
        want = []
        if size[0] > page:
            want += [("final", w, greedy, False), ("extend", w, "replace")]
        if size[0] > 2 * page:
            want.append(("extend", w, "accum"))
        return [k for k in want if k not in built()]

    for size in classes:
        for copies in (4, 6, 8):
            if not missing(size):
                break
            first_token_then_cancel([size] * copies)
        else:
            report["uncovered"] += [list(k) for k in missing(size)]
    report["built_shared"] = len(log.events) - n0
    blocker.cancel()
    wait_done([blocker], 60.0)
    report["prefill_s"] = time.monotonic() - t_start

    # (2) decode-round variants: steps 8/4/2/1 x one row / several rows
    spr = engine.cfg.steps_per_round
    B = engine.cfg.max_slots
    window_keys = {k[0] for k in engine._round_fns}

    def tail(steps: int) -> int:
        # the engine plans a round for max_tokens - tokens so far
        # steps: a request's last round has `steps` steps when that
        # many (for one step: a full round and one) remain
        return steps if steps > 1 else spr + 1

    def have(steps: int, ba: int) -> bool:
        return any((w, steps, greedy, ba) in engine._round_fns
                   for w in window_keys)

    for steps in _ladder(spr):
        for _ in range(3):
            if have(steps, 1):
                break
            s = engine.submit(ids(16), make_sampling(tail(steps), 1))
            wait_done([s], 120.0)
        else:
            report["uncovered"].append(["round", steps, "one row"])
    if B > 1:
        for steps in _ladder(spr):
            for _ in range(4):
                if have(steps, B):
                    break
                # two short requests admitted in one round beside a
                # decoding blocker run in lockstep; with the blocker
                # cancelled they reach their last steps together
                blocker = decoder()
                pair = [engine.submit(ids(8), make_sampling(
                    3 * spr + tail(steps), 1 + i)) for i in range(2)]
                for s in pair:
                    wait_first_token(s, 120.0)
                blocker.cancel()
                wait_done(pair + [blocker], 120.0)
            else:
                report["uncovered"].append(["round", steps, "several rows"])
    report["decode_s"] = time.monotonic() - t_start - report["prefill_s"]

    # (3) the cell's own mix until the programs and the budget stand still
    quiet_s = float(wl.get("warmup_quiet_s", 3.0))
    soak_max = float(wl.get("warmup_soak_max_s", 20.0))
    soak_min = float(wl.get("warmup_soak_min_s", 4.0))
    t_soak = time.monotonic()
    live: list = []
    budget = engine.stats["sched_round_budget_tokens"]
    t_budget = t_soak
    clients = int(wl.get("clients", 0))
    rate = float(wl.get("rate_rps", 0.0))
    next_due = t_soak
    trail = [[0.0, int(budget)]]
    while True:
        now = time.monotonic()
        b = engine.stats["sched_round_budget_tokens"]
        if b != budget:
            budget, t_budget = b, now
            trail.append([round(now - t_soak, 2), int(b)])
        still_since = max(t_budget, log.last_t(), t_soak)
        if now - t_soak >= soak_max or now > deadline or (
                now - t_soak >= soak_min and now - still_since >= quiet_s):
            break
        live = [s for s in live if s.finish_reason is None]
        if mix["loop"] == "closed":
            while len(live) < clients:
                r = gen.next()
                live.append(engine.submit(
                    r.prompt_ids, make_sampling(r.max_tokens,
                                                r.sampling_seed)))
        elif now >= next_due and len(live) < 4 * engine.cfg.max_slots:
            r = gen.next()
            live.append(engine.submit(
                r.prompt_ids, make_sampling(r.max_tokens, r.sampling_seed)))
            next_due = max(next_due + 1.0 / rate, now - 1.0)
        time.sleep(0.002)
    for s in live:
        s.cancel()
    wait_done(live, 120.0)
    report.update(soak_s=time.monotonic() - t_soak, budget_trail=trail,
                  budget_tokens=int(budget))
    return report
