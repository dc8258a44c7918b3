"""Microbenchmark the decode round on the real chip.

Times a jitted 16-step decode round (the engine's actual dispatch unit)
and ablations of it — a multi-step fused program amortizes the
per-dispatch host cost, so per-step numbers are the device's.
Run on TPU: python tools/profile_decode.py

``--json PATH`` additionally writes the roofline attribution (unembed /
KV window stream / weight-stream floor, ms per step) as a machine-
readable artifact — committed each round as ``PROFILE_rNN.json`` next
to BENCH so perf attribution is driver-verifiable rather than narrated
(VERDICT r5 "Next round" #8).

``--slots 8,16,32,64`` switches to SWEEP mode: the same attribution is
measured at every slot rung (shared params, per-rung pool) and the
artifact carries one entry per rung plus each rung's achieved-HBM-
bandwidth fraction — the 8→64 utilization decay of BENCH_SWEEP_r05 as
one reproducible command instead of N hand-rolled runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.utils.hbm import peak_bw as _peak_bw


def profile_rung(params, cfg, *, slots: int, window: int, live_pages: int,
                 steps: int, page: int, dtype, kv_quant: bool,
                 param_bytes: int, use_kernel: bool,
                 verify_tokens: int = 8, mesh=None) -> dict:
    """Measure one slot-count rung: the full decode round and its
    ablations (no-unembed, window=1), per step, plus the speculative
    VERIFY step (one ``verify_tokens``-position multi-token forward at
    this decode occupancy — the dispatch unit of engine/spec_decode.py,
    priced against the round budget via StepCostModel's
    ``verify_ms_per_token``). Returns the per-rung attribution dict the
    sweep artifact collects."""
    from generativeaiexamples_tpu.models import llama

    B, W, K = slots, window, steps
    n_pages = B * W + 1
    cache = llama.init_paged_kv_cache(cfg, n_pages, page, dtype,
                                      quantized=kv_quant)
    if mesh is not None:
        # Honest tp rungs: the pool lives sharded exactly as the
        # engine's device state does (KV heads over tp when they
        # divide), so the measured step includes the same collectives.
        from jax.sharding import NamedSharding
        from generativeaiexamples_tpu.parallel.sharding import (
            paged_kv_cache_spec)
        cache = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            cache, paged_kv_cache_spec(cfg, mesh, quantized=kv_quant))
    table = jnp.asarray(
        np.arange(1, 1 + B * W, dtype=np.int32).reshape(B, W))
    pos0 = jnp.full((B,), live_pages * page - K - 2, jnp.int32)
    tokens0 = jnp.ones((B,), jnp.int32)

    def make_round(ablate=None):
        def round_fn(params, cache, tok, pos):
            def body(carry, _):
                cache, tok, pos = carry
                wp = jnp.take_along_axis(table, (pos // page)[:, None],
                                         axis=1)[:, 0]
                if ablate == "window1":
                    tbl, p_eff = table[:, :1], jnp.minimum(pos, page - 1)
                else:
                    tbl, p_eff = table, pos
                logits, cache = llama.apply_decode_paged(
                    params, cfg, tok[:, None], p_eff[:, None], cache, tbl,
                    p_eff + 1, wp, p_eff % page, use_kernel=use_kernel,
                    mesh=mesh)
                if ablate == "no_unembed":
                    tok = (logits[:, 0, :8].sum(-1) * 0).astype(
                        jnp.int32) + tok
                else:
                    tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
                return (cache, tok, pos + 1), tok
            (cache, tok, pos), toks = jax.lax.scan(
                body, (cache, tok, pos), None, length=K)
            return cache, tok, pos, toks
        return jax.jit(round_fn, donate_argnums=(1,))

    state = {"cache": cache}

    def run(label, f, extra_bytes=0):
        c, tok, pos = state["cache"], tokens0, pos0
        for _ in range(2):
            c, tok, pos, toks = f(params, c, tok, pos0)
        jax.block_until_ready(toks)
        n = 6
        t0 = time.perf_counter()
        for _ in range(n):
            c, tok, pos, toks = f(params, c, tok, pos0)
        jax.block_until_ready((c, toks))
        ms = (time.perf_counter() - t0) / n / K * 1e3
        state["cache"] = c
        bw = (param_bytes + extra_bytes) / ms * 1e3 / 1e9
        print(f"[{B:>3} slots] {label}: {ms:.2f} ms/step "
              f"({bw:.0f} GB/s apparent, {B/ms*1e3:.0f} tok/s)")
        return ms

    # bytes per cached token: int8 rows + bf16 scales under PROF_KV_QUANT
    row_bytes = (cfg.head_dim + 2) if kv_quant else cfg.head_dim * 2
    kv_live = (live_pages * page * cfg.num_layers * cfg.num_kv_heads
               * row_bytes * 2 * B)
    full = run("full round   ", make_round(), kv_live)
    nou = run("no unembed   ", make_round("no_unembed"), kv_live)
    w1 = run("window=1     ", make_round("window1"),
             kv_live // max(live_pages, 1))
    achieved = (param_bytes + kv_live) / full * 1e3  # bytes/s
    dev = jax.local_devices()[0]
    # No HBM roofline on a CPU: the share reads 0.0 there.
    bw_fraction = (0.0 if dev.platform == "cpu"
                   else achieved / _peak_bw(dev))

    # Speculative verify step: S = verify_tokens positions per slot in
    # ONE forward (llama.apply_verify_paged — the jnp gather path the
    # engine's verify rounds take on every backend). Measured at the
    # same occupancy as the decode round above, so the scheduler's
    # budget pricing compares like with like; per-token = the call
    # divided by its slots x S scored positions (the unit
    # StepCostModel.verify_cost_tokens ratios against
    # prefill_ms_per_token).
    S = verify_tokens
    base_pos = max(0, live_pages * page - S - 2)

    def verify_fn(params, cache, tok, pos):
        positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        tokens = jnp.broadcast_to(tok[:, None], (B, S))
        wp = jnp.take_along_axis(table, positions // page, axis=1)
        out, cache = llama.apply_verify_paged(
            params, cfg, tokens, positions, cache, table, pos + S,
            wp, positions % page)
        nxt = jnp.argmax(out[:, -1], -1).astype(jnp.int32)
        return cache, nxt

    vfn = jax.jit(verify_fn, donate_argnums=(1,))
    c, tok, posv = state["cache"], tokens0, jnp.full((B,), base_pos,
                                                     jnp.int32)
    for _ in range(2):
        c, tok = vfn(params, c, tok, posv)
    jax.block_until_ready(tok)
    n = 6
    t0 = time.perf_counter()
    for _ in range(n):
        c, tok = vfn(params, c, tok, posv)
    jax.block_until_ready(tok)
    verify_ms = (time.perf_counter() - t0) / n * 1e3
    state["cache"] = c
    print(f"[{B:>3} slots] verify x{S}   : {verify_ms:.2f} ms/step "
          f"({verify_ms / (B * S):.4f} ms/token)")

    del state["cache"]  # free this rung's pool before the next builds
    return {
        "slots": B,
        "window_pages": W,
        "live_pages": live_pages,
        "kv_live_bytes": kv_live,
        "full_ms_per_step": round(full, 3),
        "no_unembed_ms_per_step": round(nou, 3),
        "window1_ms_per_step": round(w1, 3),
        "unembed_ms_per_step": round(full - nou, 3),
        "window_stream_ms_per_step": round(full - w1, 3),
        "tokens_per_sec": round(B / full * 1e3, 1),
        # Roofline: bytes the step MUST move (weights once + live KV
        # window) over measured step time, as a fraction of the chip's
        # peak — the ladder whose 8→64 decay this round exists to close.
        "achieved_bw_gbps": round(achieved / 1e9, 1),
        "achieved_bw_fraction": round(bw_fraction, 3),
        # Speculative verify cost at this occupancy: the S-position
        # dispatch and its per-scored-token cost (StepCostModel input —
        # prices verify rounds against the PR-6 token budget).
        "verify_ms_per_step": round(verify_ms, 3),
        "verify_ms_per_token": round(verify_ms / (B * S), 4),
    }


def parse_mesh_arg(spec: str) -> dict:
    """``tp=2`` / ``tp=2,sp=2`` -> {"tp": 2, "sp": 2}; the shared
    ``parallel.mesh.parse_mesh_spec`` grammar, surfaced as the CLI exit
    (a typo'd axis would silently profile single-chip)."""
    from generativeaiexamples_tpu.parallel.mesh import parse_mesh_spec
    try:
        return parse_mesh_spec(spec)
    except ValueError as exc:
        raise SystemExit(f"--mesh {exc}")


def main(json_path: str = "", slots_arg: str = "", mesh_arg: str = ""):
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.models.configs import get_model_config
    from generativeaiexamples_tpu.ops.quant import quantize_params
    from generativeaiexamples_tpu.utils.compile_cache import (
        enable_compile_cache)

    enable_compile_cache()
    model = os.environ.get("PROF_MODEL", "llama-2-7b-chat")
    B = int(os.environ.get("PROF_SLOTS", "8"))
    W = int(os.environ.get("PROF_WINDOW", "8"))
    K = int(os.environ.get("PROF_STEPS", "16"))
    live_pages = int(os.environ.get("PROF_LIVE_PAGES", str(W)))
    page = 128
    cfg = get_model_config(model)
    dt = jnp.bfloat16
    quant = os.environ.get("PROF_QUANT", "int8")
    slots_arg = slots_arg or os.environ.get("PROF_SLOTS_SWEEP", "")
    sweep = [int(s) for s in slots_arg.split(",") if s] if slots_arg \
        else []

    def make(k):
        p = llama.init_params(cfg, k, dtype=dt)
        return quantize_params(p, quant) if quant != "none" else p
    params = jax.jit(make)(jax.random.key(0))
    jax.block_until_ready(params)

    # --mesh tp=N (or PROF_MESH): measure the SHARDED decode round —
    # params placed per llama_param_specs, the pool per
    # paged_kv_cache_spec, kernel shard_mapped when the heads divide —
    # so the artifact carries per-TOPOLOGY costs. The topology label
    # (engine/scheduler.py topology_key) keys the row; the engine's
    # StepCostModel.load(topology=...) picks the matching one, which is
    # what makes a tp engine's first-round budget honest.
    mesh = None
    mesh_arg = mesh_arg or os.environ.get("PROF_MESH", "")
    topo = "tp=1"
    if mesh_arg:
        from generativeaiexamples_tpu.engine.scheduler import topology_key
        from generativeaiexamples_tpu.parallel import (
            MeshPlan, llama_param_specs, make_mesh, shard_params)
        axes = parse_mesh_arg(mesh_arg)
        n_dev = 1
        for v in axes.values():
            n_dev *= v
        mesh = make_mesh(MeshPlan(**axes), jax.devices()[:n_dev])
        params = shard_params(params, mesh, llama_param_specs(cfg, mesh))
        topo = topology_key(dict(mesh.shape))
        print(f"mesh: {dict(mesh.shape)} -> topology {topo!r}")
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"params: {param_bytes/1e9:.2f} GB  "
          f"slots={sweep or B} window={W} live={live_pages} steps={K}")

    kv_quant = os.environ.get("PROF_KV_QUANT", "") == "int8"
    use_kernel = jax.default_backend() == "tpu" \
        and llama.kernel_tp_compatible(cfg, mesh)
    dev0 = jax.local_devices()[0]
    floor = (0.0 if dev0.platform == "cpu"
             else param_bytes / _peak_bw(dev0) * 1e3)
    verify_tokens = int(os.environ.get("PROF_VERIFY_TOKENS", "8"))

    rungs = [profile_rung(
        params, cfg, slots=s, window=W, live_pages=live_pages, steps=K,
        page=page, dtype=dt, kv_quant=kv_quant, param_bytes=param_bytes,
        use_kernel=use_kernel, verify_tokens=verify_tokens, mesh=mesh)
        for s in (sweep or [B])]
    r0 = rungs[0]
    print(f"=> unembed+argmax ~{r0['unembed_ms_per_step']:.2f} ms/step, "
          f"window stream ~{r0['window_stream_ms_per_step']:.2f} ms/step, "
          f"matmul floor {floor:.2f} ms/step @peak")
    if sweep:
        ladder = " -> ".join(f"{r['slots']}:{r['achieved_bw_fraction']}"
                             for r in rungs)
        print(f"=> bandwidth ladder (fraction of peak): {ladder}")

    # Prefill token cost: one bucket-shaped forward (the engine's
    # admission program minus insert), timed per token. This is the
    # OTHER half of the scheduler's step-cost model
    # (engine/scheduler.py StepCostModel): the per-round chunk budget is
    # decode_round_ms / prefill_ms_per_token, so regenerating this
    # artifact per deployment re-derives the budget for that hardware.
    S = min(int(os.environ.get("PROF_PREFILL_BUCKET", "512")),
            cfg.max_position_embeddings)

    def prefill_fn(p, tokens, positions):
        c = llama.init_kv_cache(cfg, 1, S, dt)
        logits, _ = llama.apply(p, cfg, tokens, positions, c)
        return logits[:, -1]

    pf = jax.jit(prefill_fn)
    tok1 = jnp.ones((1, S), jnp.int32)
    pos1 = jnp.arange(S, dtype=jnp.int32)[None, :]
    for _ in range(2):
        jax.block_until_ready(pf(params, tok1, pos1))
    n = 4
    t0 = time.perf_counter()
    for _ in range(n):
        out = pf(params, tok1, pos1)
    jax.block_until_ready(out)
    prefill_ms_tok = (time.perf_counter() - t0) / n / S * 1e3
    print(f"prefill@{S}: {prefill_ms_tok:.4f} ms/token "
          f"({S/( (time.perf_counter()-t0)/n ):.0f} tok/s-equivalent)")

    if json_path:
        # Roofline attribution as a committed round artifact: the same
        # shape every round, so the driver diffs attribution (did the
        # window stream shrink? did unembed grow?) not just the headline.
        shared = {
            "tool": "profile_decode",
            "model": model,
            "device": str(jax.local_devices()[0].device_kind),
            "platform": jax.default_backend(),
            "quant": quant,
            "kv_quant": "int8" if kv_quant else "",
            "steps_per_round": K, "page_size": page,
            "param_gb": round(param_bytes / 1e9, 3),
            "matmul_floor_ms_per_step": round(floor, 3),
            # Step-cost model inputs for the token-budget scheduler
            # (engine/scheduler.py): prefill cost per prompt token at
            # the measured bucket, and the verify-round geometry the
            # per-rung verify_ms_per_token was measured at.
            "prefill_bucket_tokens": S,
            "prefill_ms_per_token": round(prefill_ms_tok, 4),
            "verify_positions": verify_tokens,
            # Topology row key (engine/scheduler.py topology_key):
            # which mesh shape these costs were measured at. "tp=1" =
            # single chip; StepCostModel.load(topology=...) matches an
            # engine's mesh against this label (or a "topologies" dict
            # of per-mesh rows merged over the shared fields).
            "topology": topo,
            "mesh_devices": mesh.devices.size if mesh is not None else 1,
        }
        if sweep:
            # Sweep shape: one attribution entry per slot rung. The
            # single-rung keys the scheduler's StepCostModel reads
            # (full_ms_per_step, verify_ms_per_token, slots,
            # prefill_ms_per_token) are mirrored at top level from the
            # FIRST rung so an _rNN sweep artifact still feeds the cost
            # model unchanged.
            artifact = dict(
                shared,
                slots_sweep=sweep,
                slots=r0["slots"],
                full_ms_per_step=r0["full_ms_per_step"],
                verify_ms_per_token=r0["verify_ms_per_token"],
                rungs=rungs,
            )
        else:
            artifact = dict(shared, **r0)
        with open(json_path, "w") as f:
            json.dump(artifact, f, indent=2)
            f.write("\n")
        print(f"wrote {json_path}")
        return artifact


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write the roofline attribution as a JSON "
                         "artifact (PROFILE_rNN.json round record)")
    ap.add_argument("--slots", default="", metavar="A,B,C",
                    help="sweep mode: comma-separated slot rungs "
                         "(e.g. 8,16,32,64) measured with shared params; "
                         "the artifact carries per-rung attribution + "
                         "achieved-bandwidth fraction")
    ap.add_argument("--mesh", default="", metavar="tp=N",
                    help="measure the TP-SHARDED decode round on a mesh "
                         "(axis=N pairs, e.g. tp=2 or tp=2,sp=2): params "
                         "+ paged pool placed per the serving shardings, "
                         "artifact stamped with the topology_key row the "
                         "engine's cost model matches against")
    args = ap.parse_args()
    main(json_path=args.json, slots_arg=args.slots, mesh_arg=args.mesh)
