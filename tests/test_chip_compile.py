"""Compile the serving hot path for a TPU v5e that is DESCRIBED, not
attached (on-chip-measurement guide §2.3): the chip's own compiler runs
here on the CPU sandbox and raises what it would raise on the machine —
misaligned slices, too much VMEM, programs that do not fit 16 GB,
kernels that cannot be partitioned. Nothing executes, so these tests say
nothing about results or times; they guard every later PR's kernels and
step programs at llama-2-7b width (depth cut to 2 layers so each compile
stays seconds) at no chip time.

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
tests steer it (``tpu_backend`` fixture) — otherwise the model would
embed the INTERPRETED kernel, or skip it.
"""

import dataclasses
import os
import math
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import get_model_config
from generativeaiexamples_tpu.ops.quant import quantize_params

PAGE = 128
CFG = dataclasses.replace(get_model_config("llama-2-7b-chat"), num_layers=2)
HBM_BYTES = 16 << 30    # one v5e chip

# the chip's compiler is the subject: every pass, as on the machine
pytestmark = pytest.mark.usefixtures("full_optimisation")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"TPU topology cannot be described here: {exc}")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A described-topology compile can be written to the persistent
    cache but never read back without a chip (the next run warns and
    recompiles) — keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture
def tpu_backend(monkeypatch):
    """Steer the kernel gates (models/llama.py, ops/quant.py) onto their
    TPU branch: compiled Pallas, not interpret mode."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def on(tree, sharding):
    """Shapes of ``tree`` placed on ``sharding``."""
    return jax.tree.map(lambda x: sds(x.shape, x.dtype, sharding), tree)


def param_shapes(cfg, quant="int8"):
    def make(key):
        return quantize_params(
            llama.init_params(cfg, key, dtype=jnp.bfloat16), quant)
    return jax.eval_shape(make, jax.random.key(0))


def assert_fits(compiled, budget=HBM_BYTES):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < budget, (total, m)
    return total


# ------------------------------------------------------------- kernels


@pytest.mark.parametrize("batch,heads,kv_heads,kv_int8", [
    (8, 32, 32, False), (64, 32, 32, False),     # llama-2-7b, bf16 KV
    (8, 32, 32, True), (64, 32, 32, True),       # llama-2-7b, int8 KV
    (8, 40, 40, False),                          # llama-2-13b
    (8, 64, 8, False),                           # GQA, 8 queries per KV head
    (8, 8, 8, False),                            # the 7B shard at tp=4
    (1, 8, 8, False),                            # one slot (smoke tp4 step)
], ids=["7b-b8", "7b-b64", "7b-int8kv-b8", "7b-int8kv-b64", "13b", "gqa8",
        "tp4-shard", "tp4-shard-b1"])
def test_paged_attention_kernel_compiles(topo, batch, heads, kv_heads,
                                         kv_int8):
    from generativeaiexamples_tpu.ops.paged_attention import (
        paged_attention_decode)
    dev = SingleDeviceSharding(topo.devices[0])
    H, KV, hd = heads, kv_heads, CFG.head_dim
    L, N, W = 2, batch * 4 + 1, 8
    pool_dt = jnp.int8 if kv_int8 else jnp.bfloat16
    pool = sds((L, N, KV, PAGE, hd), pool_dt, dev)
    scales = sds((L, N, KV, PAGE), jnp.bfloat16, dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731
    cur = sds((batch, KV, hd), jnp.bfloat16, dev)

    def step(q, pk, pv, ks, vs, tbl, lens, ck, cv, wp, off, li):
        extra = dict(pool_ks=ks, pool_vs=vs) if kv_int8 else {}
        return paged_attention_decode(q, pk, pv, tbl, lens, ck, cv, wp,
                                      off, li, **extra)

    compiled = jax.jit(step).lower(
        sds((batch, H, hd), jnp.bfloat16, dev), pool, pool, scales, scales,
        i32(batch, W), i32(batch), cur, cur, i32(batch), i32(batch),
        i32(1)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's stable name, for whoever reads a profile
    assert ("paged_attn_decode_int8kv" if kv_int8
            else "paged_attn_decode") in text


@pytest.mark.parametrize("batch,W,masked", [
    (32, 8, False), (8, 8, False), (16, 130, True)],
    ids=["b32", "b8", "b16-keep-mask"])
def test_latent_attention_kernel_compiles(topo, batch, W, masked):
    """The latent decode kernel at the published widths of the
    configurations that have a latent cache: 64 heads over a 512 + 64
    row, pages of 128, the rotary pool transposed; and with the keep mask
    of learned sparse attention as an operand over the 130-page window of
    that cell (a block's bits read at a dynamic sublane, turned to a
    column through a float32 transpose)."""
    from generativeaiexamples_tpu.ops.latent_attention import (
        kernel_supported, latent_attention_decode)
    dev = SingleDeviceSharding(topo.devices[0])
    H, R, rope = 64, 512, 64
    assert kernel_supported(PAGE, R, rope)
    L, N = 2, batch * 4 + 1
    bf = lambda *shape: sds(shape, jnp.bfloat16, dev)   # noqa: E731
    i32 = lambda *shape: sds(shape, jnp.int32, dev)     # noqa: E731
    mask = (sds((batch, W * PAGE), jnp.bool_, dev),
            sds((batch,), jnp.bool_, dev)) if masked else ()

    def step(qc, qr, pc, pr, tbl, lens, cc, cr, wp, off, li, *mask):
        more = dict(zip(("keep", "cur_keep"), mask))
        return latent_attention_decode(qc, qr, pc, pr, tbl, lens, cc, cr,
                                       wp, off, li, scale=0.13, **more)

    compiled = jax.jit(step, donate_argnums=(2, 3)).lower(
        bf(batch, H, R), bf(batch, H, rope), bf(L, N, 1, PAGE, R),
        bf(L, N, 1, rope, PAGE), i32(batch, W), i32(batch), bf(batch, R),
        bf(batch, rope), i32(batch), i32(batch), i32(1), *mask).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "latent_attn_decode" in text


@pytest.mark.parametrize("group", [0, 128], ids=["perchannel", "g128"])
@pytest.mark.parametrize("K,N,M", [
    (4096, 11008, 8),           # gate/up projection, decode rows
    (11008, 4096, 8),           # down projection
    (4096, 4096, 8),            # attention projections
    (4096, 4096, 512),          # ... at prefill rows
    (4096, 11008, 512),
    (4096, 32000, 8),           # lm_head
    (5120, 13824, 8),           # llama-2-13b / codellama-13b widths
    (13824, 5120, 8),
    (5120, 5120, 8),
], ids=["gate", "down", "attn", "attn-prefill", "gate-prefill", "head",
        "13b-gate", "13b-down", "13b-attn"])
def test_int4_matmul_kernel_compiles(topo, K, N, group, M):
    from generativeaiexamples_tpu.ops.int4_matmul import (int4_matmul,
                                                          supported)
    assert supported(K, N, group_size=group)
    dev = SingleDeviceSharding(topo.devices[0])
    scale = (sds((K // group, N), jnp.bfloat16, dev) if group
             else sds((N,), jnp.bfloat16, dev))
    compiled = int4_matmul.lower(
        sds((M, K), jnp.bfloat16, dev), sds((K // 2, N), jnp.int8, dev),
        scale).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and '"int4_matmul"' in text


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_fused_sampler_tail_256k_vocab_compiles(topo, greedy):
    """The vocab-tiled unembed+sampling tail at nemotron-8b-chat's 256k
    vocabulary (ROADMAP R0's first dense cell): (rows, V) logits must
    never materialize — 8 rows x 256k x f32 is small, but the same tail
    runs ba*S verify rows, and the tile stream is the design."""
    from generativeaiexamples_tpu.ops.fused_sampler import (
        fused_unembed_sample)
    from generativeaiexamples_tpu.ops.sampling import mask_words
    cfg = dataclasses.replace(get_model_config("nemotron-8b-chat"),
                              num_layers=1)
    dev = SingleDeviceSharding(topo.devices[0])
    params = on(param_shapes(cfg), dev)
    B, V = 8, cfg.vocab_size
    assert V == 256000
    words = sds((B, mask_words(V)), jnp.uint32, dev)
    f32 = sds((B,), jnp.float32, dev)

    def tail(params, hn, key, temp, top_k, top_p, rep_pen, seen, banned):
        return fused_unembed_sample(
            lambda t0, tile: llama.lm_head_tile(params, cfg, hn, t0, tile),
            V, key=key, temp=temp, top_k=top_k, top_p=top_p,
            rep_pen=rep_pen, seen_words=seen, banned_words=banned,
            greedy=greedy)

    compiled = jax.jit(tail).lower(
        params, sds((B, cfg.hidden_size), jnp.bfloat16, dev),
        jax.eval_shape(lambda: jax.random.key(0)), f32,
        sds((B,), jnp.int32, dev), f32, f32, words, words).compile()
    # temp excludes the weights: a materialized (B, V) f32 alone is 8 MB,
    # a few live copies of it (sort, mask, noise) would show here.
    assert compiled.memory_analysis().temp_size_in_bytes < (32 << 20)


@pytest.mark.parametrize("D,V,rows,storage", [
    (2048, 200192, 16, "int8"),     # trinity-mini: 98 blocks of 2048
    (7168, 163840, 32, "int8"),     # kimi-k2-instruct: 320 blocks of 512
    (4096, 256000, 1, "int8"),      # a first token's one row
    (2560, 151936, 5, "tied"),      # the (V, D) embedding, NT blocks
    (4096, 32000, 16, "raw"),
])
def test_greedy_head_argmax_kernel_compiles(topo, D, V, rows, storage):
    """The greedy tail's kernel (ops/head_argmax.py) at the widths the
    cells serve, every operand it can take: a few MB a block in VMEM, no
    (rows, V) array in HBM."""
    from generativeaiexamples_tpu.ops.head_argmax import greedy_head_argmax
    from generativeaiexamples_tpu.ops.sampling import mask_words
    dev = SingleDeviceSharding(topo.devices[0])
    tree = {"int8": {"lm_head": {"q": sds((D, V), jnp.int8, dev),
                                 "scale": sds((V,), jnp.float32, dev)}},
            "tied": {"embed": sds((V, D), jnp.bfloat16, dev)},
            "raw": {"lm_head": sds((D, V), jnp.bfloat16, dev)}}[storage]
    words = sds((rows, mask_words(V)), jnp.uint32, dev)

    def tail(hn, tree, rep, seen, banned, ban_tok, ban_hit):
        return greedy_head_argmax(hn, tree, V, rep_pen=rep, seen_words=seen,
                                  banned_words=banned, ban_tok=ban_tok,
                                  ban_hit=ban_hit)

    compiled = jax.jit(tail).lower(
        sds((rows, D), jnp.bfloat16, dev), tree,
        sds((rows,), jnp.float32, dev), words, words,
        sds((rows, 7), jnp.int32, dev),
        sds((rows, 7), jnp.bool_, dev)).compile()
    assert '"greedy_head_argmax"' in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (4 << 20)


@pytest.mark.parametrize("D,V,rows,storage", [
    (4096, 256000, 4, "int8"),      # nemotron-8b-chat: chat-steady's rung
    (4096, 256000, 16, "int8"),     # ... and its widest
    (2560, 151936, 5, "tied"),      # the (V, D) embedding, NT blocks
])
def test_sampled_head_kernel_compiles(topo, D, V, rows, storage):
    """The sampled tail over the head kernel (ops/fused_sampler.py
    ``head_kernel_sample``) at served widths: ONE Mosaic program streams
    the head, and what it leaves in HBM is the noise it read and the
    ``scaled`` array it wrote, a few (16, V) float32 arrays — no second
    pass over the head, nothing the size of a head tile."""
    from generativeaiexamples_tpu.ops.fused_sampler import head_kernel_sample
    from generativeaiexamples_tpu.ops.sampling import mask_words
    dev = SingleDeviceSharding(topo.devices[0])
    tree = {"int8": {"lm_head": {"q": sds((D, V), jnp.int8, dev),
                                 "scale": sds((V,), jnp.float32, dev)}},
            "tied": {"embed": sds((V, D), jnp.bfloat16, dev)}}[storage]
    words = sds((rows, mask_words(V)), jnp.uint32, dev)
    f32 = sds((rows,), jnp.float32, dev)

    def tail(hn, tree, key, temp, top_k, top_p, rep, seen, banned, ban_tok,
             ban_hit):
        return head_kernel_sample(
            hn, tree, V, key=key, temp=temp, top_k=top_k, top_p=top_p,
            rep_pen=rep, seen_words=seen, banned_words=banned,
            ban_tok=ban_tok, ban_hit=ban_hit, stats=True)

    compiled = jax.jit(tail).lower(
        sds((rows, D), jnp.bfloat16, dev), tree,
        jax.eval_shape(lambda: jax.random.key(0)), f32,
        sds((rows,), jnp.int32, dev), f32, f32, words, words,
        sds((rows, 7), jnp.int32, dev),
        sds((rows, 7), jnp.bool_, dev)).compile()
    text = compiled.as_text()
    assert text.count('"sampled_head_stream"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 16 * V * 4


# ------------------------------------------------- engine step programs


@pytest.fixture(scope="module")
def engine(topo):
    """A real Engine at llama-2-7b width / 2 layers / int8 weights whose
    jitted programs are LOWERED for the described chip. It lives on the
    CPU (zero weights; nothing runs), with ``jax.default_backend``
    steered so the kernel path and row-major layout pins are armed the
    way they are on the chip."""
    from generativeaiexamples_tpu.engine import Engine, EngineConfig
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          param_shapes(CFG))
    eng = Engine(params, CFG, ByteTokenizer(), EngineConfig(
        max_slots=8, max_input_length=2048, max_output_length=128,
        prefill_buckets=(512, 1024), max_prefill_bucket=1024,
        kv_pool_tokens=8 * 1024, steps_per_round=8))
    assert eng._use_kernel and eng._fused_tail and not eng.downgrades
    assert eng.programs.tail.kernel     # a per-column int8 head: the kernel's
    yield eng
    mp.undo()


def engine_args(eng, topo):
    """(params, state) shapes placed on the described chip, the pool in
    the engine's pinned row-major layout."""
    dev = SingleDeviceSharding(topo.devices[0])
    state = on({k: v for k, v in eng._state.items() if k != "cache"}, dev)
    state["cache"] = {
        k: sds(v.shape, v.dtype, Format(
            Layout(major_to_minor=tuple(range(v.ndim))), dev))
        for k, v in eng._state["cache"].items()}
    return on(eng.params, dev), state, dev


def test_prefill_bucket_program_compiles(engine, topo, tpu_backend):
    """Fused prefill + first-token sample + page scatter, S=1024."""
    params, state, dev = engine_args(engine, topo)
    S = 1024
    i32 = sds((), jnp.int32, dev)
    f32 = sds((), jnp.float32, dev)
    from generativeaiexamples_tpu.ops.sampling import mask_words
    compiled = engine.programs.prefill_insert.lower(
        state, params, sds((1, S), jnp.int32, dev), i32, i32,
        sds((engine._pmax,), jnp.int32, dev), f32, i32, f32, f32,
        sds((mask_words(CFG.vocab_size),), jnp.uint32, dev),
        sds((engine.MAX_BAD_SEQS, engine.MAX_BAD_LEN), jnp.int32, dev),
        sds((engine.MAX_BAD_SEQS,), jnp.int32, dev),
        jax.eval_shape(lambda: jax.random.key(0)), i32,
        sds((), jnp.bool_, dev), True).compile()
    assert_fits(compiled)


def test_decode_round_program_compiles(engine, topo, tpu_backend):
    """8 fused decode steps: Pallas paged attention with the pool aliased
    through the scan carry, then the fused vocab-tiled sampling tail."""
    params, state, dev = engine_args(engine, topo)
    B = engine.cfg.max_slots
    fn = engine._round_fn(engine._pmax, 8, True, B)
    compiled = fn.lower(params, state,
                        jax.eval_shape(lambda: jax.random.key(0)),
                        sds((B,), jnp.int32, dev)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the greedy tail is the head kernel, once a step
    assert '"greedy_head_argmax"' in text
    assert_fits(compiled)
    # the pool is donated and aliased in place: no second pool in temps
    pool_bytes = sum(v.nbytes for v in engine._state["cache"].values())
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


def test_sampled_decode_round_program_compiles(engine, topo, tpu_backend):
    """The SAMPLED round of the same engine: its tail is the sampled
    head kernel, once a step, and no scan of head slices."""
    params, state, dev = engine_args(engine, topo)
    ba = 4
    fn = engine._round_fn(engine._pmax, 8, False, ba)
    compiled = fn.lower(params, state,
                        jax.eval_shape(lambda: jax.random.key(0)),
                        sds((ba,), jnp.int32, dev)).compile()
    text = compiled.as_text()
    assert '"sampled_head_stream"' in text
    assert '"greedy_head_argmax"' not in text
    assert_fits(compiled)


def test_chunked_prefill_program_compiles(engine, topo, tpu_backend):
    """One non-final chunk of the paged (chunked) prefill admission."""
    params, state, dev = engine_args(engine, topo)
    window = engine._windows[-1]
    fn = engine._chunk_extend_fn(window, "accum")
    i32 = sds((), jnp.int32, dev)
    compiled = fn.lower(state, params, sds((1, 512), jnp.int32, dev),
                        i32, i32, i32,
                        sds((1, window), jnp.int32, dev)).compile()
    assert_fits(compiled)


def test_decode_step_kernel_path_compiles(topo, tpu_backend):
    """The bare model step the engine's round scans: 2 layers at 7B
    width, int8 weights, ``use_kernel=True`` -> one Pallas call."""
    dev = SingleDeviceSharding(topo.devices[0])
    B, W, N = 8, 8, 65
    pool = sds((2, N, CFG.num_kv_heads, PAGE, CFG.head_dim),
               jnp.bfloat16, dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731

    def step(params, tok, pos, cache, tbl, valid, wp, off):
        return llama.apply_decode_paged(params, CFG, tok, pos, cache, tbl,
                                        valid, wp, off, use_kernel=True)

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        on(param_shapes(CFG), dev), i32(B, 1), i32(B, 1),
        {"k": pool, "v": pool}, i32(B, W), i32(B), i32(B),
        i32(B)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert_fits(compiled)


def test_tp4_decode_step_compiles(topo, tpu_backend, monkeypatch):
    """The same step on the described 2x2 mesh at tp=4: params and pool
    sharded as the engine shards them, the kernel under ``shard_map``,
    then the tp-sharded fused sampling tail. Each device must hold about
    a quarter of the sharded bytes."""
    from generativeaiexamples_tpu.ops.fused_sampler import (
        fused_unembed_sample_tp)
    from generativeaiexamples_tpu.ops.sampling import mask_words
    from generativeaiexamples_tpu.parallel.mesh import MeshPlan, make_mesh
    from generativeaiexamples_tpu.parallel.sharding import (
        llama_param_specs, paged_kv_cache_spec, shard_params)
    mesh = make_mesh(MeshPlan(tp=4), topo.devices)
    shapes = param_shapes(CFG)
    # shard_params' own spec derivation (QTensor leaves included), with
    # placement swapped for shape annotation
    with monkeypatch.context() as mp:
        mp.setattr(jax, "device_put",
                   lambda x, s: sds(x.shape, x.dtype, s))
        params = shard_params(shapes, mesh, llama_param_specs(CFG, mesh))
    rep = NamedSharding(mesh, P())
    B, W, N, V = 8, 8, 65, CFG.vocab_size
    kv_spec = paged_kv_cache_spec(CFG, mesh)["k"]
    pool = sds((2, N, CFG.num_kv_heads, PAGE, CFG.head_dim), jnp.bfloat16,
               NamedSharding(mesh, kv_spec))
    i32 = lambda *shape: sds(shape, jnp.int32, rep)  # noqa: E731
    f32 = sds((B,), jnp.float32, rep)
    words = sds((B, mask_words(V)), jnp.uint32, rep)
    head_specs = llama.lm_head_specs(shapes, mesh)

    def step(params, tok, pos, cache, tbl, valid, wp, off, key, temp,
             top_k, top_p, rep_pen, seen, banned):
        h, cache = llama.apply_decode_paged(
            params, CFG, tok, pos, cache, tbl, valid, wp, off,
            use_kernel=True, mesh=mesh, return_hidden=True)
        hn = llama.unembed_norm(params, CFG, h[:, 0])
        tok = fused_unembed_sample_tp(
            mesh, "tp", llama.lm_head_subtree(params), head_specs,
            lambda head, rows, t0, tile: llama.lm_head_tile(
                head, CFG, rows, t0, tile),
            V, hn=hn, key=key, temp=temp, top_k=top_k, top_p=top_p,
            rep_pen=rep_pen, seen_words=seen, banned_words=banned,
            greedy=True)    # the sampled stream compiles ~10 s slower
        return tok, cache

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, i32(B, 1), i32(B, 1), {"k": pool, "v": pool}, i32(B, W),
        i32(B), i32(B), i32(B), jax.eval_shape(lambda: jax.random.key(0)),
        f32, i32(B), f32, f32, words, words).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text or "all-gather" in text
    # memory_analysis is per device: arguments ~ a quarter of the
    # sharded parameter + pool bytes (embed and norms replicate)
    whole = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves((shapes, pool, pool)))
    per_dev = compiled.memory_analysis().argument_size_in_bytes
    assert per_dev < 0.45 * whole, (per_dev, whole)


# --------------------------------- how the layer scan reads its weights


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk_512"])
@pytest.mark.parametrize("model", ["nemotron-8b-chat",
                                   "mixtral-8x7b-instruct"])
def test_layer_weights_are_read_in_place(topo, tpu_backend, model, program):
    """Every stacked int8 layer matrix is consumed by the fusion that
    holds its matmul: the per-layer slice happens INSIDE that fusion and
    the stack is read in the layout it is stored in. What this guards
    against (ISSUE 26): the TPU compiler folding the head reshape that
    follows the q/k/v projections into the dot, which turns each into a
    convolution over the head axis whose kernel wants the weight K-minor
    — a transposing ``copy`` of the whole stack a program, and every
    layer's slice copied out before its matmul instead of streaming
    through it (2.9 of 17.5 ms a decode step on nemotron-8b-chat)."""
    from tools.dump_hlo import weight_report
    # the benchmark's widths (benchmarks/configs/*.json), 2 layers deep
    cfg = dataclasses.replace(get_model_config(model), num_layers=2)
    dev = SingleDeviceSharding(topo.devices[0])
    params = on(param_shapes(cfg), dev)
    N, W = 65, 8
    pool = sds((2, N, cfg.num_kv_heads, PAGE, cfg.head_dim),
               jnp.bfloat16, dev)
    cache = {"k": pool, "v": pool}
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731
    if program == "decode_step":
        B = 16

        def step(params, tok, pos, cache, tbl, valid, wp, off):
            return llama.apply_decode_paged(
                params, cfg, tok, pos, cache, tbl, valid, wp, off,
                use_kernel=True)

        lowered = jax.jit(step, donate_argnums=(3,)).lower(
            params, i32(B, 1), i32(B, 1), cache, i32(B, W), i32(B),
            i32(B), i32(B))
    else:
        def chunk(params, tok, pos, cache, tbl, valid, start):
            return llama.apply_prefill_paged(params, cfg, tok, pos, cache,
                                             tbl, valid, start)

        lowered = jax.jit(chunk, donate_argnums=(3,)).lower(
            params, i32(1, 512), i32(1, 512), cache, i32(1, W), i32(1),
            i32())
    report = weight_report(lowered.compile().as_text())
    expected = {"wq", "wk", "wv", "wo"}
    if not cfg.num_experts:     # a MoE block's experts are bf16 einsums
        expected |= {"w_up", "w_down"}      # squared-ReLU FFN: no gate
    assert set(report["weights"]) == expected, report
    assert not report["stack_copies"], report["stack_copies"]
    assert not report["slice_results"], report["slice_results"]
    assert set(report["matmul_fusions"]) == expected, report


_T3 = ("(s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)}, "
       "/*index=2*/s8[2,64,64]{2,1,0:T(8,128)(4,1)})")

# A two-layer scan over one stacked weight, as optimised HLO prints it.
# %(fused)s are the fusions' computations, %(layer)s the loop body's
# use of the stack %%w, %(stack)s what the entry hands the loop.
_HLO_LOOP = """HloModule jit_step, is_scheduled=true

%(fused)s

%%cond (arg: (s32[], bf16[8,64], s8[2,64,64])) -> pred[] {
  %%arg = """ + _T3 + """ parameter(0)
  %%i = s32[]{:T(128)} get-tuple-element(%%arg), index=0
  %%n = s32[]{:T(128)} constant(2)
  ROOT %%lt = pred[]{:T(512)} compare(%%i, %%n), direction=LT
}

%%body (arg: (s32[], bf16[8,64], s8[2,64,64])) -> (s32[], bf16[8,64], s8[2,64,64]) {
  %%arg = """ + _T3 + """ parameter(0)
  %%i = s32[]{:T(128)} get-tuple-element(%%arg), index=0
  %%h = bf16[8,64]{1,0:T(8,128)(2,1)} get-tuple-element(%%arg), index=1
  %%w = s8[2,64,64]{2,1,0:T(8,128)(4,1)} get-tuple-element(%%arg), index=2
%(layer)s
  ROOT %%t = """ + _T3 + """ tuple(%%i, %%fusion.7, %%w)
}

ENTRY %%main (h0: bf16[8,64], wq: s8[2,64,64]) -> bf16[8,64] {
  %%h0 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0)
  %%wq = s8[2,64,64]{2,1,0:T(8,128)(4,1)} parameter(1), sharding={replicated}, metadata={op_name="params[\\'layers\\'][\\'wq\\'][\\'q\\']"}
%(stack)s
  %%zero = s32[]{:T(128)} constant(0)
  %%init = """ + _T3 + """ tuple(%%zero, %%h0, %%stack)
  %%loop = """ + _T3 + """ while(%%init), condition=%%cond, body=%%body
  ROOT %%out = bf16[8,64]{1,0:T(8,128)(2,1)} get-tuple-element(%%loop), index=1
}
"""

_HLO_FORMS = {
    # the slice inside the matmul's fusion, the stack read as stored
    "in_place": dict(
        fused="""%fused_dot (p0: s8[2,64,64], p1: s32[], p2: bf16[8,64]) -> bf16[8,64] {
  %p0 = s8[2,64,64]{2,1,0:T(8,128)(4,1)} parameter(0)
  %p1 = s32[]{:T(128)} parameter(1)
  %p2 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(2)
  %ds = s8[1,64,64]{2,1,0:T(8,128)(4,1)} dynamic-slice(%p0, %p1), dynamic_slice_sizes={1,64,64}
  %b = s8[64,64]{1,0:T(8,128)(4,1)} bitcast(%ds)
  ROOT %dot.1 = bf16[8,64]{1,0:T(8,128)(2,1)} convolution(%p2, %b), dim_labels=bf_io->bf
}""",
        layer="  %fusion.7 = bf16[8,64]{1,0:T(8,128)(2,1)} "
              "fusion(%w, %i, %h), kind=kOutput, calls=%fused_dot, "
              'metadata={op_name="jit(step)/while/body/attn_proj/dot"}',
        stack="  %stack = s8[2,64,64]{2,1,0:T(8,128)(4,1)} bitcast(%wq)"),
    # as the parent compiled it: the stack transposed once, each layer's
    # slice copied out of it, the matmul fed from the copy
    "copied": dict(
        fused="""%fused_slice (p0: s8[2,64,64], p1: s32[]) -> s8[1,64,64] {
  %p0 = s8[2,64,64]{1,2,0:T(8,128)(4,1)} parameter(0)
  %p1 = s32[]{:T(128)} parameter(1)
  ROOT %ds = s8[1,64,64]{1,2,0:T(8,128)(4,1)} dynamic-slice(%p0, %p1), dynamic_slice_sizes={1,64,64}
}

%fused_dot_sliced (p0: s8[1,64,64], p2: bf16[8,64]) -> bf16[8,64] {
  %p0 = s8[1,64,64]{1,2,0:T(8,128)(4,1)} parameter(0)
  %p2 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(1)
  %b = s8[64,64]{0,1:T(8,128)(4,1)} bitcast(%p0)
  ROOT %dot.2 = bf16[8,64]{1,0:T(8,128)(2,1)} convolution(%p2, %b), dim_labels=bf_io->bf
}""",
        layer="  %slice_fusion.3 = s8[1,64,64]{1,2,0:T(8,128)(4,1)} "
              "fusion(%w, %i), kind=kLoop, calls=%fused_slice\n"
              "  %fusion.7 = bf16[8,64]{1,0:T(8,128)(2,1)} "
              "fusion(%slice_fusion.3, %h), kind=kOutput, "
              "calls=%fused_dot_sliced",
        stack="  %stack = s8[2,64,64]{1,2,0:T(8,128)(4,1)} copy(%wq)"),
}


@pytest.mark.parametrize("form", ["in_place", "copied"])
def test_weight_report_follows_a_stack_through_the_loop(form):
    """``tools/dump_hlo.weight_report`` on hand-written optimised HLO:
    a weight is named only at the entry parameter, and is found again
    as an element of the ``while`` tuple inside the layer loop."""
    from tools.dump_hlo import weight_report
    report = weight_report(_HLO_LOOP % _HLO_FORMS[form])
    assert report["weights"] == ["wq"]
    if form == "in_place":
        assert report["matmul_fusions"] == {"wq": ["fusion.7"]}
        assert not report["stack_copies"] and not report["slice_results"]
    else:
        assert report["matmul_fusions"] == {}
        assert [c.split(" ")[0] for c in report["stack_copies"]] == [
            "stack"]
        assert [c.split(" ")[0] for c in report["slice_results"]] == [
            "slice_fusion.3"]


# ------------------------------ how a chunk program reads the KV pool


@pytest.mark.parametrize("kv", ["bf16kv", "int8kv"])
@pytest.mark.parametrize("model,layers", [
    ("nemotron-8b-chat", 2), ("mixtral-8x7b-instruct", 2),
    ("smallthinker-21b-a3b-instruct", 4)],      # one period of kinds
    ids=["nemotron", "mixtral", "smallthinker"])
def test_chunk_program_reads_the_pool_in_place(topo, tpu_backend, model,
                                               layers, kv):
    """The 512-token chunk program holds NO instruction whose result is
    a layer's slab of the pool (N, KV, page, hd), or a second pool: the
    layer scan keeps the pool out of its sliced inputs and a prefix
    block's pages are gathered by (layer, page) out of the whole array.
    What this guards against (ISSUE 29): with the pool among the scan's
    ``xs`` every layer of every chunk program copied its whole K and V
    slab (``dynamic-slice_bitcast_fusion.4/.5``: 2 x 142 MB a layer of
    ``long-context-decode``'s 1088 pages, 30 % of a chunk program) to
    attend eight pages of it. The pool is sized so that a bf16 slab is
    256 MiB — no activation of a chunk comes near it — and the program's
    temporaries must stay under ONE slab, the donated pool updated in
    place."""
    from tools.dump_hlo import pool_report
    cfg = dataclasses.replace(get_model_config(model), num_layers=layers)
    dev = SingleDeviceSharding(topo.devices[0])
    n_pages = 1 + (256 << 20) // (cfg.num_kv_heads * PAGE * cfg.head_dim * 2)
    cache = on(jax.eval_shape(lambda: llama.init_paged_kv_cache(
        cfg, n_pages, PAGE, quantized=kv == "int8kv")), dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731

    def chunk(params, tok, pos, cache, tbl, valid, start):
        return llama.apply_prefill_paged(params, cfg, tok, pos, cache, tbl,
                                         valid, start)

    compiled = jax.jit(chunk, donate_argnums=(3,)).lower(
        on(param_shapes(cfg), dev), i32(1, 512), i32(1, 512), cache,
        i32(1, 24), i32(1), i32()).compile()
    leaves = [(v.dtype.name, v.shape) for v in cache.values()]
    assert pool_report(compiled.as_text(), leaves) == []
    m = compiled.memory_analysis()
    pool_bytes = sum(v.size * v.dtype.itemsize for v in cache.values())
    slab = cache["k"].size * cache["k"].dtype.itemsize // layers
    assert m.temp_size_in_bytes < slab, (m.temp_size_in_bytes, slab)
    assert m.alias_size_in_bytes >= pool_bytes, m


# ------------------------- where a latent chunk program keeps its scores


@pytest.mark.parametrize("form,rows", [("kernel", 1), ("jnp", 1),
                                       ("kernel", 4)])
def test_latent_chunk_program_keeps_its_scores_in_the_kernel(
        topo, tpu_backend, form, rows):
    """``kimi-k2-instruct``'s 512-token chunk program at published widths
    (2 layers, one chip's 12 held experts, the cell's 2176-page pool and
    68-page window) compiles for the described chip with the chunk kernel
    in it — VMEM fits, no misaligned slice — and its compiled text holds
    NO float32 array of heads x chunk x block = 64 x 512 x 512 (67 MB)
    anywhere: as jnp blocks the same program holds over a hundred such
    instructions (ISSUE 37: they were a quarter of the cell's device
    time), which is what the ``jnp`` case shows the parse can see. The
    program of four prompts' rows (PR 39) likewise, with a bound of its
    own on what it holds beside the pool."""
    from tools.dump_hlo import parse_hlo
    cfg = dataclasses.replace(get_model_config("kimi-k2-instruct"),
                              num_layers=2, experts_held=12, experts_first=0)
    dev = SingleDeviceSharding(topo.devices[0])
    cache = on(jax.eval_shape(
        lambda: llama.init_paged_kv_cache(cfg, 2177, PAGE)), dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731
    assert llama.use_prefix_kernel(cfg, PAGE)

    def chunk(params, tok, pos, cache, tbl, valid, start):
        return llama.apply_prefill_paged(params, cfg, tok, pos, cache, tbl,
                                         valid, start,
                                         use_kernel=form == "kernel")

    compiled = jax.jit(chunk, donate_argnums=(3,)).lower(
        on(param_shapes(cfg), dev), i32(rows, 512), i32(rows, 512), cache,
        i32(rows, 68), i32(rows), i32(rows) if rows > 1 else i32()).compile()
    assert_fits(compiled)
    text = compiled.as_text()
    tile = sorted((cfg.num_heads, 512, 512))
    tiles = [i["name"] for ins in parse_hlo(text).values() for i in ins
             if (m := re.match(r"f32\[([\d,]+)\]", i["shape"])) and sorted(
                 int(d) for d in m.group(1).split(",") if d != "1") == tile]
    temp = compiled.memory_analysis().temp_size_in_bytes
    if form == "jnp":
        assert len(tiles) > 50 and "chunk_attn" not in text
        return
    assert tiles == []
    # the prefix kernel and the chunk's own update, both stacks
    assert text.count('custom_call_target="tpu_custom_call"') >= 4
    assert "%chunk_attn" in text
    assert_prefix_is_one_kernel(text)
    # one prompt: 120.6 MB where the jnp blocks took 233.7 (compile, PR
    # 37), 75.8 since a share's padded expert layout is walked in short
    # segments; four prompts' rows: 394.1 (compile, PR 39)
    assert temp < (160 << 20 if rows == 1 else 448 << 20), temp


def assert_prefix_is_one_kernel(text):
    """A latent chunk program's prefix is the kernel ``chunk_attn_prefix``
    (PR 48) under the scope ``prefill_attn_ms_per_ktok`` sums, and no
    chunk kernel sits in the branch of a ``cond`` any more: that branch
    was a live block's step of the scan over the prefix."""
    assert re.search(r'op_name="[^"]*/attn/[^"]*chunk_attn_prefix/', text)
    assert not re.search(r'op_name="[^"]*/cond/[^"]*chunk_attn', text)


def sparse_cfg():
    """``glm-5.2`` at published widths, three layers deep: the dense
    layer (full) and two expert layers (shared, full), one chip's 8 held
    experts."""
    return dataclasses.replace(
        get_model_config("glm-5.2"), num_layers=3, num_dense_layers=1,
        index_layers=(1, 0, 1), experts_held=8, experts_first=0)


@pytest.mark.parametrize("rows", [1, 4])
def test_sparse_latent_chunk_program_compiles(topo, tpu_backend, rows):
    """A 512-token chunk program under learned sparse attention at the
    cell's sizes (2080-page pool, 130-page window): the chunk kernel takes
    the keep mask as an operand and a head's 192 + 64 key columns folded
    to 256 whole lanes; the full layers' (512 x 17152) index scores are
    built a head at a time — no float32 array of index heads x chunk x
    keys exists — and the program fits beside the pool."""
    from tools.dump_hlo import parse_hlo
    cfg = sparse_cfg()
    dev = SingleDeviceSharding(topo.devices[0])
    cache = on(jax.eval_shape(
        lambda: llama.init_paged_kv_cache(cfg, 2081, PAGE)), dev)
    assert set(cache) == {"c", "r", "i"} and cache["i"].shape[0] == 2
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731
    assert llama.use_prefix_kernel(cfg, PAGE)

    def chunk(params, tok, pos, cache, tbl, valid, start):
        return llama.apply_prefill_paged(params, cfg, tok, pos, cache, tbl,
                                         valid, start, use_kernel=True)

    compiled = jax.jit(chunk, donate_argnums=(3,)).lower(
        on(param_shapes(cfg), dev), i32(rows, 512), i32(rows, 512), cache,
        i32(rows, 130), i32(rows), i32(rows) if rows > 1 else i32()).compile()
    assert_fits(compiled)
    text = compiled.as_text()
    assert "%chunk_attn" in text
    assert_prefix_is_one_kernel(text)
    keys = 132 * PAGE + 512                 # the table padded to blocks
    big = [i["name"] for ins in parse_hlo(text).values() for i in ins
           if (m := re.match(r"f32\[([\d,]+)\]", i["shape"]))
           and math.prod(int(d) for d in m.group(1).split(","))
           >= 32 * 512 * keys]
    assert big == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (640 << 20 if rows == 1 else 1024 << 20), temp


def test_sparse_latent_decode_step_compiles(topo, tpu_backend):
    """The decode step of this pool at the cell's sizes, 16 rows over a
    130-page window: the full layers' index window gathered and the top
    2048 of 16640 selected, the read the latent decode kernel with the
    keep mask as an operand, the pool in the layer scan's carry — ONE
    pool, no copy of a leaf (the index key's scatter is in place) and no
    gathered window of latent rows: the program's temporaries are a
    tenth of what the gathered read held."""
    from tools.dump_hlo import parse_hlo, pool_report
    cfg = sparse_cfg()
    dev = SingleDeviceSharding(topo.devices[0])
    cache = on(jax.eval_shape(
        lambda: llama.init_paged_kv_cache(cfg, 2081, PAGE)), dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731
    B = 16

    def step(params, tok, pos, cache, tbl, wp, off):
        return llama.apply_decode_paged(params, cfg, tok, pos, cache, tbl,
                                        pos[:, 0] + 1, wp, off,
                                        use_kernel=True, return_hidden=True)

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        on(param_shapes(cfg), dev), i32(B, 1), i32(B, 1), cache,
        i32(B, 130), i32(B), i32(B)).compile()
    assert_fits(compiled)
    pool = sum(math.prod(x.shape) * 2 for x in cache.values())
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= pool            # donated, not copied
    assert m.temp_size_in_bytes < 192 << 20, m.temp_size_in_bytes
    text = compiled.as_text()
    # the kernel under the scope the benchmark's stage reader sums
    assert re.search(r'op_name="[^"]*/attn/[^"]*latent_attn_decode', text)
    assert pool_report(text, [(v.dtype.name, v.shape)
                              for v in cache.values()]) == []
    # no array of slots x window rows x a latent row (16 x 16640 x 512)
    window = sorted((B, 130 * PAGE, cfg.kv_lora_rank))
    wide = [i["name"] for ins in parse_hlo(text).values() for i in ins
            if (m := re.match(r"\w+\[([\d,]+)\]", i["shape"])) and sorted(
                int(d) for d in m.group(1).split(",") if d != "1") == window]
    assert wide == []


# ------------------------------------- hyper-connected residual streams


@pytest.mark.parametrize("T", [1, 16, 512, 1536, 2048])
def test_sinkhorn_kernel_compiles(topo, T):
    """``hc_sinkhorn`` at every token count the cell's programs have (a
    decode round of one row and of sixteen, a chunk, the check's one-shot
    prompt, four prompts' rows): Mosaic takes the layout (16, T / 128,
    128) and the unrolled twenty pairs."""
    from generativeaiexamples_tpu.ops import hyper_connection as hc
    dev = SingleDeviceSharding(topo.devices[0])
    compiled = hc.sinkhorn_kernel.lower(
        sds((16, T), jnp.float32, dev), n=4, iters=20, eps=1e-6,
        clamp=30.0).compile()
    assert "hc_sinkhorn" in compiled.as_text()


def xing_cfg():
    return dataclasses.replace(get_model_config("xing4.0-29b-a4b"),
                               num_layers=3, num_dense_layers=1)


@pytest.mark.parametrize("rows", [1, 4])
def test_hyper_connected_chunk_program_compiles(topo, tpu_backend, rows):
    """``xing4.0-29b-a4b``'s 512-token chunk program at published widths
    (1 dense + 2 expert layers, all 64 experts, the cell's 28-page
    window), one prompt and four: the chain from a sublayer's logits to
    ``H_res`` is ONE kernel under the scope ``hc_pre`` — not the four
    fusions a normalisation pair it is in jax.numpy —, and the four
    prompts' program holds its streams inside the engine's reserve."""
    from tools.dump_hlo import parse_hlo
    cfg = xing_cfg()
    dev = SingleDeviceSharding(topo.devices[0])
    cache = on(jax.eval_shape(
        lambda: llama.init_paged_kv_cache(cfg, 449, PAGE)), dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731

    def chunk(params, tok, pos, cache, tbl, valid, start):
        return llama.apply_prefill_paged(params, cfg, tok, pos, cache, tbl,
                                         valid, start, use_kernel=True)

    compiled = jax.jit(chunk, donate_argnums=(3,)).lower(
        on(param_shapes(cfg), dev), i32(rows, 512), i32(rows, 512), cache,
        i32(rows, 28), i32(rows), i32(rows) if rows > 1 else i32()).compile()
    assert_fits(compiled)
    text = compiled.as_text()
    assert re.search(r'op_name="[^"]*/hc_pre/[^"]*hc_sinkhorn', text)
    ins = [i for c in parse_hlo(text).values() for i in c]
    scoped = [i for i in ins if re.search(r'op_name="[^"]*/hc_(pre|post)/',
                                          i["attrs"])]
    # two sublayers' chains in each of the two stacks' loop bodies: a
    # handful of fusions each, where the jnp chain had 80 a sublayer
    assert 0 < sum(i["op"] == "fusion" for i in scoped) < 120
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (128 << 20 if rows == 1 else 448 << 20), temp


def test_hyper_connected_decode_step_compiles(topo, tpu_backend):
    """The decode step at the cell's 16 rows over the latent kernel, the
    pool in the layer scan's carry and donated, ``hc_row_defect`` among
    its results."""
    cfg = xing_cfg()
    dev = SingleDeviceSharding(topo.devices[0])
    cache = on(jax.eval_shape(
        lambda: llama.init_paged_kv_cache(cfg, 449, PAGE)), dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731
    B = 16

    def step(params, tok, pos, cache, tbl, wp, off, active):
        return llama.apply_decode_paged(params, cfg, tok, pos, cache, tbl,
                                        pos[:, 0] + 1, wp, off,
                                        use_kernel=True, return_hidden=True,
                                        active=active, stats=True)

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        on(param_shapes(cfg), dev), i32(B, 1), i32(B, 1), cache, i32(B, 28),
        i32(B), i32(B), sds((B,), jnp.bool_, dev)).compile()
    assert_fits(compiled)
    m = compiled.memory_analysis()
    pool = sum(math.prod(x.shape) * 2 for x in cache.values())
    assert m.alias_size_in_bytes >= pool            # donated, not copied
    text = compiled.as_text()
    assert re.search(r'op_name="[^"]*/hc_pre/[^"]*hc_sinkhorn', text)
    assert re.search(r'op_name="[^"]*/attn/[^"]*latent_attn_decode', text)


_POOL = "bf16[2,9,4,16,64]{4,3,2,1,0:T(8,128)(2,1)}"
_SLAB = "bf16[9,4,16,64]{3,2,1,0:T(8,128)(2,1)}"
_BLOCK = "bf16[8,4,16,64]{3,2,1,0:T(8,128)(2,1)}"
_H = "bf16[32,64]{1,0:T(8,128)(2,1)}"
_T4 = f"(s32[]{{:T(128)}}, {_H}, {_POOL}, s32[8]{{0:T(128)}})"

# A two-layer chunk program over one pool array, as optimised HLO prints
# it: the layer loop gathers a block's eight pages (@LAYER@), then the
# entry scatters the chunk's own pages into the donated pool in place.
_HLO_CHUNK = f"""HloModule jit_chunk, is_scheduled=true, input_output_alias={{ {{1}}: (1, {{}}, may-alias) }}

@FUSED@

%fused_attend (p0: bf16[32,64], p1: bf16[8,4,16,64]) -> bf16[32,64] {{
  %p0 = {_H} parameter(0)
  %p1 = {_BLOCK} parameter(1)
  %kb = bf16[64,512]{{1,0:T(8,128)(2,1)}} bitcast(%p1)
  %s = bf16[32,512]{{1,0:T(8,128)(2,1)}} dot(%p0, %kb), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}
  ROOT %o = {_H} dot(%s, %kb), lhs_contracting_dims={{1}}, rhs_contracting_dims={{1}}
}}

%fused_scatter (p0: bf16[2,9,4,16,64], p1: s32[2], p2: bf16[2,2,4,16,64]) -> bf16[2,9,4,16,64] {{
  %p0 = {_POOL} parameter(0)
  %p1 = s32[2]{{0:T(128)}} parameter(1)
  %p2 = bf16[2,2,4,16,64]{{4,3,2,1,0:T(8,128)(2,1)}} parameter(2)
  ROOT %scatter.1 = {_POOL} scatter(%p0, %p1, %p2), update_window_dims={{0,2,3,4}}, inserted_window_dims={{1}}, scatter_dims_to_operand_dims={{1}}, index_vector_dim=1, to_apply=%assign
}}

%cond (arg: (s32[], bf16[32,64], bf16[2,9,4,16,64], s32[8])) -> pred[] {{
  %arg = {_T4} parameter(0)
  %i = s32[]{{:T(128)}} get-tuple-element(%arg), index=0
  %n = s32[]{{:T(128)}} constant(2)
  ROOT %lt = pred[]{{:T(512)}} compare(%i, %n), direction=LT
}}

%body (arg: (s32[], bf16[32,64], bf16[2,9,4,16,64], s32[8])) -> (s32[], bf16[32,64], bf16[2,9,4,16,64], s32[8]) {{
  %arg = {_T4} parameter(0)
  %i = s32[]{{:T(128)}} get-tuple-element(%arg), index=0
  %h = {_H} get-tuple-element(%arg), index=1
  %pool = {_POOL} get-tuple-element(%arg), index=2
  %pages = s32[8]{{0:T(128)}} get-tuple-element(%arg), index=3
@LAYER@
  %fusion.9 = {_H} fusion(%h, %gather_fusion.2), kind=kOutput, calls=%fused_attend
  %one = s32[]{{:T(128)}} constant(1)
  %next = s32[]{{:T(128)}} add(%i, %one)
  ROOT %t = {_T4} tuple(%next, %fusion.9, %pool, %pages)
}}

ENTRY %main (h0: bf16[32,64], k: bf16[2,9,4,16,64], pages: s32[8], dest: s32[2], new: bf16[2,2,4,16,64]) -> (bf16[32,64], bf16[2,9,4,16,64]) {{
  %h0 = {_H} parameter(0)
  %k = {_POOL} parameter(1), metadata={{op_name="kv_cache[\\'k\\']"}}
  %pages = s32[8]{{0:T(128)}} parameter(2)
  %dest = s32[2]{{0:T(128)}} parameter(3)
  %new = bf16[2,2,4,16,64]{{4,3,2,1,0:T(8,128)(2,1)}} parameter(4)
@ENTRY@
  %zero = s32[]{{:T(128)}} constant(0)
  %init = {_T4} tuple(%zero, %h0, %read, %pages)
  %loop = {_T4} while(%init), condition=%cond, body=%body
  %out = {_H} get-tuple-element(%loop), index=1
  %scatter_fusion = {_POOL} fusion(%k, %dest, %new), kind=kInput, calls=%fused_scatter
  ROOT %r = ({_H}, {_POOL}) tuple(%out, %scatter_fusion)
}}
"""

_POOL_FORMS = {
    # eight pages gathered by (layer, page) out of the whole pool
    "in_place": dict(
        fused=f"""%fused_gather (p0: bf16[18,4,16,64], p1: s32[8], p2: s32[]) -> bf16[8,4,16,64] {{
  %p0 = bf16[18,4,16,64]{{3,2,1,0:T(8,128)(2,1)}} parameter(0)
  %p1 = s32[8]{{0:T(128)}} parameter(1)
  %p2 = s32[]{{:T(128)}} parameter(2)
  ROOT %g = {_BLOCK} gather(%p0, %p1), offset_dims={{1,2,3}}, collapsed_slice_dims={{0}}, start_index_map={{0}}, index_vector_dim=1, slice_sizes={{1,4,16,64}}
}}""",
        layer=f"  %flat = bf16[18,4,16,64]{{3,2,1,0:T(8,128)(2,1)}} "
              f"bitcast(%pool)\n"
              f"  %gather_fusion.2 = {_BLOCK} fusion(%flat, %pages, %i), "
              f"kind=kLoop, calls=%fused_gather",
        entry=f"  %read = {_POOL} bitcast(%k)"),
    # as the parent compiled it: the layer's slab sliced out of the pool
    # first (and, for the report's other half, a second pool made for
    # the loop to read)
    "copied": dict(
        fused=f"""%fused_slice (p0: bf16[2,9,4,16,64], p1: s32[]) -> bf16[9,4,16,64] {{
  %p0 = {_POOL} parameter(0)
  %p1 = s32[]{{:T(128)}} parameter(1)
  %c0 = s32[]{{:T(128)}} constant(0)
  %ds = bf16[1,9,4,16,64]{{4,3,2,1,0:T(8,128)(2,1)}} dynamic-slice(%p0, %p1, %c0, %c0, %c0, %c0), dynamic_slice_sizes={{1,9,4,16,64}}
  ROOT %b = {_SLAB} bitcast(%ds)
}}

%fused_gather (p0: bf16[9,4,16,64], p1: s32[8]) -> bf16[8,4,16,64] {{
  %p0 = {_SLAB} parameter(0)
  %p1 = s32[8]{{0:T(128)}} parameter(1)
  ROOT %g = {_BLOCK} gather(%p0, %p1), offset_dims={{1,2,3}}, collapsed_slice_dims={{0}}, start_index_map={{0}}, index_vector_dim=1, slice_sizes={{1,4,16,64}}
}}""",
        layer=f"  %dynamic-slice_bitcast_fusion.4 = {_SLAB} fusion(%pool, "
              f"%i), kind=kLoop, calls=%fused_slice\n"
              f"  %gather_fusion.2 = {_BLOCK} fusion("
              f"%dynamic-slice_bitcast_fusion.4, %pages), kind=kLoop, "
              f"calls=%fused_gather",
        entry=f"  %read = {_POOL} copy(%k)"),
}


@pytest.mark.parametrize("form", ["in_place", "copied"])
def test_pool_report_finds_a_slab_and_a_second_pool(form):
    """``tools/dump_hlo.pool_report`` on hand-written optimised HLO:
    a slab sliced out inside the layer loop and a whole-pool ``copy``
    are reported; parameters, tuple elements, bitcasts and the in-place
    scatter of the donated pool are not."""
    from tools.dump_hlo import pool_report
    text = _HLO_CHUNK
    for mark, part in _POOL_FORMS[form].items():
        text = text.replace(f"@{mark.upper()}@", part)
    found = pool_report(text, [("bfloat16", (2, 9, 4, 16, 64))])
    assert [f.split(" ")[0] for f in found] == {
        "in_place": [],
        "copied": ["dynamic-slice_bitcast_fusion.4", "read"]}[form]


# --------------------------------------------------- the recurrence (PR 46)


@pytest.mark.parametrize("state", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16_state"])
def test_gated_delta_step_kernel_compiles(topo, state):
    """The decode step of a gated delta-rule layer over the cache's whole
    state leaf at qwen3-next-80b-a3b-instruct's widths (9 recurrent
    layers x 32 slots x 32 heads of 128 x 128), in place: the leaf is
    aliased through the call and appears once in the program."""
    from generativeaiexamples_tpu.ops.gated_delta import (
        gated_delta_step_kernel, step_kernel_supported)
    dev = SingleDeviceSharding(topo.devices[0])
    Lg, B, H, dk, dv = 9, 32, 32, 128, 128
    assert step_kernel_supported(H, dk, dv)
    f32 = jnp.float32
    args = (sds((B, H, dk), f32, dev), sds((B, H, dk), f32, dev),
            sds((B, H, dv), f32, dev), sds((B, H), f32, dev),
            sds((B, H), f32, dev),
            (sds((B,), jnp.int32, dev), sds((1,), jnp.int32, dev)),
            sds((Lg, B, H, dk, dv), state, dev), sds((), jnp.int32, dev))
    compiled = jax.jit(gated_delta_step_kernel, donate_argnums=(6,)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "gated_delta_step" in text and "tpu_custom_call" in text
    # no second copy of the 0.6 GB leaf beside the donated one
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows", [1, 4])
def test_gated_delta_scan_kernel_compiles(topo, rows):
    """The chunked scan of a gated delta-rule layer as ONE kernel at
    qwen3-next-80b-a3b-instruct's widths (a 512-token chunk of one
    prompt and of four, 16 key / 32 value heads of 128 x 128): Mosaic
    takes the float32 products at HIGHEST precision, the contraction
    over tokens in the state's update and the 64-lane halves of a pair's
    tile; nothing but the operands and results is on the program's
    books (a block's temporaries stay in VMEM)."""
    from generativeaiexamples_tpu.ops.gated_delta import (
        gated_delta_chunked_kernel, scan_kernel_supported)
    dev = SingleDeviceSharding(topo.devices[0])
    T, Hk, Hv, dk, dv = 512, 16, 32, 128, 128
    assert scan_kernel_supported(T, Hk, Hv, dk, dv)
    f32 = jnp.float32
    args = (sds((rows, T, Hk * dk), f32, dev), sds((rows, T, Hk * dk), f32, dev),
            sds((rows, T, Hv * dv), f32, dev), sds((rows, T, Hv), f32, dev),
            sds((rows, T, Hv), f32, dev), sds((rows, Hv, dk, dv), f32, dev))
    compiled = jax.jit(lambda *a: gated_delta_chunked_kernel(
        *a, interpret=False)).lower(*args).compile()
    text = compiled.as_text()
    assert "gated_delta_scan" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# ------------------------------------- a decay a channel (PR 49)


def test_kda_step_kernel_compiles(topo):
    """The decode step's twin for a decay a CHANNEL of a head's keys over
    the cache's whole state leaf at ling-3.0-flash's widths (10 recurrent
    layers x 32 slots x 32 heads of 128 x 128), in place: the decays
    come in as wide as the keys and are turned beside them."""
    from generativeaiexamples_tpu.ops.gated_delta import (
        gated_delta_step_kernel, step_kernel_supported)
    dev = SingleDeviceSharding(topo.devices[0])
    Lg, B, H, dk, dv = 10, 32, 32, 128, 128
    assert step_kernel_supported(H, dk, dv)
    f32 = jnp.float32
    args = (sds((B, H, dk), f32, dev), sds((B, H, dk), f32, dev),
            sds((B, H, dv), f32, dev), sds((B, H, dk), f32, dev),
            sds((B, H), f32, dev),
            (sds((B,), jnp.int32, dev), sds((1,), jnp.int32, dev)),
            sds((Lg, B, H, dk, dv), f32, dev), sds((), jnp.int32, dev))
    compiled = jax.jit(gated_delta_step_kernel, donate_argnums=(6,)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "kda_delta_step" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows", [1, 4])
def test_kda_scan_kernel_compiles(topo, rows):
    """The chunked scan for a decay a CHANNEL as ONE kernel at
    ling-3.0-flash's widths (a 512-token chunk of one prompt and of
    four, 32 heads of 128 x 128, the values read behind q and k in the
    convolution's output): Mosaic takes the shifted adds down the
    sublanes, the turn of a product's lanes by half a tile, a pair's
    tile transposed and the (8, 128) tile of decays turned; nothing but
    the operands and results is on the program's books."""
    from generativeaiexamples_tpu.ops.gated_delta import (
        kda_chunked_kernel, kda_scan_kernel_supported)
    dev = SingleDeviceSharding(topo.devices[0])
    T, H, dk, dv = 512, 32, 128, 128
    assert kda_scan_kernel_supported(T, H, dk, dv)
    f32 = jnp.float32
    args = (sds((rows, T, H * dk), f32, dev), sds((rows, T, H * dk), f32, dev),
            sds((rows, T, 3 * H * dv), f32, dev),
            sds((rows, T, H * dk), f32, dev), sds((rows, T, H), f32, dev),
            sds((rows, H, dk, dv), f32, dev))
    compiled = jax.jit(lambda *a: kda_chunked_kernel(
        *a, v_at=2 * H * dk, interpret=False)).lower(*args).compile()
    text = compiled.as_text()
    assert "kda_delta_scan" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def ling_cfg():
    """Six layers at published widths in periods of three: a dense KDA
    layer, then an expert stack that begins INSIDE a period — [K L] [K K
    L] with group 0's experts: the head and the whole period are two
    steps of one scan (``llama._run_stack`` ``ragged_periods``)."""
    cfg = dataclasses.replace(
        get_model_config("ling-3.0-flash"), num_layers=6,
        num_dense_layers=1, full_attention_interval=3, experts_held=64)
    assert llama._stack_periods(cfg, 1, 5) == (1, 1, 0)
    return cfg


@pytest.mark.parametrize("rows", [4])
def test_kda_latent_chunk_program_compiles(topo, tpu_backend, rows):
    """``ling-3.0-flash``'s 512-token chunk program of four prompts'
    rows over a state a slot beside a latent pool, the cell's 68-page
    window: the chunked scan for a decay a channel is the kernel
    ``kda_delta_scan`` (under the scope ``kda_scan``), the latent
    layers' prefix walk the chunk kernel, and the program fits beside
    the engine's reserve."""
    cfg = ling_cfg()
    dev = SingleDeviceSharding(topo.devices[0])
    cache = on(jax.eval_shape(lambda: llama.init_paged_kv_cache(
        cfg, 2177, PAGE, slots=32)), dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731

    def chunk(params, tok, pos, cache, tbl, valid, start, slots):
        return llama.apply_prefill_paged(params, cfg, tok, pos, cache, tbl,
                                         valid, start, use_kernel=True,
                                         slots=slots)

    compiled = jax.jit(chunk, donate_argnums=(3,)).lower(
        on(param_shapes(cfg), dev), i32(rows, 512), i32(rows, 512), cache,
        i32(rows, 68), i32(rows), i32(rows) if rows > 1 else i32(),
        i32(rows)).compile()
    assert_fits(compiled)
    text = compiled.as_text()
    assert re.search(r'op_name="[^"]*/kda_scan/[^"]*kda_delta_scan', text)
    assert re.search(r'op_name="[^"]*/kda_state/', text)
    assert "chunk_attention_prefix" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (1 << 30 if rows == 1 else 3 << 30), temp


def test_kda_latent_decode_step_compiles(topo, tpu_backend):
    """The decode step at the cell's 32 rows: the state leaf and the
    latent pool in the layer scan's carry and donated, the recurrence
    the kernel ``kda_delta_step`` under the scope ``kda_step``, the
    latent read the latent decode kernel, ``route_groups_held_pct``
    among its results."""
    cfg = ling_cfg()
    dev = SingleDeviceSharding(topo.devices[0])
    cache = on(jax.eval_shape(lambda: llama.init_paged_kv_cache(
        cfg, 2177, PAGE, slots=32)), dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731
    B = 32

    def step(params, tok, pos, cache, tbl, wp, off, active):
        return llama.apply_decode_paged(params, cfg, tok, pos, cache, tbl,
                                        pos[:, 0] + 1, wp, off,
                                        use_kernel=True, return_hidden=True,
                                        active=active, stats=True)

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        on(param_shapes(cfg), dev), i32(B, 1), i32(B, 1), cache, i32(B, 68),
        i32(B), i32(B), sds((B,), jnp.bool_, dev)).compile()
    assert_fits(compiled)
    m = compiled.memory_analysis()
    pool = sum(math.prod(x.shape) * x.dtype.itemsize for x in cache.values())
    assert m.alias_size_in_bytes >= pool            # donated, not copied
    text = compiled.as_text()
    assert re.search(r'op_name="[^"]*/kda_step/[^"]*kda_delta_step', text)
    assert re.search(r'op_name="[^"]*/attn/[^"]*latent_attn_decode', text)
    assert set(jax.eval_shape(step, on(param_shapes(cfg), dev), i32(B, 1),
                              i32(B, 1), cache, i32(B, 68), i32(B), i32(B),
                              sds((B,), jnp.bool_, dev))[2]) == {
        "experts_touched", "local_assignments", "route_rows_read",
        "route_groups_held_pct"}


def test_ssd_step_kernel_compiles(topo):
    """The decode step of a state-space layer over the cache's whole
    state leaf at granite-4.0-h-micro's widths (36 layers x 32 slots x
    64 heads of 64 x 128, N on the lanes), in place: the 2.4 GB leaf is
    aliased through the call and appears once in the program."""
    from generativeaiexamples_tpu.ops.ssd import (ssd_step_kernel,
                                                  step_kernel_supported)
    dev = SingleDeviceSharding(topo.devices[0])
    Lg, B, H, P, N = 36, 32, 64, 64, 128
    assert step_kernel_supported(H, 1, P, N)
    f32 = jnp.float32
    args = (sds((B, H, P), f32, dev), sds((B, H), f32, dev),
            sds((H,), f32, dev), sds((B, 1, N), f32, dev),
            sds((B, 1, N), f32, dev), sds((H,), f32, dev),
            (sds((B,), jnp.int32, dev), sds((1,), jnp.int32, dev)),
            sds((Lg, B, H, P, N), f32, dev),
            sds((), jnp.int32, dev))
    compiled = jax.jit(ssd_step_kernel, donate_argnums=(7,)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "ssd_step" in text and "tpu_custom_call" in text
    # no second copy of the leaf beside the donated one
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows", [1, 4])
def test_ssd_scan_kernel_compiles(topo, rows):
    """The chunked form of a state-space layer as ONE kernel at
    granite-4.0-h-micro's widths (a 512-token chunk of one prompt and of
    four, 64 heads of 64 x 128, one group; ``x`` read out of the
    convolution's whole output): Mosaic takes the float32 products at
    HIGHEST precision, the contraction over tokens in the state's write,
    the read-out against the state transposed, the 0 / 1 spreads and a
    pair's (8, 128) tile of decays turned; nothing but the operands and
    results is on the program's books."""
    from generativeaiexamples_tpu.ops.ssd import (scan_kernel_supported,
                                                  ssd_chunked_kernel)
    dev = SingleDeviceSharding(topo.devices[0])
    T, H, P, N = 512, 64, 64, 128
    assert scan_kernel_supported(T, H, 1, P, N)
    f32 = jnp.float32
    args = (sds((rows, T, H * P + 2 * N), f32, dev),
            sds((rows, T, H), f32, dev),
            sds((H,), f32, dev), sds((rows, T, N), f32, dev),
            sds((rows, T, N), f32, dev), sds((H,), f32, dev),
            sds((rows, H, P, N), f32, dev))
    compiled = jax.jit(lambda *a: ssd_chunked_kernel(
        *a, interpret=False)).lower(*args).compile()
    text = compiled.as_text()
    assert "ssd_scan" in text and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
