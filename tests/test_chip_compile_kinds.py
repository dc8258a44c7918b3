"""Compile, for a TPU v5e that is DESCRIBED and not attached, what the
per-layer kinds and dropless experts add to the serving path (the same
rehearsal as ``test_chip_compile.py``, a file of its own so that nothing
there is edited): the windowed paged-attention kernel at 28 query / 4 KV
heads — a group of 7, not a multiple of the 8-row sublane tile — and the
decode step of the window / global / no-RoPE / dropless-expert model at
its published widths (depth 4 = one period), whose experts' (L, E, in,
out) stacks must be read in place by the block loop. Nothing runs.
"""

import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import get_model_config
from generativeaiexamples_tpu.ops.quant import quantize_params

PAGE = 128
CFG = dataclasses.replace(get_model_config("smallthinker-21b-a3b-instruct"),
                          num_layers=4)

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
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16kv", "int8kv"])
def test_windowed_kernel_group_of_seven_compiles(topo, kv_int8):
    from generativeaiexamples_tpu.ops.paged_attention import (
        paged_attention_decode)
    dev = SingleDeviceSharding(topo.devices[0])
    B, H, KV, hd = 16, CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
    assert (H // KV) % 8                 # the case this test is for
    L, N, W = 4, 65, 68
    pool = sds((L, N, KV, PAGE, hd), jnp.int8 if kv_int8 else jnp.bfloat16,
               dev)
    scales = sds((L, N, KV, PAGE), jnp.bfloat16, dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731
    cur = sds((B, KV, hd), jnp.bfloat16, dev)

    def step(q, pk, pv, ks, vs, tbl, lens, ck, cv, wp, off, li, win):
        extra = dict(pool_ks=ks, pool_vs=vs) if kv_int8 else {}
        return paged_attention_decode(q, pk, pv, tbl, lens, ck, cv, wp,
                                      off, li, window=win, **extra)

    text = jax.jit(step).lower(
        sds((B, H, hd), jnp.bfloat16, dev), pool, pool, scales, scales,
        i32(B, W), i32(B), cur, cur, i32(B), i32(B), i32(1),
        i32(1)).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("paged_attn_decode_int8kv" if kv_int8
            else "paged_attn_decode") in text


@pytest.mark.parametrize("rows", [16, 512], ids=["decode", "chunk"])
def test_grouped_expert_kernel_compiles(topo, rows):
    """The dropless layer's block loop as the Pallas kernel: a decode
    batch's blocks of 16 rows and a prefill chunk's of 64, each block's
    expert DMA'd out of the whole (L, E, in, out) stacks."""
    from generativeaiexamples_tpu.ops.grouped_ffn import grouped_expert_ffn
    from generativeaiexamples_tpu.parallel.moe import dropless_block_rows
    dev = SingleDeviceSharding(topo.devices[0])
    L, E, D, F = 4, CFG.num_experts, CFG.hidden_size, CFG.intermediate_size
    bm = dropless_block_rows(rows)
    NB = rows * CFG.num_experts_per_tok // bm + E
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731

    def ffn(x, be, n, li, g, u, d):
        return grouped_expert_ffn(x, be, n, li, g, u, d, bm=bm, relu=True)

    compiled = jax.jit(ffn).lower(
        sds((NB * bm, D), jnp.bfloat16, dev), i32(NB), i32(), i32(),
        sds((L, E, D, F), jnp.bfloat16, dev),
        sds((L, E, D, F), jnp.bfloat16, dev),
        sds((L, E, F, D), jnp.bfloat16, dev)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_expert_ffn" in text
    # nothing but the rows in and out: no copy of a stack
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_decode_step_reads_expert_stacks_in_place(topo, tpu_backend):
    """One period of the model at published widths, int8 attention and
    bf16 experts: the step compiles with the kernel, and its temporaries
    stay far under ONE layer's slab of experts (755 MB) — the block loop
    slices an expert's matrix out of the whole (L, E, in, out) stack
    where it is used; a scan that sliced the layer first would hand the
    loop a copy of the slab."""
    dev = SingleDeviceSharding(topo.devices[0])
    B, W, N = 16, 68, 129
    shapes = jax.eval_shape(lambda k: quantize_params(
        llama.init_params(CFG, k, dtype=jnp.bfloat16), "int8"),
        jax.random.key(0))
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype, dev), shapes)
    pool = sds((CFG.num_layers, N, CFG.num_kv_heads, PAGE, CFG.head_dim),
               jnp.bfloat16, dev)
    i32 = lambda *shape: sds(shape, jnp.int32, dev)  # noqa: E731

    def step(params, tok, pos, cache, tbl, valid, wp, off, active):
        return llama.apply_decode_paged(params, CFG, tok, pos, cache, tbl,
                                        valid, wp, off, use_kernel=True,
                                        active=active, stats=True)

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, i32(B, 1), i32(B, 1), {"k": pool, "v": pool}, i32(B, W),
        i32(B), i32(B), i32(B), sds((B,), bool, dev)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "grouped_expert_ffn" in text
    slab = CFG.num_experts * 3 * CFG.hidden_size * CFG.intermediate_size * 2
    assert compiled.memory_analysis().temp_size_in_bytes < slab // 8
    # no instruction's result is a whole expert stack or a layer's slab
    E, D, F = CFG.num_experts, CFG.hidden_size, CFG.intermediate_size
    for shape in (f"bf16[{CFG.num_layers},{E},{D},{F}]", f"bf16[{E},{D},{F}]",
                  f"bf16[1,{E},{D},{F}]"):
        made = [ln for ln in text.splitlines() if re.match(
            rf"\s*(ROOT )?%\S+ = {re.escape(shape)}\S* (copy|fusion|"
            rf"dynamic-slice)\(", ln)]
        assert not made, made[:2]
