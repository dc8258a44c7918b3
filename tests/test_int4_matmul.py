"""Packed-int4 Pallas matmul vs the XLA unpack path (interpret mode).

The kernel's job is identical math at int4 HBM bytes; these tests pin
the math (per-channel exact, grouped within bf16 dequant tolerance),
the geometry gate, and the qmm dispatch seam.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.ops import quant
from generativeaiexamples_tpu.ops.int4_matmul import int4_matmul, supported


def _case(K, N, M, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32) * 0.05)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    return w, x


@pytest.mark.parametrize("K,N,M", [
    (256, 384, 8),     # minimal geometry
    (512, 256, 3),     # M below one sublane tile (padded)
    (256, 128, 33),    # M across tiles
    (768, 640, 16),    # bn/bk divisors below the caps
])
def test_per_channel_matches_xla(K, N, M):
    w, x = _case(K, N, M)
    t = quant.quantize_tensor(w, bits=4)
    expect = jax.lax.dot_general(
        x, quant._int_weights(t),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * t["scale"]
    got = int4_matmul(x, t["q4"], t["scale"], interpret=True,
                      out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("K,N,M,gs", [
    (256, 384, 8, 128),     # 2 groups per 128-lane k tile (AWQ-128)
    (512, 256, 9, 256),     # 1 group per k tile
    (1024, 128, 4, 512),    # group spans multiple k tiles
])
def test_grouped_matches_xla(K, N, M, gs):
    """f32 activations: no bf16 weight rounding in play, so the kernel
    must track the XLA grouped path tightly (measured ~4e-7 RMS rel;
    the former 2e-2 tolerance would have hidden a real math bug)."""
    w, x = _case(K, N, M, seed=1)
    t = quant.quantize_tensor_grouped(w, group_size=gs)
    expect = quant.matmul(x, t)  # XLA grouped path (kernel off on CPU)
    got = int4_matmul(x, t["q4"], t["gscale"], interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("K,N,M,gs", [
    (256, 384, 8, 128),
    (1024, 128, 4, 512),
])
def test_grouped_bf16_rounding_trade_within_documented_bound(K, N, M, gs):
    """Pin the documented precision trade (module docstring / ADVICE
    r5): with bf16 activations the kernel folds group scales into the
    weight tile and rounds every dequantized weight through bf16 before
    the dot, which the XLA path (f32 scales after the partial dots)
    does not. A bf16 rounding is 0.166 % RMS whatever the geometry, so
    the kernel IS an f32 matmul over bf16-rounded weights (held to
    1e-5), and against the XLA path, whose own output is rounded to
    bf16 once more, it reads sqrt 2 times 0.166 %: 0.2344 % here at
    K = 256 in two groups of 128, 0.2341 % at K = 1024 in two groups of
    512 (CPU, PR 30) — bounded at 4e-3 so a regression past the
    documented trade fails loudly. (Until PR 30 the first case read
    nothing: the CPU backend refused the interpreted kernel's bf16 x
    bf16 -> f32 dot at that geometry.)"""
    w, x = _case(K, N, M, seed=1)
    t = quant.quantize_tensor_grouped(w, group_size=gs)
    xb = x.astype(jnp.bfloat16)
    expect = np.asarray(quant.matmul(xb, t).astype(jnp.float32))
    got = np.asarray(int4_matmul(xb, t["q4"], t["gscale"], interpret=True,
                                 out_dtype=jnp.float32))

    def rms_rel(a, b):
        return np.sqrt(((a - b) ** 2).mean()) / np.sqrt((b ** 2).mean())

    assert 1e-3 < rms_rel(got, expect) < 4e-3, rms_rel(got, expect)
    dequantized = (np.asarray(quant._int_weights(t), np.float32)
                   .reshape(K // gs, gs, N)
                   * np.asarray(t["gscale"], np.float32)[:, None, :])
    rounded = np.asarray(jnp.asarray(dequantized.reshape(K, N))
                         .astype(jnp.bfloat16).astype(jnp.float32))
    assert rms_rel(got, np.asarray(xb, np.float32) @ rounded) < 1e-5


def test_leading_dims_and_out_dtype():
    w, x = _case(256, 128, 6)
    t = quant.quantize_tensor(w, bits=4)
    x3 = x.reshape(2, 3, 256)
    got = int4_matmul(x3, t["q4"], t["scale"], interpret=True,
                      out_dtype=jnp.float32)
    assert got.shape == (2, 3, 128) and got.dtype == jnp.float32
    flat = int4_matmul(x, t["q4"], t["scale"], interpret=True,
                       out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got).reshape(6, 128),
                               np.asarray(flat), rtol=1e-6)


def test_supported_gate():
    assert supported(4096, 11008)
    assert supported(11008, 4096)
    assert not supported(4096, 100)    # N not lane multiple
    assert not supported(120, 128)     # K2 not lane multiple
    # dispatch seam: CPU backend never takes the kernel
    t = quant.quantize_tensor(_case(256, 128, 2)[0], bits=4)
    assert not quant._use_int4_kernel(t)


def test_odd_group_size_rejected():
    w, x = _case(768, 128, 4)
    t = quant.quantize_tensor_grouped(w, group_size=384)  # gk2=192 vs bk
    with pytest.raises(ValueError, match="group size"):
        int4_matmul(x, t["q4"], t["gscale"], interpret=True)
