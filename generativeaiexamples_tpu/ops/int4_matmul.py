"""Pallas packed-int4 matmul: int4 weights at int4 HBM bandwidth.

The XLA path for int4 (`ops/quant.matmul`) must unpack the nibble-packed
weight to a full int8 tensor before the dot. Inside the decode step that
unpack cannot be hoisted (weights ride the layer scan), so every decode
step pays: read q4 (0.5 B/weight) + write int8 (1 B) + read int8 (1 B) =
5x the int4 bytes, plus VPU shift work serialized ahead of the MXU —
measured r5 on v5e-1/7B: 72 tok/s at 8 slots vs 504 for int8 weights.

This kernel streams the PACKED tensor straight to VMEM and unpacks
per-tile in registers, so HBM sees only the int4 bytes — decode becomes
weight-bound at half the int8 traffic, and int4 stops being a capacity-
only trade. (The reference's int4-AWQ engines get the same property
from TRT-LLM's CUDA kernels; reference: conversion_scripts/llama/
build.py:543-580, model_server quantization flags __main__.py:60-66.)

Nibble layout trick: `quantize_tensor` packs reduction-axis row pairs
``(2r, 2r+1)`` as (low, high) nibbles of one byte. Splitting the
ACTIVATION columns into even/odd (cheap XLA slices of a small tensor)
turns the whole contraction into two half-size dots with NO in-kernel
interleave:

    y = x @ W = x[:, 0::2] @ W[0::2, :] + x[:, 1::2] @ W[1::2, :]
              = xe @ sign_extend(q4)    + xo @ (q4 >> 4)

Grid: (M/bm, N/bn, K2/bk) with the contraction innermost ("arbitrary"
semantics); an f32 VMEM accumulator carries partial sums across k and
writes the output tile once, applying per-channel or per-group (AWQ)
scales — group boundaries align with k tiles because group_size/2 is a
multiple of bk.

Precision trade, grouped (AWQ) path — ACCEPTED, by design: per-group
scales are folded into the unpacked weight tile and the product is cast
to the ACTIVATION dtype before the dot, so on real (bf16) configs every
dequantized weight rounds through bf16 on its way to the MXU. The XLA
fallback (``quant.matmul``) instead applies group scales in f32 after
the partial dots. What that costs, by the reasoning and as measured
(CPU, interpreted, PR 30): a value rounded to bf16 is off by 2^-9
relative at most, 0.166 % RMS, and a dot of K such weights against any
activations is off by the same 0.166 % whatever K, N, M or the group
size — the kernel's output equals an f32 matmul over the bf16-rounded
dequantized weights to 1e-6, and reads 0.148-0.176 % RMS against the
f32 matmul over the unrounded ones (eight geometries, two seeds). The
test compares against the fallback's output, which is itself ROUNDED TO
bf16 (another, independent 0.165 %), so it reads sqrt 2 times that:
0.2344 % at K = 256 in two groups of 128, 0.2341 % at K = 1024 in two
groups of 512, 0.221-0.238 % over all sixteen. The bound is 4e-3, 1.7
times the largest reading. With f32 activations the paths agree to
~1e-6 — the error IS the bf16 weight rounding, not the kernel math.
Bit-closeness to the XLA path would need one extra f32 accumulator per
group per k-tile; the bandwidth win is the point of this kernel, so the
rounding stays. Both are pinned by tests/test_int4_matmul.py
(test_grouped_bf16_rounding_trade_within_documented_bound).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANE = 128


def _divisor_block(dim: int, cap: int, unit: int) -> int:
    for cand in range(min(cap, dim), unit - 1, -unit):
        if dim % cand == 0:
            return cand
    return unit


def supported(K: int, N: int, group_size: int = 0) -> bool:
    """Kernel geometry gate: the packed reduction dim (K/2) must tile by
    one 128 lane (in-kernel activation slices are lane-width granular)
    and the output dim by one 128 lane. For grouped scales the k block
    must align with group boundaries (``group_size/2`` divides or is
    divided by the chosen block) — callers gate here so incompatible
    group sizes fall back to the XLA path instead of failing
    mid-forward."""
    if K % 256 or N % _LANE:
        return False
    if group_size:
        gk2 = group_size // 2
        bk = _divisor_block(K // 2, 256, _LANE)
        if gk2 <= 0 or (bk % gk2 and gk2 % bk):
            return False
    return True


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def int4_matmul(x: jax.Array, q4: jax.Array, scale: jax.Array,
                *, out_dtype=None, interpret: bool = False) -> jax.Array:
    """``x @ unpack(q4) * scale`` without materializing the unpacked
    weight.

    x:     (..., K) activations (any float dtype)
    q4:    (K/2, N) int8 nibble pairs (ops/quant.py packing)
    scale: (N,) per-output-channel scale, or (G, N) per-group (AWQ),
           groups along the reduction axis (G divides K, and
           (K/G)/2 must tile by the k block).
    Returns (..., N) in ``out_dtype`` (default: x.dtype).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    out_dtype = out_dtype or x.dtype
    *lead, K = x.shape
    K2, N = q4.shape
    assert K == 2 * K2, (x.shape, q4.shape)
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, K)
    grouped = scale.ndim == 2
    G = scale.shape[0] if grouped else 1

    # Even/odd activation split OUTSIDE the kernel: (M, K) is tiny next
    # to the weight, and strided slices are free for XLA.
    xe = x2[:, 0::2]
    xo = x2[:, 1::2]

    # Block sizes: bm covers the whole (padded) M for decode/prefill
    # shapes. bn/bk must DIVIDE their dims (a non-dividing block silently
    # truncates the grid), and bk must be a 128 multiple — the in-kernel
    # activation k-slice is on the lane dim, where sub-128 widths do not
    # lower (measured: bk=64 kernels fail to compile on v5e). A k tile
    # may therefore span multiple groups; scales go onto the weight tile
    # rows pre-dot in that case.
    bm = min(-(-M // 8) * 8, 256)
    bn = _divisor_block(N, 512, _LANE)
    bk = _divisor_block(K2, 256, _LANE)
    if grouped:
        gk2 = K2 // G                 # packed rows per group
        if bk % gk2 and gk2 % bk:
            raise ValueError(
                f"group size {2 * gk2} does not tile the k block {bk}; "
                f"use a power-of-two group size")
    Mp = -(-M // bm) * bm
    if Mp != M:
        pad = ((0, Mp - M), (0, 0))
        xe = jnp.pad(xe, pad)
        xo = jnp.pad(xo, pad)
    nm, nn, nk = Mp // bm, N // bn, K2 // bk

    def kernel(xe_ref, xo_ref, q4_ref, s_ref, o_ref, acc):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _():
            acc[:] = jnp.zeros_like(acc)

        # unpack in int32: Mosaic has no int8 shifts (measured: int8
        # shift lowerings fail to compile on v5e)
        q = q4_ref[...].astype(jnp.int32)
        lo = (q << 28) >> 28                          # sign-extended low
        hi = q >> 4                                   # arithmetic high
        if grouped:
            # scales go onto the UNPACKED WEIGHT TILE rows pre-dot: a
            # 128-lane-aligned k tile can span several groups (AWQ-128
            # has 64 packed rows per group), so a single post-dot scale
            # per tile does not exist. The scale block carries ALL
            # groups (full-dim blocks dodge Mosaic's %8 sublane rule
            # when G isn't a multiple of 8); rows are selected with
            # iota masks — dynamic sublane slicing by a grid-derived
            # index does not lower.
            gk2 = K2 // G
            gpg = max(1, bk // gk2)      # groups this tile touches
            g0 = (k * bk) // gk2
            grow = jax.lax.broadcasted_iota(jnp.int32, (G, bn), 0)
            sub = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0)
            sfull = s_ref[...].astype(jnp.float32)
            s_rows = jnp.zeros((bk, bn), jnp.float32)
            for j in range(gpg):
                sj = jnp.sum(jnp.where(grow == g0 + j, sfull, 0.0),
                             axis=0, keepdims=True)   # (1, bn)
                s_rows = jnp.where(sub // gk2 == j, sj, s_rows)
            lo = (lo.astype(jnp.float32) * s_rows)
            hi = (hi.astype(jnp.float32) * s_rows)
        lo = lo.astype(xe_ref.dtype)
        hi = hi.astype(xe_ref.dtype)
        # activations stay whole-row in VMEM (tiny next to the weight
        # tiles); the k slice happens in-register at lane-aligned offsets
        xe_k = xe_ref[:, pl.ds(k * bk, bk)]
        xo_k = xo_ref[:, pl.ds(k * bk, bk)]
        if interpret:
            # off the TPU the operands, ALREADY rounded to the activation
            # dtype, multiply as f32: the MXU's bf16 x bf16 products are
            # exact in f32 too, and the CPU backend has no bf16 x bf16 ->
            # f32 dot at every geometry (jax 0.9: UNIMPLEMENTED in
            # DotThunk at K = 256, M = 8)
            xe_k, xo_k, lo, hi = (a.astype(jnp.float32)
                                  for a in (xe_k, xo_k, lo, hi))
        part = (
            jax.lax.dot(xe_k, lo, preferred_element_type=jnp.float32)
            + jax.lax.dot(xo_k, hi, preferred_element_type=jnp.float32))
        acc[:] += part

        @pl.when(k == nk - 1)
        def _():
            out = acc[...]
            if not grouped:
                out = out * s_ref[...].astype(jnp.float32)
            o_ref[...] = out.astype(o_ref.dtype)

    in_specs = [
        pl.BlockSpec((bm, K2), lambda m, n, k: (m, 0)),
        pl.BlockSpec((bm, K2), lambda m, n, k: (m, 0)),
        pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
    ]
    if grouped:
        in_specs.append(pl.BlockSpec((G, bn), lambda m, n, k: (0, n)))
        s_arg = scale
    else:
        in_specs.append(pl.BlockSpec((1, bn), lambda m, n, k: (0, n)))
        s_arg = scale.reshape(1, N)

    out = pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="int4_matmul",
    )(xe, xo, q4, s_arg)
    return out[:M].reshape(*lead, N)
