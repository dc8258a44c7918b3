"""Rotary position embeddings (RoPE), HF ``rotate_half`` convention.

The reference bakes RoPE into its TRT GPT-attention plugin with optional
linear/dynamic scaling (reference: conversion_scripts/llama/build.py:399-408
``rotary_scaling``). Here it is a pure function of absolute positions so the
same code serves full-sequence prefill and single-token decode (positions are
just different), which is what XLA wants: no data-dependent shapes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float = 10000.0,
                     scaling_factor: float = 1.0) -> jax.Array:
    """Inverse frequencies, shape (head_dim // 2,), float32.

    ``scaling_factor > 1`` implements "linear" RoPE scaling (positions are
    divided by the factor), parity with the reference's
    ``rotary_scaling type=linear`` flag (build.py:399-408).
    """
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta ** exponents)
    return inv_freq / scaling_factor


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> jax.Array:
    """YaRN's inverse frequencies over a rotary part of ``dim`` values,
    shape (dim // 2,), float32 (Peng et al. 2023, "NTK-by-parts"; the
    arithmetic is that of transformers' ``_compute_yarn_parameters``).

    A frequency that turns more than ``beta_fast`` times within
    ``original_max`` positions is kept; one that turns fewer than
    ``beta_slow`` times is divided by ``factor`` (its positions
    interpolated); between the two indices the blend is linear. The
    score multiplier that goes with it is the configuration's
    (``LlamaConfig.score_scale``), not a scale on cos and sin.
    """
    def index_of(turns: float) -> float:
        # the (fractional) frequency index that turns ``turns`` times
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(index_of(beta_fast)), 0)
    high = min(math.ceil(index_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001                   # as published: no singularity
    plain = rope_frequencies(dim, theta)
    idx = jnp.arange(dim // 2, dtype=jnp.float32)
    kept = 1.0 - jnp.clip((idx - low) / (high - low), 0.0, 1.0)
    return plain / factor * (1.0 - kept) + plain * kept


def deinterleave(x: jax.Array) -> jax.Array:
    """Rotary pairs published as (2i, 2i+1) brought to the (i, i + half)
    layout ``apply_rope`` rotates: the even columns, then the odd. Applied
    to queries and keys alike, so their products are the published
    ones."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def apply_rope(q: jax.Array, k: jax.Array, positions: jax.Array,
               inv_freq: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Rotate q and k by position-dependent angles.

    q: (..., S, H, hd), k: (..., S, KV, hd), positions: (..., S) int32.
    Uses the HF non-interleaved layout: the head dim is split into two
    halves and rotated as (x1, x2) -> (x1*cos - x2*sin, x2*cos + x1*sin),
    matching transformers' ``rotate_half`` so HF-imported weights are
    bit-compatible.
    """
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]

    def rot(x: jax.Array) -> jax.Array:
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.astype(x.dtype)

    return rot(q), rot(k)


def apply_rope_partial(q: jax.Array, k: jax.Array, positions: jax.Array,
                       inv_freq: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``apply_rope`` over the FIRST ``2 * len(inv_freq)`` values of each
    head (pairs (i, i + half) of that part); the values past them pass
    unrotated (a ``partial_rotary_factor`` below 1)."""
    rot = 2 * inv_freq.shape[-1]
    qr, kr = apply_rope(q[..., :rot], k[..., :rot], positions, inv_freq)
    return (jnp.concatenate([qr, q[..., rot:]], axis=-1),
            jnp.concatenate([kr, k[..., rot:]], axis=-1))
