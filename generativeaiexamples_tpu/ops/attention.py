"""Attention: GQA with absolute-position causal masking.

Replaces the reference's TRT GPT-attention plugin (reference:
conversion_scripts/llama/build.py:624-628 ``set_gpt_attention_plugin`` with
paged KV + remove-input-padding). Paged-KV decode attention lives in
``models/llama.py:apply_decode_paged`` (page gather + this kernel); XLA
fuses the masking/softmax chain here into the attention einsums.

Layout conventions (chosen for TPU tiling — head_dim last, 128-aligned):
  q:        (B, S, H,  hd)
  k, v:     (B, T, KV, hd)      T = key length (cache capacity)
  output:   (B, S, H,  hd)
GQA: H = KV * G. We reshape q to (B, S, KV, G, hd) and batch the KV heads —
the XLA analogue of the reference's KV-head duplication trick
(reference: conversion_scripts/llama/weight.py:150-157 ``dup_kv_weight``),
but without materializing duplicated KV.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: avoids NaN from 0*inf


_CHUNK = 512  # key-block size for the online-softmax path


def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  q_positions: jax.Array, kv_valid_len: jax.Array | None = None,
                  *, causal: bool = True,
                  window: jax.Array | None = None,
                  scale: float | None = None) -> jax.Array:
    """Grouped-query attention over an absolute-position KV buffer.

    q_positions: (B, S) int32 — absolute position of each query token.
    kv_valid_len: (B,) int32 — number of valid keys per row (rest is padding
        in a fixed-capacity cache). None = all T keys valid.
    causal: query at position p attends keys at cache indices <= p. The KV
        buffer is indexed by absolute position (index i holds the token at
        position i), which is what the slotted cache guarantees.
    window: () int32 (traced: a layer's own) or None — the query at
        position p attends only keys p - window < i <= p; 0 = no window.
    scale: what the scores are multiplied by where the model states it
        (``LlamaConfig.score_scale``); None = head_dim ** -0.5.

    Long key buffers take a flash-style chunked path: keys are consumed in
    ``_CHUNK`` blocks with an online softmax, so peak memory holds one
    (B, KV, G, S, chunk) score block instead of the full (…, S, T) score
    tensor — the difference between ~130 MB and ~1.1 GB of transient per
    layer for a 2048-token llama-2-7b prefill, which is what let the KV
    pool claim that HBM instead (round-4 sizing work).
    """
    T = k.shape[1]
    chunk = next((c for c in (_CHUNK, 256, 128) if T % c == 0), None)
    if T > _CHUNK and chunk is not None:
        return _gqa_chunked(q, k, v, q_positions, kv_valid_len,
                            causal=causal, chunk=chunk, window=window,
                            scale=scale)
    return _gqa_dense(q, k, v, q_positions, kv_valid_len, causal=causal,
                      window=window, scale=scale)


def _window_mask(mask, key_idx, q_positions, window):
    """``mask`` (B, S, T) with the keys behind each query's window out."""
    if window is None:
        return mask
    lo = jnp.where(window > 0, q_positions - window + 1, 0)     # (B, S)
    return mask & (key_idx[None, None, :] >= lo[:, :, None])


def _gqa_dense(q, k, v, q_positions, kv_valid_len, *, causal, window=None,
               scale=None):
    B, S, H, hd = q.shape
    _, T, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / (hd ** 0.5) if scale is None else scale

    qf = q.astype(jnp.float32).reshape(B, S, KV, G, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # scores: (B, KV, G, S, T)
    scores = jnp.einsum("bskgh,btkh->bkgst", qf, kf) * scale

    key_idx = jnp.arange(T, dtype=jnp.int32)
    mask = jnp.ones((B, S, T), dtype=bool)
    if causal:
        mask = key_idx[None, None, :] <= q_positions[:, :, None]
    if kv_valid_len is not None:
        mask = mask & (key_idx[None, None, :] < kv_valid_len[:, None, None])
    mask = _window_mask(mask, key_idx, q_positions, window)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, vf)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def _gqa_chunked(q, k, v, q_positions, kv_valid_len, *, causal, chunk,
                 window=None, scale=None):
    """Online-softmax over key blocks. Operands stay in their storage
    dtype into the MXU (f32 accumulation via preferred_element_type) —
    casting whole K/V to f32 up front doubled their HBM traffic."""
    B, S, H, hd = q.shape
    _, T, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / (hd ** 0.5) if scale is None else scale
    qr = q.reshape(B, S, KV, G, hd)
    n_blocks = T // chunk

    acc0 = jnp.zeros((B, KV, G, S, hd), jnp.float32)
    m0 = jnp.full((B, KV, G, S, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, S, 1), jnp.float32)

    def body(i, carry):
        acc, m, l = carry
        kb = jax.lax.dynamic_slice_in_dim(k, i * chunk, chunk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk, axis=1)
        scores = jnp.einsum("bskgh,btkh->bkgst", qr, kb,
                            preferred_element_type=jnp.float32) * scale
        key_idx = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        mask = jnp.ones((B, S, chunk), dtype=bool)
        if causal:
            mask = key_idx[None, None, :] <= q_positions[:, :, None]
        if kv_valid_len is not None:
            mask = mask & (key_idx[None, None, :]
                           < kv_valid_len[:, None, None])
        mask = _window_mask(mask, key_idx, q_positions, window)
        maskb = mask[:, None, None, :, :]
        scores = jnp.where(maskb, scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        # explicit zeroing (not exp of NEG-NEG): a fully-masked block
        # would otherwise contribute exp(0)=1 per masked key
        p = jnp.where(maskb, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bkgst,btkh->bkgsh", p, vb.astype(jnp.float32))
        return acc * alpha + pv, m_new, l

    acc, m, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)
    # (B, KV, G, S, hd) -> (B, S, H, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)
