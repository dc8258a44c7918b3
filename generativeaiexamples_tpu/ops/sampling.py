"""On-device token sampling: temperature / top-k / top-p / greedy.

Replaces the sampling config the reference passes as Triton tensors into the
TRT-LLM backend (reference: ensemble_models/llama/ensemble/config.pbtxt:27-117
``top_k``/``top_p``/``temperature``/``random_seed``; client defaults temp 1.0,
top_k 1, top_p 0 in model_server_client/trt_llm.py:68-74).

Everything is batched and static-shape: per-request knobs are vectors, the
"is greedy" decision is a ``where``, and top-k works for any k via a sort +
rank mask (no data-dependent shapes under jit).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30

# Seen/banned vocab masks live packed: 32 tokens per uint32 word (bit i of
# word w covers token w*32+i). A (B, V) bool mask is 1 byte per token in
# HBM; the packed form is 1 bit — 8x less mask traffic every decode step,
# and the fused sampler slices words per vocab tile instead of streaming
# byte-bools for the whole vocabulary.
MASK_BITS = 32


def mask_words(vocab_size: int) -> int:
    """uint32 words needed to cover ``vocab_size`` mask bits."""
    return -(-vocab_size // MASK_BITS)


def pack_mask(mask: jax.Array) -> jax.Array:
    """(…, V) bool -> (…, ceil(V/32)) uint32 bitfield (bit i of word w =
    token w*32+i). Tokens past V pad with 0 (never banned/seen)."""
    V = mask.shape[-1]
    Wn = mask_words(V)
    pad = Wn * MASK_BITS - V
    if pad:
        mask = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, pad)])
    bits = mask.reshape(*mask.shape[:-1], Wn, MASK_BITS).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(MASK_BITS, dtype=jnp.uint32))
    return (bits * weights).sum(-1).astype(jnp.uint32)


def unpack_mask(words: jax.Array, vocab_size: int) -> jax.Array:
    """(…, Wn) uint32 -> (…, vocab_size) bool. ``vocab_size`` may cover a
    slice (e.g. one vocab tile's words with vocab_size = tile)."""
    bits = (words[..., :, None]
            >> jnp.arange(MASK_BITS, dtype=jnp.uint32)) & jnp.uint32(1)
    flat = bits.reshape(*words.shape[:-1], -1)
    return flat[..., :vocab_size].astype(bool)


def pack_mask_np(mask: np.ndarray) -> np.ndarray:
    """numpy twin of pack_mask for host-side mask rendering (the engine
    builds bad-words/prefix-seen masks on the submitting thread)."""
    V = int(mask.shape[-1])
    Wn = mask_words(V)
    padded = np.zeros(mask.shape[:-1] + (Wn * MASK_BITS,), bool)
    padded[..., :V] = mask
    bits = padded.reshape(*mask.shape[:-1], Wn, MASK_BITS)
    weights = (np.uint32(1) << np.arange(MASK_BITS, dtype=np.uint32))
    return (bits.astype(np.uint32) * weights).sum(-1).astype(np.uint32)


def set_token_bits(words: jax.Array, tokens: jax.Array,
                   on: jax.Array) -> jax.Array:
    """Set each row's ``tokens[b]`` bit where ``on[b]`` (rows with
    on=False are untouched). words: (B, Wn) uint32, tokens/on: (B,).
    One word per row is touched, so a gather/modify/scatter is exact."""
    rows = jnp.arange(words.shape[0])
    wi = (tokens // MASK_BITS).astype(jnp.int32)
    bit = (on.astype(jnp.uint32)
           << (tokens % MASK_BITS).astype(jnp.uint32))
    return words.at[rows, wi].set(words[rows, wi] | bit)


@jax.named_scope("tail")
def sample(logits: jax.Array, key: jax.Array, temperature: jax.Array,
           top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Sample next tokens.

    logits:      (B, V) float
    temperature: (B,) — <= 0 means greedy
    top_k:       (B,) int — <= 0 means unlimited
    top_p:       (B,) float — <= 0 or >= 1 means unlimited
    Returns (B,) int32 token ids.
    """
    B, V = logits.shape
    lf = logits.astype(jnp.float32)
    greedy_ids = jnp.argmax(lf, axis=-1).astype(jnp.int32)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = lf / temp

    with jax.named_scope("tail_select"):   # the vocab-wide sort
        # Rank of each vocab entry (0 = best) via descending sort.
        sort_idx = jnp.argsort(-scaled, axis=-1)                     # (B, V)
        ranks = jnp.zeros_like(sort_idx).at[
            jnp.arange(B)[:, None], sort_idx
        ].set(jnp.broadcast_to(jnp.arange(V), (B, V)))

        k = jnp.where(top_k[:, None] <= 0, V, top_k[:, None])
        keep = ranks < k

        # top-p: keep the smallest prefix of sorted probs with cumsum >= p.
        sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
        sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(sorted_probs, axis=-1)
        p = jnp.where((top_p[:, None] <= 0) | (top_p[:, None] >= 1.0),
                      1.0, top_p[:, None])
        # token at sorted position j survives if the cumulative mass
        # *before* it is < p (so the first token always survives).
        sorted_keep_p = (cum - sorted_probs) < p
        keep_p = jnp.zeros_like(keep).at[
            jnp.arange(B)[:, None], sort_idx
        ].set(sorted_keep_p)

    masked = jnp.where(keep & keep_p, scaled, NEG_INF)
    sampled = jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)

    is_greedy = (temperature <= 0) | (top_k == 1)
    return jnp.where(is_greedy, greedy_ids, sampled)


def seen_mask(token_history: jax.Array, valid_len: jax.Array,
              vocab_size: int) -> jax.Array:
    """(B, V) bool mask of tokens present in each row's history.

    token_history: (B, T) int32, valid_len: (B,) valid prefix per row.
    """
    B, T = token_history.shape
    pos_valid = jnp.arange(T)[None, :] < valid_len[:, None]
    return jnp.zeros((B, vocab_size), bool).at[
        jnp.arange(B)[:, None], token_history
    ].max(pos_valid)


@jax.named_scope("tail")
def apply_repetition_penalty(logits: jax.Array, seen: jax.Array,
                             penalty: jax.Array) -> jax.Array:
    """CTRL-style repetition penalty over already-seen tokens.

    seen: (B, V) bool (from ``seen_mask`` or maintained incrementally),
    penalty: (B,) — 1.0 is a no-op.
    Parity with the reference's ``repetition_penalty`` ensemble tensor
    (ensemble/config.pbtxt).
    """
    pen = penalty[:, None]
    lf = logits.astype(jnp.float32)
    penalized = jnp.where(lf > 0, lf / pen, lf * pen)
    return jnp.where(seen, penalized, lf).astype(logits.dtype)
