"""Learned sparse attention's two new steps, and its masked reads.

A FULL layer of a model with ``index_topk`` (models/configs.py) scores
every cached token for a query with its indexer,

    I(t, s) = sum_j w_j(t) relu(q_j(t) . k(s)),     j over the index heads

and attends the ``index_topk`` causal positions that score highest
(``index_scores``, ``topk_keep``). The set is a boolean KEEP MASK over
the keys, the form in which it rides the layer scan to the shared layers
above (models/llama.py ``_run_stack``) and meets the attention: the
reads here are MASKED — every cached row of the context is read and the
unchosen keys are masked out of the softmax, the same numbers as a
gather of the chosen rows at the dense read's bytes and operations
(``absorbed_masked`` for the verify forward and the decode step off the
chip, ``expanded_masked`` for the tokens given; the decode step on the
chip and a chunk's prefix go through the Pallas kernels of
ops/latent_attention.py and ops/chunk_attention.py with the mask as one
more operand).

The selection is EXACT and by token: ``topk_keep`` finds the k-th
largest score of a row by bisection over the scores' bit patterns — 32
counting passes, no sort — keeps what lies above it and, of the scores
equal to it, the lowest positions, which is the set ``lax.top_k`` returns
(a sort of (512, 16k) scores a full layer a chunk is what it replaces).
Index scores are products of the operands as stored (bf16) accumulated
in float32; relu, head weights and their sum in float32 on the vector
unit (a float32 matmul at the default precision would round them to
bf16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged_attention import NEG

_SCORES_AT_ONCE = 256 << 20   # bytes of (B, S, heads, T) float32 scores


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """q (B, S, Hi, d) index queries, w (B, S, Hi) float32 head weights
    (scale factors folded in), keys (B, T, d) the cached index keys:
    I (B, S, T) float32. Every head at once where (B, S, Hi, T) is small
    (a decode step), else a head at a time — never (heads x chunk x
    context) at once."""
    B, S, H, _ = q.shape
    T = keys.shape[1]
    w = w.astype(jnp.float32)
    if B * S * H * T * 4 <= _SCORES_AT_ONCE:
        s = jnp.einsum("bshd,btd->bsht", q, keys,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(s) * w[..., None], axis=2)

    def head(acc, xs):
        qj, wj = xs                                 # (B, S, d), (B, S)
        s = jnp.einsum("bsd,btd->bst", qj, keys,
                       preferred_element_type=jnp.float32)
        return acc + jax.nn.relu(s) * wj[..., None], None

    acc, _ = jax.lax.scan(head, jnp.zeros((B, S, T), jnp.float32),
                          (jnp.moveaxis(q, 2, 0), jnp.moveaxis(w, 2, 0)))
    return acc


def _sortable(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order
    (-0.0 made +0.0 first: the two compare equal as floats)."""
    b = jax.lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def topk_keep(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """The ``k`` largest of each row's ``valid`` scores as a mask over
    the last axis: exactly ``k`` Trues a row that has that many valid
    entries (all of them otherwise), ties to the LOWER position — the set
    ``lax.top_k`` picks. scores float32 (finite where valid; anything
    elsewhere), valid bool of the same shape."""
    if scores.shape[-1] <= k:
        return valid
    key = jnp.where(valid, _sortable(scores.astype(jnp.float32)),
                    jnp.uint32(0))              # below every valid key

    def bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.uint32(1),
                                       (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, prefix)

    # the k-th largest key: the largest v with at least k keys >= v
    # (0 where the row has fewer than k valid entries)
    kth = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above = key > kth
    tie = (key == kth) & valid
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    first = jnp.cumsum(tie.astype(jnp.int32), axis=-1) <= need
    return (above | (tie & first)) & valid


def _softmax_sum(scores, keep, values, eq: str, dtype):
    """Masked softmax over the last axis and the weighted sum: masked
    scores to ``NEG`` before the maximum, masked probabilities to 0
    explicitly, the probabilities cast to the value dtype before the
    product, float32 accumulation (ops/chunk_attention.py's update, in
    one piece). A query that keeps no key gets zeros."""
    s = jnp.where(keep, scores, NEG)
    p = jnp.where(keep, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    return jnp.einsum(eq, p.astype(values.dtype), values,
                      preferred_element_type=jnp.float32).astype(dtype)


def absorbed_masked(q_c: jax.Array, q_r: jax.Array, c: jax.Array,
                    k_r: jax.Array, keep: jax.Array, scale: float
                    ) -> jax.Array:
    """Absorbed latent attention over the kept keys: q_c (B, S, H, R)
    the absorbed query, q_r (B, S, H, rope); c (B, T, R) and k_r (B, T,
    rope) the cached rows, each key AND value; keep (B, S, T) bool, the
    same for all heads. Operands as stored into the matmuls, float32
    accumulation. Rows no query keeps are zeroed, not only their
    probabilities (the trash page may hold anything: 0 x NaN = NaN).
    Returns the latent-space sum (B, S, H, R)."""
    kept = jnp.any(keep, axis=1)                            # (B, T)
    c = jnp.where(kept[..., None], c, 0).astype(q_c.dtype)
    k_r = jnp.where(kept[..., None], k_r, 0).astype(q_c.dtype)
    scores = (jnp.einsum("bshc,btc->bhst", q_c, c,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", q_r, k_r,
                           preferred_element_type=jnp.float32)) * scale
    return _softmax_sum(scores, keep[:, None], c, "bhst,btc->bshc",
                        q_c.dtype)


def expanded_masked(q_nope: jax.Array, q_r: jax.Array, k_nope: jax.Array,
                    k_r: jax.Array, v: jax.Array, keep: jax.Array,
                    scale: float) -> jax.Array:
    """Expanded latent attention over the kept keys: q_nope (B, S, H,
    nope), q_r (B, S, H, rope); k_nope (B, T, H, nope), k_r (B, T, rope),
    v (B, T, H, vd); keep (B, S, T). Returns (B, S, H, vd)."""
    scores = (jnp.einsum("bshj,bthj->bhst", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", q_r, k_r,
                           preferred_element_type=jnp.float32)) * scale
    return _softmax_sum(scores, keep[:, None], v, "bhst,bthv->bshv",
                        q_nope.dtype)
