"""Vocab-tiled fused unembed + sampling for the decode round.

The former decode tail materialized, every step, a full ``(B, V)`` f32
logit tensor, a second ``(B, V)`` penalized copy, and two ``(B, V)``
bool masks — then ran a full vocab sort. ``PROFILE_r06.json`` attributes
0.378 ms/step to that tail on a model whose matmul floor is 0.001 ms:
on an HBM-bound decode step every one of those bytes is tokens/s lost.

This module streams the ``lm_head`` in vocab tiles instead and folds the
whole penalize→mask→sample chain into each tile, carrying only O(B·K)
running state across tiles:

- repetition penalty and bad-words masks are applied per tile, read from
  uint32 *bitfield* masks (``ops/sampling.py pack_mask``: 1 bit per
  token, sliced per tile — no (B, V) bool ever exists);
- greedy is a running argmax (``_greedy_stream``);
- sampling uses the Gumbel-max formulation (``argmax(scaled + gumbel)``
  == categorical) with per-tile noise keyed by ``fold_in(key, tile)``,
  plus a running top-``cand_k`` of raw scaled values (the Gumbel-top-k
  carry) so top-k / top-p truncation can be resolved AFTER the stream
  from the candidate set alone, with an exact running logsumexp for the
  top-p mass. Full penalized logits never exist in any buffer.

Which stream runs where. The SCAN of head slices above (``_greedy_stream``,
``_sample_stream``, ``_verify_stream``) serves int4 and grouped heads,
the tp-sharded streams, the verify stream of speculative decoding and
every backend but the TPU. Over a head ``head_argmax.supported`` takes (a
per-column int8, raw or tied head), off-mesh, on a TPU, the engine's
decode tails are ONE Pallas pass over the stored head instead
(``ops/head_argmax.py``): greedy ``greedy_head_argmax`` — penalties and
the running argmax under the weight stream, the same tokens — and
sampled :func:`head_kernel_sample` — ``sampled_head_stream`` carries the
logsumexp, the Gumbel-max winner and the greedy winner under the weight
stream and writes the rung's penalised scaled logits once, as ONE
``(rows, V)`` float32 array (1.6 % of a 256k int8 head's bytes at 16
rows), over whose tiles the candidate merge below then runs. Its noise
is one field keyed by (key, row, token id) (:func:`row_gumbel`), so its
tokens for a seed are another realisation of the same distribution than
the scan's; its oracle is :func:`sample_reference_rows`.

The candidate merge *selects before it sorts* (``_merge_tile``): a tile
of thousands brings a handful of entrants, so each tile is reduced to
the few largest elements of each of its strided buckets, only carry +
winners are sorted, and one count — no element outside the winners
reaches the new ``cand_k``-th value — proves the small sort lost
nothing; where it fails the whole tile is sorted under a ``lax.cond``,
as every tile was before. The carry is bit for bit the whole sort's
either way, so every exactness statement below holds unchanged; the
sampled streams report how often the whole sort ran (``stats``).

Tensor-parallel serving (``fused_unembed_sample_tp`` /
``fused_verify_sample_tp``): the same stream runs SHARDED over the
mesh's ``tp`` axis — each chip streams only its own vocab shard's
32-aligned tiles (its slice of the tp-sharded ``lm_head``), folds
penalties/masks locally against the replicated bitfields, and carries
the identical running state. At the end of the stream ONE small
cross-chip merge combines the per-shard carries: an ``all_gather`` of
the ``(B, cand_k)`` candidate rows (stable top-k over the shard-ordered
concatenation — ties keep ascending vocab id, exactly the single-chip
tie rule), a running-argmax reduce for the greedy/Gumbel-max winners,
and a ``logsumexp`` fold of the per-shard mass. ``(B, V)`` never exists
on ANY chip; the collective payload is O(B·cand_k), not O(B·V).

Exactness: greedy, pure temperature sampling (no truncation), and any
top-k/top-p whose kept prefix fits in ``cand_k`` candidates are
*sample-exact* against :func:`sample_reference_tiled` (the materialized
penalize-then-sample oracle sharing the same per-tile noise layout; the
kernel tail against :func:`sample_reference_rows`, likewise) —
pinned by tier-1 tests, sharded paths included (the tp stream consumes
the same per-tile Gumbel field, indexed by GLOBAL tile number). A top-p
set wider than ``cand_k`` tokens is truncated at ``cand_k`` (vLLM-style
candidate cap; raise ``SAMPLER_CAND_K`` to widen).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from . import head_argmax
from .sampling import MASK_BITS, NEG_INF, unpack_mask

DEFAULT_TILE = 4096
# The sampled and verify streams' tile. Wider than the greedy scan's:
# a sampled tile pays a Gumbel field, a logsumexp, three running maxima
# and a candidate merge on top of its slice of the head, and on a v5e
# the 256000-column stream of 16 rows read 3.32 ms a step without any
# merge at 64 tiles of 4000, 2.61 at 20 of 12800, 2.55 at 8 of 32000
# (chip, PR 33); a (16, 16000) float32 transient is 1 MB.
DEFAULT_SAMPLE_TILE = 16384
DEFAULT_CAND_K = 64
_LANES = 128      # a vector register's lanes: the bucket count's unit
_PASSES = 4       # winners taken from each bucket of a tile
_NO_ID = jnp.iinfo(jnp.int32).max


def default_tile(sampled: bool = False) -> int:
    """The tile target: ``SAMPLER_TILE`` where set (both streams), else
    the greedy stream's default or the sampled streams' own."""
    return int(os.environ.get("SAMPLER_TILE", str(
        DEFAULT_SAMPLE_TILE if sampled else DEFAULT_TILE)))


def default_cand_k() -> int:
    return int(os.environ.get("SAMPLER_CAND_K", str(DEFAULT_CAND_K)))


def choose_tile(vocab_size: int, target: int | None = None, *,
                sampled: bool = False) -> int:
    """The vocabulary tile of a stream (``sampled``: the sampling and
    verify streams, whose default target is their own — the noise is
    laid out by tile, so an oracle takes the tile of the stream it
    checks). Largest divisor of ``vocab_size`` that is <= ``target`` and a
    multiple of 32 (so each tile covers whole mask words and the word
    slice is a contiguous dynamic_slice, not a gather) — or, where that
    is under an eighth of the target, the smallest such divisor above
    it (see below). Falls back to a
    single whole-vocab tile (tiny or 32-indivisible vocabs only — real
    vocabs are 32-divisible and always admit a 32-aligned divisor)."""
    target = max(1, min(target or default_tile(sampled), vocab_size))
    if vocab_size % MASK_BITS == 0:
        for t in range(target - target % MASK_BITS, 0, -MASK_BITS):
            if vocab_size % t == 0:
                break
        # A vocabulary whose only small divisors are tiny (151936 = 128 x
        # 1187, a prime: 1187 tiles of 128 a step, 5.4 ms of a 13 ms
        # decode step where the head's bytes take 0.5; chip, PR 28) takes
        # the smallest aligned divisor ABOVE the target instead, up to
        # 16 x it: a (rows, tile) float32 transient of a few MB.
        if t * 8 < target:
            for up in range(target + MASK_BITS - target % MASK_BITS,
                            min(16 * target, vocab_size) + 1, MASK_BITS):
                if vocab_size % up == 0:
                    return up
        return t
    return vocab_size


def tp_shardable(vocab_size: int, n_shards: int) -> bool:
    """Whether the vocab stream can shard over ``n_shards`` chips: each
    shard must own an equal slice whose tiles still cover whole mask
    words (the per-tile bitfield slice stays a contiguous
    dynamic_slice). Real vocabs divide cleanly for any power-of-two tp;
    failing geometries keep the materialized tail (the engine logs an
    ``engine_feature_downgrade``)."""
    return (n_shards > 1 and vocab_size % n_shards == 0
            and (vocab_size // n_shards) % MASK_BITS == 0)


def _slice_tile_mask(words: jax.Array, t0: jax.Array, tile: int,
                     batch: int) -> jax.Array:
    """Bool mask (B, tile) for tokens [t0, t0+tile) out of a (B, Wn) or
    (Wn,) uint32 bitfield. Requires tile % 32 == 0 OR a single tile
    covering the whole vocab (choose_tile guarantees one of the two)."""
    if words.ndim == 1:
        words = words[None, :]
    if tile % MASK_BITS == 0:
        w0 = t0 // MASK_BITS
        ws = jax.lax.dynamic_slice_in_dim(words, w0, tile // MASK_BITS,
                                          axis=1)
        m = unpack_mask(ws, tile)
    else:  # single whole-vocab tile (tiny/odd vocab fallback)
        m = unpack_mask(words, tile)
    return jnp.broadcast_to(m, (batch, tile))


def _penalize_tile(logits, t0, tile, *, seen_words, banned_words, rep_pen,
                   ban_tok=None, ban_hit=None):
    """Fold repetition penalty + bad-words masks into one vocab tile.
    ``logits``: (B, tile) f32 for tokens [t0, t0+tile). ``ban_tok`` /
    ``ban_hit``: optional (B, S) sequence-ban tails (mask token
    ban_tok[b, s] wherever ban_hit[b, s]) — the multi-token bad-words
    rule, resolved per tile by an id compare instead of a vocab scatter."""
    B = logits.shape[0]
    lf = logits.astype(jnp.float32)
    seen = _slice_tile_mask(seen_words, t0, tile, B)
    pen = rep_pen[:, None]
    lf = jnp.where(seen, jnp.where(lf > 0, lf / pen, lf * pen), lf)
    banned = _slice_tile_mask(banned_words, t0, tile, B)
    lf = jnp.where(banned, NEG_INF, lf)
    if ban_tok is not None:
        ids = t0 + jnp.arange(tile, dtype=jnp.int32)
        hit = jnp.any((ids[None, :, None] == ban_tok[:, None, :])
                      & ban_hit[:, None, :], axis=-1)
        lf = jnp.where(hit, NEG_INF, lf)
    return lf


# ------------------------------------------------------- candidate merge
#
# The running top-``cand_k`` of the sampling and verify streams. The carry
# ``(cv, ci, cp)`` holds the largest raw scaled values seen so far in
# descending order with their vocabulary ids and Gumbel perturbations;
# among equal values the carry comes before the tile and a lower id
# before a higher one: the order of the oracle's stable argsort.


def _full_merge(cv, ci, cp, scaled, idb, pert, cand_k: int):
    """Sort the carry and the whole tile: ``lax.top_k`` over the
    carry-first concatenation, stable, so the tie order is the
    concatenation's. Exact by definition, and a sort of ``cand_k +
    tile`` numbers a row to keep ``cand_k`` of them."""
    av = jnp.concatenate([cv, scaled], axis=-1)
    ai = jnp.concatenate([ci, idb], axis=-1)
    ap = jnp.concatenate([cp, pert], axis=-1)
    cv, sel = jax.lax.top_k(av, cand_k)
    return (cv, jnp.take_along_axis(ai, sel, axis=-1),
            jnp.take_along_axis(ap, sel, axis=-1))


def select_plan(tile: int, cand_k: int) -> tuple[int, int] | None:
    """``(buckets, passes)`` of the pre-selection for a tile of this
    width, or ``None`` where the tile is no wider than a few ``cand_k``
    and sorting it whole IS the small sort. From shapes known at trace
    time only: whole lanes of buckets, at least two a candidate, so that
    a stream's first tile (all ``cand_k`` entrants at once) seldom puts
    more than ``_PASSES`` of them into one."""
    buckets = -(-2 * cand_k // _LANES) * _LANES
    small = cand_k + _PASSES * buckets
    return (buckets, _PASSES) if tile >= 4 * small else None


def _bucket_winner(a, b):
    """The larger value of two (value, id, perturbation) triples, the
    lower id among equals: the reducer of a bucket's maximum."""
    (av, ai, ap), (bv, bi, bp) = a, b
    first = (av > bv) | ((av == bv) & (ai < bi))
    return (jnp.where(first, av, bv), jnp.where(first, ai, bi),
            jnp.where(first, ap, bp))


def _merge_tile(cv, ci, cp, scaled, idb, pert, cand_k: int):
    """One tile into the carry: ``((cv, ci, cp), resorted)`` with
    ``resorted`` a bool scalar, whether this tile took the whole sort.

    *Select before sorting.* A tile of thousands brings a handful of
    entrants (tile ``t`` of a stream about ``cand_k / t`` a row), so the
    tile is first cut into ``buckets`` strided buckets (element ``j``
    lies in bucket ``j % buckets``: a reduction over whole vregs, no
    lane crosses another) and each bucket gives its ``passes`` largest
    elements with their ids and perturbations; only the carry and those
    winners are ordered, by a two-key sort on (value descending, id
    ascending) — the carry's ids are all below the tile's, so this is
    the carry-first order of :func:`_full_merge`. *The proof, one count:*
    with ``thr2`` the new ``cand_k``-th value, the small sort lost
    nothing iff no element of the tile outside the winners is
    ``>= thr2`` (an equal one might have won the tie). Where any row
    fails it — several entrants in one bucket: the first tiles of a
    stream, where nearly everything enters, and ties — the whole batch
    takes :func:`_full_merge` under a ``lax.cond``. Either way the carry
    is bit for bit what :func:`_full_merge` alone leaves."""
    R, tile = scaled.shape
    plan = select_plan(tile, cand_k)
    if plan is None:
        return _full_merge(cv, ci, cp, scaled, idb, pert,
                           cand_k), jnp.bool_(False)
    buckets, passes = plan
    # element j of the tile lies in bucket j % buckets; a ragged last
    # stride is filled with what never wins
    depth = -(-tile // buckets)
    fill = ((0, 0), (0, depth * buckets - tile))
    x, i3, p3 = (jnp.pad(a, fill, constant_values=c).reshape(
        R, depth, buckets) for a, c in (
            (scaled, -jnp.inf), (idb, _NO_ID), (pert, -jnp.inf)))
    init = (jnp.float32(-jnp.inf), jnp.int32(_NO_ID),
            jnp.float32(-jnp.inf))
    won = []
    for n in range(passes):
        if n:   # take the last winner out of its bucket
            x = jnp.where(i3 == won[-1][1][:, None, :], -jnp.inf, x)
        won.append(jax.lax.reduce((x, i3, p3), init, _bucket_winner, (1,)))
    wv = jnp.concatenate([w[0] for w in won], axis=-1)
    # the negated value ascending is the value descending, and lax.sort
    # holds -0.0 equal to 0.0 as top_k's comparison does
    nv, ni, npert = jax.lax.sort(
        (jnp.concatenate([-cv, -wv], axis=-1),
         jnp.concatenate([ci] + [w[1] for w in won], axis=-1),
         jnp.concatenate([cp] + [w[2] for w in won], axis=-1)),
        dimension=1, num_keys=2)
    fast = (-nv[:, :cand_k], ni[:, :cand_k], npert[:, :cand_k])
    thr2 = fast[0][:, -1:]
    lost = (jnp.sum(scaled >= thr2, axis=-1)
            != jnp.sum(wv >= thr2, axis=-1))
    resorted = jnp.any(lost)
    return jax.lax.cond(
        resorted,
        lambda: _full_merge(cv, ci, cp, scaled, idb, pert, cand_k),
        lambda: fast), resorted


# --------------------------------------------------------- tile streams
#
# The scan bodies shared by the single-chip and tp-sharded paths. Each
# takes ``masked_tile(t) -> (t0, lf)`` producing the PENALIZED (B, tile)
# logits for local tile ``t`` with GLOBAL token offset ``t0``, and
# ``noise_tile(t)`` mapping the local tile number to the global tile
# index the Gumbel field is keyed on — so a shard streaming tiles
# [k, k+n) consumes exactly the noise the whole-vocab stream would have
# at those tiles, and sharded sampling stays sample-exact.


def _greedy_stream(masked_tile, n_tiles: int, tile: int, B: int):
    """Running argmax over the tile stream: (best value, best id), ties
    keeping the lowest vocab id (first tile wins; within a tile argmax
    picks the lowest index)."""

    def body(carry, t):
        best, best_id = carry
        t0, lf = masked_tile(t)
        ids = t0 + jnp.arange(tile, dtype=jnp.int32)
        tbest = jnp.max(lf, axis=-1)
        tid = jnp.take(ids, jnp.argmax(lf, axis=-1))
        better = tbest > best
        return (jnp.where(better, tbest, best),
                jnp.where(better, tid, best_id)), None

    init = (jnp.full((B,), -jnp.inf, jnp.float32),
            jnp.zeros((B,), jnp.int32))
    (best, best_id), _ = jax.lax.scan(
        body, init, jnp.arange(n_tiles, dtype=jnp.int32))
    return best, best_id


def _sample_stream(masked_tile, noise_tile, key, tf, n_tiles: int,
                   tile: int, B: int, cand_k: int):
    """Sampling carry over the tile stream. Returns
    ``(cv, ci, cp, lse, bpert, bpid, braw, brid, resort)``: the
    top-``cand_k`` raw scaled values with ids + Gumbel perturbations, the
    running logsumexp, the untruncated Gumbel-max winner, the running
    greedy argmax (for temp<=0 / top_k==1 rows of the batch), and the
    share of the tiles whose merge took the whole sort (0..1)."""

    def body(carry, t):
        cv, ci, cp, lse, bpert, bpid, braw, brid, n_resort = carry
        t0, lf = masked_tile(t)
        ids = t0 + jnp.arange(tile, dtype=jnp.int32)
        idb = jnp.broadcast_to(ids, lf.shape)
        scaled = lf / tf
        g = jax.random.gumbel(jax.random.fold_in(key, noise_tile(t)),
                              (B, tile), jnp.float32)
        pert = scaled + g
        # running logsumexp of the scaled logits (exact top-p mass)
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(scaled, axis=-1))
        # running untruncated Gumbel-max (the pure-categorical case)
        tb = jnp.max(pert, axis=-1)
        ti = jnp.take_along_axis(idb, jnp.argmax(pert, -1)[:, None],
                                 axis=1)[:, 0]
        up = tb > bpert
        bpert, bpid = jnp.where(up, tb, bpert), jnp.where(up, ti, bpid)
        # running greedy argmax (temp<=0 / top_k==1 members of the batch)
        rb = jnp.max(lf, axis=-1)
        ri = jnp.take_along_axis(idb, jnp.argmax(lf, -1)[:, None],
                                 axis=1)[:, 0]
        ug = rb > braw
        braw, brid = jnp.where(ug, rb, braw), jnp.where(ug, ri, brid)
        # candidate merge: keep the top-cand_k raw scaled values seen so
        # far, with their ids and Gumbel perturbations
        with jax.named_scope("tail_select"):
            (cv, ci, cp), resorted = _merge_tile(cv, ci, cp, scaled, idb,
                                                 pert, cand_k)
        return (cv, ci, cp, lse, bpert, bpid, braw, brid,
                n_resort + resorted), None

    init = (jnp.full((B, cand_k), -jnp.inf, jnp.float32),
            jnp.zeros((B, cand_k), jnp.int32),
            jnp.full((B, cand_k), -jnp.inf, jnp.float32),
            jnp.full((B,), -jnp.inf, jnp.float32),
            jnp.full((B,), -jnp.inf, jnp.float32),
            jnp.zeros((B,), jnp.int32),
            jnp.full((B,), -jnp.inf, jnp.float32),
            jnp.zeros((B,), jnp.int32),
            jnp.int32(0))
    carry, _ = jax.lax.scan(body, init,
                            jnp.arange(n_tiles, dtype=jnp.int32))
    return carry[:-1] + (carry[-1] / n_tiles,)


def _verify_stream(masked_tile, noise_tile, key, tf, draft_ids,
                   n_tiles: int, tile: int, R: int, cand_k: int):
    """Verification carry over the tile stream. Returns
    ``(cv, ci, cp, lse, braw, brid, sd, sfound, npert, npid)`` — the
    sampling carry pieces plus the draft token's accumulated scaled
    logit (``sd``; the draft lives in exactly one tile of one shard, so
    a masked sum — and, sharded, a psum — is a gather), whether the
    draft id was seen at all, and the draft-masked running Gumbel-max
    (the untruncated residual sample)."""

    def body(carry, t):
        (cv, ci, cp, lse, braw, brid, sd, sfound, npert, npid) = carry
        t0, lf = masked_tile(t)
        ids = t0 + jnp.arange(tile, dtype=jnp.int32)
        idb = jnp.broadcast_to(ids, lf.shape)
        scaled = lf / tf
        g = jax.random.gumbel(jax.random.fold_in(key, noise_tile(t)),
                              (R, tile), jnp.float32)
        pert = scaled + g
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(scaled, axis=-1))
        # running greedy argmax (greedy rows + the greedy accept test)
        rb = jnp.max(lf, axis=-1)
        ri = jnp.take_along_axis(idb, jnp.argmax(lf, -1)[:, None],
                                 axis=1)[:, 0]
        ug = rb > braw
        braw, brid = jnp.where(ug, rb, braw), jnp.where(ug, ri, brid)
        # the draft token's scaled logit (each id lives in exactly one
        # tile, so a masked sum is a gather)
        dm = idb == draft_ids[:, None]
        sd = sd + jnp.sum(jnp.where(dm, scaled, 0.0), axis=-1)
        sfound = sfound | jnp.any(dm, axis=-1)
        # running Gumbel-argmax with the draft masked: the UNTRUNCATED
        # residual sample (draft -1 matches nothing -> plain sample)
        pert_nod = jnp.where(dm, -jnp.inf, pert)
        nb = jnp.max(pert_nod, axis=-1)
        ni = jnp.take_along_axis(idb, jnp.argmax(pert_nod, -1)[:, None],
                                 axis=1)[:, 0]
        un = nb > npert
        npert, npid = jnp.where(un, nb, npert), jnp.where(un, ni, npid)
        # candidate merge (the sampling stream's)
        with jax.named_scope("tail_select"):
            (cv, ci, cp), _ = _merge_tile(cv, ci, cp, scaled, idb, pert,
                                          cand_k)
        return (cv, ci, cp, lse, braw, brid, sd, sfound, npert, npid), None

    init = (jnp.full((R, cand_k), -jnp.inf, jnp.float32),
            jnp.zeros((R, cand_k), jnp.int32),
            jnp.full((R, cand_k), -jnp.inf, jnp.float32),
            jnp.full((R,), -jnp.inf, jnp.float32),
            jnp.full((R,), -jnp.inf, jnp.float32),
            jnp.zeros((R,), jnp.int32),
            jnp.zeros((R,), jnp.float32),
            jnp.zeros((R,), bool),
            jnp.full((R,), -jnp.inf, jnp.float32),
            jnp.zeros((R,), jnp.int32))
    carry, _ = jax.lax.scan(body, init,
                            jnp.arange(n_tiles, dtype=jnp.int32))
    return carry


# ------------------------------------------------------------ finalizers


@jax.named_scope("tail_select")
def _finalize_sample(cv, ci, cp, lse, bpid, brid, *, temp, top_k, top_p,
                     vocab_size: int, cand_k: int) -> jax.Array:
    """Resolve top-k/top-p truncation from the candidate carry alone and
    pick the sampled (or greedy) token per row."""
    V = vocab_size
    kk = jnp.where(top_k <= 0, V, top_k)
    p = jnp.where((top_p <= 0) | (top_p >= 1.0), 1.0, top_p)
    # kept set == a prefix of the value-sorted order (both truncations
    # keep prefixes): token at sorted position j survives if j < k and
    # the cumulative mass before it is < p — same rule as
    # ops.sampling.sample, evaluated on the candidate prefix.
    probs = jnp.exp(cv - lse[:, None])
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    keep = ((jnp.arange(cand_k)[None, :] < kk[:, None])
            & (cum_before < p[:, None]))
    kept_pert = jnp.where(keep, cp, -jnp.inf)
    trunc_tok = jnp.take_along_axis(
        ci, jnp.argmax(kept_pert, -1)[:, None], axis=1)[:, 0]
    untruncated = (kk >= V) & (p >= 1.0)
    sampled = jnp.where(untruncated, bpid, trunc_tok)
    is_greedy = (temp <= 0) | (top_k == 1)
    return jnp.where(is_greedy, brid, sampled).astype(jnp.int32)


@jax.named_scope("tail_select")
def _finalize_verify(cv, ci, cp, lse, brid, sd, sfound, npid, *, u, temp,
                     top_k, top_p, draft_ids, vocab_size: int,
                     cand_k: int) -> tuple[jax.Array, jax.Array]:
    """Resolve the per-row accept/resample verdicts from the carry."""
    sd = jnp.where(sfound, sd, -jnp.inf)
    V = vocab_size
    kk = jnp.where(top_k <= 0, V, top_k)
    p = jnp.where((top_p <= 0) | (top_p >= 1.0), 1.0, top_p)
    probs = jnp.exp(cv - lse[:, None])
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    keep = ((jnp.arange(cand_k)[None, :] < kk[:, None])
            & (cum_before < p[:, None]))
    # Truncated target: normalizer over the KEPT candidates only; the
    # draft's probability is exp(scaled_d - Z_kept) when the draft made
    # the kept set, else exactly 0.
    z_kept = jax.nn.logsumexp(jnp.where(keep, cv, -jnp.inf), axis=-1)
    is_draft = ci == draft_ids[:, None]
    draft_kept = jnp.any(is_draft & keep, axis=-1)
    p_trunc = jnp.where(draft_kept, jnp.exp(sd - z_kept), 0.0)
    # Truncated residual: Gumbel-argmax over kept candidates minus the
    # draft.  A kept set of exactly {draft} has an empty residual — but
    # then p(draft) == 1 and the residual is never consumed; fall back
    # to the draft itself so a float-rounded reject can't emit ci[0].
    kept_res = keep & ~is_draft
    res_pert = jnp.where(kept_res, cp, -jnp.inf)
    trunc_res = jnp.take_along_axis(
        ci, jnp.argmax(res_pert, -1)[:, None], axis=1)[:, 0]
    trunc_res = jnp.where(jnp.any(kept_res, axis=-1), trunc_res,
                          draft_ids)
    untruncated = (kk >= V) & (p >= 1.0)
    p_acc = jnp.where(untruncated, jnp.exp(sd - lse), p_trunc)
    resample = jnp.where(untruncated, npid, trunc_res)
    accept = u < p_acc
    out_tok = resample.astype(jnp.int32)
    is_greedy = (temp <= 0) | (top_k == 1)
    accept = jnp.where(is_greedy, draft_ids == brid, accept)
    out_tok = jnp.where(is_greedy, brid, out_tok)
    return accept, out_tok


# ----------------------------------------------------- cross-chip merges


def _merge_running_max(axis: str, val, idx):
    """Combine per-shard running-argmax carries: strictly-greater wins,
    ties keep the LOWEST shard — shard order == ascending vocab ranges,
    so the global tie rule stays "lowest vocab id", identical to the
    single-chip stream."""
    vs = jax.lax.all_gather(val, axis)          # (n_shards, B)
    ids = jax.lax.all_gather(idx, axis)
    win = jnp.argmax(vs, axis=0)                # first max -> lowest shard
    take = lambda a: jnp.take_along_axis(a, win[None, :], axis=0)[0]  # noqa: E731
    return take(vs), take(ids)


@jax.named_scope("tail_select")
def _merge_candidates(axis: str, cv, ci, cp, cand_k: int):
    """Combine per-shard candidate carries: gather the (B, cand_k) rows
    shard-major and re-take the stable top-k. Each global top-cand_k
    element is within its own shard's top-cand_k, so the merge is exact;
    stable top_k over the shard-ordered concatenation keeps ascending-id
    tie order, matching the single-chip carry-first rule. This gather is
    the ONLY place candidate state crosses the interconnect: O(B·cand_k)
    per merge, never O(B·V)."""
    gv = jax.lax.all_gather(cv, axis)           # (n_shards, B, cand_k)
    gi = jax.lax.all_gather(ci, axis)
    gp = jax.lax.all_gather(cp, axis)
    flat = lambda a: jnp.moveaxis(a, 0, 1).reshape(  # noqa: E731
        a.shape[1], -1)
    av, ai, ap = flat(gv), flat(gi), flat(gp)
    cv2, sel = jax.lax.top_k(av, cand_k)
    return (cv2, jnp.take_along_axis(ai, sel, axis=-1),
            jnp.take_along_axis(ap, sel, axis=-1))


def _merge_lse(axis: str, lse):
    return jax.nn.logsumexp(jax.lax.all_gather(lse, axis), axis=0)


def _shard_geometry(mesh, axis: str, vocab_size: int, tile: int | None,
                    sampled: bool) -> tuple[int, int, int]:
    n_shards = int(mesh.shape[axis])
    if not tp_shardable(vocab_size, n_shards):
        raise ValueError(
            f"vocab_size={vocab_size} cannot shard over {axis}="
            f"{n_shards} in whole 32-token mask words")
    v_local = vocab_size // n_shards
    t = choose_tile(v_local, tile, sampled=sampled)
    return n_shards, v_local, t


# ------------------------------------------------------------ public API


@jax.named_scope("tail")
def fused_unembed_sample(tile_logits_fn, vocab_size: int, *, key, temp,
                         top_k, top_p, rep_pen, seen_words, banned_words,
                         ban_tok=None, ban_hit=None, greedy: bool = False,
                         tile: int | None = None,
                         cand_k: int | None = None, stats: bool = False):
    """Stream the vocab in tiles and sample without materializing it.

    tile_logits_fn(t0, tile) -> (B, tile) f32 raw logits for tokens
    [t0, t0+tile) — typically a sliced lm_head projection
    (models/llama.py ``lm_head_tile``). Returns (B,) int32 tokens with
    the semantics of ``ops.sampling.sample`` applied to the penalized
    logits (greedy when ``greedy`` — trace-time, the engine's all-greedy
    round variant — no noise, no candidate carry, just a running argmax).
    With ``stats`` (sampled streams only) returns ``(tokens, resort)``,
    ``resort`` the share of the stream's tiles, 0..1, whose candidate
    merge took the whole sort (:func:`_merge_tile`).
    """
    tile = choose_tile(vocab_size, tile, sampled=not greedy)
    cand_k = cand_k or default_cand_k()
    n_tiles = vocab_size // tile
    probe = jax.eval_shape(lambda: tile_logits_fn(jnp.int32(0), tile))
    B = probe.shape[0]

    def masked_tile(t):
        t0 = (t * tile).astype(jnp.int32)
        lf = _penalize_tile(
            tile_logits_fn(t0, tile), t0, tile, seen_words=seen_words,
            banned_words=banned_words, rep_pen=rep_pen,
            ban_tok=ban_tok, ban_hit=ban_hit)
        return t0, lf

    if greedy:
        _, best_id = _greedy_stream(masked_tile, n_tiles, tile, B)
        return best_id

    tf = jnp.maximum(temp, 1e-6)[:, None]
    cv, ci, cp, lse, _, bpid, _, brid, resort = _sample_stream(
        masked_tile, lambda t: t, key, tf, n_tiles, tile, B, cand_k)
    tok = _finalize_sample(cv, ci, cp, lse, bpid, brid, temp=temp,
                           top_k=top_k, top_p=top_p,
                           vocab_size=vocab_size, cand_k=cand_k)
    return (tok, resort) if stats else tok


def _candidate_stream(scaled, noise, n_tiles: int, tile: int, R: int,
                      cand_k: int):
    """The candidate carry of :func:`_sample_stream` over tiles of the
    ``scaled`` array the head kernel wrote (and the noise it added):
    ``(cv, ci, cp, resort)``, bit for bit the carry of a stream that
    made each tile from the head."""

    def body(carry, t):
        cv, ci, cp, n_resort = carry
        t0 = t * tile
        sc = jax.lax.dynamic_slice(scaled, (0, t0), (R, tile))
        g = jax.lax.dynamic_slice(noise, (0, t0), (R, tile))
        idb = jnp.broadcast_to(t0 + jnp.arange(tile, dtype=jnp.int32),
                               sc.shape)
        with jax.named_scope("tail_select"):
            (cv, ci, cp), resorted = _merge_tile(cv, ci, cp, sc, idb,
                                                 sc + g, cand_k)
        return (cv, ci, cp, n_resort + resorted), None

    init = (jnp.full((R, cand_k), -jnp.inf, jnp.float32),
            jnp.zeros((R, cand_k), jnp.int32),
            jnp.full((R, cand_k), -jnp.inf, jnp.float32),
            jnp.int32(0))
    (cv, ci, cp, n_resort), _ = jax.lax.scan(
        body, init, jnp.arange(n_tiles, dtype=jnp.int32))
    return cv, ci, cp, n_resort / n_tiles


def row_gumbel(key, rows: int, vocab_size: int) -> jax.Array:
    """The (rows, V) Gumbel field of the head kernel's sampled tail: row
    ``r`` is ``gumbel(fold_in(key, r), (V,))`` — a function of the key,
    the row and the token id alone, whatever the rows are padded to and
    however wide a block is."""
    return jax.vmap(lambda r: jax.random.gumbel(
        jax.random.fold_in(key, r), (vocab_size,), jnp.float32))(
            jnp.arange(rows, dtype=jnp.int32))


@jax.named_scope("tail")
def head_kernel_sample(hn, head_tree, vocab_size: int, *, key, temp, top_k,
                       top_p, rep_pen, seen_words, banned_words,
                       ban_tok=None, ban_hit=None, tile: int | None = None,
                       cand_k: int | None = None, stats: bool = False,
                       block: int | None = None, interpret: bool = False):
    """:func:`fused_unembed_sample` (sampled) over a head
    ``head_argmax.supported`` takes, the head streamed through VMEM ONCE:
    ``ops/head_argmax.py sampled_head_stream`` leaves the logsumexp, the
    Gumbel-max and greedy winners, and the penalised scaled logits of
    the already-normed rows ``hn`` (rows, D) as one (rows, V) float32
    array; the select-before-sort candidate merge then runs over tiles
    of THAT array (:func:`_candidate_stream`), and the truncation is
    resolved from the carry as ever. The noise is :func:`row_gumbel`,
    drawn before the kernel and streamed in beside the head's blocks;
    sample-exact against :func:`sample_reference_rows`. ``stats``: also
    the share of the selection's tiles that took the whole sort."""
    R = hn.shape[0]
    tile = choose_tile(vocab_size, tile, sampled=True)
    cand_k = cand_k or default_cand_k()
    # drawn for the rows there are, laid out in the rows the kernel holds
    noise = jnp.pad(row_gumbel(key, R, vocab_size), (
        (0, head_argmax.padded_rows(R, hn.dtype.itemsize) - R), (0, 0)))
    scaled, lse, bpid, brid = head_argmax.sampled_head_stream(
        hn, head_tree, vocab_size, noise=noise, temp=temp, rep_pen=rep_pen,
        seen_words=seen_words, banned_words=banned_words, ban_tok=ban_tok,
        ban_hit=ban_hit, block=block, interpret=interpret)
    cv, ci, cp, resort = _candidate_stream(
        scaled, noise, vocab_size // tile, tile, R, cand_k)
    tok = _finalize_sample(cv, ci, cp, lse, bpid, brid, temp=temp,
                           top_k=top_k, top_p=top_p,
                           vocab_size=vocab_size, cand_k=cand_k)
    return (tok, resort) if stats else tok


@jax.named_scope("tail")
def fused_unembed_sample_tp(mesh, axis: str, head_tree, head_specs,
                            local_tile_fn, vocab_size: int, *, hn, key,
                            temp, top_k, top_p, rep_pen, seen_words,
                            banned_words, ban_tok=None, ban_hit=None,
                            greedy: bool = False, tile: int | None = None,
                            cand_k: int | None = None, stats: bool = False):
    """:func:`fused_unembed_sample` with the vocab stream SHARDED over
    the mesh's ``axis``: each chip streams only its local lm_head
    shard's tiles and the per-shard carries merge with one small
    cross-chip collective (see module docstring).

    ``head_tree``/``head_specs``: the lm_head (or tied-embedding) leaves
    and their PartitionSpecs (models/llama.py ``lm_head_subtree`` /
    ``lm_head_specs``). ``local_tile_fn(head_local, hn, t0, tile)``
    projects the already-normed hidden rows onto the LOCAL shard's
    tokens [t0, t0+tile). Noise is keyed on the GLOBAL tile index, so
    with a matching tile size the sharded stream is sample-exact against
    the single-chip stream and the materialized oracle. The returned
    (B,) tokens are replicated on every chip — harvest-safe by
    construction. ``stats`` as in :func:`fused_unembed_sample`: the mean
    over the shards of each one's share of whole-sort tiles."""
    from jax.sharding import PartitionSpec as P

    n_shards, v_local, tile = _shard_geometry(mesh, axis, vocab_size,
                                              tile, not greedy)
    cand_k = cand_k or default_cand_k()
    n_tiles = v_local // tile
    B = hn.shape[0]
    tf = None if greedy else jnp.maximum(temp, 1e-6)[:, None]
    has_ban = ban_tok is not None

    def shard_fn(head_local, hn, temp, top_k, top_p, rep_pen,
                 seen_words, banned_words, *ban):
        idx = jax.lax.axis_index(axis)
        base = (idx * v_local).astype(jnp.int32)
        tile_base = idx * n_tiles
        ban_tok_, ban_hit_ = ban if has_ban else (None, None)

        def masked_tile(t):
            t0 = base + (t * tile).astype(jnp.int32)   # GLOBAL offset
            lf = _penalize_tile(
                local_tile_fn(head_local, hn, (t * tile).astype(jnp.int32),
                              tile),
                t0, tile, seen_words=seen_words,
                banned_words=banned_words, rep_pen=rep_pen,
                ban_tok=ban_tok_, ban_hit=ban_hit_)
            return t0, lf

        if greedy:
            best, best_id = _greedy_stream(masked_tile, n_tiles, tile, B)
            _, win_id = _merge_running_max(axis, best, best_id)
            return win_id
        cv, ci, cp, lse, bpert, bpid, braw, brid, resort = _sample_stream(
            masked_tile, lambda t: tile_base + t, key, tf, n_tiles, tile,
            B, cand_k)
        cv, ci, cp = _merge_candidates(axis, cv, ci, cp, cand_k)
        lse = _merge_lse(axis, lse)
        _, bpid = _merge_running_max(axis, bpert, bpid)
        _, brid = _merge_running_max(axis, braw, brid)
        tok = _finalize_sample(cv, ci, cp, lse, bpid, brid, temp=temp,
                               top_k=top_k, top_p=top_p,
                               vocab_size=vocab_size, cand_k=cand_k)
        return (tok, jax.lax.pmean(resort, axis)) if stats else tok

    args = (head_tree, hn, temp, top_k, top_p, rep_pen, seen_words,
            banned_words) + ((ban_tok, ban_hit) if has_ban else ())
    in_specs = (head_specs,) + (P(),) * (len(args) - 1)
    return jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=(P(), P()) if stats and not greedy
                         else P(), check_vma=False)(*args)


@jax.named_scope("tail")
def fused_verify_sample(tile_logits_fn, vocab_size: int, *, key, u, temp,
                        top_k, top_p, rep_pen, seen_words, banned_words,
                        draft_ids, ban_tok=None, ban_hit=None,
                        tile: int | None = None,
                        cand_k: int | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Speculative-decoding verification on the vocab-tiled stream:
    per row, the EXACT rejection-sampling verdict for one draft token,
    without materializing (R, V) logits.

    Each row scores one position; ``draft_ids[r]`` is the draft token
    proposed there (−1 = no draft: a bonus/padding row that always
    "rejects" and resamples from the full target distribution).
    ``u``: (R,) uniforms in [0, 1) drawn by the caller (shared with the
    reference oracle so exactness is testable token-for-token).

    Returns ``(accept, out_tok)``:

    - ``accept[r]`` — keep the draft token (prompt-lookup drafting is a
      point mass, so Leviathan et al.'s ``min(1, p/q)`` test reduces to
      ``u < p(draft)`` under the penalized+truncated target
      distribution; a greedy row — temp<=0 or top_k==1 — accepts iff
      the draft equals the running argmax);
    - ``out_tok[r]`` — the token to emit at the FIRST rejected position
      (a sample from the residual ``p`` with the draft token removed,
      renormalized — with a point-mass proposal the residual is exactly
      that) or at the bonus position (draft −1 masks nothing, so the
      residual IS ``p``).  Greedy rows return the argmax.

    Sequentially applying this rule position by position leaves the
    output distribution identical to non-speculative sampling (the
    fixed-key distribution-preservation test pins it).  Exactness
    contract matches :func:`fused_unembed_sample`: rows whose kept
    top-k/top-p prefix fits ``cand_k`` candidates are sample-exact vs
    :func:`verify_reference_tiled`; a draft outside the candidate set
    of a truncated row has p = 0 there (it cannot be in the kept set).
    """
    tile = choose_tile(vocab_size, tile, sampled=True)
    cand_k = cand_k or default_cand_k()
    n_tiles = vocab_size // tile
    probe = jax.eval_shape(lambda: tile_logits_fn(jnp.int32(0), tile))
    R = probe.shape[0]
    tf = jnp.maximum(temp, 1e-6)[:, None]

    def masked_tile(t):
        t0 = (t * tile).astype(jnp.int32)
        lf = _penalize_tile(
            tile_logits_fn(t0, tile), t0, tile, seen_words=seen_words,
            banned_words=banned_words, rep_pen=rep_pen,
            ban_tok=ban_tok, ban_hit=ban_hit)
        return t0, lf

    (cv, ci, cp, lse, _, brid, sd, sfound, _, npid) = _verify_stream(
        masked_tile, lambda t: t, key, tf, draft_ids, n_tiles, tile, R,
        cand_k)
    return _finalize_verify(cv, ci, cp, lse, brid, sd, sfound, npid,
                            u=u, temp=temp, top_k=top_k, top_p=top_p,
                            draft_ids=draft_ids, vocab_size=vocab_size,
                            cand_k=cand_k)


@jax.named_scope("tail")
def fused_verify_sample_tp(mesh, axis: str, head_tree, head_specs,
                           local_tile_fn, vocab_size: int, *, hn, key, u,
                           temp, top_k, top_p, rep_pen, seen_words,
                           banned_words, draft_ids, ban_tok=None,
                           ban_hit=None, tile: int | None = None,
                           cand_k: int | None = None
                           ) -> tuple[jax.Array, jax.Array]:
    """:func:`fused_verify_sample` with the vocab stream sharded over
    ``axis`` — the speculative verify tail for tp-sharded serving. Same
    per-shard stream + one-merge structure as
    :func:`fused_unembed_sample_tp`; the draft token's scaled logit
    lives on exactly one shard, so its gather is a ``psum`` over zeros
    elsewhere. Verdicts come back replicated on every chip."""
    from jax.sharding import PartitionSpec as P

    n_shards, v_local, tile = _shard_geometry(mesh, axis, vocab_size,
                                              tile, True)
    cand_k = cand_k or default_cand_k()
    n_tiles = v_local // tile
    R = hn.shape[0]
    tf = jnp.maximum(temp, 1e-6)[:, None]
    has_ban = ban_tok is not None

    def shard_fn(head_local, hn, u, temp, top_k, top_p, rep_pen,
                 seen_words, banned_words, draft_ids, *ban):
        idx = jax.lax.axis_index(axis)
        base = (idx * v_local).astype(jnp.int32)
        tile_base = idx * n_tiles
        ban_tok_, ban_hit_ = ban if has_ban else (None, None)

        def masked_tile(t):
            t0 = base + (t * tile).astype(jnp.int32)
            lf = _penalize_tile(
                local_tile_fn(head_local, hn, (t * tile).astype(jnp.int32),
                              tile),
                t0, tile, seen_words=seen_words,
                banned_words=banned_words, rep_pen=rep_pen,
                ban_tok=ban_tok_, ban_hit=ban_hit_)
            return t0, lf

        (cv, ci, cp, lse, braw, brid, sd, sfound, npert, npid) = \
            _verify_stream(masked_tile, lambda t: tile_base + t, key, tf,
                           draft_ids, n_tiles, tile, R, cand_k)
        cv, ci, cp = _merge_candidates(axis, cv, ci, cp, cand_k)
        lse = _merge_lse(axis, lse)
        _, brid = _merge_running_max(axis, braw, brid)
        _, npid = _merge_running_max(axis, npert, npid)
        # sd accumulated only on the shard owning the draft id (zeros
        # elsewhere); sfound likewise — one psum each completes them.
        sd = jax.lax.psum(sd, axis)
        sfound = jax.lax.psum(sfound.astype(jnp.int32), axis) > 0
        return _finalize_verify(cv, ci, cp, lse, brid, sd, sfound, npid,
                                u=u, temp=temp, top_k=top_k, top_p=top_p,
                                draft_ids=draft_ids,
                                vocab_size=vocab_size, cand_k=cand_k)

    args = (head_tree, hn, u, temp, top_k, top_p, rep_pen, seen_words,
            banned_words, draft_ids) + ((ban_tok, ban_hit) if has_ban
                                        else ())
    in_specs = (head_specs,) + (P(),) * (len(args) - 1)
    return jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=(P(), P()), check_vma=False)(*args)


def verify_reference_tiled(logits, key, u, temp, top_k, top_p, draft_ids,
                           tile: int) -> tuple[jax.Array, jax.Array]:
    """Materialized oracle for :func:`fused_verify_sample`: full (R, V)
    penalized logits in, the same accept/resample verdicts out, sharing
    the fused path's per-tile Gumbel noise layout and uniforms — the
    fused path must produce IDENTICAL verdicts for the same key
    whenever the kept prefix fits its candidate carry (tier-1 pinned).
    Also the verification tail for the engine's materialized
    (non-fused) decode path under ``ENGINE_FUSED_SAMPLER=0`` or a
    downgraded mesh geometry."""
    R, V = logits.shape
    lf = logits.astype(jnp.float32)
    greedy_ids = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    scaled = lf / jnp.maximum(temp, 1e-6)[:, None]
    sort_idx = jnp.argsort(-scaled, axis=-1)
    ranks = jnp.zeros_like(sort_idx).at[
        jnp.arange(R)[:, None], sort_idx
    ].set(jnp.broadcast_to(jnp.arange(V), (R, V)))
    kk = jnp.where(top_k[:, None] <= 0, V, top_k[:, None])
    keep = ranks < kk
    sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    p = jnp.where((top_p[:, None] <= 0) | (top_p[:, None] >= 1.0),
                  1.0, top_p[:, None])
    sorted_keep_p = (cum - sorted_probs) < p
    keep_p = jnp.zeros_like(keep).at[
        jnp.arange(R)[:, None], sort_idx
    ].set(sorted_keep_p)
    kept = keep & keep_p
    is_draft = jnp.arange(V)[None, :] == draft_ids[:, None]
    sd = jnp.where(jnp.any(is_draft, axis=-1),
                   jnp.sum(jnp.where(is_draft, scaled, 0.0), axis=-1),
                   -jnp.inf)
    untruncated = (kk[:, 0] >= V) & (p[:, 0] >= 1.0)
    z_kept = jax.nn.logsumexp(jnp.where(kept, scaled, -jnp.inf), axis=-1)
    lse = jax.nn.logsumexp(scaled, axis=-1)
    draft_kept = jnp.any(is_draft & kept, axis=-1)
    p_trunc = jnp.where(draft_kept, jnp.exp(sd - z_kept), 0.0)
    p_acc = jnp.where(untruncated, jnp.exp(sd - lse), p_trunc)
    pert = scaled + tiled_gumbel(key, R, V, tile)
    kept_res = kept & ~is_draft
    masked = jnp.where(kept_res, pert, -jnp.inf)
    resample = jnp.argmax(masked, axis=-1).astype(jnp.int32)
    resample = jnp.where(jnp.any(kept_res, axis=-1), resample, draft_ids)
    accept = u < p_acc
    is_greedy = (temp <= 0) | (top_k == 1)
    accept = jnp.where(is_greedy, draft_ids == greedy_ids, accept)
    out_tok = jnp.where(is_greedy, greedy_ids, resample)
    return accept, out_tok.astype(jnp.int32)


def tiled_gumbel(key, batch: int, vocab_size: int, tile: int) -> jax.Array:
    """The full (B, V) Gumbel field the fused sampler consumes tile by
    tile — oracle/test use only (it materializes what the fused path
    exists to avoid)."""
    n_tiles = -(-vocab_size // tile)
    parts = [jax.random.gumbel(jax.random.fold_in(key, t),
                               (batch, tile), jnp.float32)
             for t in range(n_tiles)]
    return jnp.concatenate(parts, axis=-1)[:, :vocab_size]


def sample_reference_tiled(logits, key, temp, top_k, top_p,
                           tile: int) -> jax.Array:
    """Materialized penalize-then-sample oracle with the tile scan's
    noise layout (:func:`tiled_gumbel`): the scan must produce IDENTICAL
    tokens for the same key whenever the kept prefix fits in its
    candidate carry (tier-1 pinned)."""
    B, V = logits.shape
    return _sample_reference(logits, tiled_gumbel(key, B, V, tile), temp,
                             top_k, top_p)


def sample_reference_rows(logits, key, temp, top_k, top_p) -> jax.Array:
    """The same oracle with the head kernel's noise layout
    (:func:`row_gumbel`): what :func:`head_kernel_sample` must return
    token for token."""
    B, V = logits.shape
    return _sample_reference(logits, row_gumbel(key, B, V), temp, top_k,
                             top_p)


def _sample_reference(logits, noise, temp, top_k, top_p) -> jax.Array:
    """Full (B, V) penalized logits and their (B, V) Gumbel field in:
    stable descending sort, top-k / top-p prefix keep, argmax over kept
    Gumbel-perturbed values."""
    B, V = logits.shape
    lf = logits.astype(jnp.float32)
    greedy_ids = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    scaled = lf / jnp.maximum(temp, 1e-6)[:, None]
    sort_idx = jnp.argsort(-scaled, axis=-1)
    ranks = jnp.zeros_like(sort_idx).at[
        jnp.arange(B)[:, None], sort_idx
    ].set(jnp.broadcast_to(jnp.arange(V), (B, V)))
    k = jnp.where(top_k[:, None] <= 0, V, top_k[:, None])
    keep = ranks < k
    sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    p = jnp.where((top_p[:, None] <= 0) | (top_p[:, None] >= 1.0),
                  1.0, top_p[:, None])
    sorted_keep_p = (cum - sorted_probs) < p
    keep_p = jnp.zeros_like(keep).at[
        jnp.arange(B)[:, None], sort_idx
    ].set(sorted_keep_p)
    pert = scaled + noise
    masked = jnp.where(keep & keep_p, pert, -jnp.inf)
    sampled = jnp.argmax(masked, axis=-1).astype(jnp.int32)
    is_greedy = (temp <= 0) | (top_k == 1)
    return jnp.where(is_greedy, greedy_ids, sampled)
