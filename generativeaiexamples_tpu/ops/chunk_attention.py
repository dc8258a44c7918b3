"""A latent chunk's attention: the online softmax whose score tiles, and
since PR 48 whose carry over the prefix, live and die on the chip.

A prefill chunk (models/llama.py ``apply_prefill_paged``) attends its
prefix block by block and then itself, under ONE online softmax whose
state — running maximum, row sum, accumulator — is carried from block to
block. Two Pallas kernels run it (``LatentKV.attend_prefix`` in
models/kv_cache.py is the only caller today):

- ``chunk_attention_prefix`` (Mosaic name ``chunk_attn_prefix``) is the
  whole PREFIX of one layer of one prompt's chunk: it walks the row's
  block table itself, fetches a block's pages out of the latent pool,
  zeroes the rows at or past the chunk's start, expands them through the
  layer's ``wk_b`` / ``wv_b`` as stored — a head group's columns at a
  time, in VMEM — and folds every live block into a carry that never
  leaves the chip between blocks. The queries are read once a head
  group. As a ``lax.scan`` of one update a block the same walk moved the
  carry (64 heads x 256 x 512 x 4 B = 33.5 MB, in and out), the queries
  (16.8 MB) and the expanded block (~120 MB written and re-read by XLA's
  expansion, fold and transpose) through HBM for every 512 keys.
- ``chunk_attention_update`` (``chunk_attn``) is ONE step over keys and
  values the caller hands it expanded: scores, mask, maximum, exponent,
  sum and the PV product of all heads against one key block, the
  ``(heads, T, C)`` float32 scores never leaving VMEM (as XLA operations
  that tensor, 67 MB, was written and re-read half a dozen times a
  block). It knows nothing of a latent or a page; it folds the chunk's
  OWN tokens, seeded with the prefix kernel's carry (``HeadKV``'s reader
  could call it once it has a GQA group axis).

The softmax update of a block is the same lines in both, so the prefix
kernel equals the scan of updates it replaced to the bit
(tests/test_chunk_attention.py).

Everything lies QUERIES ON THE LANES: scores are ``(T, C)``, the
accumulator ``(dv, C)``, maximum and sum ``(1, C)`` rows. A row statistic
is then one lane-dense row a head — ``(H, 1, C)`` float32 in HBM, 128 KB
for 64 heads x 512 queries — where the usual ``(C, 1)`` column would be
padded to 128 lanes (16.8 MB, as much as the accumulator) on every trip;
reductions run over sublanes (elementwise across vregs), and the
broadcasts are sublane broadcasts. Values likewise lie keys on the lanes,
``(dv, T)``: the prefix kernel makes them so (it contracts ``R`` of the
turned weight slice with ``R`` of the block), the update takes them so.

The mathematics is ``online`` of the jnp form it replaces, operation for
operation: operands as stored into the MXU with float32 accumulation,
``scale`` on the float32 scores, masked scores to ``NEG`` before the
maximum, masked probabilities to 0 explicitly (not by underflow), the
probabilities cast to the value dtype before the PV product, float32
state. A query that has seen no key keeps ``m = NEG, l = 0, acc = 0`` and
``finish`` gives it zeros, not NaN.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .paged_attention import NEG
from .quant import int_weights_and_scale

_VMEM_LIMIT = 64 << 20
_HEADS = 4           # heads a grid step
_QUERIES = 512       # queries a grid step


def kernel_supported(page: int, dk: int, dv: int, ds: int = 0) -> bool:
    """Whether the compiled kernel takes this geometry: key blocks and
    chunks are whole pages, so lane-width pages make every (T, C) tile
    whole; a head's keys are a lane-aligned slice of ``(T, H * dk)``, its
    values a sublane-aligned slice of ``(H * dv, T)``; the shared key
    part joins a head's keys at a lane boundary."""
    return page % 128 == 0 and dk % 128 == 0 and dv % 16 == 0 \
        and ds % 8 == 0


def init_carry(H: int, C: int, dv: int):
    """(m, l, acc) before any key: (H, 1, C), (H, 1, C), (H, dv, C)."""
    return (jnp.full((H, 1, C), NEG, jnp.float32),
            jnp.zeros((H, 1, C), jnp.float32),
            jnp.zeros((H, dv, C), jnp.float32))


def finish(carry, dtype) -> jax.Array:
    """The attention output (C, H, dv) of a carry. Valid queries attend
    at least themselves (l > 0); padded rows attend nothing — the floor
    on the denominator gives them zeros."""
    _, l, acc = carry
    return (acc / jnp.maximum(l, 1e-30)).transpose(2, 0, 1).astype(dtype)


def _tile(n: int, want: int, unit: int) -> int:
    """The largest divisor of ``n`` that is ``want`` halved some times
    and still a multiple of ``unit``; ``n`` itself where there is none."""
    t = want
    while t >= unit:
        if n % t == 0:
            return t
        t //= 2
    return n


@functools.partial(jax.jit, static_argnames=("scale", "causal", "interpret"))
def chunk_attention_update(q: jax.Array, k: jax.Array, vt: jax.Array,
                           carry, k0, limit, q0, *, scale: float,
                           causal: bool, k_shared: jax.Array = None,
                           keep: jax.Array = None,
                           interpret: bool = False):
    """One key block folded into the carry.

    q:        (H, C, dk + ds)  queries; the last ``ds`` values of a query
                               meet ``k_shared``
    k:        (T, H * dk)      the block's keys, heads along the lanes
    vt:       (H * dv, T)      its values, TRANSPOSED (keys on the lanes)
    k_shared: (T, ds) or None  a key part all heads share
    keep:     (T, C) or None   float32, > 0 where the query (column) may
                               attend the key (row) at all: a per-query
                               key set, the same for all heads (learned
                               sparse attention), ANDed with the mask
                               the positions give
    carry:    (m, l, acc)      as ``init_carry``
    k0:       () int32         position of the block's first key
    limit:    () int32         keys at or past it are masked
    q0:       () int32         position of the first query; under
                               ``causal`` a key is visible to the queries
                               at or after it
    The caller zeroes the rows of masked keys in ``k`` and ``vt`` (a
    masked probability is 0, and 0 x NaN is NaN).
    Returns the new carry.

    Jitted so that its trace is made ONCE a shape and flag, not once a
    call site: an engine builds some 150 chunk programs, each with this
    call under two layer stacks, and tracing the kernel body anew in
    each doubled the set-up's tracing time (chip, PR 37).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, l, acc = carry
    H, C, dq = q.shape
    T = k.shape[0]
    ds = 0 if k_shared is None else k_shared.shape[1]
    dk, dv = dq - ds, vt.shape[0] // H
    hg = _tile(H, _HEADS, 1)
    if (hg * dk) % 128:
        hg = H          # a head group's keys must be whole lanes, or all
    cq = _tile(C, _QUERIES, 128)

    def kernel(pos_ref, q_ref, k_ref, *refs):
        ks_ref = refs[0] if ds else None
        keep_ref = refs[1 if ds else 0] if keep is not None else None
        vt_ref, m_ref, l_ref, acc_ref, mo_ref, lo_ref, acco_ref = \
            refs[bool(ds) + (keep is not None):]
        k_first, k_limit = pos_ref[0], pos_ref[1]
        q_first = pos_ref[2] + pl.program_id(1) * cq
        live = k_first < k_limit
        if causal:      # no key of the block before the tile's last query
            live = live & (k_first <= q_first + (cq - 1))

        @pl.when(live)
        def _():
            kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (T, cq), 0)
            valid = kpos < k_limit
            if causal:
                valid = valid & (kpos <= q_first + jax.lax.broadcasted_iota(
                    jnp.int32, (T, cq), 1))
            if keep is not None:
                valid = valid & (keep_ref[...] > 0.0)
            for j in range(hg):
                kj = k_ref[:, j * dk:(j + 1) * dk]
                if ds:
                    kj = jnp.concatenate([kj, ks_ref[...]], axis=1)
                s = jax.lax.dot_general(
                    kj, q_ref[j], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (T, cq)
                s = jnp.where(valid, s, NEG)
                m_old = m_ref[j]                                 # (1, cq)
                m_new = jnp.maximum(m_old,
                                    jnp.max(s, axis=0, keepdims=True))
                alpha = jnp.exp(m_old - m_new)
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                mo_ref[j] = m_new
                lo_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=0,
                                                       keepdims=True)
                pv = jnp.dot(vt_ref[j * dv:(j + 1) * dv, :],
                             p.astype(vt_ref.dtype),
                             preferred_element_type=jnp.float32)  # (dv, cq)
                acco_ref[j] = acc_ref[j] * alpha + pv

        @pl.when(jnp.logical_not(live))
        def _():
            mo_ref[...] = m_ref[...]
            lo_ref[...] = l_ref[...]
            acco_ref[...] = acc_ref[...]

    def heads(g, qi, *_):
        return (g, 0, qi)

    state = [pl.BlockSpec((hg, 1, cq), heads), pl.BlockSpec((hg, 1, cq), heads),
             pl.BlockSpec((hg, dv, cq), heads)]
    shared = [] if k_shared is None else [k_shared]
    kept = [] if keep is None else [keep]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,       # (k0, limit, q0)
        grid=(H // hg, C // cq),
        in_specs=[
            pl.BlockSpec((hg, cq, dq), lambda g, qi, *_: (g, qi, 0)),
            pl.BlockSpec((T, hg * dk), lambda g, qi, *_: (0, g)),
            *[pl.BlockSpec((T, ds), lambda g, qi, *_: (0, 0))
              for _ in shared],
            *[pl.BlockSpec((T, cq), lambda g, qi, *_: (0, qi))
              for _ in kept],
            pl.BlockSpec((hg * dv, T), lambda g, qi, *_: (g, 0)),
            *state,
        ],
        out_specs=state,
    )
    n_in = 4 + len(shared) + len(kept)   # operands before the carry
    pos = jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                     for x in (k0, limit, q0)])
    return tuple(pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (m, l, acc)],
        input_output_aliases={n_in + i: i for i in range(3)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="chunk_attn",
    )(pos, q, k, *shared, *kept, vt, m, l, acc))


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_pages", "interpret"))
def chunk_attention_prefix(q: jax.Array, wk_b, wv_b, pool_c: jax.Array,
                           pool_r: jax.Array, table: jax.Array, start, *,
                           scale: float, block_pages: int = 4,
                           keep: jax.Array = None,
                           interpret: bool = False):
    """Every PREFIX key of a latent chunk folded into a fresh carry: the
    walk over the block table, the expansion and the online softmax in
    one kernel, a layer of one prompt's chunk a call.

    q:       (H, C, nope + rope)   queries, the rotary part last
    wk_b:    (R, H * nope)         the layer's key expansion as stored:
                                   raw, or int8 ``q`` + column ``scale``
                                   (ops/quant.py)
    wv_b:    (R, H * dv)           its value expansion, likewise
    pool_c:  (L * N, page, R)      the latent pool, layers flattened
    pool_r:  (L * N, rope, page)   its rotary leaf (positions on lanes)
    table:   (nb * block_pages,)   int32: the row's pages, already offset
                                   by the layer, padded to whole blocks
    start:   () int32              keys at or past it are not prefix
    keep:    (nb * T, C) or None   float32 as ``chunk_attention_update``'s,
                                   rows as the padded table has them
    Returns ``(m, l, acc)`` as ``init_carry`` lays them, equal to the
    bit (tests/test_chunk_attention.py) to ``chunk_attention_update``
    once a block of ``T = block_pages * page`` keys, in order, over keys
    and values expanded by ``ops.quant.matmul``.

    Grid (head group, query tile); a step walks the LIVE blocks (those
    with a key before ``start``) under one ``fori_loop``: a block's pages
    (and its rows of ``keep``) arrive by DMA through the table into one
    of two buffers while the block before is multiplied; rows at or past
    ``start`` are zeroed BEFORE the expansion (the trash page may hold
    NaN); the group's keys are ``cb @ wk`` and its values, keys on the
    lanes, ``wv^T cb^T`` — float32 accumulation, ``* scale``, the cast:
    ``matmul``'s operations, for the group's heads alone; the rotary part
    joins each head's keys in VMEM. The group's weight slices are widened
    (and ``wv`` turned) once a step, before the first block. ``m``, ``l``
    and ``acc`` stay in their output blocks from the first block to the
    last: no carry, no query and no expanded key crosses HBM between
    blocks.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    wk, wk_scale = int_weights_and_scale(wk_b)
    wv, wv_scale = int_weights_and_scale(wv_b)
    H, C, dq = q.shape
    _, page, R = pool_c.shape
    rope = pool_r.shape[1]
    nope, dv = dq - rope, wv.shape[1] // H
    PB = block_pages
    T = PB * page
    nb = table.shape[0] // PB
    cd = q.dtype
    hg = _tile(H, _HEADS, 1)
    if (hg * nope) % 128 or (hg * dv) % 128:
        hg = H          # a group's weight columns must be whole lanes
    cq = _tile(C, _QUERIES, 128)
    masked = keep is not None
    # ``matmul``'s product: float32 accumulation, then the scale and the
    # cast. A raw weight's is ``x @ w`` in the activation dtype, which on
    # the chip is the same accumulation and one cast (Mosaic wants it
    # said so); XLA's CPU rounds that product its own way, so interpreted
    # it is said as ``matmul`` says it
    acc_k = None if interpret and wk_scale is None else jnp.float32
    acc_v = None if interpret and wv_scale is None else jnp.float32

    def kernel(tbl_ref, pos_ref, q_ref, wk_ref, wv_ref, *refs):
        ks_ref = vs_ref = keep_hbm = kbuf = ksem = None
        if wk_scale is not None:
            ks_ref, *refs = refs
        if wv_scale is not None:
            vs_ref, *refs = refs
        c_hbm, r_hbm, *refs = refs
        if masked:
            keep_hbm, *refs = refs
        m_ref, l_ref, acc_ref, cbuf, rbuf, sem, wk_s, wvt_s, *refs = refs
        if masked:
            kbuf, ksem = refs
        first = pos_ref[0]
        live = jnp.minimum(jax.lax.div(first + (T - 1), T), nb)
        q_first = pl.multiple_of(pl.program_id(1) * cq, 128)

        def copies(b, slot):
            out = []
            for j in range(PB):
                pg = tbl_ref[b * PB + j]
                out.append(pltpu.make_async_copy(
                    c_hbm.at[pg], cbuf.at[slot, j], sem.at[slot, j, 0]))
                out.append(pltpu.make_async_copy(
                    r_hbm.at[pg], rbuf.at[slot, j], sem.at[slot, j, 1]))
            if masked:
                out.append(pltpu.make_async_copy(
                    keep_hbm.at[pl.ds(pl.multiple_of(b * T, T), T),
                                pl.ds(q_first, cq)],
                    kbuf.at[slot], ksem.at[slot]))
            return out

        @pl.when(live > 0)
        def _():
            for d in copies(0, 0):
                d.start()

        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        # the group's weights as the MXU takes them, once: the keys' as
        # they lie, the values' turned (so a block's values come out
        # keys on the lanes) with their column scales a column
        wk_s[...] = wk_ref[...].astype(cd)
        for j in range(hg):
            wvt_s[j * dv:(j + 1) * dv, :] = wv_ref[
                :, j * dv:(j + 1) * dv].astype(jnp.float32).T.astype(cd)
        if vs_ref is not None:
            vs_col = jnp.broadcast_to(vs_ref[...], (8, hg * dv)).T[:, :1]

        def body(b, carry):
            slot = jax.lax.rem(b, 2)

            @pl.when(b + 1 < live)
            def _():
                for d in copies(b + 1, 1 - slot):
                    d.start()
            for d in copies(b, slot):
                d.wait()
            k_first = b * T
            # rows at or past ``start`` zeroed before the expansion
            rows = k_first + jax.lax.broadcasted_iota(
                jnp.int32, (T, 1), 0) < first
            cb = jnp.where(rows, cbuf[slot].reshape(T, R).astype(cd), 0)
            lanes = k_first + jax.lax.broadcasted_iota(
                jnp.int32, (1, T), 1) < first
            rbt = jnp.where(lanes, jnp.concatenate(
                [rbuf[slot, j] for j in range(PB)], axis=1), 0)
            rb = rbt.astype(jnp.float32).T.astype(cd)           # (T, rope)
            kb = jnp.dot(cb, wk_s[...], preferred_element_type=acc_k)
            if ks_ref is not None:
                kb = kb * ks_ref[...]
            kb = kb.astype(cd)                              # (T, hg * nope)
            vt = jax.lax.dot_general(
                wvt_s[...], cb, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_v)
            if vs_ref is not None:
                vt = vt * vs_col
            vt = vt.astype(cd)                              # (hg * dv, T)

            kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (T, cq), 0)
            valid = kpos < first
            if masked:
                valid = valid & (kbuf[slot] > 0.0)
            for j in range(hg):
                kj = jnp.concatenate(
                    [kb[:, j * nope:(j + 1) * nope], rb], axis=1)
                s = jax.lax.dot_general(
                    kj, q_ref[j], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (T, cq)
                s = jnp.where(valid, s, NEG)
                m_old = m_ref[j]                                 # (1, cq)
                m_new = jnp.maximum(m_old,
                                    jnp.max(s, axis=0, keepdims=True))
                alpha = jnp.exp(m_old - m_new)
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                m_ref[j] = m_new
                l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=0,
                                                      keepdims=True)
                pv = jnp.dot(vt[j * dv:(j + 1) * dv, :], p.astype(cd),
                             preferred_element_type=jnp.float32)  # (dv, cq)
                acc_ref[j] = acc_ref[j] * alpha + pv
            return carry

        jax.lax.fori_loop(0, live, body, jnp.int32(0))

    def heads(g, qi, *_):
        return (g, 0, qi)

    def cols(g, qi, *_):
        return (0, g)

    state = [pl.BlockSpec((hg, 1, cq), heads), pl.BlockSpec((hg, 1, cq), heads),
             pl.BlockSpec((hg, dv, cq), heads)]
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    k_scale = [] if wk_scale is None else [wk_scale.reshape(1, H * nope)]
    v_scale = [] if wv_scale is None else [wv_scale.reshape(1, H * dv)]
    kept = [keep] if masked else []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,       # the table, (start,)
        grid=(H // hg, C // cq),
        in_specs=[
            pl.BlockSpec((hg, cq, dq), lambda g, qi, *_: (g, qi, 0)),
            pl.BlockSpec((R, hg * nope), cols),
            pl.BlockSpec((R, hg * dv), cols),
            *[pl.BlockSpec((1, hg * nope), cols) for _ in k_scale],
            *[pl.BlockSpec((1, hg * dv), cols) for _ in v_scale],
            any_space, any_space,    # the pool stays in HBM
            *[any_space for _ in kept],
        ],
        out_specs=state,
        scratch_shapes=[
            pltpu.VMEM((2, PB, page, R), pool_c.dtype),
            pltpu.VMEM((2, PB, rope, page), pool_r.dtype),
            pltpu.SemaphoreType.DMA((2, PB, 2)),
            pltpu.VMEM((R, hg * nope), cd),
            pltpu.VMEM((hg * dv, R), cd),
            *([pltpu.VMEM((2, T, cq), jnp.float32),
               pltpu.SemaphoreType.DMA((2,))] if masked else []),
        ],
    )
    pos = jnp.asarray(start, jnp.int32).reshape(1)
    return tuple(pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32)
                   for s in ((H, 1, C), (H, 1, C), (H, dv, C))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="chunk_attn_prefix",
    )(table.astype(jnp.int32), pos, q, wk, wv, *k_scale, *v_scale,
      pool_c, pool_r, *kept))
