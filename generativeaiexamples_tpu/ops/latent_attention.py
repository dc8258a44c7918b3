"""Latent attention: one cached row a token serves every head.

A latent-attention layer (models/configs.py ``kv_lora_rank``) leaves in
the cache a normed latent ``c`` (R values) and one rotated key part
``k_r`` (``rope`` values) a token, for all H heads. A head's keys and
values are ``c`` through the layer's ``wk_b`` / ``wv_b``; the same
numbers come out two ways:

- EXPANDED (``expanded_attention``): keys and values made from the
  latent, then plain attention. Right where many queries share the
  expansion: a prefill chunk, a prompt.
- ABSORBED (``absorbed_attention``, the Pallas kernel): the query is
  carried into the latent space, ``q~_h = q_nope_h W_UK,h^T`` (R wide),
  scored against ``c`` itself, the probabilities sum ``c`` itself, and
  ``W_UV,h`` is applied to the (R wide) sum (``head_product`` both
  times). Right at decode: a step reads each cached row ONCE and uses it
  as key and value for all heads.

``latent_attention_decode`` is the decode step on the chip, after
ops/paged_attention.py (whose program layout it keeps: slot groups, one
flat loop over a group's live pages behind an ``_NBUF``-deep ring of
DMA buffers, per-slot online-softmax state in scratch, the step's row
appended through an aligned tile whose other rows were staged from the
streamed window). What differs: a block of ``block_pages`` pages arrives
once and meets all H heads on the MXU as (H, R) x (R, block) and
(H, rope) x (rope, block) scores and a (H, block) x (block, R) sum, where
a GQA group is 4-8 query rows a KV head; the pool is two leaves,
``c: (L, N, 1, page, R)`` and ``r: (L, N, 1, rope, page)`` — the rotary
part lies TRANSPOSED, positions on the lanes (models/kv_cache.py says
why), so it is the scores' right-hand side as it lies and its append is
one lane of a (rope, page) block; and rows no query may read are zeroed
before the sum, not only their probabilities (the slot's last page holds
stale rows past its length, the trash page anything: 0 x NaN = NaN).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged_attention import NEG, _NBUF, _TILE, group_size
from .quant import int_weights_and_scale

_BLOCK_PAGES = 4     # pages a loop step streams and multiplies at once
_VMEM_LIMIT = 48 << 20


def kernel_supported(page: int, rank: int, rope: int) -> bool:
    """Lane-width pages and latent rows; the rotary part whole sublane
    tiles (it lies (rope, page))."""
    return page % 128 == 0 and rank % 128 == 0 and rope % 16 == 0


def head_product(x: jax.Array, w, num_heads: int,
                 transposed: bool = False) -> jax.Array:
    """A per-head product with one of the latent's up-projections, ``w``
    (R, H * n) raw or quantized per output column (ops/quant.py).

    ``transposed`` False: x (B, S, H, R) -> (B, S, H, n), ``x_h W_h``
    (``wv_b`` after the absorbed sum). True: x (B, S, H, n) -> (B, S, H,
    R), ``x_h W_h^T`` (the query absorbed through ``wk_b``). The integer
    weights go into the dot as they are stored (a mixed-dtype dot, as
    ``ops.quant.matmul``); the column scales multiply whichever side
    carries the columns."""
    q, scale = int_weights_and_scale(w)
    H = num_heads
    n = q.shape[-1] // H
    q3 = q.reshape(q.shape[0], H, n)
    if scale is not None:
        scale = scale.reshape(H, n)
    if transposed:
        if scale is not None:
            x = (x.astype(jnp.float32) * scale).astype(x.dtype)
        dims = (((3,), (2,)), ((2,), (1,)))
    else:
        dims = (((3,), (0,)), ((2,), (1,)))
    try:
        y = jax.lax.dot_general(x, q3, dims,
                                preferred_element_type=jnp.float32)
    except TypeError:  # backend/version without mixed-dtype dots
        y = jax.lax.dot_general(x, q3.astype(x.dtype), dims,
                                preferred_element_type=jnp.float32)
    y = y.transpose(1, 2, 0, 3)                             # (B, S, H, .)
    if not transposed and scale is not None:
        y = y * scale
    return y.astype(x.dtype)


def _column(row: jax.Array) -> jax.Array:
    """Inside the decode kernel: a (1, T) bool row, positions on the
    lanes, as a (T, 1) column, positions on the sublanes — what zeroes
    the ROWS of a streamed block. Through a float32 transpose of whole
    tiles: the chip's compiler refuses a reshape across the lane axis
    and the transpose of a boolean."""
    T = row.shape[1]
    return jnp.broadcast_to(row.astype(jnp.float32), (8, T)).T[:, :1] != 0


def _causal(positions, kv_valid_len, T: int):
    """(B, 1, S, T) bool: the key at cache index t is visible to the
    query at absolute position p iff t <= p and t < kv_valid_len."""
    t = jnp.arange(T, dtype=jnp.int32)
    mask = t[None, None, :] <= positions[:, :, None]
    if kv_valid_len is not None:
        mask = mask & (t[None, None, :] < kv_valid_len[:, None, None])
    return mask[:, None]


def _softmax_sum(scores, mask, values, eq: str, dtype):
    scores = jnp.where(mask, scores, NEG)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(mask, p, 0.0)
    return jnp.einsum(eq, p, values.astype(jnp.float32)).astype(dtype)


def _f32(*xs):
    """Operands upcast, as ops/attention.py ``_gqa_dense`` has them: these
    are the small forms (a prompt bucket, a verify window, the CPU)."""
    return tuple(x.astype(jnp.float32) for x in xs)


def expanded_attention(q_nope, q_r, k_nope, k_r, v, positions,
                       kv_valid_len, scale: float) -> jax.Array:
    """q_nope (B, S, H, nope), q_r (B, S, H, rope); k_nope (B, T, H,
    nope), k_r (B, T, rope) shared by the heads, v (B, T, H, vd); keys
    indexed by absolute position. Returns (B, S, H, vd)."""
    dtype = q_nope.dtype
    q_nope, q_r, k_nope, k_r = _f32(q_nope, q_r, k_nope, k_r)
    scores = (jnp.einsum("bshj,bthj->bhst", q_nope, k_nope)
              + jnp.einsum("bshr,btr->bhst", q_r, k_r)) * scale
    mask = _causal(positions, kv_valid_len, k_r.shape[1])
    return _softmax_sum(scores, mask, v, "bhst,bthv->bshv", dtype)


def absorbed_attention(q_c, q_r, c, k_r, positions, kv_valid_len,
                       scale: float) -> jax.Array:
    """q_c (B, S, H, R) the absorbed query, q_r (B, S, H, rope); c (B, T,
    R) and k_r (B, T, rope) the cached rows by absolute position, each
    key AND value. Returns the latent-space sum (B, S, H, R)."""
    dtype = q_c.dtype
    q_c, q_r, c, k_r = _f32(q_c, q_r, c, k_r)
    scores = (jnp.einsum("bshc,btc->bhst", q_c, c)
              + jnp.einsum("bshr,btr->bhst", q_r, k_r)) * scale
    mask = _causal(positions, kv_valid_len, c.shape[1])
    # rows no query may read are zeroed, not only their probabilities
    visible = jnp.any(mask, axis=(1, 2))                    # (B, T)
    c = jnp.where(visible[..., None], c, 0)
    return _softmax_sum(scores, mask, c, "bhst,btc->bshc", dtype)


def latent_attention_decode(q_c: jax.Array, q_r: jax.Array,
                            pool_c: jax.Array, pool_r: jax.Array,
                            block_table: jax.Array, lengths: jax.Array,
                            cur_c: jax.Array, cur_r: jax.Array,
                            write_page: jax.Array, write_offset: jax.Array,
                            layer: jax.Array, *, scale: float,
                            interpret: bool = False,
                            block_pages: int = _BLOCK_PAGES,
                            keep: jax.Array | None = None,
                            cur_keep: jax.Array | None = None):
    """Absorbed decode attention + row append over the latent pool, one
    query token a slot.

    q_c:          (B, H, R)            absorbed queries
    q_r:          (B, H, rope)         rotated query parts
    pool_c:       (L, N, 1, page, R)   latent rows, all layers (the pool
                                       rides the caller's layer scan and
                                       passes through aliased in place)
    pool_r:       (L, N, 1, rope, page) rotary key parts, transposed
    block_table:  (B, W) int32         physical page of each logical page
    lengths:      (B,) int32           cached tokens a slot (== pos; the
                                       current token is NOT in the pool)
    cur_c/cur_r:  (B, R) / (B, rope)   the current token's row, pool dtype
    write_page/write_offset: (B,)      where it goes (page 0 = trash)
    layer:        (1,) int32
    keep:         (B, W * page) bool   optional (learned sparse attention:
                                       ops/sparse_index.py): a slot's
                                       query attends the cached row at
                                       logical position t only where set
                                       (AND t < its length) — one more
                                       operand, every live page still
                                       streamed. A row the mask drops is
                                       zeroed before the sum, not only
                                       its probability, as
                                       ``absorbed_masked`` has it
    cur_keep:     (B,) bool            with ``keep``: whether the current
                                       token is among the kept (it
                                       competes for its place like any
                                       causal position); folded in after
                                       the loop only then
    Returns (o_c (B, H, R) in q_c.dtype, pool_c, pool_r). Without a mask
    nothing of it is traced: the program is what it was
    (tests/test_latent_program_pins.py).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, R = q_c.shape
    rope = q_r.shape[-1]
    L, N, _, page, _ = pool_c.shape
    Gs = group_size(B)
    PB = block_pages
    T = PB * page
    masked = keep is not None

    def kernel(tbl_ref, len_ref, wp_ref, off_ref, l_ref, *refs):
        if masked:      # the current token's bit, a scalar a slot
            ck_ref, *refs = refs
        qc_ref, qr_ref, c_hbm, r_hbm, cc_ref, cr_ref, crt_ref, *refs = refs
        if masked:      # (Gs, blocks, T) int32: a block's bits, a row
            kp_ref, *refs = refs
        (out_ref, opc_ref, opr_ref, cbuf, rbuf, accs, ms, ls, stc, strp,
         crw, rrw, sem, rw_sem) = refs
        gi = pl.program_id(0)
        li = l_ref[0]
        b0 = gi * Gs

        # a slot's live pages, and its blocks of PB pages; the group's
        # blocks stream as ONE flat sequence t in [0, total)
        pages_of = [jax.lax.div(len_ref[b0 + i] + (page - 1), page)
                    for i in range(Gs)]
        counts = [jax.lax.div(c + (PB - 1), PB) for c in pages_of]
        starts = [jnp.int32(0)]
        for c in counts:
            starts.append(starts[-1] + c)
        total = starts[Gs]

        for i in range(Gs):
            accs[i] = jnp.zeros((H, R), jnp.float32)
            ms[i] = jnp.full((H, 1), NEG, jnp.float32)
            ls[i] = jnp.zeros((H, 1), jnp.float32)

        def locate(t):
            """flat index -> (slot in group, block in slot, its pages)."""
            sidx = jnp.int32(0)
            base = jnp.int32(0)
            for i in range(Gs - 1):
                past = t >= starts[i + 1]
                sidx = sidx + past.astype(jnp.int32)
                base = base + jnp.where(past, counts[i], 0)
            npg = pages_of[Gs - 1]
            for i in range(Gs - 1):
                npg = jnp.where(sidx == i, pages_of[i], npg)
            return sidx, t - base, npg

        def dmas(sidx, wb, npg, slot):
            """A block's 2 * PB copies. Pages past the slot's last are
            its last page again: their positions lie past its length."""
            out = []
            for j in range(PB):
                pg = tbl_ref[b0 + sidx, jnp.minimum(wb * PB + j, npg - 1)]
                out.append(pltpu.make_async_copy(
                    c_hbm.at[li, pg, 0], cbuf.at[slot, j], sem.at[slot, j, 0]))
                out.append(pltpu.make_async_copy(
                    r_hbm.at[li, pg, 0], rbuf.at[slot, j], sem.at[slot, j, 1]))
            return out

        def start_fetch(t):
            sidx, wb, npg = locate(t)
            for d in dmas(sidx, wb, npg, jax.lax.rem(t, _NBUF)):
                d.start()

        for j in range(_NBUF - 1):
            @pl.when(jnp.int32(j) < total)
            def _(j=j):
                start_fetch(jnp.int32(j))

        def body(t, carry):
            @pl.when(t + _NBUF - 1 < total)
            def _():
                start_fetch(t + _NBUF - 1)
            slot = jax.lax.rem(t, _NBUF)
            sidx, wb, npg = locate(t)
            b = b0 + sidx
            for d in dmas(sidx, wb, npg, slot):
                d.wait()
            length = len_ref[b]
            cp = cbuf[slot].reshape(T, R)
            # every head at once: (H, R) x (R, T) and (H, rope) x (rope, T)
            s = jax.lax.dot_general(
                qc_ref[sidx], cp, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)              # (H, T)
            qr = qr_ref[sidx]
            s = s + jnp.concatenate(
                [jnp.dot(qr, rbuf[slot, j],
                         preferred_element_type=jnp.float32)
                 for j in range(PB)], axis=1)
            t0 = wb * T
            valid = (t0 + jax.lax.broadcasted_iota(
                jnp.int32, (1, T), 1)) < length
            if masked:
                kept = kp_ref[sidx, pl.ds(wb, 1), :] != 0        # (1, T)
                valid = valid & kept
            s = jnp.where(valid, s * scale, NEG)
            m = ms[sidx]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)        # (H, T)
            ls[sidx] = ls[sidx] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            ms[sidx] = m_new
            # the cached rows again, as values; those past the length
            # zeroed (stale rows of the last page, repeated pages)
            live = (t0 + jax.lax.broadcasted_iota(
                jnp.int32, (T, 1), 0)) < length
            if masked:
                live = live & _column(kept)
            pv = jnp.dot(p.astype(cp.dtype), jnp.where(live, cp, 0),
                         preferred_element_type=jnp.float32)     # (H, R)
            accs[sidx] = accs[sidx] * alpha + pv

            # at the slot's last block, stage what the append preserves:
            # the write row's 8-row tile of latents and the whole
            # (rope, page) block of its last page
            off = off_ref[b]
            tile0 = (off // _TILE) * _TILE

            @pl.when((wb + 1) * PB >= npg)
            def _():
                jl = npg - 1 - wb * PB
                stc[sidx] = cbuf[slot, jl, pl.ds(tile0, _TILE), :]
                strp[sidx] = rbuf[slot, jl]
            return carry

        jax.lax.fori_loop(0, total, body, jnp.int32(0))

        # per slot: fold the current (not yet pooled) token in exactly,
        # then append its row. Rows to preserve were staged from the
        # streamed window (the write page IS the slot's last page when
        # off > 0; a fresh page's other rows are never read); a slot
        # that streamed nothing (idle: the trash page) writes zeros
        # beside its row, not whatever the scratch held.
        writes = []
        for i in range(Gs):
            b = b0 + i
            cc = cc_ref[i]                                       # (1, R)
            ccf = cc.astype(jnp.float32)
            s_cur = (jnp.sum(qc_ref[i].astype(jnp.float32) * ccf,
                             axis=-1, keepdims=True)
                     + jnp.sum(qr_ref[i].astype(jnp.float32)
                               * cr_ref[i].astype(jnp.float32),
                               axis=-1, keepdims=True)) * scale  # (H, 1)
            m = ms[i]
            m2 = jnp.maximum(m, s_cur)
            if masked:
                # the current token competes for its place like any other
                # causal position: folded in only if the mask keeps it (a
                # slot that keeps nothing at all, an idle one, gets zeros)
                own = ck_ref[b] != 0
                m2 = jnp.where(own, m2, m)
            a = jnp.exp(m - m2)
            bta = jnp.exp(s_cur - m2)
            if masked:
                bta = jnp.where(own, bta, 0.0)
            out = accs[i] * a + ccf * bta
            norm = ls[i] * a + bta
            if masked:
                norm = jnp.maximum(norm, 1e-30)
            out_ref[i] = (out / norm).astype(out_ref.dtype)

            off = off_ref[b]
            tile0 = (off // _TILE) * _TILE
            streamed = counts[i] > 0
            row = jax.lax.broadcasted_iota(
                jnp.int32, (_TILE, 1), 0) == (off - tile0)
            crw[i] = jnp.where(row, cc, jnp.where(streamed, stc[i], 0))
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1) == off
            rrw[i] = jnp.where(lane, crt_ref[i],
                               jnp.where(streamed, strp[i], 0))
            wp = wp_ref[b]
            slot_writes = [
                pltpu.make_async_copy(
                    crw.at[i], opc_ref.at[li, wp, 0, pl.ds(tile0, _TILE)],
                    rw_sem.at[i, 0]),
                pltpu.make_async_copy(rrw.at[i], opr_ref.at[li, wp, 0],
                                      rw_sem.at[i, 1]),
            ]
            for wcp in slot_writes:
                wcp.start()
            writes += slot_writes
        for wcp in writes:
            wcp.wait()

    def rows(g, *_):
        return (g, 0, 0)

    scalars = (block_table, lengths, write_page, write_offset, layer)
    operands = (q_c, q_r, pool_c, pool_r, cur_c[:, None], cur_r[:, None],
                cur_r[:, :, None])
    in_specs = [
        pl.BlockSpec((Gs, H, R), rows),
        pl.BlockSpec((Gs, H, rope), rows),
        pl.BlockSpec(memory_space=pl.ANY),      # latent pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),      # rotary pool likewise
        pl.BlockSpec((Gs, 1, R), rows),
        pl.BlockSpec((Gs, 1, rope), rows),
        pl.BlockSpec((Gs, rope, 1), rows),
    ]
    if masked:
        # the mask cut to the kernel's blocks, a row of T bits a block
        # (whole sublane tiles of blocks): int32, a block's row read at a
        # dynamic sublane
        W = block_table.shape[1]
        nb = -(-W // PB)
        nb += -nb % 8
        bits = jnp.pad(keep.astype(jnp.int32),
                       ((0, 0), (0, nb * T - W * page))).reshape(B, nb, T)
        scalars += (cur_keep.astype(jnp.int32),)
        operands += (bits,)
        in_specs.append(pl.BlockSpec((Gs, nb, T), rows))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # table, lengths, write page/offset, layer (, the current bit)
        num_scalar_prefetch=len(scalars),
        grid=(B // Gs,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((Gs, H, R), rows),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((_NBUF, PB, page, R), pool_c.dtype),
            pltpu.VMEM((_NBUF, PB, rope, page), pool_r.dtype),
            pltpu.VMEM((Gs, H, R), jnp.float32),            # accs
            pltpu.VMEM((Gs, H, 1), jnp.float32),            # ms
            pltpu.VMEM((Gs, H, 1), jnp.float32),            # ls
            pltpu.VMEM((Gs, _TILE, R), pool_c.dtype),       # staged latents
            pltpu.VMEM((Gs, rope, page), pool_r.dtype),     # staged rotary
            pltpu.VMEM((Gs, _TILE, R), pool_c.dtype),       # latent writeback
            pltpu.VMEM((Gs, rope, page), pool_r.dtype),     # rotary writeback
            pltpu.SemaphoreType.DMA((_NBUF, PB, 2)),
            pltpu.SemaphoreType.DMA((Gs, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, R), q_c.dtype),
            jax.ShapeDtypeStruct(pool_c.shape, pool_c.dtype),
            jax.ShapeDtypeStruct(pool_r.shape, pool_r.dtype),
        ],
        # operands: tbl=0, lens=1, wp=2, off=3, layer=4, q_c=5, q_r=6,
        # pool_c=7, pool_r=8, ... (one later under a mask)
        input_output_aliases={len(scalars) + 2: 1, len(scalars) + 3: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="latent_attn_decode",
    )(*scalars, *operands)


#: The kernel call under ``jax.jit``: ONE trace of the kernel body serves
#: every decode program of a process and both stacks of a model (traced
#: anew it is ~0.8 s of set-up a decode program; ops/chunk_attention.py
#: does the same). ``LatentKV``'s unmasked call stays bare: its lowered
#: programs are pinned to the byte (tests/test_latent_program_pins.py).
latent_attention_decode_jit = jax.jit(
    latent_attention_decode,
    static_argnames=("scale", "interpret", "block_pages"))


def latent_attention_decode_reference(q_c, q_r, pool_c, pool_r, block_table,
                                      lengths, cur_c, cur_r, scale: float):
    """Pure-jnp oracle of the kernel's attention (one layer's pool: (N, 1,
    page, R) and (N, 1, rope, page)), float32 at the highest precision;
    the append is left to the caller."""
    B, H, R = q_c.shape
    W = block_table.shape[1]
    page = pool_c.shape[2]
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    c = pool_c[block_table][:, :, 0].reshape(B, W * page, R).astype(f32)
    r = pool_r[block_table][:, :, 0].swapaxes(2, 3).reshape(
        B, W * page, -1).astype(f32)
    c = jnp.concatenate([c, cur_c.astype(f32)[:, None]], axis=1)
    r = jnp.concatenate([r, cur_r.astype(f32)[:, None]], axis=1)
    s = (jnp.einsum("bhc,btc->bht", q_c.astype(f32), c, precision=hi)
         + jnp.einsum("bhr,btr->bht", q_r.astype(f32), r, precision=hi)
         ) * scale
    t = jnp.arange(W * page + 1)[None, None, :]
    ok = (t < lengths[:, None, None]) | (t == W * page)
    p = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
    c = jnp.where(ok[:, 0, :, None], c, 0)
    return jnp.einsum("bht,btc->bhc", jnp.where(ok, p, 0), c,
                      precision=hi).astype(q_c.dtype)
