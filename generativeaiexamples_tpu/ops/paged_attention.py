"""Pallas paged-attention decode kernel (TPU).

The decode hot path reads each slot's KV page window from the shared pool
and appends the step's new K/V row. Doing either through XLA ops was the
bottleneck and the round-2/3 OOMs in one:

- ``pool[block_table]`` lowers to a generic gather that runs far below
  DMA speed;
- the row scatter makes XLA prefer a permuted pool layout while the kernel
  needs row-major, so every round paid a full-pool relayout copy (2x pool
  HBM);
- pool reads inside an opaque kernel plus an external scatter defeat
  XLA's aliasing analysis, double-buffering the loop carry.

This kernel does the whole step natively: the block table and write
location ride scalar prefetch (SMEM), page windows stream HBM->VMEM
through a manual multi-buffered DMA pipeline, attention accumulates
page-by-page with an online softmax (flash style) over PER-SLOT dynamic
page counts (HBM reads follow each sequence's live length, not the batch
max), and the new K/V row lands in the pool via an aligned 8-row-tile
write whose preserved rows come from the already-streamed window page —
no read-modify-write round trip. The pool is aliased in/out
(``input_output_aliases``), so the whole decode step leaves the pool in
place, in one layout, with zero XLA gathers/scatters/copies.

Program layout (round 8): programs are SLOT GROUPS, not single slots.
The former one-program-per-slot grid ran B sequential programs per layer,
and each program boundary drained its private 2-deep DMA pipeline — at 64
slots the drains and fixed per-program overhead add up. Now one
program owns ``_GROUP`` slots and streams ALL their live pages through a
single flat (slot, page) loop behind one ``_NBUF``-deep buffer ring:

- page fetches batch across slots — the fetch for the next slot's first
  page issues while the current slot's last pages are still computing, so
  a short or finished slot never leaves the stream idle;
- per-slot online-softmax state lives in VMEM scratch, indexed by the
  flat loop's current slot;
- the pipeline depth (``_NBUF - 1`` fetches in flight) rides out
  per-page DMA latency variance that double buffering could not;
- program count (and per-program fixed overhead) drops by the group
  factor.

Same role as the paged-KV device kernels the reference gets from the
TRT-LLM C++ backend (reference: ensemble_models/llama/tensorrt_llm/
config.pbtxt.j2:28-34 paged_kv_cache; model_server/server.py:67-71).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

NEG = -1e30
_TILE = 8   # sublane tile: HBM DMA slices must be 8-row aligned
_NBUF = 4   # page-buffer ring depth: _NBUF - 1 fetches stay in flight
_GROUP = 8  # slots per program (largest divisor of B <= this)


def group_size(batch: int) -> int:
    """Slots per kernel program: the largest divisor of ``batch`` that is
    <= ``PAGED_GROUP_SLOTS`` (default 8). A divisor keeps the grid exact;
    the env knob exists for VMEM-constrained geometries."""
    cap = int(os.environ.get("PAGED_GROUP_SLOTS", str(_GROUP)))
    g = max(1, min(batch, cap))
    while batch % g:
        g -= 1
    return g


def kernel_supported(page: int, num_heads: int, num_kv_heads: int,
                     head_dim: int) -> bool:
    """Kernel preconditions: lane-width page/head_dim (Mosaic tiling) and
    GQA-divisible head counts (the (KV, G, hd) query reshape)."""
    return (head_dim % 128 == 0 and page % 128 == 0
            and num_kv_heads > 0 and num_heads % num_kv_heads == 0)


def paged_attention_decode(q: jax.Array, pool_k: jax.Array,
                           pool_v: jax.Array, block_table: jax.Array,
                           lengths: jax.Array, cur_k: jax.Array,
                           cur_v: jax.Array, write_page: jax.Array,
                           write_offset: jax.Array, layer: jax.Array,
                           *, pool_ks: jax.Array | None = None,
                           pool_vs: jax.Array | None = None,
                           interpret: bool = False,
                           window: jax.Array | None = None,
                           scale: float | None = None):
    """GQA decode attention + KV append over a paged pool, one query token
    per slot. ``scale``: what the scores are multiplied by where the
    model states it (``LlamaConfig.score_scale``); None = head_dim **
    -0.5.

    q:            (B, H, hd)           current token's queries
    pool_k/v:     (L, N, KV, page, hd) shared page pool, all layers (the
                                       caller scans layers with the pools
                                       in the carry; passing whole pools
                                       through the aliased call keeps the
                                       scan carry in place)
    block_table:  (B, W) int32         physical page of each logical page
    lengths:      (B,) int32           cached tokens per slot (== pos;
                                       current token is NOT in the pool)
    cur_k/cur_v:  (B, KV, hd)          current token's K/V (pool dtype,
                                       or bf16/f32 when the pool is int8 —
                                       the kernel quantizes on append)
    write_page:   (B,) int32           physical page for the new row
                                       (page 0 = trash, inactive slots)
    write_offset: (B,) int32           row within that page
    layer:        (1,) int32           which layer to read/write
    pool_ks/vs:   (L, N, KV, page)     OPTIONAL per-row scales: presence
                                       switches the kernel to the int8-KV
                                       path (ops/kv_quant.py) — int8 pages
                                       stream at half the HBM bytes, are
                                       widened to bf16 once in VMEM, and
                                       the scales fold into scores (K) and
                                       probabilities (V) around the MXU
                                       dots; the append quantizes the new
                                       row in-kernel and writes its scale
                                       back through the already-streamed
                                       scale page.
    window:       (1,) int32           OPTIONAL: the layer's window in
                                       keys (0 = its whole context). The
                                       query, at position ``lengths[b]``,
                                       attends itself and the cached keys
                                       at positions > lengths[b] - window:
                                       a slot's page loop starts at the
                                       page that holds the first of them
                                       and masks that page's rows below
                                       it, so pages wholly behind the
                                       window are never read. None traces
                                       the kernel without any of this.
    Returns (attn (B, H, hd) in q.dtype, new_pool_k, new_pool_v[,
    new_pool_ks, new_pool_vs]) with the pools aliased in place. Scaling
    (1/sqrt(hd)) applied here.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    L, N, KV, page, _ = pool_k.shape
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    quant = pool_ks is not None
    if quant:
        return _paged_attention_decode_quant(
            q, pool_k, pool_v, pool_ks, pool_vs, block_table, lengths,
            cur_k, cur_v, write_page, write_offset, layer,
            interpret=interpret, window=window, scale=scale)
    Gs = group_size(B)
    windowed = window is not None

    def kernel(tbl_ref, len_ref, wp_ref, off_ref, l_ref, *refs):
        if windowed:
            win_ref, refs = refs[0], refs[1:]
        (q_ref, k_hbm, v_hbm, ck_ref, cv_ref, out_ref, opk_ref, opv_ref,
         kbuf, vbuf, accs, ms, ls, stk, stv, krw, vrw, sem, rw_sem) = refs
        gi = pl.program_id(0)
        li = l_ref[0]
        b0 = gi * Gs

        def first_key(length):
            """The first cached position a slot's query attends."""
            return _first_key(length, win_ref[0])

        # Per-slot live page counts and their flat prefix starts: the
        # group's pages stream as ONE flat sequence t in [0, total),
        # slot boundaries invisible to the DMA pipeline.
        counts = [jax.lax.div(len_ref[b0 + i] + (page - 1), page)
                  for i in range(Gs)]
        if windowed:      # a slot's pages from its window's first on
            counts = [c - jax.lax.div(first_key(len_ref[b0 + i]), page)
                      for i, c in enumerate(counts)]
        starts = [jnp.int32(0)]
        for c in counts:
            starts.append(starts[-1] + c)
        total = starts[Gs]

        # Scratch persists across grid programs: re-init this group's
        # softmax state (a zero-page slot must fold its current token
        # against a fresh carry, not the previous group's).
        for i in range(Gs):
            accs[i] = jnp.zeros((KV, G, hd), jnp.float32)
            ms[i] = jnp.full((KV, G), NEG, jnp.float32)
            ls[i] = jnp.zeros((KV, G), jnp.float32)

        def locate(t):
            """flat index -> (slot-in-group, page-within-slot, count)."""
            sidx = jnp.int32(0)
            base = jnp.int32(0)
            for i in range(Gs - 1):
                past = t >= starts[i + 1]
                sidx = sidx + past.astype(jnp.int32)
                base = base + jnp.where(past, counts[i], 0)
            cnt = counts[Gs - 1]
            for i in range(Gs - 1):
                cnt = jnp.where(sidx == i, counts[i], cnt)
            return sidx, t - base, cnt

        def dmas(sidx, w, slot):
            if windowed:
                w = w + jax.lax.div(first_key(len_ref[b0 + sidx]), page)
            pg = tbl_ref[b0 + sidx, w]
            return (pltpu.make_async_copy(k_hbm.at[li, pg], kbuf.at[slot],
                                          sem.at[slot, 0]),
                    pltpu.make_async_copy(v_hbm.at[li, pg], vbuf.at[slot],
                                          sem.at[slot, 1]))

        def start_fetch(t):
            sidx, w, _ = locate(t)
            for d in dmas(sidx, w, jax.lax.rem(t, _NBUF)):
                d.start()

        # Prologue: fill the ring (up to _NBUF - 1 fetches in flight).
        for j in range(_NBUF - 1):
            @pl.when(jnp.int32(j) < total)
            def _(j=j):
                start_fetch(jnp.int32(j))

        def body(t, carry):
            # Top off the pipeline first: buffer (t-1) % _NBUF was freed
            # by the previous step's (program-ordered) compute.
            @pl.when(t + _NBUF - 1 < total)
            def _():
                start_fetch(t + _NBUF - 1)
            slot = jax.lax.rem(t, _NBUF)
            # ONE locate per iteration: the wait descriptors reuse its
            # result (the top-off fetch above locates t + _NBUF - 1, a
            # different flat index).
            sidx, w, cnt = locate(t)
            b = b0 + sidx
            for d in dmas(sidx, w, slot):
                d.wait()
            length = len_ref[b]
            qv = q_ref[sidx].reshape(KV, G, hd)
            # Operands stay in pool dtype into the MXU; accumulation is
            # f32 via preferred_element_type — no widened VMEM copies.
            kp = kbuf[slot]                                  # (KV,page,hd)
            vp = vbuf[slot]
            scores = jax.lax.dot_general(
                qv, kp, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale  # (KV,G,page)
            if windowed:
                # rows of the window's first page below its first key
                # (every streamed page keeps at least one valid row)
                lo = first_key(length)
                tpos = (w + jax.lax.div(lo, page)) * page \
                    + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
                valid = (tpos < length) & (tpos >= lo)
            else:
                valid = (w * page + jax.lax.broadcasted_iota(
                    jnp.int32, (1, 1, page), 2)) < length
            scores = jnp.where(valid, scores, NEG)

            m = ms[sidx][..., None]
            l = ls[sidx][..., None]
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new)                      # (KV,G,page)
            ls[sidx] = (l * alpha + jnp.sum(p, axis=-1,
                                            keepdims=True))[..., 0]
            ms[sidx] = m_new[..., 0]
            pv = jax.lax.dot_general(
                p.astype(vp.dtype), vp, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)          # (KV,G,hd)
            accs[sidx] = accs[sidx] * alpha + pv

            # Stage the append-source tile at the slot's LAST page: the
            # ring reuses this buffer _NBUF pages later (possibly mid-way
            # through ANOTHER slot), so the 8 preserved rows are copied
            # out now instead of read back from HBM in the epilogue.
            off = off_ref[b]
            tile0 = (off // _TILE) * _TILE

            @pl.when(w + 1 == cnt)
            def _():
                stk[sidx] = kbuf[slot, :, pl.ds(tile0, _TILE), :]
                stv[sidx] = vbuf[slot, :, pl.ds(tile0, _TILE), :]
            return carry

        jax.lax.fori_loop(0, total, body, jnp.int32(0))

        # Per-slot epilogue: fold the current (not yet pooled) token in
        # exactly via partials, then append the new row without a
        # read-modify-write round trip — rows to preserve (rows < off of
        # the write page) were staged from the streamed window; when
        # off == 0 the page is fresh and dead rows are garbage attention
        # masks (rows >= length are never read).
        writes = []
        for i in range(Gs):
            b = b0 + i
            qv = q_ref[i].reshape(KV, G, hd)
            m = ms[i][..., None]
            l = ls[i][..., None]
            acc = accs[i]
            ck = ck_ref[i].astype(jnp.float32)               # (KV,hd)
            cv = cv_ref[i].astype(jnp.float32)
            s_cur = jnp.sum(qv.astype(jnp.float32) * ck[:, None, :],
                            axis=-1, keepdims=True) * scale  # (KV,G,1)
            m2 = jnp.maximum(m, s_cur)
            a = jnp.exp(m - m2)
            bta = jnp.exp(s_cur - m2)
            out = acc * a + cv[:, None, :] * bta
            denom = l * a + bta
            out_ref[i] = (out / denom).reshape(H, hd).astype(out_ref.dtype)

            off = off_ref[b]
            tile0 = (off // _TILE) * _TILE
            row_mask = jax.lax.broadcasted_iota(
                jnp.int32, (1, _TILE, 1), 1) == (off - tile0)
            krw[i] = jnp.where(row_mask, ck_ref[i][:, None, :], stk[i])
            vrw[i] = jnp.where(row_mask, cv_ref[i][:, None, :], stv[i])
            wp = wp_ref[b]
            kwr = pltpu.make_async_copy(
                krw.at[i], opk_ref.at[li, wp, :, pl.ds(tile0, _TILE)],
                rw_sem.at[i, 0])
            vwr = pltpu.make_async_copy(
                vrw.at[i], opv_ref.at[li, wp, :, pl.ds(tile0, _TILE)],
                rw_sem.at[i, 1])
            kwr.start()
            vwr.start()
            writes += [kwr, vwr]
        for wcp in writes:
            wcp.wait()

    n_pre = 5 + windowed         # table, lengths, write page/offset,
    grid_spec = pltpu.PrefetchScalarGridSpec(      # layer[, window]
        num_scalar_prefetch=n_pre,
        grid=(B // Gs,),
        in_specs=[
            pl.BlockSpec((Gs, H, hd), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V pool stays in HBM
            pl.BlockSpec((Gs, KV, hd), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec((Gs, KV, hd), lambda g, *_: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Gs, H, hd), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((_NBUF, KV, page, hd), pool_k.dtype),
            pltpu.VMEM((_NBUF, KV, page, hd), pool_v.dtype),
            pltpu.VMEM((Gs, KV, G, hd), jnp.float32),   # accs
            pltpu.VMEM((Gs, KV, G), jnp.float32),       # ms
            pltpu.VMEM((Gs, KV, G), jnp.float32),       # ls
            pltpu.VMEM((Gs, KV, _TILE, hd), pool_k.dtype),  # staged k
            pltpu.VMEM((Gs, KV, _TILE, hd), pool_v.dtype),  # staged v
            pltpu.VMEM((Gs, KV, _TILE, hd), pool_k.dtype),  # k writeback
            pltpu.VMEM((Gs, KV, _TILE, hd), pool_v.dtype),  # v writeback
            pltpu.SemaphoreType.DMA((_NBUF, 2)),
            pltpu.SemaphoreType.DMA((Gs, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, hd), q.dtype),
            jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
            jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype),
        ],
        # operand numbering includes the scalar-prefetch args (tbl=0,
        # lens=1, wp=2, off=3, layer=4, q=5, pool_k=6, pool_v=7, ck=8,
        # cv=9)
        input_output_aliases={n_pre + 1: 1, n_pre + 2: 2},
        interpret=interpret,
        name="paged_attn_decode",
    )(block_table, lengths, write_page, write_offset, layer,
      *((window,) if windowed else ()), q, pool_k, pool_v, cur_k, cur_v)


def _first_key(length, window):
    """First cached position the query at position ``length`` attends
    under ``window`` keys (itself among them; 0 = no window)."""
    return jnp.where(window > 0, jnp.maximum(length - window + 1, 0), 0)


def _paged_attention_decode_quant(q, pool_k, pool_v, pool_ks, pool_vs,
                                  block_table, lengths, cur_k, cur_v,
                                  write_page, write_offset, layer,
                                  *, interpret=False, window=None,
                                  scale=None):
    """int8-KV variant of the decode kernel (see paged_attention_decode).

    Same slot-grouped program structure — flat cross-slot page loop,
    ``_NBUF``-deep buffer ring, per-slot softmax scratch, staged
    appends — with int8 pool pages and a bf16 per-row scale pool
    (``(L, N, KV, page)``) streamed alongside. HBM page traffic: int8
    K+V (half the bf16 bytes) + the scale blocks (~1/128 of the int8
    bytes each). The int8->compute-dtype widen happens once per page in
    VMEM; the MXU dots stay in the query dtype. K scales fold into the
    scores AFTER the QK^T dot (each K row scales its column of scores);
    V scales fold INTO the probabilities before the PV dot (each V row
    scales its contribution).

    The append quantizes the current row in-kernel (symmetric per-row,
    ops/kv_quant.py semantics: scale cast to bf16 before the divide) and
    writes the int8 8-row tile the same way as the bf16 kernel. The
    SCALE write is a full (KV, page) block instead of a tile: the page
    dim sits on lanes there (so score broadcasting needs no transpose),
    and lane-dim slices can't DMA — but the block to preserve was staged
    from the streamed window at the slot's last page (the write page IS
    the last streamed window page when off > 0; fresh-page rows are
    garbage that attention masks), so the write-back costs one small
    extra DMA, not a read-modify-write.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    L, N, KV, page, _ = pool_k.shape
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    cd = q.dtype  # compute dtype for the MXU dots
    Gs = group_size(B)
    windowed = window is not None

    def kernel(tbl_ref, len_ref, wp_ref, off_ref, l_ref, *refs):
        if windowed:
            win_ref, refs = refs[0], refs[1:]
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, ck_ref, cv_ref,
         out_ref, opk_ref, opv_ref, opks_ref, opvs_ref,
         kbuf, vbuf, ksbuf, vsbuf, accs, ms, ls,
         stk, stv, stks, stvs, krw, vrw, ksrw, vsrw, sem, rw_sem) = refs
        gi = pl.program_id(0)
        li = l_ref[0]
        b0 = gi * Gs

        def first_key(length):
            return _first_key(length, win_ref[0])

        counts = [jax.lax.div(len_ref[b0 + i] + (page - 1), page)
                  for i in range(Gs)]
        if windowed:      # as the bf16 kernel: from the window's page on
            counts = [c - jax.lax.div(first_key(len_ref[b0 + i]), page)
                      for i, c in enumerate(counts)]
        starts = [jnp.int32(0)]
        for c in counts:
            starts.append(starts[-1] + c)
        total = starts[Gs]

        for i in range(Gs):
            accs[i] = jnp.zeros((KV, G, hd), jnp.float32)
            ms[i] = jnp.full((KV, G), NEG, jnp.float32)
            ls[i] = jnp.zeros((KV, G), jnp.float32)

        def locate(t):
            sidx = jnp.int32(0)
            base = jnp.int32(0)
            for i in range(Gs - 1):
                past = t >= starts[i + 1]
                sidx = sidx + past.astype(jnp.int32)
                base = base + jnp.where(past, counts[i], 0)
            cnt = counts[Gs - 1]
            for i in range(Gs - 1):
                cnt = jnp.where(sidx == i, counts[i], cnt)
            return sidx, t - base, cnt

        def dmas(sidx, w, slot):
            if windowed:
                w = w + jax.lax.div(first_key(len_ref[b0 + sidx]), page)
            pg = tbl_ref[b0 + sidx, w]
            pairs = ((k_hbm, kbuf), (v_hbm, vbuf),
                     (ks_hbm, ksbuf), (vs_hbm, vsbuf))
            return [pltpu.make_async_copy(hbm.at[li, pg], buf.at[slot],
                                          sem.at[slot, which])
                    for which, (hbm, buf) in enumerate(pairs)]

        def start_fetch(t):
            sidx, w, _ = locate(t)
            for d in dmas(sidx, w, jax.lax.rem(t, _NBUF)):
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
            # ONE locate per iteration (the top-off above locates its
            # own flat index); wait descriptors reuse the result.
            sidx, w, cnt = locate(t)
            b = b0 + sidx
            for d in dmas(sidx, w, slot):
                d.wait()
            length = len_ref[b]
            qv = q_ref[sidx].reshape(KV, G, hd)
            kp = kbuf[slot].astype(cd)                       # (KV,page,hd)
            vp = vbuf[slot].astype(cd)
            ks = ksbuf[slot].astype(jnp.float32)             # (KV,page)
            vs = vsbuf[slot].astype(jnp.float32)
            scores = jax.lax.dot_general(
                qv, kp, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)          # (KV,G,page)
            scores = scores * ks[:, None, :] * scale
            if windowed:
                lo = first_key(length)
                tpos = (w + jax.lax.div(lo, page)) * page \
                    + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
                valid = (tpos < length) & (tpos >= lo)
            else:
                valid = (w * page + jax.lax.broadcasted_iota(
                    jnp.int32, (1, 1, page), 2)) < length
            scores = jnp.where(valid, scores, NEG)

            m = ms[sidx][..., None]
            l = ls[sidx][..., None]
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new)                      # (KV,G,page)
            # Zero masked probabilities AND scales explicitly before the
            # PV dot: p underflows to ~0 for masked lanes, but the scale
            # lanes beyond `length` hold whatever bytes the page carries
            # (garbage on a fresh page), and 0 * NaN = NaN would poison
            # the accumulator. Prefix-cache page sharing makes page-
            # content invariants load-bearing — same hygiene as the
            # sibling _paged_prefix_attention.
            p = jnp.where(valid, p, 0.0)
            vs = jnp.where(valid[0], vs, 0.0)
            ls[sidx] = (l * alpha + jnp.sum(p, axis=-1,
                                            keepdims=True))[..., 0]
            ms[sidx] = m_new[..., 0]
            pv = jax.lax.dot_general(
                (p * vs[:, None, :]).astype(cd), vp,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)          # (KV,G,hd)
            accs[sidx] = accs[sidx] * alpha + pv

            off = off_ref[b]
            tile0 = (off // _TILE) * _TILE

            @pl.when(w + 1 == cnt)
            def _():
                stk[sidx] = kbuf[slot, :, pl.ds(tile0, _TILE), :]
                stv[sidx] = vbuf[slot, :, pl.ds(tile0, _TILE), :]
                stks[sidx] = ksbuf[slot]
                stvs[sidx] = vsbuf[slot]
            return carry

        jax.lax.fori_loop(0, total, body, jnp.int32(0))

        # Per-slot epilogue: exact current-token fold (unquantized), then
        # the in-kernel quantized append. quantize_rows is the SAME
        # function the engine's insert/gather paths use (ops/kv_quant.py)
        # — plain jnp, and single-sourcing it keeps appended rows
        # bit-identical to bucket-inserted rows.
        from .kv_quant import quantize_rows
        writes = []
        for i in range(Gs):
            b = b0 + i
            qv = q_ref[i].reshape(KV, G, hd)
            m = ms[i][..., None]
            l = ls[i][..., None]
            acc = accs[i]
            ck = ck_ref[i].astype(jnp.float32)               # (KV,hd)
            cv = cv_ref[i].astype(jnp.float32)
            s_cur = jnp.sum(qv.astype(jnp.float32) * ck[:, None, :],
                            axis=-1, keepdims=True) * scale  # (KV,G,1)
            m2 = jnp.maximum(m, s_cur)
            a = jnp.exp(m - m2)
            bta = jnp.exp(s_cur - m2)
            out = acc * a + cv[:, None, :] * bta
            denom = l * a + bta
            out_ref[i] = (out / denom).reshape(H, hd).astype(out_ref.dtype)

            k_int, k_s = quantize_rows(ck)      # (KV, hd) int8, (KV,) bf16
            v_int, v_s = quantize_rows(cv)
            off = off_ref[b]
            tile0 = (off // _TILE) * _TILE
            row_mask = jax.lax.broadcasted_iota(
                jnp.int32, (1, _TILE, 1), 1) == (off - tile0)
            krw[i] = jnp.where(row_mask, k_int[:, None, :], stk[i])
            vrw[i] = jnp.where(row_mask, v_int[:, None, :], stv[i])
            # Scale block: lane `off` takes the new scale, every other
            # lane keeps the streamed page's value (garbage on a fresh
            # page — rows >= length are never attended). When NO page
            # was streamed (a trash-page append for an inactive slot)
            # the staging scratch is uninitialized VMEM — fill the other
            # lanes with zeros instead of copying a possible NaN bit
            # pattern into the pool.
            lane = jax.lax.broadcasted_iota(
                jnp.int32, (1, page), 1) == off
            streamed = counts[i] > 0
            ksrw[i] = jnp.where(lane, k_s[:, None].astype(jnp.bfloat16),
                                jnp.where(streamed, stks[i], 0))
            vsrw[i] = jnp.where(lane, v_s[:, None].astype(jnp.bfloat16),
                                jnp.where(streamed, stvs[i], 0))
            wp = wp_ref[b]
            slot_writes = [
                pltpu.make_async_copy(
                    krw.at[i], opk_ref.at[li, wp, :, pl.ds(tile0, _TILE)],
                    rw_sem.at[i, 0]),
                pltpu.make_async_copy(
                    vrw.at[i], opv_ref.at[li, wp, :, pl.ds(tile0, _TILE)],
                    rw_sem.at[i, 1]),
                pltpu.make_async_copy(ksrw.at[i], opks_ref.at[li, wp],
                                      rw_sem.at[i, 2]),
                pltpu.make_async_copy(vsrw.at[i], opvs_ref.at[li, wp],
                                      rw_sem.at[i, 3]),
            ]
            for wcp in slot_writes:
                wcp.start()
            writes += slot_writes
        for wcp in writes:
            wcp.wait()

    n_pre = 5 + windowed         # table, lengths, write page/offset,
    grid_spec = pltpu.PrefetchScalarGridSpec(      # layer[, window]
        num_scalar_prefetch=n_pre,
        grid=(B // Gs,),
        in_specs=[
            pl.BlockSpec((Gs, H, hd), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K pool (int8, HBM)
            pl.BlockSpec(memory_space=pl.ANY),   # V pool (int8, HBM)
            pl.BlockSpec(memory_space=pl.ANY),   # K scales (HBM)
            pl.BlockSpec(memory_space=pl.ANY),   # V scales (HBM)
            pl.BlockSpec((Gs, KV, hd), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec((Gs, KV, hd), lambda g, *_: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Gs, H, hd), lambda g, *_: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((_NBUF, KV, page, hd), pool_k.dtype),
            pltpu.VMEM((_NBUF, KV, page, hd), pool_v.dtype),
            pltpu.VMEM((_NBUF, KV, page), pool_ks.dtype),
            pltpu.VMEM((_NBUF, KV, page), pool_vs.dtype),
            pltpu.VMEM((Gs, KV, G, hd), jnp.float32),   # accs
            pltpu.VMEM((Gs, KV, G), jnp.float32),       # ms
            pltpu.VMEM((Gs, KV, G), jnp.float32),       # ls
            pltpu.VMEM((Gs, KV, _TILE, hd), pool_k.dtype),  # staged k
            pltpu.VMEM((Gs, KV, _TILE, hd), pool_v.dtype),  # staged v
            pltpu.VMEM((Gs, KV, page), pool_ks.dtype),      # staged ks
            pltpu.VMEM((Gs, KV, page), pool_vs.dtype),      # staged vs
            pltpu.VMEM((Gs, KV, _TILE, hd), pool_k.dtype),  # k writeback
            pltpu.VMEM((Gs, KV, _TILE, hd), pool_v.dtype),  # v writeback
            pltpu.VMEM((Gs, KV, page), pool_ks.dtype),      # ks writeback
            pltpu.VMEM((Gs, KV, page), pool_vs.dtype),      # vs writeback
            pltpu.SemaphoreType.DMA((_NBUF, 4)),
            pltpu.SemaphoreType.DMA((Gs, 4)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, hd), q.dtype),
            jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
            jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype),
            jax.ShapeDtypeStruct(pool_ks.shape, pool_ks.dtype),
            jax.ShapeDtypeStruct(pool_vs.shape, pool_vs.dtype),
        ],
        # operands: tbl=0, lens=1, wp=2, off=3, layer=4, q=5, pool_k=6,
        # pool_v=7, pool_ks=8, pool_vs=9, ck=10, cv=11
        input_output_aliases={n_pre + 1 + i: 1 + i for i in range(4)},
        interpret=interpret,
        name="paged_attn_decode_int8kv",
    )(block_table, lengths, write_page, write_offset, layer,
      *((window,) if windowed else ()),
      q, pool_k, pool_v, pool_ks, pool_vs, cur_k, cur_v)


def paged_attention_decode_reference(q, pool_k, pool_v, block_table,
                                     lengths, cur_k, cur_v, window=0,
                                     scale=None):
    """Pure-jnp attention oracle with identical masking/softmax semantics
    (tests + non-TPU backends); the pool append is left to the caller.
    This is the gather formulation the kernel replaces."""
    B, H, hd = q.shape
    N, KV, page, _ = pool_k.shape
    W = block_table.shape[1]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale

    kg = pool_k[block_table].swapaxes(2, 3).reshape(B, W * page, KV, hd)
    vg = pool_v[block_table].swapaxes(2, 3).reshape(B, W * page, KV, hd)
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, kg.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST) * scale
    tpos = jnp.arange(W * page)[None, None, None, :]
    scores = jnp.where(tpos < lengths[:, None, None, None], scores, NEG)
    if window:       # keys behind the window (the query sits at lengths)
        lo = jnp.maximum(lengths - window + 1, 0)
        scores = jnp.where(tpos >= lo[:, None, None, None], scores, NEG)
    s_cur = jnp.einsum("bkgd,bkd->bkg", qg, cur_k.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST) * scale
    all_scores = jnp.concatenate([scores, s_cur[..., None]], axis=-1)
    probs = jax.nn.softmax(all_scores, axis=-1)
    vg_all = jnp.concatenate(
        [vg.astype(jnp.float32),
         cur_v.astype(jnp.float32)[:, None, :, :]], axis=1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, vg_all,
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(B, H, hd).astype(q.dtype)
