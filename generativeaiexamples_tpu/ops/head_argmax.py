"""The decode tail as one pass: the stored ``lm_head`` streamed through
VMEM once, the penalties and the running winners under the weight stream.

The arithmetic of the vocabulary tile scan (``ops/fused_sampler.py``
``_greedy_stream`` / ``_sample_stream``) as one Pallas program a tail.
The scan's tile is a slice of the head and a mixed dot, two bitfields
unpacked by a reshape of the minor dimension, a chain of selects, maxima
and argmaxima — separate fusions inside a ``while``, so the DMA of tile
t+1 waits for the vector work on tile t and the times add. Here a grid
walks the vocabulary blocks in order, the head's block is brought in by
the pipeline (block t+1's DMA runs under block t's work), the normed
rows stay resident, and per block, shared by both kernels

- int8 -> the rows' dtype, the dot on the MXU with float32
  accumulation, ``x scale`` for a quantised head: the numerics of
  ``ops/quant.py matmul_f32`` (:func:`_block_logits`);
- the repetition penalty and the two bitfields straight from the
  block's WORDS (``ops/sampling.py``: bit i of word w = token 32 w + i).
  Words become lanes on the MXU: the four bytes of each word of the
  aligned 128-word window around the block, as exact small integers,
  against a 0/1 selector whose column l picks word l // 32 (one non-zero
  term an output: exact), then byte ``(l % 32) // 8`` by a select and
  bit ``l % 8`` by a shift. No reshape of a minor dimension anywhere;
  the sequence bans by an id compare; the lanes past the vocabulary at
  ``-inf`` (:func:`_penalised`);
- a block's max and its first argmax folded into a running ``(rows, 1)``
  best and its id, resident outputs. Ties keep the lowest id, as the
  scan (:func:`_fold_winner`).

:func:`greedy_head_argmax` is those three and nothing else.
:func:`sampled_head_stream` folds the greedy winner, and under the same
weight stream ``scaled = lf / max(temp, 1e-6)``, its running logsumexp
(a max and a sum rescaled to it), and the running Gumbel-max winner of
``scaled + noise`` — the noise a blocked operand drawn before the kernel
(a function of key, row and token id that plain ``jnp`` reproduces:
``fused_sampler.row_gumbel``) — and writes ``scaled`` out block by
block: the one ``(rows, V)`` array the candidate selection then reads
in place of a second pass over the head
(``fused_sampler.head_kernel_sample``).

Which heads take them is read off the head's storage (:func:`supported`):
a per-column int8 ``QTensor`` (``q``, ``scale``, optional
``pre_scale``), a raw ``(D, V)`` array, the tied ``(V, D)`` embedding.
int4 and grouped heads and the tp-sharded stream keep the scan. The
block width comes from the shapes (:func:`block_width`); there is no
knob.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant import is_grouped, is_quantized
from .sampling import MASK_BITS, NEG_INF

_LANE = 128
_BLOCK_BYTES = 4 << 20    # a head block in VMEM (the pipeline holds two)
_MAX_BLOCK = _LANE * MASK_BITS   # 4096 tokens: one window of mask words
_K_CHUNK = 512            # rows of a block converted and multiplied at once
_VMEM_LIMIT = 48 << 20
_NO_ID = jnp.iinfo(jnp.int32).max


def _storage(head_tree: dict):
    """``(weights, scale, pre_scale, tied)`` of the unembedding as
    ``models/llama.py lm_head_subtree`` hands it over, or ``None`` where
    the kernel does not take this storage."""
    head = head_tree.get("lm_head")
    if head is None:
        w, scale, pre, tied = head_tree["embed"], None, None, True
    elif is_quantized(head):
        if "q" not in head or is_grouped(head):
            return None
        w, scale, pre, tied = (head["q"], head["scale"],
                               head.get("pre_scale"), False)
    else:
        w, scale, pre, tied = head, None, None, False
    return (w, scale, pre, tied) if getattr(w, "ndim", 0) == 2 else None


def supported(head_tree: dict) -> bool:
    """Whether the kernels take this head, from its storage alone."""
    return _storage(head_tree) is not None


def armed(head_tree: dict) -> bool:
    """Whether a decode tail over this head, greedy or sampled, runs a
    kernel here: a supported storage on a TPU backend. Elsewhere the
    scan runs."""
    return jax.default_backend() == "tpu" and supported(head_tree)


def block_width(hidden: int, itemsize: int) -> int:
    """Tokens a block covers: the largest power of two of lanes whose
    block of the stored head is at most ``_BLOCK_BYTES`` (D 7168 int8 ->
    512, D 2048 int8 -> 2048), between one lane tile and one window of
    mask words — a power of two, so a block's words never straddle two
    windows."""
    tv = _LANE
    while tv < _MAX_BLOCK and 2 * tv * hidden * itemsize <= _BLOCK_BYTES:
        tv *= 2
    return tv


def _k_chunk(hidden: int) -> int:
    for kb in range(_K_CHUNK, 0, -_LANE):
        if hidden % kb == 0:
            return kb
    return hidden


def padded_rows(rows: int, itemsize: int) -> int:
    """Rows as the kernels hold them: whole sublane tiles of the rows'
    dtype (16 of a 2-byte dtype, 8 of float32)."""
    return -(-rows // 16) * 16 if itemsize < 4 else -(-rows // 8) * 8


def _pad_rows(a: jax.Array, rows: int, fill=0) -> jax.Array:
    return jnp.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1),
                   constant_values=fill)


def _words(words: jax.Array, rows: int, width: int) -> jax.Array:
    """A bitfield as the kernel holds it: int32, whole sublane tiles of
    rows, whole windows of words."""
    if words.ndim == 1:
        words = words[None, :]
    w = jax.lax.bitcast_convert_type(words, jnp.int32)
    return jnp.pad(w, ((0, rows - w.shape[0]), (0, width - w.shape[1])))


class _Geometry(NamedTuple):
    """What both kernels read off the shapes: the head as stored, the
    block, the padded rows and words."""
    w: jax.Array
    scale: jax.Array | None
    tied: bool
    R: int          # rows handed in
    D: int
    V: int
    tv: int         # tokens a block covers
    nb: int         # blocks
    wb: int         # mask words a block covers
    Wp: int         # mask words, in whole windows
    kb: int         # rows of a block multiplied at once
    Rp: int         # rows in whole sublane tiles of the rows' dtype
    per_row_ban: bool   # banned words (rows, W), not one (W,) for all
    Rb: int         # rows of the banned bitfield as the kernel holds it
    cdtype: jnp.dtype
    has_ban: bool


def _geometry(hn, head_tree, vocab_size, banned_words, ban_tok, block):
    """``(rows, geometry)``: the rows with the head's activation
    smoothing folded in, and the kernels' shapes."""
    w, scale, pre, tied = _storage(head_tree)
    R, D = hn.shape
    V = vocab_size
    assert w.shape == ((V, D) if tied else (D, V)), (w.shape, V, D)
    if pre is not None:     # activation smoothing folds into the rows
        hn = (hn * pre).astype(hn.dtype)
    tv = block or block_width(D, w.dtype.itemsize)
    assert _LANE <= tv <= _MAX_BLOCK and tv & (tv - 1) == 0, tv
    tv = min(tv, -(-V // _LANE) * _LANE)
    nb = -(-V // tv)
    wb = tv // MASK_BITS                    # words a block covers
    Rp = padded_rows(R, hn.dtype.itemsize)
    cdtype = hn.dtype if w.dtype == jnp.int8 \
        else jnp.promote_types(hn.dtype, w.dtype)
    per_row_ban = banned_words.ndim == 2
    return hn, _Geometry(
        w=w, scale=scale, tied=tied, R=R, D=D, V=V, tv=tv, nb=nb, wb=wb,
        Wp=-(-nb * wb // _LANE) * _LANE, kb=_k_chunk(D), Rp=Rp,
        per_row_ban=per_row_ban, Rb=Rp if per_row_ban else 8,
        cdtype=cdtype, has_ban=ban_tok is not None)


def _whole(shape):
    return pl.BlockSpec(shape, lambda j: (0, 0))


def _shared_operands(g: _Geometry, hn, rep_pen, seen_words, banned_words,
                     ban_tok, ban_hit):
    """``(args, in_specs)`` of the operands every head kernel takes, in
    the order :func:`_shared_refs` reads them: the resident rows, the
    head's block (and its scales'), the penalty, the two bitfields, the
    sequence bans."""
    args = [_pad_rows(hn, g.Rp), g.w]
    in_specs = [_whole((g.Rp, g.D)),
                pl.BlockSpec((g.tv, g.D), lambda j: (j, 0)) if g.tied
                else pl.BlockSpec((g.D, g.tv), lambda j: (0, j))]
    if g.scale is not None:
        args.append(g.scale.astype(jnp.float32).reshape(1, g.V))
        in_specs.append(pl.BlockSpec((1, g.tv), lambda j: (0, j)))
    args += [_pad_rows(rep_pen.astype(jnp.float32)[:, None], g.Rp, 1.0),
             _words(seen_words, g.Rp, g.Wp),
             _words(banned_words, g.Rb, g.Wp)]
    in_specs += [_whole((g.Rp, 1)), _whole((g.Rp, g.Wp)),
                 _whole((g.Rb, g.Wp))]
    if g.has_ban:     # a ban that did not hit matches no id
        args.append(_pad_rows(jnp.where(ban_hit, ban_tok, -1)
                              .astype(jnp.int32), g.Rp, -1))
        in_specs.append(_whole(args[-1].shape))
    return args, in_specs


def _shared_refs(g: _Geometry, it):
    """The shared operands' refs off the kernel's argument iterator:
    ``(hn, w, scale | None, pen, seen, banned, ban | None)``."""
    hn_ref, w_ref = next(it), next(it)
    scale_ref = next(it) if g.scale is not None else None
    pen_ref, seen_ref, banned_ref = next(it), next(it), next(it)
    ban_ref = next(it) if g.has_ban else None
    return hn_ref, w_ref, scale_ref, pen_ref, seen_ref, banned_ref, ban_ref


def _block_logits(g: _Geometry, hn_ref, w_ref, scale_ref):
    """The block's logits (Rp, tv) float32: the numerics of
    ``matmul_f32``."""
    lf = None
    for k0 in range(0, g.D, g.kb):
        x = hn_ref[:, k0:k0 + g.kb].astype(g.cdtype)
        wk = (w_ref[:, k0:k0 + g.kb] if g.tied
              else w_ref[k0:k0 + g.kb, :]).astype(g.cdtype)
        part = jax.lax.dot_general(
            x, wk, (((1,), (1 if g.tied else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        lf = part if lf is None else lf + part
    if scale_ref is not None:
        lf = lf * scale_ref[...]
    return lf


def _penalised(g: _Geometry, j, lf, pen_ref, seen_ref, banned_ref, ban_ref):
    """``(lf, ids)``: block ``j``'s logits under the repetition penalty,
    the banned words and the sequence bans, the lanes past the
    vocabulary at ``-inf``; and the lanes' token ids (1, tv)."""
    tv, Rp, M = g.tv, g.Rp, g.Rp + g.Rb
    # ---- words to lanes: the window's bytes against a selector
    w0 = j * g.wb
    ws = pl.multiple_of((w0 // _LANE) * _LANE, _LANE)
    words = jnp.concatenate(
        [seen_ref[:, pl.ds(ws, _LANE)],
         banned_ref[:, pl.ds(ws, _LANE)]], axis=0)       # (M, 128)
    bytes_ = jnp.concatenate(
        [((words >> (8 * k)) & 0xFF).astype(jnp.float32)
         for k in range(4)], axis=0)                     # (4M, 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tv), 1)
    # row w of the selector feeds the lanes of word w0 - ws + l // 32
    word_of = (w0 - ws) + (jax.lax.broadcasted_iota(
        jnp.int32, (_LANE, tv), 1) >> 5)
    sel = (jax.lax.broadcasted_iota(jnp.int32, (_LANE, tv), 0)
           == word_of).astype(jnp.float32)
    # bytes and 0/1 are exact in bfloat16
    spread = jax.lax.dot(bytes_.astype(jnp.bfloat16),
                         sel.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
    which = (lane >> 3) & 3          # the lane's byte of its word
    byte = spread[3 * M:]
    for k in (2, 1, 0):
        byte = jnp.where(which == k, spread[k * M:(k + 1) * M], byte)
    bit = ((byte.astype(jnp.int32) >> (lane & 7)) & 1) != 0   # (M, tv)
    seen = bit[:Rp]
    banned = bit[Rp:] if g.per_row_ban else bit[Rp:Rp + 1]

    # ---- penalties, bans, the lanes past the vocabulary
    pen = pen_ref[...]                                   # (Rp, 1)
    lf = jnp.where(seen, jnp.where(lf > 0, lf / pen, lf * pen), lf)
    lf = jnp.where(banned, NEG_INF, lf)
    ids = j * tv + lane
    if ban_ref is not None:
        hit = ids == ban_ref[:, 0:1]
        for s in range(1, ban_ref.shape[1]):
            hit = hit | (ids == ban_ref[:, s:s + 1])
        lf = jnp.where(hit, NEG_INF, lf)
    if g.V % tv:
        lf = jnp.where(ids < g.V, lf, -jnp.inf)
    return lf, ids


def _fold_winner(val, ids, best_ref, id_ref):
    """The block's max and its first argmax into the running
    ``(rows, 1)`` best and its id. Ties keep the lowest id."""
    m = jnp.max(val, axis=1, keepdims=True)
    first = jnp.min(jnp.where(val == m, ids, _NO_ID), axis=1,
                    keepdims=True)
    better = m > best_ref[...]
    best_ref[...] = jnp.where(better, m, best_ref[...])
    id_ref[...] = jnp.where(better, first, id_ref[...])


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_VMEM_LIMIT)


@jax.named_scope("tail")
def greedy_head_argmax(hn: jax.Array, head_tree: dict, vocab_size: int, *,
                       rep_pen, seen_words, banned_words, ban_tok=None,
                       ban_hit=None, block: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """``argmax`` over the vocabulary of the penalised logits of the
    already-normed rows ``hn`` (rows, D): ``(rows,)`` int32, the tokens
    ``fused_unembed_sample(..., greedy=True)`` returns. ``seen_words``
    (rows, W) and ``banned_words`` (W,) or (rows, W) are uint32
    bitfields, ``ban_tok`` / ``ban_hit`` the optional (rows, S) sequence
    bans. ``block`` (tests): a block width other than the shapes' own, a
    power of two of lanes."""
    hn, g = _geometry(hn, head_tree, vocab_size, banned_words, ban_tok,
                      block)

    def kernel(*refs):
        it = iter(refs)     # the operands in the order `args` is built
        hn_ref, w_ref, scale_ref, *masks = _shared_refs(g, it)
        best_ref, id_ref = it
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _():
            best_ref[...] = jnp.full_like(best_ref, -jnp.inf)
            id_ref[...] = jnp.zeros_like(id_ref)

        lf = _block_logits(g, hn_ref, w_ref, scale_ref)
        lf, ids = _penalised(g, j, lf, *masks)
        _fold_winner(lf, ids, best_ref, id_ref)

    args, in_specs = _shared_operands(g, hn, rep_pen, seen_words,
                                      banned_words, ban_tok, ban_hit)
    _, ids = pl.pallas_call(
        kernel,
        grid=(g.nb,),
        in_specs=in_specs,
        out_specs=(_whole((g.Rp, 1)), _whole((g.Rp, 1))),
        out_shape=(jax.ShapeDtypeStruct((g.Rp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((g.Rp, 1), jnp.int32)),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="greedy_head_argmax",
    )(*args)
    return ids[:g.R, 0]


@jax.named_scope("tail")
def sampled_head_stream(hn: jax.Array, head_tree: dict, vocab_size: int, *,
                        noise, temp, rep_pen, seen_words, banned_words,
                        ban_tok=None, ban_hit=None,
                        block: int | None = None, interpret: bool = False):
    """The sampled tail's pass over the head: ``(scaled, lse, bpid,
    brid)`` of the already-normed rows ``hn`` (rows, D).

    ``scaled`` (Rp, >= V) float32 — the penalised logits over
    ``max(temp, 1e-6)``, written block by block as the head streams
    (rows past ``rows`` are padding, lanes past ``V`` are ``-inf``): what
    the candidate selection reads in place of a second pass over the
    head. ``lse`` (rows,) their exact logsumexp, ``bpid`` the untruncated
    Gumbel-max winner ``argmax(scaled + noise)``, ``brid`` the greedy
    winner ``argmax`` of the penalised logits — running ``(rows, 1)``
    carries under the weight stream, ties to the lowest id.

    ``noise`` (Rp, V) float32 is streamed in beside the head's blocks
    (:func:`padded_rows` gives ``Rp``); the penalties' operands as
    :func:`greedy_head_argmax` takes them."""
    hn, g = _geometry(hn, head_tree, vocab_size, banned_words, ban_tok,
                      block)
    assert noise.shape == (g.Rp, g.V), (noise.shape, g.Rp, g.V)

    def kernel(*refs):
        it = iter(refs)
        hn_ref, w_ref, scale_ref, *masks = _shared_refs(g, it)
        tf_ref, noise_ref = next(it), next(it)
        (scaled_ref, m_ref, s_ref, bpert_ref, bpid_ref, braw_ref,
         brid_ref) = it
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _():
            for ref in (m_ref, bpert_ref, braw_ref):
                ref[...] = jnp.full_like(ref, -jnp.inf)
            for ref in (s_ref, bpid_ref, brid_ref):
                ref[...] = jnp.zeros_like(ref)

        lf = _block_logits(g, hn_ref, w_ref, scale_ref)
        lf, ids = _penalised(g, j, lf, *masks)
        _fold_winner(lf, ids, braw_ref, brid_ref)
        scaled = lf / tf_ref[...]
        scaled_ref[...] = scaled
        # the running logsumexp: a max and a sum rescaled to it (a block
        # holds at least one lane of the vocabulary: its max is finite)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(scaled, axis=1, keepdims=True))
        s_ref[...] = (s_ref[...] * jnp.exp(m_old - m_new)
                      + jnp.sum(jnp.exp(scaled - m_new), axis=1,
                                keepdims=True))
        m_ref[...] = m_new
        pert = scaled + noise_ref[...]
        if g.V % g.tv:      # what a block past the end reads is no noise
            pert = jnp.where(ids < g.V, pert, -jnp.inf)
        _fold_winner(pert, ids, bpert_ref, bpid_ref)

    args, in_specs = _shared_operands(g, hn, rep_pen, seen_words,
                                      banned_words, ban_tok, ban_hit)
    args += [_pad_rows(jnp.maximum(temp, 1e-6).astype(jnp.float32)[:, None],
                       g.Rp, 1.0), noise]
    blocked = pl.BlockSpec((g.Rp, g.tv), lambda j: (0, j))
    in_specs += [_whole((g.Rp, 1)), blocked]
    carry = lambda dtype: jax.ShapeDtypeStruct((g.Rp, 1), dtype)  # noqa: E731
    f32, i32 = jnp.float32, jnp.int32
    scaled, m, s, _, bpid, _, brid = pl.pallas_call(
        kernel,
        grid=(g.nb,),
        in_specs=in_specs,
        out_specs=(blocked,) + (_whole((g.Rp, 1)),) * 6,
        out_shape=(jax.ShapeDtypeStruct((g.Rp, g.nb * g.tv), f32),
                   carry(f32), carry(f32), carry(f32), carry(i32),
                   carry(f32), carry(i32)),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="sampled_head_stream",
    )(*args)
    lse = (m + jnp.log(s))[:g.R, 0]
    return scaled, lse, bpid[:g.R, 0], brid[:g.R, 0]
