"""The greedy tail as one pass: ``argmax`` of the penalised logits with
the stored ``lm_head`` streamed through VMEM once.

The arithmetic of the vocabulary tile scan
(``ops/fused_sampler.py _greedy_stream``) as one Pallas program. The
scan's tile is a slice of the head and a mixed dot, two bitfields
unpacked by a reshape of the minor dimension, a chain of selects, a max
and an argmax — separate fusions inside a ``while``, so the DMA of tile
t+1 waits for the vector work on tile t and the times add. Here a grid
walks the vocabulary blocks in order, the head's block is brought in by
the pipeline (block t+1's DMA runs under block t's work), the normed
rows stay resident, and per block

- int8 -> the rows' dtype, the dot on the MXU with float32
  accumulation, ``x scale`` for a quantised head: the numerics of
  ``ops/quant.py matmul_f32``;
- the repetition penalty and the two bitfields straight from the
  block's WORDS (``ops/sampling.py``: bit i of word w = token 32 w + i).
  Words become lanes on the MXU: the four bytes of each word of the
  aligned 128-word window around the block, as exact small integers,
  against a 0/1 selector whose column l picks word l // 32 (one non-zero
  term an output: exact), then byte ``(l % 32) // 8`` by a select and
  bit ``l % 8`` by a shift. No reshape of a minor dimension anywhere;
- the sequence bans by an id compare;
- the block's max and its first argmax folded into a running
  ``(rows, 1)`` best and its id, which are the kernel's resident
  outputs. Ties keep the lowest id, as the scan.

Which heads take it is read off the head's storage (:func:`supported`):
a per-column int8 ``QTensor`` (``q``, ``scale``, optional
``pre_scale``), a raw ``(D, V)`` array, the tied ``(V, D)`` embedding.
int4 and grouped heads and the tp-sharded stream keep the scan. The
block width comes from the shapes (:func:`block_width`); there is no
knob.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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
    """Whether :func:`greedy_head_argmax` takes this head, from its
    storage alone."""
    return _storage(head_tree) is not None


def armed(head_tree: dict) -> bool:
    """Whether a greedy tail over this head runs the kernel here: a
    supported storage on a TPU backend. Elsewhere the scan runs."""
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
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

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
    Wp = -(-nb * wb // _LANE) * _LANE
    kb = _k_chunk(D)
    # rows in whole sublane tiles of the rows' dtype
    Rp = -(-R // 16) * 16 if hn.dtype.itemsize < 4 else -(-R // 8) * 8
    per_row_ban = banned_words.ndim == 2
    Rb = Rp if per_row_ban else 8
    M = Rp + Rb
    cdtype = hn.dtype if w.dtype == jnp.int8 \
        else jnp.promote_types(hn.dtype, w.dtype)
    has_ban = ban_tok is not None

    def kernel(*refs):
        it = iter(refs)     # the operands in the order `args` is built
        hn_ref, w_ref = next(it), next(it)
        scale_ref = next(it) if scale is not None else None
        pen_ref, seen_ref, banned_ref = next(it), next(it), next(it)
        ban_ref = next(it) if has_ban else None
        best_ref, id_ref = it
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _():
            best_ref[...] = jnp.full_like(best_ref, -jnp.inf)
            id_ref[...] = jnp.zeros_like(id_ref)

        # ---- the block's logits: the numerics of matmul_f32
        lf = None
        for k0 in range(0, D, kb):
            x = hn_ref[:, k0:k0 + kb].astype(cdtype)
            wk = (w_ref[:, k0:k0 + kb] if tied
                  else w_ref[k0:k0 + kb, :]).astype(cdtype)
            part = jax.lax.dot_general(
                x, wk, (((1,), (1 if tied else 0,)), ((), ())),
                preferred_element_type=jnp.float32)
            lf = part if lf is None else lf + part
        if scale_ref is not None:
            lf = lf * scale_ref[...]

        # ---- words to lanes: the window's bytes against a selector
        w0 = j * wb
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
        banned = bit[Rp:] if per_row_ban else bit[Rp:Rp + 1]

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
        if V % tv:
            lf = jnp.where(ids < V, lf, -jnp.inf)

        # ---- the block's max and first argmax into the running best
        m = jnp.max(lf, axis=1, keepdims=True)
        first = jnp.min(jnp.where(lf == m, ids, _NO_ID), axis=1,
                        keepdims=True)
        better = m > best_ref[...]
        best_ref[...] = jnp.where(better, m, best_ref[...])
        id_ref[...] = jnp.where(better, first, id_ref[...])

    whole = lambda shape: pl.BlockSpec(shape, lambda j: (0, 0))  # noqa: E731
    args = [_pad_rows(hn, Rp), w]
    in_specs = [whole((Rp, D)),
                pl.BlockSpec((tv, D), lambda j: (j, 0)) if tied
                else pl.BlockSpec((D, tv), lambda j: (0, j))]
    if scale is not None:
        args.append(scale.astype(jnp.float32).reshape(1, V))
        in_specs.append(pl.BlockSpec((1, tv), lambda j: (0, j)))
    args += [_pad_rows(rep_pen.astype(jnp.float32)[:, None], Rp, 1.0),
             _words(seen_words, Rp, Wp), _words(banned_words, Rb, Wp)]
    in_specs += [whole((Rp, 1)), whole((Rp, Wp)), whole((Rb, Wp))]
    if has_ban:     # a ban that did not hit matches no id
        args.append(_pad_rows(jnp.where(ban_hit, ban_tok, -1)
                              .astype(jnp.int32), Rp, -1))
        in_specs.append(whole(args[-1].shape))

    _, ids = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=(whole((Rp, 1)), whole((Rp, 1))),
        out_shape=(jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, 1), jnp.int32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="greedy_head_argmax",
    )(*args)
    return ids[:R, 0]
