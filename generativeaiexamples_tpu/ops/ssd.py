"""A state-space layer's recurrence (Mamba-2): a state, a scalar decay a
head, NO delta rule.

A head keeps one matrix ``S`` (value width P x state width N, float32) a
sequence. Token ``t`` with input ``x`` (P), step size ``dt > 0`` (a
scalar a head, ``softplus`` of its projection: the caller's), the head's
rate ``A < 0``, and an input and an output vector ``B, C`` (N) that the
heads of a GROUP share (one group: every head):

    S <- exp(dt A) S + dt x B^T         the state forgets and is written
    y  = S C + D x                      read out, beside a skip

The third member of the family ops/gated_delta.py holds two of, and what
neither of its kernels computes: no ``beta k k^T`` erase term, the write
strength IS the step size that sets the decay, a skip, a state that is
not square and one key / query pair for all the value heads. What it
shares with them is the rule for what is not the sequence's: a token
with ``dt = 0`` decays nothing and writes nothing — how the caller pads
— and :func:`~.gated_delta.causal_conv` in front of it.

Five forms, all but the scan kernel taking float32 ``x`` (B, T, H, P),
``dt`` (B, T, H), ``A`` and ``D`` (H,), ``Bm`` and ``Cm`` (B, T, G, N)
and a state (B, H, P, N) (the step: no T axis), computed in float32 and
handed back in the dtype the state came in (the cache's is float32):

- :func:`ssd_step`: one token a row, the decode step: the state read
  once and written once (32 KiB a head at 64 x 128), memory-bound,
  elementwise — nothing is a matmul on purpose (a dot of float32
  operands is bf16 passes on the chip).
- :func:`ssd_recurrent`: the step scanned over the tokens: the oracle.
- :func:`ssd_chunked`: blocks of ``block`` tokens, exact for any block
  size. With ``L_t`` the running sum of ``dt A`` inside a block,
  ``y_t = exp(L_t) S_in C_t + sum_{s<=t} exp(L_t - L_s) (C_t . B_s) dt_s
  x_s + D x_t`` and ``S_out = exp(L_Q) S_in + sum_s exp(L_Q - L_s) dt_s
  x_s B_s^T``; every exponent is a DIFFERENCE <= 0 taken before the
  ``exp`` (never ``exp(L_t) / exp(L_s)``: a run of large steps
  underflows the divisor). ``C B^T`` once a group; a short scan over the
  blocks carries the state. Plain XLA at HIGHEST precision: the CPU's
  path, the ragged lengths' and what the scan kernel is held to.
- :func:`ssd_chunked_kernel`: the chunked form as ONE Pallas kernel a
  layer over the operands as the mixer has them (lane-dense ``x``, one
  group's ``B, C``): every chunk program's path on a TPU.
- :func:`ssd_step_kernel`: the step over the cache's WHOLE state leaf,
  in place, ONE Pallas kernel a layer.

**The state's layout.** ``S`` is (P, N) with N on the lanes (128 in the
published models), not the transpose the delta rules keep: P = 64 there
is half a vector register, the leaf stored padded to twice its bytes."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _grouped(x, G: int, axis: int):
    """The head axis ``axis`` of ``x`` as (G, H / G): a group's heads
    are consecutive."""
    shape = x.shape
    return x.reshape(shape[:axis] + (G, shape[axis] // G) + shape[axis + 1:])


def ssd_step(x, dt, A, Bm, Cm, D, state):
    """One token a row: ``x`` (B, H, P), ``dt`` (B, H), ``Bm, Cm`` (B,
    G, N), ``state`` (B, H, P, N). Returns ``(y (B, H, P) float32,
    state)``."""
    G = Bm.shape[-2]
    f32 = jnp.float32
    s = _grouped(state.astype(f32), G, 1)                   # (B,G,Hg,P,N)
    dec = _grouped(jnp.exp(dt * A), G, 1)[..., None, None]
    xd = _grouped(x * dt[..., None], G, 1)[..., None]       # (B,G,Hg,P,1)
    s = s * dec + xd * Bm[:, :, None, None, :]
    y = jnp.sum(s * Cm[:, :, None, None, :], axis=-1)       # (B,G,Hg,P)
    y = y.reshape(x.shape) + D[:, None] * x
    return y, s.reshape(state.shape).astype(state.dtype)


def ssd_recurrent(x, dt, A, Bm, Cm, D, state):
    """The recurrence token by token (module docstring)."""
    def step(s, t):
        xt, dtt, bt, ct = t
        y, s = ssd_step(xt, dtt, A, bt, ct, D, s)
        return s, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm))
    state, y = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunked(x, dt, A, Bm, Cm, D, state, block: int = 64):
    """The recurrence over blocks of ``block`` tokens (module
    docstring): the same ``y`` (B, T, H, P) float32 and final state as
    :func:`ssd_recurrent`."""
    B, T, H, P = x.shape
    G, N = Bm.shape[-2:]
    Q, f32 = block, jnp.float32
    pad = -T % Q
    if pad:     # a ragged last block: tokens that leave the state alone
        x, dt, Bm, Cm = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, Bm, Cm))
    nb = (T + pad) // Q

    def blocks(a):      # (B, T, ...) -> (nb, B, Q, ...)
        return jnp.moveaxis(a.reshape((B, nb, Q) + a.shape[2:]), 1, 0)

    xb = blocks(_grouped(x, G, 2))                          # (nb,B,Q,G,Hg,P)
    dtb = blocks(_grouped(dt, G, 2))                        # (nb,B,Q,G,Hg)
    Bb, Cb = blocks(Bm), blocks(Cm)                         # (nb,B,Q,G,N)
    # the running log-decay, tokens last: (nb, B, G, Hg, Q)
    L = jnp.moveaxis(jnp.cumsum(dtb * _grouped(A, G, 0), axis=2), 2, -1)
    xd = xb * dtb[..., None]
    i = jnp.arange(Q)
    # exp(L_i - L_j) where j <= i (<= 1), 0 above the diagonal
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              L[..., :, None] - L[..., None, :], -jnp.inf))
    cb = jnp.einsum("nbigk,nbjgk->nbgij", Cb, Bb, precision=_HI)
    y = jnp.einsum("nbghij,nbjghp->nbighp", cb[:, :, :, None] * decay, xd,
                   precision=_HI)
    into = jnp.moveaxis(jnp.exp(L), -1, 2)[..., None]       # (nb,B,Q,G,Hg,1)
    # a block's own write, each token decayed to the block's end
    xw = xd * jnp.moveaxis(jnp.exp(L[..., -1:] - L), -1, 2)[..., None]
    total = jnp.exp(L[..., -1])[..., None, None]            # (nb,B,G,Hg,1,1)

    def step(s, blk):
        xw, Bb, Cb, into, total = blk
        y_in = jnp.einsum("bigk,bghpk->bighp", Cb, s, precision=_HI) * into
        s = total * s + jnp.einsum("bjghp,bjgk->bghpk", xw, Bb,
                                   precision=_HI)
        return s, y_in

    s, y_in = jax.lax.scan(step, _grouped(state.astype(f32), G, 1),
                           (xw, Bb, Cb, into, total))
    y = jnp.moveaxis(y + y_in, 0, 1).reshape(B, T + pad, H, P)[:, :T]
    return (y + D[:, None] * x[:, :T],
            s.reshape(state.shape).astype(state.dtype))


_STEP_BYTES = 4 << 20   # a row's state a layer the decode kernel holds


def step_kernel_supported(heads: int, groups: int, P: int, N: int) -> bool:
    """Whether :func:`ssd_step_kernel` takes these shapes: one group,
    whole (sublane, lane) tiles of a head's (P, N) state, and a row's
    whole layer of state in one grid step."""
    return (groups == 1 and N % 128 == 0 and P % 8 == 0
            and heads * P * N * 4 <= _STEP_BYTES)


def ssd_step_kernel(x, dt, A, Bm, Cm, D, live, states, layer, *,
                    interpret=False):
    """:func:`ssd_step` over the cache's WHOLE state leaf, in place:
    ``states`` (Lg, B, H, P, N) aliased to the second result, ``layer``
    () int32 the recurrent layer to step, row ``b`` its slot ``b``,
    ``Bm, Cm`` (B, 1, N). One Pallas kernel, a grid over the rows: a
    row's H states of the layer (2 MiB at 64 x 64 x 128) are read once,
    decayed, written, read out and stored once. ``live``:
    :func:`~.gated_delta.live_first` of the rows that hold a sequence.
    The grid walks the live rows; the steps left over name the last
    live row's block, which moves nothing, so an idle row's 2 MiB stay
    where they lie in the aliased leaf, bit for bit, at no cost in
    bytes, and its ``y`` is 0. Returns ``(y (B, H, P) float32,
    states)``. ``interpret``: as ``pallas_call``'s.

    What a head needs down the sublanes beside its (P, N) state — its
    ``dt x`` and its decay — comes in with the heads on the LANES, (B,
    P, H), and ``y`` leaves so: the three small transposes are XLA's,
    outside, and the kernel has none."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .gated_delta import _visited

    Lg, B, H, P, N = states.shape
    f32 = jnp.float32

    def kernel(layer_ref, order_ref, n_ref, xd_ref, dec_ref, b_ref, c_ref,
               s_ref, y_ref, s_out_ref):
        i, n = pl.program_id(0), n_ref[0]

        @pl.when(i < n)
        def _():
            xd, dec = xd_ref[0], dec_ref[0]                 # (P, H)
            bv, cv = b_ref[0], c_ref[0]                     # (1, N)
            lane = jax.lax.broadcasted_iota(jnp.int32, (P, H), 1)
            y = jnp.zeros((P, H), f32)
            for h in range(H):
                col = slice(h, h + 1)
                s = s_ref[0, 0, h].astype(f32) * dec[:, col] \
                    + xd[:, col] * bv
                y = jnp.where(lane == h,
                              jnp.sum(s * cv, axis=1, keepdims=True), y)
                s_out_ref[0, 0, h] = s.astype(s_out_ref.dtype)
            y_ref[0] = y

        @pl.when(i >= n)
        def _():
            y_ref[...] = jnp.zeros(y_ref.shape, f32)

        # no live row: the one block every step names goes back as it
        # came (no other step writes the output buffer)
        @pl.when((n == 0) & (i == 0))
        def _():
            s_out_ref[...] = s_ref[...]

    def heads_last(a):      # (B, H, P) -> (B, P, H)
        return jnp.swapaxes(a.astype(f32), 1, 2)

    def small(i, layer, order, n):      # the row a step names
        return (_visited(i, order, n), 0, 0)

    def leaf(i, layer, order, n):
        return (layer[0], _visited(i, order, n), 0, 0, 0)

    dec = jnp.broadcast_to(jnp.exp(dt * A)[..., None], (B, H, P))
    cols = pl.BlockSpec((1, P, H), small)
    vec = pl.BlockSpec((1, 1, N), small)
    slab = pl.BlockSpec((1, 1, H, P, N), leaf)
    y, states = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[cols, cols, vec, vec, slab],
            out_specs=[
                pl.BlockSpec((1, P, H),
                             lambda i, layer, order, n: (order[i], 0, 0)),
                slab]),
        out_shape=[jax.ShapeDtypeStruct((B, P, H), f32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="ssd_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *live,
      heads_last(x * dt[..., None]), heads_last(dec), Bm.astype(f32),
      Cm.astype(f32), states)
    return jnp.swapaxes(y, 1, 2) + D[:, None] * x, states


_SCAN_BLOCK = 64    # tokens a block of the scan kernel, as the XLA form's
_SCAN_HEADS = 16    # heads a grid step of the scan kernel holds


def scan_kernel_supported(tokens: int, heads: int, groups: int, P: int,
                          N: int) -> bool:
    """Whether :func:`ssd_chunked_kernel` takes these shapes: whole
    blocks of tokens, one group, the state's N whole 128-lane slices, a
    head's P values half a slice (a PAIR of heads a tile), heads in
    whole grid steps."""
    return (tokens > 0 and tokens % _SCAN_BLOCK == 0 and groups == 1
            and N % 128 == 0 and P == _SCAN_BLOCK
            and heads % _SCAN_HEADS == 0)


def scan_kernel_armed(tokens: int, heads: int, groups: int, P: int,
                      N: int) -> bool:
    """Whether a scan over these shapes runs the kernel HERE: on a TPU,
    where it takes them. Everywhere else :func:`ssd_chunked` runs (the
    CPU's path; a test hands the mixer the interpreted kernel by
    pointing this name at :func:`scan_kernel_supported`)."""
    return jax.default_backend() == "tpu" and scan_kernel_supported(
        tokens, heads, groups, P, N)


def ssd_chunked_kernel(x, dt, A, Bm, Cm, D, state, *, x_at: int = 0,
                       interpret: Optional[bool] = None):
    """:func:`ssd_chunked` as ONE Pallas kernel, over the operands as
    the mixer has them: ``x`` (B, T, H * P) — or a wider array that
    holds the inputs from lane ``x_at`` on (the convolution's whole
    output: a slice of it would be a copy in front of the kernel) —,
    ``dt`` (B, T, H) float32 (0 on what is not the sequence's), ``A``
    and ``D`` (H,), ``Bm, Cm`` (B, T, N), ``state`` (B, H, P, N).
    Returns ``(y (B, T, H * P) float32, state)``.

    Grid (row, group of ``_SCAN_HEADS`` heads, block of 64 tokens), the
    blocks in order, the group's states float32 in VMEM from the first
    block to the last, read from and written to HBM once a program. What
    one group of B and C buys: the state leaf (H, P, N) IS a (H * P, N)
    matrix with N on the lanes, so a block's carried read-out is ONE
    product ``C S^T`` whose result lies in ``x``'s own lane-dense layout
    and its write ONE product ``xw^T B``; ``C B^T`` is one product a
    block. Only the inside of a block is a head's: ``(C B^T o decay_h)
    xd_h``, a PAIR of heads a product — their (64, 64) matrices side by
    side on 128 lanes against their inputs block-diagonal in a (128,
    128) tile. ``L``, the running sum of ``dt A`` inside the block, is
    one product with a triangle of ones; a head's column of it (and of
    ``dt``, and its ``D``) reaches the lanes that want it by a product
    with a 0 / 1 matrix, exact in three bf16 passes
    (:func:`~.gated_delta._spread`), and the same spread serves the 64
    token lanes of a head's block matrix and the 64 value lanes of its
    input. Every exponent is a difference <= 0 taken before the ``exp``,
    every product takes float32 operands at HIGHEST precision, and a
    whole block of ``dt = 0`` leaves the state bit for bit (its total
    decay is ``exp(0)``, its write a product with zeros).

    The call is jitted and the body keeps to ``lax`` forms for the warm
    start's sake (PERF.md section 6, PR 47)."""
    H = dt.shape[-1]
    if Bm.ndim != 3 or not scan_kernel_supported(
            dt.shape[1], H, 1, *state.shape[-2:]):
        raise ValueError(
            f"no scan kernel for T={dt.shape[1]}, heads {H}, B and C "
            f"{Bm.shape}, widths {state.shape[-2:]}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _scan_kernel(x, dt, A, Bm, Cm, D, state, x_at=x_at,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("x_at", "interpret"))
def _scan_kernel(x, dt, A, Bm, Cm, D, state, *, x_at: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .gated_delta import _dot, _spread

    B, T, H = dt.shape
    P, N = state.shape[-2:]
    Q, Hg, f32 = _SCAN_BLOCK, _SCAN_HEADS, jnp.float32
    nb, W = T // Q, Hg * P      # W lanes: (head of the group, 64)
    if x_at % W:                # no whole block of a group's inputs
        x, x_at = x[..., x_at:x_at + H * P], 0
    exp, bq = jax.lax.exp, Q.bit_length() - 1

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    def keep(mask, x, other=0.0):
        return jax.lax.select(mask, jnp.broadcast_to(x, mask.shape),
                              jnp.full(mask.shape, other, x.dtype))

    def kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, s_in_ref, y_ref,
               s_out_ref, s_scr):
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _():
            s_scr[...] = s_in_ref[0].astype(f32)

        row = iota((Q, W), 0)
        col = iota((Q, W), 1) & (Q - 1)
        steps = dt_ref[0]                                   # (Q, H)
        # the running sum of dt A inside the block, every head's
        tri = (iota((Q, Q), 0) >= iota((Q, Q), 1)).astype(f32)
        L = _dot(tri, steps * a_ref[...])
        # a head's column over its 64 lanes of the group's W (exact)
        wants = iota((H, W), 0) == Hg * pl.program_id(1) + (
            iota((H, W), 1) >> bq)
        spread = _spread(
            jnp.concatenate([L, steps, jnp.broadcast_to(d_ref[...], (16, H))],
                            axis=0), wants.astype(jnp.bfloat16))
        Lw, dtw, skip = spread[:Q], spread[Q:2 * Q], spread[2 * Q:2 * Q + 1]
        # lane (h, j): L_j of head h, a (1, W) row
        along = jnp.sum(keep(row == col, Lw), axis=0, keepdims=True)
        last = Lw[Q - 1:]                                   # (1, W)
        # exp(L_i - L_j) where j <= i (<= 1), 0 above the diagonal
        decay = exp(keep(row >= col, Lw - along, -jnp.inf))
        Bb, Cb = b_ref[0], c_ref[0]                         # (Q, N)
        # C B^T once a block, twice side by side: a pair's tile
        cb = _dot(Cb, jnp.concatenate([Bb, Bb], axis=0), ((1,), (1,)))
        mix = jnp.concatenate([cb] * (Hg // 2), axis=1) * decay
        xs = x_ref[0]                                       # (Q, W)
        xd = xs * dtw
        # what the state carried in, read out: lanes as x has them
        y = _dot(Cb, s_scr[...], ((1,), (1,))) * exp(Lw) + skip * xs
        # (2Q, 2P): a pair's two heads' inputs block-diagonal
        own = iota((2 * Q, 2 * P), 0) >> bq == iota((2 * Q, 2 * P), 1) >> bq
        for p in range(Hg // 2):
            pair = slice(2 * p * P, 2 * (p + 1) * P)
            xp = xd[:, pair]
            y_ref[0, :, pair] = y[:, pair] + _dot(
                mix[:, pair], keep(own, jnp.concatenate([xp, xp], axis=0)))
        # the block's own write, each token decayed to the block's end,
        # onto the state decayed over the whole block
        new = _dot(xd * exp(last - Lw), Bb, ((0,), (0,)))   # (W, N)
        # a head's whole decay over the block, DOWN the sublanes as the
        # state has its heads' rows: a pair's (8, 128) tile transposed
        total = exp(Lw[Q - 8:])
        for p in range(Hg // 2):
            pair = slice(2 * p * P, 2 * (p + 1) * P)
            s_scr[pair] = total[:, pair].T[:, 7:8] * s_scr[pair] + new[pair]

        @pl.when(t == nb - 1)
        def _():
            s_out_ref[0] = s_scr[...].astype(s_out_ref.dtype)

    def tokens(at=0):       # a group's W lanes of a block's tokens
        return pl.BlockSpec((1, Q, W), lambda b, h, t: (b, t, at + h))

    def whole(width):
        return pl.BlockSpec((1, Q, width), lambda b, h, t: (b, t, 0))

    heads = pl.BlockSpec((1, H), lambda b, h, t: (0, 0))
    states = pl.BlockSpec((1, W, N), lambda b, h, t: (b, h, 0))
    y, new = pl.pallas_call(
        kernel,
        grid=(B, H // Hg, nb),
        in_specs=[tokens(x_at // W), whole(H), heads, heads, whole(N),
                  whole(N), states],
        out_specs=[tokens(), states],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * P), f32),
                   jax.ShapeDtypeStruct((B, H * P, N), state.dtype)],
        scratch_shapes=[pltpu.VMEM((W, N), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="ssd_scan",
    )(x.astype(f32), dt.astype(f32), A.astype(f32).reshape(1, H),
      D.astype(f32).reshape(1, H), Bm.astype(f32), Cm.astype(f32),
      state.reshape(B, H * P, N))
    return y, new.reshape(state.shape)
