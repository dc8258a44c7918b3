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
neither of its kernels computes: no ``beta k k^T`` erase term (the write
is not corrected by what the state already holds), the write strength IS
the step size that sets the decay, a skip, a state that is not square
and one key / query pair for all the value heads. What it shares with
them is the rule for what is not the sequence's: a token with ``dt = 0``
decays nothing and writes nothing — how the caller pads — and
:func:`~.gated_delta.causal_conv` in front of it.

Four forms, each taking float32 ``x`` (B, T, H, P), ``dt`` (B, T, H),
``A`` and ``D`` (H,), ``Bm`` and ``Cm`` (B, T, G, N) and a state (B, H,
P, N) (the step: no T axis), computed in float32 and handed back in the
dtype the state came in (the cache's is float32, always):

- :func:`ssd_step`: one token a row, the decode step: the state read
  once and written once (32 KiB a head at 64 x 128), memory-bound,
  elementwise — nothing is a matmul on purpose (a dot of float32
  operands is bf16 passes on the chip).
- :func:`ssd_recurrent`: the step scanned over the tokens: the oracle,
  never a served path.
- :func:`ssd_chunked`: blocks of ``block`` tokens, exact for any block
  size. With ``L_t`` the running sum of ``dt A`` inside a block,
  ``y_t = exp(L_t) S_in C_t + sum_{s<=t} exp(L_t - L_s) (C_t . B_s) dt_s
  x_s + D x_t`` and ``S_out = exp(L_Q) S_in + sum_s exp(L_Q - L_s) dt_s
  x_s B_s^T``; every exponent is a DIFFERENCE <= 0 taken before the
  ``exp`` (never ``exp(L_t) / exp(L_s)``: a run of large steps
  underflows the divisor). ``C B^T`` is computed once a group, not a
  head; a short scan over the blocks carries the state. Plain XLA at
  HIGHEST precision: every chunk program's path, and what a scan kernel
  would be held to.
- :func:`ssd_step_kernel`: the step over the cache's WHOLE state leaf in
  place, ONE Pallas kernel a layer (as plain XLA a step passes over the
  state four times: ops/gated_delta.py).

**The state's layout.** ``S`` is (P, N) with N — 128 in the published
models — on the lanes, not the transpose the delta rules keep (key width
x value width): P = 64 on the lanes would be half a vector register and
the leaf would be stored padded to twice its bytes.
"""

from __future__ import annotations

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


def ssd_step_kernel(x, dt, A, Bm, Cm, D, active, states, layer, *,
                    interpret: bool = False):
    """:func:`ssd_step` over the cache's WHOLE state leaf, in place:
    ``states`` (Lg, B, H, P, N) aliased to the second result, ``layer``
    () int32 the recurrent layer to step, row ``b`` its slot ``b``,
    ``Bm, Cm`` (B, 1, N). One Pallas kernel, a grid over the rows: a
    row's H states of the layer (2 MiB at 64 x 64 x 128) are read once,
    decayed, written, read out and stored once. ``active`` (B,) bool: an
    idle row's state is written back as it was read, bit for bit, and
    its ``y`` is 0. Returns ``(y (B, H, P) float32, states)``.

    What a head needs down the sublanes beside its (P, N) state — its
    ``dt x`` and its decay — comes in with the heads on the LANES, (B,
    P, H), and ``y`` leaves so: the three small transposes are XLA's,
    outside, and the kernel has none."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Lg, B, H, P, N = states.shape
    f32 = jnp.float32

    def kernel(layer_ref, act_ref, xd_ref, dec_ref, b_ref, c_ref, s_ref,
               y_ref, s_out_ref):
        live = act_ref[pl.program_id(0)] > 0

        @pl.when(live)
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

        @pl.when(jnp.logical_not(live))
        def _():
            s_out_ref[...] = s_ref[...]
            y_ref[...] = jnp.zeros(y_ref.shape, f32)

    def heads_last(a):      # (B, H, P) -> (B, P, H)
        return jnp.swapaxes(a.astype(f32), 1, 2)

    dec = jnp.broadcast_to(jnp.exp(dt * A)[..., None], (B, H, P))
    cols = pl.BlockSpec((1, P, H), lambda b, layer, act: (b, 0, 0))
    vec = pl.BlockSpec((1, 1, N), lambda b, layer, act: (b, 0, 0))
    slab = pl.BlockSpec((1, 1, H, P, N),
                        lambda b, layer, act: (layer[0], b, 0, 0, 0))
    y, states = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[cols, cols, vec, vec, slab],
            out_specs=[cols, slab]),
        out_shape=[jax.ShapeDtypeStruct((B, P, H), f32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="ssd_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), active.astype(jnp.int32),
      heads_last(x * dt[..., None]), heads_last(dec), Bm.astype(f32),
      Cm.astype(f32), states)
    return jnp.swapaxes(y, 1, 2) + D[:, None] * x, states
