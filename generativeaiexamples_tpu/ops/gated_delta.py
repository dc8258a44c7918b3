"""The gated delta rule: a token mixer whose cache is a STATE, not rows.

A head keeps one matrix ``S`` (key width x value width, float32) a
sequence. Token ``t`` with query ``q``, key ``k`` (both l2-normed by the
caller), value ``v``, log-decay ``g <= 0`` and write strength ``beta``:

    S <- exp(g) S                       the state forgets
    d  = beta (v - S^T k)               what the state gets wrong about k
    S <- S + k d^T                      corrected (the delta rule)
    o  = S^T q

Three forms of the same recurrence, all taking and returning float32
``q, k`` (B, T, H, dk), ``v`` (B, T, H, dv), ``g, beta`` (B, T, H) and a
state (B, H, dk, dv), computed in float32 and handed back in the dtype
it came in (the cache's is float32, always: a test hands in bf16 to
show what that would cost):

- :func:`gated_delta_step`: one token a row, the decode step. The work
  is the state read once and written once (2 x 64 KiB a head at 128 x
  128): memory-bound, and nothing here is a matmul on purpose — a dot of
  float32 operands is bf16 passes on the chip. On the chip the step
  over the cache's whole state leaf is ONE Pallas kernel that does just
  that (:func:`gated_delta_step_kernel`; 4.4 ms a step of plain XLA
  fusions became the kernel at PR 46).
- :func:`gated_delta_recurrent`: ``gated_delta_step`` scanned over the
  tokens. T dependent steps: what the chunked form is held to
  (tests/test_gated_delta.py), never a served path.
- :func:`gated_delta_chunked`: blocks of ``block`` tokens. With ``G`` the
  running sum of ``g`` inside a block and ``A = tril(beta_i exp(G_i -
  G_j) k_i.k_j, -1)``, the block's corrections solve ``(I + A) D =
  beta (V - exp(G) K S0)``; ``A`` is strictly lower triangular: its
  16-row diagonal blocks are inverted by substitution and merged
  (``_unit_lower_inverse``). Everything that does not involve
  ``S0`` is computed for all blocks at once; a short scan over the
  blocks carries the state. A token with ``g = 0`` and ``beta = 0``
  leaves the state as it found it: that is how the caller pads (a ragged
  last block here, the tokens past a chunk's valid length there).

:func:`causal_conv` is the depthwise causal convolution in front of it,
whose cache is the last ``K - 1`` inputs of a sequence.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def causal_conv(u: jax.Array, tail: jax.Array, weight: jax.Array,
                n_valid: Optional[jax.Array] = None
                ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution with SiLU, no bias: ``out_t =
    silu(sum_j weight[:, j] * ext[t + j])`` over ``ext`` = the sequence's
    last ``K - 1`` inputs (``tail`` (B, K - 1, Ch); zeros before the
    sequence) followed by ``u`` (B, T, Ch). Returns float32 (B, T, Ch)
    and the new tail: the ``K - 1`` inputs up to the row's last VALID
    token (``n_valid`` (B,): tokens past it are padding and leave the
    tail alone; None: all T are valid)."""
    B, T, Ch = u.shape
    K = weight.shape[-1]
    ext = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    w = weight.astype(jnp.float32)
    out = sum(ext[:, j:j + T].astype(jnp.float32) * w[:, j]
              for j in range(K))
    if n_valid is None:
        new_tail = ext[:, T:]
    else:
        new_tail = jax.vmap(lambda e, n: jax.lax.dynamic_slice(
            e, (n, 0), (K - 1, Ch)))(ext, jnp.clip(n_valid, 0, T))
    return jax.nn.silu(out), new_tail.astype(tail.dtype)


def l2norm(x: jax.Array) -> jax.Array:
    """A head's q or k scaled to unit length (eps 1e-6 under the root):
    what keeps ``beta k k^T`` a contraction."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row: ``q, k`` (B, H, dk), ``v`` (B, H, dv), ``g,
    beta`` (B, H), ``state`` (B, H, dk, dv). Returns ``(o (B, H, dv)
    float32, state)``."""
    s = state.astype(jnp.float32) * jnp.exp(g)[..., None, None]
    d = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * d[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s.astype(state.dtype)


_STEP_HEADS = 8     # heads a grid step of the decode kernel holds


def step_kernel_supported(heads: int, dk: int, dv: int) -> bool:
    """Whole (sublane, lane) tiles of a head's state and of a group's
    keys (transposed in the kernel), whole groups of heads."""
    return dk % 128 == 0 and dv % 128 == 0 and heads % _STEP_HEADS == 0


def gated_delta_step_kernel(q, k, v, g, beta, active, states, layer, *,
                            interpret: bool = False):
    """:func:`gated_delta_step` over the cache's WHOLE state leaf, in
    place: ``states`` (Lg, B, H, dk, dv) aliased to the second result,
    ``layer`` () int32 the recurrent layer to step, row ``b`` its slot
    ``b``. One Pallas kernel, a grid over (row, group of heads): a
    head's 64 KiB are read once, decayed, corrected, read out and
    written once — plain XLA reads the state four times a step (two
    reductions, the update, the slice out of the leaf). ``active`` (B,)
    bool: an idle row's state is written back as it was read, bit for
    bit. Returns ``(o (B, H, dv) float32, states)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Lg, B, H, dk, dv = states.shape
    hb = min(H, _STEP_HEADS)
    f32 = jnp.float32

    def kernel(layer_ref, q_ref, k_ref, v_ref, dec_ref, beta_ref, act_ref,
               s_ref, o_ref, s_out_ref):
        # a head's key and query down the sublanes, beside its (dk, dv)
        # state: one transpose of the group's (hb, dk) tile
        kt, qt = k_ref[0].T, q_ref[0].T                     # (dk, hb)
        for h in range(hb):
            row = slice(h, h + 1)
            old = s_ref[0, 0, h]                            # (dk, dv)
            kc = kt[:, row]                                 # (dk, 1)
            s = old.astype(f32) * dec_ref[0, row]           # x (1, dv)
            d = beta_ref[0, row] * (
                v_ref[0, row] - jnp.sum(s * kc, axis=0, keepdims=True))
            s = s + kc * d
            o_ref[0, row] = jnp.sum(s * qt[:, row], axis=0, keepdims=True)
            s_out_ref[0, 0, h] = jnp.where(act_ref[0, row] > 0,
                                           s.astype(old.dtype), old)

    def over_values(x):         # (B, H) -> (B, H, dv): a row a head
        return jnp.broadcast_to(x.astype(f32)[..., None], (B, H, dv))

    keys = pl.BlockSpec((1, hb, dk), lambda b, h, layer: (b, h, 0))
    vals = pl.BlockSpec((1, hb, dv), lambda b, h, layer: (b, h, 0))
    slab = pl.BlockSpec((1, 1, hb, dk, dv),
                        lambda b, h, layer: (layer[0], b, h, 0, 0))
    o, states = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // hb),
            in_specs=[keys, keys, vals, vals, vals, vals, slab],
            out_specs=[vals, slab]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gated_delta_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), q.astype(f32),
      k.astype(f32), v.astype(f32), over_values(jnp.exp(g)),
      over_values(beta), over_values(jnp.broadcast_to(
          active[:, None], (B, H))), states)
    return o, states


def gated_delta_recurrent(q, k, v, g, beta, state):
    """The recurrence token by token (module docstring)."""
    def step(s, x):
        o, s = gated_delta_step(*x, s)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


_SOLVE = 16        # rows of a diagonal block solved by substitution


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for strictly lower triangular ``a`` (..., c, c), c
    up to ``_SOLVE`` or a power-of-two multiple of it: the diagonal
    blocks of ``_SOLVE`` rows by forward substitution (row i of the
    inverse is ``e_i - sum_j a_ij row_j``, the rows from i on still
    ``e_j`` and ``a_ij`` zero there: 15 dependent steps of ONE loop
    body, each over every block of every head at once), then pairs of
    blocks merged, ``[[X1, 0], [-X2 a21 X1, X2]]``, all pairs of a
    level in one product, until one is left. The
    finite product ``(I - a)(I + a^2)(I + a^4)...`` is the same matrix
    in exact arithmetic and NOT what runs: a run of equal tokens (a
    prompt of one repeated id, a page of spaces) makes every entry of
    ``a`` nearly beta, the powers' entries reach C(64, 32) beta^32, and
    float32 loses the result to cancellation. (A loop and batched
    merges, not fifteen unrolled steps and a product a pair: the same
    numbers from a third less code in every chunk program, which the
    compile cache holds thirty of: PERF.md section 6, PR 46.)"""
    c = a.shape[-1]
    s = min(c, _SOLVE)
    n, lead = c // s, a.shape[:-2]
    if c % s or n & (n - 1):
        raise ValueError(f"block of {c} tokens: up to {_SOLVE}, or a "
                         f"power-of-two multiple of it")
    grid = a.reshape(lead + (n, s, n, s))
    diag = jnp.stack([grid[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = jnp.eye(s, dtype=a.dtype)

    def row(i, x):
        a_i = jax.lax.dynamic_index_in_dim(diag, i, axis=-2, keepdims=False)
        new = eye[i] - jnp.sum(a_i[..., :, None] * x, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(x, new, i, axis=-2)

    x = jax.lax.fori_loop(1, s, row, jnp.broadcast_to(eye, diag.shape))
    while n > 1:
        pairs = x.reshape(lead + (n // 2, 2, s, s))
        x1, x2 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        a21 = jnp.stack([grid[..., m + 1, :, m, :] for m in range(0, n, 2)],
                        axis=-3)
        low = -jnp.matmul(jnp.matmul(x2, a21, precision=_HI), x1,
                          precision=_HI)
        x = jnp.concatenate([
            jnp.concatenate([x1, jnp.zeros_like(x1)], axis=-1),
            jnp.concatenate([low, x2], axis=-1)], axis=-2)
        s, n = 2 * s, n // 2
        grid = a.reshape(lead + (n, s, n, s))
    return x[..., 0, :, :]


def gated_delta_chunked(q, k, v, g, beta, state, block: int = 64):
    """The recurrence over blocks of ``block`` tokens (module
    docstring): the same ``o`` (B, T, H, dv) float32 and final state as
    :func:`gated_delta_recurrent`."""
    B, T, H, dk = q.shape
    c = block
    pad = -T % c
    if pad:     # a ragged last block: tokens that leave the state alone
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                   for a in (g, beta))
    nb = (T + pad) // c

    def blocks(a):      # (B, T, H, ...) -> (nb, B, H, c, ...)
        a = a.reshape((B, nb, c, H) + a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    q, k, v, g, beta = (blocks(a) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                              # (nb, B, H, c)
    i = jnp.arange(c)
    at_or_below = i[:, None] >= i[None, :]
    # exp(G_i - G_j) where j <= i (<= 1), 0 above the diagonal
    decay = jnp.exp(jnp.where(at_or_below,
                              G[..., :, None] - G[..., None, :], -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", k, k, precision=_HI)
    a = jnp.where(i[:, None] > i[None, :],
                  beta[..., :, None] * kk * decay, 0.0)
    inv = _unit_lower_inverse(a)
    # D = U - W S0: the corrections as a function of the entering state
    w = jnp.matmul(inv, k * (beta * jnp.exp(G))[..., None], precision=_HI)
    u = jnp.matmul(inv, v * beta[..., None], precision=_HI)
    qk = jnp.einsum("...id,...jd->...ij", q, k, precision=_HI) * decay
    q_in = q * jnp.exp(G)[..., None]
    k_out = k * jnp.exp(G[..., -1:] - G)[..., None]
    g_out = jnp.exp(G[..., -1])[..., None, None]            # (nb, B, H, 1, 1)

    def step(s, x):
        w, u, qk, q_in, k_out, g_out = x
        sf = s.astype(jnp.float32)
        d = u - jnp.matmul(w, sf, precision=_HI)
        o = jnp.matmul(q_in, sf, precision=_HI) \
            + jnp.matmul(qk, d, precision=_HI)
        sf = g_out * sf + jnp.einsum("...ck,...cv->...kv", k_out, d,
                                     precision=_HI)
        return sf.astype(s.dtype), o

    state, o = jax.lax.scan(step, state, (w, u, qk, q_in, k_out, g_out))
    # (nb, B, H, c, dv) -> (B, T, H, dv)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(
        B, nb * c, H, o.shape[-1])
    return o[:, :T], state
