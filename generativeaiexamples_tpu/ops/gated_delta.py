"""The gated delta rule: a token mixer whose cache is a STATE, not rows.

A head keeps one matrix ``S`` (key width x value width, float32) a
sequence. Token ``t`` with query ``q``, key ``k`` (both l2-normed by the
caller), value ``v``, log-decay ``g <= 0`` and write strength ``beta``:

    S <- exp(g) S                       the state forgets
    d  = beta (v - S^T k)               what the state gets wrong about k
    S <- S + k d^T                      corrected (the delta rule)
    o  = S^T q

Four forms of the same recurrence, the first three taking and returning
float32 ``q, k`` (B, T, H, dk), ``v`` (B, T, H, dv), ``g, beta`` (B, T,
H) and a state (B, H, dk, dv), computed in float32 and handed back in
the dtype it came in (the cache's is float32, always: a test hands in
bf16 to show what that would cost). The recurrence token by token is
what the chunked form is held to, the chunked form what its kernel is
held to, each at 2e-5 of the largest value (tests/test_gated_delta.py).
``g`` (B, T, H, dk), a decay a CHANNEL of a head's keys (KDA), has the
same four: step and recurrence are one code for both, the chunked form
:func:`kda_chunked` and its kernel :func:`kda_chunked_kernel` twins:

- :func:`gated_delta_step`: one token a row, the decode step. The work
  is the state read once and written once (2 x 64 KiB a head at 128 x
  128): memory-bound, and nothing here is a matmul on purpose — a dot of
  float32 operands is bf16 passes on the chip. On the chip the step
  over the cache's whole state leaf is ONE Pallas kernel that does just
  that (:func:`gated_delta_step_kernel`; 4.4 ms a step of plain XLA
  fusions became the kernel at PR 46).
- :func:`gated_delta_recurrent`: ``gated_delta_step`` scanned over the
  tokens. T dependent steps: the oracle, never a served path.
- :func:`gated_delta_chunked`: blocks of ``block`` tokens. With ``G`` the
  running sum of ``g`` inside a block and ``A = tril(beta_i exp(G_i -
  G_j) k_i.k_j, -1)``, the block's corrections solve ``(I + A) D =
  beta (V - exp(G) K S0)``; ``A`` is strictly lower triangular: its
  16-row diagonal blocks are inverted by substitution and merged
  (``_unit_lower_inverse``). Everything that does not involve
  ``S0`` is computed for all blocks at once; a short scan over the
  blocks carries the state. A token with ``g = 0`` and ``beta = 0``
  leaves the state as it found it: that is how the caller pads. A
  dozen float32 XLA stages whose operands and results cross HBM: the
  kernel's oracle, the CPU's path and the ragged lengths'.
- :func:`gated_delta_chunked_kernel` (PR 47; the vector decay's since
  PR 52): the chunked form as ONE Pallas kernel, on a TPU wherever the
  shapes allow (``scan_kernel_armed``, ``kda_scan_kernel_armed``): the
  blocks of a (row, group of heads) in order, the group's states in
  VMEM, a block's decays, ``k k^T``, inverse, corrections and state
  update never leaving the chip, the operands read as the mixer has
  them. Float32 at HIGHEST precision, the inverse as the XLA form's.

:func:`causal_conv` is the depthwise causal convolution in front of it,
whose cache is the last ``K - 1`` inputs of a sequence.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def causal_conv(u: jax.Array, tail: jax.Array, weight: jax.Array,
                n_valid: Optional[jax.Array] = None,
                bias: Optional[jax.Array] = None
                ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution with SiLU: ``out_t = silu(sum_j
    weight[:, j] * ext[t + j] (+ bias))`` (``bias`` (Ch,), where the
    model has one) over ``ext`` = the sequence's
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
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return jax.nn.silu(out), new_tail.astype(tail.dtype)


def l2norm(x: jax.Array) -> jax.Array:
    """A head's q or k scaled to unit length (eps 1e-6 under the root):
    what keeps ``beta k k^T`` a contraction."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row: ``q, k`` (B, H, dk), ``v`` (B, H, dv), ``g,
    beta`` (B, H), ``state`` (B, H, dk, dv). Returns ``(o (B, H, dv)
    float32, state)``. ``g`` (B, H, dk) is a decay a CHANNEL of the
    head's keys: row ``c`` of the state decays by ``exp(g[c])``."""
    decay = jnp.exp(g)[..., None] if g.ndim == q.ndim \
        else jnp.exp(g)[..., None, None]
    s = state.astype(jnp.float32) * decay
    d = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * d[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s.astype(state.dtype)


_STEP_HEADS = 8     # heads a grid step of the decode kernel holds


def step_kernel_supported(heads: int, dk: int, dv: int) -> bool:
    """Whole (sublane, lane) tiles of a head's state and of a group's
    keys (transposed in the kernel), whole groups of heads."""
    return dk % 128 == 0 and dv % 128 == 0 and heads % _STEP_HEADS == 0


def live_first(active):
    """The decode kernels' visiting order over a state leaf's rows:
    ``(order (B,) int32, n_live (1,) int32)`` from ``active`` (B,) bool,
    the live rows first, each group in slot order. Grid step ``i <
    n_live`` works on row ``order[i]``; every later step names the LAST
    live row's block again (:func:`_visited`) and leaves the state
    alone, so an idle row's state is neither fetched nor written. A
    sort of a few dozen booleans: ONCE a decode step, outside the layer
    loop (models/llama.py ``_recurrent_step``)."""
    order = jnp.argsort(jnp.logical_not(active), stable=True)
    return (order.astype(jnp.int32),
            jnp.sum(active, dtype=jnp.int32).reshape(1))


def _visited(i, order, n_live):
    """The row whose state grid step ``i`` names: its own while rows are
    live, then the last live row's over and over — the pipeline moves a
    block only when its index changes. Row ``order[0]`` where none is
    live: that one block is fetched, and the kernel's first step copies
    it to the output buffer that goes back at the end of the grid."""
    return order[jnp.minimum(i, jnp.maximum(n_live[0] - 1, 0))]


def gated_delta_step_kernel(q, k, v, g, beta, live, states, layer, *,
                            interpret=False):
    """:func:`gated_delta_step` over the cache's WHOLE state leaf, in
    place: ``states`` (Lg, B, H, dk, dv) aliased to the second result,
    ``layer`` () int32 the recurrent layer to step, row ``b`` its slot
    ``b``. One Pallas kernel, a grid over (row, group of heads): a
    head's 64 KiB are read once, decayed, corrected, read out and
    written once — plain XLA reads the state four times a step (two
    reductions, the update, the slice out of the leaf). ``live``:
    :func:`live_first` of the rows that hold a sequence. The grid walks
    the live rows; the steps left over name the last live step's block
    (its row AND its last group of heads), which moves nothing, so an
    idle row's state stays where it lies in the aliased leaf, bit for
    bit, at no cost in bytes, and its ``o`` is 0. Returns ``(o (B, H,
    dv) float32, states)``. ``interpret``: as ``pallas_call``'s (the TPU
    interpreter's parameters model the pipeline's buffers: the tests').

    ``g`` (B, H, dk), a decay a CHANNEL of a head's keys, runs the
    kernel's twin ``kda_delta_step``: the same grid, reads and writes,
    the decays down the sublanes beside the key (one more transpose of
    a (hb, dk) tile) so that the state's ROWS decay each at its own
    rate."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Lg, B, H, dk, dv = states.shape
    hb = min(H, _STEP_HEADS)
    f32 = jnp.float32
    channel = g.ndim == 3

    def kernel(layer_ref, order_ref, n_ref, q_ref, k_ref, v_ref, dec_ref,
               beta_ref, s_ref, o_ref, s_out_ref):
        i, n = pl.program_id(0), n_ref[0]

        @pl.when(i < n)
        def _():
            # a head's key and query down the sublanes, beside its (dk,
            # dv) state: one transpose of the group's (hb, dk) tile
            kt, qt = k_ref[0].T, q_ref[0].T                 # (dk, hb)
            if channel:
                dt = dec_ref[0].T                           # (dk, hb)
            for h in range(hb):
                row = slice(h, h + 1)
                kc = kt[:, row]                             # (dk, 1)
                # x (1, dv): the head's one decay; x (dk, 1): a row its
                # own
                s = s_ref[0, 0, h].astype(f32) * (
                    dt[:, row] if channel else dec_ref[0, row])
                d = beta_ref[0, row] * (
                    v_ref[0, row] - jnp.sum(s * kc, axis=0, keepdims=True))
                s = s + kc * d
                o_ref[0, row] = jnp.sum(s * qt[:, row], axis=0,
                                        keepdims=True)
                s_out_ref[0, 0, h] = s.astype(s_out_ref.dtype)

        @pl.when(i >= n)
        def _():
            o_ref[...] = jnp.zeros(o_ref.shape, f32)

        # no live row: the one block every step names goes back as it
        # came (no other step writes the output buffer)
        @pl.when((n == 0) & (i == 0) & (pl.program_id(1) == 0))
        def _():
            s_out_ref[...] = s_ref[...]

    def over_values(x):         # (B, H) -> (B, H, dv): a row a head
        return jnp.broadcast_to(x.astype(f32)[..., None], (B, H, dv))

    def small(b, h, layer, order, n):   # the (row, group) a step names
        return (_visited(b, order, n),
                jnp.where(b < n[0], h, H // hb - 1), 0)

    def leaf(b, h, layer, order, n):
        return (layer[0],) + small(b, h, layer, order, n) + (0,)

    keys = pl.BlockSpec((1, hb, dk), small)
    vals = pl.BlockSpec((1, hb, dv), small)
    slab = pl.BlockSpec((1, 1, hb, dk, dv), leaf)
    o, states = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, H // hb),
            in_specs=[keys, keys, vals, keys if channel else vals, vals,
                      slab],
            out_specs=[
                pl.BlockSpec((1, hb, dv),
                             lambda b, h, layer, order, n: (order[b], h, 0)),
                slab]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_delta_step" if channel else "gated_delta_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *live, q.astype(f32),
      k.astype(f32), v.astype(f32),
      jnp.exp(g.astype(f32)) if channel else over_values(jnp.exp(g)),
      over_values(beta), states)
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


def _blocks(a, nb: int, c: int):
    """(B, T, H, ...) -> (nb, B, H, c, ...): blocks of ``c`` tokens."""
    B, _, H = a.shape[:3]
    a = a.reshape((B, nb, c, H) + a.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)


def _carry_blocks(state, w, u, qk, q_in, k_out, g_out, T: int):
    """The short scan over the blocks that carries the state, for either
    chunked form: ``D = U - W S``, ``o = q_in S + qk D``, ``S <- g_out S
    + k_out^T D`` (``g_out`` a head's scalar or a column a key channel).
    Returns ``(o (B, T, H, dv), state)``."""
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
    nb, B, H, c, dv = o.shape
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(B, nb * c, H, dv)
    return o[:, :T], state


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
    q, k, v, g, beta = (_blocks(a, nb, c) for a in (q, k, v, g, beta))
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
    return _carry_blocks(state, w, u, qk, q_in, k_out, g_out, T)


def kda_chunked(q, k, v, g, beta, state, block: int = 64):
    """:func:`gated_delta_chunked` for a decay a CHANNEL of a head's
    keys, ``g`` (B, T, H, dk): the same ``o`` and final state as
    :func:`gated_delta_recurrent` over the same operands.

    With ``G`` the running sum of ``g`` inside a block (a vector a
    token) the block's matrices are ``A_ij = beta_i sum_c k_i[c] k_j[c]
    exp(G_i[c] - G_j[c])`` (``j < i``) and ``q_i`` against the same for
    ``j <= i``: the decay no longer factors out of ``k k^T``, so keys
    and queries carry it INTO the products. ``exp(-G_j)`` alone would
    overflow (a channel may decay by e^-5 a token, e^-320 a block), so
    the products are formed a SUB-BLOCK of ``_SOLVE`` key tokens at a
    time, relative to its first token ``b0``: the rows' factor
    ``exp(G_i - G_b0)`` is at most 1 for every row at or after ``b0``
    (the rows before it are above the diagonal and masked; their
    exponent is held at 0), the keys' ``exp(G_b0 - G_j)`` at most
    ``exp(15 x 5)``, finite in float32 — which is what a model's lower
    bound on ``g`` is for (clamped at 80 here, so a caller without one
    loses accuracy, not finiteness). Everything else is as in the
    scalar form: the corrections solve ``(I + A) D = beta (V - K+ S0)``
    with ``K+ = k exp(G)`` (``_unit_lower_inverse``), a short scan over
    the blocks carries the state, ``S <- diag(exp(G_c)) S0 + sum_j (k_j
    exp(G_c - G_j)) d_j^T``. A token with ``g = 0`` and ``beta = 0``
    leaves the state as it found it."""
    B, T, H, dk = q.shape
    c = block
    pad = -T % c
    if pad:     # a ragged last block: tokens that leave the state alone
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nb = (T + pad) // c
    sub = min(c, _SOLVE)
    q, k, v, g, beta = (_blocks(a, nb, c) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)                          # (nb, B, H, c, dk)
    qk_rows = jnp.concatenate([q, k], axis=-2)          # (..., 2c, dk)
    G_rows = jnp.concatenate([G, G], axis=-2)
    cols = []
    for b0 in range(0, c, sub):
        ref = G[..., b0:b0 + 1, :]
        rows = qk_rows * jnp.exp(jnp.minimum(G_rows - ref, 0.0))
        keys = k[..., b0:b0 + sub, :] * jnp.exp(jnp.minimum(
            ref - G[..., b0:b0 + sub, :], 80.0))
        cols.append(jnp.einsum("...id,...jd->...ij", rows, keys,
                               precision=_HI))
    prod = jnp.concatenate(cols, axis=-1)               # (..., 2c, c)
    i = jnp.arange(c)
    qk = jnp.where(i[:, None] >= i[None, :], prod[..., :c, :], 0.0)
    a = jnp.where(i[:, None] > i[None, :],
                  beta[..., :, None] * prod[..., c:, :], 0.0)
    inv = _unit_lower_inverse(a)
    grow = jnp.exp(G)
    w = jnp.matmul(inv, k * grow * beta[..., None], precision=_HI)
    u = jnp.matmul(inv, v * beta[..., None], precision=_HI)
    q_in = q * grow
    k_out = k * jnp.exp(G[..., -1:, :] - G)
    g_out = jnp.exp(G[..., -1, :])[..., None]           # (nb, B, H, dk, 1)
    return _carry_blocks(state, w, u, qk, q_in, k_out, g_out, T)


_SCAN_BLOCK = 64    # tokens a block of the scan kernel, as the XLA form's
_SCAN_PAIRS = 2     # key heads, each with its two value heads, a grid step


def scan_kernel_supported(tokens: int, key_heads: int, value_heads: int,
                          dk: int, dv: int) -> bool:
    """Whether :func:`gated_delta_chunked_kernel` takes these shapes:
    whole blocks of tokens, a head a whole number of 128-lane slices,
    two value heads a key head, whole groups of key heads."""
    return (tokens > 0 and tokens % _SCAN_BLOCK == 0
            and dk % 128 == 0 and dv % 128 == 0
            and value_heads == 2 * key_heads
            and key_heads % _SCAN_PAIRS == 0)


def scan_kernel_armed(tokens: int, key_heads: int, value_heads: int,
                      dk: int, dv: int) -> bool:
    """Whether a scan over these shapes runs the kernel HERE: on a TPU,
    where it takes them. Everywhere else :func:`gated_delta_chunked`
    runs (the CPU's path; a test hands the mixer the interpreted kernel
    by pointing this name at :func:`scan_kernel_supported`)."""
    return jax.default_backend() == "tpu" and scan_kernel_supported(
        tokens, key_heads, value_heads, dk, dv)


def _dot(a, b, dims=((1,), (0,))):
    """A float32 product at the accuracy ``Precision.HIGHEST`` gives."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _spread(x, ones):
    """``x @ ones`` for a 0 / 1 matrix, EXACT in three bf16 passes: the
    three bf16 terms of a float32 sum to it, and each meets one 1."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16)
    r = x - hi.astype(f32)
    mid = r.astype(bf16)
    lo = (r - mid.astype(f32)).astype(bf16)
    return sum(jnp.dot(t, ones, preferred_element_type=f32)
               for t in (hi, mid, lo))


def gated_delta_chunked_kernel(q, k, v, g, beta, state, *, v_at: int = 0,
                               interpret: Optional[bool] = None):
    """:func:`gated_delta_chunked` as ONE Pallas kernel, over the
    operands as the mixer has them: ``q, k`` (B, T, Hk * dk) by KEY head
    (value heads ``2j, 2j + 1`` share key head ``j``: no ``repeat``),
    ``v`` (B, T, Hv * dv) — or a wider array that holds the values from
    lane ``v_at`` on (the convolution's whole output: a slice of it
    would be a copy in front of the kernel) —, ``g, beta`` (B, T, Hv),
    ``state`` (B, Hv, dk, dv). Returns ``(o (B, T, Hv * dv) float32,
    state)``.

    Grid (row, group of ``_SCAN_PAIRS`` key heads, block of 64 tokens),
    the blocks in order, the group's states float32 in VMEM from the
    first block to the last. A key head's pair of value heads is worked
    on side by side: a block's token-by-token matrices of both lie on
    128 lanes of a tile — ``[q; k] k^T`` is one product a key head —
    and, block-diagonal in a (128, 128) tile, multiply both heads' token
    rows stacked on the sublanes; the group's pairs lie side by side in
    turn, so everything elementwise is written once for the group and
    only the products are a pair's or a head's. ``(I + a)^-1`` as
    :func:`_unit_lower_inverse` computes it: the 16-row diagonal blocks
    of every head of the group at once by forward substitution (15
    steps; row ``i``'s coefficients spread over the lanes beforehand, so
    a step is a multiply and a sum down the sublanes), then the two
    levels of merges as ``X - (X a_off) X``. Every product takes float32
    operands at HIGHEST precision; what only moves numbers (a head's
    column of ``g`` to the lanes that want it, a coefficient over its
    block's lanes) is a product with a 0 / 1 matrix, exact in three bf16
    passes.

    The call is jitted and the body keeps to few and ``lax`` forms: a
    warm start traces and lowers some sixty chunk programs that hold the
    kernel, and pays for the body once a shape and once a program — a
    first form, unrolled over four key heads with ``jnp.where`` and
    ``//`` in it, made a warm start 24 s longer (PERF.md section 6, PR
    47)."""
    Hv = g.shape[-1]
    if not scan_kernel_supported(g.shape[1], Hv // 2, Hv, *state.shape[-2:]):
        raise ValueError(
            f"no scan kernel for T={g.shape[1]}, heads {Hv // 2} / {Hv}, "
            f"widths {state.shape[-2:]}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _scan_kernel(q, k, v, g, beta, state, v_at=v_at,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("v_at", "interpret"))
def _scan_kernel(q, k, v, g, beta, state, *, v_at: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, Hv = g.shape
    dk, dv = state.shape[-2:]
    Hk, c, P, s16 = Hv // 2, _SCAN_BLOCK, _SCAN_PAIRS, _SOLVE
    nb, f32 = T // c, jnp.float32
    if v_at % (2 * P * dv):     # no whole block of a group's values
        v, v_at = v[..., v_at:v_at + Hv * dv], 0
    v_block = v_at // (2 * P * dv)

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    def keep(mask, x, other=0.0):
        # (not ``jnp.where``, nor ``//`` and ``%`` below: each is a jitted
        # function the kernel's lowering traces anew, in every program)
        return jax.lax.select(mask, jnp.broadcast_to(x, mask.shape),
                              jnp.full(mask.shape, other, x.dtype))

    b16, b32, bc_ = (n.bit_length() - 1 for n in (s16, 2 * s16, c))

    def kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s_in_ref, o_ref,
               s_out_ref, s_scr):
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _():
            s_scr[...] = s_in_ref[0].astype(f32)

        # (c, W): rows a token, lanes (key head, value head of its pair,
        # token) — every pair of the group side by side
        W = P * 2 * c
        row, lane = iota((c, W), 0), iota((c, W), 1)
        col = lane & (c - 1)
        below, above, diag = row > col, row < col, row == col
        same16 = row >> b16 == col >> b16
        offs = ((s16, (row >> b32 == col >> b32)
                 & (row >> b16 > col >> b16)),
                (2 * s16, row >> b32 > col >> b32))
        # (2c, W): a pair's two heads block-diagonal in its 2c lanes
        own = iota((2 * c, W), 0) >> bc_ == (iota((2 * c, W), 1) >> bc_) & 1
        sub, ln = iota((s16, W), 0), iota((s16, W), 1) & (s16 - 1)
        ones = (iota((2 * c, 2 * c), 0) >> b16
                == iota((2 * c, 2 * c), 1) >> b16).astype(jnp.bfloat16)

        def pairs(x):       # (., W) or (., P * d) -> its P lane slices
            n = x.shape[1] // P
            return [x[:, p * n:(p + 1) * n] for p in range(P)]

        def both(x2):       # (c, W) side by side -> (2c, W) diagonal
            return keep(own, jnp.concatenate([x2, x2], axis=0))

        def along(x2):      # (c, W) column-spread -> its (1, W) row
            return jnp.sum(keep(diag, x2), axis=0, keepdims=True)

        # the running sum of g inside the block, every head at once; a
        # head's column spread over the lanes that want it by a 0 / 1
        # matrix (exact)
        tri = (iota((c, c), 0) >= iota((c, c), 1)).astype(f32)
        g_run, betas = _dot(tri, g_ref[0]), beta_ref[0]     # (c, Hv)
        first = 2 * P * pl.program_id(1)    # the group's first value head

        def wants(width, at):   # (Hv, width): lane l wants head at(l)
            lanes = iota((Hv, width), 1)
            return iota((Hv, width), 0) == first + at(lanes)

        def of_pair(d, e):      # head e of the pair whose d lanes l is in
            return lambda l: 2 * jax.lax.div(l, jnp.full_like(l, d)) + e

        widths = (W,) + (P * dk,) * 2 + (P * dv,) * 2 * (dv != dk)
        spreads = _spread(
            jnp.concatenate([g_run, betas], axis=0),
            jnp.concatenate(
                [wants(W, lambda l: l >> bc_)] + [wants(
                    P * d, of_pair(d, e)) for d in (dk, dv)[:1 + (dv != dk)]
                    for e in (0, 1)], axis=1).astype(jnp.bfloat16))
        at, parts = 0, []
        for n in widths:
            parts.append(spreads[:, at:at + n])
            at += n
        G, bt = parts[0][:c], parts[0][c:]                  # (c, W)
        # (2c, P * d): rows (head of the pair, token)
        Gs, bs = (jnp.concatenate([parts[1][r], parts[2][r]], axis=0)
                  for r in (slice(0, c), slice(c, 2 * c)))
        bv = bs if dv == dk else jnp.concatenate(
            [parts[3][c:], parts[4][c:]], axis=0)

        diff = G - along(G)                         # G_row - G_lane
        decay = jnp.exp(keep(row >= col, diff, -jnp.inf))
        q, k = q_ref[0], k_ref[0]                   # (c, P * dk)
        # [q; k] k^T once a key head, for both its value heads
        prod = [_dot(jnp.concatenate([qh, kh], axis=0),
                     jnp.concatenate([kh, kh], axis=0), ((1,), (1,)))
                for qh, kh in zip(pairs(q), pairs(k))]
        qk = jnp.concatenate([x[:c] for x in prod], axis=1)
        kk = jnp.concatenate([x[c:] for x in prod], axis=1)
        a = keep(below, bt * kk * decay)
        # a^T: k k^T is symmetric, so the same three factors with row
        # and lane exchanged
        a_t = keep(above, along(bt) * kk * jnp.exp(
            keep(above, -diff, -jnp.inf)))
        diag_t = keep(same16, a_t)
        # the diagonal blocks' inverses, every head of the group at
        # once: (16, W) rows j of a block, lanes (pair, head, block, l)
        packed = sum(diag_t[m * s16:(m + 1) * s16] for m in range(c // s16))
        # row i's coefficients a_ij over their block's lanes
        steps = (s16 - 1) * s16
        coeff = jnp.concatenate([_spread(x, ones) for x in pairs(keep(
            (iota((steps, W), 0) >> b16) + 1
            == iota((steps, W), 1) & (s16 - 1),
            jnp.concatenate([packed] * (s16 - 1), axis=0)))],
            axis=1)                                 # (15 * 16, W)
        eye = (sub == ln).astype(f32)
        x = eye
        for i in range(1, s16):
            new = eye[i:i + 1] - jnp.sum(
                coeff[(i - 1) * s16:i * s16] * x, axis=0, keepdims=True)
            x = jax.lax.select(sub == i, jnp.broadcast_to(new, x.shape), x)
        inv = both(keep(same16, jnp.concatenate([x] * (c // s16), axis=0)))
        # a level: X - (X a_off) X, whose only rows that change are the
        # second block's of each pair of n-row blocks
        for n, off in offs:
            odd = [slice(r, r + n) for r in range(n, 2 * c, 2 * n)]
            low = jnp.concatenate([
                _dot(_dot(lhs, mid), rhs) for lhs, mid, rhs in zip(
                    pairs(jnp.concatenate([inv[r] for r in odd], axis=0)),
                    pairs(both(keep(off, a))), pairs(inv))], axis=1)
            rows = []
            for m, r in enumerate(odd):
                rows += [inv[r.start - n:r.start],
                         inv[r] - low[m * n:(m + 1) * n]]
            inv = jnp.concatenate(rows, axis=0)
        # token rows of a pair's two heads stacked: (2c, P * d)
        k2, q2 = (jnp.concatenate([x_, x_], axis=0) for x_ in (k, q))
        v = v_ref[0]                                # lanes (pair, head, dv)
        vs = jnp.concatenate([jnp.concatenate(
            [v[:, (2 * p + e) * dv:(2 * p + e + 1) * dv] for p in range(P)],
            axis=1) for e in (0, 1)], axis=0)
        last = jnp.concatenate([jnp.broadcast_to(
            Gs[e * c + c - 1:(e + 1) * c], (c, P * dk)) for e in (0, 1)],
            axis=0)
        # a head's whole decay over the block, a (1, P * dv) row
        whole = [parts[-2 + e][c - 1:c] for e in (0, 1)]
        grow = jnp.exp(Gs)
        kb, vb, q_in = k2 * (bs * grow), vs * bv, q2 * grow
        k_out = k2 * jnp.exp(last - Gs)
        mix = pairs(both(qk * decay))
        for p, (inv_p, kb_p, vb_p, q_p, k_p) in enumerate(zip(
                pairs(inv), pairs(kb), pairs(vb), pairs(q_in),
                pairs(k_out))):
            wu = _dot(inv_p, jnp.concatenate([kb_p, vb_p], axis=1))
            w, u = wu[:, :dk], wu[:, dk:]
            d, qs = [], []
            for e in (0, 1):
                rows = slice(e * c, (e + 1) * c)
                ws = _dot(jnp.concatenate([w[rows], q_p[rows]], axis=0),
                          s_scr[2 * p + e])
                d.append(u[rows] - ws[:c])
                qs.append(ws[c:])
            o = jnp.concatenate(qs, axis=0) + _dot(
                mix[p], jnp.concatenate(d, axis=0))
            for e in (0, 1):
                rows = slice(e * c, (e + 1) * c)
                h = 2 * p + e
                o_ref[0, :, h * dv:(h + 1) * dv] = o[rows]
                s_scr[h] = jnp.exp(whole[e][:, p * dv:(p + 1) * dv]) \
                    * s_scr[h] + _dot(k_p[rows], d[e], ((0,), (0,)))

        @pl.when(t == nb - 1)
        def _():
            s_out_ref[0] = s_scr[...].astype(s_out_ref.dtype)

    def tokens(width, at=0):
        return pl.BlockSpec((1, c, width), lambda b, h, t: (b, t, at + h))

    whole = pl.BlockSpec((1, c, Hv), lambda b, h, t: (b, t, 0))
    states = pl.BlockSpec((1, 2 * P, dk, dv), lambda b, h, t: (b, h, 0, 0))
    o, state = pl.pallas_call(
        kernel,
        grid=(B, Hk // P, nb),
        in_specs=[tokens(P * dk), tokens(P * dk),
                  tokens(2 * P * dv, v_block), whole, whole, states],
        out_specs=[tokens(2 * P * dv), states],
        out_shape=[jax.ShapeDtypeStruct((B, T, Hv * dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        scratch_shapes=[pltpu.VMEM((2 * P, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_scan",
    )(q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
      beta.astype(f32), state)
    return o, state


_KDA_HEADS = 8      # heads a grid step of the vector decay's scan holds


def kda_scan_kernel_supported(tokens: int, heads: int, dk: int,
                              dv: int) -> bool:
    """Whether :func:`kda_chunked_kernel` takes these shapes: whole
    blocks of tokens, heads of 128 lanes (as many key as value heads),
    whole groups of them."""
    return (tokens > 0 and tokens % _SCAN_BLOCK == 0
            and dk == 128 and dv == 128 and heads % _KDA_HEADS == 0)


def kda_scan_kernel_armed(tokens: int, heads: int, dk: int, dv: int) -> bool:
    """:func:`scan_kernel_armed` for the vector decay: on a TPU, where
    :func:`kda_chunked_kernel` takes the shapes; everywhere else
    :func:`kda_chunked` runs."""
    return jax.default_backend() == "tpu" and kda_scan_kernel_supported(
        tokens, heads, dk, dv)


def kda_chunked_kernel(q, k, v, g, beta, state, *, v_at: int = 0,
                       interpret: Optional[bool] = None):
    """:func:`kda_chunked` as ONE Pallas kernel, over the operands as
    the mixer has them: ``q, k`` (B, T, H * dk) l2-normed, ``v`` (B, T,
    H * dv) — or a wider array that holds the values from lane ``v_at``
    on (the convolution's whole output) —, ``g`` (B, T, H * dk) float32,
    ``beta`` (B, T, H), ``state`` (B, H, dk, dv). Returns ``(o (B, T,
    H * dv) float32, state)``.

    Grid (row, group of ``_KDA_HEADS`` heads, block of 64 tokens), the
    blocks in order, the group's states float32 in VMEM from the first
    block to the last, as in :func:`gated_delta_chunked_kernel`; a PAIR
    of heads is to this kernel what a key head's pair of value heads is
    to that one (their token-by-token matrices side by side on 128
    lanes, block-diagonal in a (128, 128) tile over both heads' token
    rows). What :func:`kda_chunked` computes, in the order a kernel
    wants it:

    - ``G``, the running sum of ``g`` inside the block, by six shifted
      adds down the sublanes (no product).
    - ``[q; k] k^T`` a sub-block of 16 tokens at a time, relative to one
      of its tokens — of 16 ROW tokens and their MIDDLE token ``m``,
      where the XLA form takes 16 key tokens and their first: a row's
      factor ``exp(G_i - G_m)`` is one matrix for the block, the keys'
      ``exp(G_m - G_j)`` one a sub-block (far below 1 for the keys of
      earlier sub-blocks, as it should be). At the published floor of
      -5 a token neither exponent passes 40; both are held at 80, so a
      decay below the floor loses accuracy, not finiteness. Two
      sub-blocks' keys fill the 128 columns of one product, so a head's
      four are two products of 64 rows, each row reading its own
      sub-block's half. (The XLA form's rows 16 tokens past a key
      sub-block's first carry ``exp(-80)`` at the floor, where a head's
      128 channels are small enough to go subnormal and be flushed: it
      stands 7e-5 of the largest output from the recurrence there, this
      one 4e-7: tests/test_kda_delta.py.)
    - ``(I + a)^-1`` by substitution and two levels of merges, exactly
      as the scalar kernel (``a^T`` from a pair's tile transposed: ``k
      k^T`` with the decay inside is not symmetric).
    - ``D = inv (beta V - K+ S)`` — the corrections' own equation, one
      product with the inverse where ``W`` and ``U`` apart are two —,
      ``o = q_in S + qk D``, ``S <- diag(exp(G_c)) S + k_out^T D``, the
      channels' whole decay turned down the sublanes as a (8, 128)
      tile's transpose.

    Every product takes float32 operands at HIGHEST precision; a
    coefficient over its block's lanes is a product with a 0 / 1 matrix
    (exact). ``beta`` is handed in by group (a (T, group) block; a
    head's column is broadcast over its lanes on the chip). The body
    keeps to ``lax`` forms for the warm start's sake (PERF.md section 6,
    PR 47)."""
    H = beta.shape[-1]
    if not kda_scan_kernel_supported(g.shape[1], H, *state.shape[-2:]):
        raise ValueError(
            f"no scan kernel for T={g.shape[1]}, heads {H}, widths "
            f"{state.shape[-2:]}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _kda_scan_kernel(q, k, v, g, beta, state, v_at=v_at,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("v_at", "interpret"))
def _kda_scan_kernel(q, k, v, g, beta, state, *, v_at: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H = beta.shape
    d = state.shape[-1]         # a head's lanes: key and value width
    c, s16, Hg = _SCAN_BLOCK, _SOLVE, _KDA_HEADS
    nb, P, f32 = T // c, Hg // 2, jnp.float32
    if v_at % (Hg * d):         # no whole block of a group's values
        v, v_at = v[..., v_at:v_at + H * d], 0
    exp = jax.lax.exp

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    def keep(mask, x, other=0.0):
        return jax.lax.select(mask, jnp.broadcast_to(x, mask.shape),
                              jnp.full(mask.shape, other, x.dtype))

    b16, b32, bc_ = (n.bit_length() - 1 for n in (s16, 2 * s16, c))

    def kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s_in_ref, o_ref,
               s_out_ref, s_scr):
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _():
            s_scr[...] = s_in_ref[0].astype(f32)

        # (c, W): rows a token, lanes (head, token) — the group's heads
        # side by side, a PAIR of them a 128-lane tile
        W = Hg * c
        row, lane = iota((c, W), 0), iota((c, W), 1)
        col = lane & (c - 1)
        below, same16 = row > col, row >> b16 == col >> b16
        offs = ((s16, (row >> b32 == col >> b32)
                 & (row >> b16 > col >> b16)),
                (2 * s16, row >> b32 > col >> b32))
        # (2c, W): a pair's two heads block-diagonal in its 2c lanes
        own = iota((2 * c, W), 0) >> bc_ == (iota((2 * c, W), 1) >> bc_) & 1
        sub, ln = iota((s16, W), 0), iota((s16, W), 1) & (s16 - 1)
        ones = (iota((2 * c, 2 * c), 0) >> b16
                == iota((2 * c, 2 * c), 1) >> b16).astype(jnp.bfloat16)
        # (c, 2c), a pair's tile: its first head's lanes; the rows of the
        # first of two sub-blocks (rows q 16, q 16, k 16, k 16)
        first, first2 = (iota((n, 2 * c), 1) < c for n in (c, 2 * c))
        even = iota((c, 2 * c), 0) >> b16 & 1 == 0

        def pairs(x):       # (., P * n) -> its P lane slices
            n = x.shape[1] // P
            return [x[:, p * n:(p + 1) * n] for p in range(P)]

        def head(x, h):     # (., Hg * d) -> head h's d lanes
            return x[:, h * d:(h + 1) * d]

        def both(x2):       # (c, W) side by side -> (2c, W) diagonal
            return keep(own, jnp.concatenate([x2, x2], axis=0))

        q, k, G = q_ref[0], k_ref[0], g_ref[0]              # (c, Hg * d)
        # the running sum of g inside the block
        token = iota((c, Hg * d), 0)
        for n in (1, 2, 4, 8, 16, 32):
            G = G + keep(token >= n, pltpu.roll(G, n, 0))
        # [q; k] k^T: a row relative to its sub-block's middle token,
        # the keys relative to the same token, a sub-block of rows at a
        # time
        def held(x):        # exp(min(x, 80)): finite whatever the decay
            return exp(jax.lax.min(x, jnp.full_like(x, 80.0)))

        mids = [G[m:m + 1] for m in range(s16 // 2, c, s16)]
        decayed = held(G - jnp.concatenate(
            [jnp.broadcast_to(x, (s16, Hg * d)) for x in mids], axis=0))
        qd, kd = q * decayed, k * decayed
        keys = [k * held(x - G) for x in mids]
        tiles = []
        for p in range(P):
            side = []
            for e in (0, 1):
                h, halves = 2 * p + e, []
                for m in (0, 2 * s16):      # sub-blocks 0, 1 then 2, 3
                    rows = slice(m, m + 2 * s16)
                    x = _dot(
                        jnp.concatenate([head(qd, h)[rows],
                                         head(kd, h)[rows]], axis=0),
                        jnp.concatenate([head(keys[m >> b16], h),
                                         head(keys[(m >> b16) + 1], h)],
                                        axis=0), ((1,), (1,)))  # (c, 2c)
                    # a row's own sub-block's columns, in this head's
                    # half of the pair's lanes
                    turned = pltpu.roll(x, c, 1)
                    halves.append(jax.lax.select(even, x, turned) if e == 0
                                  else jax.lax.select(even, turned, x))
                side.append(jnp.concatenate(
                    [halves[0][:2 * s16], halves[1][:2 * s16],
                     halves[0][2 * s16:], halves[1][2 * s16:]], axis=0))
            tiles.append(jax.lax.select(first2, *side))
        qk = keep(row >= col, jnp.concatenate([x[:c] for x in tiles],
                                              axis=1))
        kk = jnp.concatenate([x[c:] for x in tiles], axis=1)
        # a head's write strength down its lanes: a (c, 1) column of the
        # group's block, broadcast
        betas = beta_ref[0, 0]                              # (c, Hg)
        bd = jnp.concatenate([jnp.broadcast_to(betas[:, h:h + 1], (c, d))
                              for h in range(Hg)], axis=1)
        bt = jnp.concatenate([jax.lax.select(
            first,          # (no slice of a mask: Mosaic refuses one)
            jnp.broadcast_to(betas[:, 2 * p:2 * p + 1], (c, 2 * c)),
            jnp.broadcast_to(betas[:, 2 * p + 1:2 * p + 2], (c, 2 * c)))
            for p in range(P)], axis=1)
        a2 = both(keep(below, bt * kk))                     # (2c, W)
        # a^T, a head's (c, c) at a time: the pair's diagonal tile
        # transposed, its two heads' rows added back side by side
        a_t = jnp.concatenate(
            [x[:c] + x[c:] for x in (y.T for y in pairs(a2))], axis=1)
        diag_t = keep(same16, a_t)
        # the diagonal blocks' inverses, every head of the group at
        # once: (16, W) rows j of a block, lanes (head, block, l)
        packed = sum(diag_t[m * s16:(m + 1) * s16] for m in range(c // s16))
        # row i's coefficients a_ij over their block's lanes
        steps = (s16 - 1) * s16
        coeff = jnp.concatenate([_spread(x, ones) for x in pairs(keep(
            (iota((steps, W), 0) >> b16) + 1
            == iota((steps, W), 1) & (s16 - 1),
            jnp.concatenate([packed] * (s16 - 1), axis=0)))],
            axis=1)                                 # (15 * 16, W)
        eye = (sub == ln).astype(f32)
        x = eye
        for i in range(1, s16):
            new = eye[i:i + 1] - jnp.sum(
                coeff[(i - 1) * s16:i * s16] * x, axis=0, keepdims=True)
            x = jax.lax.select(sub == i, jnp.broadcast_to(new, x.shape), x)
        inv = both(keep(same16, jnp.concatenate([x] * (c // s16), axis=0)))
        # a level: X - (X a_off) X, whose only rows that change are the
        # second block's of each pair of n-row blocks
        for n, off in offs:
            odd = [slice(r, r + n) for r in range(n, 2 * c, 2 * n)]
            low = jnp.concatenate([
                _dot(_dot(lhs, mid), rhs) for lhs, mid, rhs in zip(
                    pairs(jnp.concatenate([inv[r] for r in odd], axis=0)),
                    pairs(keep(jnp.concatenate([off, off], axis=0), a2)),
                    pairs(inv))], axis=1)
            rows = []
            for m, r in enumerate(odd):
                rows += [inv[r.start - n:r.start],
                         inv[r] - low[m * n:(m + 1) * n]]
            inv = jnp.concatenate(rows, axis=0)
        grow = exp(G)
        kb, vb, q_in = k * grow * bd, v_ref[0] * bd, q * grow
        k_out = k * exp(G[c - 1:c] - G)
        # a channel's whole decay over the block, DOWN the sublanes as
        # a head's state has its key channels: a tile's transpose
        whole = exp(G[c - 8:])                              # (8, Hg * d)
        mix = pairs(both(qk))
        for p, inv_p in enumerate(pairs(inv)):
            rest, qs = [], []
            for h in (2 * p, 2 * p + 1):
                ks = _dot(jnp.concatenate([head(kb, h), head(q_in, h)],
                                          axis=0), s_scr[h])    # (2c, d)
                rest.append(head(vb, h) - ks[:c])
                qs.append(ks[c:])
            # token rows of the pair's two heads stacked: (2c, d)
            cor = _dot(inv_p, jnp.concatenate(rest, axis=0))
            o = jnp.concatenate(qs, axis=0) + _dot(mix[p], cor)
            for e in (0, 1):
                rows = slice(e * c, (e + 1) * c)
                h = 2 * p + e
                o_ref[0, :, h * d:(h + 1) * d] = o[rows]
                s_scr[h] = head(whole, h).T[:, 7:8] * s_scr[h] + _dot(
                    head(k_out, h), cor[rows], ((0,), (0,)))

        @pl.when(t == nb - 1)
        def _():
            s_out_ref[0] = s_scr[...].astype(s_out_ref.dtype)

    def tokens(at=0):
        return pl.BlockSpec((1, c, Hg * d), lambda b, h, t: (b, t, at + h))

    states = pl.BlockSpec((1, Hg, d, d), lambda b, h, t: (b, h, 0, 0))
    o, state = pl.pallas_call(
        kernel,
        grid=(B, H // Hg, nb),
        in_specs=[tokens(), tokens(), tokens(v_at // (Hg * d)), tokens(),
                  pl.BlockSpec((1, 1, c, Hg), lambda b, h, t: (b, h, t, 0)),
                  states],
        out_specs=[tokens(), states],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * d), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        scratch_shapes=[pltpu.VMEM((Hg, d, d), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="kda_delta_scan",
    )(q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
      # a group's heads a (T, group) block of their own
      jnp.moveaxis(beta.astype(f32).reshape(B, T, H // Hg, Hg), 2, 1), state)
    return o, state
