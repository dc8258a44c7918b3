"""Manifold-constrained hyper-connections (mHC, arXiv 2512.24880, over
Hyper-Connections, arXiv 2409.19606): the residual path of a model whose
stream between blocks is ``n`` copies wide.

A token's stream is ``X`` (n, C), carried flat as vec(X): (..., n x C),
stream ``i`` its columns ``[i C, (i + 1) C)`` — a (..., n, C) array would
pad n = 4 to a tile's sublanes on the chip. A sublayer ``F`` (with its
own pre-norm) is applied as

    x^     = vec(X) / sqrt(mean(vec(X)^2) + eps)            float32
    [p, q, r] = x^ . phi           phi (n C, n + n + n^2)
    H_pre  = sigmoid(a_pre p + b_pre)                       (n,)
    H_post = 2 sigmoid(a_post q + b_post)                   (n,)
    M      = exp(clip(a_res mat(r) + b_res, -clamp, clamp)) (n, n), row-major
    iters times:  M <- M / (rowsum(M) + eps);  M <- M / (colsum(M) + eps)
    H_res  = M                                              doubly stochastic
    u      = H_pre . X                                      (C,)   ``hc_pre``
    X'     = H_res . X + H_post^T F(u)                      (n, C) ``hc_post``

The coefficients are float32 whatever the stream's dtype, one column a
token: ``H_pre`` / ``H_post`` (n, T) and ``H_res`` (n, n, T), the tokens
on the minor axis, so that the normalisations are elementwise over whole
vectors of tokens. On the chip the chain from the logits to ``H_res``
(clamp, exp, ``iters`` pairs) is ONE Pallas kernel, ``hc_sinkhorn``: the
chip's compiler will not duplicate a divide into its four consumers, so
the same chain in ``jax.numpy`` compiles to four fusions a pair — 80
dependent launches a sublayer, 1280 a decode step of 8 layers
(tools/dump_hlo.py; ``sinkhorn`` is that form: the CPU's, and what the
tests hold the kernel to). ``hc_pre`` reads ``X`` for the statistic, the
projection and the mix (the mix fused by the compiler into the
sublayer's pre-norm); ``hc_post`` reads ``X`` and ``y`` once and writes
``X'`` once.

A model starts its stream as the embedding row repeated (``expand``) and
ends it as the sum of the copies (``collapse``), before the final norm.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` row-then-column normalisations of ``m`` (n, n, T), each
    sum ``+ eps``. The sums over the two leading axes are written out as
    adds of slices: elementwise, nothing reduced across lanes."""
    n = m.shape[0]

    def pair(_, m):
        m = m / (sum(m[:, j] for j in range(n))[:, None] + eps)
        return m / (sum(m[i] for i in range(n))[None] + eps)
    return jax.lax.fori_loop(0, iters, pair, m, unroll=True)


def _sinkhorn_kernel(logits_ref, out_ref, *, n: int, iters: int, eps: float,
                     clamp: float):
    """Entry (i, j) of every token of the tile is one (rows, 128) array:
    all of the chain is elementwise over whole registers."""
    m = [[jnp.exp(jnp.clip(logits_ref[i * n + j], -clamp, clamp))
          for j in range(n)] for i in range(n)]
    for _ in range(iters):
        for i in range(n):
            s = sum(m[i][1:], m[i][0]) + eps
            m[i] = [v / s for v in m[i]]
        for j in range(n):
            s = sum((m[i][j] for i in range(1, n)), m[0][j]) + eps
            for i in range(n):
                m[i][j] = m[i][j] / s
    for i in range(n):
        for j in range(n):
            out_ref[i * n + j] = m[i][j]


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp",
                                             "interpret"))
def sinkhorn_kernel(logits: jax.Array, *, n: int, iters: int, eps: float,
                    clamp: float, interpret: bool = False) -> jax.Array:
    """``H_res`` (n, n, T) from its logits (n x n, T) float32 — clamp,
    exponential and ``iters`` normalisation pairs — as ONE Pallas kernel
    (``hc_sinkhorn`` in a trace). The tokens are laid out (T / 128, 128)
    so every entry of the matrix is whole registers; T is padded to the
    lanes with zeros (a padded token's matrix is uniform, and dropped).
    Jitted: one trace serves every sublayer of every program."""
    T = logits.shape[1]
    rows = -(-T // LANES)
    block = 8 if rows % 8 == 0 else rows
    padded = jnp.pad(logits, ((0, 0), (0, rows * LANES - T)))
    spec = pl.BlockSpec((n * n, block, LANES), lambda t: (0, t, 0))
    out = pl.pallas_call(
        functools.partial(_sinkhorn_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp),
        grid=(rows // block,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n * n, rows, LANES), jnp.float32),
        interpret=interpret, name="hc_sinkhorn",
    )(padded.reshape(n * n, rows, LANES))
    return out.reshape(n * n, rows * LANES)[:, :T].reshape(n, n, T)


def coefficients(x: jax.Array, w: tuple, *, n: int, iters: int, eps: float,
                 clamp: float) -> tuple:
    """``(H_pre (n, T), H_post (n, T), H_res (n, n, T))`` float32 of the
    tokens ``x`` (T, n x C); ``w`` = (phi (n C, n^2 + 2n), alpha (3,),
    b (n^2 + 2n,))."""
    phi, alpha, b = w
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1) + eps)          # (T,)
    z = jax.lax.dot_general(x, phi.astype(x.dtype), (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    z = (z * inv[:, None]).T                                       # (m, T)
    alpha, b = alpha.astype(jnp.float32), b.astype(jnp.float32)[:, None]
    h_pre = jax.nn.sigmoid(alpha[0] * z[:n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[n:2 * n] + b[n:2 * n])
    logits = alpha[2] * z[2 * n:] + b[2 * n:]                      # (n n, T)
    if jax.default_backend() == "tpu":
        h_res = sinkhorn_kernel(logits, n=n, iters=iters, eps=eps,
                                clamp=clamp)
    else:
        h_res = sinkhorn(jnp.exp(jnp.clip(logits, -clamp, clamp)).reshape(
            n, n, -1), iters, eps)
    return h_pre, h_post, h_res


def _streams(x: jax.Array, n: int) -> list:
    D = x.shape[-1] // n
    return [x[:, i * D:(i + 1) * D].astype(jnp.float32) for i in range(n)]


def hc_pre(x: jax.Array, w: tuple, *, n: int, iters: int, eps: float,
           clamp: float) -> tuple:
    """A sublayer's input and its write-back coefficients: ``x`` (...,
    n x C) -> ``(u (..., C), H_post (n, T), H_res (n, n, T))``."""
    with jax.named_scope("hc_pre"):
        xt = x.reshape(-1, x.shape[-1])
        h_pre, h_post, h_res = coefficients(xt, w, n=n, iters=iters, eps=eps,
                                            clamp=clamp)
        u = sum(h_pre[i][:, None] * s for i, s in enumerate(_streams(xt, n)))
        # The mix is the compiler's to place: on the chip it fuses it
        # into the sublayer's pre-norm, whose fusion then reads the
        # stream under the SUBLAYER's scope (``attn_proj``, ``moe_route``,
        # ``mlp``), not this one — the faster program; a trace books the
        # statistic, the projection and ``hc_sinkhorn`` here.
        u = u.astype(x.dtype)
        return u.reshape(x.shape[:-1] + (-1,)), h_post, h_res


def hc_post(x: jax.Array, y: jax.Array, h_post: jax.Array,
            h_res: jax.Array) -> jax.Array:
    """The stream after a sublayer: ``H_res . X + H_post^T y``, ``x``
    (..., n x C) the stream ``hc_pre`` read and ``y`` (..., C) the
    sublayer's output."""
    n = h_post.shape[0]
    with jax.named_scope("hc_post"):
        xs = _streams(x.reshape(-1, x.shape[-1]), n)
        yf = y.reshape(-1, y.shape[-1]).astype(jnp.float32)
        out = [sum(h_res[i, j][:, None] * xs[j] for j in range(n))
               + h_post[i][:, None] * yf for i in range(n)]
        return jnp.concatenate(out, axis=-1).astype(x.dtype).reshape(x.shape)


def row_defect(h_res: jax.Array) -> jax.Array:
    """Mean over the tokens of max_i |rowsum_i(H_res) - 1|: 0 for a
    doubly stochastic matrix (the columns are exact after the last
    normalisation; the rows are what the iterations converge)."""
    rows = sum(h_res[:, j] for j in range(h_res.shape[1]))          # (n, T)
    return jnp.mean(jnp.max(jnp.abs(rows - 1.0), axis=0))


def expand(h: jax.Array, n: int) -> jax.Array:
    """The stream a model starts from: the embedding row, ``n`` times."""
    with jax.named_scope("hc_pre"):
        return jnp.tile(h, (1,) * (h.ndim - 1) + (n,))


def collapse(x: jax.Array, n: int) -> jax.Array:
    """What the final norm reads: the sum of the ``n`` copies."""
    with jax.named_scope("hc_post"):
        D = x.shape[-1] // n
        return sum(x[..., i * D:(i + 1) * D].astype(jnp.float32)
                   for i in range(n)).astype(x.dtype)
