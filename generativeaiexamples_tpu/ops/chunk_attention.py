"""A chunk's attention over one block of keys: the online-softmax update
whose score tile lives and dies on the chip.

A prefill chunk (models/llama.py ``apply_prefill_paged``) attends its
prefix block by block and then itself, under ONE online softmax whose
state — running maximum, row sum, accumulator — is carried from block to
block. ``chunk_attention_update`` is one such step as a Pallas kernel:
scores, mask, maximum, exponent, sum and the PV product of all heads
against one key block, the ``(heads, T, C)`` float32 scores never leaving
VMEM. As XLA operations the same step wrote and re-read that tensor
(64 heads x 512 x 512 x 4 B = 67 MB) half a dozen times a block.

It knows nothing of a latent or a page: queries, a key block, a value
block, the carry, and three positions for the mask. What the caller
gathers, zeroes and expands stays the caller's (``LatentKV.attend_prefix``
in models/kv_cache.py, the only caller today; ``HeadKV``'s reader could
call it once it has a GQA group axis).

Everything lies QUERIES ON THE LANES: scores are ``(T, C)``, the
accumulator ``(dv, C)``, maximum and sum ``(1, C)`` rows. A row statistic
is then one lane-dense row a head — ``(H, 1, C)`` float32 in HBM, 128 KB
for 64 heads x 512 queries — where the usual ``(C, 1)`` column would be
padded to 128 lanes (16.8 MB, as much as the accumulator) on every trip;
reductions run over sublanes (elementwise across vregs), and the
broadcasts are sublane broadcasts.

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
