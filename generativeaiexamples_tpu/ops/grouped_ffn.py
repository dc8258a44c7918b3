"""Pallas grouped expert FFN (TPU): one gated MLP per BLOCK of rows, each
block against the weights of its own expert.

The dropless expert layer (parallel/moe.py ``dropless_moe_ffn``) sorts the
token-to-expert assignments by expert and pads each expert's run to whole
blocks of ``bm`` rows. What is left is a product of (bm, D) row blocks
with per-block weights — and at decode the weights are nearly all the
bytes a step streams, so the point of this kernel is the weight stream:

- the block -> expert table and the count of blocks that hold rows ride
  scalar prefetch, and the weights' ``index_map`` reads them: a grid step
  DMAs expert ``block_expert[b]``'s tiles straight out of the whole
  (L, E, in, out) stacks, double-buffered by the Pallas pipeline, so the
  next block's weights arrive while this block computes (an XLA loop of
  three fusions a block starts each block's stream cold: 44 us a block
  where the bytes take 14, chip, PR 28);
- grid steps past the last block that holds rows keep the previous
  step's block indices, so they fetch nothing and compute nothing: a
  decode step reads the experts its rows touch and no others;
- the FFN width is tiled (``tf``) only as far as VMEM asks; the
  down-projection accumulates over the tiles in float32 scratch.

``out[r] = (act(x[r] Wg[e]) * (x[r] Wu[e])) Wd[e]`` for the rows r of a
block of expert e. Rows of blocks that hold no assignment are never
written: the caller reads only rows it placed.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

_VMEM_WEIGHT_BUDGET = 24 << 20     # gate + up + down tiles, double-buffered
_VMEM_LIMIT = 48 << 20


def ffn_tile(D: int, F: int, itemsize: int = 2) -> int:
    """Columns of the FFN width one grid step takes: all of it if the
    three tiles fit the budget twice over, else the largest multiple of
    128 that divides F and does."""
    if F % 128 or 6 * D * F * itemsize <= _VMEM_WEIGHT_BUDGET:
        return F
    tf = F
    while tf > 128 and (F % tf or tf % 128
                        or 6 * D * tf * itemsize > _VMEM_WEIGHT_BUDGET):
        tf -= 128
    return tf


def kernel_supported(D: int, F: int, bm: int, dtype) -> bool:
    """Lane-aligned widths, whole sublane tiles of rows, bf16 or f32."""
    return (D % 128 == 0 and F % 128 == 0 and bm % 16 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def use_kernel(D: int, F: int, bm: int, dtype) -> bool:
    """On TPU backends. A geometry the kernel does not take runs the
    caller's plain block loop there, about three times slower at decode
    (26 ms a step where the kernel takes 9; chip, PR 28), and says so."""
    if jax.default_backend() != "tpu":
        return False
    if not kernel_supported(D, F, bm, dtype):
        logger.warning(
            "grouped expert FFN: D=%d F=%d bm=%d %s is not a geometry the "
            "Pallas kernel takes; running the plain block loop", D, F, bm,
            jnp.dtype(dtype).name)
        return False
    return True


def grouped_expert_ffn(x_pad: jax.Array, block_expert: jax.Array,
                       n_blocks: jax.Array, layer_index: jax.Array,
                       w_gate: jax.Array, w_up: jax.Array,
                       w_down: jax.Array, *, bm: int, relu: bool,
                       interpret: bool = False) -> jax.Array:
    """x_pad: (NB * bm, D) rows laid out in blocks; block_expert: (NB,)
    int32; n_blocks: () int32 blocks that hold rows (they come first);
    layer_index: () int32; w_gate / w_up: (L, E, D, F), w_down:
    (L, E, F, D). Returns (NB * bm, D) in x_pad.dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, D = x_pad.shape
    NB = R // bm
    F = w_gate.shape[-1]
    tf = ffn_tile(D, F, w_gate.dtype.itemsize)
    nF = F // tf
    act = jax.nn.relu if relu else jax.nn.silu

    def kernel(be_ref, meta_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
               acc_ref):
        b, f = pl.program_id(0), pl.program_id(1)

        @pl.when(b < meta_ref[0])
        def _():
            x = x_ref[...]
            gate = jnp.dot(x, wg_ref[...],
                           preferred_element_type=jnp.float32)
            up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
            y = jnp.dot((act(gate) * up).astype(x.dtype), wd_ref[...],
                        preferred_element_type=jnp.float32)
            if nF == 1:
                o_ref[...] = y.astype(o_ref.dtype)
            else:
                @pl.when(f == 0)
                def _():
                    acc_ref[...] = y

                @pl.when(f > 0)
                def _():
                    acc_ref[...] += y

                @pl.when(f == nF - 1)
                def _():
                    o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    def live(b, meta):            # the block this step works on, or the
        return jnp.maximum(jnp.minimum(b, meta[0] - 1), 0)   # last real one

    def tile(b, f, meta):         # ... and its FFN tile, likewise
        return jnp.where(b < meta[0], f, nF - 1)

    def rows(b, f, be, meta):
        return (live(b, meta), 0)

    def w_in(b, f, be, meta):
        return (meta[1], be[live(b, meta)], 0, tile(b, f, meta))

    def w_out(b, f, be, meta):
        return (meta[1], be[live(b, meta)], tile(b, f, meta), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # block -> expert; (n_blocks, layer)
        grid=(NB, nF),
        in_specs=[
            pl.BlockSpec((bm, D), rows),
            pl.BlockSpec((None, None, D, tf), w_in),
            pl.BlockSpec((None, None, D, tf), w_in),
            pl.BlockSpec((None, None, tf, D), w_out),
        ],
        out_specs=pl.BlockSpec((bm, D), rows),
        scratch_shapes=[pltpu.VMEM((bm, D), jnp.float32)],
    )
    meta = jnp.stack([n_blocks.astype(jnp.int32),
                      layer_index.astype(jnp.int32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), x_pad.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_expert_ffn",
    )(block_expert.astype(jnp.int32), meta, x_pad, w_gate, w_up, w_down)
