"""Pipeline parallelism: GPipe-style microbatched schedule over the ``pp``
mesh axis.

The reference gets pipeline parallelism from the TRT-LLM engine build
(reference: model_server/__main__.py:99-104 ``--pipeline-parallelism``,
conversion_scripts/llama/build.py:516 ``pp_size`` in the Mapping). TPU-native
version: every device holds ``L/pp`` contiguous layers (the same leading-L
sharding the param specs already use), microbatches stream through the
stages, and activations hop stage->stage with ``lax.ppermute`` over ICI —
one SPMD program, no per-rank processes.

Schedule: ``M`` microbatches over ``pp`` stages takes ``M + pp - 1`` ticks.
Each tick every stage (a) picks its input — the embedded microbatch for
stage 0, the activation received from the previous stage otherwise —
(b) runs its local layer stack, (c) ppermutes the result forward. The last
stage writes logits into the output buffer for the microbatch it just
finished. Bubble fraction is the usual ``(pp-1)/(M+pp-1)``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models import llama
from ..models.configs import LlamaConfig
from ..utils.errors import ShardingError


def pipeline_forward(mesh: Mesh, params: llama.Params, cfg: LlamaConfig,
                     tokens: jax.Array, positions: jax.Array,
                     n_microbatches: int = 2,
                     kv_valid_len: jax.Array | None = None) -> jax.Array:
    """Forward pass with the layer stack pipelined over the ``pp`` axis.

    tokens/positions: (B, S); B must divide into ``n_microbatches``.
    kv_valid_len: optional (B,) valid-token count per row (padding mask
    for attention), sliced per microbatch like the tokens.
    Embedding and the output head are replicated across stages (they are
    small next to the layer stack); only stage 0 consumes the embedding and
    only the last stage's logits survive. Returns (B, S, V) float32 logits,
    replicated over pp.
    """
    pp = mesh.shape["pp"]
    B, S = tokens.shape
    M = n_microbatches
    if cfg.num_layers % pp:
        raise ShardingError(
            f"num_layers {cfg.num_layers} not divisible by pp={pp} "
            f"(the layers%pp check of the reference, build.py:519-521)")
    if B % M:
        raise ShardingError(f"batch {B} not divisible by "
                            f"n_microbatches={M}")
    mb = B // M

    def stage_fn(layers, embed, tokens, positions, valid):
        stage = jax.lax.axis_index("pp")
        is_first = stage == 0
        is_last = stage == pp - 1

        def tick(carry, t):
            recv, outbuf = carry
            my_mb = t - stage                  # microbatch at this stage now
            active = (my_mb >= 0) & (my_mb < M)
            idx = jnp.clip(my_mb, 0, M - 1) * mb
            tok_mb = jax.lax.dynamic_slice(tokens, (idx, 0), (mb, S))
            pos_mb = jax.lax.dynamic_slice(positions, (idx, 0), (mb, S))
            val_mb = jax.lax.dynamic_slice(valid, (idx,), (mb,))
            h_in = jnp.where(is_first, jnp.take(embed, tok_mb, axis=0), recv)
            h_out = llama.run_layers(layers, cfg, h_in, pos_mb,
                                     kv_valid_len=val_mb)
            # the last stage commits hidden states for its (valid)
            # microbatch; others re-write what is already there
            current = jax.lax.dynamic_slice(outbuf, (idx, 0, 0), h_out.shape)
            outbuf = jax.lax.dynamic_update_slice(
                outbuf, jnp.where(active & is_last, h_out, current),
                (idx, 0, 0))
            # hop activations to the next stage (nothing enters stage 0)
            recv_next = jax.lax.ppermute(
                h_out, "pp", [(i, i + 1) for i in range(pp - 1)])
            return (recv_next, outbuf), None

        # carries become device-varying after axis_index/ppermute; mark the
        # initial values as varying over pp so the scan types line up
        recv0 = jax.lax.pcast(
            jnp.zeros((mb, S, cfg.hidden_size), embed.dtype),
            ("pp",), to="varying")
        outbuf0 = jax.lax.pcast(
            jnp.zeros((B, S, cfg.hidden_size), embed.dtype),
            ("pp",), to="varying")
        (_, outbuf), _ = jax.lax.scan(
            tick, (recv0, outbuf0), jnp.arange(M + pp - 1))
        # only the last stage holds real hidden states; replicate across pp
        # (a (B,S,D) psum — V/D times cheaper than exchanging logits)
        return jax.lax.psum(
            jnp.where(is_last, outbuf, jnp.zeros_like(outbuf)), "pp")

    if kv_valid_len is None:
        # every position valid: same in-sequence causal masking as the
        # unpipelined forward's default
        kv_valid_len = jnp.full((B,), S, jnp.int32)
    layer_specs = jax.tree.map(lambda _: P("pp"), params["layers"])
    hidden = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(layer_specs, P(), P(), P(), P()),
        out_specs=P())(
        params["layers"], params["embed"], tokens, positions,
        kv_valid_len.astype(jnp.int32))
    # unembed once, outside the pipeline (head weights are pp-replicated)
    return llama.unembed(params, cfg, hidden)


def pipeline_loss_fn(mesh: Mesh, cfg: LlamaConfig, n_microbatches: int = 2):
    """Cross-entropy loss with the forward pipelined over pp — drop-in for
    a pp>1 training step (grads flow through ppermute/scan)."""
    fwd = partial(pipeline_forward, mesh, n_microbatches=n_microbatches)

    def loss_fn(params, batch):
        B, S = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        # same contract as the pp==1 branch: "mask" is the LOSS mask,
        # attention validity comes from "length" when provided (SFT
        # batches mask prompt tokens out of the loss but not attention)
        length = batch.get("length")
        if length is None:
            length = jnp.sum(batch["mask"], axis=-1)
        logits = fwd(params, cfg, batch["tokens"], positions,
                     kv_valid_len=length)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, batch["targets"][..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        mask = batch["mask"].astype(jnp.float32)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    return loss_fn
