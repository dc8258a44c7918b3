"""Sparse mixture-of-experts: top-k routing with capacity-bounded dispatch.

The reference never runs Mixtral locally — it reaches it through cloud
endpoints (reference: examples/5_mins_rag_no_gpu/main.py:50). Here expert
parallelism is first-class: this module is the sparse-compute path promised
by ``models/llama.py`` — O(tokens x k) expert FLOPs instead of the dense
formulation's O(tokens x E).

Design (TPU-first):
- **Static shapes.** Each expert processes a fixed-capacity buffer
  ``C = ceil(T*k/E * capacity_factor)``; overflowing tokens are dropped
  (their combine weight is zero) — the GShard/Switch capacity discipline
  that keeps XLA shapes static.
- **Scatter/gather dispatch.** Tokens are routed with one scatter-add into
  ``(E, C, D)`` and one gather back — O(T*k*D) data movement, not the
  O(T*E*C*D) one-hot-einsum formulation (quadratic in T at prefill).
- **EP sharding.** Under GSPMD the expert axis of the ``(E, C, D)`` buffers
  follows the ``ep``-sharded expert weights, so XLA inserts the token
  all-to-all over ICI on its own. ``ep_expert_ffn`` is the explicit
  ``shard_map`` equivalent (experts over ``ep``, FFN width over ``tp`` with
  a psum), used where manual control is wanted and as the parity oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.configs import LlamaConfig


def expert_capacity(n_tokens: int, n_experts: int, k: int,
                    capacity_factor: float) -> int:
    """Per-expert token slots; static given the (padded) token count."""
    return max(1, int(-(-n_tokens * k * capacity_factor // n_experts)))


def route_topk(router_logits: jax.Array, k: int, capacity: int):
    """Top-k routing with in-expert slot assignment.

    router_logits: (T, E). Returns flat (T*k,) arrays, token-major:
      expert  — chosen expert id per claim
      slot    — position inside that expert's capacity buffer
      weight  — softmaxed router weight (float32)
      keep    — False where the expert's capacity was already full
    Earlier tokens claim slots first (deterministic, order-based priority).
    """
    T, E = router_logits.shape
    w, idx = jax.lax.top_k(router_logits, k)                    # (T, k)
    w = jax.nn.softmax(w.astype(jnp.float32), axis=-1)
    expert = idx.reshape(-1)                                    # (T*k,)
    claims = jax.nn.one_hot(expert, E, dtype=jnp.int32)         # (T*k, E)
    pos = jnp.cumsum(claims, axis=0) - 1                        # claim rank
    slot = jnp.take_along_axis(pos, expert[:, None], axis=1)[:, 0]
    keep = slot < capacity
    return expert, jnp.clip(slot, 0, capacity - 1), w.reshape(-1), keep


def _dispatch(x_flat: jax.Array, expert: jax.Array, slot: jax.Array,
              keep: jax.Array, n_experts: int, capacity: int) -> jax.Array:
    """(T, D) tokens -> (E, C, D) expert buffers (scatter; slots unique)."""
    T, D = x_flat.shape
    k = expert.shape[0] // T
    t_idx = jnp.repeat(jnp.arange(T), k)
    contrib = x_flat[t_idx] * keep[:, None].astype(x_flat.dtype)
    return jnp.zeros((n_experts, capacity, D), x_flat.dtype).at[
        expert, slot].add(contrib)


def _combine(expert_out: jax.Array, expert: jax.Array, slot: jax.Array,
             weight: jax.Array, keep: jax.Array, n_tokens: int) -> jax.Array:
    """(E, C, D) expert outputs -> (T, D) weighted token outputs (gather)."""
    k = expert.shape[0] // n_tokens
    t_idx = jnp.repeat(jnp.arange(n_tokens), k)
    y = expert_out[expert, slot]                                # (T*k, D)
    w = (weight * keep).astype(y.dtype)[:, None]
    return jnp.zeros((n_tokens, expert_out.shape[-1]), y.dtype).at[
        t_idx].add(y * w)


def _expert_ffn(expert_in: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array) -> jax.Array:
    """Per-expert SwiGLU on (E, C, D) with stacked (E, D, F) weights."""
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, w_gate))
    up = jnp.einsum("ecd,edf->ecf", expert_in, w_up)
    return jnp.einsum("ecf,efd->ecd", gate * up, w_down)


def sparse_moe_ffn(x: jax.Array, lp: dict[str, jax.Array],
                   cfg: LlamaConfig) -> jax.Array:
    """Sparse MoE layer: (B, S, D) -> (B, S, D), top-k experts per token.

    Pure jnp — under jit with ``ep``-sharded expert weights GSPMD reshards
    the (E, C, D) buffers over ``ep`` and emits the all-to-all itself.
    """
    B, S, D = x.shape
    T = B * S
    x_flat = x.reshape(T, D)
    C = expert_capacity(T, cfg.num_experts, cfg.num_experts_per_tok,
                        cfg.moe_capacity_factor)
    # Stage scopes (models/llama.py SCOPES): routing, the capacity
    # scatter and the weighted gather are ``moe_route``; the expert
    # einsums — the weight stream — are ``moe_experts``.
    with jax.named_scope("moe_route"):
        logits = x_flat.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        expert, slot, weight, keep = route_topk(
            logits, cfg.num_experts_per_tok, C)
        expert_in = _dispatch(x_flat, expert, slot, keep, cfg.num_experts, C)
    with jax.named_scope("moe_experts"):
        expert_out = _expert_ffn(expert_in, lp["w_gate"], lp["w_up"],
                                 lp["w_down"])
    with jax.named_scope("moe_route"):
        return _combine(expert_out, expert, slot, weight, keep,
                        T).reshape(B, S, D)


def ep_expert_ffn(mesh: Mesh, expert_in: jax.Array, w_gate: jax.Array,
                  w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """Explicit shard_map expert FFN: experts over ``ep``, FFN width over
    ``tp`` (row-parallel down-projection closed with a psum over tp)."""
    def local(ei, g, u, d):
        out = _expert_ffn(ei, g, u, d)
        return jax.lax.psum(out, "tp")

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("ep", None, None), P("ep", None, "tp"),
                  P("ep", None, "tp"), P("ep", "tp", None)),
        out_specs=P("ep", None, None))(expert_in, w_gate, w_up, w_down)


def ep_sparse_moe_ffn(mesh: Mesh, x: jax.Array, lp: dict[str, jax.Array],
                      cfg: LlamaConfig) -> jax.Array:
    """``sparse_moe_ffn`` with the expert compute under explicit shard_map
    (dispatch/combine stay global: XLA lowers the boundary resharding to
    the ep all-to-all over ICI)."""
    B, S, D = x.shape
    T = B * S
    x_flat = x.reshape(T, D)
    C = expert_capacity(T, cfg.num_experts, cfg.num_experts_per_tok,
                        cfg.moe_capacity_factor)
    # capacity must tile over ep shards evenly for the shard_map specs
    with jax.named_scope("moe_route"):
        logits = x_flat.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        expert, slot, weight, keep = route_topk(
            logits, cfg.num_experts_per_tok, C)
        expert_in = _dispatch(x_flat, expert, slot, keep, cfg.num_experts, C)
    with jax.named_scope("moe_experts"):
        expert_out = ep_expert_ffn(mesh, expert_in, lp["w_gate"],
                                   lp["w_up"], lp["w_down"])
    with jax.named_scope("moe_route"):
        return _combine(expert_out, expert, slot, weight, keep,
                        T).reshape(B, S, D)
