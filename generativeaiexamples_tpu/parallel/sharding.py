"""Sharding rules for the model param trees (megatron-style TP).

Replaces the reference's per-rank weight splitting
(reference: conversion_scripts/llama/weight.py:141-148 ``split`` slices each
tensor per MPI rank at import time). Here the full logical tree is annotated
with ``PartitionSpec``s and ``jax.device_put`` / GSPMD does the physical
placement — one code path for any mesh shape.

Rules (leading axis of every layer tensor is L, sharded over ``pp`` when
pipeline parallelism is on):
  wq/wk/wv  (L, D, heads*hd)  → column-parallel: shard out dim over tp
  wo        (L, heads*hd, D)  → row-parallel: shard in dim over tp
  w_gate/up (L, D, F)         → column-parallel
  w_down    (L, F, D)         → row-parallel
  embed     (V, D)            → shard V over tp (vocab-parallel)
  lm_head   (D, V)            → shard V over tp
  MoE experts (L, E, ...)     → shard E over ep, then tp on the inner dims
XLA inserts the all-reduce after row-parallel matmuls — the compiled
equivalent of the reference's NCCL all-reduce plugin
(reference: build.py:341-345 ``use_custom_all_reduce``).

GQA note: when tp > num_kv_heads the reference duplicates KV weights
(weight.py:150-157). Here ``kv_tp_axis`` degrades wk/wv to replicated in
that case and XLA re-partitions the attention einsum itself.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.configs import LlamaConfig

Specs = dict[str, Any]


def _axis_on(mesh: Mesh, name: str) -> Optional[str]:
    """Axis name if it exists in the mesh with size > 1, else None."""
    return name if mesh.shape.get(name, 1) > 1 else None


def llama_param_specs(cfg: LlamaConfig, mesh: Mesh) -> Specs:
    tp = _axis_on(mesh, "tp")
    pp = _axis_on(mesh, "pp")
    ep = _axis_on(mesh, "ep")
    # KV projections can only shard over tp if heads divide evenly.
    kv_tp = tp if tp and cfg.num_kv_heads % mesh.shape["tp"] == 0 else None
    q_tp = tp if tp and cfg.num_heads % mesh.shape["tp"] == 0 else None

    def stack(experts: bool) -> Specs:
        """One layer stack's specs: the expert stack, or a dense one."""
        layers: Specs = {
            "attn_norm": P(pp, None),
            "mlp_norm": P(pp, None),
            "wq": P(pp, None, q_tp),
            "wk": P(pp, None, kv_tp),
            "wv": P(pp, None, kv_tp),
            "wo": P(pp, q_tp, None),
        }
        norms = ["attn_norm", "mlp_norm"]
        if cfg.attn_gate:           # a column of the gate per attn column
            layers["wz"] = P(pp, None, q_tp)
        if cfg.qk_norm:             # (L, hd): shared by the heads
            layers.update({"q_norm": P(pp, None), "k_norm": P(pp, None)})
        if cfg.post_norms:
            norms += ["post_attn_norm", "post_mlp_norm"]
            layers.update({n: P(pp, None) for n in norms[2:]})
        # GPT-Next/Nemotron extras (norm biases, projection biases):
        # biases shard like their projection's output dim.
        if cfg.norm == "layernorm1p":
            layers.update({n + "_b": P(pp, None) for n in norms})
        if cfg.attn_bias:
            layers.update({"bq": P(pp, q_tp), "bk": P(pp, kv_tp),
                           "bv": P(pp, kv_tp), "bo": P(pp, None)})
        if experts:
            layers.update({
                "router": P(pp, None, None),
                "w_gate": P(pp, ep, None, tp),
                "w_up": P(pp, ep, None, tp),
                "w_down": P(pp, ep, tp, None),
            })
            if cfg.router_bias:
                layers["router_bias"] = P(pp, None)
            if cfg.num_shared_experts:  # dense: every device's tokens
                layers.update({
                    "ws_gate": P(pp, None, tp),
                    "ws_up": P(pp, None, tp),
                    "ws_down": P(pp, tp, None),
                })
        elif cfg.mlp == "squared_relu":
            layers.update({
                "w_up": P(pp, None, tp),
                "w_down": P(pp, tp, None),
            })
            if cfg.mlp_bias:
                layers.update({"b_up": P(pp, tp), "b_down": P(pp, None)})
        else:
            layers.update({
                "w_gate": P(pp, None, tp),
                "w_up": P(pp, None, tp),
                "w_down": P(pp, tp, None),
            })
        return layers

    specs: Specs = {
        "embed": P(tp, None),
        "layers": stack(bool(cfg.num_experts)),
        "final_norm": P(None),
    }
    if cfg.num_dense_layers:        # the leading dense stack
        specs["dense_layers"] = stack(False)
    if cfg.norm == "layernorm1p":
        specs["final_norm_b"] = P(None)
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, tp)
    return specs


def kv_cache_spec(cfg: LlamaConfig, mesh: Mesh) -> Specs:
    """Cache (L, B, T, KV, hd): batch over dp, KV heads over tp."""
    tp = _axis_on(mesh, "tp")
    dp = _axis_on(mesh, "dp")
    pp = _axis_on(mesh, "pp")
    kv_tp = tp if tp and cfg.num_kv_heads % mesh.shape["tp"] == 0 else None
    spec = P(pp, dp, None, kv_tp, None)
    return {"k": spec, "v": spec}


def paged_kv_cache_spec(cfg: LlamaConfig, mesh: Mesh,
                        quantized: bool = False) -> Specs:
    """Paged cache (L, N, KV, page, hd): KV heads over tp, pages replicated.

    The page pool has no batch axis (slots share it through block tables),
    so dp does not appear; layers shard over pp like the params.
    int8-KV mode adds per-row scale pools (L, N, KV, page) — same sharding
    minus the head dim (ops/kv_quant.py).
    """
    tp = _axis_on(mesh, "tp")
    pp = _axis_on(mesh, "pp")
    kv_tp = tp if tp and cfg.num_kv_heads % mesh.shape["tp"] == 0 else None
    spec = P(pp, None, kv_tp, None, None)
    specs = {"k": spec, "v": spec}
    if quantized:
        specs["ks"] = specs["vs"] = P(pp, None, kv_tp, None)
    return specs


def activation_spec(mesh: Mesh) -> P:
    """Token/hidden activations: batch over dp, replicated over tp."""
    return P(_axis_on(mesh, "dp"), None)


def shard_params(params: Any, mesh: Mesh, specs: Any) -> Any:
    """Place a param tree onto the mesh per its specs.

    Quantized leaves (``{"q"|"q4", "scale"}`` dicts from ops.quant) reuse
    the raw weight's spec: the int tensor takes it verbatim; the
    per-output-channel scale (one rank lower, reduction axis gone) takes
    the spec minus its second-to-last axis.
    """
    from ..ops.quant import is_quantized

    def place(x, s):
        return jax.device_put(x, NamedSharding(mesh, s))

    def walk(p: Any, s: Any) -> Any:
        if isinstance(p, dict):
            if is_quantized(p):
                w_spec = tuple(s)
                scale_spec = (P(*(w_spec[:-2] + w_spec[-1:]))
                              if len(w_spec) >= 2 else P())

                def leaf_spec(k):
                    if k in ("q", "q4"):
                        return s
                    if k in ("gscale", "gbias"):
                        # (..., G, N): same rank as the weight — the
                        # group axis stands where K stood
                        return P(*w_spec)
                    if k == "pre_scale":
                        return (P(*w_spec[:-1]) if len(w_spec) >= 1
                                else P())
                    return scale_spec
                return {k: place(v, leaf_spec(k)) for k, v in p.items()}
            return {k: walk(v, s[k]) for k, v in p.items()}
        return place(p, s)

    return walk(params, specs)
