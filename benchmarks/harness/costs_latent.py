"""Operations and bytes a decode step NEEDS of a model with a LATENT
cache and an EXPERT SHARE, from the ``model`` group of a configuration
file alone — never read from the program. ``costs.py`` and
``costs_layerwise.py`` count per-head keys and values and every expert
the file names: both wrong for such a model. Named for what they are
and for no model, the keys read beside ``costs.py``'s:

    kv_lora_rank, qk_rope_head_dim   a cached token leaves rank + rope
                            values a layer, ONCE for all heads
    q_lora_rank, qk_nope_head_dim, v_head_dim   the attention's five
                            matrices: hidden x q_rank, q_rank x heads x
                            (nope + rope), hidden x (rank + rope),
                            rank x heads x (nope + v) [stored as its two
                            halves], heads x v x hidden
    experts_held            the experts whose matrices this chip holds,
                            of the router's ``num_experts`` columns
                            (absent or 0: all)
    moe_intermediate_size, num_dense_layers, num_shared_experts,
    router_bias             as ``costs_layerwise.py`` reads them

A decode step reads: every attention matrix as stored; (rank + rope)
values a cached token a layer, once; a layer the router's columns, the
HELD experts its rows are expected to touch (bf16) and the shared expert
as stored; the dense layers' MLP as stored; the tail as ``costs.py``
counts it. It computes two operations a weight a row for the matrices a
row passes through — of the routed experts the share of its
``num_experts_per_tok`` that is expected to fall on held ones — and the
ABSORBED attention's 2 x ((rank + rope) + rank) x heads operations a
cached token a layer (a score over rank + rope values, a sum over rank).
What it does not count: norm weights, the embedding rows' source.
"""

from __future__ import annotations

from benchmarks.harness import costs
from benchmarks.harness.costs import _wbytes


def attn_matrices(m: dict) -> list:
    """A latent layer's attention matrices as (rows, cols); the
    latent's up-projection as its two stored halves."""
    D, H = m["hidden_size"], m["num_heads"]
    R, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    nope, vd, Rq = m["qk_nope_head_dim"], m["v_head_dim"], m["q_lora_rank"]
    return [(D, Rq), (Rq, H * (nope + rope)), (D, R + rope),
            (R, H * nope), (R, H * vd), (H * vd, D)]


def kv_values_per_token(m: dict) -> int:
    """Values ONE cached token leaves in ONE layer, for all heads."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def held_experts(m: dict) -> int:
    return m.get("experts_held") or m["num_experts"]


def expected_held_touched(m: dict, rows: float) -> float:
    """Mean distinct HELD experts that ``rows`` tokens reach, each
    choosing ``num_experts_per_tok`` of ALL ``num_experts`` at random."""
    E, k = m["num_experts"], m.get("num_experts_per_tok", 2)
    return held_experts(m) * (1.0 - (1.0 - k / E) ** rows)


def _gated(D: int, width: int) -> list:
    return [(D, width), (D, width), (width, D)]


def mlp_stage(m: dict, quant: str, rows: float) -> dict:
    """The MLP stage over all layers: the dense layers' MLP; an expert
    layer's router (+ float32 bias), touched held experts and shared
    expert."""
    D, F = m["hidden_size"], m["intermediate_size"]
    L, nd = m["num_layers"], m.get("num_dense_layers", 0)
    E, k = m["num_experts"], m.get("num_experts_per_tok", 2)
    Fe = m.get("moe_intermediate_size") or F
    shared = _gated(D, m.get("num_shared_experts", 0) * Fe) \
        if m.get("num_shared_experts") else []
    one = sum(r * c for r, c in _gated(D, Fe))
    dense_b = sum(_wbytes(r, c, quant) for r, c in _gated(D, F))
    dense_f = sum(2 * r * c for r, c in _gated(D, F))
    expert_b = (expected_held_touched(m, rows) * 2 * one + 2 * D * E
                + (4 * E if m.get("router_bias") else 0)
                + sum(_wbytes(r, c, quant) for r, c in shared))
    expert_f = (k * held_experts(m) / E * 2 * one + 2 * D * E
                + sum(2 * r * c for r, c in shared))
    return {"bytes": nd * dense_b + (L - nd) * expert_b,
            "flops": rows * (nd * dense_f + (L - nd) * expert_f)}


def decode_stage(m: dict, quant: str, stage: str, rows: float,
                 kv_tokens: float, kv_dtype_bytes: int = 2) -> dict:
    """As ``costs.decode_stage``: ``attn`` = the latent pool's rows of
    the live contexts once plus a new row a sequence, and the absorbed
    attention's operations; ``mlp`` = ``mlp_stage``; ``tail`` is
    ``costs.py``'s."""
    if stage == "attn":
        L, H = m["num_layers"], m["num_heads"]
        n = kv_values_per_token(m)
        return {"bytes": L * (kv_tokens + rows) * n * kv_dtype_bytes,
                "flops": L * 2 * (n + m["kv_lora_rank"]) * H * kv_tokens}
    if stage == "mlp":
        return mlp_stage(m, quant, rows)
    return costs.decode_stage(m, quant, stage, rows, kv_tokens,
                              kv_dtype_bytes)


def decode_step(m: dict, quant: str, rows: float, kv_tokens: float,
                kv_dtype_bytes: int = 2) -> dict:
    """As ``costs.decode_step``, for one decode step of ``rows``
    sequences that attend ``kv_tokens`` cached tokens."""
    L, D = m["num_layers"], m["hidden_size"]
    attn = attn_matrices(m)
    attn_b = L * sum(_wbytes(r, c, quant) for r, c in attn)
    attn_f = rows * L * sum(2 * r * c for r, c in attn)
    parts = [decode_stage(m, quant, s, rows, kv_tokens, kv_dtype_bytes)
             for s in costs.STAGES]
    kv_bytes = parts[0]["bytes"]
    weight_bytes = attn_b + parts[1]["bytes"] + parts[2]["bytes"] \
        + rows * 2 * D
    return {"weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
            "bytes": weight_bytes + kv_bytes,
            "flops": attn_f + sum(p["flops"] for p in parts)}
