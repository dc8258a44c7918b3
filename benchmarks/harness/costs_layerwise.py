"""Operations and bytes a decode step NEEDS, summed LAYER BY LAYER from
the ``model`` group of a configuration file — for a model whose layers
are not all alike: a layer is dense or has experts, an expert has a
width of its own, and a shared expert or a further attention matrix is
counted where the group names it. Never read from the program.

``harness/costs.py`` reads ``intermediate_size`` as the width of every
MLP of every layer; this module reads, beside the keys it reads, these
optional ones, named for what they are and for no model:

    moe_intermediate_size   an expert's width (absent or 0: the dense one)
    num_dense_layers        leading layers with a dense MLP in a model
                            that has experts (absent: 0)
    num_shared_experts      gated MLPs of the expert width every token
                            passes through, stored as a dense matrix
    attn_gate               a (hidden, heads x head_dim) matrix beside q
    router_bias             one float32 an expert beside the router

For a model without them ``decode_step`` and ``decode_stage`` return what
``costs.decode_step`` and ``costs.decode_stage`` return, to the byte (a
test holds it on every configuration file): the same terms, summed a
group of equal layers at a time. A later ``benchmark`` PR can point the
accepted roofline shares here and take ``costs.py``'s two functions
away. What neither counts: norm weights, the embedding rows' source.
Peaks, ``least_seconds``, the KV terms and the ``attn`` and ``tail``
stages are ``costs.py``'s.
"""

from __future__ import annotations

from benchmarks.harness import costs
from benchmarks.harness.costs import _wbytes


def layer_groups(m: dict) -> list:
    """The model's layers as ``[(count, layer)]``, equal layers together
    and in the order they run. A layer: ``attn`` and ``mlp`` (dense
    matrices as (rows, cols): the dense MLP, or the shared expert),
    ``experts``, ``expert`` (one routed expert's matrices) and
    ``router_floats`` (float32 values beside the bf16 router)."""
    D, F = m["hidden_size"], m["intermediate_size"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    attn = [(D, H * hd), (D, KV * hd), (D, KV * hd), (H * hd, D)]
    if m.get("attn_gate"):
        attn.insert(3, (D, H * hd))

    def gated(width):
        if m.get("mlp", "swiglu") == "squared_relu":
            return [(D, width), (width, D)]
        return [(D, width), (D, width), (width, D)]

    L, E = m["num_layers"], m.get("num_experts", 0)
    dense = {"attn": attn, "mlp": gated(F), "experts": 0, "expert": [],
             "router_floats": 0}
    if not E:
        return [(L, dense)]
    Fe = m.get("moe_intermediate_size") or F
    shared = m.get("num_shared_experts", 0)
    sparse = {"attn": attn, "mlp": gated(shared * Fe) if shared else [],
              "experts": E, "expert": [(D, Fe), (D, Fe), (Fe, D)],
              "router_floats": E if m.get("router_bias") else 0}
    n_dense = m.get("num_dense_layers", 0)
    return ([(n_dense, dense)] if n_dense else []) + [(L - n_dense, sparse)]


def _layer(m: dict, layer: dict, quant: str, rows: float) -> dict:
    """Bytes and operations-a-row of ONE layer's attention matrices and
    of its MLP stage (dense MLP; or router, touched experts and shared
    expert)."""
    D = m["hidden_size"]
    out = {"attn_b": sum(_wbytes(r, c, quant) for r, c in layer["attn"]),
           "attn_f": sum(2 * r * c for r, c in layer["attn"]),
           "mlp_b": sum(_wbytes(r, c, quant) for r, c in layer["mlp"]),
           "mlp_f": sum(2 * r * c for r, c in layer["mlp"])}
    E = layer["experts"]
    if E:
        k = m.get("num_experts_per_tok", 2)
        touched = costs.expected_experts_touched(E, k, rows)
        one = sum(r * c for r, c in layer["expert"])
        # costs.py's terms first, in its order; what it lacks after
        out["mlp_b"] = (touched * 2 * one + 2 * D * E
                        + 4 * layer["router_floats"] + out["mlp_b"])
        out["mlp_f"] = k * 2 * one + 2 * D * E + out["mlp_f"]
    return out


def _summed(m: dict, quant: str, rows: float, *names: str, times=1):
    """``times`` x the sum over the layers of the named terms added up
    within a layer (the products in ``costs.py``'s order: a float sum
    is to agree to the last bit)."""
    total = 0
    for count, layer in layer_groups(m):
        one = _layer(m, layer, quant, rows)
        total = total + times * count * sum(one[n] for n in names)
    return total


def decode_step(m: dict, quant: str, rows: float, kv_tokens: float,
                kv_dtype_bytes: int = 2) -> dict:
    """As ``costs.decode_step``: one decode step of ``rows`` sequences
    that attend ``kv_tokens`` context tokens. Every matmul weight of
    every layer once as stored (of routed experts those the rows are
    expected to reach, bf16), the lm_head once, the KV of the live
    contexts once and a new row a sequence; two operations a weight a
    row for the matmuls a row passes through, and the attention's."""
    L, D, V = m["num_layers"], m["hidden_size"], m["vocab_size"]
    H, hd = m["num_heads"], m["head_dim"]
    head_b = (_wbytes(D, V, quant)
              if not m.get("tie_word_embeddings", False) else 2 * D * V)
    kv_b = costs.kv_bytes_per_token(m, kv_dtype_bytes)
    weight_bytes = _summed(m, quant, rows, "attn_b", "mlp_b") + head_b \
        + rows * 2 * D
    kv_bytes = kv_tokens * kv_b + rows * kv_b
    flops = rows * (_summed(m, quant, rows, "attn_f", "mlp_f")
                    + 2 * D * V) + L * 4 * H * hd * kv_tokens
    return {"weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
            "bytes": weight_bytes + kv_bytes, "flops": flops}


def decode_stage(m: dict, quant: str, stage: str, rows: float,
                 kv_tokens: float, kv_dtype_bytes: int = 2) -> dict:
    """As ``costs.decode_stage``. ``mlp`` = over the layers, the dense
    MLP's weights, or the router, the touched experts' and the shared
    expert's; ``attn`` and ``tail`` are ``costs.py``'s own."""
    if stage != "mlp":
        return costs.decode_stage(m, quant, stage, rows, kv_tokens,
                                  kv_dtype_bytes)
    return {"bytes": _summed(m, quant, rows, "mlp_b"),
            "flops": _summed(m, quant, rows, "mlp_f", times=rows)}
