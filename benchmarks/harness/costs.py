"""Peaks of the chips, and the operations and bytes a step NEEDS — the
denominators of every roofline share the benchmark reports. Computed
from the configuration's shapes alone, never read from the program.

A model is described by the ``model`` group of its configuration file
(the ``LlamaConfig`` fields as run) and ``weight_quant`` ("int8" stores
the attention, dense-MLP and lm_head matrices as one byte a weight plus
a float32 scale per output channel; expert matrices stay bf16, as
``ops/quant.py`` leaves them; the embedding stays bf16 and a step reads
only the rows it looks up).
"""

from __future__ import annotations

# Published peaks of one chip. Source for "TPU v5 lite": Google Cloud
# TPU documentation, "TPU v5e" system architecture page (197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks(device_kind: str) -> dict:
    """Peaks for a ``device_kind`` as JAX reports it; an unknown kind is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; add it to "
            f"benchmarks/harness/costs.py PEAKS with its source") from None


def _wbytes(rows: int, cols: int, quant: str) -> int:
    """Stored bytes of one (rows, cols) matmul weight."""
    if quant == "int8":
        return rows * cols + 4 * cols
    if quant in ("", "none", "bf16"):
        return 2 * rows * cols
    raise ValueError(f"unknown weight_quant {quant!r}")


def layer_shapes(m: dict) -> dict:
    D, F = m["hidden_size"], m["intermediate_size"]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    attn = [(D, H * hd), (D, KV * hd), (D, KV * hd), (H * hd, D)]
    E = m.get("num_experts", 0)
    if E:
        mlp = [(D, F), (D, F), (F, D)]            # per expert
    elif m.get("mlp", "swiglu") == "squared_relu":
        mlp = [(D, F), (F, D)]
    else:
        mlp = [(D, F), (D, F), (F, D)]
    return {"attn": attn, "mlp": mlp, "experts": E,
            "router": (D, E) if E else None}


def expected_experts_touched(experts: int, per_tok: int, rows: float) -> float:
    """Mean number of distinct experts that ``rows`` tokens choosing
    ``per_tok`` of ``experts`` uniformly at random reach."""
    if not experts:
        return 0.0
    return experts * (1.0 - (1.0 - per_tok / experts) ** rows)


def kv_bytes_per_token(m: dict, kv_dtype_bytes: int = 2) -> int:
    return (m["num_layers"] * m["num_kv_heads"] * m["head_dim"]
            * 2 * kv_dtype_bytes)


def weight_bytes_resident(m: dict, quant: str) -> int:
    """Bytes of every parameter as stored on the chip (the embedding and
    all experts included)."""
    s = layer_shapes(m)
    L, D, V = m["num_layers"], m["hidden_size"], m["vocab_size"]
    per_layer = sum(_wbytes(r, c, quant) for r, c in s["attn"])
    if s["experts"]:
        per_layer += s["experts"] * sum(2 * r * c for r, c in s["mlp"])
        per_layer += 2 * D * s["experts"]
    else:
        per_layer += sum(_wbytes(r, c, quant) for r, c in s["mlp"])
    per_layer += 4 * D * 2                       # two norms (+ biases)
    total = L * per_layer + 2 * V * D + 2 * D * 2
    if not m.get("tie_word_embeddings", False):
        total += _wbytes(D, V, quant)
    return total


def decode_step(m: dict, quant: str, rows: float, kv_tokens: float,
                kv_dtype_bytes: int = 2) -> dict:
    """Operations and bytes ONE decode step needs with ``rows`` active
    sequences whose contexts sum to ``kv_tokens`` tokens.

    Bytes: every matmul weight once as stored (for experts: the experts
    the rows reach, in bf16), the lm_head once, the KV cache of the live
    contexts once, one new KV row per sequence written. Operations: two
    per weight per row for the matmuls the row passes through (its
    top-k experts only), and per layer 4 x head_dim x heads per context
    token for attention scores and values.
    """
    s = layer_shapes(m)
    L, D, V = m["num_layers"], m["hidden_size"], m["vocab_size"]
    H, hd = m["num_heads"], m["head_dim"]
    k = m.get("num_experts_per_tok", 2)
    attn_b = sum(_wbytes(r, c, quant) for r, c in s["attn"])
    attn_f = sum(2 * r * c for r, c in s["attn"])
    if s["experts"]:
        touched = expected_experts_touched(s["experts"], k, rows)
        one = sum(r * c for r, c in s["mlp"])
        mlp_b = touched * 2 * one + 2 * D * s["experts"]
        mlp_f = k * 2 * one + 2 * D * s["experts"]
    else:
        mlp_b = sum(_wbytes(r, c, quant) for r, c in s["mlp"])
        mlp_f = sum(2 * r * c for r, c in s["mlp"])
    head_b = (_wbytes(D, V, quant)
              if not m.get("tie_word_embeddings", False) else 2 * D * V)
    kv_b = kv_bytes_per_token(m, kv_dtype_bytes)
    weight_bytes = L * (attn_b + mlp_b) + head_b + rows * 2 * D
    kv_bytes = kv_tokens * kv_b + rows * kv_b
    flops = rows * (L * (attn_f + mlp_f) + 2 * D * V) \
        + L * 4 * H * hd * kv_tokens
    return {"weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
            "bytes": weight_bytes + kv_bytes, "flops": flops}


def prefill_tokens(m: dict, quant: str, tokens: int, context: int = 0) -> dict:
    """Operations and bytes a prefill of ``tokens`` new tokens needs
    (on top of ``context`` already cached): weights once, the matmul
    operations per token, causal attention over context + half the
    chunk. Capacity-routed experts all stream (a chunk reaches all)."""
    s = layer_shapes(m)
    L, D, V = m["num_layers"], m["hidden_size"], m["vocab_size"]
    H, hd = m["num_heads"], m["head_dim"]
    k = m.get("num_experts_per_tok", 2)
    attn_b = sum(_wbytes(r, c, quant) for r, c in s["attn"])
    attn_f = sum(2 * r * c for r, c in s["attn"])
    if s["experts"]:
        one = sum(r * c for r, c in s["mlp"])
        mlp_b, mlp_f = s["experts"] * 2 * one, k * 2 * one
    else:
        mlp_b = sum(_wbytes(r, c, quant) for r, c in s["mlp"])
        mlp_f = sum(2 * r * c for r, c in s["mlp"])
    kv_b = kv_bytes_per_token(m)
    flops = tokens * L * (attn_f + mlp_f) + 2 * D * V \
        + L * 4 * H * hd * tokens * (context + tokens / 2)
    bytes_ = L * (attn_b + mlp_b) + _wbytes(D, V, quant) \
        + (context + tokens) * kv_b
    return {"bytes": bytes_, "flops": flops}


def least_seconds(cost: dict, peak: dict, quant: str = "") -> dict:
    """The least time the chip could take for ``cost``: the larger of
    bytes over peak bytes/s and operations over peak FLOP/s (bf16 — the
    int8 weights are dequantized into bf16 matmuls by this program, so
    the bf16 peak is the one that binds), and which of the two bounds."""
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    t_flops = cost["flops"] / peak["bf16_flops"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops",
            "t_bytes": t_bytes, "t_flops": t_flops}
