"""Peaks of the chips, and the operations and bytes a step NEEDS — the
denominators of every roofline share the benchmark reports. Computed
from the configuration's shapes alone, never read from the program.

A model is described by the ``model`` group of its configuration file
(the ``LlamaConfig`` fields as run) and ``weight_quant`` ("int8" stores
the attention, dense-MLP and lm_head matrices as one byte a weight plus
a float32 scale per output channel; expert matrices stay bf16, as
``ops/quant.py`` leaves them; the embedding stays bf16 and a step reads
only the rows it looks up).

Two optional keys of the ``model`` group say which layers attend a
window, named for what they are and for no model: ``sliding_window``
(tokens; absent or 0 = none) and ``window_layers`` (one 0/1 a layer, or
a period that is repeated over the layers; absent = all 0). A window
layer attends ``min(context, window)`` tokens of a row, so the KV bytes
and attention operations are summed over layers AND rows
(``attended_tokens``): the rows' contexts one by one, never their sum.
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
    """Bytes one token's keys and values take over ALL layers (a window
    layer stores a token like any other, until it is released)."""
    return (m["num_layers"] * m["num_kv_heads"] * m["head_dim"]
            * 2 * kv_dtype_bytes)


def layer_windows(m: dict) -> list:
    """Per layer, the window it attends in tokens (0 = the whole
    context), from ``sliding_window`` and ``window_layers``."""
    L = m["num_layers"]
    window = int(m.get("sliding_window") or 0)
    pattern = list(m.get("window_layers") or [])
    if not window or not pattern:
        return [0] * L
    return [window if pattern[i % len(pattern)] else 0 for i in range(L)]


def attended_tokens(m: dict, contexts) -> float:
    """Context tokens one decode step attends, summed over the rows and
    AVERAGED over the layers: each layer attends ``min(context, its
    window)`` of each row. Without a window layer this is the sum of the
    contexts, exactly."""
    windows = layer_windows(m)
    if not any(windows):
        return sum(contexts)
    return sum(sum(min(c, w) if w else c for c in contexts)
               for w in windows) / len(windows)


def attended_in_prefill(m: dict, tokens: int, context: int) -> float:
    """Sum over a chunk's ``tokens`` new tokens of the context tokens
    each attends (causal: ``context`` cached ones plus half the chunk),
    averaged over the layers. Without a window layer:
    tokens x (context + tokens / 2), exactly."""
    def one(w):
        if not w or context + tokens <= w:
            return tokens * (context + tokens / 2)
        if context >= w:
            return tokens * w
        grow = w - context           # tokens whose context still grows
        return grow * (context + grow / 2) + (tokens - grow) * w
    windows = layer_windows(m)
    if not any(windows):
        return one(0)
    return sum(one(w) for w in windows) / len(windows)


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
    sequences that attend ``kv_tokens`` context tokens: the sum of their
    contexts, or with window layers ``attended_tokens`` of them (a mean
    over time of either is as good: every term is linear in it).

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
        + L * 4 * H * hd * attended_in_prefill(m, tokens, context)
    bytes_ = L * (attn_b + mlp_b) + _wbytes(D, V, quant) \
        + (attended_tokens(m, [context]) + tokens) * kv_b
    return {"bytes": bytes_, "flops": flops}


STAGES = ("attn", "mlp", "tail")


def decode_stage(m: dict, quant: str, stage: str, rows: float,
                 kv_tokens: float, kv_dtype_bytes: int = 2) -> dict:
    """Operations and bytes ONE named stage of a decode step needs, with
    ``rows`` and ``kv_tokens`` as ``decode_step`` takes them. The stages
    are the program's scopes (``readers/device_scope.py``): ``attn`` =
    the KV cache of the live contexts once plus one new row a sequence
    (the projections are a stage of their own, not counted here);
    ``mlp`` = the MLP's weights, or the router and the touched experts'
    weights; ``tail`` = the ``lm_head`` as stored, once. The parts do
    not add up to the step: projections, norms and the embedding are in
    none of them."""
    s = layer_shapes(m)
    L, D, V = m["num_layers"], m["hidden_size"], m["vocab_size"]
    H, hd = m["num_heads"], m["head_dim"]
    if stage == "attn":
        kv_b = kv_bytes_per_token(m, kv_dtype_bytes)
        return {"bytes": (kv_tokens + rows) * kv_b,
                "flops": L * 4 * H * hd * kv_tokens}
    if stage == "mlp":
        if s["experts"]:
            k = m.get("num_experts_per_tok", 2)
            one = sum(r * c for r, c in s["mlp"])
            touched = expected_experts_touched(s["experts"], k, rows)
            return {"bytes": L * (touched * 2 * one + 2 * D * s["experts"]),
                    "flops": rows * L * (k * 2 * one + 2 * D * s["experts"])}
        return {"bytes": L * sum(_wbytes(r, c, quant) for r, c in s["mlp"]),
                "flops": rows * L * sum(2 * r * c for r, c in s["mlp"])}
    if stage == "tail":
        head_b = (_wbytes(D, V, quant)
                  if not m.get("tie_word_embeddings", False) else 2 * D * V)
        return {"bytes": head_b, "flops": rows * 2 * D * V}
    raise ValueError(f"no cost for stage {stage!r}; known: {STAGES}")


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
