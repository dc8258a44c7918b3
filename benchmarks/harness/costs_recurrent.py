"""Operations and bytes a step NEEDS of a model whose layers are not all
attention — RECURRENT layers (a state a sequence) beside attention
layers (rows a token), routed experts of which this chip may hold a
share — from the ``model`` group of a configuration file alone, never
read from the program. ``costs.py`` counts K and V rows on every layer
and every expert the file names: both wrong for such a model. Named for
what they are and for no model, the keys read beside ``costs.py``'s:

    full_attention_interval   layer i attends when (i + 1) % it == 0;
                            the others are recurrent
    linear_num_key_heads, linear_num_value_heads, linear_key_head_dim,
    linear_value_head_dim, linear_conv_kernel_dim
                            a recurrent layer: its state is value heads
                            x key width x value width in float32, its
                            convolution's tail (taps - 1) x (q, k, v
                            channels)
    attn_gate               an attention layer's gate matrix, as wide
                            as its queries
    experts_held, moe_intermediate_size, num_shared_experts,
    shared_expert_gate      as ``costs_latent.py`` reads them; the gate
                            is one hidden-wide vector a layer

A decode step reads every mixer matrix as stored (int8: the two wide
recurrent projections, the attention's five, the shared expert, the
head; bf16: the 2 x value-heads-wide decay / write-strength projection,
the convolution's taps, the router, the touched held experts); reads AND
writes the live rows' state and tail on every recurrent layer; reads the
attention layers' K and V rows of the live contexts; and computes two
operations a weight a row, 6 x key width x value width a value head a
row for the recurrence (the state's prediction, its correction, the
read-out) and the attention's 4 x head width x heads a cached token.
What it does not count: norm weights, the embedding rows' source, the
per-head decay and bias vectors.
"""

from __future__ import annotations

from benchmarks.harness import costs, costs_latent
from benchmarks.harness.costs import _wbytes

ACT_BYTES = 2
STATE_BYTES = 4     # the state is float32, whatever the activations are


def layer_counts(m: dict) -> tuple:
    """(attention layers, recurrent layers)."""
    L, n = m["num_layers"], m["full_attention_interval"]
    return L // n, L - L // n


def conv_channels(m: dict) -> int:
    return (2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
            + m["linear_num_value_heads"] * m["linear_value_head_dim"])


def state_values(m: dict) -> int:
    """Values of ONE sequence's state on ONE recurrent layer."""
    return (m["linear_num_value_heads"] * m["linear_key_head_dim"]
            * m["linear_value_head_dim"])


def slot_bytes(m: dict, act_bytes: int = ACT_BYTES) -> int:
    """Bytes a sequence costs whatever its length, all recurrent
    layers: state and convolution tail."""
    tail = (m["linear_conv_kernel_dim"] - 1) * conv_channels(m)
    return layer_counts(m)[1] * (state_values(m) * STATE_BYTES
                                 + tail * act_bytes)


def kv_bytes_per_token(m: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes a cached token takes: K and V rows on the ATTENTION layers
    only."""
    return (layer_counts(m)[0] * m["num_kv_heads"] * m["head_dim"]
            * 2 * kv_dtype_bytes)


def recurrent_matrices(m: dict) -> tuple:
    """A recurrent layer's matrices: ``(quantised, bf16)`` lists of
    (rows, cols) — the in-projection of q, k, v and the output gate and
    the out-projection; the decay / write-strength projection and the
    convolution's taps."""
    D, Hv = m["hidden_size"], m["linear_num_value_heads"]
    wide = Hv * m["linear_value_head_dim"]
    return ([(D, conv_channels(m) + wide), (wide, D)],
            [(D, 2 * Hv), (conv_channels(m), m["linear_conv_kernel_dim"])])


def attention_matrices(m: dict) -> list:
    D, H = m["hidden_size"], m["num_heads"]
    KV, hd = m["num_kv_heads"], m["head_dim"]
    gate = [(D, H * hd)] if m.get("attn_gate") else []
    return [(D, H * hd), (D, KV * hd), (D, KV * hd), (H * hd, D)] + gate


def mixer_weights(m: dict, quant: str) -> dict:
    """Stored bytes and operations a row of every layer's mixer
    matrices."""
    Lf, Lg = layer_counts(m)
    q, raw = recurrent_matrices(m)
    attn = attention_matrices(m)
    return {
        "bytes": Lg * (sum(_wbytes(r, c, quant) for r, c in q)
                       + sum(2 * r * c for r, c in raw))
        + Lf * sum(_wbytes(r, c, quant) for r, c in attn),
        "flops": Lg * sum(2 * r * c for r, c in q + raw)
        + Lf * sum(2 * r * c for r, c in attn)}


def experts_stage(m: dict, quant: str, rows: float) -> dict:
    """Every layer's router, the HELD experts its rows are expected to
    touch (bf16), the shared expert as stored and its gate vector."""
    D, L = m["hidden_size"], m["num_layers"]
    E, k = m["num_experts"], m.get("num_experts_per_tok", 2)
    Fe = m.get("moe_intermediate_size") or m["intermediate_size"]
    one = 3 * D * Fe
    shared = [(D, Fe * m.get("num_shared_experts", 0))] * 2 \
        + [(Fe * m.get("num_shared_experts", 0), D)] \
        if m.get("num_shared_experts") else []
    gate = 2 * D if m.get("shared_expert_gate") else 0
    held = costs_latent.held_experts(m)
    return {
        "bytes": L * (costs_latent.expected_held_touched(m, rows) * 2 * one
                      + 2 * D * E + gate
                      + sum(_wbytes(r, c, quant) for r, c in shared)),
        "flops": rows * L * (k * held / E * 2 * one + 2 * D * E + gate
                             + sum(2 * r * c for r, c in shared))}


def state_step(m: dict, rows: float) -> dict:
    """The recurrence of ONE decode step over ``rows`` live sequences:
    each recurrent layer reads and writes a row's state and tail."""
    Lg = layer_counts(m)[1]
    return {"bytes": rows * 2 * slot_bytes(m),
            "flops": rows * Lg * 6 * state_values(m)}


def state_chunks(m: dict, tokens: float, rows: float) -> dict:
    """The recurrence of chunk programs that hold ``tokens`` tokens in
    ``rows`` rows (a row a prompt a program): a token's q, k, v in and
    its output out as activations, its decay and write strength in
    float32, a row's state and tail read and written once a program;
    6 x key width x value width operations a value head a token."""
    Lg, Hv = layer_counts(m)[1], m["linear_num_value_heads"]
    token = (conv_channels(m) + Hv * m["linear_value_head_dim"]) * ACT_BYTES \
        + 2 * Hv * 4
    return {"bytes": Lg * tokens * token + rows * 2 * slot_bytes(m),
            "flops": tokens * Lg * 6 * state_values(m)}


def decode_step(m: dict, quant: str, rows: float, kv_tokens: float,
                kv_dtype_bytes: int = 2) -> dict:
    """As ``costs.decode_step``, for one decode step of ``rows``
    sequences that attend ``kv_tokens`` cached tokens on the attention
    layers."""
    Lf = layer_counts(m)[0]
    mix, exp = mixer_weights(m, quant), experts_stage(m, quant, rows)
    tail = costs.decode_stage(m, quant, "tail", rows, kv_tokens)
    state = state_step(m, rows)
    kv_b = kv_bytes_per_token(m, kv_dtype_bytes)
    weight_bytes = mix["bytes"] + exp["bytes"] + tail["bytes"] \
        + rows * 2 * m["hidden_size"]
    kv_bytes = (kv_tokens + rows) * kv_b
    return {"weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
            "state_bytes": state["bytes"],
            "bytes": weight_bytes + kv_bytes + state["bytes"],
            "flops": rows * mix["flops"] + exp["flops"] + tail["flops"]
            + state["flops"]
            + Lf * 4 * m["num_heads"] * m["head_dim"] * kv_tokens}
