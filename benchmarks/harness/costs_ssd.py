"""Operations and bytes a step NEEDS of a model whose recurrent layers
are STATE-SPACE layers (Mamba-2: a scalar decay a head, no delta rule)
beside attention layers, every block with a DENSE MLP and the head tied
to the embedding — from the ``model`` group of a configuration file
alone, never read from the program. ``costs_recurrent.py`` counts a
delta rule (6 x key width x value width a head), the attention layer at
its period's end and routed experts: all three wrong here. Named for
what they are and for no model, the keys read beside ``costs.py``'s:

    full_attention_interval, full_attention_place
                            layer i attends when i % interval == place
                            (-1: the period's last); the others are
                            state-space layers
    linear_num_value_heads (H), linear_value_head_dim (P),
    linear_key_head_dim (N), linear_num_key_heads (G),
    linear_conv_kernel_dim (K)
                            a state-space layer: its state is H x P x N
                            in float32, its convolution runs over H P +
                            2 G N channels (x, and ONE B and C a group)
                            and keeps a tail of K - 1 inputs
    intermediate_size       the block's dense gated MLP
    tie_word_embeddings     the head is the bf16 embedding

A decode step reads every matrix as stored (int8: ``[z | xBC]`` in, out,
the attention's four, the MLP's three; bf16: the step's projection of H
columns, the taps and their bias, the tied head); reads AND writes the
live rows' state and tail on every state-space layer; reads the
attention layers' K and V rows of the live contexts; and computes two
operations a weight a row, 6 x P x N a head a row for the recurrence
(decay, the rank-one write, the read-out: a multiply and an add each)
and the attention's 4 x head width x heads a cached token. What it does
not count: norm weights, the embedding rows' source, the per-head A, D
and bias vectors, B and C themselves (2 G N values a row).
"""

from __future__ import annotations

from benchmarks.harness import costs
from benchmarks.harness.costs import _wbytes

ACT_BYTES = 2
STATE_BYTES = 4     # the state is float32, whatever the activations are


def attention_layers(m: dict) -> list:
    n = m["full_attention_interval"]
    at = m.get("full_attention_place", -1) % n
    return [i for i in range(m["num_layers"]) if i % n == at]


def layer_counts(m: dict) -> tuple:
    """(attention layers, state-space layers)."""
    Lf = len(attention_layers(m))
    return Lf, m["num_layers"] - Lf


def inner_width(m: dict) -> int:
    return m["linear_num_value_heads"] * m["linear_value_head_dim"]


def conv_channels(m: dict) -> int:
    return inner_width(m) + 2 * m["linear_num_key_heads"] \
        * m["linear_key_head_dim"]


def state_values(m: dict) -> int:
    """Values of ONE sequence's state on ONE state-space layer."""
    return inner_width(m) * m["linear_key_head_dim"]


def slot_bytes(m: dict, act_bytes: int = ACT_BYTES) -> int:
    """Bytes a sequence costs whatever its length, all state-space
    layers: state and convolution tail."""
    tail = (m["linear_conv_kernel_dim"] - 1) * conv_channels(m)
    return layer_counts(m)[1] * (state_values(m) * STATE_BYTES
                                 + tail * act_bytes)


def kv_bytes_per_token(m: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes a cached token takes: K and V rows on the ATTENTION layers
    only."""
    return (layer_counts(m)[0] * m["num_kv_heads"] * m["head_dim"]
            * 2 * kv_dtype_bytes)


def ssd_matrices(m: dict) -> tuple:
    """A state-space layer's matrices: ``(quantised, bf16)`` lists of
    (rows, cols) — ``[z | xBC]`` in and the out-projection; the step's
    projection, the taps and their bias."""
    D, H = m["hidden_size"], m["linear_num_value_heads"]
    Ch = conv_channels(m)
    return ([(D, inner_width(m) + Ch), (inner_width(m), D)],
            [(D, H), (Ch, m["linear_conv_kernel_dim"]), (Ch, 1)])


def attention_matrices(m: dict) -> list:
    D, H = m["hidden_size"], m["num_heads"]
    KV, hd = m["num_kv_heads"], m["head_dim"]
    return [(D, H * hd), (D, KV * hd), (D, KV * hd), (H * hd, D)]


def mlp_matrices(m: dict) -> list:
    D, F = m["hidden_size"], m["intermediate_size"]
    return [(D, F), (D, F), (F, D)]


def layer_weights(m: dict, quant: str) -> dict:
    """Stored bytes and operations a row of every layer's matrices:
    both mixers' and the dense MLP's."""
    Lf, Lg = layer_counts(m)
    q, raw = ssd_matrices(m)
    # the taps are no matmul: a multiply and an add a tap a channel
    matmuls = q + raw[:1]
    taps = 2 * conv_channels(m) * m["linear_conv_kernel_dim"]
    attn, mlp = attention_matrices(m), mlp_matrices(m)
    return {
        "bytes": Lg * (sum(_wbytes(r, c, quant) for r, c in q)
                       + sum(2 * r * c for r, c in raw))
        + Lf * sum(_wbytes(r, c, quant) for r, c in attn)
        + (Lf + Lg) * sum(_wbytes(r, c, quant) for r, c in mlp),
        "flops": Lg * (sum(2 * r * c for r, c in matmuls) + taps)
        + Lf * sum(2 * r * c for r, c in attn)
        + (Lf + Lg) * sum(2 * r * c for r, c in mlp)}


def weight_bytes_resident(m: dict, quant: str) -> int:
    """Every stored matrix and the bf16 embedding (the tied head is the
    embedding, counted once)."""
    head = 0 if m.get("tie_word_embeddings") else _wbytes(
        m["hidden_size"], m["vocab_size"], quant)
    return layer_weights(m, quant)["bytes"] \
        + 2 * m["vocab_size"] * m["hidden_size"] + head


def state_step(m: dict, rows: float) -> dict:
    """The recurrence of ONE decode step over ``rows`` live sequences:
    each state-space layer reads and writes a row's state and tail."""
    Lg = layer_counts(m)[1]
    return {"bytes": rows * 2 * slot_bytes(m),
            "flops": rows * Lg * 6 * state_values(m)}


def state_chunks(m: dict, tokens: float, rows: float) -> dict:
    """The recurrence of chunk programs that hold ``tokens`` tokens in
    ``rows`` rows (a row a prompt a program): a token's x, B, C in and
    its output out as activations, its step size in float32, a row's
    state and tail read and written once a program; 6 x P x N
    operations a head a token."""
    Lg, H = layer_counts(m)[1], m["linear_num_value_heads"]
    token = (conv_channels(m) + inner_width(m)) * ACT_BYTES + H * 4
    return {"bytes": Lg * tokens * token + rows * 2 * slot_bytes(m),
            "flops": tokens * Lg * 6 * state_values(m)}


def decode_step(m: dict, quant: str, rows: float, kv_tokens: float,
                kv_dtype_bytes: int = 2) -> dict:
    """As ``costs.decode_step``, for one decode step of ``rows``
    sequences that attend ``kv_tokens`` cached tokens on the attention
    layers."""
    Lf = layer_counts(m)[0]
    w = layer_weights(m, quant)
    tail = costs.decode_stage(m, quant, "tail", rows, kv_tokens)
    state = state_step(m, rows)
    kv_b = kv_bytes_per_token(m, kv_dtype_bytes)
    weight_bytes = w["bytes"] + tail["bytes"] + rows * 2 * m["hidden_size"]
    kv_bytes = (kv_tokens + rows) * kv_b
    return {"weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
            "state_bytes": state["bytes"],
            "bytes": weight_bytes + kv_bytes + state["bytes"],
            "flops": rows * w["flops"] + tail["flops"] + state["flops"]
            + Lf * 4 * m["num_heads"] * m["head_dim"] * kv_tokens}
