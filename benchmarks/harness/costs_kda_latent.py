"""Operations and bytes a step NEEDS of a model whose recurrent layers
decay a CHANNEL of a head's keys at its own rate, beside LATENT
attention layers, behind leading dense layers, under a router LIMITED TO
GROUPS of which this chip holds a share — from the ``model`` group of a
configuration file alone, never read from the program.
``costs_recurrent.py`` counts per-head K and V rows on the attention
layers, one decay a head and every layer an expert layer;
``costs_latent.py`` a latent row on every layer: each wrong for such a
model. Named for what they are and for no model, the keys read beside
theirs:

    full_attention_interval, num_dense_layers   layer i (the model's
                            index) is a latent layer when (i + 1) % the
                            interval == 0, else recurrent; its MLP is
                            dense when i < num_dense_layers
    linear_decay "channel"  a recurrent layer has q, k and v as ONE
                            projection, a decay projection and an output
                            gate as wide as the heads (all stored
                            quantised), a write-strength column a head
                            and the convolution's taps (bf16); a token's
                            log-decay is heads x key width float32
    q_lora_rank 0           a latent layer's queries are ONE matrix
    attn_gate "head"        and its gate a column a head (bf16)
    n_group, topk_group     the router's limit (below)

A decode step reads every mixer matrix as stored; reads AND writes the
live rows' state and tail on every recurrent layer; reads the latent
rows of the live contexts on the latent layers only; the dense layers'
MLP, each expert layer's router, shared expert and the HELD experts its
rows are expected to touch; the tail. It computes two operations a
weight a row, 7 x key width x value width a head a row for the
recurrence (a row's decay, the state's prediction, its correction, the
read-out: one more than under a decay a head, where the decay is a
scalar's) and the absorbed attention's operations a cached token a
latent layer.

UNDER THE GROUP LIMIT a token's ``num_experts_per_tok`` lie in
``topk_group`` of ``n_group`` groups. That concentrates a ROW's choices
— on a chip that holds one group of 8 with 4 kept, half the rows send
nothing and the other half about two assignments each — and leaves an
EXPERT's chance of being chosen by a row what it is without the limit
under a balanced router: (topk_group / n_group) x k / (topk_group x
group size) = k / num_experts. So the held experts a step's rows are
expected to touch, and the assignments that fall on them, are
``costs_latent.py``'s; what the limit adds is ``groups_held_share``, the
share of rows that may send this chip anything at all.
"""

from __future__ import annotations

import math

from benchmarks.harness import costs, costs_latent
from benchmarks.harness.costs import _wbytes
# what does not depend on the kind of decay or of attention layer:
# (attention layers, recurrent layers) — here the attention layers are
# latent —, the convolution's channels, a layer's state, a slot's bytes
from benchmarks.harness.costs_recurrent import (
    ACT_BYTES, conv_channels, layer_counts, slot_bytes, state_values)

STEP_OPS = 7        # operations a state value a token (module docstring)


def kv_bytes_per_token(m: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes a cached token takes: a latent row on the LATENT layers
    only."""
    return (layer_counts(m)[0] * costs_latent.kv_values_per_token(m)
            * kv_dtype_bytes)


def recurrent_matrices(m: dict) -> tuple:
    """A recurrent layer's matrices: ``(quantised, bf16)`` lists of
    (rows, cols) — q, k and v; the decay's projection; the output gate;
    the out-projection; then the write strength and the taps."""
    D, H = m["hidden_size"], m["linear_num_value_heads"]
    keys, wide = H * m["linear_key_head_dim"], H * m["linear_value_head_dim"]
    return ([(D, conv_channels(m)), (D, keys), (D, wide), (wide, D)],
            [(D, H), (conv_channels(m), m["linear_conv_kernel_dim"])])


def latent_matrices(m: dict) -> tuple:
    """A latent layer's: ``(quantised, bf16)`` — the one query matrix,
    the latent's down-projection, its up-projection's two stored halves
    and the output; then the gate a head."""
    D, H = m["hidden_size"], m["num_heads"]
    R, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    nope, vd = m["qk_nope_head_dim"], m["v_head_dim"]
    gate = [(D, H)] if m.get("attn_gate") else []
    return ([(D, H * (nope + rope)), (D, R + rope), (R, H * nope),
             (R, H * vd), (H * vd, D)], gate)


def mixer_weights(m: dict, quant: str) -> dict:
    """Stored bytes and operations a row of every layer's mixer
    matrices."""
    out = {"bytes": 0, "flops": 0}
    for n, (q, raw) in zip(layer_counts(m), (latent_matrices(m),
                                             recurrent_matrices(m))):
        out["bytes"] += n * (sum(_wbytes(r, c, quant) for r, c in q)
                             + sum(2 * r * c for r, c in raw))
        out["flops"] += n * sum(2 * r * c for r, c in q + raw)
    return out


def groups_held_share(m: dict) -> float:
    """The share of rows whose kept groups include one this chip holds
    experts of, under a balanced router: 1 - C(G - g, t) / C(G, t) for
    ``g`` of ``G`` groups held and ``t`` kept."""
    G, t = m.get("n_group", 1), m.get("topk_group", 1)
    size = m["num_experts"] // G
    first, held = m.get("experts_first", 0), costs_latent.held_experts(m)
    g = (first + held - 1) // size - first // size + 1
    return 1.0 - math.comb(G - g, t) / math.comb(G, t)


def held_assignments(m: dict, rows: float) -> float:
    """Assignments ``rows`` rows are expected to make to held experts."""
    return (rows * m["num_experts_per_tok"] * costs_latent.held_experts(m)
            / m["num_experts"])


def state_step(m: dict, rows: float) -> dict:
    """The recurrence of ONE decode step over ``rows`` live sequences:
    each recurrent layer reads and writes a row's state and tail."""
    return {"bytes": rows * 2 * slot_bytes(m),
            "flops": rows * layer_counts(m)[1] * STEP_OPS * state_values(m)}


def state_chunks(m: dict, tokens: float, rows: float) -> dict:
    """The recurrence of chunk programs that hold ``tokens`` tokens in
    ``rows`` rows (a row a prompt a program): a token's q, k, v in and
    its output out as activations, its decay (heads x key width) and
    write strength in float32, a row's state and tail read and written
    once a program."""
    Lg, H = layer_counts(m)[1], m["linear_num_value_heads"]
    token = (conv_channels(m) + H * m["linear_value_head_dim"]) * ACT_BYTES \
        + (H * m["linear_key_head_dim"] + H) * 4
    return {"bytes": Lg * tokens * token + rows * 2 * slot_bytes(m),
            "flops": tokens * Lg * STEP_OPS * state_values(m)}


def latent_rows(m: dict, rows: float, kv_tokens: float,
                kv_dtype_bytes: int = 2) -> dict:
    """The latent layers' cached rows of the live contexts, once, and a
    new row a sequence; the absorbed attention's operations."""
    Lf, H = layer_counts(m)[0], m["num_heads"]
    n = costs_latent.kv_values_per_token(m)
    return {"bytes": (kv_tokens + rows) * kv_bytes_per_token(
                m, kv_dtype_bytes),
            "flops": Lf * 2 * (n + m["kv_lora_rank"]) * H * kv_tokens}


def decode_step(m: dict, quant: str, rows: float, kv_tokens: float,
                kv_dtype_bytes: int = 2) -> dict:
    """As ``costs.decode_step``, for one decode step of ``rows``
    sequences that attend ``kv_tokens`` cached tokens on the latent
    layers."""
    mix = mixer_weights(m, quant)
    mlp = costs_latent.mlp_stage(m, quant, rows)    # dense, then experts
    tail = costs.decode_stage(m, quant, "tail", rows, kv_tokens)
    state = state_step(m, rows)
    kv = latent_rows(m, rows, kv_tokens, kv_dtype_bytes)
    weight_bytes = mix["bytes"] + mlp["bytes"] + tail["bytes"] \
        + rows * 2 * m["hidden_size"]
    return {"weight_bytes": weight_bytes, "kv_bytes": kv["bytes"],
            "state_bytes": state["bytes"],
            "bytes": weight_bytes + kv["bytes"] + state["bytes"],
            "flops": rows * mix["flops"] + mlp["flops"] + tail["flops"]
            + state["flops"] + kv["flops"]}
