"""Bytes-and-operations functions against hand sums, both configurations."""

import json
import os

import pytest

from benchmarks.harness import costs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


NEMO = config("nemotron-8b-chat")
MIX = config("mixtral-8x7b-instruct")
V5E = costs.peaks("TPU v5 lite")


def test_peaks_table_and_unknown_kind():
    assert V5E["hbm_bytes_per_s"] == 819e9 and V5E["bf16_flops"] == 197e12
    assert V5E["int8_ops"] == 393e12 and "source" in V5E
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")


def test_nemotron_decode_step_by_hand():
    D, F, V, L = 4096, 16384, 256000, 32
    attn = 4 * D * D                       # wq wk wv wo, MHA
    mlp = 2 * D * F                        # squared-ReLU: up and down
    scales = 4 * (4 * D + F + D)           # f32 per output channel
    weight_bytes = L * (attn + mlp + scales) + (D * V + 4 * V) + 12 * 2 * D
    kv_tok = L * 32 * 128 * 2 * 2          # 512 KiB a token
    assert costs.kv_bytes_per_token(NEMO["model"]) == kv_tok == 524288
    c = costs.decode_step(NEMO["model"], "int8", rows=12, kv_tokens=5400)
    assert c["weight_bytes"] == weight_bytes
    assert c["kv_bytes"] == 5400 * kv_tok + 12 * kv_tok
    flops = 12 * (L * 2 * (attn + mlp) + 2 * D * V) \
        + L * 4 * 32 * 128 * 5400
    assert c["flops"] == flops
    least = costs.least_seconds(c, V5E)
    assert least["bound"] == "bytes"
    # 7.49 GB of weights alone are 9.1 ms at 819 GB/s
    assert 7.45e9 < weight_bytes < 7.55e9
    assert least["seconds"] == pytest.approx(
        (weight_bytes + c["kv_bytes"]) / 819e9)


def test_nemotron_resident_bytes_by_hand():
    got = costs.weight_bytes_resident(NEMO["model"], "int8")
    # 6.44 GB layers + 1.05 GB lm_head + 2.10 GB bf16 embedding
    assert 9.55e9 < got < 9.65e9


def test_mixtral_decode_step_by_hand():
    m = MIX["model"]
    D, F, V, L, E = 4096, 14336, 32000, m["num_layers"], 8
    attn_shapes = [(D, 4096), (D, 1024), (D, 1024), (4096, D)]  # GQA 32/8
    attn_b = sum(r * c + 4 * c for r, c in attn_shapes)
    attn_f = sum(2 * r * c for r, c in attn_shapes)
    expert = 3 * D * F
    touched = E * (1 - (1 - 2 / E) ** 16)
    assert costs.expected_experts_touched(8, 2, 16) == pytest.approx(touched)
    assert 7.9 < touched < 8.0
    c = costs.decode_step(m, "int8", rows=16, kv_tokens=16 * 400)
    want_w = L * (attn_b + touched * 2 * expert + 2 * D * E) \
        + (D * V + 4 * V) + 16 * 2 * D
    assert c["weight_bytes"] == pytest.approx(want_w)
    want_f = 16 * (L * (attn_f + 2 * 2 * expert + 2 * D * E) + 2 * D * V) \
        + L * 4 * 32 * 128 * 6400
    assert c["flops"] == pytest.approx(want_f)
    assert costs.kv_bytes_per_token(m) == L * 8 * 128 * 2 * 2
    # expert weights are ~97 % of what a step streams
    assert L * touched * 2 * expert / c["bytes"] > 0.95
    assert costs.least_seconds(c, V5E)["bound"] == "bytes"


def test_mixtral_resident_bytes_by_hand():
    m = MIX["model"]
    got = costs.weight_bytes_resident(m, "int8")
    experts = m["num_layers"] * 8 * 3 * 4096 * 14336 * 2
    assert experts < got < experts + 0.7e9
    # the compile rehearsal's own figure for the weights program's outputs
    assert got == pytest.approx(11_835_908_608, rel=0.01)


def test_one_lone_row_reaches_two_experts():
    assert costs.expected_experts_touched(8, 2, 1) == pytest.approx(2.0)
    assert costs.expected_experts_touched(0, 2, 16) == 0.0


def test_prefill_is_compute_bound_at_length():
    c = costs.prefill_tokens(NEMO["model"], "int8", tokens=1536)
    least = costs.least_seconds(c, V5E)
    assert least["bound"] == "flops"
    assert 20e12 < c["flops"] < 30e12          # ~23 TFLOP, ~120 ms at peak
    short = costs.prefill_tokens(NEMO["model"], "int8", tokens=16)
    assert costs.least_seconds(short, V5E)["bound"] == "bytes"


def test_unknown_quant_raises():
    with pytest.raises(ValueError):
        costs.decode_step(NEMO["model"], "fp4", 1, 1)


# ------------------------------------------- layers that attend a window

# What the two configuration files read at PR 26 (the parent of the PR
# that made the counts follow each layer's window), to the byte:
# decode_step(model, "int8", rows=11.5, kv_tokens=5432.25) and
# prefill_tokens(model, "int8", tokens=512, context=1024).
PINNED = {
    "nemotron-8b-chat": (
        {"weight_bytes": 7496863744.0, "kv_bytes": 2854092800.0,
         "bytes": 10350956544.0, "flops": 175141683200.0},
        {"bytes": 8302075904, "flops": 6942764302336.0}, 524288),
    "mixtral-8x7b-instruct": (
        {"weight_bytes": 11161405203.675106, "kv_bytes": 89190400.0,
         "bytes": 11250595603.675106, "flops": 39646019584.0},
        {"bytes": 11598590976, "flops": 1658119520256.0}, 16384),
}


@pytest.mark.parametrize("name", sorted(PINNED))
@pytest.mark.parametrize("what", ["decode_step", "prefill_tokens",
                                  "kv_bytes_per_token"])
def test_files_without_window_keys_read_what_they_read_before(name, what):
    cfg = config(name)
    m = cfg["model"]
    assert "sliding_window" not in m and "window_layers" not in m
    decode, prefill, kv = PINNED[name]
    if what == "decode_step":
        assert costs.decode_step(m, "int8", 11.5, 5432.25) == decode
        # the rows one by one add up to the same tokens, exactly
        assert costs.attended_tokens(m, [5000.0, 432.25]) == 5432.25
    elif what == "prefill_tokens":
        assert costs.prefill_tokens(m, "int8", 512, 1024) == prefill
    else:
        assert costs.kv_bytes_per_token(m) == kv


def windowed(**kw):
    return {**NEMO["model"], "num_layers": 8, "sliding_window": 4096,
            "window_layers": [0, 1, 1, 1], **kw}


def test_window_layers_repeat_their_period_over_the_layers():
    assert costs.layer_windows(windowed()) == [0, 4096, 4096, 4096] * 2
    assert costs.layer_windows(windowed(window_layers=[1])) == [4096] * 8
    for none in (dict(sliding_window=0), dict(window_layers=[]),
                 dict(window_layers=None), dict(window_layers=[0, 0])):
        assert not any(costs.layer_windows(windowed(**none)))


def test_a_window_layer_reads_min_context_window_a_row():
    """Rows of 2048 and 8192 tokens under [0, 1, 1, 1] with a window of
    4096: a period reads (2048 + 8192) + 3 x (2048 + 4096) context
    tokens of KV — each row against the window, never their sum."""
    m = windowed()
    period = (2048 + 8192) + 3 * (2048 + 4096)
    assert costs.attended_tokens(m, [2048, 8192]) * 4 == period
    assert costs.attended_tokens(m, [2048, 8192]) * 4 != \
        (2048 + 8192) + 3 * min(2048 + 8192, 4096)
    c = costs.decode_step(m, "int8", 2, costs.attended_tokens(m, [2048, 8192]))
    per_layer = 32 * 128 * 2 * 2                      # KV heads x hd x k,v x bf16
    assert c["kv_bytes"] == 2 * period * per_layer + 2 * 8 * per_layer
    full = costs.decode_step(dict(m, sliding_window=0), "int8", 2,
                             2048 + 8192)
    assert c["weight_bytes"] == full["weight_bytes"]
    assert c["kv_bytes"] < full["kv_bytes"] and c["flops"] < full["flops"]
    assert full["flops"] - c["flops"] == 6 * 4 * 32 * 128 * (8192 - 4096)


@pytest.mark.parametrize("tokens,context,want", [
    (512, 1024, 512 * (1024 + 256)),            # all inside the window
    (512, 8192, 512 * 4096),                    # all past it
    (1024, 3584, 512 * (3584 + 256) + 512 * 4096),    # crossing it
])
def test_a_chunk_attends_its_window_in_prefill(tokens, context, want):
    m = windowed(window_layers=[1])
    assert costs.attended_in_prefill(m, tokens, context) == want
    full = dict(m, sliding_window=0)
    assert costs.attended_in_prefill(full, tokens, context) == \
        tokens * (context + tokens / 2)
    c = costs.prefill_tokens(m, "int8", tokens, context)
    assert c["flops"] <= costs.prefill_tokens(full, "int8", tokens,
                                              context)["flops"]


@pytest.mark.parametrize("cfg", [NEMO, MIX], ids=["nemotron", "mixtral"])
def test_stage_costs_are_parts_of_the_step(cfg):
    m, rows, kv = cfg["model"], 12.0, 6000.0
    step = costs.decode_step(m, "int8", rows, kv)
    parts = {s: costs.decode_stage(m, "int8", s, rows, kv)
             for s in costs.STAGES}
    assert parts["attn"]["bytes"] == step["kv_bytes"]
    assert sum(p["bytes"] for p in parts.values()) < step["bytes"]
    assert sum(p["flops"] for p in parts.values()) < step["flops"]
    # what is in no stage: the attention projections and the new rows
    s = costs.layer_shapes(m)
    proj_b = m["num_layers"] * sum(costs._wbytes(r, c, "int8")
                                   for r, c in s["attn"])
    assert step["bytes"] - sum(p["bytes"] for p in parts.values()) == \
        pytest.approx(proj_b + rows * 2 * m["hidden_size"])
    with pytest.raises(ValueError, match="no cost for stage"):
        costs.decode_stage(m, "int8", "embed", rows, kv)


def test_nemotron_tail_is_the_lm_head_as_stored():
    t = costs.decode_stage(NEMO["model"], "int8", "tail", 6, 3000)
    assert t["bytes"] == 4096 * 256000 + 4 * 256000
    assert costs.least_seconds(t, V5E)["bound"] == "bytes"
    assert costs.least_seconds(t, V5E)["seconds"] == pytest.approx(
        1.28e-3, rel=0.01)           # PERF.md section 5: 1.28 of 5.83 ms
