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
