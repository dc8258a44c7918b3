"""The layer-by-layer count: a model whose layers are not all alike by
hand; equal to ``costs.py`` on every configuration it can count; a
stage is a part of the step."""

import json
import os

import pytest

from benchmarks.harness import costs, costs_layerwise as lw

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
UNIFORM = ["nemotron-8b-chat", "mixtral-8x7b-instruct",
           "smallthinker-21b-a3b-instruct"]


def config(name):
    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


TRINITY = config("trinity-mini")
V5E = costs.peaks("TPU v5 lite")
OCCUPANCY = [(1, 100.0), (3.8, 5700.5), (14.29, 56400.0), (16, 131072)]


def test_trinity_layers_are_one_dense_and_seven_with_experts():
    groups = lw.layer_groups(TRINITY["model"])
    assert [n for n, _ in groups] == [1, 7]
    dense, sparse = groups[0][1], groups[1][1]
    assert dense["experts"] == 0 and sparse["experts"] == 128
    assert dense["mlp"] == [(2048, 6144), (2048, 6144), (6144, 2048)]
    assert sparse["expert"] == [(2048, 1024), (2048, 1024), (1024, 2048)]
    assert sparse["mlp"] == sparse["expert"]            # one shared expert
    assert len(dense["attn"]) == 5 and dense["attn"] == sparse["attn"]
    assert sparse["router_floats"] == 128


def test_trinity_decode_step_by_hand():
    m, rows, kv = TRINITY["model"], 14.3, 31000.0
    D, V, E, k = 2048, 200192, 128, 8
    # attention, int8 + a float32 scale an output channel: wq and the
    # gate (D x 4096), wk and wv (D x 512), wo (4096 x D)
    attn = 2 * (D * 4096 + 4 * 4096) + 2 * (D * 512 + 4 * 512) \
        + (4096 * D + 4 * D)
    assert attn == 27_308_032                   # the issue's 27.3M weights
    dense_mlp = 2 * (D * 6144 + 4 * 6144) + (6144 * D + 4 * D)
    shared = 2 * (D * 1024 + 4 * 1024) + (1024 * D + 4 * D)
    one_expert = 3 * D * 1024                   # weights; bf16: 2 bytes
    touched = E * (1 - (1 - k / E) ** rows)
    assert touched == pytest.approx(77.0, abs=0.5)
    expert_layer = touched * 2 * one_expert + 2 * D * E + 4 * E + shared
    head = D * V + 4 * V
    weight_bytes = 8 * attn + dense_mlp + 7 * expert_layer + head \
        + rows * 2 * D
    c = lw.decode_step(m, "int8", rows, kv)
    assert c["weight_bytes"] == pytest.approx(weight_bytes, rel=1e-12)
    kv_tok = 8 * 4 * 128 * 2 * 2                # 16 KiB a token
    assert c["kv_bytes"] == kv * kv_tok + rows * kv_tok
    attn_f = 2 * (2 * D * 4096 + 2 * D * 512 + 4096 * D)
    flops = rows * (8 * attn_f + 2 * 3 * D * 6144
                    + 7 * (k * 2 * one_expert + 2 * D * E
                           + 2 * 3 * D * 1024) + 2 * D * V) \
        + 8 * 4 * 32 * 128 * kv
    assert c["flops"] == pytest.approx(flops, rel=1e-12)
    assert costs.least_seconds(c, V5E)["bound"] == "bytes"
    # ~6.9 GB of ~8.2 GB a step are the touched experts'
    assert 7 * touched * 2 * one_expert == pytest.approx(6.78e9, rel=0.01)
    assert 7.4e9 < c["weight_bytes"] < 7.6e9
    # costs.py reads every expert at the DENSE width: six times over
    wrong = costs.decode_step(m, "int8", rows, kv)
    assert wrong["weight_bytes"] > 5 * c["weight_bytes"]


@pytest.mark.parametrize("rows,kv", OCCUPANCY)
@pytest.mark.parametrize("name", UNIFORM)
def test_equal_to_costs_py_where_the_layers_are_all_alike(name, rows, kv):
    cfg = config(name)
    m, quant = cfg["model"], cfg["weight_quant"]
    assert len(lw.layer_groups(m)) == 1
    assert lw.decode_step(m, quant, rows, kv) == costs.decode_step(
        m, quant, rows, kv)
    for stage in costs.STAGES:
        assert lw.decode_stage(m, quant, stage, rows, kv) \
            == costs.decode_stage(m, quant, stage, rows, kv)
    assert lw.decode_step(m, "", rows, kv, 1) == costs.decode_step(
        m, "", rows, kv, 1)                     # bf16 weights, int8 KV


@pytest.mark.parametrize("name", UNIFORM + ["trinity-mini"])
def test_a_stage_is_a_part_of_the_step(name):
    cfg = config(name)
    m, quant = cfg["model"], cfg["weight_quant"]
    step = lw.decode_step(m, quant, 14.0, 40000.0)
    parts = [lw.decode_stage(m, quant, s, 14.0, 40000.0)
             for s in costs.STAGES]
    for part in parts:
        assert 0 < part["bytes"] < step["bytes"]
        assert 0 < part["flops"] < step["flops"]
    # what no stage counts: the projections and the embedding rows
    assert sum(p["bytes"] for p in parts) < step["bytes"]
    with pytest.raises(ValueError):
        lw.decode_stage(m, quant, "nowhere", 1, 1)


def test_trinity_mlp_stage_holds_router_experts_shared_and_dense():
    m = TRINITY["model"]
    mlp = lw.decode_stage(m, "int8", "mlp", 16, 1000.0)
    touched = costs.expected_experts_touched(128, 8, 16)
    assert touched == pytest.approx(82.4, abs=0.1)
    D = 2048
    want = 7 * (touched * 2 * 3 * D * 1024 + 2 * D * 128 + 4 * 128
                + 3 * D * 1024 + 4 * (2 * 1024 + D)) \
        + 3 * D * 6144 + 4 * (2 * 6144 + D)
    assert mlp["bytes"] == pytest.approx(want, rel=1e-12)
    none_shared = dict(m, num_shared_experts=0)
    assert lw.decode_stage(none_shared, "int8", "mlp", 16, 1000.0)[
        "bytes"] == pytest.approx(want - 7 * (3 * D * 1024
                                              + 4 * (2 * 1024 + D)))
