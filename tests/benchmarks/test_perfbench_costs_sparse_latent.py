"""``harness/costs_sparse_latent.py``: every term against a hand count at
the published widths of the configuration that uses it, the reader over
it on the recorded scoped trace (the two context sums taken from the
contexts themselves), and the new metric files."""

import json
import os
import types

import pytest

from benchmarks.harness import costs, costs_latent, costs_sparse_latent, trace
from benchmarks.harness.context import Context
from benchmarks.harness.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SCOPED = os.path.join(HERE, "fixtures", "tpu_v5e_spans_scopes.xplane.pb")
DECODE = "^jit_decode_round$"
CELL = "glm-5.2.long-context-mixed-16"

with open(os.path.join(REPO, "benchmarks", "configs", "glm-5.2.json")) as f:
    GLM = json.load(f)
M = GLM["model"]
L = M["num_layers"]


def test_full_layers_of_the_cut():
    assert L == 8 and costs_sparse_latent.full_layers(M) == 2
    assert costs_sparse_latent.full_layers(
        dict(M, index_layers=[1, 0, 0, 0])) == 2          # a period
    assert costs_sparse_latent.full_layers(dict(M, index_layers=[])) == 8


def test_the_attention_and_indexer_matrices_as_stored():
    weights = sum(r * c for r, c in costs_latent.attn_matrices(M))
    assert weights == (6144 * 2048 + 2048 * 16384 + 6144 * 576
                       + 512 * 12288 + 512 * 16384 + 16384 * 6144) \
        == 165_019_648
    index = sum(r * c for r, c in costs_sparse_latent.index_matrices(M))
    assert index == 2048 * 4096 + 6144 * 128 + 6144 * 32 == 9_371_648
    # int8: a byte a weight and a float32 scale an output column; the
    # indexer bf16, on the two full layers
    cols = 2048 + 16384 + 576 + 12288 + 16384 + 6144
    step = costs_sparse_latent.decode_step(M, "int8", 0.0, 0.0, 0.0)
    rest = costs_latent.mlp_stage(M, "int8", 0.0)["bytes"] \
        + costs.decode_stage(M, "int8", "tail", 0.0, 0.0)["bytes"]
    assert step["weight_bytes"] - rest == L * (weights + 4 * cols) \
        + 2 * 2 * index
    assert step["kv_bytes"] == 0


def test_index_keys_of_every_cached_token_and_the_chosen_rows():
    contexts = [1000, 2048, 9000, 16384]
    assert costs_sparse_latent.selected_tokens(M, contexts) \
        == 1000 + 2048 + 2048 + 2048
    rows, indexed, selected = 4.0, float(sum(contexts)), 7144.0
    attn = costs_sparse_latent.decode_stage(M, "int8", "attn", rows,
                                            indexed, selected)
    index_w = 9_371_648
    # 128 values a cached token a FULL layer; 576 a chosen token a layer;
    # a new row a sequence in both
    assert attn["bytes"] == 2 * (2 * index_w + (indexed + 4) * 128 * 2) \
        + 8 * (selected + 4) * 576 * 2
    # 2 x 32 x 128 a cached token a full layer, 2 x (576 + 512) x 64 a
    # chosen token a layer, and a row through the indexer's matrices
    assert attn["flops"] == 2 * (4 * 2 * index_w + 2 * 32 * 128 * indexed) \
        + 8 * 2 * (576 + 512) * 64 * selected
    # a dense read of the same contexts is what costs_latent.py counts
    dense = costs_latent.decode_stage(M, "int8", "attn", rows, indexed)
    assert dense["bytes"] == 8 * (indexed + 4) * 576 * 2
    assert attn["bytes"] < 0.45 * dense["bytes"]


def test_the_mlp_stage_and_the_tail_are_the_older_counts():
    rows = 13.0
    mlp = costs_sparse_latent.decode_stage(M, "int8", "mlp", rows, 1e5, 2e4)
    assert mlp == costs_latent.mlp_stage(M, "int8", rows)
    one = 3 * 6144 * 2048
    touched = 8 * (1 - (31 / 32) ** rows)
    assert costs_latent.expected_held_touched(M, rows) == pytest.approx(
        touched)
    assert touched == pytest.approx(2.71, abs=0.01)
    shared_b = one + 4 * (2048 + 2048 + 6144)
    dense_b = 3 * 6144 * 12288 + 4 * (12288 + 12288 + 6144)
    assert mlp["bytes"] == pytest.approx(
        dense_b + 7 * (touched * 2 * one + 2 * 6144 * 256 + 4 * 256
                       + shared_b))
    tail = costs_sparse_latent.decode_stage(M, "int8", "tail", rows, 1e5, 2e4)
    assert tail == costs.decode_stage(M, "int8", "tail", rows, 1e5)
    assert tail["bytes"] == 6144 * 154880 + 4 * 154880


def test_the_step_is_its_parts():
    rows, indexed = 13.0, 13 * 9000.0
    selected = 13 * 2048.0
    step = costs_sparse_latent.decode_step(M, "int8", rows, indexed, selected)
    parts = {s: costs_sparse_latent.decode_stage(M, "int8", s, rows, indexed,
                                                 selected)
             for s in costs.STAGES}
    attn_w = 165_019_648
    assert step["bytes"] == pytest.approx(
        sum(p["bytes"] for p in parts.values())
        + L * (attn_w + 4 * 53824) + rows * 2 * 6144)
    assert step["flops"] == pytest.approx(
        sum(p["flops"] for p in parts.values()) + rows * L * 2 * attn_w)
    # the issue's reckoning: ~4.2 GB of matrices a step, 0.3 GB of reads
    assert 3.6e9 < step["weight_bytes"] < 4.4e9
    assert 0.25e9 < step["kv_bytes"] < 0.37e9


# --------------------------------------------------------------- reader


@pytest.fixture
def scoped_ctx(monkeypatch):
    monkeypatch.setattr(trace, "find_xplane", lambda _dir: SCOPED)
    rounds = [types.SimpleNamespace(decode_steps=3, prefill_tokens=200)] * 3
    cell = types.SimpleNamespace(name="some.cell", config={})
    return Context(cell=cell, rows=[], t0=0.0, t_end=1.0, drain_limit_s=1.0,
                   trace=trace.reduce(trace.load(SCOPED)),
                   trace_rounds=rounds)


def test_sparse_latent_roofline_on_the_recorded_trace(scoped_ctx):
    """The fixture's program is a toy, so the arithmetic is held: the
    least time of the count over the device time of the module or the
    scope, the chosen rows summed row by row; nothing where there is
    nothing to read (a program without the indexer's scopes among
    them)."""
    from benchmarks.harness.loadgen import Row
    from benchmarks.harness.traffic import Request
    from benchmarks.readers import (device_scope, device_trace,
                                    sparse_latent_roofline)
    ctx = scoped_ctx
    ctx.peaks = costs.peaks("TPU v5 lite")
    # a configuration without a learned selection: not this reader's
    ctx.cell.config = {"model": {"num_layers": 2, "kv_lora_rank": 512},
                       "weight_quant": "int8"}
    assert sparse_latent_roofline.read(ctx, DECODE) is None
    ctx.cell.config = {"model": M, "weight_quant": "int8"}
    assert sparse_latent_roofline.read(ctx, DECODE) is None   # no rows
    ctx.trace_t0, ctx.trace_t1 = 0.0, 1.0
    stream = types.SimpleNamespace(first_token_time=-1.0, finish_time=2.0,
                                   token_ids=[5] * 30, finish_reason="length")
    # two rows under the selection's size, two far over it
    ctx.rows = [Row(Request(i, [3] * n, 30, 1), 0.0, 0.0, stream=stream)
                for i, n in enumerate((100, 1000, 5000, 9000))]
    rows, indexed = ctx.mean_occupancy(sum)
    assert rows == 4 and indexed == pytest.approx(15100 + 4 * 15.0, rel=0.01)
    selected = ctx.mean_occupancy(
        lambda c: costs_sparse_latent.selected_tokens(M, c))[1]
    assert selected == pytest.approx(115 + 1015 + 2048 + 2048, rel=0.01)
    share = sparse_latent_roofline.read(ctx, DECODE)
    ms = device_trace.read(ctx, "module_ms_per", modules=DECODE, per="step")
    least = costs.least_seconds(costs_sparse_latent.decode_step(
        M, "int8", rows, indexed, selected), ctx.peaks)
    assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    note = ctx.notes["sparse_latent_roofline"]["step"]
    assert note["bound"] == least["bound"]
    assert note["mean_selected_tokens"] == pytest.approx(selected)
    for stage in costs.STAGES:
        scope = f"(^|/){stage}(/|$)"
        share = sparse_latent_roofline.read(ctx, DECODE, stage=stage,
                                            scope=scope)
        ms = device_scope.read(ctx, scope, DECODE, per="step")
        least = costs.least_seconds(costs_sparse_latent.decode_stage(
            M, "int8", stage, rows, indexed, selected), ctx.peaks)
        assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    # the attention stage as its metric file names it: the fixture's
    # program has no attn_index / attn_select, the read's scope is there
    args = Spec().layer_metric("sparse_decode_attn_roofline")["args"]
    assert sparse_latent_roofline.read(ctx, **args) == pytest.approx(
        sparse_latent_roofline.read(ctx, DECODE, stage="attn",
                                    scope="(^|/)attn(/|$)"))
    # ... and the indexer's own time reads nothing there, and does not raise
    index = Spec().layer_metric("decode_index_ms")["args"]
    assert device_scope.read(ctx, **index) is None
    assert sparse_latent_roofline.read(ctx, "^no_such_module$") is None
    ctx.trace = None
    assert sparse_latent_roofline.read(ctx, DECODE) is None


@pytest.mark.parametrize("name,reader", [
    ("sparse_decode_step_roofline", "sparse_latent_roofline"),
    ("sparse_decode_attn_roofline", "sparse_latent_roofline"),
    ("sparse_decode_mlp_roofline", "sparse_latent_roofline"),
    ("decode_index_ms", "device_scope"),
    ("prefill_index_ms_per_ktok", "device_scope"),
    ("sparse_selected_pct", "decode_round_fields")])
def test_new_metric_files_name_their_reader(name, reader):
    spec = Spec()
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "out_tok_per_s"
    assert spec.layer_metric(name)["reader"] == reader


def test_the_selected_share_is_read_from_the_round_records():
    from benchmarks.readers import decode_round_fields
    rec = types.SimpleNamespace
    ctx = types.SimpleNamespace(rounds=[
        rec(decode_slots=0, decode_steps=0, kv_selected_pct=0.0),
        rec(decode_slots=13, decode_steps=8, kv_selected_pct=24.0),
        rec(decode_slots=12, decode_steps=8, kv_selected_pct=26.0)])
    args = Spec().layer_metric("sparse_selected_pct")["args"]
    assert decode_round_fields.read(ctx, **args) == pytest.approx(25.0)
    # a program without the counter: nothing, and no error
    old = types.SimpleNamespace(rounds=[rec(decode_slots=3, decode_steps=8)])
    assert decode_round_fields.read(old, **args) is None


def test_the_cell_and_its_mix_as_the_issue_names_them():
    spec = Spec()
    cell = spec.cell(CELL)
    assert cell.workload["clients"] == 16 and cell.workload["chips"] == 1
    assert cell.workload["drain_limit_s"] == 45.0
    assert set(cell.workload["reports"]) == {"out_tok_per_s", "setup_s"}
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "long-context-mixed-16.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "closed" and mix["set_size"] == 128
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                    "sigma": 0.4, "min": 4096, "max": 16384}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128, "max": 256}
    assert mix["sampling"]["top_k"] == 1
    assert mix["prefix_sharing"] == {"groups": 0, "shared_tokens": 0}
    lc = GLM["logits_check"]
    assert lc["prompts"] >= 4 and lc["prompt_pages"] >= 36 \
        and lc["decode_steps"] >= 2 and lc["tolerance"] <= 0.05
    assert GLM["engine"]["max_slots"] == 16
    assert GLM["engine"]["max_input_length"] == 16384
    assert GLM["chips_sharing_a_layer"] == 32
