"""``harness/costs_latent.py``: every term against a hand count at the
published widths of the configuration that uses it, and the reader over
it on the recorded scoped trace."""

import json
import os
import types

import pytest

from benchmarks.harness import costs, costs_latent, trace
from benchmarks.harness.context import Context
from benchmarks.harness.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SCOPED = os.path.join(HERE, "fixtures", "tpu_v5e_spans_scopes.xplane.pb")
DECODE = "^jit_decode_round$"

with open(os.path.join(REPO, "benchmarks", "configs",
                       "kimi-k2-instruct.json")) as f:
    KIMI = json.load(f)
M = KIMI["model"]
L = M["num_layers"]


def test_the_five_attention_matrices_as_stored():
    weights = sum(r * c for r, c in costs_latent.attn_matrices(M))
    assert weights == (7168 * 1536 + 1536 * 12288 + 7168 * 576
                       + 512 * 16384 + 8192 * 7168) == 101_122_048
    # int8: a byte a weight and a float32 scale an output column
    cols = 1536 + 12288 + 576 + 8192 + 8192 + 7168
    step = costs_latent.decode_step(M, "int8", rows=0.0, kv_tokens=0.0)
    rest = costs_latent.mlp_stage(M, "int8", 0.0)["bytes"] \
        + costs.decode_stage(M, "int8", "tail", 0.0, 0.0)["bytes"]
    assert step["weight_bytes"] - rest == L * (weights + 4 * cols)


def test_a_cached_token_is_576_values_a_layer_read_once():
    assert costs_latent.kv_values_per_token(M) == 576
    attn = costs_latent.decode_stage(M, "int8", "attn", rows=28,
                                     kv_tokens=112_000)
    assert attn["bytes"] == L * (112_000 + 28) * 576 * 2
    # a score over 576 values and a sum over 512, two operations each,
    # for each of 64 heads
    assert attn["flops"] == L * 2 * (576 + 512) * 64 * 112_000


def test_the_held_experts_the_rows_are_expected_to_touch():
    assert costs_latent.held_experts(M) == 12
    assert costs_latent.held_experts({"num_experts": 8}) == 8
    got = costs_latent.expected_held_touched(M, 28)
    assert got == pytest.approx(12 * (1 - (47 / 48) ** 28))
    assert got == pytest.approx(5.34, abs=0.01)
    # every expert held: costs.py's count
    whole = dict(M, experts_held=0)
    assert costs_latent.expected_held_touched(whole, 28) == pytest.approx(
        costs.expected_experts_touched(384, 8, 28))


def test_the_mlp_stage_layer_by_layer():
    rows = 28
    one = 3 * 7168 * 2048                       # an expert's weights
    shared_b = one + 4 * (2048 + 2048 + 7168)   # int8 + column scales
    dense_b = 3 * 7168 * 18432 + 4 * (18432 + 18432 + 7168)
    touched = 12 * (1 - (47 / 48) ** rows)
    mlp = costs_latent.mlp_stage(M, "int8", rows)
    assert mlp["bytes"] == pytest.approx(
        dense_b + 7 * (touched * 2 * one + 2 * 7168 * 384 + 4 * 384
                       + shared_b))
    # a row's eight assignments fall on held experts 12 times in 384
    assert mlp["flops"] == pytest.approx(rows * (
        2 * 3 * 7168 * 18432
        + 7 * (8 * 12 / 384 * 2 * one + 2 * 7168 * 384 + 2 * one)))


def test_the_step_is_its_parts_and_the_tail_is_costs_py():
    rows, kv = 28.0, 112_000.0
    step = costs_latent.decode_step(M, "int8", rows, kv)
    parts = {s: costs_latent.decode_stage(M, "int8", s, rows, kv)
             for s in costs.STAGES}
    assert parts["tail"] == costs.decode_stage(M, "int8", "tail", rows, kv)
    assert parts["tail"]["bytes"] == 7168 * 163840 + 4 * 163840
    attn_w = sum(r * c for r, c in costs_latent.attn_matrices(M))
    assert step["kv_bytes"] == parts["attn"]["bytes"]
    assert step["bytes"] == pytest.approx(
        sum(p["bytes"] for p in parts.values())
        + L * (attn_w + 4 * 37952) + rows * 2 * 7168)
    assert step["flops"] == pytest.approx(
        sum(p["flops"] for p in parts.values()) + rows * L * 2 * attn_w)
    # the issue's reckoning: about 7 GB a step, the attention within a
    # factor of two of the chip's ridge
    assert 6.5e9 < step["bytes"] < 7.5e9
    peak = costs.peaks("TPU v5 lite")
    least = costs.least_seconds(parts["attn"], peak)
    assert 0.5 < least["t_flops"] / least["t_bytes"] < 1.0


# --------------------------------------------------------------- reader


@pytest.fixture
def scoped_ctx(monkeypatch):
    monkeypatch.setattr(trace, "find_xplane", lambda _dir: SCOPED)
    rounds = [types.SimpleNamespace(decode_steps=3, prefill_tokens=200)] * 3
    cell = types.SimpleNamespace(name="some.cell", config={})
    return Context(cell=cell, rows=[], t0=0.0, t_end=1.0, drain_limit_s=1.0,
                   trace=trace.reduce(trace.load(SCOPED)),
                   trace_rounds=rounds)


def test_latent_roofline_on_the_recorded_trace(scoped_ctx):
    """The fixture's program is a toy, so the arithmetic is held: the
    least time of the count over the device time of the module or the
    scope; nothing where there is nothing to read."""
    from benchmarks.harness.loadgen import Row
    from benchmarks.harness.traffic import Request
    from benchmarks.readers import device_scope, device_trace, latent_roofline
    ctx = scoped_ctx
    ctx.peaks = costs.peaks("TPU v5 lite")
    # a configuration without a latent cache: not this reader's
    ctx.cell.config = {"model": {"num_layers": 2}, "weight_quant": "int8"}
    assert latent_roofline.read(ctx, DECODE) is None
    ctx.cell.config = {"model": M, "weight_quant": "int8"}
    assert latent_roofline.read(ctx, DECODE) is None     # no rows stamped
    ctx.trace_t0, ctx.trace_t1 = 0.0, 1.0
    stream = types.SimpleNamespace(first_token_time=-1.0, finish_time=2.0,
                                   token_ids=[5] * 30, finish_reason="length")
    ctx.rows = [Row(Request(i, [3] * 100, 30, 1), 0.0, 0.0, stream=stream)
                for i in range(4)]
    rows, kv = ctx.mean_occupancy(sum)
    assert rows == 4 and kv == pytest.approx(4 * 115.0, rel=0.01)
    share = latent_roofline.read(ctx, DECODE)
    ms = device_trace.read(ctx, "module_ms_per", modules=DECODE, per="step")
    least = costs.least_seconds(
        costs_latent.decode_step(M, "int8", rows, kv), ctx.peaks)
    assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    assert ctx.notes["latent_roofline"]["step"]["bound"] == least["bound"]
    for stage in costs.STAGES:
        scope = f"(^|/){stage}(/|$)"
        share = latent_roofline.read(ctx, DECODE, stage=stage, scope=scope)
        ms = device_scope.read(ctx, scope, DECODE, per="step")
        least = costs.least_seconds(costs_latent.decode_stage(
            M, "int8", stage, rows, kv), ctx.peaks)
        assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
    assert latent_roofline.read(ctx, DECODE, stage="attn",
                                scope="no_such_scope") is None
    assert latent_roofline.read(ctx, "^no_such_module$") is None
    ctx.trace = None
    assert latent_roofline.read(ctx, DECODE) is None


@pytest.mark.parametrize("name,reader", [
    ("latent_decode_step_roofline", "latent_roofline"),
    ("latent_decode_attn_roofline", "latent_roofline"),
    ("latent_decode_mlp_roofline", "latent_roofline"),
    ("decode_latent_proj_ms", "device_scope"),
    ("moe_local_assignments", "decode_round_fields")])
def test_new_metric_files_name_their_reader(name, reader):
    spec = Spec()
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["kimi-k2-instruct.long-context-decode-32"]
    assert entry["moves"] == "out_tok_per_s"
    assert spec.layer_metric(name)["reader"] == reader
