"""Each per-layer reader on a hand-made run context."""

import os
import types

import pytest

from benchmarks.harness import costs, trace
from benchmarks.harness.context import Context, read_layer_metric
from benchmarks.harness.loadgen import Row
from benchmarks.harness.traffic import Request
from benchmarks.readers import (compile_events, device_trace, engine_report,
                                roofline, round_records)

HERE = os.path.dirname(os.path.abspath(__file__))


class Timeline:
    def __init__(self, events):
        self._events = events

    def events_snapshot(self):
        return [(i, t, n, None) for i, (t, n) in enumerate(self._events)]


def stream(first, finish, tokens, events=()):
    return types.SimpleNamespace(
        first_token_time=first, finish_time=finish,
        token_ids=[5] * tokens, finish_reason="length",
        timeline=Timeline(list(events)))


def rec(**kw):
    base = dict(t_start=1.0, decode_steps=0, active_decodes=0, grants=[],
                prefill_tokens=0, dispatch_ms=0.0, round_ms=0.0, done=True)
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.fixture
def ctx():
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "fixtures",
                           "synthetic_trace.textproto")) as f:
        planes = trace.planes_of(ProfileData.from_text_proto(f.read()))
    rows = [Row(Request(i, [3] * 100, 20, 1), due_t=1.0 + i,
                send_t=1.0 + i + 0.001 * (i + 1),
                stream=stream(2.0, 4.0, 20, [
                    (1.0 + i, "engine_submit"),
                    (1.0 + i + 0.05 * (i + 1), "engine_admit_dispatch")]))
            for i in range(10)]
    cell = types.SimpleNamespace(config={
        "model": {"vocab_size": 1000, "hidden_size": 64,
                  "intermediate_size": 128, "num_layers": 2, "num_heads": 4,
                  "num_kv_heads": 2, "head_dim": 16},
        "weight_quant": "int8"})
    rounds = [rec(decode_steps=8, active_decodes=4, dispatch_ms=2.0),
              rec(decode_steps=4, active_decodes=2, dispatch_ms=4.0,
                  grants=[("a", 128), ("b", 64)], prefill_tokens=192),
              rec(grants=[("a", 512)], prefill_tokens=512, dispatch_ms=6.0)]
    return Context(
        cell=cell, rows=rows, t0=0.0, t_end=20.0, drain_limit_s=5.0,
        stats0={"prefills": 10, "decode_steps": 100, "rounds_completed": 5},
        stats1={"prefills": 12, "decode_steps": 112, "rounds_completed": 8,
                "sched_round_budget_tokens": 512},
        rounds=rounds, trace_rounds=rounds,
        trace=trace.reduce(planes, window_s=500e-6),
        trace_t0=2.5, trace_t1=3.5,
        compiles_in_window=[], engine_report={"pool_pages": 71},
        peaks=costs.peaks("TPU v5 lite"))


def test_round_records_mean_sum_and_per_counter(ctx):
    assert round_records.read(ctx, "dispatch_ms") == pytest.approx(4.0)
    assert round_records.read(ctx, "decode_steps", agg="mean",
                              where="decode_steps") == pytest.approx(6.0)
    assert round_records.read(ctx, "prefill_tokens", agg="sum") == 704
    # three grants over two admissions
    assert round_records.read(ctx, "grants", agg="per_counter",
                              per_counter="prefills") == pytest.approx(1.5)
    with pytest.raises(ValueError):
        round_records.read(ctx, "bw_util")        # modelled: not read
    with pytest.raises(ValueError):
        round_records.read(ctx, "device_ms")      # host estimate: not read
    ctx.rounds = []
    assert round_records.read(ctx, "dispatch_ms") is None


def test_device_trace_idle_and_module_time(ctx):
    assert device_trace.read(ctx, "idle_pct") == pytest.approx(
        100 * (1 - 210 / 500))
    dec = r"^jit_decode_round$"
    assert device_trace.read(ctx, "module_ms_per", modules=dec,
                             per="call") == pytest.approx(0.1)
    # two executions x a mean of 6 steps a round
    assert device_trace.read(ctx, "module_ms_per", modules=dec,
                             per="step") == pytest.approx(0.2 / 12)
    assert device_trace.read(ctx, "module_ms_per", modules=r"^jit_extend$",
                             per="ktok") == pytest.approx(0.05 / 0.704)
    assert device_trace.read(ctx, "module_ms_per", modules="absent") is None
    with pytest.raises(ValueError):
        device_trace.read(ctx, "flops")
    ctx.trace = None
    assert device_trace.read(ctx, "idle_pct") is None


def test_roofline_is_least_time_over_measured_time(ctx):
    share = roofline.read(ctx, modules=r"^jit_decode_round$")
    note = ctx.notes["roofline"]
    assert note["bound"] in ("bytes", "flops")
    assert note["mean_rows"] == pytest.approx(10.0)
    assert share == pytest.approx(100 * note["least_ms"] / note["step_ms"])
    want = costs.least_seconds(costs.decode_step(
        ctx.cell.config["model"], "int8", note["mean_rows"],
        note["mean_kv_tokens"]), ctx.peaks)["seconds"] * 1e3
    assert note["least_ms"] == pytest.approx(want)
    ctx.trace = None
    assert roofline.read(ctx, modules="x") is None


def test_compile_events_counts_and_names(ctx):
    assert compile_events.read(ctx) == 0.0 and "compiles_in_window" \
        not in ctx.notes
    ctx.compiles_in_window = [(1.0, "jit(extend)", 3.3)]
    assert compile_events.read(ctx) == 1.0
    assert ctx.notes["compiles_in_window"] == [["jit(extend)", 3.3]]
    ctx.compiles_in_window = None
    assert compile_events.read(ctx) is None


def test_engine_report_field(ctx):
    assert engine_report.read(ctx, field="pool_pages") == 71.0
    assert engine_report.read(ctx, field="absent") is None


def test_reader_is_found_by_the_metric_files_name(ctx):
    metric = {"reader": "engine_report", "args": {"field": "pool_pages"}}
    assert read_layer_metric(ctx, metric) == 71.0
    with pytest.raises(ModuleNotFoundError):
        read_layer_metric(ctx, {"reader": "no_such_reader"})
