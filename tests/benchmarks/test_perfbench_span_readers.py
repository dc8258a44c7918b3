"""The readers of the program's spans, scopes and counters: host_spans
and round_fields / engine_stats on hand-made inputs (exact sums), and
device_scope and the clock offset on a small scoped trace recorded on a
TPU v5e by benchmarks/record_scoped_fixture.py."""

import os
import types

import pytest

from benchmarks.harness import trace
from benchmarks.harness.context import Context, read_layer_metric
from benchmarks.harness.spec import Spec
from benchmarks.readers import (device_scope, engine_stats, host_spans,
                                round_fields)

HERE = os.path.dirname(os.path.abspath(__file__))
FIX = os.path.join(HERE, "fixtures")
SCOPED = os.path.join(FIX, "tpu_v5e_spans_scopes.xplane.pb")
DECODE = "^jit_decode_round$"


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    with open(os.path.join(FIX, "synthetic_trace_host.textproto")) as f:
        return ProfileData.from_text_proto(f.read())


def make_ctx(**kw):
    cell = types.SimpleNamespace(name="some.cell", config={})
    return Context(cell=cell, rows=[], t0=0.0, t_end=1.0, drain_limit_s=1.0,
                   **kw)


@pytest.fixture
def scoped_ctx(monkeypatch):
    """A run context whose cell's trace is the recorded fixture."""
    monkeypatch.setattr(trace, "find_xplane", lambda _dir: SCOPED)
    planes = trace.load(SCOPED)
    rounds = [types.SimpleNamespace(decode_steps=3, prefill_tokens=200)] * 3
    return make_ctx(trace=trace.reduce(planes), trace_rounds=rounds)


# ----------------------------------------------------------- host spans


def test_spans_are_read_with_their_arguments(synthetic):
    spans = host_spans.load_spans(synthetic)
    assert sorted(spans) == [
        "PjitFunction(convert_element_type)", "PjitFunction(decode_round)",
        "PjitFunction(extend)", "chunk_dispatch", "engine_harvest_wait",
        "engine_round", "loop_admit", "loop_dispatch", "loop_idle",
        "loop_plan"]
    # the runtime prints each jitted call twice, nested: kept once
    assert [(s, e) for s, e, _ in spans["PjitFunction(decode_round)"]] == [
        (pytest.approx(8e3), pytest.approx(16e3)),
        (pytest.approx(320e3), pytest.approx(321e3))]
    (start, end, stats), = spans["chunk_dispatch"]
    assert (end - start) == pytest.approx(28e3)
    assert stats == {"round_id": 1, "request_id": "req-a", "tokens": 100,
                     "padded": 128, "mode": "middle"}
    assert [s[2]["round_id"] for s in spans["engine_round"]] == [1, 2]


def test_launches_pair_with_executions_in_order():
    launches = [("decode", "l0"), ("prefill", "l1"), ("decode", "l2")]
    execs = [("prefill", "before the trace"), ("decode", "e0"),
             ("prefill", "e1"), ("decode", "e2")]
    assert host_spans.pair_fifo(launches, execs) == [
        ("l0", "e0"), ("l1", "e1"), ("l2", "e2")]
    # executions that outlast the trace's launches are left alone
    assert host_spans.pair_fifo(launches[:2], execs) == [
        ("l0", "e0"), ("l1", "e1")]
    assert host_spans.pair_fifo([("decode", "l")], [("prefill", "e")]) == []


def test_clock_offset_lies_between_its_two_bounds(synthetic):
    note = host_spans.summarise(synthetic)["note"]
    off = note["clock_offset_ms"]
    assert off["lower"] == pytest.approx(0.019)     # launch 319 - start 300
    assert off["upper"] == pytest.approx(0.023)     # readback 423 - end 400
    assert off["used"] == pytest.approx(0.021)
    assert off["residual"] == pytest.approx(0.002)
    assert note["alignment"] == {"pairs": 3, "launches": 3, "executions": 3}


def test_queue_ahead_is_dispatch_end_to_device_start(synthetic):
    q = host_spans.summarise(synthetic)["note"]["queue_ahead_ms"]
    assert q["n"] == 1
    assert q["p50"] == q["p90"] == pytest.approx(0.121)


def test_idle_gaps_are_put_down_to_the_host_phase_that_covers_them(synthetic):
    gaps = host_spans.summarise(synthetic)["note"]["idle_gaps"]
    assert gaps["loop_idle"] == {"n": 1, "ms": pytest.approx(0.08)}
    assert gaps["loop_plan"] == {"n": 1, "ms": pytest.approx(0.1)}
    assert set(gaps) == {"loop_idle", "loop_plan"}   # the 10 us gap is out


def test_a_gap_shorter_than_the_residual_is_not_attributed():
    spans = {"loop_idle": [(0.0, 1e6, {})]}
    device = {"busy": [(0.0, 100e3), (400e3, 500e3), (560e3, 600e3)]}
    gaps = host_spans.gaps_by_phase(spans, device, offset_ns=0.0,
                                    residual_ns=200e3)
    assert gaps == {"loop_idle": {"n": 1, "ms": pytest.approx(0.3)},
                    "under_residual": {"n": 1, "ms": pytest.approx(0.06)}}
    none = host_spans.gaps_by_phase({}, device, 0.0, 0.0)
    assert none == {"none": {"n": 2, "ms": pytest.approx(0.36)}}


def test_a_span_that_launched_nothing_pairs_with_no_execution(synthetic):
    spans = host_spans.load_spans(synthetic)
    refused = (60e3, 62e3, {"round_id": 1, "request_id": "req-b"})
    spans["chunk_dispatch"].append(refused)      # no jitted call inside
    al = host_spans.align(spans, host_spans.load_device(synthetic))
    assert (al["pairs"], al["launches"], al["executions"]) == (3, 3, 3)
    assert al["queue_ahead_ms"]["n"] == 1


def test_calls_inside_the_dispatch_spans_are_summed_by_function(synthetic):
    note = host_spans.summarise(synthetic)["note"]
    assert note["calls_in_dispatch_ms"] == {
        "convert_element_type": pytest.approx(0.008),   # 16 us / 2 rounds
        "decode_round": pytest.approx(0.0045),          # (8 + 1) / 2
        "extend": pytest.approx(0.0045)}
    assert "PjitFunction(extend)" not in note["per_round_ms"]


def test_span_dispatch_time_is_held_against_the_round_records(synthetic):
    spans = host_spans.load_spans(synthetic)
    recs = [types.SimpleNamespace(round_id=1, dispatch_ms=0.05),
            types.SimpleNamespace(round_id=2, dispatch_ms=0.01),
            types.SimpleNamespace(round_id=3, dispatch_ms=9.0)]
    d = host_spans.dispatch_against_records(spans, recs)
    assert d == {"rounds": 2, "spans": pytest.approx((10 + 35 + 8) / 2e3),
                 "records": pytest.approx(0.03)}
    assert host_spans.dispatch_against_records(spans, []) is None


def test_slack_bounds_fall_back_to_the_readback_bound():
    d0, d1 = (0.0, 100e6, "jit_decode_round"), (100e6, 200e6,
                                                 "jit_decode_round")
    spans = {"PjitFunction(decode_round)": [(-150e6, -149.5e6, {}),
                                            (-50e6, -49.5e6, {})],
             "loop_dispatch": [(-150.1e6, -149e6, {"round_id": 1}),
                               (-50.1e6, -49e6, {"round_id": 2})],
             "engine_harvest_wait": [(0.0, 100.3e6, {"round_id": 1}),
                                     (100.4e6, 200.2e6, {"round_id": 2})]}
    al = host_spans.align(spans, {"modules": [d0, d1], "busy": []})
    off = al["clock_offset_ms"]
    assert off["lower"] == pytest.approx(-150.0)     # the queue was full
    assert off["upper"] == pytest.approx(0.2)
    assert off["used"] == pytest.approx(0.2)
    assert off["residual"] == pytest.approx(host_spans.RESIDUAL_NS * 1e-6)


def test_host_span_reader_aggregates(synthetic, monkeypatch):
    monkeypatch.setattr(trace, "find_xplane", lambda _dir: "synthetic")
    monkeypatch.setattr(host_spans, "summary",
                        lambda _path: host_spans.summarise(synthetic))
    ctx = make_ctx()
    assert host_spans.read(ctx, ["loop_plan"], "ms_per_round") == \
        pytest.approx(0.0555)                        # (5 + 106) / 2 rounds
    assert host_spans.read(ctx, ["loop_dispatch", "chunk_dispatch"],
                           "mean_ms") == pytest.approx((10 + 8 + 28) / 3e3)
    assert host_spans.read(ctx, ["chunk_dispatch"], "count") == 1.0
    assert host_spans.read(ctx, ["no_such_span"]) is None
    assert ctx.notes["host_spans"]["per_round_ms"]["loop_admit"] == \
        pytest.approx(0.0175)
    with pytest.raises(ValueError):
        host_spans.read(ctx, ["loop_plan"], "median")


def test_readers_find_nothing_where_there_is_no_trace(monkeypatch):
    monkeypatch.setattr(trace, "find_xplane", lambda _dir: None)
    ctx = make_ctx()
    assert host_spans.read(ctx, ["loop_plan"]) is None
    assert device_scope.read(ctx, "attn", DECODE) is None
    assert ctx.notes == {}


# ---------------------------------------------------- protobuf, by hand


def varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def ld(num, payload):
    return varint(num << 3 | 2) + varint(len(payload)) + payload


def test_scope_map_walks_the_wire_format(tmp_path):
    def instruction(name, opcode, op_name):
        return ld(2, ld(1, name.encode()) + ld(2, opcode.encode())
                  + varint(35 << 3 | 0) + varint(300)       # an id: skipped
                  + ld(7, ld(1, b"dot_general") + ld(2, op_name.encode())))
    comp = ld(3, ld(1, b"main") + instruction(
        "fusion.7", "fusion", "jit(decode_round)/while/body/attn/dot")
        + instruction("while.1", "while", "jit(decode_round)/while"))
    hlo = ld(1, ld(1, b"jit_decode_round") + comp)
    stat = ld(5, varint(1 << 3 | 0) + varint(1) + ld(6, hlo))
    meta = ld(2, varint(1 << 3 | 0) + varint(9)
              + ld(2, b"jit_decode_round(42)") + stat)
    plane = ld(1, ld(2, b"/host:metadata")
               + ld(4, varint(1 << 3 | 0) + varint(9) + meta))
    other = ld(1, ld(2, b"/host:CPU") + ld(4, meta))
    path = tmp_path / "x.pb"
    path.write_bytes(other + plane)
    assert device_scope.scope_map(str(path)) == {"jit_decode_round(42)": {
        "fusion.7": ("fusion", "jit(decode_round)/while/body/attn/dot"),
        "while.1": ("while", "jit(decode_round)/while")}}


def test_stage_of_takes_the_first_stage_on_the_path():
    from generativeaiexamples_tpu.models import llama
    assert device_scope.STAGES + ("tail_select",) == llama.SCOPES
    assert device_scope.stage_of(
        "jit(decode_round)/while/body/closed_call/attn_proj/dot") == \
        "attn_proj"
    assert device_scope.stage_of("jit(f)/tail/tail_select/sort") == "tail"
    assert device_scope.stage_of("jit(f)/while/body/add") == ""
    assert device_scope.stage_of("") == ""


# ------------------------------------- the scoped trace from the chip


def test_scope_map_of_the_recorded_trace_names_both_programs():
    scopes = device_scope.scope_map(SCOPED)
    mods = sorted(trace.module_name(m) for m in scopes)
    assert mods == ["jit_decode_round", "jit_extend"]
    decode = next(v for m, v in scopes.items() if "decode_round" in m)
    assert any(op == "while" for op, _ in decode.values())
    paths = {p for _, p in decode.values()}
    assert any("/tail/tail_select/" in p for p in paths)
    assert any(p.startswith("jit(decode_round)/") for p in paths)


def test_scoped_time_adds_up_to_the_module(scoped_ctx):
    red = device_scope.reduce_scopes(SCOPED, DECODE)
    # wrappers are dropped: what is left cannot outlast the module
    assert 0.9 * red["module_s"] < red["leaf_s"] <= red["module_s"]
    step = read_layer_metric(scoped_ctx, {
        "reader": "device_trace", "args": {
            "kind": "module_ms_per", "modules": DECODE, "per": "step"}})
    parts = {s: device_scope.read(scoped_ctx, f"(^|/){s}(/|$)", DECODE)
             for s in ("attn", "mlp", "tail", "tail_select")}
    assert all(v and v > 0 for v in parts.values()), parts
    assert parts["tail_select"] < parts["tail"]
    note = scoped_ctx.notes["device_scope"][DECODE]
    assert note["attn"] == pytest.approx(parts["attn"])
    assert note["unscoped"] > 0                 # the matmul left outside
    stages = sum(note[k] for k in note
                 if k not in ("per", "module_ms", "unscoped_top"))
    assert note["unscoped_top"][0][1] <= note["unscoped"]
    assert stages == pytest.approx(step) and note["module_ms"] == step
    assert parts["attn"] + parts["mlp"] + parts["tail"] > 0.8 * step
    assert device_scope.read(scoped_ctx, "no_such_scope", DECODE) is None
    assert device_scope.read(scoped_ctx, "attn", "^jit_nothing$") is None


def test_clock_offset_of_the_recorded_trace(scoped_ctx):
    """As written the device plane runs AHEAD of the host plane: every
    module starts before the launch that caused it. The offset puts it
    back, between the launch bound and the readback bound."""
    assert host_spans.read(scoped_ctx, ["engine_round"], "count") == 3.0
    note = scoped_ctx.notes["host_spans"]
    off = note["clock_offset_ms"]
    assert off["lower"] > 0.1                  # device before its launch
    assert off["lower"] <= off["used"] <= off["upper"]
    assert off["residual"] <= 1.0
    assert note["alignment"] == {"pairs": 6, "launches": 6, "executions": 6}
    assert 0 <= note["queue_ahead_ms"]["p50"] < 5.0
    assert note["queue_ahead_ms"]["n"] == 3
    assert note["per_round_ms"]["loop_idle"] > 9.0     # slept 10 ms
    assert "loop_idle" in note["idle_gaps"]


# ------------------------------------------------- counters and fields


def test_engine_stats_reads_a_level_or_a_delta():
    ctx = make_ctx(stats0={"programs_built": 80, "program_trace_s": 20.0,
                           "program_compile_s": 5.5},
                   stats1={"programs_built": 83, "program_trace_s": 21.0,
                           "program_compile_s": 5.5})
    assert engine_stats.read(ctx, ["programs_built"]) == 80.0
    assert engine_stats.read(ctx, ["programs_built"], "delta") == 3.0
    assert engine_stats.read(
        ctx, ["program_trace_s", "program_compile_s", "program_lower_s"]
    ) == 25.5                                   # a missing field adds 0
    assert ctx.notes["engine_stats"] == {"program_trace_s": 20.0,
                                         "program_compile_s": 5.5}
    assert engine_stats.read(ctx, ["not_kept"]) is None
    assert engine_stats.read(make_ctx(), ["programs_built"]) is None
    with pytest.raises(ValueError):
        engine_stats.read(ctx, ["programs_built"], "mean")


def test_round_fields_mean_and_share():
    rec = lambda **kw: types.SimpleNamespace(**kw)     # noqa: E731
    ctx = make_ctx(rounds=[rec(blocked_on_pages=0, pool_used_pages=10),
                           rec(blocked_on_pages=2, pool_used_pages=30),
                           rec(blocked_on_pages=1, pool_used_pages=50),
                           rec(blocked_on_pages=0, pool_used_pages=70)])
    assert round_fields.read(ctx, "blocked_on_pages", "share_pct") == 50.0
    assert round_fields.read(ctx, "pool_used_pages") == 40.0
    # a program whose records lack the field: nothing to read
    assert round_fields.read(ctx, "plan_ms") is None
    assert round_fields.read(make_ctx(rounds=[]), "plan_ms") is None
    with pytest.raises(ValueError):
        round_fields.read(ctx, "pool_used_pages", "max")


def test_stage_roofline_divides_a_stages_least_time_by_its_device_time(
        scoped_ctx):
    """On the recorded trace: the fixture's program is a toy, so only the
    arithmetic is held — least time of the stage's own bytes and
    operations over the stage's device time, a stage without time left
    out."""
    from benchmarks.harness import costs
    from benchmarks.readers import stage_roofline
    model = {"vocab_size": 32000, "hidden_size": 512,
             "intermediate_size": 1024, "num_layers": 2, "num_heads": 8,
             "num_kv_heads": 8, "head_dim": 64}
    scoped_ctx.cell.config = {"model": model, "weight_quant": "int8"}
    scoped_ctx.peaks = costs.peaks("TPU v5 lite")
    assert stage_roofline.read(scoped_ctx, "attn", "(^|/)attn(/|$)",
                               DECODE) is None        # no rows stamped
    scoped_ctx.trace_t0, scoped_ctx.trace_t1 = 0.0, 1.0
    stream = types.SimpleNamespace(first_token_time=-1.0, finish_time=2.0,
                                   token_ids=[5] * 30, finish_reason="length")
    from benchmarks.harness.loadgen import Row
    from benchmarks.harness.traffic import Request
    scoped_ctx.rows = [Row(Request(i, [3] * 100, 30, 1), 0.0, 0.0,
                           stream=stream) for i in range(4)]
    for stage in costs.STAGES:
        scope = f"(^|/){stage}(/|$)"
        share = stage_roofline.read(scoped_ctx, stage, scope, DECODE)
        note = scoped_ctx.notes["stage_roofline"][stage]
        ms = device_scope.read(scoped_ctx, scope, DECODE)
        assert note["stage_ms"] == ms and share > 0
        rows, kv = scoped_ctx.mean_occupancy()
        assert rows == 4 and kv == pytest.approx(4 * 115.0, rel=0.01)
        least = costs.least_seconds(costs.decode_stage(
            model, "int8", stage, rows, kv), scoped_ctx.peaks)
        assert share == pytest.approx(100 * least["seconds"] * 1e3 / ms)
        assert note["bound"] == least["bound"]
    assert stage_roofline.read(scoped_ctx, "attn", "no_such_scope",
                               DECODE) is None


# ------------------------------------------------- the entries themselves


NEW = {"decode_attn_ms": "device_scope", "decode_mlp_ms": "device_scope",
       "decode_tail_ms": "device_scope", "plan_ms_per_round": "host_spans",
       "dispatch_ms_per_program": "host_spans",
       "setup_programs": "engine_stats", "setup_program_s": "engine_stats",
       "pool_blocked_rounds_pct": "round_fields",
       "decode_attn_roofline": "stage_roofline",
       "decode_mlp_roofline": "stage_roofline",
       "decode_tail_roofline": "stage_roofline"}


@pytest.mark.parametrize("name,reader", sorted(NEW.items()))
def test_new_metric_files_name_their_reader(name, reader):
    spec = Spec()
    m = spec.layer_metric(name)
    assert m["reader"] == reader
    entries = [e for e in spec.doc["per_layer"]
               if spec.layer_metric_file(e["name"]) == name]
    assert entries, name
    for e in entries:
        assert (e["unit"], e["better"], e["source"], e["layer"]) == (
            m["unit"], m["better"], m["source"], m["layer"])
        for cell in e["workloads"]:
            reports = spec.cell(cell).workload["reports"]
            assert e["moves"] in reports, (e["name"], cell)


def test_every_cell_reports_the_new_entries_the_issue_lists():
    spec = Spec()
    per_cell = {c: {m["name"] for m in spec.cell(c).per_layer}
                for c in spec.cell_names()}
    cs, db, rp = (per_cell["nemotron-8b-chat.chat-steady"],
                  per_cell["mixtral-8x7b-instruct.decode-batch"],
                  per_cell["nemotron-8b-chat.rag-prefill"])
    common = {"setup_programs", "setup_program_s"}
    decode = {"decode_attn_ms", "decode_mlp_ms", "decode_tail_ms",
              "plan_ms_per_round", "dispatch_ms_per_program",
              "decode_attn_roofline", "decode_mlp_roofline",
              "decode_tail_roofline"}
    assert common | decode <= cs and "pool_blocked_rounds_pct" not in cs
    assert common | decode | {"pool_blocked_rounds_pct"} <= db
    assert common | {"tput.plan_ms_per_round", "pool_blocked_rounds_pct",
                     "tput.dispatch_ms_per_program"} <= rp
    assert not decode & rp
