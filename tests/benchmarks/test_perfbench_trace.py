"""The reduction from a profiler trace to numbers: on a hand-made
XSpace (exact sums) and on a small trace recorded on a TPU v5e."""

import glob
import os

import pytest

from benchmarks.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
FIX = os.path.join(HERE, "fixtures")


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    with open(os.path.join(FIX, "synthetic_trace.textproto")) as f:
        return trace.planes_of(ProfileData.from_text_proto(f.read()))


def test_merge_is_a_union():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (3, 3), (6, 9)]) == [
        (0, 3), (5, 9)]


def test_module_name_strips_the_id():
    assert trace.module_name("jit_decode_round(7351)") == "jit_decode_round"
    assert trace.module_name("jit_final") == "jit_final"


def test_op_name_keeps_what_stands_before_the_equals_sign():
    line = ("%fusion.3 = bf16[16,4096]{1,0:T(8,128)(2,1)} fusion(bf16[16]"
            " %p), kind=kLoop, calls=%fused_computation")
    assert trace.op_name(line) == "fusion.3"
    assert trace.op_name("custom-call.2") == "custom-call.2"


def test_breakdown_lists_what_a_loop_holds_not_the_loop():
    p = trace.Plane("/device:TPU:0", {
        "XLA Modules": [("jit_decode_round(1)", 0.0, 100.0)],
        "XLA Ops": [("%while.7 = (s32[]) while(...)", 0.0, 100.0),
                    ("%fusion.1 = f32[] fusion()", 10.0, 30.0),
                    ("%conditional.2 = f32[] conditional()", 50.0, 40.0),
                    ("%custom-call.5 = f32[] custom-call()", 55.0, 20.0)]})
    r = trace.reduce([p])
    assert r.busy_s == pytest.approx(100e-9)
    names = [n for n, _ in r.breakdown()["device_ops"]]
    assert names == ["fusion.1", "custom-call.5"]


def test_busy_is_the_union_of_operations(synthetic):
    r = trace.reduce(synthetic)
    d = r.devices[0]
    assert d.plane == "/device:TPU:0"
    assert d.busy_s == pytest.approx(210e-6)      # not 220: ops overlap
    assert d.span_s == pytest.approx(400e-6)
    assert r.busy_s == pytest.approx(210e-6)


def test_time_and_count_per_module(synthetic):
    r = trace.reduce(synthetic, window_s=500e-6)
    assert r.module_seconds(r"^jit_decode_round$") == pytest.approx(200e-6)
    assert r.module_count(r"^jit_decode_round$") == 2
    assert r.module_seconds(r"^jit_(extend|final)$") == pytest.approx(50e-6)
    assert r.module_seconds("nothing") == 0 and r.module_count("nope") == 0
    assert r.window_s == pytest.approx(500e-6)


def test_idle_gaps_are_labelled_by_their_neighbours(synthetic):
    d = trace.reduce(synthetic).devices[0]
    gaps = dict(d.gaps)
    assert gaps["jit_extend->jit_decode_round"] == pytest.approx(100e-6)
    assert gaps["jit_decode_round->jit_extend"] == pytest.approx(80e-6)
    assert gaps["inside jit_decode_round"] == pytest.approx(10e-6)
    assert d.gaps[0][1] >= d.gaps[-1][1]          # longest first


def test_breakdown_has_the_contracts_shape(synthetic):
    b = trace.reduce(synthetic, window_s=500e-6).breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert b["device_ops"][0] == ["custom-call.2", pytest.approx(90e-6)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    labels = [g[0] for g in b["idle_gaps"]]
    assert "window edges (before first / after last op)" in labels
    total_idle = sum(g[1] for g in b["idle_gaps"])
    assert total_idle == pytest.approx(500e-6 - 210e-6)


def test_a_trace_without_a_device_plane_reduces_to_nothing(synthetic):
    host_only = [p for p in synthetic if not p.name.startswith("/device")]
    assert trace.reduce(host_only) is None
    renamed = [trace.Plane("/device:GPU:0", p.lines) for p in synthetic]
    assert trace.reduce(renamed) is None


def test_modules_alone_give_busy_time(synthetic):
    p = synthetic[0]
    only = trace.Plane(p.name, {trace.MODULES_LINE:
                                p.lines[trace.MODULES_LINE]})
    assert trace.reduce([only]).busy_s == pytest.approx(250e-6)


def test_describe_lists_planes_and_lines(synthetic):
    d = trace.describe(synthetic)
    assert d[0] == {"plane": "/device:TPU:0",
                    "lines": {"XLA Modules": 3, "XLA Ops": 5}}


def test_find_xplane_picks_the_newest(tmp_path):
    assert trace.find_xplane(str(tmp_path)) is None
    for i, run in enumerate(("a", "b")):
        d = tmp_path / "plugins" / "profile" / run
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(b"x")
        os.utime(d / "vm.xplane.pb", (i, i))
    assert trace.find_xplane(str(tmp_path)).endswith("b/vm.xplane.pb")


# ------------------------------------------------- the recorded TPU trace


RECORDED = sorted(glob.glob(os.path.join(FIX, "*.xplane.pb")))


@pytest.mark.skipif(not RECORDED, reason="no recorded trace committed")
def test_recorded_tpu_trace_reduces():
    """benchmarks/record_trace_fixture.py on a TPU v5e: 3 executions of
    jit_decode_round and 2 of jit_extend, 20 ms apart."""
    planes = trace.load(RECORDED[0])
    r = trace.reduce(planes)
    assert r is not None and len(r.devices) == 1
    d = r.devices[0]
    assert d.module_n["jit_decode_round"] == 3
    assert d.module_n["jit_extend"] == 2
    assert 0 < d.busy_s <= d.span_s
    assert d.busy_s <= sum(d.module_s.values()) * 1.001
    assert d.op_s                                  # operations are named
    # the device idles between executions: the gaps carry module labels
    assert any("->" in label for label, _ in d.gaps)
    assert d.span_s > 0.05                         # five runs 20 ms apart
