"""``benchmarks/readers/request_life.py``: hand-built rows, span trees and
round records with known answers, and the rehearsal cell on the CPU with
the new metrics laid over a copy of its data."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmarks.harness.context import Context, read_layer_metric
from benchmarks.harness.loadgen import Row
from benchmarks.harness.spec import Spec
from benchmarks.harness.traffic import Request
from benchmarks.readers import request_life
from generativeaiexamples_tpu.obs.flight import Timeline

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
QUANTITIES = ("admit_wait_ms", "prefill_behind_decode_ms",
              "prefill_behind_chunks_ms", "gap_behind_prefill_pct")


def life(rid, submit, backlog, prefill, armed, first, finish, chunk_rounds,
         finish_reason="length", tokens=10):
    """A stream with a whole span tree: ``backlog`` is [(t0, cause)] and
    the request is pulled at its first entry."""
    tl = Timeline(rid)
    root = tl.enter(None, "request", submit, 0)
    st = tl.enter(None, "req_intake", submit, 0)
    for t0, cause in backlog:
        st = tl.enter(st, "req_backlog", t0, 0, cause=cause)
    st = tl.enter(st, "req_prefill", prefill, chunk_rounds[0], n=1024, m=0)
    for k in chunk_rounds:
        tl.child(st, "req_chunk", prefill, prefill + 0.001, k, 512, 512)
    st = tl.enter(st, "req_first_token", armed, chunk_rounds[-1])
    st = tl.enter(st, "req_decode", first, chunk_rounds[-1])
    st.close(finish, 6)
    root.close(finish, 6)
    return types.SimpleNamespace(
        request_id=rid, timeline=tl, submit_time=submit,
        first_token_time=first, finish_time=finish,
        token_ids=[5] * tokens, finish_reason=finish_reason)


def rnd(round_id, t_start, t_done, grants=(), decode_slots=0, t_parts=None,
        emit_ms=0.5):
    return types.SimpleNamespace(
        round_id=round_id, t_start=t_start, t_done=t_done, done=True,
        grants=list(grants), decode_slots=decode_slots,
        t_parts=[t_done] if t_parts is None else t_parts, emit_ms=emit_ms,
        kind="x", decode_steps=8 * bool(decode_slots), queued_ahead=1,
        waiting_slot=0, waiting_pages=1, waiting_budget=0,
        prefill_ungranted=0, blocked_on_pages=0, dispatch_ms=2.0,
        harvest_wait_ms=1.0)


def row(uid, stream, max_tokens=10):
    return Row(Request(uid, [3] * 1024, max_tokens, 1),
               due_t=stream.submit_time - 0.002,
               send_t=stream.submit_time - 0.001, stream=stream)


ROUNDS = [
    rnd(0, 10.0, 10.4, [("A", 512)]),                 # A's first chunk
    rnd(1, 10.1, 10.5, decode_slots=1),               # queued behind it
    rnd(2, 10.2, 10.9, [("A", 512), ("B", 512)]),     # halves: A then B
    rnd(3, 10.3, 11.3, [("B", 512)], decode_slots=1,  # decode | B's chunk
        t_parts=[11.0, 11.3]),
    rnd(4, 11.2, 11.5, decode_slots=1),
    rnd(5, 11.3, 11.9, [("C", 512)], decode_slots=1),  # no stamp to divide
    rnd(6, 12.0, 12.1, decode_slots=1, emit_ms=2.5),   # 0.1 s nobody covers
]


def make_ctx(rows, rounds=ROUNDS):
    return Context(cell=types.SimpleNamespace(name="hand"), rows=rows,
                   t0=9.0, t_end=12.5, drain_limit_s=5.0, rounds=rounds)


@pytest.fixture
def ctx():
    a = life("A", 9.9, [(9.95, "slot"), (9.98, "budget")], 10.0, 10.6,
             10.75, 12.1, [0, 2])
    b = life("B", 10.0, [(10.05, "pages")], 10.5, 11.25, 11.35, 12.05,
             [2, 3])
    # C finishes after the window's end; D was cut short: both left out
    c = life("C", 10.2, [(10.25, "slot")], 11.5, 11.9, 12.0, 13.0, [5])
    d = life("D", 10.2, [(10.25, "slot")], 11.5, 11.9, 12.0, 12.2, [5],
             finish_reason="cancelled", tokens=3)
    return make_ctx([row(1, a), row(2, b), row(3, c), row(4, d)])


@pytest.mark.parametrize("quantity,want", [
    # A waits 100 ms, B 500
    ("admit_wait_ms", 300.0),
    # A: round 1's 0.1 s; B: round 3's decode part 0.1 + 0.05 of round 4
    ("prefill_behind_decode_ms", 125.0),
    # A: 0.05 s of B's half of round 2; B: A's half, 0.2 s
    ("prefill_behind_chunks_ms", 125.0),
    # req_decode: A 1.35 s with 0.4 decode, B 0.7 s with 0.2
    ("gap_behind_prefill_pct", 100.0 * (1 - 0.6 / 2.05)),
])
def test_each_quantity_on_a_hand_built_window(ctx, quantity, want):
    assert request_life.read(ctx, quantity) == pytest.approx(want, abs=1e-6)
    assert ctx.notes["request_life"]["requests"] == 2


def test_emit_ms_per_round_is_a_data_file_over_round_fields(ctx):
    spec = Spec()
    m = spec.layer_metric("emit_ms_per_round")
    assert m["reader"] == "round_fields"
    assert spec.layer_metric("tput.emit_ms_per_round") == m
    want = (6 * 0.5 + 2.5) / 7
    assert read_layer_metric(ctx, m) == pytest.approx(want)


def test_the_note_books_every_second_and_closes(ctx):
    request_life.read(ctx, "admit_wait_ms")
    note = json.loads(json.dumps(ctx.notes["request_life"]))   # JSON-ready
    ttft, dec = note["booked_ms"]["ttft"], note["booked_ms"]["req_decode"]
    # means over the two requests, ms
    assert ttft == pytest.approx({"own": 550.0, "chunks": 125.0,
                                  "decode": 125.0, "mixed": 0.0,
                                  "idle": 0.0}, abs=1e-6)
    assert dec == pytest.approx({"own": 0.0, "chunks": 225.0,
                                 "decode": 300.0, "mixed": 400.0,
                                 "idle": 100.0}, abs=1e-6)
    cl = note["closure"]
    assert cl["life_err_ms_max"] < 1e-6 and cl["ttft_err_ms_max"] < 1e-6
    total = 2.2 + 2.05
    assert note["request_seconds"] == pytest.approx(total)
    assert cl["mixed_pct"] == pytest.approx(100 * 0.8 / total)
    assert cl["idle_pct"] == pytest.approx(100 * 0.2 / total)
    st = note["states"]
    assert st["req_backlog:slot"]["p50"] == pytest.approx(30.0)
    assert st["req_backlog:budget"]["n"] == 1
    assert st["req_backlog:pages"]["max"] == pytest.approx(450.0)
    assert st["req_backlog"]["n"] == 2
    assert st["req_backlog"]["share_pct"] == pytest.approx(
        100 * 0.5 / total)
    assert sum(st[k]["share_pct"] for k in request_life.STATES) \
        == pytest.approx(100.0)
    assert note["ttft_ms"]["p50"] == pytest.approx(850.0)
    assert note["ttft_ms"]["window_line_p50_same_requests"] \
        == pytest.approx(852.0)
    # tpot of A is 150 ms, of B 77.8: p50 the lower; x the decode share
    assert note["own_step_ms"] == pytest.approx(
        note["tpot_p50_ms_same_requests"] * 0.6 / 2.05)
    # rounds 1, 3 (its decode part), 4 and 6: 0.5 s of 32 decode steps
    assert note["window_step_ms"] == pytest.approx(500.0 / 32)


def test_a_mixed_round_divides_at_its_first_part_or_is_booked_mixed():
    pieces = request_life.service_pieces(ROUNDS, {})
    by_round = [(round(a, 3), round(b, 3), what, who)
                for a, b, what, who in pieces]
    assert (10.9, 11.0, "decode", 8) in by_round
    assert (11.0, 11.3, "chunk", "B") in by_round
    assert (11.5, 11.9, "mixed", None) in by_round
    assert (10.5, 10.7, "chunk", "A") in by_round
    # no piece starts before the round's own dispatch (idle 11.9 - 12.0)
    assert (12.0, 12.1, "decode", 8) in by_round
    assert all(a < b for a, b, _, _ in pieces)
    assert all(x[1] <= y[0] + 1e-12 for x, y in zip(pieces, pieces[1:]))


def test_chunks_of_a_round_share_it_by_their_padded_tokens():
    r = rnd(0, 1.0, 2.0, [("A", 100), ("B", 512)])
    even = request_life.service_pieces([r], {("A", 0): 512, ("B", 0): 512})
    assert [round(p[1], 6) for p in even] == [1.5, 2.0]
    by_tokens = request_life.service_pieces([r], {})
    assert by_tokens[0][1] == pytest.approx(1.0 + 100 / 612)


def test_the_slowest_request_comes_with_its_spans_and_rounds(ctx):
    request_life.read(ctx, "admit_wait_ms")
    slow = ctx.notes["request_life"]["slowest"]
    assert slow["request_id"] == "B"             # 1350 ms against A's 850
    assert slow["ttft_ms"] == pytest.approx(1350.0)
    assert [s["span"] for s in slow["spans"]][:3] == [
        "request", "req_intake", "req_backlog"]
    assert slow["spans"][2]["cause"] == "pages"
    ids = [r[slow["round_fields"].index("round_id")] for r in slow["rounds"]]
    assert ids == [0, 1, 2, 3]                   # to the arming round
    own = [r[slow["round_fields"].index("own_tokens")]
           for r in slow["rounds"]]
    assert own == [0, 0, 512, 512] and slow["rounds_omitted"] == 0


def test_a_program_without_the_spans_or_the_stamps_reads_none(ctx):
    class OldTimeline:                      # the parent's: events only
        def events_snapshot(self):
            return []
    for r in ctx.rows:
        r.stream.timeline = OldTimeline()
    assert all(request_life.read(ctx, q) is None for q in QUANTITIES)
    assert "request_life" not in ctx.notes


def test_rounds_without_t_done_read_none(ctx):
    old = [types.SimpleNamespace(**{k: v for k, v in vars(r).items()
                                    if k not in ("t_done", "t_parts")})
           for r in ROUNDS]
    ctx.rounds = old
    assert request_life.read(ctx, "gap_behind_prefill_pct") is None


def test_no_request_finished_inside_the_window_reads_none(ctx):
    ctx.t_end = 11.0
    assert request_life.read(ctx, "admit_wait_ms") is None


def test_an_open_span_leaves_the_request_out(ctx):
    ctx.rows[1].stream.timeline.spans[-1].t1 = None
    assert request_life.read(ctx, "admit_wait_ms") == pytest.approx(100.0)
    assert ctx.notes["request_life"]["requests"] == 1


# ------------------------------------- beside a recorded device plane

# tests/benchmarks/fixtures/tpu_v5e_spans_scopes.xplane.pb: three rounds
# of decode steps and one 200-token chunk each (req-0, req-1, req-2).
# Its second round on the DEVICE's clock, ns: jit_decode_round
# 62154684-62696682, jit_extend (req-1's, by the FIFO pairing)
# 62699024-62706802. monotonic ns -> device ns is the median of the
# engine_round spans' (start - t_mono_ns) less align's offset.
SHIFT_NS = -47683813945 - 1560298


@pytest.mark.parametrize("rid,own_ms,chunks_ms", [
    ("req-1", 0.007778, 0.0),       # the chunk is its own
    ("req-9", 0.0, 0.007778),       # the same interval, another's chunk
])
def test_the_traced_interval_is_read_off_the_device_plane(
        monkeypatch, rid, own_ms, chunks_ms):
    fx = os.path.join(HERE, "fixtures", "tpu_v5e_spans_scopes.xplane.pb")
    monkeypatch.setattr(request_life.trace, "find_xplane", lambda d: fx)
    a, b = ((d - SHIFT_NS) * 1e-9 for d in (62.0e6, 62.71e6))
    mid = (a + b) / 2
    spans = {"req_prefill": [types.SimpleNamespace(t0=a, t1=mid)],
             "req_first_token": [types.SimpleNamespace(t0=mid, t1=b)]}
    booked = {"ttft": {"own": own_ms * 1e-3, "chunks": chunks_ms * 1e-3,
                       "decode": 0.000542}}
    r = types.SimpleNamespace(stream=types.SimpleNamespace(request_id=rid))
    ctx = types.SimpleNamespace(trace_t0=a - 1.0, trace_t1=b + 1.0,
                                cell=types.SimpleNamespace(name="hand"))
    got = request_life.traced_check(ctx, [(r, spans, booked)])
    assert got["requests"] == 1
    assert got["by_device_ms"] == pytest.approx(
        {"own": own_ms, "chunks": chunks_ms, "decode": 0.541998}, abs=1e-6)
    assert got["by_rounds_ms"]["decode"] == pytest.approx(0.542)
    assert got["disagree_pct"]["decode"] == pytest.approx(
        100 * (0.542 - 0.541998) / 0.541998, abs=1e-3)
    # each paired chunk's wait from its dispatch's end to its device start
    assert got["chunk_queue_ahead_ms"]["n"] == 3
    # a request whose prefill began before the trace did is left out
    ctx.trace_t0 = a + 1e-6
    assert request_life.traced_check(ctx, [(r, spans, booked)]) == {
        "requests": 0}


# ----------------------------------------- the rehearsal cell, on the CPU


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The tests' rehearsal data copied aside with this PR's metrics laid
    over it (the rehearsal's own files are the benchmark's and are not
    edited), run traced on the CPU."""
    data = tmp_path_factory.mktemp("rehearsal")
    src = os.path.join(HERE, "rehearsal")
    shutil.copytree(src, data, dirs_exist_ok=True)
    with open(os.path.join(src, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        ours = json.load(f)
    cell = "tiny-dense.tiny-closed"
    for m in ours["per_layer"]:
        file = Spec().layer_metric_file(m["name"])
        if file in QUANTITIES + ("emit_ms_per_round",) \
                and not m["name"].startswith("tput."):
            shutil.copy(os.path.join(REPO, "benchmarks", "layer_metrics",
                                     file + ".json"),
                        os.path.join(data, "layer_metrics"))
            doc["per_layer"].append(dict(m, workloads=[cell],
                                         moves="out_tok_per_s"))
    bench = os.path.join(data, "BENCHMARK.json")
    with open(bench, "w") as f:
        json.dump(doc, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--benchmark-json", bench, "--data", str(data), "--workload", cell,
         "--seed", str(2 ** 31 + 38), "--seconds", "3", "--trace", "1"],
        cwd=REPO, env=env, timeout=900, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    return [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]


@pytest.mark.parametrize("name", QUANTITIES + ("emit_ms_per_round",))
def test_rehearsal_reports_each_new_metric_as_a_number(rehearsed, name):
    m = rehearsed[-1]["metrics"]
    assert rehearsed[-1]["correct"] is True
    assert isinstance(m[name]["value"], float) and m[name]["value"] >= 0.0
    if name.endswith("_pct"):
        assert m[name]["value"] <= 100.0


def test_rehearsal_window_line_closes_and_names_its_slowest(rehearsed):
    window = next(ln for ln in rehearsed if ln.get("phase") == "window")
    note = window["notes"]["request_life"]
    assert note["requests"] >= 3
    assert note["closure"]["life_err_ms_max"] < 1e-3
    assert note["closure"]["ttft_err_ms_max"] < 1.0
    assert note["closure"]["mixed_pct"] < 5.0
    assert abs(note["ttft_ms"]["p50"]
               - note["ttft_ms"]["window_line_p50_same_requests"]) \
        <= note["ttft_ms"]["lateness_ms_max"] + 1e-6
    assert sum(note["states"][s]["share_pct"]
               for s in request_life.STATES if s in note["states"]) \
        == pytest.approx(100.0, abs=1e-6)
    slow = note["slowest"]
    assert slow["spans"][0]["span"] == "request" and slow["rounds"]
    assert len(slow["rounds"][0]) == len(slow["round_fields"])
