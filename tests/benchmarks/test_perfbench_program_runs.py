"""``benchmarks/readers/program_runs.py``: hand-built windows of round
records and their programs with known answers, the five entries that
read it STAGED beside this file (``program_runs/per_layer.json`` and
``program_runs/layer_metrics/``: ``BENCHMARK.json`` cannot hold them
until ``test_perfbench_costs_kda_latent.py``'s pin of the last six
entries is loosened, see ``PERF.md`` §7), and the rehearsal cell traced
on the CPU with them laid over a copy of its data.

``stage(dest)`` lays the staged files over a copy of the benchmark's
data: ``run.py --benchmark-json <dest>/BENCHMARK.json --data
<dest>/benchmarks`` then reports the five in the cells they list."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmarks.harness.context import Context, read_layer_metric
from benchmarks.harness.spec import Spec
from benchmarks.readers import program_runs
from generativeaiexamples_tpu.obs.rounds import ProgramRun, RoundRecorder

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
STAGED = os.path.join(HERE, "program_runs")
NEW = ("chunk_program_ms_per_ktok", "extend_program_ms",
       "decode_program_ms_per_step", "tput.decode_program_ms_per_step",
       "device_starved_pct")
FILES = tuple(n for n in NEW if not n.startswith("tput."))


def staged_entries():
    with open(os.path.join(STAGED, "per_layer.json")) as f:
        return json.load(f)


def stage(dest):
    """``BENCHMARK.json`` with the staged entries APPENDED and the
    benchmark's data files with the staged four beside them, under
    ``dest``; nothing of the repo's is written."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"] += staged_entries()
    data = os.path.join(str(dest), "benchmarks")
    for kind in ("configs", "traffic", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", kind),
                        os.path.join(data, kind), dirs_exist_ok=True)
    shutil.copytree(os.path.join(STAGED, "layer_metrics"),
                    os.path.join(data, "layer_metrics"), dirs_exist_ok=True)
    bench = os.path.join(str(dest), "BENCHMARK.json")
    with open(bench, "w") as f:
        f.write(json.dumps(doc, indent=1) + "\n")
    return bench, data


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return Spec(*stage(tmp_path_factory.mktemp("staged")))


def run(name, launch0, launch1, done, prev_done, tokens=512, padded=512,
        rows=1, steps=0, window=0):
    p = ProgramRun(name, tokens, padded, rows, steps, window, launch0)
    p.t_launch1, p.t_done, p.t_prev_done = launch1, done, prev_done
    return p


def rnd(round_id, programs, grants=(), active_decodes=0, waiting=0):
    return types.SimpleNamespace(
        round_id=round_id, programs=programs, grants=list(grants),
        active_decodes=active_decodes, waiting_slot=waiting,
        waiting_pages=0, waiting_budget=0, prefill_ungranted=0, done=True)


def window():
    """Ten seconds, six rounds. Times in seconds from 100."""
    T = 100.0
    return [
        # A's first two chunks, queued behind each other: 100 + 120 ms
        rnd(0, [run("extend", T, T + .001, T + .101, 0.0),
                run("extend", T + .001, T + .002, T + .221, T + .101)],
            grants=[("A", 512), ("A", 512)]),
        # A's short last chunk (300 of 512) after 9 ms with NOTHING
        # queued though A was live: launch ends at .230, service 50 ms
        rnd(1, [run("final", T + .225, T + .230, T + .280, T + .221,
                    tokens=300)], grants=[("A", 300)]),
        # a decode round of 8 steps, 2 rows, queued at once: 80 ms
        rnd(2, [run("decode_round", T + .231, T + .232, T + .360, T + .280,
                    tokens=16, padded=16, rows=2, steps=8)],
            active_decodes=2),
        # half a second with no request at all, then B arrives: a
        # one-shot prompt of 200 tokens, 40 ms
        rnd(3, [run("prefill_insert", T + .860, T + .861, T + .901,
                    T + .360, tokens=200, padded=512)],
            grants=[("B", 200)]),
        # four prompts' chunks in one program of four rows, queued
        # behind it: 400 ms ...
        rnd(4, [run("extend_rows", T + .870, T + .871, T + 1.301, T + .901,
                    tokens=2048, padded=2048, rows=4)],
            grants=[(x, 512) for x in "CDEF"], active_decodes=1),
        # ... and a decode round of 4 steps launched 2 ms after it was
        # done, while a request waited for a slot: 48 ms
        rnd(5, [run("decode_round", T + 1.301, T + 1.303, T + 1.351,
                    T + 1.301, tokens=4, padded=4, rows=1, steps=4)],
            waiting=1),
    ]


def make_ctx(rounds, config=None):
    cell = types.SimpleNamespace(name="hand", config=config or {
        "engine": {"max_prefill_bucket": 512}})
    return Context(cell=cell, rows=[], t0=100.0, t_end=110.0,
                   drain_limit_s=5.0, rounds=rounds)


@pytest.mark.parametrize("name,want", [
    # (100 + 120 + 50 + 40 + 400) ms over 512 + 512 + 300 + 200 + 2048
    ("chunk_program_ms_per_ktok", 710.0 / 3572 * 1000),
    # the two whole one-row non-final chunks: 100 and 120 ms (the final,
    # the one-shot and the program of four rows are other programs);
    # the harness's percentile takes the lower of two
    ("extend_program_ms", 100.0),
    # (80 + 48) ms over 8 + 4 steps
    ("decode_program_ms_per_step", 128.0 / 12),
    ("tput.decode_program_ms_per_step", 128.0 / 12),
    # 9 ms before A's last chunk + 2 ms before the last round, of 10 s;
    # the half second before B's first grant had no request in it
    ("device_starved_pct", 100.0 * 0.011 / 10.0),
])
def test_each_entry_on_a_hand_built_window(spec, name, want):
    ctx = make_ctx(window())
    m = spec.layer_metric(name)
    assert m["reader"] == "program_runs"
    assert read_layer_metric(ctx, m) == pytest.approx(want, rel=1e-9)
    note = ctx.notes["program_runs"]
    assert note["programs"] == 7
    assert note["by_name"]["extend"]["n"] == 2
    assert note["by_name"]["decode_round"]["steps"] == 12
    assert note["starved_ms"]["n"] == 2
    assert note["starved_ms"]["sum"] == pytest.approx(11.0)
    assert note["longest_launch"]["name"] == "final"
    assert note["longest_launch"]["launch_ms"] == pytest.approx(5.0)
    assert "closure" not in note            # no trace
    json.dumps(note)


def test_median_takes_whole_buckets_of_one_row_only():
    rounds = window()
    # a one-row extend of a smaller shape does not count
    rounds[0].programs += [
        run("extend", 100.002, 100.003, 100.222, 100.221, tokens=256,
            padded=256),
        run("extend", 100.003, 100.004, 100.352, 100.222)]
    ctx = make_ctx(rounds)
    assert program_runs.read(ctx, "median_ms", names=["extend"],
                             where={"rows": 1, "padded": "largest"}) \
        == pytest.approx(120.0)             # 100, 120, 130
    assert program_runs.read(ctx, "median_ms", names=["extend"]) \
        == pytest.approx(100.0)             # 1, 100, 120, 130
    # without the configuration's word, the largest shape seen a row
    ctx = make_ctx(rounds, config={"engine": {}})
    assert program_runs.largest_bucket(ctx, program_runs.runs_of(ctx)) == 512


def test_a_program_without_the_record_reads_nothing(spec):
    """The parent's rounds carry no ``programs``: every entry is left
    out, and none raises."""
    old = [types.SimpleNamespace(round_id=i, grants=[], active_decodes=1,
                                 done=True) for i in range(3)]
    ctx = make_ctx(old)
    for name in NEW:
        assert read_layer_metric(ctx, spec.layer_metric(name)) is None
    assert "program_runs" not in ctx.notes
    assert read_layer_metric(make_ctx([]), spec.layer_metric(NEW[0])) \
        is None


def test_nothing_that_matches_reads_nothing():
    ctx = make_ctx([rnd(0, [run("decode_round", 100.0, 100.001, 100.1, 0.0,
                                tokens=8, padded=8, steps=8)],
                        active_decodes=1)])
    assert program_runs.read(ctx, "ms_per_ktok", names=["extend"]) is None
    assert program_runs.read(ctx, "median_ms", names=["extend"],
                             where={"rows": 1}) is None
    assert program_runs.read(ctx, "ms_per_step",
                             names=["decode_round"]) == pytest.approx(
        (100.1 - 100.001) * 1e3 / 8)
    assert program_runs.read(ctx, "starved_pct") == 0.0
    with pytest.raises(ValueError):
        program_runs.read(ctx, "mean_ms")


def test_an_unfinished_program_is_left_out():
    rounds = window()
    rounds[5].programs.append(run("decode_round", 101.4, 101.401, 0.0, 0.0,
                                  steps=8))
    ctx = make_ctx(rounds)
    assert len(program_runs.runs_of(ctx)) == 7


def test_the_reader_reads_what_the_recorder_writes():
    """The same arithmetic over records the recorder itself finalised."""
    rec = RoundRecorder(cap=8)
    T = 50.0
    r = rec.begin(engine_tag="t", active_decodes=1, decode_steps=4)
    d = rec.launch(r, "decode_round", tokens=4, padded=4, rows=1, steps=4,
                   t_launch0=T)
    e = rec.launch(r, "extend", tokens=512, padded=512, rows=1, window=8,
                   t_launch0=T + .001)
    d.t_launch1, e.t_launch1 = T + .001, T + .002
    rec.seal(r, parts=2, prefill_tokens=512)
    rec.complete_part(r, tokens=4, program=d, t_done=T + .041)
    rec.complete_part(r, program=e, t_done=T + .141)
    assert r.done
    ctx = make_ctx(rec.records())
    assert program_runs.read(ctx, "ms_per_step", names=["decode_round"]) \
        == pytest.approx(10.0)
    assert program_runs.read(ctx, "ms_per_ktok", names=["extend"]) \
        == pytest.approx(100.0 / 0.512)
    assert ctx.notes["program_runs"]["longest_launch"][
        "done_during_launch"] == 0


# ------------------------------------- beside a recorded device plane

# tests/benchmarks/fixtures/tpu_v5e_spans_scopes.xplane.pb: three rounds
# of one jit_decode_round and one jit_extend execution each, on the
# DEVICE's clock, ns. monotonic ns -> device ns is the median of the
# engine_round spans' (start - t_mono_ns) less align's offset
# (test_perfbench_request_life.py).
SHIFT_NS = -47683813945 - 1560298
EXECS = [(47833482, 48374748, 48376990, 48384738),
         (62154684, 62696682, 62699024, 62706802),
         (75076091, 75618409, 75620516, 75628120)]


def test_the_closure_pairs_each_execution_with_its_own_record(monkeypatch):
    """Programs stamped 2 us (decode) and 1 us (chunk) after their
    executions end, a program before the trace and one after it: each
    execution pairs with its own record, the sums stand side by side;
    the plane's first and last execution, which the trace's edges may
    cut, are left out."""
    fx = os.path.join(HERE, "fixtures", "tpu_v5e_spans_scopes.xplane.pb")
    monkeypatch.setattr(program_runs.trace, "find_xplane", lambda d: fx)

    def mono(device_ns):
        return (device_ns - SHIFT_NS) * 1e-9

    # an execution the trace does not hold, a second before it
    rounds = [rnd(0, [run("extend", mono(46.0e6) - 1.0, mono(46.0e6) - 1.0,
                          mono(46.9e6) - 1.0, 0.0)])]
    prev = rounds[0].programs[0].t_done
    for i, (d0, d1, c0, c1) in enumerate(EXECS):
        dec = run("decode_round", mono(d0) - .01, mono(d0) - .009,
                  mono(d1 + 2e3), prev, tokens=8, padded=8, steps=8)
        name = "extend_rows" if i == 1 else "extend"
        ext = run(name, mono(d0) - .009, mono(d0) - .008, mono(c1 + 1e3),
                  dec.t_done)
        prev = ext.t_done
        rounds.append(rnd(i + 1, [dec, ext], active_decodes=1))
    rounds.append(rnd(9, [run("decode_round", mono(80e6), mono(80e6),
                              mono(81e6), prev, steps=8)]))
    ctx = make_ctx(rounds)
    ctx.trace_t0, ctx.trace_t1 = mono(47e6), mono(76e6)
    got = program_runs.closure(ctx, program_runs.runs_of(ctx))
    mods = got["modules"]
    assert set(mods) == {"jit_decode_round", "jit_extend"}
    dec, ext = mods["jit_decode_round"], mods["jit_extend"]
    # the first decode round and the last chunk are the plane's edges
    assert (dec["executions"], dec["pairs"]) == (2, 2) == (
        ext["executions"], ext["pairs"])
    assert ext["programs"] == {"extend": 1, "extend_rows": 1}
    assert dec["device_ms"] == pytest.approx(
        sum(d1 - d0 for d0, d1, _, _ in EXECS[1:]) * 1e-6)
    assert ext["device_ms"] == pytest.approx(
        sum(c1 - c0 for _, _, c0, c1 in EXECS[:2]) * 1e-6)
    assert dec["stamp_late_ms"]["max"] == pytest.approx(0.002, abs=1e-4)
    assert ext["stamp_late_ms"]["p50"] == pytest.approx(0.001, abs=1e-4)
    # a chunk's service runs from the decode stamp (2 us late) to its
    # own (1 us late): the execution and the gap before it, less 1 us
    want = sum((c1 - d1) * 1e-6 - 0.001 for _, d1, _, c1 in EXECS[:2])
    assert ext["service_ms"] == pytest.approx(want, abs=1e-4)
    assert ext["service_over_device"] == pytest.approx(
        want / ext["device_ms"], abs=1e-2)
    assert got["clock_offset_residual_ms"] == pytest.approx(0.504261)
    assert got["late_stamps"] == {"over_ms": 5.0, "n": 0, "latest": []}
    # a stamp 6 ms late is named, with its round
    rounds[2].programs[0].t_done += 6e-3
    late = program_runs.closure(ctx, program_runs.runs_of(ctx))[
        "late_stamps"]
    assert late["n"] == 1 and late["latest"][0]["name"] == "decode_round"
    assert late["latest"][0]["round_id"] == 2
    assert late["latest"][0]["late_ms"] == pytest.approx(6.002, abs=1e-3)
    # where host_spans pairs nothing and gives no offset, the host's
    # profiler clock stands in: every stamp reads the offset later, the
    # pairs and the sums stay
    from benchmarks.readers import host_spans
    whole = host_spans.summary(fx)
    bare = {"spans": whole["spans"],
            "note": {k: v for k, v in whole["note"].items()
                     if k != "clock_offset_ms"}}
    monkeypatch.setattr(host_spans, "summary", lambda path: bare)
    loose = program_runs.closure(ctx, program_runs.runs_of(ctx))
    assert loose["clock_offset_residual_ms"] is None
    assert loose["modules"]["jit_extend"]["pairs"] == 2
    assert loose["modules"]["jit_extend"]["service_ms"] == pytest.approx(
        ext["service_ms"])
    assert loose["modules"]["jit_extend"]["stamp_late_ms"]["p50"] \
        == pytest.approx(0.001 + 1.560298, abs=1e-4)
    # no traced interval, no closure
    ctx.trace_t0 = None
    assert program_runs.closure(ctx, program_runs.runs_of(ctx)) is None


# ------------------------------------------- the staged entries


def test_the_staged_entries_append_to_the_benchmark_as_it_stands(spec):
    """What the driver asks of a PR's entries: every entry the benchmark
    has stays where it is, the five come after the last of them; and the
    benchmark itself holds none of them yet (its last six are pinned)."""
    ours = Spec()
    had = ours.doc["per_layer"]
    assert not {m["name"] for m in had} & set(NEW)
    per = spec.doc["per_layer"]
    assert per[:len(had)] == had
    assert [m["name"] for m in per[len(had):]] == list(NEW)
    assert {k: v for k, v in spec.doc.items() if k != "per_layer"} \
        == {k: v for k, v in ours.doc.items() if k != "per_layer"}
    for m in staged_entries():
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert any(x["layer"] == m["layer"] for x in had)


@pytest.mark.parametrize("m", staged_entries(), ids=lambda m: m["name"])
def test_a_staged_entry_and_its_file_agree(spec, m):
    """What ``test_perfbench_spec.py`` asks of an entry of the benchmark
    and its file, asked of each staged one before it moves in."""
    from benchmarks.harness import spec as spec_mod
    assert spec_mod.NAME_RE.match(m["name"]) \
        and spec_mod.UNIT_RE.match(m["unit"])
    assert m["source"] in spec_mod.SOURCES
    assert m["better"] in ("lower", "higher")
    f = spec.layer_metric(m["name"])
    assert set(f) - {"note"} == {"unit", "better", "source", "layer",
                                 "reader", "args"}
    for k in ("unit", "better", "source", "layer"):
        assert f[k] == m[k], k
    assert f["args"]["agg"] in ("median_ms", "ms_per_ktok", "ms_per_step",
                                "starved_pct")


def test_the_entries_list_no_cell_a_pin_keeps_out(spec):
    e2e = {m["name"]: set(m["workloads"]) for m in spec.doc["end_to_end"]
           if "workloads" in m}
    cells = set(spec.cell_names())
    for m in staged_entries():
        assert m["source"] == "program_span"
        assert set(m["workloads"]) <= cells
        assert not [w for w in m["workloads"]
                    if w.startswith(("ling-3.0-flash.", "kimi-k2-instruct."))]
        # every listed cell reports the end-to-end metric it moves
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
        assert spec.layer_metric(m["name"])["layer"] == m["layer"]
        glm = "glm-5.2.long-context-mixed-16" in m["workloads"]
        assert glm == ("xing4.0-29b-a4b.rag-prefill-16" in m["workloads"])
        for cell in m["workloads"]:
            assert m["name"] in [x["name"] for x in spec.cell(cell).per_layer]
    by = {m["name"]: m["workloads"] for m in staged_entries()}
    assert "mixtral-8x7b-instruct.decode-batch" \
        not in by["extend_program_ms"]      # its prompts are one program
    assert not set(by["decode_program_ms_per_step"]) \
        & set(by["tput.decode_program_ms_per_step"])


def test_each_file_is_data_over_the_one_reader(spec):
    for name in FILES:
        m = spec.layer_metric(name)
        assert m["reader"] == "program_runs" and m["source"] == "program_span"
        assert os.path.exists(os.path.join(STAGED, "layer_metrics",
                                           name + ".json"))
        # not among the benchmark's own files, which have to be used
        assert not os.path.exists(os.path.join(REPO, "benchmarks",
                                               "layer_metrics",
                                               name + ".json"))
    assert sorted(os.listdir(os.path.join(STAGED, "layer_metrics"))) \
        == sorted(n + ".json" for n in FILES)
    assert spec.layer_metric_file("tput.decode_program_ms_per_step") \
        == "decode_program_ms_per_step"


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
    cell = "tiny-dense.tiny-closed"
    for m in staged_entries():
        if m["name"] in FILES:
            shutil.copy(os.path.join(STAGED, "layer_metrics",
                                     m["name"] + ".json"),
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
         "--seed", str(2 ** 31 + 51), "--seconds", "3", "--trace", "1"],
        cwd=REPO, env=env, timeout=900, capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    return [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]


@pytest.mark.parametrize("name", FILES)
def test_rehearsal_reports_each_new_metric_as_a_number(rehearsed, name):
    m = rehearsed[-1]["metrics"]
    assert rehearsed[-1]["correct"] is True
    if name == "extend_program_ms" and name not in m:
        # the rehearsal's prompts (at most 96 tokens over a 64 bucket)
        # may hold no whole non-final chunk: then it is left out
        line = next(x for x in rehearsed if x.get("phase") == "window")
        ext = line["notes"]["program_runs"]["by_name"].get("extend")
        assert ext is None or ext["padded"] < 64 * ext["n"]
        return
    assert isinstance(m[name]["value"], float) and m[name]["value"] >= 0.0
    if name.endswith("_pct"):
        assert m[name]["value"] <= 100.0


def test_rehearsal_note_counts_every_program_of_the_window(rehearsed):
    line = next(x for x in rehearsed if x.get("phase") == "window")
    note = line["notes"]["program_runs"]
    by = note["by_name"]
    assert by["decode_round"]["n"] >= 1 and by["decode_round"]["steps"] >= 1
    assert sum(v["n"] for v in by.values()) == note["programs"]
    chunk = sum(v["tokens"] for k, v in by.items()
                if k in ("prefill_insert", "extend", "extend_rows", "final"))
    # the rounds that began in the window, whose programs these are,
    # granted the same tokens
    assert chunk > 0 and by["decode_round"]["tokens"] >= \
        by["decode_round"]["steps"]
    assert set(note["longest_launch"]) >= {"name", "launch_ms",
                                           "done_during_launch"}
    assert note["longest_launch"]["done_during_launch"] is not None
