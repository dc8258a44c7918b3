"""Round telemetry (obs/rounds.py + engine wiring): recorder ring
semantics and thread safety, live-engine plan+execution records that
reconcile with engine.stats(), the /debug/rounds endpoint, online
step-cost calibration (budget convergence from a wrong prior), and the
drift gauge + slow-round dump under fault injection."""

import json
import logging
import threading
import time

import pytest

import jax
import jax.numpy as jnp
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.engine import (Engine, EngineConfig,
                                             SamplingParams)
from generativeaiexamples_tpu.engine.scheduler import (
    OnlineCalibrator, StepCostModel, derive_round_budget,
    online_calib_enabled)
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.obs.rounds import (PROGRAM_NAMES,
                                                 ROUND_METRICS,
                                                 RoundRecorder,
                                                 debug_rounds_response)
from generativeaiexamples_tpu.utils import faults

CFG = LlamaConfig(vocab_size=259 + 5, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position_embeddings=256)

PAGE = 16

_PARAMS = None


def _engine(**over):
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
    global _PARAMS
    cfg = dict(max_slots=2, max_input_length=64, max_output_length=16,
               prefill_buckets=(16, 32, 64), dtype="float32",
               page_size=PAGE, kv_pool_tokens=None, max_queue=64,
               steps_per_round=4)
    cfg.update(over)
    if _PARAMS is None:
        _PARAMS = llama.init_params(CFG, jax.random.key(3),
                                    dtype=jnp.float32)
    eng = Engine(_PARAMS, CFG, ByteTokenizer(), EngineConfig(**cfg))
    eng.rounds = RoundRecorder(cap=512)   # private ring per test
    return eng


# ------------------------------------------------------- recorder units


def test_ring_bounded_and_ids_monotone_across_reset():
    rec = RoundRecorder(cap=8)
    for _ in range(20):
        r = rec.begin(engine_tag="t")
        rec.seal(r, parts=0)   # zero-part seal finalizes immediately
    assert len(rec.records()) == 8        # bounded
    last_id = rec.records()[-1].round_id
    assert last_id == 19
    rec.reset()
    assert rec.records() == []
    r = rec.begin(engine_tag="t")
    # the id sequence continues — a reset shows as a gap, never a replay
    assert r.round_id == 20


def test_discard_removes_and_keeps_ids_monotone():
    rec = RoundRecorder(cap=8)
    a = rec.begin(engine_tag="t")
    b = rec.begin(engine_tag="t")
    rec.discard(a)
    assert [r.round_id for r in rec.records()] == [b.round_id]
    assert rec.begin(engine_tag="t").round_id == b.round_id + 1


def test_completion_order_is_commutative():
    """The harvest thread can outrun the scheduler's seal on short
    rounds: parts completed BEFORE seal() must still finalize."""
    rec = RoundRecorder(cap=8)
    r = rec.begin(engine_tag="t")
    rec.complete_part(r, tokens=4)         # harvest outran the seal
    assert not r.done
    rec.seal(r, parts=1, modeled_ms=1.0)
    assert r.done and r.tokens_emitted == 4
    # and the usual order: seal first, completion finalizes
    r2 = rec.begin(engine_tag="t")
    rec.seal(r2, parts=2, modeled_ms=1.0)
    rec.complete_part(r2, tokens=1)
    assert not r2.done
    rec.complete_part(r2, tokens=2, harvest_wait_ms=0.5)
    assert r2.done and r2.tokens_emitted == 3
    assert r2.harvest_wait_ms == pytest.approx(0.5)


def test_snapshot_aggregates_and_limit():
    rec = RoundRecorder(cap=32)
    for i in range(6):
        r = rec.begin(engine_tag="t", decode_steps=4, budget_tokens=32)
        r.decode_slots = 1
        if i % 2:
            r.prefill_tokens = PAGE
        rec.seal(r, parts=1, prefill_tokens=r.prefill_tokens,
                 modeled_ms=2.0)
        rec.complete_part(r, tokens=4)
    snap = rec.snapshot(limit=3)
    assert len(snap["rounds"]) == 3
    assert snap["retained"] == 6
    agg = snap["aggregates"]
    assert agg["rounds_completed"] == 6
    assert agg["tokens_emitted"] == 24
    assert agg["interleaved_share"] == pytest.approx(0.5)
    # newest first
    ids = [r["round_id"] for r in snap["rounds"]]
    assert ids == sorted(ids, reverse=True)
    json.dumps(snap)   # JSON-clean


def _launch(rec, r, name, t0, t1, **what):
    """One program launched over [t0, t1] (scheduler thread's half)."""
    what = dict(dict(tokens=4, padded=4, rows=1), **what)
    run = rec.launch(r, name, t_launch0=t0, **what)
    run.t_launch1 = t1
    return run


def test_shared_recorder_isolates_engines():
    """Multi-engine processes share the global recorder: one engine's
    completion must not truncate another's device-time estimate (the
    value feeds its calibrator), and snapshots filter by engine tag."""
    rec = RoundRecorder(cap=32)
    a = rec.begin(engine_tag="eA", decode_steps=4)
    b = rec.begin(engine_tag="eB", decode_steps=4)
    now = time.monotonic()
    pa = _launch(rec, a, "decode_round", now, now, steps=4)
    pb = _launch(rec, b, "decode_round", now, now, steps=4)
    rec.seal(a, parts=1, modeled_ms=1.0)
    rec.seal(b, parts=1, modeled_ms=1.0)
    t_sealed = max(a.t_dispatch_done, b.t_dispatch_done)
    time.sleep(0.05)
    rec.complete_part(a, tokens=4, program=pa)  # A completes first...
    time.sleep(0.05)
    rec.complete_part(b, tokens=4, program=pb)  # ...B's clock starts at
    # ITS launch end, not at A's completion: both device_ms cover their
    # own full ~0.05-0.1 s window.
    assert b.device_ms >= 90.0
    assert a.device_ms >= 45.0
    assert t_sealed > 0
    snap_a = rec.snapshot(limit=10, engine_tag="eA")
    assert [r["engine"] for r in snap_a["rounds"]] == ["eA"]
    assert snap_a["aggregates"]["rounds_completed"] == 1
    assert rec.snapshot(limit=10)["aggregates"]["rounds_completed"] == 2


# ------------------------------------------- a record a device program


@pytest.mark.parametrize("chunks", [1, 2, 3, 4])
def test_a_rounds_programs_sum_to_its_device_ms(chunks):
    """A round of one decode program and 1-4 chunk programs: each is
    stamped as its OWN readback returns, in the device's FIFO order; a
    program's service runs from the later of its launch end and the
    previous program's readback; the services sum to ``device_ms``."""
    rec = RoundRecorder(cap=8)
    T = time.monotonic()
    r = rec.begin(engine_tag="t", decode_steps=4)
    dec = _launch(rec, r, "decode_round", T, T + 0.001, tokens=8, padded=16,
                  rows=2, steps=4)
    names = ["extend_rows"] * (chunks == 4) + ["extend"] * (chunks - 1)
    names = names[:chunks - 1] + ["final"]
    runs = []
    for i, name in enumerate(names):
        rows = 4 if name == "extend_rows" else 1
        runs.append(_launch(rec, r, name, T + 0.002 + i * 0.001,
                            T + 0.0025 + i * 0.001, tokens=16 * rows,
                            padded=16 * rows, rows=rows, window=8))
    rec.seal(r, parts=1 + chunks, prefill_tokens=16 * chunks)
    assert [p.name for p in r.programs] == ["decode_round"] + names
    rec.complete_part(r, tokens=8, program=dec, t_done=T + 0.020)
    for i, run in enumerate(runs[:-1]):
        assert not r.done
        rec.complete_part(r, program=run, t_done=T + 0.050 + i * 0.030)
    assert not r.done
    # the final chunk is stamped by its first token's readback
    t_first = T + 0.050 + (chunks - 1) * 0.030
    rec.first_token(r, wait_ms=1.0, program=runs[-1], t_done=t_first)
    assert r.done and r.first_tokens == 1
    stamps = [p.t_done for p in r.programs]
    assert stamps == sorted(stamps) and stamps[-1] == t_first
    for prev, p in zip(r.programs, r.programs[1:]):
        assert p.t_prev_done == prev.t_done
    assert dec.service_ms == pytest.approx(19.0)        # from its launch end
    assert [p.service_ms for p in runs] == pytest.approx([30.0] * chunks)
    assert r.device_ms == pytest.approx(sum(p.service_ms
                                            for p in r.programs))
    # request_life's stamps: the decode part once emitted, then the LAST
    # chunk program's own readback
    assert len(r.t_parts) == 2 and r.t_parts[1] == t_first == r.t_done
    if chunks == 4:
        assert (runs[0].name, runs[0].rows, runs[0].tokens) == (
            "extend_rows", 4, 64)
    d = r.to_dict()["execution"]
    assert [p["name"] for p in d["programs"]] == ["decode_round"] + names
    assert d["device_ms"] == pytest.approx(
        sum(p["service_ms"] for p in d["programs"]), abs=0.01)
    agg = rec.snapshot()["aggregates"]["programs"]
    assert agg["decode_round"] == {"count": 1, "p50_service_ms": 19.0,
                                   "p90_service_ms": 19.0}
    assert agg["final"]["count"] == 1
    json.dumps(rec.snapshot())


def test_a_stalled_launch_counts_what_the_device_completed_meanwhile():
    """``done_during_launch`` on a round's longest launch: completions
    inside it mean the host waited for room in a queue the chip was
    draining; none, that the chip (or the machine) stood still."""
    rec = RoundRecorder(cap=8)
    T = time.monotonic()
    a = rec.begin(engine_tag="t")
    a1 = _launch(rec, a, "extend", T, T + 0.001)
    a2 = _launch(rec, a, "extend", T + 0.001, T + 0.002)
    rec.seal(a, parts=2)
    # the host blocks 20 ms launching b's program...
    b = rec.begin(engine_tag="t")
    b1 = _launch(rec, b, "extend", T + 0.005, T + 0.025)
    b2 = _launch(rec, b, "final", T + 0.025, T + 0.026)
    rec.seal(b, parts=2)
    # ... while the device completes both of a's
    rec.complete_part(a, program=a1, t_done=T + 0.010)
    rec.complete_part(a, program=a2, t_done=T + 0.020)
    rec.complete_part(b, program=b1, t_done=T + 0.030)
    rec.first_token(b, program=b2, t_done=T + 0.040)
    assert a.done and b.done
    assert b1.done_during_launch == 2 and b2.done_during_launch is None
    assert b1.launch_ms == pytest.approx(20.0)
    # a's own longest launch saw nothing complete: nothing was queued
    assert [a1.done_during_launch, a2.done_during_launch].count(0) == 1
    # a launch that blocks while the device completes NOTHING
    c = rec.begin(engine_tag="t")
    c1 = _launch(rec, c, "decode_round", T + 0.050, T + 0.090, steps=4)
    rec.seal(c, parts=1)
    rec.complete_part(c, tokens=4, program=c1, t_done=T + 0.100)
    assert c1.done_during_launch == 0
    assert c1.service_ms == pytest.approx(10.0)
    stalled = b.to_dict()["execution"]["programs"][0]
    assert stalled["done_during_launch"] == 2
    assert stalled["launch_ms"] == pytest.approx(20.0)
    assert "done_during_launch" not in \
        b.to_dict()["execution"]["programs"][1]


def test_thread_safety_no_torn_records():
    """Satellite: scheduler-thread appends racing harvest-thread
    completions racing snapshot readers — no torn records (a done
    record's outcome always matches what its round deterministically
    emitted), bounded memory, monotone ids across a mid-stream
    reset()."""
    rec = RoundRecorder(cap=64)
    N = 400
    import queue as _q
    pipe: "_q.Queue" = _q.Queue()
    errors: list = []
    seen_ids: list[int] = []

    def scheduler():
        try:
            for i in range(N):
                r = rec.begin(engine_tag="t", decode_steps=4)
                r.decode_slots = 1
                now = time.monotonic()
                _launch(rec, r, "decode_round", now, now, steps=4)
                rec.seal(r, parts=1, prefill_tokens=(i % 3) * PAGE,
                         modeled_ms=1.0)
                pipe.put(r)
                if i == N // 2:
                    rec.reset()   # mid-stream reset must not break ids
            pipe.put(None)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)
            pipe.put(None)

    def harvester():
        try:
            while True:
                r = pipe.get()
                if r is None:
                    return
                rec.complete_part(r, tokens=r.round_id % 7,
                                  harvest_wait_ms=0.01,
                                  program=r.programs[0])
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    def reader():
        try:
            for _ in range(200):
                snap = rec.snapshot(limit=16)
                json.dumps(snap)
                for d in snap["rounds"]:
                    if d["done"]:
                        # no torn record: outcome matches the round's
                        # deterministic emission
                        assert (d["outcome"]["tokens_emitted"]
                                == d["round_id"] % 7), d
                        # ... and its program is stamped
                        (p,) = d["execution"]["programs"]
                        assert p["done_at_ms"] is not None, d
                seen_ids.extend(r.round_id for r in rec.records())
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=f)
               for f in (scheduler, harvester, reader, reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    assert len(rec.records()) <= 64            # bounded memory
    ids = [r.round_id for r in rec.records()]
    assert ids == sorted(ids)                  # monotone in the ring
    assert ids[-1] == N - 1                    # ...through the reset


# ------------------------------------------------------ calibrator units


def test_online_calib_env_gate(monkeypatch):
    monkeypatch.delenv("SCHED_ONLINE_CALIB", raising=False)
    assert online_calib_enabled()
    monkeypatch.setenv("SCHED_ONLINE_CALIB", "0")
    assert not online_calib_enabled()
    monkeypatch.setenv("SCHED_ONLINE_CALIB", "1")
    assert online_calib_enabled()


def test_calibrator_blends_toward_measurement():
    prior = StepCostModel(decode_step_ms=100.0, prefill_ms_per_token=10.0)
    cal = OnlineCalibrator(prior, warmup=2)
    assert cal.current() is prior              # no evidence: the prior
    for _ in range(50):
        cal.observe_decode(4, 8.0)             # measured 2 ms/step
        cal.observe_prefill(100, 10.0)         # measured 0.1 ms/token
    cur = cal.current()
    # heavily-sampled EWMA converges to the measurement, prior ~gone
    assert cur.decode_step_ms == pytest.approx(2.0, rel=0.1)
    assert cur.prefill_ms_per_token == pytest.approx(0.1, rel=0.1)
    assert cur.source.endswith("+online")
    # junk observations are ignored
    cal.observe_decode(0, 5.0)
    cal.observe_prefill(10, -1.0)


def test_scheduler_recalibrate_moves_unpinned_budget_only():
    from generativeaiexamples_tpu.engine.scheduler import (
        TokenBudgetScheduler)
    prior = StepCostModel(decode_step_ms=100.0, prefill_ms_per_token=0.01)
    cal = OnlineCalibrator(prior, warmup=1)
    sched = TokenBudgetScheduler(prior, page_size=PAGE, steps_per_round=4,
                                 calibrator=cal)
    big = sched.round_budget_tokens
    assert big == derive_round_budget(prior, 4, PAGE)
    assert not sched.recalibrate()             # no new evidence yet
    for _ in range(50):
        cal.observe_decode(4, 8.0)             # really 2 ms/step
        cal.observe_prefill(16, 2.0)           # really 0.125 ms/token
    assert sched.recalibrate()
    assert sched.round_budget_tokens < big
    expect = derive_round_budget(cal.current(), 4, PAGE)
    assert sched.round_budget_tokens == expect
    # a PINNED budget never moves, with the same calibrator evidence
    pinned = TokenBudgetScheduler(prior, page_size=PAGE,
                                  steps_per_round=4,
                                  round_budget_tokens=48, calibrator=cal)
    cal.observe_decode(4, 8.0)
    assert not pinned.recalibrate()
    assert pinned.round_budget_tokens == 48


# ----------------------------------------------------- live engine level


def test_engine_rounds_reconcile_with_stats():
    """Acceptance: a live CPU engine's round records carry plan AND
    execution halves, and their per-round token counts reconcile with
    engine.stats() exactly."""
    eng = _engine()
    try:
        eng.start()
        streams = [
            eng.submit([5] * 40, SamplingParams(max_tokens=8, top_k=1,
                                                ignore_eos=True)),
            eng.submit([9] * 8, SamplingParams(max_tokens=8, top_k=1,
                                               ignore_eos=True)),
        ]
        for s in streams:
            s.text()
        deadline = time.monotonic() + 10
        while (any(not r.done for r in eng.rounds.records())
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        eng.stop()
    stats = eng.stats
    recs = eng.rounds.records()
    assert recs and all(r.done for r in recs)
    assert stats["rounds_completed"] == len(recs)
    # every generated token is attributed to exactly one round
    assert sum(r.tokens_emitted + r.first_tokens for r in recs) \
        == stats["tokens_generated"]
    # plan half present: budgets stamped, prefill grants name requests
    assert all(r.budget_tokens > 0 for r in recs)
    granted = [g for r in recs for g in r.grants]
    assert {rid for rid, _ in granted} \
        == {s.request_id for s in streams}
    assert sum(n for _, n in granted) == stats["sched_prefill_tokens"]
    # execution half present on completed records
    assert all(r.round_ms > 0 and r.modeled_ms > 0 for r in recs)
    decode_recs = [r for r in recs if r.decode_steps]
    assert decode_recs and all(r.decode_slots >= 1 for r in decode_recs)
    assert all(r.hbm_bytes > 0 for r in recs)
    # drift gauge live (0.0 would mean no completed round fed it)
    assert stats["sched_cost_drift_ratio"] > 0


def test_debug_rounds_endpoint():
    """The shared handler serves the engine's records with ?limit= and
    rolling aggregates (same contract on both servers)."""
    eng = _engine()

    async def run() -> dict:
        app = web.Application()

        async def handler(request):
            return debug_rounds_response(request, eng.rounds)

        app.router.add_get("/debug/rounds", handler)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/debug/rounds", params={"limit": 2})
            assert resp.status == 200
            body = await resp.json()
            bad = await client.get("/debug/rounds",
                                   params={"limit": "x"})
            assert bad.status == 400
            return body
        finally:
            await client.close()

    try:
        eng.start()
        eng.submit([7] * 8, SamplingParams(max_tokens=6, top_k=1,
                                           ignore_eos=True)).text()
        deadline = time.monotonic() + 10
        while (any(not r.done for r in eng.rounds.records())
               and time.monotonic() < deadline):
            time.sleep(0.01)
        import asyncio
        body = asyncio.new_event_loop().run_until_complete(run())
    finally:
        eng.stop()
    assert len(body["rounds"]) == 2
    assert body["aggregates"]["rounds_completed"] >= 2
    assert body["aggregates"]["tokens_emitted"] == 6
    rec = body["rounds"][0]
    assert {"plan", "execution", "outcome"} <= set(rec)


def test_budget_converges_from_wrong_prior(tmp_path, monkeypatch):
    """Acceptance: SCHED_ONLINE_CALIB=1 + a deliberately wrong
    SCHED_PROFILE_JSON prior — the derived round budget converges
    toward the measured costs within a few rounds."""
    # Absurd prior: decode steps cost 10 s each, prefill is free -> the
    # derived budget is astronomically large.
    wrong = tmp_path / "PROFILE_wrong.json"
    wrong.write_text(json.dumps({
        "full_ms_per_step": 10_000.0, "prefill_ms_per_token": 0.001,
        "slots": 2}))
    monkeypatch.setenv("SCHED_PROFILE_JSON", str(wrong))
    monkeypatch.setenv("SCHED_ONLINE_CALIB", "1")
    eng = _engine()
    try:
        initial = eng.stats["sched_round_budget_tokens"]
        assert initial >= 10_000   # the wrong prior really took
        eng.start()
        # Sequential requests: prefill-only rounds calibrate the prefill
        # cost, decode-only rounds the step cost.
        for i in range(4):
            eng.submit([4 + i] * 32, SamplingParams(
                max_tokens=9, top_k=1, ignore_eos=True)).text()
        deadline = time.monotonic() + 10
        while (any(not r.done for r in eng.rounds.records())
               and time.monotonic() < deadline):
            time.sleep(0.01)
        # One more planning pass so the last observations are folded in.
        eng.submit([99] * 8, SamplingParams(max_tokens=2, top_k=1,
                                            ignore_eos=True)).text()
        stats = eng.stats
    finally:
        eng.stop()
    assert stats["sched_budget_recalibrations"] >= 1
    final = stats["sched_round_budget_tokens"]
    # Converged toward reality: ORDERS of magnitude below the wrong
    # prior, and in the neighborhood of what the calibrated model
    # derives. Not exact equality: rounds completing after the last
    # recalibrate() keep nudging the EWMA, so the live derivation can
    # sit a page or two away from the budget snapshot (races the
    # harvest thread by design).
    assert final < initial / 100
    derived = derive_round_budget(eng._calib.current(),
                                  eng.cfg.steps_per_round, PAGE)
    assert derived / 4 <= final <= derived * 4


def test_dispatch_fault_drives_drift_and_slow_round_dump(monkeypatch,
                                                        caplog):
    """Acceptance: FAULT_PLAN engine.dispatch=delay:... drives
    sched_cost_drift_ratio past threshold and produces the slow-round
    structured dump."""
    monkeypatch.setenv("SCHED_ONLINE_CALIB", "0")   # pin the model
    monkeypatch.setenv("ROUND_DRIFT_DUMP_RATIO", "3")
    eng = _engine()
    try:
        faults.set_plan("engine.dispatch=delay:0.15")
        with caplog.at_level(logging.WARNING):
            eng.start()
            eng.submit([6] * 24, SamplingParams(max_tokens=6, top_k=1,
                                                ignore_eos=True)).text()
            deadline = time.monotonic() + 10
            while (any(not r.done for r in eng.rounds.records())
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        stats = eng.stats
    finally:
        faults.clear()
        eng.stop()
    assert stats["sched_cost_drift_ratio"] > 3
    dumps = [r for r in caplog.records if "slow_round" in r.getMessage()]
    assert dumps, "no slow_round dump emitted"
    payload = json.loads(dumps[0].getMessage().split(" ", 1)[1])
    assert payload["drift_ratio"] > 3
    assert {"plan", "execution", "outcome"} <= set(payload["round"])
    # the dump counter moved too
    from generativeaiexamples_tpu.obs import metrics as obs_metrics
    snap = obs_metrics.REGISTRY.snapshot()
    assert snap.get("engine_round_slow_dumps_total", 0) >= 1


def test_failed_dispatch_discards_unsealed_record():
    """A round that dies mid-dispatch (fault injection) must not leave
    a permanently not-done record in the ring."""
    eng = _engine()
    try:
        faults.set_plan("engine.dispatch=fail")
        eng.start()
        s = eng.submit([5] * 8, SamplingParams(max_tokens=4, top_k=1,
                                               ignore_eos=True))
        with pytest.raises(Exception):
            s.text()
        deadline = time.monotonic() + 5
        while eng._fatal is None and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        faults.clear()
        eng.stop()
    # the failed round's record was discarded, not retained as debris
    assert all(r.done for r in eng.rounds.records())


def test_round_metrics_surface_declared_and_fed():
    """Every completed round feeds the declared ROUND_METRICS surface
    (the names docs/observability.md fences and check_metrics_docs
    enforces)."""
    eng = _engine()
    try:
        eng.start()
        eng.submit([3] * 8, SamplingParams(max_tokens=5, top_k=1,
                                           ignore_eos=True)).text()
        deadline = time.monotonic() + 10
        while (any(not r.done for r in eng.rounds.records())
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        eng.stop()
    from generativeaiexamples_tpu.obs import metrics as obs_metrics
    snap = obs_metrics.REGISTRY.snapshot()
    assert snap["engine_rounds_total"] >= 2
    assert snap["engine_round_seconds_count"] >= 2
    assert snap["engine_round_tokens_count"] >= 2
    assert "sched_cost_drift_ratio" in snap
    assert set(ROUND_METRICS) == {
        "engine_rounds_total", "engine_round_seconds",
        "engine_round_device_seconds", "engine_round_tokens",
        "engine_round_bw_util", "engine_round_hbm_bytes_total",
        "sched_cost_drift_ratio", "engine_round_slow_dumps_total",
        "engine_program_seconds"}
    # one series a program name, fed a completed program
    text = obs_metrics.REGISTRY.render_prometheus()
    for name in ("prefill_insert", "decode_round"):
        assert ('engine_program_seconds_count{program="%s"}' % name) \
            in text
    assert not obs_metrics.lint_prometheus(text)


def test_round_spans_emitted_when_tracing_on(monkeypatch):
    """With tracing on, every completed round replays as an
    engine_round span carrying round id/kind/token attributes."""
    from generativeaiexamples_tpu.obs import tracing

    spans = []

    class FakeSpan:
        def __init__(self, name, attributes):
            self.name = name
            self.attributes = attributes

        def end(self, end_time=None):
            pass

    class FakeTracer:
        def start_span(self, name, context=None, start_time=None,
                       attributes=None):
            span = FakeSpan(name, dict(attributes or {}))
            spans.append(span)
            return span

    monkeypatch.setattr(tracing, "_enabled_override", True)
    monkeypatch.setattr(tracing, "_tracer", FakeTracer())
    eng = _engine()
    try:
        eng.start()
        eng.submit([7] * 8, SamplingParams(max_tokens=5, top_k=1,
                                           ignore_eos=True)).text()
        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline
               and not any(s.name == "engine_round" for s in spans)):
            time.sleep(0.02)
    finally:
        eng.stop()
    rounds = [s for s in spans if s.name == "engine_round"]
    assert rounds
    attrs = rounds[0].attributes
    assert attrs["round.engine"] == eng._engine_tag
    assert {"round.id", "round.kind", "round.tokens_emitted",
            "round.device_ms", "round.drift_ratio"} <= set(attrs)


def test_bench_rounds_snapshot_keys_pinned_by_schema():
    """bench.rounds_snapshot's keys ARE the schema's engine_rounds
    section — renaming either side alone fails tier-1."""
    import bench
    from tools.check_bench_schema import load_schema

    class _FakeEngine:
        rounds = RoundRecorder(cap=8)
        engine_tag = "e-test"
        stats = {"rounds_completed": 0, "sched_cost_drift_ratio": 0.0,
                 "sched_budget_recalibrations": 0}

    snap = bench.rounds_snapshot(_FakeEngine())
    schema = load_schema()
    assert set(snap) == set(schema["engine_rounds"])


# ------------------------------------- a request's life as one span tree

from generativeaiexamples_tpu.obs.flight import (FlightRecorder,  # noqa: E402
                                                 REQUEST_STATES)

GREEDY = dict(top_k=1, ignore_eos=True)


def _wait_rounds_done(eng, timeout=20.0):
    deadline = time.monotonic() + timeout
    while (any(not r.done for r in eng.rounds.records())
           and time.monotonic() < deadline):
        time.sleep(0.01)


def _states(stream):
    return [sp for sp in stream.timeline.spans if sp.parent == "request"]


def _assert_partition(stream):
    """The states tile [submit, finish]: each begins where the last
    ended, all closed, in the order of REQUEST_STATES."""
    states = _states(stream)
    root = stream.timeline.spans[0]
    assert root.name == "request" and root.parent is None
    assert root.t0 == stream.submit_time == states[0].t0
    assert root.t1 == stream.finish_time == states[-1].t1
    for a, b in zip(states, states[1:]):
        assert a.t1 == b.t0
        assert REQUEST_STATES.index(a.name) <= REQUEST_STATES.index(b.name)
    assert all(sp.t1 is not None for sp in stream.timeline.spans)
    assert sum(sp.t1 - sp.t0 for sp in states) == pytest.approx(
        stream.finish_time - stream.submit_time, abs=1e-9)
    return states


@pytest.fixture(scope="module")
def one_slot():
    """One slot, one 16-token chunk shape: every wait is for the slot."""
    eng = _engine(max_slots=1, prefill_buckets=(16,), max_prefill_bucket=16,
                  sched_round_budget_tokens=64, prefix_cache=False)
    eng.flight = FlightRecorder(completed_cap=64)
    with eng:
        yield eng


@pytest.mark.parametrize("prompt,chunks", [(10, 1), (16, 1), (40, 3),
                                           (64, 4)])
def test_states_partition_the_life_of_a_request(one_slot, prompt, chunks):
    eng = one_slot
    s = eng.submit([7] * prompt, SamplingParams(max_tokens=9, **GREEDY))
    s.text()
    states = _assert_partition(s)
    assert [sp.name for sp in states] == list(REQUEST_STATES)
    by = {sp.name: sp for sp in states}
    # the first four states ARE the stream's time to first token
    assert by["req_decode"].t0 == s.first_token_time
    assert (by["req_prefill"].n, by["req_prefill"].m) == (prompt, 0)
    kids = [sp for sp in s.timeline.spans if sp.name == "req_chunk"]
    assert len(kids) == chunks and all(k.parent == "req_prefill"
                                       for k in kids)
    assert sum(k.n for k in kids) == prompt
    assert all(k.m == 16 for k in kids)             # the bucket it ran in
    assert all(by["req_prefill"].t0 <= k.t0 <= k.t1 <= by["req_prefill"].t1
               for k in kids)
    # each chunk names the round that granted it
    _wait_rounds_done(eng)
    grants = {r.round_id: dict(r.grants) for r in eng.rounds.records()}
    for k in kids:
        assert grants[k.round_id0][s.request_id] == k.n
    (rb,) = [sp for sp in s.timeline.spans if sp.name == "req_readback"]
    assert rb.parent == "req_first_token"
    assert by["req_first_token"].t0 <= rb.t0 <= rb.t1 \
        <= by["req_first_token"].t1 + 1e-3
    assert (by["req_decode"].n, by["req_decode"].m) == (9, 2)
    assert s.timeline.spans[0].cause == "length"


def test_no_free_slot_is_the_cause_slot(one_slot):
    eng = one_slot
    a = eng.submit([5] * 16, SamplingParams(max_tokens=16, **GREEDY))
    b = eng.submit([6] * 16, SamplingParams(max_tokens=4, **GREEDY))
    a.text(), b.text()
    _assert_partition(a)
    waits = [sp for sp in _assert_partition(b) if sp.name == "req_backlog"]
    assert [w.cause for w in waits] == ["slot"]
    # it waited for all of a's decode: until a's finish, near enough
    assert waits[0].t1 >= a.finish_time
    _wait_rounds_done(eng)
    assert any(r.waiting_slot == 1 for r in eng.rounds.records())
    assert all(r.waiting_pages == 0 and r.waiting_budget == 0
               for r in eng.rounds.records())


def test_cancel_and_deadline_drop_close_the_open_span(one_slot):
    eng = one_slot
    blocker = eng.submit([5] * 16, SamplingParams(max_tokens=16, **GREEDY))
    dropped = eng.submit([6] * 16, SamplingParams(max_tokens=4, **GREEDY),
                         deadline_t=time.monotonic() - 1.0)
    cancelled = eng.submit([8] * 16, SamplingParams(max_tokens=4, **GREEDY))
    cancelled.cancel()
    for s in (blocker, dropped, cancelled):
        s.text()
    assert dropped.finish_reason == "deadline_queue"
    assert cancelled.finish_reason == "cancelled"
    for s in (dropped, cancelled):
        states = _assert_partition(s)
        assert [sp.name for sp in states][0] == "req_intake"
        assert states[-1].name in ("req_intake", "req_backlog")
        assert s.timeline.spans[0].cause == s.finish_reason
        # the dropped request's queue wait reads from its spans, under
        # the stage name the load shedders ask for
        assert s.timeline.stage_durations()["engine_admit_pickup"] \
            == pytest.approx(s.finish_time - s.submit_time, abs=1e-6)


def test_pool_refusal_is_the_cause_pages_and_a_change_opens_a_span():
    """Two slots and a pool of 7 pages: a (4 pages) and b (2 pages) run,
    c (5 pages) first waits for a SLOT, then — b gone, a still holding
    its four — for PAGES: two req_backlog spans, one a cause."""
    eng = _engine(max_slots=2, max_output_length=48, prefill_buckets=(16, 64),
                  kv_pool_tokens=7 * PAGE, sched_round_budget_tokens=128,
                  prefix_cache=False)
    eng.flight = FlightRecorder(completed_cap=16)
    with eng:
        a = eng.submit([5] * 16, SamplingParams(max_tokens=48, **GREEDY))
        b = eng.submit([6] * 16, SamplingParams(max_tokens=8, **GREEDY))
        c = eng.submit([7] * 64, SamplingParams(max_tokens=16, **GREEDY))
        for s in (a, b, c):
            s.text()
        _wait_rounds_done(eng)
    waits = [sp for sp in _assert_partition(c) if sp.name == "req_backlog"]
    assert [w.cause for w in waits] == ["slot", "pages"]
    assert waits[0].t1 == waits[1].t0
    assert waits[1].round_id0 > waits[0].round_id0
    recs = eng.rounds.records()
    assert any(r.waiting_slot for r in recs)
    assert any(r.waiting_pages or r.blocked_on_pages for r in recs)
    view = recs[-1].to_dict()
    assert {"waiting_slot", "waiting_pages", "waiting_budget",
            "prefill_ungranted"} <= set(view["plan"])
    assert {"queued_ahead", "done_ms"} <= set(view["execution"])


def test_a_budget_of_one_chunk_is_the_cause_budget():
    """Free slots for both, a round budget of one 16-token chunk: the
    planner is offered both prompts and grants one."""
    eng = _engine(max_slots=2, prefill_buckets=(16,), max_prefill_bucket=16,
                  sched_round_budget_tokens=PAGE, prefix_cache=False)
    eng.flight = FlightRecorder(completed_cap=16)
    # both are queued before the loop starts, so its first plan sees both
    a = eng.submit([5] * 48, SamplingParams(max_tokens=4, **GREEDY))
    b = eng.submit([6] * 48, SamplingParams(max_tokens=4, **GREEDY))
    with eng:
        a.text(), b.text()
        _wait_rounds_done(eng)
    causes = [sp.cause for s in (a, b) for sp in _assert_partition(s)
              if sp.name == "req_backlog"]
    assert "budget" in causes and "slot" not in causes \
        and "pages" not in causes
    recs = eng.rounds.records()
    assert any(r.waiting_budget == 1 for r in recs)
    # a prompt mid-prefill that a plan passed over is counted too
    assert any(r.prefill_ungranted for r in recs)
    assert all(r.t_done >= r.t_start and len(r.t_parts) >= 1
               for r in recs if r.done)


def test_a_long_answer_keeps_every_boundary_while_the_ring_wraps():
    """Nine chunks in, 600 tokens out at eight steps a round: the ring's
    64 events wrap (75 decode_round events), the span tree keeps every
    state boundary, and admission control still counts the request."""
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
    cfg = LlamaConfig(vocab_size=259 + 5, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16,
                      max_position_embeddings=1024)
    eng = Engine(llama.init_params(cfg, jax.random.key(3), dtype=jnp.float32),
                 cfg, ByteTokenizer(), EngineConfig(
                     max_slots=1, max_input_length=144,
                     max_output_length=600, prefill_buckets=(16,),
                     max_prefill_bucket=16, sched_round_budget_tokens=64,
                     dtype="float32", page_size=PAGE, kv_pool_tokens=None,
                     steps_per_round=8, prefix_cache=False))
    eng.rounds = RoundRecorder(cap=512)
    eng.flight = FlightRecorder(completed_cap=16)
    with eng:
        s = eng.submit([7] * 144, SamplingParams(max_tokens=600, **GREEDY),
                       request_id="long-1")
        s.text()
        _wait_rounds_done(eng)
    tl = s.timeline
    view = tl.to_dict()
    assert view["events_dropped"] > 0 and view["spans_dropped"] == 0
    states = _assert_partition(s)
    assert [sp.name for sp in states] == list(REQUEST_STATES)
    assert len([sp for sp in tl.spans if sp.name == "req_chunk"]) == 9
    assert states[-1].n == 600 and states[-1].m >= 75
    rendered = [e["event"] for e in view["events"]]
    for name in ("engine_submit", "engine_admit_pickup",
                 "engine_admit_dispatch", "engine_first_readback",
                 "engine_ttft"):
        assert name in rendered, name
    assert rendered.count("engine_prefill_chunk") == 9
    assert [d["span"] for d in view["spans"]][:3] == [
        "request", "req_intake", "req_backlog"]
    n, avg = eng.flight.recent_stage_ms("engine_admit_pickup")
    assert n == 1 and avg == pytest.approx(
        (states[2].t0 - s.submit_time) * 1e3, abs=1e-6)
    durs = tl.stage_durations()
    assert durs["engine_ttft"] == pytest.approx(
        s.first_token_time - s.submit_time, abs=1e-9)
    assert durs["engine_admit_dispatch"] == pytest.approx(
        sum(sp.t1 - sp.t0 for sp in tl.spans if sp.name == "req_chunk"))


# ---------------------------------- a record a dispatched device program


def _census(eng):
    """Every ProgramRun of the engine's records, after its rounds are
    done; asserts each is whole."""
    _wait_rounds_done(eng)
    recs = eng.rounds.records()
    assert recs and all(r.done for r in recs)
    runs = [p for r in recs for p in r.programs]
    for r in recs:
        assert r.device_ms == pytest.approx(
            sum(p.service_ms for p in r.programs))
        assert sum(p.done_during_launch is not None
                   for p in r.programs) == 1
    for p in runs:
        assert p.name in PROGRAM_NAMES
        assert p.t_launch0 <= p.t_launch1 and p.t_done > p.t_launch0
        assert 0 < p.tokens <= p.padded and p.rows >= 1
        assert (p.steps > 0) == (p.name in ("decode_round", "verify_round"))
    # the harvest thread stamped them in the order they were launched
    stamps = [p.t_done for p in runs]
    assert stamps == sorted(stamps)
    for prev, p in zip(runs, runs[1:]):
        assert p.t_prev_done == prev.t_done
    return runs


def test_every_dispatched_program_has_one_completed_run():
    """One slot-limited engine, prompts of one chunk and of several, one
    cancelled between its chunks and one past its deadline: every
    dispatched program — ``sched_chunk_programs`` plus the harvested
    rounds — has exactly one completed ProgramRun."""
    eng = _engine(max_slots=2, prefill_buckets=(16,), max_prefill_bucket=16,
                  sched_round_budget_tokens=32, prefix_cache=False)
    with eng:
        warm = eng.submit([3] * 8, SamplingParams(max_tokens=2, **GREEDY))
        warm.text()
        faults.set_plan("engine.dispatch=delay:0.03")
        try:
            whole = eng.submit([5] * 40, SamplingParams(max_tokens=8,
                                                        **GREEDY))
            gone = eng.submit([6] * 64, SamplingParams(max_tokens=8,
                                                       **GREEDY))
            t_end = time.monotonic() + 30
            while not any(sp.name == "req_chunk"
                          for sp in gone.timeline.spans):
                assert time.monotonic() < t_end
                time.sleep(0.002)
            gone.cancel()            # between its chunks
            late = eng.submit([7] * 64, SamplingParams(max_tokens=8,
                                                       **GREEDY),
                              deadline_t=time.monotonic() + 0.12)
            short = eng.submit([9] * 8, SamplingParams(max_tokens=8,
                                                       **GREEDY))
            for s in (whole, gone, late, short):
                s.text()
        finally:
            faults.clear()
        runs = _census(eng)
        stats = eng.stats
    assert gone.finish_reason == "cancelled"
    assert late.finish_reason in ("deadline", "deadline_queue")
    assert whole.finish_reason == short.finish_reason == "length"
    chunks = [p for p in runs if not p.steps]
    assert len(chunks) == stats["sched_chunk_programs"]
    assert len(runs) - len(chunks) == stats["harvest_rounds"]
    assert sum(p.tokens for p in chunks) == stats["sched_prefill_tokens"]
    assert sum(p.padded for p in chunks) \
        == stats["sched_prefill_padded_tokens"]
    assert sum(p.steps for p in runs) == stats["decode_steps"]
    names = {p.name for p in runs}
    assert {"prefill_insert", "extend", "final", "decode_round"} <= names
    assert all(p.window > 0 for p in chunks
               if p.name in ("extend", "final"))
    # a cancelled prompt's chunks ran and are recorded; it has no final
    # (warm, whole and short have; late only if it beat its deadline)
    assert 3 <= sum(p.name in ("final", "prefill_insert")
                    for p in chunks) <= 4


def test_the_program_of_several_prompts_is_one_run_of_four_rows():
    """Four long prompts on an idle engine: their whole-bucket chunks go
    out as ``extend_rows`` programs of four rows, one ProgramRun each."""
    eng = _engine(max_slots=4, prefill_buckets=(16,), max_prefill_bucket=16,
                  sched_round_budget_tokens=64, prefix_cache=False)
    with eng:
        streams = [eng.submit([4 + i] * 40, SamplingParams(max_tokens=4,
                                                           **GREEDY))
                   for i in range(4)]
        for s in streams:
            s.text()
        runs = _census(eng)
        stats = eng.stats
    rows = [p for p in runs if p.name == "extend_rows"]
    assert rows and all((p.rows, p.tokens, p.padded, p.window)
                        == (4, 64, 64, 0) for p in rows)
    assert len([p for p in runs if not p.steps]) \
        == stats["sched_chunk_programs"]


def test_a_stalled_launch_is_named_in_the_slow_round_event(caplog):
    """An UNTRACED run whose launch blocks (a fault at the chunk
    programs' ``engine.dispatch`` site) logs ONE ``slow_round`` event
    for that round; its ``programs`` name the blocked program, how long
    its launch took and what the device completed meanwhile (nothing:
    the engine was idle, so the stall was not the queue's)."""
    eng = _engine()
    with eng:
        eng.submit([3] * 8, SamplingParams(max_tokens=2, **GREEDY)).text()
        _wait_rounds_done(eng)
        before = {r.round_id for r in eng.rounds.records()}
        faults.set_plan("engine.dispatch=delay:0.4*1")
        try:
            with caplog.at_level(logging.WARNING):
                eng.submit([6] * 8, SamplingParams(max_tokens=2,
                                                   **GREEDY)).text()
                _wait_rounds_done(eng)
        finally:
            faults.clear()
        stalled = min((r for r in eng.rounds.records()
                       if r.round_id not in before),
                      key=lambda r: r.round_id)
    events = [json.loads(r.getMessage().split(" ", 1)[1])
              for r in caplog.records if "slow_round" in r.getMessage()]
    mine = [e for e in events
            if e["round"]["round_id"] == stalled.round_id]
    assert len(mine) == 1
    (prog,) = mine[0]["round"]["execution"]["programs"]
    assert prog["name"] == "prefill_insert" and prog["tokens"] == 8
    assert prog["launch_ms"] >= 400.0
    assert prog["done_during_launch"] == 0
    assert prog["service_ms"] < prog["launch_ms"]
