"""Flight-recorder tests: timeline ring semantics, recorder thread
safety, request-ID adoption/propagation, the /debug/requests endpoint,
and finish/cancel reasons recorded end to end through a real engine."""

import asyncio
import json
import threading
import time

import pytest

import jax
import jax.numpy as jnp
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.obs import flight
from generativeaiexamples_tpu.obs.flight import (FlightRecorder, Timeline,
                                                 adopt_request_id)


# ----------------------------------------------------------- ring basics

def test_timeline_ring_eviction_and_dropped_count():
    tl = Timeline("r1", event_cap=8)
    for i in range(20):
        tl.event(f"e{i}", i)
    events = tl.events_snapshot()
    assert len(events) == 8
    # oldest were overwritten: only the last cap events survive, in order
    assert [e[2] for e in events] == [f"e{i}" for i in range(12, 20)]
    assert tl.to_dict()["events_dropped"] == 12


def test_timeline_value_conventions_render():
    tl = Timeline("r2")
    tl.stage("prefill", 0.25)          # float -> duration
    tl.event("decode_round", 16)       # int -> count
    tl.event("finish", "eos")          # str -> annotation
    tl.event("engine_submit")          # None -> marker
    rendered = {e["event"]: e for e in tl.to_dict()["events"]}
    assert rendered["prefill"]["dur_ms"] == 250.0
    assert rendered["decode_round"]["value"] == 16
    assert rendered["finish"]["value"] == "eos"
    assert "value" not in rendered["engine_submit"]
    assert tl.stage_durations() == {"prefill": 0.25}


def test_recorder_begin_idempotent_and_completed_ring_bounded():
    rec = FlightRecorder(completed_cap=16, event_cap=8)
    tl = rec.begin("shared")
    assert rec.begin("shared") is tl          # chain + engine share one
    # an EDGE seeing the same client ID while the first is in flight is
    # a different request: fresh=True disambiguates instead of merging
    dup = rec.begin("shared", fresh=True)
    assert dup is not tl and dup.request_id == "shared#2"
    rec.complete(dup)
    rec.complete(tl)
    rec.complete(tl)                          # idempotent
    assert rec.find("shared") is tl
    for i in range(40):
        rec.complete(rec.begin(f"r{i}"))
    snap = rec.snapshot(limit=100)
    assert snap["completed_retained"] == 16
    assert len(snap["completed"]) == 16
    assert rec.find("shared") is None         # evicted from the ring
    assert rec.find("r39") is not None


def test_recorder_thread_safety_under_concurrent_append_and_scrape():
    """Scheduler-thread + harvest-thread appends racing a /debug scraper
    and a begin/complete churn: no exception, bounded structures, every
    surviving event well-formed."""
    rec = FlightRecorder(completed_cap=32, event_cap=16)
    tl = rec.begin("hot")
    stop = threading.Event()
    errors = []

    def appender(name):
        try:
            while not stop.is_set():
                tl.stage(name, 0.001)
                tl.event("decode_round", 8)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    def churner():
        try:
            i = 0
            while not stop.is_set():
                rec.complete(rec.begin(f"churn-{i}"))
                i += 1
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    def scraper():
        try:
            while not stop.is_set():
                snap = rec.snapshot()
                json.dumps(snap)  # JSON-able under concurrent writes
                for t in snap["in_flight"] + snap["completed"]:
                    for e in t["events"]:
                        assert "event" in e and "t_ms" in e
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = ([threading.Thread(target=appender, args=(f"s{i}",))
                for i in range(2)]
               + [threading.Thread(target=churner),
                  threading.Thread(target=scraper)])
    for t in threads:
        t.start()
    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not errors, errors
    assert len(rec.snapshot(limit=1000)["completed"]) <= 32
    # ring still ordered after the stampede
    seqs = [e[0] for e in tl.events_snapshot()]
    assert seqs == sorted(seqs)


def test_adopt_request_id():
    assert adopt_request_id({"X-Request-ID": "abc-123"}) == "abc-123"
    # traceparent trace-id adopted when no explicit header
    tp = "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"
    assert adopt_request_id({"traceparent": tp}) == \
        "0af7651916cd43dd8448eb211c80319c"
    # sanitized: quotes/braces stripped, length capped
    rid = adopt_request_id({"X-Request-ID": 'a"b{c}' + "x" * 500})
    assert '"' not in rid and "{" not in rid and len(rid) <= 128
    # minted when absent — via the caller's minter (the OpenAI surface
    # keeps its cmpl- id shape on malformed/absent headers)
    assert adopt_request_id({}) and adopt_request_id(None)
    assert adopt_request_id({"X-Request-ID": "  "},
                            mint=lambda: "cmpl-x") == "cmpl-x"
    assert adopt_request_id({"traceparent": "garbage"},
                            mint=lambda: "cmpl-y") == "cmpl-y"


# --------------------------------------------------- /debug/requests HTTP

def _run(coro):
    return asyncio.get_event_loop_policy().new_event_loop() \
        .run_until_complete(coro)


def test_debug_requests_endpoint_inflight_vs_completed(monkeypatch):
    """A mid-generation request shows under in_flight with the adopted
    X-Request-ID (echoed in the response header); after the stream
    drains it moves to completed with its finish reason."""
    from generativeaiexamples_tpu.chains.base import BaseExample
    from generativeaiexamples_tpu.chains.server import create_app

    rec = FlightRecorder(completed_cap=16)
    monkeypatch.setattr(flight, "RECORDER", rec)

    release = threading.Event()

    class SlowExample(BaseExample):
        def llm_chain(self, context, question, num_tokens):
            yield "first "
            release.wait(timeout=30)
            yield "second"

        def rag_chain(self, prompt, num_tokens):
            yield from self.llm_chain("", prompt, num_tokens)

        def ingest_docs(self, data_dir, filename):
            pass

    async def fn():
        client = TestClient(TestServer(create_app(SlowExample())))
        await client.start_server()
        try:
            resp = await client.post(
                "/generate",
                json={"question": "q", "use_knowledge_base": False,
                      "num_tokens": 8},
                headers={"X-Request-ID": "dbg-1"})
            assert resp.headers["X-Request-ID"] == "dbg-1"
            await resp.content.read(6)          # first chunk arrived

            dbg = await (await client.get("/debug/requests")).json()
            inflight = {t["request_id"]: t for t in dbg["in_flight"]}
            assert "dbg-1" in inflight
            assert not inflight["dbg-1"]["done"]
            assert inflight["dbg-1"]["meta"]["route"] == "/generate"

            release.set()
            await resp.read()                   # drain to completion

            for _ in range(100):                # worker finishes async
                dbg = await (await client.get(
                    "/debug/requests?limit=5")).json()
                done = {t["request_id"]: t for t in dbg["completed"]}
                if "dbg-1" in done:
                    break
                await asyncio.sleep(0.05)
            assert "dbg-1" in done
            assert done["dbg-1"]["meta"]["finish"] == "done"
            assert not any(t["request_id"] == "dbg-1"
                           for t in dbg["in_flight"])

            # bad limit is a 400, not a 500
            assert (await client.get("/debug/requests?limit=x")).status \
                == 400
        finally:
            release.set()
            await client.close()
    _run(fn())


# ------------------------------------------------------- engine end-to-end

from generativeaiexamples_tpu.engine import (Engine, EngineConfig,  # noqa: E402
                                             SamplingParams)
from generativeaiexamples_tpu.models import llama  # noqa: E402
from generativeaiexamples_tpu.models.configs import LlamaConfig  # noqa: E402
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer  # noqa: E402

CFG = LlamaConfig(vocab_size=259 + 5, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position_embeddings=256)

ENGINE_CFG = EngineConfig(max_slots=2, max_input_length=32,
                          max_output_length=16, prefill_buckets=(16, 32),
                          dtype="float32", max_queue=16,
                          steps_per_round=4)


@pytest.fixture(scope="module")
def engine():
    params = llama.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    eng = Engine(params, CFG, ByteTokenizer(), ENGINE_CFG)
    eng.flight = FlightRecorder(completed_cap=64)
    with eng:
        yield eng


def test_request_id_stamped_on_stream_and_timeline(engine):
    stream = engine.submit(
        engine.tokenizer.encode("hello"),
        SamplingParams(max_tokens=6, top_k=1, ignore_eos=True),
        request_id="prop-1")
    stream.text()
    assert stream.request_id == "prop-1"
    tl = engine.flight.find("prop-1")
    assert tl is not None and tl.done
    names = [e[2] for e in tl.events_snapshot()]
    for expected in ("engine_submit", "engine_admit_pickup",
                     "engine_admit_dispatch", "engine_first_readback",
                     "engine_ttft", "finish"):
        assert expected in names, (expected, names)
    assert tl.meta["finish"] == "length"
    assert tl.meta["generated"] == 6
    assert tl.meta["prompt_tokens"] == len(engine.tokenizer.encode("hello"))
    assert tl.meta["ttft_ms"] is not None
    # a decode_round token-count event exists (per ROUND, not per token).
    # The harvest worker appends it just AFTER delivering the round's
    # tokens, so it can land microseconds after text() returns — poll.
    deadline = time.monotonic() + 10
    rounds: list = []
    while not rounds and time.monotonic() < deadline:
        rounds = [e[3] for e in tl.events_snapshot()
                  if e[2] == "decode_round"]
        if not rounds:
            time.sleep(0.02)
    assert rounds and sum(rounds) <= 6


def test_request_id_adopted_from_bound_context(engine):
    """The chain-server path: the ID bound on the calling context (the
    adopted X-Request-ID) reaches Engine.submit without being passed —
    header in, same ID on the engine stream and its timeline. The EDGE
    owns completion: the engine sub-call annotates but must not retire
    the request's timeline (agent chains run several engine calls per
    request)."""
    tl_edge = engine.flight.begin("ctx-77")
    token = flight.bind(tl_edge)
    try:
        stream = engine.submit(
            engine.tokenizer.encode("abc"),
            SamplingParams(max_tokens=4, top_k=1, ignore_eos=True))
    finally:
        flight.unbind(token)
    stream.text()
    assert stream.request_id == "ctx-77"
    assert stream.timeline is tl_edge          # shared, not a duplicate
    assert not stream.owns_timeline
    deadline = time.monotonic() + 10           # harvest thread annotates
    while tl_edge.meta.get("finish") is None \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    assert tl_edge.meta["finish"] == "length"
    assert not tl_edge.done                    # edge completes, not engine
    # second sub-call on the same request timeline: stats accumulate
    token = flight.bind(tl_edge)
    try:
        engine.submit(
            engine.tokenizer.encode("de"),
            SamplingParams(max_tokens=3, top_k=1, ignore_eos=True)).text()
    finally:
        flight.unbind(token)
    deadline = time.monotonic() + 10
    while tl_edge.meta.get("generated", 0) < 7 \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    assert tl_edge.meta["generated"] == 4 + 3
    engine.flight.complete(tl_edge)            # the edge's finally
    assert engine.flight.find("ctx-77").done


def test_cancel_reason_recorded(engine):
    stream = engine.submit(
        engine.tokenizer.encode("zzzz"),
        SamplingParams(max_tokens=12, top_k=1, ignore_eos=True),
        request_id="cxl-1")
    stream.cancel()
    stream.text()
    assert stream.finish_reason == "cancelled"
    tl = engine.flight.find("cxl-1")
    assert tl.done and tl.meta["finish"] == "cancelled"
    finishes = [e[3] for e in tl.events_snapshot() if e[2] == "finish"]
    assert finishes == ["cancelled"]


def test_queue_full_rejection_recorded(engine):
    """A SchedulerFullError'd submit retires its timeline as 'rejected'
    instead of leaking a forever-in-flight entry."""
    import queue as _q

    from generativeaiexamples_tpu.utils.errors import SchedulerFullError

    full_q: "_q.Queue" = _q.Queue(maxsize=1)
    full_q.put_nowait(("sentinel", None))
    orig = engine._pending
    engine._pending = full_q
    try:
        with pytest.raises(SchedulerFullError):
            engine.submit(engine.tokenizer.encode("x"),
                          SamplingParams(max_tokens=2),
                          request_id="rej-1")
    finally:
        engine._pending = orig
    tl = engine.flight.find("rej-1")
    assert tl is not None and tl.done and tl.meta["finish"] == "rejected"
    assert "rej-1" not in {t.request_id
                           for t in engine.flight._inflight.values()}


def test_slow_request_dump_carries_request_id(engine, caplog):
    """SLO breach → one structured slow_request log line whose JSON
    payload carries the same request ID as the timeline."""
    import logging

    rec = engine.flight
    old_ttft = rec.slo_ttft_ms
    rec.slo_ttft_ms = 0.000001  # everything breaches
    try:
        with caplog.at_level(logging.WARNING,
                             logger="generativeaiexamples_tpu.obs.flight"):
            engine.submit(engine.tokenizer.encode("slow"),
                          SamplingParams(max_tokens=2, top_k=1,
                                         ignore_eos=True),
                          request_id="slo-1").text()
            # the dump fires on the harvest thread just after the stream
            # drains — poll briefly for the record
            deadline = time.monotonic() + 10
            lines: list = []
            while time.monotonic() < deadline and not lines:
                lines = [r.getMessage() for r in caplog.records
                         if r.getMessage().startswith("slow_request ")]
                if not lines:
                    time.sleep(0.02)
    finally:
        rec.slo_ttft_ms = old_ttft
    assert lines, caplog.records
    payload = json.loads(lines[-1].split(" ", 1)[1])
    assert payload["request_id"] == "slo-1"
    assert payload["timeline"]["request_id"] == "slo-1"


def test_span_replay_emits_engine_stage_spans(engine, monkeypatch):
    """With tracing on, completion replays duration events as spans
    carrying the request ID — engine stages join the request's trace."""
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
    engine.submit(engine.tokenizer.encode("sp"),
                  SamplingParams(max_tokens=2, top_k=1, ignore_eos=True),
                  request_id="span-1").text()
    # completion happens on the harvest thread; wait for the replay
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not any(
            s.attributes.get("request.id") == "span-1" for s in spans):
        time.sleep(0.02)
    mine = [s for s in spans if s.attributes.get("request.id") == "span-1"]
    assert {"engine_admit_dispatch", "engine_ttft"} <= {s.name
                                                        for s in mine}


# ------------------------------------------------------- the span tree

from generativeaiexamples_tpu.obs.flight import (SPAN_CAP,  # noqa: E402
                                                 WAIT_CAUSES, Span)


def _life(tl, t=100.0, chunks=2, cause="slot"):
    """A whole request stamped by hand: submit at ``t``, 10 ms of
    intake, 40 ms waiting, ``chunks`` chunks of 5 ms 20 ms apart, 30 ms
    to the first token (a 12 ms readback), 200 ms of decode."""
    root = tl.enter(None, "request", t, 3)
    st = tl.enter(None, "req_intake", t, 3)
    st = tl.enter(st, "req_backlog", t + 0.010, 3, cause=cause)
    st = tl.enter(st, "req_prefill", t + 0.050, 5, n=chunks * 16, m=16)
    at = t + 0.050
    for k in range(chunks):
        tl.child(st, "req_chunk", at + 0.001, at + 0.006, 5 + k, 16, 16)
        at += 0.020
    st = tl.enter(st, "req_first_token", at, 5 + chunks - 1)
    tl.child(st, "req_readback", at + 0.010, at + 0.022, 5 + chunks - 1)
    st = tl.enter(st, "req_decode", at + 0.030, 5 + chunks - 1)
    st.close(at + 0.230, 40)
    root.cause = "length"
    root.close(at + 0.230, 40)
    return root


def test_span_enter_closes_the_state_before_and_close_is_first_wins():
    tl = Timeline("sp-1")
    a = tl.enter(None, "req_intake", 1.0, 7)
    b = tl.enter(a, "req_backlog", 1.5, 8, cause="pages")
    assert (a.t0, a.t1, a.round_id0, a.round_id1) == (1.0, 1.5, 7, 8)
    assert a.parent == "request" and a.seconds == 0.5
    assert (b.t1, b.round_id1, b.seconds, b.cause) == (None, -1, None,
                                                       "pages")
    b.close(2.0, 9)
    b.close(3.0, 11)                       # a terminal race: the first holds
    assert (b.t1, b.round_id1) == (2.0, 9)
    assert tl.enter(None, "request", 0.5).parent is None
    assert [sp.name for sp in tl.spans] == ["req_intake", "req_backlog",
                                            "request"]


@pytest.mark.parametrize("first,then,spans", [
    (None, "slot", 1),          # the first plan names the open span
    ("slot", "slot", 1),        # the same cause again: nothing
    ("slot", "pages", 2),       # a change of cause: a new span
    ("budget", "slot", 2),
])
def test_recause_opens_a_span_only_on_a_change_of_cause(first, then, spans):
    assert {"slot", "pages", "budget"} == set(WAIT_CAUSES)
    tl = Timeline("sp-2")
    st = tl.enter(None, "req_backlog", 1.0, 0, cause=first)
    new = tl.recause(st, then, 2.0, 4)
    assert len(tl.spans) == spans and new.cause == then
    if spans == 2:
        assert (st.t1, st.round_id1, new.t0, new.round_id0) == (2.0, 4,
                                                                2.0, 4)
    else:
        assert new is st and st.t1 is None


def test_span_cap_drops_children_and_recauses_never_a_state():
    tl = Timeline("sp-3")
    st = tl.enter(None, "req_prefill", 1.0)
    for k in range(SPAN_CAP + 10):
        tl.child(st, "req_chunk", 1.0 + k, 1.5 + k, k, 16, 16)
    assert len(tl.spans) == SPAN_CAP and tl.spans_dropped == 11
    wait = Span("req_backlog", 0.0, "request", cause="slot")
    assert tl.recause(wait, "pages", 9.0) is wait and tl.spans_dropped == 12
    nxt = tl.enter(st, "req_first_token", 2.0, 3)      # a boundary: kept
    assert tl.spans[-1] is nxt and len(tl.spans) == SPAN_CAP + 1
    assert tl.to_dict()["spans_dropped"] == 12


@pytest.mark.parametrize("name,seconds", [
    ("engine_admit_pickup", 0.050),
    ("engine_prefill_chunk", 0.005),        # first occurrence wins
    ("engine_admit_dispatch", 0.015),       # the three chunks' sum
    ("engine_first_readback", 0.012),
    ("engine_ttft", 0.140),                 # 50 + 3 x 20 + 30
])
def test_old_stage_names_render_from_the_spans(name, seconds):
    tl = Timeline("sp-4")
    _life(tl, chunks=3)
    assert tl.stage_durations()[name] == pytest.approx(seconds)
    view = tl.to_dict()
    ev = next(e for e in view["events"] if e["event"] == name)
    assert ev["dur_ms"] == pytest.approx(seconds * 1e3, abs=1e-3)
    assert [e["event"] for e in view["events"]][0] == "engine_submit"


def test_span_events_merge_with_the_ring_in_order_of_time():
    tl = Timeline("sp-5", event_cap=8)
    _life(tl, t=tl.t_start, chunks=1)
    tl.event("decode_round", 8, t=tl.t_start + 0.15)
    tl.event("llm", 0.3, t=tl.t_start + 1.0)   # a chain's stage, later
    names = [e[2] for e in tl.events_snapshot()]
    assert names.index("engine_ttft") < names.index("decode_round")
    assert names[-1] == "llm" and names[0] == "engine_submit"
    assert tl.stage_durations()["llm"] == 0.3


def test_spans_dict_is_json_ready_and_relative():
    tl = Timeline("sp-6")
    _life(tl, t=tl.t_start + 1.0, chunks=2, cause="budget")
    tl.enter(None, "req_intake", tl.t_start + 5.0, 50)   # still open
    spans = json.loads(json.dumps(tl.to_dict()["spans"]))
    assert [s["span"] for s in spans] == [
        "request", "req_intake", "req_backlog", "req_prefill", "req_chunk",
        "req_chunk", "req_first_token", "req_readback", "req_decode",
        "req_intake"]
    wait = spans[2]
    assert (wait["cause"], wait["parent"], wait["round_id0"],
            wait["round_id1"]) == ("budget", "request", 3, 5)
    assert wait["t0_ms"] == pytest.approx(1010.0, abs=1e-3)
    assert spans[0]["cause"] == "length" and spans[0]["parent"] is None
    assert (spans[4]["n"], spans[4]["m"], spans[4]["parent"]) == (
        16, 16, "req_prefill")
    assert spans[-1]["t1_ms"] is None and "cause" not in spans[-1]


def test_a_request_that_died_waiting_still_renders_its_queue_wait():
    tl = Timeline("sp-7")
    root = tl.enter(None, "request", 10.0)
    st = tl.enter(None, "req_intake", 10.0)
    st = tl.enter(st, "req_backlog", 10.01, cause="slot")
    assert "engine_admit_pickup" not in tl.stage_durations()   # still open
    st.close(12.5)
    root.close(12.5)
    assert tl.stage_durations() == {"engine_admit_pickup": 2.5}
    # a second engine call on the same (adopted) timeline renders its own
    _life(tl, t=20.0, chunks=1)
    picks = [e[3] for e in tl.events_snapshot()
             if e[2] == "engine_admit_pickup"]
    assert picks == [2.5, pytest.approx(0.050)]


def test_recent_stage_ms_counts_spans_and_seeded_ring_stages_alike():
    rec = FlightRecorder(completed_cap=8)
    a = rec.begin("a")
    _life(a, t=a.t_start)
    b = rec.begin("b")
    b.stage("engine_admit_pickup", 0.150)          # a test's seed
    rec.complete(a), rec.complete(b)
    n, avg = rec.recent_stage_ms("engine_admit_pickup")
    assert (n, avg) == (2, pytest.approx(100.0))


def test_engine_span_tree_on_debug_requests_and_adopted_timelines(engine):
    """Two engine calls on one adopted timeline leave two whole trees
    end to end; /debug/requests' snapshot carries them."""
    tl_edge = engine.flight.begin("tree-1")
    token = flight.bind(tl_edge)
    try:
        for text in ("ab", "cd"):
            engine.submit(
                engine.tokenizer.encode(text),
                SamplingParams(max_tokens=3, top_k=1, ignore_eos=True)
            ).text()
    finally:
        flight.unbind(token)
    engine.flight.complete(tl_edge)
    snap = engine.flight.snapshot(limit=50)
    view = next(t for t in snap["completed"] if t["request_id"] == "tree-1")
    names = [s["span"] for s in view["spans"]]
    assert names.count("request") == 2 and names.count("req_decode") == 2
    assert all(s["t1_ms"] is not None for s in view["spans"])
    roots = [s for s in view["spans"] if s["span"] == "request"]
    assert roots[0]["t1_ms"] <= roots[1]["t0_ms"]
    assert [s["cause"] for s in roots] == ["length", "length"]
    assert view["spans_dropped"] == 0


def test_rejected_submit_closes_its_spans(engine):
    from generativeaiexamples_tpu.engine.engine import SchedulerFullError
    ids = engine.tokenizer.encode("q")
    sp = SamplingParams(max_tokens=16, top_k=1, ignore_eos=True)
    streams, rejected = [], None
    for i in range(64):
        try:
            streams.append(engine.submit(ids, sp, request_id=f"full-{i}"))
        except SchedulerFullError:
            rejected = engine.flight.find(f"full-{i}")
            break
    for s in streams:
        s.text()
    assert rejected is not None and rejected.done
    assert [sp.name for sp in rejected.spans] == ["request", "req_intake"]
    assert all(sp.t1 is not None for sp in rejected.spans)
    assert rejected.spans[0].cause == "rejected"


def test_span_replay_gives_every_old_stage_name(engine, monkeypatch):
    from generativeaiexamples_tpu.obs import tracing
    seen = []

    class FakeSpan:
        def end(self, end_time=None):
            pass

    class FakeTracer:
        def start_span(self, name, context=None, start_time=None,
                       attributes=None):
            if (attributes or {}).get("request.id") == "replay-2":
                seen.append((name, start_time))
            return FakeSpan()

    monkeypatch.setattr(tracing, "_enabled_override", True)
    monkeypatch.setattr(tracing, "_tracer", FakeTracer())
    engine.submit(engine.tokenizer.encode("sp"),
                  SamplingParams(max_tokens=2, top_k=1, ignore_eos=True),
                  request_id="replay-2").text()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not any(
            n == "engine_ttft" for n, _ in seen):
        time.sleep(0.02)
    assert {"engine_admit_pickup", "engine_admit_dispatch",
            "engine_prefill_chunk", "engine_first_readback",
            "engine_ttft"} <= {n for n, _ in seen}
    assert all(isinstance(t0, int) and t0 > 0 for _, t0 in seen)
