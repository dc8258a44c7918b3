"""Robustness-layer tests: fault injection, circuit breakers, bounded
retry, per-request deadlines, queue-full storms, and the HTTP edge's
admission control (429/503/504 instead of in-stream error text)."""

import json
import threading
import time

import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.engine import Engine, EngineConfig, SamplingParams
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.obs import flight as obs_flight
from generativeaiexamples_tpu.utils import faults, resilience
from generativeaiexamples_tpu.utils.errors import (BreakerOpenError,
                                                   RetrievalError,
                                                   SchedulerFullError)

CFG = LlamaConfig(vocab_size=259 + 5, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position_embeddings=256)


@pytest.fixture(autouse=True)
def _clean_faults_and_breakers():
    faults.clear()
    resilience.reset_breakers()
    yield
    faults.clear()
    resilience.reset_breakers()


# ------------------------------------------------------------------ faults

def test_fault_plan_parse_and_modes():
    faults.set_plan("retrieval.search=fail; embed=delay:0.01; "
                    "engine.dispatch=fail:timeout*2")
    with pytest.raises(faults.FaultInjected):
        faults.inject("retrieval.search")
    t0 = time.monotonic()
    faults.inject("embed")  # delay, then continue
    assert time.monotonic() - t0 >= 0.01
    for _ in range(2):
        with pytest.raises(TimeoutError):
            faults.inject("engine.dispatch")
    faults.inject("engine.dispatch")  # *2 budget exhausted → no-op
    assert faults.fired("engine.dispatch") == 2


def test_fault_plan_rejects_unknown_point_and_mode():
    with pytest.raises(faults.FaultPlanError):
        faults.set_plan("retrieval.serch=fail")  # typo must be LOUD
    with pytest.raises(faults.FaultPlanError):
        faults.set_plan("embed=explode")


def test_faults_noop_when_disabled():
    assert not faults.active()
    faults.inject("retrieval.search")  # must be a no-op, not a KeyError


def test_fault_hang_unblocks_on_clear():
    faults.set_plan("retrieval.search=hang")
    done = threading.Event()

    def victim():
        faults.inject("retrieval.search")
        done.set()

    t = threading.Thread(target=victim, daemon=True)
    t.start()
    assert not done.wait(0.1)  # hung
    faults.clear()
    assert done.wait(2.0)      # released by the plan swap


# ----------------------------------------------------------------- breaker

def test_breaker_open_half_open_closed_cycle():
    clock = [0.0]
    br = resilience.CircuitBreaker("t", failure_threshold=2, cooldown_s=5.0,
                                   clock=lambda: clock[0])
    assert br.state == "closed"
    br.record_failure()
    assert br.state == "closed"      # below threshold
    br.record_failure()
    assert br.state == "open"        # threshold hit
    assert br.trips == 1
    assert not br.allow()
    assert br.retry_after_s() == pytest.approx(5.0)
    clock[0] = 5.1
    assert br.state == "half_open"   # cooldown elapsed
    assert br.allow()                # one probe
    assert not br.allow()            # second concurrent probe refused
    br.record_success()
    assert br.state == "closed"      # probe succeeded


def test_breaker_half_open_failure_reopens():
    clock = [0.0]
    br = resilience.CircuitBreaker("t2", failure_threshold=1, cooldown_s=3.0,
                                   clock=lambda: clock[0])
    br.record_failure()
    assert br.state == "open"
    clock[0] = 3.5
    assert br.allow()
    br.record_failure()              # probe failed
    assert br.state == "open"        # straight back to open
    assert br.trips == 2
    assert not br.allow()


def test_breaker_release_probe_neither_closes_nor_wedges():
    """A half-open probe that never exercised the dependency (shed,
    client cancel, upstream failure) must release WITHOUT closing the
    breaker — and leave the half-open slot available for a real probe."""
    clock = [0.0]
    br = resilience.CircuitBreaker("t3", failure_threshold=1, cooldown_s=2.0,
                                   clock=lambda: clock[0])
    br.record_failure()
    clock[0] = 2.5
    assert br.allow()            # the half-open probe slot
    br.release_probe()
    assert br.state == "half_open"   # NOT closed: nothing was proven
    assert br.allow()            # and NOT wedged: slot is free again
    br.record_success()
    assert br.state == "closed"


def test_breaker_call_fail_fast_and_name():
    br = resilience.CircuitBreaker("dep", failure_threshold=1,
                                   cooldown_s=60.0)
    with pytest.raises(ValueError):
        br.call(lambda: (_ for _ in ()).throw(ValueError("x")))
    with pytest.raises(BreakerOpenError) as ei:
        br.call(lambda: "never runs")
    assert ei.value.breaker == "dep"
    assert ei.value.retry_after_s > 0


# ------------------------------------------------------------------- retry

def test_retry_gives_up_after_budget_with_backoff_jitter():
    calls, delays = [], []

    def flaky():
        calls.append(1)
        raise ConnectionError("nope")

    with pytest.raises(ConnectionError):
        resilience.retry_call(flaky, attempts=4, base_delay=0.1,
                              max_delay=10.0, rng=lambda: 1.0,
                              sleep=delays.append)
    assert len(calls) == 4                     # bounded budget
    assert delays == [0.1, 0.2, 0.4]           # exponential (rng pinned)

    # full jitter: rng scales each delay down
    delays2 = []
    calls.clear()
    with pytest.raises(ConnectionError):
        resilience.retry_call(flaky, attempts=3, base_delay=0.1,
                              rng=lambda: 0.5, sleep=delays2.append)
    assert delays2 == [0.05, 0.1]


def test_retry_succeeds_mid_budget_and_ignores_other_errors():
    state = {"n": 0}

    def eventually():
        state["n"] += 1
        if state["n"] < 3:
            raise ConnectionError("again")
        return "ok"

    assert resilience.retry_call(eventually, attempts=5,
                                 sleep=lambda s: None) == "ok"
    assert state["n"] == 3

    def wrong_type():
        raise ValueError("not retryable")

    with pytest.raises(ValueError):
        resilience.retry_call(wrong_type, attempts=5, sleep=lambda s: None)


# ------------------------------------------------- docstore degradation

def _index():
    from generativeaiexamples_tpu.embed.encoder import HashEmbedder
    from generativeaiexamples_tpu.retrieval.docstore import (Document,
                                                             DocumentIndex)
    idx = DocumentIndex(HashEmbedder(dim=32))
    idx.add_documents([Document(text="the MXU is a systolic array",
                                metadata={"source": "kb.txt"})])
    return idx


def test_similarity_search_wraps_failures_typed():
    idx = _index()
    faults.set_plan("retrieval.search=fail")
    with pytest.raises(RetrievalError) as ei:
        idx.similarity_search("mxu", k=1)
    assert ei.value.reason == "retrieval"
    faults.set_plan("embed=fail")
    with pytest.raises(RetrievalError) as ei:
        idx.similarity_search("mxu", k=1)
    assert ei.value.reason == "embed"


def test_similarity_search_breaker_opens_after_storm():
    idx = _index()
    faults.set_plan("retrieval.search=fail")
    br = resilience.get_breaker("retrieval", failure_threshold=3,
                                cooldown_s=60.0)
    for _ in range(3):
        with pytest.raises(RetrievalError):
            idx.similarity_search("mxu", k=1)
    assert br.state == "open"
    # Now the fault doesn't even fire: the breaker fails fast first.
    fired_before = faults.fired("retrieval.search")
    with pytest.raises(BreakerOpenError):
        idx.similarity_search("mxu", k=1)
    assert faults.fired("retrieval.search") == fired_before


def test_is_connect_failure_excludes_mid_response_resets():
    """Only connect-phase failures may be replayed: a reset AFTER bytes
    were in flight may mean the server already ran the generation."""
    import requests as rq

    from generativeaiexamples_tpu.serving.client import is_connect_failure
    assert is_connect_failure(ConnectionError("injected"))
    assert is_connect_failure(ConnectionRefusedError())
    assert is_connect_failure(rq.exceptions.ConnectTimeout())
    assert is_connect_failure(rq.exceptions.ConnectionError(
        "HTTPConnectionPool: Max retries exceeded (Caused by "
        "NewConnectionError('Failed to establish a new connection'))"))
    assert not is_connect_failure(ConnectionResetError())
    assert not is_connect_failure(BrokenPipeError())
    assert not is_connect_failure(rq.exceptions.ConnectionError(
        "('Connection aborted.', RemoteDisconnected('Remote end closed "
        "connection without response'))"))


def test_degrade_notice_not_emitted_when_llm_also_down():
    """Retrieval down AND the LLM down: the fallback must fail
    PRE-STREAM (typed error, no notice chunk emitted) so the chain
    server can return a real 503 and feed its breaker — not a 200
    carrying notice-then-error text."""
    from generativeaiexamples_tpu.chains.examples.developer_rag import QAChatbot
    from generativeaiexamples_tpu.chains.llm import EchoLLM
    from generativeaiexamples_tpu.embed.encoder import HashEmbedder
    from generativeaiexamples_tpu.utils.app_config import AppConfig
    from generativeaiexamples_tpu.utils.configuration import from_dict
    from generativeaiexamples_tpu.utils.errors import EngineError

    class DeadLLM(EchoLLM):
        def stream(self, *a, **kw):
            raise EngineError("engine is dead")
            yield  # pragma: no cover

    cfg = from_dict(AppConfig, {
        "llm": {"model_engine": "echo"},
        "embeddings": {"model_engine": "hash", "dimensions": 32},
    })
    ex = QAChatbot(llm=DeadLLM(), embedder=HashEmbedder(dim=32), config=cfg)
    ex.index.add_texts(["some doc"])
    faults.set_plan("retrieval.search=fail")
    from generativeaiexamples_tpu.obs import metrics as obs_metrics
    before = obs_metrics.REGISTRY.snapshot().get(
        'degraded_total{reason="retrieval"}', 0.0)
    gen = ex.rag_chain("q", 8)
    with pytest.raises(EngineError):
        next(gen)  # nothing emitted before the typed failure
    assert obs_metrics.REGISTRY.snapshot().get(
        'degraded_total{reason="retrieval"}', 0.0) == before


# ------------------------------------------------------- engine deadlines

def _tiny_engine(**over):
    kw = dict(max_slots=2, max_input_length=64, max_output_length=32,
              prefill_buckets=(16, 32, 64), dtype="float32", max_queue=4)
    kw.update(over)
    params = llama.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    eng = Engine(params, CFG, ByteTokenizer(), EngineConfig(**kw))
    eng.flight = obs_flight.FlightRecorder(completed_cap=256)
    return eng


def test_deadline_expired_in_queue_never_prefills():
    eng = _tiny_engine()
    with eng:
        # Saturate both slots so the deadline victim has to queue.
        busy = [eng.submit([7 + i] * 8, SamplingParams(max_tokens=24,
                                                       ignore_eos=True))
                for i in range(2)]
        victim = eng.submit([9] * 8, SamplingParams(max_tokens=8),
                            deadline_t=time.monotonic())  # already expired
        assert victim.text() == ""                        # empty, not hung
        assert victim.finish_reason == "deadline_queue"
        for s in busy:
            s.text()
        prefills = eng.stats["prefills"]
        assert eng.stats["deadline_queue_drops"] == 1
        tl = eng.flight.find(victim.request_id)
        assert tl is not None and tl.done
        assert tl.meta["finish"] == "deadline_queue"
    assert prefills == 2  # the victim's prompt never reached the device


def test_deadline_mid_decode_stops_generation():
    eng = _tiny_engine()
    with eng:
        s = eng.submit([11] * 8,
                       SamplingParams(max_tokens=32, ignore_eos=True),
                       deadline_t=time.monotonic() + 0.010)
        out = s.text()
        assert s.finish_reason == "deadline"
        assert 0 < len(s.token_ids) < 32  # stopped early, after some tokens
        assert isinstance(out, str)
        assert eng.stats["deadline_stops"] == 1
        tl = eng.flight.find(s.request_id)
        assert tl.meta["finish"] == "deadline"


def test_deadline_adopted_from_contextvar_timeline():
    """The chain server arms the deadline on the request's timeline; the
    engine must pick it up through the same contextvar as the ID."""
    eng = _tiny_engine()
    with eng:
        tl = eng.flight.begin("ctx-deadline", fresh=True)
        tl.set_deadline(0.001)  # 1 us in the past by submit time
        token = obs_flight.bind(tl)
        try:
            time.sleep(0.01)
            s = eng.submit([13] * 8, SamplingParams(max_tokens=8))
        finally:
            obs_flight.unbind(token)
        s.text()
        assert s.finish_reason in ("deadline_queue", "deadline")
        eng.flight.complete(tl)


def test_queue_full_storm_no_leaks():
    """N concurrent submitters vs max_queue=4, max_slots=2: every stream
    must terminate with a recorded reason and the engine must end with
    all slots and pages back on the free lists."""
    eng = _tiny_engine(prefix_cache=False)
    N = 12
    streams, rejects, lock = [], [], threading.Lock()
    with eng:
        free_pages_before = len(eng._free_pages)

        def submitter(i):
            try:
                s = eng.submit([3 + (i % 5)] * 8,
                               SamplingParams(max_tokens=8, ignore_eos=True))
                with lock:
                    streams.append(s)
            except SchedulerFullError:
                with lock:
                    rejects.append(i)

        threads = [threading.Thread(target=submitter, args=(i,))
                   for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for s in streams:
            s.text()  # block to completion
        assert len(streams) + len(rejects) == N
        assert eng.stats["rejected_full"] == len(rejects)
        for s in streams:
            assert s.finish_reason in ("length", "eos", "stop")
        # The stream finishes on the harvest thread; slot/page release is
        # the scheduler's NEXT drain — give it a moment to settle.
        deadline = time.monotonic() + 5.0
        while (eng._slots or len(eng._free_slots) < 2) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        # no slot/page leak
        assert sorted(eng._free_slots) == [0, 1]
        assert len(eng._free_pages) == free_pages_before
        assert not eng._slots
        # every accepted request's timeline is retired with a reason
        snap = eng.flight.snapshot(limit=N)
        assert snap["in_flight"] == []
        reasons = {t["request_id"]: t["meta"].get("finish")
                   for t in snap["completed"]}
        for s in streams:
            assert reasons.get(s.request_id) in ("length", "eos", "stop")


# ------------------------------------------------------ chain-server edge

def _run(coro):
    import asyncio
    return asyncio.get_event_loop_policy().new_event_loop() \
        .run_until_complete(coro)


def _echo_example():
    from generativeaiexamples_tpu.chains.examples.developer_rag import QAChatbot
    from generativeaiexamples_tpu.chains.llm import EchoLLM
    from generativeaiexamples_tpu.embed.encoder import HashEmbedder
    from generativeaiexamples_tpu.utils.app_config import AppConfig
    from generativeaiexamples_tpu.utils.configuration import from_dict
    cfg = from_dict(AppConfig, {
        "llm": {"model_engine": "echo"},
        "embeddings": {"model_engine": "hash", "dimensions": 32},
    })
    ex = QAChatbot(llm=EchoLLM(prefix="", tail_chars=4000),
                   embedder=HashEmbedder(dim=32), config=cfg)
    return ex, cfg


def test_generate_queue_full_pre_stream_is_429():
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.chains.base import BaseExample
    from generativeaiexamples_tpu.chains.server import create_app

    class FullExample(BaseExample):
        def llm_chain(self, context, question, num_tokens):
            raise SchedulerFullError("request queue full (4)")
            yield  # pragma: no cover — make it a generator

        def rag_chain(self, prompt, num_tokens):
            yield from self.llm_chain("", prompt, num_tokens)

        def ingest_docs(self, data_dir, filename):
            pass

    async def fn():
        app = create_app(FullExample())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post("/generate", json={
                "question": "x", "num_tokens": 8})
            assert resp.status == 429
            assert int(resp.headers["Retry-After"]) >= 1
            body = await resp.json()
            assert body["error"]["type"] == "queue_full"
            assert resp.headers["X-Request-ID"] == body["request_id"]
        finally:
            await client.close()
    _run(fn())


def test_generate_breaker_fast_503_and_half_open_recovery():
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.chains.base import BaseExample
    from generativeaiexamples_tpu.chains.server import (GENERATE_BREAKER,
                                                        create_app)
    from generativeaiexamples_tpu.utils.errors import EngineError

    class FlakyEngineExample(BaseExample):
        down = True

        def llm_chain(self, context, question, num_tokens):
            if self.down:
                raise EngineError("engine is dead")
            yield "recovered"

        def rag_chain(self, prompt, num_tokens):
            yield from self.llm_chain("", prompt, num_tokens)

        def ingest_docs(self, data_dir, filename):
            pass

    async def fn():
        ex = FlakyEngineExample()
        app = create_app(ex)
        breaker = app[GENERATE_BREAKER]
        breaker.failure_threshold = 2
        breaker.cooldown_s = 0.05
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            for _ in range(2):  # two real 503s trip the breaker
                resp = await client.post("/generate", json={
                    "question": "x", "num_tokens": 8})
                assert resp.status == 503
                assert (await resp.json())["error"]["type"] == "engine_error"
            assert breaker.state == "open"
            resp = await client.post("/generate", json={
                "question": "x", "num_tokens": 8})
            assert resp.status == 503   # fast path, engine untouched
            body = await resp.json()
            assert body["error"]["type"] == "engine_unavailable"
            assert "Retry-After" in resp.headers
            # cooldown → half-open probe → recovery closes the breaker
            ex.down = False
            import asyncio
            await asyncio.sleep(0.06)
            resp = await client.post("/generate", json={
                "question": "x", "num_tokens": 8})
            assert resp.status == 200
            assert (await resp.read()).decode() == "recovered"
            assert breaker.state == "closed"
        finally:
            await client.close()
    _run(fn())


def test_document_search_timeout_504(monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.chains.server import create_app

    ex, _ = _echo_example()
    orig = ex.document_search

    def slow_search(content, num_docs):
        time.sleep(1.0)
        return orig(content, num_docs)

    ex.document_search = slow_search
    monkeypatch.setenv("CHAIN_EXECUTOR_TIMEOUT_S", "0.05")

    async def fn():
        app = create_app(ex)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post("/documentSearch", json={
                "content": "x", "num_docs": 1})
            assert resp.status == 504
            assert (await resp.json())["error"]["type"] == "timeout"
        finally:
            await client.close()
    _run(fn())


def test_generate_deadline_header_sheds_when_hopeless():
    """With recent queue waits far above the caller's deadline, the edge
    rejects before streaming: 429 + Retry-After derived from the
    estimate."""
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.chains.server import create_app

    ex, _ = _echo_example()

    async def fn():
        app = create_app(ex)
        # Seed the recorder with slow completed requests (5 s queue
        # wait) — the whole last-32 estimator window, so completed
        # requests left behind by other tests on the global recorder
        # can't dilute the average below the shed threshold.
        for i in range(32):
            tl = obs_flight.RECORDER.begin(f"seed-{i}", fresh=True)
            tl.stage("engine_admit_pickup", 5.0)
            obs_flight.RECORDER.complete(tl)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/generate", json={"question": "x", "num_tokens": 8},
                headers={"X-Deadline-Ms": "100"})
            assert resp.status == 429
            body = await resp.json()
            assert body["error"]["type"] == "deadline_unmeetable"
            assert int(resp.headers["Retry-After"]) >= 5
            # no deadline → no shed, streams normally
            resp = await client.post(
                "/generate", json={"question": "hello", "num_tokens": 64,
                                   "use_knowledge_base": False})
            assert resp.status == 200
        finally:
            await client.close()
    _run(fn())


# -------------------------------------------------- chat client parsing

def test_chat_client_separates_error_frames():
    from generativeaiexamples_tpu.frontend.chat_client import ChatClient

    c = ChatClient("http://unused:1")
    c.last_request_id = "rid-1"
    raw = ("partial answer\n[error] store exploded\n\nevent: error\n"
           "data: " + json.dumps({"error": "RuntimeError",
                                  "message": "store exploded",
                                  "request_id": "rid-1"}) + "\n\n")

    class FakeResp:
        status_code = 200
        headers = {}

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def raise_for_status(self):
            pass

        def iter_content(self, chunk_size=16, decode_unicode=False):
            b = raw.encode()
            for i in range(0, len(b), chunk_size):
                yield b[i:i + chunk_size]

    c._post = lambda path, **kw: FakeResp()
    chunks = [x for x in c.predict("q")]
    assert chunks[-1] is None
    answer = "".join(x for x in chunks if x)
    assert answer == "partial answer"         # error text filtered out
    assert c.last_error["message"] == "store exploded"
    assert c.last_error["request_id"] == "rid-1"


def test_chat_client_clean_stream_has_no_error():
    from generativeaiexamples_tpu.frontend.chat_client import ChatClient

    c = ChatClient("http://unused:1")
    raw = "a perfectly normal answer with [brackets] even"

    class FakeResp:
        status_code = 200
        headers = {}

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def raise_for_status(self):
            pass

        def iter_content(self, chunk_size=16, decode_unicode=False):
            b = raw.encode()
            for i in range(0, len(b), chunk_size):
                yield b[i:i + chunk_size]

    c._post = lambda path, **kw: FakeResp()
    chunks = [x for x in c.predict("q")]
    assert "".join(x for x in chunks if x) == raw
    assert c.last_error is None


def test_chat_client_retries_connect_with_budget(monkeypatch):
    """ChatClient rides serving.client's shared post_with_retry: bare
    connect failures are replayed up to the budget, then surface."""
    from generativeaiexamples_tpu.frontend import chat_client as mod
    from generativeaiexamples_tpu.serving import client as sc

    attempts = []

    def failing_post(url, **kw):
        attempts.append(url)
        raise ConnectionError("refused")

    monkeypatch.setattr(sc.requests, "post", failing_post)
    monkeypatch.setenv("HTTP_RETRY_ATTEMPTS", "3")
    c = mod.ChatClient("http://unused:1")
    with pytest.raises(ConnectionError):
        list(c.predict("q"))
    assert len(attempts) == 3  # bounded retry, then give up


def test_chat_client_surfaces_structured_429(monkeypatch):
    """The server's JSON error contract survives into the client: a 429
    shed becomes a typed ChainServerError carrying error.type and the
    Retry-After hint, not a bare status line."""
    from generativeaiexamples_tpu.frontend import chat_client as mod
    from generativeaiexamples_tpu.serving import client as sc

    class Resp:
        status_code = 429
        headers = {"Retry-After": "7"}

        def json(self):
            return {"error": {"type": "queue_full",
                              "message": "request queue full (4)"},
                    "request_id": "rid-9"}

        def raise_for_status(self):
            raise AssertionError("structured path should raise first")

    monkeypatch.setattr(sc.requests, "post", lambda url, **kw: Resp())
    c = mod.ChatClient("http://unused:1")
    with pytest.raises(mod.ChainServerError) as ei:
        c.search("q")
    assert ei.value.err_type == "queue_full"
    assert ei.value.retry_after_s == 7.0
    assert ei.value.request_id == "rid-9"


# ------------------------------------------------------- the harness itself

def test_the_harness_set_its_flags_before_jax_was_imported():
    """``tests/conftest.py`` sets them through the environment, which
    ``jax`` reads once, at import: a plugin or a ``conftest.py`` that
    imported ``jax`` first would leave every program of this worker to
    XLA's expensive passes (or on another platform), and nothing else
    would say so."""
    assert jax.config.read("jax_disable_most_optimizations")
    assert jax.default_backend() == "cpu" and len(jax.devices()) == 8


def test_a_case_past_its_limit_fails_by_name_and_the_module_goes_on(
        tmp_path, repo_root):
    """``tests/conftest.py``'s per-case limit: a case that sleeps past
    it fails with its node id in the message, and the next case of its
    file still runs. The sleeping case brings the armed alarm forward to
    a fraction of a second (the handler is the fixture's own); a file
    outside ``tests/`` gets the fixtures by loading the harness as a
    plugin, the only module on the child's path."""
    import os
    import subprocess
    import sys

    (tmp_path / "test_two_cases.py").write_text(
        "import signal, time\n"
        "def test_sleeps():\n"
        "    signal.setitimer(signal.ITIMER_REAL, 0.2)\n"
        "    time.sleep(30)\n"
        "def test_after_it():\n"
        "    assert signal.getitimer(signal.ITIMER_REAL)[0] > 200\n")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "test_two_cases.py", "-q",
         "-p", "conftest", "-p", "no:cacheprovider"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=str(repo_root / "tests")))
    out = proc.stdout + proc.stderr
    assert "1 failed, 1 passed" in out, out
    assert "test_two_cases.py::test_sleeps ran past 300 s" in out, out
