"""Engine phases and model stages on the profiler's clock
(obs/tracing.py ``phase``, the spans of engine/engine.py, the stage
scopes of models/llama.py, the build log of utils/compile_cache.py and
the pool fields of obs/rounds.py), all on the CPU at a tiny size."""

import contextlib
import glob
import os
import re
import time

import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.engine import (Engine, EngineConfig,
                                             SamplingParams)
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.obs import flight, metrics, tracing
from generativeaiexamples_tpu.obs.rounds import RoundRecorder
from generativeaiexamples_tpu.obs.tracing import phase, record_stage
from generativeaiexamples_tpu.utils import compile_cache

CFG = LlamaConfig(vocab_size=259 + 5, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position_embeddings=256)
PAGE = 16
SAMPLED = dict(temperature=0.7, top_p=0.9, top_k=0, random_seed=5,
               ignore_eos=True)

# the traced engine's programs are read as compiled text and as the
# device operations a profile of the optimised programs names
pytestmark = pytest.mark.usefixtures("full_optimisation")

#: every span of docs/observability.md's table -> the arguments it carries
SPANS = {
    "loop_drain": (), "loop_plan": (), "loop_idle": (),
    "engine_round": ("round_id", "kind", "t_mono_ns"),
    "loop_dispatch": ("round_id", "steps", "rows", "ba"),
    "loop_admit": ("round_id",),
    "chunk_dispatch": ("round_id", "request_id", "tokens", "padded", "mode"),
    "engine_harvest_wait": ("round_id",),
    "engine_first_readback": ("round_id",),
    "engine_emit": ("round_id", "tokens"),
    "engine_submit": ("request_id",),
}

_PARAMS = None


def make_engine(**over):
    global _PARAMS
    cfg = dict(max_slots=2, max_input_length=64, max_output_length=16,
               prefill_buckets=(16, 32), dtype="float32", page_size=PAGE,
               kv_pool_tokens=None, max_queue=64, steps_per_round=4,
               sched_round_budget_tokens=32)
    cfg.update(over)
    if _PARAMS is None:
        _PARAMS = llama.init_params(CFG, jax.random.key(3),
                                    dtype=jnp.float32)
    eng = Engine(_PARAMS, CFG, ByteTokenizer(), EngineConfig(**cfg))
    eng.rounds = RoundRecorder(cap=2048)
    return eng


def stage_count(name: str) -> int:
    m = re.search(r'engine_stage_seconds_count\{stage="%s"\} (\d+)' % name,
                  metrics.REGISTRY.render_prometheus())
    return int(m.group(1)) if m else 0


def finish(streams, timeout=120.0):
    t_end = time.monotonic() + timeout
    for s in streams:
        while s.finish_reason is None:
            assert time.monotonic() < t_end, "engine did not finish"
            time.sleep(0.005)


# ------------------------------------------------------------ phase()


def test_phase_feeds_stage_histogram_and_timeline_as_record_stage_does():
    tl = flight.FlightRecorder().begin("phase-test", fresh=True)
    token = flight.bind(tl)
    try:
        n0 = stage_count("phase_unit_a")
        with phase("phase_unit_a", round_id=7, kind="decode") as ph:
            time.sleep(0.002)
        record_stage("phase_unit_b", ph.seconds)    # the hand-timed way
    finally:
        flight.unbind(token)
    assert ph.seconds >= 0.002
    assert stage_count("phase_unit_a") == n0 + 1
    durs = tl.stage_durations()
    assert durs["phase_unit_a"] == durs["phase_unit_b"] == ph.seconds
    kinds = [e[2] for e in tl.events_snapshot()]
    assert kinds.count("phase_unit_a") == kinds.count("phase_unit_b") == 1


def test_phase_survives_an_exception_and_nests():
    n_out, n_in = stage_count("phase_outer"), stage_count("phase_inner")
    with pytest.raises(ValueError):
        with phase("phase_outer") as outer:
            with phase("phase_inner") as inner:
                time.sleep(0.001)
                raise ValueError("boom")
    assert stage_count("phase_outer") == n_out + 1
    assert stage_count("phase_inner") == n_in + 1
    assert outer.seconds >= inner.seconds >= 0.001


def test_phase_that_did_no_work_stays_out_of_the_histogram():
    n0 = stage_count("phase_quiet")
    with phase("phase_quiet") as ph:
        ph.record = False
    assert stage_count("phase_quiet") == n0 and ph.seconds >= 0.0


def test_stage_collector_is_gone():
    hook = "stage_" + "collector"      # (so a grep for the name is clean)
    assert not hasattr(tracing, "set_" + hook)
    assert not hasattr(tracing, "_" + hook)


def test_phase_costs_microseconds_with_no_session():
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        with phase("phase_cost", round_id=i) as ph:
            ph.record = False
    per = (time.perf_counter() - t0) / n
    assert per < 50e-6, per     # ~2 us here; the bound only catches a lock


# --------------------------------------------- spans of a traced engine


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny engine serving three sampled requests (one-shot prompts and
    one chunked over the 32-token budget) under a profiler session with
    the options benchmarks/run.py uses; its programs' compiled text is
    taken at their first launch."""
    from jax.profiler import ProfileData
    out = str(tmp_path_factory.mktemp("trace"))
    eng = make_engine()
    texts: dict = {}

    def recording(fn, name):
        def call(*a):
            if name not in texts:
                texts[name] = fn.lower(*a).compile().as_text()
            return fn(*a)
        return call

    progs = eng.programs        # where the loop takes its programs from
    for attr, name in (("round_fn", "round"), ("chunk_extend_fn", "extend"),
                       ("chunk_final_fn", "final")):
        orig = getattr(progs, attr)
        setattr(progs, attr, lambda *a, _o=orig, _n=name: recording(
            _o(*a), _n))
    progs.prefill_insert = recording(progs.prefill_insert, "prefill_insert")
    eng.start()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        sp = SamplingParams(max_tokens=12, **SAMPLED)
        streams = [eng.submit([5 + i] * n, sp)
                   for i, n in enumerate((20, 60, 9))]
        finish(streams)
        time.sleep(0.15)        # an idle iteration or two
    finally:
        jax.profiler.stop_trace()
        eng.stop()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    return {"engine": eng, "streams": streams, "spans": spans,
            "texts": texts, "path": path}


def test_every_span_of_the_table_is_on_the_host_plane(traced):
    for name, args in SPANS.items():
        found = traced["spans"].get(name)
        assert found, f"no {name} span in the trace"
        for _, _, stats in found:
            assert set(args) <= set(stats), (name, stats)


def test_round_ids_join_the_round_records(traced):
    recs = {r.round_id: r for r in traced["engine"].rounds.records()}
    rounds = traced["spans"]["engine_round"]
    assert {s["round_id"] for _, _, s in rounds} == set(recs)
    for _, _, s in rounds:
        rec = recs[s["round_id"]]
        assert abs(s["t_mono_ns"] * 1e-9 - rec.t_start) < 1e-3
    for name in ("loop_dispatch", "loop_admit", "chunk_dispatch",
                 "engine_harvest_wait", "engine_first_readback",
                 "engine_emit"):
        assert {s["round_id"] for _, _, s in traced["spans"][name]} \
            <= set(recs), name
    # the record carries the same split without a profiler
    done = [r for r in recs.values() if r.done]
    assert done and all(r.plan_ms >= 0 and r.emit_ms >= 0 for r in done)
    assert any(r.emit_ms > 0 for r in done)
    assert any(r.plan_ms > 0 for r in done)


def test_every_launch_and_readback_span_has_its_program_run(traced):
    """One ProgramRun a dispatched program: its launch IS the
    ``loop_dispatch`` / ``chunk_dispatch`` span that launched something,
    and a non-final chunk's readback an ``engine_harvest_wait`` span of
    its own (argument ``program``), a final's the first-token span."""
    recs = [r for r in traced["engine"].rounds.records() if r.done]
    runs = [p for r in recs for p in r.programs]
    assert runs and all(p.t_done for p in runs)
    spans = traced["spans"]
    n_chunk = len(spans["chunk_dispatch"])
    assert len(spans["loop_dispatch"]) + n_chunk == len(runs)
    waits = spans["engine_harvest_wait"]
    marks = [s for _, _, s in waits if "program" in s]
    extends = [p for p in runs if p.name in ("extend", "extend_rows")]
    assert marks and len(marks) == len(extends)
    assert {s["program"] for s in marks} == {p.name for p in extends}
    assert len(waits) - len(marks) == sum(p.steps > 0 for p in runs)
    assert len(spans["engine_first_readback"]) == sum(
        p.name in ("final", "prefill_insert") for p in runs)
    # the launch stamps are the spans' own clock reads: the runs'
    # launches last what the dispatch spans lasted (the annotation is
    # entered a moment before the clock is read and left a moment after)
    spans_ms = sum(d for name in ("loop_dispatch", "chunk_dispatch")
                   for _, d, _ in spans[name]) * 1e-6
    mine_ms = sum(p.launch_ms for p in runs)
    assert mine_ms <= spans_ms + 0.01
    assert spans_ms - mine_ms < max(2.0, 0.1 * spans_ms), (spans_ms, mine_ms)


def test_children_lie_inside_their_round_span(traced):
    rounds = {s["round_id"]: (t, t + d)
              for t, d, s in traced["spans"]["engine_round"]}
    for name in ("loop_dispatch", "loop_admit", "chunk_dispatch"):
        for t, d, s in traced["spans"][name]:
            a, b = rounds[s["round_id"]]
            assert a <= t and t + d <= b + 1, name


def test_request_ids_join_the_flight_timelines(traced):
    ids = {s.request_id for s in traced["streams"]}
    assert {s["request_id"] for _, _, s in
            traced["spans"]["engine_submit"]} == ids
    chunks = traced["spans"]["chunk_dispatch"]
    assert {s["request_id"] for _, _, s in chunks} == ids
    for rid in ids:
        assert traced["engine"].flight.find(rid) is not None
    modes = {s["mode"] for _, _, s in chunks}
    assert "one-shot" in modes and "final" in modes and "first" in modes
    for _, _, s in chunks:
        assert 0 < s["tokens"] <= s["padded"]
    grants = sorted(n for r in traced["engine"].rounds.records()
                    for _, n in r.grants)
    assert sorted(s["tokens"] for _, _, s in chunks) == grants


def test_dispatch_span_names_the_batch_rung(traced):
    for _, _, s in traced["spans"]["loop_dispatch"]:
        assert 1 <= s["rows"] <= s["ba"] and s["steps"] >= 1


@pytest.mark.parametrize("program,present,absent", [
    ("round", ("embed", "attn_proj", "attn", "mlp", "tail", "tail_select"),
     ("moe_route", "moe_experts")),
    ("extend", ("embed", "attn_proj", "attn", "mlp"), ("tail",)),
    ("final", ("embed", "attn_proj", "attn", "mlp", "tail", "tail_select"),
     ()),
    ("prefill_insert", ("embed", "attn_proj", "attn", "mlp", "tail",
                        "tail_select"), ()),
])
def test_compiled_programs_hold_the_scope_names(traced, program, present,
                                                absent):
    text = traced["texts"][program]
    assert set(present) | set(absent) <= set(llama.SCOPES)
    for name in present:
        assert re.search(r'op_name="[^"]*/%s/' % name, text), (program, name)
    for name in absent:
        assert not re.search(r'op_name="[^"]*/%s/' % name, text), name
    assert re.search(r'op_name="[^"]*/tail/tail_select/', text) or \
        "tail_select" not in present


def test_moe_scopes_and_jit_names_are_in_the_compiled_text():
    cfg = LlamaConfig(vocab_size=264, hidden_size=64, intermediate_size=128,
                      num_layers=1, num_heads=4, num_kv_heads=2, head_dim=16,
                      max_position_embeddings=64, num_experts=4,
                      num_experts_per_tok=2)
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)

    def decode_round(params, tokens):
        pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
        return llama.apply(params, cfg, tokens, pos)[0]

    text = jax.jit(decode_round).lower(
        params, jnp.zeros((1, 8), jnp.int32)).compile().as_text()
    for name in ("moe_route", "moe_experts", "attn", "tail"):
        assert re.search(r'op_name="[^"]*/%s/' % name, text), name
    assert "jit_decode_round" in text and "jit(decode_round)" in text
    assert not re.search(r'op_name="[^"]*/mlp/', text)


def test_kernels_carry_their_names():
    import inspect

    from generativeaiexamples_tpu.ops import int4_matmul, paged_attention
    src = inspect.getsource(paged_attention)
    assert 'name="paged_attn_decode"' in src
    assert 'name="paged_attn_decode_int8kv"' in src
    assert 'name="int4_matmul"' in inspect.getsource(int4_matmul)


def test_greedy_tokens_are_unchanged_by_the_scopes(monkeypatch):
    prompts = [[7] * 20, [9] * 45]
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)

    def run():
        eng = make_engine()
        eng.start()
        try:
            streams = [eng.submit(p, sp) for p in prompts]
            finish(streams)
            return [list(s.token_ids) for s in streams]
        finally:
            eng.stop()

    with_scopes = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert run() == with_scopes


# ------------------------------------------------------------ build log


def test_build_log_counts_a_cold_and_a_cached_build(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    compile_cache.install_build_log()
    cache_dir = "jax_compilation_" + "cache_dir"   # (one writer: a grep)
    old = {k: getattr(jax.config, k) for k in (
        cache_dir, "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update(cache_dir, str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        def build():
            # a fresh function object: nothing in-process remembers it
            return jax.jit(lambda x: jnp.tanh(x @ x).sum() * 3.25)(
                jnp.ones((32, 32)))
        a = compile_cache.build_log()
        build()
        b = compile_cache.build_log()
        build()
        c = compile_cache.build_log()
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
    cold = {k: b[k] - a[k] for k in a}
    warm = {k: c[k] - b[k] for k in b}
    assert cold["programs_built"] >= 1 and warm["programs_built"] >= 1
    assert cold["program_cache_hits"] == 0 and cold["program_compile_s"] > 0
    assert cold["program_trace_s"] > 0 and cold["program_lower_s"] > 0
    assert warm["program_cache_hits"] >= 1
    assert warm["program_cache_load_s"] > 0
    assert warm["program_compile_s"] >= -1e-9      # the load is not in it
    assert set(a) == {"programs_built", "program_trace_s", "program_lower_s",
                      "program_compile_s", "program_cache_load_s",
                      "program_cache_hits"}


def test_engine_stats_carry_the_build_log_and_the_pool():
    eng = make_engine()
    stats = eng.stats
    for key in ("programs_built", "program_trace_s", "program_lower_s",
                "program_compile_s", "program_cache_load_s",
                "program_cache_hits", "pool_used_pages",
                "pool_blocked_rounds"):
        assert key in stats, key
    assert stats["pool_used_pages"] == 0
    assert stats["programs_built"] == compile_cache.build_log()[
        "programs_built"]


# ------------------------------------------------------------- the pool


def test_pool_used_pages_returns_to_idle_after_a_drained_burst():
    eng = make_engine(max_slots=2)
    eng.start()
    try:
        sp = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
        finish([eng.submit([3 + i] * 40, sp) for i in range(4)])
        time.sleep(0.2)
        recs = [r for r in eng.rounds.records() if r.done]
        assert max(r.pool_used_pages for r in recs) >= 3   # 48 tokens
        assert eng._pool_used_pages() == 0     # warm prefix pages are free
        assert eng.stats["pool_used_pages"] == recs[-1].pool_used_pages
        assert all(r.blocked_on_pages == 0 for r in recs)
        assert eng.stats["pool_blocked_rounds"] == 0
    finally:
        eng.stop()


def test_blocked_on_pages_rises_when_the_pool_holds_one_request():
    # 40 in + 24 out = 64 tokens = 4 pages: a pool of 5 holds one request
    eng = make_engine(max_slots=2, max_output_length=24,
                      kv_pool_tokens=5 * PAGE, prefix_cache=False)
    eng.start()
    try:
        sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
        finish([eng.submit([3 + i] * 40, sp) for i in range(2)])
        time.sleep(0.1)
        recs = eng.rounds.records()
        blocked = [r for r in recs if r.blocked_on_pages > 0]
        # the gauge is taken when a round BEGINS: the round that admits
        # the first request and refuses the second still reads 0
        assert blocked and all(r.pool_used_pages >= 4 for r in blocked[1:])
        assert eng.stats["pool_blocked_rounds"] == len(blocked)
        assert eng._pool_used_pages() == 0
    finally:
        eng.stop()
