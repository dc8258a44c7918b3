"""The engine loop's chunk program of several prompts
(``engine.py`` ``_execute_plan_inner`` / ``_advance_prefill_rows``): the
whole-bucket non-final grants of ONE plan run four to a program while no
stream is decoding. Tiny model on the CPU, a one-bucket ladder of 64
tokens.

What is held: the tokens are those of each prompt served alone; programs
are fewer than grants; a member that was cancelled or ran out of time
between its chunks leaves, the others finish; the pool's refusal stops
admission in plan order with its cause; capacity routing, a budget of
one bucket and a decoding stream build no program of rows; one
``chunk_dispatch`` span a program and one ``req_chunk`` child a member;
the request record closes.
"""

import dataclasses
import glob
import os
import time
import types

import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness.context import Context, read_layer_metric
from benchmarks.harness.loadgen import Row
from benchmarks.harness.spec import Spec
from benchmarks.harness.traffic import Request
from benchmarks.readers import request_life
from generativeaiexamples_tpu.engine import Engine, EngineConfig, SamplingParams
from generativeaiexamples_tpu.engine.engine import engine_stat_keys
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.utils import faults

PAGE, C = 16, 64
CFG = LlamaConfig(vocab_size=259 + 5, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position_embeddings=512)
# the same widths with four experts, capacity-routed or dropless
MOE = dict(num_experts=4, num_experts_per_tok=2, intermediate_size=32)
SP = SamplingParams(max_tokens=6, top_k=1, ignore_eos=True)
# two whole buckets and a tail, three and a tail, ...: every prompt has
# whole-bucket non-final chunks, at different starts once one finishes
LENS = (150, 214, 131, 280)


def engine(model=CFG, **over):
    cfg = dict(max_slots=4, max_input_length=300, max_output_length=8,
               prefill_buckets=(C,), max_prefill_bucket=C, dtype="float32",
               page_size=PAGE, kv_pool_tokens=None, max_queue=64,
               steps_per_round=4, prefix_cache=False,
               sched_round_budget_tokens=4 * C + 4 * 4)
    cfg.update(over)
    params = llama.init_params(model, jax.random.key(3), dtype=jnp.float32)
    return Engine(params, model, ByteTokenizer(), EngineConfig(**cfg))


def prompts(lens=LENS):
    return [[3 + (i * 5 + j) % 11 for i in range(n)]
            for j, n in enumerate(lens)]


def row_keys(eng) -> list:
    return sorted(k for k in eng._chunk_fns if k[0] == "extend_rows")


def records(eng) -> list:
    return [r for r in eng.rounds.records() if r.engine_tag == eng.engine_tag]


def serve(eng, ps, sp=SP) -> list:
    streams = [eng.submit(p, sp) for p in ps]
    eng.start()
    for s in streams:
        s.text()
    return streams


@pytest.fixture(scope="module")
def grouped(tmp_path_factory):
    """Four long prompts submitted together under a budget that grants
    each a whole chunk a plan, traced; then each alone."""
    from jax.profiler import ProfileData

    tmp = str(tmp_path_factory.mktemp("trace"))
    eng = engine()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        t0 = time.monotonic()
        streams = [eng.submit(p, SP) for p in prompts()]
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            eng.start()
            for s in streams:
                s.text()
        finally:
            jax.profiler.stop_trace()
        t_end = time.monotonic()
        stats, recs, keys = eng.stats, records(eng), row_keys(eng)
        traffic = (eng._param_bytes, eng._kv_bytes_per_token())
        alone = []
        for p in prompts():
            alone.append(eng.submit(p, SP))
            alone[-1].text()
        stats_alone = eng.stats
    finally:
        eng.stop()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = [dict(e.stats)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name == "chunk_dispatch"]
    return types.SimpleNamespace(
        streams=streams, alone=alone, stats=stats, stats_alone=stats_alone,
        recs=recs, keys=keys, spans=spans, t0=t0, t_end=t_end,
        traffic=traffic)


def test_grouped_prompts_serve_the_tokens_each_serves_alone(grouped):
    assert [s.token_ids for s in grouped.streams] \
        == [s.token_ids for s in grouped.alone]
    assert all(len(s.token_ids) == 6 for s in grouped.streams)


def test_programs_are_fewer_than_grants(grouped):
    st, recs = grouped.stats, grouped.recs
    n_grants = sum(len(r.grants) for r in recs)
    # 150, 214, 131, 280 tokens: 3 + 4 + 3 + 5 chunks
    assert n_grants == 15
    assert 0 < st["sched_chunk_programs"] < n_grants
    assert st["sched_prefill_tokens"] == sum(LENS)
    # the four first and the four second chunks in ONE program each;
    # then two are on their last chunk, and once those decode nothing
    # groups: 2 programs of four rows + 7 of one
    assert grouped.keys == [("extend_rows", 4)]
    assert st["sched_chunk_programs"] == 9
    assert "sched_chunk_programs" in engine_stat_keys()
    # served alone nothing groups: a program a grant
    alone = grouped.stats_alone["sched_chunk_programs"] \
        - st["sched_chunk_programs"]
    assert alone == n_grants


def test_prefill_rows_per_program_reads_above_one(grouped):
    m = Spec().layer_metric("prefill_rows_per_program")
    assert m["reader"] == "round_records" and m["unit"] == "count"
    ctx = Context(cell=types.SimpleNamespace(name="cpu"), rows=[],
                  t0=grouped.t0, t_end=grouped.t_end, drain_limit_s=5.0,
                  stats0={}, stats1=grouped.stats, rounds=grouped.recs)
    got = read_layer_metric(ctx, m)
    assert got == pytest.approx(15 / grouped.stats["sched_chunk_programs"])
    assert got > 1.5
    # a program without the counter (the parent) reads nothing
    ctx.stats1 = {k: v for k, v in grouped.stats.items()
                  if k != "sched_chunk_programs"}
    assert read_layer_metric(ctx, m) is None


def test_one_span_a_program_and_one_child_a_member(grouped):
    spans, st = grouped.spans, grouped.stats
    assert len(spans) == st["sched_chunk_programs"]
    assert sum(s["rows"] for s in spans) == 15
    assert sorted(s["rows"] for s in spans) == [1] * 7 + [4] * 2
    assert all(s["mode"] == "rows" and s["tokens"] == s["padded"]
               == s["rows"] * C for s in spans if s["rows"] > 1)
    assert sum(s["padded"] for s in spans) \
        == st["sched_prefill_padded_tokens"]
    assert sum(s["tokens"] for s in spans) == st["sched_prefill_tokens"]
    for s, n in zip(grouped.streams, LENS):
        chunks = [sp for sp in s.timeline.spans if sp.name == "req_chunk"]
        assert len(chunks) == -(-n // C)
        assert sum(sp.n for sp in chunks) == n
    # the weights once a program in the round's traffic estimate
    first = next(r for r in grouped.recs if len(r.grants) == 4)
    assert first.prefill_tokens == first.prefill_padded_tokens == 4 * C
    weights, kv = grouped.traffic
    assert first.hbm_bytes == weights + 4 * C * kv


def test_request_life_closes_on_a_run_that_grouped(grouped):
    rows = [Row(Request(i, p, 6, 1), due_t=s.submit_time,
                send_t=s.submit_time, stream=s)
            for i, (p, s) in enumerate(zip(prompts(), grouped.streams))]
    ctx = Context(cell=types.SimpleNamespace(name="cpu"), rows=rows,
                  t0=grouped.t0, t_end=grouped.t_end, drain_limit_s=5.0,
                  rounds=grouped.recs)
    assert request_life.read(ctx, "prefill_behind_chunks_ms") > 0
    note = ctx.notes["request_life"]
    assert note["requests"] == 4
    assert note["closure"]["life_err_ms_max"] < 1e-6
    assert note["closure"]["ttft_err_ms_max"] < 1e-6
    assert note["closure"]["mixed_pct"] == 0.0


@pytest.mark.parametrize("how", ["cancelled", "deadline"])
def test_a_member_that_leaves_between_chunks(how):
    """Four prompts in one program; before the next plan one is
    cancelled (or its deadline passes): it leaves its group and the
    other three finish with the tokens they serve alone."""
    eng = engine()
    ps = prompts((280, 280, 280, 280))
    try:
        # hold the loop after the first program so the abort lands
        # between chunks whatever the CPU's speed
        faults.set_plan("engine.dispatch=delay:0.3")
        streams = [eng.submit(p, SP) for p in ps]
        victim = streams[1]
        eng.start()
        time.sleep(0.15)
        if how == "cancelled":
            victim.cancel()
        else:
            req = next(r for r in eng._slots.values()
                       if r.stream is victim)
            req.deadline_t = time.monotonic() - 1.0
        faults.set_plan("")
        for s in streams:
            s.text()
        stats = eng.stats
        alone = []
        for p in ps:
            alone.append(eng.submit(p, SP))
            alone[-1].text()
    finally:
        faults.set_plan("")
        eng.stop()
    assert victim.finish_reason == how and not victim.token_ids
    for s, a in zip(streams, alone):
        if s is not victim:
            assert s.finish_reason == "length"
            assert s.token_ids == a.token_ids
    assert stats["deadline_stops"] == (how == "deadline")
    assert ("extend_rows", 4) in eng._chunk_fns


def test_a_pool_refusal_stops_admission_in_plan_order():
    """The pool holds four of six prompts: the fifth is refused for
    pages, it and the sixth wait with that cause, the first four run as
    ONE program of four rows; all six finish."""
    # 150 in + 8 out = 10 pages a request; 45 pages hold four
    eng = engine(kv_pool_tokens=45 * PAGE, max_slots=6,
                 sched_round_budget_tokens=6 * C + 6 * 4)
    ps = prompts((150,) * 6)
    refused = []
    begin = eng._begin_prefill

    def counting(req, rec=None):
        ok = begin(req, rec)
        if ok is False:
            refused.append(req.stream.request_id)
        return ok

    eng._begin_prefill = counting
    try:
        streams = serve(eng, ps)
        recs, stats = records(eng), eng.stats
    finally:
        eng.stop()
    assert all(len(s.token_ids) == 6 for s in streams)
    assert refused and refused[0] == streams[4].request_id
    first = recs[0]
    assert [rid for rid, _ in first.grants] \
        == [s.request_id for s in streams[:4]]
    assert first.blocked_on_pages == 1
    assert row_keys(eng) == [("extend_rows", 4)]
    for s in streams[4:]:
        causes = [sp.cause for sp in s.timeline.spans
                  if sp.name == "req_backlog"]
        assert "pages" in causes
    assert stats["pool_blocked_rounds"] >= 1


@pytest.mark.parametrize("case", ["sparse", "one_bucket_budget"])
def test_no_program_of_rows_is_built(case):
    """Capacity routing never groups (its drops depend on what is routed
    together); a budget of one bucket never plans two whole grants."""
    if case == "sparse":
        eng = engine(dataclasses.replace(CFG, moe_impl="sparse", **MOE))
    else:
        eng = engine(sched_round_budget_tokens=C)
    try:
        streams = serve(eng, prompts())
        stats = eng.stats
        n_grants = sum(len(r.grants) for r in records(eng))
    finally:
        eng.stop()
    assert all(len(s.token_ids) == 6 for s in streams)
    assert row_keys(eng) == []
    assert stats["sched_chunk_programs"] == n_grants


def test_dropless_experts_group_and_serve_the_same_tokens():
    eng = engine(dataclasses.replace(CFG, moe_impl="dropless", **MOE))
    try:
        together = serve(eng, prompts())
        keys = row_keys(eng)
        alone = []
        for p in prompts():
            alone.append(eng.submit(p, SP))
            alone[-1].text()
    finally:
        eng.stop()
    assert keys == [("extend_rows", 4)]
    assert [s.token_ids for s in together] == [s.token_ids for s in alone]


def test_beside_a_decoding_stream_every_grant_keeps_its_own_program():
    """The same four prompts, submitted while another stream decodes:
    no program of rows is built, a program a grant, the same tokens."""
    eng = engine(max_slots=5, max_output_length=400)
    try:
        eng.start()
        blocker = eng.submit([5, 6, 7], SamplingParams(
            max_tokens=400, top_k=1, ignore_eos=True))
        while blocker.first_token_time is None:     # its slot is armed
            time.sleep(0.01)
        n0 = eng.stats["sched_chunk_programs"]
        beside = [eng.submit(p, SP) for p in prompts()]
        for s in beside:
            s.text()
        still = blocker.finish_reason is None
        programs = eng.stats["sched_chunk_programs"] - n0
        keys = row_keys(eng)
        blocker.cancel()
        blocker.text()
        alone = []
        for p in prompts():
            alone.append(eng.submit(p, SP))
            alone[-1].text()
    finally:
        eng.stop()
    assert still and keys == [] and programs == 15
    assert [s.token_ids for s in beside] == [s.token_ids for s in alone]


def test_a_prefix_cache_hit_seeds_alone_then_joins():
    """A prompt whose first chunk seeds its seen mask from a prefix-cache
    hit takes the single-prompt program for that chunk and joins a
    program of rows with its next; repetition penalties see the whole
    prompt either way."""
    sp = SamplingParams(max_tokens=6, top_k=1, ignore_eos=True,
                        repetition_penalty=1.3)
    shared = [3 + i % 7 for i in range(4 * PAGE)]
    ps = [shared + [20 + (i * 3 + j) % 13 for i in range(200)]
          for j in range(4)]
    eng = engine(prefix_cache=True)
    try:
        eng.start()
        warm = eng.submit(shared + [40] * 30, sp)
        warm.text()                     # registers the shared pages
        together = [eng.submit(p, sp) for p in ps]
        for s in together:
            s.text()
        hits = eng.stats["prefix_cache_hits"]
        keys = row_keys(eng)
    finally:
        eng.stop()
    cold = engine(prefix_cache=False)
    try:
        alone = serve(cold, ps, sp)
    finally:
        cold.stop()
    assert hits >= 4 and keys
    assert [s.token_ids for s in together] == [s.token_ids for s in alone]
