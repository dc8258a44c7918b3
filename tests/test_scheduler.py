"""Token-budget continuous scheduler (engine/scheduler.py): budget
packing, slack ordering, chunk accounting — plus engine-level proof that
chunked prefill actually interleaves with decode (a long prompt no
longer blocks a concurrent short request's first token) while a
decode-only workload plans exactly the rounds it always got."""

import time

import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.engine import Engine, EngineConfig, SamplingParams
from generativeaiexamples_tpu.engine.scheduler import (
    PrefillJob, RoundPlan, StepCostModel, TokenBudgetScheduler,
    derive_round_budget)
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer

CFG = LlamaConfig(vocab_size=259 + 5, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position_embeddings=256)

PAGE = 16


def make_sched(budget=64, chunk=None, cost=None, one_shot_cap=64):
    return TokenBudgetScheduler(
        cost or StepCostModel(decode_step_ms=2.0, prefill_ms_per_token=0.25),
        page_size=PAGE, steps_per_round=4, round_budget_tokens=budget,
        chunk_tokens=chunk, max_one_shot_tokens=one_shot_cap)


# --------------------------------------------------------- cost model


def test_cost_model_from_profile_and_default_prefill_estimate():
    m = StepCostModel.from_profile({
        "full_ms_per_step": 3.0, "slots": 4,
        "prefill_ms_per_token": 0.5})
    assert m.decode_step_ms == 3.0 and m.prefill_ms_per_token == 0.5
    # artifacts predating the prefill measurement estimate it from the
    # decode step (per-slot cost / 4x batching efficiency)
    old = StepCostModel.from_profile({"full_ms_per_step": 4.0, "slots": 8})
    assert old.prefill_ms_per_token == pytest.approx(4.0 / 8 / 4)
    assert old.prefill_s(1000) == pytest.approx(0.125)


def test_derive_round_budget_page_quantized_and_floored():
    m = StepCostModel(decode_step_ms=2.0, prefill_ms_per_token=0.25)
    # 4 steps * 2 ms / 0.25 ms per token = 32 tokens -> 2 pages of 16
    assert derive_round_budget(m, 4, PAGE) == 32
    # a pathological model still yields at least one page
    tiny = StepCostModel(decode_step_ms=0.001, prefill_ms_per_token=10.0)
    assert derive_round_budget(tiny, 4, PAGE) == PAGE


def test_load_falls_back_to_defaults(tmp_path, monkeypatch):
    monkeypatch.setenv("SCHED_PROFILE_JSON", str(tmp_path / "missing.json"))
    # unreadable env path falls through to the committed artifact or the
    # defaults — never raises
    m = StepCostModel.load()
    assert m.decode_step_ms > 0 and m.prefill_ms_per_token > 0


# --------------------------------------------------- topology-keyed rows


def test_topology_key_canonicalization():
    from generativeaiexamples_tpu.engine.scheduler import topology_key

    assert topology_key(None) == "tp=1"
    assert topology_key({}) == "tp=1"
    # trivial axes drop; non-trivial ones sort, so one canonical label
    # per mesh shape however the dict was built
    assert topology_key({"dp": 1, "pp": 1, "tp": 2}) == "tp=2"
    assert topology_key({"tp": 2, "sp": 4}) == "sp=4,tp=2"
    assert topology_key({"dp": 1, "sp": 1, "tp": 1}) == "tp=1"


def test_load_matches_topology_row(tmp_path, monkeypatch):
    """Topology precedence (docs/scheduler.md): an artifact's own label
    (absent == tp=1) or a ``topologies`` row matching the engine's mesh
    wins; the row's keys override the shared fields; with no matching
    row anywhere the newest parseable artifact is used as-is."""
    import json

    art = tmp_path / "PROFILE_topo.json"
    art.write_text(json.dumps({
        "full_ms_per_step": 2.0, "prefill_ms_per_token": 0.25,
        "slots": 8,
        "topologies": {"tp=2": {"full_ms_per_step": 1.5,
                                "prefill_ms_per_token": 0.125}},
    }))
    monkeypatch.setenv("SCHED_PROFILE_JSON", str(art))

    single = StepCostModel.load(topology="tp=1")
    assert single.decode_step_ms == 2.0
    assert single.topology == "tp=1"

    tp2 = StepCostModel.load(topology="tp=2")
    assert tp2.decode_step_ms == 1.5
    assert tp2.prefill_ms_per_token == 0.125
    assert tp2.topology == "tp=2"
    assert tp2.source.endswith("@tp=2")
    # the budgets the two rows derive DIFFER — the acceptance-criterion
    # fact the multichip bench pins end-to-end
    assert derive_round_budget(tp2, 4, PAGE) != \
        derive_round_budget(single, 4, PAGE)

    # no matching row: the artifact still beats built-in defaults, and
    # its topology field records the mismatch (tp=1 measurement)
    tp4 = StepCostModel.load(topology="tp=4")
    assert tp4.decode_step_ms == 2.0 and tp4.topology == "tp=1"


def test_load_artifact_own_topology_label(tmp_path, monkeypatch):
    """A --mesh-generated artifact (topology stamped at top level) is
    matched by label; a tp=1 engine skips it in favor of an untagged
    (single-chip) artifact even when the tagged one sorts newer."""
    import json

    (tmp_path / "PROFILE_r98.json").write_text(json.dumps({
        "full_ms_per_step": 3.0, "prefill_ms_per_token": 0.3,
        "slots": 8}))
    (tmp_path / "PROFILE_r99.json").write_text(json.dumps({
        "full_ms_per_step": 1.0, "prefill_ms_per_token": 0.1,
        "slots": 8, "topology": "tp=2"}))
    monkeypatch.chdir(tmp_path)
    import generativeaiexamples_tpu.engine.scheduler as sched
    monkeypatch.setattr(sched, "_REPO_ROOT", str(tmp_path))

    tp2 = StepCostModel.load(topology="tp=2")
    assert tp2.decode_step_ms == 1.0 and tp2.topology == "tp=2"
    single = StepCostModel.load(topology="tp=1")
    assert single.decode_step_ms == 3.0 and single.topology == "tp=1"


# ------------------------------------------------------ budget packing


def test_plan_decode_only_unchanged():
    plan = make_sched().plan_round(decode_steps=4, active_decodes=2)
    assert plan.decode_steps == 4 and not plan.chunks
    assert plan.decode_cost_tokens == 8
    assert not plan.interleaved


def test_plan_respects_budget_and_page_quantizes():
    sched = make_sched(budget=48)
    long_job = PrefillJob(key="long", remaining=200, seq=0, started=True)
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[long_job])
    # whole leftover, quantized down to whole pages, never over budget
    assert plan.chunks == [("long", 48)]
    assert plan.prefill_tokens <= plan.budget_tokens


def test_plan_decode_cost_shrinks_prefill_share():
    sched = make_sched(budget=64)
    job = PrefillJob(key="j", remaining=500, seq=0, started=True)
    # 4 steps x 2 active slots = 8 token-equivalents of decode cost;
    # the prefill grant shrinks accordingly (56 -> 48 after paging)
    plan = sched.plan_round(decode_steps=4, active_decodes=2,
                            inflight=[job])
    assert plan.decode_cost_tokens == 8
    assert plan.chunks == [("j", 48)]
    assert plan.interleaved


def test_plan_liveness_floor_under_decode_saturation():
    # decode eats the whole budget; a waiting prefill still gets a page
    sched = make_sched(budget=32)
    job = PrefillJob(key="j", remaining=100, seq=0, started=True)
    plan = sched.plan_round(decode_steps=4, active_decodes=32,
                            inflight=[job])
    assert plan.chunks == [("j", PAGE)]


def test_plan_idle_engine_one_shots_a_lone_short_prompt():
    sched = make_sched(budget=PAGE, one_shot_cap=64)
    job = PrefillJob(key="j", remaining=30, seq=0)
    plan = sched.plan_round(decode_steps=0, active_decodes=0, backlog=[job])
    # nothing to protect: the whole prompt goes in one grant even though
    # it exceeds the budget — up to 2x the budget
    assert plan.chunks == [("j", 30)]
    # ...beyond 2x the budget a lone prompt CHUNKS even on an idle
    # engine: a dispatched grant is un-preemptible, so an unbounded
    # one-shot would re-open the prefill wall for the next arrival
    big = PrefillJob(key="b", remaining=60, seq=0)
    plan = sched.plan_round(decode_steps=0, active_decodes=0, backlog=[big])
    assert plan.chunks[0][1] <= 2 * PAGE
    # the bucket cap binds when it is the smaller of the two
    tight = make_sched(budget=64, one_shot_cap=PAGE)
    huge = PrefillJob(key="h", remaining=65, seq=0)
    plan = tight.plan_round(decode_steps=0, active_decodes=0, backlog=[huge])
    assert plan.chunks[0][1] <= PAGE


def test_plan_fair_share_admits_short_behind_long():
    # The acceptance shape: a long in-flight prefill plus a short
    # waiting prompt. Fair-share packing must grant the short its WHOLE
    # prompt this round (it fits the share), not starve it behind the
    # long prefill.
    sched = make_sched(budget=32)
    long_job = PrefillJob(key="long", remaining=100, seq=0, started=True)
    short_job = PrefillJob(key="short", remaining=8, seq=1)
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[long_job], backlog=[short_job])
    grants = dict(plan.chunks)
    assert grants["short"] == 8          # final grant, sub-page allowed
    assert grants["long"] >= PAGE        # long still progresses
    assert plan.prefill_tokens <= plan.budget_tokens


def test_plan_greedy_second_pass_uses_leftover():
    # one small job + one big job, lots of budget: the big job gets the
    # share AND the leftover the small job didn't need
    sched = make_sched(budget=64)
    big = PrefillJob(key="big", remaining=300, seq=0, started=True)
    small = PrefillJob(key="small", remaining=8, seq=1, started=True)
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[big, small])
    grants = dict(plan.chunks)
    assert grants["small"] == 8
    assert grants["big"] == 48  # 64 - 8 = 56 -> page-quantized 48


def test_plan_max_new_caps_admissions_to_free_slots():
    """``max_new`` (the engine's free-slot count) bounds how many
    backlog jobs get grants — budget is never split across jobs the
    executor cannot admit, and the slack-ordered FRONT of the backlog
    is what gets through, not arrival order."""
    sched = make_sched(budget=64)
    inflight = PrefillJob(key="busy", remaining=200, seq=0, started=True)
    relaxed = PrefillJob(key="relaxed", remaining=32, seq=1,
                         deadline_t=100.0)
    urgent = PrefillJob(key="urgent", remaining=32, seq=2, deadline_t=1.0)
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[inflight],
                            backlog=[relaxed, urgent], now=0.0, max_new=1)
    grants = dict(plan.chunks)
    assert "urgent" in grants          # smallest slack wins the slot
    assert "relaxed" not in grants     # no grant for a job with no slot
    # the budget the capped job would have eaten goes to live work
    assert grants["busy"] >= PAGE
    assert plan.prefill_tokens <= plan.budget_tokens


def test_plan_chunk_cap_bounds_single_grant():
    sched = make_sched(budget=64, chunk=PAGE)
    job = PrefillJob(key="j", remaining=500, seq=0, started=True)
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[job])
    assert plan.chunks == [("j", PAGE)]


def test_plan_chunk_grants_capped_at_prefill_bucket():
    """A grant can never exceed the largest compiled prefill bucket —
    the engine clamps the dispatch there, so a bigger grant would burn
    budget on tokens that never execute."""
    sched = TokenBudgetScheduler(
        StepCostModel(decode_step_ms=2.0, prefill_ms_per_token=0.25),
        page_size=PAGE, steps_per_round=4, round_budget_tokens=256,
        max_one_shot_tokens=64)
    a = PrefillJob(key="a", remaining=500, seq=0, started=True)
    b = PrefillJob(key="b", remaining=500, seq=1, started=True)
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[a, b])
    grants = dict(plan.chunks)
    assert max(grants.values()) <= 64
    # the budget the cap freed went to the OTHER job, not to waste
    assert grants["a"] + grants["b"] > 64


def test_plan_scarcity_rotation_bounds_single_page_starvation():
    """1-page leftover (the PROFILE-derived default budget on real
    configs) + two jobs: a fixed packing order would hand the same job
    the page every round. Rotation alternates, so the second job's wait
    for its first page is bounded by ~len(jobs) rounds."""
    sched = make_sched(budget=PAGE)
    first_page_owner = []
    for _ in range(4):
        long_job = PrefillJob(key="long", remaining=400, seq=0,
                              started=True)
        short_job = PrefillJob(key="short", remaining=8, seq=1)
        plan = sched.plan_round(decode_steps=0, active_decodes=1,
                                inflight=[long_job], backlog=[short_job])
        assert plan.prefill_tokens >= 8  # liveness floor every round
        first_page_owner.append(plan.chunks[0][0])
    assert "short" in first_page_owner    # the waiter got a round
    assert "long" in first_page_owner     # the long prefill still moves


# ------------------------------------------ grants in the device's shapes
#
# The engine hands the planner its chunk programs' shapes (the bucket
# ladder); a scheduler built without one takes every page multiple as a
# shape. One algorithm over three sets of shapes.

BIG_PAGE = 128
LADDERS = {"one-bucket": (512,), "shipped": (128, 512), "none": None}


def ladder_sched(ladder, budget=512, chunk=None):
    return TokenBudgetScheduler(
        StepCostModel(decode_step_ms=2.0, prefill_ms_per_token=0.25),
        page_size=BIG_PAGE, steps_per_round=8, round_budget_tokens=budget,
        chunk_tokens=chunk, chunk_shapes=ladder,
        max_one_shot_tokens=None if ladder else 512)


def charge(sched, remaining, n):
    """What a grant of ``n`` to a job with ``remaining`` left costs."""
    return sched._charge(sched.shapes_of(remaining), n)


def run_plans(sched, remaining, plans, *, decode_steps=0, active=0):
    """Plan ``plans`` rounds over in-flight jobs {key: remaining},
    applying each plan's grants; returns per plan [(key, grant,
    remaining before the grant)]."""
    out = []
    for _ in range(plans):
        jobs = [PrefillJob(key=k, remaining=r, seq=i, started=True)
                for i, (k, r) in enumerate(remaining.items()) if r > 0]
        plan = sched.plan_round(decode_steps=decode_steps,
                                active_decodes=active, inflight=jobs)
        out.append([(k, n, remaining[k]) for k, n in plan.chunks])
        for k, n in plan.chunks:
            remaining[k] -= n
    return out


def test_ladder_is_the_largest_grant_and_one_shot_cap():
    sched = ladder_sched((128, 512), budget=4096)
    assert sched.max_one_shot_tokens == 512 and sched.chunk_tokens == 512
    # a ladder that starts at one page has no finer shapes under it
    assert sched.shapes_of(4000) == sched.shapes_of(300) == (128, 512)
    # one bucket: a job past it is granted whole buckets only; a job
    # that fits one program keeps the page multiples under it
    one = ladder_sched((512,))
    assert one.shapes_of(513) == (512,)
    assert one.shapes_of(512) == (128, 256, 384, 512)
    assert [charge(one, 2000, n) for n in (1, 130, 512)] == [512] * 3
    assert [charge(one, 300, n) for n in (1, 130, 300)] == [128, 256, 384]
    # no ladder: every page multiple up to the chunk cap is a shape
    loose = ladder_sched(None, budget=384)
    assert loose.shapes_of(4000) == loose.shapes_of(100) == (128, 256, 384)


def test_four_waiting_prompts_take_turns_at_one_whole_bucket():
    """Budget 512, one 512 bucket, four 2000-token prompts: the parent
    cut the budget into four 128-token pages, each run in a 512-shaped
    program. Now ONE job a plan gets the whole bucket, and rotation
    reaches every job within four plans."""
    sched = ladder_sched((512,))
    remaining = {k: 2000 for k in "abcd"}
    chunks = run_plans(sched, remaining, 4)
    assert all(len(c) == 1 and c[0][1] == 512 for c in chunks)
    assert sorted(c[0][0] for c in chunks) == list("abcd")


@pytest.mark.parametrize("ladder", list(LADDERS))
@pytest.mark.parametrize("jobs,decodes", [(1, 0), (2, 3), (4, 1), (5, 16)])
def test_every_nonfinal_grant_is_a_whole_shape(ladder, jobs, decodes):
    sched = ladder_sched(LADDERS[ladder])
    remaining = {f"j{i}": 700 + 391 * i for i in range(jobs)}
    start = dict(remaining)
    for chunks in run_plans(sched, remaining, 40, decode_steps=8,
                            active=decodes):
        assert chunks                       # liveness: work every plan
        charged = 0
        for key, n, before in chunks:
            assert n > 0
            if n < before:      # non-final: exactly one of its shapes
                assert n in sched.shapes_of(before), (key, n, before)
                if before > 512:
                    assert n in (LADDERS[ladder] or range(128, 513, 128))
            charged += charge(sched, before, n)
        # the budget is charged the shapes, decode's cost first, with
        # the floor of one smallest shape
        assert charged <= max(512 - 8 * decodes, sched.shapes_of(4000)[0])
        if not any(remaining.values()):
            break
    # every prompt is computed whole, tails included
    assert not any(remaining.values()), (start, remaining)


@pytest.mark.parametrize("ladder,grant", [("one-bucket", 512),
                                          ("shipped", 128),
                                          ("none", 384)])
def test_decoding_batch_does_not_cut_a_lone_prefill_under_a_bucket(
        ladder, grant):
    """512 - 8 steps x 10 rows leaves 432: with one 512 bucket the floor
    is the whole bucket (the parent page-quantised to 384 inside a 512
    program); a ladder with a smaller shape grants that shape; without
    a ladder the page-multiple meaning holds."""
    sched = ladder_sched(LADDERS[ladder])
    job = PrefillJob(key="j", remaining=2000, seq=0, started=True)
    plan = sched.plan_round(decode_steps=8, active_decodes=10,
                            inflight=[job])
    assert plan.chunks == [("j", grant)]
    # ...and a lone 500-token prompt is one program, not 384 + 116
    if ladder != "none":
        job = PrefillJob(key="j", remaining=500, seq=0)
        plan = sched.plan_round(decode_steps=8, active_decodes=10,
                                backlog=[job], max_new=1)
        assert plan.chunks == [("j", 500 if ladder == "one-bucket"
                                else 128)]


def test_final_grant_keeps_its_tail_and_a_long_job_pays_its_bucket():
    sched = ladder_sched((512,), budget=1024)
    a = PrefillJob(key="a", remaining=130, seq=0, started=True)
    b = PrefillJob(key="b", remaining=2000, seq=1, started=True)
    c = PrefillJob(key="c", remaining=2000, seq=2, started=True)
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[a, b])
    assert dict(plan.chunks) == {"a": 130, "b": 512}
    # a long job is charged its bucket whatever it computes; the tail
    # is charged its pages (256): two buckets do not pay for all three
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[b, c, a])
    assert sorted(n for _, n in plan.chunks) == [130, 512]
    b.remaining = c.remaining = 600     # 512 now, an 88-token tail next
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[b, c])
    assert dict(plan.chunks) == {"b": 512, "c": 512}
    # a ladder with a small shape cuts a tail to it rather than pad it
    # to the next: 128 now, two tokens in a 128 program next
    small = ladder_sched((128, 512))
    plan = small.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[a, b])
    assert dict(plan.chunks) == {"a": 128, "b": 128}
    a.remaining = 100
    plan = small.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[a, b])
    assert dict(plan.chunks) == {"a": 100, "b": 128}


@pytest.mark.parametrize("ladder", list(LADDERS))
def test_short_prompt_behind_long_prefill_granted_within_len_jobs(ladder):
    """A 2816-token prefill in flight, a short prompt arrives: its
    grant comes within len(jobs) plans, not after the long one."""
    sched = ladder_sched(LADDERS[ladder])
    long_left = 2816
    waited = 0
    for _ in range(2):
        long_job = PrefillJob(key="long", remaining=long_left, seq=0,
                              started=True)
        short = PrefillJob(key="short", remaining=90, seq=1)
        plan = sched.plan_round(decode_steps=8, active_decodes=2,
                                inflight=[long_job], backlog=[short],
                                max_new=4)
        grants = dict(plan.chunks)
        long_left -= grants.get("long", 0)
        if "short" in grants:
            assert grants["short"] == 90
            break
        waited += 1
    else:
        pytest.fail("short prompt starved behind the long prefill")
    assert waited < 2 and long_left > 0


def test_backlog_job_without_a_grant_is_not_in_the_plan():
    """Under scarcity a waiting backlog job that gets nothing this plan
    takes no slot and no pages: it is simply absent from the chunks."""
    sched = ladder_sched((512,))
    inflight = PrefillJob(key="busy", remaining=2000, seq=0, started=True)
    waiting = [PrefillJob(key=f"w{i}", remaining=1500, seq=1 + i)
               for i in range(3)]
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[inflight], backlog=waiting,
                            max_new=3)
    assert len(plan.chunks) == 1 and plan.chunks[0][1] == 512


def test_fair_share_is_the_largest_shape_under_an_equal_split():
    # 8320 = 16 x 512 + 8 x 16: sixteen prompts and a decoding batch
    # still leave every prompt a whole bucket
    sched = ladder_sched((512,), budget=8320)
    jobs = [PrefillJob(key=i, remaining=4000, seq=i, started=True)
            for i in range(15)]
    plan = sched.plan_round(decode_steps=8, active_decodes=1, inflight=jobs)
    assert [n for _, n in plan.chunks] == [512] * 15
    # the chunk cap bounds one job's grant a plan: the second pass does
    # not hand a job a second bucket
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=jobs[:3])
    assert [n for _, n in plan.chunks] == [512] * 3


def test_chunk_cap_under_the_smallest_shape_grants_that_shape():
    # a cap finer than any compiled program would only be padding
    sched = ladder_sched((512,), budget=512, chunk=128)
    job = PrefillJob(key="j", remaining=2000, seq=0, started=True)
    plan = sched.plan_round(decode_steps=8, active_decodes=4,
                            inflight=[job])
    assert plan.chunks == [("j", 512)]


def test_prompts_that_fit_one_program_still_share_in_pages():
    """Four 300-token prompts beside a decoding request, one 512
    bucket: each gets a page, as before whole-shape grants. Kept for
    the benchmark's warm-up, which reaches the chunk programs of small
    windows (first, middle and final chunk of a SHORT prompt) only
    through such shared grants (scheduler.shapes_of)."""
    sched = ladder_sched((512,))
    remaining = {k: 300 for k in "abcd"}
    chunks = run_plans(sched, remaining, 3, decode_steps=8, active=1)
    assert [sorted(n for _, n, _ in c) for c in chunks] \
        == [[128] * 4, [128] * 4, [44] * 4]
    # the tail of a long prompt is such a job too: among long jobs it
    # takes its turn and finishes in one program
    sched = ladder_sched((512,))
    tail = PrefillJob(key="tail", remaining=300, seq=0, started=True)
    longs = [PrefillJob(key=f"l{i}", remaining=2000, seq=1 + i,
                        started=True) for i in range(3)]
    seen = []
    for _ in range(4):
        plan = sched.plan_round(decode_steps=8, active_decodes=1,
                                inflight=[tail] + longs)
        assert len(plan.chunks) == 1
        seen.append(plan.chunks[0])
    assert ("tail", 300) in seen
    assert sorted(k for k, _ in seen) == ["l0", "l1", "l2", "tail"]


# ------------------------------------------------------- slack ordering


def test_slack_ordering_deadlines_first_then_arrival():
    sched = make_sched()
    now = 100.0
    relaxed = PrefillJob(key="r", remaining=64, deadline_t=now + 10, seq=0)
    urgent = PrefillJob(key="u", remaining=64, deadline_t=now + 0.1, seq=1)
    nodeadline_a = PrefillJob(key="a", remaining=64, seq=2)
    nodeadline_b = PrefillJob(key="b", remaining=64, seq=3)
    order = [j.key for j in sched.order(
        [nodeadline_b, relaxed, nodeadline_a, urgent], now)]
    assert order == ["u", "r", "a", "b"]


def test_slack_accounts_for_prefill_time():
    # same deadline, different prompt length: the longer prompt has less
    # slack (its prefill eats more of the budget) and goes first
    sched = make_sched(cost=StepCostModel(decode_step_ms=2.0,
                                          prefill_ms_per_token=1.0))
    now = 0.0
    short_p = PrefillJob(key="s", remaining=10, deadline_t=1.0, seq=0)
    long_p = PrefillJob(key="l", remaining=900, deadline_t=1.0, seq=1)
    assert sched.slack_s(long_p, now) < sched.slack_s(short_p, now)
    assert [j.key for j in sched.order([short_p, long_p], now)] == ["l", "s"]


def test_chunk_accounting_with_prefix_cache_hit():
    # a warm request's job carries only the UNCACHED suffix, so its
    # grants (and modeled slack) shrink by the cached prefix
    sched = make_sched(budget=32)
    cold = PrefillJob(key="cold", remaining=64, seq=0, started=True)
    warm = PrefillJob(key="warm", remaining=16, seq=1, started=True)
    plan = sched.plan_round(decode_steps=0, active_decodes=0,
                            inflight=[cold, warm])
    grants = dict(plan.chunks)
    assert grants["warm"] == 16          # the suffix completes this round
    assert grants["cold"] == 16
    assert sched.cost.prefill_s(warm.remaining) < \
        sched.cost.prefill_s(cold.remaining)


# --------------------------------------------------------- engine-level


def _engine(**over):
    cfg = dict(max_slots=2, max_input_length=64, max_output_length=16,
               prefill_buckets=(16, 32, 64), dtype="float32",
               page_size=PAGE, kv_pool_tokens=None, max_queue=64,
               steps_per_round=4)
    cfg.update(over)
    params = llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    return Engine(params, CFG, ByteTokenizer(), EngineConfig(**cfg))


def test_engine_interleaves_short_past_long_prefill():
    """One long + one short prompt submitted together: the short
    request's first token must land BEFORE the long prompt finishes its
    chunked prefill — the prefill wall this PR exists to kill. (Before
    the scheduler, admission ran the long prefill to completion first:
    the long request's first token always beat the short's.)"""
    eng = _engine(sched_round_budget_tokens=32)
    try:
        long_s = eng.submit([5] * 64, SamplingParams(max_tokens=4, top_k=1,
                                                     ignore_eos=True))
        short_s = eng.submit([9] * 8, SamplingParams(max_tokens=4, top_k=1,
                                                     ignore_eos=True))
        eng.start()   # both requests are in the same first round plan
        short_s.text()
        long_s.text()
        assert short_s.first_token_time < long_s.first_token_time
        assert len(short_s.token_ids) == 4 and len(long_s.token_ids) == 4
        stats = eng.stats
        # the long prompt streamed through in >= 2 budget-sized chunks
        assert stats["sched_prefill_tokens"] >= 64 + 8
        assert stats["sched_round_budget_tokens"] == 32
        # decode rounds for the short request ran while the long prompt
        # was still prefilling — the interleaving itself
        assert stats["sched_interleaved_rounds"] >= 1
    finally:
        eng.stop()


def test_engine_chunked_output_matches_one_shot():
    """Forcing tiny chunks must not change WHAT the long prompt
    generates — chunked paged prefill is exact (same math as the
    one-shot bucket, modulo dispatch boundaries)."""
    prompt = [3 + (i % 7) for i in range(64)]
    sp = SamplingParams(max_tokens=6, top_k=1, ignore_eos=True)
    # One engine serves both phases (prefix cache off so the second run
    # really recomputes): a lone prompt on an IDLE engine one-shots even
    # under a tiny budget — the idle fast-path — then a decoding
    # neighbor keeps the engine busy so the resubmission takes the
    # chunked path.
    eng = _engine(sched_round_budget_tokens=PAGE, prefix_cache=False)
    try:
        eng.start()
        one_shot = eng.submit(prompt, sp)
        one_shot.text()
        assert eng.stats["sched_interleaved_rounds"] == 0
        noise = eng.submit([11] * 8, SamplingParams(
            max_tokens=16, top_k=1, ignore_eos=True))
        chunked = eng.submit(prompt, sp)
        chunked.text()
        noise.text()
        assert eng.stats["sched_interleaved_rounds"] >= 1
    finally:
        eng.stop()
    assert chunked.token_ids == one_shot.token_ids


def test_engine_concurrent_prompts_fill_their_chunk_programs(tmp_path):
    """Three multi-chunk prompts at once under a ONE-bucket ladder: the
    planner hands the round's bucket to one prompt at a time, so the
    chunk programs run full (the page-sized fair shares of before ran
    each a quarter full), the tokens are those of each prompt served
    alone, and the padded-token counter is the sum of what the
    ``chunk_dispatch`` spans say each program was padded to."""
    import glob
    import os

    from jax.profiler import ProfileData

    eng = _engine(max_slots=4, max_input_length=200, max_output_length=8,
                  prefill_buckets=(64,), max_prefill_bucket=64,
                  sched_round_budget_tokens=64, prefix_cache=False)
    assert eng._buckets == (64,)
    prompts = [[3 + (i * 5 + j) % 11 for i in range(n)]
               for j, n in enumerate((150, 170, 131))]
    sp = SamplingParams(max_tokens=6, top_k=1, ignore_eos=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        together = [eng.submit(p, sp) for p in prompts]
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            eng.start()
            for s in together:
                s.text()
        finally:
            jax.profiler.stop_trace()
        stats = eng.stats
        alone = []
        for p in prompts:
            alone.append(eng.submit(p, sp))
            alone[-1].text()
    finally:
        eng.stop()
    assert [s.token_ids for s in together] == [s.token_ids for s in alone]
    assert stats["sched_prefill_tokens"] == sum(map(len, prompts))
    assert stats["sched_prefill_fill"] >= 0.7
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = [dict(e.stats)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name == "chunk_dispatch"]
    assert spans and all(s["padded"] == 64 for s in spans)
    assert sum(s["padded"] for s in spans) \
        == stats["sched_prefill_padded_tokens"]
    assert sum(s["tokens"] for s in spans) == stats["sched_prefill_tokens"]
    recs = [r for r in eng.rounds.records()
            if r.engine_tag == eng.engine_tag]
    assert sum(r.prefill_padded_tokens for r in recs) \
        == eng.stats["sched_prefill_padded_tokens"]


def test_engine_holds_back_a_prompt_the_pool_refused():
    """The pool holds one request. The second prompt is offered to the
    planner, refused for want of pages ONCE, and then held back until
    the pool can give more: under one-bucket rotation a plan's only
    grant must not go, round after round, to a prompt that cannot
    start. The rounds it waits through still count as pool-blocked."""
    # 150 in + 8 out = 158 tokens = 10 pages: a pool of 12 holds one
    eng = _engine(max_slots=4, max_input_length=200, max_output_length=8,
                  prefill_buckets=(64,), max_prefill_bucket=64,
                  sched_round_budget_tokens=64, prefix_cache=False,
                  kv_pool_tokens=12 * PAGE)
    refusals = []
    begin = eng._begin_prefill

    def counting(req, rec=None):
        ok = begin(req, rec)
        if ok is False:
            refusals.append(req.stream.request_id)
        return ok

    eng._begin_prefill = counting
    sp = SamplingParams(max_tokens=8, top_k=1, ignore_eos=True)
    prompts = [[3 + (i + j) % 7 for i in range(150)] for j in range(2)]
    try:
        streams = [eng.submit(p, sp) for p in prompts]
        eng.start()
        for s in streams:
            s.text()
        stats = eng.stats
        recs = [r for r in eng.rounds.records()
                if r.engine_tag == eng.engine_tag]
    finally:
        eng.stop()
    assert all(len(s.token_ids) == 8 for s in streams)
    assert refusals == [streams[1].request_id]
    # the first prompt's three chunks and its decode rounds all ran
    # while the second waited on the pool
    assert stats["pool_blocked_rounds"] >= 3
    assert stats["pool_blocked_rounds"] \
        == sum(1 for r in recs if r.blocked_on_pages > 0)
    assert stats["sched_prefill_fill"] >= 0.7


def test_engine_decode_only_rounds_unchanged():
    """No prefill pending: the plan dispatches full steps_per_round
    rounds with a right-sized tail — exactly the pre-scheduler cadence
    (tokens per round unchanged; nothing counted as interleaved)."""
    eng = _engine(max_slots=1, steps_per_round=8)
    try:
        eng.start()
        s = eng.submit([7] * 8, SamplingParams(max_tokens=17, top_k=1,
                                               ignore_eos=True))
        s.text()
    finally:
        eng.stop()
    # read SETTLED: the harvest worker ends the stream the moment it has
    # the last round, while the loop that dispatched it is still
    # counting it (``sched_decode_tokens``); stop() joined both
    stats = eng.stats
    recs = [r for r in eng.rounds.records()
            if r.engine_tag == eng.engine_tag and r.decode_steps]
    assert len(s.token_ids) == 17
    # 1 prefill token + 16 decode tokens in rounds of 8
    emitting = [r for r in recs if r.tokens_emitted]
    assert [(r.decode_steps, r.tokens_emitted) for r in emitting] \
        == [(8, 8), (8, 8)]
    # The loop plans by ``proj_pos``, an UPPER bound on the device's
    # position that counts the prefill's token as a step still to take:
    # where it runs ahead of the harvest that retires the request it
    # dispatches one more round, of the one step it thinks is left,
    # which decodes nothing (ROADMAP D17). Whether it got there first is
    # a race; that nothing else was dispatched is not.
    assert [r.decode_steps for r in recs if not r.tokens_emitted] \
        in ([], [1])
    assert stats["decode_steps"] == sum(r.decode_steps for r in recs)
    assert stats["sched_decode_tokens"] == stats["decode_steps"]
    assert stats["harvest_rounds"] >= 2
    assert stats["sched_interleaved_rounds"] == 0


def test_engine_budget_env_override(monkeypatch):
    monkeypatch.setenv("SCHED_ROUND_BUDGET_TOKENS", "48")
    eng = _engine()
    try:
        assert eng._sched.round_budget_tokens == 48
        assert eng.stats["sched_round_budget_tokens"] == 48
    finally:
        eng.stop()


def test_engine_warm_admission_prefills_suffix_only():
    """PR-1 interaction: a prefix-cache hit shrinks the chunk plan — the
    warm admission's granted prefill tokens cover only the uncached
    suffix."""
    eng = _engine(max_slots=1)
    try:
        eng.start()
        prompt = [4 + (i % 9) for i in range(32)]
        sp = SamplingParams(max_tokens=2, top_k=1, ignore_eos=True)
        eng.submit(prompt, sp).text()
        cold_tokens = eng.stats["sched_prefill_tokens"]
        eng.submit(prompt, sp).text()
        warm_tokens = eng.stats["sched_prefill_tokens"] - cold_tokens
        hit = eng.stats["prefix_cache_hit_tokens"]
        assert hit > 0
        assert warm_tokens == len(prompt) - hit
        assert warm_tokens < cold_tokens
    finally:
        eng.stop()


def test_engine_stats_expose_sched_gauges():
    eng = _engine()
    try:
        stats = eng.stats
        for key in ("sched_round_budget_tokens", "sched_prefill_tokens",
                    "sched_prefill_padded_tokens",
                    "sched_decode_tokens", "sched_interleaved_rounds",
                    "sched_prefill_share", "sched_prefill_fill"):
            assert key in stats
        assert stats["sched_round_budget_tokens"] >= PAGE
        assert stats["sched_prefill_share"] == 0.0
        assert stats["sched_prefill_fill"] == 0.0
    finally:
        eng.stop()


def test_engine_deadline_sheds_from_reordered_backlog():
    """PR-5 integration: queue-expired requests shed via deadline_queue
    from anywhere in the backlog (not just FIFO head), and deadline'd
    traffic is admitted ahead of earlier-arrived no-deadline traffic."""
    eng = _engine(max_slots=1)
    try:
        # occupy the only slot so later submissions queue
        busy = eng.submit([7] * 8, SamplingParams(max_tokens=16, top_k=1,
                                                  ignore_eos=True))
        eng.start()
        filler = eng.submit([8] * 8, SamplingParams(max_tokens=2, top_k=1,
                                                    ignore_eos=True))
        expired = eng.submit([9] * 8, SamplingParams(max_tokens=2),
                             deadline_t=time.monotonic())  # already past
        assert expired.text() == ""
        assert expired.finish_reason == "deadline_queue"
        busy.text()
        filler.text()
        assert eng.stats["deadline_queue_drops"] == 1
    finally:
        eng.stop()
