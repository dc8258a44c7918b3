"""The engine end to end over a tiny model with recurrent layers
(``Engine.submit``, the scheduler, chunk programs of one and of several
prompts, decode rounds, the fused tail), on the CPU: its greedy tokens
are the plain forward's while other slots prefill, decode, finish and
are reused; the state is reserved and counted; the prefix cache is off
for such a model and says so; and everything that cannot take a state
yet refuses it BY NAME when the engine is configured."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.engine import (Engine, EngineConfig,
                                                    SamplingParams)
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.obs.rounds import RoundRecorder
from generativeaiexamples_tpu.utils.errors import ConfigError, EngineError

from test_recurrent_layers import CFG

ENGINE = dict(max_slots=4, max_input_length=512, max_output_length=32,
              prefill_buckets=(64,), max_prefill_bucket=64, page_size=32,
              steps_per_round=4, kv_pool_tokens=None, dtype="float32")
N_OUT = 10


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, 250, n)]


@jax.jit
def _logits(params, ids, n):
    out, _ = llama.apply(params, CFG, ids[None],
                         jnp.arange(ids.shape[0])[None])
    return out[0, n - 1]


def plain_greedy(params, ids, n):
    """The plain forward's own greedy chain, no cache: one buffer (the
    longest prompt here is 400 tokens), a forward a token (causal: what
    follows a position does not move it)."""
    buf = np.zeros(400 + N_OUT, np.int32)
    buf[:len(ids)] = ids
    for at in range(len(ids), len(ids) + n):
        buf[at] = int(jnp.argmax(_logits(params, jnp.asarray(buf), at)))
    return [int(t) for t in buf[len(ids):len(ids) + n]]


def submit(engine, ids, n=N_OUT):
    return engine.submit(ids, SamplingParams(max_tokens=n, temperature=0.0,
                                             ignore_eos=True))


def serve(engine, ids, n=N_OUT):
    s = submit(engine, ids, n)
    list(s)
    assert s.finish_reason == "length"
    return list(s.token_ids)


def make_engine(params, **kw):
    eng = Engine(params, CFG, ByteTokenizer(), EngineConfig(**ENGINE, **kw))
    eng.rounds = RoundRecorder(cap=512)
    return eng


@pytest.fixture(scope="module")
def engine(params):
    eng = make_engine(params)
    eng.start()
    yield eng
    eng.stop()


@pytest.mark.parametrize("n", [300, 40, 65], ids=[
    "five_chunks", "one_bucket", "a_chunk_and_one_token"])
def test_engine_tokens_are_the_plain_forwards(engine, params, n):
    """300 tokens: five chunk programs carrying state and tail from one
    to the next, the last padded; 40: the bucket prefill (the dense
    cache's state inserted into the slot); then decode rounds."""
    ids = prompt(n, n)
    assert serve(engine, ids) == plain_greedy(params, ids, N_OUT)


def test_slots_prefill_decode_finish_and_are_reused(engine, params):
    """Seven requests over four slots: prompts chunked while other slots
    decode (an idle row of a decode round is a slot mid-prefill), rows
    that finish inside a round (its surplus steps), chunk programs of
    several prompts, and slots taken again after a finished request —
    every one answers as a fresh engine would: the plain forward's
    tokens."""
    sizes = (300, 200, 260, 140, 70, 400, 33)
    outs = (10, 7, 10, 5, 9, 10, 6)
    ps = [prompt(n, 100 + n) for n in sizes]
    streams = [submit(engine, p, o) for p, o in zip(ps, outs)]
    for p, o, s in zip(ps, outs, streams):
        list(s)
        assert list(s.token_ids) == plain_greedy(params, p, o), len(p)
    assert len({s.timeline.meta["slot"] for s in streams}) < len(streams)


def test_a_cancelled_requests_slot_serves_the_next(engine, params):
    long = submit(engine, prompt(400, 5), 32)
    for _ in long:
        break                       # decoding: its state is in the slot
    long.cancel()
    list(long)
    ids = prompt(150, 6)
    assert serve(engine, ids) == plain_greedy(params, ids, N_OUT)


def test_the_state_is_reserved_and_counted(engine):
    st = engine.stats
    slot = 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)        # float32 activations
    assert st["slot_bytes"] == slot
    assert st["state_bytes"] == 4 * slot
    assert st["kv_bytes_per_token"] == 2 * 2 * 64 * 2 * 4   # 2 layers of 4
    cache = engine._state["cache"]
    assert cache["s"].shape[:2] == (2, 4) and cache["k"].shape[0] == 2
    assert cache["s"].nbytes + cache["conv"].nbytes == st["state_bytes"]
    # ... before the pool is sized: in the headroom the sizer subtracts
    assert engine._headroom_bytes() >= st["state_bytes"] + (256 << 20)
    assert st["local_assignments_rounds"] > 0       # the share's scalars


def test_the_prefix_cache_is_off_and_says_so(engine, params):
    """A hit would skip the chunks that compute the state: the cache is
    off for such a model whatever the configuration asks, with a
    counter, and a prompt served twice gives the same tokens twice."""
    assert engine.cfg.prefix_cache and engine._prefix_cache is None
    assert engine.stats["prefix_cache_off"] == 1
    ids = prompt(200, 11)
    first = serve(engine, ids)
    again = serve(engine, ids)
    assert first == again == plain_greedy(params, ids, N_OUT)
    assert engine.stats.get("prefix_cache_hit_tokens", 0) == 0


def test_an_engine_without_the_state_reports_none():
    from test_layer_kinds_moe import TINY
    cfg = TINY["mixtral"]
    p = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    eng = Engine(p, cfg, ByteTokenizer(), EngineConfig(**ENGINE))
    st = eng.stats
    assert st["state_bytes"] == st["slot_bytes"] == 0
    assert st["prefix_cache_off"] == 0 and eng._prefix_cache is not None
    assert not eng.programs.spec.state_step_kernel
    assert st["state_rows_idle"] == 0 == st["state_rows_live"]


def test_suspend_resume_and_handoff_refuse(engine):
    """They ship host-tier blobs, and the tier refuses a state."""
    with pytest.raises(EngineError, match="tiering is disabled"):
        engine.suspend_session(prompt(200, 1))
    with pytest.raises(EngineError, match="tiering is disabled"):
        engine.resume_session(b"")
    with pytest.raises(EngineError, match="tiering is disabled"):
        engine.export_handoff(prompt(200, 1))


# --------------------------------------------- refused at configuration


def refused(params, match, mesh=None, cfg=CFG, **kw):
    with pytest.raises(ConfigError, match=match) as err:
        Engine(params, cfg, ByteTokenizer(),
               EngineConfig(**{**ENGINE, **kw}), mesh=mesh)
    # the message names the mechanism that refused, and only that one
    assert "a recurrent state (full_attention_interval=2) refuses" \
        in str(err.value)
    assert "kv_lora_rank" not in str(err.value)


def test_speculative_decoding_is_refused(params, monkeypatch):
    refused(params, "speculative decoding", spec_decode=True)
    monkeypatch.setenv("ENGINE_SPEC_DECODE", "1")
    refused(params, "rolled back by length")


def test_an_int8_kv_pool_is_refused(params):
    refused(params, "int8 KV pool", kv_quant="int8")


def test_the_host_kv_tier_is_refused(params, monkeypatch):
    refused(params, "host KV tier", kv_host_pool_tokens=4096)
    monkeypatch.setenv("KV_HOST_POOL_TOKENS", "4096")
    refused(params, "suspend, resume and handoff")


@pytest.mark.parametrize("role", ["prefill", "decode"])
def test_prefill_decode_handoff_is_refused(params, role):
    refused(params, "handoff", role=role)


@pytest.mark.parametrize("axis", ["tp", "sp", "ep"])
def test_a_mesh_is_refused(params, axis):
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2])
    # every expert held: an expert share refuses tp and ep for itself
    whole = dataclasses.replace(CFG, experts_held=0, experts_first=0)
    refused(llama.init_params(whole, jax.random.key(3), jnp.float32),
            "no sharding over tp, sp, ep or pp", cfg=whole,
            mesh=Mesh(devs.reshape(1, 2), ("dp", axis)))


def test_the_refusal_names_the_mechanism_that_refused():
    """The small repair: a latent model's refusal no longer prints an
    expert share's and a stream's keys."""
    from test_latent_attention import CFG as LATENT
    p = llama.init_params(LATENT, jax.random.key(0), dtype=jnp.float32)
    with pytest.raises(ConfigError) as err:
        Engine(p, LATENT, ByteTokenizer(),
               EngineConfig(**{**ENGINE, "page_size": 128,
                               "prefill_buckets": (128,),
                               "max_prefill_bucket": 128,
                               "kv_quant": "int8"}))
    msg = str(err.value)
    assert msg.startswith("a latent KV pool (kv_lora_rank=")
    assert "experts_held" not in msg and "hc_mult" not in msg


# ------------------------------------------- the chunked scan as a kernel


def _served_by(params, cfg, prompts, n=6):
    """Greedy tokens, the slots' final state and the settled stats of an
    engine over ``cfg`` serving ``prompts`` one after the other."""
    eng = Engine(params, cfg, ByteTokenizer(), EngineConfig(**{
        **ENGINE, "page_size": 64, "prefill_buckets": (64,),
        "max_prefill_bucket": 64}))
    eng.start()
    try:
        tokens = [serve(eng, ids, n) for ids in prompts]
    finally:
        eng.stop()
    return tokens, np.asarray(eng._state["cache"]["s"]), eng.stats


def test_chunk_programs_over_the_scan_kernel_serve_the_xla_forms_tokens(
        monkeypatch):
    """A tiny model at widths the scan kernel takes (128-lane heads, a
    whole group of key heads with two value heads each, pages of whole
    blocks): its chunk programs with the kernel (interpreted here, armed
    as a TPU arms it) serve the greedy tokens and leave the state of the
    same engine over the XLA form — a prompt of two chunks, the second
    from the first's state and padded, and a one-bucket prompt — and
    count every chunk program."""
    from generativeaiexamples_tpu.ops import gated_delta as gd
    cfg = dataclasses.replace(
        CFG, linear_key_head_dim=128, linear_value_head_dim=128,
        linear_num_key_heads=gd._SCAN_PAIRS,
        linear_num_value_heads=2 * gd._SCAN_PAIRS)
    p = llama.init_params(cfg, jax.random.key(3), dtype=jnp.float32)
    prompts = [prompt(100, 21), prompt(40, 22)]
    want, want_s, plain = _served_by(p, cfg, prompts)
    assert plain["scan_kernel"] == 0 == plain["scan_kernel_chunks"]
    assert plain["downgrades"] == 0 and plain["sched_chunk_programs"] > 0
    monkeypatch.setattr(gd, "scan_kernel_armed", gd.scan_kernel_supported)
    traced, kernel = [], gd.gated_delta_chunked_kernel
    monkeypatch.setattr(gd, "gated_delta_chunked_kernel",
                        lambda *a, **kw: traced.append(a[0].shape)
                        or kernel(*a, **kw))
    got, got_s, stats = _served_by(p, cfg, prompts)
    assert got == want and (1, 64, 128 * gd._SCAN_PAIRS) in traced
    assert np.abs(got_s - want_s).max() <= 2e-5 * np.abs(want_s).max()
    assert stats["scan_kernel"] == 1 and stats["downgrades"] == 0
    assert stats["scan_kernel_chunks"] == stats["sched_chunk_programs"] \
        == plain["sched_chunk_programs"]


def test_a_tpu_engine_that_cannot_take_the_scan_kernel_says_so(
        params, monkeypatch):
    """16-lane heads on a "TPU": the chunk programs keep the XLA form
    and the engine records ONE downgrade, by name; a model without
    recurrent layers records none."""
    from test_layer_kinds_moe import TINY
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = make_engine(params)
    assert [d["feature"] for d in eng.downgrades] == ["scan_kernel"]
    assert eng.downgrades[0]["fallback"] == "xla_chunked"
    assert eng.stats["scan_kernel"] == 0 and eng.stats["downgrades"] == 1
    cfg = TINY["mixtral"]
    p = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    plain = Engine(p, cfg, ByteTokenizer(), EngineConfig(**ENGINE))
    assert plain.downgrades == [] and plain.stats["scan_kernel"] == 0


# -------------------------- the decode step as the kernel over the leaf

# widths the two decode kernels take: the paged attention's and the
# recurrence's own step over the cache's whole state leaf
KCFG = dataclasses.replace(CFG, head_dim=128, linear_key_head_dim=128,
                           linear_value_head_dim=128,
                           linear_num_value_heads=8)


@pytest.fixture(scope="module")
def kernel_engine():
    """An engine whose decode rounds run the Pallas kernels (wanted by
    the environment: the CPU does not want them by itself; interpreted):
    every recurrent layer's step is ``gated_delta_step_kernel`` over all
    four slots' state, which walks the live rows and moves no other."""
    p = llama.init_params(KCFG, jax.random.key(3), dtype=jnp.float32)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("GENAI_TPU_PAGED_KERNEL", "1")
        eng = Engine(p, KCFG, ByteTokenizer(), EngineConfig(**{
            **ENGINE, "page_size": 128, "prefill_buckets": (128,),
            "max_prefill_bucket": 128}))
    eng.rounds = RoundRecorder(cap=512)
    assert eng._use_kernel and eng.programs.spec.state_step_kernel
    assert eng.downgrades == []
    eng.start()
    yield eng
    eng.stop()


def test_a_slot_mid_prefill_keeps_its_state_across_decode_rounds(
        kernel_engine):
    """The case where an idle row's state is live data: a prompt of
    four chunks admitted beside a decoding neighbour, every dispatch
    slowed so that the neighbour's decode rounds come back between the
    chunk programs (the CPU queues four chunks in a millisecond). In
    those rounds the prompt's slot is idle — the step kernel names no
    block of it — and the next chunk reads the state the last one left:
    its tokens are its tokens alone, and the neighbour's its own."""
    from generativeaiexamples_tpu.utils import faults
    eng = kernel_engine
    near, far = prompt(40, 31), prompt(500, 32)
    alone = [serve(eng, near, 32), serve(eng, far)]
    seen = len(eng.rounds.records())
    faults.set_plan("engine.dispatch=delay:0.3")
    try:
        first = submit(eng, near, 32)
        for _ in first:
            break                   # decoding: the rounds have begun
        second = submit(eng, far)
        assert [list(first) and list(first.token_ids),
                list(second) and list(second.token_ids)] == alone
    finally:
        faults.clear()
    recs = eng.rounds.records()[seen:]
    chunks = [r.round_id for r in recs if any(
        rid == second.timeline.request_id for rid, _ in r.grants)]
    between = [r for r in recs if r.decode_slots
               and chunks[0] <= r.round_id <= chunks[-1]]
    assert len(chunks) == 4 and len(between) >= 2 and all(
        r.state_rows_idle_pct == 75.0 for r in between)


def test_the_rows_the_step_kernel_leaves_alone_are_counted(
        kernel_engine, engine):
    """One live row of four slots: three quarters of the state leaf's
    rows idle in every decoding round, by the record and by the
    counters; a round that decoded nothing, an engine whose step is
    XLA's and a model without recurrent layers carry no such field."""
    before = dict(kernel_engine.stats)
    seen = len(kernel_engine.rounds.records())
    serve(kernel_engine, prompt(200, 33))
    st = kernel_engine.stats
    steps = st["decode_steps"] - before["decode_steps"]
    assert st["state_rows_idle"] - before["state_rows_idle"] == 3 * steps
    assert st["state_rows_live"] - before["state_rows_live"] == steps > 0
    recs = kernel_engine.rounds.records()[seen:]
    decoding = [r for r in recs if r.decode_slots]
    assert decoding and all(r.state_rows_idle_pct == 75.0 for r in decoding)
    assert decoding[-1].to_dict()["outcome"]["state_rows_idle_pct"] == 75.0
    chunks = [r for r in recs if not r.decode_slots]
    assert chunks and not any(hasattr(r, "state_rows_idle_pct")
                              for r in chunks)
    assert chunks[0].to_dict()["outcome"]["state_rows_idle_pct"] is None
    # the XLA step (16-lane heads, the CPU's path): nothing to report
    assert not engine.programs.spec.state_step_kernel
    serve(engine, prompt(40, 34))
    assert not any(hasattr(r, "state_rows_idle_pct")
                   for r in engine.rounds.records())
    assert engine.stats["state_rows_idle"] == 0 == engine.stats[
        "state_rows_live"]
