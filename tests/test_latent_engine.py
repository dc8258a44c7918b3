"""The engine end to end over a tiny latent model with an expert share
(``Engine.submit``, the scheduler, the one paged pool, chunk programs,
decode rounds, the fused tail), on the CPU: its greedy tokens are the
plain forward's; a prefix-cache hit and a speculative verify round work
over the latent pool (pages are pages); and everything that cannot take
a latent pool or a share yet refuses it BY NAME when the engine is
configured."""

import dataclasses
import functools

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

from test_latent_attention import CFG as LATENT

CFG = dataclasses.replace(LATENT, experts_held=4, experts_first=4)
ENGINE = dict(max_slots=4, max_input_length=512, max_output_length=32,
              prefill_buckets=(128,), max_prefill_bucket=128, page_size=128,
              steps_per_round=4, kv_pool_tokens=None, dtype="float32")
N_OUT = 10


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, 250, n)]


@functools.partial(jax.jit, static_argnums=0)
def _logits(cfg, params, ids, n):
    out, _ = llama.apply(params, cfg, ids[None],
                         jnp.arange(ids.shape[0])[None])
    return out[0, n - 1]


def plain_greedy(params, ids, n, cfg=CFG):
    """The plain forward's own greedy chain, no cache: ONE compiled
    program over a fixed buffer (the longest prompt here is 447 tokens),
    a forward a token (causal: what follows a position does not move
    it) — a forward a LENGTH compiled the model once a token."""
    buf = np.zeros(512, np.int32)
    buf[:len(ids)] = ids
    for at in range(len(ids), len(ids) + n):
        buf[at] = int(jnp.argmax(_logits(cfg, params, jnp.asarray(buf), at)))
    return [int(t) for t in buf[len(ids):len(ids) + n]]


def serve(engine, ids, n=N_OUT):
    s = engine.submit(ids, SamplingParams(max_tokens=n, temperature=0.0,
                                          ignore_eos=True))
    list(s)
    assert s.finish_reason == "length"
    return list(s.token_ids)


def make_engine(params, **kw):
    """An engine with a round recorder of its own: the process-wide ring
    is other test files' too."""
    eng = Engine(params, CFG, ByteTokenizer(), EngineConfig(**ENGINE, **kw))
    eng.rounds = RoundRecorder(cap=512)
    return eng


@pytest.fixture(scope="module")
def engine(params):
    eng = make_engine(params)
    eng.start()
    yield eng
    eng.stop()


@pytest.mark.parametrize("n", [300, 50], ids=["three_chunks", "one_bucket"])
def test_engine_tokens_are_the_plain_forwards(engine, params, n):
    """300 tokens: three 128-token chunks, the later ones reading the
    earlier back from the latent pool; 50: the bucket prefill (dense
    latent cache, then whole pages inserted). Then decode rounds."""
    ids = prompt(n, n)
    assert serve(engine, ids) == plain_greedy(params, ids, N_OUT)


def test_the_share_and_the_cache_are_counted(engine):
    serve(engine, prompt(40, 7))
    st = engine.stats
    assert st["kv_bytes_per_token"] == CFG.num_layers * (128 + 16) * 4
    assert st["local_assignments_rounds"] > 0
    assert st["local_assignments_rounds"] == st["experts_touched_rounds"]
    # one live row: each of its assignments that stays here is an expert
    mean = st["local_assignments_sum"] / st["local_assignments_rounds"]
    assert 0 < mean <= CFG.num_experts_per_tok
    assert any(r.local_assignments > 0 for r in engine.rounds.records())
    # what serving them walked: whole trips of 16-row blocks, rows read
    # an assignment held on the round record and in the stats
    assert st["route_rows_read_rounds"] == st["local_assignments_rounds"]
    assert st["route_rows_per_assignment"] == pytest.approx(
        st["route_rows_read_sum"] / st["local_assignments_sum"], abs=0.01)
    recs = [r for r in engine.rounds.records()
            if r.engine_tag == engine.engine_tag and r.local_assignments > 0]
    assert all(r.route_rows_read >= 16 and r.route_rows_per_assignment
               == pytest.approx(r.route_rows_read / r.local_assignments)
               for r in recs)
    assert "route_rows_per_assignment" in recs[-1].to_dict()["outcome"]


def test_a_prefix_cache_hit_reads_the_latent_pool(engine, params):
    ids = prompt(290, 11)
    first = serve(engine, ids)
    hits0 = engine.stats["prefix_cache_hit_tokens"]
    again = serve(engine, ids)
    assert engine.stats["prefix_cache_hit_tokens"] >= hits0 + 256
    assert again == first == plain_greedy(params, ids, N_OUT)


def test_speculative_verify_runs_over_the_latent_pool(params, monkeypatch):
    # single tokens match: whatever the model says that the prompt holds
    # too gives the prompt-lookup drafter something to propose
    monkeypatch.setenv("SPEC_NGRAM_MIN", "1")
    eng = make_engine(params, spec_decode=True, spec_max_draft_tokens=3)
    eng.start()
    try:
        ids = list(range(3, 253)) + list(range(3, 200))
        got = serve(eng, ids, 24)
        assert eng.stats["spec_verify_rounds"] > 0
        assert eng.stats["spec_draft_tokens"] > 0
    finally:
        eng.stop()
    assert got == plain_greedy(params, ids, 24)


def test_suspend_and_resume_refuse(engine):
    """They ship host-tier blobs, and the tier refuses a latent pool."""
    with pytest.raises(EngineError, match="tiering is disabled"):
        engine.suspend_session(prompt(200, 1))
    with pytest.raises(EngineError, match="tiering is disabled"):
        engine.resume_session(b"")


# --------------------------------------------- refused at configuration


def refused(params, match, mesh=None, **kw):
    with pytest.raises(ConfigError, match=match):
        Engine(params, CFG, ByteTokenizer(),
               EngineConfig(**{**ENGINE, **kw}), mesh=mesh)


def test_an_int8_kv_pool_is_refused(params):
    refused(params, "int8 KV pool", kv_quant="int8")


def test_the_host_kv_tier_is_refused(params, monkeypatch):
    refused(params, "host KV tier", kv_host_pool_tokens=4096)
    monkeypatch.setenv("KV_HOST_POOL_TOKENS", "4096")
    refused(params, "host KV tier")


@pytest.mark.parametrize("role", ["prefill", "decode"])
def test_prefill_decode_handoff_is_refused(params, role):
    refused(params, "handoff", role=role)


@pytest.mark.parametrize("axis", ["tp", "sp"])
def test_a_tp_or_sp_mesh_is_refused(params, axis):
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2])
    refused(params, f"{axis} mesh",
            mesh=Mesh(devs.reshape(1, 2), ("dp", axis)))


def test_an_expert_share_under_an_ep_mesh_is_refused(params):
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2])
    refused(params, "expert share under an ep or tp mesh",
            mesh=Mesh(devs.reshape(1, 2), ("dp", "ep")))


# ------------------------------------------------- the chunk kernel armed


def test_an_engine_that_cannot_arm_the_chunk_kernel_says_so(params,
                                                            monkeypatch):
    """With the decode kernel wanted (here by the environment: the CPU
    does not want it by itself), heads 32 wide are no lane-aligned slice
    of a key block: ONE downgrade, named ``prefix_kernel``."""
    monkeypatch.setenv("GENAI_TPU_PAGED_KERNEL", "1")
    eng = make_engine(params)
    assert eng._use_kernel and not eng.programs.spec.use_prefix_kernel
    assert [(d["feature"], d["fallback"]) for d in eng.downgrades] \
        == [("prefix_kernel", "jnp_blocks")]
    assert eng.stats["downgrades"] == 1


def test_a_fully_armed_engine_reports_no_downgrade(monkeypatch):
    """The published head's widths (128 | 64, values 128): both kernels
    armed, interpreted here; a three-chunk prompt's tokens are the plain
    forward's."""
    monkeypatch.setenv("GENAI_TPU_PAGED_KERNEL", "1")
    cfg = dataclasses.replace(CFG, num_heads=2, head_dim=192,
                              qk_nope_head_dim=128, qk_rope_head_dim=64,
                              v_head_dim=128)
    p = llama.init_params(cfg, jax.random.key(5), dtype=jnp.float32)
    eng = Engine(p, cfg, ByteTokenizer(), EngineConfig(**ENGINE))
    assert eng._use_kernel and eng.programs.spec.use_prefix_kernel
    assert eng.downgrades == [] and eng.stats["downgrades"] == 0
    ids = prompt(300, 3)
    with eng:
        got = serve(eng, ids, 4)
    assert got == plain_greedy(p, ids, 4, cfg)
