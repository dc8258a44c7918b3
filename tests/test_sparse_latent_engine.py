"""The engine end to end over a tiny model with learned sparse attention
and an expert share (``Engine.submit``, the scheduler, the one paged pool
with its index leaf, chunk programs, decode rounds, the fused tail), on
the CPU, at contexts ABOVE its ``index_topk``: its greedy tokens are the
plain forward's; a prefix-cache hit serves latent rows AND index keys
(pages are pages); a speculative verify round works over the pool; the
selection's counters count; and everything that cannot take this pool
refuses it BY NAME when the engine is configured."""

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

from test_sparse_latent_attention import CFG as SPARSE, TOPK

CFG = dataclasses.replace(SPARSE, experts_held=4, experts_first=4)
ENGINE = dict(max_slots=4, max_input_length=512, max_output_length=32,
              prefill_buckets=(128,), max_prefill_bucket=128, page_size=128,
              steps_per_round=4, kv_pool_tokens=None, dtype="float32")
N_OUT = 10


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, 250, n)]


@jax.jit
def _forward(params, ids):
    return llama.apply(params, CFG, ids, jnp.arange(ids.shape[1])[None])[0]


def plain_greedy(params, ids, n):
    """The plain forward's own greedy chain, no cache: ONE compiled
    program over a fixed length (causal: what follows a position does not
    move it)."""
    ids = list(ids)
    for _ in range(n):
        padded = jnp.asarray(ids + [0] * (544 - len(ids)))[None]
        ids.append(int(jnp.argmax(_forward(params, padded)[0, len(ids) - 1])))
    return ids[-n:]


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
    """300 tokens (above ``index_topk``): three 128-token chunks, the
    later ones reading latent rows and index keys back from the pool,
    then decode rounds that select 160 of ~300; 50: the bucket prefill
    (a dense cache of three leaves, then whole pages inserted)."""
    assert n < TOPK or n > TOPK + 100
    ids = prompt(n, n)
    assert serve(engine, ids) == plain_greedy(params, ids, N_OUT)


def test_the_selection_and_the_cache_are_counted(engine):
    before = dict(engine.stats)
    serve(engine, prompt(300, 7))
    st = engine.stats
    # 6 latent rows and 2 index keys a token, float32
    assert st["kv_bytes_per_token"] == (6 * (128 + 32) + 2 * 64) * 4
    assert st["index_bytes_per_token"] == 2 * 64 * 4
    selected = st["kv_rows_selected"] - before["kv_rows_selected"]
    indexed = st["kv_rows_indexed"] - before["kv_rows_indexed"]
    steps = st["decode_steps"] - before["decode_steps"]
    # every step of this one live row reads 160 chosen rows of ~305
    assert selected == TOPK * steps
    assert 300 * steps < indexed < 312 * steps
    recs = [r for r in engine.rounds.records() if r.kv_rows_selected]
    assert recs and all(45 < r.kv_selected_pct < 55 for r in recs[-2:])
    assert "kv_rows_indexed" in recs[0].to_dict()["outcome"]
    assert st["local_assignments_rounds"] > 0


def test_a_prefix_cache_hit_serves_rows_and_index_keys(engine, params):
    ids = prompt(390, 11)
    first = serve(engine, ids)
    hits0 = engine.stats["prefix_cache_hit_tokens"]
    again = serve(engine, ids)
    assert engine.stats["prefix_cache_hit_tokens"] >= hits0 + 384
    # the last chunk's full layers scored index keys they did not write
    assert again == first == plain_greedy(params, ids, N_OUT)


def test_speculative_verify_runs_over_the_sparse_pool(params, monkeypatch):
    monkeypatch.setenv("SPEC_NGRAM_MIN", "1")
    eng = make_engine(params, spec_decode=True, spec_max_draft_tokens=3)
    eng.start()
    try:
        # nearly every token of the vocabulary once: whatever the model
        # says, the prompt holds it and the drafter proposes what follows
        ids = list(range(3, 503))
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
