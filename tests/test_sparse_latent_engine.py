"""The engine end to end over a tiny model with learned sparse attention
and an expert share (``Engine.submit``, the scheduler, the one paged pool
with its index leaf, chunk programs, decode rounds, the fused tail), on
the CPU, at contexts ABOVE its ``index_topk``: its greedy tokens are the
plain forward's; a prefix-cache hit serves latent rows AND index keys
(pages are pages); a speculative verify round works over the pool; the
selection's counters count; the decode rounds over the latent decode
kernel with the keep mask as an operand (interpreted) serve the same
tokens, count what they stream, and reserve no gathered window; and
everything that cannot take this pool refuses it BY NAME when the engine
is configured."""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.engine import (Engine, EngineConfig,
                                                    SamplingParams)
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.kv_cache import kv_cache_of
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


# ---------------------------------------- decode rounds over the kernel


@pytest.fixture(scope="module")
def kernel_engine(params):
    """The same engine with the decode kernel wanted (by the environment:
    the CPU does not want it by itself), interpreted: the latent decode
    kernel with the keep mask as an operand, the pool in the carry."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("GENAI_TPU_PAGED_KERNEL", "1")
        eng = make_engine(params)
    assert eng._use_kernel and eng.programs.spec.use_prefix_kernel
    assert eng.downgrades == []
    eng.start()
    yield eng
    eng.stop()


def test_engine_tokens_through_the_kernel_are_the_plain_forwards(
        kernel_engine, params):
    """Three chunks through the chunk kernel, then decode rounds whose
    attention is the masked decode kernel: 160 of ~300 kept."""
    ids = prompt(300, 300)
    assert serve(kernel_engine, ids) == plain_greedy(params, ids, N_OUT)


@pytest.mark.parametrize("form", ["gathered", "kernel"])
def test_the_rows_a_step_streams_are_counted(engine, kernel_engine, form):
    """One live row of ~305 tokens, 160 selected a step: gathered, every
    slot's window at the round's rung (4 slots x 4 pages); over the
    kernel the row's cached context in whole blocks of four pages (one
    block)."""
    eng = engine if form == "gathered" else kernel_engine
    before = dict(eng.stats)
    serve(eng, prompt(300, 7))
    st = eng.stats
    steps = st["decode_steps"] - before["decode_steps"]
    read = st["kv_rows_read"] - before["kv_rows_read"]
    selected = st["kv_rows_selected"] - before["kv_rows_selected"]
    want = 4 * 4 * 128 if form == "gathered" else 4 * 128
    assert read == want * steps and selected == TOPK * steps
    recs = [r for r in eng.rounds.records() if r.kv_rows_selected]
    assert recs and all(r.kv_read_per_selected == want / TOPK
                        for r in recs[-2:])
    assert recs[-1].to_dict()["outcome"]["kv_read_per_selected"] \
        == round(want / TOPK, 2)


def test_the_headroom_holds_the_index_window_not_sixteen_windows(
        engine, kernel_engine):
    """Over the kernel the decode step gathers no window of latent rows:
    the reserve drops every slot's gathered window and keeps the full
    layers' index window and its scores (all index heads at once)."""
    kvc = kv_cache_of(CFG)
    keys = 4 * 5 * 128                  # slots x window pages x page
    window = keys * kvc.token_bytes(4)
    index = keys * (64 * 4 + 4 * CFG.index_n_heads)
    assert kvc.index_window_bytes(4, 5 * 128, 4) == index < window
    assert engine._headroom_bytes() - kernel_engine._headroom_bytes() \
        == window - index
    # what stays is there under both forms: the chunk's selection
    chunk = kvc.select_bytes(128, 5 * 128 + 128)
    assert kernel_engine._headroom_bytes() > index + chunk


def test_the_read_per_selected_metric_names_its_reader_and_its_cell():
    """``sparse_read_per_selected``: a data file over a reader the
    benchmark has; on a program without the counter it reads nothing and
    does not raise."""
    from benchmarks.harness.spec import Spec
    from benchmarks.readers import decode_round_fields
    spec = Spec()
    name = "sparse_read_per_selected"
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    names = [m["name"] for m in spec.doc["per_layer"]]     # appended: after
    assert names.index(name) > names.index("sparse_selected_pct")  # PR 40's
    assert entry["workloads"][0] == "glm-5.2.long-context-mixed-16"
    assert (entry["moves"], entry["layer"], entry["unit"], entry["source"],
            entry["better"]) == ("out_tok_per_s", "kernels", "count",
                                 "program_counter", "lower")
    metric = spec.layer_metric(name)
    assert metric["reader"] == "decode_round_fields"
    assert metric["args"] == {"field": "kv_read_per_selected",
                              "per": "round"}
    with open(os.path.join(os.path.dirname(spec.path), "benchmarks",
                           "layer_metrics", name + ".json")) as f:
        assert {k: entry[k] for k in ("unit", "better", "source", "layer")} \
            == {k: v for k, v in json.load(f).items() if k in entry}
    rec = types.SimpleNamespace
    ctx = rec(rounds=[
        rec(decode_slots=0, decode_steps=0, kv_read_per_selected=0.0),
        rec(decode_slots=5, decode_steps=8, kv_read_per_selected=4.0),
        rec(decode_slots=4, decode_steps=8, kv_read_per_selected=4.5)])
    assert decode_round_fields.read(ctx, **metric["args"]) \
        == pytest.approx(4.25)
    old = rec(rounds=[rec(decode_slots=3, decode_steps=8,
                          kv_selected_pct=25.0)])
    assert decode_round_fields.read(old, **metric["args"]) is None


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
