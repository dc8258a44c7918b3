"""TP-sharded serving parity suite (tier-1, virtual 8-device CPU mesh).

The tentpole contract of the sharded decode hot path: a tp engine is the
SAME engine, faster — greedy output is token-identical to single-chip
with the fused sampler AND speculative decoding active, warm
prefix-cache turns included; the sharded tail never materializes
``(rows, V)`` on any chip (jaxpr-walked, shard_map bodies included); and
an un-shardable geometry downgrades OBSERVABLY (``engine_downgrades`` +
structured event), never silently."""

import logging

import numpy as np
import pytest

import jax
import jax.extend.core
import jax.numpy as jnp

from generativeaiexamples_tpu.engine import (Engine, EngineConfig,
                                             SamplingParams)
from generativeaiexamples_tpu.engine import programs
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.parallel import MeshPlan, make_mesh

# vocab 320 shards over tp=2 into 160-token halves (whole 32-token mask
# words); heads 4 / kv-heads 2 divide tp=2. Over tp=4 the 80-token
# shard breaks the mask-word rule — the downgrade test uses that.
CFG = LlamaConfig(vocab_size=320, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position_embeddings=512)

ECFG = dict(max_slots=4, max_input_length=128, max_output_length=32,
            prefill_buckets=(32, 64, 128), dtype="float32", page_size=16,
            steps_per_round=4, max_queue=32)

# Copy-heavy prompt: prompt-lookup drafting fires on the repeated
# n-grams, so the spec engines below really run verify rounds.
COPY_PROMPT = "the quick brown fox jumps. " * 4


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(9), dtype=jnp.float32)


def _mesh(tp):
    return make_mesh(MeshPlan(tp=tp), jax.devices()[:tp])


def _chat_run(engine, tok):
    """Greedy chat: a cold turn, a warm SAME-prefix turn (prefix-cache
    hit), and a concurrent open-loop-style mini-wave with varied
    lengths. Returns every stream's token ids, in a deterministic
    order."""
    sp = SamplingParams(max_tokens=12, top_k=1, ignore_eos=True)
    outs = []
    cold = engine.submit(tok.encode(COPY_PROMPT), sp)
    cold.text()
    outs.append(list(cold.token_ids))
    warm = engine.submit(tok.encode(COPY_PROMPT), sp)
    warm.text()
    outs.append(list(warm.token_ids))
    wave = [engine.submit(tok.encode(f"wave {i} " + COPY_PROMPT[:40]),
                          SamplingParams(max_tokens=4 + i, top_k=1,
                                         ignore_eos=True))
            for i in range(3)]
    for s in wave:
        s.text()
        outs.append(list(s.token_ids))
    return outs


def test_tp2_engine_token_identical_with_fused_sampler_and_spec(params):
    """THE acceptance criterion: a tp=2 engine with the sharded fused
    sampler AND speculative decoding active produces token-identical
    greedy output to the single-chip engine — cold turn, warm
    prefix-cache turn, and a concurrent mini-wave — while actually
    speculating (verify rounds ran) and without a single downgrade."""
    tok = ByteTokenizer()
    ecfg = EngineConfig(spec_decode=True, spec_max_draft_tokens=3,
                        **ECFG)

    with Engine(params, CFG, tok, ecfg) as single:
        ref = _chat_run(single, tok)
        ref_stats = single.stats

    with Engine(params, CFG, tok, ecfg, mesh=_mesh(2)) as sharded:
        assert sharded.programs.tail.kind == "sharded"
        assert sharded._spec is not None, "spec must arm under a mesh"
        got = _chat_run(sharded, tok)
        stats = sharded.stats

    assert got == ref
    # both engines really speculated (the copy-heavy prompt drafts) ...
    assert stats["spec_verify_rounds"] > 0
    assert ref_stats["spec_verify_rounds"] > 0
    # ... the warm turn really hit the prefix cache ...
    assert stats["prefix_cache_hit_tokens"] > 0
    # ... and nothing was downgraded to get there.
    assert stats["downgrades"] == 0


def test_tp2_sharded_fused_vs_materialized_tail_parity(params,
                                                       monkeypatch):
    """Engine-level greedy parity of the SHARDED fused tail against the
    materialized oracle tail on the same tp=2 mesh
    (ENGINE_FUSED_SAMPLER=0) — the PR-8 parity contract re-pinned where
    the tail is a shard_mapped stream."""
    tok = ByteTokenizer()
    sp = SamplingParams(max_tokens=10, top_k=1, ignore_eos=True)
    prompt = tok.encode("sharded tail parity probe " * 3)
    ecfg = EngineConfig(**ECFG)

    monkeypatch.setenv("ENGINE_FUSED_SAMPLER", "0")
    with Engine(params, CFG, tok, ecfg, mesh=_mesh(2)) as oracle:
        assert not oracle._fused_tail
        # the explicit env off-switch is an operator choice, NOT a
        # downgrade
        assert oracle.stats["downgrades"] == 0
        ref = oracle.submit(prompt, sp)
        ref.text()

    monkeypatch.delenv("ENGINE_FUSED_SAMPLER")
    with Engine(params, CFG, tok, ecfg, mesh=_mesh(2)) as fused:
        assert fused.programs.tail.kind == "sharded"
        got = fused.submit(prompt, sp)
        got.text()
    assert got.token_ids == ref.token_ids


# ------------------------------------------------- jaxpr memory proof


def _jaxprs_in(val):
    if isinstance(val, jax.extend.core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jax.extend.core.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _jaxprs_in(v)


def _walk_avals(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.extend(v.aval for v in eqn.outvars)
        for val in eqn.params.values():
            for sub in _jaxprs_in(val):
                _walk_avals(sub, out)


def _assert_no_vocab_wide(avals, vocab):
    offenders = [a for a in avals
                 if getattr(a, "ndim", 0) >= 2 and a.shape[-1] == vocab]
    assert not offenders, (
        f"sharded round materializes vocab-wide intermediates: "
        f"{[(a.shape, str(a.dtype)) for a in offenders]}")


def test_sharded_rounds_never_materialize_vocab(params):
    """The memory proof RE-PINNED WITH SHARDING (acceptance criterion):
    trace the tp=2 engine's actual fused decode round AND speculative
    verify round and walk every jaxpr — shard_map bodies included — for
    (rows, V) intermediates. Each shard streams (rows, V/tp)-at-most
    tiles; the cross-chip merge is (shards, rows, cand_k)-sized."""
    tok = ByteTokenizer()
    eng = Engine(params, CFG, tok,
                 EngineConfig(spec_decode=True, spec_max_draft_tokens=3,
                              **ECFG),
                 mesh=_mesh(2))
    try:
        assert eng.programs.tail.kind == "sharded"
        ba = 1
        fn = programs.make_round(eng.programs.spec, eng._windows[0], 2,
                                 False, ba)
        jaxpr = jax.make_jaxpr(fn)(
            eng.params, eng._state, jax.random.key(1),
            jnp.zeros((ba,), jnp.int32)).jaxpr
        avals = []
        _walk_avals(jaxpr, avals)
        _assert_no_vocab_wide(avals, CFG.vocab_size)
        # sanity: the trace really saw tiled vocab work (tile <= V/tp)
        assert any(getattr(a, "ndim", 0) >= 2
                   and 0 < a.shape[-1] <= CFG.vocab_size // 2
                   and a.shape[-1] % 32 == 0 for a in avals)

        S = eng._spec_S
        B = eng.cfg.max_slots
        vfn = programs.make_verify(eng.programs.spec, eng._windows[0],
                                   False, ba)
        vjaxpr = jax.make_jaxpr(vfn)(
            eng.params, eng._state, jax.random.key(2),
            jnp.zeros((ba,), jnp.int32),
            jnp.zeros((B, S - 1), jnp.int32),
            jnp.zeros((B,), jnp.int32)).jaxpr
        avals = []
        _walk_avals(vjaxpr, avals)
        _assert_no_vocab_wide(avals, CFG.vocab_size)
    finally:
        eng.stop()


# ------------------------------------------------ observable downgrade


def test_unshardable_vocab_downgrades_observably(params, caplog):
    """tp=4 splits vocab 320 into 80-token shards — not whole mask
    words — so the fused tail must downgrade to the materialized tail
    LOUDLY: one structured engine_feature_downgrade event, the
    engine_downgrades stat, and the reason retrievable from the engine;
    serving itself still works (and pp-incompatibility of the kernel is
    already covered by its own downgrade path)."""
    tok = ByteTokenizer()
    with caplog.at_level(logging.WARNING):
        eng = Engine(params, CFG, tok, EngineConfig(**ECFG),
                     mesh=_mesh(4))
    try:
        assert eng.programs.tail.kind == "materialised"
        assert eng.stats["downgrades"] >= 1
        feats = [d["feature"] for d in eng.downgrades]
        assert "fused_sampler" in feats
        down = next(d for d in eng.downgrades
                    if d["feature"] == "fused_sampler")
        assert down["fallback"] == "materialized_tail"
        assert "tp=4" in down["reason"]
        assert any("engine_feature_downgrade" in r.message
                   for r in caplog.records)
        with eng:
            s = eng.submit(tok.encode("degrade probe"),
                           SamplingParams(max_tokens=5, top_k=1,
                                          ignore_eos=True))
            s.text()
            assert len(s.token_ids) == 5
    finally:
        eng.stop()


def test_tp2_sampled_decode_serves_on_sharded_tail(params):
    """Temperature>0 on the tp=2 sharded tail: the Gumbel-max candidate
    carry merges across chips and serving completes with in-vocab
    tokens (distribution exactness is pinned at the op level in
    test_fused_sampler.py's sharded parity tests)."""
    tok = ByteTokenizer()
    with Engine(params, CFG, tok, EngineConfig(**ECFG),
                mesh=_mesh(2)) as eng:
        assert eng.programs.tail.kind == "sharded"
        s = eng.submit(tok.encode("sampled sharded tail"),
                       SamplingParams(max_tokens=8, temperature=0.9,
                                      top_k=12, top_p=0.9,
                                      ignore_eos=True))
        s.text()
        assert len(s.token_ids) == 8
        assert all(0 <= t < CFG.vocab_size for t in s.token_ids)
        assert np.asarray(s.token_ids).dtype.kind == "i"
