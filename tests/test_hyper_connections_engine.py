"""The engine end to end over a tiny latent expert model on hyper-connected
streams (``Engine.submit``, the scheduler, the latent pool, chunk
programs of one and of several prompts, decode rounds, the fused tail),
on the CPU: its greedy tokens are the plain forward's; the counter
``hc_row_defect`` reaches the round records and the stats; the pool's
reserve counts the streams; the published names load; and everything
that cannot take the streams yet refuses them BY NAME."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.engine import (Engine, EngineConfig,
                                                    SamplingParams)
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LLAMA_TINY
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.obs.rounds import RoundRecorder
from generativeaiexamples_tpu.utils.errors import ConfigError

from test_hyper_connections import CFG, PLAIN

ENGINE = dict(max_slots=4, max_input_length=512, max_output_length=32,
              prefill_buckets=(128,), max_prefill_bucket=128, page_size=128,
              steps_per_round=4, kv_pool_tokens=None, dtype="float32")
N_OUT = 8
DENSE = dataclasses.replace(LLAMA_TINY, hc_mult=4)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, 250, n)]


@functools.lru_cache(maxsize=4)
def _padded_forward(cfg, length):
    return jax.jit(lambda params, ids: llama.apply(
        params, cfg, ids, jnp.arange(length)[None])[0][0])


def plain_greedy(params, cfg, ids, n):
    """The plain forward's own greedy chain, no cache: one jitted program
    a padded length (causal: what follows a position does not move it)."""
    ids = list(ids)
    length = -(-(len(ids) + n) // 64) * 64
    forward = _padded_forward(cfg, length)
    for _ in range(n):
        logits = forward(params, jnp.asarray(
            ids + [0] * (length - len(ids)))[None])
        ids.append(int(jnp.argmax(logits[len(ids) - 1])))
    return ids[-n:]


def make_engine(params, cfg=CFG, mesh=None, **kw):
    eng = Engine(params, cfg, ByteTokenizer(),
                 EngineConfig(**{**ENGINE, **kw}), mesh=mesh)
    eng.rounds = RoundRecorder(cap=512)
    return eng


@pytest.fixture(scope="module")
def engine(params):
    eng = make_engine(params)
    eng.start()
    yield eng
    eng.stop()


def serve(engine, ids, n=N_OUT):
    s = engine.submit(ids, SamplingParams(max_tokens=n, temperature=0.0,
                                          ignore_eos=True))
    list(s)
    assert s.finish_reason == "length"
    return list(s.token_ids)


@pytest.mark.parametrize("n", [300, 50], ids=["three_chunks", "one_bucket"])
def test_engine_tokens_are_the_plain_forwards(engine, params, n):
    ids = prompt(n, n)
    assert serve(engine, ids) == plain_greedy(params, CFG, ids, N_OUT)


def test_a_burst_runs_the_chunk_program_of_several_prompts(params):
    """Four long prompts onto an idle engine under a budget that grants
    each a whole chunk a plan: their whole-bucket grants run as ONE
    program of four rows (4 streams a row), and each answer is still the
    plain forward's."""
    eng = make_engine(params, prefix_cache=False,
                      sched_round_budget_tokens=4 * 128 + 4 * 4)
    prompts = [prompt(290, 20 + i) for i in range(4)]
    try:
        streams = [eng.submit(ids, SamplingParams(
            max_tokens=4, temperature=0.0, ignore_eos=True))
            for ids in prompts]
        eng.start()
        for s in streams:
            list(s)
        assert ("extend_rows", 4) in eng._chunk_fns
    finally:
        eng.stop()
    for ids, s in zip(prompts, streams):
        assert list(s.token_ids) == plain_greedy(params, CFG, ids, 4)


def test_the_row_defect_reaches_the_round_records(engine):
    serve(engine, prompt(40, 7))
    st = engine.stats
    assert st["hc_row_defect_rounds"] > 0
    assert st["hc_row_defect_rounds"] == st["experts_touched_rounds"]
    mean = st["hc_row_defect_sum"] / st["hc_row_defect_rounds"]
    assert 0 < mean < 1e-5
    recs = [r for r in engine.rounds.records() if r.hc_row_defect > 0]
    assert recs and all(r.hc_row_defect < 1e-5 for r in recs)
    assert recs[0].to_dict()["outcome"]["hc_row_defect"] > 0


def test_a_program_that_loses_iterations_shows_in_every_round(params):
    eng = make_engine(params, dataclasses.replace(CFG, hc_sinkhorn_iters=1))
    eng.start()
    try:
        serve(eng, prompt(40, 7))
        # a round that emitted: one dispatched past the request's last
        # token has no live step and reports the mean of none, 0
        recs = [r for r in eng.rounds.records()
                if r.decode_slots > 0 and r.tokens_emitted > 0]
        assert recs and all(r.hc_row_defect > 0.01 for r in recs)
    finally:
        eng.stop()


def test_the_plain_path_reports_no_defect(params):
    plain = llama.init_params(PLAIN, jax.random.key(3), dtype=jnp.float32)
    eng = make_engine(plain, PLAIN)
    eng.start()
    try:
        serve(eng, prompt(40, 7))
        assert eng.stats["hc_row_defect_rounds"] == 0
        assert all(r.hc_row_defect == 0 for r in eng.rounds.records())
    finally:
        eng.stop()


def test_the_pools_reserve_counts_the_streams(params):
    """A 4-stream configuration reserves more than its 1-stream twin: the
    widest chunk program's rows x bucket x hc_mult x hidden_size, the
    stream read, the stream written and a float32 copy."""
    plain = llama.init_params(PLAIN, jax.random.key(3), dtype=jnp.float32)
    wide, narrow = make_engine(params), make_engine(plain, PLAIN)
    rows, S = max(wide._row_ladder), max(wide._buckets)
    assert (rows, S) == (4, 128)
    extra = wide._headroom_bytes() - narrow._headroom_bytes()
    assert extra == rows * S * 4 * CFG.hidden_size * (2 * 4 + 4)


# ------------------------------------------------------- refused by name


def test_an_sp_mesh_refuses_the_streams():
    from jax.sharding import Mesh
    p = llama.init_params(DENSE, jax.random.key(0), dtype=jnp.float32)
    devs = np.array(jax.devices()[:2])
    with pytest.raises(ConfigError, match="hyper-connection streams under "
                                          "an sp mesh"):
        make_engine(p, DENSE, mesh=Mesh(devs.reshape(1, 2), ("dp", "sp")))


def test_ring_attention_refuses_the_streams():
    with pytest.raises(NotImplementedError, match="hyper-connections"):
        llama._refuse_kinds(DENSE, "apply_sp")
    p = llama.init_params(DENSE, jax.random.key(0), dtype=jnp.float32)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "sp"))
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="hc_mult=4"):
        llama.apply_sp(p, DENSE, toks, jnp.arange(8)[None], mesh)


def test_a_pipeline_stage_refuses_the_streams():
    """What crosses a stage is hc_mult streams: a WHOLE stack is refused
    too (``run_layers`` neither widens nor sums)."""
    from jax.sharding import Mesh

    from generativeaiexamples_tpu.parallel.pipeline import pipeline_forward
    p = llama.init_params(DENSE, jax.random.key(0), dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="hyper-connections"):
        llama.run_layers(p["layers"], DENSE, jnp.zeros((1, 4, 128)),
                         jnp.arange(4)[None])
    mesh = Mesh(np.array(jax.devices()[:2]), ("pp",))
    with pytest.raises(NotImplementedError, match="pipeline stage"):
        pipeline_forward(mesh, p, DENSE, jnp.zeros((2, 8), jnp.int32),
                         jnp.broadcast_to(jnp.arange(8), (2, 8)))


def test_lora_refuses_the_streams():
    from generativeaiexamples_tpu.lora import init_lora
    p = llama.init_params(DENSE, jax.random.key(0), dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="hyper-connection"):
        init_lora(DENSE, p, jax.random.key(0))


# ---------------------------------------------------------------- import


def test_mapping_weights_round_trip_through_import_hf():
    """A seeded tree saved under the names the importer reads (the block's
    are the dense llama names here; ``attn_hc.*`` / ``mlp_hc.*`` are
    ASSUMED, models/import_hf.py) loads back leaf for leaf, ``phi``
    stored (out, in) like every projection, ``alpha`` and ``bias`` in
    float32."""
    from generativeaiexamples_tpu.models.import_hf import (
        params_from_named_tensors)
    p = llama.init_params(DENSE, jax.random.key(5), dtype=jnp.float32)
    named = {"model.embed_tokens.weight": p["embed"],
             "model.norm.weight": p["final_norm"],
             "lm_head.weight": p["lm_head"].T}
    plain = {"attn_norm": "input_layernorm.weight",
             "mlp_norm": "post_attention_layernorm.weight",
             "hc_attn_alpha": "attn_hc.alpha", "hc_attn_b": "attn_hc.bias",
             "hc_mlp_alpha": "mlp_hc.alpha", "hc_mlp_b": "mlp_hc.bias"}
    turned = {"wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
              "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
              "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
              "w_down": "mlp.down_proj.weight",
              "hc_attn_phi": "attn_hc.phi.weight",
              "hc_mlp_phi": "mlp_hc.phi.weight"}
    tree = p["layers"]
    assert set(tree) == set(plain) | set(turned)
    for i in range(DENSE.num_layers):
        pre = f"model.layers.{i}."
        for name, hf in plain.items():
            named[pre + hf] = tree[name][i]
        for name, hf in turned.items():
            named[pre + hf] = tree[name][i].T
    named = {k: np.asarray(v) for k, v in named.items()}
    assert named["model.layers.0.attn_hc.phi.weight"].shape == (24, 4 * 128)
    back = params_from_named_tensors(iter(named.items()), DENSE, jnp.bfloat16)
    assert jax.tree.structure(back) == jax.tree.structure(p)
    for name, leaf in back["layers"].items():
        f32 = name.endswith(("_alpha", "_b"))
        assert leaf.dtype == (jnp.float32 if f32 else jnp.bfloat16), name
        assert np.array_equal(leaf, tree[name].astype(leaf.dtype)), name
