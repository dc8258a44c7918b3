"""``references/glm_dsa.py`` against the package, at toy sizes on the
CPU, as ``test_perfbench_references.py`` holds the older references: its
plain forward AND its paged path (prefill into the pool with its index
leaf, then teacher-forced decode steps that select through the cache), at
contexts above the toy's ``index_topk``, over seeded weights as they are
stored, raw and int8; and with a fault put into it — the selection off,
another layer's set, no rotation in the indexer — it fails the logits
check at the rehearsal configuration's tolerances."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import system
from benchmarks.references import glm_dsa

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = {
    "vocab_size": 320, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_layers": 4, "num_dense_layers": 1,
    "num_heads": 4, "num_kv_heads": 1, "head_dim": 32,
    "max_position_embeddings": 512, "rope_theta": 8000000.0,
    "rms_norm_eps": 1e-5, "num_experts": 8, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "experts_held": 4, "experts_first": 2,
    "moe_impl": "dropless", "router_score_func": "sigmoid",
    "router_norm_topk": True, "router_scale": 2.5,
    "router_bias": "selection", "kv_lora_rank": 32, "q_lora_rank": 32,
    "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_interleave": True, "index_topk": 24, "index_n_heads": 2,
    "index_head_dim": 16, "index_layers": [1, 0, 1, 0],
    "index_rope_interleave": True, "weight_init": "unit_stream"}
PAGE, T, N_DEC = 16, 48, 11

with open(os.path.join(HERE, "rehearsal", "configs", "tiny-dense.json")) as f:
    TOY = json.load(f)


def config_of(quant):
    return dict(TOY, model=MODEL, reference="glm_dsa", weight_quant=quant,
                engine=dict(TOY["engine"], page_size=PAGE),
                logits_check=dict(TOY["logits_check"], prompt_pages=3,
                                  max_share_over=0.3))


@pytest.fixture(scope="module")
def built():
    out = {}

    def get(quant):
        if quant not in out:
            config = config_of(quant)
            cfg = system.model_config(config)
            params = system.make_params(cfg, quant, 11)
            if not quant:       # raw: float32 through and through
                params = jax.tree.map(
                    lambda a: a.astype(jnp.float32)
                    if a.dtype == jnp.bfloat16 else a, params)
            out[quant] = (config, cfg, params)
        return out[quant]
    return get


def ids_of(seed, n=T):
    return np.random.default_rng(seed).integers(3, MODEL["vocab_size"], n)


def agree(got, want, exact=False) -> bool:
    """bf16 activations against float32: the median position within 0.04
    of the logits' scale, at most half over. At toy size 48 tokens
    compete for 24 places under 2 index heads, and a near-tie at the 24th
    place that bf16 flips swaps one key of 24 (a whole share of a head's
    softmax): it moves THAT position, as a router's flipped near-tie
    does. A fault moves every position by a fifth or more (below); the
    float32 tests hold the sets exactly (tests/
    test_sparse_latent_attention.py)."""
    e = np.asarray(jnp.max(jnp.abs(got - want), axis=-1)
                   / jnp.max(jnp.abs(want), axis=-1))
    if exact:       # float32 through and through: the same sets
        return bool(e.max() < 1e-4)
    return bool(np.median(e) < 0.04 and (e > 0.04).sum() <= len(e) // 2)


@pytest.mark.parametrize("quant", ["", "int8"])
def test_reference_agrees_with_the_packages_plain_forward(built, quant):
    from generativeaiexamples_tpu.models import llama
    config, cfg, params = built(quant)
    ids = ids_of(1)
    want = glm_dsa.forward(params, config["model"], ids[None], np.arange(T))
    assert want.shape == (T, 320) and want.dtype == jnp.float32
    dtype = jnp.bfloat16 if quant else jnp.float32
    cache = llama.init_kv_cache(cfg, 1, T, dtype)
    got, _ = llama.apply(params, cfg, jnp.asarray(ids, jnp.int32)[None],
                         jnp.arange(T, dtype=jnp.int32)[None], cache,
                         kv_valid_len=jnp.asarray([T], jnp.int32))
    assert agree(got[0].astype(jnp.float32), want, exact=not quant)


@pytest.mark.parametrize("quant", ["", "int8"])
def test_reference_agrees_with_prefill_then_decode_through_the_pool(built,
                                                                    quant):
    from generativeaiexamples_tpu.models import llama
    config, cfg, params = built(quant)
    ids = ids_of(2, T + N_DEC)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    nb = -(-len(ids) // PAGE)
    pool = llama.init_paged_kv_cache(
        cfg, nb + 1, PAGE, jnp.bfloat16 if quant else jnp.float32)
    assert set(pool) == {"c", "r", "i"}
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    hidden, pool = llama.apply_prefill_paged(
        params, cfg, jnp.asarray(ids[:T], jnp.int32)[None],
        jnp.arange(T, dtype=jnp.int32)[None], pool, table, i32(T),
        jnp.int32(0))
    out = [llama.unembed(params, cfg, hidden[:, T - 1:T])[0, 0]]
    for at in range(T, len(ids)):
        step, pool = llama.apply_decode_paged(
            params, cfg, i32(ids[at])[None], i32(at)[None], pool, table,
            i32(at + 1), i32(1 + at // PAGE), i32(at % PAGE))
        out.append(step[0, 0])
    got = jnp.stack(out).astype(jnp.float32)
    want = glm_dsa.forward(params, config["model"], ids[None],
                           np.arange(T - 1, T + N_DEC))
    assert agree(got, want, exact=not quant)


def test_what_follows_a_position_does_not_move_its_logits(built):
    config, _, params = built("int8")
    ids = ids_of(3)
    short = glm_dsa.forward(params, config["model"], ids[None, :30],
                            np.arange(30))
    padded = glm_dsa.forward(params, config["model"], ids[None],
                             np.arange(30))
    assert float(jnp.max(jnp.abs(padded - short))
                 / jnp.max(jnp.abs(short))) < 1e-5


def no_index_rotation(monkeypatch):
    real = glm_dsa._rope_pairs
    monkeypatch.setattr(
        glm_dsa, "_rope_pairs", lambda x, inv, interleave=True:
        real(x, inv * 0 if x.shape[-1] == 8 and x.shape[1] in (1, 2)
             else inv, interleave))


FAULTS = {
    "selection_off": dict(index_topk=4096),
    "half_the_selection": dict(index_topk=12),
    "first_layers_set_everywhere": dict(index_layers=[1, 0, 0, 0]),
    "pairs_as_halves_in_the_indexer": dict(index_rope_interleave=False),
    "no_shared_expert_share": dict(experts_first=0),
    "no_rotation_in_the_indexer": no_index_rotation,
}


@pytest.fixture(scope="module")
def sound(built):
    """Held to the sound reference the paged path passes the check's
    tolerances (once: the faults below are held to the same paged
    side)."""
    config, cfg, params = built("int8")
    return system.logits_check(params, cfg, config, 4)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_reference_with_a_fault_fails_the_logits_check(built, sound, fault,
                                                         monkeypatch):
    """... and held to a reference with the fault it does not. A fault
    in the model group is another set of static arguments; one patched
    into a jitted function needs the caches cleared around it."""
    assert sound["share_over_tolerance"] <= 0.3
    config, cfg, params = built("int8")
    model = dict(config["model"])
    patched = callable(FAULTS[fault])
    if patched:
        jax.clear_caches()
        FAULTS[fault](monkeypatch)
    else:
        model.update(FAULTS[fault])
    with pytest.raises(system.CheckFailed, match="differ from the reference"):
        system.logits_check(
            params, cfg, config, 4, forward=lambda p, m, ids, pos:
            glm_dsa.forward(p, model, ids, pos))
    if patched:
        jax.clear_caches()


def test_a_model_group_it_does_not_describe_is_refused(built):
    config, _, params = built("")
    ids = ids_of(1)
    for change, match in [(dict(index_topk=0), "no index_topk"),
                          (dict(router_score_func="softmax"),
                           "GLM-5.2's block"),
                          (dict(index_layers=[0, 1]), "layer 0 is shared")]:
        with pytest.raises(ValueError, match=match):
            glm_dsa.forward(params, dict(config["model"], **change),
                            ids[None], [0])
