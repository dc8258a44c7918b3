"""``references/xing4_0.py`` against the package, at toy sizes on the CPU,
as ``test_perfbench_references.py`` holds the older references: its plain
forward AND its paged path (prefill into the latent pool, then
teacher-forced decode steps through the cache), over seeded weights as
they are stored, raw and int8; and with a fault put into it — ``hc_eps``
a tenth, no factor 2 in ``H_post``, ``H_res`` the identity, a stream
that starts as ``[h, 0, 0, 0]``, the routed weights un-scaled — it fails
the logits check at the rehearsal configuration's tolerances."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import system
from benchmarks.references import xing4_0

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = {
    "vocab_size": 320, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_layers": 3, "num_dense_layers": 1,
    "num_heads": 4, "num_kv_heads": 1, "head_dim": 32,
    "max_position_embeddings": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "moe_impl": "dropless",
    "router_score_func": "sigmoid", "router_norm_topk": True,
    "router_scale": 2.0, "router_bias": "selection", "kv_lora_rank": 32,
    "q_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_interleave": True, "rope_scaling_type": "yarn",
    "rope_scaling_factor": 64.0, "rope_original_max": 64,
    "rope_beta_fast": 32.0, "rope_beta_slow": 1.0,
    "rope_mscale_all_dim": 1.0, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "hc_res_clamp": 30.0,
    "weight_init": "unit_stream_thin_experts"}
PAGE, T, N_DEC = 16, 48, 3

with open(os.path.join(HERE, "rehearsal", "configs", "tiny-dense.json")) as f:
    TOY = json.load(f)


def config_of(quant):
    return dict(TOY, model=MODEL, reference="xing4_0", weight_quant=quant,
                engine=dict(TOY["engine"], page_size=PAGE),
                logits_check=dict(TOY["logits_check"], prompt_pages=3,
                                  max_share_over=0.1))


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
    of the logits' scale, at most a third over (a router's flipped
    near-tie moves one position); float32 through and through: 1e-4."""
    e = np.asarray(jnp.max(jnp.abs(got - want), axis=-1)
                   / jnp.max(jnp.abs(want), axis=-1))
    if exact:
        return bool(e.max() < 1e-4)
    return bool(np.median(e) < 0.04 and (e > 0.04).sum() <= len(e) // 3)


def test_reference_is_independent_and_says_what_it_assumes():
    with open(xing4_0.__file__) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(generativeaiexamples_tpu|"
                         r"benchmarks)", text, re.M)
    doc = xing4_0.__doc__
    for said in ("ASSUMED", "hc_eps", "no gain", "before columns", "SUM",
                 "factor 2", "row-major", "pre-norm", "2512.24880",
                 "2409.19606"):
        assert said in doc, said


@pytest.mark.parametrize("quant", ["", "int8"])
def test_reference_agrees_with_the_packages_plain_forward(built, quant):
    from generativeaiexamples_tpu.models import llama
    config, cfg, params = built(quant)
    ids = ids_of(1)
    want = xing4_0.forward(params, config["model"], ids[None], np.arange(T))
    assert want.shape == (T, 320) and want.dtype == jnp.float32
    got, _ = llama.apply(params, cfg, jnp.asarray(ids, jnp.int32)[None],
                         jnp.arange(T, dtype=jnp.int32)[None])
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
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    hidden, pool = llama.apply_prefill_paged(
        params, cfg, jnp.asarray(ids[:T], jnp.int32)[None],
        jnp.arange(T, dtype=jnp.int32)[None], pool, table, i32(T),
        jnp.int32(0))
    assert hidden.shape == (1, T, 64)            # the streams collapsed
    out = [llama.unembed(params, cfg, hidden[:, T - 1:T])[0, 0]]
    decode = jax.jit(lambda pool, tok, at: llama.apply_decode_paged(
        params, cfg, tok[None], at[None], pool, table, at + 1,
        1 + at // PAGE, at % PAGE))
    for at in range(T, len(ids)):
        step, pool = decode(pool, i32(ids[at]), i32(at))
        out.append(step[0, 0])
    got = jnp.stack(out).astype(jnp.float32)
    want = xing4_0.forward(params, config["model"], ids[None],
                           np.arange(T - 1, T + N_DEC))
    assert agree(got, want, exact=not quant)


def test_what_follows_a_position_does_not_move_its_logits(built):
    config, _, params = built("int8")
    ids = ids_of(3)
    short = xing4_0.forward(params, config["model"], ids[None, :30],
                            np.arange(20, 30))
    padded = xing4_0.forward(params, config["model"], ids[None],
                             np.arange(20, 30))
    assert float(jnp.max(jnp.abs(padded - short))
                 / jnp.max(jnp.abs(short))) < 1e-5


def _patch_read(monkeypatch, change):
    real = xing4_0._hc_read
    monkeypatch.setattr(xing4_0, "_hc_read",
                        lambda X, *w, **kw: change(*real(X, *w, **kw)))


def no_factor_2(monkeypatch):
    _patch_read(monkeypatch, lambda u, post, res: (u, 0.5 * post, res))


def h_res_identity(monkeypatch):
    _patch_read(monkeypatch, lambda u, post, res: (
        u, post, jnp.broadcast_to(jnp.eye(4), res.shape)))


def first_stream_only(monkeypatch):
    real = jnp.repeat
    monkeypatch.setattr(
        xing4_0.jnp, "repeat", lambda x, n, axis=None:
        real(x, n, axis=axis) * (jnp.arange(4) == 0)[None, :, None])


FAULTS = {
    "hc_eps_1e-1": dict(hc_eps=0.1),
    "no_shared_expert_scale": dict(router_scale=1.0),
    "no_factor_2_in_h_post": no_factor_2,
    "h_res_identity": h_res_identity,
    "streams_start_as_h_0_0_0": first_stream_only,
}


@pytest.fixture(scope="module")
def sound(built):
    config, cfg, params = built("int8")
    return system.logits_check(params, cfg, config, 4)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_reference_with_a_fault_fails_the_logits_check(built, sound, fault,
                                                         monkeypatch):
    """Held to the sound reference the paged path passes (0.0075 at the
    median position, no position over 0.05); held to a reference with
    the fault it does not. Three layers deep in bf16 the two weakest
    faults of the chip's list read too near it to be held here — one
    iteration for twenty 0.0157, the clamp at 1 0.0107 — and are held in
    float32, where the sound program reads 5e-5
    (tests/test_hyper_connections.py). A fault in the model group is
    another set of static arguments; the patched ones wrap what the
    reference's un-jitted ``forward`` looks up at every call."""
    assert sound["share_over_tolerance"] <= 0.1
    config, cfg, params = built("int8")
    model = dict(config["model"])
    if callable(FAULTS[fault]):
        FAULTS[fault](monkeypatch)
    else:
        model.update(FAULTS[fault])
    with pytest.raises(system.CheckFailed):     # by the median or the share
        system.logits_check(
            params, cfg, config, 4, forward=lambda p, m, ids, pos:
            xing4_0.forward(p, model, ids, pos))


def test_a_model_group_it_does_not_describe_is_refused(built):
    config, _, params = built("")
    ids = ids_of(1)
    for change, match in [(dict(hc_mult=0), "no hc_mult"),
                          (dict(router_score_func="softmax"),
                           "DeepseekV3 block"),
                          (dict(kv_lora_rank=0), "no kv_lora_rank")]:
        with pytest.raises(ValueError, match=match):
            xing4_0.forward(params, dict(config["model"], **change),
                            ids[None], [0])
