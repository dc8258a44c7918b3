"""``references/bailing_hybrid.py`` against the package, at toy sizes on
the CPU, as its siblings hold the older references: its plain forward
AND its paged path (prefill into the latent pool and the state, then
teacher-forced decode steps through both), over seeded weights as they
are stored, raw and int8, under an expert share and a router limited to
groups; and with a fault put into the program — the benchmark's faults
file — or the weights one precision step down it fails the logits check
at the rehearsal configuration's tolerances (what has no key is planted
in float32: tests/test_kda_layers.py)."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import system
from benchmarks.references import bailing_hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = {
    "vocab_size": 320, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_layers": 8, "num_dense_layers": 2,
    "num_heads": 4, "num_kv_heads": 1, "head_dim": 24,
    "max_position_embeddings": 512, "rope_theta": 6000000.0,
    "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "experts_held": 8, "experts_first": 4,
    "moe_impl": "dropless", "router_score_func": "sigmoid",
    "router_norm_topk": True, "router_scale": 2.5,
    "router_bias": "selection", "n_group": 4, "topk_group": 2,
    "kv_lora_rank": 32, "q_lora_rank": 0, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_interleave": True,
    "attn_gate": "head", "full_attention_interval": 3,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_decay": "channel",
    "linear_decay_floor": -5.0, "weight_init": "unit_stream"}
PAGE, T, N_DEC = 16, 48, 3

with open(os.path.join(HERE, "rehearsal", "configs", "tiny-dense.json")) as f:
    TOY = json.load(f)


def config_of(quant):
    return dict(TOY, model=MODEL, reference="bailing_hybrid",
                weight_quant=quant,
                engine=dict(TOY["engine"], page_size=PAGE),
                logits_check=dict(TOY["logits_check"], prompts=2,
                                  prompt_pages=3, max_share_over=0.1))


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
    e = np.asarray(jnp.max(jnp.abs(got - want), axis=-1)
                   / jnp.max(jnp.abs(want), axis=-1))
    if exact:
        return bool(e.max() < 1e-4)
    return bool(np.median(e) < 0.04 and (e > 0.04).sum() <= len(e) // 3)


def test_reference_is_independent_and_says_what_it_assumes():
    with open(bailing_hybrid.__file__) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(generativeaiexamples_tpu|"
                         r"benchmarks)", text, re.M)
    doc = bailing_hybrid.__doc__
    for said in ("ASSUMED", "TOKEN BY TOKEN", "A CHANNEL", "a gate a HEAD",
                 "ONE matrix", "two largest", "WHERE THE LEAVES LIE",
                 "multi-token-prediction", "activation limits",
                 "PUBLISHED index"):
        assert said in doc, said
    assert "lax.scan(token" in text         # the recurrence, not a chunked form
    assert 'default_matmul_precision("highest")' in text


@pytest.mark.parametrize("quant", ["int8"])    # raw float32, to 2e-5:
# tests/test_kda_layers.py
def test_reference_agrees_with_the_packages_plain_forward(built, quant):
    from generativeaiexamples_tpu.models import llama
    config, cfg, params = built(quant)
    ids = ids_of(1)
    want = bailing_hybrid.forward(params, config["model"], ids[None],
                                  np.arange(T))
    assert want.shape == (T, 320) and want.dtype == jnp.float32
    got, _ = llama.apply(params, cfg, jnp.asarray(ids, jnp.int32)[None],
                         jnp.arange(T, dtype=jnp.int32)[None])
    assert agree(got[0].astype(jnp.float32), want, exact=not quant)


@pytest.mark.parametrize("quant", ["int8"])
def test_reference_agrees_with_prefill_then_decode_through_the_state(built,
                                                                     quant):
    from generativeaiexamples_tpu.models import llama
    config, cfg, params = built(quant)
    ids = ids_of(2, T + N_DEC)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    nb = -(-len(ids) // PAGE)
    pool = llama.init_paged_kv_cache(
        cfg, nb + 1, PAGE, jnp.bfloat16 if quant else jnp.float32)
    assert pool["s"].dtype == jnp.float32 and pool["s"].shape[:2] == (6, 1)
    assert pool["c"].shape[0] == 2
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    hidden, pool = llama.apply_prefill_paged(
        params, cfg, jnp.asarray(ids[:T], jnp.int32)[None],
        jnp.arange(T, dtype=jnp.int32)[None], pool, table, i32(T),
        jnp.int32(0))
    out = [llama.unembed(params, cfg, hidden[:, T - 1:T])[0, 0]]
    decode = jax.jit(lambda pool, tok, at: llama.apply_decode_paged(
        params, cfg, tok[None], at[None], pool, table, at + 1,
        1 + at // PAGE, at % PAGE))
    for at in range(T, len(ids)):
        step, pool = decode(pool, i32(ids[at]), i32(at))
        out.append(step[0, 0])
    got = jnp.stack(out).astype(jnp.float32)
    want = bailing_hybrid.forward(params, config["model"], ids[None],
                                  np.arange(T - 1, T + N_DEC))
    assert agree(got, want, exact=not quant)


@pytest.fixture(scope="module")
def sound(built):
    from benchmarks.check_sensitivity import recorded
    config, cfg, params = built("int8")
    record, replay = recorded(bailing_hybrid.forward)
    return system.logits_check(params, cfg, config, 4, forward=record), replay


with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmarks",
                       "faults", "ling-3.0-flash.json")) as f:
    PROGRAM_FAULTS = json.load(f)


@pytest.mark.parametrize("name", sorted(PROGRAM_FAULTS))
def test_a_program_with_a_fault_fails_the_logits_check(built, sound, name):
    """The benchmark's faults file, on the CPU at the toy size: the
    PROGRAM one mechanism short, the reference as it is (its logits
    replayed)."""
    assert sound[0]["share_over_tolerance"] <= 0.1
    config, cfg, params = built("int8")
    fields = dict(PROGRAM_FAULTS[name])
    if "experts_first" in fields:
        fields["experts_first"] = 8         # the toy's next group pair
    if "topk_group" in fields:
        fields["topk_group"] = 4            # the toy's every group
    broken = dataclasses.replace(cfg, **fields)
    with pytest.raises(system.CheckFailed):
        system.logits_check(params, broken, config, 4, forward=sound[1])


def test_weights_one_precision_step_down_fail_the_logits_check(built, sound):
    config, cfg, _ = built("int8")
    lower = system.make_params(cfg, "int4", 11)
    with pytest.raises(system.CheckFailed, match="median position"):
        system.logits_check(lower, cfg, config, 4, forward=sound[1])


def test_a_model_group_it_does_not_describe_is_refused(built):
    config, _, params = built("int8")
    ids = ids_of(1)
    for change, match in [(dict(linear_decay="head"), "bailing_hybrid block"),
                          (dict(router_score_func="softmax"),
                           "bailing_hybrid block"),
                          (dict(q_lora_rank=16), "ONE\\s+query matrix")]:
        with pytest.raises(ValueError, match=match):
            bailing_hybrid.forward(params, dict(config["model"], **change),
                                   ids[None], [0])
