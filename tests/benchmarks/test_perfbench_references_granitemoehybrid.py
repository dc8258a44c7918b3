"""``references/granitemoehybrid.py`` against the package, at toy sizes
on the CPU, as its siblings hold the older references: its plain forward
AND its paged path (prefill into the pool and the state, then
teacher-forced decode steps through both), over seeded weights as they
are stored, raw (float32 through and through) and int8, over a TIED
head; and with a fault put into the reference, each fault of the
benchmark's faults file put into the program, or the weights one
precision step down, it fails the logits check at the rehearsal
configuration's tolerances."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import system
from benchmarks.references import granitemoehybrid

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = {
    "vocab_size": 320, "hidden_size": 64, "intermediate_size": 128,
    "num_layers": 8, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "rope_layers": [0], "embed_scale": 12.0,
    "residual_multiplier": 0.22, "attention_multiplier": 0.03125,
    "logits_divisor": 8.0, "full_attention_interval": 4,
    "full_attention_place": 1, "linear_num_key_heads": 1,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "linear_decay": "ssd", "weight_init": "unit_stream"}
PAGE, T, N_DEC = 16, 48, 3

with open(os.path.join(HERE, "rehearsal", "configs", "tiny-dense.json")) as f:
    TOY = json.load(f)


def config_of(quant):
    return dict(TOY, model=MODEL, reference="granitemoehybrid",
                weight_quant=quant,
                engine=dict(TOY["engine"], page_size=PAGE),
                logits_check=dict(TOY["logits_check"], prompts=2,
                                  prompt_pages=3))


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
    with open(granitemoehybrid.__file__) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(generativeaiexamples_tpu|"
                         r"benchmarks)", text, re.M)
    doc = granitemoehybrid.__doc__
    for said in ("ASSUMED", "TOKEN BY TOKEN", "the gate BEFORE the norm",
                 "NO rotary", "attention_multiplier", "logits_divisor",
                 "TIED", "no clamp", "WITH bias", "WHERE THE LEAVES LIE",
                 "taken apart"):
        assert said in doc, said
    assert "lax.scan(\n        token" in text   # token by token, no chunks
    assert 'default_matmul_precision("highest")' in text


@pytest.mark.parametrize("quant", ["", "int8"], ids=["raw", "int8"])
def test_reference_agrees_with_the_packages_plain_forward(built, quant):
    from generativeaiexamples_tpu.models import llama
    config, cfg, params = built(quant)
    assert "lm_head" not in params          # the tied head
    ids = ids_of(1)
    want = granitemoehybrid.forward(params, config["model"], ids[None],
                                    np.arange(T))
    assert want.shape == (T, 320) and want.dtype == jnp.float32
    got, _ = llama.apply(params, cfg, jnp.asarray(ids, jnp.int32)[None],
                         jnp.arange(T, dtype=jnp.int32)[None])
    assert agree(got[0].astype(jnp.float32), want, exact=not quant)


@pytest.mark.parametrize("quant", ["", "int8"], ids=["raw", "int8"])
def test_reference_agrees_with_prefill_then_decode_through_the_state(built,
                                                                     quant):
    from generativeaiexamples_tpu.models import llama
    config, cfg, params = built(quant)
    ids = ids_of(2, T + N_DEC)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    nb = -(-len(ids) // PAGE)
    pool = llama.init_paged_kv_cache(
        cfg, nb + 1, PAGE, jnp.bfloat16 if quant else jnp.float32)
    # a head's state (P, N): N on the lanes; pages for 2 attention layers
    assert pool["s"].dtype == jnp.float32
    assert pool["s"].shape == (6, 1, 4, 8, 16) and pool["k"].shape[0] == 2
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
    want = granitemoehybrid.forward(params, config["model"], ids[None],
                                    np.arange(T - 1, T + N_DEC))
    assert agree(got, want, exact=not quant)


@pytest.fixture(scope="module")
def sound(built):
    from benchmarks.check_sensitivity import recorded
    config, cfg, params = built("int8")
    record, replay = recorded(granitemoehybrid.forward)
    return system.logits_check(params, cfg, config, 4, forward=record), replay


@pytest.mark.parametrize("fault", [
    "no_conv_bias", "no_skip", "head_dim_score_scale"])
def test_a_reference_with_a_fault_fails_the_logits_check(built, sound,
                                                         monkeypatch, fault):
    """The REFERENCE one mechanism short, the program as it is."""
    assert sound[0]["share_over_tolerance"] == 0.0
    config, cfg, params = built("int8")
    model = dict(config["model"])
    if fault == "head_dim_score_scale":
        model["attention_multiplier"] = 0.0
    else:
        inner = granitemoehybrid._mamba2      # jitted: the leaves go in

        def broken(x, w, **kw):
            leaf = "ssd_conv_b" if fault == "no_conv_bias" else "ssd_D"
            return inner(x, {**w, leaf: jnp.zeros_like(w[leaf])}, **kw)
        monkeypatch.setattr(granitemoehybrid, "_mamba2", broken)
    with pytest.raises(system.CheckFailed):
        system.logits_check(
            params, cfg, dict(config, model=model), 4,
            forward=granitemoehybrid.forward)


with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmarks",
                       "faults", "granite-4.0-h-micro.json")) as f:
    PROGRAM_FAULTS = json.load(f)


def test_the_faults_file_plants_what_the_model_adds():
    assert set(PROGRAM_FAULTS) >= {
        "residual_multiplier_1", "score_scale_head_dim", "logits_divisor_1",
        "rotary_on", "attention_at_period_end", "embed_scale_1"}


@pytest.mark.parametrize("name", sorted(PROGRAM_FAULTS))
def test_a_program_with_a_fault_fails_the_logits_check(built, sound, name):
    """The benchmark's faults file, on the CPU at the toy size: the
    PROGRAM one mechanism short, the reference as it is (its logits
    replayed)."""
    config, cfg, params = built("int8")
    broken = dataclasses.replace(cfg, **PROGRAM_FAULTS[name])
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
    for change, match in [(dict(linear_decay="head"), "granitemoehybrid"),
                          (dict(num_experts=8), "granitemoehybrid"),
                          (dict(rope_layers=[1]), "rotates nothing")]:
        with pytest.raises(ValueError, match=match):
            granitemoehybrid.forward(params, dict(config["model"], **change),
                                     ids[None], [0])
