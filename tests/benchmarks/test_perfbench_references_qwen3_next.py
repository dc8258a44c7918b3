"""``references/qwen3_next.py`` against the package, at toy sizes on the
CPU, as its siblings hold the older references: its plain forward AND
its paged path (prefill into pool and state, then teacher-forced decode
steps through both), over seeded weights as they are stored, raw and
int8, under an expert share; and with a fault put into it — no decay,
beta 1, no l2 norm, the whole head rotated, a gate left out, the share
shifted, in the reference or in the program — it fails the logits check
at the rehearsal configuration's tolerances (what 48 tokens and four
layers in bf16 cannot show is held in float32:
tests/test_recurrent_layers.py)."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import system
from benchmarks.references import qwen3_next

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = {
    "vocab_size": 320, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_layers": 4, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 32, "max_position_embeddings": 512,
    "rope_theta": 10000000.0, "rms_norm_eps": 1e-6, "num_experts": 16,
    "num_experts_per_tok": 4, "num_shared_experts": 1,
    "shared_expert_gate": True, "experts_held": 8, "experts_first": 4,
    "moe_impl": "dropless", "qk_norm": True, "attn_gate": True,
    "partial_rotary_factor": 0.25, "full_attention_interval": 2,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "weight_init": "unit_stream"}
PAGE, T, N_DEC = 16, 48, 3

with open(os.path.join(HERE, "rehearsal", "configs", "tiny-dense.json")) as f:
    TOY = json.load(f)


def config_of(quant):
    return dict(TOY, model=MODEL, reference="qwen3_next", weight_quant=quant,
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
    """bf16 activations against float32: the median position within 0.04
    of the logits' scale, at most a third over (a router's flipped
    near-tie moves one position); float32 through and through: 1e-4."""
    e = np.asarray(jnp.max(jnp.abs(got - want), axis=-1)
                   / jnp.max(jnp.abs(want), axis=-1))
    if exact:
        return bool(e.max() < 1e-4)
    return bool(np.median(e) < 0.04 and (e > 0.04).sum() <= len(e) // 3)


def test_reference_is_independent_and_says_what_it_assumes():
    with open(qwen3_next.__file__) as f:
        text = f.read()
    assert not re.search(r"^\s*(from|import)\s+(generativeaiexamples_tpu|"
                         r"benchmarks)", text, re.M)
    doc = qwen3_next.__doc__
    for said in ("ASSUMED", "TOKEN BY TOKEN", "zero-centred", "1 + w",
                 "grouped by key head", "WHERE THE LEAVES LIE",
                 "i // interval", "i - i //", "multi-token-prediction",
                 "AFTER\nthe convolution"):
        assert said in doc, said
    assert "lax.scan(token" in text         # the recurrence, not a chunked form


@pytest.mark.parametrize("quant", ["", "int8"])
def test_reference_agrees_with_the_packages_plain_forward(built, quant):
    from generativeaiexamples_tpu.models import llama
    config, cfg, params = built(quant)
    ids = ids_of(1)
    want = qwen3_next.forward(params, config["model"], ids[None],
                              np.arange(T))
    assert want.shape == (T, 320) and want.dtype == jnp.float32
    got, _ = llama.apply(params, cfg, jnp.asarray(ids, jnp.int32)[None],
                         jnp.arange(T, dtype=jnp.int32)[None])
    assert agree(got[0].astype(jnp.float32), want, exact=not quant)


@pytest.mark.parametrize("quant", ["", "int8"])
def test_reference_agrees_with_prefill_then_decode_through_the_state(built,
                                                                     quant):
    from generativeaiexamples_tpu.models import llama
    config, cfg, params = built(quant)
    ids = ids_of(2, T + N_DEC)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    nb = -(-len(ids) // PAGE)
    pool = llama.init_paged_kv_cache(
        cfg, nb + 1, PAGE, jnp.bfloat16 if quant else jnp.float32)
    assert pool["s"].dtype == jnp.float32 and pool["s"].shape[:2] == (2, 1)
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
    want = qwen3_next.forward(params, config["model"], ids[None],
                              np.arange(T - 1, T + N_DEC))
    assert agree(got, want, exact=not quant)


def test_what_follows_a_position_does_not_move_its_logits(built):
    config, _, params = built("int8")
    ids = ids_of(3)
    short = qwen3_next.forward(params, config["model"], ids[None, :30],
                               np.arange(20, 30))
    padded = qwen3_next.forward(params, config["model"], ids[None],
                                np.arange(20, 30))
    assert float(jnp.max(jnp.abs(padded - short))
                 / jnp.max(jnp.abs(short))) < 1e-5


def whole_head_rotated(monkeypatch):
    real = qwen3_next._rope_first
    monkeypatch.setattr(qwen3_next, "_rope_first",
                        lambda x, theta, part: real(x, theta, x.shape[-1]))


def no_l2_norm(monkeypatch):
    """sqrt is the l2 norms' (and the norms' own, which a constant
    cannot pass for): q and k left as the convolution gave them."""
    real = qwen3_next._delta_rule.__wrapped__

    def unnormed(x, w, **kw):
        saved = qwen3_next.jnp.sqrt
        qwen3_next.jnp.sqrt = lambda y: jnp.ones_like(y) \
            if y.ndim == 3 and y.shape[-1] == 1 else saved(y)
        try:
            return real(x, w, **kw)
        finally:
            qwen3_next.jnp.sqrt = saved
    monkeypatch.setattr(qwen3_next, "_delta_rule", unnormed)


FAULTS = {
    "whole_head_rotated": whole_head_rotated,
    "no_l2_norm": no_l2_norm,
    "share_shifted": dict(experts_first=8),
}


@pytest.fixture(scope="module")
def sound(built):
    """The sound check's result, and its reference logits to replay: a
    fault in the PROGRAM is held to them without running the reference
    again (as benchmarks/check_faults.py does)."""
    from benchmarks.check_sensitivity import recorded
    config, cfg, params = built("int8")
    record, replay = recorded(qwen3_next.forward)
    return system.logits_check(params, cfg, config, 4, forward=record), replay


_slow = pytest.mark.slow    # 11-13 s each: the cache cleared around them


@pytest.mark.parametrize("fault", [
    "share_shifted", pytest.param("whole_head_rotated", marks=_slow),
    pytest.param("no_l2_norm", marks=_slow)])
def test_a_reference_with_a_fault_fails_the_logits_check(built, sound, fault,
                                                         monkeypatch):
    """Held to the sound reference the paged path passes; held to a
    reference with the fault it does not. A fault in the model group is
    another set of static arguments; the patched ones wrap what the
    reference's un-jitted ``forward`` looks up at every call (its jitted
    pieces are re-traced: the cache is cleared around them)."""
    assert sound[0]["share_over_tolerance"] <= 0.1
    config, cfg, params = built("int8")
    model = dict(config["model"])
    patched = callable(FAULTS[fault])
    if patched:
        jax.clear_caches()
        FAULTS[fault](monkeypatch)
    else:
        model.update(FAULTS[fault])
    try:
        with pytest.raises(system.CheckFailed):
            system.logits_check(
                params, cfg, config, 4, forward=lambda p, m, ids, pos:
                qwen3_next.forward(p, dict(model, routed_together=None),
                                   ids, pos))
    finally:
        if patched:
            monkeypatch.undo()
            jax.clear_caches()


with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmarks",
                       "faults", "qwen3-next-80b-a3b-instruct.json")) as f:
    PROGRAM_FAULTS = json.load(f)


def test_the_faults_file_names_what_has_a_key():
    """Every mixer fault a configuration key can plant; the recurrence's
    own (no decay, beta 1, no l2 norm, a bf16 state) have none — the
    program has one recurrence — and are planted in the ops, in float32
    (tests/test_recurrent_layers.py)."""
    assert set(PROGRAM_FAULTS) == {
        "rotation_over_all_256", "no_attn_gate", "no_shared_expert_gate",
        "share_shifted_to_128_255", "no_qk_norm"}


@pytest.mark.parametrize("name", sorted(PROGRAM_FAULTS))    # ~5 s each
def test_a_program_with_a_fault_fails_the_logits_check(built, sound, name):
    """The benchmark's faults file, on the CPU at the toy size: the
    PROGRAM one mechanism short, the reference as it is (its logits
    replayed)."""
    import dataclasses
    config, cfg, params = built("int8")
    fields = PROGRAM_FAULTS[name]
    if "experts_first" in fields:
        fields = {"experts_first": 8}       # the toy's other share
    broken = dataclasses.replace(cfg, **fields)
    with pytest.raises(system.CheckFailed):
        system.logits_check(params, broken, config, 4, forward=sound[1])


def test_weights_one_precision_step_down_fail_the_logits_check(built, sound):
    """The harness's precision control (benchmarks/check_sensitivity.py
    ``--weights-lower-seeds``) at the toy size: the same seed's weights
    stored int4 where int8 is stated, the reference's logits replayed."""
    config, cfg, _ = built("int8")
    lower = system.make_params(cfg, "int4", 11)
    with pytest.raises(system.CheckFailed, match="median position"):
        system.logits_check(lower, cfg, config, 4, forward=sound[1])


def test_a_model_group_it_does_not_describe_is_refused(built):
    config, _, params = built("")
    ids = ids_of(1)
    for change, match in [(dict(shared_expert_gate=False), "qwen3_next block"),
                          (dict(router_score_func="sigmoid"),
                           "qwen3_next block"),
                          (dict(attn_gate=False), "qwen3_next block")]:
        with pytest.raises(ValueError, match=match):
            qwen3_next.forward(params, dict(config["model"], **change),
                               ids[None], [0])
