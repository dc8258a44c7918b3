"""Each plain reference against the package, at toy sizes on the CPU:
its plain forward AND its paged path (prefill into the pool, then
teacher-forced decode steps through the cache), over seeded weights as
they are stored, raw and int8; a reference with a fault put into it
fails the logits check at the rehearsal configuration's own tolerances;
nothing under ``references/`` imports the package."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import system
from benchmarks.references import gptnext, mixtral

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
REFS = os.path.join(REPO, "benchmarks", "references")

BASE = {"vocab_size": 320, "hidden_size": 64, "intermediate_size": 128,
        "num_layers": 3, "num_heads": 4, "head_dim": 16,
        "max_position_embeddings": 512}
ARCH = {
    "mha": ("gptnext", dict(BASE, num_kv_heads=4, norm="layernorm1p",
                            mlp="squared_relu")),
    "gqa": ("mixtral", dict(BASE, num_kv_heads=2, rope_theta=1e6)),
    "experts": ("mixtral", dict(BASE, num_kv_heads=2, rope_theta=1e6,
                                num_experts=4, num_experts_per_tok=2,
                                moe_impl="dense")),
    # a capacity so small that it drops: half a slot a claim
    "capacity": ("mixtral", dict(BASE, num_kv_heads=2, rope_theta=1e6,
                                 num_experts=4, num_experts_per_tok=2,
                                 moe_impl="sparse",
                                 moe_capacity_factor=0.5)),
}
MODULES = {"gptnext": gptnext, "mixtral": mixtral}
PAGE, T, N_DEC = 16, 48, 4
CASES = [(a, q) for a in ARCH for q in ("", "int8")]

with open(os.path.join(HERE, "rehearsal", "configs", "tiny-dense.json")) as f:
    TOY = json.load(f)


def config_of(arch, quant):
    """The rehearsal configuration's own check and tolerances over
    another toy architecture."""
    name, model = ARCH[arch]
    return dict(TOY, model=model, reference=name, weight_quant=quant,
                engine=dict(TOY["engine"], page_size=PAGE))


@pytest.fixture(scope="module")
def built():
    out = {}

    def get(arch, quant):
        if (arch, quant) not in out:
            config = config_of(arch, quant)
            cfg = system.model_config(config)
            out[arch, quant] = (config, cfg,
                                system.make_params(cfg, quant, 11))
        return out[arch, quant]
    return get


def forward_of(arch):
    return MODULES[ARCH[arch][0]].forward


def ids_of(seed, n=T):
    return np.random.default_rng(seed).integers(3, BASE["vocab_size"], n)


def rel_err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def agree(got, want, arch) -> bool:
    """bf16 activations against float32: every position within 0.04 of
    the logits' scale. A router in bf16 flips a near-tie of random
    weights at a position or two, which moves THAT position by a fifth
    or more: with experts the median position is held, and at most one
    position in eight (or one) may lie over."""
    e = np.asarray(jnp.max(jnp.abs(got - want), axis=-1)
                   / jnp.max(jnp.abs(want), axis=-1))
    if "num_experts" not in ARCH[arch][1]:
        return bool(e.max() < 0.04)
    return bool(np.median(e) < 0.04
                and (e > 0.04).sum() <= max(1, len(e) // 8))


def package_plain(params, cfg, ids):
    from generativeaiexamples_tpu.models import llama
    n = len(ids)
    cache = llama.init_kv_cache(cfg, 1, n, jnp.bfloat16)
    logits, _ = llama.apply(
        params, cfg, jnp.asarray(ids, jnp.int32)[None],
        jnp.arange(n, dtype=jnp.int32)[None], cache,
        kv_valid_len=jnp.asarray([n], jnp.int32))
    return logits[0].astype(jnp.float32)


def package_paged(params, cfg, ids, n_prompt):
    """Prefill of ``ids[:n_prompt]`` into a paged pool, then one
    teacher-forced decode step for each further id: the logits of the
    last prompt position and of every step."""
    from generativeaiexamples_tpu.models import llama
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    nb = -(-len(ids) // PAGE)
    pool = llama.init_paged_kv_cache(cfg, nb + 1, PAGE, jnp.bfloat16)
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    hidden, pool = llama.apply_prefill_paged(
        params, cfg, jnp.asarray(ids[:n_prompt], jnp.int32)[None],
        jnp.arange(n_prompt, dtype=jnp.int32)[None], pool, table,
        i32(n_prompt), jnp.int32(0))
    out = [llama.unembed(params, cfg, hidden[:, n_prompt - 1:n_prompt])[0, 0]]
    for at in range(n_prompt, len(ids)):
        step, pool = llama.apply_decode_paged(
            params, cfg, i32(ids[at])[None], i32(at)[None], pool, table,
            i32(at + 1), i32(1 + at // PAGE), i32(at % PAGE),
            use_kernel=False)
        out.append(step[0, 0])
    return jnp.stack(out).astype(jnp.float32)


@pytest.mark.parametrize("arch,quant", CASES)
def test_reference_agrees_with_the_packages_plain_forward(built, arch, quant):
    config, cfg, params = built(arch, quant)
    ids = ids_of(1)
    want = forward_of(arch)(params, config["model"], ids[None], np.arange(T))
    assert want.shape == (T, BASE["vocab_size"]) and want.dtype == jnp.float32
    assert agree(package_plain(params, cfg, ids), want, arch)


@pytest.mark.parametrize("arch,quant", CASES)
def test_reference_agrees_with_prefill_then_decode_through_the_pool(
        built, arch, quant):
    config, cfg, params = built(arch, quant)
    ids = ids_of(2, T + N_DEC)           # a prompt of whole pages
    n_prompt = T
    got = package_paged(params, cfg, ids, n_prompt)
    want = forward_of(arch)(
        params, dict(config["model"], routed_together=[T] + [1] * N_DEC),
        ids[None], np.arange(n_prompt - 1, T + N_DEC))
    assert got.shape == want.shape == (N_DEC + 1, BASE["vocab_size"])
    assert agree(got, want, arch)


def test_what_follows_a_position_does_not_move_its_logits(built):
    """The harness pads a sequence to a bucket: causal, so harmless."""
    config, _, params = built("experts", "int8")
    ids = ids_of(3)
    short = mixtral.forward(params, config["model"], ids[None, :20],
                            np.arange(20))
    padded = mixtral.forward(params, config["model"], ids[None],
                             np.arange(20))
    assert rel_err(padded, short) < 1e-5


def broken_mask(monkeypatch, module):
    """Every position attends the whole sequence."""
    real = jnp.where
    monkeypatch.setattr(module.jnp, "where",
                        lambda c, a, b: a if getattr(c, "ndim", 0) == 3
                        and c.dtype == bool else real(c, a, b))


def swapped_rope(monkeypatch, module):
    """The halves rotate the other way."""
    real = module._rope
    monkeypatch.setattr(module, "_rope", lambda x, theta: real(
        x[::-1], theta)[::-1])


FAULTS = {"broken_mask": broken_mask, "swapped_rope": swapped_rope,
          "dropped_layer": None}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("arch", sorted(ARCH))
def test_a_reference_with_a_fault_fails_the_logits_check(
        built, arch, fault, monkeypatch):
    """Held to the sound reference the paged path passes the rehearsal
    configuration's own tolerances; held to one with the fault it does
    not (with experts: a bf16 router's near-ties, as ``agree`` says, so
    one position in eight may lie over)."""
    config, cfg, params = built(arch, "int8")
    if arch == "experts":
        config = dict(config, logits_check=dict(
            config["logits_check"], max_share_over=0.125))
    module = MODULES[ARCH[arch][0]]
    system.logits_check(params, cfg, config, 4)
    jax.clear_caches()               # the fault goes into a jitted function
    model = dict(config["model"])
    if FAULTS[fault] is None:
        model["num_layers"] -= 1
    else:
        FAULTS[fault](monkeypatch, module)
    with pytest.raises(system.CheckFailed, match="differ from the reference"):
        system.logits_check(
            params, cfg, config, 4, forward=lambda p, m, ids, pos:
            module.forward(p, model, ids, pos))
    jax.clear_caches()


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(REFS) if f.endswith(".py")))
def test_no_reference_imports_the_package_or_another_file(name):
    with open(os.path.join(REFS, name)) as f:
        src = f.read()
    assert "generativeaiexamples_tpu" not in src
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", src, re.M)
    assert set(i.split(".")[0] for i in imports) <= {
        "functools", "jax", "math"}


@pytest.mark.parametrize("arch,quant", CASES)
def test_reference_reads_the_tree_as_the_package_stores_it(built, arch, quant):
    """int8 times its scale, bf16 upcast: a layer's leaf read by the
    reference is the package's own dequantised leaf."""
    from generativeaiexamples_tpu.ops.quant import dequantize, is_quantized
    _, _, params = built(arch, quant)
    module = MODULES[ARCH[arch][0]]
    some_int8 = False
    for name, leaf in params["layers"].items():
        theirs = dequantize(leaf, jnp.float32) if is_quantized(leaf) \
            else leaf.astype(jnp.float32)
        some_int8 |= is_quantized(leaf)
        assert jnp.allclose(module._f32(leaf, 1), theirs[1],
                            rtol=1e-6, atol=1e-8), name
    assert some_int8 == bool(quant)
    rows = jnp.asarray([5, 7])
    assert jnp.array_equal(module._f32(params["embed"], rows=rows),
                           params["embed"][rows].astype(jnp.float32))


def test_a_storage_the_reference_cannot_read_is_refused():
    with pytest.raises(ValueError, match="reads bf16 and per-channel int8"):
        mixtral._f32({"q4": jnp.zeros((2, 2), jnp.int8),
                      "scale": jnp.ones((2,))})


def test_a_dropless_reference_cannot_follow_a_capacity_that_drops(built):
    """The toy's capacity drops (half a slot a claim): the reference
    that routes as the configuration runs it agrees with the package,
    the same reference without the capacity does not."""
    config, cfg, params = built("capacity", "int8")
    ids = ids_of(5)
    got = package_plain(params, cfg, ids)
    assert agree(got, mixtral.forward(params, config["model"], ids[None],
                                      np.arange(T)), "capacity")
    dropless = dict(config["model"], moe_impl="dense")
    assert not agree(got, mixtral.forward(params, dropless, ids[None],
                                          np.arange(T)), "capacity")
    with pytest.raises(ValueError, match="does not cover"):
        mixtral.forward(params, dict(config["model"], routed_together=[T - 1]),
                        ids[None], np.arange(T))
