"""The second layer stack and the leaves a block's variants add, outside
the forward: a checkpoint's names onto the tree, a sharding spec for
every leaf, and the engine's count of the bytes a decode step streams
held against the benchmark's own layer-by-layer count."""

import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.harness import costs_layerwise  # noqa: E402
from benchmarks.references import afmoe  # noqa: E402
from generativeaiexamples_tpu.engine import engine as engine_mod  # noqa: E402
from generativeaiexamples_tpu.models import llama  # noqa: E402
from generativeaiexamples_tpu.models.configs import LlamaConfig  # noqa: E402
from generativeaiexamples_tpu.models.import_hf import (  # noqa: E402
    params_from_named_tensors)
from generativeaiexamples_tpu.ops.quant import quantize_params  # noqa: E402
from generativeaiexamples_tpu.parallel import (  # noqa: E402
    MeshPlan, llama_param_specs, make_mesh, shard_params)
from generativeaiexamples_tpu.utils.errors import ModelLoadError  # noqa: E402

MODEL = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_layers=4, num_dense_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, max_position_embeddings=256,
    num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
    moe_impl="dropless", router_score_func="sigmoid", router_scale=2.826,
    router_bias="selection", sliding_window=8, window_layers=[1, 1, 1, 0],
    rope_layers=[1, 1, 1, 0], qk_norm=True, attn_gate=True, post_norms=True,
    embed_scale=8.0, weight_init="unit_stream")
CFG = LlamaConfig(**MODEL)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(2), dtype=jnp.float32)


# --------------------------------------------------------- the checkpoint

ATTN = {"attn_norm": "input_layernorm.weight",
        "post_attn_norm": "post_attention_layernorm.weight",
        "mlp_norm": "pre_mlp_layernorm.weight",
        "post_mlp_norm": "post_mlp_layernorm.weight",
        "q_norm": "self_attn.q_norm.weight",
        "k_norm": "self_attn.k_norm.weight",
        "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
        "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
        "wz": "self_attn.gate_proj.weight"}
GATED = {"gate": "gate_proj.weight", "up": "up_proj.weight",
         "down": "down_proj.weight"}


def state_dict(params):
    """The tree under an ``afmoe`` checkpoint's names (out-major
    matrices, a layer and an expert a tensor)."""
    def out(leaf):
        a = np.asarray(leaf)
        return a.T if a.ndim == 2 else a

    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]),
          "model.norm.weight": np.asarray(params["final_norm"]),
          "lm_head.weight": np.asarray(params["lm_head"]).T}
    for layer in range(CFG.num_layers):
        dense = layer < CFG.num_dense_layers
        tree = params["dense_layers" if dense else "layers"]
        at = layer if dense else layer - CFG.num_dense_layers
        pre = f"model.layers.{layer}."
        for leaf, name in ATTN.items():
            sd[pre + name] = out(tree[leaf][at])
        for part, name in GATED.items():
            if dense:
                sd[pre + "mlp." + name] = out(tree["w_" + part][at])
                continue
            sd[pre + "mlp.shared_experts." + name] = out(
                tree["ws_" + part][at])
            for e in range(CFG.num_experts):
                sd[pre + f"mlp.experts.{e}." + name] = out(
                    tree["w_" + part][at, e])
        if not dense:
            sd[pre + "mlp.router.gate.weight"] = out(tree["router"][at])
            sd[pre + "mlp.expert_bias"] = out(tree["router_bias"][at])
    return sd


def test_checkpoint_names_land_on_the_two_stacks(params):
    got = params_from_named_tensors(iter(state_dict(params).items()), CFG,
                                    jnp.float32)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # in bf16 the bias stays the float32 buffer it is
    low = params_from_named_tensors(iter(state_dict(params).items()), CFG)
    assert low["layers"]["router_bias"].dtype == jnp.float32
    assert low["layers"]["router"].dtype == jnp.bfloat16
    # and the imported tree is the model: the reference agrees
    ids = np.asarray(jax.random.randint(jax.random.key(1), (1, 24), 3, 256))
    want = afmoe.forward(got, MODEL, ids, np.arange(24))
    logits, _ = llama.apply(got, CFG, jnp.asarray(ids),
                            jnp.arange(24)[None])
    assert float(jnp.max(jnp.abs(logits[0] - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def test_a_checkpoint_short_of_an_expert_or_a_layers_leaf_is_refused(params):
    sd = state_dict(params)
    for gone in ("model.layers.3.mlp.experts.5.up_proj.weight",
                 "model.layers.1.self_attn.gate_proj.weight",
                 "model.layers.2.mlp.expert_bias"):
        with pytest.raises(ModelLoadError):
            params_from_named_tensors(
                ((k, v) for k, v in sd.items() if k != gone), CFG)


# ------------------------------------------------------------ the specs


@pytest.mark.parametrize("quant", ["", "int8"])
def test_specs_cover_the_tree_leaf_for_leaf(params, quant, cpu_devices):
    mesh = make_mesh(MeshPlan(dp=2, tp=2, ep=2))
    specs = llama_param_specs(CFG, mesh)
    assert set(specs) == set(params)
    for stack in ("layers", "dense_layers"):
        assert set(specs[stack]) == set(params[stack]), stack
        for name, spec in specs[stack].items():
            assert len(spec) == params[stack][name].ndim, (stack, name)
    assert specs["layers"]["wz"] == specs["layers"]["wq"]
    assert specs["layers"]["ws_down"] == specs["dense_layers"]["w_down"]
    assert specs["layers"]["w_gate"][1] == "ep"
    tree = quantize_params(params, quant) if quant else params
    sharded = shard_params(tree, mesh, specs)
    ids = jnp.asarray(jax.random.randint(jax.random.key(1), (2, 16), 3, 256))
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    want, _ = llama.apply(tree, CFG, ids, pos)
    got, _ = jax.jit(lambda p: llama.apply(p, CFG, ids, pos))(sharded)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_a_model_of_one_stack_has_no_second_spec(cpu_devices):
    mesh = make_mesh(MeshPlan(dp=4, tp=2))
    assert "dense_layers" not in llama_param_specs(LlamaConfig(), mesh)


# ---------------------------------------------- the engine's static count


def config_file(name):
    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def stored_tree(config):
    """The shapes of the served tree at the file's own sizes."""
    cfg = LlamaConfig(**config["model"])
    return cfg, jax.eval_shape(lambda k: quantize_params(
        llama.init_params(cfg, k, dtype=jnp.bfloat16),
        config["weight_quant"]), jax.random.key(0))


# (all, routed) bytes as the engine counted them before this tree had a
# second stack: what these three configurations' rounds report must not
# move
TODAY = {
    "nemotron-8b-chat": (9_594_986_496, 0),
    "mixtral-8x7b-instruct": (11_835_905_024, 0),
    "smallthinker-21b-a3b-instruct": (10_483_235_328, 9_059_696_640),
}


@pytest.mark.parametrize("name", sorted(TODAY))
def test_the_count_of_the_other_three_trees_is_todays(name):
    cfg, tree = stored_tree(config_file(name))
    assert engine_mod.weight_bytes_of(tree, cfg) == TODAY[name]


@pytest.mark.parametrize("rows", [1, 8, 14.3, 16])
@pytest.mark.parametrize("name", sorted(TODAY) + ["trinity-mini"])
def test_step_weight_bytes_against_the_layerwise_count(name, rows):
    """What a decode step streams by the engine's plan (``RoundRecord.
    hbm_bytes``) against ``costs_layerwise``: the engine reads the tree
    — and counts the whole embedding and the norms, which the benchmark
    does not (a step reads the rows it looks up)."""
    config = config_file(name)
    cfg, tree = stored_tree(config)
    every, routed = engine_mod.weight_bytes_of(tree, cfg)
    step = engine_mod.Engine._step_weight_bytes(SimpleNamespace(
        _param_bytes=every, _expert_bytes=routed, model_cfg=cfg), rows)
    want = costs_layerwise.decode_step(
        config["model"], config["weight_quant"], rows, 0.0)["weight_bytes"]
    embedding = 2 * cfg.vocab_size * cfg.hidden_size - rows * 2 \
        * cfg.hidden_size
    if cfg.moe_impl == "sparse" and cfg.num_experts:
        # capacity routing streams every expert; the benchmark counts
        # the experts a step's rows reach
        assert step - embedding > want
        return
    assert step - embedding == pytest.approx(want, rel=2e-4)
    if name == "trinity-mini":
        assert routed == 7 * 128 * 3 * 2048 * 1024 * 2
        assert 12.80e9 < every < 12.82e9        # the weights program's
