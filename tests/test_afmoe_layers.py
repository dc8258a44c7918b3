"""A model whose blocks are not all alike — a leading dense layer and an
expert stack in two runs of the one layer driver over ONE pool, sigmoid
routing with a selection bias beside a shared expert, gated QK-normed
attention, four norms a block, an embedding multiplier — at a toy size on
the CPU, with seeded weights and a toy window SHORTER than the prompts,
against the benchmark's plain reference of the architecture
(``benchmarks/references/afmoe.py``).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.references import afmoe  # noqa: E402
from generativeaiexamples_tpu.models import llama  # noqa: E402
from generativeaiexamples_tpu.models.configs import (  # noqa: E402
    MODEL_REGISTRY, LlamaConfig)
from generativeaiexamples_tpu.ops.quant import (  # noqa: E402
    is_quantized, quantize_params)
from generativeaiexamples_tpu.ops.rope import rope_frequencies  # noqa: E402
from generativeaiexamples_tpu.parallel import moe  # noqa: E402

PAGE = 128
WINDOW = 160                 # starts mid-page, shorter than every prompt
MODEL = dict(
    vocab_size=512, hidden_size=128, intermediate_size=192,
    moe_intermediate_size=64, num_layers=4, num_dense_layers=1,
    num_heads=4, num_kv_heads=2, head_dim=128, max_position_embeddings=2048,
    rope_theta=1e4, rms_norm_eps=1e-5, num_experts=16,
    num_experts_per_tok=4, num_shared_experts=1, moe_impl="dropless",
    router_score_func="sigmoid", router_norm_topk=True, router_scale=2.826,
    router_bias="selection", sliding_window=WINDOW,
    window_layers=[1, 1, 0, 1], rope_layers=[1, 1, 0, 1], qk_norm=True,
    attn_gate=True, post_norms=True, embed_scale=128 ** 0.5,
    weight_init="unit_stream")
CFG = LlamaConfig(**MODEL)
S = 384                      # three pages; positions 160.. lie past it


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def ids():
    return np.asarray(jax.random.randint(jax.random.key(1), (1, S), 3, 512))


@pytest.fixture(scope="module")
def ref(params, ids):
    return afmoe.forward(params, MODEL, ids, np.arange(S))


def rel_err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def plain(params, cfg, ids):
    return jax.jit(lambda p, i: llama.apply(
        p, cfg, i, jnp.arange(S)[None]))(params, jnp.asarray(ids))[0][0]


def test_config_names_two_stacks_and_refuses_what_it_cannot_run():
    assert CFG.layer_stacks == (("dense_layers", 0, 1), ("layers", 1, 3))
    assert LlamaConfig().layer_stacks == (("layers", 0, 32),)
    assert CFG.expert_width == 64 and LlamaConfig().expert_width == 11008
    assert llama.layer_kinds(CFG, 1, 3)["window"].tolist() == [WINDOW, 0,
                                                               WINDOW]
    for bad in (dict(router_score_func="tanh"), dict(router_bias="maybe"),
                dict(router_scale=2.0), dict(num_dense_layers=1),
                dict(num_experts=4, num_shared_experts=1),
                dict(num_experts=4, moe_impl="dropless", num_layers=2,
                     num_dense_layers=2)):
        with pytest.raises(ValueError):
            LlamaConfig(**bad)


def test_registry_serves_the_published_depth():
    cfg = MODEL_REGISTRY["trinity-mini"]
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.hidden_size) == (
        32, 2, 2048)
    assert (cfg.intermediate_size, cfg.expert_width) == (6144, 1024)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 4, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.num_shared_experts) == (128, 8, 1)
    assert cfg.layer_windows[:5] == (2048, 2048, 2048, 0, 2048)
    assert sum(cfg.layer_rope) == 24 and cfg.vocab_size == 200192
    assert cfg.layer_stacks == (("dense_layers", 0, 2), ("layers", 2, 30))
    shapes = jax.eval_shape(lambda k: llama.init_params(
        dataclasses.replace(cfg, num_layers=3), k), jax.random.key(0))
    attention = {"wq", "wk", "wv", "wo", "wz", "q_norm", "k_norm",
                 "attn_norm", "mlp_norm", "post_attn_norm", "post_mlp_norm"}
    assert set(shapes["dense_layers"]) == attention | {
        "w_gate", "w_up", "w_down"}
    assert set(shapes["layers"]) == attention | {
        "router", "router_bias", "w_gate", "w_up", "w_down", "ws_gate",
        "ws_up", "ws_down"}
    assert shapes["dense_layers"]["w_gate"].shape == (2, 2048, 6144)
    assert shapes["layers"]["w_gate"].shape == (1, 128, 2048, 1024)
    assert shapes["layers"]["ws_down"].shape == (1, 1024, 2048)


def test_the_draw_gives_a_unit_stream_and_a_bias_that_chooses(params):
    stream = params["embed"] * CFG.embed_scale
    assert abs(float(jnp.std(stream)) - 1.0) < 0.05
    bias = params["layers"]["router_bias"]
    assert bias.dtype == jnp.float32 and 0.05 < float(jnp.std(bias)) < 0.2
    for name in ("q_norm", "k_norm", "post_attn_norm", "post_mlp_norm"):
        for stack in ("layers", "dense_layers"):
            w = params[stack][name]
            spread = float(jnp.std(w) / jnp.mean(w))
            assert 0.05 < spread < 0.2           # near its centre, not on it
    # the post-norms carry what wo / w_down cannot: 1/sqrt(2L), and the
    # MLP's a fifth of it
    L = CFG.num_layers
    for stack in ("layers", "dense_layers"):
        assert float(jnp.mean(params[stack]["post_attn_norm"])) \
            == pytest.approx((2 * L) ** -0.5, rel=0.05)
        assert float(jnp.mean(params[stack]["post_mlp_norm"])) \
            == pytest.approx(0.2 * (2 * L) ** -0.5, rel=0.05)


def test_the_selection_bias_evens_the_load_of_unequal_columns(params):
    """Router columns of unequal reach, and the bias that lifts each
    expert to the same cut: over many normed rows every expert is chosen
    about as often, although the bias is twenty times the gap between
    neighbours in rank; without it the long-reach experts take all."""
    cfg = dataclasses.replace(CFG, num_experts=128, num_experts_per_tok=8)
    lp = {k: v[0] for k, v in llama.init_params(
        cfg, jax.random.key(2), dtype=jnp.float32)["layers"].items()}
    m = jax.random.normal(jax.random.key(9), (4096, cfg.hidden_size))
    m = m / jnp.sqrt(jnp.mean(m * m, -1, keepdims=True))
    logits = m @ lp["router"]

    def usage(cfg):
        select, _ = moe.router_scores(logits, lp, cfg)
        chosen = np.asarray(jax.lax.top_k(select, 8)[1])
        counts = np.bincount(chosen.ravel(), minlength=128) / (4096 / 16)
        touched = np.mean([len(set(chosen[i:i + 14].ravel()))
                           for i in range(0, 4096 - 14, 14)])
        return counts, touched
    counts, touched = usage(cfg)
    assert 0.4 < counts.min() and counts.max() < 2.5
    assert touched == pytest.approx(
        128 * (1 - (1 - 8 / 128) ** 14), rel=0.05)          # 76.9
    assert 0.05 < float(jnp.std(lp["router_bias"])) < 0.2
    bare, fewer = usage(dataclasses.replace(cfg, router_bias=""))
    assert bare.max() > 3.0 and fewer < 0.9 * touched


@pytest.mark.parametrize("quant", ["", "int8"])
def test_reference_against_apply(params, ids, ref, quant):
    if not quant:
        assert rel_err(plain(params, CFG, ids), ref) < 1e-4
        return
    stored = quantize_params(params, "int8")
    for stack in ("layers", "dense_layers"):
        assert is_quantized(stored[stack]["wz"])
        assert not is_quantized(stored[stack]["q_norm"])
    assert is_quantized(stored["layers"]["ws_gate"])
    assert not is_quantized(stored["layers"]["w_gate"])     # routed: as is
    assert not is_quantized(stored["layers"]["router"])
    # the reference reads the tree AS STORED: int8 times its scale
    want = afmoe.forward(stored, MODEL, ids, np.arange(S))
    assert rel_err(plain(stored, CFG, ids), want) < 1e-3
    assert rel_err(want, ref) > 1e-3          # and int8 is not the raw tree


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_reference_against_prefill_then_decode(params, ids, ref, use_kernel):
    """Two pages through ``apply_prefill_paged`` in two chunks (the
    second reads the first back through the window), then the third
    page's first tokens a decode step each through the pool: the gather
    path (pool held across both stacks) and the kernel (pool carried
    from the dense stack into the expert stack)."""
    pool = llama.init_paged_kv_cache(CFG, 5, PAGE, jnp.float32)
    table = jnp.arange(1, 5, dtype=jnp.int32)[None]
    for c in range(2):
        pos = jnp.arange(c * PAGE, (c + 1) * PAGE)[None]
        h, pool = jax.jit(lambda p, pool, t, pos, c: llama.apply_prefill_paged(
            p, CFG, t, pos, pool, table, pos[:, -1] + 1, c))(
            params, pool, jnp.asarray(ids[:, c * PAGE:(c + 1) * PAGE]), pos,
            jnp.int32(c))
        got = llama.unembed(params, CFG, h)[0]
        assert rel_err(got, ref[c * PAGE:(c + 1) * PAGE]) < 1e-4

    @jax.jit
    def step(p, pool, tok, at):
        return llama.apply_decode_paged(
            p, CFG, tok[None, None], at[None, None], pool, table,
            (at + 1)[None], (1 + at // PAGE)[None], (at % PAGE)[None],
            use_kernel=use_kernel)
    for at in range(2 * PAGE, 2 * PAGE + 4):
        logits, pool = step(params, pool, jnp.asarray(ids[0, at]),
                            jnp.int32(at))
        assert rel_err(logits[0, 0], ref[at]) < 1e-4


FAULTS = {
    "no_attn_gate": dict(attn_gate=False),
    "no_qk_norm": dict(qk_norm=False),
    "rotary_in_global_layers": dict(rope_layers=()),
    "window_one_short": dict(sliding_window=WINDOW - 32),
    "no_window": dict(sliding_window=0),
    "weights_from_biased_scores": dict(router_bias="scores"),
    "no_selection_bias": dict(router_bias=""),
    "route_scale_1": dict(router_scale=1.0),
    "no_route_norm": dict(router_norm_topk=False),
    "no_shared_expert": dict(num_shared_experts=0),
    "no_post_norms": dict(post_norms=False),
    "no_embed_scale": dict(embed_scale=1.0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_programs_configuration_fails(params, ids, ref, fault):
    """Each mechanism the model adds, taken out of the PROGRAM's
    configuration over the same tree, the reference as it is."""
    broken = dataclasses.replace(CFG, **FAULTS[fault])
    assert rel_err(plain(params, broken, ids), ref) > 0.02


def test_int4_for_int8_fails(params, ids):
    want = afmoe.forward(quantize_params(params, "int8"), MODEL, ids,
                         np.arange(S))
    assert rel_err(plain(quantize_params(params, "int4"), CFG, ids),
                   want) > 0.02


# ------------------------------------------------------------- the router


def test_a_bias_moves_the_choice_and_leaves_the_weights_unbiased():
    T, E, k = 64, 16, 4
    logits = jax.random.normal(jax.random.key(3), (T, E), jnp.float32)
    bias = 0.3 * jax.random.normal(jax.random.key(4), (E,), jnp.float32)
    scores = jax.nn.sigmoid(logits)
    lp = {"router_bias": bias}
    select, weigh = moe.router_scores(logits, lp, CFG)
    rt = moe.route_sorted(select, k, 16, None, weigh)
    _, want = jax.lax.top_k(scores + bias, k)
    _, unbiased = jax.lax.top_k(scores, k)
    chosen = np.sort(np.asarray(
        jnp.argsort(-(scores + bias), axis=1)[:, :k]), axis=1)
    assert (np.sort(np.asarray(want), axis=1) == chosen).all()
    assert (np.sort(np.asarray(want), 1)
            != np.sort(np.asarray(unbiased), 1)).any()      # it chooses
    got = rt["weight"]                                      # (T, k)
    np.testing.assert_allclose(
        got, jnp.take_along_axis(scores, want, axis=1), rtol=1e-6)
    scaled = moe.scale_chosen(got, CFG)
    np.testing.assert_allclose(jnp.sum(scaled, -1), CFG.router_scale,
                               rtol=1e-5)
    # "scores": the bias moves the weights too
    both = dataclasses.replace(CFG, router_bias="scores")
    select2, weigh2 = moe.router_scores(logits, lp, both)
    assert weigh2 is select2 and bool(jnp.all(select2 == select))
    # softmax scoring: today's callers, one score for both
    soft = dataclasses.replace(CFG, router_score_func="softmax",
                               router_bias="", router_scale=1.0)
    select3, weigh3 = moe.router_scores(logits, {}, soft)
    assert select3 is logits and weigh3 is None
    rt3 = moe.route_sorted(select3, k, 16)
    np.testing.assert_allclose(
        rt3["weight"], jax.nn.softmax(jax.lax.top_k(logits, k)[0], -1),
        rtol=1e-6)


def test_a_tokens_output_does_not_depend_on_its_neighbours(params, ids):
    """Dropless: a token alone and among 127 others gets the same
    experts' outputs."""
    lp = {k: v[1] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.key(5), (1, PAGE, CFG.hidden_size))

    def layer(x):
        logits = x.astype(jnp.float32) @ lp["router"]
        return moe.dropless_moe_ffn(x, logits, lp, CFG)[0]
    crowd = layer(x)
    for t in (0, 17, PAGE - 1):
        alone = layer(x[:, t:t + 1])
        assert rel_err(alone[0, 0], crowd[0, t]) < 1e-5


# ------------------------------------------------------------ two stacks


def by_hand(params, tokens, positions, attend_for=None):
    """``decoder_layer`` called once a layer, by hand, over the model's
    four layers: the dense one then the three expert ones."""
    inv_freq = rope_frequencies(CFG.head_dim, CFG.rope_theta)
    h = llama._embed(params, tokens, CFG.embed_scale)
    kinds = llama.layer_kinds(CFG)
    new = []
    for layer in range(CFG.num_layers):
        stack, at = (("dense_layers", layer) if layer < CFG.num_dense_layers
                     else ("layers", layer - CFG.num_dense_layers))
        lp = {k: v[at] for k, v in params[stack].items()}
        lp.update({k: v[layer] for k, v in kinds.items()})
        h, out = llama.decoder_layer(
            h, lp, CFG, positions, inv_freq, None,
            attend=attend_for and attend_for(layer, lp))
        new.append(out)
    return h, new


def test_two_stacks_equal_decoder_layer_four_times_by_hand(params, ids):
    tokens, pos = jnp.asarray(ids[:, :PAGE]), jnp.arange(PAGE)[None]
    h, _ = by_hand(params, tokens, pos)
    want = llama.unembed(params, CFG, h)
    got, _ = llama.apply(params, CFG, tokens, pos)
    assert rel_err(got, want) < 1e-5


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_each_layers_rows_land_in_its_own_slab_of_the_pool(params, ids,
                                                           use_kernel):
    """A chunk's K and V by hand, a layer at a time, against what the
    two-stack driver wrote into the pool: layer l's rows in slab l, the
    dense stack's layer first; then one decode step appends at the same
    layers (the kernel counts its layer from the stack's first)."""
    tokens, pos = jnp.asarray(ids[:, :PAGE]), jnp.arange(PAGE)[None]

    def keep(layer, lp):
        def attend(q, k, v):
            from generativeaiexamples_tpu.ops.attention import gqa_attention
            return gqa_attention(q, k, v, pos, None,
                                 window=lp.get("window")), (k[0], v[0])
        return attend
    _, rows = by_hand(params, tokens, pos, keep)
    pool = llama.init_paged_kv_cache(CFG, 4, PAGE, jnp.float32)
    table = jnp.asarray([[2, 3]], jnp.int32)
    _, pool = llama.apply_prefill_paged(params, CFG, tokens, pos, pool, table,
                                        jnp.asarray([PAGE]), jnp.int32(0))
    for layer, (k, v) in enumerate(rows):
        # pool: (L, N, KV, page, hd); rows: (page, KV, hd)
        np.testing.assert_allclose(pool["k"][layer, 2], k.swapaxes(0, 1),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(pool["v"][layer, 2], v.swapaxes(0, 1),
                                   rtol=2e-5, atol=2e-5)
    assert not pool["k"][:, 3].any() and not pool["k"][:, 1].any()
    # one decode step: every layer appends row 0 of page 3 and nothing
    # past it (the kernel writes the row's whole 8-row tile)
    before = pool
    _, pool = llama.apply_decode_paged(
        params, CFG, jnp.asarray(ids[:, PAGE:PAGE + 1]),
        jnp.asarray([[PAGE]]), pool, table, jnp.asarray([PAGE + 1]),
        jnp.asarray([3]), jnp.asarray([0]), use_kernel=use_kernel)
    wrote = np.asarray(pool["k"] != before["k"]).any(axis=(2, 4))
    for layer in range(CFG.num_layers):
        assert wrote[layer, 3, 0] and not wrote[layer, 3, 8:].any()
        assert not wrote[layer, :3].any()
    # and each layer's new key differs: no layer wrote another's slab
    new = np.asarray(pool["k"][:, 3, :, 0])
    assert len({new[layer].tobytes() for layer in range(4)}) == 4


def test_experts_touched_is_a_mean_over_the_expert_layers(params, ids):
    """One row, k experts a layer: the mean over the three expert layers
    is k; a mean that counted the dense layer's zero would read 3k/4."""
    pool = llama.init_paged_kv_cache(CFG, 3, PAGE, jnp.float32)
    table = jnp.asarray([[1, 2]], jnp.int32)
    for use_kernel in (False, True):
        _, _, stats = llama.apply_decode_paged(
            params, CFG, jnp.asarray(ids[:, :1]), jnp.asarray([[0]]), pool,
            table, jnp.asarray([1]), jnp.asarray([1]), jnp.asarray([0]),
            use_kernel=use_kernel, stats=True)
        assert float(stats["experts_touched"]) == CFG.num_experts_per_tok
