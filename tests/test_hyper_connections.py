"""Hyper-connected residual streams (``hc_mult``) on the CPU at tiny sizes,
seeded weights: the coefficients (doubly stochastic after the configured
normalisations, not after one), the two mixes against the plain
reference's lines (benchmarks/references/xing4_0.py), the whole model —
one pass, chunked prefill through the latent pool, decode through the
cache over the kernel and off it — against the reference's ONE full pass
on logits, raw float32 and int8 in bf16; every fault that no
configuration key can inject must show; ``hc_mult`` 0 leaves
``decoder_layer``'s jaxpr and every other tree as they were; the counter
``hc_row_defect`` reaches the round record; and one test for every path
that refuses the streams by name."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import xing4_0 as ref
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import (LLAMA_TINY,
                                                     MODEL_REGISTRY,
                                                     LlamaConfig)
from generativeaiexamples_tpu.ops import hyper_connection as hc
from generativeaiexamples_tpu.ops.quant import quantize_params

PAGE = 128
PLAIN = LlamaConfig(
    vocab_size=512, hidden_size=128, intermediate_size=256,
    moe_intermediate_size=64, num_layers=3, num_dense_layers=1, num_heads=4,
    num_kv_heads=1, head_dim=48, max_position_embeddings=4096,
    rope_theta=10000.0, rms_norm_eps=1e-6, num_experts=16,
    num_experts_per_tok=4, num_shared_experts=1, moe_impl="dropless",
    router_score_func="sigmoid", router_norm_topk=True, router_scale=2.0,
    router_bias="selection", kv_lora_rank=128, q_lora_rank=64,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    rope_interleave=True, rope_scaling_type="yarn", rope_scaling_factor=64.0,
    rope_original_max=64, rope_beta_fast=32.0, rope_beta_slow=1.0,
    rope_mscale_all_dim=1.0, weight_init="unit_stream")
# the draw of benchmarks/configs/xing4.0-29b-a4b.json: every expert held
CFG = dataclasses.replace(PLAIN, hc_mult=4,
                          weight_init="unit_stream_thin_experts")
HYPER = dict(n=4, iters=20, eps=1e-6, clamp=30.0)
T, N_PRE = 300, 256


def err(got, want):
    return float(jnp.max(jnp.abs(got - want)))


@pytest.fixture(scope="module")
def built():
    """Float32 weights, ids, and the reference's ONE full pass."""
    p = llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (1, T), 0, CFG.vocab_size)
    want = ref.forward(p, dataclasses.asdict(CFG), ids, list(range(T)))
    return p, ids, jnp.arange(T)[None], want


COEFF = jax.jit(hc.coefficients, static_argnames=("n", "iters", "eps",
                                                 "clamp"))


def weights(p, part="attn", layer=0, dtype=None):
    w = tuple(p["layers"][f"hc_{part}_{n}"][layer]
              for n in ("phi", "alpha", "b"))
    return w if dtype is None else (w[0].astype(dtype),) + w[1:]


# ---------------------------------------------------------- coefficients


def test_h_res_is_doubly_stochastic_after_20_iterations_not_after_1(built):
    x = jax.random.normal(jax.random.key(5), (256, 4 * CFG.hidden_size))
    for part, layer in (("attn", 0), ("mlp", 1)):
        w = weights(built[0], part, layer)
        _, _, h20 = COEFF(x, w, **HYPER)
        _, _, h1 = COEFF(x, w, **dict(HYPER, iters=1))
        rows, cols = h20.sum(1), h20.sum(0)
        assert float(jnp.max(jnp.abs(rows - 1))) < 1e-5
        assert float(jnp.max(jnp.abs(cols - 1))) < 1e-5
        assert bool(jnp.all(h20 > 0))
        # one pair leaves the columns exact and the rows a tenth off
        assert float(jnp.max(jnp.abs(h1.sum(0) - 1))) < 1e-5
        assert float(jnp.mean(jnp.max(jnp.abs(h1.sum(1) - 1), 0))) > 0.03
        assert float(hc.row_defect(h20)) < 1e-5 < 0.03 \
            < float(hc.row_defect(h1))


def test_the_draw_keeps_no_mapping_near_its_trivial_value(built):
    x = jax.random.normal(jax.random.key(6), (256, 4 * CFG.hidden_size))
    h_pre, h_post, h_res = COEFF(x, weights(built[0]), **HYPER)
    eye = jnp.eye(4)[:, :, None]
    assert float(jnp.mean(jnp.abs(h_res - eye))) > 0.1     # not the identity
    assert float(jnp.std(h_res, axis=-1).mean()) > 0.01     # input moves it
    assert float(jnp.std(h_pre, axis=-1).mean()) > 0.1
    assert float(jnp.ptp(jnp.mean(h_pre, axis=-1))) > 0.1   # unequal streams
    assert 0.5 < float(jnp.mean(h_post)) < 1.5


def test_the_clamp_bounds_the_logits_of_h_res():
    n, C = 4, 32
    x = jax.random.normal(jax.random.key(0), (8, n * C))
    phi = jnp.zeros((n * C, 24))
    b = jnp.zeros((24,)).at[8].set(80.0).at[9].set(-80.0)
    for clamp in (30.0, 1.0):
        m0 = hc.coefficients(x, (phi, jnp.ones(3), b),
                             **dict(HYPER, iters=1, clamp=clamp))[2]
        # after one pair the first row still carries exp(+-clamp), not
        # exp(+-80): the column sums move the ratio by under e
        ratio = float(jnp.log(m0[0, 0, 0] / m0[0, 1, 0]))
        assert 2 * clamp - 1 < ratio <= 2 * clamp + 1e-3


# ------------------------------------------- the two mixes, line by line


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_hc_pre_and_hc_post_are_the_references_lines(built, dtype):
    n, C = 4, CFG.hidden_size
    x = jax.random.normal(jax.random.key(7), (2, 24, n * C)).astype(dtype)
    y = jax.random.normal(jax.random.key(8), (2, 24, C)).astype(dtype)
    w = weights(built[0], "mlp", 1)
    u, h_post, h_res = hc.hc_pre(x, (w[0].astype(dtype),) + w[1:], **HYPER)
    out = hc.hc_post(x, y, h_post, h_res)
    assert u.shape == (2, 24, C) and out.shape == x.shape
    assert u.dtype == dtype and out.dtype == dtype
    assert h_post.dtype == h_res.dtype == jnp.float32      # whatever x is
    X = x.astype(jnp.float32).reshape(48, n, C)
    w32 = tuple(a.astype(dtype).astype(jnp.float32) for a in w[:1]) + w[1:]
    with jax.default_matmul_precision("highest"):
        ru, rpost, rres = ref._hc_read(X, *w32, **HYPER)
        rout = ref._hc_write(X, y.astype(jnp.float32).reshape(48, C),
                             rpost, rres)
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    assert err(h_post.T, rpost) < 1e-4 and err(
        jnp.moveaxis(h_res, -1, 0), rres) < 1e-4
    assert err(u.astype(jnp.float32).reshape(48, C), ru) < tol
    assert err(out.astype(jnp.float32).reshape(48, n, C), rout) < tol


def test_the_stream_starts_as_copies_and_ends_as_their_sum():
    h = jax.random.normal(jax.random.key(2), (2, 5, 16))
    x = hc.expand(h, 4)
    assert x.shape == (2, 5, 64)
    for i in range(4):
        assert np.array_equal(x[..., 16 * i:16 * (i + 1)], h)
    x = x * jnp.repeat(jnp.arange(1.0, 5.0), 16)
    # the SUM, before the final norm: a scale-free norm after it cannot
    # tell it from the mean, so this is where that fault is held
    assert err(hc.collapse(x, 4), 10.0 * h) < 1e-5
    assert err(hc.collapse(x, 4), 2.5 * h) > 1.0


# -------------------------------------- the whole model = the reference


def test_plain_and_dense_cache_forwards_match_the_reference(built):
    p, ids, pos, want = built
    with jax.default_matmul_precision("highest"):
        got, _ = llama.apply(p, CFG, ids, pos)
        assert got.shape == (1, T, CFG.vocab_size)
        assert err(got[0], want) < 5e-5
        cache = llama.init_kv_cache(CFG, 1, 384, jnp.float32)
        got, cache = llama.apply(p, CFG, ids, pos, cache)
        assert err(got[0], want) < 5e-5
        hidden, _ = llama.apply(p, CFG, ids, pos, return_hidden=True)
        assert hidden.shape == (1, T, CFG.hidden_size)   # collapsed


@pytest.fixture(scope="module")
def prefilled(built):
    """Two 128-token chunks through the latent pool (the second reads the
    first back from it), the trash page full of NaN."""
    p, ids, pos, want = built
    pool = llama.init_paged_kv_cache(CFG, 6, PAGE, jnp.float32)
    pool = jax.tree.map(
        lambda a: jnp.full_like(a, jnp.nan).at[:, 1:].set(0), pool)
    table = jnp.array([[1, 2, 3, 0]])
    outs = []
    with jax.default_matmul_precision("highest"):
        # one traced program for the two chunks
        chunk = jax.jit(lambda p, *a: llama.apply_prefill_paged(
            p, CFG, *a, with_logits=True))
        for c0 in range(0, N_PRE, PAGE):
            logits, pool = chunk(
                p, ids[:, c0:c0 + PAGE], pos[:, c0:c0 + PAGE], pool,
                table, jnp.array([c0 + PAGE]), jnp.int32(c0 // PAGE))
            outs.append(logits[0])
    return pool, table, jnp.concatenate(outs)


def test_chunked_prefill_through_the_latent_pool(built, prefilled):
    _, _, chunk_logits = prefilled
    assert bool(jnp.all(jnp.isfinite(chunk_logits)))
    assert err(chunk_logits, built[3][:N_PRE]) < 5e-5


def test_a_chunk_program_of_several_prompts_equals_each_alone(built):
    p, ids, pos, want = built
    pool = llama.init_paged_kv_cache(CFG, 5, PAGE, jnp.float32)
    tables = jnp.array([[1, 2], [3, 4]])
    toks = jnp.stack([ids[0, :PAGE], ids[0, 100:100 + PAGE]])
    rows_pos = jnp.broadcast_to(jnp.arange(PAGE), (2, PAGE))
    with jax.default_matmul_precision("highest"):
        h, _ = llama.apply_prefill_paged(
            p, CFG, toks, rows_pos, pool, tables, jnp.array([PAGE, PAGE]),
            jnp.array([0, 0]))
        assert h.shape == (2, PAGE, CFG.hidden_size)
        got = llama.unembed(p, CFG, h[:1])[0]
    assert err(got, want[:PAGE]) < 5e-5


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["fallback", "kernel"])
def test_decode_through_the_cache_matches_the_one_full_pass(
        built, prefilled, use_kernel):
    p, ids, pos, want = built
    pool, table, _ = prefilled
    step = jax.jit(lambda pool, tok, at: llama.apply_decode_paged(
        p, CFG, tok, at[None], pool, table, at + 1, table[0, at // PAGE],
        at % PAGE, use_kernel=use_kernel, active=jnp.ones((1,), bool),
        stats=True))
    with jax.default_matmul_precision("highest"):
        for t in range(N_PRE, N_PRE + 3):
            logits, pool, stats = step(pool, ids[:, t:t + 1],
                                       jnp.array([t]))
            assert err(logits[0, 0], want[t]) < 5e-5, t
            assert set(stats) == {"experts_touched", "hc_row_defect"}
            assert 0 < float(stats["hc_row_defect"]) < 1e-5


def test_int8_tree_in_bf16_follows_the_reference(built):
    """The served storage and dtype: within bf16's error of the float32
    reference over the same stored tree, prefill then one decode step."""
    _, ids, pos, _ = built
    p = quantize_params(llama.init_params(CFG, jax.random.key(3),
                                          dtype=jnp.bfloat16), "int8")
    assert p["layers"]["hc_attn_phi"].dtype == jnp.bfloat16   # not int8
    assert p["layers"]["hc_attn_alpha"].dtype == jnp.float32
    assert isinstance(p["layers"]["wq_a"], dict)
    n = 2 * PAGE
    want = ref.forward(p, dataclasses.asdict(CFG), ids[:, :n + 1],
                       list(range(n - 16, n + 1)))
    pool = llama.init_paged_kv_cache(CFG, 4, PAGE, jnp.bfloat16)
    table = jnp.array([[1, 2, 3]])
    h, pool = llama.apply_prefill_paged(
        p, CFG, ids[:, :n], pos[:, :n], pool, table, jnp.array([n]),
        jnp.int32(0))
    got = llama.unembed(p, CFG, h[:, n - 16:n])[0]
    step, _ = llama.apply_decode_paged(
        p, CFG, ids[:, n:n + 1], pos[:, n:n + 1], pool, table,
        jnp.array([n + 1]), jnp.array([3]), jnp.array([0]), use_kernel=True)
    got = jnp.concatenate([got, step[0]]).astype(jnp.float32)
    e = np.asarray(jnp.max(jnp.abs(got - want), -1)
                   / jnp.max(jnp.abs(want), -1))
    assert np.median(e) < 0.04, e


# ------------------------------- faults no configuration key can inject


def _no_factor_2(x, w, **kw):
    h_pre, h_post, h_res = COEFFICIENTS(x, w, **kw)
    return h_pre, 0.5 * h_post, h_res


def _h_pre_without_its_sigmoid(x, w, **kw):
    h_pre, h_post, h_res = COEFFICIENTS(x, w, **kw)
    return jnp.log(h_pre) - jnp.log1p(-h_pre), h_post, h_res


def _h_res_identity(x, w, **kw):
    h_pre, h_post, h_res = COEFFICIENTS(x, w, **kw)
    return h_pre, h_post, jnp.broadcast_to(jnp.eye(4)[:, :, None],
                                           h_res.shape)


def _row_softmax(x, w, **kw):
    """A softmax over each row in place of the iterations: rows exact,
    columns not."""
    h_pre, h_post, h_res = COEFFICIENTS(x, w, **dict(kw, iters=1))
    return h_pre, h_post, h_res / h_res.sum(1, keepdims=True)


def _bf16_coefficients(x, w, **kw):
    h_pre, h_post, h_res = COEFFICIENTS(
        x.astype(jnp.bfloat16).astype(x.dtype), w, **kw)
    down = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    return down(h_pre), down(h_post), down(h_res)


def _first_stream_only(h, n):
    return jnp.concatenate([h] + [jnp.zeros_like(h)] * (n - 1), axis=-1)


COEFFICIENTS = hc.coefficients
FAULTS = {
    "no_factor_2_in_h_post": ("coefficients", _no_factor_2),
    "h_pre_without_its_sigmoid": ("coefficients",
                                  _h_pre_without_its_sigmoid),
    "h_res_identity": ("coefficients", _h_res_identity),
    "row_softmax_for_sinkhorn": ("coefficients", _row_softmax),
    "bf16_coefficients": ("coefficients", _bf16_coefficients),
    "streams_start_as_h_0_0_0": ("expand", _first_stream_only),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_with_no_configuration_key_fails(built, fault, monkeypatch):
    """Each moves the logits of the float32 program off the reference's
    by far more than the sound program's 5e-5 (a mean for the sum at the
    collapse is held before the scale-free final norm:
    ``test_the_stream_starts_as_copies_and_ends_as_their_sum``)."""
    p, ids, pos, want = built
    name, broken = FAULTS[fault]
    monkeypatch.setattr(hc, name, broken)
    with jax.default_matmul_precision("highest"):
        got, _ = llama.apply(p, CFG, ids[:, :64], pos[:, :64])
    scale = float(jnp.max(jnp.abs(want[:64])))
    floor = 1e-3 if fault == "bf16_coefficients" else 0.02
    assert err(got[0], want[:64]) > floor * scale, fault


@pytest.mark.parametrize("field,value", [
    ("hc_sinkhorn_iters", 1), ("hc_res_clamp", 1.0), ("hc_eps", 0.1)])
def test_a_fault_with_a_configuration_key_fails(built, field, value):
    p, ids, pos, want = built
    broken = dataclasses.replace(CFG, **{field: value})
    with jax.default_matmul_precision("highest"):
        got, _ = llama.apply(p, broken, ids[:, :64], pos[:, :64])
    assert err(got[0], want[:64]) > 0.02 * float(jnp.max(jnp.abs(want[:64])))


# ------------------------------------- hc_mult 0 is the model it was


def layer_jaxpr(cfg):
    p = jax.eval_shape(lambda k: llama.init_params(cfg, k, jnp.float32),
                       jax.random.key(0))
    stack = cfg.layer_stacks[-1][0]
    lp = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
          for k, v in p[stack].items()}
    h = jax.ShapeDtypeStruct(
        (2, 16, max(cfg.hc_mult, 1) * cfg.hidden_size), jnp.float32)
    pos = jax.ShapeDtypeStruct((2, 16), jnp.int32)

    def f(h, lp, pos):
        lp = dict(lp)
        if "router" in lp and cfg.moe_impl == "dropless":
            lp["layer_index"] = jnp.int32(0)
            lp = {**lp, **{k: v[None] for k, v in lp.items()
                           if k in ("w_gate", "w_up", "w_down")}}
        return llama.decoder_layer(h, lp, cfg, pos, llama._inv_freq(cfg),
                                   None)[0]
    return str(jax.make_jaxpr(f)(h, lp, pos))


# sha256 of ``decoder_layer``'s jaxpr on PR 42's PARENT (commit 98399f1),
# computed by this function there. Re-pin only on purpose (a new JAX
# re-words the text: re-pin from one commit).
LAYER_PINS = {
    "llama-tiny":
        "c0c147792a039d2973cf6dc6941b7920f34ead9e90977ff35085ea931585b687",
    "latent-experts":
        "4926cdbc2f982e3bf397f9243242bf3952464aaec54c623e0b8b0aedd54f60c5",
}


@pytest.mark.parametrize("name", sorted(LAYER_PINS))
def test_hc_mult_0_leaves_decoder_layers_jaxpr_unchanged(name):
    cfg = {"llama-tiny": LLAMA_TINY, "latent-experts": PLAIN}[name]
    text = layer_jaxpr(cfg)
    assert hashlib.sha256(text.encode()).hexdigest() == LAYER_PINS[name]
    assert text != layer_jaxpr(dataclasses.replace(cfg, hc_mult=4))


@pytest.mark.parametrize("cfg", [PLAIN, LLAMA_TINY],
                         ids=["latent-experts", "llama-tiny"])
def test_the_streams_add_leaves_and_move_no_other(cfg):
    key = jax.random.key(11)
    plain = llama.init_params(cfg, key, jnp.bfloat16)
    wide = llama.init_params(dataclasses.replace(cfg, hc_mult=4), key,
                             jnp.bfloat16)
    added = set()
    for name, _, n in cfg.layer_stacks:
        for leaf, a in plain[name].items():
            assert np.array_equal(a, wide[name][leaf]), (name, leaf)
        new = set(wide[name]) - set(plain[name])
        added |= new
        m, nC = 24, 4 * cfg.hidden_size
        for part in ("attn", "mlp"):
            assert wide[name][f"hc_{part}_phi"].shape == (n, nC, m)
            assert wide[name][f"hc_{part}_phi"].dtype == jnp.bfloat16
            assert wide[name][f"hc_{part}_alpha"].shape == (n, 3)
            assert wide[name][f"hc_{part}_b"].dtype == jnp.float32
    assert added == {f"hc_{part}_{leaf}" for part in ("attn", "mlp")
                     for leaf in ("phi", "alpha", "b")}
    for top in ("embed", "final_norm", "lm_head"):
        if top in plain:
            assert np.array_equal(plain[top], wide[top])


@pytest.mark.parametrize("hc_mult", [0, 4])
def test_thin_experts_is_a_draw_of_its_own(hc_mult):
    """``weight_init`` "unit_stream_thin_experts" is "unit_stream" with
    the routed experts' ``w_down`` at a fifth (``init_params`` (4) says
    why), with or without streams: nothing of the draw follows
    ``hc_mult``."""
    key = jax.random.key(11)
    cfg = dataclasses.replace(PLAIN, hc_mult=hc_mult)
    full = llama.init_params(cfg, key, jnp.bfloat16)
    thin = llama.init_params(dataclasses.replace(
        cfg, weight_init="unit_stream_thin_experts"), key, jnp.bfloat16)
    assert jax.tree.structure(full) == jax.tree.structure(thin)
    for name, _, _ in cfg.layer_stacks:
        for leaf, a in full[name].items():
            if leaf == "w_down" and "router" in full[name]:
                assert np.allclose(np.asarray(a, np.float32) / 5,
                                   np.asarray(thin[name][leaf], np.float32),
                                   rtol=0.01, atol=1e-6)
            else:       # the shared expert's ``ws_down`` among them
                assert np.array_equal(a, thin[name][leaf]), (name, leaf)
    for top in ("embed", "final_norm", "lm_head"):
        assert np.array_equal(full[top], thin[top])


def test_registry_entry_is_the_published_model():
    xing = MODEL_REGISTRY["xing4.0-29b-a4b"]
    assert (xing.num_layers, xing.num_dense_layers, xing.num_experts,
            xing.held_experts, xing.num_experts_per_tok) == (40, 2, 64, 64, 4)
    assert (xing.hidden_size, xing.intermediate_size, xing.expert_width,
            xing.vocab_size, xing.num_heads) == (3584, 9216, 1024, 131072, 32)
    assert (xing.q_lora_rank, xing.kv_lora_rank, xing.qk_nope_head_dim,
            xing.qk_rope_head_dim, xing.v_head_dim) == (768, 512, 128, 64,
                                                        128)
    assert (xing.hc_mult, xing.hc_sinkhorn_iters, xing.hc_eps,
            xing.hc_res_clamp) == (4, 20, 1e-6, 30.0)
    assert xing.score_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64.0) + 1.0) ** 2)


def test_configuration_refuses_what_the_streams_cannot_mean():
    with pytest.raises(ValueError, match="hc_mult"):
        LlamaConfig(hc_mult=1)
    with pytest.raises(ValueError, match="hc_sinkhorn_iters"):
        dataclasses.replace(CFG, hc_sinkhorn_iters=0)
    with pytest.raises(ValueError, match="block_input"):
        dataclasses.replace(CFG, router_input="block_input")


# ------------------------------------------------------------ the kernel


@pytest.mark.parametrize("T,iters", [(1, 3), (16, 20), (512, 3), (1536, 3),
                                     (2048, 3)])
def test_sinkhorn_kernel_matches_the_jnp_chain(T, iters):
    """Interpret mode, at every token count the engine's programs have:
    a decode round of one row and of sixteen, a chunk, the check's
    one-shot prompt, a chunk program of four prompts. The twenty pairs
    once (the interpreter takes 40 s to unroll them), three elsewhere:
    the pairs are one loop body."""
    logits = 1.5 * jnp.eye(4).reshape(16, 1) + jax.random.normal(
        jax.random.key(T), (16, T))
    logits = logits.at[3, 0].set(80.0).at[7, 0].set(-80.0)   # clamped
    args = dict(n=4, iters=iters, eps=1e-6, clamp=30.0)
    got = hc.sinkhorn_kernel(logits, interpret=True, **args)
    want = hc.sinkhorn(jnp.exp(jnp.clip(logits, -30.0, 30.0)).reshape(
        4, 4, T), iters, 1e-6)
    assert got.shape == (4, 4, T) and got.dtype == jnp.float32
    assert err(got, want) < 1e-6
    assert float(jnp.max(jnp.abs(got.sum(0) - 1))) < 1e-5
