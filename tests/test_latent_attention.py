"""Latent attention on the CPU at tiny sizes, seeded weights: the program's
absorbed and expanded forms against each other and against the plain
reference (benchmarks/references/deepseek_v3.py: expanded only, float32,
no cache), through every forward that has a pool; YaRN's frequencies and
score multiplier against a direct evaluation; the latent decode kernel in
interpret mode against its jnp oracle with a NaN-filled trash page; the
cache object's writes and reads, both implementations; the engine end to
end; and one test for every path that refuses a latent pool by name."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import deepseek_v3 as ref
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import (MODEL_REGISTRY,
                                                     LlamaConfig)
from generativeaiexamples_tpu.models.kv_cache import (HeadKV, LatentKV,
                                                      kv_cache_of)
from generativeaiexamples_tpu.ops import latent_attention as la
from generativeaiexamples_tpu.ops.quant import quantize_params
from generativeaiexamples_tpu.ops.rope import (rope_frequencies,
                                               yarn_frequencies)

PAGE = 128
CFG = LlamaConfig(
    vocab_size=512, hidden_size=128, intermediate_size=256,
    moe_intermediate_size=64, num_layers=3, num_dense_layers=1, num_heads=4,
    num_kv_heads=1, head_dim=48, max_position_embeddings=4096,
    rope_theta=50000.0, rms_norm_eps=1e-6, num_experts=16,
    num_experts_per_tok=4, num_shared_experts=1, moe_impl="dropless",
    router_score_func="sigmoid", router_norm_topk=True, router_scale=2.827,
    router_bias="selection", kv_lora_rank=128, q_lora_rank=64,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    rope_interleave=True, rope_scaling_type="yarn", rope_scaling_factor=32.0,
    rope_original_max=64, rope_beta_fast=1.0, rope_beta_slow=1.0,
    rope_mscale_all_dim=1.0, weight_init="unit_stream")
T, N_PRE = 300, 256


@pytest.fixture(scope="module")
def built():
    """Float32 weights, ids, and the reference's ONE full pass."""
    p = llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (1, T), 0, CFG.vocab_size)
    want = ref.forward(p, dataclasses.asdict(CFG), ids, list(range(T)))
    return p, ids, jnp.arange(T)[None], want


def err(got, want):
    return float(jnp.max(jnp.abs(got - want)))


# ------------------------------------------------------------------ YaRN


def test_yarn_frequencies_against_a_direct_evaluation():
    dim, theta, factor, orig = 64, 50000.0, 32.0, 4096
    got = np.asarray(yarn_frequencies(dim, theta, factor, orig, 1.0, 1.0))
    # index i turns orig / (2 pi theta^(2i/dim)) times in the original
    # context: more than one turn is kept, fewer is divided by the factor
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        turns = orig * plain / (2 * math.pi)
        want = plain if turns > 1.0 else plain / factor
        assert got[i] == pytest.approx(want, rel=1e-5), i
    assert sum(g < theta ** (-2.0 * i / dim) * 0.5
               for i, g in enumerate(got)) == 12        # indices 20..31
    # the published defaults (32, 1) blend in between
    wide = np.asarray(yarn_frequencies(dim, theta, factor, orig, 32.0, 1.0))
    plain = np.asarray(rope_frequencies(dim, theta))
    ratio = wide / plain
    assert ratio[0] == pytest.approx(1.0) and ratio[-1] == pytest.approx(
        1 / factor)
    assert np.all(np.diff(ratio) <= 1e-7) and 0 < np.sum(
        (ratio < 0.999) & (ratio > 1 / factor * 1.001)) < 16
    assert np.allclose(ref.yarn_inv_freq(dim, theta, factor, orig, 1.0, 1.0),
                       got, rtol=1e-6)


def test_score_multiplier_is_m_squared():
    kimi = MODEL_REGISTRY["kimi-k2-instruct"]
    m = 0.1 * 1.0 * math.log(32.0) + 1.0
    assert m == pytest.approx(1.3466, abs=1e-4)
    assert kimi.score_scale == pytest.approx(192 ** -0.5 * m * m)
    assert dataclasses.replace(kimi, rope_mscale_all_dim=0.0).score_scale \
        == pytest.approx(192 ** -0.5)
    assert LlamaConfig().score_scale == pytest.approx(128 ** -0.5)


def test_registry_entry_is_the_published_model():
    kimi = MODEL_REGISTRY["kimi-k2-instruct"]
    assert (kimi.num_layers, kimi.num_dense_layers, kimi.num_experts,
            kimi.held_experts, kimi.num_experts_per_tok) == (61, 1, 384,
                                                             384, 8)
    assert (kimi.hidden_size, kimi.intermediate_size, kimi.expert_width,
            kimi.vocab_size, kimi.num_heads) == (7168, 18432, 2048, 163840,
                                                 64)
    assert (kimi.q_lora_rank, kimi.kv_lora_rank, kimi.qk_nope_head_dim,
            kimi.qk_rope_head_dim, kimi.v_head_dim) == (1536, 512, 128, 64,
                                                        128)
    assert isinstance(kv_cache_of(kimi), LatentKV)
    assert isinstance(kv_cache_of(MODEL_REGISTRY["trinity-mini"]), HeadKV)
    # a token's cache, a layer: 576 values for all 64 heads
    assert kv_cache_of(kimi).token_bytes(2) == 1152


# ----------------------------------------- absorbed = expanded = reference


def test_plain_and_dense_cache_forwards_match_the_reference(built):
    p, ids, pos, want = built
    with jax.default_matmul_precision("highest"):
        got, _ = llama.apply(p, CFG, ids, pos)
        assert err(got[0], want) < 5e-5
        cache = llama.init_kv_cache(CFG, 1, 384, jnp.float32)
        assert set(cache) == {"c", "r"}
        got, cache = llama.apply(p, CFG, ids, pos, cache)
        assert err(got[0], want) < 5e-5 and set(cache) == {"c", "r"}


@pytest.fixture(scope="module")
def prefilled(built):
    """Two 128-token chunks through the latent pool (the second reads the
    first back from it, expanded), the trash page full of NaN."""
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


def test_chunked_prefill_reads_its_prefix_back_from_the_pool(built,
                                                             prefilled):
    _, _, chunk_logits = prefilled
    assert bool(jnp.all(jnp.isfinite(chunk_logits)))
    assert err(chunk_logits, built[3][:N_PRE]) < 5e-5


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["absorbed_jnp", "absorbed_kernel"])
def test_decode_through_the_pool_matches_the_one_full_pass(
        built, prefilled, use_kernel):
    """Logits, not tokens: expanded prefill, then absorbed decode over the
    rows it left, against the reference's expanded pass without a
    cache."""
    p, ids, pos, want = built
    pool, table, _ = prefilled
    with jax.default_matmul_precision("highest"):
        # one traced program for the five steps
        step = jax.jit(lambda p, *a: llama.apply_decode_paged(
            p, CFG, *a, use_kernel=use_kernel))
        for t in range(N_PRE, N_PRE + 5):
            logits, pool = step(
                p, ids[:, t:t + 1], pos[:, t:t + 1], pool, table,
                jnp.array([t + 1]), table[0, t // PAGE][None],
                jnp.array([t % PAGE]))
            assert bool(jnp.all(jnp.isfinite(logits)))
            assert err(logits[0, 0], want[t]) < 5e-5, t


def test_verify_forward_over_the_latent_pool(built, prefilled):
    p, ids, pos, want = built
    pool, table, _ = prefilled
    with jax.default_matmul_precision("highest"):
        logits, pool = llama.apply_verify_paged(
            p, CFG, ids[:, N_PRE:N_PRE + 3], pos[:, N_PRE:N_PRE + 3], pool,
            table, jnp.array([N_PRE + 3]), jnp.array([[3, 3, 3]]),
            jnp.array([[0, 1, 2]]))
    assert err(logits[0], want[N_PRE:N_PRE + 3]) < 5e-5
    # ... and it left its three rows where the next step reads them
    c, r = kv_cache_of(CFG).window(pool, 0, table)
    assert bool(jnp.all(jnp.isfinite(c[0, :N_PRE + 3])))
    assert float(jnp.abs(c[0, N_PRE + 2]).sum()) > 0
    assert float(jnp.abs(r[0, N_PRE + 2]).sum()) > 0


def test_int8_tree_in_bf16_follows_the_reference(built):
    """The served storage (five attention matrices int8) through the
    paged path in bf16: within bf16's error of the float32 reference
    over the same stored tree."""
    p32, ids, pos, _ = built
    p = quantize_params(llama.init_params(CFG, jax.random.key(3),
                                          dtype=jnp.bfloat16), "int8")
    assert all(isinstance(p["layers"][n], dict)
               for n in ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo"))
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
        jnp.array([n + 1]), jnp.array([3]), jnp.array([0]),
        use_kernel=True)
    got = jnp.concatenate([got, step[0]]).astype(jnp.float32)
    e = np.asarray(jnp.max(jnp.abs(got - want), -1)
                   / jnp.max(jnp.abs(want), -1))
    assert np.median(e) < 0.04, e


# ------------------------------------------------------------ the kernel


@pytest.mark.parametrize("block_pages", [1, 2, 4])
def test_kernel_matches_its_oracle_with_a_nan_trash_page(block_pages):
    """Interpret mode, one group of eight slots: lengths of one row, at a
    page boundary, one to either side of it, past several blocks, and an
    idle slot (length 0, parked on the trash page). Every pool row no
    query may read is NaN: the trash page, and each slot's rows past its
    length."""
    B, H, R, rope, L, W, li = 8, 4, 128, 16, 2, 5, 1
    ks = jax.random.split(jax.random.key(block_pages), 6)
    f = jnp.float32
    q_c = jax.random.normal(ks[0], (B, H, R), f)
    q_r = jax.random.normal(ks[1], (B, H, rope), f)
    cur_c = jax.random.normal(ks[4], (B, R), f)
    cur_r = jax.random.normal(ks[5], (B, rope), f)
    lengths = jnp.array([0, 1, 127, 128, 129, 300, 512, 639])
    N = 1 + B * W
    table = 1 + jnp.arange(B * W, dtype=jnp.int32).reshape(B, W)
    # logical position of every pool row of a slot's pages; NaN past its
    # length and all over the trash page
    pos = jnp.arange(W * PAGE).reshape(W, PAGE)
    ok = jnp.concatenate([jnp.zeros((1, PAGE), bool)] + [
        pos < lengths[b] for b in range(B)])                    # (N, page)
    pool_c = jnp.where(ok[None, :, None, :, None], jax.random.normal(
        ks[2], (L, N, 1, PAGE, R), f), jnp.nan)
    pool_r = jnp.where(ok[None, :, None, None, :], jax.random.normal(
        ks[3], (L, N, 1, rope, PAGE), f), jnp.nan)
    table = table.at[0].set(0)
    wp = jnp.where(lengths > 0,
                   table[jnp.arange(B), jnp.minimum(lengths // PAGE, W - 1)],
                   0)
    off = lengths % PAGE
    got, npc, npr = la.latent_attention_decode(
        q_c, q_r, pool_c, pool_r, table, lengths, cur_c, cur_r, wp, off,
        jnp.array([li]), scale=0.11, interpret=True,
        block_pages=block_pages)
    want = la.latent_attention_decode_reference(
        q_c, q_r, pool_c[li], pool_r[li], table, lengths, cur_c, cur_r, 0.11)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert err(got, want) < 2e-5
    # the idle slot attends its own token alone
    assert np.allclose(got[0], np.broadcast_to(cur_c[0], (H, R)), atol=1e-6)
    for b in range(B):
        # the append: the new row where it belongs, the rows before it on
        # its page as they were
        p_, o_ = int(wp[b]), int(off[b])
        assert np.allclose(npc[li, p_, 0, o_], cur_c[b])
        assert np.allclose(npr[li, p_, 0, :, o_], cur_r[b])
        if p_:
            assert np.array_equal(npc[li, p_, 0, :o_], pool_c[li, p_, 0, :o_])
            assert np.array_equal(npr[li, p_, 0, :, :o_],
                                  pool_r[li, p_, 0, :, :o_])
    # an idle slot leaves no NaN of its own on the trash page, and the
    # other layer is untouched
    assert bool(jnp.all(jnp.isfinite(npc[li, 0, 0, :8])))
    assert bool(jnp.all(jnp.isfinite(npr[li, 0, 0])))
    assert np.array_equal(npc[0], pool_c[0], equal_nan=True)
    assert np.array_equal(npr[0], pool_r[0], equal_nan=True)


def test_kernel_serves_a_group_of_slots_at_once():
    B, H, R, rope, W = 16, 4, 128, 16, 3
    ks = jax.random.split(jax.random.key(9), 6)
    f = jnp.float32
    q_c, q_r = (jax.random.normal(ks[0], (B, H, R), f),
                jax.random.normal(ks[1], (B, H, rope), f))
    N = 1 + B * W
    pool_c = jax.random.normal(ks[2], (1, N, 1, PAGE, R), f)
    pool_r = jax.random.normal(ks[3], (1, N, 1, rope, PAGE), f)
    cur_c, cur_r = (jax.random.normal(ks[4], (B, R), f),
                    jax.random.normal(ks[5], (B, rope), f))
    lengths = jnp.asarray(np.random.default_rng(0).integers(
        0, W * PAGE - 1, B), jnp.int32).at[3].set(0)
    table = 1 + jnp.arange(B * W, dtype=jnp.int32).reshape(B, W)
    wp = jnp.where(lengths > 0, table[jnp.arange(B), lengths // PAGE], 0)
    got, npc, npr = la.latent_attention_decode(
        q_c, q_r, pool_c, pool_r, table, lengths, cur_c, cur_r, wp,
        lengths % PAGE, jnp.array([0]), scale=0.2, interpret=True)
    want = la.latent_attention_decode_reference(
        q_c, q_r, pool_c[0], pool_r[0], table, lengths, cur_c, cur_r, 0.2)
    assert err(got, want) < 2e-5
    for b in range(B):
        if int(lengths[b]):
            assert np.allclose(npc[0, int(wp[b]), 0, int(lengths[b]) % PAGE],
                               cur_c[b])


def test_kernel_geometry():
    assert la.kernel_supported(128, 512, 64)
    assert not la.kernel_supported(64, 512, 64)
    assert not la.kernel_supported(128, 576, 64)
    kimi = MODEL_REGISTRY["kimi-k2-instruct"]
    assert kv_cache_of(kimi).kernel_supported(128)


# ------------------------------------------------------- the cache object


TINY_HEAD = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        head_dim=8)


@pytest.mark.parametrize("cfg", [CFG, TINY_HEAD], ids=["latent", "per_head"])
def test_rows_and_whole_pages_written_are_what_is_read(cfg):
    kvc = kv_cache_of(cfg)
    L, page = cfg.num_layers, 16
    pool = kvc.init_pool(5, page, jnp.float32)
    assert set(pool) == set(kvc.leaves) and kvc.page_size(pool) == page
    if cfg.kv_lora_rank:
        shapes = [(cfg.kv_lora_rank,), (cfg.qk_rope_head_dim,)]
    else:
        shapes = [(cfg.num_kv_heads, cfg.head_dim)] * 2
    ks = jax.random.split(jax.random.key(0), 4)
    # a chunk of two whole pages to pages 3 and 1
    k_new, v_new = (jax.random.normal(k, (L, 2 * page) + s)
                    for k, s in zip(ks, shapes))
    pool = kvc.write(pool, k_new, v_new, jnp.array([3, 1]))
    # three rows, two slots, to pages 2 / 4 at offsets
    pages = jnp.array([[2, 2, 2], [4, 4, 4]])
    offs = jnp.array([[5, 6, 7], [0, 1, 15]])
    k_row, v_row = (jax.random.normal(k, (L, 2, 3) + s)
                    for k, s in zip(ks[2:], shapes))
    pool = kvc.write(pool, k_row, v_row, pages, offs)
    table = jnp.array([[3, 1, 2], [4, 0, 0]])
    for layer in range(L):
        if cfg.kv_lora_rank:
            got = kvc.window(pool, layer, table)
        else:
            got = [kvc.window(pool, n, layer, table, jnp.float32)
                   for n in kvc.leaves]
        for g, chunk, row in zip(got, (k_new, v_new), (k_row, v_row)):
            assert np.array_equal(g[0, :2 * page], chunk[layer])
            assert np.array_equal(g[0, 2 * page + 5:2 * page + 8],
                                  row[layer, 0])
            assert np.array_equal(g[1, :2], row[layer, 1, :2])
            assert np.array_equal(g[1, 15], row[layer, 1, 2])
    # what a token costs, as the engine sizes the pool
    want = 2 * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) \
        if cfg.kv_lora_rank else 2 * 2 * cfg.num_kv_heads * cfg.head_dim
    assert kvc.token_bytes(2) == want
    leaves = jax.eval_shape(lambda: kvc.init_pool(5, page, jnp.bfloat16))
    assert sum(int(np.prod(x.shape)) * 2 for x in leaves.values()) \
        == 5 * page * L * want


def test_insert_pages_is_the_whole_page_write():
    kvc = kv_cache_of(CFG)
    L, page, S = CFG.num_layers, 16, 32
    dense = kvc.init_dense(1, S, jnp.float32)
    dense = {n: jax.random.normal(jax.random.key(i), a.shape)
             for i, (n, a) in enumerate(dense.items())}
    pool = kvc.insert_pages(kvc.init_pool(4, page, jnp.float32), dense["c"],
                            dense["r"], jnp.array([2, 3]))
    c, r = kvc.window(pool, 1, jnp.array([[2, 3]]))
    assert np.array_equal(c[0], dense["c"][1, 0])
    assert np.array_equal(r[0], dense["r"][1, 0])


# -------------------------------------------------------------- refusals


def test_an_int8_latent_pool_is_refused():
    with pytest.raises(NotImplementedError, match="int8 KV pool"):
        llama.init_paged_kv_cache(CFG, 4, PAGE, quantized=True)


def test_ring_attention_and_a_pipeline_stage_refuse_latent_attention(built):
    p, ids, pos, _ = built
    plain = dataclasses.replace(
        CFG, num_experts=0, num_dense_layers=0, num_shared_experts=0,
        moe_intermediate_size=0, router_score_func="softmax",
        router_bias="", router_scale=1.0, moe_impl="sparse")
    with pytest.raises(NotImplementedError, match="latent attention"):
        llama._refuse_kinds(plain, "apply_sp")
    half = jax.tree.map(lambda a: a[:1], p["layers"])
    with pytest.raises(NotImplementedError, match="latent attention"):
        llama.run_layers(half, plain, jnp.zeros((1, 4, 128)), pos[:, :4])


def test_lora_refuses_latent_attention(built):
    from generativeaiexamples_tpu.lora import init_lora
    with pytest.raises(NotImplementedError, match="latent-attention"):
        init_lora(CFG, built[0], jax.random.key(0))


def test_configuration_states_its_latent_attention_whole():
    with pytest.raises(ValueError, match="needs qk_nope_head_dim"):
        LlamaConfig(kv_lora_rank=64, num_kv_heads=1)
    with pytest.raises(ValueError, match="num_kv_heads is 1"):
        dataclasses.replace(CFG, num_kv_heads=4)
    with pytest.raises(ValueError, match="no window"):
        dataclasses.replace(CFG, sliding_window=64, window_layers=(1,))
    with pytest.raises(ValueError, match="rope_scaling_type"):
        LlamaConfig(rope_scaling_type="ntk")


# ---------------------------------------------------------------- import


def test_published_names_round_trip_through_import_hf(built):
    """The tree written out under the published DeepseekV3 names (``kv_b``
    joined as published, matrices (out, in)) loads back to the same tree;
    a share keeps the experts it holds."""
    from generativeaiexamples_tpu.models.import_hf import (
        params_from_named_tensors)
    cfg = dataclasses.replace(CFG, experts_held=4, experts_first=8)
    p = llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    H, nope, vd, R = 4, 32, 32, 128
    named = {"model.embed_tokens.weight": p["embed"],
             "model.norm.weight": p["final_norm"],
             "lm_head.weight": p["lm_head"].T}
    plain = {"attn_norm": "input_layernorm.weight",
             "mlp_norm": "post_attention_layernorm.weight",
             "q_a_norm": "self_attn.q_a_layernorm.weight",
             "kv_a_norm": "self_attn.kv_a_layernorm.weight",
             "router_bias": "mlp.gate.e_score_correction_bias"}
    turned = {"wq_a": "self_attn.q_a_proj.weight",
              "wq_b": "self_attn.q_b_proj.weight",
              "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
              "wo": "self_attn.o_proj.weight", "router": "mlp.gate.weight",
              "ws_gate": "mlp.shared_experts.gate_proj.weight",
              "ws_up": "mlp.shared_experts.up_proj.weight",
              "ws_down": "mlp.shared_experts.down_proj.weight"}
    for stack, first, n in CFG.layer_stacks:
        tree = p[stack]
        for i in range(n):
            pre = f"model.layers.{first + i}."
            for name, hf in plain.items():
                if name in tree:
                    named[pre + hf] = tree[name][i]
            for name, hf in turned.items():
                if name in tree:
                    named[pre + hf] = tree[name][i].T
            kv_b = jnp.concatenate(
                [tree["wk_b"][i].reshape(R, H, nope),
                 tree["wv_b"][i].reshape(R, H, vd)], axis=-1)
            named[pre + "self_attn.kv_b_proj.weight"] = \
                kv_b.reshape(R, H * (nope + vd)).T
            for w in ("gate", "up", "down"):
                if "router" not in tree:
                    named[pre + f"mlp.{w}_proj.weight"] = tree["w_" + w][i].T
                    continue
                for e in range(CFG.num_experts):
                    named[pre + f"mlp.experts.{e}.{w}_proj.weight"] = \
                        tree["w_" + w][i, e].T
    named = {k: np.asarray(v) for k, v in named.items()}
    back = params_from_named_tensors(iter(named.items()), CFG, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(p)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(p)):
        assert np.array_equal(a, b)
    share = params_from_named_tensors(iter(named.items()), cfg, jnp.float32)
    for w in ("w_gate", "w_up", "w_down"):
        assert np.array_equal(share["layers"][w], p["layers"][w][:, 8:12])
    assert share["layers"]["router"].shape[-1] == CFG.num_experts
