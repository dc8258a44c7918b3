"""Learned sparse attention over the latent cache on the CPU at tiny
sizes, seeded weights: the exact top-k mask against ``lax.top_k`` (ties,
short rows); the indexer's scores and the selected SETS against the plain
reference (benchmarks/references/glm_dsa.py: float32, ``lax.top_k`` over
the whole score matrix, no cache); shared layers attending the set of
the full layer below, across the stack boundary; selection off =
``LatentKV`` to the bit; every forward that has a pool — chunks that read
latent rows AND index keys back, a chunk program of four prompts, decode
and verify — against the reference's one full pass, with a NaN-filled
trash page; the chunk kernel with its mask operand against the jnp form;
the latent decode kernel with its mask operand (interpreted) against
``absorbed_masked`` over the same pool and mask, the decode step over it
against the gathered form, the verify forward and the full pass, and the
three rows it appends; the cache object's three leaves; the published
names through import_hf; and what is refused."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import glm_dsa as ref
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import (MODEL_REGISTRY,
                                                     LlamaConfig)
from generativeaiexamples_tpu.models.kv_cache import (LatentKV,
                                                      SparseLatentKV,
                                                      kv_cache_of)
from generativeaiexamples_tpu.ops import chunk_attention as ca
from generativeaiexamples_tpu.ops import sparse_index as si
from generativeaiexamples_tpu.ops.quant import quantize_params

# every forward ONE compiled program a shape (op by op, the file is
# thousands of small compilations)
apply_j = jax.jit(llama.apply, static_argnums=(1,))
prefill_j = jax.jit(llama.apply_prefill_paged, static_argnums=(1,),
                    static_argnames=("with_logits", "use_kernel"))
decode_j = jax.jit(llama.apply_decode_paged, static_argnums=(1,),
                   static_argnames=("use_kernel",))
verify_j = jax.jit(llama.apply_verify_paged, static_argnums=(1,))

PAGE = 128
TOPK = 160
# one dense layer (full), then five expert layers: shared, shared, full,
# shared, shared — the first expert layers attend the dense layer's set.
# A head's keys are 96 + 32 = 128 wide: whole lanes only WITH the rotary
# part, as the published 192 + 64
CFG = LlamaConfig(
    vocab_size=512, hidden_size=128, intermediate_size=256,
    moe_intermediate_size=64, num_layers=6, num_dense_layers=1, num_heads=4,
    num_kv_heads=1, head_dim=128, max_position_embeddings=4096,
    rope_theta=8_000_000.0, rms_norm_eps=1e-5, num_experts=16,
    num_experts_per_tok=4, num_shared_experts=1, moe_impl="dropless",
    router_score_func="sigmoid", router_norm_topk=True, router_scale=2.5,
    router_bias="selection", kv_lora_rank=128, q_lora_rank=64,
    qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=48,
    rope_interleave=True, index_topk=TOPK, index_n_heads=4,
    index_head_dim=64, index_layers=(1, 0, 0, 1, 0, 0),
    index_rope_interleave=True, weight_init="unit_stream")
T, N_PRE = 400, 384


def model_of(cfg):
    return dict(dataclasses.asdict(cfg), index_layers=list(cfg.index_layers))


def build(cfg, dtype=jnp.float32):
    return llama.init_params(cfg, jax.random.key(3), dtype=dtype)


@pytest.fixture(scope="module")
def built():
    """Float32 weights, ids, and the reference's ONE full pass."""
    p = build(CFG)
    ids = jax.random.randint(jax.random.key(1), (1, T), 0, CFG.vocab_size)
    want = ref.forward(p, model_of(CFG), ids, list(range(T)))
    return p, ids, jnp.arange(T)[None], want


def err(got, want):
    return float(jnp.max(jnp.abs(got - want)))


# --------------------------------------------------------- the exact top-k


def top_k_mask(scores, valid, k):
    """``lax.top_k``'s set, as a mask (-0.0 and 0.0 one value, as the
    reference has them)."""
    masked = jnp.where(valid, scores + 0.0, -jnp.inf)
    _, idx = jax.lax.top_k(masked, min(k, scores.shape[-1]))
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, idx].set(True) & valid


@pytest.mark.parametrize("case", ["random", "ties", "short_rows", "all_equal",
                                  "k_covers_the_row", "negative_zero"])
def test_topk_keep_is_lax_top_ks_set(case):
    n, k = 257, 40
    key = jax.random.key(7)
    scores = jax.random.normal(key, (9, n))
    valid = jnp.arange(n)[None, :] <= jnp.arange(9)[:, None] * 31 + 5
    if case == "ties":          # a handful of distinct values: many ties
        scores = jnp.round(scores * 2) / 2
    elif case == "short_rows":
        valid = jnp.arange(n)[None, :] < jnp.arange(9)[:, None] * 7
    elif case == "all_equal":
        scores = jnp.zeros((9, n))
    elif case == "k_covers_the_row":
        k = n
    elif case == "negative_zero":
        scores = jnp.where(jnp.arange(n) % 2 == 0, 0.0, -0.0) \
            * jnp.ones((9, 1))
    got = si.topk_keep(scores, valid, k)
    want = top_k_mask(scores, valid, k)
    assert np.array_equal(got, want)
    assert np.array_equal(got.sum(-1), np.minimum(valid.sum(-1), k))
    # what is not valid may hold anything
    junk = jnp.where(valid, scores, jnp.nan)
    assert np.array_equal(si.topk_keep(junk, valid, k), want)


def test_index_scores_both_forms_are_the_equation():
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (2, 5, 3, 16))
    w = jax.random.normal(ks[1], (2, 5, 3))
    keys = jax.random.normal(ks[2], (2, 33, 16))
    want = np.einsum("bsh,bsht->bst", np.asarray(w), np.maximum(
        np.einsum("bshd,btd->bsht", np.asarray(q), np.asarray(keys)), 0))
    with jax.default_matmul_precision("highest"):
        at_once = si.index_scores(q, w, keys)
        try:
            si._SCORES_AT_ONCE, old = 0, si._SCORES_AT_ONCE
            a_head_at_a_time = si.index_scores(q, w, keys)
        finally:
            si._SCORES_AT_ONCE = old
    assert np.allclose(at_once, want, atol=1e-4)
    assert np.allclose(a_head_at_a_time, want, atol=1e-4)


# ----------------------------------------- scores and sets = the reference


def layer0(p, ids, pos):
    """The first (dense, full) layer's projections from both sides."""
    w = {n: ref._f32(p["dense_layers"][n], 0) for n in
         ref.ATTENTION + ref.INDEXER}
    x = ref._f32(p["embed"], rows=ids[0])
    inv = llama._inv_freq(CFG)
    a, c_q, _ = ref._latents(x, w, inv, heads=4, nope=96, rope=32, eps=1e-5,
                             interleave=True)
    lp = {n: v[0] for n, v in p["dense_layers"].items()
          if not n.startswith("index_")}
    lp.update({n: v for n, v in p["dense_layers"].items()
               if n.startswith("index_")})
    lp.update(index=jnp.int32(0), index_first=0,
              selection=jnp.zeros((1, T, T), bool))
    xn = llama.block_norm(x[None], lp, "attn_norm", CFG)
    *_, cq = llama._latent_qkv(xn, lp, CFG, pos, inv, with_latent=True)
    index = llama._index_project(xn, cq, lp, CFG, pos, inv)
    return a, c_q, w, inv, index


def test_selected_sets_are_the_references(built):
    """Contexts below, at and above ``index_topk``: row t of the mask."""
    p, ids, pos, _ = built
    with jax.default_matmul_precision("highest"):
        a, c_q, w, inv, index = layer0(p, ids, pos)
        want = ref._select(a, c_q, w, inv, heads=4, dim=64, rope=32,
                           interleave=True, top_k=TOPK)
        valid = jnp.tril(jnp.ones((T, T), bool))[None]
        got = kv_cache_of(CFG).select(index, lambda: index["k"], valid)[0]
    assert np.array_equal(got, want)
    sizes = np.asarray(got.sum(-1))
    assert np.array_equal(sizes, np.minimum(np.arange(T) + 1, TOPK))
    # no prefix, suffix or window of the context: a late row keeps old
    # tokens and drops recent ones
    late = np.asarray(got[-1])
    assert late[:T - TOPK].sum() > 20 and (~late[T - TOPK:T]).sum() > 20


def test_index_scores_are_the_references(built):
    p, ids, pos, _ = built
    with jax.default_matmul_precision("highest"):
        a, c_q, w, inv, index = layer0(p, ids, pos)
        got = si.index_scores(index["q"], index["w"], index["k"])[0]
        q = (c_q @ w["index_wq"]).reshape(T, 4, 64)
        k = a @ w["index_wk"]
        k = (k - k.mean(-1, keepdims=True)) / jnp.sqrt(
            k.var(-1, keepdims=True) + 1e-6)
        k = (k * w["index_k_norm"] + w["index_k_norm_b"])[:, None]
        q = jnp.concatenate([ref._rope_pairs(q[..., :32], inv),
                             q[..., 32:]], -1)
        k = jnp.concatenate([ref._rope_pairs(k[..., :32], inv),
                             k[..., 32:]], -1)[:, 0]
        weight = (a @ w["index_wp"]) * (4 ** -0.5 * 64 ** -0.5)
        want = jnp.einsum("th,ths->ts", weight, jax.nn.relu(
            jnp.einsum("thd,sd->ths", q, k)))
    assert err(got, want) < 1e-4 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("pattern", [(1, 0, 0, 1, 0, 0), (1, 0, 0, 0, 0, 0),
                                     (1, 1, 1, 1, 1, 1), (1, 0, 1, 0, 0, 1)],
                         ids=["as_run", "first_only", "every_layer",
                              "uneven"])
def test_shared_layers_attend_the_full_layer_below(built, pattern):
    """The set crosses the stack boundary (the dense layer is full, the
    expert layers after it shared); a stack without a full layer holds no
    indexer at all."""
    _, ids, pos, _ = built
    cfg = dataclasses.replace(CFG, index_layers=pattern)
    p = build(cfg)
    n_full = sum(pattern[1:])
    if n_full:
        assert p["layers"]["index_wq"].shape[0] == n_full
    else:
        assert not any(n.startswith("index_") for n in p["layers"])
    assert p["dense_layers"]["index_wk"].shape == (1, 128, 64)
    want = ref.forward(p, model_of(cfg), ids, list(range(T)))
    with jax.default_matmul_precision("highest"):
        got, _ = apply_j(p, cfg, ids, pos)
    assert err(got[0], want) < 5e-5
    if pattern != CFG.index_layers:     # and the pattern matters
        other = ref.forward(build(CFG), model_of(CFG), ids, [T - 1])
        assert err(want[-1:], other) > 1e-2


def test_selection_off_is_the_latent_cache_to_the_bit(built):
    p, ids, pos, _ = built
    wide = dataclasses.replace(CFG, index_topk=4096)
    latent = dataclasses.replace(
        CFG, index_topk=0, index_n_heads=0, index_head_dim=0,
        index_layers=(), index_rope_interleave=False)
    assert type(kv_cache_of(latent)) is LatentKV
    p_latent = {k: ({n: a for n, a in v.items()
                     if not n.startswith("index_")}
                    if isinstance(v, dict) else v) for k, v in p.items()}
    got, _ = llama.apply(p, wide, ids, pos)
    want, _ = llama.apply(p_latent, latent, ids, pos)
    assert np.array_equal(got, want)
    on, _ = llama.apply(p, CFG, ids, pos)
    assert err(on[0, -1], want[0, -1]) > 1e-2   # the selection is felt


# ------------------------------------------ every forward = the one pass


def test_plain_and_dense_cache_forwards_match_the_reference(built):
    p, ids, pos, want = built
    with jax.default_matmul_precision("highest"):
        got, _ = apply_j(p, CFG, ids, pos)
        assert err(got[0], want) < 5e-5
        cache = llama.init_kv_cache(CFG, 1, 512, jnp.float32)
        assert set(cache) == {"c", "r", "i"}
        outs = []
        for c0 in range(0, T, 100):     # chunks read the dense index rows
            got, cache = apply_j(p, CFG, ids[:, c0:c0 + 100],
                                     pos[:, c0:c0 + 100], cache)
            outs.append(got[0])
        assert err(jnp.concatenate(outs), want) < 5e-5


def nan_trash(pool):
    return jax.tree.map(
        lambda a: jnp.full_like(a, jnp.nan).at[:, 1:].set(0), pool)


@pytest.fixture(scope="module", params=[False, True],
                ids=["jnp_blocks", "chunk_kernel"])
def prefilled(built, request):
    """Three 128-token chunks through the pool (the later ones read
    latent rows and index keys back from it; the third's context is above
    ``index_topk``), the trash page full of NaN."""
    p, ids, pos, want = built
    pool = nan_trash(llama.init_paged_kv_cache(CFG, 7, PAGE, jnp.float32))
    table = jnp.array([[1, 2, 3, 4, 0]])
    outs = []
    with jax.default_matmul_precision("highest"):
        for c0 in range(0, N_PRE, PAGE):
            logits, pool = prefill_j(
                p, CFG, ids[:, c0:c0 + PAGE], pos[:, c0:c0 + PAGE], pool,
                table, jnp.array([c0 + PAGE]), jnp.int32(c0 // PAGE),
                with_logits=True, use_kernel=request.param)
            outs.append(logits[0])
    return pool, table, jnp.concatenate(outs)


def test_chunked_prefill_reads_rows_and_index_keys_back(built, prefilled):
    pool, _, chunk_logits = prefilled
    assert bool(jnp.all(jnp.isfinite(chunk_logits)))
    assert err(chunk_logits, built[3][:N_PRE]) < 5e-5
    # the index leaf holds the two full layers only
    assert pool["i"].shape == (2, 7, 1, PAGE, 64)
    assert float(jnp.abs(pool["i"][:, 1:4]).min()) > 0


FORMS = pytest.mark.parametrize("form", ["gathered", "kernel"])


@pytest.mark.parametrize("form", ["gathered", "kernel", "alternating"])
def test_decode_through_the_pool_matches_the_one_full_pass(built, prefilled,
                                                           form):
    """Logits, not tokens: expanded masked chunks, then absorbed masked
    decode over the rows and index keys they left, against the
    reference's pass without a cache — the window gathered (the one-token
    verify forward), the latent decode kernel with the keep mask as an
    operand (interpreted), and the two taking turns over ONE pool: each
    reads the three rows the other appended."""
    p, ids, pos, want = built
    pool, table, _ = prefilled
    with jax.default_matmul_precision("highest"):
        for t in range(N_PRE, N_PRE + 5):
            logits, pool = decode_j(
                p, CFG, ids[:, t:t + 1], pos[:, t:t + 1], pool, table,
                jnp.array([t + 1]), table[0, t // PAGE][None],
                jnp.array([t % PAGE]),
                use_kernel={"gathered": False, "kernel": True,
                            "alternating": t % 2 == 0}[form])
            assert bool(jnp.all(jnp.isfinite(logits)))
            assert err(logits[0, 0], want[t]) < 5e-5, t


@FORMS
def test_the_decode_step_is_the_verify_forwards_first_row(built, prefilled,
                                                          form):
    """One query a row and three: the same masked read, so a decode step's
    logits are those of the verify forward's first position — gathered
    as the verify forward itself, or through the kernel."""
    p, ids, pos, _ = built
    pool, table, _ = prefilled
    t = N_PRE
    with jax.default_matmul_precision("highest"):
        one, _ = decode_j(
            p, CFG, ids[:, t:t + 1], pos[:, t:t + 1], pool, table,
            jnp.array([t + 1]), table[0, t // PAGE][None],
            jnp.array([t % PAGE]), use_kernel=form == "kernel")
        three, _ = verify_j(
            p, CFG, ids[:, t:t + 3], pos[:, t:t + 3], pool, table,
            jnp.array([t + 3]), jnp.array([[4, 4, 4]]),
            jnp.array([[0, 1, 2]]))
    assert err(one[0, 0], three[0, 0]) < 2e-5


def test_the_kernel_step_appends_the_rows_where_write_puts_them(built,
                                                                prefilled):
    """Two slots, the second idle: the kernel step leaves in the pool
    what the gathered step's ONE ``write`` leaves — a latent row and its
    rotary lane on every layer, an index key on the full layers, at the
    slot's page and offset and nowhere else but the trash page."""
    p, ids, pos, _ = built
    pool, table, _ = prefilled
    t = N_PRE
    table = jnp.concatenate([table, jnp.zeros_like(table)])

    def step(pool, t, use_kernel):
        tok = jnp.concatenate([ids[:, t:t + 1], jnp.zeros((1, 1), ids.dtype)])
        return decode_j(p, CFG, tok, jnp.array([[t], [0]]), pool, table,
                        jnp.array([t + 1, 1]), jnp.array([4, 0]),
                        jnp.array([t % PAGE, 0]), use_kernel=use_kernel)[1]

    with jax.default_matmul_precision("highest"):
        pool = step(pool, t, False)     # the page begun: row 0 of page 4
        want, got = step(pool, t + 1, False), step(pool, t + 1, True)
    assert set(got) == {"c", "r", "i"}
    for name in got:
        g, w, old = got[name][:, 1:], want[name][:, 1:], pool[name][:, 1:]
        assert err(g, w) < 2e-5, name
        moved = np.argwhere(np.asarray(g != old))
        # every layer of the leaf; page 4 (3 without the trash page);
        # its second row / lane
        assert set(moved[:, 0]) == set(range(g.shape[0])), name
        assert set(moved[:, 1]) == {3}, name
        assert set(moved[:, 4 if name == "r" else 3]) == {1}, name


def test_a_window_no_longer_than_index_topk_runs_without_a_mask(built):
    """One page of context under ``index_topk`` 160: every causal key is
    kept, so the kernel step scores nothing and takes no mask — the same
    logits as the gathered step, and no index score in the program."""
    p, ids, pos, want = built
    pool = nan_trash(llama.init_paged_kv_cache(CFG, 3, PAGE, jnp.float32))
    table = jnp.array([[1]])
    n = 100
    with jax.default_matmul_precision("highest"):
        _, pool = prefill_j(p, CFG, jnp.pad(ids[:, :n], ((0, 0), (0, 28))),
                            pos[:, :PAGE], pool, table, jnp.array([n]),
                            jnp.int32(0))
        args = (ids[:, n:n + 1], pos[:, n:n + 1], pool, table,
                jnp.array([n + 1]), jnp.array([1]), jnp.array([n]))
        a, _ = decode_j(p, CFG, *args, use_kernel=False)
        b, _ = decode_j(p, CFG, *args, use_kernel=True)
    assert err(a, b) < 2e-5 and err(b[0, 0], want[n]) < 5e-5
    text = decode_j.lower(p, CFG, *args, use_kernel=True).as_text(
        debug_info=True)
    assert "attn_select" not in text and "latent_attn_decode" in text


def test_verify_forward_over_the_sparse_pool(built, prefilled):
    p, ids, pos, want = built
    pool, table, _ = prefilled
    with jax.default_matmul_precision("highest"):
        logits, pool = verify_j(
            p, CFG, ids[:, N_PRE:N_PRE + 3], pos[:, N_PRE:N_PRE + 3], pool,
            table, jnp.array([N_PRE + 3]), jnp.array([[4, 4, 4]]),
            jnp.array([[0, 1, 2]]))
    assert err(logits[0], want[N_PRE:N_PRE + 3]) < 5e-5
    gi = kv_cache_of(CFG).index_window(pool, 1, table)
    assert float(jnp.abs(gi[0, N_PRE + 2]).sum()) > 0


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp_blocks", "chunk_kernel"])
def test_a_chunk_program_of_four_prompts(built, use_kernel):
    """Four rows, each a different prompt at its own start (contexts
    below and above ``index_topk``), one program: each row's logits are
    those of the row run alone."""
    p, ids, pos, want = built
    kvc = kv_cache_of(CFG)
    pool = nan_trash(llama.init_paged_kv_cache(CFG, 13, PAGE, jnp.float32))
    starts = [0, 128, 256, 128]
    tables = jnp.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [10, 11, 12]])
    roll = [0, 3, 5, 11]            # four different prompts
    rows = [jnp.roll(ids, r, axis=1) for r in roll]
    with jax.default_matmul_precision("highest"):
        alone = []
        for r, (tok, s) in enumerate(zip(rows, starts)):
            for c0 in range(0, s + PAGE, PAGE):     # the row by itself
                h, pool = prefill_j(
                    p, CFG, tok[:, c0:c0 + PAGE], pos[:, c0:c0 + PAGE], pool,
                    tables[r:r + 1], jnp.array([c0 + PAGE]),
                    jnp.int32(c0 // PAGE))
            alone.append(h[0])
        keys_alone = kvc.index_window(pool, 1, tables)
        # the last chunk of each row again, all four in one program
        tok = jnp.concatenate([t[:, s:s + PAGE]
                               for t, s in zip(rows, starts)])
        st = jnp.array(starts)
        h, pool = prefill_j(
            p, CFG, tok, st[:, None] + jnp.arange(PAGE)[None], pool, tables,
            st + PAGE, st // PAGE, use_kernel=use_kernel)
    for r in range(4):
        assert err(h[r], alone[r]) < 5e-5, r
    assert err(kvc.index_window(pool, 1, tables), keys_alone) < 1e-5


def test_int8_tree_in_bf16_follows_the_reference(built):
    """The served storage (attention int8, indexer bf16) through the
    paged path in bf16 at a context above ``index_topk``: within bf16's
    error of the float32 reference over the same stored tree."""
    _, ids, pos, _ = built
    p = quantize_params(build(CFG, jnp.bfloat16), "int8")
    assert all(isinstance(p["layers"][n], dict)
               for n in ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo"))
    assert all(p[s][n].dtype == jnp.bfloat16 for s in ("layers",
               "dense_layers") for n in p[s] if n.startswith("index_"))
    n = 2 * PAGE
    want = ref.forward(p, model_of(CFG), ids[:, :n + 1],
                       list(range(n - 16, n + 1)))
    pool = llama.init_paged_kv_cache(CFG, 4, PAGE, jnp.bfloat16)
    table = jnp.array([[1, 2, 3]])
    h, pool = prefill_j(
        p, CFG, ids[:, :n], pos[:, :n], pool, table, jnp.array([n]),
        jnp.int32(0))
    got = llama.unembed(p, CFG, h[:, n - 16:n])[0]
    step, _ = decode_j(
        p, CFG, ids[:, n:n + 1], pos[:, n:n + 1], pool, table,
        jnp.array([n + 1]), jnp.array([3]), jnp.array([0]))
    got = jnp.concatenate([got, step[0]]).astype(jnp.float32)
    e = np.asarray(jnp.max(jnp.abs(got - want), -1)
                   / jnp.max(jnp.abs(want), -1))
    assert np.median(e) < 0.05, e


# ------------------------------------------------------------ the kernel


def _decode_kernel_case(case):
    """A pool of float32 rows, four slots over a five-page window, and a
    keep mask a slot (its last bit the current token's)."""
    B, H, R, rope, W, N = 4, 4, 128, 32, 5, 12
    ks = jax.random.split(jax.random.key(11), 8)
    pc = jax.random.normal(ks[0], (2, N, 1, PAGE, R))
    pr = jax.random.normal(ks[1], (2, N, 1, rope, PAGE))
    pc, pr = pc.at[:, 0].set(jnp.nan), pr.at[:, 0].set(jnp.nan)
    qc = jax.random.normal(ks[2], (B, H, R))
    qr = jax.random.normal(ks[3], (B, H, rope))
    cc = jax.random.normal(ks[4], (B, R))
    cr = jax.random.normal(ks[5], (B, rope))
    tbl = jnp.array([[1, 2, 3, 4, 5], [6, 7, 0, 0, 0], [0, 0, 0, 0, 0],
                     [8, 9, 10, 11, 0]])
    # a full last page, a short row, an IDLE slot, a row that ends a block
    lens = jnp.array([600, 130, 0, 512 if case == "lengths" else 511])
    t = jnp.arange(W * PAGE)[None]
    causal = t <= lens[:, None]
    keep = {
        "random": jax.random.uniform(ks[6], causal.shape) < 0.3,
        "drops_current": t < lens[:, None] - 3,
        "one_key": t == (lens[:, None] // 2),
        "lengths": jax.random.uniform(ks[7], causal.shape) < 0.5,
        "all_kept": causal,
    }[case] & causal
    if case == "random":        # the current token kept on some rows
        keep = keep.at[jnp.arange(B), lens].set(
            jnp.array([True, False, True, True]))
    return qc, qr, pc, pr, tbl, lens, cc, cr, keep


@pytest.mark.parametrize("case", ["random", "drops_current", "one_key",
                                  "lengths", "all_kept"])
def test_decode_kernel_with_a_keep_mask_is_absorbed_masked(case):
    """The latent decode kernel with the mask as an operand (interpreted)
    against ``absorbed_masked`` over the gathered window, the current row
    set at its position: a random mask, one that drops the current token,
    one kept key, rows of different lengths beside an idle slot, and the
    causal mask itself, which is the kernel WITHOUT a mask. NaN in the
    trash page and past nothing: rows no query keeps are zeroed."""
    from generativeaiexamples_tpu.ops.latent_attention import (
        latent_attention_decode)
    qc, qr, pc, pr, tbl, lens, cc, cr, keep = _decode_kernel_case(case)
    B, W = tbl.shape
    rows = jnp.arange(B)
    active = np.asarray(lens) > 0
    wp = jnp.where(lens > 0, tbl[rows, lens // PAGE], 0)
    args = (qc, qr, pc, pr, tbl, lens, cc, cr, wp, lens % PAGE,
            jnp.array([1]))
    with jax.default_matmul_precision("highest"):
        got, npc, npr = latent_attention_decode(
            *args, scale=0.2, interpret=True, keep=keep,
            cur_keep=keep[rows, lens])
        gc = pc[1][tbl][:, :, 0].reshape(B, W * PAGE, -1)
        gr = pr[1][tbl][:, :, 0].swapaxes(2, 3).reshape(B, W * PAGE, -1)
        want = si.absorbed_masked(
            qc[:, None], qr[:, None], gc.at[rows, lens].set(cc),
            gr.at[rows, lens].set(cr), keep[:, None], 0.2)[:, 0]
        assert bool(jnp.all(jnp.isfinite(got)))
        # the idle slot too: its own row if the mask keeps it, else zeros
        assert err(got, want) < 2e-5
        assert case != "drops_current" or not bool(jnp.any(got[~active]))
        # the rows appended, mask or none
        assert np.array_equal(npc[1, wp, 0, lens % PAGE][active], cc[active])
        assert np.array_equal(npr[1, wp, 0, :, lens % PAGE][active],
                              cr[active])
        if case == "all_kept":
            plain, _, _ = latent_attention_decode(*args, scale=0.2,
                                                  interpret=True)
            assert err(got[active], plain[active]) < 2e-6


@pytest.mark.parametrize("causal", [False, True])
def test_chunk_kernel_with_a_keep_mask_is_the_jnp_update(causal):
    H, C, Tk, dk, dv = 4, 128, 256, 128, 48
    ks = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(ks[0], (H, C, dk))
    k = jax.random.normal(ks[1], (Tk, H * dk))
    v = jax.random.normal(ks[2], (Tk, H * dv))
    keep = jax.random.bernoulli(ks[3], 0.3, (Tk, C))
    k0, limit, q0 = 64, 64 + 200, 64 + 100
    carry = ca.init_carry(H, C, dv)
    got = ca.chunk_attention_update(
        q, k, v.T, carry, k0, limit, q0, scale=0.1, causal=causal,
        keep=keep.astype(jnp.float32), interpret=True)
    kpos = k0 + jnp.arange(Tk)[:, None]
    ok = (kpos < limit) & keep
    if causal:
        ok = ok & (kpos <= q0 + jnp.arange(C)[None, :])
    s = jnp.einsum("hcd,thd->htc", q, k.reshape(Tk, H, dk)) * 0.1
    s = jnp.where(ok[None], s, -1e30)
    m = jnp.max(s, axis=1, keepdims=True)
    pr = jnp.where(ok[None], jnp.exp(s - m), 0.0)
    want = jnp.einsum("htc,thv->hvc", pr, v.reshape(Tk, H, dv))
    seen = np.asarray(ok.any(0))
    assert np.allclose(np.asarray(got[2])[:, :, seen],
                       np.asarray(want)[:, :, seen], atol=1e-3)
    assert np.allclose(np.asarray(got[1])[:, 0, seen],
                       np.asarray(pr.sum(1))[:, seen], rtol=1e-4)
    # a query that keeps no key of the block keeps its empty carry
    assert np.all(np.asarray(got[1])[:, 0, ~seen] == 0)


def test_kernel_geometry():
    kvc = kv_cache_of(MODEL_REGISTRY["glm-5.2"])
    assert isinstance(kvc, SparseLatentKV)
    # a decode kernel of its own, not the latent cache's unmasked one
    assert kvc.kernel_attend.__func__ is not LatentKV.kernel_attend
    # 192 key columns a head are not whole lanes; 192 + 64 are
    assert not ca.kernel_supported(128, 192, 256, 64)
    assert kvc.prefix_kernel_supported(128) and kvc.kernel_supported(128)
    assert kvc._fold_shared and not kv_cache_of(
        MODEL_REGISTRY["kimi-k2-instruct"])._fold_shared
    assert not kvc.kernel_supported(64)


# ------------------------------------------------------- the cache object


def test_three_leaves_written_are_what_is_read():
    kvc = kv_cache_of(CFG)
    L, page = CFG.num_layers, 16
    pool = nan_trash(kvc.init_pool(5, page, jnp.float32))
    assert set(pool) == set(kvc.leaves) and kvc.leaves == ("c", "r", "i")
    assert pool["i"].shape == (2, 5, 1, page, 64) and kvc.full == (0, 3)
    widths = [(128,), (32,), (64,)]
    ks = jax.random.split(jax.random.key(0), 6)
    chunk = [jax.random.normal(k, (L, 2 * page) + s)
             for k, s in zip(ks, widths)]
    pool = kvc.write(pool, *chunk, jnp.array([3, 1]))
    pages = jnp.array([[2, 2, 2], [4, 4, 4]])
    offs = jnp.array([[5, 6, 7], [0, 1, 15]])
    rows = [jax.random.normal(k, (L, 2, 3) + s)
            for k, s in zip(ks[3:], widths)]
    pool = kvc.write(pool, *rows, pages, offs)
    table = jnp.array([[3, 1, 2], [4, 0, 0]])
    for layer in range(L):
        got = list(kvc.window(pool, layer, table))
        want_chunk, want_rows = chunk[:2], rows[:2]
        if layer in kvc.full:       # a shared layer's index rows go nowhere
            got.append(kvc.index_window(pool, kvc.full.index(layer), table))
            want_chunk, want_rows = chunk, rows
        for g, c, r in zip(got, want_chunk, want_rows):
            assert np.array_equal(g[0, :2 * page], c[layer])
            assert np.array_equal(g[0, 2 * page + 5:2 * page + 8],
                                  r[layer, 0])
            assert np.array_equal(g[1, :2], r[layer, 1, :2])
            assert np.array_equal(g[1, 15], r[layer, 1, 2])
    # what a token costs, as the engine sizes the pool: a latent row a
    # layer, an index key a full layer
    assert kvc.model_token_bytes(2) == 2 * (6 * (128 + 32) + 2 * 64)
    assert kvc.token_bytes(2) == 2 * (128 + 32 + 64)
    leaves = jax.eval_shape(lambda: kvc.init_pool(5, page, jnp.bfloat16))
    assert sum(int(np.prod(x.shape)) * 2 for x in leaves.values()) \
        == 5 * page * kvc.model_token_bytes(2)
    glm = kv_cache_of(MODEL_REGISTRY["glm-5.2"])
    assert glm.model_token_bytes(2) == 78 * 1152 + 21 * 256


def test_insert_pages_is_the_whole_page_write():
    kvc = kv_cache_of(CFG)
    page, S = 16, 32
    dense = kvc.init_dense(1, S, jnp.float32)
    assert dense["i"].shape == (6, 1, S, 64)    # a row on EVERY layer
    dense = {n: jax.random.normal(jax.random.key(i), a.shape)
             for i, (n, a) in enumerate(dense.items())}
    pool = kvc.insert_pages(kvc.init_pool(4, page, jnp.float32), dense["c"],
                            dense["r"], dense["i"], jnp.array([2, 3]))
    c, r = kvc.window(pool, 4, jnp.array([[2, 3]]))
    assert np.array_equal(c[0], dense["c"][4, 0])
    assert np.array_equal(r[0], dense["r"][4, 0])
    gi = kvc.index_window(pool, 1, jnp.array([[2, 3]]))
    assert np.array_equal(gi[0], dense["i"][3, 0])      # layer 3: full no. 1


# -------------------------------------------------------------- refusals


def test_an_int8_pool_is_refused():
    with pytest.raises(NotImplementedError, match="int8 KV pool"):
        llama.init_paged_kv_cache(CFG, 4, PAGE, quantized=True)


def test_ring_attention_and_a_pipeline_stage_refuse_the_indexer(built):
    p, _, pos, _ = built
    with pytest.raises(NotImplementedError, match="per-layer kinds"):
        llama._refuse_kinds(CFG, "apply_sp")
    half = jax.tree.map(lambda a: a[:1], {
        n: a for n, a in p["layers"].items() if not n.startswith("index_")})
    with pytest.raises(NotImplementedError, match="per-layer kinds"):
        llama.run_layers(half, CFG, jnp.zeros((1, 4, 128)), pos[:, :4])


def test_lora_refuses_it(built):
    from generativeaiexamples_tpu.lora import init_lora
    with pytest.raises(NotImplementedError, match="latent-attention"):
        init_lora(CFG, built[0], jax.random.key(0))


@pytest.mark.parametrize("change, match", [
    (dict(kv_lora_rank=0, q_lora_rank=0, qk_nope_head_dim=0,
          qk_rope_head_dim=0, v_head_dim=0, rope_interleave=False,
          num_kv_heads=4), "needs latent attention"),
    (dict(index_n_heads=0), "index_n_heads"),
    (dict(index_head_dim=16), "holds the rotary part"),
    (dict(index_layers=(0, 1)), "layer 0"),
    (dict(index_topk=0), "are the indexer's"),
], ids=["no_latent", "no_heads", "narrow_key", "shared_first", "no_topk"])
def test_configuration_states_its_indexer_whole(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **change)


# ---------------------------------------------------------------- import


def test_registry_entry_is_the_published_model():
    glm = MODEL_REGISTRY["glm-5.2"]
    assert (glm.num_layers, glm.num_dense_layers, glm.num_experts,
            glm.held_experts, glm.num_experts_per_tok) == (78, 3, 256, 256,
                                                           8)
    assert (glm.hidden_size, glm.intermediate_size, glm.expert_width,
            glm.vocab_size, glm.num_heads) == (6144, 12288, 2048, 154880,
                                               64)
    assert (glm.q_lora_rank, glm.kv_lora_rank, glm.qk_nope_head_dim,
            glm.qk_rope_head_dim, glm.v_head_dim, glm.head_dim) == (
        2048, 512, 192, 64, 256, 256)
    assert (glm.index_topk, glm.index_n_heads, glm.index_head_dim) == (
        2048, 32, 128)
    full = [i for i, f in enumerate(glm.layer_index) if f]
    assert full[:5] == [0, 1, 2, 6, 10] and full[-1] == 74 \
        and len(full) == 21
    assert glm.score_scale == pytest.approx(256 ** -0.5)
    assert glm.router_scale == 2.5 and glm.rope_theta == 8e6


def test_published_names_round_trip_through_import_hf(built):
    """The tree written out under the published names (``kv_b`` joined,
    matrices (out, in), the indexer's leaves on the full layers only)
    loads back to the same tree."""
    from generativeaiexamples_tpu.models.import_hf import (
        params_from_named_tensors)
    p = built[0]
    H, nope, vd, R = 4, 96, 48, 128
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
    indexer = {"index_wq": ("self_attn.indexer.wq_b.weight", True),
               "index_wk": ("self_attn.indexer.wk.weight", True),
               "index_k_norm": ("self_attn.indexer.k_norm.weight", False),
               "index_k_norm_b": ("self_attn.indexer.k_norm.bias", False),
               "index_wp": ("self_attn.indexer.weights_proj.weight", True)}
    for stack, first, n in CFG.layer_stacks:
        tree = p[stack]
        at = 0
        for i in range(n):
            pre = f"model.layers.{first + i}."
            for name, hf in plain.items():
                if name in tree:
                    named[pre + hf] = tree[name][i]
            for name, hf in turned.items():
                if name in tree:
                    named[pre + hf] = tree[name][i].T
            if CFG.layer_index[first + i]:
                for name, (hf, turn) in indexer.items():
                    named[pre + hf] = tree[name][at].T if turn \
                        else tree[name][at]
                at += 1
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
    # a full layer without its indexer is an incomplete checkpoint
    from generativeaiexamples_tpu.utils.errors import ModelLoadError
    del named["model.layers.3.self_attn.indexer.wk.weight"]
    with pytest.raises(ModelLoadError, match="index_wk"):
        params_from_named_tensors(iter(named.items()), CFG, jnp.float32)


def test_the_new_scopes_are_in_the_programs(built):
    """``attn_index`` and ``attn_select`` name the indexer's and the
    selection's operations in the decode and the chunk program alike
    (``llama.INDEX_SCOPES``; the benchmark's readers find them by these
    names)."""
    p = built[0]
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    pool = llama.init_paged_kv_cache(CFG, 5, PAGE, jnp.float32)

    def step(p, pool, tok, pos, table, wp, off, use_kernel=False):
        return decode_j(p, CFG, tok, pos, pool, table,
                                        pos[:, 0] + 1, wp, off,
                                        use_kernel=use_kernel)

    def chunk(p, pool, tok, pos, table):
        return prefill_j(p, CFG, tok, pos, pool, table,
                                         pos[:, -1] + 1, jnp.int32(0))
    # four pages: above index_topk, or no score would be computed at all
    texts = [jax.jit(step, static_argnums=(7,)).lower(
                 p, pool, z(2, 1), z(2, 1), z(2, 4), z(2), z(2),
                 kernel).as_text(debug_info=True) for kernel in (False, True)]
    # the step over the decode kernel: the (jitted) kernel call under
    # ``attn`` too — a compiled program's op names carry the prefix into
    # the call (tests/test_chip_compile.py)
    assert re.search(r'"attn/jit\(latent_attention_decode\)"', texts[1])
    texts += [jax.jit(chunk).lower(p, pool, z(1, PAGE), z(1, PAGE),
                                  z(1, 4)).as_text(debug_info=True)]
    assert llama.INDEX_SCOPES == ("attn_index", "attn_select")
    for text in texts:
        # the scores and the top-k inside ``attn`` (under the full
        # layer's cond), the projections beside ``attn_proj``
        for scope in (r'attn/[^"]*attn_index/', r'attn/[^"]*attn_select/',
                      r'[("/]attn_index/cond', "attn_proj/", "moe_route/",
                      "mlp/moe_shared/"):
            assert re.search(scope, text), scope
