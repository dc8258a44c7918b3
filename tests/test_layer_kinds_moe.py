"""Per-layer kinds in the one layer scan (window and global layers, a
layer without rotary embedding), a router that reads the block's input,
dropless routing over many narrow experts — at a toy size on the CPU,
with seeded weights and a toy window SHORTER than the prompts, against
the benchmark's plain reference of the architecture
(``benchmarks/references/smallthinker.py``).
"""

import dataclasses
import hashlib
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.references import smallthinker  # noqa: E402
from generativeaiexamples_tpu.models import llama  # noqa: E402
from generativeaiexamples_tpu.models.configs import (  # noqa: E402
    GPTNEXT_TINY, MODEL_REGISTRY, LlamaConfig)
from generativeaiexamples_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention_decode, paged_attention_decode_reference)
from generativeaiexamples_tpu.ops.quant import quantize_params  # noqa: E402
from generativeaiexamples_tpu.parallel import moe  # noqa: E402

PAGE = 128
WINDOW = 160                 # starts mid-page, shorter than every prompt
MODEL = dict(
    vocab_size=512, hidden_size=128, intermediate_size=64, num_layers=4,
    num_heads=14, num_kv_heads=2, head_dim=128, max_position_embeddings=2048,
    rope_theta=1.5e6, rms_norm_eps=1e-6, num_experts=8,
    num_experts_per_tok=3, moe_impl="dropless", mlp="relu_glu",
    router_input="block_input", sliding_window=WINDOW,
    window_layers=[0, 1, 1, 1], rope_layers=[0, 1, 1, 1],
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
    return smallthinker.forward(params, MODEL, ids, np.arange(S))


def rel_err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_config_turns_lists_into_tuples_and_repeats_the_period():
    assert CFG.window_layers == (0, 1, 1, 1) and hash(CFG)
    assert CFG.layer_windows == (0, WINDOW, WINDOW, WINDOW)
    assert CFG.layer_rope == (0, 1, 1, 1)
    plain = LlamaConfig()
    assert not any(plain.layer_windows) and all(plain.layer_rope)
    assert llama.layer_kinds(plain) == {}
    with pytest.raises(ValueError):
        LlamaConfig(router_input="somewhere")


def test_registry_serves_the_published_depth():
    cfg = MODEL_REGISTRY["smallthinker-21b-a3b-instruct"]
    assert (cfg.num_layers, cfg.hidden_size, cfg.intermediate_size) == (
        52, 2560, 768)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (28, 4, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 6)
    assert cfg.layer_windows[:5] == (0, 4096, 4096, 4096, 0)
    assert sum(cfg.layer_rope) == 39 and cfg.vocab_size == 151936
    shapes = jax.eval_shape(
        lambda k: llama.init_params(dataclasses.replace(cfg, num_layers=1),
                                    k), jax.random.key(0))
    assert set(shapes["layers"]) == {
        "wq", "wk", "wv", "wo", "router", "w_gate", "w_up", "w_down",
        "attn_norm", "mlp_norm"}        # Mixtral's leaves, no others


def test_reference_against_apply(params, ids, ref):
    full, _ = jax.jit(lambda p, i: llama.apply(
        p, CFG, i, jnp.arange(S)[None]))(params, jnp.asarray(ids))
    assert rel_err(full[0], ref) < 1e-4


@pytest.mark.parametrize("kv_int8", [False, True], ids=["raw", "int8kv"])
@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_reference_against_prefill_then_decode(params, ids, ref, kv_int8,
                                               use_kernel):
    """Two pages through ``apply_prefill_paged`` in two chunks (the
    second reads the first back through the window), then the third
    page's first tokens a decode step each, through the pool."""
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    pool = llama.init_paged_kv_cache(CFG, 5, PAGE, jnp.float32,
                                     quantized=kv_int8)
    table = jnp.arange(1, 5, dtype=jnp.int32)[None]
    # heads that choose keys (weight_init unit_stream) turn an int8 key's
    # rounding into a visible change of the softmax
    tol = 0.1 if kv_int8 else 1e-4
    for c in range(2):
        pos = jnp.arange(c * PAGE, (c + 1) * PAGE)[None]
        h, pool = jax.jit(lambda p, pool, t, pos, c: llama.apply_prefill_paged(
            p, CFG, t, pos, pool, table, pos[:, -1] + 1, c))(
            params, pool, jnp.asarray(ids[:, c * PAGE:(c + 1) * PAGE]), pos,
            jnp.int32(c))
        got = llama.unembed(params, CFG, h)[0]
        assert rel_err(got, ref[c * PAGE:(c + 1) * PAGE]) < tol

    @jax.jit
    def step(p, pool, tok, at):
        return llama.apply_decode_paged(
            p, CFG, tok[None, None], at[None, None], pool, table,
            (at + 1)[None], (1 + at // PAGE)[None], (at % PAGE)[None],
            use_kernel=use_kernel)
    for at in range(2 * PAGE, 2 * PAGE + 4):
        logits, pool = step(params, pool, jnp.asarray(ids[0, at]),
                            jnp.int32(at))
        assert rel_err(logits[0, 0], ref[at]) < tol


@pytest.mark.parametrize("arch", ["kinds", "plain"])
def test_chunk_ignores_what_lies_in_the_trash_page(params, ids, arch):
    """A chunk's prefix attention reads whole blocks of pages: the end
    of the prefix shares its block with the chunk's own (stale) pages
    and, past the extent, the trash page. Non-finite values there (the
    decode kernel parks idle slots' rows in the trash page beside
    whatever its scratch held) must not reach any query: 0 x NaN = NaN
    through the PV product made a request answer with garbage (PERF.md
    section 7 row 1)."""
    cfg, p = CFG, params
    if arch == "plain":
        cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                          intermediate_size=256, num_layers=2, num_heads=4,
                          num_kv_heads=2, head_dim=32,
                          max_position_embeddings=2048)
        p = llama.init_params(cfg, jax.random.key(2), dtype=jnp.float32)
    table = jnp.asarray([[1, 2, 3, 0, 0, 0, 0, 0]], jnp.int32)

    def two_chunks(pool):
        outs = []
        for c in range(2):
            pos = jnp.arange(c * PAGE, (c + 1) * PAGE)[None]
            h, pool = llama.apply_prefill_paged(
                p, cfg, jnp.asarray(ids[:, c * PAGE:(c + 1) * PAGE]), pos,
                pool, table, pos[:, -1] + 1, jnp.int32(c))
            outs.append(h)
        return jnp.concatenate(outs, axis=1)

    clean = llama.init_paged_kv_cache(cfg, 4, PAGE, jnp.float32)
    dirty = {k: v.at[:, 0].set(jnp.nan).at[:, 2:].set(jnp.inf)
             for k, v in clean.items()}     # trash, and pages not yet written
    want, got = jax.jit(two_chunks)(clean), jax.jit(two_chunks)(dirty)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert jnp.array_equal(got, want)


def faulty_reference(old: str, new: str):
    """The reference's source with one line changed, as a module."""
    src = open(smallthinker.__file__).read()
    assert src.count(old) == 1
    spec = importlib.util.spec_from_loader("faulty_smallthinker", None)
    mod = importlib.util.module_from_spec(spec)
    exec(compile(src.replace(old, new), "faulty_smallthinker", "exec"),
         mod.__dict__)
    return mod


@pytest.mark.parametrize("old,new", [
    ("seen = seen & (i - j < window)", "seen = seen & (i - j <= window)"),
    ("    if rotary:\n", "    if True:\n"),
    ('logits = x @ w["router"]',
     'logits = _norm(x, w["attn_norm"], eps) @ w["router"]'),
], ids=["window_off_by_one", "rotary_in_a_global_layer",
        "router_after_the_norm"])
def test_a_fault_in_the_reference_is_seen(params, ids, ref, old, new):
    """The comparison has to be able to fail: each equation the
    architecture adds, got wrong in the reference, moves the logits past
    the window by far more than the paths above differ."""
    bad = faulty_reference(old, new).forward(params, MODEL, ids,
                                             np.arange(S))
    assert rel_err(bad[:WINDOW - 1], ref[:WINDOW - 1]) < 1e-4 \
        or "router" in new or "True" in new   # the window bites later
    assert rel_err(bad[WINDOW + 8:], ref[WINDOW + 8:]) > 0.01


def test_engine_chunked_path_follows_the_reference(params, ids, ref,
                                                   monkeypatch):
    """The engine's own programs: a prompt of two and a half pages
    admitted in one-page chunks (``extend`` / ``final`` read the prefix
    back through the window), then greedy decode rounds through the
    windowed kernel (interpreted); every token is the reference's argmax
    of a forward over what came before."""
    monkeypatch.setenv("GENAI_TPU_PAGED_KERNEL", "1")
    from generativeaiexamples_tpu.engine import (Engine, EngineConfig,
                                                 SamplingParams)
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
    n_prompt, n_new = 2 * PAGE + 37, 6
    eng = Engine(params, CFG, ByteTokenizer(), EngineConfig(
        max_slots=2, max_input_length=3 * PAGE, max_output_length=PAGE,
        max_prefill_bucket=PAGE, prefill_buckets=(PAGE,), dtype="float32",
        sched_round_budget_tokens=PAGE, prefix_cache=False))
    eng.start()
    try:
        prompt = [int(t) for t in ids[0, :n_prompt]]
        s = eng.submit(prompt, SamplingParams(max_tokens=n_new, top_k=1,
                                              ignore_eos=True))
        for _ in s:
            pass
        assert s.finish_reason == "length" and len(s.token_ids) == n_new
        stats = eng.stats
        # this engine's rounds: the recorder is the process's, and under
        # one worker it still holds other tests' engines' records
        recs = [r for r in eng.rounds.records()
                if r.engine_tag == eng.engine_tag and r.decode_slots]
    finally:
        eng.stop()
    seq = np.asarray(prompt + s.token_ids)[None]
    logits = smallthinker.forward(params, MODEL, seq,
                                  np.arange(n_prompt - 1, seq.shape[1] - 1))
    for i, tok in enumerate(s.token_ids):
        gap = float(jnp.max(logits[i]) - logits[i][tok]) \
            / float(jnp.max(jnp.abs(logits[i])))
        assert gap < 1e-3, (i, tok, gap)
    # the counters: one row reaches 3 experts a layer; the window
    # layers (3 of 4) skip the page behind the window
    # ... in every round that emitted a token. Under a loaded host the
    # loop dispatches a surplus round after the last token (ROADMAP S15):
    # it decodes no row, reports 0 and adds 0 to the sum.
    rounds = stats["experts_touched_sum"] / 3.0
    assert rounds == pytest.approx(round(rounds))
    assert 1 <= round(rounds) <= stats["experts_touched_rounds"]
    emitted = [r for r in recs if r.tokens_emitted]
    assert emitted and all(r.experts_touched == pytest.approx(3.0)
                           for r in emitted), [
        (r.round_id, r.tokens_emitted, r.experts_touched) for r in recs]
    assert stats["kv_pages_skipped"] > 0
    assert all(r.kv_pages_skipped > 0 for r in recs)
    assert "kv_pages_skipped" in recs[0].to_dict()["outcome"]


@pytest.mark.parametrize("window,lengths", [
    (160, (300, 37, 384, 129)), (129, (383, 128, 1, 256)),
    (0, (300, 37, 384, 129))], ids=["mid_page", "page_edge", "none"])
def test_kernel_window_against_the_gather_reference(window, lengths):
    """Interpret mode, 28/4 heads (a group of 7), a window that starts in
    the middle of a page: the page loop starts at the window's first
    page and masks its rows below the window."""
    B, H, KV, hd, N = 4, 28, 4, 128, 13
    k = iter(jax.random.split(jax.random.key(3), 8))
    q = jax.random.normal(next(k), (B, H, hd), jnp.float32)
    pool_k = jax.random.normal(next(k), (1, N, KV, PAGE, hd), jnp.float32)
    pool_v = jax.random.normal(next(k), (1, N, KV, PAGE, hd), jnp.float32)
    ck = jax.random.normal(next(k), (B, KV, hd), jnp.float32)
    cv = jax.random.normal(next(k), (B, KV, hd), jnp.float32)
    table = jnp.arange(1, 13, dtype=jnp.int32).reshape(B, 3)
    lens = jnp.asarray(lengths, jnp.int32)
    wp = table[jnp.arange(B), lens // PAGE % 3]
    got, new_k, _ = paged_attention_decode(
        q, pool_k, pool_v, table, lens, ck, cv, wp, lens % PAGE,
        jnp.zeros((1,), jnp.int32), interpret=True,
        window=jnp.asarray([window], jnp.int32))
    want = paged_attention_decode_reference(
        q, pool_k[0], pool_v[0], table, lens, ck, cv, window=window)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    # the append landed where it was sent
    assert jnp.array_equal(new_k[0, wp, :, lens % PAGE], ck)


def dense_experts(x, logits, lp, k):
    """Every expert for every token, the unchosen weighted by zero."""
    w, idx = jax.lax.top_k(logits, k)
    mix = jax.nn.softmax(w, axis=-1)
    T = x.shape[0]
    weight = jnp.zeros_like(logits).at[jnp.arange(T)[:, None], idx].set(mix)
    out = jnp.zeros_like(x)
    for e in range(logits.shape[1]):
        y = (jax.nn.relu(x @ lp["w_gate"][e]) * (x @ lp["w_up"][e])) \
            @ lp["w_down"][e]
        out = out + weight[:, e:e + 1] * y
    return out


@pytest.fixture(scope="module")
def layer():
    E, D, F = 64, 32, 16
    k = iter(jax.random.split(jax.random.key(5), 4))
    return {"w_gate": jax.random.normal(next(k), (E, D, F)) / D ** 0.5,
            "w_up": jax.random.normal(next(k), (E, D, F)) / D ** 0.5,
            "w_down": jax.random.normal(next(k), (E, F, D)) / F ** 0.5}


MOE_CFG = LlamaConfig(hidden_size=32, intermediate_size=16, num_experts=64,
                      num_experts_per_tok=6, moe_impl="dropless",
                      mlp="relu_glu")


@pytest.mark.parametrize("rows", [1, 16, 512])
def test_dropless_loses_nothing_when_all_rows_choose_the_same_six(layer,
                                                                  rows):
    """The case a capacity drops: every row wants the same six experts."""
    x = jax.random.normal(jax.random.key(6), (rows, 1, 32))
    logits = jnp.zeros((rows, 1, 64)).at[:, :, 10:16].set(
        jnp.arange(6, dtype=jnp.float32) + 1.0)
    got, touched = jax.jit(lambda x, l: moe.dropless_moe_ffn(
        x, l, layer, MOE_CFG))(x, logits)
    want = dense_experts(x[:, 0], logits[:, 0], layer, 6)
    assert float(touched) == 6.0
    assert float(jnp.max(jnp.abs(got[:, 0] - want))) < 1e-5
    assert float(jnp.min(jnp.max(jnp.abs(got[:, 0]), axis=-1))) > 0


def test_dropless_rows_do_not_depend_on_their_neighbours(layer):
    x = jax.random.normal(jax.random.key(8), (16, 1, 32))
    logits = jax.random.normal(jax.random.key(9), (16, 1, 64))
    f = jax.jit(lambda x, l, m: moe.dropless_moe_ffn(x, l, layer, MOE_CFG,
                                                     m))
    every = jnp.ones((16,), bool)
    got, touched = f(x, logits, every)
    want = dense_experts(x[:, 0], logits[:, 0], layer, 6)
    assert float(jnp.max(jnp.abs(got[:, 0] - want))) < 1e-5
    # other neighbours, same row 3
    other, _ = f(x.at[4:].set(x[4:] * -2.0),
                 logits.at[4:].set(logits[4:][::-1]), every)
    assert jnp.array_equal(got[3], other[3])
    # an idle row touches no expert and gets nothing
    alone, t1 = f(x, logits, jnp.arange(16) == 3)
    assert float(t1) == 6.0 and float(touched) > 6.0
    assert jnp.allclose(alone[3], got[3], atol=1e-6)
    assert float(jnp.max(jnp.abs(alone[4:]))) == 0.0


@pytest.mark.parametrize("rows", [1, 16, 200])
def test_grouped_kernel_against_the_block_loop(rows):
    """The Pallas kernel of ops/grouped_ffn.py, interpreted, against the
    ``fori_loop`` of plain dots that runs off the TPU: lane-aligned
    widths, a layer picked out of (L, E, in, out) stacks, idle rows."""
    from generativeaiexamples_tpu.ops.grouped_ffn import (
        grouped_expert_ffn, kernel_supported)
    E, D, F, L, K = 8, 256, 256, 3, 3
    k = iter(jax.random.split(jax.random.key(11), 5))
    w = (jax.random.normal(next(k), (L, E, D, F)) / D ** 0.5,
         jax.random.normal(next(k), (L, E, D, F)) / D ** 0.5,
         jax.random.normal(next(k), (L, E, F, D)) / F ** 0.5)
    x = jax.random.normal(next(k), (rows, D))
    logits = jax.random.normal(next(k), (rows, E))
    mask = jnp.arange(rows) % 3 != 1
    bm = moe.dropless_block_rows(rows)
    assert kernel_supported(D, F, bm, x.dtype)

    @jax.jit
    def both(x, logits, mask):
        rt = moe.route_sorted(logits, K, bm, mask)
        x_pad = jnp.where(rt["valid"][:, None], x[rt["token"]], 0)
        args = (x_pad, rt["block_expert"], rt["n_blocks"], jnp.int32(2), *w)
        want = moe.block_loop_ffn(*args, bm=bm, relu=True)
        got = grouped_expert_ffn(*args, bm=bm, relu=True, interpret=True)
        return (moe._combine_sorted(want, rt, x.dtype, (rows, 1, D))[0],
                moe._combine_sorted(got, rt, x.dtype, (rows, 1, D))[0])

    want, got = both(x, logits, mask)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.sum(jnp.abs(got[1::3]))) == 0.0     # idle rows


def test_weight_init_is_a_field_of_its_own():
    """The draw follows ``weight_init`` and nothing else: a router that
    reads the block's input keeps the "fan_in" tree bit for bit."""
    base = dict(MODEL, weight_init="fan_in")
    a = llama.init_params(LlamaConfig(**base), jax.random.key(3))
    b = llama.init_params(LlamaConfig(**dict(base, router_input="mlp_norm")),
                          jax.random.key(3))
    assert all(jnp.array_equal(x, y) for x, y in zip(
        jax.tree.leaves(a), jax.tree.leaves(b)))
    c = llama.init_params(CFG, jax.random.key(3))
    assert float(jnp.std(c["embed"].astype(jnp.float32))) > 0.9
    assert float(jnp.std(a["embed"].astype(jnp.float32))) < 0.2
    L = CFG.num_layers
    ratio = (jnp.std(c["layers"]["wo"].astype(jnp.float32))
             / jnp.std(a["layers"]["wo"].astype(jnp.float32)))
    assert abs(float(ratio) - (2 * L) ** -0.5) < 0.01
    gain = (jnp.std(c["layers"]["wq"].astype(jnp.float32))
            / jnp.std(a["layers"]["wq"].astype(jnp.float32)))
    assert abs(float(gain) - 4.0) < 0.05
    with pytest.raises(ValueError):
        LlamaConfig(weight_init="somehow")


def test_vocab_tile_of_an_awkward_vocabulary():
    """151936 = 128 x 1187 (a prime): the fused sampler takes the
    smallest aligned divisor above its target, not 1187 tiles of 128;
    the vocabularies the benchmark already had keep their tile."""
    from generativeaiexamples_tpu.ops.fused_sampler import choose_tile
    assert choose_tile(151936) == 37984 and 151936 % 37984 == 0
    assert choose_tile(256000) == 4000 and choose_tile(32000) == 4000
    assert choose_tile(512) == 512 and choose_tile(32016) == 32016
    assert choose_tile(151936, 64) == 64


def test_capacity_routing_refuses_what_only_dropless_has():
    cfg = dataclasses.replace(MOE_CFG, moe_impl="sparse")
    with pytest.raises(ValueError):
        llama._moe_mlp(jnp.zeros((1, 1, 32)), {}, cfg)


# The three architectures the benchmark has: their parameter trees for a
# seed and the lowered text of the programs a cell runs or falls back to
# (sha256 at tiny sizes, int8 weights: ``lowered_text`` below, hashed).
# ``kernel`` / ``jnp``: the decode step over a bf16 pool, Pallas call or
# gather path; ``kernel_int8kv``: the kernel step over an int8 pool;
# ``chunk``: ``apply_prefill_paged``, bf16 pool; ``verify``: the
# three-token verify forward. A change that moves a ``kernel`` or
# ``chunk`` pin moves the configurations' cells: re-pin only on purpose
# (a new JAX re-words the text: re-pin BOTH sides from one commit).
# All were computed on PR 30's parent. PR 30 (one layer-stack driver,
# one pool writer) left the two trees, ``kernel`` of mixtral and
# nemotron and all three ``chunk`` pins as the parent had them, and
# re-pinned: ``jnp`` and ``verify`` (on purpose: the gather path reads
# the pool by (layer, page) and no longer takes it as scan inputs);
# ``kernel_int8kv`` (the carry holds the cache dict, so the loop's
# operands are k, ks, v, vs where they were k, v, ks, vs: six lines of
# ~8,500, the same count); ``smallthinker_decode_kernel`` (the window's
# (1,) view is taken where the kernel is called, not at the head of the
# body: the same lines but for their numbering, one of them elsewhere).
PINS = {
    "mixtral_tree":
        "122c47ae888d512af2a4f299e95930ba1ba1700cf8489900e35aef13fdbde819",
    "mixtral_decode_jnp":
        "7c618560d2e46b0c39cb885fb57bb9bac97a06c5aaa8b9f974f4184dee279aea",
    "mixtral_decode_kernel":
        "490c8024b37f88077809206032731335a051af3803ea9359babf3067c9e4868a",
    "mixtral_decode_kernel_int8kv":
        "37f3a97a6dfc8c94215d92a151abcbe8f9db487c787c38433d60c7ec4ddbb212",
    "mixtral_decode_chunk":
        "3eb70b1e4f361b6ba50da74fcf6d536a546e0a9337710defa74a616ffe73b139",
    "mixtral_decode_verify":
        "e4e4bff37a0f61755a0b72e9e3590dc45da49641e7c41426dfd524c7d4264a4f",
    "nemotron_tree":
        "9017a962e8f5a4bfaa6cf3ab591cb069937ac3d0de8c3c7cd8fe7985ac4c51b7",
    "nemotron_decode_jnp":
        "dd7b48109ef552fac43c1d2a096571d411b0666258a3b884758dc4b73310a87e",
    "nemotron_decode_kernel":
        "8742589a84520739873f5ade3ffef388b8b90cc4c0c35c116c7267511c97d841",
    "nemotron_decode_kernel_int8kv":
        "66b1b7d9e33bc19aa2c2b7e82b3e22aee9491fd025cd8b1b1c38b71e36a9874c",
    "nemotron_decode_chunk":
        "0183d7a9d5e042285508ba75eafc858d679ecd3a1fc34d875c81117140354913",
    "nemotron_decode_verify":
        "addba2725a059d2f6049cc3b6af2aeceb6d30558f90deb5228a87169b5fd2a5c",
    "smallthinker_tree":
        "ed0e86ded7ac3df11ba816fd6860a3a488d3d7c6b77e9745d5dd99c481f21849",
    "smallthinker_decode_jnp":
        "12f9e8868087f77d626a59aa60e7f106d9ab8dc73e2485b158d6898e1620436a",
    "smallthinker_decode_kernel":
        "c32f8a14269971750f3eb58a56c47427ec6e8206d3b5905c4276aa596bd91030",
    "smallthinker_decode_kernel_int8kv":
        "3f24a21e36f8b97de0cd161fc9d5b4b4b33bf780cc14fd7b063fbf97cc1370bd",
    "smallthinker_decode_chunk":
        "adffc4e47943af8f524d230fff966cd02e9ff0599f8cd8f6b13ba5a78105c85e",
    "smallthinker_decode_verify":
        "7dea63cdadd9ced0ea43598d2156d5e701daae2fb17632c518866d70112b307d",
}
TINY = {
    "mixtral": LlamaConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=128,
        max_position_embeddings=512, rope_theta=1e6, num_experts=8,
        num_experts_per_tok=2),
    "nemotron": dataclasses.replace(GPTNEXT_TINY, head_dim=128, num_heads=2,
                                    num_kv_heads=2, hidden_size=256),
    "smallthinker": CFG,
}


@pytest.fixture(scope="module", params=sorted(TINY))
def old_arch(request):
    cfg = TINY[request.param]
    p = quantize_params(llama.init_params(cfg, jax.random.key(7),
                                          dtype=jnp.bfloat16), "int8")
    return request.param, cfg, p


def test_existing_trees_unchanged(old_arch):
    name, _, p = old_arch
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(p)[0]
    for path, leaf in sorted(leaves, key=lambda x: str(x[0])):
        h.update(str(path).encode())
        h.update(str(leaf.dtype).encode())
        h.update(np.asarray(leaf.astype(jnp.float32)
                            if leaf.dtype == jnp.bfloat16 else leaf).tobytes())
    assert h.hexdigest() == PINS[name + "_tree"]


def lowered_text(cfg, p, path):
    """The lowered text of one program of ``cfg`` over the tree ``p``."""
    pool = llama.init_paged_kv_cache(cfg, 5, PAGE, jnp.bfloat16,
                                     quantized=path == "kernel_int8kv")
    B = 2
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    if path == "chunk":
        def chunk(p, pool, tok, pos, table, start_page):
            return llama.apply_prefill_paged(p, cfg, tok, pos, pool, table,
                                             pos[:, -1] + 1, start_page)
        return jax.jit(chunk).lower(p, pool, z(1, PAGE), z(1, PAGE), z(1, 4),
                                    z()).as_text()
    if path == "verify":
        def verify(p, pool, tok, pos, table, wp, off):
            return llama.apply_verify_paged(p, cfg, tok, pos, pool, table,
                                            pos[:, -1] + 1, wp, off)
        return jax.jit(verify).lower(p, pool, z(B, 3), z(B, 3), z(B, 4),
                                     z(B, 3), z(B, 3)).as_text()

    def step(p, pool, tok, pos, table, wp, off, use_kernel):
        return llama.apply_decode_paged(p, cfg, tok, pos, pool, table,
                                        pos[:, 0] + 1, wp, off,
                                        use_kernel=use_kernel)
    return jax.jit(step, static_argnums=(7,)).lower(
        p, pool, z(B, 1), z(B, 1), z(B, 4), z(B), z(B),
        path != "jnp").as_text()


@pytest.mark.parametrize("path", ["jnp", "kernel", "kernel_int8kv", "chunk",
                                  "verify"])
def test_existing_decode_programs_unchanged(old_arch, path):
    name, cfg, p = old_arch
    text = lowered_text(cfg, p, path)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PINS[f"{name}_decode_{path}"]
