"""A model whose recurrent layers decay a CHANNEL at its own rate (KDA:
``linear_decay`` "channel") beside LATENT attention layers, two leading
dense layers before the periods, a router limited to groups and an
expert share: a state a slot beside a latent pool that only the latent
layers write. The served forwards are held to the benchmark's plain
reference (benchmarks/references/bailing_hybrid.py: float32, the
recurrence token by token, no cache), logits not tokens; then what has
no configuration key is planted, one fault a test."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import bailing_hybrid as ref
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.models.kv_cache import (HeadKV, LatentKV,
                                                      RecurrentKV,
                                                      kv_cache_of)
from generativeaiexamples_tpu.ops import gated_delta as gd
from generativeaiexamples_tpu.parallel import moe

# 2 dense + 6 expert layers at period 3: the dense stack is two KDA
# layers, the expert stack [L] [K K L] [K K L] begins INSIDE a period
MODEL = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_layers=8, num_dense_layers=2, num_heads=4,
    num_kv_heads=1, head_dim=24, rope_theta=6e6, rms_norm_eps=1e-6,
    num_experts=16, num_experts_per_tok=4, num_shared_experts=1,
    moe_impl="dropless", router_score_func="sigmoid", router_norm_topk=True,
    router_scale=2.5, router_bias="selection", n_group=4, topk_group=2,
    kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_interleave=True, attn_gate="head",
    full_attention_interval=3, linear_num_key_heads=4,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_decay="channel", linear_decay_floor=-5.0,
    weight_init="unit_stream", experts_held=8, experts_first=4)
CFG = LlamaConfig(**MODEL)
PAGE, T = 16, 88            # 5.5 pages: the last chunk is padded
HI = functools.partial(jax.default_matmul_precision, "highest")


@pytest.fixture(scope="module")
def p32():
    return llama.init_params(CFG, jax.random.key(0), jnp.float32)


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.key(1), (1, T), 3, 512)


@pytest.fixture(scope="module")
def want(p32, ids):
    return ref.forward(p32, MODEL, np.asarray(ids), np.arange(T))


def rel(got, want):
    return float(jnp.max(jnp.max(jnp.abs(got - want), -1)
                         / jnp.max(jnp.abs(want), -1)))


def fresh_pool(cfg=CFG, slots=2, pages=9, dtype=jnp.float32):
    return llama.init_paged_kv_cache(cfg, pages, PAGE, dtype, slots=slots)


TABLE = jnp.arange(1, 9)[None]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _chunk(p, pool, tok, start, valid, cfg=CFG):
    C = tok.shape[1]
    with HI():
        return llama.apply_prefill_paged(
            p, cfg, tok, start + jnp.arange(C)[None], pool, TABLE,
            valid[None], start // PAGE, with_logits=True,
            slots=jnp.asarray([1]))


@jax.jit
def _decode(p, pool, tok, at):
    with HI():
        return llama.apply_decode_paged(
            p, CFG, tok[None, None], at[None, None], pool, TABLE, at[None] + 1,
            TABLE[0, at // PAGE][None], (at % PAGE)[None],
            slots=jnp.asarray([1]))


def prefill(p, ids, n, C=32, pool=None, between=None, cfg=CFG):
    """``n`` prompt tokens through chunk programs of ``C``; ``between``
    edits the pool after the first chunk."""
    pool, outs = fresh_pool() if pool is None else pool, []
    for s0 in range(0, n, C):
        tok = jnp.where(jnp.arange(C)[None] + s0 < n,
                        jnp.pad(ids, ((0, 0), (0, C)))[:, s0:s0 + C], 0)
        lg, pool = _chunk(p, pool, tok, jnp.int32(s0),
                          jnp.int32(min(s0 + C, n)), cfg=cfg)
        outs.append(lg[0, :min(C, n - s0)])
        if between is not None and s0 == 0:
            pool = between(pool)
    return jnp.concatenate(outs), pool


# ------------------------------------------------------ the reference holds

def test_the_plain_forward_follows_the_reference(p32, ids, want):
    with HI():
        got, _ = jax.jit(lambda p, i: llama.apply(
            p, CFG, i, jnp.arange(T)[None]))(p32, ids)
    assert rel(got[0], want) < 2e-5


def test_chunks_then_decode_follow_the_reference(p32, ids, want):
    """80 tokens as three chunk programs (the last padded), each from
    the state and tail the one before left in the slot, then eight
    decode steps through state, tail and the latent pool."""
    got, pool = prefill(p32, ids, 80)
    assert rel(got, want[:80]) < 2e-5
    for at in range(80, T):
        lg, pool = _decode(p32, pool, ids[0, at], jnp.int32(at))
        assert rel(lg[0], want[at:at + 1]) < 2e-5, at


def test_a_dense_cache_follows_the_reference(p32, ids, want):
    """``apply`` over a dense cache in two calls: the cache's slices of
    each kind ride the scans of a stack that begins inside a period."""
    cache = llama.init_kv_cache(CFG, 1, 96, jnp.float32)
    assert set(cache) == {"c", "r", "s", "conv"}
    half = jax.jit(lambda tok, pos, cache: llama.apply(     # one trace
        p32, CFG, tok, pos, cache))
    with HI():
        a, cache = half(ids[:, :T // 2], jnp.arange(T // 2)[None], cache)
        b, cache = half(ids[:, T // 2:], jnp.arange(T // 2, T)[None], cache)
    assert rel(jnp.concatenate([a[0], b[0]]), want) < 2e-5


def test_rows_of_several_prompts_follow_the_reference(p32, want, ids):
    """The grouped chunk program: two prompts' rows at their own starts
    and slots, one of them padded."""
    other = jax.random.randint(jax.random.key(7), (1, T), 3, 512)
    want2 = ref.forward(p32, MODEL, np.asarray(other), np.arange(T))
    pool = llama.init_paged_kv_cache(CFG, 17, PAGE, jnp.float32, slots=3)
    table = jnp.stack([jnp.arange(1, 9), jnp.arange(9, 17)])
    tok = jnp.concatenate([ids[:, :32], other[:, :32]])
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))

    @jax.jit
    def rows(p, pool, tok, pos, valid, start_page):
        with HI():
            h, pool = llama.apply_prefill_paged(
                p, CFG, tok, pos, pool, table, valid, start_page,
                slots=jnp.asarray([2, 0]))
            return llama.unembed(p, CFG, h), pool

    lg, pool = rows(p32, pool, tok, pos, jnp.asarray([32, 32]),
                    jnp.asarray([0, 0]))
    assert rel(lg[0], want[:32]) < 2e-5 and rel(lg[1], want2[:32]) < 2e-5
    tok = jnp.concatenate([ids[:, 32:64], jnp.where(
        jnp.arange(32) < 20, other[:, 32:64], 0)])
    lg, pool = rows(p32, pool, tok, pos + 32, jnp.asarray([64, 52]),
                    jnp.asarray([2, 2]))
    assert rel(lg[0], want[32:64]) < 2e-5
    assert rel(lg[1, :20], want2[32:52]) < 2e-5


# ---------------------------------------------------------- what it is made of

def test_the_cache_is_a_state_beside_a_latent_pool():
    kvc = kv_cache_of(CFG)
    assert isinstance(kvc, RecurrentKV) and isinstance(kvc.paged, LatentKV)
    assert kvc.leaves == ("c", "r", "s", "conv")
    pool = fresh_pool(slots=3, dtype=jnp.bfloat16)
    assert pool["c"].shape == (2, 9, 1, PAGE, 32)      # the latent layers
    assert pool["r"].shape == (2, 9, 1, 8, PAGE)
    assert pool["s"].shape == (6, 3, 4, 16, 16) and pool["s"].dtype == \
        jnp.float32
    assert pool["conv"].shape == (6, 3, 3 * 192)
    assert kvc.model_token_bytes(2) == 2 * (32 + 8) * 2
    assert kvc.slot_bytes(2) == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    plain = dataclasses.replace(
        CFG, kv_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=0,
        v_head_dim=0, rope_interleave=False, attn_gate=False)
    assert isinstance(kv_cache_of(plain).paged, HeadKV)


@pytest.mark.parametrize("first,n,period,want", [
    (0, 12, 4, (-1, 3, 0)),         # whole periods from a period's start
    (0, 2, 6, (-1, 0, 2)),          # leading dense layers: no attention
    (2, 10, 6, (3, 1, 0)),          # begins inside a period
    (2, 6, 3, (0, 1, 2)),           # begins ON a period's attention layer
    (2, 6, 6, (3, 0, 2)),           # a head and a tail, no whole period
    (0, 8, 6, (-1, 1, 2)),
], ids=str)
def test_a_stacks_periods(first, n, period, want):
    cfg = dataclasses.replace(CFG, num_layers=max(first + n, period),
                              num_dense_layers=first,
                              full_attention_interval=period)
    assert llama._stack_periods(cfg, first, n) == want


def test_the_draw_spreads_a_heads_decays_over_its_channels(p32):
    """A channel's state halves in 16 to 4096 tokens, log-uniform ACROSS
    the channels of one head."""
    for stack in ("dense_layers", "layers"):
        lp = p32[stack]
        g = -5.0 * jax.nn.sigmoid(
            jnp.exp(lp["kda_A_log"])[..., None]
            * lp["kda_dt_bias"].reshape(lp["kda_A_log"].shape + (16,)))
        half = jnp.log(2.0) / -g
        assert float(half.min()) >= 15.9 and float(half.max()) <= 4100
        spread = jnp.max(half, -1) / jnp.min(half, -1)      # within a head
        assert float(jnp.min(spread)) > 4
    assert p32["layers"]["kda_wqkv"].shape[0] == 4
    assert p32["dense_layers"]["kda_wqkv"].shape[0] == 2
    assert "wq" not in p32["dense_layers"]
    assert p32["layers"]["wz_head"].shape == (2, 64, 4)


@pytest.mark.parametrize("change,says", [
    (dict(linear_decay_floor=0.0), "negative linear_decay_floor"),
    (dict(linear_num_key_heads=2), "as many key as value"),
    (dict(linear_decay="row"), "linear_decay is"),
    (dict(attn_gate=True), "attn_gate is"),
    (dict(topk_group=5), "limited to groups"),
    (dict(n_group=3), "limited to groups"),
    (dict(index_topk=8, index_n_heads=2, index_head_dim=8), "q_lora_rank"),
    (dict(num_layers=2, num_dense_layers=1), "holds once"),
    (dict(full_attention_interval=0), "recurrent layers'"),
], ids=lambda c: "-".join(c) if isinstance(c, dict) else None)
def test_configurations_that_are_refused(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CFG, **change)


# ------------------------------------------------------------------ the router

def test_the_group_limited_choice_is_the_references():
    key = jax.random.key(3)
    m = jax.random.normal(key, (64, 32))
    router = jax.random.normal(jax.random.fold_in(key, 1), (32, 16)) * 0.4
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (16,))
    want = ref.route(m, router, bias, top_k=4, scale=2.5, n_group=4,
                     topk_group=2)
    cfg = dataclasses.replace(CFG, experts_held=0, experts_first=0)
    select, weigh = moe.router_scores(m @ router, {"router_bias": bias}, cfg)
    rt = moe.route_sorted(select, 4, 8, None, weigh, None)
    w, idx = jax.lax.top_k(select, 4)
    got = jnp.zeros((64, 16)).at[jnp.arange(64)[:, None], idx].set(
        moe.scale_chosen(rt["weight"], cfg))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # a token's four lie in two groups of four
    groups = np.asarray(idx) // 4
    assert all(len(set(g)) <= 2 for g in groups)
    # and the limit binds: without it some token chooses otherwise
    free = dataclasses.replace(cfg, topk_group=4)
    _, idx_free = jax.lax.top_k(moe.router_scores(
        m @ router, {"router_bias": bias}, free)[0], 4)
    assert not np.array_equal(np.sort(idx), np.sort(idx_free))


@pytest.mark.parametrize("held", [4])
def test_the_shares_add_up(p32, held):
    """The expert layer's results under every share of the layer's
    experts, the shared expert counted once, sum to the uncut layer."""
    full = dataclasses.replace(CFG, experts_held=0, experts_first=0)
    pf = llama.init_params(full, jax.random.key(0), jnp.float32)["layers"]
    lp = {k: v[1] for k, v in pf.items() if k in (
        "mlp_norm", "router", "router_bias", "w_gate", "w_up", "w_down",
        "ws_gate", "ws_up", "ws_down")}
    h = jax.random.normal(jax.random.key(5), (40, 64))
    w = {k: lp[k] for k in ref.EXPERTS}
    stacks = [lp[k][None] for k in ("w_gate", "w_up", "w_down")]
    kw = dict(top_k=4, scale=2.5, n_group=4, topk_group=2, eps=1e-6)
    with HI():
        uncut = ref._expert_block(h, w, jnp.int32(0), *stacks, first=0, **kw)
        shared = ref._gated(ref._rms(h, w["mlp_norm"], 1e-6), w["ws_gate"],
                            w["ws_up"], w["ws_down"])
        total = jnp.zeros_like(h)
        x = llama.rmsnorm(h, lp["mlp_norm"], 1e-6)[None]
        for first in range(0, 16, held):
            cfg = dataclasses.replace(CFG, experts_held=held,
                                      experts_first=first)
            part = {**lp, **{k: lp[k][first:first + held]
                             for k in ("w_gate", "w_up", "w_down")}}
            aux = {}
            out, _ = moe.dropless_moe_ffn(
                x, x[0] @ lp["router"], part, cfg, None, aux)
            total = total + out[0]
            assert 0 <= float(aux["route_groups_held_pct"]) <= 100
    np.testing.assert_allclose(h + total + shared, uncut, atol=2e-5)


def test_groups_held_counts_who_may_send(p32):
    """With 2 of 4 groups kept and a held share of two groups, the rows
    whose kept groups miss both held ones send nothing."""
    lp = {k: v[0] for k, v in p32["layers"].items() if k in (
        "router", "router_bias", "w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.key(9), (1, 200, 64))
    aux = {}
    moe.dropless_moe_ffn(x, x[0] @ lp["router"], lp, CFG, None, aux)
    biased = jax.nn.sigmoid(x[0] @ lp["router"]) + lp["router_bias"]
    keep = moe.group_limit(biased, CFG)
    may = np.asarray(jnp.any(keep[:, 4:12], axis=1))
    assert float(aux["route_groups_held_pct"]) == pytest.approx(
        100.0 * may.mean(), abs=1e-3)
    assert 0 < may.mean() < 1


# ------------------------------------------------- faults that have no key

def test_a_heads_decays_averaged_to_a_scalar_fail(p32, ids, want,
                                                  monkeypatch):
    def averaged(q, k, v, g, beta, state, **kw):
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        return gd.gated_delta_recurrent(q, k, v, g, beta, state)
    monkeypatch.setattr(gd, "kda_chunked", averaged)
    with HI():
        got, _ = llama.apply(p32, CFG, ids, jnp.arange(T)[None])
    assert rel(got[0], want) > 0.02


@pytest.mark.parametrize("leaf", ["s", "conv"])
def test_state_or_tail_dropped_between_chunks_fails(p32, ids, want, leaf):
    got, _ = prefill(p32, ids, 64, between=lambda pool: {
        **pool, leaf: jnp.zeros_like(pool[leaf])})
    assert rel(got[:32], want[:32]) < 2e-5
    assert rel(got[32:], want[32:64]) > 1e-3


def test_padding_stays_out_of_state_and_tail(p32, ids):
    """A padded chunk (20 of 32 valid) leaves the state and tail that the
    20 tokens alone leave; taken as valid, the padding moves both."""
    _, pool = prefill(p32, ids, 20)
    tok = jnp.where(jnp.arange(32)[None] < 20, ids[:, :32], 0)
    _, alone = _chunk(p32, fresh_pool(), tok[:, :32], jnp.int32(0),
                      jnp.int32(20))
    _, let_in = _chunk(p32, fresh_pool(), tok, jnp.int32(0), jnp.int32(32))
    for leaf in ("s", "conv"):
        np.testing.assert_array_equal(pool[leaf], alone[leaf])
        assert float(jnp.max(jnp.abs(let_in[leaf] - pool[leaf]))) > 1e-3


def test_an_idle_rows_state_is_left_bit_for_bit(p32, ids):
    _, pool = prefill(p32, ids, 32)
    pool = {**pool, "s": pool["s"].at[:, 0].set(7.0),
            "conv": pool["conv"].at[:, 0].set(3.0)}
    tok = jnp.asarray([[5], [int(ids[0, 32])]])
    at = jnp.asarray([[0], [32]])
    table = jnp.concatenate([jnp.zeros_like(TABLE), TABLE])
    with HI():
        _, new = jax.jit(lambda p, pool: llama.apply_decode_paged(
            p, CFG, tok, at, pool, table, at[:, 0] + 1,
            jnp.asarray([0, TABLE[0, 2]]), jnp.asarray([0, 0]),
            active=jnp.asarray([False, True])))(p32, pool)
    for leaf in ("s", "conv"):
        np.testing.assert_array_equal(new[leaf][:, 0], pool[leaf][:, 0])
        assert not np.array_equal(new[leaf][:, 1], pool[leaf][:, 1])


def test_a_slots_old_state_is_not_read_at_position_zero(p32, ids, want):
    dirty = jax.tree.map(lambda a: jnp.full_like(a, 3.0), fresh_pool())
    got, _ = prefill(p32, ids, 32, pool=dirty)
    assert rel(got, want[:32]) < 2e-5


def test_an_engine_over_the_scan_kernel_follows_the_reference(monkeypatch):
    """The model at widths the scan kernel takes (one group of 128-lane
    heads), a prompt of two chunks — the second from the state and tail
    the first left, and padded — through chunk programs whose scan is
    the kernel (interpreted here, armed as a TPU arms it), then a decode
    round: every served token is the reference's choice after the tokens
    before it, and every chunk program is counted."""
    from generativeaiexamples_tpu.engine.engine import (Engine, EngineConfig,
                                                        SamplingParams)
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
    G = gd._KDA_HEADS
    model = {**MODEL, "num_layers": 6, "linear_num_key_heads": G,
             "linear_num_value_heads": G,
             "linear_key_head_dim": 128, "linear_value_head_dim": 128}
    cfg = LlamaConfig(**model)
    p = llama.init_params(cfg, jax.random.key(0), jnp.float32)
    monkeypatch.setattr(gd, "kda_scan_kernel_armed",
                        gd.kda_scan_kernel_supported)
    traced, kernel = [], gd.kda_chunked_kernel
    monkeypatch.setattr(gd, "kda_chunked_kernel",
                        lambda *a, **kw: traced.append(a[0].shape)
                        or kernel(*a, **kw))
    eng = Engine(p, cfg, ByteTokenizer(), EngineConfig(
        max_slots=2, max_input_length=128, max_output_length=8,
        prefill_buckets=(64,), max_prefill_bucket=64, page_size=64,
        steps_per_round=4, kv_pool_tokens=None, dtype="float32"))
    ids = [int(t) for t in np.random.default_rng(5).integers(3, 250, 100)]
    eng.start()
    try:
        stream = eng.submit(ids, SamplingParams(
            max_tokens=4, temperature=0.0, ignore_eos=True))
        list(stream)
    finally:
        eng.stop()
    out = list(stream.token_ids)
    after = np.arange(len(ids) - 1, len(ids) + len(out) - 1)
    rows = np.asarray(ref.forward(p, model, np.asarray([ids + out]), after))
    for tok, row in zip(out, rows):
        assert row[tok] >= row.max() - 1e-4 * np.abs(row).max()
    stats = eng.stats
    assert (1, 64, 128 * G) in traced
    assert stats["scan_kernel"] == 1 and stats["downgrades"] == 0
    assert stats["scan_kernel_chunks"] == stats["sched_chunk_programs"] == 2


def test_a_tpu_arms_the_scan_kernel_or_names_the_downgrade(monkeypatch):
    """``ProgramSpec.resolve`` on a TPU: the vector decay's scan kernel
    armed where the shapes are a whole group of 128-lane heads, and
    where they fall short (this file's 16-lane heads) ONE downgrade by
    name, ``scan_kernel -> xla_chunked``."""
    from generativeaiexamples_tpu.engine.programs import ProgramSpec
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def resolve(cfg):
        shapes = jax.eval_shape(lambda: llama.init_params(
            cfg, jax.random.key(0), jnp.float32))
        return ProgramSpec.resolve(shapes, cfg, page_size=64, max_slots=2,
                                   pmax=4, dtype=jnp.float32, mesh=None,
                                   eos_id=2)

    short = resolve(CFG)
    assert not short.scan_kernel
    assert [d[:2] for d in short.downgrades if d[0] == "scan_kernel"] == [
        ("scan_kernel", "xla_chunked")]
    G = gd._KDA_HEADS
    wide = resolve(dataclasses.replace(
        CFG, linear_num_key_heads=G, linear_num_value_heads=G,
        linear_key_head_dim=128, linear_value_head_dim=128))
    assert wide.scan_kernel
    assert "scan_kernel" not in [d[0] for d in wide.downgrades]


def test_pipeline_and_ring_refuse_by_name(p32):
    with pytest.raises(NotImplementedError, match="recurrent layers"):
        llama._refuse_kinds(CFG, "apply_sp")


# ----------------------------------------------------------------- import

def published_names(p, cfg):
    """The tree as a ``bailing_hybrid`` checkpoint is ASSUMED to name it
    (models/import_hf.py): q, k, v and their convolutions apart, the
    latent layers by DeepseekV3's names with one ``q_proj`` and a gate a
    head."""
    n, nd = cfg.full_attention_interval, cfg.num_dense_layers
    H, dk = cfg.linear_num_value_heads, cfg.linear_key_head_dim
    yield "model.embed_tokens.weight", np.asarray(p["embed"])
    yield "model.norm.weight", np.asarray(p["final_norm"])
    yield "lm_head.weight", np.asarray(p["lm_head"]).T
    seen = {"dense_layers": [0, 0], "layers": [0, 0]}
    for i in range(cfg.num_layers):
        stack, at = ("dense_layers", i) if i < nd else ("layers", i - nd)
        lay = {k: np.asarray(v, np.float32) for k, v in p[stack].items()}
        pre = f"model.layers.{i}."
        full = (i + 1) % n == 0
        j = seen[stack][full]
        seen[stack][full] += 1
        yield pre + "input_layernorm.weight", lay["attn_norm"][at]
        yield pre + "post_attention_layernorm.weight", lay["mlp_norm"][at]
        if full:
            yield pre + "self_attn.q_proj.weight", lay["wq"][j].T
            yield pre + "self_attn.g_proj.weight", lay["wz_head"][j].T
            yield pre + "self_attn.kv_a_proj_with_mqa.weight", \
                lay["wkv_a"][j].T
            yield pre + "self_attn.kv_a_layernorm.weight", lay["kv_a_norm"][j]
            kb = lay["wk_b"][j].reshape(-1, cfg.num_heads,
                                        cfg.qk_nope_head_dim)
            vb = lay["wv_b"][j].reshape(-1, cfg.num_heads, cfg.v_head_dim)
            yield pre + "self_attn.kv_b_proj.weight", np.concatenate(
                [kb, vb], axis=-1).reshape(kb.shape[0], -1).T
            yield pre + "self_attn.o_proj.weight", lay["wo"][j].T
        else:
            for part, w, c in zip("qkv", np.split(lay["kda_wqkv"][j], 3, -1),
                                  np.split(lay["kda_conv"][j], 3, 0)):
                yield pre + f"self_attn.{part}_proj.weight", w.T
                yield pre + f"self_attn.{part}_conv1d.weight", c[:, None, :]
            for hf, name in (("f_proj", "kda_wf"), ("g_proj", "kda_wg"),
                             ("b_proj", "kda_wb"), ("o_proj", "kda_wout")):
                yield pre + f"self_attn.{hf}.weight", lay[name][j].T
            yield pre + "self_attn.A_log", lay["kda_A_log"][j].reshape(
                1, 1, H, 1)
            yield pre + "self_attn.dt_bias", lay["kda_dt_bias"][j]
            yield pre + "self_attn.o_norm.weight", lay["kda_norm"][j]
        if i < nd:
            for hf in ("gate", "up", "down"):
                yield pre + f"mlp.{hf}_proj.weight", lay["w_" + hf][at].T
            continue
        yield pre + "mlp.gate.weight", lay["router"][at].T
        yield pre + "mlp.gate.e_score_correction_bias", lay["router_bias"][at]
        for hf in ("gate", "up", "down"):
            yield pre + f"mlp.shared_experts.{hf}_proj.weight", \
                lay["ws_" + hf][at].T
            for e in range(cfg.num_experts):    # every expert of the layer
                held = e - cfg.experts_first
                w = lay["w_" + hf][at][held] \
                    if 0 <= held < cfg.held_experts \
                    else np.full(lay["w_" + hf][at][0].shape, np.nan)
                yield pre + f"mlp.experts.{e}.{hf}_proj.weight", w.T


def test_a_checkpoint_by_the_assumed_names_is_the_tree(p32, ids):
    from generativeaiexamples_tpu.models.import_hf import (
        params_from_named_tensors)
    got = params_from_named_tensors(published_names(p32, CFG), CFG,
                                    jnp.float32)
    for stack in ("dense_layers", "layers"):
        assert set(got[stack]) == set(p32[stack])
        for name, want in p32[stack].items():
            assert got[stack][name].shape == want.shape, name
            np.testing.assert_allclose(got[stack][name], want, rtol=1e-6,
                                       atol=1e-7, err_msg=name)
    forward = jax.jit(lambda p: llama.apply(        # one trace for both
        p, CFG, ids[:, :24], jnp.arange(24)[None]))
    (a, _), (b, _) = forward(got), forward(p32)
    assert rel(a[0], b[0]) <= 1e-5


def _published():
    import json
    import os
    from benchmarks.harness.spec import REPO
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "ling-3.0-flash.json")) as f:
        return json.load(f)


def test_the_published_keys_give_the_configuration_as_run():
    """The benchmark file's ``model`` group is what the published keys
    say, cut to its layers and its share — nothing typed twice can
    drift."""
    from generativeaiexamples_tpu.models.import_hf import (
        bailing_hybrid_config)
    doc = _published()
    hf = {**doc["published"], "num_experts": 512}
    cfg = bailing_hybrid_config(hf, num_layers=12, experts_held=64,
                                weight_init="unit_stream")
    assert cfg == LlamaConfig(**doc["model"])
    assert cfg.layer_full == (0, 0, 0, 0, 0, 1) * 2
    assert [llama._stack_periods(cfg, f, n) for _, f, n in
            cfg.layer_stacks] == [(-1, 0, 2), (3, 1, 0)]


@pytest.mark.parametrize("change,says", [
    ({"num_hidden_layers": 42}, "layers \\[35, 36"),
    ({"use_kda_lora": True}, "use_kda_lora"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
], ids=lambda c: next(iter(c)) if isinstance(c, dict) else None)
def test_what_the_loader_has_no_form_for_is_refused_by_name(change, says):
    from generativeaiexamples_tpu.models.import_hf import (
        bailing_hybrid_config)
    from generativeaiexamples_tpu.utils.errors import ModelLoadError
    hf = {**_published()["published"], "num_hidden_layers": None, **change}
    with pytest.raises(ModelLoadError, match=says):
        bailing_hybrid_config(hf, num_layers=hf["num_hidden_layers"] or 12)
