"""A model whose recurrent layers are STATE-SPACE layers (Mamba-2:
``linear_decay`` "ssd") beside NoPE attention layers that sit in the
MIDDLE of their period, a residual multiplier, a stated score scale and
scaled logits over a tied head: a state a slot beside a paged pool that
only the attention layers write. The served forwards are held to the
benchmark's plain reference (benchmarks/references/granitemoehybrid.py:
float32, the recurrence token by token, no cache), logits not tokens;
then what a sequence carries is dropped, padded into or left stale, one
fault a test."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import granitemoehybrid as ref
from generativeaiexamples_tpu.models import import_hf, llama
from generativeaiexamples_tpu.models.configs import (MODEL_REGISTRY,
                                                     LlamaConfig)
from generativeaiexamples_tpu.models.kv_cache import (HeadKV, RecurrentKV,
                                                      kv_cache_of)
from generativeaiexamples_tpu.ops.quant import is_quantized, quantize_params

# period 4, the attention layer at place 1: [S A S S] [S A S S] runs as
# a head of 1, one whole run of 3 and a tail of 2
MODEL = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=8,
    num_heads=4, num_kv_heads=2, head_dim=16, rms_norm_eps=1e-5,
    tie_word_embeddings=True, rope_layers=(0,), embed_scale=12.0,
    residual_multiplier=0.22, attention_multiplier=1 / 32,
    logits_divisor=8.0, full_attention_interval=4, full_attention_place=1,
    linear_num_key_heads=1, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, linear_decay="ssd", weight_init="unit_stream")
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


def fresh_pool(slots=2, pages=9):
    return llama.init_paged_kv_cache(CFG, pages, PAGE, jnp.float32,
                                     slots=slots)


TABLE = jnp.arange(1, 9)[None]


@jax.jit
def _chunk(p, pool, tok, start, valid):
    C = tok.shape[1]
    with HI():
        return llama.apply_prefill_paged(
            p, CFG, tok, start + jnp.arange(C)[None], pool, TABLE,
            valid[None], start // PAGE, with_logits=True,
            slots=jnp.asarray([1]))


@jax.jit
def _decode(p, pool, tok, at):
    with HI():
        return llama.apply_decode_paged(
            p, CFG, tok[None, None], at[None, None], pool, TABLE, at[None] + 1,
            TABLE[0, at // PAGE][None], (at % PAGE)[None],
            slots=jnp.asarray([1]))


def prefill(p, ids, n, C=32, pool=None, between=None):
    """``n`` prompt tokens through chunk programs of ``C``; ``between``
    edits the pool after the first chunk."""
    pool, outs = fresh_pool() if pool is None else pool, []
    for s0 in range(0, n, C):
        tok = jnp.where(jnp.arange(C)[None] + s0 < n,
                        jnp.pad(ids, ((0, 0), (0, C)))[:, s0:s0 + C], 0)
        lg, pool = _chunk(p, pool, tok, jnp.int32(s0),
                          jnp.int32(min(s0 + C, n)))
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
    decode steps through state, tail and the paged pool."""
    got, pool = prefill(p32, ids, 80)
    assert rel(got, want[:80]) < 2e-5
    for at in range(80, T):
        lg, pool = _decode(p32, pool, ids[0, at], jnp.int32(at))
        assert rel(lg[0], want[at:at + 1]) < 2e-5, at


def test_a_dense_cache_follows_the_reference(p32, ids, want):
    cache = llama.init_kv_cache(CFG, 1, 96, jnp.float32)
    assert set(cache) == {"k", "v", "s", "conv"}
    half = jax.jit(lambda tok, pos, cache: llama.apply(     # one trace
        p32, CFG, tok, pos, cache))
    with HI():
        a, cache = half(ids[:, :T // 2], jnp.arange(T // 2)[None], cache)
        b, cache = half(ids[:, T // 2:], jnp.arange(T // 2, T)[None], cache)
    assert rel(jnp.concatenate([a[0], b[0]]), want) < 2e-5


# ---------------------------------------------------------- what it is made of

def test_the_cache_is_a_state_beside_pages():
    kvc = kv_cache_of(CFG)
    assert isinstance(kvc, RecurrentKV) and isinstance(kvc.paged, HeadKV)
    pool = fresh_pool(slots=3)
    # a head's state (P, N): the 128-wide N of the published model on
    # the lanes; pages on the 2 attention layers only
    assert pool["s"].shape == (6, 3, 4, 8, 16)
    assert pool["s"].dtype == jnp.float32
    assert pool["conv"].shape == (6, 3, 3 * (32 + 2 * 16))
    assert pool["k"].shape[0] == 2
    big = kv_cache_of(MODEL_REGISTRY["granite-4.0-h-micro"])
    assert big.slot_bytes(2) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert big.slot_bytes(2) == 76_437_504              # 76.4 MB a slot
    assert big.step_kernel_supported()
    assert big._state_shapes(32)[0] == (36, 32, 64, 64, 128)
    assert big.scale == 1 / 64
    assert kv_cache_of(MODEL_REGISTRY["llama-2-7b-chat"]).scale is None


@pytest.mark.parametrize("name,full,periods,places", [
    ("granite-4.0-h-micro", [5, 15, 25, 35], (5, 3, 4),
     {0: 0, 4: 4, 6: 5, 14: 13, 16: 14, 39: 35}),
    ("qwen3-next-80b-a3b-instruct", list(range(3, 48, 4)), (-1, 12, 0),
     {0: 0, 2: 2, 4: 3, 46: 35}),
    ("ling-3.0-flash", list(range(5, 42, 6)), (3, 6, 0),
     {0: 0, 4: 4, 6: 5, 40: 34}),
])
def test_where_the_attention_layer_sits_is_one_stated_value(name, full,
                                                            periods, places):
    """``layer_full``, a stack's periods and a layer's place among its
    kind, for the attention layer in mid-period and at its end."""
    cfg = MODEL_REGISTRY[name]
    assert [i for i, f in enumerate(cfg.layer_full) if f] == full
    _, first, n = cfg.layer_stacks[-1]
    assert llama._stack_periods(cfg, first, n) == periods
    for layer, place in places.items():         # among the recurrent ones
        assert layer - cfg.full_before(layer) == place
    for place, layer in enumerate(full):        # among the attention ones
        assert cfg.full_before(layer) == place
        assert int(cfg.full_before(jnp.int32(layer))) == place


def test_the_toys_periods_run_head_whole_and_tail():
    assert CFG.layer_full == (0, 1, 0, 0, 0, 1, 0, 0)
    assert llama._stack_periods(CFG, 0, 8) == (1, 1, 2)


def test_the_draw_is_mamba_ssms(p32):
    lay = p32["layers"]
    A = np.exp(np.asarray(lay["ssd_A_log"]))
    step = np.log1p(np.exp(np.asarray(lay["ssd_dt_bias"])))
    assert A.min() >= 1.0 and A.max() <= 16.0 and A.std() > 1.0
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 1e-1 * 1.001
    np.testing.assert_array_equal(lay["ssd_D"], 1.0)
    assert lay["ssd_win"].shape == (6, 64, 32 + 64)
    assert lay["ssd_wdt"].shape == (6, 64, 4) and "lm_head" not in p32
    assert lay["wq"].shape[0] == 2 and lay["w_gate"].shape[0] == 8
    q = quantize_params(p32, "int8")["layers"]
    assert is_quantized(q["ssd_win"]) and is_quantized(q["ssd_wout"])
    assert not is_quantized(q["ssd_wdt"]) and not is_quantized(q["ssd_conv"])


@pytest.mark.parametrize("change,says", [
    (dict(full_attention_place=4), "full_attention_place"),
    (dict(linear_decay_floor=-5.0), "linear_decay is"),
    (dict(rope_layers=(0, 1)), "window or rope"),
    (dict(linear_decay="mamba"), "linear_decay is"),
], ids=lambda c: next(iter(c)) if isinstance(c, dict) else None)
def test_configurations_that_are_refused(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CFG, **change)


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
    _, let_in = _chunk(p32, fresh_pool(), tok, jnp.int32(0), jnp.int32(32))
    _, alone = _chunk(p32, fresh_pool(), ids[:, :32], jnp.int32(0),
                      jnp.int32(20))
    for leaf in ("s", "conv"):
        np.testing.assert_allclose(alone[leaf], pool[leaf], atol=1e-6)
        assert float(jnp.max(jnp.abs(let_in[leaf] - pool[leaf]))) > 1e-3


def test_an_idle_rows_state_is_left_bit_for_bit(p32, ids):
    """A decode round's idle row (a surplus step, an empty slot) leaves
    its slot's state and tail as they were, whatever they hold."""
    _, pool = prefill(p32, ids, 32)
    pool = {**pool, "s": pool["s"].at[:, 0].set(jnp.inf),
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


def test_the_decode_step_over_the_kernel_is_the_step(p32, ids, monkeypatch):
    """The carried-pool decode path with the Pallas step kernel over the
    state leaf (interpreted) against the jnp path, at widths the kernel
    takes (N = 128)."""
    cfg = dataclasses.replace(CFG, linear_key_head_dim=128, num_layers=4)
    p = llama.init_params(cfg, jax.random.key(2), jnp.float32)
    pool = llama.init_paged_kv_cache(cfg, 5, PAGE, jnp.float32, slots=2)
    pool = {**pool, "s": pool["s"] + 0.5, "conv": pool["conv"] + 0.25}
    assert kv_cache_of(cfg).step_kernel_supported()
    tok, at = jnp.asarray([[7], [9]]), jnp.asarray([[3], [17]])
    table = jnp.asarray([[1, 2], [3, 4]])
    args = (tok, at, pool, table, at[:, 0] + 1, jnp.asarray([1, 4]),
            jnp.asarray([3, 1]))
    active = jnp.asarray([True, False])
    with HI():
        want_l, want_pool = llama.apply_decode_paged(
            p, cfg, *args, use_kernel=False, active=active)
        monkeypatch.setattr(
            HeadKV, "kernel_supported", lambda self, page: True)
        got_l, got_pool = llama.apply_decode_paged(
            p, cfg, *args, use_kernel=True, active=active)
    assert rel(got_l[0], want_l[0]) < 2e-5
    np.testing.assert_allclose(got_pool["s"][:, 0], want_pool["s"][:, 0],
                               atol=1e-5)
    np.testing.assert_array_equal(got_pool["s"][:, 1], pool["s"][:, 1])


def test_heads_of_64_are_packed_two_a_lane_row():
    """Under lane-width pages a pool of 64-wide heads is built (L, N, KV
    / 2, page, 128), a reshape of a token's rows: the chunk program and
    the gathered decode read it as they read any pool, and the decode
    KERNEL (interpreted; the state's kernel beside it) takes a query
    beside zeros in the other head's half — all three the plain
    forward's logits, the two decode paths one pool."""
    cfg = dataclasses.replace(
        CFG, hidden_size=128, num_layers=4, num_heads=8, num_kv_heads=4,
        head_dim=64, attention_multiplier=1 / 64, linear_key_head_dim=128)
    page, n = 128, 200
    kvc = kv_cache_of(cfg)
    assert kvc.kernel_supported(page) and not kvc.kernel_supported(64)
    assert not HeadKV(cfg).kernel_supported(page)   # a sharded pool: as it was
    p = llama.init_params(cfg, jax.random.key(2), jnp.float32)
    pool = llama.init_paged_kv_cache(cfg, 5, page, jnp.float32, slots=2)
    assert pool["k"].shape == (1, 5, 2, 128, 128)
    assert llama.init_paged_kv_cache(cfg, 5, 64, jnp.float32)["k"].shape \
        == (1, 5, 4, 64, 64)
    with pytest.raises(NotImplementedError, match="packed heads"):
        HeadKV(cfg, pack_heads=True).init_pool(5, page, quantized=True)
    ids = jax.random.randint(jax.random.key(1), (1, 256), 3, 512)
    table = jnp.asarray([[1, 2], [3, 4]])
    with HI():
        want, _ = llama.apply(p, cfg, ids[:, :n + 2],
                              jnp.arange(n + 2)[None])
        tok = jnp.where(jnp.arange(256)[None] < n, ids, 0)
        got, pool = llama.apply_prefill_paged(
            p, cfg, tok, jnp.arange(256)[None], pool, table[:1],
            jnp.asarray([n]), jnp.int32(0), with_logits=True,
            slots=jnp.asarray([0]))
        assert rel(got[0, :n], want[0, :n]) < 2e-5
        active = jnp.asarray([True, False])
        # one traced program a path for the two steps
        step = {kern: jax.jit(
            lambda p, *a, kern=kern: llama.apply_decode_paged(
                p, cfg, *a, use_kernel=kern, active=active))
            for kern in (False, True)}
        for at in (n, n + 1):
            pos = jnp.asarray([[at], [0]])
            args = (jnp.asarray([[int(ids[0, at])], [5]]), pos, pool, table,
                    pos[:, 0] + 1, jnp.asarray([table[0, at // page], 0]),
                    jnp.asarray([at % page, 0]))
            gathered, a = step[False](p, *args)
            kernel, pool = step[True](p, *args)
            assert rel(gathered[0], want[0, at:at + 1]) < 2e-5
            assert rel(kernel[0], want[0, at:at + 1]) < 2e-5
            np.testing.assert_array_equal(a["k"][:, 1:3], pool["k"][:, 1:3])
            np.testing.assert_allclose(a["s"], pool["s"], atol=1e-5)


def test_an_engine_over_the_scan_kernel_follows_the_reference(monkeypatch):
    """The model at widths the scan kernel takes (one group, sixteen heads
    of 64 values over 128-lane states), a prompt of two chunks — the
    second from the state and tail the first left, and padded — through
    chunk programs whose scan is the kernel (interpreted here, armed as
    a TPU arms it) over ``x`` where the convolution left it, then a
    decode round: every served token is the reference's choice after the
    tokens before it, and every chunk program is counted."""
    from generativeaiexamples_tpu.engine.engine import (Engine, EngineConfig,
                                                        SamplingParams)
    from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
    from generativeaiexamples_tpu.ops import ssd
    model = {**MODEL, "num_layers": 4, "linear_num_value_heads": 16,
             "linear_value_head_dim": 64, "linear_key_head_dim": 128}
    cfg = LlamaConfig(**model)
    p = llama.init_params(cfg, jax.random.key(0), jnp.float32)
    monkeypatch.setattr(ssd, "scan_kernel_armed", ssd.scan_kernel_supported)
    traced, kernel = [], ssd.ssd_chunked_kernel
    monkeypatch.setattr(ssd, "ssd_chunked_kernel",
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
    assert (1, 64, 16 * 64 + 2 * 128) in traced     # the convolution's width
    assert stats["scan_kernel"] == 1 and stats["downgrades"] == 0
    assert stats["scan_kernel_chunks"] == stats["sched_chunk_programs"] == 2


def test_a_tpu_arms_the_scan_kernel_or_names_the_downgrade(monkeypatch):
    """``ProgramSpec.resolve`` on a TPU: the state-space scan's kernel
    armed where the shapes are one group of 64-value heads over 128-lane
    states in whole grid steps, and where they fall short (this file's
    8-value heads over 16 lanes) ONE downgrade by name, ``scan_kernel ->
    xla_chunked``."""
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
    wide = resolve(dataclasses.replace(
        CFG, linear_num_value_heads=16, linear_value_head_dim=64,
        linear_key_head_dim=128))
    assert wide.scan_kernel
    assert "scan_kernel" not in [d[0] for d in wide.downgrades]
    granite = resolve(dataclasses.replace(
        MODEL_REGISTRY["granite-4.0-h-micro"], num_layers=10))
    assert granite.scan_kernel and not granite.downgrades


# ------------------------------------------------------------------ the ends

def test_the_divisor_is_on_every_tails_row(p32, ids):
    """The logits over a stated constant, applied once on the normed
    row: ``unembed``, a vocabulary tile of the streams and the greedy
    head kernel over the TIED head all read the divided logits."""
    from generativeaiexamples_tpu.ops.sampling import pack_mask
    from generativeaiexamples_tpu.ops.head_argmax import greedy_head_argmax
    h = jax.random.normal(jax.random.key(5), (3, 64))
    plain = dataclasses.replace(CFG, logits_divisor=1.0)
    with HI():
        logits = llama.unembed(p32, CFG, h[None])[0]
        np.testing.assert_allclose(
            logits, llama.unembed(p32, plain, h[None])[0] / 8.0, rtol=1e-5)
        rows = llama.unembed_norm(p32, CFG, h)
        tile = llama.lm_head_tile(p32, CFG, rows, jnp.int32(128), 128)
        np.testing.assert_allclose(tile, logits[:, 128:256], rtol=1e-5,
                                   atol=1e-6)
        none = jnp.zeros((3, 512), bool)
        got = greedy_head_argmax(
            rows, llama.lm_head_subtree(p32), 512, rep_pen=jnp.ones((3,)),
            seen_words=pack_mask(none), banned_words=pack_mask(none[0]),
            interpret=True)
    assert "embed" in llama.lm_head_subtree(p32)
    np.testing.assert_array_equal(got, jnp.argmax(logits, -1))


# ---------------------------------------------------------------- the import

def published_tensors(p):
    """The tree under the published names (``GraniteMoeHybrid*``)."""
    lay, out = p["layers"], {"model.embed_tokens.weight": p["embed"],
                             "model.norm.weight": p["final_norm"]}
    full = recurrent = 0
    for i, attends in enumerate(CFG.layer_full):
        pre = f"model.layers.{i}."
        out[pre + "input_layernorm.weight"] = lay["attn_norm"][i]
        out[pre + "post_attention_layernorm.weight"] = lay["mlp_norm"][i]
        out[pre + "shared_mlp.input_linear.weight"] = jnp.concatenate(
            [lay["w_gate"][i].T, lay["w_up"][i].T])
        out[pre + "shared_mlp.output_linear.weight"] = lay["w_down"][i].T
        if attends:
            for ours, theirs in (("wq", "q"), ("wk", "k"), ("wv", "v"),
                                 ("wo", "o")):
                out[pre + f"self_attn.{theirs}_proj.weight"] = \
                    lay[ours][full].T
            full += 1
            continue
        g = recurrent
        out[pre + "mamba.in_proj.weight"] = jnp.concatenate(
            [lay["ssd_win"][g].T, lay["ssd_wdt"][g].T])
        out[pre + "mamba.conv1d.weight"] = lay["ssd_conv"][g][:, None, :]
        for ours, theirs in (("ssd_conv_b", "conv1d.bias"),
                             ("ssd_dt_bias", "dt_bias"),
                             ("ssd_A_log", "A_log"), ("ssd_D", "D"),
                             ("ssd_norm", "norm.weight")):
            out[pre + "mamba." + theirs] = lay[ours][g]
        out[pre + "mamba.out_proj.weight"] = lay["ssd_wout"][g].T
        recurrent += 1
    return {k: np.asarray(v) for k, v in out.items()}


def test_a_checkpoint_by_the_published_names_is_the_tree(p32):
    got = import_hf.params_from_named_tensors(
        iter(published_tensors(p32).items()), CFG, jnp.float32)
    assert jax.tree.structure(got) == jax.tree.structure(p32)
    jax.tree.map(np.testing.assert_array_equal, got, p32)
    with pytest.raises(import_hf.ModelLoadError, match="in_proj"):
        import_hf.params_from_named_tensors(
            iter(published_tensors(p32).items()),
            dataclasses.replace(CFG, linear_num_value_heads=2,
                                linear_value_head_dim=16), jnp.float32)


def test_the_published_keys_give_the_configuration_as_run():
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    got = import_hf.granitemoehybrid_config(
        config["published"], weight_init="unit_stream")
    assert got == LlamaConfig(**config["model"])
    assert got == MODEL_REGISTRY["granite-4.0-h-micro"]
    assert config["reduced"] == []
    for change, says in [
            (dict(num_local_experts=8), "num_local_experts=8"),
            (dict(position_embedding_type="rope"), "variant"),
            (dict(attention_bias=True), "attention_bias"),
            (dict(layer_types=["mamba", "attention"] * 20), None)]:
        hf = dict(config["published"], **change)
        if says is None:        # another period is another model, not refused
            assert import_hf.granitemoehybrid_config(
                hf).full_attention_interval == 2
            continue
        with pytest.raises(import_hf.ModelLoadError, match=says):
            import_hf.granitemoehybrid_config(hf)
