"""The sampled tail as one pass (ops/head_argmax.py
``sampled_head_stream`` under ops/fused_sampler.py
``head_kernel_sample``), interpreted on the CPU: sample-exact against the
materialised oracle that shares its noise layout
(``sample_reference_rows``), its samples follow the softmax, an engine
serves the oracle's tokens over it — and the greedy kernel, whose helpers
it shares, still lowers to the Mosaic program the parent commit's did."""

import base64
import dataclasses
import functools
import hashlib
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.ops import fused_sampler, head_argmax
from generativeaiexamples_tpu.ops.fused_sampler import (
    head_kernel_sample, row_gumbel, sample_reference_rows)
from generativeaiexamples_tpu.ops.quant import matmul_f32
from generativeaiexamples_tpu.ops.sampling import mask_words, pack_mask

from test_fused_sampler import _head_tree, _oracle_penalize, _tiny_engine

D, VOCAB, BLOCK = 64, 128 * 11, 256     # the last block is half past V
TILE, CAND_K = 352, 8


def _case(storage, rows, ban_rows, seed=0):
    """Rows, a head in ``storage`` and every penalty operand; ``logits``
    the materialised penalised logits the oracle takes."""
    ks = jax.random.split(jax.random.key(seed), 8)
    hn = jax.random.normal(ks[1], (rows, D), jnp.float32)
    head = 0.5 * jax.random.normal(ks[0], (D, VOCAB), jnp.float32)
    tree = _head_tree(storage.split("_")[0], head)
    rows_in = hn
    if storage == "int8_pre":   # the smoothing scale folds into the rows
        pre = 1.0 + jax.random.uniform(ks[7], (D,))
        tree = {"lm_head": dict(tree["lm_head"], pre_scale=pre)}
        rows_in = hn * pre
    seen = jax.random.bernoulli(ks[2], 0.3, (rows, VOCAB))
    banned = jax.random.bernoulli(
        ks[3], 0.05, (rows, VOCAB) if ban_rows else (VOCAB,))
    rep = 1.0 + jax.random.uniform(ks[4], (rows,))
    ban_tok = jax.random.randint(ks[5], (rows, 7), 0, VOCAB)
    ban_hit = jax.random.bernoulli(ks[6], 0.5, (rows, 7))
    plain = {k: ({n: a for n, a in v.items() if n != "pre_scale"}
                 if isinstance(v, dict) else v) for k, v in tree.items()}
    w = plain.get("lm_head")
    raw = matmul_f32(rows_in, w) if w is not None \
        else rows_in @ plain["embed"].T
    logits = _oracle_penalize(raw, seen, jnp.broadcast_to(banned, seen.shape),
                              rep, ban_tok, ban_hit)
    kw = dict(rep_pen=rep, seen_words=pack_mask(seen),
              banned_words=pack_mask(banned), ban_tok=ban_tok,
              ban_hit=ban_hit)
    return hn, tree, kw, logits


@functools.lru_cache(maxsize=None)
def _kernel_tail(cand_k=CAND_K):
    """One jitted tail an operand structure: the sampling parameters are
    values, so the cases of a storage share a program."""
    def tail(hn, tree, key, temp, top_k, top_p, kw):
        return head_kernel_sample(
            hn, tree, VOCAB, key=key, temp=temp, top_k=top_k, top_p=top_p,
            tile=TILE, cand_k=cand_k, stats=True, block=BLOCK,
            interpret=True, **kw)
    return jax.jit(tail)


# (temp, top_k, top_p) a row; the kept prefix fits CAND_K except where
# the case says otherwise
SAMPLING = {
    "temperature": ([0.7, 1.0, 1.6, 0.3, 2.0], [0] * 5, [0.0, 1.0, 0.0, 1.0,
                                                        0.0]),
    "top_k": ([0.7, 1.0, 1.6, 0.3, 2.0], [2, 8, 5, 3, 7], [0.0] * 5),
    "top_p": ([0.05, 0.1, 0.08, 0.12, 0.06], [0] * 5,
              [0.9, 0.5, 0.7, 0.95, 0.3]),
    # a flat distribution: 0.9 of the mass is hundreds of tokens wide,
    # so the carry caps the kept set at CAND_K (the candidate cap)
    "top_p_wider_than_cand_k": ([3.0] * 5, [0] * 5, [0.9] * 5),
    # temp <= 0 and top_k == 1 rows among sampled ones
    "mixed_greedy_rows": ([0.7, 0.0, 1.0, -1.0, 0.9], [0, 0, 1, 4, 6],
                          [0.9, 0.9, 0.0, 0.0, 0.5]),
}


@pytest.mark.parametrize("case", sorted(SAMPLING))
@pytest.mark.parametrize("storage", ["int8", "int8_pre", "raw", "tied"])
def test_sampled_kernel_is_its_oracle_token_for_token(storage, case):
    """``head_kernel_sample`` against ``sample_reference_rows`` over the
    same penalised logits and the same key: identical tokens over every
    head storage the kernel takes, a rung of rows, a vocabulary that is
    not a multiple of the block, the repetition penalty, per-row banned
    words and sequence bans — for pure temperature, top-k, top-p, a
    top-p set wider than the candidate carry (kept at ``cand_k``: the
    oracle told so through ``top_k``), and greedy rows in the batch."""
    hn, tree, kw, logits = _case(storage, 5, True)
    temp, top_k, top_p = (jnp.asarray(a, d) for a, d in zip(
        SAMPLING[case], (jnp.float32, jnp.int32, jnp.float32)))
    key = jax.random.key(11)
    got, resort = _kernel_tail()(hn, tree, key, temp, top_k, top_p, kw)
    cap_k = top_k
    if case == "top_p_wider_than_cand_k":
        cap_k = jnp.full_like(top_k, CAND_K)
    want = sample_reference_rows(logits, key, temp, cap_k, top_p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 0.0 <= float(resort) <= 1.0
    if case == "top_p_wider_than_cand_k":   # the cap did bind
        free = sample_reference_rows(logits, key, temp, top_k, top_p)
        assert (np.asarray(free) != np.asarray(want)).any()


@pytest.mark.parametrize("rows,ban_rows", [(1, False), (4, False),
                                           (16, True), (32, True)])
def test_sampled_kernel_rows_and_ban_shapes(rows, ban_rows):
    """One row, a rung of rows, more rows than one sublane tile; banned
    words shared ``(W,)`` and per row ``(rows, W)`` — and the noise a
    row draws does not depend on how many rows ride with it."""
    hn, tree, kw, logits = _case("int8", rows, ban_rows, seed=rows)
    temp = jnp.linspace(0.5, 1.5, rows)
    # untruncated rows, and kept prefixes that fit the carry
    r = jnp.arange(rows)
    top_k = jnp.where(r % 4 == 0, 0, 2 + r % 3 * 2).astype(jnp.int32)
    top_p = jnp.where(r % 2 == 0, 0.0, 0.8)
    key = jax.random.key(rows)
    got = head_kernel_sample(
        hn, tree, VOCAB, key=key, temp=temp, top_k=top_k, top_p=top_p,
        tile=TILE, cand_k=CAND_K, block=BLOCK, interpret=True, **kw)
    want = sample_reference_rows(logits, key, temp, top_k, top_p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(row_gumbel(key, rows + 16, VOCAB)[:rows]),
        np.asarray(row_gumbel(key, rows, VOCAB)))


@pytest.mark.parametrize("rule", ["temperature", "top_k", "greedy"])
def test_sampled_kernel_tie_rule(rule):
    """Equal logits: the lowest id wins the greedy carry (across two
    blocks), the candidate carry keeps ascending ids among equals (the
    oracle's stable sort), and an all-zero head — every logit ties with
    every other — still samples the oracle's token."""
    rows = 3
    hn = jnp.ones((rows, D), jnp.float32)
    tree = {"lm_head": jnp.zeros((D, VOCAB), jnp.float32)
            .at[:, jnp.asarray([300, 1100])].set(1.0)}
    zeros = jnp.zeros((rows, mask_words(VOCAB)), jnp.uint32)
    kw = dict(rep_pen=jnp.ones((rows,)), seen_words=zeros,
              banned_words=zeros)
    temp, top_k, top_p = {
        "temperature": (1.0, 0, 0.0), "top_k": (1.0, 4, 0.0),
        "greedy": (0.0, 0, 0.0)}[rule]
    temp, top_k, top_p = (jnp.full((rows,), temp), jnp.full(
        (rows,), top_k, jnp.int32), jnp.full((rows,), top_p))
    key = jax.random.key(5)
    got = head_kernel_sample(
        hn, tree, VOCAB, key=key, temp=temp, top_k=top_k, top_p=top_p,
        tile=TILE, cand_k=CAND_K, block=BLOCK, interpret=True, **kw)
    want = sample_reference_rows(hn @ tree["lm_head"], key, temp, top_k,
                                 top_p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if rule == "greedy":
        assert np.asarray(got).tolist() == [300] * rows
    if rule == "top_k":     # the kept four: the tied pair, then ids 0, 1
        assert set(np.asarray(got).tolist()) <= {300, 1100, 0, 1}


@pytest.mark.parametrize("top_k", [0, 4], ids=["softmax", "top4"])
def test_sampled_kernel_follows_the_softmax(top_k):
    """The realisation for a seed is the kernel's own, so the
    DISTRIBUTION is what is pinned: 1536 samples (32 rows of one
    distribution x 48 keys) of a 256-token vocabulary land on the
    softmax of the scaled logits — renormalised over the four likeliest
    under ``top_k`` 4 — within four standard errors a token."""
    rows, vocab, n_keys, temp = 32, 256, 48, 0.8
    ks = jax.random.split(jax.random.key(3), 2)
    row = jax.random.normal(ks[0], (D,), jnp.float32)
    hn = jnp.broadcast_to(row, (rows, D))
    head = 0.15 * jax.random.normal(ks[1], (D, vocab), jnp.float32)
    zeros = jnp.zeros((rows, mask_words(vocab)), jnp.uint32)

    @jax.jit
    def draw(key):
        return head_kernel_sample(
            hn, {"lm_head": head}, vocab, key=key,
            temp=jnp.full((rows,), temp),
            top_k=jnp.full((rows,), top_k, jnp.int32),
            top_p=jnp.zeros((rows,)), rep_pen=jnp.ones((rows,)),
            seen_words=zeros, banned_words=zeros, cand_k=CAND_K,
            block=128, interpret=True)

    toks = np.concatenate([np.asarray(draw(jax.random.key(100 + i)))
                           for i in range(n_keys)])
    n = toks.size
    scaled = np.asarray(row @ head, np.float64) / temp
    if top_k:
        scaled[np.argsort(-scaled)[top_k:]] = -np.inf
    p = np.exp(scaled - scaled.max())
    p /= p.sum()
    freq = np.bincount(toks, minlength=vocab) / n
    assert (p > 0.05).sum() >= 3            # a distribution worth testing
    sigma = np.sqrt(p * (1 - p) / n)
    assert np.all(np.abs(freq - p) <= 4 * sigma + 1.0 / n), (
        np.abs(freq - p).max())


def test_engine_serves_the_oracles_tokens_over_the_sampled_kernel():
    """End to end, the kernel interpreted: an engine whose sampled decode
    rounds take ``head_kernel_sample`` serves the tokens of the same
    engine with the noise-matched materialised oracle as its tail, under
    a temperature, top-k with top-p (a kept prefix the carry holds), a
    repetition penalty and a banned word — and counts every decode round
    in ``tail_kernel_rounds``."""
    from generativeaiexamples_tpu.engine import SamplingParams, programs
    from generativeaiexamples_tpu.ops.fused_sampler import _penalize_tile

    def oracle_tail(hn, head_tree, vocab, *, key, temp, top_k, top_p,
                    stats=False, **masks):
        logits = _penalize_tile(matmul_f32(hn, head_tree["lm_head"]),
                                jnp.int32(0), vocab, **masks)
        tok = sample_reference_rows(logits, key, temp, top_k, top_p)
        return (tok, jnp.float32(0.0)) if stats else tok

    sampling = SamplingParams(max_tokens=6, temperature=0.9, top_k=20,
                              top_p=0.9, ignore_eos=True,
                              repetition_penalty=1.3,
                              bad_words=["a"])
    prompts = [[5, 6, 7, 8], list(range(3, 43))]
    served, rounds = {}, {}
    for tail in ("kernel", "oracle"):
        eng = _tiny_engine("int8")
        # the engine's programs, over the tail meant
        eng.programs = programs.Programs(dataclasses.replace(
            eng.programs.spec, tail=programs.Tail(
                "kernel", eng.model_cfg, sample_kernel=functools.partial(
                    head_kernel_sample, interpret=True)
                if tail == "kernel" else oracle_tail)))
        # both prompts wait BEFORE the loop starts, so its first pull
        # admits them in one plan: a round's key is the engine's step
        # counter, which admissions share, so a sampled stream's tokens
        # follow from how the loop interleaved the two (ROADMAP D17) —
        # here it must interleave them the same way for both tails
        streams = [eng.submit(p, sampling) for p in prompts]
        eng.start()
        try:
            for stream in streams:
                for _ in stream:
                    pass
        finally:
            eng.stop()
        served[tail] = [stream.token_ids for stream in streams]
        stats = eng.stats       # settled: stop() joined loop and harvest
        rounds[tail] = stats["tail_kernel_rounds"]
        assert stats["tail_kernel"] == 1 and stats["downgrades"] == 0
        assert stats["tail_resort_pct_rounds"] == rounds[tail] > 0
    assert served["kernel"] == served["oracle"]
    assert all(len(t) == 6 for t in served["kernel"])
    assert len({tuple(t) for t in served["kernel"]}) == 2


def test_scan_engine_counts_no_tail_kernel_round():
    """Off the TPU the tails are the scan's: the armed flag and the
    rounds' counter both read 0, and that is no downgrade."""
    from generativeaiexamples_tpu.engine import SamplingParams
    eng = _tiny_engine("int8")
    eng.start()
    try:
        stream = eng.submit([5, 6, 7, 8], SamplingParams(
            max_tokens=4, temperature=0.8, top_k=0, ignore_eos=True))
        for _ in stream:
            pass
        stats = eng.stats
        assert stats["tail_kernel"] == 0 == stats["tail_kernel_rounds"]
        assert stats["downgrades"] == 0 and stats["rounds_completed"] > 0
    finally:
        eng.stop()


# ------------- the greedy kernel's Mosaic program, held to the parent's


def mosaic_text(fn, *args) -> str:
    """The Mosaic module of the one ``pallas_call`` in ``fn``, lowered
    for a TPU (no chip: nothing is compiled or run), printed WITHOUT
    source locations — the serialised module carries them, and a
    refactor that only moves lines must not move the digest."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    configs = re.findall(r'backend_config = "((?:[^"\\]|\\.)*)"', text)
    assert len(configs) == 1, len(configs)
    body = base64.b64decode(json.loads(configs[0].replace("\\22", '"'))[
        "custom_call_config"]["body"])
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True  # the versioned dialect's names
    with ctx:
        return ir.Module.parse(body).operation.get_asm(
            enable_debug_info=False)


def lower_greedy(storage):
    Dk, V, R = 128, 128 * 11, 5
    sds = jax.ShapeDtypeStruct
    tree = {"int8": {"lm_head": {"q": sds((Dk, V), jnp.int8),
                                 "scale": sds((V,), jnp.float32)}},
            "tied": {"embed": sds((V, Dk), jnp.bfloat16)},
            "raw": {"lm_head": sds((Dk, V), jnp.bfloat16)}}[storage]
    words = sds((R, mask_words(V)), jnp.uint32)

    def tail(hn, tree, rep, seen, banned, ban_tok, ban_hit):
        return head_argmax.greedy_head_argmax(
            hn, tree, V, rep_pen=rep, seen_words=seen, banned_words=banned,
            ban_tok=ban_tok, ban_hit=ban_hit, block=256)

    return mosaic_text(tail, sds((R, Dk), jnp.bfloat16), tree,
                       sds((R,), jnp.float32), words, words,
                       sds((R, 7), jnp.int32), sds((R, 7), jnp.bool_))


GREEDY_PINS = {
    "int8":
        "c74139f086abbc27fe0816a612c66179c0a8a5e40a352a6deb4237692e1ba926",
    "raw":
        "b0dca5da12144f7f7daf251cb417c682b1e868ae0bf188fbd4f3385b5e8c8323",
    "tied":
        "d690c8c4db6032e8ac197cf3c4f6fdf50733d44a7dda774fac20b4555fae5370",
}


@pytest.mark.parametrize("storage", sorted(GREEDY_PINS))
def test_greedy_kernel_mosaic_program_is_the_parents(storage):
    """``greedy_head_argmax`` shares its helpers with the sampled kernel
    since PR 44; its Mosaic program at a toy shape is still the one the
    tree before that gave (digests taken on the parent commit, 275faaa):
    the seven greedy cells' tails cannot have moved. Re-pin only on
    purpose (a new JAX re-words the text: re-pin from one commit)."""
    text = lower_greedy(storage)
    assert "greedy_head_argmax" in text
    assert hashlib.sha256(text.encode()).hexdigest() == GREEDY_PINS[storage]


def test_sampled_kernel_streams_the_head_once():
    """The sampled tail's program: ONE ``pallas_call`` whose operands
    hold the head, and nothing else in the tail touches the head — the
    selection scans the ``scaled`` array the kernel wrote."""
    hn, tree, kw, _ = _case("int8", 4, True)
    rows = hn.shape[0]
    jaxpr = jax.make_jaxpr(lambda hn, tree, kw: head_kernel_sample(
        hn, tree, VOCAB, key=jax.random.key(0), temp=jnp.ones((rows,)),
        top_k=jnp.zeros((rows,), jnp.int32), top_p=jnp.ones((rows,)),
        tile=TILE, cand_k=CAND_K, **kw))(hn, tree, kw).jaxpr
    head_shape = tree["lm_head"]["q"].shape
    users = [e.primitive.name for e in jaxpr.eqns
             if any(getattr(v.aval, "shape", None) == head_shape
                    for v in e.invars)]
    assert users == ["pallas_call"], users
    call = next(e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
    assert call.params["name"] == "sampled_head_stream"
    assert fused_sampler.choose_tile(VOCAB, TILE, sampled=True) == TILE
