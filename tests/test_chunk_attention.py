"""The chunk kernels (ops/chunk_attention.py) on the CPU, interpreted:
one update against a few lines of jnp; the prefix kernel against the scan
of such updates it replaced, to the bit; ``LatentKV.attend_prefix`` with
the kernels against its jnp blocks over prefix lengths, padded tables,
padded query rows, NaN wherever no query may read, unequal key and value
widths, float32 and bf16 rows; and the whole ``apply_prefill_paged`` of a
tiny latent configuration both ways against the plain reference's one
pass."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import deepseek_v3 as ref
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import MODEL_REGISTRY
from generativeaiexamples_tpu.models.kv_cache import LatentKV, kv_cache_of
from generativeaiexamples_tpu.ops import chunk_attention as ca
from generativeaiexamples_tpu.ops.quant import matmul as qmm, quantize_tensor

from test_latent_attention import CFG, PAGE  # every width a quarter lane

# the published head: keys 128 + 64 wide, values 128; two heads of it
WIDE = dataclasses.replace(CFG, num_heads=2, head_dim=192,
                           qk_nope_head_dim=128, qk_rope_head_dim=64,
                           v_head_dim=128)

# a case below reads the COMPILED chunk program's operation names, as a
# trace of the optimised program carries them
pytestmark = pytest.mark.usefixtures("full_optimisation")


def err(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


# --------------------------------------------------------- one update


@pytest.mark.parametrize("shared", [True, False], ids=["shared", "plain"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_one_update_is_the_online_softmax_step(dtype, shared):
    """Four blocks in a row through one carry: a whole block, a partial
    one, the causal diagonal, and a block no query may read (the carry
    passes through)."""
    H, C, T, dk, ds, dv = 8, 256, 256, 128, 64 if shared else 0, 64
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (H, C, dk + ds), dtype)
    k = jax.random.normal(ks[1], (T, H * dk), dtype)
    vt = jax.random.normal(ks[2], (H * dv, T), dtype)
    k_s = jax.random.normal(ks[3], (T, ds), dtype) if shared else None

    def step(carry, k0, limit, q0, causal):
        m, l, acc = carry
        kk = k.reshape(T, H, dk)
        if shared:
            kk = jnp.concatenate(
                [kk, jnp.broadcast_to(k_s[:, None], (T, H, ds))], -1)
        s = jnp.einsum("thd,hcd->htc", kk, q,
                       preferred_element_type=jnp.float32) * 0.1
        kpos = k0 + jnp.arange(T)[:, None]
        ok = jnp.broadcast_to(kpos < limit, (T, C))
        if causal:
            ok = ok & (kpos <= q0 + jnp.arange(C)[None])
        s = jnp.where(ok[None], s, ca.NEG)
        m_new = jnp.maximum(m, s.max(1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(ok[None], jnp.exp(s - m_new), 0.0)
        pv = jnp.einsum("hvt,htc->hvc", vt.reshape(H, dv, T),
                        p.astype(dtype), preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(1, keepdims=True), acc * alpha + pv

    got = want = ca.init_carry(H, C, dv)
    for k0, limit, q0, causal in [(0, 256, 512, False), (256, 300, 512, False),
                                  (512, 700, 512, True),
                                  (768, 700, 512, True)]:
        before = got
        got = ca.chunk_attention_update(
            q, k, vt, got, k0, limit, q0, scale=0.1, causal=causal,
            k_shared=k_s, interpret=True)
        want = step(want, k0, limit, q0, causal)
        for g, w in zip(got, want):
            assert bool(jnp.all(jnp.isfinite(g)))
            assert err(g, w) < (1e-5 if dtype == jnp.float32 else 1e-2)
    assert all(np.array_equal(a, b) for a, b in zip(got, before))
    out = ca.finish(got, dtype)
    assert out.shape == (C, H, dv) and bool(jnp.all(jnp.isfinite(out)))


def test_a_query_that_saw_no_key_gives_zeros():
    H, C, dv = 2, 128, 16
    out = ca.finish(ca.init_carry(H, C, dv), jnp.bfloat16)
    assert out.shape == (C, H, dv) and not bool(jnp.any(out))


def test_kernel_geometry():
    assert ca.kernel_supported(128, 128, 128, 64)
    assert not ca.kernel_supported(64, 128, 128, 64)      # half-lane pages
    assert not ca.kernel_supported(128, 32, 32, 16)       # CFG's heads
    kimi = kv_cache_of(MODEL_REGISTRY["kimi-k2-instruct"])
    assert kimi.prefix_kernel_supported(128)
    assert not kv_cache_of(CFG).prefix_kernel_supported(128)
    assert llama.use_prefix_kernel(MODEL_REGISTRY["kimi-k2-instruct"],
                                   128) is False          # the CPU
    # per-head K and V have no chunk kernel: nothing to want
    assert not llama.use_prefix_kernel(MODEL_REGISTRY["trinity-mini"], 128)


# ------------------------------------- every prefix block in one kernel


def scan_of_updates(q, wk_b, wv_b, pc, pr, tbl, start, keep, form, PB=2):
    """What ``attend_prefix`` ran a prefix through before the prefix
    kernel: a block's pages gathered, the rows at or past ``start``
    zeroed, ``wk_b`` / ``wv_b`` by ``matmul``, and ONE
    ``chunk_attention_update`` a live block. ``form``: how the rotary
    part meets the keys — beside them (``shared``) or copied into every
    head's (``folded``)."""
    H, C, dq = q.shape
    R, rope = pc.shape[2], pr.shape[1]
    nope, T, cd = dq - rope, PB * PAGE, q.dtype
    carry = ca.init_carry(H, C, jax.eval_shape(qmm, pc[0], wv_b).shape[1]
                          // H)
    for bi in range(tbl.shape[0] // PB):
        if bi * T >= start:
            continue
        pages = tbl[bi * PB:(bi + 1) * PB]
        mask = (bi * T + jnp.arange(T) < start)[:, None]
        cb = jnp.where(mask, pc[pages].reshape(T, R).astype(cd), 0)
        rb = jnp.where(mask, pr[pages].swapaxes(1, 2).reshape(T, rope)
                       .astype(cd), 0)
        kb, vb = qmm(cb, wk_b), qmm(cb, wv_b)
        if form == "folded":
            kb = jnp.concatenate(
                [kb.reshape(T, H, nope),
                 jnp.broadcast_to(rb[:, None], (T, H, rope))],
                axis=-1).reshape(T, H * dq)
            rb = None
        more = {} if keep is None else {"keep": keep[bi * T:(bi + 1) * T]}
        carry = ca.chunk_attention_update(
            q, kb, vb.T, carry, bi * T, start, start, scale=0.1,
            causal=False, k_shared=rb, interpret=True, **more)
    return carry


def prefix_case(geometry, dtype, storage, masked, dirty=None, start=None):
    """Operands of one prefix walk: a pool of two layers (the table is
    offset into the second), seven pages padded to four blocks of two by
    the trash page; with ``dirty``, every pool row at or past ``start``
    and the whole trash page hold it."""
    nope, rope, dv, _ = GEOMETRY[geometry]
    H, C, R, n_pages = 4, 128, 128, 12
    ks = jax.random.split(jax.random.key(nope + dv), 6)
    pc = jax.random.normal(ks[0], (2 * n_pages, PAGE, R), dtype)
    pr = jax.random.normal(ks[1], (2 * n_pages, rope, PAGE), dtype)
    table = TABLE + [0]
    if dirty is not None:
        pos = np.full((2 * n_pages, PAGE), 1 << 30)
        for i, pg in enumerate(TABLE):
            pos[n_pages + pg] = i * PAGE + np.arange(PAGE)
        stale = jnp.asarray(pos >= start)
        pc = jnp.where(stale[:, :, None], dirty, pc)
        pr = jnp.where(stale[:, None, :], dirty, pr)
    wk = jax.random.normal(ks[2], (R, H * nope), dtype) * R ** -.5
    wv = jax.random.normal(ks[3], (R, H * dv), dtype) * R ** -.5
    if storage == "int8":
        wk, wv = quantize_tensor(wk), quantize_tensor(wv)
    q = jax.random.normal(ks[4], (H, C, nope + rope), dtype)
    keep = None
    if masked:
        keep = jax.random.uniform(ks[5], (len(table) * PAGE, C)) < 0.3
        # query 5 keeps nothing at all, query 7 nothing of the first block
        keep = keep.at[:, 5].set(False).at[:2 * PAGE, 7].set(False)
        keep = keep.astype(jnp.float32)
    return q, wk, wv, pc, pr, jnp.asarray(table, jnp.int32) + n_pages, keep


#: nope, rope, value width, and how the replaced scan handed the rotary
#: part to its kernel: the three forms that run on the chip
GEOMETRY = {"192+64_folded": (192, 64, 256, "folded"),
            "128+64_shared": (128, 64, 128, "shared"),
            "192_plain_values_128": (128, 64, 128, "folded")}


@pytest.mark.parametrize("masked", [False, True], ids=["all", "keep"])
@pytest.mark.parametrize("storage", ["int8", "raw"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_prefix_kernel_is_the_scan_of_updates_to_the_bit(geometry, dtype,
                                                         storage, masked):
    """No prefix, part of a block, blocks and a part, and a prefix that
    reaches into the block the trash page pads: ``m``, ``l`` and ``acc``
    of ``chunk_attention_prefix`` equal the scan's bit for bit."""
    *ops, keep = prefix_case(geometry, dtype, storage, masked)
    for start in (0, 128, 640, 896):
        want = scan_of_updates(*ops, start, keep, GEOMETRY[geometry][3])
        got = ca.chunk_attention_prefix(
            *ops, jnp.int32(start), scale=0.1, block_pages=2, keep=keep,
            interpret=True)
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == w.dtype
            assert np.array_equal(np.asarray(g), np.asarray(w)), start
    if masked:      # the query that keeps nothing saw no key
        m, l, acc = got
        assert float(l[:, 0, 5].max()) == 0 and not bool(acc[:, :, 5].any())


@pytest.mark.parametrize("masked", [False, True], ids=["all", "keep"])
def test_prefix_kernel_reads_nothing_at_or_past_start(masked):
    """NaN in every pool row at or past ``start`` — the rest of the last
    live block's pages, the chunk's own stale pages — and in the whole
    trash page: every output finite, and equal to the clean pool's."""
    for start in (0, 200, 896):
        *ops, keep = prefix_case("128+64_shared", jnp.bfloat16, "int8",
                                 masked, dirty=jnp.nan, start=start)
        got = ca.chunk_attention_prefix(
            *ops, jnp.int32(start), scale=0.1, block_pages=2, keep=keep,
            interpret=True)
        *ops, keep = prefix_case("128+64_shared", jnp.bfloat16, "int8",
                                 masked, dirty=0.0, start=start)
        clean = ca.chunk_attention_prefix(
            *ops, jnp.int32(start), scale=0.1, block_pages=2, keep=keep,
            interpret=True)
        for g, c in zip(got, clean):
            assert bool(jnp.all(jnp.isfinite(g)))
            assert np.array_equal(np.asarray(g), np.asarray(c))


def test_prefix_kernel_lies_under_the_attn_scope():
    """In a compiled chunk program every operation of the prefix kernel
    carries the scope the benchmark's ``prefill_attn_ms_per_ktok`` sums
    (its data file's regular expression, over the scope path a trace
    rebuilds from ``op_name``)."""
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "layer_metrics",
                           "prefill_attn_ms_per_ktok.json")) as f:
        scope = re.compile(json.load(f)["args"]["scope"])
    p = jax.eval_shape(lambda k: llama.init_params(CFG, k, jnp.float32),
                       jax.random.key(0))
    pool = jax.eval_shape(
        lambda: llama.init_paged_kv_cache(CFG, 6, PAGE, jnp.float32))
    z = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731

    def chunk(p, pool, tok, pos, table, sp):
        return llama.apply_prefill_paged(p, CFG, tok, pos, pool, table,
                                         pos[:, -1] + 1, sp, use_kernel=True)

    text = jax.jit(chunk).lower(p, pool, z(1, PAGE), z(1, PAGE), z(1, 4),
                                z()).compile().as_text()
    # (a reduction's own little computation is named from the kernel
    # down; everything that runs is named from the program down)
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if n.startswith("jit(chunk)") and "chunk_attn_prefix" in n]
    assert len(names) > 50
    for n in names:
        assert scope.search(n[:n.index("chunk_attn_prefix")].rstrip("/")), n


# ----------------------------------------- under LatentKV.attend_prefix


def attend_case(cfg, dtype, C, start, valid, table, dirty, block_pages=2,
                layer=1):
    """``attend_prefix`` both ways over a pool whose pages past the
    prefix — the chunk's own stale pages, the trash page — hold ``dirty``.
    Returns (jnp, kernel)."""
    kvc = kv_cache_of(cfg)
    assert isinstance(kvc, LatentKV)
    H, R, rope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    ks = jax.random.split(jax.random.key(start + C), 7)
    n_pages = 12
    pool = {"c": jax.random.normal(ks[0], (2, n_pages, 1, PAGE, R), dtype),
            "r": jax.random.normal(ks[1], (2, n_pages, 1, rope, PAGE),
                                   dtype)}
    # logical position of every row of a physical page under ``table``
    pos = np.full((n_pages, PAGE), 1 << 30)
    for i, pg in enumerate(table):
        if pg:
            pos[pg] = i * PAGE + np.arange(PAGE)
    stale = jnp.asarray(pos >= start)
    pool = {"c": jnp.where(stale[None, :, None, :, None], dirty, pool["c"]),
            "r": jnp.where(stale[None, :, None, None, :], dirty, pool["r"])}
    lp = {"wk_b": jax.random.normal(ks[2], (R, H * nope), dtype) * R ** -.5,
          "wv_b": jax.random.normal(ks[3], (R, H * vd), dtype) * R ** -.5}
    q = jax.random.normal(ks[4], (1, C, H, nope + rope), dtype)
    c = jax.random.normal(ks[5], (1, C, R), dtype)
    k_r = jax.random.normal(ks[6], (1, C, rope), dtype)
    out = [kvc.attend_prefix(
        q, c, k_r, lp, pool, jnp.asarray([table], jnp.int32),
        jnp.int32(start), jnp.asarray([valid], jnp.int32), layer,
        block_pages=block_pages, use_kernel=use_kernel)
        for use_kernel in (False, True)]
    assert out[0].shape == out[1].shape == (1, C, H, vd)
    return out


TABLE = [3, 5, 1, 7, 9, 2, 4]       # 7 pages: 3.5 blocks of two, padded


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("start", [0, 128, 256, 640],
                         ids=["no_prefix", "partial_block", "one_block",
                              "several_blocks"])
def test_prefix_lengths_and_a_table_padded_past_the_extent(start, dtype,
                                                           tol):
    """The table's 7 pages are padded to 4 blocks of two with page 0, the
    trash page; it and every page at or past ``start`` (the chunk's own
    stale pages among them) hold NaN."""
    C = 128
    a, b = attend_case(CFG, dtype, C, start, start + C, TABLE, jnp.nan)
    assert bool(jnp.all(jnp.isfinite(a))) and bool(jnp.all(jnp.isfinite(b)))
    assert err(a, b) < tol
    # ... and what lies past the prefix moves nothing
    clean = attend_case(CFG, dtype, C, start, start + C, TABLE, 0.0)[1]
    assert np.array_equal(np.asarray(b, np.float32),
                          np.asarray(clean, np.float32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_keys_192_wide_values_128_wide(dtype, tol):
    """The published head (nope 128 | rope 64, values 128), a chunk of two
    query tiles' worth of keys (two self blocks at C = 1024 are the
    benchmark's logits check; here C = 256 in blocks of one page)."""
    a, b = attend_case(WIDE, dtype, 256, 384, 384 + 256, TABLE, jnp.nan,
                       block_pages=1)
    assert bool(jnp.all(jnp.isfinite(b))) and err(a, b) < tol


def test_a_last_chunk_with_padded_rows():
    """70 of 128 rows hold a token: the rest are padding past
    ``kv_valid_len``, which no row may read as a key."""
    start, C, valid = 256, 128, 256 + 70
    a, b = attend_case(CFG, jnp.float32, C, start, valid, TABLE, jnp.nan)
    assert bool(jnp.all(jnp.isfinite(b))) and err(a, b) < 2e-5
    # nothing to read at all (an empty slot's shape): zeros, not NaN
    a, b = attend_case(CFG, jnp.float32, C, 0, 0, TABLE, jnp.nan)
    assert not bool(jnp.any(a)) and not bool(jnp.any(b))


# ------------------------------------------- the whole chunked prefill


@pytest.fixture(scope="module")
def built():
    T = 300
    p = llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (1, T), 0, CFG.vocab_size)
    want = ref.forward(p, dataclasses.asdict(CFG), ids, list(range(T)))
    return p, ids, jnp.arange(T)[None], want


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_chunked_prefill_matches_the_one_full_pass(built, use_kernel):
    """Two 128-token chunks through the latent pool, the second reading
    the first back from it, the trash page full of NaN: within the limit
    tests/test_latent_attention.py holds the jnp blocks to."""
    p, ids, pos, want = built
    pool = llama.init_paged_kv_cache(CFG, 6, PAGE, jnp.float32)
    pool = jax.tree.map(
        lambda a: jnp.full_like(a, jnp.nan).at[:, 1:].set(0), pool)
    table = jnp.array([[1, 2, 3, 0]])
    outs = []
    with jax.default_matmul_precision("highest"):
        # one traced program for the two chunks
        chunk = jax.jit(lambda p, *a: llama.apply_prefill_paged(
            p, CFG, *a, with_logits=True, use_kernel=use_kernel))
        for c0 in range(0, 256, PAGE):
            logits, pool = chunk(
                p, ids[:, c0:c0 + PAGE], pos[:, c0:c0 + PAGE], pool,
                table, jnp.array([c0 + PAGE]), jnp.int32(c0 // PAGE))
            outs.append(logits[0])
    got = jnp.concatenate(outs)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert err(got, want[:256]) < 5e-5
