"""Pallas paged-attention decode kernel vs the jnp oracle.

Runs the kernel in interpreter mode on the CPU test mesh — numerics are
exact there, so tolerances are tight. On TPU the same kernel runs compiled
(gated by models.llama.use_paged_kernel)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.ops.paged_attention import (
    kernel_supported, paged_attention_decode,
    paged_attention_decode_reference)

L, N, KV, hd, page = 2, 12, 4, 64, 16


def _setup(B, H, W, lengths, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    pool_k = jax.random.normal(ks[1], (L, N, KV, page, hd), dtype)
    pool_v = jax.random.normal(ks[2], (L, N, KV, page, hd), dtype)
    table = (jnp.arange(1, 1 + B * W, dtype=jnp.int32).reshape(B, W)
             % (N - 1) + 1)
    cur_k = jax.random.normal(ks[3], (B, KV, hd), dtype)
    cur_v = jax.random.normal(ks[4], (B, KV, hd), dtype)
    return q, pool_k, pool_v, table, jnp.asarray(lengths, jnp.int32), \
        cur_k, cur_v


@pytest.mark.parametrize("B,H,W,lengths", [
    (2, 8, 1, [5, 16]),            # single page, partial + full
    (2, 8, 2, [20, 32]),           # two pages
    (4, 8, 3, [5, 20, 33, 0]),     # ragged, incl. zero cached tokens
    (2, 4, 2, [17, 30]),           # MHA (G=1): H == KV
])
def test_kernel_matches_reference(B, H, W, lengths):
    q, pk, pv, table, lens, ck, cv = _setup(B, H, W, lengths)
    wp = jnp.zeros((B,), jnp.int32)          # write to trash: reads clean
    off = lens % page
    layer = jnp.zeros((1,), jnp.int32)
    ref = paged_attention_decode_reference(q, pk[0], pv[0], table, lens,
                                           ck, cv)
    out, _, _ = paged_attention_decode(q, pk, pv, table, lens, ck, cv,
                                       wp, off, layer, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_kernel_writes_row_in_place():
    """KV append contract (matches the engine's invariant wp ==
    table[pos // page]): the new row lands at (layer, wp, :, off); every
    row < length anywhere in the pool is preserved; rows >= length inside
    the written 8-row tile are DON'T-CARE (the zero-copy append sources
    preserved rows from the streamed window page instead of re-reading
    the write page, so dead rows may hold garbage — attention masks
    them). Covers off > 0 (write page == last streamed page) and
    off == 0 (fresh page, nothing to preserve)."""
    B, H, W = 3, 8, 3
    lengths = [20, 33, 16]                   # offs 4, 1, 0 (fresh page)
    q, pk, pv, table, lens, ck, cv = _setup(B, H, W, lengths)
    tbl = np.asarray(table)
    wp = jnp.asarray([tbl[b, lengths[b] // page] for b in range(B)],
                     jnp.int32)
    off = lens % page
    layer = jnp.ones((1,), jnp.int32)        # write layer 1
    before_k = np.asarray(pk)
    before_v = np.asarray(pv)
    _, new_k, new_v = paged_attention_decode(q, pk, pv, table, lens, ck, cv,
                                             wp, off, layer, interpret=True)
    nk = np.array(new_k)
    nv = np.array(new_v)
    tile = 8
    for b in range(B):
        w, o = int(wp[b]), int(off[b])
        np.testing.assert_allclose(nk[1, w, :, o, :], np.asarray(ck)[b],
                                   rtol=1e-6)
        np.testing.assert_allclose(nv[1, w, :, o, :], np.asarray(cv)[b],
                                   rtol=1e-6)
        # live rows below the new one inside the written tile survive
        t0 = o // tile * tile
        np.testing.assert_array_equal(nk[1, w, :, t0:o, :],
                                      before_k[1, w, :, t0:o, :])
        np.testing.assert_array_equal(nv[1, w, :, t0:o, :],
                                      before_v[1, w, :, t0:o, :])
    # everything outside the written tiles is untouched
    keep = np.ones(nk.shape, bool)
    for b in range(B):
        t0 = int(off[b]) // tile * tile
        keep[1, int(wp[b]), :, t0:t0 + tile, :] = False
    np.testing.assert_array_equal(nk[keep], before_k[keep])
    np.testing.assert_array_equal(nv[keep], before_v[keep])


def _quantize_pools(pk, pv):
    from generativeaiexamples_tpu.ops.kv_quant import quantize_rows
    kq, ks = quantize_rows(pk)
    vq, vs = quantize_rows(pv)
    return kq, vq, ks, vs


@pytest.mark.parametrize("B,H,W,lengths", [
    (2, 8, 2, [20, 32]),
    (4, 8, 3, [5, 20, 33, 0]),     # ragged, incl. zero cached tokens
])
def test_quant_kernel_matches_dequant_oracle(B, H, W, lengths):
    """int8-KV kernel == full-precision oracle run on the DEQUANTIZED
    pools (the quantization error itself is covered separately) — the
    kernel's scale folding introduces no additional error."""
    from generativeaiexamples_tpu.ops.kv_quant import dequantize_rows
    q, pk, pv, table, lens, ck, cv = _setup(B, H, W, lengths)
    kq, vq, ks, vs = _quantize_pools(pk, pv)
    wp = jnp.zeros((B,), jnp.int32)
    off = lens % page
    layer = jnp.zeros((1,), jnp.int32)
    ref = paged_attention_decode_reference(
        q, dequantize_rows(kq, ks, jnp.float32)[0],
        dequantize_rows(vq, vs, jnp.float32)[0], table, lens, ck, cv)
    out, *_ = paged_attention_decode(q, kq, vq, table, lens, ck, cv,
                                     wp, off, layer, pool_ks=ks,
                                     pool_vs=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_quant_kernel_append_row_and_scale():
    """The int8 append: new row quantized in-kernel with kv_quant
    semantics, its scale written through the streamed scale page, live
    rows + scales preserved, everything else untouched."""
    from generativeaiexamples_tpu.ops.kv_quant import quantize_rows
    B, H, W = 3, 8, 3
    lengths = [20, 33, 16]                   # offs 4, 1, 0 (fresh page)
    q, pk, pv, table, lens, ck, cv = _setup(B, H, W, lengths)
    kq, vq, ks, vs = _quantize_pools(pk, pv)
    tbl = np.asarray(table)
    wp = jnp.asarray([tbl[b, lengths[b] // page] for b in range(B)],
                     jnp.int32)
    off = lens % page
    layer = jnp.ones((1,), jnp.int32)
    before = [np.asarray(x) for x in (kq, vq, ks, vs)]
    _, nk, nv, nks, nvs = paged_attention_decode(
        q, kq, vq, table, lens, ck, cv, wp, off, layer,
        pool_ks=ks, pool_vs=vs, interpret=True)
    nk, nv, nks, nvs = (np.asarray(x) for x in (nk, nv, nks, nvs))
    for b in range(B):
        w, o = int(wp[b]), int(off[b])
        ek, es = quantize_rows(ck[b])
        np.testing.assert_array_equal(nk[1, w, :, o, :], np.asarray(ek))
        np.testing.assert_array_equal(
            nks[1, w, :, o].astype(np.float32),
            np.asarray(es).astype(np.float32))
        ev, evs = quantize_rows(cv[b])
        np.testing.assert_array_equal(nv[1, w, :, o, :], np.asarray(ev))
        np.testing.assert_array_equal(
            nvs[1, w, :, o].astype(np.float32),
            np.asarray(evs).astype(np.float32))
        # live rows + their scales below the new row survive
        t0 = o // 8 * 8
        np.testing.assert_array_equal(nk[1, w, :, t0:o, :],
                                      before[0][1, w, :, t0:o, :])
        np.testing.assert_array_equal(nks[1, w, :, :o],
                                      before[2][1, w, :, :o])
    # scale pages not written this step are untouched
    keep = np.ones(nks.shape, bool)
    for b in range(B):
        keep[1, int(wp[b])] = False
    np.testing.assert_array_equal(nks[keep], before[2][keep])
    np.testing.assert_array_equal(nvs[keep], before[3][keep])


def test_kv_quant_roundtrip_error_bound():
    """Per-row int8 quantization keeps relative row error ~<1%."""
    from generativeaiexamples_tpu.ops.kv_quant import (dequantize_rows,
                                                       quantize_rows)
    x = jax.random.normal(jax.random.key(3), (4, 16, 64), jnp.float32) * 5
    qx, s = quantize_rows(x)
    back = dequantize_rows(qx, s, jnp.float32)
    rel = np.abs(np.asarray(back - x)).max() / np.abs(np.asarray(x)).max()
    assert rel < 0.02, rel


@pytest.mark.parametrize("quant", [False, True])
def test_paged_prefix_attention_multiblock_matches_gather(quant):
    """The chunked-prefill streamed-prefix attention vs the gather
    formulation it replaced, with a prefix spanning SEVERAL stream
    blocks (block_pages=2 over a 7-page prefix) — the cross-block
    online-softmax rescale and nonzero dynamic-slice offsets are
    exactly the paths single-block engine tests never reach."""
    from generativeaiexamples_tpu.models.configs import LlamaConfig
    from generativeaiexamples_tpu.models.llama import \
        _paged_prefix_attention
    from generativeaiexamples_tpu.ops.attention import gqa_attention
    from generativeaiexamples_tpu.ops.kv_quant import (dequantize_rows,
                                                       quantize_rows)

    cfg = LlamaConfig(vocab_size=64, hidden_size=64, intermediate_size=64,
                      num_layers=1, num_heads=8, num_kv_heads=4,
                      head_dim=hd, max_position_embeddings=512)
    ks = jax.random.split(jax.random.key(9), 6)
    C = 32                                  # chunk (2 pages of 16)
    start = 7 * page                        # prefix: 7 pages -> 4 blocks
    Pw = 10                                 # window incl. chunk + slack
    valid = jnp.asarray([start + C - 5], jnp.int32)   # ragged tail
    pool_k = jax.random.normal(ks[0], (1, N, KV, page, hd), jnp.float32)
    pool_v = jax.random.normal(ks[1], (1, N, KV, page, hd), jnp.float32)
    table = jnp.asarray([[1, 3, 5, 7, 2, 4, 6, 8, 9, 10]], jnp.int32)
    q = jax.random.normal(ks[2], (1, C, 8, hd), jnp.float32)
    k_self = jax.random.normal(ks[3], (1, C, KV, hd), jnp.float32)
    v_self = jax.random.normal(ks[4], (1, C, KV, hd), jnp.float32)

    kc, vc, ksc, vsc = pool_k[0], pool_v[0], None, None
    if quant:
        kq, kscale = quantize_rows(pool_k[0])
        vq, vscale = quantize_rows(pool_v[0])
        kc = dequantize_rows(kq, kscale, jnp.float32)
        vc = dequantize_rows(vq, vscale, jnp.float32)

    # oracle: the old formulation — gather the whole window, insert the
    # chunk in-register, run the house gqa_attention
    kg = kc[table].swapaxes(2, 3).reshape(1, Pw * page, KV, hd)
    vg = vc[table].swapaxes(2, 3).reshape(1, Pw * page, KV, hd)
    kg = jax.lax.dynamic_update_slice(kg, k_self, (0, start, 0, 0))
    vg = jax.lax.dynamic_update_slice(vg, v_self, (0, start, 0, 0))
    positions = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
    want = gqa_attention(q, kg, vg, positions, valid)

    got = _paged_prefix_attention(
        q, k_self, v_self,
        kq if quant else kc, vq if quant else vc,
        kscale if quant else None, vscale if quant else None,
        table, jnp.asarray(start, jnp.int32), valid, page, cfg,
        block_pages=2)
    # rows past kv_valid_len are don't-care (engine discards them)
    n_ok = C - 5
    np.testing.assert_allclose(np.asarray(got)[0, :n_ok],
                               np.asarray(want)[0, :n_ok],
                               rtol=2e-5, atol=2e-5)


def test_kernel_supported_gate():
    assert kernel_supported(128, 32, 32, 128)
    assert not kernel_supported(128, 32, 32, 64)   # hd not lane-width
    assert not kernel_supported(64, 32, 32, 128)   # page not lane-width
    assert not kernel_supported(128, 30, 4, 128)   # H % KV != 0


def test_kernel_gate_is_off_on_cpu():
    """On the CPU test backend the jnp gather fallback runs (the engine
    parity tests in test_engine.py cover that path end-to-end)."""
    from generativeaiexamples_tpu.models.configs import LLAMA2_7B
    from generativeaiexamples_tpu.models.llama import use_paged_kernel
    assert not use_paged_kernel(LLAMA2_7B, 128)


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("group", [8, 4])
def test_slot_grouped_kernel_boundary_lengths(quant, group, monkeypatch):
    """Round-8 slot-grouped program parity at the nasty boundaries: the
    flat cross-slot page loop must locate slot/page exactly when slot
    lengths sit at k*page ± 1, when a ZERO-length slot sits mid-group
    (it contributes no pages — its neighbors' flat offsets shift), and
    across group boundaries (B=16 -> 2 programs at group 8, 4 programs
    at group 4). GQA G=2 throughout (H=8, KV=4); quant runs the int8-KV
    variant against the dequantized oracle."""
    from generativeaiexamples_tpu.ops.kv_quant import dequantize_rows
    from generativeaiexamples_tpu.ops.paged_attention import group_size

    monkeypatch.setenv("PAGED_GROUP_SLOTS", str(group))
    assert group_size(16) == group
    B, H, W = 16, 8, 3
    lengths = [15, 16, 17, 0, 31, 32, 33, 1,     # k*page ± 1, zero, one
               48, 0, 33, 16, 5, 47, 2, 32]      # full window, mid zeros
    q, pk, pv, table, lens, ck, cv = _setup(B, H, W, lengths, seed=11)
    wp = jnp.zeros((B,), jnp.int32)              # trash writes: reads clean
    off = lens % page
    layer = jnp.zeros((1,), jnp.int32)
    if quant:
        kq, vq, ks, vs = _quantize_pools(pk, pv)
        ref = paged_attention_decode_reference(
            q, dequantize_rows(kq, ks, jnp.float32)[0],
            dequantize_rows(vq, vs, jnp.float32)[0], table, lens, ck, cv)
        out, *_ = paged_attention_decode(q, kq, vq, table, lens, ck, cv,
                                         wp, off, layer, pool_ks=ks,
                                         pool_vs=vs, interpret=True)
    else:
        ref = paged_attention_decode_reference(q, pk[0], pv[0], table,
                                               lens, ck, cv)
        out, *_ = paged_attention_decode(q, pk, pv, table, lens, ck, cv,
                                         wp, off, layer, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_group_size_divisor_contract():
    """Programs are exact divisors of the batch: the largest divisor
    <= the cap, never a remainder group."""
    from generativeaiexamples_tpu.ops.paged_attention import group_size
    assert group_size(64) == 8
    assert group_size(16) == 8
    assert group_size(12) == 6    # 12 % 8 != 0 -> fall to 6
    assert group_size(7) == 7     # prime <= cap: whole batch, one program
    assert group_size(1) == 1
