"""What a recurrent state does not forgive, a test each (the model and
its helpers are tests/test_recurrent_layers.py's): a chunk at position 0
starts from zeros whatever its slot held; tokens past a padded grant's
valid length leave state and tail alone; one program, two chunks and
chunks of unequal grants agree; idle rows of a decode step and rows by
slot harm nothing; the dense cache carries the state too."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.kv_cache import kv_cache_of

from test_recurrent_layers import (CFG, PAGE, TOL, chunk, fresh_pool, ids_of,
                                   rel)


@pytest.fixture(scope="module")
def p32():
    return llama.init_params(CFG, jax.random.key(0), jnp.float32)


# ------------------------------------------------------- the state's rules


def test_a_chunk_at_position_0_starts_from_zeros(p32):
    """Whatever the slot held: a cancelled request's state, NaN here."""
    ids = ids_of(2)
    clean, pool_clean = chunk(p32, fresh_pool(jnp.float32), ids, 0, 32, 32)
    dirty = fresh_pool(jnp.float32)
    dirty = dict(dirty, s=jnp.full_like(dirty["s"], jnp.nan),
                 conv=jnp.full_like(dirty["conv"], jnp.nan))
    got, pool = chunk(p32, dirty, ids, 0, 32, 32)
    np.testing.assert_array_equal(got, clean)
    for leaf in ("s", "conv"):
        np.testing.assert_array_equal(pool[leaf], pool_clean[leaf])
    # ... and a chunk that does NOT start there reads what is there
    later, _ = chunk(p32, dirty, ids, 32, 16, 16)
    assert not bool(jnp.all(jnp.isfinite(later)))


def test_padding_does_not_touch_the_state(p32):
    """Tokens past ``valid`` in a padded grant leave S and the
    convolution's tail as the last valid token left them — whatever the
    padding tokens are."""
    ids = ids_of(3)
    _, exact = chunk(p32, fresh_pool(jnp.float32), ids, 0, 32, 32)
    _, exact = chunk(p32, exact, ids, 32, 8, 8 + 8)   # page-aligned grant
    pool = fresh_pool(jnp.float32)
    _, pool = chunk(p32, pool, ids, 0, 32, 32)
    loud = ids.at[:, 40:].set(511)          # what lies past the valid 8
    tok = loud[:, 32:32 + 48]
    pos = (32 + jnp.arange(48))[None]
    _, padded = llama.apply_prefill_paged(
        p32, CFG, tok, pos, pool, jnp.arange(1, 8)[None],
        jnp.asarray([40]), jnp.int32(2))
    # to rounding: the two programs multiply 48 and 16 rows
    for leaf in ("conv", "s"):
        np.testing.assert_allclose(padded[leaf], exact[leaf], rtol=2e-5,
                                   atol=2e-6)


def test_one_program_two_chunks_unequal_grants_agree(p32):
    ids = ids_of(4)
    one, _ = chunk(p32, fresh_pool(jnp.float32), ids, 0, 80, 80)
    for cuts in (((0, 48, 48), (48, 32, 32)),
                 ((0, 16, 16), (16, 48, 48), (64, 16, 32))):
        pool, outs = fresh_pool(jnp.float32), []
        for start, n, grant in cuts:
            out, pool = chunk(p32, pool, ids, start, n, grant)
            outs.append(out)
        got = jnp.concatenate(outs)
        assert rel(got, one).max() <= TOL["float32"][1]


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel_interpreted"])
def test_idle_rows_of_a_decode_step_harm_nothing(p32, use_kernel):
    """Row 1 is idle (a slot mid-prefill, or a finished row's surplus
    step): its state and tail stay bit for bit, the live row's logits
    are those of the row alone."""
    # widths the two Pallas kernels take (interpreted here): the paged
    # attention's and the recurrence's own step over the state leaf
    cfg = dataclasses.replace(CFG, head_dim=128, linear_key_head_dim=128,
                              linear_value_head_dim=128,
                              linear_num_value_heads=8) \
        if use_kernel else CFG
    assert kv_cache_of(cfg).step_kernel_supported() == use_kernel
    p = llama.init_params(cfg, jax.random.key(0), jnp.float32) \
        if use_kernel else p32
    page = 128 if use_kernel else PAGE
    ids = ids_of(5, 2 * page)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]])
    pool = llama.init_paged_kv_cache(cfg, 7, page, jnp.float32, slots=2)
    tok, pos = ids[:, :page], jnp.arange(page)[None]
    fill = jax.jit(lambda p, pool, tbl, slots: llama.apply_prefill_paged(
        p, cfg, tok, pos, pool, tbl, jnp.asarray([page]), jnp.int32(0),
        slots=slots))       # one traced program for the two slots
    for slot in (0, 1):
        _, pool = fill(p, pool, table[slot:slot + 1], jnp.asarray([slot]))
    args = (jnp.asarray([[7], [9]]), jnp.asarray([[page], [0]]), pool, table,
            jnp.asarray([page + 1, 1]), jnp.asarray([2, 0]),
            jnp.asarray([0, 0]))
    both, after = llama.apply_decode_paged(
        p, cfg, *args, use_kernel=use_kernel,
        active=jnp.asarray([True, False]))
    for leaf in ("s", "conv"):
        np.testing.assert_array_equal(after[leaf][:, 1], pool[leaf][:, 1])
        assert not bool(jnp.all(after[leaf][:, 0] == pool[leaf][:, 0]))
    alone, _ = llama.apply_decode_paged(
        p, cfg, *(a[:1] if a is not pool else a for a in args),
        use_kernel=use_kernel, slots=jnp.asarray([0]))
    assert rel(both[0, 0][None], alone[0, 0][None]).max() <= 2e-5


def test_rows_keep_their_state_by_slot(p32):
    """A chunk program of two prompts, rows in slots (3, 1) of four: each
    row's logits are those of the prompt served alone."""
    a, b = ids_of(6, 32), ids_of(7, 32)
    pool = fresh_pool(jnp.float32, slots=4, pages=12)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]])
    slots = jnp.asarray([3, 1])
    outs = []
    rows = jax.jit(lambda p, *a: llama.apply_prefill_paged(
        p, CFG, *a, slots=slots))       # one traced program for both chunks
    for start in (0, 16):
        tok = jnp.concatenate([a, b])[:, start:start + 16]
        pos = jnp.broadcast_to(start + jnp.arange(16), (2, 16))
        h, pool = rows(
            p32, tok, pos, pool, tables, jnp.asarray([start + 16] * 2),
            jnp.asarray([start // PAGE] * 2))
        outs.append(llama.unembed(p32, CFG, h))
    got = jnp.concatenate(outs, axis=1)
    for row, ids in enumerate((a, b)):
        alone, _ = chunk(p32, fresh_pool(jnp.float32), ids, 0, 32, 32)
        assert rel(got[row], alone).max() <= TOL["float32"][1]
    # the untouched slots are untouched
    assert not bool(jnp.any(pool["s"][:, jnp.asarray([0, 2])]))


def test_the_dense_cache_carries_the_state(p32):
    """``apply`` over the dense cache (the one-shot admission, ``score``):
    a bucket padded past its length, then a second call from where the
    first stopped."""
    ids = ids_of(8, 48)
    want, _ = llama.apply(p32, CFG, ids, jnp.arange(48)[None])
    cache = llama.init_kv_cache(CFG, 1, 64, jnp.float32)
    padded = jnp.zeros((1, 32), jnp.int32).at[:, :20].set(ids[:, :20])
    first, cache = llama.apply(p32, CFG, padded, jnp.arange(32)[None], cache,
                               kv_valid_len=jnp.asarray([20]))
    rest, cache = llama.apply(p32, CFG, ids[:, 20:], jnp.arange(20, 48)[None],
                              cache, kv_valid_len=jnp.asarray([48]))
    got = jnp.concatenate([first[:, :20], rest], axis=1)
    assert rel(got[0], want[0]).max() <= TOL["float32"][1]
    nll = llama.score(p32, CFG, ids, chunk=16)
    full = llama.score(p32, CFG, ids, chunk=64)
    np.testing.assert_allclose(nll, full, rtol=1e-4, atol=1e-4)
