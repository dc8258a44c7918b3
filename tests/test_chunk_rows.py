"""``apply_prefill_paged`` over several prompts' chunks at once (the
engine's grouped chunk program): R rows, each a different prompt at its
own start, length and block table — the tables at ONE width, wider than
any row needs —, against the R single-row calls it stands for: the
hidden states and the WHOLE pool. Every pool row no query may read holds
NaN where a float can (the trash page, each row's own stale pages, pages
nobody holds: PERF.md section 7 row 1), so a row that read past its
start would say so.

``HeadKV`` with window and global layers and dropless experts, bf16 and
int8 KV; ``LatentKV`` with the chunk kernel interpreted and with the jnp
update; a model of two layer stacks (a leading dense layer, then experts
beside a shared expert).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig

import test_afmoe_layers as afmoe_t
import test_latent_attention as latent_t
from test_prefill_pool_in_place import CFGS as HEAD_CFGS, bits

# name -> (configuration, page, chunk tokens, parameter dtype)
CASES = {
    "plain": (HEAD_CFGS["plain"], 16, 32, jnp.bfloat16),
    "kinds": (HEAD_CFGS["kinds"], 16, 32, jnp.bfloat16),
    "two_stacks": (afmoe_t.CFG, afmoe_t.PAGE, afmoe_t.PAGE, jnp.float32),
    "latent": (latent_t.CFG, latent_t.PAGE, latent_t.PAGE, jnp.float32),
}
# a row's start in CHUNKS (0: a prompt's first chunk, no prefix to read)
# and the tokens of its ragged end; row 1's prefix passes one stream
# block of HeadKV's reader at a 16-token page (8 pages)
ROWS = [(0, 0), (5, 3), (2, 0), (1, 7)]


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    cfg, page, C, dtype = CASES[request.param]
    return (request.param, cfg, page, C,
            llama.init_params(cfg, jax.random.key(3), dtype=dtype))


def layout(R, page, C):
    """R rows' tables at one width and the pool that holds them:
    ``(table (R, P), start (R,), valid (R,), n_pages, readable)``, the
    last the pages some row reads as its prefix."""
    per = C // page
    starts = [s * C for s, _ in ROWS[:R]]
    valid = [s * C + C - cut for s, cut in ROWS[:R]]
    width = max(starts) // page + per + 3      # past every row's extent
    n_pages = 1 + sum(s // page + per for s in starts) + 2
    # physical pages out of order, page 0 the trash page
    free = list(np.random.default_rng(5).permutation(
        np.arange(1, n_pages)))
    table = np.zeros((R, width), np.int32)
    readable = []
    for r, s in enumerate(starts):
        own = [int(free.pop()) for _ in range(s // page + per)]
        table[r, :len(own)] = own
        readable += own[:s // page]
    return (jnp.asarray(table), jnp.asarray(starts, jnp.int32),
            jnp.asarray(valid, jnp.int32), n_pages, np.asarray(readable))


def filled_pool(cfg, n_pages, page, dtype, quantized, readable, fill):
    """Seeded values in the pages a row reads back; ``fill`` everywhere
    else a float can hold it (int8 rows keep their seeded values: their
    scales carry it)."""
    pool = llama.init_paged_kv_cache(cfg, n_pages, page, dtype,
                                     quantized=quantized)
    keys = jax.random.split(jax.random.key(7), len(pool))
    keep = np.zeros((n_pages,), bool)
    keep[readable] = True
    for key, (name, leaf) in zip(keys, pool.items()):
        if leaf.dtype == jnp.int8:
            pool[name] = jax.random.randint(key, leaf.shape, -127, 128,
                                            jnp.int8)
            continue
        scale = 0.02 if name in ("ks", "vs") else 1.0
        seeded = (scale * jax.random.normal(key, leaf.shape)
                  ).astype(leaf.dtype)
        mask = jnp.asarray(keep).reshape((1, -1) + (1,) * (leaf.ndim - 2))
        pool[name] = jnp.where(mask, seeded, jnp.asarray(fill, leaf.dtype))
    return pool


def close(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.array_equal(np.isnan(got), np.isnan(want))
    scale = max(1.0, float(np.nanmax(np.abs(want))))
    assert float(np.nanmax(np.abs(got - want), initial=0.0)) <= tol * scale


def run_case(cfg, params, page, C, R, *, quantized=False, use_kernel=None,
             pool_dtype=jnp.bfloat16, tol=2e-2):
    table, start, valid, n_pages, readable = layout(R, page, C)
    tokens = jax.random.randint(jax.random.key(11), (R, C), 3,
                                cfg.vocab_size)
    positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]

    @jax.jit
    def chunk(p, pool, tok, pos, tbl, v, sp):
        return llama.apply_prefill_paged(p, cfg, tok, pos, pool, tbl, v, sp,
                                         use_kernel=use_kernel)

    def grouped(pool):
        return chunk(params, pool, tokens, positions, table, valid,
                     start // page)

    def singles(pool):
        hs = []
        for r in range(R):
            h, pool = chunk(params, pool, tokens[r:r + 1],
                            positions[r:r + 1], table[r:r + 1],
                            valid[r:r + 1], start[r] // page)
            hs.append(h)
        return jnp.concatenate(hs), pool

    def make(fill):
        return filled_pool(cfg, n_pages, page, pool_dtype, quantized,
                           readable, fill)

    dirty = make(jnp.nan)
    h, pool = grouped(dirty)
    h_want, pool_want = singles(dirty)
    assert h.shape == (R, C, cfg.hidden_size)
    # the canary: nothing a row may not read reaches any of its queries
    assert bool(jnp.all(jnp.isfinite(h.astype(jnp.float32))))
    close(h, h_want, tol)
    assert set(pool) == set(dirty)
    for name in pool:
        close(pool[name], pool_want[name], tol)
    # every row's own pages were written in every layer, no other page
    first = next(iter(pool))
    changed = np.any(bits(pool[first]) != bits(dirty[first]),
                     axis=tuple(range(2, pool[first].ndim)))
    own = np.concatenate([
        np.asarray(table[r, int(start[r]) // page:
                         int(start[r]) // page + C // page])
        for r in range(R)])
    assert changed[:, own].all()
    assert changed.sum() == changed.shape[0] * len(own)
    # ... and with zeros where the NaN was, the same to the bit
    h_clean, _ = grouped(make(0.0))
    assert np.array_equal(np.asarray(h, np.float32),
                          np.asarray(h_clean, np.float32))


@pytest.mark.parametrize("R", [2, 4])
def test_rows_equal_their_single_calls(case, R):
    name, cfg, page, C, params = case
    f32 = CASES[name][3] == jnp.float32
    run_case(cfg, params, page, C, R,
             pool_dtype=jnp.float32 if f32 else jnp.bfloat16,
             tol=2e-5 if f32 else 2e-2)


@pytest.mark.parametrize("R", [2, 4])
@pytest.mark.parametrize("arch", ["plain", "kinds"])
def test_rows_over_an_int8_pool(arch, R):
    cfg, page, C, dtype = CASES[arch]
    params = llama.init_params(cfg, jax.random.key(3), dtype=dtype)
    run_case(cfg, params, page, C, R, quantized=True)


@pytest.mark.parametrize("R", [2, 4])
def test_latent_rows_through_the_interpreted_kernel(R):
    cfg, page, C, dtype = CASES["latent"]
    params = llama.init_params(cfg, jax.random.key(3), dtype=dtype)
    run_case(cfg, params, page, C, R, use_kernel=True,
             pool_dtype=jnp.float32, tol=2e-5)


def test_capacity_routing_is_refused_over_rows():
    """Capacity is a function of the tokens routed together: several
    prompts in one call would change which assignments drop."""
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=64,
                      num_layers=1, num_heads=4, num_kv_heads=2, head_dim=32,
                      num_experts=4, num_experts_per_tok=2, moe_impl="sparse")
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)
    pool = llama.init_paged_kv_cache(cfg, 4, 16, jnp.bfloat16)
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    with pytest.raises(ValueError, match="capacity"):
        llama.apply_prefill_paged(params, cfg, z(2, 16), z(2, 16), pool,
                                  z(2, 2), z(2), z(2))
    h, _ = llama.apply_prefill_paged(params, cfg, z(1, 16), z(1, 16), pool,
                                     z(1, 2), z(1), z())
    assert h.shape == (1, 16, 64)
