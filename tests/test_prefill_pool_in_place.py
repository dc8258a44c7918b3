"""``apply_prefill_paged`` reads the KV pool whole and in place — a
layer's prefix blocks gathered by (layer, page) — and must return what
it returned while the layer scan still sliced the pool: the hidden
states and the WHOLE pool, to the bit on the CPU, against a formulation
written out here that takes ``pool[l]`` a layer explicitly. Plain and
window / no-RoPE / dropless-expert models, bf16 and int8 KV, a prompt's
first chunk and a later one whose prefix spans two stream blocks; the
trash page holds NaN throughout (PERF.md section 7 row 1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.ops.kv_quant import quantize_rows
from generativeaiexamples_tpu.ops.rope import rope_frequencies

PAGE, N, C = 16, 24, 32         # a chunk is two pages
BASE = dict(vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2,
            head_dim=32, max_position_embeddings=1024)
CFGS = {
    "plain": LlamaConfig(intermediate_size=128, num_layers=3, **BASE),
    # one period: a global layer without RoPE, three that attend 40 keys
    "kinds": LlamaConfig(
        intermediate_size=32, num_layers=4, num_experts=4,
        num_experts_per_tok=2, moe_impl="dropless", mlp="relu_glu",
        router_input="block_input", sliding_window=40,
        window_layers=[0, 1, 1, 1], rope_layers=[0, 1, 1, 1],
        weight_init="unit_stream", **BASE),
}
# a prompt's first chunk, and one behind a 12-page prefix: two stream
# blocks of eight pages, the second shared with the chunk's own stale
# pages and the table's padding (the trash page)
STARTS = {"first": 0, "later": 12 * PAGE}


def sliced_prefill(params, cfg, tokens, positions, pool, table, valid,
                   start_page):
    """One chunk with layer ``l`` attending ``pool[l]``, and its K/V
    written a layer and a page at a time."""
    L = cfg.num_layers
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta,
                                cfg.rope_scaling_factor)
    start = positions[0, 0]
    layers, held = llama.scan_layers(params, cfg)

    def layer(h, xs):
        lp, l = xs
        lp = {**lp, **held}
        one = {name: leaf[l] for name, leaf in pool.items()}

        def attend(q, k, v):
            attn = llama._paged_prefix_attention(
                q, k, v, one["k"], one["v"], one.get("ks"), one.get("vs"),
                table, start, valid, PAGE, cfg, window=lp.get("window"))
            return attn, (k[0], v[0])

        return llama.decoder_layer(h, lp, cfg, positions, inv_freq, valid,
                                   attend=attend)

    h, new = jax.lax.scan(layer, llama._embed(params, tokens),
                          (layers, jnp.arange(L)))
    new = dict(zip("kv", new))                  # (L, C, KV, hd)
    if "ks" in pool:
        for name in "kv":
            new[name], new[name + "s"] = quantize_rows(new[name])
    out = dict(pool)
    for l in range(L):
        for i in range(C // PAGE):
            dest = table[0, start_page + i]
            for name, rows in new.items():
                rows = rows[l, i * PAGE:(i + 1) * PAGE].swapaxes(0, 1)
                out[name] = out[name].at[l, dest].set(
                    rows.astype(out[name].dtype))
    return h, out


def bits(x):
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[x.itemsize])


def filled_pool(cfg, kv, trash):
    """Every page holds seeded values (a prefix, and what stale pages
    hold); the trash page holds ``trash`` where a float can."""
    pool = llama.init_paged_kv_cache(cfg, N, PAGE, jnp.bfloat16,
                                     quantized=kv == "int8")
    keys = jax.random.split(jax.random.key(7), len(pool))
    for key, (name, leaf) in zip(keys, pool.items()):
        if leaf.dtype == jnp.int8:
            pool[name] = jax.random.randint(key, leaf.shape, -127, 128,
                                            jnp.int8)
        else:
            scale = 0.02 if name in ("ks", "vs") else 1.0
            pool[name] = (scale * jax.random.normal(key, leaf.shape)
                          ).astype(leaf.dtype).at[:, 0].set(trash)
    return pool


@pytest.mark.parametrize("chunk", list(STARTS))
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("arch", list(CFGS))
def test_chunk_equals_the_sliced_formulation_to_the_bit(arch, kv, chunk):
    cfg = CFGS[arch]
    params = llama.init_params(cfg, jax.random.key(3), dtype=jnp.bfloat16)
    start = STARTS[chunk]
    # physical pages out of order; past the chunk the table is padding
    pages = np.random.default_rng(5).permutation(np.arange(1, N))[:14]
    table = jnp.asarray(np.pad(pages, (0, 2))[None], jnp.int32)
    tokens = jax.random.randint(jax.random.key(11), (1, C), 3, 256)
    positions = (start + jnp.arange(C, dtype=jnp.int32))[None]
    valid = jnp.asarray([start + C - 5], jnp.int32)     # a ragged end
    args = (tokens, positions, table, valid, jnp.int32(start // PAGE))

    def run(fn, pool):
        return jax.jit(lambda p, pool, t, pos, tbl, v, sp: fn(
            p, cfg, t, pos, pool, tbl, v, sp))(params, pool, *args)

    dirty = filled_pool(cfg, kv, jnp.nan)
    assert bool(jnp.isnan(dirty["ks" if kv == "int8" else "k"][:, 0]).all())
    h, pool = run(llama.apply_prefill_paged, dirty)
    h_want, pool_want = run(sliced_prefill, dirty)
    assert np.array_equal(bits(h), bits(h_want))
    assert set(pool) == set(dirty)
    for name in pool:
        assert np.array_equal(bits(pool[name]), bits(pool_want[name])), name
    # the chunk's own pages changed in every layer, no other page did
    own = np.asarray(pages[start // PAGE:start // PAGE + C // PAGE])
    changed = np.any(bits(pool["k"]) != bits(dirty["k"]), axis=(2, 3, 4))
    assert changed[:, own].all() and changed.sum() == cfg.num_layers * 2
    # the canary: what the trash page holds reaches no query
    assert bool(jnp.all(jnp.isfinite(h.astype(jnp.float32))))
    h_clean, _ = run(llama.apply_prefill_paged, filled_pool(cfg, kv, 0.0))
    assert np.array_equal(bits(h), bits(h_clean))
