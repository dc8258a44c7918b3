"""An expert share (``LlamaConfig.experts_held``): a layer is told which
of its experts it holds, routes over all of them and leaves out what the
others would add. The test the model-configs guide asks for: over all the
shares, the partial outputs with the shared expert counted once add up
to the uncut layer's output."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.ops.rope import rope_frequencies
from generativeaiexamples_tpu.parallel import moe

E, K, SHARES = 16, 4, 4
WHOLE = LlamaConfig(
    vocab_size=64, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=16, num_experts=E, num_experts_per_tok=K, num_shared_experts=1,
    moe_impl="dropless", router_score_func="sigmoid", router_norm_topk=True,
    router_scale=2.5, router_bias="selection", weight_init="unit_stream")


def share_of(cfg, i):
    held = E // SHARES
    return dataclasses.replace(cfg, experts_held=held, experts_first=i * held)


def layer_of(params, cfg):
    """Layer 1's parameters, the expert stacks cut to ``cfg``'s share."""
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    if cfg.experts_held:
        a, b = cfg.experts_first, cfg.experts_first + cfg.experts_held
        lp.update({n: lp[n][a:b] for n in ("w_gate", "w_up", "w_down")})
    return lp


@pytest.fixture(scope="module")
def params():
    return llama.init_params(WHOLE, jax.random.key(0), dtype=jnp.float32)


INV = rope_frequencies(WHOLE.head_dim, WHOLE.rope_theta)


def block(params, cfg, h, aux=None):
    pos = jnp.arange(h.shape[1])[None]
    return llama.decoder_layer(h, layer_of(params, cfg), cfg, pos, INV, None,
                               aux=aux)[0]


@pytest.mark.parametrize("T", [5, 40], ids=["decode_rows", "chunk_rows"])
def test_the_shares_partial_outputs_add_up_to_the_uncut_layer(params, T):
    h = jax.random.normal(jax.random.key(T), (1, T, 64), jnp.float32)
    parts, assigned, touched = [], 0.0, 0.0
    for i in range(SHARES):
        aux = {}
        parts.append(block(params, share_of(WHOLE, i), h, aux))
        assigned += float(aux["local_assignments"])
        touched += float(aux["experts_touched"])
    aux = {}
    whole = block(params, WHOLE, h, aux)
    # what a block holds beside its routed experts (the stream, the
    # attention, the shared expert) every share computes: counted once
    zero = dict(layer_of(params, share_of(WHOLE, 0)))
    zero.update({n: jnp.zeros_like(zero[n])
                 for n in ("w_gate", "w_up", "w_down")})
    rest = llama.decoder_layer(h, zero, share_of(WHOLE, 0),
                               jnp.arange(T)[None], INV, None)[0]
    total = rest + sum(p - rest for p in parts)
    assert float(jnp.max(jnp.abs(total - whole))) < 2e-5
    # every assignment fell on exactly one share; the shares' touched
    # experts are the uncut layer's
    assert assigned == T * K
    assert touched == float(aux["experts_touched"])
    assert "local_assignments" not in aux


def test_what_is_held_elsewhere_is_dropped_before_the_sort():
    T, bm = 6, 16
    logits = jax.random.normal(jax.random.key(1), (T, E), jnp.float32)
    rt_all = moe.route_sorted(logits, K, bm)
    rt = moe.route_sorted(logits, K, bm, share=(4, 4))
    _, idx = jax.lax.top_k(logits, K)
    held = np.asarray((idx >= 4) & (idx < 8))
    assert np.array_equal(rt["held"], held)
    assert float(rt["assigned"]) == held.sum()
    # no row, no block for an assignment held elsewhere
    assert int(rt["valid"].sum()) == held.sum()
    assert int(rt["n_blocks"]) == len(set(np.asarray(idx)[held]))
    assert float(rt["touched"]) == len(set(np.asarray(idx)[held]))
    # static shapes follow the HELD experts, and blocks name them from 0
    assert rt["block_expert"].shape[0] == T * K // bm + 4
    assert rt_all["block_expert"].shape[0] == T * K // bm + E
    live = np.asarray(rt["block_expert"][:int(rt["n_blocks"])])
    assert set(live) == {int(e) - 4 for e in np.asarray(idx)[held]}
    # the weights are still those of all k (normalised together later)
    assert np.allclose(rt["weight"], rt_all["weight"])
    # idle rows and a share together
    mask = jnp.array([True, False, True, True, False, True])
    rt_m = moe.route_sorted(logits, K, bm, mask, share=(4, 4))
    assert float(rt_m["assigned"]) == held[np.asarray(mask)].sum()


@pytest.mark.parametrize("crowded", [False, True],
                         ids=["as_expected", "crowded"])
def test_a_thin_share_gathers_only_the_blocks_that_can_hold_rows(crowded):
    """2 of 64 experts held, 512 tokens: the layout has 34 blocks and
    the expected load fills two, so the walk's one trip of six blocks
    holds every row; when a selection bias sends EVERY token to the held
    pair (16 blocks) it takes three. Both against a sum written out per
    token: nothing is dropped either way."""
    T, D, F, n, held, first = 512, 64, 32, 64, 2, 6
    cfg = LlamaConfig(
        vocab_size=64, hidden_size=D, intermediate_size=F,
        moe_intermediate_size=F, num_layers=1, num_heads=4, num_kv_heads=2,
        head_dim=16, num_experts=n, num_experts_per_tok=K,
        moe_impl="dropless", router_score_func="sigmoid",
        router_norm_topk=True, router_scale=2.5, router_bias="selection",
        experts_held=held, experts_first=first, weight_init="unit_stream")
    ks = jax.random.split(jax.random.key(5), 5)
    x = jax.random.normal(ks[0], (1, T, D), jnp.float32)
    logits = jax.random.normal(ks[1], (1, T, n), jnp.float32)
    bias = jnp.zeros((n,)).at[first:first + held].set(
        5.0 if crowded else 0.0)
    lp = {"router_bias": bias,
          "w_gate": jax.random.normal(ks[2], (held, D, F)) / 8,
          "w_up": jax.random.normal(ks[3], (held, D, F)) / 8,
          "w_down": jax.random.normal(ks[4], (held, F, D)) / 8}
    aux = {}
    got, touched = jax.jit(lambda x, lg, lp: moe.dropless_moe_ffn(
        x, lg, lp, cfg, aux=aux))(x, logits, lp)
    # the layout, and whether this draw fits one trip of six blocks
    select, weigh = moe.router_scores(logits[0], lp, cfg)
    rt = moe.route_sorted(select, K, 64, None, weigh, (first, held))
    assert rt["block_expert"].shape[0] == 34
    assert (int(rt["n_blocks"]) > 6) == crowded
    # per token: its chosen experts' outputs, weighted over all K, the
    # held ones kept
    _, idx = jax.lax.top_k(select, K)
    w = moe.scale_chosen(jnp.take_along_axis(weigh, idx, axis=1), cfg)
    want = jnp.zeros((T, D))
    for j in range(K):
        e = idx[:, j] - first
        here = (e >= 0) & (e < held)
        e = jnp.clip(e, 0, held - 1)
        gate = jax.nn.silu(jnp.einsum("td,tdf->tf", x[0], lp["w_gate"][e]))
        up = jnp.einsum("td,tdf->tf", x[0], lp["w_up"][e])
        y = jnp.einsum("tf,tfd->td", gate * up, lp["w_down"][e])
        want = want + jnp.where(here[:, None], y * w[:, j:j + 1], 0.0)
    assert float(jnp.max(jnp.abs(got[0] - want))) < 2e-5
    assert float(touched) == held
    assert float(rt["assigned"]) == (2 * T if crowded else
                                     float(jnp.sum(rt["held"])))


def test_tree_holds_the_share_and_the_router_every_column():
    cfg = share_of(WHOLE, 2)
    p = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    assert p["layers"]["router"].shape == (2, 64, E)
    assert p["layers"]["router_bias"].shape == (2, E)
    assert p["layers"]["w_gate"].shape == (2, E // SHARES, 64, 32)
    assert cfg.held_experts == 4 and WHOLE.held_experts == E
    from generativeaiexamples_tpu.engine.engine import weight_bytes_of
    total, routed = weight_bytes_of(p, cfg)
    assert routed == 2 * 4 * 3 * 64 * 32 * 4


def test_a_share_lies_inside_the_layers_experts():
    with pytest.raises(ValueError, match="outside the layer's"):
        dataclasses.replace(WHOLE, experts_held=8, experts_first=12)
    with pytest.raises(ValueError, match="needs experts"):
        LlamaConfig(experts_held=2)


@pytest.mark.parametrize("T", [13, 48], ids=["decode_rows", "chunk_rows"])
def test_the_shares_add_up_at_a_router_of_256_columns(T):
    """The sizes of the widest router served as a share: 256 columns, 8 a
    token, x 2.5, 32 shares of 8 — what one chip of 32 holds. Thirteen
    rows reach few of a share's eight experts, and the sum over the
    shares is still the uncut layer's."""
    n, k, shares = 256, 8, 32
    whole = dataclasses.replace(WHOLE, num_experts=n, num_experts_per_tok=k)
    params = llama.init_params(whole, jax.random.key(2), dtype=jnp.float32)
    held = n // shares
    h = jax.random.normal(jax.random.key(T), (1, T, 64), jnp.float32)
    pos = jnp.arange(T)[None]

    def cut(i):
        cfg = dataclasses.replace(whole, experts_held=held,
                                  experts_first=i * held)
        lp = jax.tree.map(lambda a: a[1], params["layers"])
        lp.update({name: lp[name][i * held:(i + 1) * held]
                   for name in ("w_gate", "w_up", "w_down")})
        return cfg, lp

    parts, assigned, touched = [], 0.0, []
    for i in range(shares):
        cfg, lp = cut(i)
        aux = {}
        parts.append(llama.decoder_layer(h, lp, cfg, pos, INV, None,
                                         aux=aux)[0])
        assigned += float(aux["local_assignments"])
        touched.append(float(aux["experts_touched"]))
    cfg, lp = cut(0)
    lp.update({name: jnp.zeros_like(lp[name])
               for name in ("w_gate", "w_up", "w_down")})
    rest = llama.decoder_layer(h, lp, cfg, pos, INV, None)[0]
    aux = {}
    uncut = llama.decoder_layer(
        h, jax.tree.map(lambda a: a[1], params["layers"]), whole, pos, INV,
        None, aux=aux)[0]
    total = rest + sum(p - rest for p in parts)
    assert float(jnp.max(jnp.abs(total - uncut))) < 5e-5
    assert assigned == T * k and sum(touched) == float(aux["experts_touched"])
    # a share of 8 is thinly reached at decode: 8 (1 - (31/32)^13) = 2.7
    if T == 13:
        assert 1.0 < sum(touched) / shares < 4.5
