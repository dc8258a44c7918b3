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
