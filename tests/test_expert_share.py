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
    # static shapes follow the HELD experts — every assignment held here
    # stays servable, in whole trips of the walk — and blocks name them
    # from 0
    few = moe.share_walk(T, K, E, 4, bm)
    assert rt["few"] == few and 1 <= few <= T * K // bm + 4
    assert rt["block_expert"].shape[0] == -(-(T * K // bm + 4) // few) * few
    assert rt["token"].shape == rt["valid"].shape \
        == (rt["block_expert"].shape[0] * bm,)
    assert rt_all["block_expert"].shape[0] == T * K // bm + E
    assert "few" not in rt_all and "rows_read" not in rt_all
    # a held assignment's padded row names its token, and no other row
    # is valid
    src = np.flatnonzero(held.reshape(-1))
    rows = np.asarray(rt["row_of"]).reshape(-1)[src]
    assert np.array_equal(np.sort(rows), np.flatnonzero(rt["valid"]))
    assert np.array_equal(np.asarray(rt["token"])[rows], src // K)
    # the walk's trips, from the returned counters
    trips = -(-int(rt["n_blocks"]) // few)
    assert float(rt["rows_read"]) == trips * few * bm
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
@pytest.mark.parametrize("grouped", [False, True],
                         ids=["any_expert", "group_limited"])
@pytest.mark.parametrize("T", [24, 512], ids=["decode_rows", "chunk_rows"])
@pytest.mark.parametrize("first,held", [(6, 2), (16, 16)],
                         ids=["thin_share", "quarter_share"])
def test_a_thin_share_gathers_only_the_blocks_that_can_hold_rows(
        first, held, T, grouped, crowded):
    """2 or 16 of 64 experts held, a chunk's 512 tokens or a decode
    step's 24 rows (five of them idle): the layout is sized for every
    assignment falling here and walked ``share_walk`` blocks a trip —
    what the held experts are expected to fill — so a draw as expected
    takes one trip and, when a selection bias sends EVERY token to the
    held experts, more (a chunk's: its 16-row blocks fill several times
    a trip's count). Both against a sum written out per token: nothing
    is dropped either way, under a router limited to groups too. The
    trips are read from the returned counters: ceil(n_blocks / few), and
    ``rows_read`` is what they gathered."""
    D, F, n = 64, 32, 64
    cfg = LlamaConfig(
        vocab_size=64, hidden_size=D, intermediate_size=F,
        moe_intermediate_size=F, num_layers=1, num_heads=4, num_kv_heads=2,
        head_dim=16, num_experts=n, num_experts_per_tok=K,
        moe_impl="dropless", router_score_func="sigmoid",
        router_norm_topk=True, router_scale=2.5, router_bias="selection",
        n_group=8 if grouped else 1, topk_group=4 if grouped else 1,
        experts_held=held, experts_first=first, weight_init="unit_stream")
    ks = jax.random.split(jax.random.key(5), 5)
    decode = T < 64
    shape = (T, 1) if decode else (1, T)
    x = jax.random.normal(ks[0], shape + (D,), jnp.float32)
    logits = jax.random.normal(ks[1], shape + (n,), jnp.float32)
    mask = jnp.arange(T) % 5 != 2 if decode else None
    live = np.ones((T,), bool) if mask is None else np.asarray(mask)
    bias = jnp.zeros((n,)).at[first:first + held].set(
        5.0 if crowded else 0.0)
    lp = {"router_bias": bias,
          "w_gate": jax.random.normal(ks[2], (held, D, F)) / 8,
          "w_up": jax.random.normal(ks[3], (held, D, F)) / 8,
          "w_down": jax.random.normal(ks[4], (held, F, D)) / 8}

    def layer(x, lg, lp):
        aux = {}
        return moe.dropless_moe_ffn(x, lg, lp, cfg, mask, aux), aux

    (got, touched), aux = jax.jit(layer)(x, logits, lp)
    got = got.reshape(T, D)
    # the layout as the layer makes it, and the trips its walk takes
    select, weigh = moe.router_scores(logits.reshape(T, n), lp, cfg)
    bm = moe.dropless_block_rows(T, T * K / n)
    assert bm == (16 if decode else 32)         # not 64: an expert's rows
    rt = moe.route_sorted(select, K, bm, mask, weigh, (first, held))
    few = moe.share_walk(T, K, n, held, bm)
    assert rt["few"] == few < T * K // bm + held
    trips = -(-int(rt["n_blocks"]) // few)
    assert float(aux["route_rows_read"]) == float(rt["rows_read"]) \
        == trips * few * bm
    if not decode:
        assert (trips > 1) == crowded
    elif not crowded:
        assert trips == 1
    # per token: its chosen experts' outputs, weighted over all K, the
    # held ones kept
    _, idx = jax.lax.top_k(select, K)
    w = moe.scale_chosen(jnp.take_along_axis(weigh, idx, axis=1), cfg)
    xs = x.reshape(T, D)
    want, n_held = jnp.zeros((T, D)), 0
    for j in range(K):
        e = idx[:, j] - first
        here = (e >= 0) & (e < held) & live
        n_held += int(here.sum())
        e = jnp.clip(e, 0, held - 1)
        gate = jax.nn.silu(jnp.einsum("td,tdf->tf", xs, lp["w_gate"][e]))
        up = jnp.einsum("td,tdf->tf", xs, lp["w_up"][e])
        y = jnp.einsum("tf,tfd->td", gate * up, lp["w_down"][e])
        want = want + jnp.where(here[:, None], y * w[:, j:j + 1], 0.0)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    assert not np.asarray(got)[~live].any()
    assert float(aux["local_assignments"]) == float(rt["assigned"]) == n_held
    if crowded:     # every live token's K (its two) on the held experts
        assert n_held == min(K, held) * live.sum()
        assert float(touched) == held or decode


def test_rows_read_follow_the_blocks_that_hold_rows():
    """Where every touched expert has few rows the walk reads about a
    block's rows an assignment: sixteen rows send one assignment each to
    sixteen different held experts (and sixteen rows send none) — one
    trip of ``few`` = 16 blocks, 16 padded rows an assignment; with every
    row sending one, two rows an expert, 8."""
    T, n, bm = 32, 64, 16
    few = moe.share_walk(T, 2, n, 16, bm)
    assert few == 16
    for senders, per_assignment in ((16, 16.0), (32, 8.0)):
        t = np.arange(T)
        scores = np.zeros((T, n), np.float32)
        scores[t, 16 + t % 16] = np.where(t < senders, 2.0, 0.0)   # held
        scores[t, 40 + t % 8] += 1.5            # each row's other choice,
        scores[t, 48 + t % 8] += 1.0            # and a non-sender's second
        rt = moe.route_sorted(jnp.asarray(scores), 2, bm, share=(16, 16))
        assert float(rt["assigned"]) == senders
        assert int(rt["n_blocks"]) == 16 and float(rt["rows_read"]) == 256
        ratio = float(rt["rows_read"]) / float(rt["assigned"])
        assert ratio == per_assignment <= bm + 1


def test_the_way_back_is_exact_in_bfloat16_too():
    """A bfloat16 layer's way back is three single-pass products: a
    row's float32 weight as three bfloat16 terms that sum to it bit for
    bit, so each product with a bfloat16 output is exact in the float32
    sum. Against the weighted sum written out over the SAME bfloat16
    expert outputs, in float32."""
    w = jax.random.uniform(jax.random.key(0), (4096,), jnp.float32) \
        * jnp.exp(3 * jax.random.normal(jax.random.key(1), (4096,)))
    parts = moe._exact_parts(w, jnp.bfloat16)
    assert len(parts) == 3 and all(p.dtype == jnp.bfloat16 for p in parts)
    assert jnp.array_equal(sum(p.astype(jnp.float32) for p in parts), w)
    assert moe._exact_parts(w, jnp.float32) == (w,)
    T, D, n, bm = 48, 32, 64, 16
    ks = jax.random.split(jax.random.key(7), 3)
    x = jax.random.normal(ks[0], (T, D), jnp.bfloat16)
    rt = moe.route_sorted(jax.random.normal(ks[1], (T, n)), K, bm,
                          share=(16, 16))
    rt["weight"] = rt["weight"] * rt["held"]
    mix = jax.random.normal(ks[2], (16, D, D), jnp.bfloat16) / 6

    def ffn(x_pad, block_expert, n_blocks):     # a block's expert's matrix
        return jnp.einsum("bmd,bde->bme", x_pad.reshape(-1, bm, D),
                          mix[block_expert]).reshape(x_pad.shape)

    got = moe._walk_share(x, rt, ffn, bm)
    assert got.dtype == jnp.float32
    # the one trip's outputs, made again outside the loop
    seg = rt["few"] * bm
    x_pad = jnp.where(rt["valid"][:seg, None], x[rt["token"][:seg]], 0)
    y = np.asarray(ffn(x_pad, rt["block_expert"][:rt["few"]],
                       rt["n_blocks"]).astype(jnp.float32))
    rows, w = np.asarray(rt["row_of"]), np.asarray(rt["weight"])
    assert int(rt["n_blocks"]) <= rt["few"]     # this draw fits the trip
    want = sum(w[:, j:j + 1] * y[rows[:, j]] for j in range(K))
    assert float(np.max(np.abs(np.asarray(got) - want))) < 1e-6


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
