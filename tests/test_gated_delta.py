"""ops/gated_delta.py: the chunked scan against the recurrence token by
token, the decode step, the convolution's tail, and what padding may not
do to a state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import types

from generativeaiexamples_tpu.ops import gated_delta as _gd



def _kernel_form(q, k, v, g, beta, state, block=64):
    """The Pallas kernel (interpreted) behind ``gated_delta_chunked``'s
    signature: q and k handed over by KEY head, every operand as the
    mixer has it (a head a lane slice of the last axis)."""
    assert block == 64
    Bq, T = g.shape[:2]
    o, s = _gd.gated_delta_chunked_kernel(
        q[:, :, ::2].reshape(Bq, T, -1), k[:, :, ::2].reshape(Bq, T, -1),
        v.reshape(Bq, T, -1), g, beta, state, interpret=True)
    return o.reshape(v.shape), s


# the forms under test, jitted: eagerly each is hundreds of dispatches
gd = types.SimpleNamespace(
    causal_conv=_gd.causal_conv,
    gated_delta_step=jax.jit(_gd.gated_delta_step),
    gated_delta_recurrent=jax.jit(_gd.gated_delta_recurrent),
    gated_delta_chunked=jax.jit(_gd.gated_delta_chunked,
                                static_argnames=("block",)),
    kernel=jax.jit(_kernel_form, static_argnames=("block",)))

B, H, DK, DV = 2, 3, 16, 8
# what the kernel takes: 128-lane heads, two value heads a key head, a
# whole group of key heads
KERNEL = dict(H=2 * _gd._SCAN_PAIRS, DK=128, DV=128)
FORMS = ["chunked", "kernel"]


def scan(form):
    return gd.kernel if form == "kernel" else gd.gated_delta_chunked


def draw(T, seed=0, half_life=(4.0, 400.0), form="chunked"):
    """Operands in the forms' common layout, (B, T, H, .). The kernel's
    draw has its shapes, and q and k of value heads ``2j, 2j + 1`` equal
    (their key head ``j``'s, ``repeat``ed)."""
    H, DK, DV = (KERNEL[n] if form == "kernel" else globals()[n]
                 for n in ("H", "DK", "DV"))
    ks = jax.random.split(jax.random.key(seed), 7)
    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2(jax.random.normal(ks[0], (B, T, H, DK))) * DK ** -0.5
    k = l2(jax.random.normal(ks[1], (B, T, H, DK)))
    if form == "kernel":
        q, k = (jnp.repeat(a[:, :, ::2], 2, axis=2) for a in (q, k))
    v = jax.random.normal(ks[2], (B, T, H, DV))
    rate = jnp.log(2.0) / jnp.exp(jax.random.uniform(
        ks[3], (B, T, H), minval=jnp.log(half_life[0]),
        maxval=jnp.log(half_life[1])))
    beta = jax.nn.sigmoid(1.5 * jax.random.normal(ks[4], (B, T, H)))
    s0 = jax.random.normal(ks[5], (B, H, DK, DV))
    return q, k, v, -rate, beta, s0


def close(a, b, tol=2e-5):
    scale = float(jnp.max(jnp.abs(b)))
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale


@pytest.mark.parametrize("form,block,T", [
    ("chunked", 1, 40), ("chunked", 16, 64), ("chunked", 64, 128),
    ("chunked", 64, 150), ("chunked", 16, 37), ("kernel", 64, 64),
    ("kernel", 64, 192)],
    ids=["block1", "block16", "block64", "ragged64", "ragged16",
         "kernel_one_block", "kernel_three_blocks"])
def test_chunked_scan_is_the_recurrence(form, block, T):
    q, k, v, g, beta, s0 = draw(T, seed=T, form=form)
    want_o, want_s = gd.gated_delta_recurrent(q, k, v, g, beta, s0)
    o, s = scan(form)(q, k, v, g, beta, s0, block=block)
    close(o, want_o)
    close(s, want_s)


def test_recurrence_is_the_written_equations():
    """One head, numpy, the four lines of the module's docstring."""
    q, k, v, g, beta, s0 = (np.asarray(a, np.float64)
                            for a in draw(9, seed=3))
    S = s0[0, 0].copy()
    outs = []
    for t in range(9):
        S = np.exp(g[0, t, 0]) * S
        d = beta[0, t, 0] * (v[0, t, 0] - S.T @ k[0, t, 0])
        S = S + np.outer(k[0, t, 0], d)
        outs.append(S.T @ q[0, t, 0])
    o, s = gd.gated_delta_recurrent(*draw(9, seed=3))
    np.testing.assert_allclose(o[0, :, 0], np.stack(outs), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(s[0, 0], S, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("form,T,at", [("chunked", 96, 40),
                                       ("kernel", 192, 64)],
                         ids=FORMS)
def test_two_chunks_carry_the_state(form, T, at):
    q, k, v, g, beta, s0 = draw(T, seed=5, form=form)
    want_o, want_s = gd.gated_delta_chunked(q, k, v, g, beta, s0)
    cut = lambda a, lo, hi: a[:, lo:hi]     # noqa: E731
    o1, s1 = scan(form)(*(cut(a, 0, at) for a in (q, k, v, g, beta)), s0)
    o2, s2 = scan(form)(*(cut(a, at, T) for a in (q, k, v, g, beta)), s1)
    close(jnp.concatenate([o1, o2], 1), want_o)
    close(s2, want_s)
    # a program that dropped the state between the chunks is far off
    o2_dropped, _ = scan(form)(
        *(cut(a, at, T) for a in (q, k, v, g, beta)), jnp.zeros_like(s1))
    assert float(jnp.max(jnp.abs(o2_dropped - want_o[:, at:]))) \
        > 0.05 * float(jnp.max(jnp.abs(want_o)))


@pytest.mark.parametrize("form", FORMS)
def test_padding_leaves_the_state_alone(form):
    """Tokens with g = 0 and beta = 0 — however large their q, k, v —
    leave the state as the last valid token left it, bit for bit in the
    step and to rounding in the scan; a whole block of them in the
    kernel, bit for bit."""
    q, k, v, g, beta, s0 = draw(64, seed=7, form=form)
    _, want = gd.gated_delta_chunked(*(a[:, :23] for a in (q, k, v, g, beta)),
                                     s0)
    valid = (jnp.arange(64) < 23)[None, :, None]
    loud = jnp.where(valid[..., None], 1.0, 100.0)      # the padding's
    padded = (q, loud * k, loud * v, jnp.where(valid, g, 0.0),
              jnp.where(valid, beta, 0.0))
    _, got = scan(form)(*padded, s0)
    close(got, want)
    if form == "kernel":
        none = jnp.zeros_like(g)
        _, more = gd.kernel(*(jnp.concatenate([a, b], axis=1) for a, b in zip(
            padded, (q, 100.0 * k, 100.0 * v, none, none))), s0)
        assert bool(jnp.all(more == got))
    _, idle = gd.gated_delta_step(q[:, 0], k[:, 0], v[:, 0],
                                  jnp.zeros_like(g[:, 0]),
                                  jnp.zeros_like(g[:, 0]), s0)
    assert bool(jnp.all(idle == s0))


def test_a_bf16_state_is_not_the_recurrence():
    """The nearest precision below: a state kept and carried in bf16 is
    off by hundreds of times what the float32 forms differ by."""
    q, k, v, g, beta, s0 = draw(256, seed=11)
    want_o, _ = gd.gated_delta_recurrent(q, k, v, g, beta, s0)
    o, _ = gd.gated_delta_chunked(q, k, v, g, beta,
                                  s0.astype(jnp.bfloat16))
    err = float(jnp.max(jnp.abs(o - want_o))) / float(jnp.max(jnp.abs(want_o)))
    assert err > 1e-3       # the float32 forms agree to 2e-5 (above)


def test_causal_conv_and_its_tail():
    T, Ch, K = 20, 6, 4
    ks = jax.random.split(jax.random.key(2), 3)
    u = jax.random.normal(ks[0], (B, T, Ch))
    w = jax.random.normal(ks[1], (Ch, K))
    tail0 = jax.random.normal(ks[2], (B, K - 1, Ch))
    ext = np.concatenate([np.asarray(tail0), np.asarray(u)], axis=1)
    want = np.stack([sum(ext[:, t + j] * np.asarray(w)[:, j]
                         for j in range(K)) for t in range(T)], axis=1)
    out, tail = gd.causal_conv(u, tail0, w)
    np.testing.assert_allclose(out, jax.nn.silu(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(tail, u[:, T - (K - 1):])
    # in two pieces, the tail carried
    o1, t1 = gd.causal_conv(u[:, :7], tail0, w)
    o2, t2 = gd.causal_conv(u[:, 7:], t1, w)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), out, rtol=1e-6)
    np.testing.assert_array_equal(t2, tail)
    # padding: the tail ends at each row's last valid token; a row with
    # none keeps the tail it came with
    n_valid = jnp.asarray([5, 0])
    _, tp = gd.causal_conv(u, tail0, w, n_valid)
    np.testing.assert_array_equal(tp[0], u[0, 2:5])
    np.testing.assert_array_equal(tp[1], tail0[1])
    # fewer valid tokens than taps: the old tail's end, then the new
    _, tp = gd.causal_conv(u, tail0, w, jnp.asarray([1, 2]))
    np.testing.assert_array_equal(
        tp[0], jnp.concatenate([tail0[0, 1:], u[0, :1]]))


@pytest.mark.parametrize("form", FORMS)
def test_a_run_of_equal_tokens_is_still_the_recurrence(form):
    """Every key of a block the same and beta near 1 — a prompt of one
    repeated id — is where the finite product of powers loses float32 to
    cancellation; the block inverse does not."""
    T = 128
    q, k, v, g, beta, s0 = draw(T, seed=13, form=form)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    q = jnp.broadcast_to(q[:, :1], q.shape)
    beta = jnp.full_like(beta, 0.97)
    g = jnp.full_like(g, -1e-3)
    want_o, want_s = gd.gated_delta_recurrent(q, k, v, g, beta, s0)
    o, s = scan(form)(q, k, v, g, beta, s0)
    close(o, want_o, 1e-4)
    close(s, want_s, 1e-4)


def test_rows_of_different_lengths_in_one_kernel_call():
    """B > 1, each row padded past its own ``n_valid`` as the mixer pads
    (g = 0, beta = 0): every row's state is the recurrence over its own
    valid tokens, its outputs there the recurrence's."""
    T, n_valid = 128, (100, 23)
    q, k, v, g, beta, s0 = draw(T, seed=17, form="kernel")
    valid = (jnp.arange(T)[None, :] < jnp.asarray(n_valid)[:, None])[..., None]
    o, s = gd.kernel(q, k, v, jnp.where(valid, g, 0.0),
                     jnp.where(valid, beta, 0.0), s0)
    for b, n in enumerate(n_valid):
        want_o, want_s = gd.gated_delta_recurrent(
            *(a[b:b + 1, :n] for a in (q, k, v, g, beta)), s0[b:b + 1])
        close(o[b:b + 1, :n], want_o)
        close(s[b:b + 1], want_s)


def test_the_kernel_reads_q_and_k_by_key_head():
    """Value heads ``2j, 2j + 1`` share key head ``j``: the kernel over
    (B, T, Hk * dk) is the XLA form over the ``repeat``ed (B, T, Hv,
    dk), and no other pairing of the heads is."""
    q, k, v, g, beta, s0 = draw(64, seed=19, form="kernel")
    want_o, want_s = gd.gated_delta_chunked(q, k, v, g, beta, s0)
    o, s = gd.kernel(q, k, v, g, beta, s0)
    close(o, want_o, 1e-5)
    close(s, want_s, 1e-5)
    # heads (j, j + Hk) sharing a key head, as a tiled q and k would
    # pair them, is far off
    Hk = KERNEL["H"] // 2
    tiled = lambda a: jnp.tile(a[:, :, ::2], (1, 1, 2, 1))  # noqa: E731
    other, _ = gd.gated_delta_chunked(tiled(q), tiled(k), v, g, beta, s0)
    assert Hk > 1 and float(jnp.max(jnp.abs(other - want_o))) \
        > 0.05 * float(jnp.max(jnp.abs(want_o)))


@pytest.mark.parametrize("at", [2 * _gd._SCAN_PAIRS * 128, 128],
                         ids=["whole_blocks", "sliced"])
def test_the_kernel_reads_v_out_of_a_wider_array(at):
    """``v_at``: the values where the convolution left them, behind q and
    k on the lanes of its output — read in place where the offset is
    whole blocks of a group's values, sliced first where it is not; bit
    for bit the call over ``v`` alone."""
    q, k, v, g, beta, s0 = draw(64, seed=23, form="kernel")
    flat = lambda a: a.reshape(B, 64, -1)       # noqa: E731
    want = _gd.gated_delta_chunked_kernel(
        flat(q[:, :, ::2]), flat(k[:, :, ::2]), flat(v), g, beta, s0,
        interpret=True)
    wide = jnp.concatenate([jnp.full((B, 64, at), jnp.nan), flat(v),
                            jnp.full((B, 64, 128), jnp.nan)], axis=-1)
    got = _gd.gated_delta_chunked_kernel(
        flat(q[:, :, ::2]), flat(k[:, :, ::2]), wide, g, beta, s0, v_at=at,
        interpret=True)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_the_kernel_is_taken_only_where_it_fits(monkeypatch):
    """``scan_kernel_supported`` reads the path off the shapes — a head
    of 64 lanes, a ragged length, a head count that is no whole group or
    no pair — ``scan_kernel_armed`` adds the backend, and ``_gdn_mixer``
    runs the XLA form wherever the answer is no."""
    from generativeaiexamples_tpu.models import llama
    from test_recurrent_layers import CFG
    import dataclasses

    P = _gd._SCAN_PAIRS
    assert _gd.scan_kernel_supported(512, 4 * P, 8 * P, 128, 128)
    assert not _gd.scan_kernel_supported(512, 4 * P, 8 * P, 64, 128)
    assert not _gd.scan_kernel_supported(512, 4 * P, 8 * P, 128, 64)
    assert not _gd.scan_kernel_supported(150, 4 * P, 8 * P, 128, 128)
    assert not _gd.scan_kernel_supported(512, P + 1, 2 * P + 2, 128, 128)
    assert not _gd.scan_kernel_supported(512, 4 * P, 4 * P, 128, 128)
    # the CPU is not armed, whatever the shapes
    assert not _gd.scan_kernel_armed(512, 4 * P, 8 * P, 128, 128)
    with pytest.raises(ValueError, match="no scan kernel"):
        _gd.gated_delta_chunked_kernel(
            *(jnp.zeros(s) for s in ((1, 64, 64), (1, 64, 64), (1, 64, 128),
                                     (1, 64, 2), (1, 64, 2),
                                     (1, 2, 64, 64))))

    def kernels_in(cfg, S):
        lp = {n: jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
              for n, a in jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.key(0), jnp.float32)
        )["layers"].items()}
        text = str(jax.make_jaxpr(
            lambda x, lp: llama._gdn_mixer(x, lp, cfg))(
                jax.ShapeDtypeStruct((1, S, cfg.hidden_size), jnp.float32),
                lp))
        return text.count("pallas_call")

    wide = dataclasses.replace(
        CFG, linear_key_head_dim=128, linear_value_head_dim=128,
        linear_num_key_heads=P, linear_num_value_heads=2 * P)
    assert kernels_in(wide, 128) == 0           # the CPU: the XLA form
    # as on a TPU (the kernel interpreted here)
    monkeypatch.setattr(_gd, "scan_kernel_armed", _gd.scan_kernel_supported)
    assert kernels_in(wide, 128) == 1
    assert kernels_in(wide, 100) == 0           # a ragged length
    assert kernels_in(CFG, 128) == 0            # 16-lane heads
    assert kernels_in(dataclasses.replace(
        wide, linear_num_key_heads=P + 1,
        linear_num_value_heads=2 * P + 2), 128) == 0


# which of a leaf's four rows hold a sequence
ACTIVITY = {"none": (0, 0, 0, 0), "all": (1, 1, 1, 1),
            "leading_idle": (0, 0, 1, 1), "trailing_idle": (1, 1, 0, 0),
            "alternating": (0, 1, 0, 1), "one_live_in_the_middle": (0, 0, 1, 0)}


@pytest.mark.parametrize("rows,dtype", [
    (rows, jnp.float32) for rows in ACTIVITY.values()] + [
    (ACTIVITY["alternating"], jnp.bfloat16)],
    ids=list(ACTIVITY) + ["alternating_bf16_state"])
def test_the_step_kernel_is_the_step_in_place(rows, dtype):
    """The Pallas kernel over a cache's whole state leaf (3 layers x 4
    slots x two groups of heads), under the TPU interpreter — which
    models the pipeline's buffers, so an output buffer no step wrote
    goes back as NaN (plain ``interpret=True`` reads the block anew
    every step and cannot see that): the named layer's live rows
    stepped as ``gated_delta_step`` steps them; an idle row's state —
    whatever a stale slot holds, ``inf`` and NaN too: it is never moved
    — and every other layer's bit for bit as they were, with no row
    live as well."""
    from jax.experimental.pallas import tpu as pltpu

    Lg, Bk, Hk, dk, dv = 3, 4, 16, 128, 128
    ks = jax.random.split(jax.random.key(0), 6)
    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True))

    q = unit(jax.random.normal(ks[0], (Bk, Hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (Bk, Hk, dk)))
    v = jax.random.normal(ks[2], (Bk, Hk, dv))
    g = -0.1 * jax.random.uniform(ks[3], (Bk, Hk))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (Bk, Hk)))
    active = jnp.asarray(rows, bool)
    live, idle = np.asarray(active), ~np.asarray(active)
    states = jax.random.normal(ks[5], (Lg, Bk, Hk, dk, dv))
    garbage = states[1].at[:, 0].set(jnp.inf).at[:, Hk - 1].set(jnp.nan)
    states = states.at[1].set(jnp.where(
        active[:, None, None, None], states[1], garbage)).astype(dtype)
    assert _gd.step_kernel_supported(Hk, dk, dv)
    assert not _gd.step_kernel_supported(Hk, 16, dv)
    o, new = jax.jit(lambda *a: _gd.gated_delta_step_kernel(
        *a, interpret=pltpu.InterpretParams(uninitialized_memory="nan")))(
        q, k, v, g, beta, _gd.live_first(active), states, jnp.int32(1))
    want_o, want_s = _gd.gated_delta_step(q, k, v, g, beta, states[1])
    if live.any():
        close(o[live], want_o[live], 1e-6)
        close(new[1][live].astype(jnp.float32),
              want_s[live].astype(jnp.float32),
              1e-6 if dtype == jnp.float32 else 1e-2)
    bits = lambda a: np.asarray(a).view(                    # noqa: E731
        np.uint32 if dtype == jnp.float32 else np.uint16)
    assert new.dtype == dtype
    np.testing.assert_array_equal(bits(new[1])[idle], bits(states[1])[idle])
    for other in (0, 2):
        np.testing.assert_array_equal(bits(new[other]), bits(states[other]))
    assert not np.any(np.asarray(o)[idle])      # nothing is read out
