"""ops/gated_delta.py: the chunked scan against the recurrence token by
token, the decode step, the convolution's tail, and what padding may not
do to a state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import types

from generativeaiexamples_tpu.ops import gated_delta as _gd

# the forms under test, jitted: eagerly each is hundreds of dispatches
gd = types.SimpleNamespace(
    causal_conv=_gd.causal_conv,
    gated_delta_step=jax.jit(_gd.gated_delta_step),
    gated_delta_recurrent=jax.jit(_gd.gated_delta_recurrent),
    gated_delta_chunked=jax.jit(_gd.gated_delta_chunked,
                                static_argnames=("block",)))

B, H, DK, DV = 2, 3, 16, 8


def draw(T, seed=0, half_life=(4.0, 400.0)):
    ks = jax.random.split(jax.random.key(seed), 7)
    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2(jax.random.normal(ks[0], (B, T, H, DK))) * DK ** -0.5
    k = l2(jax.random.normal(ks[1], (B, T, H, DK)))
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


@pytest.mark.parametrize("block,T", [(1, 40), (16, 64), (64, 128),
                                     (64, 150), (16, 37)],
                         ids=["block1", "block16", "block64",
                              "ragged64", "ragged16"])
def test_chunked_scan_is_the_recurrence(block, T):
    q, k, v, g, beta, s0 = draw(T, seed=T)
    want_o, want_s = gd.gated_delta_recurrent(q, k, v, g, beta, s0)
    o, s = gd.gated_delta_chunked(q, k, v, g, beta, s0, block=block)
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


def test_two_chunks_carry_the_state():
    q, k, v, g, beta, s0 = draw(96, seed=5)
    want_o, want_s = gd.gated_delta_chunked(q, k, v, g, beta, s0)
    cut = lambda a, lo, hi: a[:, lo:hi]     # noqa: E731
    o1, s1 = gd.gated_delta_chunked(*(cut(a, 0, 40)
                                      for a in (q, k, v, g, beta)), s0)
    o2, s2 = gd.gated_delta_chunked(*(cut(a, 40, 96)
                                      for a in (q, k, v, g, beta)), s1)
    close(jnp.concatenate([o1, o2], 1), want_o)
    close(s2, want_s)
    # a program that dropped the state between the chunks is far off
    o2_dropped, _ = gd.gated_delta_chunked(
        *(cut(a, 40, 96) for a in (q, k, v, g, beta)), jnp.zeros_like(s1))
    assert float(jnp.max(jnp.abs(o2_dropped - want_o[:, 40:]))) \
        > 0.05 * float(jnp.max(jnp.abs(want_o)))


def test_padding_leaves_the_state_alone():
    """Tokens with g = 0 and beta = 0 — however large their q, k, v —
    leave the state as the last valid token left it, bit for bit in the
    step and to rounding in the scan."""
    q, k, v, g, beta, s0 = draw(64, seed=7)
    _, want = gd.gated_delta_chunked(*(a[:, :23] for a in (q, k, v, g, beta)),
                                     s0)
    valid = (jnp.arange(64) < 23)[None, :, None]
    loud = jnp.where(valid[..., None], 1.0, 100.0)      # the padding's
    _, got = gd.gated_delta_chunked(
        q, loud * k, loud * v, jnp.where(valid, g, 0.0),
        jnp.where(valid, beta, 0.0), s0)
    close(got, want)
    _, idle = gd.gated_delta_step(q[:, 0], k[:, 0], v[:, 0],
                                  jnp.zeros((B, H)), jnp.zeros((B, H)), s0)
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


def test_a_run_of_equal_tokens_is_still_the_recurrence():
    """Every key of a block the same and beta near 1 — a prompt of one
    repeated id — is where the finite product of powers loses float32 to
    cancellation; the block inverse does not."""
    T = 128
    q, k, v, g, beta, s0 = draw(T, seed=13)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    q = jnp.broadcast_to(q[:, :1], q.shape)
    beta = jnp.full_like(beta, 0.97)
    g = jnp.full_like(g, -1e-3)
    want_o, want_s = gd.gated_delta_recurrent(q, k, v, g, beta, s0)
    o, s = gd.gated_delta_chunked(q, k, v, g, beta, s0)
    close(o, want_o, 1e-4)
    close(s, want_s, 1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16_state"])
def test_the_step_kernel_is_the_step_in_place(dtype):
    """The Pallas kernel (interpreted) over a cache's whole state leaf:
    the named layer's live rows stepped as ``gated_delta_step`` steps
    them, an idle row's state and every other layer's bit for bit as
    they were — NaN included."""
    Lg, Bk, Hk, dk, dv = 3, 4, 16, 128, 128
    ks = jax.random.split(jax.random.key(0), 6)
    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True))

    q = unit(jax.random.normal(ks[0], (Bk, Hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (Bk, Hk, dk)))
    v = jax.random.normal(ks[2], (Bk, Hk, dv))
    g = -0.1 * jax.random.uniform(ks[3], (Bk, Hk))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (Bk, Hk)))
    states = jax.random.normal(ks[5], (Lg, Bk, Hk, dk, dv)).astype(dtype)
    states = states.at[1, 1].set(jnp.nan)               # the idle row's
    active = jnp.asarray([True, False, True, True])
    assert _gd.step_kernel_supported(Hk, dk, dv)
    assert not _gd.step_kernel_supported(Hk, 16, dv)
    o, new = _gd.gated_delta_step_kernel(q, k, v, g, beta, active, states,
                                         jnp.int32(1), interpret=True)
    want_o, want_s = _gd.gated_delta_step(q, k, v, g, beta, states[1])
    live = jnp.asarray([0, 2, 3])
    close(o[live], want_o[live], 1e-6)
    close(new[1][live].astype(jnp.float32),
          want_s[live].astype(jnp.float32), 1e-6 if dtype == jnp.float32
          else 1e-2)
    assert new.dtype == dtype and bool(jnp.all(jnp.isnan(new[1, 1])))
    for other in (0, 2):
        np.testing.assert_array_equal(new[other], states[other])
