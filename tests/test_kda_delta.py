"""ops/gated_delta.py where the decay is a CHANNEL's (Kimi Delta
Attention): the chunked form against the recurrence token by token at
gates down to the lower bound, the decode step and its kernel's twin
against the written equations, and what averaging a head's decays
loses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.ops import gated_delta as gd

B, H, DK, DV = 2, 3, 16, 8
recurrent = jax.jit(gd.gated_delta_recurrent)
chunked = jax.jit(gd.kda_chunked, static_argnames=("block",))


def draw(T, gates, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    q = gd.l2norm(jax.random.normal(ks[0], (B, T, H, DK))) * DK ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (B, T, H, DK)))
    v = jax.random.normal(ks[2], (B, T, H, DV))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H)))
    s0 = jax.random.normal(ks[4], (B, H, DK, DV))
    if gates == "slow":         # half-lives of 4 to 400 tokens a channel
        g = -jnp.log(2.0) / jnp.exp(jax.random.uniform(
            ks[5], (B, T, H, DK), minval=jnp.log(4.0), maxval=jnp.log(400.0)))
    elif gates == "mixed":      # anywhere in (-5, 0), token by token
        g = -5.0 * jax.nn.sigmoid(3 * jax.random.normal(
            ks[5], (B, T, H, DK)) - 2)
    else:                       # the lower bound, a whole block through
        g = jnp.full((B, T, H, DK), -5.0)
    return q, k, v, g, beta, s0


def close(a, b, tol=2e-5):
    return float(jnp.max(jnp.abs(a - b))) <= tol * max(
        float(jnp.max(jnp.abs(b))), 1e-30)


@pytest.mark.parametrize("gates", ["slow", "mixed", "floor"])
@pytest.mark.parametrize("block,T", [(64, 150), (16, 40)])
def test_the_chunked_form_is_the_recurrence(gates, block, T):
    """Down to -5 a step through a whole block nothing overflows: the
    keys' factor is taken inside a sub-block of 16, exp(75) at most."""
    args = draw(T, gates)
    o, s = recurrent(*args)
    oc, sc = chunked(*args, block=block)
    assert bool(jnp.all(jnp.isfinite(oc)) & jnp.all(jnp.isfinite(sc)))
    assert close(oc, o) and close(sc, s)


def test_a_decay_below_the_bound_stays_finite():
    """Without a lower bound (-20 a step) the keys' exponent is held at
    80: accuracy goes, finiteness does not."""
    q, k, v, _, beta, s0 = draw(64, "floor")
    g = jnp.full((B, 64, H, DK), -20.0)
    oc, sc = chunked(q, k, v, g, beta, s0)
    assert bool(jnp.all(jnp.isfinite(oc)) & jnp.all(jnp.isfinite(sc)))


def test_two_chunks_carry_the_state():
    args = draw(96, "mixed", seed=3)
    o, s = chunked(*args)
    first = [a[:, :40] for a in args[:5]]
    rest = [a[:, 40:] for a in args[:5]]
    o1, s1 = chunked(*first, args[5])
    o2, s2 = chunked(*rest, s1)
    assert close(jnp.concatenate([o1, o2], 1), o) and close(s2, s)


def test_padding_leaves_the_state_alone():
    q, k, v, g, beta, s0 = draw(70, "mixed", seed=5)
    _, s = chunked(q[:, :50], k[:, :50], v[:, :50], g[:, :50], beta[:, :50],
                   s0)
    pad = jnp.arange(70)[None, :, None] < 50
    _, sp = chunked(q, k, v, jnp.where(pad[..., None], g, 0.0),
                    jnp.where(pad, beta, 0.0), s0)
    assert close(sp, s, 1e-6)


def test_the_step_is_the_written_equations():
    *token, s0 = draw(1, "mixed")
    q, k, v, g, beta = (a[:, 0] for a in token)
    o, s = gd.gated_delta_step(q, k, v, g, beta, s0)
    for b in range(B):
        for h in range(H):
            S = np.diag(np.exp(np.asarray(g[b, h]))) @ np.asarray(s0[b, h])
            d = float(beta[b, h]) * (np.asarray(v[b, h])
                                     - S.T @ np.asarray(k[b, h]))
            S = S + np.outer(np.asarray(k[b, h]), d)
            np.testing.assert_allclose(s[b, h], S, rtol=2e-5, atol=1e-6)
            np.testing.assert_allclose(o[b, h], S.T @ np.asarray(q[b, h]),
                                       rtol=2e-5, atol=1e-6)


def test_a_heads_decays_averaged_are_another_recurrence():
    """What a program with ONE decay a head computes: far from this."""
    q, k, v, g, beta, s0 = draw(64, "slow", seed=2)
    o, _ = recurrent(q, k, v, g, beta, s0)
    mean = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    o_mean, _ = recurrent(q, k, v, mean, beta, s0)
    o_head, _ = recurrent(q, k, v, mean[..., 0], beta, s0)   # (B, T, H)
    assert close(o_mean, o_head, 1e-5)      # the scalar form IS the mean's
    assert not close(o_mean, o, 1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16_state"])
def test_the_step_kernels_twin_is_the_step_in_place(dtype):
    """The kernel ``kda_delta_step`` (interpreted) over the whole state
    leaf: the stepped layer's live rows are ``gated_delta_step``'s with
    the vector decay, an idle row and every other layer bit for bit."""
    Lg, Bk, Hk, dk, dv = 3, 3, 8, 128, 128
    ks = jax.random.split(jax.random.key(1), 6)
    q = gd.l2norm(jax.random.normal(ks[0], (Bk, Hk, dk))) * dk ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (Bk, Hk, dk)))
    v = jax.random.normal(ks[2], (Bk, Hk, dv))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (Bk, Hk, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (Bk, Hk)))
    states = jax.random.normal(ks[5], (Lg, Bk, Hk, dk, dv)).astype(dtype)
    active = jnp.asarray([True, False, True])
    o, new = jax.jit(lambda *a: gd.gated_delta_step_kernel(
        *a, interpret=True))(q, k, v, g, beta, active, states, jnp.int32(1))
    o_ref, s_ref = gd.gated_delta_step(q, k, v, g, beta, states[1])
    live = np.asarray(active)
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(o[live], o_ref[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(new[1][live].astype(jnp.float32),
                               s_ref[live].astype(jnp.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(new[1, 1], states[1, 1])
    np.testing.assert_array_equal(new[0], states[0])
    np.testing.assert_array_equal(new[2], states[2])
    assert new.dtype == dtype
