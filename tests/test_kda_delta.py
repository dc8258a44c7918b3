"""ops/gated_delta.py where the decay is a CHANNEL's (Kimi Delta
Attention): the chunked form and its kernel (interpreted) against the
recurrence token by token at gates down to the lower bound, the decode
step and its kernel's twin against the written equations, and what
averaging a head's decays loses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.ops import gated_delta as gd

B, H, DK, DV = 2, 3, 16, 8
# what the kernel takes: 128-lane heads, one whole group of them
KERNEL = dict(H=gd._KDA_HEADS, DK=128, DV=128)
FORMS = ["chunked", "kernel"]
recurrent = jax.jit(gd.gated_delta_recurrent)
chunked = jax.jit(gd.kda_chunked, static_argnames=("block",))


@jax.jit
def kernel(q, k, v, g, beta, state):
    """The Pallas kernel (interpreted) behind ``kda_chunked``'s
    signature: every operand handed over as the mixer has it (a head a
    lane slice of the last axis)."""
    flat = lambda a: a.reshape(a.shape[:2] + (-1,))     # noqa: E731
    o, s = gd.kda_chunked_kernel(flat(q), flat(k), flat(v), flat(g), beta,
                                 state, interpret=True)
    return o.reshape(v.shape), s


def scan(form):
    return kernel if form == "kernel" else chunked


def draw(T, gates, seed=0, form="chunked"):
    H, DK, DV = (KERNEL[n] if form == "kernel" else globals()[n]
                 for n in ("H", "DK", "DV"))
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
@pytest.mark.parametrize("form,block,T", [
    ("chunked", 64, 150), ("chunked", 16, 40), ("kernel", 64, 128)])
def test_the_chunked_form_is_the_recurrence(gates, form, block, T):
    """Down to -5 a step through a whole block nothing overflows: the
    keys' factor is taken inside a sub-block of 16, exp(75) at most. The
    kernel is held to the recurrence AND to the XLA form — but for the
    floor a whole block through at ITS widths: there the XLA form's rows
    16 tokens past a sub-block's first carry exp(-80), 128 channels a
    head are small enough to go subnormal and be flushed, and it is the
    XLA form that stands 7e-5 from the recurrence; the kernel, whose
    exponents are a sub-block's own (40 at most), stays at 2e-5."""
    args = draw(T, gates, form=form)
    o, s = recurrent(*args)
    oc, sc = scan(form)(*args) if form == "kernel" \
        else chunked(*args, block=block)
    assert bool(jnp.all(jnp.isfinite(oc)) & jnp.all(jnp.isfinite(sc)))
    assert close(oc, o) and close(sc, s)
    if form == "kernel":
        ox, sx = chunked(*args)
        assert close(sc, sx)
        assert close(oc, ox) if gates != "floor" else not close(ox, o)


@pytest.mark.parametrize("form", FORMS)
def test_a_decay_below_the_bound_stays_finite(form):
    """Without a lower bound (-20 a step) the exponents are held at 80:
    accuracy goes, finiteness does not."""
    q, k, v, g, beta, s0 = draw(128, "floor", form=form)
    oc, sc = scan(form)(q, k, v, jnp.full_like(g, -20.0), beta, s0)
    assert bool(jnp.all(jnp.isfinite(oc)) & jnp.all(jnp.isfinite(sc)))


@pytest.mark.parametrize("form,T,at", [("chunked", 96, 40),
                                       ("kernel", 128, 64)], ids=FORMS)
def test_two_chunks_carry_the_state(form, T, at):
    args = draw(T, "mixed", seed=3, form=form)
    o, s = chunked(*args)
    first = [a[:, :at] for a in args[:5]]
    rest = [a[:, at:] for a in args[:5]]
    o1, s1 = scan(form)(*first, args[5])
    o2, s2 = scan(form)(*rest, s1)
    assert close(jnp.concatenate([o1, o2], 1), o) and close(s2, s)


@pytest.mark.parametrize("form,T,n", [("chunked", 70, 50),
                                      ("kernel", 128, 50)], ids=FORMS)
def test_padding_leaves_the_state_alone(form, T, n):
    """Tokens with g = 0 and beta = 0, however loud their k and v: to
    rounding where they share a block with valid ones, and in the kernel
    BIT FOR BIT where a whole block is such tokens."""
    q, k, v, g, beta, s0 = draw(T, "mixed", seed=5, form=form)
    _, s = chunked(q[:, :n], k[:, :n], v[:, :n], g[:, :n], beta[:, :n], s0)
    pad = jnp.arange(T)[None, :, None] < n
    loud = jnp.where(pad[..., None], 1.0, 100.0)
    padded = (q, loud * k, loud * v, jnp.where(pad[..., None], g, 0.0),
              jnp.where(pad, beta, 0.0))
    _, sp = scan(form)(*padded, s0)
    assert close(sp, s, 1e-6 if form == "chunked" else 2e-5)
    if form == "kernel":        # the second block is all padding
        _, one = kernel(*(a[:, :64] for a in padded), s0)
        np.testing.assert_array_equal(sp, one)


def test_the_kernel_over_a_run_of_equal_tokens_and_ragged_rows():
    """One call, two rows: row 0 a run of equal tokens at beta near 1 (a
    prompt of one repeated id: where a finite product of powers would
    lose float32), row 1 valid for 23 tokens and padded as the mixer
    pads — each is the recurrence over its own valid tokens."""
    T, n_valid = 128, (128, 23)
    q, k, v, g, beta, s0 = draw(T, "slow", seed=13, form="kernel")
    same = lambda a: a.at[0].set(a[0, :1])              # noqa: E731
    q, k = same(q), same(k)
    beta = beta.at[0].set(0.97)
    valid = (jnp.arange(T)[None, :] < jnp.asarray(n_valid)[:, None])[..., None]
    o, s = kernel(q, k, v, jnp.where(valid[..., None], g, 0.0),
                  jnp.where(valid, beta, 0.0), s0)
    for b, n in enumerate(n_valid):
        want_o, want_s = recurrent(
            *(a[b:b + 1, :n] for a in (q, k, v, g, beta)), s0[b:b + 1])
        assert close(o[b:b + 1, :n], want_o, 1e-4 if b == 0 else 2e-5)
        assert close(s[b:b + 1], want_s, 1e-4 if b == 0 else 2e-5)


@pytest.mark.parametrize("at", [gd._KDA_HEADS * 128, 128],
                         ids=["whole_blocks", "sliced"])
def test_the_kernel_reads_v_out_of_a_wider_array(at):
    """``v_at``: the values where the convolution left them, behind q and
    k on the lanes of its output — read in place where the offset is
    whole blocks of a group's values, sliced first where it is not; bit
    for bit the call over ``v`` alone."""
    q, k, v, g, beta, s0 = draw(128, "mixed", seed=23, form="kernel")
    flat = lambda a: a.reshape(a.shape[:2] + (-1,))     # noqa: E731
    want = kernel(q, k, v, g, beta, s0)
    wide = jnp.concatenate([jnp.full((B, 128, at), jnp.nan), flat(v),
                            jnp.full((B, 128, 128), jnp.nan)], axis=-1)
    got = gd.kda_chunked_kernel(flat(q), flat(k), wide, flat(g), beta, s0,
                                v_at=at, interpret=True)
    np.testing.assert_array_equal(got[0].reshape(v.shape), want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_the_kernel_is_taken_only_where_it_fits(monkeypatch):
    """``kda_scan_kernel_supported`` reads the path off the shapes — a
    head of 64 lanes, a ragged length, a head count that is no whole
    group — ``kda_scan_kernel_armed`` adds the backend, and
    ``_kda_mixer`` runs the XLA form wherever the answer is no."""
    import dataclasses
    from generativeaiexamples_tpu.models import llama
    from test_kda_layers import CFG

    G = gd._KDA_HEADS
    assert gd.kda_scan_kernel_supported(512, 4 * G, 128, 128)
    assert not gd.kda_scan_kernel_supported(512, 4 * G, 64, 128)
    assert not gd.kda_scan_kernel_supported(512, 4 * G, 128, 64)
    assert not gd.kda_scan_kernel_supported(150, 4 * G, 128, 128)
    assert not gd.kda_scan_kernel_supported(512, G + 1, 128, 128)
    # the CPU is not armed, whatever the shapes
    assert not gd.kda_scan_kernel_armed(512, 4 * G, 128, 128)
    with pytest.raises(ValueError, match="no scan kernel"):
        gd.kda_chunked_kernel(
            *(jnp.zeros(s) for s in ((1, 64, 128), (1, 64, 128), (1, 64, 128),
                                     (1, 64, 128), (1, 64, 2),
                                     (1, 2, 64, 64))))

    def kernels_in(cfg, S):
        lp = {n: jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
              for n, a in jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.key(0), jnp.float32)
        )["dense_layers"].items()}
        text = str(jax.make_jaxpr(
            lambda x, lp: llama._kda_mixer(x, lp, cfg))(
                jax.ShapeDtypeStruct((1, S, cfg.hidden_size), jnp.float32),
                lp))
        return text.count("pallas_call")

    wide = dataclasses.replace(
        CFG, linear_key_head_dim=128, linear_value_head_dim=128,
        linear_num_key_heads=G, linear_num_value_heads=G)
    assert kernels_in(wide, 128) == 0           # the CPU: the XLA form
    # as on a TPU (the kernel interpreted here)
    monkeypatch.setattr(gd, "kda_scan_kernel_armed",
                        gd.kda_scan_kernel_supported)
    assert kernels_in(wide, 128) == 1
    assert kernels_in(wide, 100) == 0           # a ragged length
    assert kernels_in(wide, 1) == 0             # the decode step
    assert kernels_in(CFG, 128) == 0            # 16-lane heads
    assert kernels_in(dataclasses.replace(
        wide, linear_num_key_heads=G + 1,
        linear_num_value_heads=G + 1), 128) == 0


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


# which of a leaf's four rows hold a sequence
ACTIVITY = {"none": (0, 0, 0, 0), "all": (1, 1, 1, 1),
            "leading_idle": (0, 0, 1, 1), "trailing_idle": (1, 1, 0, 0),
            "alternating": (0, 1, 0, 1), "one_live_in_the_middle": (0, 0, 1, 0)}


@pytest.mark.parametrize("rows,dtype", [
    (rows, jnp.float32) for rows in ACTIVITY.values()] + [
    (ACTIVITY["alternating"], jnp.bfloat16)],
    ids=list(ACTIVITY) + ["alternating_bf16_state"])
def test_the_step_kernels_twin_is_the_step_in_place(rows, dtype):
    """The kernel ``kda_delta_step`` over the whole state leaf (3 layers
    x 4 slots x two groups of heads), under the TPU interpreter — which
    models the pipeline's buffers, so an output buffer no step wrote
    goes back as NaN (plain ``interpret=True`` cannot see that): the
    stepped layer's live rows are ``gated_delta_step``'s with the vector
    decay; an idle row — ``inf`` and NaN in it too: it is never moved —
    and every other layer bit for bit, with no row live as well."""
    from jax.experimental.pallas import tpu as pltpu

    Lg, Bk, Hk, dk, dv = 3, 4, 16, 128, 128
    ks = jax.random.split(jax.random.key(1), 6)
    q = gd.l2norm(jax.random.normal(ks[0], (Bk, Hk, dk))) * dk ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (Bk, Hk, dk)))
    v = jax.random.normal(ks[2], (Bk, Hk, dv))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (Bk, Hk, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (Bk, Hk)))
    active = jnp.asarray(rows, bool)
    live, idle = np.asarray(active), ~np.asarray(active)
    states = jax.random.normal(ks[5], (Lg, Bk, Hk, dk, dv))
    garbage = states[1].at[:, 0].set(jnp.inf).at[:, Hk - 1].set(jnp.nan)
    states = states.at[1].set(jnp.where(
        active[:, None, None, None], states[1], garbage)).astype(dtype)
    o, new = jax.jit(lambda *a: gd.gated_delta_step_kernel(
        *a, interpret=pltpu.InterpretParams(uninitialized_memory="nan")))(
        q, k, v, g, beta, gd.live_first(active), states, jnp.int32(1))
    o_ref, s_ref = gd.gated_delta_step(q, k, v, g, beta, states[1])
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(o[live], o_ref[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(new[1][live].astype(jnp.float32),
                               s_ref[live].astype(jnp.float32),
                               rtol=tol, atol=tol)
    bits = lambda a: np.asarray(a).view(                    # noqa: E731
        np.uint32 if dtype == jnp.float32 else np.uint16)
    assert new.dtype == dtype
    np.testing.assert_array_equal(bits(new[1])[idle], bits(states[1])[idle])
    for other in (0, 2):
        np.testing.assert_array_equal(bits(new[other]), bits(states[other]))
    assert not np.any(np.asarray(o)[idle])      # nothing is read out
