"""ops/ssd.py: a state-space layer's recurrence (Mamba-2: a scalar decay
a head, no delta rule) in its five forms, float32 on the CPU: the
recurrence token by token is what the chunked form is held to at every
block size and ragged length — and, as one more form of the same tests,
the chunked form as ONE Pallas kernel over the operands as the mixer has
them (interpreted here; at the kernels' tolerance, 2e-5 of the largest
output and state) —, the step what the Pallas kernel over the cache's
whole state leaf is held to (interpreted), in place, an idle row's state
left bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.ops import ssd


def draw(seed, B, T, H=4, P=8, N=16, G=1, steps="mixed", equal=False):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    Bm = jax.random.normal(ks[1], (B, T, G, N))
    Cm = jax.random.normal(ks[2], (B, T, G, N))
    if equal:   # a run of one repeated token: a page of spaces
        x, Bm, Cm = (jnp.broadcast_to(a[:, :1], a.shape)
                     for a in (x, Bm, Cm))
    lo, hi = {"mixed": (-7.0, -2.0), "tiny": (-30.0, -25.0),
              "large": (0.0, 2.0)}[steps]
    dt = jax.nn.softplus(jax.random.uniform(ks[3], (B, T, H), minval=lo,
                                            maxval=hi))
    A = -jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0)
    s = jax.random.normal(ks[5], (B, H, P, N))
    return x, dt, A, Bm, Cm, jnp.ones((H,)), s


def close(got, want, tol=1e-5):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


KERNEL = dict(H=16, P=64, N=128)    # one grid step's heads, a pair a tile
# one traced program a shape (op by op the XLA form is thirty compiles)
recurrent = jax.jit(ssd.ssd_recurrent)
xla_chunked = jax.jit(ssd.ssd_chunked, static_argnames=("block",))


def tol(block):     # the kernels' tolerance, or the XLA form's
    return 2e-5 if block == "kernel" else 1e-5


def chunked(x, dt, A, Bm, Cm, D, s, block):
    """The chunked form by ``block`` tokens, or (``"kernel"``) as the
    interpreted scan kernel over lane-dense ``x`` and one group's B, C."""
    if block != "kernel":
        return xla_chunked(x, dt, A, Bm, Cm, D, s, block=block)
    y, s = ssd.ssd_chunked_kernel(
        x.reshape(x.shape[:2] + (-1,)), dt, A, Bm[:, :, 0], Cm[:, :, 0], D,
        s, interpret=True)
    return y.reshape(x.shape), s


@pytest.mark.parametrize("T,block,G", [
    (1, 4, 1), (7, 4, 2), (64, 16, 1), (100, 64, 1), (100, 16, 2),
    (33, 8, 1), (128, "kernel", 1)])
def test_the_chunked_form_is_the_recurrence(T, block, G):
    # the kernel: two blocks carried, two grid steps of heads, two rows
    args = draw(T, 2, T, **{**KERNEL, "H": 32}) if block == "kernel" \
        else draw(T + block, 2, T, G=G)
    y0, s0 = recurrent(*args)
    y1, s1 = chunked(*args, block)
    close(y1, y0, tol(block))
    close(s1, s0, tol(block))


@pytest.mark.parametrize("block", [32, "kernel"])
@pytest.mark.parametrize("steps,equal", [
    ("mixed", True), ("tiny", False), ("tiny", True), ("large", False)],
    ids=["equal_tokens", "tiny_steps", "tiny_equal", "large_steps"])
def test_runs_that_cancel_or_underflow_stay_the_recurrence(steps, equal,
                                                           block):
    """Every exponent is a difference taken before the ``exp``: a run of
    large steps (exp(-32 a token): a block's total underflows) and a run
    of tiny ones (every decay 1 - 1e-12) are the recurrence still."""
    args = draw(5, 1, 128, steps=steps, equal=equal, **KERNEL) \
        if block == "kernel" else draw(5, 1, 96, steps=steps, equal=equal)
    y0, s0 = recurrent(*args)
    y1, s1 = chunked(*args, block)
    assert bool(jnp.all(jnp.isfinite(y1))) and bool(jnp.all(jnp.isfinite(s1)))
    close(y1, y0, tol(block))
    close(s1, s0, tol(block))


def test_the_step_is_the_written_equations():
    x, dt, A, Bm, Cm, D, s = draw(3, 2, 1)
    y, new = ssd.ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, s)
    for b in range(2):
        for h in range(4):
            S = np.exp(float(dt[b, 0, h] * A[h])) * np.asarray(s[b, h]) \
                + float(dt[b, 0, h]) * np.outer(x[b, 0, h], Bm[b, 0, 0])
            np.testing.assert_allclose(new[b, h], S, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                y[b, h], S @ np.asarray(Cm[b, 0, 0]) + np.asarray(x[b, 0, h]),
                rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("T,at,block", [
    (96, 40, 16), (64, 64, 16), (128, 64, "kernel")])
def test_two_chunks_carry_the_state(T, at, block):
    """(The kernel's two calls are held to the recurrence over the
    whole: the state it hands on is the state it takes.)"""
    kernel = block == "kernel"
    x, dt, A, Bm, Cm, D, s = draw(11, 1 if kernel else 2, T,
                                  **(KERNEL if kernel else {}))
    y0, s0 = recurrent(x, dt, A, Bm, Cm, D, s) if kernel \
        else xla_chunked(x, dt, A, Bm, Cm, D, s, block=16)
    ya, mid = chunked(x[:, :at], dt[:, :at], A, Bm[:, :at], Cm[:, :at], D,
                      s, block)
    if at == T:
        close(mid, s0, 1e-6)
        return
    yb, s1 = chunked(x[:, at:], dt[:, at:], A, Bm[:, at:], Cm[:, at:], D,
                     mid, block)
    close(jnp.concatenate([ya, yb], 1), y0, tol(block))
    close(s1, s0, tol(block))


@pytest.mark.parametrize("T,n,block", [
    (70, 50, 16), (32, 0, 16), (128, 50, "kernel"), (64, 0, "kernel")])
def test_a_step_of_zero_leaves_the_state_alone(T, n, block):
    """How the caller pads: tokens whose ``dt`` is 0 decay nothing and
    write nothing, whatever their x, B and C hold — a whole block of
    them leaves the state bit for bit."""
    kernel = block == "kernel"
    x, dt, A, Bm, Cm, D, s = draw(13, 1 if kernel else 2, T,
                                  **(KERNEL if kernel else {}))
    dt = jnp.where(jnp.arange(T)[None, :, None] < n, dt, 0.0)
    _, got = chunked(x, dt, A, Bm, Cm, D, s, block)
    if n:
        _, want = recurrent(x[:, :n], dt[:, :n], A, Bm[:, :n],
                                    Cm[:, :n], D, s)
        close(got, want, tol(block))
    else:
        np.testing.assert_array_equal(got, s)


@pytest.mark.parametrize("x_at", [1024, 128], ids=["whole_block", "sliced"])
def test_the_kernel_reads_x_where_the_convolution_left_it(x_at):
    """``x`` out of a wider array from lane ``x_at`` on: a whole block
    of a grid step's inputs further on is another block index, anything
    else a slice in front of the kernel — the same numbers to the bit."""
    x, dt, A, Bm, Cm, D, s = draw(23, 1, 64, **KERNEL)
    flat = x.reshape(1, 64, -1)
    wide = jnp.concatenate([jnp.full((1, 64, x_at), jnp.nan), flat,
                            jnp.full((1, 64, 256), jnp.nan)], axis=-1)
    want = ssd.ssd_chunked_kernel(flat, dt, A, Bm[:, :, 0], Cm[:, :, 0], D,
                                  s, interpret=True)
    got = ssd.ssd_chunked_kernel(wide, dt, A, Bm[:, :, 0], Cm[:, :, 0], D,
                                 s, x_at=x_at, interpret=True)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_the_kernel_takes_ragged_rows_and_a_run_of_equal_tokens():
    """One call, as a chunk program of several prompts makes it: a row
    that ends inside the second block, a row of ONE repeated token (a
    page of spaces) and a row that is all padding, each the recurrence
    over its own valid tokens."""
    T, n_valid = 128, (100, 128, 0)
    x, dt, A, Bm, Cm, D, s = draw(29, 3, T, **KERNEL)
    x, Bm, Cm = (a.at[1].set(a[1, :1]) for a in (x, Bm, Cm))
    dt = jnp.where(jnp.arange(T)[None, :, None]
                   < jnp.asarray(n_valid)[:, None, None], dt, 0.0)
    y, new = chunked(x, dt, A, Bm, Cm, D, s, "kernel")
    for b, n in enumerate(n_valid):
        if not n:
            np.testing.assert_array_equal(new[b], s[b])
            continue
        y0, s0 = recurrent(*(a[b:b + 1, :n] for a in (x, dt)), A,
                                   Bm[b:b + 1, :n], Cm[b:b + 1, :n], D,
                                   s[b:b + 1])
        close(y[b:b + 1, :n], y0, 2e-5)
        close(new[b:b + 1], s0, 2e-5)


# which of a leaf's four rows hold a sequence
ACTIVITY = {"none": (0, 0, 0, 0), "all": (1, 1, 1, 1),
            "leading_idle": (0, 0, 1, 1), "trailing_idle": (1, 1, 0, 0),
            "alternating": (0, 1, 0, 1), "one_live_in_the_middle": (0, 0, 1, 0)}


def bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("rows", ACTIVITY.values(), ids=ACTIVITY.keys())
def test_the_step_kernel_is_the_step_in_place(rows):
    """The kernel over the WHOLE leaf (3 layers x 4 slots), layer 1,
    under the TPU interpreter — which models the pipeline's buffers, so
    an output buffer no step wrote goes back as NaN (plain
    ``interpret=True`` reads the block anew every step and cannot see
    that): the live rows' state and read-out are the step's; an idle
    row's state — whatever a stale slot holds, ``inf`` and NaN too: it
    is never moved, let alone multiplied by one — and every other
    layer's are bit for bit what they were, with no row live as well."""
    from jax.experimental.pallas import tpu as pltpu
    from generativeaiexamples_tpu.ops.gated_delta import live_first

    x, dt, A, Bm, Cm, D, s = draw(17, 4, 1, N=128)
    active = jnp.asarray(rows, bool)
    live, idle = np.asarray(active), ~np.asarray(active)
    garbage = s.at[:, 0].set(jnp.inf).at[:, 1].set(jnp.nan)
    leaf = jnp.stack([s, jnp.where(active[:, None, None, None], 2.0 * s,
                                   garbage), 3.0 * s])
    dt1 = jnp.where(active[:, None], dt[:, 0], 0.0)
    y, new = jax.jit(lambda *a: ssd.ssd_step_kernel(
        *a, interpret=pltpu.InterpretParams(uninitialized_memory="nan")))(
        x[:, 0], dt1, A, Bm[:, 0], Cm[:, 0], D, live_first(active), leaf,
        jnp.int32(1))
    y0, s0 = ssd.ssd_step(x[:, 0], dt1, A, Bm[:, 0], Cm[:, 0], D, leaf[1])
    if live.any():
        close(y[live], y0[live])
        close(new[1][live], s0[live], 1e-6)
    np.testing.assert_array_equal(bits(new[1])[idle], bits(leaf[1])[idle])
    np.testing.assert_array_equal(bits(new[0]), bits(leaf[0]))
    np.testing.assert_array_equal(bits(new[2]), bits(leaf[2]))
    # an idle row reads out nothing: what is left is the skip
    np.testing.assert_array_equal(y[idle], (D[:, None] * x[:, 0])[idle])


def test_the_kernel_is_taken_only_where_it_fits():
    assert ssd.step_kernel_supported(64, 1, 64, 128)    # granite-4.0-h
    assert not ssd.step_kernel_supported(64, 8, 64, 128)    # B, C a group
    assert not ssd.step_kernel_supported(64, 1, 64, 64)     # half the lanes
    assert not ssd.step_kernel_supported(128, 1, 128, 128)  # 8 MiB a row
    # the scan: a chunk and the chunk of four prompts' rows, granite's
    for T in (512, 1024):
        assert ssd.scan_kernel_supported(T, 64, 1, 64, 128)
    assert not ssd.scan_kernel_supported(96, 64, 1, 64, 128)    # ragged
    assert not ssd.scan_kernel_supported(512, 64, 2, 64, 128)   # two groups
    assert not ssd.scan_kernel_supported(512, 64, 1, 64, 64)    # half lanes
    assert not ssd.scan_kernel_supported(512, 64, 1, 128, 128)  # no pair
    assert not ssd.scan_kernel_supported(512, 60, 1, 64, 128)   # odd heads
    # the CPU is not armed, whatever the shapes
    assert not ssd.scan_kernel_armed(512, 64, 1, 64, 128)
    with pytest.raises(ValueError, match="no scan kernel"):
        ssd.ssd_chunked_kernel(*(jnp.zeros(shape) for shape in (
            (1, 96, 1024), (1, 96, 16), (16,), (1, 96, 128), (1, 96, 128),
            (16,), (1, 16, 64, 128))))
