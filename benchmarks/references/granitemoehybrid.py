"""Granite 4.0-H's decoder (IBM, ``model_type: granitemoehybrid``), as
its ``config.json`` and the published ``modeling_granitemoehybrid.py``
give it, for a model WITHOUT routed experts (``num_local_experts`` 0:
the block's only FFN is the dense "shared" MLP). ``x`` is a block's
input, ``rms(y; g) = g y / sqrt(mean y^2 + eps)``, ``r`` the
``residual_multiplier``. A block is ``h = x + r mixer(rms(x; g_in))``,
``out = h + r mlp(rms(h; g_mlp))``; layer ``i`` (0-based) mixes by
ATTENTION when ``i % full_attention_interval == full_attention_place``
(the published ``layer_types`` list, which is periodic) and by a
MAMBA-2 state-space layer otherwise.

Mamba-2 (H heads of width P, a state of N values a head channel, G
groups sharing B and C, a convolution of K taps), ``a`` the normed input
of token t:

    [z xBC] = a W_in;  dt = a W_dt     z: H P; xBC: H P + 2 G N; dt: H
    xBC_t <- silu(sum_j c[:, j] xBC_{t-K+1+j} + b_c)   depthwise, causal,
                                   WITH bias; xBC before the sequence is 0
    [x B C] = xBC                  x: H x P; B, C: G x N, a group's heads
                                   (H / G consecutive) share them
    dt = softplus(dt + dt_bias)    no clamp;  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T     S: P x N a head, S_0 = 0
    y_t = S_t C_t + D x_t
    y = rms(y_t silu(z_t); g_o)    the gate BEFORE the norm, the mean
                                   square over a group's H P / G channels
    mixer = y W_out

run here TOKEN BY TOKEN (a ``lax.scan`` over the positions): no chunked
form, no cache, no kernel.

Attention (H query heads, KV key/value heads of width hd), NO rotary
embedding (``position_embedding_type`` "nope"):

    q, k, v = a Wq, a Wk, a Wv
    causal softmax(q.k * attention_multiplier) v;  mixer = attn Wo

MLP: ``W_down (silu(m W_gate) * (m W_up))``. Ends: the embedding row
times ``embed_scale``; a final rms; logits = h E^T / ``logits_divisor``
over the TIED embedding E.

ASSUMED (no key in ``config.json``; the published modelling code; the
configuration file's ``assumed`` says the same): ``in_proj`` is
published as one matrix ``[z | xBC | dt]`` and stored here taken apart
(``ssd_win`` = ``[z | xBC]``, ``ssd_wdt`` = ``dt``), the dense MLP's
``input_linear`` as ``w_gate | w_up``; the gate is applied before the
norm and the norm runs over ALL the inner channels of a group
(``rmsnorm=True, norm_before_gate=False``); the step has no clamp
(``time_step_limit`` (0, inf)); the convolution is followed by SiLU;
``dt``, ``A``, ``D`` and ``S`` are float32.

WHERE THE LEAVES LIE: one stack, ``params["layers"]``. Leaves every
layer has are stacked over all L layers (``attn_norm``, ``mlp_norm``,
``w_gate``, ``w_up``, ``w_down``). The attention layers' (``wq``,
``wk``, ``wv``, ``wo``) are stacked over the Lf attention layers alone,
the state-space layers' (``ssd_win``, ``ssd_wdt``, ``ssd_conv``
(channels, K), ``ssd_conv_b``, ``ssd_A_log``, ``ssd_dt_bias``,
``ssd_D``, ``ssd_norm``, ``ssd_wout``) over the Lg others, each layer
at its place among its kind. ``params["lm_head"]`` is absent.
"""

import functools

import jax
import jax.numpy as jnp


def _f32(leaf, *index, rows=None):
    """One stored leaf (of a layer) as float32: bf16 upcast, or int8
    times its float32 scale an output channel. ``rows`` gathers rows
    before the upcast (the embedding of a large vocabulary)."""
    if isinstance(leaf, dict):
        if set(leaf) != {"q", "scale"}:
            raise ValueError(f"stored as {sorted(leaf)}: this reference "
                             f"reads bf16 and per-channel int8")
        parts = (leaf["q"], leaf["scale"])
    else:
        parts = (leaf,)
    return _pick(parts, index, rows)


@jax.jit
def _pick(parts, index, rows):
    """The indices are traced, so a leaf's layers share one program."""
    for i in index:
        parts = tuple(p[i] for p in parts)
    if rows is not None:
        parts = (parts[0][rows],) + parts[1:]
    if len(parts) == 2:
        return parts[0].astype(jnp.float32) * parts[1][..., None, :]
    return parts[0].astype(jnp.float32)


def _rms(y, g, eps):
    return g * y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "scale", "r"))
def _attention(x, w, *, heads, kv_heads, eps, scale, r):
    """The attention mixer, no rotary embedding; returns the stream."""
    T, D = x.shape
    a = _rms(x, w["attn_norm"], eps)
    q, k, v = a @ w["wq"], a @ w["wk"], a @ w["wv"]
    hd = q.shape[-1] // heads
    q = q.reshape(T, heads, hd)
    k, v = k.reshape(T, kv_heads, hd), v.reshape(T, kv_heads, hd)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    group = heads // kv_heads

    def one_kv_head(qkv):                     # its queries: (T, group, hd)
        qg, kg, vg = qkv
        s = jnp.einsum("thd,sd->hts", qg, kg) * scale
        p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,sd->thd", p, vg)

    att = jax.lax.map(one_kv_head, (
        q.reshape(T, kv_heads, group, hd).swapaxes(0, 1),
        k.swapaxes(0, 1), v.swapaxes(0, 1)))  # (kv, T, group, hd)
    att = att.swapaxes(0, 1).reshape(T, heads * hd)
    return x + r * (att @ w["wo"])


@functools.partial(jax.jit, static_argnames=(
    "groups", "heads", "n", "p", "eps", "r"))
def _mamba2(x, w, *, groups, heads, n, p, eps, r):
    """The state-space mixer, the recurrence one token at a time;
    returns the stream."""
    T, D = x.shape
    inner = heads * p
    a = _rms(x, w["attn_norm"], eps)
    zu, dt = a @ w["ssd_win"], a @ w["ssd_wdt"]
    z, u = zu[:, :inner], zu[:, inner:]
    c = w["ssd_conv"]                                     # (channels, K)
    K, ch = c.shape[-1], u.shape[-1]
    ext = jnp.concatenate([jnp.zeros((K - 1, ch), u.dtype), u])
    u = jax.nn.silu(sum(ext[j:j + T] * c[:, j] for j in range(K))
                    + w["ssd_conv_b"])
    xs = u[:, :inner].reshape(T, groups, heads // groups, p)
    Bm = u[:, inner:inner + groups * n].reshape(T, groups, n)
    Cm = u[:, inner + groups * n:].reshape(T, groups, n)
    dt = jax.nn.softplus(dt + w["ssd_dt_bias"]).reshape(
        T, groups, heads // groups)
    A = -jnp.exp(w["ssd_A_log"]).reshape(groups, heads // groups)
    D = w["ssd_D"].reshape(groups, heads // groups)

    def token(S, t):                          # S: (G, H / G, P, N)
        x_t, b_t, c_t, dt_t = t
        S = S * jnp.exp(dt_t * A)[..., None, None] + (
            dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        y = jnp.sum(S * c_t[:, None, None, :], axis=-1)
        return S, y + D[..., None] * x_t

    _, y = jax.lax.scan(
        token, jnp.zeros((groups, heads // groups, p, n), jnp.float32),
        (xs, Bm, Cm, dt))
    y = y.reshape(T, groups, inner // groups) * jax.nn.silu(z).reshape(
        T, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return x + r * ((y.reshape(T, inner) * w["ssd_norm"]) @ w["ssd_wout"])


@functools.partial(jax.jit, static_argnames=("eps", "r"))
def _mlp(h, w, *, eps, r):
    m = _rms(h, w["mlp_norm"], eps)
    return h + r * ((jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"]))
                    @ w["w_down"])


VOCAB_BLOCK = 32768       # the head, a block of the vocabulary at a time

ATTENTION = ("wq", "wk", "wv", "wo")
RECURRENT = ("ssd_win", "ssd_wdt", "ssd_conv", "ssd_conv_b", "ssd_A_log",
             "ssd_dt_bias", "ssd_D", "ssd_norm", "ssd_wout")
MLP = ("mlp_norm", "w_gate", "w_up", "w_down")


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, *, eps):
    return _rms(x, w, eps)


@jax.jit
def _tied_head(h, rows):
    return h @ rows.T


def forward(params, model, ids, positions):
    """``params`` is the served tree as stored (module docstring), read
    a layer at a time. Returns the logits at ``positions``."""
    stated = {"linear_decay": "ssd", "tie_word_embeddings": True,
              "mlp": "swiglu", "num_experts": 0, "qk_norm": False,
              "attn_gate": False, "norm": "rmsnorm"}
    for key, want in stated.items():
        if model.get(key, want) != want:
            raise ValueError(f"this reference is the granitemoehybrid "
                             f"block without routed experts ({key} "
                             f"{want!r}); the model group states "
                             f"{model[key]!r}")
    if any(model.get("rope_layers", ())) or "lm_head" in params:
        raise ValueError("this reference rotates nothing (rope_layers all "
                         "0) and reads a tied head")
    eps = float(model.get("rms_norm_eps", 1e-5))
    period = int(model["full_attention_interval"])
    place = int(model.get("full_attention_place", -1)) % period
    hd = int(model["head_dim"])
    scale = float(model.get("attention_multiplier", 0.0)) or hd ** -0.5
    r = float(model.get("residual_multiplier", 1.0))
    leaves = params["layers"]
    full = recurrent = 0
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"], rows=jnp.asarray(ids[0])) \
            * float(model.get("embed_scale", 1.0))
        for layer in range(model["num_layers"]):
            w = {"attn_norm": _f32(leaves["attn_norm"], layer)}
            if layer % period == place:
                w.update({k: _f32(leaves[k], full) for k in ATTENTION})
                full += 1
                h = _attention(
                    x, w, heads=model["num_heads"],
                    kv_heads=model["num_kv_heads"], eps=eps, scale=scale,
                    r=r)
            else:
                w.update({k: _f32(leaves[k], recurrent) for k in RECURRENT})
                recurrent += 1
                h = _mamba2(
                    x, w, groups=model["linear_num_key_heads"],
                    heads=model["linear_num_value_heads"],
                    n=model["linear_key_head_dim"],
                    p=model["linear_value_head_dim"], eps=eps, r=r)
            x = _mlp(h, {k: _f32(leaves[k], layer) for k in MLP}, eps=eps,
                     r=r)
            jax.block_until_ready(x)      # a layer's float32 at a time
        h = _final_norm(x[jnp.asarray(positions)], _f32(params["final_norm"]),
                        eps=eps)
        V = model["vocab_size"]
        logits = jnp.concatenate([
            _tied_head(h, _f32(params["embed"],
                               rows=jnp.arange(a, min(a + VOCAB_BLOCK, V))))
            for a in range(0, V, VOCAB_BLOCK)], axis=-1)
        return logits / float(model.get("logits_divisor", 1.0))
