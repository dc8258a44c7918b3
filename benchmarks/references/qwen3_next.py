"""Qwen3-Next's decoder (Qwen, ``model_type: qwen3_next``), as its
``config.json`` and the published ``modeling_qwen3_next.py`` give it.
``x`` is a block's input, ``rms(y; g) = g y / sqrt(mean y^2 + eps)``.
A block is ``h = x + mixer(rms(x; g_in))``, ``out = h + moe(rms(h;
g_mlp))``; layer ``i`` (0-based) mixes by ATTENTION when ``(i + 1) %
full_attention_interval == 0`` and by the GATED DELTA RULE otherwise.

Gated delta rule (Hk key heads, Hv value heads of width dk / dv, a
convolution of K taps), ``a`` the normed input of token t:

    [q k v z] = a W_qkvz           q, k: Hk x dk; v, z: Hv x dv
    [b g_in]  = a W_ba             Hv + Hv scalars
    u = [q k v];  u_t <- silu(sum_j c[:, j] u_{t-K+1+j})   depthwise,
                                   causal, no bias; u before the
                                   sequence is 0
    q, k: each key head serves Hv / Hk consecutive value heads
    q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk);  k <- k / sqrt(sum k^2 + 1e-6)
    beta = sigmoid(b);  g = -exp(A_log) softplus(g_in + dt_bias)
    S_t = exp(g_t) S_{t-1}         S: dk x dv a head, S_0 = 0
    d_t = beta_t (v_t - S_t^T k_t);  S_t <- S_t + k_t d_t^T
    o_t = S_t^T q_t
    y = rms(o_t; g_o) silu(z_t)    per head over its dv values
    mixer = concat_heads(y) W_out

run here TOKEN BY TOKEN (a ``lax.scan`` over the positions): no chunked
form, no cache, no kernel.

Attention (H query heads, KV key/value heads of width hd):

    [q gate] = a Wq, a Wz          the gate as wide as the queries
    k, v = a Wk, a Wv
    q, k = rms(q; g_q), rms(k; g_k)     per head, before the rotation
    the FIRST hd * partial_rotary_factor values of each head rotated,
        pairs (i, i + half of that part), theta^(-2i / part); the rest pass
    causal softmax(q.k / sqrt(hd)) v;  mixer = (attn * sigmoid(gate)) Wo

Experts (every layer): ``p = softmax(m W_r)`` over all experts, the
``num_experts_per_tok`` largest, weights ``p_i / sum_chosen p``; an
expert is ``W_down (silu(m W_gate) * (m W_up))``; the shared expert
times ``sigmoid(m . w_s)``. Under a share (``experts_held`` /
``experts_first``) the tree holds those experts only and what a token
sends elsewhere adds nothing, as on a chip of the deployment before the
exchange. Then a final rms and an untied head.

ASSUMED (no key in ``config.json``; the published modelling code; the
configuration file's ``assumed`` says the same): every norm weight but
``g_o`` is published zero-centred and stored here as ``1 + w``; the
convolution has no bias and SiLU behind it; q and k are l2-normed AFTER
the convolution; ``g``, ``beta`` and ``S`` are float32; ``W_qkvz`` /
``W_ba`` are published grouped by key head and stored here taken apart
(``[q k v z]``, ``[b a]``); rotary pairs are (i, i + half); the
multi-token-prediction module is left out.

WHERE THE LEAVES LIE: one stack, ``params["layers"]``. Leaves every
layer has are stacked over all L layers (``attn_norm``, ``mlp_norm``,
``router``, the expert stacks ``w_gate`` / ``w_up`` / ``w_down`` (L, E
held, ...), ``ws_gate`` / ``ws_up`` / ``ws_down``, ``ws_gate_w``). The
attention layers' leaves (``wq``, ``wk``, ``wv``, ``wz``, ``wo``,
``q_norm``, ``k_norm``) are stacked over the Lf attention layers alone,
layer i at ``i // interval``; the recurrent layers' (``gdn_wqkvz``,
``gdn_wba``, ``gdn_conv`` (channels, K), ``gdn_A_log``, ``gdn_dt_bias``,
``gdn_norm``, ``gdn_wout``) over the Lg others, layer i at ``i - i //
interval``.
"""

import functools

import jax
import jax.numpy as jnp


def _f32(leaf, *index, rows=None, cols=None):
    """One stored leaf (of a layer) as float32: bf16 upcast, or int8
    times its float32 scale an output channel. ``rows`` gathers rows and
    ``cols=(a, b)`` takes a block of output channels before the upcast
    (the embedding and the head of a large vocabulary)."""
    if isinstance(leaf, dict):
        if set(leaf) != {"q", "scale"}:
            raise ValueError(f"stored as {sorted(leaf)}: this reference "
                             f"reads bf16 and per-channel int8")
        parts = (leaf["q"], leaf["scale"])
    else:
        parts = (leaf,)
    return _pick(parts, index, rows, cols)


@functools.partial(jax.jit, static_argnums=(3,))
def _pick(parts, index, rows, cols):
    """The indices are traced, so a leaf's layers share one program."""
    for i in index:
        parts = tuple(p[i] for p in parts)
    if rows is not None:
        parts = (parts[0][rows],) + parts[1:]
    if cols is not None:
        parts = tuple(p[..., cols[0]:cols[1]] for p in parts)
    if len(parts) == 2:
        return parts[0].astype(jnp.float32) * parts[1][..., None, :]
    return parts[0].astype(jnp.float32)


def _rms(y, g, eps):
    return g * y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)


def _rope_first(x, theta, part):
    """x: (T, heads, hd), position t = row t; the first ``part`` values
    rotated, pairs (i, i + part / 2)."""
    T = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, part, 2, dtype=jnp.float32) / part)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :part // 2], x[..., part // 2:part]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., part:]], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "theta", "part"))
def _attention(x, w, *, heads, kv_heads, eps, theta, part):
    """The gated attention mixer; returns the residual stream."""
    T, D = x.shape
    a = _rms(x, w["attn_norm"], eps)
    q, k, v, z = a @ w["wq"], a @ w["wk"], a @ w["wv"], a @ w["wz"]
    hd = q.shape[-1] // heads
    q = _rms(q.reshape(T, heads, hd), w["q_norm"], eps)
    k = _rms(k.reshape(T, kv_heads, hd), w["k_norm"], eps)
    v = v.reshape(T, kv_heads, hd)
    q, k = _rope_first(q, theta, part), _rope_first(k, theta, part)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    group = heads // kv_heads

    def one_kv_head(qkv):                     # its queries: (T, group, hd)
        qg, kg, vg = qkv
        s = jnp.einsum("thd,sd->hts", qg, kg) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,sd->thd", p, vg)

    att = jax.lax.map(one_kv_head, (
        q.reshape(T, kv_heads, group, hd).swapaxes(0, 1),
        k.swapaxes(0, 1), v.swapaxes(0, 1)))  # (kv, T, group, hd)
    att = att.swapaxes(0, 1).reshape(T, heads * hd)
    return x + (att * jax.nn.sigmoid(z)) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv", "eps"))
def _delta_rule(x, w, *, hk, hv, dk, dv, eps):
    """The gated delta-rule mixer, the recurrence one token at a time;
    returns the residual stream."""
    T, D = x.shape
    a = _rms(x, w["attn_norm"], eps)
    ch = 2 * hk * dk + hv * dv
    qkvz, ba = a @ w["gdn_wqkvz"], a @ w["gdn_wba"]
    u, z = qkvz[:, :ch], qkvz[:, ch:]
    c = w["gdn_conv"]                                     # (channels, K)
    K = c.shape[-1]
    ext = jnp.concatenate([jnp.zeros((K - 1, ch), u.dtype), u])
    u = jax.nn.silu(sum(ext[j:j + T] * c[:, j] for j in range(K)))
    q = u[:, :hk * dk].reshape(T, hk, dk)
    k = u[:, hk * dk:2 * hk * dk].reshape(T, hk, dk)
    v = u[:, 2 * hk * dk:].reshape(T, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / dk ** 0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(w["gdn_A_log"]) * jax.nn.softplus(
        ba[:, hv:] + w["gdn_dt_bias"])

    def token(S, t):                          # S: (hv, dk, dv)
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    y = _rms(o, w["gdn_norm"], eps) * jax.nn.silu(z.reshape(T, hv, dv))
    return x + y.reshape(T, hv * dv) @ w["gdn_wout"]


def _gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


@functools.partial(jax.jit, static_argnames=("top_k", "first", "eps"))
def _expert_block(h, w, at, gate, up, down, *, top_k, first, eps):
    """Softmax routing over ALL the router's columns; the HELD experts
    one at a time (each cut out of the stored stack and upcast where it
    is used; gate/up: (L, E held, D, F), down: (L, E held, F, D), bf16;
    ``at`` the layer; held expert ``e`` is the layer's expert ``first +
    e``); the shared expert under its gate; the add."""
    T = h.shape[0]
    m = _rms(h, w["mlp_norm"], eps)
    p = jax.nn.softmax(m @ w["router"], axis=-1)          # (T, E)
    mix, chosen = jax.lax.top_k(p, top_k)
    mix = mix / jnp.sum(mix, axis=-1, keepdims=True)
    weight = jnp.zeros_like(p).at[jnp.arange(T)[:, None], chosen].set(mix)

    def expert(t, e):         # one matrix, never the layer's whole slab
        return jax.lax.dynamic_slice(
            t, (at, e, 0, 0), (1, 1) + t.shape[2:])[0, 0].astype(jnp.float32)

    def one(acc, e):
        y = _gated(m, expert(gate, e), expert(up, e), expert(down, e))
        return acc + weight[:, first + e][:, None] * y, None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(gate.shape[1]))
    shared = _gated(m, w["ws_gate"], w["ws_up"], w["ws_down"])
    return h + y + jax.nn.sigmoid(m @ w["ws_gate_w"])[:, None] * shared


VOCAB_BLOCK = 32768       # the head, a block of the vocabulary at a time

ATTENTION = ("wq", "wk", "wv", "wz", "wo", "q_norm", "k_norm")
RECURRENT = ("gdn_wqkvz", "gdn_wba", "gdn_conv", "gdn_A_log", "gdn_dt_bias",
             "gdn_norm", "gdn_wout")
EXPERTS = ("mlp_norm", "router", "ws_gate", "ws_up", "ws_down", "ws_gate_w")


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, *, eps):
    return _rms(x, w, eps)


@jax.jit
def _head(h, lm_head):
    return h @ lm_head


def forward(params, model, ids, positions):
    """``params`` is the served tree as stored (module docstring), read
    a layer at a time. Returns the logits at ``positions``."""
    stated = {"router_score_func": "softmax", "qk_norm": True,
              "attn_gate": True, "shared_expert_gate": True,
              "mlp": "swiglu", "moe_impl": "dropless",
              "num_shared_experts": 1}
    for key, want in stated.items():
        if model.get(key, want) != want:
            raise ValueError(f"this reference is the qwen3_next block "
                             f"({key} {want!r}); the model group states "
                             f"{model[key]!r}")
    eps = float(model.get("rms_norm_eps", 1e-5))
    n = int(model["full_attention_interval"])
    hd = int(model["head_dim"])
    part = int(hd * float(model.get("partial_rotary_factor", 1.0)))
    leaves = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"], rows=jnp.asarray(ids[0]))
        for layer in range(model["num_layers"]):
            w = {"attn_norm": _f32(leaves["attn_norm"], layer)}
            if (layer + 1) % n == 0:
                w.update({k: _f32(leaves[k], layer // n) for k in ATTENTION})
                h = _attention(
                    x, w, heads=model["num_heads"],
                    kv_heads=model["num_kv_heads"], eps=eps,
                    theta=float(model.get("rope_theta", 10000.0)), part=part)
            else:
                w.update({k: _f32(leaves[k], layer - layer // n)
                          for k in RECURRENT})
                h = _delta_rule(
                    x, w, hk=model["linear_num_key_heads"],
                    hv=model["linear_num_value_heads"],
                    dk=model["linear_key_head_dim"],
                    dv=model["linear_value_head_dim"], eps=eps)
            w = {k: _f32(leaves[k], layer) for k in EXPERTS}
            x = _expert_block(
                h, w, jnp.int32(layer), *(
                    leaves[k] for k in ("w_gate", "w_up", "w_down")),
                top_k=int(model["num_experts_per_tok"]),
                first=int(model.get("experts_first", 0)), eps=eps)
            jax.block_until_ready(x)      # a layer's float32 at a time
        h = _final_norm(x[jnp.asarray(positions)], _f32(params["final_norm"]),
                        eps=eps)
        V = model["vocab_size"]
        return jnp.concatenate([
            _head(h, _f32(params["lm_head"],
                          cols=(a, min(a + VOCAB_BLOCK, V))))
            for a in range(0, V, VOCAB_BLOCK)], axis=-1)
