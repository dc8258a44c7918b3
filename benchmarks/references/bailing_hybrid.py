"""Ling-3.0's decoder (inclusionAI, ``model_type: bailing_hybrid``), as
its ``config.json`` gives it and as the published forms its keys name
spell the parts out: Kimi Delta Attention (Kimi Linear, arXiv:2510.26692;
``fla``'s ``KimiDeltaAttention`` / ``kda_gate``), DeepseekV3's latent
attention and its ``noaux_tc`` router with the group limit
(``get_topk_indices``), and the ``layer_group_size`` rule of the same
organisation's ``bailing_moe_linear`` models. ``x`` is a block's input,
``rms(y; g) = g y / sqrt(mean y^2 + eps)``. A block is ``h = x +
mixer(rms(x; g_in))``, ``out = h + ffn(rms(h; g_mlp))``; layer ``i``
(0-based, the PUBLISHED index) mixes by LATENT ATTENTION when ``(i + 1) %
full_attention_interval == 0`` and by KDA otherwise; its ffn is a dense
SwiGLU MLP when ``i < num_dense_layers`` and the experts otherwise.

KDA (H heads of width dk = dv, a convolution of K taps), ``a`` the
normed input of token t:

    u = a W_qkv                    [q k v], each H x dk
    u_t <- silu(sum_j c[:, j] u_{t-K+1+j})     depthwise, causal, no
                                   bias; u before the sequence is 0
    q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk);  k <- k / sqrt(sum k^2 + 1e-6)
    g = floor * sigmoid(exp(A_log[h]) (a W_f + dt_bias))   H x dk, in
                                   (floor, 0): a log-decay A CHANNEL
    beta = sigmoid(a W_b)          one a head
    S_t = diag(exp(g_t)) S_{t-1}   S: dk x dv a head, S_0 = 0; ROW c of
                                   the state decays by exp(g_t[c])
    d_t = beta_t (v_t - S_t^T k_t);  S_t <- S_t + k_t d_t^T
    o_t = S_t^T q_t
    y = rms(o_t; g_o) sigmoid(a W_g)   per head over its dv values
    mixer = concat_heads(y) W_out

run here TOKEN BY TOKEN (a ``lax.scan`` over the positions): no chunked
form, no cache, no kernel.

Latent attention (H heads; R latent, nope + rope key, v value widths):

    [q_n | q_r] = a W_q            ONE matrix, no query norm
    [c | k_r] = a W_kva;  c <- rms(c; g_c)
    k_n, v = c W_kb, c W_vb        H x nope, H x v
    q_r, k_r rotated, pairs (2i, 2i+1), theta^(-2i / rope), no scaling
    causal softmax((q_n.k_n + q_r.k_r) / sqrt(nope + rope)) v
    mixer = concat_heads(o_h sigmoid(a w_gate)[h]) W_o     a gate a HEAD

Experts: ``s = sigmoid(m W_r)`` over all E, float32; ``b = s + e_bias``;
the E experts are ``n_group`` groups of consecutive experts, a group's
score the sum of its two largest ``b``; the ``topk_group`` best groups
stay and ``b`` is filled with 0 elsewhere; the ``num_experts_per_tok``
largest are CHOSEN; their weights are the un-biased ``s`` over their sum
(+ 1e-20) times ``router_scale``; an expert is ``W_down (silu(m W_gate)
* (m W_up))``; the shared expert is added un-weighted. Under a share
(``experts_held`` / ``experts_first``) the tree holds those experts only
and what a token sends elsewhere adds nothing, as on a chip of the
deployment before the exchange. Then a final rms and an untied head.

ASSUMED (the configuration file's ``assumed`` says the same): the latent
layer is the LAST of a group of ``layer_group_size``; as many key as
value heads; the decay's and the output gate's projections are one
matrix each (``no_kda_lora``); the safe gate with its lower bound as
``fla``'s ``kda_gate(lower_bound=)``; SiLU behind the convolution; q and
k l2-normed after it; float32 ``g``, ``beta`` and ``S``; the
multi-token-prediction module and the activation limits (0 on every
layer held) are left out.

WHERE THE LEAVES LIE: two stacks, ``params["dense_layers"]`` (the
``num_dense_layers`` leading layers) and ``params["layers"]`` (the
rest). In each, what every layer has (``attn_norm``, ``mlp_norm``, the
MLP or the experts) is stacked over the stack's layers; the KDA layers'
leaves (``kda_*``) over the stack's KDA layers alone and the latent
layers' over its latent layers alone, in order.
"""

import functools
import math

import jax
import jax.numpy as jnp


def _f32(leaf, *index, rows=None, cols=None, krows=None):
    """One stored leaf (of a layer) as float32: bf16 upcast, or int8
    times its float32 scale an output channel. ``rows`` gathers rows,
    ``cols=(a, b)`` takes a block of output channels and ``krows=(a, b)``
    a block of input rows before the upcast."""
    if isinstance(leaf, dict):
        if set(leaf) != {"q", "scale"}:
            raise ValueError(f"stored as {sorted(leaf)}: this reference "
                             f"reads bf16 and per-channel int8")
        parts = (leaf["q"], leaf["scale"])
    else:
        parts = (leaf,)
    return _pick(parts, index, rows, cols, krows)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _pick(parts, index, rows, cols, krows):
    """The indices are traced, so a leaf's layers share one program."""
    for i in index:
        parts = tuple(p[i] for p in parts)
    if rows is not None:
        parts = (parts[0][rows],) + parts[1:]
    if cols is not None:
        parts = tuple(p[..., cols[0]:cols[1]] for p in parts)
    if krows is not None:
        parts = (parts[0][..., krows[0]:krows[1], :],) + parts[1:]
    if len(parts) == 2:
        return parts[0].astype(jnp.float32) * parts[1][..., None, :]
    return parts[0].astype(jnp.float32)


def _rms(y, g, eps):
    return g * y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)


@functools.partial(jax.jit, static_argnames=("heads", "dk", "dv", "floor",
                                             "eps"))
def _kda(x, w, *, heads, dk, dv, floor, eps):
    """The KDA mixer, the recurrence one token at a time; returns the
    residual stream."""
    T, D = x.shape
    a = _rms(x, w["attn_norm"], eps)
    u = a @ w["kda_wqkv"]
    c = w["kda_conv"]                                     # (channels, K)
    K = c.shape[-1]
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    u = jax.nn.silu(sum(ext[j:j + T] * c[:, j] for j in range(K)))
    q = u[:, :heads * dk].reshape(T, heads, dk)
    k = u[:, heads * dk:2 * heads * dk].reshape(T, heads, dk)
    v = u[:, 2 * heads * dk:].reshape(T, heads, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / dk ** 0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    pre = (a @ w["kda_wf"] + w["kda_dt_bias"]).reshape(T, heads, dk)
    g = floor * jax.nn.sigmoid(jnp.exp(w["kda_A_log"])[None, :, None] * pre)
    beta = jax.nn.sigmoid(a @ w["kda_wb"])                # (T, heads)

    def token(S, t):                          # S: (heads, dk, dv)
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[:, :, None]      # a ROW its own decay
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    y = _rms(o, w["kda_norm"], eps) * jax.nn.sigmoid(
        (a @ w["kda_wg"]).reshape(T, heads, dv))
    return x + y.reshape(T, heads * dv) @ w["kda_wout"]


def _rope_pairs(x, inv, interleave):
    """x: (T, heads, d), position t = row t; pairs (2i, 2i+1) as
    published, or (i, i + d/2). The rotated pairs come back evens first,
    then odds (queries and keys alike, so their products are the
    published ones)."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = (x[..., 0::2], x[..., 1::2]) if interleave \
        else (x[..., :half], x[..., half:])
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


HEAD_GROUP = 8            # attention, this many heads at a time


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "eps", "theta", "interleave", "gated"))
def _latent_attention(x, w, *, heads, nope, rope, eps, theta, interleave,
                      gated):
    """The latent-attention mixer, expanded; returns the residual
    stream."""
    T, D = x.shape
    a = _rms(x, w["attn_norm"], eps)
    q = (a @ w["wq"]).reshape(T, heads, nope + rope)
    kv = a @ w["wkv_a"]
    R = kv.shape[-1] - rope
    c = _rms(kv[:, :R], w["kv_a_norm"], eps)
    inv = 1.0 / theta ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    q_n = q[..., :nope]
    q_r = _rope_pairs(q[..., nope:], inv, interleave)
    k_r = _rope_pairs(kv[:, None, R:], inv, interleave)[:, 0]
    k_n = (c @ w["wk_b"]).reshape(T, heads, nope)
    v = (c @ w["wv_b"]).reshape(T, heads, -1)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    scale = (nope + rope) ** -0.5

    def some_heads(part):                     # (T, HEAD_GROUP, .) each
        qn, qr, kn, vh = part
        s = (jnp.einsum("thd,shd->hts", qn, kn)
             + jnp.einsum("thd,sd->hts", qr, k_r)) * scale
        p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, vh)

    group = math.gcd(heads, HEAD_GROUP)

    def grouped(y):
        return y.reshape(T, heads // group, group,
                         y.shape[-1]).swapaxes(0, 1)
    att = jax.lax.map(some_heads, (grouped(q_n), grouped(q_r),
                                   grouped(k_n), grouped(v)))
    att = att.swapaxes(0, 1).reshape(T, heads, -1)
    if gated:                                 # one gate a head
        att = att * jax.nn.sigmoid(a @ w["wz_head"])[:, :, None]
    return x + att.reshape(T, -1) @ w["wo"]


def _gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


@jax.jit
def _add_gated(acc, m, gate, up, down):
    return acc + _gated(m, gate, up, down)


def route(m, router, bias, *, top_k, scale, n_group, topk_group):
    """A token's weight on each of the router's E columns, (T, E): the
    group-limited choice of the module docstring."""
    T = m.shape[0]
    s = jax.nn.sigmoid(m @ router)                        # (T, E)
    b = s + bias
    if topk_group < n_group:
        groups = b.reshape(T, n_group, -1)
        score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(score, topk_group)
        keep = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], kept].set(True)
        b = jnp.where(jnp.repeat(keep, groups.shape[-1], axis=1), b, 0.0)
    _, chosen = jax.lax.top_k(b, top_k)
    rows = jnp.arange(T)[:, None]
    mix = s[rows, chosen]                                 # un-biased
    mix = mix / (jnp.sum(mix, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.zeros_like(s).at[rows, chosen].set(mix)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "first", "n_group", "topk_group", "eps"))
def _expert_block(h, w, at, gate, up, down, *, top_k, scale, first,
                  n_group, topk_group, eps):
    """The HELD experts one at a time (each cut out of the stored stack
    and upcast where it is used; gate/up: (L, E held, D, F), down: (L, E
    held, F, D), bf16; ``at`` the layer's place in them; held expert
    ``e`` is the layer's expert ``first + e``), the shared expert, the
    add. An expert that is not held adds nothing."""
    m = _rms(h, w["mlp_norm"], eps)
    weight = route(m, w["router"], w["router_bias"], top_k=top_k,
                   scale=scale, n_group=n_group, topk_group=topk_group)

    def expert(t, e):         # one matrix, never the layer's whole slab
        return jax.lax.dynamic_slice(
            t, (at, e, 0, 0), (1, 1) + t.shape[2:])[0, 0].astype(jnp.float32)

    def one(acc, e):
        y = _gated(m, expert(gate, e), expert(up, e), expert(down, e))
        return acc + weight[:, first + e][:, None] * y, None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(gate.shape[1]))
    return h + y + _gated(m, w["ws_gate"], w["ws_up"], w["ws_down"])


VOCAB_BLOCK = 32768       # the head, a block of the vocabulary at a time
MLP_BLOCKS = 4            # the dense MLP, a quarter of its width at a time

KDA = ("kda_wqkv", "kda_wf", "kda_wg", "kda_wb", "kda_conv", "kda_A_log",
       "kda_dt_bias", "kda_norm", "kda_wout")
LATENT = ("wq", "wkv_a", "kv_a_norm", "wk_b", "wv_b", "wo")
EXPERTS = ("mlp_norm", "router", "router_bias", "ws_gate", "ws_up",
           "ws_down")


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms(x, w, eps)


@jax.jit
def _head(h, lm_head):
    return h @ lm_head


def forward(params, model, ids, positions):
    """``params`` is the served tree as stored (module docstring), read
    a layer at a time. Returns the logits at ``positions``."""
    stated = {"router_score_func": "sigmoid", "router_bias": "selection",
              "router_norm_topk": True, "mlp": "swiglu",
              "moe_impl": "dropless", "linear_decay": "channel",
              "num_shared_experts": 1}
    for key, want in stated.items():
        if model.get(key, want) != want:
            raise ValueError(f"this reference is the bailing_hybrid block "
                             f"({key} {want!r}); the model group states "
                             f"{model[key]!r}")
    if not model.get("kv_lora_rank") or model.get("q_lora_rank"):
        raise ValueError("this reference's attention is latent with ONE "
                         "query matrix: the model group states no "
                         "kv_lora_rank, or a q_lora_rank")
    eps = float(model.get("rms_norm_eps", 1e-6))
    n = int(model["full_attention_interval"])
    dense = int(model.get("num_dense_layers", 0))
    first = int(model.get("experts_first", 0))
    kinds = {"dense_layers": [0, 0], "layers": [0, 0]}    # layers run so far
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"], rows=jnp.asarray(ids[0]))
        for layer in range(model["num_layers"]):
            stack, at = (("dense_layers", layer) if layer < dense
                         else ("layers", layer - dense))
            leaves = params[stack]
            full = (layer + 1) % n == 0
            place = kinds[stack][full]
            kinds[stack][full] += 1
            w = {"attn_norm": _f32(leaves["attn_norm"], at)}
            if full:
                gated = bool(model.get("attn_gate"))
                w.update({k: _f32(leaves[k], place) for k in LATENT
                          + (("wz_head",) if gated else ())})
                h = _latent_attention(
                    x, w, heads=model["num_heads"],
                    nope=int(model["qk_nope_head_dim"]),
                    rope=int(model["qk_rope_head_dim"]), eps=eps,
                    theta=float(model.get("rope_theta", 10000.0)),
                    interleave=bool(model.get("rope_interleave", False)),
                    gated=gated)
            else:
                w.update({k: _f32(leaves[k], place) for k in KDA})
                h = _kda(x, w, heads=model["linear_num_value_heads"],
                         dk=model["linear_key_head_dim"],
                         dv=model["linear_value_head_dim"],
                         floor=float(model["linear_decay_floor"]), eps=eps)
            del w
            if layer < dense:
                F = model["intermediate_size"]
                step = -(-F // MLP_BLOCKS)
                mm = _norm(h, _f32(leaves["mlp_norm"], at), eps=eps)
                x = h
                for a in range(0, F, step):
                    b = min(a + step, F)
                    x = _add_gated(
                        x, mm, _f32(leaves["w_gate"], at, cols=(a, b)),
                        _f32(leaves["w_up"], at, cols=(a, b)),
                        _f32(leaves["w_down"], at, krows=(a, b)))
            else:
                w = {k: _f32(leaves[k], at) for k in EXPERTS}
                x = _expert_block(
                    h, w, jnp.int32(at), *(
                        leaves[k] for k in ("w_gate", "w_up", "w_down")),
                    top_k=int(model["num_experts_per_tok"]),
                    scale=float(model.get("router_scale", 1.0)),
                    first=first, n_group=int(model.get("n_group", 1)),
                    topk_group=int(model.get("topk_group", 1)), eps=eps)
            jax.block_until_ready(x)      # a layer's float32 at a time
        h = _norm(x[jnp.asarray(positions)], _f32(params["final_norm"]),
                  eps=eps)
        V = model["vocab_size"]
        return jnp.concatenate([
            _head(h, _f32(params["lm_head"],
                          cols=(a, min(a + VOCAB_BLOCK, V))))
            for a in range(0, V, VOCAB_BLOCK)], axis=-1)
