"""Xing4.0-29B-A4B's decoder (XingChen-AGI, ``model_type: xing4_0``): the
published DeepseekV3 block (``modeling_deepseek_v3.py``: latent
attention, a sigmoid ``noaux_tc`` router over 64 experts, 4 a token, one
shared expert) on a residual path of ``n = hc_mult = 4`` streams mixed by
manifold-constrained hyper-connections (mHC, arXiv 2512.24880, on top of
Hyper-Connections, arXiv 2409.19606). ``rms(y; g) = g y / sqrt(mean y^2 +
eps)``; ``C = hidden_size``; a token's stream is ``X`` (n, C).

THE RESIDUAL PATH. The model starts ``X`` as the embedding row repeated n
times. A sublayer ``F`` — with its OWN pre-norm, as below — is applied as

    x^     = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)         (n C,) no gain
    [p, q, r] = x^ . phi          phi (n C, n + n + n^2)    4, 4, 16 values
    H_pre  = sigmoid(a_pre p + b_pre)                       (n,)
    H_post = 2 sigmoid(a_post q + b_post)                   (n,)
    M      = exp(clip(a_res mat(r) + b_res, -clamp, +clamp))   mat row-major
    hc_sinkhorn_iters (20) times:
             M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
    H_res  = M                                     doubly stochastic (n, n)
    u      = H_pre . X                                      (C,)
    y      = F(u)
    X'     = H_res . X + H_post^T y                         (n, C)

twice a block, around the attention and around the MLP (or experts), each
with its own ``phi`` (bf16), ``alpha`` = (a_pre, a_post, a_res) and ``b``
(float32; ``b`` = [b_pre | b_post | b_res row-major]). After the last
block ``h = sum_i X[i]``, then the final rms and the untied head. All of
the coefficient path is float32.

THE SUBLAYERS (``x`` their input ``u``; 32 heads, each query 128 ``nope``
+ 64 ``rope`` values, each value 128):

    a = rms(x; g_in)
    c_q = rms(a W_qa; g_qa)                 3584 -> 768
    q_h = c_q W_qb,h = [q_nope_h | q_r_h]   768 -> 32 x (128 + 64)
    [c_kv | k_r] = a W_kva                  3584 -> 512 + 64
    c = rms(c_kv; g_kva)                    the latent; k_r is ONE key part
                                            for all heads
    q_r_h, k_r rotated: pairs (2i, 2i+1), YaRN's frequencies over the 64
    k_nope_h = c W_UK,h, v_h = c W_UV,h     512 -> 128 each: the EXPANDED
                                            form, and the only one here
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_r_h(t).k_r(s))
                    / sqrt(192) * m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    causal softmax over s <= t; o_h = sum_s p_h(t, s) v_h(s)
    F_attn(x) = concat_h(o_h) W_o           4096 -> 3584

    m_ = rms(x; g_mlp)
    layer 0 (the first num_dense_layers):
        F_mlp(x) = W_down (silu(m_ W_gate) * (m_ W_up))     width 9216
    an expert layer:
        s = sigmoid(m_ W_r)                 float32, all 64 columns
        e = top4(s + b)                     b: e_score_correction_bias
        w = s[e] / (sum s[e] + 1e-20) * 2   the UN-biased scores
        F_mlp(x) = sum_i w_i W_down[e_i] (silu(m_ W_gate[e_i]) * (m_ W_up[e_i]))
                   + S_down (silu(m_ S_gate) * (m_ S_up))   the shared expert

YaRN's frequencies: index i of the 32 turns ``original_max / (2 pi
theta^(2i/64))`` times in the original context; those that turn more than
``beta_fast`` times are kept, those that turn fewer than ``beta_slow``
times are divided by ``factor``, between them a linear ramp.

ASSUMED (the catalog's ``config`` names ``hc_mult``, ``hc_sinkhorn_iters``,
``hc_eps`` and the clamp, and nothing of where they enter; the papers
decide, and the configuration file's ``assumed`` says the same, one entry
each): ``hc_eps`` under the RMS statistic AND in every row and column
sum; ``x^`` has no gain; rows are normalised before columns; the streams
start as copies of the embedding and end as their SUM (a scale-free final
norm cannot tell a sum from a mean: the tests hold the sum before the
norm); ``H_post`` carries the factor 2 and ``H_pre`` its sigmoid; ``mat``
is row-major; each sublayer keeps its pre-norm. Of the block: the two
latent norms and their place; ``rope_interleave``; the score multiplier
m^2 (the cos/sin multiplier mscale / mscale_all_dim is 1); the 1e-20;
float32 router scores; the shared expert un-weighted; no biases anywhere.

WHERE THE SERVED TREE DEPARTS FROM THE PUBLISHED MODEL, and this
reference with it (it reads the tree as stored): ``kv_b_proj`` is stored
as its two halves, ``wk_b`` and ``wv_b``; the mapping weights are
``hc_attn_phi`` / ``hc_attn_alpha`` / ``hc_attn_b`` and ``hc_mlp_*``;
the depth and the number of leading dense layers are the model group's;
the multi-token-prediction module is not built (it lies after the last
layer and moves no served token); an ``experts_held`` share, where the
model group states one, adds what the held experts give and nothing else;
weights are drawn from a seed.

In the served program the stream is (B, S, n x C) bf16, the latent pool
``{"c", "r"}`` and decode runs the ABSORBED form; none of that is here.
Attention runs eight heads at a time and the wide dense MLP a quarter of
its width at a time: the float32 copies must fit beside the weights.
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


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """YaRN's inverse frequencies over ``dim`` rotary values, a plain
    list: evaluated index by index."""
    def index_of(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(index_of(beta_fast)), 0)
    high = min(math.ceil(index_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain * (1.0 - ramp) + plain / factor * ramp)
    return out


def _rope_pairs(x, inv):
    """x: (T, heads, d) with pairs (2i, 2i+1); position t = row t. The
    rotated pairs come back evens first, then odds (queries and keys
    alike, so their products are the published ones)."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


HEAD_GROUP = 8            # attention, this many heads at a time


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "eps", "scale", "interleave"))
def _attention_block(x, w, inv, *, heads, nope, rope, eps, scale,
                     interleave):
    """The attention sublayer ``F_attn`` of its input ``x``, expanded
    form, its pre-norm included."""
    T, D = x.shape
    a = _rms(x, w["attn_norm"], eps)
    q = (_rms(a @ w["wq_a"], w["q_a_norm"], eps) @ w["wq_b"]).reshape(
        T, heads, nope + rope)
    kv = a @ w["wkv_a"]
    R = kv.shape[-1] - rope
    c = _rms(kv[:, :R], w["kv_a_norm"], eps)
    q_nope, q_r, k_r = q[..., :nope], q[..., nope:], kv[:, None, R:]
    if not interleave:        # halves: bring them to pairs' order first
        def pair(y):
            return jnp.stack([y[..., :rope // 2], y[..., rope // 2:]],
                             axis=-1).reshape(y.shape)
        q_r, k_r = pair(q_r), pair(k_r)
    q_r, k_r = _rope_pairs(q_r, inv), _rope_pairs(k_r, inv)[:, 0]
    k_nope = (c @ w["wk_b"]).reshape(T, heads, nope)
    v = (c @ w["wv_b"]).reshape(T, heads, -1)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i

    def some_heads(part):                     # (T, HEAD_GROUP, .) each
        qn, qr, kn, vh = part
        s = (jnp.einsum("thd,shd->hts", qn, kn)
             + jnp.einsum("thd,sd->hts", qr, k_r)) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, vh)

    group = math.gcd(heads, HEAD_GROUP)

    def grouped(y):
        return y.reshape(T, heads // group, group,
                         y.shape[-1]).swapaxes(0, 1)
    att = jax.lax.map(some_heads, (grouped(q_nope), grouped(q_r),
                                   grouped(k_nope), grouped(v)))
    att = att.swapaxes(0, 1).reshape(T, -1)
    return att @ w["wo"]


def _gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


@jax.jit
def _add_gated(acc, m, gate, up, down):
    return acc + _gated(m, gate, up, down)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "first",
                                              "eps"))
def _expert_block(x, w, at, gate, up, down, *, top_k, scale, first, eps):
    """The expert sublayer ``F_mlp`` of its input ``x``, its pre-norm
    included: sigmoid routing over ALL the router's columns; the HELD
    experts one at a time (each cut out of the stored stack and upcast
    where it is used; gate/up: (L, E_held, D, F), down: (L, E_held, F,
    D) as stored, bf16; ``at`` the layer's place in them; stored expert
    e is the layer's expert ``first`` + e) and the shared expert. An
    expert that is not held adds nothing."""
    m = _rms(x, w["mlp_norm"], eps)
    T = m.shape[0]
    s = jax.nn.sigmoid(m @ w["router"])                   # (T, E)
    _, chosen = jax.lax.top_k(s + w["router_bias"], top_k)
    rows = jnp.arange(T)[:, None]
    mix = s[rows, chosen]                                 # un-biased
    mix = mix / (jnp.sum(mix, axis=-1, keepdims=True) + 1e-20) * scale
    weight = jnp.zeros_like(s).at[rows, chosen].set(mix)

    def expert(t, e):         # one matrix, never the layer's whole slab
        return jax.lax.dynamic_slice(
            t, (at, e, 0, 0), (1, 1) + t.shape[2:])[0, 0].astype(jnp.float32)

    def one(acc, e):
        y = _gated(m, expert(gate, e), expert(up, e), expert(down, e))
        return acc + weight[:, first + e][:, None] * y, None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(gate.shape[1]))
    return y + _gated(m, w["ws_gate"], w["ws_up"], w["ws_down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _mlp_norm(x, g, *, eps):
    return _rms(x, g, eps)


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp"))
def _hc_read(X, phi, alpha, b, *, n, iters, eps, clamp):
    """``X`` (T, n, C) -> the sublayer's input ``u`` (T, C) and the
    write-back coefficients ``H_post`` (T, n), ``H_res`` (T, n, n)."""
    T = X.shape[0]
    flat = X.reshape(T, -1)
    xhat = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + eps)
    z = xhat @ phi                                        # (T, n + n + n n)
    h_pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(alpha[2] * z[:, 2 * n:] + b[2 * n:], -clamp,
                         clamp)).reshape(T, n, n)
    for _ in range(iters):
        M = M / (jnp.sum(M, axis=2, keepdims=True) + eps)     # rows
        M = M / (jnp.sum(M, axis=1, keepdims=True) + eps)     # columns
    return jnp.einsum("ti,tic->tc", h_pre, X), h_post, M


def _read(X, leaves, at, part, hyper):
    """``_hc_read`` with a sublayer's (``attn`` / ``mlp``) stored mapping
    weights of layer ``at`` of a stack."""
    return _hc_read(X, *(_f32(leaves[f"hc_{part}_{name}"], at)
                         for name in ("phi", "alpha", "b")), **hyper)


@jax.jit
def _hc_write(X, y, h_post, h_res):
    """``H_res . X + H_post^T y``."""
    return jnp.einsum("tij,tjc->tic", h_res, X) \
        + h_post[:, :, None] * y[:, None, :]


VOCAB_BLOCK = 8192        # the head, a block of the vocabulary at a time
MLP_BLOCKS = 4            # the dense MLP, a quarter of its width at a time

ATTENTION = ("attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
             "wk_b", "wv_b", "wo")


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, *, eps):
    return _rms(x, w, eps)


@jax.jit
def _head(h, lm_head):
    return h @ lm_head


def forward(params, model, ids, positions):
    """``params`` is the served tree as stored (``embed``, ``dense_layers``
    and ``layers`` with every leaf stacked over the stack's layers and an
    expert's over the held experts too, ``final_norm``, ``lm_head``),
    read a layer at a time. Returns the logits at ``positions``."""
    stated = {"router_score_func": "sigmoid", "router_bias": "selection",
              "router_norm_topk": True, "mlp": "swiglu",
              "router_input": "mlp_norm", "rope_scaling_type": "yarn"}
    for key, want in stated.items():
        if model.get(key, want) != want:
            raise ValueError(f"this reference is the DeepseekV3 block "
                             f"({key} {want!r}); the model group states "
                             f"{model[key]!r}")
    if not model.get("kv_lora_rank"):
        raise ValueError("this reference is latent attention: the model "
                         "group states no kv_lora_rank")
    if not model.get("hc_mult"):
        raise ValueError("this reference is the hyper-connected residual "
                         "path: the model group states no hc_mult")
    eps = float(model.get("rms_norm_eps", 1e-6))
    dense = int(model.get("num_dense_layers", 0))
    nope, rope = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    factor = float(model["rope_scaling_factor"])
    inv = jnp.asarray(yarn_inv_freq(
        rope, float(model["rope_theta"]), factor,
        int(model["rope_original_max"]),
        float(model.get("rope_beta_fast", 32.0)),
        float(model.get("rope_beta_slow", 1.0))), jnp.float32)
    all_dim = float(model.get("rope_mscale_all_dim", 0.0))
    m = 0.1 * all_dim * math.log(factor) + 1.0 if all_dim and factor > 1 \
        else 1.0
    scale = (nope + rope) ** -0.5 * m * m
    first = int(model.get("experts_first", 0))
    n = int(model["hc_mult"])
    hyper = dict(n=n, iters=int(model.get("hc_sinkhorn_iters", 20)),
                 eps=float(model.get("hc_eps", 1e-6)),
                 clamp=float(model.get("hc_res_clamp", 30.0)))
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"], rows=jnp.asarray(ids[0]))
        X = jnp.repeat(x[:, None, :], n, axis=1)          # (T, n, C)
        for layer in range(model["num_layers"]):
            stack, at = (("dense_layers", layer) if layer < dense
                         else ("layers", layer - dense))
            leaves = params[stack]
            u, h_post, h_res = _read(X, leaves, at, "attn", hyper)
            w = {k: _f32(leaves[k], at) for k in ATTENTION}
            y = _attention_block(
                u, w, inv, heads=model["num_heads"], nope=nope, rope=rope,
                eps=eps, scale=scale,
                interleave=bool(model.get("rope_interleave", False)))
            del w
            X = _hc_write(X, y, h_post, h_res)
            u, h_post, h_res = _read(X, leaves, at, "mlp", hyper)
            if layer < dense:
                F = model["intermediate_size"]
                step = -(-F // MLP_BLOCKS)
                mm = _mlp_norm(u, _f32(leaves["mlp_norm"], at), eps=eps)
                y = jnp.zeros_like(u)
                for a in range(0, F, step):
                    b = min(a + step, F)
                    y = _add_gated(
                        y, mm, _f32(leaves["w_gate"], at, cols=(a, b)),
                        _f32(leaves["w_up"], at, cols=(a, b)),
                        _f32(leaves["w_down"], at, krows=(a, b)))
            else:
                w = {k: _f32(leaves[k], at) for k in (
                    "mlp_norm", "router", "router_bias", "ws_gate", "ws_up",
                    "ws_down")}
                y = _expert_block(
                    u, w, jnp.int32(at), *(
                        leaves[k] for k in ("w_gate", "w_up", "w_down")),
                    top_k=int(model["num_experts_per_tok"]),
                    scale=float(model.get("router_scale", 1.0)), first=first,
                    eps=eps)
            X = _hc_write(X, y, h_post, h_res)
            jax.block_until_ready(X)      # a layer's float32 at a time
        x = jnp.sum(X, axis=1)                # the streams end as their sum
        h = _final_norm(x[jnp.asarray(positions)], _f32(params["final_norm"]),
                        eps=eps)
        V = model["vocab_size"]
        return jnp.concatenate([
            _head(h, _f32(params["lm_head"],
                          cols=(a, min(a + VOCAB_BLOCK, V))))
            for a in range(0, V, VOCAB_BLOCK)], axis=-1)
