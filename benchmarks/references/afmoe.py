"""Trinity-Mini's decoder (arcee-ai, ``model_type: afmoe``), as its
``config.json`` and the published ``afmoe`` modelling code give it. ``x``
is a block's input, ``rms(y; g) = g y / sqrt(mean y^2 + eps)``, ``W`` the
window (2048); the layer kinds repeat over the published depth with the
period window, window, window, global:

    x0 = E[token] sqrt(hidden)          mup_enabled: the embedding output
    a = rms(x; g_in)
    q, k, v, z = a Wq, a Wk, a Wv, a Wz 32 / 4 / 4 heads of 128 and a gate
                                        of 32 x 128, no bias
    q, k = rms(q; g_q), rms(k; g_k)     over each head's 128 values, one
                                        weight vector for all heads
    q, k rotated (halves, theta 1e4)    in window layers only
    query i attends keys j <= i, and in a window layer also i - j < W
        (W keys, itself among them); scores q.k / sqrt(128), softmax
    h = x + rms((attention * sigmoid(z)) Wo; g_post_attn)
    m = rms(h; g_pre_mlp)
    a dense layer (the first num_dense_layers):
        y = W_down (silu(m W_gate) * (m W_up))
    an expert layer:
        s = sigmoid(m W_r)              float32, each expert on its own
        e = top8(s + b)                 b: the layer's selection bias
        w = s[e] / (sum s[e] + 1e-20) * route_scale   the UN-biased scores
        y = sum_i w_i W_down[e_i] (silu(m W_gate[e_i]) * (m W_up[e_i]))
            + S_down (silu(m S_gate) * (m S_up))      the shared expert
    out = h + rms(y; g_post_mlp)

then a final rms and an untied head. Dropless by construction: every
expert is computed for every token and the unchosen weighted by zero.

ASSUMED (``config.json`` has no key for them; they are the published
block as the modelling code has it, and the configuration file's
``assumed`` says the same, one line each):
- the q/k norm, and that it comes BEFORE the rotary embedding;
- the output gate sigmoid(a Wz), and that it acts before ``Wo``;
- four norms a block, the two post-norms on a sub-block's OUTPUT before
  the residual add;
- no rotary embedding in the global layers;
- the experts are CHOSEN by the biased scores and WEIGHTED by the
  un-biased ones (``route_norm`` over the chosen, then ``route_scale``);
- the shared expert is un-weighted;
- the embedding multiplier sqrt(hidden) acts on the embedding output and
  nowhere else.
Weights are drawn from a seed; the depth and the number of leading dense
layers are the configuration's (``num_layers``, ``num_dense_layers`` of
the model group).

The served tree holds the leading dense layers as a stack of their own
(``dense_layers``) before ``layers``; this reference reads layer l from
whichever holds it. Attention runs a KV head (eight query heads) at a
time: 32 x 2564^2 float32 scores at once would be 0.8 GB.
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


def _rope(x, theta):
    """x: (T, heads, hd), position t = row t."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "theta", "window", "rotary"))
def _attention_block(x, w, *, heads, kv_heads, eps, theta, window, rotary):
    """The attention half of a block. ``window`` 0 = the whole context.
    Returns the residual stream and the normed input of the MLP."""
    T, D = x.shape
    a = _rms(x, w["attn_norm"], eps)
    q, k, v, z = a @ w["wq"], a @ w["wk"], a @ w["wv"], a @ w["wz"]
    hd = q.shape[-1] // heads
    q = _rms(q.reshape(T, heads, hd), w["q_norm"], eps)
    k = _rms(k.reshape(T, kv_heads, hd), w["k_norm"], eps)
    v = v.reshape(T, kv_heads, hd)
    if rotary:
        q, k = _rope(q, theta), _rope(k, theta)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < window)
    group = heads // kv_heads

    def one_kv_head(qkv):                     # its queries: (T, group, hd)
        qg, kg, vg = qkv
        s = jnp.einsum("thd,sd->hts", qg, kg) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,sd->thd", p, vg)

    att = jax.lax.map(one_kv_head, (          # one after another
        q.reshape(T, kv_heads, group, hd).swapaxes(0, 1),
        k.swapaxes(0, 1), v.swapaxes(0, 1)))  # (kv, T, group, hd)
    att = att.swapaxes(0, 1).reshape(T, heads * hd)
    h = x + _rms((att * jax.nn.sigmoid(z)) @ w["wo"], w["post_attn_norm"],
                 eps)
    return h, _rms(h, w["mlp_norm"], eps)


def _gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_block(h, m, w, *, eps):
    return h + _rms(_gated(m, w["w_gate"], w["w_up"], w["w_down"]),
                    w["post_mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "eps"))
def _expert_block(h, m, w, at, gate, up, down, *, top_k, scale, eps):
    """Sigmoid routing, ALL the layer's experts one at a time (each cut
    out of the stored stack and upcast where it is used; gate/up:
    (L, E, D, F), down: (L, E, F, D) as stored, bf16; ``at`` the layer's
    place in them), the shared expert, the post-norm and the add."""
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
        return acc + weight[:, e][:, None] * y, None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(gate.shape[1]))
    y = y + _gated(m, w["ws_gate"], w["ws_up"], w["ws_down"])
    return h + _rms(y, w["post_mlp_norm"], eps)


VOCAB_BLOCK = 32768       # the head, a block of the vocabulary at a time

ATTENTION = ("attn_norm", "mlp_norm", "post_attn_norm", "post_mlp_norm",
             "q_norm", "k_norm", "wq", "wk", "wv", "wz", "wo")


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, *, eps):
    return _rms(x, w, eps)


@jax.jit
def _head(h, lm_head):
    return h @ lm_head


def _period(pattern, layer, default):
    pattern = list(pattern or [])
    return pattern[layer % len(pattern)] if pattern else default


def forward(params, model, ids, positions):
    """``params`` is the served tree as stored (``embed``, ``dense_layers``
    and ``layers`` with every leaf stacked over the stack's layers and an
    expert's over the experts too, ``final_norm``, ``lm_head``), read a
    layer at a time. Returns the logits at ``positions``."""
    stated = {"router_score_func": "sigmoid", "router_bias": "selection",
              "router_norm_topk": True, "qk_norm": True, "attn_gate": True,
              "post_norms": True, "mlp": "swiglu",
              "router_input": "mlp_norm"}
    for key, want in stated.items():
        if model.get(key, want) != want:
            raise ValueError(f"this reference is the afmoe block ({key} "
                             f"{want!r}); the model group states "
                             f"{model[key]!r}")
    eps = float(model.get("rms_norm_eps", 1e-5))
    window = int(model.get("sliding_window") or 0)
    dense = int(model.get("num_dense_layers", 0))
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"], rows=jnp.asarray(ids[0])) \
            * jnp.float32(model.get("embed_scale", 1.0))
        for layer in range(model["num_layers"]):
            stack, at = (("dense_layers", layer) if layer < dense
                         else ("layers", layer - dense))
            leaves = params[stack]
            w = {n: _f32(leaves[n], at) for n in ATTENTION}
            h, m = _attention_block(
                x, w, heads=model["num_heads"],
                kv_heads=model["num_kv_heads"], eps=eps,
                theta=float(model.get("rope_theta", 10000.0)),
                window=window * _period(model.get("window_layers"), layer, 0),
                rotary=bool(_period(model.get("rope_layers"), layer, 1)))
            if layer < dense:
                w.update({n: _f32(leaves[n], at)
                          for n in ("w_gate", "w_up", "w_down")})
                x = _dense_block(h, m, w, eps=eps)
            else:
                w.update({n: _f32(leaves[n], at) for n in (
                    "router", "router_bias", "ws_gate", "ws_up", "ws_down")})
                x = _expert_block(
                    h, m, w, jnp.int32(at), *(
                        leaves[n] for n in ("w_gate", "w_up", "w_down")),
                    top_k=int(model["num_experts_per_tok"]),
                    scale=float(model.get("router_scale", 1.0)), eps=eps)
            jax.block_until_ready(x)      # a layer's float32 at a time
        h = _final_norm(x[jnp.asarray(positions)], _f32(params["final_norm"]),
                        eps=eps)
        V = model["vocab_size"]
        return jnp.concatenate([
            _head(h, _f32(params["lm_head"],
                          cols=(a, min(a + VOCAB_BLOCK, V))))
            for a in range(0, V, VOCAB_BLOCK)], axis=-1)
