"""SmallThinker-21BA3B-Instruct's decoder, as its ``config.json``
(PowerInfer, ``smallthinker_21b_instruct``) and the catalog's description
give it. ``x`` is a block's input, ``W`` the window (4096), and the two
layouts repeat over the depth with the period 0, 1, 1, 1:

    r = x W_router                      the router reads the block's
                                        input as it enters the block
    a = rmsnorm(x)                      y = w x / sqrt(mean x^2 + eps)
    q, k, v = a Wq, a Wk, a Wv          28 / 4 / 4 heads of 128, no bias
    q, k rotated (halves, theta 1.5e6)  only where rope_layout is 1
    query i attends keys j <= i, and where sliding_window_layout is 1
        also i - j < W (W keys, itself among them);
        scores q.k / sqrt(128), softmax
    h = x + attention Wo
    m = rmsnorm(h)
    (w, e) = top6(r);  p = softmax(w) over the six, in float32
    out = h + sum_i p_i  W_down[e_i] (relu(m W_gate[e_i]) * (m W_up[e_i]))

then a final rmsnorm and an untied head. No shared expert, no dense
layer. Dropless by construction: every expert is computed for every
token and the unchosen weighted by zero, so a token gets all six of its
experts whatever its neighbours chose.

ASSUMED (the configuration file's ``assumed`` says the same): the
catalog says "router placed before attention" and not whether the
block's norm comes first; this reference takes the UN-NORMED stream.
Weights are drawn from a seed; the depth is the configuration's
(``num_layers`` of the model group), whole periods of four.

Attention runs a KV head (seven query heads) at a time: 28 x 4612^2
float32 scores at once would be 2.4 GB.
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


def _norm(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (T, heads, hd), position t = row t."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "theta", "top_k", "window", "rotary"))
def _attend_and_route(x, w, *, heads, kv_heads, eps, theta, top_k, window,
                      rotary):
    """The attention half of a block. ``window`` 0 = the whole context.
    Returns the residual stream, the normed input of the experts and
    each token's weight per expert (zero for the unchosen)."""
    T, D = x.shape
    logits = x @ w["router"]                  # the block's input, un-normed
    a = _norm(x, w["attn_norm"], eps)
    q, k, v = a @ w["wq"], a @ w["wk"], a @ w["wv"]
    hd = q.shape[-1] // heads
    q = q.reshape(T, heads, hd)
    k = k.reshape(T, kv_heads, hd)
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
    h = x + att @ w["wo"]
    m = _norm(h, w["mlp_norm"], eps)
    top, chosen = jax.lax.top_k(logits, top_k)
    mix = jax.nn.softmax(top, axis=-1)                    # (T, k)
    weight = jnp.zeros_like(logits).at[
        jnp.arange(T)[:, None], chosen].set(mix)          # (T, E)
    return h, m, weight


@jax.jit
def _experts(h, m, weight, gate, up, down):
    """h + sum over ALL the layer's experts of weight x expert(m), one
    expert at a time, each upcast where it is used. gate/up: (E, D, F),
    down: (E, F, D) as stored (bf16)."""
    def one(acc, e):
        g, u, d = (t[e].astype(jnp.float32) for t in (gate, up, down))
        y = (jax.nn.relu(m @ g) * (m @ u)) @ d
        return acc + weight[:, e][:, None] * y, None
    out, _ = jax.lax.scan(one, h, jnp.arange(gate.shape[0]))
    return out


VOCAB_BLOCK = 32768       # the head, a block of the vocabulary at a time


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, *, eps):
    return _norm(x, w, eps)


@jax.jit
def _head(h, lm_head):
    return h @ lm_head


def _period(pattern, layer, default):
    pattern = list(pattern or [])
    return pattern[layer % len(pattern)] if pattern else default


def forward(params, model, ids, positions):
    """``params`` is the served tree as stored (``embed``, ``layers`` with
    every leaf stacked over the layers and an expert's over the experts
    too, ``final_norm``, ``lm_head``), read a layer at a time."""
    if model.get("router_input") != "block_input" \
            or model.get("mlp") != "relu_glu":
        raise ValueError("this reference routes from the block's input "
                         "through relu-gated experts; the model group "
                         "states otherwise")
    eps = float(model.get("rms_norm_eps", 1e-6))
    window = int(model.get("sliding_window") or 0)
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"], rows=jnp.asarray(ids[0]))
        for layer in range(model["num_layers"]):
            w = {n: _f32(layers[n], layer) for n in (
                "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "router")}
            h, m, weight = _attend_and_route(
                x, w, heads=model["num_heads"],
                kv_heads=model["num_kv_heads"], eps=eps,
                theta=float(model["rope_theta"]),
                top_k=int(model["num_experts_per_tok"]),
                window=window * _period(model.get("window_layers"), layer, 0),
                rotary=bool(_period(model.get("rope_layers"), layer, 1)))
            x = _experts(h, m, weight, *(
                layers[n][layer] for n in ("w_gate", "w_up", "w_down")))
            jax.block_until_ready(x)      # a layer's float32 at a time
        h = _final_norm(x[jnp.asarray(positions)], _f32(params["final_norm"]),
                        eps=eps)
        V = model["vocab_size"]
        return jnp.concatenate([
            _head(h, _f32(params["lm_head"],
                          cols=(a, min(a + VOCAB_BLOCK, V))))
            for a in range(0, V, VOCAB_BLOCK)], axis=-1)
