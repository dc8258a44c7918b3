"""GPT-Next / Nemotron-3 decoder, as NeMo's ``model_config.yaml`` of
nemotron-3-8b describes it: pre-norm blocks with ``layernorm1p``
(y = (1 + w) (x - mean) / sqrt(var + eps) + b, eps 1e-5), multi-head
causal attention with rotary embeddings over the head (theta 10000,
halves rotated: (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)), scores
scaled by 1 / sqrt(head_dim), an MLP without gate,
down(relu(up x) ** 2), no biases on the projections unless the model
group sets them, a final ``layernorm1p`` and an untied output head.

Departures from the published model, each the configuration's own
(``assumed`` in its file): rotary over the WHOLE head (the checkpoint's
``rotary_percentage`` is 0.5; the program has no partial-rotary path,
and the reference follows the configuration as it is run), and weights
drawn from a seed.
"""

import functools

import jax
import jax.numpy as jnp


def _f32(leaf, *index, rows=None, cols=None):
    """One stored leaf (of a layer, an expert) as float32: bf16 upcast,
    or int8 times its float32 scale an output channel. ``rows`` gathers
    rows and ``cols=(a, b)`` takes a block of output channels before the
    upcast (the embedding and the head of a large vocabulary)."""
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


def _norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * (1.0 + w) + b


def _rope(x, theta):
    """x: (T, heads, hd), position t = row t."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta"))
def _block(x, w, *, heads, kv_heads, eps, theta):
    T, D = x.shape
    h = _norm(x, w["attn_norm"], w["attn_norm_b"], eps)
    q = h @ w["wq"] + w.get("bq", 0.0)
    k = h @ w["wk"] + w.get("bk", 0.0)
    v = h @ w["wv"] + w.get("bv", 0.0)
    hd = q.shape[-1] // heads
    q = _rope(q.reshape(T, heads, hd), theta)
    k = _rope(k.reshape(T, kv_heads, hd), theta)
    v = v.reshape(T, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hts,shd->thd", p, v).reshape(T, heads * hd)
    x = x + a @ w["wo"] + w.get("bo", 0.0)
    h = _norm(x, w["mlp_norm"], w["mlp_norm_b"], eps)
    u = jnp.maximum(h @ w["w_up"] + w.get("b_up", 0.0), 0.0) ** 2
    return x + u @ w["w_down"] + w.get("b_down", 0.0)


VOCAB_BLOCK = 32768       # the head, a block of the vocabulary at a time


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, b, *, eps):
    return _norm(x, w, b, eps)


@jax.jit
def _head(h, lm_head):
    return h @ lm_head


LAYER_LEAVES = ("attn_norm", "attn_norm_b", "mlp_norm", "mlp_norm_b", "wq",
                "wk", "wv", "wo", "bq", "bk", "bv", "bo", "w_up", "w_down",
                "b_up", "b_down")


def forward(params, model, ids, positions):
    """``params`` is the served tree as stored (``embed``, ``layers`` with
    every leaf stacked over the layers, ``final_norm``, ``lm_head``),
    read a layer at a time."""
    eps = float(model.get("rms_norm_eps", 1e-5))
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"], rows=jnp.asarray(ids[0]))
        for layer in range(model["num_layers"]):
            w = {n: _f32(layers[n], layer) for n in LAYER_LEAVES
                 if n in layers}
            x = _block(x, w, heads=model["num_heads"],
                       kv_heads=model["num_kv_heads"], eps=eps,
                       theta=float(model.get("rope_theta", 10000.0)))
        h = _final_norm(x[jnp.asarray(positions)], _f32(params["final_norm"]),
                        _f32(params["final_norm_b"]), eps=eps)
        V = model["vocab_size"]
        return jnp.concatenate([
            _head(h, _f32(params["lm_head"],
                          cols=(a, min(a + VOCAB_BLOCK, V))))
            for a in range(0, V, VOCAB_BLOCK)], axis=-1)
