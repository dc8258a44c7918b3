"""Mixtral decoder, as ``config.json`` and the published modelling code
of Mixtral-8x7B describe it: pre-norm blocks with RMSNorm
(y = w x / sqrt(mean x^2 + eps)), grouped-query causal attention with
rotary embeddings over the head (halves rotated), scores scaled by
1 / sqrt(head_dim); then a sparse mixture of experts: the router's
logits over all experts, the top k kept, their softmax as the mixing
weights (the same as softmax over all, then renormalised over the k),
each expert down(silu(gate x) * up x). As published it is DROPLESS:
every token gets all k of its experts, however many tokens chose them.
A final RMSNorm and an untied output head.

Every expert is computed for every token and the unchosen weighted by
zero: plain, and exact. A model group without experts is the family's
dense model (Mistral): the same block with its one SwiGLU MLP.

Departures from the published model, each the configuration's own
(``assumed`` in its file): weights drawn from a seed; no sliding window
(``config.json`` has ``sliding_window: null``); and, where the model
group states ``"moe_impl": "sparse"`` with a ``moe_capacity_factor`` f,
routing under a CAPACITY, because the reference follows the
configuration as it is run: of the n tokens routed together (a prefill
chunk; the rows of one decode step) an expert takes the first
ceil(n k f / E) assignments in token order, a token's first choice
before its second, and a later one is dropped: its mixing weight is
zero and the token's other weight stays as it was. Which tokens were
routed together is the caller's to say: ``model["routed_together"]``
lists the sizes of consecutive groups (absent: the whole sequence is
one). Any other ``moe_impl`` is dropless.
"""

import math

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


def _kept(chosen, n_experts, groups, factor):
    """(T, k) bool: which of each token's choices found room."""
    T, k = chosen.shape
    claim = jax.nn.one_hot(chosen.reshape(T * k), n_experts)  # token-major
    kept, start = [], 0
    for n in groups:
        c = claim[start * k:(start + n) * k]
        ahead = jnp.sum((jnp.cumsum(c, axis=0) - c) * c, axis=1)
        kept.append(ahead < max(1, math.ceil(n * k * factor / n_experts)))
        start += n
    return jnp.concatenate(kept).reshape(T, k)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "eps", "theta", "top_k", "groups", "factor"))
def _attend_and_route(x, w, *, heads, kv_heads, eps, theta, top_k,
                      groups=None, factor=0.0):
    """The attention half of a block; returns the residual stream, the
    normed input of the experts and each token's weight per expert."""
    T, D = x.shape
    h = _norm(x, w["attn_norm"], eps)
    q, k, v = h @ w["wq"], h @ w["wk"], h @ w["wv"]
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
    x = x + a @ w["wo"]
    h = _norm(x, w["mlp_norm"], eps)
    if "router" not in w:
        return x, h, None
    logits = h @ w["router"]                              # (T, E)
    top, chosen = jax.lax.top_k(logits, top_k)
    mix = jax.nn.softmax(top, axis=-1)                    # (T, k)
    if groups is not None:
        mix = mix * _kept(chosen, logits.shape[-1], groups, factor)
    weight = jnp.zeros_like(logits).at[
        jnp.arange(T)[:, None], chosen].set(mix)          # (T, E)
    return x, h, weight


@jax.jit
def _expert(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


VOCAB_BLOCK = 32768       # the head, a block of the vocabulary at a time


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, *, eps):
    return _norm(x, w, eps)


@jax.jit
def _head(h, lm_head):
    return h @ lm_head


def forward(params, model, ids, positions):
    """``params`` is the served tree as stored (``embed``, ``layers`` with
    every leaf stacked over the layers and an expert's over the experts
    too, ``final_norm``, ``lm_head``), read a layer and an expert at a
    time."""
    eps = float(model.get("rms_norm_eps", 1e-5))
    layers = params["layers"]
    groups = None
    if model.get("num_experts") and model.get("moe_impl") == "sparse":
        groups = tuple(model.get("routed_together") or [len(ids[0])])
        if sum(groups) != len(ids[0]):
            raise ValueError(f"routed_together {groups} does not cover "
                             f"{len(ids[0])} tokens")
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"], rows=jnp.asarray(ids[0]))
        for layer in range(model["num_layers"]):
            w = {n: _f32(layers[n], layer) for n in (
                "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "router")
                if n in layers}
            x, h, weight = _attend_and_route(
                x, w, heads=model["num_heads"],
                kv_heads=model["num_kv_heads"], eps=eps,
                theta=float(model.get("rope_theta", 10000.0)),
                top_k=int(model.get("num_experts_per_tok", 2)),
                groups=groups,
                factor=float(model.get("moe_capacity_factor", 0.0)))
            mlp = [layers[n] for n in ("w_gate", "w_up", "w_down")]
            if weight is None:       # the family's dense model: one MLP
                x = x + _expert(h, *(_f32(m, layer) for m in mlp))
            for e in range(model.get("num_experts", 0)):
                y = _expert(h, *(_f32(m, layer, e) for m in mlp))
                x = x + weight[:, e:e + 1] * y
        h = _final_norm(x[jnp.asarray(positions)], _f32(params["final_norm"]),
                        eps=eps)
        V = model["vocab_size"]
        if "lm_head" not in params:                       # tied
            return _head(h, _f32(params["embed"]).T)
        return jnp.concatenate([
            _head(h, _f32(params["lm_head"],
                          cols=(a, min(a + VOCAB_BLOCK, V))))
            for a in range(0, V, VOCAB_BLOCK)], axis=-1)
