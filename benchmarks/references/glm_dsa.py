"""GLM-5.2's decoder (zai-org, ``model_type: glm_moe_dsa``): the
DeepseekV3 block — latent attention, a sigmoid router with a selection
bias, a shared expert, leading dense layers — with LEARNED SPARSE
ATTENTION ("DSA with IndexShare"): an indexer in the form DeepSeek-V3.2
published chooses, a query, the 2048 cached tokens its attention reads,
and three layers in four reuse the choice of the layer below. ``x`` is a
block's input, ``rms(y; g) = g y / sqrt(mean y^2 + eps)``; 64 heads,
each query 192 ``nope`` + 64 ``rope`` values, each value 256:

    a = rms(x; g_in)
    c_q = rms(a W_qa; g_qa)                 6144 -> 2048
    q_h = c_q W_qb,h = [q_nope_h | q_r_h]   2048 -> 64 x (192 + 64)
    [c_kv | k_r] = a W_kva                  6144 -> 512 + 64
    c = rms(c_kv; g_kva)                    the latent; k_r is ONE key part
                                            for all heads
    q_r_h, k_r rotated: pairs (2i, 2i+1), plain frequencies
                                            theta^(-2i/64), theta 8e6
    k_nope_h = c W_UK,h, v_h = c W_UV,h     512 -> 192 and 256

    on a FULL layer (``index_layers`` 1), the indexer:
        qI_j = (c_q W_Iq)_j                 2048 -> 32 x 128; the SAME c_q
        kI = LayerNorm(a W_Ik; g_I, b_I)    6144 -> 128; eps 1e-6
        the FIRST 64 of the 128 of both rotated at the token's position,
        pairs (2i, 2i+1), the model's frequencies
        w = a W_Iw * 32^-1/2 * 128^-1/2     6144 -> 32
        I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))
        S_t = the 2048 positions s <= t with the largest I(t, s): all of
              them while t + 1 <= 2048; ties to the lower position
              (``lax.top_k`` over the whole causal row)
    on a SHARED layer (``index_layers`` 0): no indexer weights, no kI;
        S_t is that of the nearest full layer below

    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_r_h(t).k_r(s)) / sqrt(256)
    softmax over s IN S_t only; o_h = sum_{s in S_t} p_h(t, s) v_h(s)
    h = x + concat_h(o_h) W_o               16384 -> 6144
    m_ = rms(h; g_mlp)
    a leading dense layer (the first num_dense_layers):
        y = W_down (silu(m_ W_gate) * (m_ W_up))          width 12288
    an expert layer:
        s = sigmoid(m_ W_r)                 float32, all 256 columns
        e = top8(s + b)                     b: e_score_correction_bias
        w = s[e] / (sum s[e] + 1e-20) * 2.5       the UN-biased scores
        y = sum_i w_i W_down[e_i] (silu(m_ W_gate[e_i]) * (m_ W_up[e_i]))
            + S_down (silu(m_ S_gate) * (m_ S_up))        the shared expert
    out = h + y

then a final rms and an untied head.

WHERE THE SERVED TREE DEPARTS FROM THE PUBLISHED MODEL, and this
reference with it (it reads the tree as stored):
- ``kv_b_proj`` is stored as its two halves, ``wk_b`` (512, 64 x 192) and
  ``wv_b`` (512, 64 x 256), each with the per-column int8 scales the
  whole would have; the other attention leaves are ``wq_a``,
  ``q_a_norm``, ``wq_b``, ``wkv_a``, ``kv_a_norm``, ``wo``, the router
  ``router`` / ``router_bias`` and the shared expert ``ws_*``, in the
  published column order (rotary pairs stay (2i, 2i+1));
- THE INDEXER's leaves lie in each stack's tree (``dense_layers``,
  ``layers``) stacked over that stack's FULL layers only, bf16:
  ``index_wq`` (Lf, 2048, 32 x 128; published ``indexer.wq_b``),
  ``index_wk`` (Lf, 6144, 128; ``indexer.wk``), ``index_k_norm`` /
  ``index_k_norm_b`` (Lf, 128; ``indexer.k_norm`` weight and bias),
  ``index_wp`` (Lf, 6144, 32; ``indexer.weights_proj``). The k-th full
  layer of a stack reads row k;
- THE EXPERT SHARE: the tree holds the matrices of ``experts_held`` of
  the layer's ``num_experts`` experts, from ``experts_first`` on (one
  chip's share of a layer that 32 chips hold). The router scores all
  256, a token's eight weights are normalised over its eight, and what
  falls on an expert that is not held adds NOTHING here: the layer's
  output is the partial sum over the held experts plus the shared
  expert, exactly what the program leaves out;
- the depth, the number of leading dense layers and which layers are
  full are the model group's; weights are drawn from a seed.

NOT BUILT, here or in the program: the published inference code's
Hadamard rotation of qI / kI (orthogonal: it leaves qI . kI as it is)
and their FP8 quantisation (a precision the configuration does not
state); the multi-token-prediction module after the last layer.

ASSUMED (the catalog's ``config`` has no key; DeepSeek-V3.2's published
indexer and the DeepseekV3 modelling code decide, and the configuration
file's ``assumed`` says the same): what ``shared`` means; ``c_q`` as the
indexer's query input; LayerNorm with bias and eps 1e-6 on kI; the
rotary part first in the 128; the two scale factors on ``w``; relu
before the head weights; ties to the lower position; the two latent
norms and their place; the 1e-20; float32 router scores; the shared
expert un-weighted; no biases elsewhere.

In the served program the pool is ``{"c": (L, N, 1, page, 512), "r": (L,
N, 1, 64, page), "i": (Lf, N, 1, page, 128)}`` (the package's
models/kv_cache.py, ``SparseLatentKV``: the third leaf holds kI over the
model's full layers only), decode runs the ABSORBED form, index scores
are bf16 products accumulated in float32 and the selection is a mask
found by bisection; none of that is here. Here: float32 under
``highest``, the expanded form, ``lax.top_k`` over the whole (T, T) score
matrix of a full layer (built a head at a time, so that 4608 x 4608
float32 fits), the set handed down to the shared layers as a (T, T)
mask, no cache, no kernel. Attention runs eight heads at a time and the
wide dense MLP a quarter of its width at a time.
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


def _rope_pairs(x, inv, interleave=True):
    """x: (T, heads, d), position t = row t; pairs (2i, 2i+1) under
    ``interleave``, else (i, i + d/2). The rotated pairs come back evens
    first, then odds (queries and keys alike, so their products are the
    published ones)."""
    T, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
    else:
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


HEAD_GROUP = 8            # attention, this many heads at a time


@functools.partial(jax.jit, static_argnames=(
    "heads", "dim", "rope", "interleave", "top_k"))
def _select(a, c_q, w, inv, *, heads, dim, rope, interleave, top_k):
    """A full layer's selection: (T, T) bool, row t the set S_t."""
    T = a.shape[0]
    q = (c_q @ w["index_wq"]).reshape(T, heads, dim)
    k = a @ w["index_wk"]
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k / jnp.sqrt(jnp.mean(k * k, axis=-1, keepdims=True) + 1e-6)
    k = (k * w["index_k_norm"] + w["index_k_norm_b"])[:, None, :]
    q = jnp.concatenate([_rope_pairs(q[..., :rope], inv, interleave),
                         q[..., rope:]], axis=-1)
    k = jnp.concatenate([_rope_pairs(k[..., :rope], inv, interleave),
                         k[..., rope:]], axis=-1)[:, 0]
    weight = (a @ w["index_wp"]) * (heads ** -0.5 * dim ** -0.5)  # (T, Hi)

    def head(acc, j):         # a head at a time: (T, T) float32 each
        s = jax.nn.relu(q[:, j] @ k.T)
        return acc + weight[:, j][:, None] * s, None
    scores, _ = jax.lax.scan(head, jnp.zeros((T, T), jnp.float32),
                             jnp.arange(heads))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if T <= top_k:
        return seen
    _, chosen = jax.lax.top_k(jnp.where(seen, scores + 0.0, -jnp.inf), top_k)
    keep = jnp.zeros((T, T), bool).at[i, chosen].set(True)
    return keep & seen        # a row shorter than top_k: all it has seen


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "eps", "interleave"))
def _latents(x, w, inv, *, heads, nope, rope, eps, interleave):
    """The projections of a block's attention: the normed input, the
    query latent, and every head's queries, keys and values."""
    T = x.shape[0]
    a = _rms(x, w["attn_norm"], eps)
    c_q = _rms(a @ w["wq_a"], w["q_a_norm"], eps)
    q = (c_q @ w["wq_b"]).reshape(T, heads, nope + rope)
    kv = a @ w["wkv_a"]
    R = kv.shape[-1] - rope
    c = _rms(kv[:, :R], w["kv_a_norm"], eps)
    q_r = _rope_pairs(q[..., nope:], inv, interleave)
    k_r = _rope_pairs(kv[:, None, R:], inv, interleave)[:, 0]
    k_nope = (c @ w["wk_b"]).reshape(T, heads, nope)
    v = (c @ w["wv_b"]).reshape(T, heads, -1)
    return a, c_q, (q[..., :nope], q_r, k_nope, k_r, v)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "scale"))
def _attend(x, qkv, keep, w, *, heads, eps, scale):
    """Expanded attention over the kept keys, the output projection and
    the residual add. Returns the stream and the normed input of the
    MLP."""
    T = x.shape[0]
    q_nope, q_r, k_nope, k_r, v = qkv

    def some_heads(part):                     # (T, HEAD_GROUP, .) each
        qn, qr, kn, vh = part
        s = (jnp.einsum("thd,shd->hts", qn, kn)
             + jnp.einsum("thd,sd->hts", qr, k_r)) * scale
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, vh)

    group = math.gcd(heads, HEAD_GROUP)

    def grouped(y):
        return y.reshape(T, heads // group, group,
                         y.shape[-1]).swapaxes(0, 1)
    att = jax.lax.map(some_heads, (grouped(q_nope), grouped(q_r),
                                   grouped(k_nope), grouped(v)))
    att = att.swapaxes(0, 1).reshape(T, -1)
    h = x + att @ w["wo"]
    return h, _rms(h, w["mlp_norm"], eps)


def _gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


@jax.jit
def _add_gated(acc, m, gate, up, down):
    return acc + _gated(m, gate, up, down)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "first"))
def _expert_block(h, m, w, at, gate, up, down, *, top_k, scale, first):
    """Sigmoid routing over ALL the router's columns; the HELD experts
    one at a time (each cut out of the stored stack and upcast where it
    is used; gate/up: (L, E_held, D, F), down: (L, E_held, F, D) as
    stored, bf16; ``at`` the layer's place in them; stored expert e is
    the layer's expert ``first`` + e), the shared expert, and the add.
    An expert that is not held adds nothing."""
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
    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(gate.shape[1]))
    return h + y + _gated(m, w["ws_gate"], w["ws_up"], w["ws_down"])


VOCAB_BLOCK = 8192        # the head, a block of the vocabulary at a time
MLP_BLOCKS = 4            # the dense MLP, a quarter of its width at a time

ATTENTION = ("attn_norm", "mlp_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a",
             "kv_a_norm", "wk_b", "wv_b", "wo")
INDEXER = ("index_wq", "index_wk", "index_k_norm", "index_k_norm_b",
           "index_wp")


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, *, eps):
    return _rms(x, w, eps)


@jax.jit
def _head(h, lm_head):
    return h @ lm_head


def full_layers(model) -> list:
    """One 0/1 a layer: ``index_layers`` as it stands, or repeated as a
    period; every layer full where it is absent."""
    pattern = list(model.get("index_layers") or [1])
    return [int(pattern[i % len(pattern)])
            for i in range(model["num_layers"])]


def forward(params, model, ids, positions):
    """``params`` is the served tree as stored (``embed``, ``dense_layers``
    and ``layers`` with every leaf stacked over the stack's layers, an
    expert's over the held experts too and the indexer's over the stack's
    full layers, ``final_norm``, ``lm_head``), read a layer at a time.
    Returns the logits at ``positions``."""
    stated = {"router_score_func": "sigmoid", "router_bias": "selection",
              "router_norm_topk": True, "mlp": "swiglu",
              "router_input": "mlp_norm", "rope_scaling_type": "linear",
              "rope_scaling_factor": 1.0}
    for key, want in stated.items():
        if model.get(key, want) != want:
            raise ValueError(f"this reference is GLM-5.2's block "
                             f"({key} {want!r}); the model group states "
                             f"{model[key]!r}")
    if not (model.get("kv_lora_rank") and model.get("index_topk")):
        raise ValueError("this reference is latent attention under a "
                         "learned selection: the model group states no "
                         "kv_lora_rank or no index_topk")
    eps = float(model.get("rms_norm_eps", 1e-5))
    dense = int(model.get("num_dense_layers", 0))
    nope, rope = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    theta = float(model["rope_theta"])
    inv = jnp.asarray([theta ** (-2.0 * i / rope) for i in range(rope // 2)],
                      jnp.float32)
    scale = (nope + rope) ** -0.5
    first = int(model.get("experts_first", 0))
    full = full_layers(model)
    if not full[0]:
        raise ValueError("layer 0 is shared: no full layer below it")
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"], rows=jnp.asarray(ids[0]))
        keep = None
        for layer in range(model["num_layers"]):
            stack, at, below = (("dense_layers", layer, 0) if layer < dense
                                else ("layers", layer - dense, dense))
            leaves = params[stack]
            w = {n: _f32(leaves[n], at) for n in ATTENTION}
            a, c_q, qkv = _latents(
                x, w, inv, heads=model["num_heads"], nope=nope, rope=rope,
                eps=eps, interleave=bool(model.get("rope_interleave", False)))
            if full[layer]:       # its row among the stack's full layers
                k = sum(full[below:layer])
                keep = _select(
                    a, c_q, {n: _f32(leaves[n], k) for n in INDEXER}, inv,
                    heads=int(model["index_n_heads"]),
                    dim=int(model["index_head_dim"]), rope=rope,
                    interleave=bool(model.get("index_rope_interleave",
                                              False)),
                    top_k=int(model["index_topk"]))
            h, mm = _attend(x, qkv, keep, w, heads=model["num_heads"],
                            eps=eps, scale=scale)
            del w, a, c_q, qkv
            if layer < dense:
                F = model["intermediate_size"]
                step = -(-F // MLP_BLOCKS)
                x = h
                for lo in range(0, F, step):
                    hi = min(lo + step, F)
                    x = _add_gated(
                        x, mm, _f32(leaves["w_gate"], at, cols=(lo, hi)),
                        _f32(leaves["w_up"], at, cols=(lo, hi)),
                        _f32(leaves["w_down"], at, krows=(lo, hi)))
            else:
                w = {n: _f32(leaves[n], at) for n in (
                    "router", "router_bias", "ws_gate", "ws_up", "ws_down")}
                x = _expert_block(
                    h, mm, w, jnp.int32(at), *(
                        leaves[n] for n in ("w_gate", "w_up", "w_down")),
                    top_k=int(model["num_experts_per_tok"]),
                    scale=float(model.get("router_scale", 1.0)), first=first)
            jax.block_until_ready(x)      # a layer's float32 at a time
        h = _final_norm(x[jnp.asarray(positions)], _f32(params["final_norm"]),
                        eps=eps)
        V = model["vocab_size"]
        return jnp.concatenate([
            _head(h, _f32(params["lm_head"],
                          cols=(lo, min(lo + VOCAB_BLOCK, V))))
            for lo in range(0, V, VOCAB_BLOCK)], axis=-1)
