"""Sparse mixture-of-experts: top-k routing with capacity-bounded dispatch.

The reference never runs Mixtral locally — it reaches it through cloud
endpoints (reference: examples/5_mins_rag_no_gpu/main.py:50). Here expert
parallelism is first-class: this module is the sparse-compute path promised
by ``models/llama.py`` — O(tokens x k) expert FLOPs instead of the dense
formulation's O(tokens x E).

Design (TPU-first):
- **Static shapes.** Each expert processes a fixed-capacity buffer
  ``C = ceil(T*k/E * capacity_factor)``; overflowing tokens are dropped
  (their combine weight is zero) — the GShard/Switch capacity discipline
  that keeps XLA shapes static.
- **Scatter/gather dispatch.** Tokens are routed with one scatter-add into
  ``(E, C, D)`` and one gather back — O(T*k*D) data movement, not the
  O(T*E*C*D) one-hot-einsum formulation (quadratic in T at prefill).
- **EP sharding.** Under GSPMD the expert axis of the ``(E, C, D)`` buffers
  follows the ``ep``-sharded expert weights, so XLA inserts the token
  all-to-all over ICI on its own. ``ep_expert_ffn`` is the explicit
  ``shard_map`` equivalent (experts over ``ep``, FFN width over ``tp`` with
  a psum), used where manual control is wanted and as the parity oracle.
- **Dropless** (``moe_impl="dropless"``, ``dropless_moe_ffn``): many
  narrow experts and several a token leave no capacity worth having (a
  capacity that can drop nothing is C = T: E/k times the work). The
  assignments are sorted by expert, each expert's rows padded to whole
  blocks of ``bm`` rows, and ONE loop walks the blocks that hold rows —
  a block is one expert's weights against ``bm`` rows. Shapes are static
  (at most ``T*k // bm + E`` blocks); the trip count is dynamic, so a
  decode step streams the weights of the experts its rows touch and no
  others, and nothing is dropped at any batch. On TPU the loop is the
  Pallas kernel of ops/grouped_ffn.py, whose pipeline fetches the next
  block's weights while this one computes; elsewhere a ``fori_loop`` of
  plain dots, which is also the kernel's oracle.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.configs import LlamaConfig
from ..ops import grouped_ffn


def expert_capacity(n_tokens: int, n_experts: int, k: int,
                    capacity_factor: float) -> int:
    """Per-expert token slots; static given the (padded) token count."""
    return max(1, int(-(-n_tokens * k * capacity_factor // n_experts)))


def route_topk(router_logits: jax.Array, k: int, capacity: int):
    """Top-k routing with in-expert slot assignment.

    router_logits: (T, E). Returns flat (T*k,) arrays, token-major:
      expert  — chosen expert id per claim
      slot    — position inside that expert's capacity buffer
      weight  — softmaxed router weight (float32)
      keep    — False where the expert's capacity was already full
    Earlier tokens claim slots first (deterministic, order-based priority).
    """
    T, E = router_logits.shape
    w, idx = jax.lax.top_k(router_logits, k)                    # (T, k)
    w = jax.nn.softmax(w.astype(jnp.float32), axis=-1)
    expert = idx.reshape(-1)                                    # (T*k,)
    claims = jax.nn.one_hot(expert, E, dtype=jnp.int32)         # (T*k, E)
    pos = jnp.cumsum(claims, axis=0) - 1                        # claim rank
    slot = jnp.take_along_axis(pos, expert[:, None], axis=1)[:, 0]
    keep = slot < capacity
    return expert, jnp.clip(slot, 0, capacity - 1), w.reshape(-1), keep


def _dispatch(x_flat: jax.Array, expert: jax.Array, slot: jax.Array,
              keep: jax.Array, n_experts: int, capacity: int) -> jax.Array:
    """(T, D) tokens -> (E, C, D) expert buffers (scatter; slots unique)."""
    T, D = x_flat.shape
    k = expert.shape[0] // T
    t_idx = jnp.repeat(jnp.arange(T), k)
    contrib = x_flat[t_idx] * keep[:, None].astype(x_flat.dtype)
    return jnp.zeros((n_experts, capacity, D), x_flat.dtype).at[
        expert, slot].add(contrib)


def _combine(expert_out: jax.Array, expert: jax.Array, slot: jax.Array,
             weight: jax.Array, keep: jax.Array, n_tokens: int) -> jax.Array:
    """(E, C, D) expert outputs -> (T, D) weighted token outputs (gather)."""
    k = expert.shape[0] // n_tokens
    t_idx = jnp.repeat(jnp.arange(n_tokens), k)
    y = expert_out[expert, slot]                                # (T*k, D)
    w = (weight * keep).astype(y.dtype)[:, None]
    return jnp.zeros((n_tokens, expert_out.shape[-1]), y.dtype).at[
        t_idx].add(y * w)


def _expert_ffn(expert_in: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array) -> jax.Array:
    """Per-expert SwiGLU on (E, C, D) with stacked (E, D, F) weights."""
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, w_gate))
    up = jnp.einsum("ecd,edf->ecf", expert_in, w_up)
    return jnp.einsum("ecf,efd->ecd", gate * up, w_down)


def sparse_moe_ffn(x: jax.Array, lp: dict[str, jax.Array],
                   cfg: LlamaConfig) -> jax.Array:
    """Sparse MoE layer: (B, S, D) -> (B, S, D), top-k experts per token.

    Pure jnp — under jit with ``ep``-sharded expert weights GSPMD reshards
    the (E, C, D) buffers over ``ep`` and emits the all-to-all itself.
    """
    B, S, D = x.shape
    T = B * S
    x_flat = x.reshape(T, D)
    C = expert_capacity(T, cfg.num_experts, cfg.num_experts_per_tok,
                        cfg.moe_capacity_factor)
    # Stage scopes (models/llama.py SCOPES): routing, the capacity
    # scatter and the weighted gather are ``moe_route``; the expert
    # einsums — the weight stream — are ``moe_experts``.
    with jax.named_scope("moe_route"):
        logits = x_flat.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        expert, slot, weight, keep = route_topk(
            logits, cfg.num_experts_per_tok, C)
        expert_in = _dispatch(x_flat, expert, slot, keep, cfg.num_experts, C)
    with jax.named_scope("moe_experts"):
        expert_out = _expert_ffn(expert_in, lp["w_gate"], lp["w_up"],
                                 lp["w_down"])
    with jax.named_scope("moe_route"):
        return _combine(expert_out, expert, slot, weight, keep,
                        T).reshape(B, S, D)


def ep_expert_ffn(mesh: Mesh, expert_in: jax.Array, w_gate: jax.Array,
                  w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """Explicit shard_map expert FFN: experts over ``ep``, FFN width over
    ``tp`` (row-parallel down-projection closed with a psum over tp)."""
    def local(ei, g, u, d):
        out = _expert_ffn(ei, g, u, d)
        return jax.lax.psum(out, "tp")

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("ep", None, None), P("ep", None, "tp"),
                  P("ep", None, "tp"), P("ep", "tp", None)),
        out_specs=P("ep", None, None))(expert_in, w_gate, w_up, w_down)


def ep_sparse_moe_ffn(mesh: Mesh, x: jax.Array, lp: dict[str, jax.Array],
                      cfg: LlamaConfig) -> jax.Array:
    """``sparse_moe_ffn`` with the expert compute under explicit shard_map
    (dispatch/combine stay global: XLA lowers the boundary resharding to
    the ep all-to-all over ICI)."""
    B, S, D = x.shape
    T = B * S
    x_flat = x.reshape(T, D)
    C = expert_capacity(T, cfg.num_experts, cfg.num_experts_per_tok,
                        cfg.moe_capacity_factor)
    # capacity must tile over ep shards evenly for the shard_map specs
    with jax.named_scope("moe_route"):
        logits = x_flat.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        expert, slot, weight, keep = route_topk(
            logits, cfg.num_experts_per_tok, C)
        expert_in = _dispatch(x_flat, expert, slot, keep, cfg.num_experts, C)
    with jax.named_scope("moe_experts"):
        expert_out = ep_expert_ffn(mesh, expert_in, lp["w_gate"],
                                   lp["w_up"], lp["w_down"])
    with jax.named_scope("moe_route"):
        return _combine(expert_out, expert, slot, weight, keep,
                        T).reshape(B, S, D)


# ------------------------------------------------------------- dropless


def dropless_block_rows(n_tokens: int, per_expert: float | None = None
                        ) -> int:
    """Rows of one block of the grouped product: a decode batch's rows in
    one block an expert (a row chooses an expert at most once), a prefill
    chunk's in blocks of 64 (its experts average T*k/E rows). Under an
    expert share the caller says how many rows an expert is to hold
    (``per_expert``: T*k / num_experts expected, with what room it wants)
    and the block is that, in the kernel's 16-row tiles, and no taller
    than without: a share's experts average 8-16 rows of a 512-token
    chunk, so a 64-row block is three quarters padding that the gather,
    the product's grid and the way back all carry."""
    rows = min(64, max(16, -(-n_tokens // 16) * 16))
    if per_expert is None:
        return rows
    return min(rows, max(16, 16 * math.ceil(per_expert / 16)))


def share_walk(n_tokens: int, k: int, n_experts: int, held: int,
               bm: int) -> int:
    """Blocks one trip of a share's walk holds (``few``): the blocks the
    held experts are EXPECTED to fill, an eighth for a router's skew and
    two standard deviations, from static shapes alone. An expert's rows
    are Binomial(T, k / n_experts) under a balanced router and it fills
    ceil(rows / bm) blocks; the held experts are taken as independent.
    A step that fills more (a more skewed router, rows crowding onto the
    held experts) takes a second trip: the count decides how much is
    walked, never what is served."""
    p = min(k / n_experts, 1 - 1e-9)
    mean = var = 0.0
    for c in range(1, n_tokens + 1):
        q = math.exp(math.lgamma(n_tokens + 1) - math.lgamma(c + 1)
                     - math.lgamma(n_tokens - c + 1) + c * math.log(p)
                     + (n_tokens - c) * math.log1p(-p))
        b = -(-c // bm)
        mean, var = mean + q * b, var + q * b * b
    var = max(var - mean * mean, 0.0)
    few = math.ceil(1.125 * held * mean + 2 * math.sqrt(held * var))
    return max(1, min(few, n_tokens * k // bm + held))


def route_sorted(select: jax.Array, k: int, bm: int,
                 row_mask: jax.Array | None = None,
                 weigh: jax.Array | None = None,
                 share: tuple[int, int] | None = None) -> dict:
    """Top-k routing laid out for a grouped product: the T*k assignments
    sorted by expert, each expert's run padded to whole ``bm``-row blocks.

    Choice and weight need not come from one score: ``select`` (T, E)
    decides which k experts a token gets, ``weigh`` (T, E) float32 what
    each chosen one weighs, as given (``router_scores``). ``weigh``
    None: the softmax over the chosen k of ``select``, the router's
    logits. ``row_mask`` (T,) bool: rows that route nowhere (idle
    decode slots) — they touch no expert. Returns
      weight        (T, k) f32   of the chosen k
      token         (R,)         source token of each padded row
      valid         (R,) bool    padded rows that hold an assignment
      row_of        (T, k)       padded row of each assignment
      block_expert  (NB,)        expert of each block
      n_blocks      ()           blocks that hold rows (the loop's bound)
      touched       ()           distinct experts with at least one row
    with R = NB * bm, NB = T*k // bm + E (static).

    Under ``share`` = (first, E) — the E experts held here of the
    ``select`` columns — what falls on an expert held elsewhere is
    dropped BEFORE the sort: it takes no row, no block and no rank, the
    blocks name the held experts from 0, and ``row_of`` is 0 there. The
    layout is then WALKED, ``few`` blocks a trip (``share_walk``: what
    the held experts are expected to fill; the blocks that hold rows
    come first), so NB is T*k // bm + E rounded up to whole trips — its
    worst case, every assignment held here, stays servable — and the
    share's entries are
      held          (T, k) bool  assignments to experts held here
      assigned      ()           assignments that hold a row
      few           int          blocks a trip of the walk (static)
      rows_read     ()           padded rows the walk's trips gather:
                                 ceil(n_blocks / few) * few * bm
    """
    T, E = select.shape
    A = T * k
    w, idx = jax.lax.top_k(select, k)                           # (T, k)
    if weigh is None:
        weight = jax.nn.softmax(w.astype(jnp.float32), axis=-1)
    else:
        weight = jnp.take_along_axis(weigh, idx, axis=1)
    expert = idx.reshape(A).astype(jnp.int32)                   # token-major
    held = None
    if share is not None:
        first, E = share
        expert = expert - first
        held = (expert >= 0) & (expert < E)
        expert = jnp.clip(expert, 0, E - 1)
    # A counting sort, not ``argsort``: a claim's rank among its expert's
    # claims is a running count down its expert's column (token order is
    # kept, as ``route_topk`` keeps it) — one cumsum where a sort, a
    # search and two scatters were (1.6 ms of a 13 ms decode step, 5 ms
    # of a 45 ms chunk; chip, PR 28).
    claims = jax.nn.one_hot(expert, E, dtype=jnp.int32)         # (A, E)
    claimed = jnp.ones((A,), bool) if held is None else held
    if row_mask is not None:        # idle rows claim nothing
        claimed = jnp.repeat(row_mask, k) if held is None \
            else held & jnp.repeat(row_mask, k)
        weight = weight * row_mask[:, None]
    if row_mask is not None or held is not None:
        claims = claims * claimed[:, None].astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(claims, axis=0) - claims,
                               expert[:, None], axis=1)[:, 0]
    counts = jnp.sum(claims, axis=0)                            # (E,)
    blocks = (counts + bm - 1) // bm
    bend = jnp.cumsum(blocks)
    bstart = bend - blocks                      # an expert's first block
    NB = A // bm + E
    if share is not None:       # whole trips of the walk
        few = share_walk(T, k, select.shape[1], E, bm)
        NB = -(-NB // few) * few
    # the expert of block b: how many experts' blocks end at or before b
    block_expert = jnp.minimum(jnp.sum(
        bend[None, :] <= jnp.arange(NB, dtype=jnp.int32)[:, None], axis=1),
        E - 1).astype(jnp.int32)
    row_of = bstart[expert] * bm + rank                         # (A,)
    # the claim each padded row holds (-1: padding), by one scatter of
    # row numbers; rows are unique, idle claims are sent out of range
    src = jnp.full((NB * bm,), -1, jnp.int32).at[
        jnp.where(claimed, row_of, NB * bm)].set(
        jnp.arange(A, dtype=jnp.int32), mode="drop")
    rt = {"weight": weight, "token": jnp.maximum(src, 0) // k,
          "valid": src >= 0,
          "row_of": jnp.where(claimed, row_of, 0).reshape(T, k),
          "block_expert": block_expert, "n_blocks": bend[-1],
          "touched": jnp.sum(counts > 0).astype(jnp.float32)}
    if held is not None:
        rt["held"] = held.reshape(T, k)
        rt["assigned"] = jnp.sum(counts).astype(jnp.float32)
        rt["few"] = few
        rt["rows_read"] = (-(-bend[-1] // few) * (few * bm)).astype(
            jnp.float32)
    return rt


def router_scores(logits: jax.Array, lp: dict[str, jax.Array],
                  cfg: LlamaConfig) -> tuple:
    """``(select, weigh)`` for ``route_sorted`` from a router's logits
    (T, E) float32. Softmax scoring: the logits, and None (the softmax
    over the chosen). Sigmoid scoring: each expert's own score; the
    stored ``router_bias`` moves the choice only ("selection") or the
    scores themselves ("scores"). Under a limit to groups
    (``cfg.n_group`` groups of consecutive experts, ``cfg.topk_group``
    kept) the scores for the choice are zero outside the kept groups, as
    the published rule fills them (``group_limit``)."""
    if cfg.router_score_func == "softmax":
        return logits, None
    scores = jax.nn.sigmoid(logits)
    biased = scores + lp["router_bias"] if cfg.router_bias else scores
    weigh = biased if cfg.router_bias == "scores" else scores
    if cfg.topk_group < cfg.n_group:
        biased = jnp.where(group_limit(biased, cfg), biased, 0.0)
    return biased, weigh


def group_limit(biased: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """(T, E) bool: the experts a token may choose among. A group's score
    is the sum of its two largest (biased) scores; the ``topk_group``
    best of the ``n_group`` groups stay (ties to the lower group, as
    ``top_k`` breaks them)."""
    T, E = biased.shape
    groups = biased.reshape(T, cfg.n_group, E // cfg.n_group)
    score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)   # (T, n_group)
    _, kept = jax.lax.top_k(score, cfg.topk_group)
    keep = jnp.sum(jax.nn.one_hot(kept, cfg.n_group, dtype=jnp.int32),
                   axis=1) > 0
    return jnp.repeat(keep, E // cfg.n_group, axis=1)


def _groups_held_pct(select: jax.Array, cfg: LlamaConfig, row_mask,
                     S: int) -> jax.Array:
    """The share (%) of the live tokens whose kept groups include one
    this tree holds experts of: who may send this chip anything at all.
    ``select`` is zero outside a token's kept groups (``router_scores``)
    and a kept expert's biased score is not."""
    first, held = cfg.experts_first, cfg.experts_held
    may = jnp.any(select[:, first:first + held] != 0, axis=1)
    live = jnp.ones_like(may) if row_mask is None \
        else jnp.repeat(row_mask, S)
    return 100.0 * jnp.sum(may & live) / jnp.maximum(jnp.sum(live), 1)


def scale_chosen(weight: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """Sigmoid scores of the chosen k (T, k), normalised over them and
    scaled as the configuration says; zero rows (idle) stay zero."""
    if cfg.router_score_func == "softmax":
        return weight
    if cfg.router_norm_topk:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return weight * cfg.router_scale


def block_loop_ffn(x_pad: jax.Array, block_expert: jax.Array,
                   n_blocks: jax.Array, layer_index: jax.Array,
                   w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                   *, bm: int, relu: bool) -> jax.Array:
    """``ops/grouped_ffn.py`` ``grouped_expert_ffn`` as a ``fori_loop`` of
    plain dots over the blocks that hold rows: what runs off the TPU, and
    the kernel's oracle. Rows of blocks without rows stay zero."""
    act = jax.nn.relu if relu else jax.nn.silu

    def expert(w, e):
        return jax.lax.dynamic_slice(
            w, (layer_index, e, 0, 0), (1, 1) + w.shape[2:])[0, 0]

    def block(b, y_pad):
        e = block_expert[b]
        xb = jax.lax.dynamic_slice_in_dim(x_pad, b * bm, bm)
        gate = jnp.dot(xb, expert(w_gate, e),
                       preferred_element_type=jnp.float32)
        up = jnp.dot(xb, expert(w_up, e), preferred_element_type=jnp.float32)
        yb = jnp.dot((act(gate) * up).astype(x_pad.dtype), expert(w_down, e),
                     preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_slice_in_dim(
            y_pad, yb.astype(x_pad.dtype), b * bm, 0)

    return jax.lax.fori_loop(0, n_blocks, block, jnp.zeros_like(x_pad))


def dropless_moe_ffn(x: jax.Array, router_logits: jax.Array,
                     lp: dict[str, jax.Array], cfg: LlamaConfig,
                     row_mask: jax.Array | None = None,
                     aux: dict | None = None):
    """Dropless sparse MoE layer: (B, S, D) -> ((B, S, D), touched).

    ``router_logits`` (B, S, E) are the caller's (they may come from
    another point of the block than ``x``). Every token gets all k of its
    experts whatever the batch, and a token's output does not depend on
    its neighbours. ``touched`` is the number of distinct experts the
    rows reached: the experts whose weights this call read.

    Under an expert share (``cfg.experts_held``: ``lp`` holds the
    matrices of experts ``experts_first`` .. + ``experts_held`` only) the
    router still scores all ``num_experts`` and a token's k weights are
    normalised over its k; what falls on experts held elsewhere takes no
    row and no weight read here, and the output is the PARTIAL sum over
    the held experts — what this chip adds before the exchange that
    nothing here stands in for. Everything after the top-k is then sized
    by what fell on the HELD experts, not by all T * k: the block height
    follows an expert's expected rows (``dropless_block_rows``), the
    dispatch gather and the product's grid walk the blocks that hold
    rows (``share_walk`` blocks a trip, the trips counted from the
    data), and the weights and the sum run over those rows
    (``_walk_share``). ``touched`` then counts among the held, and
    ``aux`` (a dict, if given) receives ``local_assignments``, the
    assignments that fell on them, and ``route_rows_read``, the padded
    rows the walk gathered to serve them.

    ``lp`` holds the layer's own (E, in, out) expert stacks or — where
    the caller keeps the stacks out of its layer scan and hands
    ``layer_index`` (models/llama.py ``scan_layers``) — the whole
    (L, E, in, out) stacks: an expert's matrix is sliced where it is
    used, so that no program copies a layer's slab of experts.
    """
    B, S, D = x.shape
    T = B * S
    k = cfg.num_experts_per_tok
    relu = cfg.mlp == "relu_glu"
    x_flat = x.reshape(T, D)
    w_gate, w_up, w_down = (lp[n] if lp[n].ndim == 4 else lp[n][None]
                            for n in ("w_gate", "w_up", "w_down"))
    li = jnp.asarray(lp.get("layer_index", 0), jnp.int32)
    share = (cfg.experts_first, cfg.experts_held) \
        if cfg.experts_held else None
    bm = dropless_block_rows(T)
    if share:
        # an expert's expected rows; with as much again for room where
        # the kernel tiles the FFN width, so that a second block of an
        # expert — which re-reads its matrices there — stays rare
        F = w_gate.shape[-1]
        tiled = grouped_ffn.ffn_tile(D, F, w_gate.dtype.itemsize) < F
        bm = dropless_block_rows(
            T, T * k / cfg.num_experts * (2 if tiled else 1))
    with jax.named_scope("moe_route"):
        select, weigh = router_scores(
            router_logits.reshape(T, cfg.num_experts), lp, cfg)
        rt = route_sorted(
            select, k, bm,
            None if row_mask is None else jnp.repeat(row_mask, S), weigh,
            share)
        rt["weight"] = scale_chosen(rt["weight"], cfg)
        if share:       # after the k were normalised together
            rt["weight"] = rt["weight"] * rt["held"]
            if aux is not None:
                aux["local_assignments"] = rt["assigned"]
                aux["route_rows_read"] = rt["rows_read"]
                if cfg.topk_group < cfg.n_group:
                    aux["route_groups_held_pct"] = _groups_held_pct(
                        select, cfg, row_mask, S)

    ffn = (grouped_ffn.grouped_expert_ffn
           if grouped_ffn.use_kernel(D, w_gate.shape[-1], bm, x.dtype)
           else block_loop_ffn)

    if not share:
        with jax.named_scope("moe_route"):
            x_pad = jnp.where(rt["valid"][:, None], x_flat[rt["token"]], 0)
        with jax.named_scope("moe_experts"):
            y_pad = ffn(x_pad, rt["block_expert"], rt["n_blocks"], li,
                        w_gate, w_up, w_down, bm=bm, relu=relu)
        return _combine_sorted(y_pad, rt, x.dtype, (B, S, D))
    out = _walk_share(
        x_flat, rt, lambda x_pad, block_expert, n_blocks: ffn(
            x_pad, block_expert, n_blocks, li, w_gate, w_up, w_down, bm=bm,
            relu=relu), bm)
    return out.astype(x.dtype).reshape(B, S, D), rt["touched"]


def _walk_share(x_flat: jax.Array, rt: dict, ffn, bm: int) -> jax.Array:
    """A share does the work of what is HELD HERE: (T, D) float32, each
    token's weighted sum over its assignments to held experts.

    The layout is sized for every assignment falling on a held expert
    and 1 in num_experts / experts_held of them is expected to: gathered
    whole, a 512-token chunk's 13312 padded rows held ~1280 assignments,
    and the way back gathered, masked and summed all T * k of them in
    float32, three in four of weight 0 — a quarter of a chunk program
    (chip, PR 47). So the layout is walked ``few`` blocks a trip
    (``share_walk``), the blocks that hold rows first: a trip gathers
    its rows' tokens, runs the grouped product ``ffn(x_pad,
    block_expert, n_blocks)`` over its blocks alone, and adds each row's
    weighted output to its token by a product with the (T, rows) matrix
    that holds a row's weight in its token's line — exact in float32
    (``_exact_parts``): the sum ``_combine_sorted`` makes, its addends
    in row order — with no gather back and no (T, k, D) tensor. A step
    whose rows crowd onto the held experts takes more trips: nothing
    is dropped, and no trip holds more than ``few`` blocks of rows."""
    T, D = x_flat.shape
    few = rt["few"]
    seg = few * bm
    with jax.named_scope("moe_route"):
        # each padded row's weight, by a scatter over the T * k
        # assignments (what is held elsewhere or idle weighs 0 and is
        # sent out of range): a gather costs by the index, ~13 ns each
        # whatever a row's width (chip, PR 50), and the layout has more
        # rows than a step has assignments
        w = rt["weight"].reshape(-1)
        R = rt["valid"].shape[0]
        w_row = jnp.zeros((R,), jnp.float32).at[
            jnp.where(w > 0, rt["row_of"].reshape(-1), R)].set(
            w, mode="drop")
        tokens = jnp.arange(T, dtype=jnp.int32)[:, None]

    def trip(i, out):
        at = lambda a, n: jax.lax.dynamic_slice_in_dim(a, i * n, n)
        with jax.named_scope("moe_route"):
            token, valid = at(rt["token"], seg), at(rt["valid"], seg)
            x_pad = jnp.where(valid[:, None], x_flat[token], 0)
        with jax.named_scope("moe_experts"):
            y_pad = ffn(x_pad, at(rt["block_expert"], few),
                        jnp.clip(rt["n_blocks"] - i * few, 0, few))
        with jax.named_scope("moe_route"):
            # blocks without rows are left unwritten by the kernel
            y = jnp.where(valid[:, None], y_pad, 0)
            hit = token[None, :] == tokens                      # (T, seg)
            for part in _exact_parts(at(w_row, seg), y.dtype):
                out = out + jnp.dot(
                    jnp.where(hit, part[None, :], 0), y,
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
            return out

    return jax.lax.fori_loop(0, -(-rt["n_blocks"] // few), trip,
                             jnp.zeros((T, D), jnp.float32))


def _exact_parts(w: jax.Array, dtype) -> tuple:
    """float32 ``w`` as terms of ``dtype`` that sum to it exactly: itself
    for float32, three bfloat16 terms (8 + 8 + 8 of its 24 mantissa
    bits) for bfloat16 — so that a product of ``w`` with bfloat16 values
    is three single-pass products, each exact in its float32 sum, where
    ONE float32 product at ``HIGHEST`` is six passes and, at a chunk's
    (512, ~2400) x (~2400, D), seven seconds of every program's compile
    (compile for a described v5e, PR 50)."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return (w,)
    parts = []
    for _ in range(3):
        parts.append(w.astype(jnp.bfloat16))
        w = w - parts[-1].astype(jnp.float32)
    return tuple(parts)


def _combine_sorted(y_pad, rt, dtype, shape):
    """Each token's k expert outputs back from their padded rows,
    weighted. A row that routed nowhere (weight 0) reads nothing: the
    kernel leaves blocks without rows unwritten."""
    with jax.named_scope("moe_route"):
        w = rt["weight"][..., None]                             # (T, k, 1)
        y = jnp.where(w > 0, y_pad[rt["row_of"]].astype(jnp.float32), 0.0)
        out = jnp.sum(y * w, axis=1)
        return out.astype(dtype).reshape(shape), rt["touched"]
