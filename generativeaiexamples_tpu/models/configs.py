"""Model architecture configs and the named-model registry.

Covers the model families the reference serves: Llama-2 chat 7B/13B/70B and
CodeLlama (reference: model_server/model.py:76-87 ``ModelTypes``
LLAMA/CODE_LLAMA/GPTNEXT; docs/rag/support_matrix.md sizing), the
e5-large-v2 embedder (reference: common/configuration.py:95-121), and
Mixtral-8x7B for expert parallelism (reference uses it via cloud endpoints
only, examples/5_mins_rag_no_gpu/main.py:50 — here it is first-class).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace


@dataclass(frozen=True)
class LlamaConfig:
    """Decoder-only transformer (Llama-2 family geometry)."""
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rope_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # MoE (Mixtral): 0 experts = dense MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # "sparse" = top-k capacity routing (parallel/moe.py, O(T*k) FLOPs);
    # "dense" = every expert on every token, zero-gated (O(T*E), no drops).
    moe_impl: str = "sparse"
    moe_capacity_factor: float = 2.0
    # GPT-Next/Nemotron architecture knobs (reference serves this family
    # as its second ensemble, ensemble_models/gptnext/ + conversion via
    # model_server/conversion/nemo.py:35-65):
    #   norm: "rmsnorm" (llama) | "layernorm1p" (NeMo's zero-centered
    #         LayerNorm: weights stored as w-1, applied as (1+w)*x_hat+b)
    #   mlp:  "swiglu" (llama gated SiLU) | "squared_relu" (GPT-Next:
    #         relu(x W_up)^2 W_down, no gate projection)
    norm: str = "rmsnorm"
    mlp: str = "swiglu"
    attn_bias: bool = False   # biases on wq/wk/wv/wo
    mlp_bias: bool = False    # biases on the MLP projections
    # Per-layer kinds, each a 0/1 pattern repeated over the depth (the
    # flags ride the one layer scan as per-layer inputs, models/llama.py
    # ``layer_kinds``; an empty pattern is the plain model and adds
    # nothing to any program):
    #   sliding_window / window_layers: a layer whose flag is 1 attends
    #         only the last ``sliding_window`` keys (itself among them);
    #         0 / () = every layer attends its whole context
    #   rope_layers: a layer whose flag is 0 applies NO rotary embedding;
    #         () = every layer rotates
    sliding_window: int = 0
    window_layers: tuple = ()
    rope_layers: tuple = ()
    # MoE variants beyond Mixtral's:
    #   mlp "relu_glu":  an expert (or dense MLP) gated by relu, not silu
    #   moe_impl "dropless": assignments sorted by expert and a grouped
    #         product over blocks of rows (parallel/moe.py); no capacity,
    #         nothing dropped at any batch; decode streams only the
    #         experts the rows touch
    #   router_input: "mlp_norm" (Mixtral: the router reads the normed
    #         post-attention stream) | "block_input" (the router reads
    #         the stream as it ENTERS the block, un-normed, before the
    #         attention; its logits are handed past it to the experts)
    router_input: str = "mlp_norm"
    # An expert model whose blocks are not all alike (moe_impl "dropless"
    # only; every default is the model above, whose programs it leaves
    # as they are):
    #   moe_intermediate_size: an expert's width where it is not the
    #         dense width (0 = ``intermediate_size`` serves both)
    #   num_dense_layers: the leading layers that keep a dense MLP of
    #         width ``intermediate_size``. Their tree has another shape,
    #         so they are a stack of their own, ``params["dense_layers"]``,
    #         run before ``params["layers"]`` over the one KV pool
    #   num_shared_experts: experts every token passes through, beside
    #         the routed ones and un-weighted: one gated MLP of width
    #         ``num_shared_experts`` x the expert width
    #   router_score_func: "softmax" (top-k of the logits, softmax over
    #         the chosen) | "sigmoid" (each expert scored on its own:
    #         top-k of the scores, a chosen expert's weight its score)
    #   router_norm_topk: sigmoid scores of the chosen divided by their
    #         sum (softmax over the chosen sums to one by itself)
    #   router_scale: the chosen weights times this
    #   router_bias: "" | "selection" (a stored per-expert bias is added
    #         to the scores for the CHOICE only; the weights stay the
    #         un-biased scores) | "scores" (added to the scores, so it
    #         moves choice and weight alike)
    #   n_group / topk_group: the choice LIMITED to groups (sigmoid
    #         scoring): the experts are ``n_group`` groups of consecutive
    #         experts, a group is scored by the sum of its two largest
    #         (biased) scores, the ``topk_group`` best groups stay and the
    #         token's experts are chosen among theirs — so a token
    #         crosses to at most ``topk_group`` devices of a deployment
    #         that holds a group a device. 1 / 1 limits nothing
    moe_intermediate_size: int = 0
    num_dense_layers: int = 0
    num_shared_experts: int = 0
    router_score_func: str = "softmax"
    router_norm_topk: bool = True
    router_scale: float = 1.0
    router_bias: str = ""
    n_group: int = 1
    topk_group: int = 1
    # Attention and block variants, each off by default:
    #   qk_norm: RMSNorm over each head's ``head_dim`` values of q and k
    #         (one weight vector each a layer, shared by the heads),
    #         BEFORE the rotary embedding: the pool holds normed keys
    #   attn_gate: the attention output times sigmoid(x W_z), x the
    #         block's normed input, elementwise, before ``wo``; "head"
    #         (latent attention's): ONE gate a head, ``wz_head`` (D, H)
    #   post_norms: a norm on each sub-block's OUTPUT (attention after
    #         ``wo``, the MLP or experts) before it is added to the
    #         stream: four norms a block
    #   embed_scale: the embedding output times this
    #   residual_multiplier: what a sub-block (mixer, MLP) writes to the
    #         stream times this (1.0: nothing in the program)
    #   attention_multiplier: what per-head or latent attention scores
    #         are multiplied by where it is NOT head_dim ** -0.5 (0.0:
    #         that; ``score_scale`` is what every kernel and reader takes)
    #   logits_divisor: the logits divided by this — applied ONCE, to the
    #         normed hidden row (``llama.unembed_norm``), which every tail
    #         (materialised, scanned, the head kernels) passes through
    qk_norm: bool = False
    attn_gate: bool | str = False
    post_norms: bool = False
    embed_scale: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_divisor: float = 1.0
    # Latent attention (``kv_lora_rank`` > 0; every default is the model
    # above): the block's input is projected to ONE latent row a token,
    # ``kv_lora_rank`` values (normed) beside one rotary key part of
    # ``qk_rope_head_dim`` values that all heads share, and that row is
    # what the cache holds (models/kv_cache.py ``LatentKV``). A head's
    # keys and values are the latent times ``wkv_b`` (``qk_nope_head_dim``
    # + ``v_head_dim`` columns a head); queries come through a second
    # low-rank pair, ``wq_a`` (``q_lora_rank``, normed) and ``wq_b`` — or,
    # where ``q_lora_rank`` is 0, through ONE matrix ``wq`` and no norm.
    # ``head_dim`` is then qk_nope_head_dim + qk_rope_head_dim, of which
    # only the rope part rotates, and ``num_kv_heads`` is 1: the one row.
    #   rope_interleave: the rotary part's pairs are (2i, 2i+1), not
    #         (i, i + half): the columns are taken apart first
    #   rope_scaling_type "yarn": ``rope_scaling_factor`` stretches only
    #         the frequencies that turn less than ``rope_beta_slow`` times
    #         in ``rope_original_max`` positions, keeps those that turn
    #         more than ``rope_beta_fast`` times and blends between
    #         (ops/rope.py); ``rope_mscale_all_dim`` > 0 multiplies the
    #         scores by (0.1 * it * ln(factor) + 1) ** 2
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    rope_scaling_type: str = "linear"
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # An expert share (``experts_held`` > 0; moe_impl "dropless"): this
    # tree holds ``experts_held`` of the layer's ``num_experts`` experts,
    # from expert ``experts_first`` on. The router keeps its
    # ``num_experts`` columns and a token its ``num_experts_per_tok``;
    # what falls on experts held elsewhere is dropped before the sort and
    # the layer's output is the partial sum (parallel/moe.py).
    experts_held: int = 0
    experts_first: int = 0
    # Learned sparse attention over the latent cache (``index_topk`` > 0;
    # latent attention only): a query attends the ``index_topk`` cached
    # tokens an INDEXER scores highest, not its whole context. A layer
    # whose ``index_layers`` flag is 1 ("full") has the indexer:
    # ``index_n_heads`` queries of ``index_head_dim`` values from the
    # query latent, ONE key of that width a token from the block's normed
    # input (LayerNorm; its first ``qk_rope_head_dim`` values rotated,
    # pairs (2i, 2i+1) under ``index_rope_interleave``), head weights
    # from the input; the score of a cached token is sum_j w_j
    # relu(q_j . k), and the ``index_topk`` best causal positions (all of
    # them up to that many; ties to the lower position) are the set the
    # layer attends. Its keys are cached beside the latent rows
    # (models/kv_cache.py ``SparseLatentKV``). A layer whose flag is 0
    # ("shared") has no indexer and no such rows and attends the set of
    # the nearest full layer below: layer 0 is full. ``index_layers`` is
    # one 0/1 a layer, or a period repeated over the depth.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_layers: tuple = ()
    index_rope_interleave: bool = False
    # Manifold-constrained hyper-connections (``hc_mult`` > 0; 0 is the
    # plain residual path, whose programs it leaves as they are): the
    # stream between blocks is ``hc_mult`` copies wide, (B, S, hc_mult x
    # hidden_size), and each sublayer (attention, MLP or experts) reads a
    # learned mixture of the copies and writes back through a doubly
    # stochastic hc_mult x hc_mult matrix (ops/hyper_connection.py has
    # the equations):
    #   hc_sinkhorn_iters: row-then-column normalisations that make it
    #   hc_eps: added under the stream's RMS statistic and to every row
    #         and column sum
    #   hc_res_clamp: the matrix's logits are clamped to +- this before
    #         the exponential
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # Recurrent layers beside attention layers (``full_attention_interval``
    # > 0; 0 is the model above, whose programs it leaves as they are):
    # layer ``i`` is an attention layer when ``i % full_attention_interval
    # == full_attention_place`` (-1: the period's LAST layer, ``(i + 1) %
    # full_attention_interval == 0``; a model whose attention layers sit
    # in mid-period states where) and a RECURRENT layer otherwise — a
    # gated delta rule (ops/gated_delta.py has the equations) or a
    # state-space layer (``linear_decay`` "ssd"): its token mixer
    # keeps no row a token but ONE state a sequence, ``linear_num_value_heads``
    # matrices of ``linear_key_head_dim`` x ``linear_value_head_dim`` in
    # float32, and the last ``linear_conv_kernel_dim - 1`` inputs of a
    # depthwise causal convolution over its q, k and v channels
    # (``linear_num_key_heads`` heads of q and k, each serving
    # ``linear_num_value_heads / linear_num_key_heads`` value heads). The
    # cache is models/kv_cache.py ``RecurrentKV``: pages for the attention
    # layers only, a state a SLOT for the others — pages of per-head K
    # and V, or latent rows where the attention layers are latent
    # (``kv_lora_rank``). The period is counted in the model's layer
    # indices whatever the stacks: leading dense layers
    # (``num_dense_layers``) are recurrent or attention layers by their
    # index like any other, and the expert stack may begin inside a
    # period. Keys beside them:
    #   linear_decay: "head" (the gated delta rule: ONE log-decay a head
    #         a token, ``g = -exp(A_log) softplus(a + dt_bias)``; q, k, v
    #         and the output gate z one projection, decay and write
    #         strength another) | "channel" (Kimi Delta Attention: a
    #         log-decay a CHANNEL of a head's keys, ``g =
    #         linear_decay_floor * sigmoid(exp(A_log) (a + dt_bias))`` in
    #         (``linear_decay_floor``, 0); the state's rows decay each at
    #         its own rate; a decay projection and an output gate as wide
    #         as the heads, the gate a sigmoid; as many key as value
    #         heads) | "ssd" (a state-space layer, Mamba-2: NO delta rule;
    #         a head's state ``linear_value_head_dim`` x
    #         ``linear_key_head_dim`` (P x N) decays by ``exp(dt A)``, ``dt
    #         = softplus(x w_dt + dt_bias)`` a scalar a head that is the
    #         write strength too, ``A = -exp(A_log)``; the
    #         ``linear_num_key_heads`` are the GROUPS that share an input
    #         and an output vector B, C of ``linear_key_head_dim`` values;
    #         a skip ``D x``; the convolution has a bias; the output is
    #         gated by silu(z) BEFORE one norm over a group's whole width
    #         — ops/ssd.py has the equations)
    #   partial_rotary_factor: the share of an attention head's values the
    #         rotary embedding turns, from the first on (pairs (i, i +
    #         half of THAT part)); the rest pass
    #   shared_expert_gate: the shared expert's output times
    #         sigmoid(x . w), ``w`` one vector a layer (``ws_gate_w``)
    full_attention_interval: int = 0
    full_attention_place: int = -1
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    linear_decay: str = "head"
    linear_decay_floor: float = 0.0
    partial_rotary_factor: float = 1.0
    shared_expert_gate: bool = False
    # How ``llama.init_params`` draws a random tree (tests, benchmarks;
    # served weights come from ``import_hf`` and ignore it):
    #   "fan_in": every matrix N(0, 1/fan_in), embedding rows of norm 1
    #   "unit_stream": embedding entries of unit variance; the two
    #         projections that write to the stream (``wo``, ``w_down``)
    #         scaled by 1/sqrt(2L) as GPT-2 and Megatron draw them; ``wq``
    #         times 4. A stream a router can read un-normed without
    #         collapsing, and heads that choose keys, so that a wrong
    #         window or rotary layer shows at the logits. The stream
    #         is the embedding AFTER ``embed_scale``; a ``router_bias``
    #         is the one that evens the load of router columns of
    #         unequal reach (deviation 0.1); the norm weights that
    #         ``qk_norm`` / ``post_norms`` add carry the gains the norms
    #         take out of ``wq``, ``wo`` and ``w_down``, each a tenth
    #         off its centre (``llama.init_params`` says why each)
    #   "unit_stream_thin_experts": "unit_stream" with the ROUTED
    #         experts' ``w_down`` at a fifth of that scale (the shared
    #         expert keeps it) — for a tree that holds EVERY expert of
    #         a layer, where each near-tie bf16 flips between a token's
    #         last chosen expert and the next shows at the logits in
    #         full (a held share hides most of them)
    weight_init: str = "fan_in"

    def __post_init__(self):
        # JSON hands lists; a frozen dataclass used as a jit static and
        # an lru_cache key must hash
        for name in ("window_layers", "rope_layers", "index_layers"):
            object.__setattr__(self, name,
                               tuple(int(x) for x in getattr(self, name)))
        object.__setattr__(self, "sliding_window",
                           int(self.sliding_window or 0))
        if self.sliding_window == 1:
            raise ValueError("sliding_window 1 attends no cached key; "
                             "use 0 (none) or >= 2")
        if self.router_input not in ("mlp_norm", "block_input"):
            raise ValueError(f"unknown router_input {self.router_input!r}")
        if self.weight_init not in ("fan_in", "unit_stream",
                                    "unit_stream_thin_experts"):
            raise ValueError(f"unknown weight_init {self.weight_init!r}")
        if self.router_score_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown router_score_func {self.router_score_func!r}")
        if self.router_bias not in ("", "selection", "scores"):
            raise ValueError(f"unknown router_bias {self.router_bias!r}")
        routed = self.router_score_func == "sigmoid"
        if not routed and (self.router_bias or self.router_scale != 1.0):
            raise ValueError("router_bias and router_scale act on sigmoid "
                             "scores (router_score_func 'sigmoid')")
        varied = (routed or self.moe_intermediate_size
                  or self.num_dense_layers or self.num_shared_experts)
        if varied and not (self.num_experts
                           and self.moe_impl == "dropless"):
            raise ValueError(
                "moe_intermediate_size, num_dense_layers, "
                "num_shared_experts and sigmoid routing need experts "
                "under moe_impl 'dropless'")
        if not 0 <= self.num_dense_layers < max(self.num_layers, 1):
            raise ValueError("num_dense_layers must leave an expert layer")
        if self.rope_scaling_type not in ("linear", "yarn"):
            raise ValueError(
                f"unknown rope_scaling_type {self.rope_scaling_type!r}")
        if self.n_group != 1 or self.topk_group != 1:
            if not (routed and self.n_group >= 1
                    and self.num_experts % self.n_group == 0
                    and self.num_experts // self.n_group >= 2
                    and 1 <= self.topk_group <= self.n_group
                    and self.num_experts_per_tok <= self.topk_group
                    * (self.num_experts // self.n_group)):
                raise ValueError(
                    "a router limited to groups (n_group, topk_group) "
                    "scores by sigmoid, cuts num_experts into n_group "
                    "groups of at least 2 and keeps topk_group of them, "
                    "which hold a token's num_experts_per_tok")
        if self.attn_gate not in (False, True, "head") or (
                (self.attn_gate == "head") != bool(
                    self.attn_gate and self.kv_lora_rank)):
            raise ValueError(
                "attn_gate is false, true (per-head attention: a gate a "
                "value) or \"head\" (latent attention: a gate a head)")
        if self.kv_lora_rank:
            if not (self.qk_nope_head_dim
                    and self.qk_rope_head_dim and self.v_head_dim):
                raise ValueError(
                    "latent attention (kv_lora_rank) needs "
                    "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
            if (self.head_dim != self.qk_nope_head_dim
                    + self.qk_rope_head_dim or self.num_kv_heads != 1):
                raise ValueError(
                    "latent attention: head_dim is qk_nope_head_dim + "
                    "qk_rope_head_dim and num_kv_heads is 1 (one latent "
                    "row a token serves every head)")
            if (self.sliding_window or self.rope_layers or self.qk_norm
                    or self.attn_bias):
                raise ValueError(
                    "latent attention takes no window, rope pattern, q/k "
                    "norm or bias")
        elif self.rope_interleave:
            raise ValueError("rope_interleave is the latent rotary part's")
        if self.index_topk:
            if not (self.kv_lora_rank and self.q_lora_rank
                    and self.index_n_heads > 0
                    and self.index_head_dim >= self.qk_rope_head_dim):
                raise ValueError(
                    "learned sparse attention (index_topk) needs latent "
                    "attention (kv_lora_rank) with a query latent "
                    "(q_lora_rank), index_n_heads and an index_head_dim "
                    "that holds the rotary part")
            if not self.layer_index[0]:
                raise ValueError(
                    "index_layers: layer 0 has no full layer below it "
                    "whose selection it could attend")
        elif (self.index_n_heads or self.index_head_dim
              or self.index_layers or self.index_rope_interleave):
            raise ValueError("index_n_heads, index_head_dim, index_layers "
                             "and index_rope_interleave are the indexer's "
                             "(index_topk)")
        if self.hc_mult < 0 or self.hc_mult == 1:
            raise ValueError("hc_mult is 0 (the plain residual path) or "
                             "the number of streams, at least 2")
        if self.hc_mult and self.router_input == "block_input":
            raise ValueError("hyper-connections (hc_mult): a block's input "
                             "is hc_mult streams, which a router of "
                             "router_input 'block_input' cannot read")
        if self.hc_mult and not (self.hc_sinkhorn_iters >= 1
                                 and self.hc_eps > 0
                                 and self.hc_res_clamp > 0):
            raise ValueError("hyper-connections (hc_mult) need "
                             "hc_sinkhorn_iters >= 1 and positive hc_eps "
                             "and hc_res_clamp")
        if self.full_attention_interval:
            n = self.full_attention_interval
            sizes = (self.linear_num_key_heads, self.linear_num_value_heads,
                     self.linear_key_head_dim, self.linear_value_head_dim)
            if n < 2 or self.num_layers < n:
                raise ValueError(
                    "full_attention_interval is 0 (every layer attends) or "
                    "a period of at least 2 that num_layers holds once")
            if (min(sizes) <= 0 or self.linear_conv_kernel_dim < 2
                    or self.linear_num_value_heads
                    % self.linear_num_key_heads):
                raise ValueError(
                    "recurrent layers (full_attention_interval) need the "
                    "five linear_* sizes, a convolution of at least 2 taps "
                    "and value heads a multiple of the key heads")
            if not -1 <= self.full_attention_place < n:
                raise ValueError(
                    "full_attention_place is the attention layer's index "
                    "in its period, or -1 for the last")
            if (self.index_topk or self.hc_mult
                    or self.sliding_window
                    or len(set(self.rope_layers)) > 1
                    or self.norm != "rmsnorm" or self.attn_bias
                    or self.router_input != "mlp_norm"
                    or (self.num_experts and self.moe_impl != "dropless")):
                raise ValueError(
                    "recurrent layers (full_attention_interval) run beside "
                    "per-head or latent attention: no learned sparse "
                    "attention, hyper-connections, "
                    "window or rope pattern of two kinds, layernorm1p, "
                    "attention bias, block_input router or capacity routing")
            if self.linear_decay not in ("head", "channel", "ssd") or (
                    self.linear_decay == "channel" and not (
                        self.linear_decay_floor < 0
                        and self.linear_num_key_heads
                        == self.linear_num_value_heads)) or (
                    self.linear_decay != "channel"
                    and self.linear_decay_floor):
                raise ValueError(
                    "linear_decay is \"head\", \"channel\" or \"ssd\"; a "
                    "decay a "
                    "channel has a negative linear_decay_floor (its "
                    "log-decay's lower bound) and as many key as value "
                    "heads")
        elif (self.linear_num_key_heads or self.linear_num_value_heads
              or self.linear_key_head_dim or self.linear_value_head_dim
              or self.linear_conv_kernel_dim or self.linear_decay != "head"
              or self.linear_decay_floor or self.full_attention_place != -1):
            raise ValueError("the linear_* keys are the recurrent layers' "
                             "(full_attention_interval)")
        if not 0.0 < self.partial_rotary_factor <= 1.0 or (
                self.partial_rotary_factor != 1.0 and (
                    self.kv_lora_rank
                    or int(self.head_dim * self.partial_rotary_factor) % 2)):
            raise ValueError(
                "partial_rotary_factor is in (0, 1], turns an even number "
                "of a per-head attention's values and is not the latent "
                "rotary part's (qk_rope_head_dim)")
        if self.shared_expert_gate and not self.num_shared_experts:
            raise ValueError("shared_expert_gate needs a shared expert "
                             "(num_shared_experts)")
        if self.experts_held or self.experts_first:
            if not (self.num_experts and self.moe_impl == "dropless"):
                raise ValueError("an expert share (experts_held) needs "
                                 "experts under moe_impl 'dropless'")
            if not (0 < self.experts_held and 0 <= self.experts_first
                    and self.experts_first + self.experts_held
                    <= self.num_experts):
                raise ValueError(
                    f"expert share [{self.experts_first}, "
                    f"{self.experts_first + self.experts_held}) lies "
                    f"outside the layer's {self.num_experts} experts")

    def layer_pattern(self, pattern: tuple, default: int) -> tuple:
        """A 0/1 period repeated over the depth (``default`` where the
        pattern is empty)."""
        if not pattern:
            return (default,) * self.num_layers
        return tuple(pattern[i % len(pattern)]
                     for i in range(self.num_layers))

    @property
    def layer_windows(self) -> tuple:
        """Per layer, the window it attends in tokens (0 = whole
        context)."""
        if not self.sliding_window:
            return (0,) * self.num_layers
        return tuple(self.sliding_window * f
                     for f in self.layer_pattern(self.window_layers, 0))

    @property
    def layer_rope(self) -> tuple:
        return self.layer_pattern(self.rope_layers, 1)

    @property
    def layer_index(self) -> tuple:
        """Per layer, 1 where it has the indexer ("full"), 0 where it
        attends the selection of the nearest full layer below."""
        return self.layer_pattern(self.index_layers, 1)

    @property
    def recurrent(self) -> bool:
        """Some layers' token mixer is the recurrence: a sequence holds
        a state a slot beside its pages (``full_attention_interval``)."""
        return self.full_attention_interval > 0

    @property
    def layer_full(self) -> tuple:
        """Per layer, 1 where its token mixer is attention, 0 where it is
        the recurrence (``full_attention_interval``)."""
        n = self.full_attention_interval
        at = self.full_attention_place % n if n else 0
        return tuple(int(not n or i % n == at)
                     for i in range(self.num_layers))

    def full_before(self, li):
        """The attention layers below layer ``li`` (an int, or a traced
        index): an attention layer's place among its kind, and what a
        recurrent layer's index less it is ITS place among its kind."""
        n = self.full_attention_interval
        shift = n - 1 - self.full_attention_place % n
        return (li + shift if shift else li) // n

    @property
    def recurrent_scope(self) -> str:
        """What a recurrent layer's leaves (``gdn_*`` / ``kda_*`` /
        ``ssd_*``) and stage names start with: the member of the family
        it runs (``linear_decay``) — the one place it is chosen."""
        return {"head": "gdn", "channel": "kda", "ssd": "ssd"}[
            self.linear_decay]

    @property
    def rotary_dim(self) -> int:
        """The values of an attention head the rotary embedding turns."""
        return self.qk_rope_head_dim or int(
            self.head_dim * self.partial_rotary_factor)

    @property
    def linear_channels(self) -> int:
        """The channels a recurrent layer's convolution runs over: q, k
        and v of every head (a state-space layer's x of every head and a
        B and a C a group: the same count)."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def layer_stacks(self) -> tuple:
        """The model's layer stacks in the order they run: ``(name in the
        parameter tree, index of its first layer, layers)``. One stack,
        ``layers``, but for an expert model with leading dense layers."""
        n = self.num_dense_layers
        if not n:
            return (("layers", 0, self.num_layers),)
        return (("dense_layers", 0, n), ("layers", n, self.num_layers - n))

    @property
    def held_experts(self) -> int:
        """The expert matrices a layer's tree holds: all of them, or its
        share."""
        return self.experts_held or self.num_experts

    @property
    def score_scale(self) -> float:
        """What the attention scores are multiplied by: head_dim ** -0.5
        (or the ``attention_multiplier`` the configuration states), times
        YaRN's m ** 2 where the configuration has it."""
        m = 1.0
        if self.rope_mscale_all_dim and self.rope_scaling_factor > 1:
            m = 0.1 * self.rope_mscale_all_dim * math.log(
                self.rope_scaling_factor) + 1.0
        return (self.attention_multiplier or self.head_dim ** -0.5) * m * m

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


@dataclass(frozen=True)
class EncoderConfig:
    """BERT-style bidirectional encoder (e5-large-v2 geometry)."""
    vocab_size: int = 30522
    hidden_size: int = 1024
    intermediate_size: int = 4096
    num_layers: int = 24
    num_heads: int = 16
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12


# ---------------------------------------------------------------------------
# Named registry. Names mirror what the reference's chain server configures
# (reference: deploy/compose/config.yaml model_name entries).
# ---------------------------------------------------------------------------

LLAMA2_7B = LlamaConfig()
LLAMA2_13B = LlamaConfig(hidden_size=5120, intermediate_size=13824,
                         num_layers=40, num_heads=40, num_kv_heads=40)
LLAMA2_70B = LlamaConfig(hidden_size=8192, intermediate_size=28672,
                         num_layers=80, num_heads=64, num_kv_heads=8)
CODELLAMA_13B = replace(LLAMA2_13B, vocab_size=32016, rope_theta=1_000_000.0,
                        max_position_embeddings=16384)
MIXTRAL_8X7B = LlamaConfig(hidden_size=4096, intermediate_size=14336,
                           num_layers=32, num_heads=32, num_kv_heads=8,
                           rope_theta=1_000_000.0,
                           max_position_embeddings=32768,
                           num_experts=8, num_experts_per_tok=2)

# A 21B-total / 3B-active sparse model (PowerInfer SmallThinker-21BA3B-
# Instruct config.json): 64 narrow ReLU-gated experts, 6 a token, routed
# from the block's input; one global layer without rotary embedding then
# three 4096-token window layers with it, thirteen times over.
SMALLTHINKER_21B_A3B = LlamaConfig(
    vocab_size=151936, hidden_size=2560, intermediate_size=768,
    num_layers=52, num_heads=28, num_kv_heads=4, head_dim=128,
    max_position_embeddings=16384, rope_theta=1_500_000.0,
    rms_norm_eps=1e-6, num_experts=64, num_experts_per_tok=6,
    moe_impl="dropless", mlp="relu_glu", router_input="block_input",
    sliding_window=4096, window_layers=(0, 1, 1, 1),
    rope_layers=(0, 1, 1, 1), weight_init="unit_stream")

# A 26B-total / 3B-active sparse model (arcee-ai Trinity-Mini config.json,
# model_type afmoe): two dense layers then thirty of 128 narrow experts,
# 8 a token by sigmoid scores with a selection bias, beside one shared
# expert; gated attention with q/k norms and four norms a block; three
# 2048-token window layers with rotary embedding then one global layer
# without, eight times over; the embedding times sqrt(hidden).
TRINITY_MINI = LlamaConfig(
    vocab_size=200192, hidden_size=2048, intermediate_size=6144,
    moe_intermediate_size=1024, num_layers=32, num_dense_layers=2,
    num_heads=32, num_kv_heads=4, head_dim=128,
    max_position_embeddings=131072, rope_theta=10000.0, rms_norm_eps=1e-5,
    num_experts=128, num_experts_per_tok=8, num_shared_experts=1,
    moe_impl="dropless", router_score_func="sigmoid",
    router_norm_topk=True, router_scale=2.826, router_bias="selection",
    sliding_window=2048, window_layers=(1, 1, 1, 0),
    rope_layers=(1, 1, 1, 0), qk_norm=True, attn_gate=True,
    post_norms=True, embed_scale=2048 ** 0.5, weight_init="unit_stream")

# A 1.04T-total / 32B-active sparse model (moonshotai Kimi-K2-Instruct
# config.json, model_type kimi_k2; the equations are DeepseekV3's): one
# dense layer then sixty of 384 SwiGLU experts, 8 a token by sigmoid
# scores with a selection bias, beside one shared expert; latent
# attention, 64 heads over one 512 + 64 wide cached row a token; YaRN
# over the 64-wide rotary part. Whole here (a share of 1 of 1): a chip
# of a deployment holds a share of the experts and of the layers
# (``experts_held``; benchmarks/configs/kimi-k2-instruct.json).
KIMI_K2 = LlamaConfig(
    vocab_size=163840, hidden_size=7168, intermediate_size=18432,
    moe_intermediate_size=2048, num_layers=61, num_dense_layers=1,
    num_heads=64, num_kv_heads=1, head_dim=192,
    max_position_embeddings=131072, rope_theta=50000.0, rms_norm_eps=1e-6,
    num_experts=384, num_experts_per_tok=8, num_shared_experts=1,
    moe_impl="dropless", router_score_func="sigmoid",
    router_norm_topk=True, router_scale=2.827, router_bias="selection",
    kv_lora_rank=512, q_lora_rank=1536, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True,
    rope_scaling_type="yarn", rope_scaling_factor=32.0,
    rope_original_max=4096, rope_beta_fast=1.0, rope_beta_slow=1.0,
    rope_mscale_all_dim=1.0, weight_init="unit_stream")

# A ~750B-total / ~40B-active sparse model (zai-org GLM-5.2 config.json,
# model_type glm_moe_dsa): three dense layers then seventy-five of 256
# SwiGLU experts, 8 a token by sigmoid scores with a selection bias,
# beside one shared expert; latent attention, 64 heads of 192 + 64 over
# one 512 + 64 wide cached row a token, values 256 wide, plain rotary
# frequencies; and learned sparse attention: an indexer of 32 heads x 128
# on layers 0, 1, 2 and every fourth from 6 on chooses the 2048 cached
# tokens a query attends, the layers between attend the set of the full
# layer below. The multi-token-prediction module is not built.
GLM_5_2 = LlamaConfig(
    vocab_size=154880, hidden_size=6144, intermediate_size=12288,
    moe_intermediate_size=2048, num_layers=78, num_dense_layers=3,
    num_heads=64, num_kv_heads=1, head_dim=256,
    max_position_embeddings=1048576, rope_theta=8_000_000.0,
    rms_norm_eps=1e-5, num_experts=256, num_experts_per_tok=8,
    num_shared_experts=1, moe_impl="dropless", router_score_func="sigmoid",
    router_norm_topk=True, router_scale=2.5, router_bias="selection",
    kv_lora_rank=512, q_lora_rank=2048, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, rope_interleave=True,
    index_topk=2048, index_n_heads=32, index_head_dim=128,
    index_layers=tuple(int(i < 3 or (i - 2) % 4 == 0) for i in range(78)),
    index_rope_interleave=True, weight_init="unit_stream")

# A 29B-total / 4B-active sparse model (XingChen-AGI Xing4.0-29B-A4B
# config.json, model_type xing4_0): the DeepseekV3 block — two dense
# layers then thirty-eight of 64 SwiGLU experts, 4 a token by sigmoid
# scores with a selection bias, beside one shared expert; latent
# attention, 32 heads of 128 + 64 over one 512 + 64 wide cached row a
# token; YaRN factor 64 — on a residual path of four streams mixed by
# manifold-constrained hyper-connections (ops/hyper_connection.py). The
# multi-token-prediction module is not built.
XING4_0_29B_A4B = LlamaConfig(
    vocab_size=131072, hidden_size=3584, intermediate_size=9216,
    moe_intermediate_size=1024, num_layers=40, num_dense_layers=2,
    num_heads=32, num_kv_heads=1, head_dim=192,
    max_position_embeddings=262144, rope_theta=10000.0, rms_norm_eps=1e-6,
    num_experts=64, num_experts_per_tok=4, num_shared_experts=1,
    moe_impl="dropless", router_score_func="sigmoid",
    router_norm_topk=True, router_scale=2.0, router_bias="selection",
    kv_lora_rank=512, q_lora_rank=768, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True,
    rope_scaling_type="yarn", rope_scaling_factor=64.0,
    rope_original_max=4096, rope_beta_fast=32.0, rope_beta_slow=1.0,
    rope_mscale_all_dim=1.0, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    hc_res_clamp=30.0, weight_init="unit_stream_thin_experts")

# GPT-Next / Nemotron-8B (the reference's second served family:
# ensemble_models/gptnext/, docs/rag/support_matrix.md:14 sizing;
# nemotron_config.yaml deployment). Rotary attention, zero-centered
# LayerNorm, squared-ReLU non-gated MLP, untied embeddings, 256k
# SentencePiece vocab.
# An 80B-total / 3B-active hybrid (Qwen/Qwen3-Next-80B-A3B-Instruct
# config.json, model_type qwen3_next): 48 layers in periods of three
# gated delta-rule layers (16 key / 32 value heads of 128, a 4-tap
# convolution) and one gated attention layer (16 query / 2 KV heads of
# 256, a quarter of each head rotated, q/k norm); every layer 512 SwiGLU
# experts of width 512, 10 a token by softmax, beside one shared expert
# under a sigmoid gate of its own. The multi-token-prediction module is
# not built.
QWEN3_NEXT_80B_A3B = LlamaConfig(
    vocab_size=151936, hidden_size=2048, intermediate_size=5120,
    moe_intermediate_size=512, num_layers=48, num_heads=16, num_kv_heads=2,
    head_dim=256, max_position_embeddings=262144, rope_theta=1e7,
    rms_norm_eps=1e-6, num_experts=512, num_experts_per_tok=10,
    num_shared_experts=1, shared_expert_gate=True, moe_impl="dropless",
    qk_norm=True, attn_gate=True, partial_rotary_factor=0.25,
    full_attention_interval=4, linear_num_key_heads=16,
    linear_num_value_heads=32, linear_key_head_dim=128,
    linear_value_head_dim=128, linear_conv_kernel_dim=4,
    weight_init="unit_stream")

# A ~125B-total / ~5.5B-active hybrid (inclusionAI Ling-3.0-flash
# config.json, model_type bailing_hybrid): 42 layers in groups of five
# Kimi-Delta-Attention layers (32 heads of 128 x 128, a log-decay a
# CHANNEL of a head's keys bounded below by -5, a 4-tap convolution) and
# one latent attention layer (32 heads of 128 + 64 over one 512 + 64
# wide cached row a token, ONE query matrix, a sigmoid gate a head); two
# leading dense layers (KDA mixers, a dense MLP), then 512 SwiGLU experts
# of width 768, 8 a token by sigmoid scores with a selection bias,
# chosen within 4 of 8 groups, beside one shared expert. The
# multi-token-prediction module and the activation limits of layers
# 34-41 are not built (models/import_hf.py ``bailing_hybrid_config``
# refuses the latter by name).
LING_3_0_FLASH = LlamaConfig(
    vocab_size=157184, hidden_size=2560, intermediate_size=6144,
    moe_intermediate_size=768, num_layers=42, num_dense_layers=2,
    num_heads=32, num_kv_heads=1, head_dim=192,
    max_position_embeddings=262144, rope_theta=6e6, rms_norm_eps=1e-6,
    num_experts=512, num_experts_per_tok=8, num_shared_experts=1,
    moe_impl="dropless", router_score_func="sigmoid",
    router_norm_topk=True, router_scale=2.5, router_bias="selection",
    n_group=8, topk_group=4, kv_lora_rank=512, q_lora_rank=0,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_interleave=True, attn_gate="head", full_attention_interval=6,
    linear_num_key_heads=32, linear_num_value_heads=32,
    linear_key_head_dim=128, linear_value_head_dim=128,
    linear_conv_kernel_dim=4, linear_decay="channel",
    linear_decay_floor=-5.0, weight_init="unit_stream")

# A 3B dense hybrid (ibm-granite granite-4.0-h-micro config.json,
# model_type granitemoehybrid): 40 layers in four periods of [five
# Mamba-2 layers, ONE attention layer, four Mamba-2 layers]; a Mamba-2
# layer keeps 64 heads of a 64 x 128 state, one B / C group, a 4-tap
# convolution with bias; the attention layers are GQA 32 / 8 of 64
# WITHOUT rotary embedding and score by 1/64; every block's FFN is the
# dense "shared" SwiGLU of width 8192 (no routed experts); the stream
# takes a sub-block's output times 0.22, the embedding times 12, and the
# logits over the tied head are divided by 8.
GRANITE_4_0_H_MICRO = LlamaConfig(
    vocab_size=100352, hidden_size=2048, intermediate_size=8192,
    num_layers=40, num_heads=32, num_kv_heads=8, head_dim=64,
    max_position_embeddings=131072, rope_theta=10000.0, rms_norm_eps=1e-5,
    tie_word_embeddings=True, rope_layers=(0,), embed_scale=12.0,
    residual_multiplier=0.22, attention_multiplier=0.015625,
    logits_divisor=8.0, full_attention_interval=10,
    full_attention_place=5, linear_num_key_heads=1,
    linear_num_value_heads=64, linear_key_head_dim=128,
    linear_value_head_dim=64, linear_conv_kernel_dim=4,
    linear_decay="ssd", weight_init="unit_stream")

NEMOTRON_8B = LlamaConfig(vocab_size=256000, hidden_size=4096,
                          intermediate_size=16384, num_layers=32,
                          num_heads=32, num_kv_heads=32, head_dim=128,
                          max_position_embeddings=4096,
                          norm="layernorm1p", mlp="squared_relu",
                          attn_bias=False, mlp_bias=False)
GPTNEXT_TINY = LlamaConfig(vocab_size=512, hidden_size=128,
                           intermediate_size=256, num_layers=2,
                           num_heads=4, num_kv_heads=4, head_dim=32,
                           max_position_embeddings=512,
                           norm="layernorm1p", mlp="squared_relu")

# Small geometries for tests/benchmarks on limited hardware.
LLAMA_TINY = LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=352,
                         num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
                         max_position_embeddings=512)
# The golden-tiny geometry: real 32k-vocab tokenizer + TRAINED weights
# (tools/make_golden_checkpoint.py trains it on the repo docs; the
# committed checkpoint under tests/fixtures/golden_tiny/ is the CI gate
# for real-vocab detokenization and quantization quality — the coverage
# random-init weights structurally cannot give).
GOLDEN_TINY = LlamaConfig(vocab_size=32000, hidden_size=64,
                          intermediate_size=176, num_layers=2,
                          num_heads=4, num_kv_heads=2, head_dim=16,
                          max_position_embeddings=512,
                          tie_word_embeddings=False)
LLAMA_1B = LlamaConfig(vocab_size=32000, hidden_size=2048,
                       intermediate_size=5632, num_layers=22,
                       num_heads=32, num_kv_heads=4, head_dim=64)

E5_LARGE_V2 = EncoderConfig()
ENCODER_TINY = EncoderConfig(vocab_size=512, hidden_size=64,
                             intermediate_size=128, num_layers=2, num_heads=4,
                             max_position_embeddings=128)

MODEL_REGISTRY: dict[str, LlamaConfig] = {
    "llama-2-7b-chat": LLAMA2_7B,
    "llama-2-13b-chat": LLAMA2_13B,
    "llama-2-70b-chat": LLAMA2_70B,
    "codellama-13b-instruct": CODELLAMA_13B,
    "mixtral-8x7b-instruct": MIXTRAL_8X7B,
    "nemotron-8b-chat": NEMOTRON_8B,
    "smallthinker-21b-a3b-instruct": SMALLTHINKER_21B_A3B,
    "trinity-mini": TRINITY_MINI,
    "kimi-k2-instruct": KIMI_K2,
    "glm-5.2": GLM_5_2,
    "xing4.0-29b-a4b": XING4_0_29B_A4B,
    "qwen3-next-80b-a3b-instruct": QWEN3_NEXT_80B_A3B,
    "ling-3.0-flash": LING_3_0_FLASH,
    "granite-4.0-h-micro": GRANITE_4_0_H_MICRO,
    "gptnext-tiny": GPTNEXT_TINY,
    "llama-tiny": LLAMA_TINY,
    "golden-tiny": GOLDEN_TINY,
    "llama-1b": LLAMA_1B,
}

ENCODER_REGISTRY: dict[str, EncoderConfig] = {
    "intfloat/e5-large-v2": E5_LARGE_V2,
    "e5-large-v2": E5_LARGE_V2,
    "encoder-tiny": ENCODER_TINY,
}


def get_model_config(name: str) -> LlamaConfig:
    try:
        return MODEL_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown model {name!r}; known: {sorted(MODEL_REGISTRY)}") from None
