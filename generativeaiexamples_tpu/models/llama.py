"""Llama-family decoder in functional JAX.

The TPU-native replacement for the reference's TRT-LLM engine build
(reference: llm-inference-server/conversion_scripts/llama/build.py) — instead
of building per-rank TensorRT engines, the model is a pure function of a
parameter pytree, jit-compiled by XLA and sharded with NamedSharding.

Design choices (TPU-first, not a port):
- **Stacked layer params + ``lax.scan``**: every per-layer tensor is stacked
  along a leading L axis and the decoder scans over layers. One layer gets
  traced/compiled, not 32/40/80 — compile time stays flat with depth, and
  sharding rules are written once per leaf. ONE function runs that scan
  (``_run_stack``) and one object reads and writes the cache
  (models/kv_cache.py: per-head K and V, or a latent row a token); the
  forwards (``apply*``, ``run_layers``) hand them only what differs.
- **Absolute-position KV cache**: cache index == token position. Prefill and
  decode are the same function with different (tokens, positions) shapes; no
  dynamic shapes ever reach XLA.
- **GQA without KV duplication**: grouped einsum in ``ops.attention`` instead
  of materializing duplicated KV heads (the reference duplicates weights when
  tp > n_kv_heads, conversion_scripts/llama/weight.py:150-157).
- **MoE branch** (Mixtral): dense-compute router mixing here; the
  expert-parallel shard_map path lives in ``parallel/``.

Param tree (all projections stored input-major so forward is ``x @ W``):
  embed:       (V, D)
  layers:
    attn_norm: (L, D)         mlp_norm: (L, D)
    wq: (L, D, H*hd)  wk: (L, D, KV*hd)  wv: (L, D, KV*hd)  wo: (L, H*hd, D)
    w_gate/w_up: (L, D, F)    w_down: (L, F, D)          [dense MLP]
    router: (L, D, E)  w_gate/w_up: (L, E, D, F)  w_down: (L, E, F, D)  [MoE]
  final_norm:  (D,)
  lm_head:     (D, V)
Configured extras (models/configs.py; absent otherwise): ``wz`` (L, D,
H*hd) the attention gate; ``q_norm`` / ``k_norm`` (L, hd);
``post_attn_norm`` / ``post_mlp_norm`` (L, D); ``router_bias`` (L, E)
float32; a shared expert ``ws_gate`` / ``ws_up`` (L, D, Fs), ``ws_down``
(L, Fs, D). An expert model with leading dense layers holds them as a
second stack, ``dense_layers`` (the same tree with a dense MLP), beside
``layers``: ``cfg.layer_stacks`` names the stacks in the order they run.
A latent-attention layer (``kv_lora_rank``) has, in place of wq / wk /
wv: ``wq_a`` (L, D, Rq), ``q_a_norm`` (L, Rq), ``wq_b`` (L, Rq, H*hd),
``wkv_a`` (L, D, R + rope), ``kv_a_norm`` (L, R), ``wk_b`` (L, R,
H*nope), ``wv_b`` (L, R, H*v), and ``wo`` (L, H*v, D). Under an expert
share (``experts_held``) the expert stacks hold the held experts only;
the router keeps every column. A model with learned sparse attention
(``index_topk``) has, in each stack, the indexer of its Lf FULL layers,
stacked over those alone: ``index_wq`` (Lf, Rq, Hi*di), ``index_wk`` (Lf,
D, di), ``index_k_norm`` / ``index_k_norm_b`` (Lf, di), ``index_wp`` (Lf,
D, Hi); bf16, never quantized (the selection is what a precision step
would move). Where not every layer attends (``full_attention_interval``)
a stack holds its attention layers' leaves stacked over those alone
(``ATTENTION_WEIGHTS``: per-head attention's, or latent attention's with
``wq`` (Lf, D, H*hd) alone where ``q_lora_rank`` is 0 and ``wz_head``
(Lf, D, H) under ``attn_gate`` "head") and its recurrent layers' over
theirs: ``gdn_*`` (a decay a head) or ``kda_*`` (a decay a channel:
``kda_wqkv`` (Lg, D, channels), ``kda_wf`` (Lg, D, H*dk), ``kda_wg`` (Lg,
D, H*dv), ``kda_wb`` (Lg, D, H), ``kda_conv`` (Lg, channels, K),
``kda_A_log`` (Lg, H), ``kda_dt_bias`` (Lg, H*dk), ``kda_norm`` (Lg, dv),
``kda_wout`` (Lg, H*dv, D)).
"""

from __future__ import annotations

from typing import Any, Optional

import functools
import os

import jax
import jax.numpy as jnp

from ..ops import gated_delta as gd
from ..ops import hyper_connection as hc
from ..ops import ssd
from ..ops.quant import matmul as qmm
from ..ops.quant import matmul_f32 as qmm_f32
from ..ops.rmsnorm import layernorm1p, rmsnorm
from ..ops.rope import (apply_rope, apply_rope_partial, deinterleave,
                        rope_frequencies, yarn_frequencies)
from .configs import LlamaConfig
from .kv_cache import (KVCache, _paged_prefix_attention,  # noqa: F401
                       kv_cache_of)

#: Stage names inside every device program (``jax.named_scope``: HLO
#: metadata only — numerics, program count and jitted-function names are
#: untouched). The same names in the decode, verify, prefill and chunk
#: programs, so a profile splits a step by stage however the compiler
#: numbers its fusions: ``embed``; per layer ``attn_proj`` (norm, QKV,
#: rope, output projection), ``attn`` (paged kernel or gather path, and
#: the KV write), ``mlp`` (dense FFN) or ``moe_route`` + ``moe_experts``
#: (parallel/moe.py); ``tail`` (final norm, lm_head tile stream,
#: penalties, sampling) with ``tail_select`` inside it (top-k / top-p
#: candidate handling: where the sort is). A shared expert runs as
#: ``mlp/moe_shared`` (a dense FFN inside ``mlp``, under a name of its
#: own). docs/observability.md lists them; the benchmark's scope reader
#: keeps an equal tuple. Learned sparse attention adds ``INDEX_SCOPES``:
#: ``attn_index`` (the indexer's projections, beside ``attn_proj``; its
#: key's write; and, inside ``attn``, the scores over the cached index
#: keys) and ``attn_select`` (the exact top-k, inside ``attn``), both on
#: full layers only.
SCOPES = ("embed", "attn_proj", "attn", "mlp", "moe_route", "moe_experts",
          "tail", "tail_select")
INDEX_SCOPES = ("attn_index", "attn_select")
#: Hyper-connections (``cfg.hc_mult``) add two, OUTSIDE the stages above:
#: ``hc_pre`` (a sublayer's coefficients and its input, the mix of the
#: streams; and the model's first stream) and ``hc_post`` (the stream
#: written back; and the sum that ends it) — ops/hyper_connection.py.
HC_SCOPES = ("hc_pre", "hc_post")
#: A recurrent layer (``cfg.full_attention_interval``) runs, in place of
#: ``attn_proj`` and ``attn``: ``gdn_proj`` (its norm, the two
#: in-projections, the gated output norm and the output projection),
#: ``gdn_conv`` (the causal convolution and its tail), and the recurrence
#: as ``gdn_step`` (one token a row: the decode step) or ``gdn_scan``
#: (the chunked form: every other forward; the kernel
#: ``gated_delta_scan`` where it takes the shapes, else the XLA stages)
#: — ops/gated_delta.py; the state's write after a scan is
#: ``gdn_state``.
GDN_SCOPES = ("gdn_proj", "gdn_conv", "gdn_step", "gdn_scan", "gdn_state")
#: The same five stages where the recurrence decays a channel at its own
#: rate (``cfg.linear_decay`` "channel": ``_kda_mixer``; the decode
#: step's kernel is ``kda_delta_step``).
KDA_SCOPES = ("kda_proj", "kda_conv", "kda_step", "kda_scan", "kda_state")
#: The same five where the recurrent layer is a state-space layer
#: (``cfg.linear_decay`` "ssd": ``_ssd_mixer``, ops/ssd.py; the decode
#: step's kernel is ``ssd_step``, the chunked form plain XLA).
SSD_SCOPES = ("ssd_proj", "ssd_conv", "ssd_step", "ssd_scan", "ssd_state")


def _embed(params: "Params", tokens: jax.Array,
           scale: float = 1.0) -> jax.Array:
    """Embedding rows times the model's ``embed_scale``."""
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], tokens, axis=0)
        if scale != 1.0:
            h = (h.astype(jnp.float32) * scale).astype(h.dtype)
        return h


def use_paged_kernel(cfg: LlamaConfig, page: int) -> bool:
    """Whether the Pallas paged-attention decode kernel will be used for
    this config (the engine pins pool layouts accordingly): on TPU
    backends with kernel-supported geometry (lane-aligned
    head_dim/page), unless disabled via GENAI_TPU_PAGED_KERNEL=0. Other
    backends take the jnp gather path."""
    flag = os.environ.get("GENAI_TPU_PAGED_KERNEL", "auto")
    if flag == "0":
        return False
    ok = kv_cache_of(cfg).kernel_supported(page)
    if flag == "1":
        return ok
    return ok and jax.default_backend() == "tpu"


def use_prefix_kernel(cfg: LlamaConfig, page: int) -> bool:
    """Whether a chunk program's attention runs the Pallas kernel of
    ops/chunk_attention.py: where the decode kernel runs
    (``use_paged_kernel``) and the cache object has a chunk kernel for
    this geometry (``LatentKV.prefix_kernel_supported``; ``HeadKV`` has
    none)."""
    supported = getattr(kv_cache_of(cfg), "prefix_kernel_supported", None)
    return (supported is not None and supported(page)
            and use_paged_kernel(cfg, page))

Params = dict[str, Any]


def _inv_freq(cfg: LlamaConfig) -> jax.Array:
    """The rotary part's inverse frequencies as the configuration scales
    them: over the whole head, or a latent layer's ``qk_rope_head_dim``."""
    dim = cfg.rotary_dim
    if cfg.rope_scaling_type == "yarn":
        return yarn_frequencies(dim, cfg.rope_theta, cfg.rope_scaling_factor,
                                cfg.rope_original_max, cfg.rope_beta_fast,
                                cfg.rope_beta_slow)
    return rope_frequencies(dim, cfg.rope_theta, cfg.rope_scaling_factor)


def init_params(cfg: LlamaConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Random-init parameter tree (for tests/benchmarks; real weights come
    from ``import_hf``)."""
    k = iter(jax.random.split(key, 16))
    D, F = cfg.hidden_size, cfg.intermediate_size
    H, KV, hd, V = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size

    def norm(rng, shape, fan_in):
        return (jax.random.normal(rng, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    # ``cfg.weight_init`` "unit_stream" (models/configs.py) draws a tree
    # with a trained model's stream, not only its shapes; "fan_in" keeps
    # the draw below bit for bit (a test pins two trees). (1) Embedding
    # entries of unit variance, not 1/sqrt(D): under rows of norm 1 the
    # first attention output outweighs the token's own row, every
    # position's stream is soon its context's running mean and all rows
    # choose the same few experts (PERF.md section 7 row 2: Mixtral's
    # router under "fan_in"). (2) The two projections that write to the
    # stream, ``wo`` and ``w_down``, scaled by 1/sqrt(2L) as GPT-2 and
    # Megatron initialise them: a block then adds a tenth of the stream,
    # not half of it. Without (2) one top-k near-tie that bf16 flips
    # against float32 moves a position's logits by 5-30 % (a third of
    # the positions of a 4608-token prompt lay over 0.05, chip, PR 28)
    # and greedy decoding falls into one repeated token in 2 requests of
    # 16; with it the share over 0.05 is 0-2 % and no request repeats.
    # (3) ``wq`` times 4, so that a query's scores have a standard
    # deviation of 4, not 1: its softmax rests on a handful of keys, as
    # a trained head's does. Under scores of deviation 1 a head averages
    # thousands of near-equal keys to almost nothing, and a program with
    # the wrong window, or rotary where none belongs, reads within the
    # sound error of a 4608-token prompt's logits; with (3) it reads
    # 4-18 times over it (chip, PR 28; PERF.md section 6).
    stream_draw = cfg.weight_init in ("unit_stream",
                                      "unit_stream_thin_experts")
    q_gain = 16 if stream_draw else 1
    resid = 2 * cfg.num_layers if stream_draw else 1
    # (5) What the configuration multiplies, the draw divides by, as the
    # norm weights of ``extras`` carry the gains their norms take out: a
    # block still adds a tenth of the stream under a residual multiplier
    # r (``wo``, ``w_down`` and a recurrent mixer's output projection
    # over r), and a query's scores still deviate by 4 under a stated
    # score scale (``wq`` over its ratio to head_dim ** -0.5) — so a
    # program that leaves either out differs at the logits by the factor.
    if stream_draw and cfg.residual_multiplier != 1.0:
        resid = resid * cfg.residual_multiplier ** 2
    if stream_draw and cfg.attention_multiplier and not cfg.kv_lora_rank:
        q_gain = q_gain / (cfg.attention_multiplier ** 2 * hd)
    # (4) "unit_stream_thin_experts": the ROUTED experts' ``w_down`` at
    # a fifth of (2), as ``post_mlp_norm`` below and for its reason.
    # Where a tree holds EVERY expert of a layer, each near-tie that
    # bf16 rounding flips between a token's last chosen sigmoid-scored
    # expert and the next shows in full (a share of 12 in 384 hides 31
    # of 32): at (2) alone the SOUND xing4.0-29b-a4b read 0.028 at the
    # median position and 0.30 of 272 positions over 0.05 of the float32
    # reference (chip, PR 42, seed 4242100001; kimi-k2-instruct's share
    # reads 0.011 / 0.01-0.02). The shared expert keeps (2); a fourth
    # chosen expert left out still reads 0.046, five times the sound
    # median (three seeds; its faults file, PERF.md section 6).
    routed = resid * (25 if cfg.weight_init == "unit_stream_thin_experts"
                      else 1)

    # layernorm1p stores weights centered at zero (applied as 1 + w)
    norm_w = jnp.zeros if cfg.norm == "layernorm1p" else jnp.ones
    (_, _, L), *leading = reversed(cfg.layer_stacks)
    Fe = cfg.expert_width

    def stack(k, L, experts, first=0):
        """One stack's tree (the model's layers ``first`` .. ``first +
        L``), its matrices drawn from ``k`` in an order the committed
        trees depend on. ``A``: its layers whose mixer is attention —
        all, but beside recurrent layers; a stack without one has no
        attention leaf."""
        layers: dict[str, jax.Array] = {
            "attn_norm": norm_w((L, D), dtype),
            "mlp_norm": norm_w((L, D), dtype),
        }
        A = sum(cfg.layer_full[first:first + L])
        if not A:
            pass
        elif cfg.kv_lora_rank:
            layers.update(latent(k, A))
        else:
            layers.update({
                "wq": norm(next(k), (A, D, H * hd), D / q_gain),
                "wk": norm(next(k), (A, D, KV * hd), D),
                "wv": norm(next(k), (A, D, KV * hd), D),
                "wo": norm(next(k), (A, H * hd, D), H * hd * resid),
            })
        if cfg.norm == "layernorm1p":
            layers["attn_norm_b"] = jnp.zeros((L, D), dtype)
            layers["mlp_norm_b"] = jnp.zeros((L, D), dtype)
        if cfg.attn_bias:
            layers["bq"] = jnp.zeros((L, H * hd), dtype)
            layers["bk"] = jnp.zeros((L, KV * hd), dtype)
            layers["bv"] = jnp.zeros((L, KV * hd), dtype)
            layers["bo"] = jnp.zeros((L, D), dtype)
        if experts:
            # the router's columns are the layer's experts; the matrices
            # are those this tree holds (all of them, or its share)
            E, Eh = cfg.num_experts, cfg.held_experts
            layers.update({
                "router": norm(next(k), (L, D, E), D),
                "w_gate": norm(next(k), (L, Eh, D, Fe), D),
                "w_up": norm(next(k), (L, Eh, D, Fe), D),
                "w_down": norm(next(k), (L, Eh, Fe, D), Fe * routed),
            })
        elif cfg.mlp == "squared_relu":
            # GPT-Next MLP: no gate projection
            layers.update({
                "w_up": norm(next(k), (L, D, F), D),
                "w_down": norm(next(k), (L, F, D), F),
            })
            if cfg.mlp_bias:
                layers["b_up"] = jnp.zeros((L, F), dtype)
                layers["b_down"] = jnp.zeros((L, D), dtype)
        else:
            layers.update({
                "w_gate": norm(next(k), (L, D, F), D),
                "w_up": norm(next(k), (L, D, F), D),
                "w_down": norm(next(k), (L, F, D), F),
            })
        return layers

    def latent(k, L):
        """A latent-attention layer's five projections (four where the
        queries come through ONE matrix: ``q_lora_rank`` 0; ``wq`` then
        carries what ``wq_b`` does, over a fan-in of D). The two latent
        norms take the gain out of ``wq_a`` and the latent columns of
        ``wkv_a``, which are drawn at HALF the fan-in deviation so that a
        program without a norm differs by more than the norm weights'
        tenth (``extras``); the rotary key columns of ``wkv_a`` meet no
        norm and keep theirs. ``wq_b`` carries the reach: a query's
        scores deviate by 4 AFTER the configuration's score multiplier
        (m ** 2 under YaRN), so leaving the multiplier out shows."""
        R, Rq = cfg.kv_lora_rank, cfg.q_lora_rank
        nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
        m2 = cfg.score_scale * hd ** 0.5
        # Under a learned selection of keys (``index_topk``) a query's
        # scores deviate by 2.4, not 4: a random indexer's choice is
        # independent of what the heads attend, so where bf16 reorders
        # two near-ties at the ``index_topk``-th place (~3 members of a
        # 4608-token query's set a full layer; chip, PR 40) the swapped
        # key may be one a head rests on. At 4 that moved the sound
        # program's logits by 3.5-3.9 % of their scale at the MEDIAN
        # position (1.0 % with the selection off on both sides); at 2.4
        # by 1.4-1.7 %, while no selection, half of it, another layer's
        # set or an indexer without its pairs still move every position
        # by 10 % or more (PERF.md section 6).
        gain = q_gain * 0.36 if cfg.index_topk else q_gain
        half = jnp.where(jnp.arange(R + cfg.qk_rope_head_dim) < R, 0.5, 1.0)
        wkv_a = norm(next(k), (L, D, R + cfg.qk_rope_head_dim), D)
        queries = {
            "wq_a": norm(next(k), (L, D, Rq), 4 * D),
            "wq_b": norm(next(k), (L, Rq, H * hd), Rq * m2 * m2 / gain),
        } if Rq else {
            "wq": norm(next(k), (L, D, H * hd), D * m2 * m2 / gain)}
        return {
            **queries,
            "wkv_a": (wkv_a.astype(jnp.float32) * half).astype(dtype),
            "wk_b": norm(next(k), (L, R, H * nope), R),
            "wv_b": norm(next(k), (L, R, H * vd), R),
            "wo": norm(next(k), (L, H * vd, D), H * vd * resid),
        }

    def extras(k, layers):
        """The leaves the configured variants add to a stack's tree,
        from keys of their own (the draws above keep theirs), and the
        router again where they re-draw it. What a norm's weight is drawn
        around says what the norm does to the draw: a q/k norm takes the
        gain out of ``wq``, so ``q_norm`` carries it (a query's scores
        keep their deviation of 4); a post-norm rescales a sub-block's
        output to its weight, so it carries the 1/sqrt(2L) that ``wo``
        and ``w_down`` no longer can — and ``post_mlp_norm`` a fifth of
        that: bf16 rounding of the stream swaps the last of a token's
        sigmoid-scored experts for the next in about every second
        layer (the chosen weigh nearly alike, so the swap is a whole
        share), and under 1/sqrt(2L) every position's logits then read
        0.14 off the float32 reference's (chip, PR 32); at a fifth a
        swap moves a hundredth of the stream. Each weight a tenth off
        its centre, so that a program without the norm differs in more
        than scale.

        The router's columns get unequal REACH (0.4 to 2.5 times the
        fan-in deviation) and the selection bias is the one that evens
        the load: an expert of long reach scores high when chosen, one
        of short reach low, and ``router_bias`` lifts each to the same
        cut, as a bias trained for balance does. So the bias is large
        (deviation 0.1, where neighbours in rank lie 0.005 apart), the
        biased and un-biased scores of the chosen differ by a tenth,
        and a decode batch still reaches the experts a uniform draw
        would (a bias drawn alone at 0.1 made some experts everybody's:
        55 of the uniform 77 at 14 rows; chip, PR 32)."""
        def near(centre, shape):
            return (centre * (1.0 + 0.1 * jax.random.normal(
                next(k), shape, jnp.float32))).astype(dtype)

        L, experts = layers["attn_norm"].shape[0], "router" in layers
        A = layers["wo"].shape[0] if "wo" in layers else 0
        out: dict[str, jax.Array] = {}
        if cfg.kv_lora_rank and A:
            if cfg.q_lora_rank:
                out["q_a_norm"] = near(1.0, (A, cfg.q_lora_rank))
            out["kv_a_norm"] = near(1.0, (A, cfg.kv_lora_rank))
        if cfg.attn_gate == "head" and A:
            # a gate a head, its pre-activation of deviation 1 over the
            # normed stream: a program without it is off by half
            out["wz_head"] = norm(next(k), (A, D, H), D)
        elif cfg.attn_gate and A:
            out["wz"] = norm(next(k), (A, D, H * hd), D)
        if cfg.qk_norm and A:
            out["q_norm"] = near(q_gain ** 0.5, (A, hd))
            out["k_norm"] = near(1.0, (A, hd))
        if cfg.post_norms:
            for name, share in (("post_attn_norm", 1.0),
                                ("post_mlp_norm", 0.2)):
                out[name] = near(share * resid ** -0.5
                                 - (cfg.norm == "layernorm1p"), (L, D))
                if cfg.norm == "layernorm1p":
                    out[name + "_b"] = jnp.zeros((L, D), dtype)
        if experts and cfg.router_bias:
            E = cfg.num_experts
            reach = jnp.exp(jax.random.uniform(
                next(k), (L, E), jnp.float32, jnp.log(0.4), jnp.log(2.5)))
            # an expert's score where it enters a token's top k
            cut = jax.nn.sigmoid(reach * jax.scipy.special.ndtri(
                1.0 - cfg.num_experts_per_tok / E))
            out["router_bias"] = jnp.mean(cut, -1, keepdims=True) - cut
            out["router"] = (layers["router"].astype(jnp.float32)
                             * reach[:, None, :]).astype(dtype)
        if experts and cfg.num_shared_experts:
            Fs = cfg.num_shared_experts * Fe
            out.update({
                "ws_gate": norm(next(k), (L, D, Fs), D),
                "ws_up": norm(next(k), (L, D, Fs), D),
                "ws_down": norm(next(k), (L, Fs, D), Fs * resid),
            })
        return out

    def indexer(k, first, n):
        """The indexer of a stack's full layers (``cfg.layer_index``),
        stacked over those alone. Plain fan-in draws: a query's dot with
        a key (both of unit entries after the norms) deviates by
        sqrt(index_head_dim), half of them pass the relu, and the head
        weights are the normed input's projection, of either sign — so a
        token's score is a signed sum over the heads, the chosen set of a
        context longer than ``index_topk`` is no prefix, suffix or
        window of it, and dropping the rotation, the norm's bias (a
        tenth) or a head's weight moves who is chosen."""
        Lf = sum(cfg.layer_index[first:first + n])
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        if not Lf:
            return {}
        return {
            "index_wq": norm(next(k), (Lf, cfg.q_lora_rank, Hi * di),
                             cfg.q_lora_rank),
            "index_wk": norm(next(k), (Lf, D, di), D),
            "index_k_norm": (1.0 + 0.1 * jax.random.normal(
                next(k), (Lf, di), jnp.float32)).astype(dtype),
            "index_k_norm_b": (0.1 * jax.random.normal(
                next(k), (Lf, di), jnp.float32)).astype(dtype),
            "index_wp": norm(next(k), (Lf, D, Hi), D),
        }

    def hyper(k, L):
        """The mapping weights of a stack's two sublayers (``cfg.hc_mult``
        streams), drawn so that no mapping is near its trivial value —
        a program that loses part of the mechanism then differs at the
        logits: ``phi`` at the fan-in deviation, so a token's projections
        of its normed stream deviate by 1; ``alpha`` (1, 1, 0.3), each a
        tenth off: the input moves the logits of ``H_pre`` and ``H_post``
        by as much as their biases do and every entry of ``H_res`` by a
        third (a fresh model's 0.01 would leave the mappings static);
        ``b_pre`` of deviation 1 (``H_pre`` unequal over the streams),
        ``b_post`` of 0.5 around 0 (``H_post`` around its 1); ``b_res``
        1.5 on the diagonal, deviation 0.3 — a matrix that keeps three
        fifths of a stream and moves the rest, whose rows after ONE
        normalisation pair are off by a tenth and after the twenty the
        configuration states by under 1e-5 at every token (the floor is
        ``hc_eps``; a wider draw leaves single tokens at 1e-3)."""
        n, m = cfg.hc_mult, cfg.hc_mult * (cfg.hc_mult + 2)
        spread = jnp.where(jnp.arange(m) < n, 1.0,
                           jnp.where(jnp.arange(m) < 2 * n, 0.5, 0.3))
        out = {}
        for part in ("attn", "mlp"):
            bias = jax.random.normal(next(k), (L, m), jnp.float32) * spread
            out.update({
                f"hc_{part}_phi": norm(next(k), (L, n * D, m), n * D),
                f"hc_{part}_alpha": jnp.asarray([1.0, 1.0, 0.3]) * (
                    1.0 + 0.1 * jax.random.normal(next(k), (L, 3),
                                                  jnp.float32)),
                f"hc_{part}_b": bias.at[:, 2 * n:].add(
                    1.5 * jnp.eye(n, dtype=jnp.float32).reshape(-1)),
            })
        return out

    def recurrent(k, L):
        """The recurrent layers' leaves (``cfg.full_attention_interval``),
        stacked over those alone, drawn so that what a sequence carries
        MATTERS at the logits — a program that dropped the state or the
        convolution tail between two chunks, or let padding into them,
        must differ by more than rounding:

        - ``gdn_A_log`` / ``gdn_dt_bias``: a head's state halves in 16 to
          4096 tokens, log-uniform over the heads (``g = -exp(A_log)
          softplus(a + dt_bias)`` at ``a = 0`` is ln 2 over that; under
          the published initialiser's ``A ~ U(0, 16)`` a state forgets
          within a few tokens). ``A_log`` itself deviates by 0.3, so a
          program without it is off by a third;
        - ``gdn_wba``: the ``b`` columns at 1.2 times the fan-in
          deviation, so ``beta = sigmoid(b)`` spreads over (0.1, 0.9);
          the ``a`` columns at half of it: a token moves its own decay
          by a factor of e^0.5 either way;
        - ``gdn_conv``: every tap of deviation 0.5 — the three inputs
          behind a token weigh together more than its own;
        - ``gdn_norm``: the gated output norm's weight, applied as it is,
          a tenth off 1; ``gdn_wout`` writes to the stream, scaled as
          ``wo`` is."""
        Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        Ch, K = cfg.linear_channels, cfg.linear_conv_kernel_dim
        wba = norm(next(k), (L, D, 2 * Hv), D).astype(jnp.float32) \
            * jnp.where(jnp.arange(2 * Hv) < Hv, 1.2, 0.5)
        a_log = 0.3 * jax.random.normal(next(k), (L, Hv), jnp.float32)
        half_life = jnp.exp(jax.random.uniform(
            next(k), (L, Hv), jnp.float32, jnp.log(16.0), jnp.log(4096.0)))
        rate = jnp.log(2.0) / half_life / jnp.exp(a_log)
        return {
            "gdn_wqkvz": norm(next(k), (L, D, Ch + Hv * dv), D),
            "gdn_wba": wba.astype(dtype),
            "gdn_conv": (0.5 * jax.random.normal(
                next(k), (L, Ch, K), jnp.float32)).astype(dtype),
            "gdn_A_log": a_log,
            "gdn_dt_bias": jnp.log(jnp.expm1(rate)),    # softplus^-1
            "gdn_norm": (1.0 + 0.1 * jax.random.normal(
                next(k), (L, dv), jnp.float32)).astype(dtype),
            "gdn_wout": norm(next(k), (L, Hv * dv, D), Hv * dv * resid),
        }

    def kda(k, L):
        """The leaves of ``L`` layers whose recurrence decays a CHANNEL
        at its own rate (``cfg.linear_decay`` "channel"), drawn as
        ``recurrent`` draws and for its reasons, with one more: the
        decays must differ ACROSS the channels of one head, or a program
        that averaged a head's decay into one scalar would pass.

        - ``kda_A_log`` (a head) / ``kda_dt_bias`` (a channel): at ``a =
          0`` a channel's state halves in 16 to 4096 tokens, log-uniform
          over the CHANNELS (``g = floor sigmoid(exp(A_log) dt_bias)``
          is ln 2 over that); ``A_log`` deviates by 0.3;
        - ``kda_wf``: the decay's projection at half the fan-in
          deviation, ``kda_wb`` at 1.2 times it (``beta`` over (0.1,
          0.9)); both act through float32;
        - ``kda_wg``: the output gate ``sigmoid(x W_g)``, a value a
          value, pre-activations of deviation 1;
        - ``kda_conv``, ``kda_norm``, ``kda_wout``: as ``gdn_*``."""
        Hv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
        Ch, K = cfg.linear_channels, cfg.linear_conv_kernel_dim
        a_log = 0.3 * jax.random.normal(next(k), (L, Hv), jnp.float32)
        half_life = jnp.exp(jax.random.uniform(
            next(k), (L, Hv, dk), jnp.float32, jnp.log(16.0),
            jnp.log(4096.0)))
        share = jnp.log(2.0) / half_life / -cfg.linear_decay_floor
        return {
            "kda_wqkv": norm(next(k), (L, D, Ch), D),
            "kda_wf": norm(next(k), (L, D, Hv * dk), 4 * D),
            "kda_wg": norm(next(k), (L, D, Hv * dv), D),
            "kda_wb": norm(next(k), (L, D, Hv), D / 1.44),
            "kda_conv": (0.5 * jax.random.normal(
                next(k), (L, Ch, K), jnp.float32)).astype(dtype),
            "kda_A_log": a_log,
            # sigmoid^-1 of the decay's share of the floor, over exp(A_log)
            "kda_dt_bias": (jnp.log(share / (1.0 - share))
                            / jnp.exp(a_log)[..., None]).reshape(L, Hv * dk),
            "kda_norm": (1.0 + 0.1 * jax.random.normal(
                next(k), (L, dv), jnp.float32)).astype(dtype),
            "kda_wout": norm(next(k), (L, Hv * dv, D), Hv * dv * resid),
        }

    def state_space(k, L):
        """The leaves of ``L`` state-space layers (``cfg.linear_decay``
        "ssd"), drawn as ``mamba_ssm`` initialises what it does not
        draw from the fan-in (assumed: the published checkpoint is not
        read) so that a program without the decay, the step's bias or
        the skip differs at the logits:

        - ``ssd_A_log``: ``A`` uniform in [1, 16]; ``ssd_dt_bias``: the
          inverse softplus of a step log-uniform in [1e-3, 1e-1] — a
          head's state halves in half a token to 700; ``ssd_D`` = 1;
        - ``ssd_win`` (z | x B C, the published order less ``dt``) at
          the fan-in deviation; ``ssd_wdt``, the step's own column a
          head, kept apart (it stays bf16 where the wide ones are
          int8, as ``gdn_wba`` does) at half of it: a token moves its
          own step by e^0.5 either way;
        - ``ssd_conv``: every tap of deviation 0.5, ``ssd_conv_b`` 0.3;
        - ``ssd_norm``: the gated norm's weight over the whole inner
          width, a tenth off 1; ``ssd_wout`` writes to the stream,
          scaled as ``wo`` is."""
        Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        Ch, K = cfg.linear_channels, cfg.linear_conv_kernel_dim
        step = jnp.exp(jax.random.uniform(
            next(k), (L, Hv), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "ssd_win": norm(next(k), (L, D, Hv * dv + Ch), D),
            "ssd_wdt": norm(next(k), (L, D, Hv), 4 * D),
            "ssd_conv": (0.5 * jax.random.normal(
                next(k), (L, Ch, K), jnp.float32)).astype(dtype),
            "ssd_conv_b": (0.3 * jax.random.normal(
                next(k), (L, Ch), jnp.float32)).astype(dtype),
            "ssd_A_log": jnp.log(jax.random.uniform(
                next(k), (L, Hv), jnp.float32, 1.0, 16.0)),
            "ssd_dt_bias": jnp.log(jnp.expm1(step)),    # softplus^-1
            "ssd_D": jnp.ones((L, Hv), jnp.float32),
            "ssd_norm": (1.0 + 0.1 * jax.random.normal(
                next(k), (L, Hv * dv), jnp.float32)).astype(dtype),
            "ssd_wout": norm(next(k), (L, Hv * dv, D), Hv * dv * resid),
        }

    kx = iter(jax.random.split(jax.random.fold_in(key, 1), 32))
    layers = stack(k, L, bool(cfg.num_experts), cfg.num_dense_layers)
    layers.update(extras(kx, layers))
    params: Params = {
        "embed": norm(next(k), (V, D),
                      cfg.embed_scale ** 2 if stream_draw else D),
        "layers": layers,
        "final_norm": norm_w((D,), dtype),
    }
    if cfg.norm == "layernorm1p":
        params["final_norm_b"] = jnp.zeros((D,), dtype)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm(next(k), (D, V), D)
    for name, first, n in leading:      # the leading dense stack
        params[name] = stack(kx, n, False, first)
        params[name].update(extras(kx, params[name]))
    if cfg.index_topk:
        ki = iter(jax.random.split(jax.random.fold_in(key, 2), 16))
        for name, first, n in cfg.layer_stacks:
            params[name].update(indexer(ki, first, n))
    if cfg.hc_mult:
        kh = iter(jax.random.split(jax.random.fold_in(key, 3), 16))
        for name, _, n in cfg.layer_stacks:
            params[name].update(hyper(kh, n))
    if cfg.recurrent:
        # a stack's recurrent layers, stacked over those alone (eight
        # keys a stack of a decay a head: the committed trees' draw)
        draw, fold, keys = {"head": (recurrent, 4, 8),
                            "channel": (kda, 6, 16),
                            "ssd": (state_space, 7, 16)}[cfg.linear_decay]
        kr = iter(jax.random.split(jax.random.fold_in(key, fold),
                                   keys * len(cfg.layer_stacks)))
        for name, first, n in cfg.layer_stacks:
            Lg = n - sum(cfg.layer_full[first:first + n])
            if Lg:
                params[name].update(draw(kr, Lg))
    if cfg.shared_expert_gate:
        # the shared expert's gate reads the normed stream (unit
        # entries): a pre-activation of deviation 1.5, so the gate lies
        # anywhere in (0.05, 0.95) and a program without it is off by
        # about half the shared expert
        layers["ws_gate_w"] = (1.5 * jax.random.normal(
            jax.random.fold_in(key, 5), (L, D), jnp.float32)
            * D ** -0.5).astype(dtype)
    return params


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int,
                  dtype: jnp.dtype = jnp.bfloat16) -> KVCache:
    """The dense absolute-position cache: ``{"k", "v"}: (L, B, T, KV,
    hd)``, or a latent model's ``{"c", "r"}`` (models/kv_cache.py)."""
    return kv_cache_of(cfg).init_dense(batch, max_len, dtype)


def init_paged_kv_cache(cfg: LlamaConfig, n_pages: int, page_size: int,
                        dtype: jnp.dtype = jnp.bfloat16,
                        quantized: bool = False,
                        slots: Optional[int] = None) -> KVCache:
    """Block-pool KV cache: {"k","v"}: (L, n_pages, KV, page, hd), or a
    latent model's two leaves; the configuration's cache object
    (models/kv_cache.py) builds it and is the one that reads and writes
    it.

    The pool is shared by all decode slots through per-slot block tables —
    the XLA-static equivalent of TRT-LLM's paged KV cache
    (reference: ensemble_models/llama/tensorrt_llm/config.pbtxt.j2:28-34).
    Page 0 is reserved as a trash page: writes for inactive slots and
    prefill-bucket overhang are routed there.

    Layout: KV heads ahead of the page dim so a page block arrives in VMEM
    as (KV, page, hd) — exactly the batched-matmul operand shape the Pallas
    decode kernel consumes, with (page, hd) on the tiled lanes and no
    in-kernel transpose.

    ``quantized``: int8 pools + bf16 per-row scale pools ``"ks"/"vs"``
    shaped (L, n_pages, KV, page) — half the HBM bytes per cached token
    (ops/kv_quant.py), the lever toward the reference's batch-128 class
    capacity (reference: config.pbtxt.j2:29).

    ``slots``: the sequences a model with recurrent layers keeps a state
    for beside the pages (``RecurrentKV``; default one). No other cache
    takes it.
    """
    extra = {} if slots is None else {"slots": slots}
    return kv_cache_of(cfg).init_pool(n_pages, page_size, dtype, quantized,
                                      **extra)


def kernel_tp_compatible(cfg: LlamaConfig, mesh) -> bool:
    """Whether the Pallas decode kernel can run under this mesh via
    shard_map: only the tp axis may shard attention state (heads divide
    cleanly); a pp axis would split the pool's layer dim out from under
    the kernel's layer indexing."""
    if mesh is None:
        return True
    tp = mesh.shape.get("tp", 1)
    if mesh.shape.get("pp", 1) != 1:
        return False
    return (cfg.num_kv_heads % tp == 0 and cfg.num_heads % tp == 0
            and (cfg.num_kv_heads // tp) > 0)


def layer_kinds(cfg: LlamaConfig, first: int = 0,
                n: Optional[int] = None) -> dict[str, jax.Array]:
    """The per-layer flags that ride the layer scan beside the stacked
    weights: ``window`` (n,) int32, the keys a layer attends (0 = its
    whole context), and ``rope`` (n,) bool, for the ``n`` layers from
    layer ``first`` (default: all). Empty for a model whose layers are
    all of one kind: its programs take no extra input."""
    last = cfg.num_layers if n is None else first + n
    kinds: dict[str, jax.Array] = {}
    if any(cfg.layer_windows):
        kinds["window"] = jnp.asarray(cfg.layer_windows[first:last],
                                      jnp.int32)
    if any(cfg.layer_rope) and not all(cfg.layer_rope):
        kinds["rope"] = jnp.asarray(cfg.layer_rope[first:last], bool)
    if cfg.index_topk:
        # a full layer's place among the MODEL's full layers (its layer
        # of the pool's index leaf), -1 on a shared layer; its place
        # among its stack's (its row of the stacked indexer weights) is
        # that less the full layers below the stack
        flags = cfg.layer_index
        kinds["index"] = jnp.asarray(
            [sum(flags[:i]) if flags[i] else -1 for i in range(first, last)],
            jnp.int32)
    return kinds


def layer_stat_names(cfg: LlamaConfig) -> tuple[str, ...]:
    """The scalars a layer reports under ``stats``: with dropless experts
    (parallel/moe.py) the distinct experts its rows reached among those
    it holds and, where it holds a share, the assignments that fell on
    them, the padded rows its walk of the layout gathered for them
    (``route_rows_read``) and, under a router limited to groups, the
    share of its rows whose kept groups include a held one; under
    hyper-connections ``hc_row_defect``, how far the rows of its two
    write-back matrices are from summing to 1
    (ops/hyper_connection.py ``row_defect``)."""
    return ("experts_touched",) + (
        ("local_assignments", "route_rows_read") if cfg.experts_held
        else ()) + (
        ("route_groups_held_pct",) if cfg.experts_held
        and cfg.topk_group < cfg.n_group else ()) + (
        ("hc_row_defect",) if cfg.hc_mult else ())


def scan_layers(params: Params, cfg: LlamaConfig, stack: str = "layers"
                ) -> tuple[dict[str, jax.Array], dict[str, jax.Array]]:
    """What the one layer scan iterates over, and what it closes over,
    for one of the model's stacks (``cfg.layer_stacks``): ``(xs,
    held)``. ``xs`` is the stacked layer tree plus the per-layer
    kinds; a scan body rebuilds a layer's parameters as ``{**lp,
    **held}``. ``held`` is empty but for dropless experts: their
    (L, E, in, out) stacks stay OUT of the scan's sliced inputs and the
    layer carries its ``layer_index`` — its place in ITS stack, whatever
    layer of the model it is — so an expert's matrix is sliced out of
    the whole stack where it is used (parallel/moe.py) — a scan that
    slices a layer's experts first hands the inner block loop a copy of
    the layer's whole slab."""
    first = next(f for name, f, _ in cfg.layer_stacks if name == stack)
    return _scan_inputs(params[stack], cfg, first)


def _scan_inputs(layers: dict[str, jax.Array], cfg: LlamaConfig, first: int):
    layers = dict(layers)
    n = jax.tree.leaves(layers)[0].shape[0]
    held: dict[str, jax.Array] = {}
    if "router" in layers and cfg.moe_impl == "dropless":
        held = {name: layers.pop(name)
                for name in ("w_gate", "w_up", "w_down")}
        layers["layer_index"] = jnp.arange(n, dtype=jnp.int32)
    # an indexer is stacked over its stack's FULL layers only: held, and
    # sliced by the layer's place among them (``_index_project``)
    held.update({name: layers.pop(name) for name in list(layers)
                 if name.startswith("index_")})
    layers.update(layer_kinds(cfg, first, n))
    if cfg.index_topk:
        held["index_first"] = sum(cfg.layer_index[:first])
    return layers, held


#: The leaves of a stack that only its ATTENTION layers have, where not
#: every layer attends (``cfg.full_attention_interval``): stacked over
#: those alone, as the recurrent layers' ``gdn_*`` / ``kda_*`` are over
#: theirs. Per-head attention's, then latent attention's.
ATTENTION_WEIGHTS = ("wq", "wk", "wv", "wo", "wz", "q_norm", "k_norm",
                     "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
                     "wk_b", "wv_b", "wz_head")
_ATTENTION_LEAVES = ATTENTION_WEIGHTS + (
    "cache_k", "cache_v", "cache_c", "cache_r")             # dense cache
_RECURRENT_CACHE = ("cache_s", "cache_conv")
RECURRENT_PREFIXES = ("gdn_", "kda_", "ssd_")


def _by_kind(stack: dict) -> tuple[dict, dict]:
    """A stack's scan inputs where not every layer attends, by the kind
    of layer that has them: ``(leaves, caches)``. Two weight trees in
    one stack, and no program computes both mixers for a layer: which
    one a layer runs is its index in the model, known when the program
    is traced.

    ``leaves`` — ``{"common": what every layer has, stacked (L, ...);
    "recurrent": (Lg, ...); "attention": (Lf, ...)}`` — are HELD, not
    scanned, and a layer's slice is taken where it is used, by its place
    among its kind: scanned as (P, period, ...) a
    layer's matrix is a second, static index into the step's slab, and
    the chip's compiler copies the slab's layer out before the matmul
    (33 MB a recurrent layer a decode step: 0.6 ms of a 12 ms step;
    chip, PR 46) where a dynamic slice of the whole stack is read in
    place, as the experts' stacks are (``scan_layers``). Only a dense
    cache's slices (``caches``: ``cache_*``, by kind too) ride a scan."""
    leaves: dict = {"common": {}, "recurrent": {}, "attention": {}}
    caches: dict = {"recurrent": {}, "attention": {}}
    for name, leaf in stack.items():
        if name in _RECURRENT_CACHE:
            caches["recurrent"][name] = leaf
        elif name.startswith("cache_"):
            caches["attention"][name] = leaf
        elif name in _ATTENTION_LEAVES:
            leaves["attention"][name] = leaf
        elif name.startswith(RECURRENT_PREFIXES):
            leaves["recurrent"][name] = leaf
        else:
            leaves["common"][name] = leaf
    return leaves, caches


def _layers_at(tree: dict, index) -> dict:
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, index, 0, keepdims=False), tree)


def _stack_periods(cfg: LlamaConfig, first: int, n: int) -> tuple:
    """How the model's layers ``first`` .. ``first + n`` (one stack) fall
    into periods, which are counted in the MODEL's layer indices: ``(r0,
    P, r1)`` — a head of ``r0`` recurrent layers and the attention layer
    behind them where those are not a whole run of ``period - 1`` (-1:
    they are, or the stack holds no attention layer), ``P`` whole runs
    of ``period - 1`` recurrent layers and an attention layer, and
    ``r1`` recurrent layers behind the last attention layer. A stack
    may begin inside a period (leading dense layers in front of it), and
    the attention layer may sit anywhere in its period
    (``cfg.full_attention_place``): at place 5 of 10, 40 layers are a
    head of 5, three runs of 9 and a tail of 4."""
    period = cfg.full_attention_interval
    full = [i for i in range(n) if cfg.layer_full[first + i]]
    if not full:
        return -1, 0, n
    r0 = full[0] if full[0] != period - 1 else -1
    return r0, len(full) - (r0 >= 0), n - 1 - full[-1]


def _run_stack(layers: dict[str, jax.Array], cfg: LlamaConfig, h: jax.Array,
               positions: jax.Array, inv_freq: jax.Array,
               kv_valid_len: Optional[jax.Array], attend=None, *,
               state=None, xs: Optional[dict] = None,
               row_mask: Optional[jax.Array] = None, stats: bool = False,
               first: int = 0, selection: Optional[jax.Array] = None,
               recur=None):
    """The ONE scan over a layer stack; every forward is a use of it
    (through ``_run_model``, once a stack of the model) and hands it
    only what differs. ``attend(q, k, v, lp, li, state) -> (attn, out)``
    is a layer's KV step (None: attention over the tokens given); ``lp``
    holds the layer's parameters, its kinds and its slice of ``xs``,
    ``li`` is its index IN THE MODEL: the stack's layers are the model's
    layers ``first``, ``first + 1``, ..., and that is where their kinds
    are read, where the kernel appends and where the pool is read.
    Returns ``(h, out, touched, selection)``, the third each layer's
    ``experts_touched`` (n,) under ``stats``.

    ``selection`` (learned sparse attention; None otherwise, and then
    nothing of it is in the program): the keep mask over the keys that a
    FULL layer's attention selects and the shared layers above it
    attend — the one value that leaves a layer's attention and enters
    the next layer's. It rides the carry beside ``h``: a layer finds it
    as ``lp["selection"]`` and its ``attend`` returns ``(attn, (out,
    selection))``; the caller hands in the mask's shape (zeros) and gets
    the last layer's back, for the next stack.

    What the scan iterates over and what it closes over was decided on
    the chip, three times, and is written down here and in ``scan_layers``:

    - *state carried* (``state`` given; ``out`` is the next layer's
      state and, at the end, the result): the decode kernel path. The
      pool rides the carry and passes through the Pallas call aliased
      in place — attention read and row append happen inside the kernel,
      so no XLA gather or scatter ever touches the pool, no layout is
      fought over and the carry is not double-buffered.
    - *pool held, rows out* (no ``state``; ``out`` is stacked over the
      layers): every other forward that has a pool. The body closes
      over the pool WHOLE and reads it by (layer, page) over its
      flattened leading axes; a layer's new rows are scan outputs and
      ONE write after the scan puts them in the pool (the cache
      object's ``write``).
      Handed to the scan as sliced inputs, each iteration's slice of the
      pool is a copy of the layer's whole K and V slab (PR 29: 2 x 142 MB
      a layer to attend eight pages), and as sliced-in / stacked-out it
      is a second pool in loop temporaries (the round-2 OOM).
    - dropless expert stacks are held too, never sliced by the scan
      (``scan_layers``; PR 28).

    A new layer kind adds its flag in ``layer_kinds`` and reads it in
    ``decoder_layer`` or an ``attend``; a new cache format is a third
    implementation of the cache object (models/kv_cache.py: its writer,
    its readers and its kernel call).

    Where not every layer attends (``cfg.full_attention_interval``) a
    scan step is one PERIOD of layers (``_by_period``), the recurrent
    ones first, each run by the same ``one`` below. ``recur(x, lp, li,
    state) -> (mixed, out)`` is a recurrent layer's step as ``attend`` is
    an attention layer's (None: a sequence from zeros, nothing kept);
    ``out`` stacks ``(k, v, s, conv)``, the first two over the attention
    layers and the last two over the recurrent ones.
    """
    stack, held = _scan_inputs(layers, cfg, first)
    stack = {**stack, **(xs or {})}
    carried = state is not None
    period = cfg.full_attention_interval

    def one(h, state, li, sel, lp):
        lp = {**lp, **held}
        if sel is not None:
            lp["selection"] = sel
        aux = {} if stats else None
        h, out = decoder_layer(
            h, lp, cfg, positions, inv_freq, kv_valid_len,
            attend=attend and (lambda q, k, v, *index: attend(
                q, k, v, lp, li, state, *index)),
            row_mask=row_mask, aux=aux,
            recur=recur and (lambda x: recur(x, lp, li, state)))
        if sel is not None:
            out, sel = out
        if carried:
            state, out = out, None
        touched = None if aux is None else {
            name: aux.get(name, jnp.float32(0.0))
            for name in layer_stat_names(cfg)}
        return h, state, sel, out, touched

    if period:
        n = jax.tree.leaves(layers)[0].shape[0]
        r0, P, r1 = _stack_periods(cfg, first, n)
        periods = jnp.arange(P, dtype=jnp.int32)
    # the Pallas call takes its layer as a (1,) scalar-prefetch operand;
    # the held form indexes the flattened pool with a scalar
    li = jnp.zeros((1,) if carried else (), jnp.int32)
    if first:
        li = li + first

    if not period:
        def body(carry, lp):
            h, state, li, sel = carry
            h, state, sel, out, touched = one(h, state, li, sel, lp)
            return (h, state, li + 1, sel), (out, touched)

        (h, state, _, selection), (out, touched) = jax.lax.scan(
            body, (h, state, li, selection), stack)
        return h, (state if carried else out), touched, selection

    # Where not every layer attends, the stack's layers are run period
    # by period (``_stack_periods``): the recurrent layers of a period
    # are one body, scanned — a period traced layer by layer is twice
    # the program (137 of them overran the chip's compile cache and
    # every start was cold, 640 s; chip, PR 46) — and the whole periods
    # one scan over that and their attention layer. A stack that begins
    # inside a period runs its head as one more step of that scan, a
    # step's recurrent layers then under a loop of as many trips as its
    # period has (``ragged_periods``); one that ends inside a period (or
    # holds no attention layer: leading dense layers) its last recurrent
    # layers alone.
    leaves, caches = _by_kind(stack)

    def recurrent_layers(h, state, li, r, g_of, c_of, cache):
        """``r`` recurrent layers, the ``j``-th the stack's layer
        ``c_of(j)`` and the ``g_of(j)``-th of its recurrent layers;
        ``cache``: their slices of a dense cache, (r, ...)."""
        def layer(carry, x):
            h, state = carry
            j, own = x
            at = li + j         # the layer's index in the model
            lp = {**_layers_at(leaves["recurrent"], g_of(j)), **own}
            lp = {**_layers_at(leaves["common"], c_of(j)), **lp}
            h, state, _, out, touched = one(h, state, at, None, lp)
            return (h, state), (out, touched)

        (h, state), (rows, stat) = jax.lax.scan(
            layer, (h, state), (jnp.arange(r, dtype=jnp.int32), cache))
        return h, state, rows, stat

    def whole_period(carry, x):
        """``period - 1`` recurrent layers and the attention layer behind
        them, period ``x["period"]`` of a stack that begins on a
        period's first layer."""
        h, state, li, sel = carry
        p = x["period"]
        h, state, rows, stat = recurrent_layers(
            h, state, li, period - 1, lambda j: p * (period - 1) + j,
            lambda j: p * period + j, x["recurrent"])
        last = li + period - 1
        own = {**_layers_at(leaves["attention"], p), **x["attention"]}
        h, state, sel, out, touched = one(
            h, state, last, sel,
            {**_layers_at(leaves["common"], (p + 1) * period - 1), **own})
        if out is not None:     # the attention layer's rows, then the
            out = out + rows    # recurrent layers' states and tails
        if stats:
            stat = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]]),
                                stat, touched)
        return (h, state, li + period, sel), (out, stat if stats else None)

    def ragged_periods(carry, sizes):
        """Periods of UNEQUAL numbers of recurrent layers — a head of
        ``sizes[0]`` and the whole periods behind it — as ONE scan whose
        step runs its period's recurrent layers under a loop of that
        many trips: the head traced beside the scanned periods is a
        second copy of both layer bodies in every program (2.8 MB of
        text a chunk program, 812 s to build a cold start's 160
        programs, 308 MB of them against the 201 MB the chip tool's
        cache keeps; chip, PR 49). A loop of a traced length stacks
        nothing, so what the recurrent layers leave (their states and
        tails where the pool is held, their scalars under ``stats``) is
        written by place into buffers in the loop's carry; the attention
        layers' come out of the scan. Returns ``(carry, out, stats)``,
        the stats the recurrent layers' and then the attention
        layers'."""
        h, state, li, sel = carry

        def recurrent_layer(h, state, li, j, g0, c0):
            at = li + j
            lp = {**_layers_at(leaves["recurrent"], g0 + j),
                  **_layers_at(caches["recurrent"], g0 + j)}
            lp = {**_layers_at(leaves["common"], c0 + j), **lp}
            h, state, _, out, touched = one(h, state, at, None, lp)
            return h, state, (out, touched)

        left = jax.eval_shape(
            lambda h, state, li: recurrent_layer(h, state, li, 0, 0, 0)[2],
            h, state, li)
        bufs = jax.tree.map(lambda x: jnp.zeros(
            (sum(sizes),) + x.shape, x.dtype), left)

        def step(carry, x):
            h, state, li, sel, bufs = carry

            def layer(j, c):
                h, state, bufs = c
                h, state, new = recurrent_layer(h, state, li, j, x["g0"],
                                                x["c0"])
                return h, state, jax.tree.map(
                    lambda b, v: jax.lax.dynamic_update_index_in_dim(
                        b, v, x["g0"] + j, 0), bufs, new)

            h, state, bufs = jax.lax.fori_loop(0, x["r"], layer,
                                               (h, state, bufs))
            last = li + x["r"]
            own = {**_layers_at(leaves["attention"], x["period"]),
                   **x["attention"]}
            h, state, sel, out, touched = one(
                h, state, last, sel,
                {**_layers_at(leaves["common"], x["c0"] + x["r"]), **own})
            return (h, state, last + 1, sel, bufs), (out, touched)

        at = [0]
        for r in sizes:
            at.append(at[-1] + r)
        xs = {"period": jnp.arange(len(sizes), dtype=jnp.int32),
              "r": jnp.asarray(sizes, jnp.int32),
              "g0": jnp.asarray(at[:-1], jnp.int32),
              "c0": jnp.asarray([g + i for i, g in enumerate(at[:-1])],
                                jnp.int32),
              "attention": cut(caches["attention"], 0, len(sizes))}
        (h, state, li, sel, (rows, stat)), (out, touched) = jax.lax.scan(
            step, (h, state, li, sel, bufs), xs)
        if out is not None:
            out = out + rows
        if stats:
            stat = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), stat,
                                touched)
        return (h, state, li, sel), out, stat if stats else None

    def cut(tree, lo, hi):
        return {name: a[lo:hi] for name, a in tree.items()}

    outs, touched = [], []
    carry = (h, state, li, selection)
    g = 0           # recurrent layers run so far
    if r0 >= 0:     # a stack that begins inside a period
        sizes = (r0,) + (period - 1,) * P
        carry, out, stat = ragged_periods(carry, sizes)
        outs.append(out)
        touched.append(stat)
        g = sum(sizes)
    elif P:
        xs_p = {"period": periods,
                "recurrent": {
                    name: x.reshape((P, period - 1) + x.shape[1:])
                    for name, x in cut(caches["recurrent"], 0,
                                       P * (period - 1)).items()},
                "attention": cut(caches["attention"], 0, P)}
        carry, (out, stat) = jax.lax.scan(whole_period, carry, xs_p)
        # (P, period - 1, ...) -> the recurrent layers in order
        outs.append(None if out is None else out[:-2] + tuple(
            x.reshape((-1,) + x.shape[2:]) for x in out[-2:]))
        touched.append(stat)
        g = P * (period - 1)
    if r1:
        h, state, li, sel = carry
        h, state, rows, stat = recurrent_layers(
            h, state, li, r1, lambda j: g + j, lambda j: n - r1 + j,
            cut(caches["recurrent"], g, g + r1))
        carry = (h, state, li + r1, sel)
        outs.append(None if rows is None else (None, None) + rows)
        touched.append(stat)
    h, state, _, selection = carry
    if len(outs) == 1:
        out, touched = outs[0], touched[0]
    else:
        out = _join_layers(outs)
        touched = jax.tree.map(lambda *t: jnp.concatenate(
            [x.reshape(-1) for x in t]), *touched) if stats else None
    return h, (state if carried else out), touched, selection


def _join_layers(outs: list):
    """The rows several runs of layers left, each a tuple of leaves
    stacked over ITS layers of each kind (None: it had none of that
    kind; a whole None: a carried pool, nothing came out), joined along
    the layer axis in the order the layers ran."""
    if any(o is None for o in outs):
        return None
    return tuple(
        None if not parts else parts[0] if len(parts) == 1
        else jnp.concatenate(parts, axis=0)
        for parts in ([o[i] for o in outs if o[i] is not None]
                      for i in range(len(outs[0]))))


def _run_model(params: Params, cfg: LlamaConfig, h: jax.Array, *args,
               state=None, xs: Optional[dict] = None,
               selection: Optional[jax.Array] = None, **kw):
    """Every layer of the model: ``_run_stack`` over each of its stacks
    in turn (``cfg.layer_stacks``: one, or the leading dense layers and
    then the expert layers) inside the caller's one program, over ONE
    pool — carried from stack to stack (``state``) or held by the
    caller's ``attend`` while each stack's rows come out and are joined
    along the layer axis. ``xs`` (L, ...) is cut to each stack's layers.
    ``touched`` (every ``layer_stat_names`` scalar) is that of the layers
    that have experts. A ``selection``
    (``_run_stack``) is handed from stack to stack: the first expert
    layers attend the set of the last full dense layer."""
    stacks = cfg.layer_stacks
    if cfg.hc_mult:
        # the stream is ``hc_mult`` copies wide between the embedding and
        # the final norm and nowhere else: every forward hands in and
        # gets back (B, S, D)
        h = hc.expand(h, cfg.hc_mult)
    if len(stacks) == 1:
        h, state, touched = _run_stack(
            params["layers"], cfg, h, *args, state=state, xs=xs,
            selection=selection, **kw)[:3]
    else:
        outs, touched = [], None
        for name, first, n in stacks:
            part = xs and {k: v[slice(*_layers_of(cfg, k, first, n))]
                           for k, v in xs.items()}
            h, out, t, selection = _run_stack(
                params[name], cfg, h, *args, state=state, xs=part,
                first=first, selection=selection, **kw)
            if state is not None:
                state = out
            outs.append(out)
            if "router" in params[name]:
                touched = t
        if state is None and cfg.recurrent:
            state = _join_layers(outs)
        elif state is None:
            state = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0),
                                 *outs)
    if cfg.hc_mult:
        h = hc.collapse(h, cfg.hc_mult)
    return h, state, touched


def _layers_of(cfg: LlamaConfig, name: str, first: int, n: int) -> tuple:
    """Where the model's layers ``first`` .. ``first + n`` lie in a dense
    cache's leaf ``name`` (``cache_*``): stacked over all layers, or
    beside recurrent layers over the layers of its kind alone."""
    if not cfg.recurrent:
        return first, first + n
    kind = int(name not in _RECURRENT_CACHE)
    before = sum(f == kind for f in cfg.layer_full[:first])
    return before, before + sum(
        f == kind for f in cfg.layer_full[first:first + n])


def _kv_step(attn, rows: tuple, index: tuple, key=None):
    """What a forward's ``attend`` returns: ``(attn, rows)``, the rows the
    layer leaves in the cache. Under learned sparse attention (``index``
    holds the layer's index projections) the cache object's reader gave
    ``(attn, selection)``: the index key (``key``; the projections' own
    by default) joins the rows and the selection rides beside them
    (``_run_stack``)."""
    if not index:
        return attn, rows
    attn, selection = attn
    return attn, (rows + (index[0]["k"] if key is None else key,),
                  selection)


def _step_result(params: Params, cfg: LlamaConfig, h: jax.Array,
                 cache: KVCache, touched, return_hidden: bool, stats: bool):
    out = h if return_hidden else unembed(params, cfg, h)
    if stats:
        return out, cache, {name: jnp.mean(t) for name, t in touched.items()}
    return out, cache


def apply_decode_paged(params: Params, cfg: LlamaConfig, tokens: jax.Array,
                       positions: jax.Array, kv_cache: KVCache,
                       block_table: jax.Array, kv_valid_len: jax.Array,
                       write_page: jax.Array, write_offset: jax.Array,
                       use_kernel: Optional[bool] = None,
                       mesh=None, return_hidden: bool = False,
                       active: Optional[jax.Array] = None,
                       stats: bool = False,
                       slots: Optional[jax.Array] = None,
                       ) -> tuple[jax.Array, KVCache]:
    """Single-token decode step over the paged KV pool.

    ``slots`` (B,): where the rows' recurrent state lies (a model with
    recurrent layers; None: row ``b`` is slot ``b``). Such a model's idle
    rows (``active`` False) leave their slot's state as it is.

    ``active`` (B,) bool: the rows that hold a sequence. Only dropless
    experts read it (an idle slot then touches no expert); every other
    layer computes idle rows and the caller discards them. ``stats``
    appends a third result, ``{"experts_touched": ()}``: the distinct
    experts a layer's rows reached, averaged over the layers (0 for a
    model without dropless experts).

    tokens/positions: (B, 1). block_table: (B, P) — physical page id of each
    slot's logical page, sliced by the engine to the smallest window covering
    every active sequence (so HBM reads scale with actual context, not cache
    capacity). write_page/write_offset: (B,) physical destination of this
    step's K/V (page 0 = trash for inactive slots). Returns
    (logits (B, 1, V), updated cache) — or (hidden (B, 1, D), cache)
    under ``return_hidden`` (the engine's fused vocab-tiled sampling
    tail does its own norm + streamed projection; see
    ops/fused_sampler.py).

    ``use_kernel``: the engine decides (None = auto, for single-device
    callers). True is the Pallas kernel with the pool in the layer
    scan's carry (``_run_stack``) — under learned sparse attention with
    the layer's keep mask as one more operand of the kernel and the mask
    in the carry beside the pool; False — the CPU, a mesh the kernel
    refuses — is the one-token case of ``apply_verify_paged``.
    """
    kvc = kv_cache_of(cfg)
    if use_kernel is None:
        use_kernel = use_paged_kernel(cfg, kvc.page_size(kv_cache))
    if not use_kernel:
        return apply_verify_paged(
            params, cfg, tokens, positions, kv_cache, block_table,
            kv_valid_len, write_page[:, None], write_offset[:, None],
            return_hidden, active=active, stats=stats, slots=slots)
    inv_freq = _inv_freq(cfg)
    h = _embed(params, tokens, cfg.embed_scale)
    pos_in_win = positions[:, 0]  # logical index of the current token
    attend = kvc.kernel_attend(kv_cache, block_table, pos_in_win,
                               write_page, write_offset, mesh, h.dtype)

    h, cache, touched = _run_model(
        params, cfg, h, positions, inv_freq, kv_valid_len, attend,
        state=kv_cache, row_mask=active, stats=stats,
        selection=_no_selection(cfg, tokens, kv_cache, block_table),
        **_recurrent_step(cfg, kvc, None, slots, active,
                          carried=tokens.shape[0]))
    return _step_result(params, cfg, h, cache, touched, return_hidden,
                        stats)


def _recurrent_step(cfg: LlamaConfig, kvc, kv_cache: Optional[KVCache],
                    slots, active, carried: int = 0) -> dict:
    """``_run_stack``'s ``recur`` for one token a row over the rows'
    slots (nothing for a model without recurrent layers): every row
    continues its sequence, idle rows touch nothing. Over a carried pool
    (``carried``: its rows) whose rows are all its slots the step is the
    Pallas kernel over the state leaf, where it takes the geometry: it
    walks the live rows alone, in an order computed HERE — once a step,
    not once a layer."""
    if not cfg.recurrent:
        return {}
    n_valid = None if active is None else active.astype(jnp.int32)
    live = None
    if carried and slots is None and kvc.step_kernel_supported():
        live = gd.live_first(jnp.ones((carried,), bool) if active is None
                             else active)
    return {"recur": _gdn_recur(
        cfg, *kvc.recur(kv_cache, slots, None, bool(carried), live),
        n_valid=n_valid)}


def _no_selection(cfg: LlamaConfig, tokens: jax.Array, kv_cache: KVCache,
                  block_table: jax.Array) -> Optional[jax.Array]:
    """The keep mask a forward over a slot window hands its first layer
    (learned sparse attention; None otherwise): (B, S, window keys)
    zeros, which the first layer, a full one, replaces."""
    if not cfg.index_topk:
        return None
    page = kv_cache_of(cfg).page_size(kv_cache)
    return jnp.zeros(tokens.shape + (block_table.shape[1] * page,), bool)


def apply_verify_paged(params: Params, cfg: LlamaConfig, tokens: jax.Array,
                       positions: jax.Array, kv_cache: KVCache,
                       block_table: jax.Array, kv_valid_len: jax.Array,
                       write_pages: jax.Array, write_offsets: jax.Array,
                       return_hidden: bool = False, *,
                       active: Optional[jax.Array] = None,
                       stats: bool = False,
                       slots: Optional[jax.Array] = None,
                       ) -> tuple[jax.Array, KVCache]:
    """Multi-token decode step over the paged KV pool: the speculative-
    decoding VERIFICATION forward (engine/spec_decode.py), and at S = 1
    the decode step wherever the Pallas kernel does not run.

    Scores ``S`` consecutive positions per slot in ONE forward — the
    last accepted token plus up to S-1 draft tokens — so the engine can
    emit several tokens per model step.  tokens/positions: (B, S) with
    each row's positions contiguous (``pos .. pos+S-1``).
    write_pages/write_offsets: (B, S) physical destination of EACH
    token's K/V (page 0 = trash for inactive slots and positions past
    the slot's draft count).  kv_valid_len: (B,) = ``pos + S`` — the
    causal mask inside :func:`gqa_attention` restricts each query to
    keys at positions <= its own, so draft token j attends the pool
    prefix plus drafts 0..j-1 exactly as a sequential decode would.
    ``active`` / ``stats``: as in ``apply_decode_paged``.

    Rollback discipline: rejected drafts need NO explicit undo.  Their
    K/V rows land at positions past the last accepted token; the engine
    simply does not advance ``pos`` past acceptance, so the next step's
    writes overwrite them and reads (masked by ``pos``) never see them
    — pages never advance past the last accepted token and prefix-cache
    block hashes (pure prompt blocks) stay consistent.

    The Pallas decode kernel stays single-token (its per-slot DMA loop
    is shaped around one query row); verify rounds take this gather
    path on every backend, trading a gathered window per layer for the
    K+1 scoring positions. Pool held, rows out (``_run_stack``).
    """
    inv_freq = _inv_freq(cfg)
    kvc = kv_cache_of(cfg)
    rows = jnp.arange(tokens.shape[0])[:, None]
    if cfg.recurrent and tokens.shape[1] != 1:
        raise NotImplementedError(
            "apply_verify_paged over several tokens a row and recurrent "
            "layers (full_attention_interval): a rejected draft is rolled "
            "back by length, and a state that has consumed it cannot be")

    def attend(q, k, v, lp, li, _, *index):
        return _kv_step(kvc.attend_window(
            q, k, v, lp, kv_cache, li, block_table, rows, positions,
            kv_valid_len, *index), (k, v), index)

    h, new, touched = _run_model(
        params, cfg, _embed(params, tokens, cfg.embed_scale), positions,
        inv_freq, kv_valid_len, attend, row_mask=active, stats=stats,
        selection=_no_selection(cfg, tokens, kv_cache, block_table),
        **_recurrent_step(cfg, kvc, kv_cache, slots, active))
    where = {"slots": slots} if cfg.recurrent else {}
    cache = kvc.write(kv_cache, *new, write_pages, write_offsets, **where)
    return _step_result(params, cfg, h, cache, touched, return_hidden,
                        stats)


def apply_prefill_paged(params: Params, cfg: LlamaConfig, tokens: jax.Array,
                        positions: jax.Array, kv_cache: KVCache,
                        block_table: jax.Array, kv_valid_len: jax.Array,
                        start_page_idx: jax.Array, *,
                        with_logits: bool = False,
                        use_kernel: Optional[bool] = None,
                        slots: Optional[jax.Array] = None,
                        ) -> tuple[jax.Array, KVCache]:
    """One CHUNK of a long-prompt prefill over the paged KV pool, of one
    prompt (B = 1) or of B prompts at once, a row each.

    ``slots`` (B,): where the rows' recurrent state lies (a model with
    recurrent layers; None: row ``b`` is slot ``b``). A row whose chunk
    starts at position 0 starts from zeros whatever its slot held, and
    the tokens past ``kv_valid_len`` leave the state alone.

    The piece that lets the engine serve prompts longer than any single
    prefill bucket: the prompt streams through in page-aligned chunks,
    each chunk's KV lands in the slot's pool pages, and its attention
    reads the whole prefix back from the pool — exact attention, bounded
    activation memory (one chunk's worth).

    tokens/positions: (1, C), C a page multiple, positions starting at a
    page boundary. block_table: (1, P) logical→physical window covering
    at least ``kv_valid_len`` tokens. kv_valid_len: (1,) = chunk start +
    valid tokens in this chunk (padding rows beyond it are causally
    masked AND their pool rows are later overwritten or never read).
    start_page_idx: () int32 — logical page index of the chunk's first
    row; destination pages are ``block_table[0, start_page_idx + i]``.
    Returns (hidden states (1, C, D), updated pool) by default — the
    engine unembeds only the sampling position; ``with_logits=True``
    returns full (1, C, V) logits instead (a large transient at big
    vocab x chunk; only for callers that truly need every position).
    ``use_kernel``: the engine decides (None = auto, as
    ``apply_decode_paged``): True runs a block's softmax update as the
    Pallas chunk kernel, where the cache object has one
    (``use_prefix_kernel``).

    The pool is held, the rows come out (``_run_stack``): a layer's
    prefix blocks are gathered out of the whole pool by (layer, page),
    and the chunk rides its own attention in-register.

    B > 1 (the engine's grouped chunk program): tokens/positions (B, C),
    block_table (B, P) at one width, kv_valid_len and start_page_idx
    (B,), each row a different prompt at its own start. Everything but
    attention runs over the B x C tokens together — so a layer's experts
    are read once for all rows (dropless and dense models only: capacity
    routing would drop by what is routed together) —, the cache object's
    ``attend_prefix`` once a row with the row's own table, start and
    length (blocks past a row's start are skipped, whatever the width),
    and ONE write after the scan puts every row's pages at their own
    destinations. Returns hidden states (B, C, D).
    """
    B, C = tokens.shape
    if B != 1 and cfg.num_experts and cfg.moe_impl == "sparse":
        raise ValueError(
            "apply_prefill_paged over several rows and moe_impl 'sparse': "
            "an expert's capacity is that of the tokens routed together, so "
            "the rows of other prompts would change which assignments drop")
    kvc = kv_cache_of(cfg)
    page = kvc.page_size(kv_cache)
    if C % page:
        raise ValueError(f"chunk {C} not a page ({page}) multiple")
    if use_kernel is None:
        use_kernel = use_prefix_kernel(cfg, page)
    kernel = {"use_kernel": True} if use_kernel else {}
    inv_freq = _inv_freq(cfg)
    h = _embed(params, tokens, cfg.embed_scale)
    start = positions[0, 0]  # absolute position of the chunk's first row

    def attend(q, k, v, lp, li, _, *index):
        # prefix streamed from the pool block-by-block (online softmax)
        # + the chunk's own K/V in-register; the pool write happens in
        # the one post-scan scatter. Never materializes the full
        # gathered window — prefix length does not bound this path's
        # memory.
        attn = kvc.attend_prefix(q, k, v, lp, kv_cache, block_table, start,
                                 kv_valid_len, li, *index, **kernel)
        return _kv_step(attn, (k[0], v[0]), index,
                        *(ix["k"][0] for ix in index))

    def attend_rows(q, k, v, lp, li, _, *index):
        # what of ``index`` is a row's own: its projections and the mask
        # carried; whether the layer selects and where its keys lie is
        # the layer's
        own = [{n: ix[n] for n in ("q", "k", "w", "keep")} for ix in index]

        def row(r):
            q, k, v, table, start, valid, *own = r
            out = kvc.attend_prefix(
                q[None], k[None], v[None], lp, kv_cache, table[None], start,
                valid[None], li, *(
                    {**ix, **{n: a[None] for n, a in o.items()}}
                    for ix, o in zip(index, own)), **kernel)
            return (out[0][0], out[1][0]) if index else out[0]
        attn = jax.lax.map(row, (q, k, v, block_table, positions[:, 0],
                                 kv_valid_len, *own))
        return _kv_step(attn, (k, v), index)

    selection = None
    if cfg.index_topk:
        selection = jnp.zeros((B, C, kvc.prefix_keys(
            block_table.shape[1], page) + C), bool)
    recur = {}
    if cfg.recurrent:
        recur["recur"] = _gdn_recur(
            cfg, *kvc.recur(kv_cache, slots, positions[:, 0] == 0),
            n_valid=kv_valid_len - positions[:, 0])
    h, new, _ = _run_model(params, cfg, h, positions, inv_freq,
                           kv_valid_len, attend if B == 1 else attend_rows,
                           selection=selection, **recur)
    state = ()
    if cfg.recurrent:
        new, state, recur = new[:2], new[2:], {"slots": slots}
    # new: (L, C, KV, hd) a leaf, to the chunk's physical pages
    if B == 1:
        dest = jax.lax.dynamic_slice(block_table[0], (start_page_idx,),
                                     (C // page,))
    else:
        # (L, B, C, ...) row-major is (L, B * C, ...): every row's pages
        # in one list, each row's own run of its table
        new = tuple(a.reshape((a.shape[0], B * C) + a.shape[3:])
                    for a in new)
        dest = jnp.take_along_axis(
            block_table, start_page_idx[:, None]
            + jnp.arange(C // page, dtype=jnp.int32)[None], axis=1
        ).reshape(-1)
    cache = kvc.write(kv_cache, *new, *state, dest, **recur)
    if not with_logits:
        return h, cache
    return unembed(params, cfg, h), cache


def _dense_mlp(x: jax.Array, lp: dict[str, jax.Array],
               cfg: LlamaConfig) -> jax.Array:
    if cfg.mlp == "squared_relu":
        # GPT-Next: relu(x W_up)^2 W_down — non-gated
        up = qmm(x, lp["w_up"])
        if "b_up" in lp:
            up = up + lp["b_up"]
        act = jnp.square(jax.nn.relu(up))
        out = qmm(act, lp["w_down"])
        if "b_down" in lp:
            out = out + lp["b_down"]
        return out
    act = jax.nn.relu if cfg.mlp == "relu_glu" else jax.nn.silu
    gate = act(qmm(x, lp["w_gate"]))
    return qmm(gate * qmm(x, lp["w_up"]), lp["w_down"])


def block_norm(x: jax.Array, lp: dict[str, jax.Array], key: str,
               cfg: LlamaConfig) -> jax.Array:
    """The per-block normalization — rmsnorm (llama) or layernorm1p
    (GPT-Next), selected by config."""
    if cfg.norm == "layernorm1p":
        return layernorm1p(x, lp[key], lp[key + "_b"], cfg.rms_norm_eps)
    return rmsnorm(x, lp[key], cfg.rms_norm_eps)


def _router_logits(x: jax.Array, lp: dict[str, jax.Array]) -> jax.Array:
    return x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)


def _moe_mlp(x: jax.Array, lp: dict[str, jax.Array], cfg: LlamaConfig,
             router_logits: Optional[jax.Array] = None,
             row_mask: Optional[jax.Array] = None,
             aux: Optional[dict] = None) -> jax.Array:
    """Mixtral MLP. Default is the sparse top-k capacity-routed path
    (parallel/moe.py, O(tokens*k) expert FLOPs); ``moe_impl="dense"``
    keeps the zero-gated all-experts formulation (O(tokens*E), no
    capacity drops) as the parity oracle; ``"dropless"`` sorts the
    assignments by expert and drops nothing (parallel/moe.py), with
    ``router_logits`` (B, S, E) from where the configuration's router
    reads (None: from ``x``)."""
    if cfg.moe_impl == "dropless":
        from ..parallel.moe import dropless_moe_ffn
        if router_logits is None:
            with jax.named_scope("moe_route"):
                router_logits = _router_logits(x, lp)
        out, touched = dropless_moe_ffn(x, router_logits, lp, cfg, row_mask,
                                        aux)
        if aux is not None:
            aux["experts_touched"] = touched
        return out
    if router_logits is not None or cfg.mlp != "swiglu":
        raise ValueError(
            f"moe_impl {cfg.moe_impl!r} routes after the norm through "
            f"SwiGLU experts only; router_input 'block_input' and mlp "
            f"{cfg.mlp!r} need moe_impl 'dropless'")
    if cfg.moe_impl == "sparse":
        from ..parallel.moe import sparse_moe_ffn
        return sparse_moe_ffn(x, lp, cfg)
    if cfg.moe_impl != "dense":
        raise ValueError(f"unknown moe_impl {cfg.moe_impl!r}; "
                         f"expected 'sparse', 'dense' or 'dropless'")
    B, S, D = x.shape
    with jax.named_scope("moe_route"):
        logits = x @ lp["router"]  # (B,S,E)
        weights, idx = jax.lax.top_k(logits, cfg.num_experts_per_tok)
        weights = jax.nn.softmax(weights.astype(jnp.float32),
                                 axis=-1).astype(x.dtype)
        # gates: (B,S,E) with softmaxed weights at the top-k positions
        gates = jnp.zeros_like(logits).at[
            jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], idx
        ].set(weights)
    with jax.named_scope("moe_experts"):
        gate = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, lp["w_gate"]))
        up = jnp.einsum("bsd,edf->bsef", x, lp["w_up"])
        down = jnp.einsum("bsef,efd->bsed", gate * up, lp["w_down"])
        return jnp.einsum("bsed,bse->bsd", down, gates)


def decoder_layer(h: jax.Array, lp: dict[str, jax.Array], cfg: LlamaConfig,
                  positions: jax.Array, inv_freq: jax.Array,
                  kv_valid_len: Optional[jax.Array],
                  attend=None, row_mask: Optional[jax.Array] = None,
                  aux: Optional[dict] = None, recur=None):
    """One transformer block. The single source of layer math: every
    forward reaches it through ``_run_stack``.

    A layer whose tree holds the recurrent mixer's leaves (``gdn_*``)
    runs ``_gdn_mixer`` in place of attention, through ``recur(x) ->
    (mixed, out)`` (None: a sequence from zeros, nothing kept), and the
    same experts after it.

    ``attend(q, k, v) -> (attn, out)`` is the whole KV step (cache read,
    write and attention; ``out`` is whatever the forward carries or
    collects); None attends the tokens given. Returns (h, out or None).
    Under learned sparse attention (``cfg.index_topk``) the layer's index
    projections go to ``attend`` as a fourth argument and ``out`` is
    ``(out, selection)`` (``_run_stack``).

    Per-layer kinds ride in ``lp`` (``layer_kinds``): ``window`` masks the
    built-in attention (an ``attend`` closure reads it itself) and
    ``rope`` switches the rotary embedding off for a layer. ``row_mask``
    (B,) marks the rows that hold a sequence, for dropless experts;
    ``aux``, a dict, receives what a layer has to say beside its output
    (``experts_touched``). What the configuration adds to a block
    (``qk_norm``, ``attn_gate``, ``post_norms``, a shared expert) is read
    from ``cfg``; whether the layer has experts from its tree.

    Under hyper-connections (``cfg.hc_mult``) ``h`` is the wide stream
    (B, S, hc_mult x D) and the two ``h + out`` below are each a read
    (``hc_pre``: the sublayer's input is a mix of the streams) and a
    write (``hc_post``) of ops/hyper_connection.py, with the sublayer
    between them as it is; ``aux`` receives ``hc_row_defect``.
    """
    B, S, _ = h.shape
    router_logits = None
    experts = "router" in lp        # a leading dense layer has none
    hyper = _hc_args(cfg)
    if hyper:
        stream = h
        h, h_post, h_res = hc.hc_pre(stream, _hc_weights(lp, "attn"),
                                     **hyper)
        defect = None if aux is None else hc.row_defect(h_res)
    if experts and cfg.router_input == "block_input":
        # the router reads the stream as it enters the block
        with jax.named_scope("moe_route"):
            router_logits = _router_logits(h, lp)
    if "gdn_wqkvz" in lp or "kda_wqkv" in lp or "ssd_win" in lp:
        with jax.named_scope(cfg.recurrent_scope + "_proj"):
            x = block_norm(h, lp, "attn_norm", cfg)
        if recur is None:
            mixed, new_cache = _recurrent_mixer(cfg)(x, lp, cfg)[0], None
        else:
            mixed, new_cache = recur(x)
        h = _residual(cfg, h, mixed)
    else:
        with jax.named_scope("attn_proj"):
            x = block_norm(h, lp, "attn_norm", cfg)
            if cfg.index_topk:
                q, k, v, c_q = _latent_qkv(x, lp, cfg, positions, inv_freq,
                                           with_latent=True)
            elif cfg.kv_lora_rank:
                q, k, v = _latent_qkv(x, lp, cfg, positions, inv_freq)
                if cfg.attn_gate == "head":     # one gate a head
                    gate = jax.nn.sigmoid(
                        qmm(x, lp["wz_head"]).astype(jnp.float32))
            else:
                q = qmm(x, lp["wq"])
                k = qmm(x, lp["wk"])
                v = qmm(x, lp["wv"])
                if "bq" in lp:
                    q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
                if cfg.attn_gate:
                    gate = jax.nn.sigmoid(
                        qmm(x, lp["wz"]).astype(jnp.float32))
                # Keep the head split OUT of the matmuls. Without the
                # barrier the TPU compiler folds `reshape(B, S, H, hd)` into
                # each dot and emits a convolution over the head axis whose
                # kernel is the weight viewed [K, H, hd] and wanted K-minor:
                # the whole stacked weight is then transposed into a
                # temporary once a program, and each layer's slice is copied
                # out of it BEFORE its matmul instead of streaming through
                # it. With it the three compile as wo / w_up / w_down do: one
                # fusion that takes (stack, layer index, scale, x), the slice
                # inside, the stack read in place (tests/test_chip_compile.py
                # holds this on the compiled text).
                q, k, v = jax.lax.optimization_barrier((q, k, v))
                q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
                k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
                v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
                if cfg.qk_norm:         # before the rotation: the pool's keys
                    q = rmsnorm(q, lp["q_norm"], cfg.rms_norm_eps)
                    k = rmsnorm(k, lp["k_norm"], cfg.rms_norm_eps)
                if "rope" in lp:
                    qr, kr = apply_rope(q, k, positions, inv_freq)
                    q = jnp.where(lp["rope"], qr, q)
                    k = jnp.where(lp["rope"], kr, k)
                elif not any(cfg.layer_rope):
                    pass        # no layer rotates: nothing of it is traced
                elif cfg.partial_rotary_factor != 1.0:
                    q, k = apply_rope_partial(q, k, positions, inv_freq)
                else:
                    q, k = apply_rope(q, k, positions, inv_freq)
        index = ()
        if cfg.index_topk:
            with jax.named_scope("attn_index"):
                index = (_index_project(x, c_q, lp, cfg, positions, inv_freq),)
        with jax.named_scope("attn"):
            if attend is not None:
                attn, new_cache = attend(q, k, v, *index)
            else:
                attn = kv_cache_of(cfg).attend_tokens(q, k, v, lp, positions,
                                                      kv_valid_len, *index)
                new_cache = None
                if index:       # the layer's selection, for the layers above
                    attn, keep = attn
                    new_cache = (None, keep)
        with jax.named_scope("attn_proj"):
            if cfg.attn_gate == "head":
                gate = jnp.repeat(gate, cfg.v_head_dim, axis=-1)
            attn = attn.reshape(
                B, S, cfg.num_heads * (cfg.v_head_dim or cfg.head_dim))
            if cfg.attn_gate:
                attn = (attn * gate).astype(attn.dtype)
            attn_out = qmm(attn, lp["wo"])
            if "bo" in lp:
                attn_out = attn_out + lp["bo"]
            if cfg.post_norms:
                attn_out = block_norm(attn_out, lp, "post_attn_norm", cfg)
            if not hyper:
                h = _residual(cfg, h, attn_out)
    if hyper:
        stream = hc.hc_post(stream, attn_out, h_post, h_res)
        h, h_post, h_res = hc.hc_pre(stream, _hc_weights(lp, "mlp"),
                                     **hyper)
        if aux is not None:
            aux["hc_row_defect"] = 0.5 * (defect + hc.row_defect(h_res))
    if experts:
        with jax.named_scope("moe_route"):
            x = block_norm(h, lp, "mlp_norm", cfg)
        mlp = _moe_mlp(x, lp, cfg, router_logits, row_mask, aux)
        if cfg.num_shared_experts:
            # every token's own — un-weighted, or under a sigmoid gate
            # of its own (``shared_expert_gate``): a dense MLP like any
            # other
            with jax.named_scope("mlp"), jax.named_scope("moe_shared"):
                shared = _dense_mlp(x, {
                    n: lp["ws_" + n[2:]]
                    for n in ("w_gate", "w_up", "w_down")}, cfg)
                if cfg.shared_expert_gate:
                    gate = jax.nn.sigmoid(jnp.sum(
                        x.astype(jnp.float32)
                        * lp["ws_gate_w"].astype(jnp.float32),
                        axis=-1, keepdims=True))
                    shared = (shared * gate).astype(shared.dtype)
                mlp = mlp + shared
        with jax.named_scope("moe_experts"):
            if cfg.post_norms:
                mlp = block_norm(mlp, lp, "post_mlp_norm", cfg)
            if not hyper:
                return _residual(cfg, h, mlp), new_cache
        return hc.hc_post(stream, mlp, h_post, h_res), new_cache
    with jax.named_scope("mlp"):
        x = block_norm(h, lp, "mlp_norm", cfg)
        mlp = _dense_mlp(x, lp, cfg)
        if cfg.post_norms:
            mlp = block_norm(mlp, lp, "post_mlp_norm", cfg)
        if not hyper:
            return _residual(cfg, h, mlp), new_cache
    return hc.hc_post(stream, mlp, h_post, h_res), new_cache


def _residual(cfg: LlamaConfig, h: jax.Array, out: jax.Array) -> jax.Array:
    """The stream plus what a sub-block writes to it, times the
    configuration's ``residual_multiplier`` (1.0: the plain sum, and
    nothing else in the program)."""
    if cfg.residual_multiplier == 1.0:
        return h + out
    return (h.astype(jnp.float32) + out.astype(jnp.float32)
            * cfg.residual_multiplier).astype(h.dtype)


def _gdn_mixer(x: jax.Array, lp: dict[str, jax.Array], cfg: LlamaConfig,
               state: Optional[jax.Array] = None,
               tail: Optional[jax.Array] = None,
               n_valid: Optional[jax.Array] = None, step_kernel=None):
    """A recurrent layer's token mixer over its normed input ``x`` (B, S,
    D): the gated delta rule (ops/gated_delta.py) behind a causal
    convolution, its output normed a head, gated and projected. Returns
    ``(mixed (B, S, D), state, tail)``.

    ``state`` (B, Hv, dk, dv) and ``tail`` (B, K - 1, channels) are what
    the rows' sequences carried in (None: zeros, a sequence's start).
    ``n_valid`` (B,) is how many of the S tokens belong to the row's
    sequence: the tokens past it — the padding of a chunk's grant, an
    idle row of a decode round (0 of its 1) — decay nothing and write
    nothing (``g = 0``, ``beta = 0``), and the convolution's new tail
    ends at the last valid one. A paged cache forgives garbage rows past
    a length; a state does not. S == 1 is the decode step
    (``gated_delta_step``; ``step_kernel(q, k, v, g, beta) ->
    (o, state)`` in its place where the cache object runs it as the
    kernel over its own leaf: ``state`` is then None and what comes back
    is the cache's), anything longer the chunked scan: ONE Pallas kernel
    where ``gd.scan_kernel_armed`` says so of the shapes (a TPU, whole
    64-token blocks, 128-lane heads in pairs), else the XLA form."""
    B, S, _ = x.shape
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    Ch, f32 = cfg.linear_channels, jnp.float32
    if tail is None:        # a sequence's start
        state = jnp.zeros((B, Hv, dk, dv), f32)
        tail = jnp.zeros((B, cfg.linear_conv_kernel_dim - 1, Ch), x.dtype)
    with jax.named_scope("gdn_proj"):
        qkvz = qmm(x, lp["gdn_wqkvz"])
        ba = qmm(x, lp["gdn_wba"]).astype(f32)
        # the head split stays out of the matmul (``decoder_layer``)
        qkvz, ba = jax.lax.optimization_barrier((qkvz, ba))
        u, z = qkvz[..., :Ch], qkvz[..., Ch:]
    with jax.named_scope("gdn_conv"):
        u, tail = gd.causal_conv(u, tail, lp["gdn_conv"], n_valid)
    step = S == 1
    # the scan as the kernel, where it takes the shapes here
    scan_kernel = not step and gd.scan_kernel_armed(S, Hk, Hv, dk, dv)
    with jax.named_scope("gdn_step" if step else "gdn_scan"):
        q = u[..., :Hk * dk].reshape(B, S, Hk, dk)
        k = u[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk)
        v = u[..., 2 * Hk * dk:].reshape(B, S, Hv, dv)
        q, k = gd.l2norm(q) * dk ** -0.5, gd.l2norm(k)
        if not scan_kernel:
            # a key head serves Hv / Hk consecutive value heads (the
            # kernel reads q and k by key head)
            q, k = (jnp.repeat(a, Hv // Hk, axis=2) for a in (q, k))
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -jnp.exp(lp["gdn_A_log"].astype(f32)) * jax.nn.softplus(
            ba[..., Hv:] + lp["gdn_dt_bias"].astype(f32))
        def scan(q, k, v, g, beta, state):
            if not scan_kernel:
                return gd.gated_delta_chunked(q, k, v, g, beta, state)
            # v read where the convolution left it, no slice in between
            o, new = gd.gated_delta_chunked_kernel(
                q.reshape(B, S, Hk * dk), k.reshape(B, S, Hk * dk), u, g,
                beta, state, v_at=2 * Hk * dk)
            return o.reshape(B, S, Hv, dv), new

        o, new = _recurrence(q, k, v, g, beta, state, n_valid, step_kernel,
                             scan)
    with jax.named_scope("gdn_proj"):
        # the norm over a head's values, its weight applied as it is,
        # times silu(z); heads joined, projected back to the stream
        mixed = _gated_head_norm(o, z, lp["gdn_norm"], lp["gdn_wout"],
                                 jax.nn.silu, cfg.rms_norm_eps, x.dtype)
    return mixed, new, tail


def _recurrence(q, k, v, g, beta, state, n_valid, step_kernel, scan):
    """The delta rule over the S tokens of every row, for either decay:
    ``(o (B, S, H, dv), state)``. What is not the sequence's (``n_valid``:
    ``_gdn_mixer``) decays nothing and writes nothing; S == 1 is the
    decode step — ``step_kernel`` where the cache object runs it over
    its own leaf, else ``gated_delta_step`` with an idle row's state
    left as it was, bit for bit and whatever it holds —, anything longer
    ``scan(q, k, v, g, beta, state)``, the caller's chunked form."""
    S = beta.shape[1]
    if n_valid is not None:
        valid = (jnp.arange(S)[None, :] < n_valid[:, None])[..., None]
        beta, g = jnp.where(valid, beta, 0.0), jnp.where(
            valid if g.ndim == beta.ndim else valid[..., None], g, 0.0)
    if S != 1:
        return scan(q, k, v, g, beta, state)
    if step_kernel is not None:
        o, new = step_kernel(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        return o[:, None], new
    o, new = gd.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], state)
    o = o[:, None]
    if n_valid is not None:
        new = jnp.where((n_valid > 0)[:, None, None, None], new, state)
    return o, new


def _gated_head_norm(o, z, norm, wout, gate, eps, dtype):
    """A recurrent mixer's output: ``o`` (B, S, H, dv) RMS-normed over a
    head's values (the weight applied as it is), times ``gate(z)``, the
    heads joined and projected back to the stream."""
    B, S, H, dv = o.shape
    of = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    y = of * norm.astype(jnp.float32) * gate(
        z.reshape(B, S, H, dv).astype(jnp.float32))
    return qmm(y.reshape(B, S, H * dv).astype(dtype), wout)


def _kda_mixer(x: jax.Array, lp: dict[str, jax.Array], cfg: LlamaConfig,
               state: Optional[jax.Array] = None,
               tail: Optional[jax.Array] = None,
               n_valid: Optional[jax.Array] = None, step_kernel=None):
    """``_gdn_mixer`` for a decay a CHANNEL of a head's keys
    (``cfg.linear_decay`` "channel": Kimi Delta Attention), the same
    arguments, results and rules for what is not the sequence's: q, k, v
    one projection behind the convolution (as many key as value heads),
    the log-decay ``g = floor * sigmoid(exp(A_log) (x W_f + dt_bias))``
    (H x dk a token, float32, in (``cfg.linear_decay_floor``, 0)), the
    write strength ``sigmoid(x W_b)`` a head, the output normed a head
    and gated by ``sigmoid(x W_g)``. S == 1 is the decode step
    (``gated_delta_step`` with the vector decay, or the cache object's
    kernel over its own leaf), anything longer the chunked scan: ONE
    Pallas kernel (``gd.kda_chunked_kernel``) where
    ``gd.kda_scan_kernel_armed`` says so of the shapes (a TPU, whole
    64-token blocks, 128-lane heads in whole groups), else the XLA form
    ``gd.kda_chunked``."""
    B, S, _ = x.shape
    H, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    Ch, f32 = cfg.linear_channels, jnp.float32
    if tail is None:        # a sequence's start
        state = jnp.zeros((B, H, dk, dv), f32)
        tail = jnp.zeros((B, cfg.linear_conv_kernel_dim - 1, Ch), x.dtype)
    with jax.named_scope("kda_proj"):
        u = qmm(x, lp["kda_wqkv"])
        a, b = qmm_f32(x, lp["kda_wf"]), qmm_f32(x, lp["kda_wb"])
        z = qmm(x, lp["kda_wg"])
        # the head split stays out of the matmul (``decoder_layer``)
        u, a, b, z = jax.lax.optimization_barrier((u, a, b, z))
    with jax.named_scope("kda_conv"):
        u, tail = gd.causal_conv(u, tail, lp["kda_conv"], n_valid)
    step = S == 1
    # the scan as the kernel, where it takes the shapes here
    scan_kernel = not step and gd.kda_scan_kernel_armed(S, H, dk, dv)
    with jax.named_scope("kda_step" if step else "kda_scan"):
        q = u[..., :H * dk].reshape(B, S, H, dk)
        k = u[..., H * dk:2 * H * dk].reshape(B, S, H, dk)
        v = u[..., 2 * H * dk:].reshape(B, S, H, dv)
        q, k = gd.l2norm(q) * dk ** -0.5, gd.l2norm(k)
        beta = jax.nn.sigmoid(b)
        rate = jnp.exp(lp["kda_A_log"].astype(f32))[:, None]    # (H, 1)
        g = cfg.linear_decay_floor * jax.nn.sigmoid(
            rate * (a + lp["kda_dt_bias"].astype(f32)).reshape(B, S, H, dk))
        def scan(q, k, v, g, beta, state):
            if not scan_kernel:
                return gd.kda_chunked(q, k, v, g, beta, state)
            # v read where the convolution left it, no slice in between
            o, new = gd.kda_chunked_kernel(
                q.reshape(B, S, H * dk), k.reshape(B, S, H * dk), u,
                g.reshape(B, S, H * dk), beta, state, v_at=2 * H * dk)
            return o.reshape(B, S, H, dv), new

        o, new = _recurrence(q, k, v, g, beta, state, n_valid, step_kernel,
                             scan)
    with jax.named_scope("kda_proj"):
        mixed = _gated_head_norm(o, z, lp["kda_norm"], lp["kda_wout"],
                                 jax.nn.sigmoid, cfg.rms_norm_eps, x.dtype)
    return mixed, new, tail


def _ssd_mixer(x: jax.Array, lp: dict[str, jax.Array], cfg: LlamaConfig,
               state: Optional[jax.Array] = None,
               tail: Optional[jax.Array] = None,
               n_valid: Optional[jax.Array] = None, step_kernel=None):
    """``_gdn_mixer`` for a state-space layer (``cfg.linear_decay``
    "ssd": Mamba-2, ops/ssd.py), the same arguments, results and rules
    for what is not the sequence's (its step size ``dt`` is 0 there: it
    decays nothing and writes nothing): ``z | x B C`` one projection and
    the step's own column a head another, the convolution (with a bias)
    over x, B and C, ``dt = softplus(. + dt_bias)`` (float32, no clamp),
    ``A = -exp(A_log)``, the state (B, H, P, N) — N on the lanes —, the
    output gated by ``silu(z)`` BEFORE one RMS norm over a group's whole
    inner width (``_gated_head_norm`` norms a head and then gates), and
    projected back. S == 1 is the decode step (``ssd.ssd_step``, or the
    cache object's kernel over its own leaf: ``step_kernel(x, dt, A, B,
    C, D) -> (y, state)``), anything longer the chunked form: ONE
    Pallas kernel (``ssd.ssd_chunked_kernel``) where
    ``ssd.scan_kernel_armed`` says so of the shapes (a TPU, whole
    64-token blocks, one group, 64-value heads over 128-lane states),
    else the XLA form ``ssd.ssd_chunked``."""
    B, S, _ = x.shape
    G, H = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    N, P = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    Ch, f32, inner = cfg.linear_channels, jnp.float32, H * P
    if tail is None:        # a sequence's start
        state = jnp.zeros((B, H, P, N), f32)
        tail = jnp.zeros((B, cfg.linear_conv_kernel_dim - 1, Ch), x.dtype)
    with jax.named_scope("ssd_proj"):
        zu = qmm(x, lp["ssd_win"])
        dt = qmm_f32(x, lp["ssd_wdt"])
        # the head split stays out of the matmul (``decoder_layer``)
        zu, dt = jax.lax.optimization_barrier((zu, dt))
        z, u = zu[..., :inner], zu[..., inner:]
    with jax.named_scope("ssd_conv"):
        u, tail = gd.causal_conv(u, tail, lp["ssd_conv"], n_valid,
                                 lp["ssd_conv_b"])
    step = S == 1
    # the scan as the kernel, where it takes the shapes here
    scan_kernel = not step and ssd.scan_kernel_armed(S, H, G, P, N)
    with jax.named_scope("ssd_step" if step else "ssd_scan"):
        xs = u[..., :inner].reshape(B, S, H, P)
        Bm = u[..., inner:inner + G * N].reshape(B, S, G, N)
        Cm = u[..., inner + G * N:].reshape(B, S, G, N)
        dt = jax.nn.softplus(dt + lp["ssd_dt_bias"].astype(f32))
        if n_valid is not None:
            dt = jnp.where((jnp.arange(S)[None, :]
                            < n_valid[:, None])[..., None], dt, 0.0)
        A = -jnp.exp(lp["ssd_A_log"].astype(f32))
        D = lp["ssd_D"].astype(f32)
        if scan_kernel:
            # x read where the convolution left it, no slice in between
            y, new = ssd.ssd_chunked_kernel(u, dt, A, Bm[:, :, 0],
                                            Cm[:, :, 0], D, state)
        elif not step:
            y, new = ssd.ssd_chunked(xs, dt, A, Bm, Cm, D, state)
        elif step_kernel is not None:
            y, new = step_kernel(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                 D)
            y = y[:, None]
        else:
            y, new = ssd.ssd_step(xs[:, 0], dt[:, 0], A, Bm[:, 0],
                                  Cm[:, 0], D, state)
            y = y[:, None]
            if n_valid is not None:     # an idle row's, bit for bit
                new = jnp.where((n_valid > 0)[:, None, None, None], new,
                                state)
    with jax.named_scope("ssd_proj"):
        y = (y.reshape(B, S, inner) * jax.nn.silu(z.astype(f32))).reshape(
            B, S, G, inner // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + cfg.rms_norm_eps)
        y = y.reshape(B, S, inner) * lp["ssd_norm"].astype(f32)
        mixed = qmm(y.astype(x.dtype), lp["ssd_wout"])
    return mixed, new, tail


def _recurrent_mixer(cfg: LlamaConfig):
    """The recurrent layers' token mixer: the member of the family the
    configuration states (``linear_decay``)."""
    return {"head": _gdn_mixer, "channel": _kda_mixer,
            "ssd": _ssd_mixer}[cfg.linear_decay]


def _gdn_recur(cfg: LlamaConfig, load, store=None, step=None,
               n_valid: Optional[jax.Array] = None):
    """A forward's ``recur`` (``_run_stack``) over the cache object's
    ``(load, store, step)`` (models/kv_cache.py ``RecurrentKV.recur``).
    The state's read and write are the recurrence's own and carry its
    scope."""
    mixer = _recurrent_mixer(cfg)

    def recur(x, lp, li, state):
        li = li.reshape(())
        lg = li - cfg.full_before(li)
        scope = cfg.recurrent_scope + (
            "_step" if x.shape[1] == 1 else "_scan")
        with jax.named_scope(scope):
            s0, tail0 = load(lg, state, lp)
        if step is not None:        # the kernel steps the carried leaf
            kernel = lambda *a: step(lg, state, *a)         # noqa: E731
            mixed, state, tail = mixer(x, lp, cfg, None, tail0,
                                       n_valid, kernel)
            s = None
        else:
            mixed, s, tail = mixer(x, lp, cfg, s0, tail0, n_valid)
        if store is None:
            return mixed, (s, tail)
        with jax.named_scope(scope):
            return mixed, store(lg, state, s, tail)
    return recur


def _hc_args(cfg: LlamaConfig) -> dict:
    """What ``hc.hc_pre`` takes of the configuration; empty on the plain
    residual path."""
    if not cfg.hc_mult:
        return {}
    return {"n": cfg.hc_mult, "iters": cfg.hc_sinkhorn_iters,
            "eps": cfg.hc_eps, "clamp": cfg.hc_res_clamp}


def _hc_weights(lp: dict[str, jax.Array], part: str) -> tuple:
    """A sublayer's (``attn`` / ``mlp``) mapping weights out of a layer's
    tree: ``(phi, alpha, b)``."""
    return tuple(lp[f"hc_{part}_{name}"] for name in ("phi", "alpha", "b"))


def _latent_qkv(x: jax.Array, lp: dict[str, jax.Array], cfg: LlamaConfig,
                positions: jax.Array, inv_freq: jax.Array,
                with_latent: bool = False):
    """A latent-attention layer's projections of its normed input ``x``
    (B, S, D): ``(q, c, k_r)`` — the queries (B, S, H, nope + rope)
    through the low-rank pair ``wq_a`` (normed) and ``wq_b`` (or the one
    matrix ``wq``), their rope part rotated; the normed latent (B, S, R); the ONE rotated key part
    (B, S, rope) all heads share. ``(c, k_r)`` is what a token leaves in
    the cache; how the heads' keys and values come out of it, expanded or
    absorbed, is the cache object's (models/kv_cache.py). ``with_latent``
    appends the normed query latent (B, S, Rq), the indexer's input."""
    B, S, _ = x.shape
    R, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    if "wq_a" in lp:
        c_q = rmsnorm(qmm(x, lp["wq_a"]), lp["q_a_norm"], cfg.rms_norm_eps)
        q = qmm(c_q, lp["wq_b"])
    else:       # ONE query matrix and no norm (``q_lora_rank`` 0)
        q = qmm(x, lp["wq"])
    kv = qmm(x, lp["wkv_a"])
    # the head split stays out of the matmul (``decoder_layer`` says why)
    q, kv = jax.lax.optimization_barrier((q, kv))
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    c = rmsnorm(kv[..., :R], lp["kv_a_norm"], cfg.rms_norm_eps)
    q_r, k_r = q[..., nope:], kv[..., None, R:]
    if cfg.rope_interleave:     # pairs (2i, 2i+1) as published
        q_r, k_r = deinterleave(q_r), deinterleave(k_r)
    q_r, k_r = apply_rope(q_r, k_r, positions, inv_freq)
    out = jnp.concatenate([q[..., :nope], q_r], axis=-1), c, k_r[:, :, 0]
    return out + (c_q,) if with_latent else out


def _index_project(x: jax.Array, c_q: jax.Array, lp: dict[str, jax.Array],
                   cfg: LlamaConfig, positions: jax.Array,
                   inv_freq: jax.Array) -> dict:
    """The indexer's projections of a layer (learned sparse attention),
    computed on a FULL layer only — a shared layer takes the zeros of
    the ``cond``'s other branch, which nothing reads:

    - ``q`` (B, S, Hi, di): the query latent ``c_q`` (the main queries'
      own) through ``index_wq``;
    - ``k`` (B, S, di): the block's normed input ``x`` through
      ``index_wk``, LayerNorm (weight and bias, eps 1e-6) — the row the
      token leaves in the index cache;
    - the first ``qk_rope_head_dim`` values of both rotated at the
      token's position with the model's frequencies (pairs (2i, 2i+1)
      under ``index_rope_interleave``);
    - ``w`` (B, S, Hi) float32: ``x`` through ``index_wp``, times
      Hi ** -0.5 * di ** -0.5.

    With them: ``full`` (whether this layer selects), ``layer`` (its
    layer of the pool's index leaf) and ``keep`` (the selection carried
    from below): what the cache object's ``attend_*`` take as
    ``index``."""
    B, S, _ = x.shape
    Hi, di, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    full = lp["index"] >= 0
    at = jnp.maximum(lp["index"] - lp["index_first"], 0)

    def weights(name):
        return jax.lax.dynamic_index_in_dim(lp[name], at, 0, keepdims=False)

    def project(_):
        q = qmm(c_q, weights("index_wq")).reshape(B, S, Hi, di)
        k = qmm(x, weights("index_wk")).astype(jnp.float32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + 1e-6)
        k = (k * weights("index_k_norm").astype(jnp.float32)
             + weights("index_k_norm_b").astype(jnp.float32)
             ).astype(x.dtype)[:, :, None]                  # (B, S, 1, di)
        q_r, k_r = q[..., :rope], k[..., :rope]
        if cfg.index_rope_interleave:
            q_r, k_r = deinterleave(q_r), deinterleave(k_r)
        q_r, k_r = apply_rope(q_r, k_r, positions, inv_freq)
        w = qmm(x, weights("index_wp")).astype(jnp.float32) \
            * (Hi ** -0.5 * di ** -0.5)
        return (jnp.concatenate([q_r, q[..., rope:]], axis=-1),
                jnp.concatenate([k_r, k[..., rope:]], axis=-1)[:, :, 0], w)

    def nothing(_):
        return (jnp.zeros((B, S, Hi, di), x.dtype),
                jnp.zeros((B, S, di), x.dtype),
                jnp.zeros((B, S, Hi), jnp.float32))

    if "index_wq" not in lp:    # a stack without a full layer
        full = jnp.bool_(False)
        q, k, w = nothing(None)
    else:
        q, k, w = jax.lax.cond(full, project, nothing, None)
    return {"q": q, "k": k, "w": w, "full": full,
            "layer": jnp.maximum(lp["index"], 0), "keep": lp["selection"]}


def run_layers(layers: dict[str, jax.Array], cfg: LlamaConfig, h: jax.Array,
               positions: jax.Array,
               kv_valid_len: Optional[jax.Array] = None) -> jax.Array:
    """Scan a (possibly partial) stacked layer stack over hidden states,
    no KV cache — the per-stage body for pipeline parallelism."""
    if cfg.hc_mult or cfg.recurrent or jax.tree.leaves(
            layers)[0].shape[0] != cfg.num_layers:
        # a stage does not know which of the model's layers it holds,
        # and what crosses a stage under hyper-connections is n streams
        _refuse_kinds(cfg, "a pipeline stage's layer stack")
    inv_freq = _inv_freq(cfg)
    return _run_stack(layers, cfg, h, positions, inv_freq, kv_valid_len)[0]


def unembed_norm(params: Params, cfg: LlamaConfig, h: jax.Array
                 ) -> jax.Array:
    """The final-norm half of ``unembed`` — the fused vocab-tiled sampler
    (ops/fused_sampler.py) applies it once and then streams the vocab
    projection itself via ``lm_head_tile``."""
    with jax.named_scope("tail"):
        if cfg.norm == "layernorm1p":
            hn = layernorm1p(h, params["final_norm"],
                             params["final_norm_b"], cfg.rms_norm_eps)
        else:
            hn = rmsnorm(h, params["final_norm"], cfg.rms_norm_eps)
        if cfg.logits_divisor != 1.0:
            # the logits over a stated constant: on the normed row, so
            # every tail's logits (and what penalties, temperature and
            # ``score`` read) are the divided ones
            hn = (hn.astype(jnp.float32)
                  * (1.0 / cfg.logits_divisor)).astype(hn.dtype)
        return hn


# lm_head QTensor leaves sliced along the vocab (output) axis; K-axis
# leaves (pre_scale) pass through whole.
_HEAD_VOCAB_LEAVES = ("q", "q4", "scale", "gscale", "gbias")


def lm_head_subtree(params: Params) -> dict:
    """The unembed-weight leaves as a standalone mini-tree — the
    shard_map operand of the tp-sharded fused sampler
    (ops/fused_sampler.py ``fused_unembed_sample_tp``). Keeps the
    ``lm_head``/``embed`` key so :func:`lm_head_tile` works on the
    LOCAL shard unchanged inside the shard_map body."""
    head = params.get("lm_head")
    if head is None:
        return {"embed": params["embed"]}
    return {"lm_head": head}


def lm_head_specs(params: Params, mesh, axis: str = "tp") -> dict:
    """PartitionSpecs for :func:`lm_head_subtree`, mirroring
    ``parallel.sharding``'s placement rules (vocab axis over ``tp``;
    quantized dicts follow ``shard_params``' per-leaf derivation) — the
    ``in_specs`` of the sharded fused-sampler tail."""
    from jax.sharding import PartitionSpec as P
    tp = axis if int(mesh.shape.get(axis, 1)) > 1 else None
    head = params.get("lm_head")
    # Tied embedding (V, D): vocab is the LEADING axis.
    if head is None:
        return {"embed": P(tp, None)}

    def leaf(k):
        # mirrors shard_params' QTensor rules for w_spec = (None, tp):
        # vocab-axis leaves keep it; the (V,) scale drops the reduction
        # axis; pre_scale (D,) stays replicated.
        if k in ("q", "q4", "gscale", "gbias"):
            return P(None, tp)
        if k == "pre_scale":
            return P(None)
        return P(tp)
    if isinstance(head, dict):
        return {"lm_head": {k: leaf(k) for k in head}}
    return {"lm_head": P(None, tp)}


def lm_head_tile(params: Params, cfg: LlamaConfig, hn: jax.Array,
                 t0: jax.Array, tile: int) -> jax.Array:
    """Project already-normed hidden states onto ONE vocab tile:
    (B, D) x head[:, t0:t0+tile] -> (B, tile) f32.

    Works for every lm_head storage the repo serves — tied embedding
    (V, D), raw (D, V), and quantized dicts (int8/int4/grouped, whose
    packing runs along the reduction axis, so an output-axis slice stays
    a valid QTensor for ops.quant.matmul_f32). Inside a tile scan — the
    sampled, verify and tp-sharded streams, and the greedy one over an
    int4 or grouped head or off the TPU — the slice reads each weight
    byte exactly once per full vocab pass: the same HBM traffic as one
    materialized unembed, with no (B, V) output. The greedy tail over a
    per-column int8, raw or tied head on a TPU does not come here: it is
    one Pallas kernel over the whole stored head
    (ops/head_argmax.py ``greedy_head_argmax``)."""
    head = params.get("lm_head")
    if head is None:
        e = jax.lax.dynamic_slice_in_dim(params["embed"], t0, tile, axis=0)
        return jax.lax.dot_general(
            hn, e, (((hn.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    if isinstance(head, dict):
        sliced = {k: (jax.lax.dynamic_slice_in_dim(v, t0, tile, axis=-1)
                      if k in _HEAD_VOCAB_LEAVES else v)
                  for k, v in head.items()}
        return qmm_f32(hn, sliced)
    return qmm_f32(hn, jax.lax.dynamic_slice_in_dim(head, t0, tile,
                                                    axis=-1))


def unembed(params: Params, cfg: LlamaConfig, h: jax.Array) -> jax.Array:
    """Final norm + output projection: (B, S, D) -> (B, S, V) float32.

    Operands stay compact (bf16/int8) with f32 MXU accumulation — casting
    to f32 first made XLA materialize an f32 copy of the whole vocab
    projection every decode step (ops/quant.py matmul_f32)."""
    h = unembed_norm(params, cfg, h)
    head = params.get("lm_head")
    with jax.named_scope("tail"):
        if head is None:
            return jax.lax.dot_general(
                h, params["embed"], (((h.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        return qmm_f32(h, head)


def apply(params: Params, cfg: LlamaConfig, tokens: jax.Array,
          positions: jax.Array, kv_cache: Optional[KVCache] = None,
          kv_valid_len: Optional[jax.Array] = None, *,
          return_hidden: bool = False,
          ) -> tuple[jax.Array, Optional[KVCache]]:
    """Forward pass. Serves prefill, decode, and training with one function.

    tokens:      (B, S) int32
    positions:   (B, S) int32 absolute positions (row-contiguous).
    kv_cache:    absolute-position cache; new K/V are written at
                 ``positions`` and attention reads the whole cache.
    kv_valid_len:(B,) valid key count per row. Defaults to
                 ``positions[:, -1] + 1`` when a cache is used, else in-seq
                 causal masking only.
    Returns (logits (B,S,V) or hidden (B,S,D), updated cache or None).
    """
    inv_freq = _inv_freq(cfg)
    attend = xs = selection = None
    recur: dict = {}
    kvc = kv_cache_of(cfg)
    if cfg.index_topk:      # the keep mask's shape: queries x keys
        keys = tokens.shape[1] if kv_cache is None \
            else kv_cache[kvc.leaves[0]].shape[2]
        selection = jnp.zeros(tokens.shape + (keys,), bool)
    if kv_cache is not None:
        if kv_valid_len is None:
            kv_valid_len = positions[:, -1] + 1
        row_start = positions[:, 0]
        xs = {"cache_" + n: kv_cache[n] for n in kvc.leaves}
        if cfg.recurrent:
            # a layer's slices of the dense cache ride the scan: the
            # rows' state as the sequence so far left it (zeros at its
            # start), out again as a scan output
            recur["recur"] = _gdn_recur(
                cfg, lambda lg, _, lp: tuple(
                    kvc.from_zeros(lp[name], row_start == 0)
                    for name in _RECURRENT_CACHE),
                n_valid=kv_valid_len - row_start)

        def attend(q, k, v, lp, li, _, *index):
            # this chunk written at its absolute positions
            new = kvc.put_dense(lp, k, v, row_start, *index)  # (B,T,KV,hd)
            attn = kvc.attend_tokens(
                q, new[0], new[1], lp, positions, kv_valid_len,
                *({**ix, "keys": new[2]} for ix in index))
            return _kv_step(attn, new[:2], index, *new[2:])

    h, new, _ = _run_model(
        params, cfg, _embed(params, tokens, cfg.embed_scale), positions,
        inv_freq, kv_valid_len, attend, xs=xs, selection=selection, **recur)
    new_cache = None if new is None else dict(
        zip(kv_cache_of(cfg).leaves, new))
    if return_hidden:
        return unembed_norm(params, cfg, h), new_cache
    return unembed(params, cfg, h), new_cache


def apply_sp(params: Params, cfg: LlamaConfig, tokens: jax.Array,
             positions: jax.Array, mesh) -> jax.Array:
    """Sequence-parallel long-context forward (ring attention).

    Activations are sharded along the sequence axis over the mesh's ``sp``
    axis — per-device activation memory shrinks by ``sp``, which is what
    lets a prefill far beyond one chip's HBM run at all. Attention is
    exact: KV blocks rotate around the ``sp`` ring with ``ppermute``
    (one ICI hop per step, overlapped with compute) and combine via
    online softmax (parallel/ring_attention.py). Everything else in the
    layer — norms, projections, MLP — is per-token, so sequence sharding
    passes through it untouched. Params are replicated across ``sp``
    (and sharded over ``dp`` batch if present).

    The reference has no long-context path to mirror (its TRT engines fix
    max_input_len at build time, conversion_scripts/llama/build.py:96-105);
    this is TPU-native surface. No KV cache is produced — the intended use
    is long-document scoring/training and as the prefill leg of
    long-context serving. tp/ep/pp must be 1 on this mesh (a dp×sp mesh);
    composing sp with in-layer tp is future work and rejected loudly.

    tokens/positions: (B, S) with S divisible by sp. Returns logits
    (B, S, V) float32, sharded (dp, sp) like the inputs.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.ring_attention import ring_gqa_attention

    n_sp = validate_sp_mesh(mesh, tokens.shape[1], "apply_sp")
    _refuse_kinds(cfg, "apply_sp")
    inv_freq = _inv_freq(cfg)
    dp = "dp" if int(mesh.shape.get("dp", 1)) > 1 else None

    def fwd(tokens_l, positions_l, params_l):
        h = jnp.take(params_l["embed"], tokens_l, axis=0)

        def attend(q, k, v, *_):
            return ring_gqa_attention(q, k, v, positions_l,
                                      axis_name="sp", axis_size=n_sp), None

        h = _run_stack(params_l["layers"], cfg, h, positions_l, inv_freq,
                       None, attend)[0]
        return unembed(params_l, cfg, h)

    seq_spec = P(dp, "sp")
    return jax.shard_map(fwd, mesh=mesh,
                         in_specs=(seq_spec, seq_spec, P()),
                         out_specs=P(dp, "sp", None),
                         check_vma=False)(tokens, positions, params)


def _refuse_kinds(cfg: LlamaConfig, fn_name: str) -> None:
    """Paths that scan the raw layer tree (ring attention, which has no
    window mask either; a pipeline stage's partial stack)."""
    if cfg.hc_mult:
        raise NotImplementedError(
            f"{fn_name}: hyper-connections (hc_mult={cfg.hc_mult}) are not "
            f"supported here: the stream between blocks is hc_mult copies "
            f"wide, widened after the embedding and summed before the "
            f"final norm by the forwards that run every stack "
            f"(_run_model)")
    if cfg.recurrent:
        raise NotImplementedError(
            f"{fn_name}: recurrent layers (full_attention_interval="
            f"{cfg.full_attention_interval}) are not supported here: a "
            f"sequence's state is neither passed around a ring nor handed "
            f"from a pipeline stage that does not know which layers it "
            f"holds")
    if layer_kinds(cfg) or cfg.embed_scale != 1.0 or cfg.kv_lora_rank or (
            cfg.num_experts and cfg.moe_impl == "dropless"):
        raise NotImplementedError(
            f"{fn_name}: per-layer kinds, dropless experts (and so a "
            f"second stack and an expert share), latent attention "
            f"(kv_lora_rank) and an embedding multiplier are not "
            f"supported here")


def validate_sp_mesh(mesh, S: int, fn_name: str = "sp") -> int:
    """Shared sp-mesh geometry checks (apply_sp / apply_prefill_sp / the
    engine's construction-time validation): sp > 1, no composed
    tp/ep/pp, sequence divisible by sp. Returns the sp size."""
    n_sp = int(mesh.shape.get("sp", 1))
    if n_sp <= 1:
        raise ValueError(f"{fn_name} needs a mesh with sp > 1")
    for ax in ("tp", "ep", "pp"):
        if int(mesh.shape.get(ax, 1)) != 1:
            raise ValueError(
                f"{fn_name} shards only dp×sp; mesh has {ax}="
                f"{mesh.shape[ax]} (composing sp with {ax} is not "
                f"supported)")
    if S % n_sp:
        raise ValueError(
            f"{fn_name}: sequence length {S} not divisible by sp={n_sp}")
    return n_sp


def apply_prefill_sp(params: Params, cfg: LlamaConfig, tokens: jax.Array,
                     positions: jax.Array, mesh, length: jax.Array,
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sequence-parallel SERVING prefill: ring attention + KV out.

    The sp leg of long-context serving (VERDICT r4 weak #9: ring
    attention drove only score/training): the bucket's activations are
    sharded along the sequence over the mesh's ``sp`` axis — per-device
    prefill activation memory shrinks by ``sp`` — while attention stays
    exact via the KV ring (parallel/ring_attention.py). Unlike
    ``apply_sp`` this RETURNS the per-layer K/V the engine's insert
    scatters into the paged pool, plus the last valid position's logits
    for first-token sampling — full (B, S, V) logits are never
    materialized (at 32k tokens x 32k vocab that transient alone would
    defeat the sharding).

    tokens/positions: (B, S), S divisible by sp; ``length``: () or (B,)
    int32 count of valid tokens (the sample position is length-1; padded
    tail rows produce K/V that the engine's extent accounting never
    attends). Returns ``(k, v, last_logits)`` with k/v
    (L, B, S, KV, hd) sharded over sp along S — the pool scatter
    consumes them without a host round trip — and last_logits (B, V)
    replicated.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.ring_attention import ring_gqa_attention

    B, S = tokens.shape
    n_sp = validate_sp_mesh(mesh, S, "apply_prefill_sp")
    _refuse_kinds(cfg, "apply_prefill_sp")
    inv_freq = _inv_freq(cfg)
    # serving prefill is B=1: batch shards over dp only when divisible,
    # otherwise the dp groups replicate the (identical) work
    n_dp = int(mesh.shape.get("dp", 1))
    dp = "dp" if n_dp > 1 and B % n_dp == 0 else None
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))

    def fwd(tokens_l, positions_l, length_l, params_l):
        h = jnp.take(params_l["embed"], tokens_l, axis=0)

        def attend(q, k, v, *_):
            return ring_gqa_attention(q, k, v, positions_l,
                                      axis_name="sp",
                                      axis_size=n_sp), (k, v)

        h, (ks, vs), *_ = _run_stack(params_l["layers"], cfg, h,
                                     positions_l, inv_freq, None, attend)
        # Last valid position's hidden state: the row lives on exactly
        # one sp shard — mask-select locally, then one psum makes it
        # replicated. (B, D) is tiny; the unembed runs on it outside.
        sel = (positions_l == (length_l[:, None] - 1))
        h_last = jax.lax.psum(
            jnp.sum(jnp.where(sel[..., None], h, 0.0), axis=1), "sp")
        return ks, vs, h_last

    seq_spec = P(dp, "sp")
    k, v, h_last = jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(seq_spec, seq_spec, P(dp), P()),
        out_specs=(P(None, dp, "sp", None, None),
                   P(None, dp, "sp", None, None), P(dp, None)),
        check_vma=False)(tokens, positions, length, params)
    logits = unembed(params, cfg, h_last[:, None])[:, 0]   # (B, V)
    return k, v, logits


@functools.lru_cache(maxsize=8)
def _score_chunk_step(cfg: LlamaConfig):
    """Jitted per-chunk forward, cached per config — a fresh jit wrapper
    per score() call would re-trace the whole model every request."""
    @jax.jit
    def step(params, cache, tok_c, pos_c):
        logits, cache = apply(params, cfg, tok_c, pos_c, cache,
                              kv_valid_len=pos_c[:, -1] + 1)
        return cache, logits
    return step


@functools.lru_cache(maxsize=8)
def _score_full_fn(cfg: LlamaConfig):
    @jax.jit
    def full(params, tokens, positions):
        logits, _ = apply(params, cfg, tokens, positions)
        return logits
    return full


@functools.lru_cache(maxsize=8)
def _score_sp_fn(cfg: LlamaConfig, mesh):
    @jax.jit
    def sp(params, tokens, positions):
        return apply_sp(params, cfg, tokens, positions, mesh)
    return sp


def score(params: Params, cfg: LlamaConfig, tokens: jax.Array, *,
          mesh=None, chunk: int = 2048) -> jax.Array:
    """Per-token negative log-likelihood of a (long) sequence.

    The served consumer of the long-context machinery: scoring/perplexity
    of documents far beyond the engine's serving window. Two paths:

    - **sp mesh** (``mesh`` with sp > 1): one ``apply_sp`` pass — ring
      attention, activations sequence-sharded, so per-device memory is
      ``1/sp`` of the unsharded forward. The path for sequences whose
      activations cannot fit one chip.
    - **single device**: chunked cached forward — chunks of ``chunk``
      tokens stream through ``apply`` against a persistent KV cache, so
      peak activation memory is one chunk's, with exact attention over
      the full prefix. (KV for the whole sequence — rounded UP to a
      power-of-two compile bucket, up to 2x the sequence's own bytes —
      must still fit; that is the boundary where the sp path takes
      over.)

    tokens: (B, S) int32, S >= 2 (position 0 has no prediction).
    Returns (B, S-1) float32 NLL of token t+1 given tokens <= t.
    """
    B, S = tokens.shape
    if S < 2:
        raise ValueError("score needs at least 2 tokens")
    if chunk < 16:
        raise ValueError(f"chunk must be >= 16, got {chunk}")

    def nll_from(logits, targets):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]

    if mesh is not None and int(mesh.shape.get("sp", 1)) > 1:
        # pad to an sp multiple; trailing pad positions are causally
        # invisible to real tokens, and their NLL rows are dropped
        n_sp = int(mesh.shape["sp"])
        S_pad = -(-S // n_sp) * n_sp
        padded = jnp.pad(tokens, ((0, 0), (0, S_pad - S)))
        positions = jnp.broadcast_to(jnp.arange(S_pad, dtype=jnp.int32),
                                     (B, S_pad))
        logits = _score_sp_fn(cfg, mesh)(params, padded, positions)
        return nll_from(logits[:, :S - 1], tokens[:, 1:])

    if S <= chunk:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        logits = _score_full_fn(cfg)(params, tokens, positions)
        return nll_from(logits[:, :-1], tokens[:, 1:])

    # Chunked: pad S up to a chunk multiple so every call shares one
    # compiled shape; the pad region is causally invisible to real tokens
    # (absolute-position cache) and its NLL rows are dropped.
    S_pad = -(-S // chunk) * chunk
    padded = jnp.pad(tokens, ((0, 0), (0, S_pad - S)))
    # The CACHE length is bucketed to powers of two (>= chunk): sizing it
    # to S_pad would give every distinct document length its own
    # compiled per-chunk step — seconds of retrace per length, serial
    # under the server's score gate (r4 advisor finding). Power-of-two
    # buckets bound the compile surface to log2(max_len) shapes per
    # chunk size. The padded cache tail is masked by kv_valid_len
    # (never wrong numerics), but it is NOT free: a document just past a
    # boundary allocates up to 2x its own KV bytes and scans the full
    # bucketed length per chunk — the single-device HBM boundary where
    # the sp path takes over moves correspondingly lower.
    cache_len = chunk
    while cache_len < S_pad:
        cache_len *= 2
    # final_norm is never quantized, so its dtype is the activation dtype
    # (embed may be a QTensor dict on quantized trees)
    cache = init_kv_cache(cfg, B, cache_len, params["final_norm"].dtype)
    step = _score_chunk_step(cfg)

    nll_parts = []
    prev_last = None
    for c0 in range(0, S_pad, chunk):
        tok_c = jax.lax.dynamic_slice_in_dim(padded, c0, chunk, axis=1)
        pos_c = jnp.broadcast_to(
            jnp.arange(c0, c0 + chunk, dtype=jnp.int32), (B, chunk))
        cache, logits = step(params, cache, tok_c, pos_c)
        if prev_last is not None:
            # the previous chunk's final position predicts this chunk's
            # first token — stitch across the boundary
            nll_parts.append(nll_from(prev_last, tok_c[:, :1]))
        nll_parts.append(nll_from(logits[:, :-1], tok_c[:, 1:]))
        prev_last = logits[:, -1:]
    return jnp.concatenate(nll_parts, axis=1)[:, :S - 1]
