"""Checkpoint importers: HF safetensors / torch .bin → the JAX param tree.

The reference builds per-rank TRT engines from HF/Meta/NeMo/FT checkpoints
(reference: conversion_scripts/llama/weight.py:188 ``load_from_hf_llama``,
387 ``load_from_meta_llama``, 587 FT binary; format sniffing in
model_server/model.py:147-173). Here import is rank-free: one logical param
tree is produced and XLA shards it onto the mesh afterwards — there is no
per-rank weight splitting step to reimplement (that was
weight.py:141-148 ``split``).

All projection matrices are transposed to input-major (D, out) and per-layer
tensors are stacked along a leading L axis to match ``models.llama``.
"""

from __future__ import annotations

import os
import re
from typing import Any, Callable, Iterator

import jax.numpy as jnp
import numpy as np

from ..utils.errors import ModelLoadError, UnsupportedFormatError
from .configs import LlamaConfig
from .llama import ATTENTION_WEIGHTS, RECURRENT_PREFIXES, Params

_HF_LAYER_KEYS = {
    "input_layernorm.weight": ("attn_norm", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
}

# A block with four norms, a gated QK-normed attention and a sigmoid router
# beside shared experts (``model_type: afmoe``; cfg.post_norms). Where a
# block has post-norms, ``post_attention_layernorm`` is what it says — the
# norm on the attention's OUTPUT — and the MLP's input norm has a name of
# its own. A leading dense layer's ``mlp.*_proj`` are the plain names above.
_HF_POST_NORM_LAYER_KEYS = {
    "post_attention_layernorm.weight": ("post_attn_norm", False),
    "pre_mlp_layernorm.weight": ("mlp_norm", False),
    "post_mlp_layernorm.weight": ("post_mlp_norm", False),
    "self_attn.q_norm.weight": ("q_norm", False),
    "self_attn.k_norm.weight": ("k_norm", False),
    "self_attn.gate_proj.weight": ("wz", True),
    "mlp.router.gate.weight": ("router", True),
    "mlp.expert_bias": ("router_bias", False),
    "mlp.shared_experts.gate_proj.weight": ("ws_gate", True),
    "mlp.shared_experts.up_proj.weight": ("ws_up", True),
    "mlp.shared_experts.down_proj.weight": ("ws_down", True),
}
# A latent-attention block (DeepseekV3's names; cfg.kv_lora_rank): two
# low-rank pairs with a norm between, and a sigmoid router with a
# selection bias beside shared experts. ``kv_b_proj`` is stored as its two
# halves (``_split_kv_b``).
_HF_LATENT_LAYER_KEYS = {
    "self_attn.q_a_proj.weight": ("wq_a", True),
    "self_attn.q_a_layernorm.weight": ("q_a_norm", False),
    "self_attn.q_b_proj.weight": ("wq_b", True),
    "self_attn.kv_a_proj_with_mqa.weight": ("wkv_a", True),
    "self_attn.kv_a_layernorm.weight": ("kv_a_norm", False),
    "mlp.gate.weight": ("router", True),
    "mlp.gate.e_score_correction_bias": ("router_bias", False),
    "mlp.shared_experts.gate_proj.weight": ("ws_gate", True),
    "mlp.shared_experts.up_proj.weight": ("ws_up", True),
    "mlp.shared_experts.down_proj.weight": ("ws_down", True),
}
# The indexer of learned sparse attention (cfg.index_topk; the published
# DeepSeek-V3.2 / GLM names): on the FULL layers only (cfg.layer_index),
# and stacked over those alone.
_HF_INDEX_LAYER_KEYS = {
    "self_attn.indexer.wq_b.weight": ("index_wq", True),
    "self_attn.indexer.wk.weight": ("index_wk", True),
    "self_attn.indexer.k_norm.weight": ("index_k_norm", False),
    "self_attn.indexer.k_norm.bias": ("index_k_norm_b", False),
    "self_attn.indexer.weights_proj.weight": ("index_wp", True),
}
# The mapping weights of hyper-connections (cfg.hc_mult), a set for each
# of a block's two sublayers. ASSUMED names: no ``xing4_0`` checkpoint or
# modelling code was at hand (benchmarks/configs/xing4.0-29b-a4b.json
# ``assumed.weight_names``); this table is the one place to change.
# ``phi`` is stored (n + n + n^2, n x hidden) like every projection.
_HF_HC_LAYER_KEYS = {
    f"{hf}_hc.{leaf}": (f"hc_{part}_{name}", leaf.endswith(".weight"))
    for hf, part in (("attn", "attn"), ("mlp", "mlp"))
    for leaf, name in (("phi.weight", "phi"), ("alpha", "alpha"),
                       ("bias", "b"))
}
# A model whose layers are not all attention (``model_type: qwen3_next``;
# cfg.full_attention_interval): an attention layer's ``q_proj`` holds the
# output gate too (``_split_q_gate``), a recurrent layer has
# ``linear_attn.*`` in its place (``in_proj_qkvz`` / ``in_proj_ba``
# published grouped by key head: ``_ungroup_qkvz`` / ``_ungroup_ba``),
# and every layer routed experts beside a gated shared one. Every norm
# weight but ``linear_attn.norm`` is published zero-centred and stored
# as ``1 + w`` (``_ZERO_CENTRED``), so the program's RMSNorm stays as it
# is.
_HF_RECURRENT_LAYER_KEYS = {
    "self_attn.q_norm.weight": ("q_norm", False),
    "self_attn.k_norm.weight": ("k_norm", False),
    "linear_attn.dt_bias": ("gdn_dt_bias", False),
    "linear_attn.A_log": ("gdn_A_log", False),
    "linear_attn.norm.weight": ("gdn_norm", False),
    "linear_attn.out_proj.weight": ("gdn_wout", True),
    "mlp.gate.weight": ("router", True),
    "mlp.shared_expert.gate_proj.weight": ("ws_gate", True),
    "mlp.shared_expert.up_proj.weight": ("ws_up", True),
    "mlp.shared_expert.down_proj.weight": ("ws_down", True),
}
_ZERO_CENTRED = ("attn_norm", "mlp_norm", "q_norm", "k_norm")
# A model whose recurrent layers decay a channel at its own rate beside
# latent attention layers (``model_type: bailing_hybrid``;
# cfg.linear_decay "channel"). A KDA layer's leaves, by ``fla``'s
# ``KimiDeltaAttention`` names without the low-rank pairs
# (``no_kda_lora``): q, k and v and their three convolutions are
# published apart and stored side by side (``_KDA_JOINED``). A latent
# layer has DeepseekV3's names with ONE ``q_proj`` and a gate a head
# (``g_proj``: (heads, D)). ASSUMED names: no checkpoint or modelling
# code was at hand (benchmarks/configs/ling-3.0-flash.json
# ``assumed.weight_names``); these tables are the one place to change.
# ``q_proj`` and ``g_proj`` name a leaf of either kind: the layer's index
# says which (``cfg.layer_full``).
_HF_KDA_LAYER_KEYS = {
    "self_attn.f_proj.weight": ("kda_wf", True),
    "self_attn.g_proj.weight": ("kda_wg", True),
    "self_attn.b_proj.weight": ("kda_wb", True),
    "self_attn.A_log": ("kda_A_log", False),
    "self_attn.dt_bias": ("kda_dt_bias", False),
    "self_attn.o_norm.weight": ("kda_norm", False),
    "self_attn.o_proj.weight": ("kda_wout", True),
}
_KDA_JOINED = {     # the tree's leaf: its published parts, in order
    "kda_wqkv": tuple(f"self_attn.{n}_proj.weight" for n in "qkv"),
    "kda_conv": tuple(f"self_attn.{n}_conv1d.weight" for n in "qkv"),
}
# A model whose recurrent layers are state-space layers (``model_type:
# granitemoehybrid``; cfg.linear_decay "ssd") WITHOUT routed experts: a
# Mamba-2 layer's leaves by the published modelling code's names
# (``mamba.*``; ``in_proj`` is ``[z | xBC | dt]`` in one matrix, stored
# taken apart: ``_SSD_SPLITS``), an attention layer's by the plain names
# above, and the block's one dense MLP as ``shared_mlp.input_linear``
# (gate | up in one matrix) and ``shared_mlp.output_linear``.
_HF_SSD_LAYER_KEYS = {
    "mamba.conv1d.bias": ("ssd_conv_b", False),
    "mamba.dt_bias": ("ssd_dt_bias", False),
    "mamba.A_log": ("ssd_A_log", False),
    "mamba.D": ("ssd_D", False),
    "mamba.norm.weight": ("ssd_norm", False),
    "mamba.out_proj.weight": ("ssd_wout", True),
    "shared_mlp.output_linear.weight": ("w_down", True),
}
_HF_LATENT_GATE = "self_attn.g_proj.weight"
_HF_KV_B = "self_attn.kv_b_proj.weight"
# buffers and coefficients the program reads in float32
_FLOAT32_LEAVES = ("router_bias", "hc_attn_alpha", "hc_attn_b",
                   "hc_mlp_alpha", "hc_mlp_b", "gdn_A_log", "gdn_dt_bias",
                   "kda_A_log", "kda_dt_bias", "ssd_A_log", "ssd_dt_bias",
                   "ssd_D")


def _split_q_gate(w: np.ndarray, cfg: LlamaConfig) -> tuple:
    """``q_proj`` (H x 2 hd, D), a head's queries then its gate, as the
    tree holds it: ``wq`` and ``wz`` (D, H x hd) each (cfg.attn_gate as
    it is)."""
    H, hd = cfg.num_heads, cfg.head_dim
    w = w.T.reshape(w.shape[1], H, 2, hd)
    return w[:, :, 0].reshape(-1, H * hd), w[:, :, 1].reshape(-1, H * hd)


def _ungroup_qkvz(w: np.ndarray, cfg: LlamaConfig) -> np.ndarray:
    """``in_proj_qkvz`` (out, D), published a key head at a time — its q,
    its k, its value heads' v, their z — as the tree holds it: (D, [q of
    every head | k | v | z])."""
    Hk, r = cfg.linear_num_key_heads, \
        cfg.linear_num_value_heads // cfg.linear_num_key_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    w = w.T.reshape(w.shape[1], Hk, 2 * dk + 2 * r * dv)
    cuts = np.cumsum([dk, dk, r * dv])
    return np.concatenate([part.reshape(w.shape[0], -1)
                           for part in np.split(w, cuts, axis=-1)], axis=-1)


def _ungroup_ba(w: np.ndarray, cfg: LlamaConfig) -> np.ndarray:
    """``in_proj_ba`` (2 Hv, D), a key head's b's then its a's, as the
    tree holds it: (D, [b of every value head | a])."""
    Hk = cfg.linear_num_key_heads
    w = w.T.reshape(w.shape[1], Hk, 2, -1)
    return np.concatenate([w[:, :, 0].reshape(w.shape[0], -1),
                           w[:, :, 1].reshape(w.shape[0], -1)], axis=-1)


def _split_kv_b(w: np.ndarray, cfg: LlamaConfig) -> tuple:
    """``kv_b_proj`` (H x (nope + v), R) as the tree holds it: ``wk_b``
    (R, H x nope), every head's ``nope`` key columns, and ``wv_b`` (R,
    H x v), its value columns — the absorbed decode reads each alone."""
    H, nope, vd = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    w = w.T.reshape(w.shape[1], H, nope + vd)
    return (w[..., :nope].reshape(w.shape[0], H * nope),
            w[..., nope:].reshape(w.shape[0], H * vd))

# Meta/fairscale checkpoint names (consolidated.*.pth). Values: (name, kind)
# where kind marks the extra transform — "q"/"k" rows additionally need the
# interleaved→half-split RoPE permutation to match ops.rope's HF convention.
_META_LAYER_KEYS = {
    "attention_norm.weight": ("attn_norm", "plain"),
    "ffn_norm.weight": ("mlp_norm", "plain"),
    "attention.wq.weight": ("wq", "q"),
    "attention.wk.weight": ("wk", "k"),
    "attention.wv.weight": ("wv", "T"),
    "attention.wo.weight": ("wo", "T"),
    "feed_forward.w1.weight": ("w_gate", "T"),
    "feed_forward.w2.weight": ("w_down", "T"),
    "feed_forward.w3.weight": ("w_up", "T"),
}


def _unpermute_rope(w: np.ndarray, n_heads: int, head_dim: int) -> np.ndarray:
    """Meta stores q/k rows in interleaved RoPE pair order; HF (and our
    ``ops.rope``) uses the half-split layout. Same permutation HF's own
    conversion script applies (transformers convert_llama_weights_to_hf)."""
    out_dim, in_dim = w.shape
    return (w.reshape(n_heads, head_dim // 2, 2, in_dim)
             .transpose(0, 2, 1, 3)
             .reshape(out_dim, in_dim))


# Mixtral MoE tensor names (block_sparse_moe.*).
_HF_MOE_GATE = "block_sparse_moe.gate.weight"
_MOE_EXPERT_RE = re.compile(
    r"block_sparse_moe\.experts\.(\d+)\.w([123])\.weight")
# Mixtral: w1=gate, w3=up, w2=down.
_MOE_W_TO_NAME = {"1": "w_gate", "3": "w_up", "2": "w_down"}
# ... and experts named as a dense MLP's projections (afmoe)
_MOE_PROJ_RE = re.compile(
    r"mlp\.experts\.(\d+)\.(gate|up|down)_proj\.weight")


def detect_checkpoint_format(path: str) -> str:
    """Sniff a checkpoint dir by file extensions.

    Parity with the reference's format sniffing
    (reference: model_server/model.py:147-173 — NEMO/PYTORCH/HUGGINGFACE/ONNX
    by extension). We recognize: 'safetensors', 'pytorch_bin', 'meta_pth'.
    """
    names = os.listdir(path)
    if any(n.endswith(".nemo") for n in names):
        return "nemo"
    from .import_quantized import sniff_quantized_format
    qfmt = sniff_quantized_format(path) \
        if any(n.endswith((".safetensors", ".pt", ".bin"))
               for n in names) else ""
    if qfmt:
        return qfmt  # 'gptq' | 'awq'
    if any(n.endswith(".safetensors") for n in names):
        return "safetensors"
    if any(re.match(r"pytorch_model.*\.bin$", n) for n in names):
        return "pytorch_bin"
    if any(n.endswith((".pth", ".pt")) for n in names):
        return "meta_pth"
    raise UnsupportedFormatError(
        f"no recognized checkpoint files in {path}: {sorted(names)[:10]}")


def _iter_safetensors(path: str) -> Iterator[tuple[str, np.ndarray]]:
    from safetensors import safe_open
    for fname in sorted(os.listdir(path)):
        if not fname.endswith(".safetensors"):
            continue
        with safe_open(os.path.join(path, fname), framework="np") as f:
            for key in f.keys():
                yield key, f.get_tensor(key)


def _iter_torch_bin(path: str) -> Iterator[tuple[str, np.ndarray]]:
    import torch
    for fname in sorted(os.listdir(path)):
        if not re.match(r"pytorch_model.*\.bin$", fname):
            continue
        sd = torch.load(os.path.join(path, fname), map_location="cpu",
                        weights_only=True)
        for key, t in sd.items():
            yield key, t.to(torch.float32).numpy()


# Fairscale TP shard axis per Meta tensor (None = replicated). Matches the
# concat dims HF's convert_llama_weights_to_hf uses when merging
# consolidated.*.pth shards: column-parallel weights shard dim 0,
# row-parallel dim 1, ParallelEmbedding shards the embedding dim.
_META_SHARD_DIM = {
    "tok_embeddings.weight": 1,
    "output.weight": 0,
    "norm.weight": None,
    "attention_norm.weight": None,
    "ffn_norm.weight": None,
    "attention.wq.weight": 0,
    "attention.wk.weight": 0,
    "attention.wv.weight": 0,
    "attention.wo.weight": 1,
    "feed_forward.w1.weight": 0,
    "feed_forward.w2.weight": 1,
    "feed_forward.w3.weight": 0,
}


def _meta_shard_dim(key: str) -> int | None:
    suffix = re.sub(r"^layers\.\d+\.", "", key)
    if suffix not in _META_SHARD_DIM:
        raise UnsupportedFormatError(
            f"unknown Meta checkpoint tensor {key!r}: cannot determine its "
            f"fairscale shard axis")
    return _META_SHARD_DIM[suffix]


def _iter_meta_pth(path: str) -> Iterator[tuple[str, np.ndarray]]:
    """Meta/fairscale checkpoints: merge consolidated.*.pth TP shards.

    Every shard holds the SAME tensor names, split along per-tensor TP axes
    (reference: conversion_scripts/llama/weight.py:387 ``load_from_meta_llama``
    re-shards them per rank; HF's convert script concatenates the same way).
    A single-file checkpoint passes through unchanged."""
    import torch
    files = sorted(f for f in os.listdir(path) if f.endswith((".pth", ".pt")))
    # mmap keeps the shards page-backed: a 70B checkpoint is 8 x ~17 GB, far
    # beyond host RAM if loaded eagerly; only the tensors being concatenated
    # become resident.
    shards = [torch.load(os.path.join(path, f), map_location="cpu",
                         weights_only=True, mmap=True) for f in files]
    for key in shards[0]:
        if key == "rope.freqs":  # precomputed buffer, not a weight
            continue
        parts = [s[key] for s in shards]
        if len(parts) == 1:
            yield key, parts[0].to(torch.float32).numpy()
            continue
        dim = _meta_shard_dim(key)
        if dim is None:
            yield key, parts[0].to(torch.float32).numpy()
        else:
            yield key, torch.cat(parts, dim=dim).to(torch.float32).numpy()


def _to_numpy(t: Any) -> np.ndarray:
    if isinstance(t, np.ndarray):
        return t
    # torch tensor (possibly bf16) without importing torch at module scope
    import torch
    if isinstance(t, torch.Tensor):
        return t.detach().to(torch.float32).cpu().numpy()
    return np.asarray(t)


_RECURRENT_SPLITS = {
    "self_attn.q_proj.weight": lambda w, cfg: dict(
        zip(("wq", "wz"), _split_q_gate(w, cfg))),
    "linear_attn.in_proj_qkvz.weight": lambda w, cfg: {
        "gdn_wqkvz": _ungroup_qkvz(w, cfg)},
    "linear_attn.in_proj_ba.weight": lambda w, cfg: {
        "gdn_wba": _ungroup_ba(w, cfg)},
    "linear_attn.conv1d.weight": lambda w, cfg: {"gdn_conv": w[:, 0, :]},
    "mlp.shared_expert_gate.weight": lambda w, cfg: {"ws_gate_w": w[0]},
}


def _split_in_proj(w: np.ndarray, cfg: LlamaConfig) -> dict:
    """``mamba.in_proj`` (z + xBC + dt, D) as its two stored leaves."""
    wide = cfg.linear_num_value_heads * cfg.linear_value_head_dim \
        + cfg.linear_channels
    if w.shape[0] != wide + cfg.linear_num_value_heads:
        raise ModelLoadError(
            f"mamba.in_proj has {w.shape[0]} rows; z | xBC | dt of this "
            f"configuration are {wide + cfg.linear_num_value_heads}")
    return {"ssd_win": w[:wide].T, "ssd_wdt": w[wide:].T}


_SSD_SPLITS = {
    "mamba.in_proj.weight": _split_in_proj,
    "mamba.conv1d.weight": lambda w, cfg: {"ssd_conv": w[:, 0, :]},
    "shared_mlp.input_linear.weight": lambda w, cfg: {
        "w_gate": w[:w.shape[0] // 2].T, "w_up": w[w.shape[0] // 2:].T},
}


def params_from_named_tensors(
        tensors: Iterator[tuple[str, Any]], cfg: LlamaConfig,
        dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Assemble the stacked param tree from HF-named tensors.

    Accepts names with or without the leading ``model.`` prefix. The
    layers are split into the model's stacks (``cfg.layer_stacks``): the
    leading dense layers of an expert model are a tree of their own.
    """
    L = cfg.num_layers
    hf_keys = dict(_HF_LAYER_KEYS)
    if cfg.post_norms:
        hf_keys.update(_HF_POST_NORM_LAYER_KEYS)
    if cfg.kv_lora_rank:
        hf_keys.update(_HF_LATENT_LAYER_KEYS)
    if cfg.index_topk:
        hf_keys.update(_HF_INDEX_LAYER_KEYS)
    if cfg.hc_mult:
        hf_keys.update(_HF_HC_LAYER_KEYS)
    recurrent = cfg.recurrent
    channel = cfg.linear_decay == "channel"
    # the published conventions of the model whose decay is a head's:
    # grouped projections, zero-centred norms
    grouped = recurrent and cfg.linear_decay == "head"
    splits = _RECURRENT_SPLITS if grouped else {}
    if grouped:
        hf_keys.update(_HF_RECURRENT_LAYER_KEYS)
    elif recurrent and not channel:     # state-space layers
        hf_keys.update(_HF_SSD_LAYER_KEYS)
        splits = _SSD_SPLITS
    kda_parts: dict[tuple, np.ndarray] = {}
    # an expert share keeps the experts it holds, numbered from its first
    first_expert, held = cfg.experts_first, cfg.held_experts
    layer_acc: dict[str, list] = {}
    top: dict[str, Any] = {}

    def put_layer(name: str, idx: int, value: np.ndarray, extra: int | None = None):
        if extra is None:
            layer_acc.setdefault(name, [None] * L)[idx] = value
        else:  # MoE expert tensors: [layer][expert]
            extra -= first_expert
            if not 0 <= extra < held:
                return              # an expert held elsewhere
            acc = layer_acc.setdefault(name, [None] * L)
            if acc[idx] is None:
                acc[idx] = [None] * held
            acc[idx][extra] = value

    for key, raw in tensors:
        key = key.removeprefix("model.")
        arr = _to_numpy(raw)
        if key in ("embed_tokens.weight", "tok_embeddings.weight"):
            top["embed"] = arr
            continue
        if key == "norm.weight":
            top["final_norm"] = arr + 1 if grouped else arr
            continue
        if key in ("lm_head.weight", "output.weight"):
            top["lm_head"] = arr.T
            continue
        m = re.match(r"layers\.(\d+)\.(.+)$", key)
        if not m:
            continue  # rotary inv_freq buffers etc.
        idx, rest = int(m.group(1)), m.group(2)
        if rest in splits:
            for name, part in splits[rest](arr, cfg).items():
                put_layer(name, idx, part)
            continue
        if channel and not cfg.layer_full[idx]:     # a KDA layer's own
            if rest in _HF_KDA_LAYER_KEYS:
                name, transpose = _HF_KDA_LAYER_KEYS[rest]
                # (``A_log`` is published (1, 1, heads, 1))
                put_layer(name, idx, arr.T if transpose else arr.reshape(
                    -1) if name in _FLOAT32_LEAVES else arr)
                continue
            joined = next((n for n, parts in _KDA_JOINED.items()
                           if rest in parts), None)
            if joined:
                kda_parts[idx, rest] = arr
                parts = [kda_parts.get((idx, r)) for r in _KDA_JOINED[joined]]
                if all(x is not None for x in parts):
                    put_layer(joined, idx, np.concatenate(
                        [x[:, 0, :] for x in parts]) if joined == "kda_conv"
                        else np.concatenate(parts).T)
                continue
        elif channel and rest == _HF_LATENT_GATE:
            put_layer("wz_head", idx, arr.T)
            continue
        if rest in hf_keys:
            name, transpose = hf_keys[rest]
            if grouped and name in _ZERO_CENTRED:
                arr = arr + 1
            put_layer(name, idx, arr.T if transpose else arr)
            continue
        if rest in _META_LAYER_KEYS:
            name, kind = _META_LAYER_KEYS[rest]
            if kind == "q":
                arr = _unpermute_rope(arr, cfg.num_heads, cfg.head_dim).T
            elif kind == "k":
                arr = _unpermute_rope(arr, cfg.num_kv_heads, cfg.head_dim).T
            elif kind == "T":
                arr = arr.T
            put_layer(name, idx, arr)
            continue
        if rest == _HF_MOE_GATE:
            put_layer("router", idx, arr.T)
            continue
        if rest == _HF_KV_B and cfg.kv_lora_rank:
            for name, half in zip(("wk_b", "wv_b"), _split_kv_b(arr, cfg)):
                put_layer(name, idx, half)
            continue
        em = _MOE_EXPERT_RE.match(rest)
        if em:
            put_layer(_MOE_W_TO_NAME[em.group(2)], idx, _to_numpy(raw).T,
                      extra=int(em.group(1)))
            continue
        em = _MOE_PROJ_RE.match(rest)
        if em:
            put_layer("w_" + em.group(2), idx, _to_numpy(raw).T,
                      extra=int(em.group(1)))
            continue

    def stacked(first: int, n: int) -> tuple[dict, list]:
        """The leaves the layers [first, first + n) hold, stacked, and
        the names some of them lack."""
        layers, missing = {}, []
        for name, per_layer in layer_acc.items():
            part = per_layer[first:first + n]
            indexer = name.startswith("index_")
            if indexer:     # the stack's full layers only, and all of them
                part = [x for x, f in zip(
                    part, cfg.layer_index[first:first + n]) if f]
            elif recurrent and (name.startswith(RECURRENT_PREFIXES)
                                or name in ATTENTION_WEIGHTS):
                # a mixer's leaves over the layers of its kind alone
                part = [x for x, f in zip(part,
                                          cfg.layer_full[first:first + n])
                        if f == (name in ATTENTION_WEIGHTS)]
            if all(x is None for x in part) and not (indexer and part):
                continue            # not a leaf of this stack's layers
            if any(x is None or (isinstance(x, list)
                                 and any(e is None for e in x))
                   for x in part):
                missing.append(name)
                continue
            if isinstance(part[0], list):   # MoE: [L][E] → (L,E,...)
                part = [np.stack(e, axis=0) for e in part]
            layers[name] = jnp.asarray(
                np.stack(part, axis=0),
                jnp.float32 if name in _FLOAT32_LEAVES else dtype)
        return layers, missing

    stacks = {name: stacked(first, n) for name, first, n in cfg.layer_stacks}
    missing = [k for _, lacking in stacks.values() for k in lacking]
    if missing or "embed" not in top or "final_norm" not in top:
        raise ModelLoadError(
            f"incomplete checkpoint: missing embed/final_norm or layer "
            f"tensors ({sorted(set(missing))[:5]}...)")

    params: Params = {
        "embed": jnp.asarray(top["embed"], dtype),
        **{name: layers for name, (layers, _) in stacks.items()},
        "final_norm": jnp.asarray(top["final_norm"], dtype),
    }
    if "lm_head" in top:
        params["lm_head"] = jnp.asarray(top["lm_head"], dtype)
    elif not cfg.tie_word_embeddings:
        raise ModelLoadError("checkpoint has no lm_head and config does not "
                             "tie word embeddings")
    return params


def bailing_hybrid_config(hf: dict, *, num_layers: int | None = None,
                          experts_held: int = 0, experts_first: int = 0,
                          **more) -> LlamaConfig:
    """The ``LlamaConfig`` of a published ``bailing_hybrid``
    ``config.json`` (``hf``, its keys as published), cut to the first
    ``num_layers`` layers and to an expert share where asked. What a
    key's NAME alone decides is listed in
    benchmarks/configs/ling-3.0-flash.json ``assumed``. Refuses BY NAME
    what the program has no form for: an activation limit
    (``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``)
    that is not 0 on a layer held — the clamp's form is not published
    with the key —, KDA's low-rank pairs, and the switches the published
    model leaves off."""
    L = int(num_layers or hf["num_hidden_layers"])
    for name in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        hit = [i for i, x in enumerate((hf.get(name) or [])[:L]) if x]
        if hit:
            raise ModelLoadError(
                f"{name}: a non-zero activation limit on layers {hit} of "
                f"the {L} held is not supported (the clamp's form is not "
                f"given by the key)")
    off = ("use_kda_lora", "value_norm", "up_proj_norm", "use_nGPT",
           "scale_router_input", "use_mla_nope", "use_bias", "use_qkv_bias")
    on = [k for k in off if hf.get(k)] + [
        k for k in ("no_kda_lora", "kda_safe_gate", "linear_silu",
                    "use_qk_norm", "norm_topk_prob",
                    "moe_router_enable_expert_bias") if not hf.get(k, True)]
    if on or hf.get("q_lora_rank") or hf.get("rope_scaling") or hf.get(
            "gated_attention_proj_granularity_type") != "head_wise":
        raise ModelLoadError(
            f"bailing_hybrid: {on or 'q_lora_rank / rope_scaling / gate'} "
            f"states a variant of the block that is not supported")
    heads = hf.get("num_kv_heads_for_linear_attn") or hf["num_attention_heads"]
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    return LlamaConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"], num_layers=L,
        num_dense_layers=hf["first_k_dense_replace"],
        num_heads=hf["num_attention_heads"], num_kv_heads=1,
        head_dim=nope + rope,
        max_position_embeddings=hf["max_position_embeddings"],
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        num_shared_experts=hf["num_shared_experts"],
        experts_held=experts_held, experts_first=experts_first,
        moe_impl="dropless", router_score_func="sigmoid",
        router_norm_topk=True,
        router_scale=float(hf["routed_scaling_factor"]),
        router_bias="selection", n_group=hf["n_group"],
        topk_group=hf["topk_group"], kv_lora_rank=hf["kv_lora_rank"],
        q_lora_rank=0, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        v_head_dim=hf["v_head_dim"],
        rope_interleave=bool(hf["rope_interleave"]), attn_gate="head",
        full_attention_interval=hf["layer_group_size"],
        linear_num_key_heads=heads, linear_num_value_heads=heads,
        linear_key_head_dim=hf["head_dim"],
        linear_value_head_dim=hf["head_dim"],
        linear_conv_kernel_dim=hf["short_conv_kernel_size"],
        linear_decay="channel",
        linear_decay_floor=float(hf["kda_lower_bound"]),
        tie_word_embeddings=bool(hf["tie_word_embeddings"]), **more)


def granitemoehybrid_config(hf: dict, **more) -> LlamaConfig:
    """The ``LlamaConfig`` of a published ``granitemoehybrid``
    ``config.json`` (``hf``, its keys as published). What no key states
    is listed in benchmarks/configs/granite-4.0-h-micro.json
    ``assumed``. Refuses BY NAME what the program has no form for:
    routed experts beside the block's dense MLP (``num_local_experts``
    > 0), biases, a rotary embedding beside the state-space layers, and
    attention layers that do not sit at ONE place of a period."""
    types = list(hf["layer_types"])
    full = [i for i, t in enumerate(types) if t == "attention"]
    L = int(hf["num_hidden_layers"])
    if hf.get("num_local_experts"):
        raise ModelLoadError(
            f"granitemoehybrid: num_local_experts="
            f"{hf['num_local_experts']}: routed experts beside the "
            f"block's dense MLP are not supported")
    want = {"attention_bias": False, "mamba_proj_bias": False,
            "position_embedding_type": "nope", "mamba_conv_bias": True,
            "hidden_act": "silu"}
    stated = [k for k, v in want.items() if hf.get(k, v) != v]
    if stated:
        raise ModelLoadError(
            f"granitemoehybrid: {stated} states a variant of the block "
            f"that is not supported")
    if len(types) != L or len(full) < 1 or len(full) == L:
        raise ModelLoadError("granitemoehybrid: layer_types names no "
                             "state-space or no attention layer")
    period = full[1] - full[0] if len(full) > 1 else L
    place = full[0]
    if place >= period or full != list(range(place, L, period)):
        raise ModelLoadError(
            f"granitemoehybrid: attention layers {full} do not sit at one "
            f"place of a period")
    heads, p = hf["mamba_n_heads"], hf["mamba_d_head"]
    if heads * p != hf["mamba_expand"] * hf["hidden_size"]:
        raise ModelLoadError("granitemoehybrid: mamba_n_heads x "
                             "mamba_d_head is not mamba_expand x hidden_size")
    return LlamaConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["shared_intermediate_size"], num_layers=L,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        max_position_embeddings=hf["max_position_embeddings"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=hf["rms_norm_eps"],
        tie_word_embeddings=bool(hf["tie_word_embeddings"]),
        rope_layers=(0,), embed_scale=float(hf["embedding_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        attention_multiplier=float(hf["attention_multiplier"]),
        logits_divisor=float(hf["logits_scaling"]),
        full_attention_interval=period, full_attention_place=place,
        linear_num_key_heads=hf["mamba_n_groups"],
        linear_num_value_heads=heads,
        linear_key_head_dim=hf["mamba_d_state"], linear_value_head_dim=p,
        linear_conv_kernel_dim=hf["mamba_d_conv"], linear_decay="ssd",
        **more)


def load_checkpoint(path: str, cfg: LlamaConfig,
                    dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Load a checkpoint directory (sniffs format)."""
    fmt = detect_checkpoint_format(path)
    if fmt in ("gptq", "awq"):
        from .import_quantized import load_quantized_checkpoint
        return load_quantized_checkpoint(path, cfg, dtype, fmt=fmt)
    if fmt == "nemo":
        from .import_nemo import load_nemo_checkpoint
        return load_nemo_checkpoint(path, cfg, dtype)
    iters: dict[str, Callable[[str], Iterator[tuple[str, np.ndarray]]]] = {
        "safetensors": _iter_safetensors,
        "pytorch_bin": _iter_torch_bin,
        "meta_pth": _iter_meta_pth,
    }
    return params_from_named_tensors(iters[fmt](path), cfg, dtype)


def params_from_hf_model(model: Any, cfg: LlamaConfig,
                         dtype: jnp.dtype = jnp.float32) -> Params:
    """Convert an in-memory ``transformers`` Llama/Mixtral model (used by the
    golden-parity tests)."""
    sd = model.state_dict()
    return params_from_named_tensors(iter(sd.items()), cfg, dtype)
