"""LoRA fine-tuning: low-rank adapters over the stacked param tree.

The reference covers model customization with NeMo LoRA/SFT notebook
recipes (reference: models/Gemma/lora.ipynb, sft.ipynb — NeMo handles the
adapter math). Here LoRA is first-class and functional: adapters are a
separate small pytree, the forward merges ``W + (alpha/r) * A @ B`` on
the fly inside the loss, and the optimizer steps only the adapters — the
base params stay frozen (and can stay quantized int8/int4, QLoRA-style,
since ``dequantize`` runs inside the merge). Works over any mesh: the
merged weights inherit the base weights' shardings.

Adapter tree shape (stacked like the base): for each target key
``{"a": (L, K, r), "b": (L, r, N)}`` — b zero-init so step 0 is exactly
the base model.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import optax

from .models import llama
from .models.configs import LlamaConfig
from .ops.quant import dequantize, is_quantized
from .training import cross_entropy_loss

DEFAULT_TARGETS = ("wq", "wk", "wv", "wo")

LoraParams = dict[str, dict[str, jax.Array]]


def _weight_shape(w: Any) -> tuple[int, ...]:
    if is_quantized(w):
        K2, N = w["q4"].shape[-2:] if "q4" in w else w["q"].shape[-2:]
        K = K2 * 2 if "q4" in w else K2
        lead = (w["q4"] if "q4" in w else w["q"]).shape[:-2]
        return (*lead, K, N)
    return tuple(w.shape)


def init_lora(cfg: LlamaConfig, base_params: llama.Params, key: jax.Array,
              rank: int = 8, targets: Sequence[str] = DEFAULT_TARGETS,
              dtype: jnp.dtype = jnp.float32) -> LoraParams:
    """Zero-delta init: a ~ N(0, 1/K), b = 0 (the standard LoRA init)."""
    if cfg.kv_lora_rank:
        raise NotImplementedError(
            "LoRA over a latent-attention model (kv_lora_rank): its "
            "projections are already low-rank pairs and none of them is "
            "a target here")
    if cfg.hc_mult:
        raise NotImplementedError(
            f"LoRA over hyper-connection streams (hc_mult={cfg.hc_mult}): "
            f"the train step merges deltas into a tree whose mapping "
            f"weights (hc_*_phi / alpha / b) are no target, and no "
            f"adapter was ever trained against a mixed stream here")
    if cfg.recurrent:
        raise NotImplementedError(
            f"LoRA over a model with recurrent layers "
            f"(full_attention_interval={cfg.full_attention_interval}): the "
            f"targets are stacked over the attention layers only, which "
            f"the merge and the train step do not know, and the recurrent "
            f"mixer's projections (gdn_* / kda_* / ssd_*) are no target")
    lora: LoraParams = {}
    keys = jax.random.split(key, len(targets))
    for k_rng, name in zip(keys, targets):
        if name not in base_params["layers"]:
            raise KeyError(f"unknown LoRA target {name!r}")
        shape = _weight_shape(base_params["layers"][name])
        if len(shape) != 3:
            raise ValueError(f"LoRA target {name!r} must be stacked "
                             f"(L, K, N); got shape {shape}")
        L, K, N = shape
        lora[name] = {
            "a": (jax.random.normal(k_rng, (L, K, rank), jnp.float32)
                  * (K ** -0.5)).astype(dtype),
            "b": jnp.zeros((L, rank, N), dtype),
        }
    return lora


def merge_lora(base_params: llama.Params, lora: LoraParams,
               alpha: float = 16.0) -> llama.Params:
    """Effective params: W + (alpha/r) * a @ b per target. Quantized base
    leaves dequantize for the merge (QLoRA-style serving of a tuned
    adapter over a quantized base)."""
    layers = dict(base_params["layers"])
    for name, ab in lora.items():
        w = layers[name]
        rank = ab["a"].shape[-1]
        scale = alpha / rank
        if is_quantized(w):
            w = dequantize(w, ab["a"].dtype)
        delta = jnp.einsum("lkr,lrn->lkn", ab["a"].astype(jnp.float32),
                           ab["b"].astype(jnp.float32)) * scale
        layers[name] = (w.astype(jnp.float32) + delta).astype(w.dtype)
    return {**base_params, "layers": layers}


def make_lora_train_step(cfg: LlamaConfig,
                         optimizer: optax.GradientTransformation,
                         alpha: float = 16.0):
    """(lora, opt_state, base_params, batch) -> (lora, opt_state, loss).

    Only the adapters receive gradients/updates; jit with donate_argnums
    (0, 1) and the base params as a captured or donated-free argument.
    """

    def loss_fn(lora: LoraParams, base_params: llama.Params,
                batch: dict[str, jax.Array]) -> jax.Array:
        params = merge_lora(base_params, lora, alpha)
        B, S = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        logits, _ = llama.apply(params, cfg, batch["tokens"], positions,
                                kv_valid_len=jnp.sum(batch["mask"],
                                                     axis=-1))
        return cross_entropy_loss(logits, batch["targets"], batch["mask"])

    def train_step(lora: LoraParams, opt_state: Any,
                   base_params: llama.Params, batch: dict[str, jax.Array]):
        loss, grads = jax.value_and_grad(loss_fn)(lora, base_params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, lora)
        lora = optax.apply_updates(lora, updates)
        return lora, opt_state, loss

    return train_step
