"""Weight-only quantization: per-channel int8/int4 + group-wise int4.

Parity point: the reference offers int8 / int4 / int4-AWQ / GPTQ
weight-only engines (reference: conversion/llama.py:81-97
``--quantization int4_awq``, conversion_scripts/llama/build.py:543-580
QuantMode wiring, weight.py:979 GPTQ / :1194 AWQ loaders).
TPU-idiomatic version: weights live in HBM as int8 (int4 packed
two-per-byte), and the matmul consumes them via mixed-dtype dots (per
channel) or per-group partial dots — the MXU still sees bf16 operands,
but HBM traffic and footprint drop 2-4x, which is what matters for
weight-bound decode.

A quantized tensor is a dict leaf:
  int8:        ``{"q":  int8[..., K, N],   "scale": f32[..., N]}``
  int4:        ``{"q4": int8[..., K/2, N], "scale": f32[..., N]}``
  group int4:  ``{"q4": int8[..., K/2, N], "gscale": f32[..., G, N]}``
               + optional ``"gbias"`` f32[..., G, N] (asymmetric zeros,
               GPTQ) and ``"pre_scale"`` f32[..., K] (AWQ activation
               smoothing scale), with G = K / group_size.
(int4 packs two nibbles per byte along the reduction axis, low nibble =
even k.) Every leaf is an array and weight rank is preserved, so one
PartitionSpec tree serves raw and quantized params alike.
"""

from __future__ import annotations

from typing import Any, Union

import os

import jax
import jax.numpy as jnp

QTensor = dict[str, jax.Array]

# Weights quantized by quantize_params; norms/embeddings stay high precision
# (embed doubles as the tied lm_head input and is gather-bound, not
# matmul-bound).
# The matmul weights of a layer stack that quantize (``wz``: the
# attention gate; ``ws_*``: a shared expert — dense matrices like the
# rest). Routed expert stacks (L, E, K, N) stay as they are, and so do
# the router, its bias and every norm.
_QUANT_LAYER_KEYS = ("wq", "wk", "wv", "wo", "wz", "w_gate", "w_up",
                     "w_down", "ws_gate", "ws_up", "ws_down",
                     # latent attention's five (models/configs.py)
                     "wq_a", "wq_b", "wkv_a", "wk_b", "wv_b",
                     # a recurrent layer's two wide ones (its ``gdn_wba``,
                     # 64 columns that set decay and write strength, stays)
                     "gdn_wqkvz", "gdn_wout",
                     # and where the decay is a channel's: q, k and v, the
                     # decay's and the output gate's projections, the
                     # output (``kda_wb``, a column a head, stays)
                     "kda_wqkv", "kda_wf", "kda_wg", "kda_wout",
                     # a state-space layer's two wide ones (``ssd_wdt``,
                     # the step's column a head, stays)
                     "ssd_win", "ssd_wout")
_LAYER_STACKS = ("layers", "dense_layers")


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and (
        ("scale" in w or "gscale" in w) and ("q" in w or "q4" in w))


def is_grouped(w: Any) -> bool:
    return isinstance(w, dict) and "gscale" in w


def quantize_tensor(w: jax.Array, bits: int = 8) -> QTensor:
    """Symmetric per-output-channel quantization over the reduction axis.

    w: (..., K, N) float → q in [-127,127] (int8) or [-7,7] (int4) with
    ``q * scale ≈ w``.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    wf = w.astype(jnp.float32)
    qmax = 127.0 if bits == 8 else 7.0
    absmax = jnp.max(jnp.abs(wf), axis=-2)              # (..., N)
    scale = jnp.maximum(absmax / qmax, 1e-12)
    q = jnp.clip(jnp.round(wf / scale[..., None, :]), -qmax, qmax
                 ).astype(jnp.int8)
    if bits == 4:
        K = q.shape[-2]
        if K % 2:
            raise ValueError(f"int4 needs even reduction dim, got {K}")
        packed = ((q[..., 0::2, :] & 0x0F) | (q[..., 1::2, :] << 4)
                  ).astype(jnp.int8)
        return {"q4": packed, "scale": scale.astype(jnp.float32)}
    return {"q": q, "scale": scale.astype(jnp.float32)}


def _unpack4(q4: jax.Array) -> jax.Array:
    """(..., K/2, N) packed nibbles → (..., K, N) int8."""
    lo = (q4 << 4).astype(jnp.int8) >> 4     # sign-extend low nibble
    hi = q4 >> 4                              # arithmetic shift: high nibble
    out = jnp.stack([lo, hi], axis=-2)        # (..., K/2, 2, N)
    return out.reshape(*q4.shape[:-2], q4.shape[-2] * 2, q4.shape[-1])


def _int_weights(w: QTensor) -> jax.Array:
    return _unpack4(w["q4"]) if "q4" in w else w["q"]


def int_weights_and_scale(w: Union[jax.Array, QTensor]):
    """``(weights, scale)`` of a raw or per-channel-quantized tensor for a
    caller that contracts it its own way: the integers as stored (int4
    unpacked) and the (..., N) column scales, or the raw array and
    None."""
    if not is_quantized(w):
        return w, None
    if is_grouped(w):
        raise ValueError("group-wise quantization has no column scale")
    return _int_weights(w), w["scale"]


def quantize_tensor_grouped(w: jax.Array, group_size: int = 128) -> QTensor:
    """Group-wise symmetric int4 (the AWQ storage format: per-(group, out)
    scales = absmax/8 over each ``group_size`` slice of the reduction
    axis — reference weight.py:1290 ``get_scale``; the activation-aware
    scale *search* needs calibration data and lives in the importer)."""
    K, N = w.shape[-2], w.shape[-1]
    if K % group_size:
        raise ValueError(f"reduction dim {K} not divisible by group "
                         f"{group_size}")
    G = K // group_size
    wf = w.astype(jnp.float32).reshape(*w.shape[:-2], G, group_size, N)
    absmax = jnp.max(jnp.abs(wf), axis=-2)                     # (..., G, N)
    gscale = jnp.maximum(absmax / 7.0, 1e-12)
    q = jnp.clip(jnp.round(wf / gscale[..., None, :]), -7, 7
                 ).astype(jnp.int8)
    q = q.reshape(*w.shape[:-2], K, N)
    packed = ((q[..., 0::2, :] & 0x0F) | (q[..., 1::2, :] << 4)
              ).astype(jnp.int8)
    return {"q4": packed, "gscale": gscale.astype(jnp.float32)}


def dequantize(w: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    q = _int_weights(w).astype(jnp.float32)
    if is_grouped(w):
        K, N = q.shape[-2], q.shape[-1]
        G = w["gscale"].shape[-2]
        qg = q.reshape(*q.shape[:-2], G, K // G, N)
        out = qg * w["gscale"][..., None, :]
        if "gbias" in w:
            out = out + w["gbias"][..., None, :]
        out = out.reshape(q.shape)
        if "pre_scale" in w:
            # fold the activation smoothing scale back for an effective
            # full-precision view: y = (x*s) @ W  ==  x @ (s[:,None]*W)
            out = out * w["pre_scale"][..., :, None]
        return out.astype(dtype)
    return (q * w["scale"][..., None, :]).astype(dtype)


def _use_int4_kernel(w: QTensor) -> bool:
    """Packed-int4 Pallas matmul gate: TPU backend, 2D weight, kernel-
    supported geometry (ops/int4_matmul.py). The XLA path must unpack
    the nibbles to a full int8 tensor inside the decode scan — 5x the
    int4 HBM bytes per step — so the kernel is the difference between
    int4 being a capacity+speed win and a capacity-only trade."""
    if os.environ.get("GENAI_TPU_INT4_KERNEL", "1") == "0":
        return False
    q4 = w["q4"]
    if q4.ndim != 2 or "gbias" in w:
        return False
    from .int4_matmul import supported
    gs = 0
    if is_grouped(w):
        gs = (2 * q4.shape[0]) // w["gscale"].shape[-2]
    return (jax.default_backend() == "tpu"
            and supported(2 * q4.shape[0], q4.shape[1], group_size=gs))


def matmul(x: jax.Array, w: Union[jax.Array, QTensor]) -> jax.Array:
    """``x @ w`` where w may be raw or quantized.

    int8 uses a mixed-dtype dot (bf16 activations x s8 weights,
    accumulated f32): the MXU feed widens s8 tiles on the fly, so HBM
    traffic is the int8 bytes and no full-precision copy of w is ever
    materialized — measured ~2x faster than dequant-then-dot on v5e,
    where XLA hoists the dequant out of the decode step loop and writes
    a bf16 copy of the whole weight. The per-channel scale is applied
    after the matmul (mathematically identical, one multiply per output
    element instead of per weight).

    int4 on TPU routes through the packed-nibble Pallas kernel
    (ops/int4_matmul.py) so HBM sees only the int4 bytes.
    """
    if not is_quantized(w):
        return x @ w
    if "q4" in w and _use_int4_kernel(w):
        from .int4_matmul import int4_matmul
        scale = w["gscale"] if is_grouped(w) else w["scale"]
        # AWQ activation smoothing folds into the inputs; GPTQ's rank-1
        # gbias term is not in the kernel (gated in _use_int4_kernel)
        xin = x * w["pre_scale"] if "pre_scale" in w else x
        return int4_matmul(xin.astype(x.dtype), w["q4"], scale)
    q = _int_weights(w)
    if is_grouped(w):
        return _grouped_matmul(x, q, w)
    dims = (((x.ndim - 1,), (q.ndim - 2,)), ((), ()))
    try:
        y = jax.lax.dot_general(x, q, dims,
                                preferred_element_type=jnp.float32)
    except TypeError:  # backend/version without mixed-dtype dots
        y = jax.lax.dot_general(x, q.astype(x.dtype), dims)
    return (y * w["scale"]).astype(x.dtype)


def matmul_f32(x: jax.Array, w: Union[jax.Array, QTensor]) -> jax.Array:
    """``x @ w`` with float32 output — the logits path.

    Unlike ``matmul`` the result is NOT downcast to the activation dtype,
    and unlike casting operands to f32 up front (which makes XLA
    materialize a full f32 copy of the weight — measured 6.9 ms/step on
    the 7B lm_head, ~25% of decode step time) the operands stay in their
    compact dtypes with f32 MXU accumulation, which is numerically the
    same: bf16/int8 operand values carry no extra mantissa to lose.
    """
    if is_quantized(w) and "q4" in w and _use_int4_kernel(w):
        from .int4_matmul import int4_matmul
        scale = w["gscale"] if is_grouped(w) else w["scale"]
        xin = x * w["pre_scale"] if "pre_scale" in w else x
        return int4_matmul(xin.astype(x.dtype), w["q4"], scale,
                           out_dtype=jnp.float32)
    if is_grouped(w):
        return _grouped_matmul(x, _int_weights(w), w,
                               out_dtype=jnp.float32)
    q = _int_weights(w) if is_quantized(w) else w
    dims = (((x.ndim - 1,), (q.ndim - 2,)), ((), ()))
    try:
        y = jax.lax.dot_general(x, q, dims,
                                preferred_element_type=jnp.float32)
    except TypeError:  # backend/version without mixed-dtype dots
        y = jax.lax.dot_general(x.astype(jnp.float32),
                                q.astype(jnp.float32), dims)
    return y * w["scale"] if is_quantized(w) else y


def _grouped_matmul(x: jax.Array, q: jax.Array, w: QTensor,
                    out_dtype=None) -> jax.Array:
    """Group-wise dequant matmul without materializing the weight:
    per-group partial dots scaled by (G, N) scales, plus a rank-1 bias
    term for asymmetric (GPTQ) zeros:
      y[n] = sum_g dot(x_g, q_g)[n] * s[g,n]  +  sum_g (sum x_g) b[g,n]
    ``out_dtype``: result dtype (default: activation dtype). The logits
    path passes f32 so accumulated values are not rounded through bf16.
    """
    if q.ndim != 2:
        raise ValueError("grouped quantization supports 2D weights only")
    K, N = q.shape
    G = w["gscale"].shape[-2]
    group = K // G
    lead = x.shape[:-1]
    xf = x.astype(jnp.float32)
    if "pre_scale" in w:
        xf = xf * w["pre_scale"]
    xg_f = xf.reshape(-1, G, group)
    xg = xg_f.astype(x.dtype)
    qg = q.reshape(G, group, N)
    try:
        # Mixed-dtype dot (activations x int weights, f32 accumulate), as
        # the int8 path: HBM traffic stays at the int bytes — no f32 copy
        # of the weight (8x the packed size) is ever materialized.
        p = jnp.einsum("bgk,gkn->bgn", xg, qg,
                       preferred_element_type=jnp.float32)
    except TypeError:  # backend/version without mixed-dtype dots
        p = jnp.einsum("bgk,gkn->bgn", xg.astype(jnp.float32),
                       qg.astype(jnp.float32))
    y = jnp.einsum("bgn,gn->bn", p, w["gscale"])
    if "gbias" in w:
        y = y + jnp.einsum("bg,gn->bn", jnp.sum(xg_f, axis=-1), w["gbias"])
    return y.reshape(*lead, N).astype(out_dtype or x.dtype)


def quantize_params(params: Any, mode: str = "int8",
                    group_size: int = 128) -> Any:
    """Quantize a llama param tree's matmul weights in place of the raw
    arrays. ``mode``: int8 | int4 (per-channel) | int4_awq (group-wise
    AWQ storage format; pre-quantized AWQ/GPTQ checkpoints instead load
    their own scales via models/import_quantized.py)."""
    if mode not in ("int8", "int4", "int4_awq"):
        raise ValueError(f"unknown quantization mode {mode!r}")

    def quant(w):
        if mode == "int4_awq":
            # stacked (L, K, N) per-layer weights: group along K per layer
            if w.ndim == 3:
                import jax as _jax
                return _jax.vmap(
                    lambda m: quantize_tensor_grouped(m, group_size))(w)
            return quantize_tensor_grouped(w, group_size)
        return quantize_tensor(w, 8 if mode == "int8" else 4)

    out = dict(params)
    for stack in _LAYER_STACKS:
        if stack not in params:
            continue
        layers = dict(params[stack])
        for key in _QUANT_LAYER_KEYS:
            # MoE expert tensors (L,E,K,N) keep full precision for now —
            # the expert einsums contract differently than plain matmul.
            if (key in layers and not is_quantized(layers[key])
                    and layers[key].ndim <= 3):
                layers[key] = quant(layers[key])
        out[stack] = layers
    if "lm_head" in out and not is_quantized(out["lm_head"]):
        out["lm_head"] = quant(out["lm_head"])
    return out
