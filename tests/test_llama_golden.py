"""Golden-parity tests: JAX Llama vs transformers on CPU.

The reference has no engine-correctness tests at all (SURVEY.md §4); its
parity story is manual smoke tests. Here every model change is gated on
logit parity with the HF implementation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LLAMA_TINY, LlamaConfig
from generativeaiexamples_tpu.models.import_hf import params_from_hf_model

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


@pytest.fixture(scope="module")
def hf_model_and_params():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=LLAMA_TINY.vocab_size,
        hidden_size=LLAMA_TINY.hidden_size,
        intermediate_size=LLAMA_TINY.intermediate_size,
        num_hidden_layers=LLAMA_TINY.num_layers,
        num_attention_heads=LLAMA_TINY.num_heads,
        num_key_value_heads=LLAMA_TINY.num_kv_heads,
        max_position_embeddings=LLAMA_TINY.max_position_embeddings,
        rms_norm_eps=LLAMA_TINY.rms_norm_eps,
        rope_theta=LLAMA_TINY.rope_theta,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg).eval()
    params = params_from_hf_model(model, LLAMA_TINY, dtype=jnp.float32)
    return model, params


def hf_logits(model, tokens: np.ndarray) -> np.ndarray:
    with torch.no_grad():
        out = model(torch.from_numpy(tokens).long())
    return out.logits.float().numpy()


def test_forward_matches_hf(hf_model_and_params):
    model, params = hf_model_and_params
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, LLAMA_TINY.vocab_size, size=(2, 17), dtype=np.int32)
    positions = np.broadcast_to(np.arange(17, dtype=np.int32), (2, 17))

    ours, _ = llama.apply(params, LLAMA_TINY, jnp.asarray(tokens),
                          jnp.asarray(positions))
    theirs = hf_logits(model, tokens)
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=2e-4, atol=2e-4)


def test_gqa_grouping_is_nontrivial():
    # LLAMA_TINY must actually exercise GQA (H != KV) for the golden test
    # to cover the grouped path.
    assert LLAMA_TINY.num_heads != LLAMA_TINY.num_kv_heads


def test_kv_cache_decode_matches_full_forward(hf_model_and_params):
    """Prefill+decode through the cache must equal the full forward."""
    _, params = hf_model_and_params
    cfg = LLAMA_TINY
    rng = np.random.default_rng(1)
    B, S_total, S_prefill = 2, 12, 8
    tokens = rng.integers(0, cfg.vocab_size, size=(B, S_total), dtype=np.int32)
    all_pos = np.broadcast_to(np.arange(S_total, dtype=np.int32), (B, S_total))

    full_logits, _ = llama.apply(params, cfg, jnp.asarray(tokens),
                                 jnp.asarray(all_pos))

    cache = llama.init_kv_cache(cfg, B, max_len=32, dtype=jnp.float32)
    pre_logits, cache = llama.apply(
        params, cfg, jnp.asarray(tokens[:, :S_prefill]),
        jnp.asarray(all_pos[:, :S_prefill]), cache)
    np.testing.assert_allclose(np.asarray(pre_logits),
                               np.asarray(full_logits[:, :S_prefill]),
                               rtol=1e-4, atol=1e-4)

    step = jax.jit(lambda p, *a: llama.apply(p, cfg, *a))   # one trace
    for t in range(S_prefill, S_total):
        step_logits, cache = step(
            params, jnp.asarray(tokens[:, t:t + 1]),
            jnp.asarray(all_pos[:, t:t + 1]), cache)
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(full_logits[:, t]),
                                   rtol=1e-4, atol=1e-4)


def test_ragged_batch_padding_invariance(hf_model_and_params):
    """A short row padded inside a longer batch must produce the same
    logits as the same row alone (mask correctness)."""
    _, params = hf_model_and_params
    cfg = LLAMA_TINY
    rng = np.random.default_rng(2)
    short = rng.integers(0, cfg.vocab_size, size=(1, 5), dtype=np.int32)
    long_ = rng.integers(0, cfg.vocab_size, size=(1, 9), dtype=np.int32)

    pos5 = np.arange(5, dtype=np.int32)[None]
    alone, _ = llama.apply(params, cfg, jnp.asarray(short), jnp.asarray(pos5),
                           kv_valid_len=jnp.asarray([5]))

    batch = np.zeros((2, 9), dtype=np.int32)
    batch[0, :5] = short[0]
    batch[1] = long_[0]
    pos9 = np.broadcast_to(np.arange(9, dtype=np.int32), (2, 9))
    batched, _ = llama.apply(params, cfg, jnp.asarray(batch),
                             jnp.asarray(pos9),
                             kv_valid_len=jnp.asarray([5, 9]))
    np.testing.assert_allclose(np.asarray(batched[0, :5]),
                               np.asarray(alone[0]), rtol=1e-4, atol=1e-4)


def test_jit_compiles_once_for_decode(hf_model_and_params):
    _, params = hf_model_and_params
    cfg = LLAMA_TINY
    cache = llama.init_kv_cache(cfg, 2, max_len=32, dtype=jnp.float32)

    calls = {"n": 0}

    @jax.jit
    def step(params, tokens, positions, cache):
        calls["n"] += 1
        return llama.apply(params, cfg, tokens, positions, cache)

    toks = jnp.zeros((2, 1), jnp.int32)
    for t in range(3):
        pos = jnp.full((2, 1), t, jnp.int32)
        _, cache = step(params, toks, pos, cache)
    assert calls["n"] == 1  # traced exactly once


def test_moe_forward_runs():
    """Mixtral-geometry MoE forward produces finite logits (EP parity comes
    in parallel/)."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                      num_experts=4, num_experts_per_tok=2)
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    tokens = jnp.zeros((1, 7), jnp.int32)
    pos = jnp.arange(7, dtype=jnp.int32)[None]
    logits, _ = llama.apply(params, cfg, tokens, pos)
    assert logits.shape == (1, 7, 128)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("attn_bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("quant", ["", "int8", "int4_awq"],
                         ids=["raw", "int8", "int4g32"])
def test_decoder_layer_barrier_changes_no_arithmetic(monkeypatch, quant,
                                                     attn_bias):
    """``decoder_layer`` holds an ``optimization_barrier`` between the
    q/k/v matmuls and the head reshape — a fence for the TPU compiler's
    fusion choices (tests/test_chip_compile.py), not arithmetic. Output
    and the gradient of a scalar loss (what ``training.py`` takes through
    the layer) must equal, bit for bit, the layer without it."""
    import dataclasses

    from generativeaiexamples_tpu.ops.quant import quantize_params
    cfg = dataclasses.replace(LLAMA_TINY, attn_bias=attn_bias)
    params = llama.init_params(cfg, jax.random.key(1), dtype=jnp.bfloat16)
    if quant:
        params = quantize_params(params, quant, group_size=32)
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    if attn_bias:   # init_params zero-fills biases: make them count
        for i, b in enumerate(("bq", "bk", "bv", "bo")):
            lp[b] = jax.random.normal(jax.random.key(10 + i), lp[b].shape,
                                      jnp.float32).astype(lp[b].dtype)
    B, S = 2, 9
    h = jax.random.normal(jax.random.key(2), (B, S, cfg.hidden_size),
                          jnp.float32).astype(jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    inv_freq = llama.rope_frequencies(cfg.head_dim, cfg.rope_theta,
                                      cfg.rope_scaling_factor)
    probe = jax.random.normal(jax.random.key(3), h.shape, jnp.float32)

    # raw weights are differentiable too; quantized leaves are integers
    argnums = (0,) if quant else (0, 1)

    def run():      # fresh closures: each call traces anew
        def layer(h, lp):
            out, _ = llama.decoder_layer(h, lp, cfg, pos, inv_freq,
                                         jnp.full((B,), S, jnp.int32))
            return out

        def loss(h, lp):
            return jnp.sum(layer(h, lp).astype(jnp.float32) * probe)

        return (jax.jit(layer)(h, lp),
                jax.jit(jax.grad(loss, argnums=argnums))(h, lp))

    fenced = run()
    skipped = []
    monkeypatch.setattr(jax.lax, "optimization_barrier",
                        lambda x: (skipped.append(1), x)[1])
    plain = run()
    assert len(skipped) == 2    # forward and gradient both traced anew
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32))),
        fenced, plain)


def _meta_state_dict(hf_model, cfg):
    """Render HF weights under Meta/fairscale names + interleaved RoPE."""
    import torch

    sd = hf_model.state_dict()

    def permute_to_meta(w, n_heads):
        # inverse of transformers' convert_llama_weights_to_hf permutation
        out_dim, in_dim = w.shape
        return (w.reshape(n_heads, 2, cfg.head_dim // 2, in_dim)
                 .transpose(0, 2, 1, 3).reshape(out_dim, in_dim))

    meta = {}
    for key, t in sd.items():
        arr = t.detach().to(torch.float32).numpy()
        key = key.removeprefix("model.")
        if key == "embed_tokens.weight":
            meta["tok_embeddings.weight"] = arr
        elif key == "norm.weight":
            meta["norm.weight"] = arr
        elif key == "lm_head.weight":
            meta["output.weight"] = arr
        else:
            m = key.split(".")
            li, rest = m[1], ".".join(m[2:])
            name_map = {
                "input_layernorm.weight": "attention_norm.weight",
                "post_attention_layernorm.weight": "ffn_norm.weight",
                "self_attn.q_proj.weight": "attention.wq.weight",
                "self_attn.k_proj.weight": "attention.wk.weight",
                "self_attn.v_proj.weight": "attention.wv.weight",
                "self_attn.o_proj.weight": "attention.wo.weight",
                "mlp.gate_proj.weight": "feed_forward.w1.weight",
                "mlp.up_proj.weight": "feed_forward.w3.weight",
                "mlp.down_proj.weight": "feed_forward.w2.weight",
            }
            if rest == "self_attn.q_proj.weight":
                arr = permute_to_meta(arr, cfg.num_heads)
            elif rest == "self_attn.k_proj.weight":
                arr = permute_to_meta(arr, cfg.num_kv_heads)
            meta[f"layers.{li}.{name_map[rest]}"] = arr
    return meta


def _assert_trees_close(got, params):
    import numpy as np

    def cmp(a, b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    jax.tree.map(cmp, got, params)


def test_meta_pth_import_matches_hf(hf_model_and_params):
    """A Meta-format (fairscale-named, interleaved-RoPE) rendering of the same
    weights must import to the identical param tree as the HF naming."""
    from generativeaiexamples_tpu.models import import_hf

    hf_model, params = hf_model_and_params
    meta = _meta_state_dict(hf_model, LLAMA_TINY)
    got = import_hf.params_from_named_tensors(
        iter(meta.items()), LLAMA_TINY, dtype=jnp.float32)
    _assert_trees_close(got, params)


def test_meta_multishard_import_matches_hf(hf_model_and_params, tmp_path):
    """Two fairscale TP shards (consolidated.00/01.pth) must merge back to
    the single logical tree (regression: shards used to silently overwrite
    each other, ADVICE.md r1 medium)."""
    import torch

    from generativeaiexamples_tpu.models import import_hf

    hf_model, params = hf_model_and_params
    meta = _meta_state_dict(hf_model, LLAMA_TINY)

    shard_dims = import_hf._META_SHARD_DIM
    shards = [{}, {}]
    for key, arr in meta.items():
        dim = import_hf._meta_shard_dim(key)
        t = torch.from_numpy(arr)
        if dim is None:
            shards[0][key] = t.clone()
            shards[1][key] = t.clone()
        else:
            a, b = torch.chunk(t, 2, dim=dim)
            shards[0][key], shards[1][key] = a.contiguous(), b.contiguous()
    assert shard_dims  # the table itself must exist
    torch.save(shards[0], tmp_path / "consolidated.00.pth")
    torch.save(shards[1], tmp_path / "consolidated.01.pth")

    got = import_hf.load_checkpoint(str(tmp_path), LLAMA_TINY,
                                    dtype=jnp.float32)
    _assert_trees_close(got, params)
