"""Converted-weight cache (models/weight_cache.py): orbax round-trip of
the served param tree + the load-or-convert gate the model server uses
(SURVEY §5 checkpoint/resume — the reference's engine-cache role)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models import llama, weight_cache
from generativeaiexamples_tpu.models.configs import LLAMA_TINY
from generativeaiexamples_tpu.ops.quant import quantize_params


@pytest.fixture(autouse=True)
def cache_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("GAIE_WEIGHT_CACHE_DIR", str(tmp_path / "wc"))
    monkeypatch.delenv("GAIE_WEIGHT_CACHE", raising=False)


def _tree_equal(a, b):
    flat_a = jax.tree.leaves_with_path(a)
    flat_b = dict(jax.tree.leaves_with_path(b))
    assert len(flat_a) == len(flat_b)
    for path, leaf in flat_a:
        other = flat_b[path]
        assert jnp.asarray(leaf).dtype == jnp.asarray(other).dtype, path
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(other),
                                      err_msg=str(path))


def test_round_trip_preserves_quantized_tree():
    """The cached tree must come back bit-identical — including int8
    QTensor leaves and their f32 scales (a dtype drift would silently
    change served numerics)."""
    params = llama.init_params(LLAMA_TINY, jax.random.key(0),
                               dtype=jnp.bfloat16)
    params = quantize_params(params, mode="int8")
    assert weight_cache.save("tiny-int8-test", params)
    restored = weight_cache.load("tiny-int8-test")
    assert restored is not None
    _tree_equal(params, restored)


def test_cached_or_convert_converts_once():
    params = llama.init_params(LLAMA_TINY, jax.random.key(1),
                               dtype=jnp.float32)
    calls = []

    def convert():
        calls.append(1)
        return params

    first, from_cache = weight_cache.cached_or_convert("ident-a", convert)
    assert not from_cache and len(calls) == 1
    second, from_cache = weight_cache.cached_or_convert("ident-a", convert)
    assert from_cache and len(calls) == 1
    _tree_equal(first, second)
    # a different identity converts again — content-hash keying is what
    # prevents a renamed/edited checkpoint masquerading as the old one
    _, from_cache = weight_cache.cached_or_convert("ident-b", convert)
    assert not from_cache and len(calls) == 2


def test_disabled_by_env(monkeypatch):
    monkeypatch.setenv("GAIE_WEIGHT_CACHE", "0")
    params = {"w": jnp.ones((2, 2))}
    assert not weight_cache.save("off", params)
    assert weight_cache.load("off") is None
    calls = []
    weight_cache.cached_or_convert("off", lambda: calls.append(1) or params)
    weight_cache.cached_or_convert("off", lambda: calls.append(1) or params)
    assert len(calls) == 2


def test_corrupt_cache_is_dropped_and_reconverted(tmp_path):
    params = {"w": jnp.arange(4.0)}
    assert weight_cache.save("corrupt", params)
    tree = weight_cache._tree_dir("corrupt")
    # mangle the checkpoint so restore fails
    import os
    for root, _, files in os.walk(tree):
        for f in files:
            with open(os.path.join(root, f), "wb") as fh:
                fh.write(b"garbage")
    assert weight_cache.load("corrupt") is None
    # the broken entry was removed; a fresh convert can re-cache
    got, from_cache = weight_cache.cached_or_convert(
        "corrupt", lambda: params)
    assert not from_cache
    assert weight_cache.load("corrupt") is not None


def test_build_services_caches_converted_checkpoint(tmp_path, monkeypatch):
    """Server integration: first boot converts a real safetensors
    checkpoint and caches the tree; a second boot loads from the cache
    (conversion not invoked) and serves the identical greedy output."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    import safetensors.torch as st

    from generativeaiexamples_tpu.engine import SamplingParams
    from generativeaiexamples_tpu.models import import_hf
    from generativeaiexamples_tpu.serving.model_server import build_services

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
        attn_implementation="eager")
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg).eval()
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    st.save_file({k: v.contiguous() for k, v in model.state_dict().items()},
                 str(ckpt / "model.safetensors"))
    # a real checkpoint dir ships a tokenizer; the vendored sentencepiece
    # model serves (ids past the tiny vocab clamp in the embed lookup —
    # determinism across boots is what this test needs, not coverage)
    import shutil as _sh
    _sh.copy(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "generativeaiexamples_tpu", "assets", "tokenizer_32k.model"),
        ckpt / "tokenizer.model")

    real_load = import_hf.load_checkpoint
    calls = []

    def counting_load(*a, **k):
        calls.append(1)
        return real_load(*a, **k)

    monkeypatch.setattr(import_hf, "load_checkpoint", counting_load)

    def boot():
        engine, _, _ = build_services(
            model_type="llama", model_name="llama-tiny",
            model_path=str(ckpt), dtype="float32", max_slots=2,
            max_input_length=64, max_output_length=16,
            with_embedder=False)
        with engine:
            out = engine.submit(engine.tokenizer.encode("cache test"),
                                SamplingParams(max_tokens=6, top_k=1,
                                               ignore_eos=True)).text()
        return out

    first = boot()
    assert len(calls) == 1
    second = boot()
    assert len(calls) == 1, "second boot re-converted despite the cache"
    assert first == second


def test_save_prunes_stale_hash_siblings():
    """A new content hash evicts the old identity's multi-GB tree —
    without eviction every checkpoint update leaks a full model copy."""
    params = {"w": jnp.ones((2,))}
    assert weight_cache.save("m-bf16-raw-aaa", params,
                             prune_prefix="m-bf16-raw-")
    assert weight_cache.save("m-bf16-raw-bbb", params,
                             prune_prefix="m-bf16-raw-")
    assert weight_cache.load("m-bf16-raw-aaa") is None   # evicted
    assert weight_cache.load("m-bf16-raw-bbb") is not None
    # different model/quant prefixes are untouched
    assert weight_cache.save("m-bf16-int8-ccc", params,
                             prune_prefix="m-bf16-int8-")
    assert weight_cache.load("m-bf16-raw-bbb") is not None


def test_skip_hash_bypasses_weight_cache(tmp_path, monkeypatch):
    """GAIE_SKIP_HASH removes the content hash from the identity, so the
    weight cache must not be consulted — a swapped checkpoint at the same
    path would otherwise serve stale weights."""
    monkeypatch.setenv("GAIE_SKIP_HASH", "1")
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    import safetensors.torch as st

    from generativeaiexamples_tpu.models import import_hf
    from generativeaiexamples_tpu.serving.model_server import build_services

    hf_cfg = transformers.LlamaConfig(
        vocab_size=LLAMA_TINY.vocab_size,
        hidden_size=LLAMA_TINY.hidden_size,
        intermediate_size=LLAMA_TINY.intermediate_size,
        num_hidden_layers=LLAMA_TINY.num_layers,
        num_attention_heads=LLAMA_TINY.num_heads,
        num_key_value_heads=LLAMA_TINY.num_kv_heads,
        max_position_embeddings=LLAMA_TINY.max_position_embeddings,
        attn_implementation="eager")
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg).eval()
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    st.save_file({k: v.contiguous() for k, v in model.state_dict().items()},
                 str(ckpt / "model.safetensors"))
    import shutil as _sh
    _sh.copy(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "generativeaiexamples_tpu", "assets", "tokenizer_32k.model"),
        ckpt / "tokenizer.model")

    calls = []
    real_load = import_hf.load_checkpoint
    monkeypatch.setattr(import_hf, "load_checkpoint",
                        lambda *a, **k: calls.append(1) or real_load(*a, **k))
    for _ in range(2):
        engine, _, _ = build_services(
            model_type="llama", model_name="llama-tiny",
            model_path=str(ckpt), dtype="float32", max_slots=2,
            max_input_length=64, max_output_length=16,
            with_embedder=False)
        engine.stop()
    assert len(calls) == 2, "weight cache served despite GAIE_SKIP_HASH"
