"""The two newest older configurations' lowered programs, pinned: the
latent model with an expert share and the two-stack model with window
layers, at their benchmark files' model groups cut to toy sizes (int8
weights), as ``tests/test_layer_kinds_moe.py`` ``PINS`` holds the first
three. Computed on PR 40's PARENT (commit 7c1cd04) and unchanged by PR 40
(learned sparse attention: a fourth element in the layer scan's carry
that is None here, an optional mask operand of the chunk kernel, leaves
handed to the pool writer as a tuple): a change that moves one of these
moves a cell. Re-pin only on purpose (a new JAX re-words the text: re-pin
from one commit). RE-PINNED ON PURPOSE at PR 48, both from that PR's one
commit: ``("kimi-k2-instruct", "chunk_kernel")`` and ``("kimi-k2-instruct",
"rows_kernel")`` — under ``use_kernel`` a latent chunk program's prefix is
ONE call of ``chunk_attention_prefix`` where it was a ``scan`` of gathers,
expansions and per-block updates. The other nine, ``chunk_jnp`` among
them, are the hashes they were: no other program moved. RE-PINNED ON
PURPOSE at PR 50, all SIX ``kimi-k2-instruct`` hashes from that PR's one
commit: every one of them holds an expert share, and under a share
``parallel/moe.py`` now sizes the block height, the dispatch gather, the
grouped product's grid and the way back by what fell on the HELD experts
(``share_walk``, ``_walk_share``). The five ``trinity-mini`` hashes —
all experts held, no share — are the hashes they were, as are all of
``tests/test_layer_kinds_moe.py`` ``PINS``: the proof that no program
without a share moved."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.ops.quant import quantize_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, B = 128, 2
PINS = {
    ("kimi-k2-instruct", "chunk_jnp"):
        "5322b87075ab92b57cab6e6326c653cd82c0919d5ccfb61d56262f9fb57b7c8c",
    ("kimi-k2-instruct", "chunk_kernel"):
        "1b79a53699deca80275f9ee80b17ef0d74b0ff572ad3f8c60a9ba40f432bfb88",
    ("kimi-k2-instruct", "rows_kernel"):
        "3c63b8cc65a9f70cfbde15188421b84e4dd5f8dd1cab9214820a133cd2a0c234",
    ("kimi-k2-instruct", "step_kernel"):
        "57fe494ddaec6092d390f0c95715563f8681eb2cfaa978b3d1ffd9bd715da465",
    ("kimi-k2-instruct", "verify"):
        "e8ba6d4396dc48f9d229de824fed37fce0e4bcde73007c0cd02b152a8438d841",
    ("kimi-k2-instruct", "apply"):
        "2887f69d8f3addeeeb698300b231cf3d0f7629a689fb2d69b86651fea08ced4d",
    ("trinity-mini", "chunk_jnp"):
        "c07f576f00f5d1b03658fbd62f6f1bfd4f9304474f82c10f64847a90b7f79118",
    ("trinity-mini", "rows_jnp"):
        "6ff4e9d38ae7e3414f471c4192abab8f888588673e32a76445b216eb834df3d9",
    ("trinity-mini", "step_kernel"):
        "c44d35d31f5f6f7105de84ee01a2fc48a0687b9c51a39e587195ddb586f93b90",
    ("trinity-mini", "verify"):
        "012d6f893891862836066f373230d0e4283d66c7042dc157ce8ac055dba5b8e6",
    ("trinity-mini", "apply"):
        "5cdd68b268285b49a7c0a9c129b4470e090ee87c628552c122b1bdc8436a7ce8",
}
CUT = {"kimi-k2-instruct": dict(num_experts=16, experts_held=4,
                                moe_intermediate_size=128, q_lora_rank=128,
                                num_heads=8),
       "trinity-mini": dict(num_experts=16, moe_intermediate_size=128)}


def toy(name):
    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        model = json.load(f)["model"]
    return LlamaConfig(**dict(model, num_layers=3, vocab_size=512,
                              hidden_size=256, intermediate_size=512,
                              **CUT[name]))


def lowered(cfg, path):
    # shapes alone: a program's text does not depend on its weights, and
    # nothing is computed or compiled here
    p = jax.eval_shape(lambda key: quantize_params(
        llama.init_params(cfg, key, jnp.bfloat16), "int8"),
        jax.random.PRNGKey(0))
    pool = jax.eval_shape(
        lambda: llama.init_paged_kv_cache(cfg, 9, PAGE, jnp.bfloat16))
    z = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    kind, _, how = path.partition("_")
    kernel = how == "kernel"
    if kind in ("chunk", "rows"):
        n = 1 if kind == "chunk" else 4

        def chunk(p, pool, tok, pos, table, sp):
            return llama.apply_prefill_paged(p, cfg, tok, pos, pool, table,
                                             pos[:, -1] + 1, sp,
                                             use_kernel=kernel)
        chunk.__name__ = kind        # the jitted function's name is text
        return jax.jit(chunk).lower(p, pool, z(n, PAGE), z(n, PAGE), z(n, 8),
                                    z() if n == 1 else z(n)).as_text()
    if kind == "step":
        def step(p, pool, tok, pos, table, wp, off):
            return llama.apply_decode_paged(
                p, cfg, tok, pos, pool, table, pos[:, 0] + 1, wp, off,
                use_kernel=kernel, active=jnp.ones((B,), bool), stats=True)
        return jax.jit(step).lower(p, pool, z(B, 1), z(B, 1), z(B, 8), z(B),
                                   z(B)).as_text()
    if kind == "verify":
        def verify(p, pool, tok, pos, table, wp, off):
            return llama.apply_verify_paged(p, cfg, tok, pos, pool, table,
                                            pos[:, -1] + 1, wp, off)
        return jax.jit(verify).lower(p, pool, z(B, 3), z(B, 3), z(B, 8),
                                     z(B, 3), z(B, 3)).as_text()
    def plain(p, tok, pos):
        return llama.apply(p, cfg, tok, pos)
    return jax.jit(plain).lower(p, z(1, 64), z(1, 64)).as_text()


@pytest.mark.parametrize("name,path", sorted(PINS))
def test_latent_and_two_stack_programs_unchanged(name, path):
    text = lowered(toy(name), path)
    assert hashlib.sha256(text.encode()).hexdigest() == PINS[name, path]
