"""The engine end to end over a tiny model whose recurrent layers are
state-space layers beside NoPE attention layers in mid-period, over a
tied head (``Engine.submit``, the scheduler, chunk programs of one and
of several prompts, decode rounds, the fused tail), on the CPU: its
greedy tokens are the plain forward's while other slots prefill, decode,
finish and are reused; the state is reserved beside a pool that only the
attention layers write; and what cannot take a state refuses BY NAME
when the engine is configured. ONE engine a module."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.engine import (Engine, EngineConfig,
                                                    SamplingParams)
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.utils.errors import ConfigError

from test_ssd_layers import CFG

ENGINE = dict(max_slots=4, max_input_length=512, max_output_length=32,
              prefill_buckets=(64,), max_prefill_bucket=64, page_size=32,
              steps_per_round=4, kv_pool_tokens=None, dtype="float32")
N_OUT = 8


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, 250, n)]


@jax.jit
def _logits(params, ids, n):
    out, _ = llama.apply(params, CFG, ids[None],
                         jnp.arange(ids.shape[0])[None])
    return out[0, n - 1]


def plain_greedy(params, ids, n):
    buf = np.zeros(512 + N_OUT, np.int32)
    buf[:len(ids)] = ids
    for at in range(len(ids), len(ids) + n):
        buf[at] = int(jnp.argmax(_logits(params, jnp.asarray(buf), at)))
    return [int(t) for t in buf[len(ids):len(ids) + n]]


def submit(engine, ids, n=N_OUT):
    return engine.submit(ids, SamplingParams(max_tokens=n, temperature=0.0,
                                             ignore_eos=True))


@pytest.fixture(scope="module")
def engine(params):
    eng = Engine(params, CFG, ByteTokenizer(), EngineConfig(**ENGINE))
    eng.start()
    yield eng
    eng.stop()


def test_engine_tokens_are_the_plain_forwards(engine, params):
    """Three chunk programs, each from the state and tail the one before
    left in the slot, then decode rounds through the state."""
    ids = prompt(150, 150)
    s = submit(engine, ids)
    list(s)
    assert s.finish_reason == "length"
    assert list(s.token_ids) == plain_greedy(params, ids, N_OUT)


def test_slots_prefill_decode_finish_and_are_reused(engine, params):
    sizes = (120, 140, 70, 90, 33)          # five requests, four slots
    outs = (4, 4, 4, 3, 4)
    ps = [prompt(n, 100 + n) for n in sizes]
    streams = [submit(engine, p, o) for p, o in zip(ps, outs)]
    for p, o, s in zip(ps, outs, streams):
        list(s)
        assert list(s.token_ids) == plain_greedy(params, p, o), len(p)
    assert len({s.timeline.meta["slot"] for s in streams}) < len(streams)


def test_the_state_is_reserved_beside_the_pool(engine):
    st = engine.stats
    slot = 6 * (4 * 8 * 16 * 4 + 3 * 64 * 4)        # float32 activations
    assert st["slot_bytes"] == slot
    assert st["state_bytes"] == 4 * slot
    assert st["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4   # 2 layers of 8
    cache = engine._state["cache"]
    assert set(cache) == {"k", "v", "s", "conv"}
    assert cache["s"].shape == (6, 4, 4, 8, 16) and cache["k"].shape[0] == 2
    assert cache["s"].nbytes + cache["conv"].nbytes == st["state_bytes"]
    assert st["prefix_cache_off"] == 1 and st["downgrades"] == 0
    assert st["scan_kernel"] == 0       # the CPU: the XLA form


def refused(params, match, **kw):
    with pytest.raises(ConfigError, match=match) as err:
        Engine(params, CFG, ByteTokenizer(), EngineConfig(**{**ENGINE, **kw}))
    return str(err.value)


def test_what_a_state_refuses(params):
    assert "refuses" in refused(params, "int8 KV pool", kv_quant="int8")
    msg = refused(params, "speculative decoding", spec_decode=True)
    assert "a recurrent state (full_attention_interval=4) refuses" in msg
    refused(params, "host KV tier", kv_host_pool_tokens=4096)
    refused(params, "handoff", role="prefill")


def test_a_mesh_lora_and_verify_are_refused(params):
    from jax.sharding import Mesh
    from generativeaiexamples_tpu import lora
    devs = np.array(jax.devices()[:2]).reshape(1, 2)
    with pytest.raises(ConfigError, match="refuses"):
        Engine(params, CFG, ByteTokenizer(), EngineConfig(**ENGINE),
               mesh=Mesh(devs, ("dp", "tp")))
    with pytest.raises(NotImplementedError, match="ssd_"):
        lora.init_lora(CFG, params, jax.random.key(0), 4, ("wq",))
    pool = llama.init_paged_kv_cache(CFG, 3, 32, jnp.float32)
    i32 = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    with pytest.raises(NotImplementedError, match="rolled"):
        llama.apply_verify_paged(
            params, CFG, i32(1, 2)[None], i32(0, 1)[None], pool,
            i32(1, 2)[None], i32(2), i32(1, 1)[None], i32(0, 1)[None])
