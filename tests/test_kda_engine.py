"""The engine end to end over a tiny model whose recurrent layers decay a
channel at its own rate beside latent attention layers, behind two
leading dense layers (``Engine.submit``, the scheduler, chunk programs of
one and of several prompts, decode rounds, the fused tail), on the CPU:
its greedy tokens are the plain forward's while other slots prefill,
decode, finish and are reused; the state is reserved beside a latent
pool that only the latent layers write; the group limit's counter is on
the round records; and what cannot take a state beside a latent pool
refuses BY NAME when the engine is configured."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.engine import (Engine, EngineConfig,
                                                    SamplingParams)
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.obs.rounds import RoundRecorder
from generativeaiexamples_tpu.utils.errors import ConfigError

from test_kda_layers import CFG

ENGINE = dict(max_slots=4, max_input_length=512, max_output_length=32,
              prefill_buckets=(64,), max_prefill_bucket=64, page_size=32,
              steps_per_round=4, kv_pool_tokens=None, dtype="float32")
N_OUT = 10


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


def serve(engine, ids, n=N_OUT):
    s = submit(engine, ids, n)
    list(s)
    assert s.finish_reason == "length"
    return list(s.token_ids)


@pytest.fixture(scope="module")
def engine(params):
    eng = Engine(params, CFG, ByteTokenizer(), EngineConfig(**ENGINE))
    eng.rounds = RoundRecorder(cap=512)
    eng.start()
    yield eng
    eng.stop()


@pytest.mark.parametrize("n", [150, 40], ids=["three_chunks", "one_bucket"])
def test_engine_tokens_are_the_plain_forwards(engine, params, n):
    ids = prompt(n, n)
    assert serve(engine, ids) == plain_greedy(params, ids, N_OUT)


def test_slots_prefill_decode_finish_and_are_reused(engine, params):
    sizes = (120, 140, 70, 90, 33)          # five requests, four slots
    outs = (4, 4, 4, 3, 4)
    ps = [prompt(n, 100 + n) for n in sizes]
    streams = [submit(engine, p, o) for p, o in zip(ps, outs)]
    for p, o, s in zip(ps, outs, streams):
        list(s)
        assert list(s.token_ids) == plain_greedy(params, p, o), len(p)
    assert len({s.timeline.meta["slot"] for s in streams}) < len(streams)


def test_the_state_is_reserved_beside_a_latent_pool(engine):
    st = engine.stats
    slot = 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4)        # float32 activations
    assert st["slot_bytes"] == slot
    assert st["state_bytes"] == 4 * slot
    assert st["kv_bytes_per_token"] == 2 * (32 + 8) * 4     # 2 layers of 8
    cache = engine._state["cache"]
    assert set(cache) == {"c", "r", "s", "conv"}
    assert cache["s"].shape[:2] == (6, 4) and cache["c"].shape[0] == 2
    assert cache["s"].nbytes + cache["conv"].nbytes == st["state_bytes"]
    assert engine.stats["prefix_cache_off"] == 1


def test_the_group_limits_counter_is_on_the_round_records(engine, params):
    serve(engine, prompt(90, 4))
    st = engine.stats
    assert st["route_groups_held_pct_rounds"] > 0
    assert st["local_assignments_rounds"] > 0
    recs = [r for r in engine.rounds.records() if r.decode_slots > 0]
    assert recs and all(0 <= r.route_groups_held_pct <= 100 for r in recs)
    assert any(r.route_groups_held_pct > 0 for r in recs)
    assert "route_groups_held_pct" in recs[-1].to_dict()["outcome"]


def refused(params, match, **kw):
    with pytest.raises(ConfigError, match=match) as err:
        Engine(params, CFG, ByteTokenizer(), EngineConfig(**{**ENGINE, **kw}))
    return str(err.value)


def test_what_a_state_beside_a_latent_pool_refuses(params, monkeypatch):
    """The union of what each refuses alone, each by the mechanism's
    name, before anything is built."""
    assert "refuses" in refused(params, "int8 KV pool", kv_quant="int8")
    msg = refused(params, "speculative decoding", spec_decode=True)
    assert "a recurrent state (full_attention_interval=3) refuses" in msg
    msg = refused(params, "host KV tier", kv_host_pool_tokens=4096)
    assert "a latent KV pool (kv_lora_rank=32) refuses" in msg
    refused(params, "handoff", role="prefill")


def test_a_mesh_is_refused(params):
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2]).reshape(1, 2)
    with pytest.raises(ConfigError, match="refuses"):
        Engine(params, CFG, ByteTokenizer(), EngineConfig(**ENGINE),
               mesh=Mesh(devs, ("dp", "tp")))
