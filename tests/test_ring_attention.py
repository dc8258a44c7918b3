"""Sequence parallelism: ring attention over the ``sp`` mesh axis.

Parity strategy as in test_parallel.py: sharded execution on the virtual
8-device CPU mesh must match the single-device math bit-for-bit-ish
(float32 tolerance). The reference has no long-context path; these tests
pin the TPU-native one (parallel/ring_attention.py, llama.apply_sp).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.ops.attention import gqa_attention
from generativeaiexamples_tpu.parallel import (MeshPlan, make_mesh,
                                               ring_gqa_attention)

CFG = LlamaConfig(vocab_size=256, hidden_size=128, intermediate_size=256,
                  num_layers=2, num_heads=8, num_kv_heads=4, head_dim=16,
                  max_position_embeddings=512)


def _qkv(key, B=2, S=64, H=8, KV=4, hd=16):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(kv, (B, S, KV, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return q, k, v, pos


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense_attention(cpu_devices, causal):
    mesh = make_mesh(MeshPlan(sp=8), cpu_devices[:8])
    q, k, v, pos = _qkv(jax.random.key(0))

    ring = jax.shard_map(
        lambda q, k, v, p: ring_gqa_attention(
            q, k, v, p, axis_name="sp", axis_size=8, causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"),
                  P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False)
    got = jax.jit(ring)(q, k, v, pos)
    want = gqa_attention(q, k, v, pos, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_respects_cross_shard_causality(cpu_devices):
    """Queries in shard 0 must see NO keys from later shards: perturbing
    the tail of the sequence cannot change the head's output."""
    mesh = make_mesh(MeshPlan(sp=8), cpu_devices[:8])
    q, k, v, pos = _qkv(jax.random.key(1))
    ring = jax.shard_map(
        lambda q, k, v, p: ring_gqa_attention(
            q, k, v, p, axis_name="sp", axis_size=8),
        mesh=mesh,
        in_specs=(P(None, "sp"),) * 4,
        out_specs=P(None, "sp"), check_vma=False)
    base = jax.jit(ring)(q, k, v, pos)
    k2 = k.at[:, 32:].add(7.0)
    v2 = v.at[:, 32:].add(-3.0)
    pert = jax.jit(ring)(q, k2, v2, pos)
    np.testing.assert_allclose(np.asarray(base[:, :32]),
                               np.asarray(pert[:, :32]), rtol=1e-6)
    assert not np.allclose(np.asarray(base[:, 32:]),
                           np.asarray(pert[:, 32:]))


def test_apply_sp_matches_single_device(cpu_devices):
    """Full-model parity: the sequence-parallel forward equals the plain
    forward — the distributed test IS the numerical test."""
    mesh = make_mesh(MeshPlan(dp=2, sp=4), cpu_devices[:8])
    params = llama.init_params(CFG, jax.random.key(2), dtype=jnp.float32)
    B, S = 2, 64
    tokens = jax.random.randint(jax.random.key(3), (B, S), 0,
                                CFG.vocab_size, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    want, _ = jax.jit(lambda p, t, pos: llama.apply(p, CFG, t, pos))(
        params, tokens, positions)
    got = jax.jit(lambda p, t, pos: llama.apply_sp(p, CFG, t, pos, mesh))(
        params, tokens, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_apply_sp_long_context_exceeds_position_table(cpu_devices):
    """The sp path is for LONG context: run a sequence at the model's full
    position budget, sharded 8 ways, and check logits stay finite and
    match the unsharded forward."""
    mesh = make_mesh(MeshPlan(sp=8), cpu_devices[:8])
    cfg = CFG
    params = llama.init_params(cfg, jax.random.key(4), dtype=jnp.float32)
    B, S = 1, cfg.max_position_embeddings  # 512 = 8 shards of 64
    tokens = jax.random.randint(jax.random.key(5), (B, S), 0,
                                cfg.vocab_size, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    got = jax.jit(lambda p, t, pos: llama.apply_sp(p, cfg, t, pos, mesh))(
        params, tokens, positions)
    assert np.isfinite(np.asarray(got)).all()
    want, _ = jax.jit(lambda p, t, pos: llama.apply(p, cfg, t, pos))(
        params, tokens, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_apply_sp_rejections(cpu_devices):
    params = llama.init_params(CFG, jax.random.key(6), dtype=jnp.float32)
    tokens = jnp.zeros((1, 64), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (1, 64))
    mesh_tp = make_mesh(MeshPlan(sp=2, tp=4), cpu_devices[:8])
    with pytest.raises(ValueError, match="tp"):
        llama.apply_sp(params, CFG, tokens, positions, mesh_tp)
    mesh_sp = make_mesh(MeshPlan(sp=8), cpu_devices[:8])
    with pytest.raises(ValueError, match="not divisible"):
        llama.apply_sp(params, CFG, tokens[:, :60], positions[:, :60],
                       mesh_sp)
    mesh_no_sp = make_mesh(MeshPlan(tp=8), cpu_devices[:8])
    with pytest.raises(ValueError, match="sp > 1"):
        llama.apply_sp(params, CFG, tokens, positions, mesh_no_sp)
