"""Tiered KV store tests (engine/kv_tier.py + engine integration).

Host-side unit coverage (store LRU/capacity, blob wire format, bounded
transfer fetch) plus engine-level serving tests on the CPU backend:
evict→offload→restore round trips must be token-identical to cold
recompute at page boundaries k·page±1 (including the COW-demoted tail
of a full-cover match), the restore-vs-recompute pricing must actually
refuse expensive restores, chaos plans must degrade to recompute /
cold placement (never an error frame), suspend/resume must round-trip
across engines, cross-replica transfer must move real pages over HTTP,
and KV_HOST_POOL_TOKENS=0 must preserve the untiered engine."""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.engine import (Engine, EngineConfig,
                                             SamplingParams)
from generativeaiexamples_tpu.engine import kv_tier
from generativeaiexamples_tpu.engine.kv_tier import (BlockRecord,
                                                     HostPageStore,
                                                     fetch_blocks,
                                                     from_blob, to_blob)
from generativeaiexamples_tpu.engine.prefix_cache import hash_blocks
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.utils import faults

PAGE = 16

CFG = LlamaConfig(vocab_size=259 + 5, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position_embeddings=256)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(31), dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _no_env_tier(monkeypatch):
    # The engine reads KV_HOST_POOL_TOKENS at build; tests control the
    # tier via EngineConfig only.
    monkeypatch.delenv("KV_HOST_POOL_TOKENS", raising=False)
    yield
    faults.clear()


def _build(params, host_tokens, pool_tokens=96, max_in=64, max_out=16):
    cfg = EngineConfig(max_slots=2, max_input_length=max_in,
                       max_output_length=max_out,
                       prefill_buckets=(32, 64), page_size=PAGE,
                       dtype="float32", kv_pool_tokens=pool_tokens,
                       steps_per_round=4,
                       kv_host_pool_tokens=host_tokens)
    return Engine(params, CFG, ByteTokenizer(), cfg)


@jax.jit
def _padded_logits(params, ids):
    out, _ = llama.apply(params, CFG, ids[None],
                         jnp.arange(ids.shape[0], dtype=jnp.int32)[None])
    return out[0]


def _greedy_reference(params, prompt_ids, n_steps):
    ids = list(prompt_ids)
    # ONE compiled program a padded length (causal: what follows a
    # position does not move it): a forward a LENGTH compiled the model
    # once a token
    length = -(-(len(ids) + n_steps) // 64) * 64
    for _ in range(n_steps):
        logits = _padded_logits(params, jnp.asarray(
            ids + [0] * (length - len(ids)), jnp.int32))
        ids.append(int(jnp.argmax(logits[len(ids) - 1])))
    return ids[len(prompt_ids):]


SP = SamplingParams(max_tokens=4, top_k=1, ignore_eos=True)


def _prompt(seed, n):
    return [(seed * 31 + i * 7) % 250 + 3 for i in range(n)]


def _wait_for_offload(eng, min_pages=1, timeout=5.0):
    """Offload materialization rides the harvest worker — wait for it."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if eng.stats["kv_tier_offload_pages"] >= min_pages:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"offload never materialized: {eng.stats['kv_tier_offload_pages']}")


def _churn(eng, seeds, sp=SP, n=32):
    """Serve distinct prompts to push earlier prefixes out of the pool
    (96-token pool = 6 pages; each request holds 3)."""
    for s in seeds:
        stream = eng.submit(_prompt(s, n), sp)
        stream.text()
        assert stream.finish_reason == "length"


# --------------------------------------------------------------- unit level

def test_host_store_lru_capacity_and_chain_match():
    # each record: one (2,2) float32 leaf = 16 bytes; cap = 2 records
    store = HostPageStore(capacity_bytes=32)
    recs = [BlockRecord(bytes([i]) * 16, None,
                        {"k": np.full((2, 2), i, np.float32)})
            for i in range(3)]
    assert store.put(recs[0]) and store.put(recs[1])
    assert store.nbytes == 32
    assert store.get(recs[0].hash) is not None   # refresh 0's recency
    store.put(recs[2])                            # evicts 1 (LRU)
    assert store.has(recs[0].hash) and store.has(recs[2].hash)
    assert not store.has(recs[1].hash)
    assert store.offload_evictions == 1
    assert store.pages == 2 and store.nbytes == 32
    # chain match stops at the first gap
    assert store.match_chain([recs[0].hash, recs[2].hash]) == 2
    assert store.match_chain([recs[1].hash, recs[0].hash]) == 0
    assert store.match_chain([recs[0].hash, recs[1].hash,
                              recs[2].hash]) == 1
    # pop keeps the byte ledger honest
    assert store.pop(recs[0].hash) is not None
    assert store.nbytes == 16
    # the capacity is BYTES: a single record over the whole budget is
    # refused outright (an inflated import cannot evict everything),
    # and a disabled store takes nothing
    huge = BlockRecord(b"h" * 16, None,
                       {"k": np.zeros((100,), np.float32)})
    assert not store.put(huge)
    assert not HostPageStore(0).put(recs[0])


def test_blob_round_trip_and_truncation():
    recs = [
        BlockRecord(b"a" * 16, None,
                    {"k": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
                     "v": np.ones((2, 3, 4), np.float32)}),
        BlockRecord(b"b" * 16, b"a" * 16,
                    {"k": np.zeros((2, 3, 4), np.float32),
                     "v": np.full((2, 3, 4), 7, np.float32)}),
    ]
    meta = {"page_size": PAGE, "kv_quant": "", "dtype": "float32"}
    blob = to_blob(recs, meta)
    meta2, recs2 = from_blob(blob)
    assert meta2["page_size"] == PAGE
    assert [r.hash for r in recs2] == [r.hash for r in recs]
    assert recs2[1].parent == b"a" * 16
    for a, b in zip(recs, recs2):
        for name in a.arrays:
            np.testing.assert_array_equal(a.arrays[name], b.arrays[name])
    with pytest.raises(ValueError):
        from_blob(blob[:-10])       # truncated payload fails loudly
    with pytest.raises(ValueError):
        from_blob(b"junk" + blob)   # bad magic


def test_fetch_blocks_hang_is_bounded():
    faults.set_plan("kv.transfer=hang")
    t0 = time.monotonic()
    out = fetch_blocks("http://127.0.0.1:1", [b"x" * 16], timeout_s=0.4)
    assert out is None
    assert time.monotonic() - t0 < 3.0   # bounded by timeout, not HANG_MAX
    faults.clear()
    # connect-refused donor: also None, no raise
    assert fetch_blocks("http://127.0.0.1:1", [b"x" * 16],
                        timeout_s=0.5) is None


# ------------------------------------------------------------- engine level

@pytest.mark.parametrize("n_tokens", [PAGE - 1, 2 * PAGE - 1, 2 * PAGE,
                                      2 * PAGE + 1, 3 * PAGE + 1])
def test_offload_restore_parity_at_page_boundaries(params, n_tokens):
    """evict→offload→restore must be token-identical to cold recompute
    at k·page±1, including the COW-demoted tail of a full-cover match
    (2*PAGE: both blocks offloaded, only the first restorable)."""
    eng = _build(params, host_tokens=4096)
    target = _prompt(1, n_tokens)
    with eng:
        cold = eng.submit(target, SP)
        cold.text()
        _churn(eng, seeds=(50, 51, 52))    # push target out of the pool
        if n_tokens >= PAGE:               # sub-page prompts cache nothing
            _wait_for_offload(eng)
        warm = eng.submit(target, SP)
        warm.text()
    stats = eng.stats
    ref = _greedy_reference(params, target, 4)
    assert cold.token_ids == ref
    assert warm.token_ids == ref
    if n_tokens >= PAGE:
        # COW cap: a full-cover chain restores one block short
        expect_pages = (n_tokens - 1) // PAGE
        assert stats["kv_tier_restore_pages"] >= min(1, expect_pages)
        if expect_pages:
            assert stats["kv_tier_restore_hits"] >= 1
            assert stats["kv_restore_hit_rate"] > 0
    # page conservation: free + cached == pool
    cached = eng._prefix_cache.cached_pages
    assert len(eng._free_pages) + cached == eng._n_pages - 1


def test_pricing_skips_expensive_restore(params, monkeypatch, tmp_path):
    """A cost model pricing H2D above recompute must deliberately
    re-prefill — and say so via kv_restore_skipped_cost — with
    token-identical output."""
    import json
    prof = tmp_path / "PROFILE_skip.json"
    prof.write_text(json.dumps({
        "full_ms_per_step": 2.0, "slots": 8,
        "prefill_ms_per_token": 0.0001, "h2d_ms_per_page": 1e9}))
    monkeypatch.setenv("SCHED_PROFILE_JSON", str(prof))
    monkeypatch.setenv("SCHED_ONLINE_CALIB", "0")
    eng = _build(params, host_tokens=4096)
    target = _prompt(2, 2 * PAGE + 1)
    with eng:
        cold = eng.submit(target, SP)
        cold.text()
        _churn(eng, seeds=(60, 61, 62))
        _wait_for_offload(eng)
        warm = eng.submit(target, SP)
        warm.text()
    stats = eng.stats
    assert stats["kv_restore_skipped_cost"] >= 1
    assert stats["kv_tier_restore_pages"] == 0
    assert warm.token_ids == cold.token_ids \
        == _greedy_reference(params, target, 4)


def test_chaos_restore_fail_falls_back_to_recompute(params):
    """kv.restore=fail: the admission recomputes the prefix — correct
    tokens, a clean `length` finish, no error surface."""
    eng = _build(params, host_tokens=4096)
    target = _prompt(3, 2 * PAGE + 1)
    with eng:
        cold = eng.submit(target, SP)
        cold.text()
        _churn(eng, seeds=(70, 71, 72))
        _wait_for_offload(eng)
        faults.set_plan("kv.restore=fail")
        try:
            warm = eng.submit(target, SP)
            text = warm.text()      # no EngineError raised
            fired = faults.fired("kv.restore")
        finally:
            faults.clear()
    assert fired >= 1
    assert warm.finish_reason == "length"
    assert "[error]" not in text
    assert warm.token_ids == cold.token_ids
    assert eng.stats["kv_tier_restore_pages"] == 0


def test_chaos_offload_fail_drops_pages_untiered(params):
    eng = _build(params, host_tokens=4096)
    faults.set_plan("kv.offload=fail")
    try:
        with eng:
            _churn(eng, seeds=(80, 81, 82, 83))
    finally:
        faults.clear()
    stats = eng.stats
    assert stats["prefix_cache_evicted_pages"] > 0   # eviction proceeded
    assert stats["kv_tier_offload_pages"] == 0       # nothing offloaded


def test_chaos_transfer_hang_places_cold(params):
    """kv.transfer=hang on the requester: submit() pays the bounded
    fetch timeout, then serves a normal cold prefill."""
    eng = _build(params, host_tokens=4096)
    eng._kv_tier.transfer_timeout_s = 0.3
    target = _prompt(4, 2 * PAGE)
    faults.set_plan("kv.transfer=hang")
    token = kv_tier.bind_transfer_source("http://127.0.0.1:1")
    try:
        with eng:
            # bound SUBMIT, where the fetch lives — text() would fold
            # in compile time and flake under parallel test load
            t0 = time.monotonic()
            stream = eng.submit(target, SP)
            submit_s = time.monotonic() - t0
            stream.text()
            assert submit_s < 5.0, submit_s
    finally:
        kv_tier.unbind_transfer_source(token)
        faults.clear()
    assert stream.finish_reason == "length"
    assert stream.token_ids == _greedy_reference(params, target, 4)
    assert eng.stats["kv_tier_transfer_pages"] == 0


def test_export_handoff_keeps_pages_resident(params):
    """Disaggregation donor side (docs/disaggregation.md): unlike
    suspend, export_handoff leaves the pages RESIDENT — the donor keeps
    serving pull-side /control/kv_pages fallbacks for the same prefix —
    and the blob round-trips the full chain."""
    eng = _build(params, host_tokens=4096)
    target = _prompt(12, 3 * PAGE)
    with eng:
        cold = eng.submit(target, SP)
        cold.text()
        cached_before = eng._prefix_cache.cached_pages
        out = eng.export_handoff(target)
        assert out is not None
        blob, n = out
        assert n == 3
        # pages stayed put — nothing was demoted or dropped
        assert eng._prefix_cache.cached_pages == cached_before
        meta, recs = from_blob(blob)
        assert [r.hash for r in recs] == hash_blocks(target, PAGE)
        assert meta["page_size"] == PAGE
        # a chain this engine never served exports nothing
        assert eng.export_handoff(_prompt(99, 2 * PAGE)) is None
    assert eng.stats["kv_tier_export_pages"] == 3
    untiered = _build(params, host_tokens=0)
    from generativeaiexamples_tpu.utils.errors import EngineError
    with pytest.raises(EngineError, match="disabled"):
        untiered.export_handoff(target)


def test_push_blob_hang_and_dead_target_bounded():
    """The handoff push (donor → decode /control/kv_resume) must be
    bounded like the pull: a hung transfer or a dead receiver answers
    False within timeout_s — the donor then reports pushed=false and
    the router falls back to recompute."""
    faults.set_plan("kv.transfer=hang")
    t0 = time.monotonic()
    assert kv_tier.push_blob("http://127.0.0.1:1", b"x",
                             timeout_s=0.4) is False
    assert time.monotonic() - t0 < 3.0
    assert faults.fired("kv.transfer") >= 1
    faults.clear()
    # connect-refused receiver: also False, no raise
    assert kv_tier.push_blob("http://127.0.0.1:1", b"x",
                             timeout_s=0.5) is False


def test_suspend_resume_round_trip_across_engines(params):
    """Suspend on engine A, resume on engine B (same geometry): B's
    next turn restores without recompute, token-identical."""
    a = _build(params, host_tokens=4096)
    history = _prompt(5, 3 * PAGE + 5)
    with a:
        cold = a.submit(history, SP)
        cold.text()
        cached_before = a._prefix_cache.cached_pages
        blob = a.suspend_session(history)
        assert blob is not None
        # demotion actually freed HBM pages
        assert a._prefix_cache.cached_pages < cached_before
        assert a.stats["kv_tier_suspended_blocks"] == 3
    b = _build(params, host_tokens=4096)
    with b:
        assert b.resume_session(blob) == 3
        warm = b.submit(history, SP)
        warm.text()
    stats = b.stats
    assert stats["kv_tier_resumed_blocks"] == 3
    assert stats["kv_tier_restore_pages"] == 3   # COW caps at 3 of 3 full
    assert warm.token_ids == cold.token_ids \
        == _greedy_reference(params, history, 4)


def test_reset_fails_pending_control_ops(params):
    """A control op queued against a generation reset() kills must fail
    its waiter immediately — never hang the 30 s timeout, never execute
    against the rebuilt state (a stale suspend would demote a fresh
    cache)."""
    import threading

    from generativeaiexamples_tpu.utils.errors import EngineError
    eng = _build(params, host_tokens=4096)
    box: dict = {}
    ev = threading.Event()
    ran = []
    eng._control.put((lambda: ran.append(1), box, ev))
    eng.reset()
    assert ev.is_set()
    assert isinstance(box.get("error"), EngineError)
    assert not ran                       # never executed
    assert eng._control.empty()          # fresh queue


def test_resume_rejects_geometry_mismatch(params):
    from generativeaiexamples_tpu.utils.errors import EngineError
    eng = _build(params, host_tokens=4096)
    history = _prompt(6, 2 * PAGE)
    with eng:
        eng.submit(history, SP).text()
        blob = eng.suspend_session(history)
    meta, recs = from_blob(blob)
    bad = to_blob(recs, dict(meta, page_size=999))
    with pytest.raises(EngineError, match="geometry"):
        eng.resume_session(bad)
    with pytest.raises(EngineError, match="blob"):
        eng.resume_session(b"not a blob at all")


def test_tier_disabled_preserves_untiered_behavior(params):
    """KV_HOST_POOL_TOKENS=0: no tier object, no offload/restore, the
    eviction path and tokens identical to the pre-tier engine."""
    eng = _build(params, host_tokens=0)
    assert eng._kv_tier is None
    target = _prompt(7, 2 * PAGE + 1)
    with eng:
        cold = eng.submit(target, SP)
        cold.text()
        _churn(eng, seeds=(90, 91, 92))
        warm = eng.submit(target, SP)   # re-prefills: pages were dropped
        warm.text()
    stats = eng.stats
    assert stats["prefix_cache_evicted_pages"] > 0
    for key in ("kv_tier_offload_pages", "kv_tier_restore_pages",
                "kv_tier_restore_hits", "kv_restore_skipped_cost",
                "kv_tier_transfer_pages", "kv_tier_host_pages"):
        assert stats[key] == 0, key
    assert warm.token_ids == cold.token_ids \
        == _greedy_reference(params, target, 4)
    from generativeaiexamples_tpu.utils.errors import EngineError
    with pytest.raises(EngineError, match="disabled"):
        eng.suspend_session(target)


def test_donor_allowlist(monkeypatch):
    monkeypatch.delenv("KV_TRANSFER_ALLOW", raising=False)
    assert kv_tier.donor_allowed("http://anything")      # default: trust
    monkeypatch.setenv("KV_TRANSFER_ALLOW",
                       "http://10.0.3.7, http://replica-2:8081")
    assert kv_tier.donor_allowed("http://10.0.3.7:8081")     # : boundary
    assert kv_tier.donor_allowed("http://10.0.3.7/x")        # / boundary
    assert kv_tier.donor_allowed("http://replica-2:8081")    # exact
    assert kv_tier.donor_allowed("http://replica-2:8081/a")
    assert not kv_tier.donor_allowed("http://attacker.example")
    # startswith alone is NOT a boundary: an attacker-controlled
    # hostname extending an allow entry must not pass
    assert not kv_tier.donor_allowed("http://10.0.3.71:8081")
    assert not kv_tier.donor_allowed(
        "http://replica-2.attacker.example")


def test_transfer_rejects_unrequested_blocks(params, monkeypatch):
    """A donor answer may only land blocks the requester ASKED for —
    anything else could poison unrelated cached prefixes through the
    shared host store."""
    eng = _build(params, host_tokens=4096)
    target = _prompt(11, 2 * PAGE)
    hashes = hash_blocks(target, PAGE)
    rogue = BlockRecord(b"R" * 16, None,
                        {"k": np.zeros((2, 2), np.float32)})
    good = BlockRecord(hashes[0], None,
                       {"k": np.zeros((2, 2), np.float32)})

    def fake_fetch(url, missing, **kw):
        return dict(eng._kv_tier.meta), [rogue, good]

    monkeypatch.setattr(kv_tier, "fetch_blocks", fake_fetch)
    token = kv_tier.bind_transfer_source("http://donor")
    try:
        req_like = type("R", (), {})()
        req_like.prompt_ids = target
        req_like.block_hashes = None
        req_like.stream = type("S", (), {"timeline": None})()
        eng._transfer_prefetch(req_like)
    finally:
        kv_tier.unbind_transfer_source(token)
    assert eng._kv_tier.store.has(hashes[0])
    assert not eng._kv_tier.store.has(b"R" * 16)
    assert eng.stats["kv_tier_transfer_pages"] == 1


def test_int8_kv_offload_restore_serves(params):
    """Structural: the tier round-trips a QUANTIZED pool's four leaves
    (int8 k/v + scale pools) — offloaded pages restore and serve. The
    reused prefix reads back dequantized, so only the structure — not
    the bit trajectory — is pinned (same caveat as warm int8 hits)."""
    cfg = EngineConfig(max_slots=2, max_input_length=64,
                       max_output_length=16, prefill_buckets=(32, 64),
                       page_size=PAGE, dtype="float32",
                       kv_pool_tokens=96, steps_per_round=4,
                       kv_quant="int8", kv_host_pool_tokens=4096)
    eng = Engine(params, CFG, ByteTokenizer(), cfg)
    target = _prompt(9, 2 * PAGE + 1)
    with eng:
        cold = eng.submit(target, SP)
        cold.text()
        _churn(eng, seeds=(95, 96, 97))
        _wait_for_offload(eng)
        warm = eng.submit(target, SP)
        warm.text()
    stats = eng.stats
    assert stats["kv_tier_restore_pages"] >= 1
    assert warm.finish_reason == "length" and len(warm.token_ids) == 4
    assert warm.token_ids[:2] == cold.token_ids[:2]


def test_cross_replica_transfer_end_to_end(params):
    """Donor replica A serves a conversation; replica B — hinted via
    the transfer contextvar, exactly what the chain server binds from
    X-KV-Transfer-From — fetches A's prefix pages over a REAL
    /control/kv_pages HTTP endpoint and restores them at admission,
    token-identical to recompute."""
    from types import SimpleNamespace

    import bench
    from generativeaiexamples_tpu.chains.server import create_app

    a = _build(params, host_tokens=4096)
    b = _build(params, host_tokens=4096)
    target = _prompt(8, 3 * PAGE)
    try:
        a.start()
        cold = a.submit(target, SP)
        cold.text()
        app = create_app(SimpleNamespace(
            llm=SimpleNamespace(engine=a)))
        (url,), stop = bench.serve_apps([app])
        try:
            token = kv_tier.bind_transfer_source(url)
            try:
                b.start()
                warm = b.submit(target, SP)
                warm.text()
            finally:
                kv_tier.unbind_transfer_source(token)
        finally:
            stop()
        stats_b = b.stats
        assert stats_b["kv_tier_transfer_pages"] == 3
        # COW: 2 of the 3 fetched blocks restore (tail recomputed)
        assert stats_b["kv_tier_restore_pages"] == 2
        assert warm.token_ids == cold.token_ids \
            == _greedy_reference(params, target, 4)
        # the donor's export also warmed its own host tier
        assert a.stats["kv_tier_host_pages"] == 3
    finally:
        a.stop()
        b.stop()


def test_transfer_donor_selection():
    """Router-side hint logic: a sibling whose sketch covers the prompt
    head strictly better than the chosen replica (and by >= min_blocks)
    is the donor; otherwise no hint."""
    from generativeaiexamples_tpu.router.table import ReplicaTable

    table = ReplicaTable()
    r0 = table.add("r0", "http://r0")
    r1 = table.add("r1", "http://r1")
    blocks = table.affinity_blocks("s" * 400)
    assert table.transfer_donor(blocks, chosen="r0") is None
    table.record_placement(r1, blocks)        # r1 knows the prefix
    assert table.transfer_donor(blocks, chosen="r0") == "http://r1"
    assert table.transfer_donor(blocks, chosen="r1") is None  # self
    # min_blocks gates small matches
    assert table.transfer_donor(blocks[:1], chosen="r0",
                                min_blocks=2) is None
    # unreachable donors are never named
    table.mark_unreachable("r1")
    assert table.transfer_donor(blocks, chosen="r0") is None
    # draining donors still serve pages
    table.update_health("r1", ok=True, ready=False,
                        body={"draining": True})
    assert table.transfer_donor(blocks, chosen="r0") == "http://r1"
    assert r0.name == "r0"
