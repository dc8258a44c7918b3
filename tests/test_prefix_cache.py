"""Shared-prefix KV cache tests.

Host-side unit coverage (block hashing, trie match, refcounts, COW
demotion, LRU leaf-first eviction — no device needed) plus engine-level
serving tests on the CPU backend: a warm request must produce EXACTLY
the cold path's tokens while skipping prefill for the cached prefix
(``prefix_cache_hit_tokens``), and eviction under pool pressure must
never strand pages.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.engine import (Engine, EngineConfig,
                                             SamplingParams)
from generativeaiexamples_tpu.engine.prefix_cache import (
    PrefixCache, hash_blocks, usable_prefix_tokens)
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models.configs import LlamaConfig
from generativeaiexamples_tpu.models.tokenizer import ByteTokenizer

PAGE = 16

CFG = LlamaConfig(vocab_size=259 + 5, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position_embeddings=256)


# --------------------------------------------------------------- unit level

def test_hash_blocks_full_blocks_only_and_chaining():
    toks = list(range(40))
    hashes = hash_blocks(toks, PAGE)
    assert len(hashes) == 2          # the 8-token tail is not hashable
    # identical prefix -> identical chain
    assert hash_blocks(toks[:32], PAGE) == hashes
    # a change in block 0 reaches block 1 through the parent chain
    other = hash_blocks([1] + toks[1:], PAGE)
    assert other[0] != hashes[0] and other[1] != hashes[1]
    # position matters: the same 16 tokens as block 1 hash differently
    assert hash_blocks(toks[:16], PAGE)[0] != \
        hash_blocks(toks[16:32] + toks[16:32], PAGE)[1]


def test_usable_prefix_tokens_cow_cap():
    assert usable_prefix_tokens(0, 40, PAGE) == 0
    assert usable_prefix_tokens(2, 40, PAGE) == 32    # tail is uncached
    assert usable_prefix_tokens(1, 17, PAGE) == 16    # 1 token to prefill
    # full cover: capped one block short so >= 1 token runs through
    # prefill (COW demotion — the tail block gets a private page)
    assert usable_prefix_tokens(2, 32, PAGE) == 16
    assert usable_prefix_tokens(1, 16, PAGE) == 0


def _chain(cache: PrefixCache, toks, pages):
    hashes = hash_blocks(toks, PAGE)
    assert len(hashes) == len(pages)
    for i, (h, p) in enumerate(zip(hashes, pages)):
        assert cache.insert(h, hashes[i - 1] if i else None, p)
    return hashes


def test_match_acquire_release_refcount_lifecycle():
    cache = PrefixCache(PAGE)
    toks = list(range(48))
    hashes = _chain(cache, toks, [1, 2, 3])
    assert cache.match(hashes) == 3
    assert cache.match(hash_blocks([9] * 48, PAGE)) == 0
    assert cache.acquire(hashes[:2]) == [1, 2]
    # refcounts (registrant's + ours) pin every page: nothing evictable
    assert cache.evict(10) == []
    cache.release(hashes[:2])
    cache.release(hashes)        # registrant retires too
    assert cache.owns(2)         # refcount 0 but still resident (warm)
    assert cache.cached_pages == 3
    # reclaim walks leaf-first so surviving chains stay walkable
    assert cache.evict(2) == [3, 2]
    assert cache.match(hashes) == 1
    assert cache.evict(5) == [1]
    assert cache.cached_pages == 0


def test_eviction_is_lru_across_chains():
    cache = PrefixCache(PAGE)
    ha = _chain(cache, list(range(32)), [1, 2])
    hb = _chain(cache, list(range(100, 116)), [3])
    cache.release(ha)            # A idle first -> older tick
    cache.release(hb)
    assert cache.evict(1) == [2]     # A's leaf, LRU
    assert cache.evict(2) == [1, 3]  # then A's root, then B


def test_evict_never_rescans_entries():
    """The evictable-leaf heap is maintained incrementally (pushed on
    release-to-zero / last-child-gone, lazily invalidated): evict()
    must do NO full scan of the entry table, however many times it is
    called in warm steady state. Pinned by swapping the entry dict for
    one whose iteration paths raise."""
    cache = PrefixCache(PAGE)
    released = []
    for r in range(50):
        toks = [(r * 97 + i) % 250 + 3 for i in range(32)]
        h = _chain(cache, toks, [2 * r + 1, 2 * r + 2])
        cache.release(h)
        released.append(h)

    class NoScanDict(dict):
        def __iter__(self):
            raise AssertionError("evict iterated _entries")

        def items(self):
            raise AssertionError("evict scanned _entries.items()")

        def keys(self):
            raise AssertionError("evict scanned _entries.keys()")

        def values(self):
            raise AssertionError("evict scanned _entries.values()")

    cache._entries = NoScanDict(cache._entries)
    freed = []
    for _ in range(30):   # one eviction per admission, steady state
        freed += cache.evict(1)
    assert len(freed) == 30
    # LRU leaf-first order intact: chain r's leaf (2r+2) before its
    # root (2r+1), chains in release (tick) order
    assert freed[:6] == [2, 1, 4, 3, 6, 5]
    # lazy invalidation: re-acquiring makes heap copies stale, a later
    # release re-arms eviction at the NEW recency
    live = released[20]
    # plain dict again (unbound dict.items bypasses the raising
    # overrides — this is test scaffolding, not evict behavior)
    cache._entries = {k: v for k, v in dict.items(cache._entries)}
    cache.acquire(live)
    assert cache.evict(2) != []             # skips the stale entries
    cache.release(live)
    # next LRU chain evicts; the re-released chain 20 moved to the
    # BACK of the LRU (new tick) — its stale heap copies are skipped
    assert set(cache.evict(2)) == {33, 34}
    rest = cache.evict(1000)
    assert rest[-2:] == [42, 41]            # chain 20 last, leaf-first
    assert cache.cached_pages == 0


def test_evict_sink_sees_victims_before_removal():
    cache = PrefixCache(PAGE)
    h = _chain(cache, list(range(32)), [1, 2])
    cache.release(h)
    seen = []
    cache.evict(2, sink=lambda hh, e: seen.append((hh, e.page, e.parent)))
    assert [(s[1], s[2]) for s in seen] == [(2, h[0]), (1, None)]


def test_remove_demotes_only_reclaimable_blocks():
    cache = PrefixCache(PAGE)
    h = _chain(cache, list(range(48)), [1, 2, 3])
    assert cache.remove(h[0]) is None      # has children
    assert cache.remove(h[2]) is None      # still referenced (refcount 1)
    cache.release(h)
    assert cache.remove(h[2]) == 3         # leaf-first works
    assert cache.remove(h[1]) == 2
    assert cache.remove(h[0]) == 1
    assert cache.remove(h[0]) is None      # gone
    assert cache.cached_pages == 0
    assert cache.stats.evicted_pages == 0  # demotion is not an eviction


def test_insert_dedup_keeps_page_private():
    cache = PrefixCache(PAGE)
    hashes = _chain(cache, list(range(16)), [1])
    assert cache.insert(hashes[0], None, 7) is False
    assert not cache.owns(7)     # duplicate block: caller keeps page 7
    assert cache.cached_pages == 1


# ------------------------------------------------------------- engine level

def _build(prompt_cap=None, pool_tokens=None, prefix=True, kv_quant="",
           max_in=128, key=31):
    params = llama.init_params(CFG, jax.random.key(key), dtype=jnp.float32)
    cfg = EngineConfig(max_slots=2, max_input_length=max_in,
                       max_output_length=16, prefill_buckets=(32, 64),
                       page_size=PAGE, dtype="float32",
                       kv_pool_tokens=pool_tokens, steps_per_round=4,
                       max_prefill_bucket=prompt_cap, prefix_cache=prefix,
                       kv_quant=kv_quant)
    return Engine(params, CFG, ByteTokenizer(), cfg), params


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


SP = SamplingParams(max_tokens=6, top_k=1, ignore_eos=True)


def _pages_conserved(eng):
    cached = eng._prefix_cache.cached_pages if eng._prefix_cache else 0
    return len(eng._free_pages) + cached == eng._n_pages - 1


def test_shared_prefix_hit_parity_with_cold_path():
    eng, params = _build()
    prompt_a = [(i * 7) % 250 + 3 for i in range(40)]
    prompt_b = prompt_a[:32] + [(i * 13) % 250 + 3 for i in range(9)]
    with eng:
        a = eng.submit(prompt_a, SP)
        a.text()
        assert eng.stats["prefix_cache_hit_tokens"] == 0
        b = eng.submit(prompt_b, SP)     # shares A's first 2 blocks
        b.text()
    stats = eng.stats
    assert stats["prefix_cache_hit_tokens"] == 32
    assert 0 < stats["prefix_cache_hit_rate"] < 1
    # token-level parity with the uncached path (pure forward)
    assert a.token_ids == _greedy_reference(params, prompt_a, 6)
    assert b.token_ids == _greedy_reference(params, prompt_b, 6)
    assert _pages_conserved(eng)


def test_identical_resubmission_cow_demotes_tail_block():
    """A fully cached, page-aligned prompt still prefills its last block
    (at least one token must produce logits): the shared tail page is
    NOT mapped — COW demotion gives that logical block a private page —
    and output parity holds."""
    eng, params = _build()
    prompt = [(i * 11) % 250 + 3 for i in range(32)]   # exactly 2 blocks
    with eng:
        a = eng.submit(prompt, SP)
        a.text()
        b = eng.submit(prompt, SP)
        b.text()
    assert eng.stats["prefix_cache_hit_tokens"] == 16  # capped, not 32
    assert a.token_ids == b.token_ids == _greedy_reference(params, prompt, 6)
    assert _pages_conserved(eng)


def test_multi_chunk_hit_after_long_prompt_admission():
    """Prefix hits compose with chunked long-prompt serving: a 98-token
    prompt sharing 48 tokens with a cached 80-token one admits as two
    suffix chunks (seeded seen mask + accumulate) and matches the pure
    forward exactly."""
    eng, params = _build(prompt_cap=32)
    prompt_a = [(i * 7) % 250 + 3 for i in range(80)]
    prompt_b = prompt_a[:48] + [(i * 5) % 250 + 3 for i in range(50)]
    with eng:
        a = eng.submit(prompt_a, SP)     # cold chunked admission
        a.text()
        b = eng.submit(prompt_b, SP)
        b.text()
    assert eng.stats["prefix_cache_hit_tokens"] == 48
    assert a.token_ids == _greedy_reference(params, prompt_a, 6)
    assert b.token_ids == _greedy_reference(params, prompt_b, 6)


def test_repetition_penalty_seen_mask_seeded_across_hit():
    """The skipped prefix must still count toward the repetition
    penalty: warm output with rep_pen equals the cold reference."""
    sp = SamplingParams(max_tokens=8, top_k=1, ignore_eos=True,
                        repetition_penalty=1.3)
    prompt = [(i * 7) % 250 + 3 for i in range(40)]
    eng, params = _build()
    with eng:
        cold = eng.submit(prompt, sp)
        cold.text()
        warm = eng.submit(prompt, sp)
        warm.text()
    assert eng.stats["prefix_cache_hit_tokens"] == 32
    assert warm.token_ids == cold.token_ids


def test_eviction_under_pool_pressure_and_page_conservation():
    """Distinct prompts churn through a pool too small to keep every
    retired prefix warm: admission evicts refcount-0 chains instead of
    backpressuring forever, every request completes, and no page is
    leaked or double-freed."""
    # extent = 32 + 16 -> 3 pages/request; 6-page pool holds at most two
    # retired 2-block prefixes, so the 4 distinct prompts force eviction
    eng, _ = _build(pool_tokens=96, max_in=32)
    sp = SamplingParams(max_tokens=4, top_k=1, ignore_eos=True)
    with eng:
        for r in range(4):
            s = eng.submit([(r * 31 + i) % 250 + 3 for i in range(32)], sp)
            s.text()
            assert s.finish_reason == "length" and len(s.token_ids) == 4
    stats = eng.stats
    assert stats["prefix_cache_evicted_pages"] > 0
    assert _pages_conserved(eng)


def test_warm_pages_reused_not_leaked_across_many_turns():
    """A growing multi-turn conversation keeps hitting: each turn's
    prompt extends the last, so hit tokens grow with the history."""
    eng, _ = _build()
    history = [(i * 3) % 250 + 3 for i in range(32)]
    hits = []
    with eng:
        for _turn in range(3):
            s = eng.submit(history, SP)
            s.text()
            hits.append(eng.stats["prefix_cache_hit_tokens"])
            history = history + s.token_ids \
                + [(len(history) * 7 + j) % 250 + 3 for j in range(10)]
    assert hits[0] == 0 and hits[1] > 0 and hits[2] > hits[1]
    assert _pages_conserved(eng)


def test_prefix_cache_disabled_by_config():
    eng, _ = _build(prefix=False)
    prompt = [(i * 7) % 250 + 3 for i in range(40)]
    with eng:
        a = eng.submit(prompt, SP)
        a.text()
        b = eng.submit(prompt, SP)
        b.text()
    assert "prefix_cache_hit_tokens" not in eng.stats
    assert a.token_ids == b.token_ids
    assert sorted(eng._free_pages) == list(range(1, eng._n_pages))


def test_int8_kv_prefix_hit_serves():
    """Structural: hits over a quantized pool admit and complete (the
    reused prefix reads back dequantized, so only the structure — not
    the bit trajectory — is pinned; same caveat as chunked int8)."""
    eng, _ = _build(kv_quant="int8")
    prompt = [(i * 9) % 250 + 3 for i in range(40)]
    with eng:
        a = eng.submit(prompt, SP)
        a.text()
        b = eng.submit(prompt, SP)
        b.text()
    assert eng.stats["prefix_cache_hit_tokens"] == 32
    assert b.finish_reason == "length" and len(b.token_ids) == 6
    assert a.token_ids[:3] == b.token_ids[:3]


def test_reset_clears_cache_and_serves_again():
    eng, _ = _build()
    prompt = [(i * 7) % 250 + 3 for i in range(40)]
    eng.start()
    eng.submit(prompt, SP).text()
    assert eng._prefix_cache.cached_pages > 0
    eng.reset()
    assert eng._prefix_cache.cached_pages == 0
    eng.start()
    s = eng.submit(prompt, SP)
    s.text()
    assert eng.stats["prefix_cache_hit_tokens"] == 0  # fresh cache
    eng.stop()
