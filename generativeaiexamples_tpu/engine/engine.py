"""Continuous-batching inference engine with a paged KV cache.

The TPU-native replacement for the reference's Triton + TRT-LLM C++ serving
core with "inflight fused batching" and paged KV
(reference: ensemble_models/llama/tensorrt_llm/config.pbtxt.j2:28-34,
model_server/server.py:67-71). Architecture:

- **Decode slots.** A fixed-size batch of decode requests (static shapes for
  XLA). Every decode step runs the whole slot batch through one jitted
  program; inactive slots are masked. Requests join and leave the batch
  between rounds, the compiled program never changes.
- **Paged KV pool.** KV lives in a shared pool of fixed-size pages; each
  slot holds a block table mapping logical to physical pages. Admission
  allocates a request's full extent (prompt + max_tokens) and backpressures
  when the pool is exhausted — so cache capacity is sized to HBM, not to
  ``slots × max_len``. Decode attention gathers only the smallest page
  window covering the longest active sequence (bucketed per compile), so
  HBM reads scale with live context.
- **Multi-step decode rounds.** Each dispatch is a ``lax.scan`` of
  ``steps_per_round`` decode steps with *device-side* eos/length
  termination — one host<->device round trip per K tokens instead of per
  token.
- **Dispatch-ahead.** Up to ``dispatch_depth`` rounds are enqueued on the
  device before dispatch pauses, overlapping host processing and device
  compute.
- **Overlapped harvest.** Device→host readbacks never run on the
  scheduling path. The scheduler thread only admits and dispatches; a
  dedicated harvest worker consumes the dispatched programs' output
  arrays IN ORDER (first tokens, then each decode round), blocking on
  each host copy off-thread and waking streams as results land. The
  readback wait therefore runs concurrently with the next
  admissions/dispatches instead of serializing the loop. Finish
  decisions feed
  back to the scheduler through a completion queue, so slot/page/cache
  bookkeeping and every device dispatch stay single-threaded.
- **Bucketed prefill.** Prompts are padded to the nearest static bucket
  (a page multiple) and prefilled as a separate jitted call, then their KV
  is scattered into the slot's pages.
- **Streaming.** Each request gets a thread-safe ``TokenStream`` — the
  decoupled-response equivalent of the reference's gRPC streaming callbacks
  (reference: model_server_client/trt_llm.py:417-442).
"""

from __future__ import annotations

import functools
import itertools
import logging
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Iterator, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ..models import llama
from ..models.configs import LlamaConfig
from ..models.kv_cache import kv_cache_of
from ..models.tokenizer import Tokenizer
from ..obs import flight as obs_flight
from ..obs import rounds as obs_rounds
from ..obs.metrics import observe_stage
from ..obs.tracing import phase, record_stage
from ..ops.sampling import pack_mask_np
from ..parallel.sharding import (llama_param_specs,
                                 shard_params)
from ..utils import compile_cache, faults
from ..utils.errors import (ConfigError, EngineError, RoleMismatchError,
                            SchedulerFullError)
from ..utils.hbm import peak_bw
from ..utils.logging import get_logger, log_event
from . import kv_tier as kv_tier_mod
from . import programs
from . import resume as engine_resume
from .detokenizer import IncrementalDetokenizer, StopWordTrap
from .kv_tier import KVTier
from .prefix_cache import PrefixCache, hash_blocks, usable_prefix_tokens
from .sampling_params import SamplingParams
from .scheduler import (OnlineCalibrator, PrefillJob, StepCostModel,
                        TokenBudgetScheduler, online_calib_enabled,
                        topology_key)
from .spec_decode import (AdaptiveDraftController, PromptLookupDrafter,
                          SpecConfig, spec_enabled)


logger = get_logger(__name__)

# Short per-engine tag stamped on round-telemetry records: multi-engine
# processes (the fleet bench, tests) share the process-global round ring,
# and the tag is what tells their rounds apart in /debug/rounds.
_ENGINE_TAGS = itertools.count()


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _pow2_ladder(top: int) -> tuple:
    """(1, 2, 4, ..., top): the compiled-shape rungs for decode page
    windows and fused-tail active-row counts — a request pays for the
    smallest rung covering it, not the maximum."""
    ladder = []
    w = 1
    while w < top:
        ladder.append(w)
        w *= 2
    return tuple(ladder + [top])


# Engine-owned cumulative counters, the keys ``stats()`` always carries.
# A TEMPLATE (each Engine copies it) so tools/check_metrics_docs.py can
# enumerate the stats surface without building an engine — the tier-1
# guard that keeps docs/observability.md's gauge table and stats() from
# drifting apart.
_STATS_TEMPLATE = {
    "requests": 0, "tokens_generated": 0, "decode_steps": 0, "prefills": 0,
    # Pipeline stage counters (cumulative ms + event counts): how long
    # the harvest worker blocked on round/first readbacks — time that
    # overlaps dispatch instead of serializing the loop.
    "harvest_wait_ms": 0.0, "harvest_rounds": 0,
    "first_readback_ms": 0.0, "first_readbacks": 0,
    # Monotonic high-water mark of the device queue (rounds dispatched
    # ahead of harvest): the live gauge reads 0 whenever the engine is
    # idle, so artifacts sampled after a run need the peak to show the
    # overlap actually happened.
    "dispatch_depth_peak": 0,
    # Robustness counters: submissions rejected at the queue (shed as
    # 429 at the HTTP edge), queued requests dropped because their
    # deadline expired before admission (they never reach prefill), and
    # decodes stopped mid-generation by a passing deadline.
    "rejected_full": 0,
    "deadline_queue_drops": 0,
    "deadline_stops": 0,
    # Token-budget scheduler (engine/scheduler.py): the resolved
    # per-round budget, cumulative prefill tokens it granted as chunks
    # and the tokens of the compiled shapes those chunks ran in (their
    # ratio is how full the chunk programs are), cumulative decode
    # token-equivalents charged against it, and how many rounds actually
    # mixed a decode dispatch with prefill chunks (the interleaving the
    # budget exists to enable).
    "sched_round_budget_tokens": 0,
    "sched_prefill_tokens": 0,
    "sched_prefill_padded_tokens": 0,
    # Chunk / prefill programs dispatched: one for a prompt's chunk, ONE
    # for the whole-bucket chunks of four prompts that one plan granted
    # together (_advance_prefill_rows) — grants over programs is
    # the rows a program carried.
    "sched_chunk_programs": 0,
    "sched_decode_tokens": 0,
    "sched_interleaved_rounds": 0,
    # Fused unembed/sampling tail (ops/fused_sampler.py): slot-rows that
    # actually ran through the vocab projection + sampler per decode
    # step, vs rows the former all-slots tail would have computed but the
    # active-slot compaction skipped (partial occupancy — the proof the
    # tail no longer pays for empty slots).
    "sampler_rows_sampled": 0,
    "sampler_rows_skipped": 0,
    # Speculative decoding (engine/spec_decode.py): draft tokens
    # proposed by the prompt-lookup drafter, how many of them the
    # batched verify step accepted, verify rounds dispatched, tokens
    # those rounds emitted (accepted drafts + the per-slot correction/
    # bonus token), and slot participations in verify rounds (the
    # denominator of the tokens-per-model-step multiplier).
    "spec_draft_tokens": 0,
    "spec_accepted_tokens": 0,
    "spec_verify_rounds": 0,
    "spec_verify_tokens": 0,
    "spec_verify_slot_steps": 0,
    # Tiered KV store (engine/kv_tier.py): refcount-0 prefix pages
    # offloaded to the host-RAM tier instead of dropped at eviction,
    # pages restored H2D at admission (and admissions that restored
    # >= 1 page), admissions whose host-tier hit was deliberately
    # re-prefilled because the step-cost model priced restore more
    # expensive than recompute, pages imported from a sibling replica
    # over /control/kv_pages, and blocks moved through session
    # suspend/resume. All 0 forever with KV_HOST_POOL_TOKENS=0.
    "kv_tier_offload_pages": 0,
    "kv_tier_restore_pages": 0,
    "kv_tier_restore_hits": 0,
    "kv_restore_skipped_cost": 0,
    "kv_tier_transfer_pages": 0,
    "kv_tier_suspended_blocks": 0,
    "kv_tier_resumed_blocks": 0,
    # Disaggregated prefill/decode handoff (docs/disaggregation.md):
    # finished prefix pages exported for push-on-completion handoff to a
    # decode replica, and donor-side /control/kv_pages exports refused
    # because the concurrent-export bound was already held (the chain
    # server's semaphore sheds with 429 + Retry-After so N simultaneous
    # handoffs can't stall this engine's decode rounds).
    "kv_tier_export_pages": 0,
    "kv_export_shed": 0,
    # KV blob integrity (engine/kv_tier.py v2 wire format): transfer /
    # handoff / session blobs whose per-array CRC32 (or framing) failed
    # verification — each one fell back cleanly to recompute instead of
    # admitting garbage pages. 0 on a healthy network.
    "kv_restore_corrupt": 0,
    # Liveness watchdog (ENGINE_WATCHDOG_STALL_S): times the watchdog
    # declared the engine stalled — work queued or in flight while the
    # round/harvest progress counters stayed frozen past the threshold.
    # Each detection dumps thread stacks + the last round record via a
    # structured ``engine_watchdog_stall`` log event and flips /health
    # to 503 until progress resumes.
    "watchdog_stalls": 0,
    # Round telemetry (obs/rounds.py): engine rounds whose plan AND
    # every harvested device output have been recorded — the flight-
    # recorder-style per-round records behind GET /debug/rounds.
    "rounds_completed": 0,
    # Online cost calibration (engine/scheduler.py OnlineCalibrator):
    # times recalibrate() actually moved the derived round budget —
    # 0 forever when SCHED_ONLINE_CALIB=0 or the budget is pinned.
    "sched_budget_recalibrations": 0,
    # Construction-time feature downgrades (fused tail -> materialized,
    # Pallas kernel -> jnp gather, ...): each one also logs a structured
    # ``engine_feature_downgrade`` event. 0 on a fully-armed engine —
    # > 0 means this engine serves correctly but below its hardware's
    # potential, which used to be a silent comment-only fallback.
    # (Mirrored as the ``engine_downgrades`` gauge.)
    "downgrades": 0,
    # 1 where this engine's decode tails, greedy AND sampled, run the
    # head-streaming kernels (ops/head_argmax.py: a per-column int8, raw
    # or tied head, off-mesh, on a TPU), 0 where they run the tile scan.
    # Static per engine. ``tail_kernel_rounds``: the decode rounds
    # dispatched whose tail ran one (every one of an armed engine's).
    "tail_kernel": 0,
    "tail_kernel_rounds": 0,
    # 1 where the chunk programs' recurrence (a model with recurrent
    # layers: ops/gated_delta.py, ops/ssd.py) runs the chunked scan as ONE
    # Pallas kernel (a TPU, whole 64-token blocks, 128-lane heads in pairs
    # — in eights where the decay is a channel's; 64-value heads, sixteen
    # a step, a state-space layer's), 0 where it runs XLA stages or none.
    # Static per engine; a TPU engine with recurrent layers that reads 0
    # also counts a downgrade. ``scan_kernel_chunks``: the chunk
    # programs dispatched whose recurrence ran it (every one of an
    # armed engine's: ``sched_chunk_programs``).
    "scan_kernel": 0,
    "scan_kernel_chunks": 0,
    # Times prewarm() had to shrink the auto-sized KV pool because the
    # worst-case request did not fit (each one also logs an
    # ``engine_pool_shrink`` event). 0 on a healthy build: > 0 means
    # the headroom model is short for this geometry and the engine
    # serves with fewer pages than the device's free memory promised.
    "pool_shrinks": 0,
    # KV pool occupancy (gauge, set when a round begins): pages live
    # requests hold = total - free - evictable prefix-cache pages; and
    # dispatched rounds in which a waiting request was kept out for want
    # of pages (RoundRecord.blocked_on_pages > 0): refused at admission,
    # or not offered since the pool last refused it.
    "pool_used_pages": 0,
    "pool_blocked_rounds": 0,
    # Window layers (LlamaConfig.sliding_window): pages of the live
    # contexts the decode rounds did NOT read because they lie behind a
    # layer's window, in whole pages averaged over the layers (the unit
    # of round_pages_touched). 0 for a model without window layers.
    "kv_pages_skipped": 0.0,
    # Learned sparse attention (LlamaConfig.index_topk): cached rows the
    # decode rounds' attention SELECTED, a layer (each live row's
    # min(context, index_topk), summed over rows and steps), and rows
    # their indexer scored, a full layer (each live row's context). From
    # the dispatch plan. 0 for a model without an indexer.
    "kv_rows_selected": 0,
    "kv_rows_indexed": 0,
    # ... and the cached rows their attention STREAMED out of HBM, a
    # layer: over the decode kernel each live row's context in whole
    # blocks of the kernel's pages, gathered every slot's whole window.
    "kv_rows_read": 0,
    # Recurrent layers whose decode step is the Pallas kernel over the
    # state leaf (ProgramSpec.state_step_kernel): slot-steps of the
    # decode rounds whose slot held no decoding sequence — state the
    # kernel neither fetched nor wrote — and slot-steps it stepped. From
    # the dispatch plan. 0 for every other model.
    "state_rows_idle": 0,
    "state_rows_live": 0,
    # Dropless experts (moe_impl "dropless"): the sum over decode rounds
    # of the mean distinct experts a layer's rows reached in a step, and
    # the rounds that reported one (their ratio is the mean experts a
    # layer streams a step). 0 for every other model.
    "experts_touched_sum": 0.0,
    "experts_touched_rounds": 0,
    # The fused tail's candidate merge (ops/fused_sampler.py
    # _merge_tile): the sum over SAMPLED decode rounds of the share, in
    # percent, of a step's vocabulary tiles whose merge could not prove
    # its pre-selection and sorted the whole tile, and the rounds that
    # reported one. 0 for greedy rounds and the materialized tail.
    "tail_resort_pct_sum": 0.0,
    "tail_resort_pct_rounds": 0,
    # An expert share (models/configs.py ``experts_held``): the sum over
    # decode rounds of the mean assignments a step's rows made to the
    # experts this tree holds, a layer, and the rounds that reported one.
    # 0 where the tree holds every expert.
    "local_assignments_sum": 0.0,
    "local_assignments_rounds": 0,
    # ... and the padded rows a layer's dispatch gather walked to serve
    # them (RoundRecord.route_rows_read), summed over the same rounds;
    # ``stats`` derives route_rows_per_assignment, rows read an
    # assignment held
    "route_rows_read_sum": 0.0,
    "route_rows_read_rounds": 0,
    # Hyper-connections (models/configs.py ``hc_mult``): the sum over
    # decode rounds of how far the rows of a layer's write-back matrices
    # were from summing to 1 (ops/hyper_connection.py ``row_defect``),
    # and the rounds that reported one. 0 on the plain residual path.
    "hc_row_defect_sum": 0.0,
    "hc_row_defect_rounds": 0,
    # under a router limited to groups and an expert share: the share
    # (%) of a decode round's live rows whose kept groups include a held
    # one (RoundRecord.route_groups_held_pct), summed over the rounds
    "route_groups_held_pct_sum": 0.0,
    "route_groups_held_pct_rounds": 0,
}

# The process's program build log (utils/compile_cache.py), read into
# every stats snapshot: programs JAX built — compiled or loaded from the
# persistent cache — and the seconds spent tracing, lowering, compiling
# and loading them. Process-wide, not per engine.
_BUILD_LOG_KEYS = ("programs_built", "program_trace_s", "program_lower_s",
                   "program_compile_s", "program_cache_load_s",
                   "program_cache_hits")


def engine_stat_keys() -> tuple[str, ...]:
    """Every key an ``Engine.stats`` snapshot can contain: the cumulative
    template above, the read-time pipeline gauge, and the prefix-cache
    counters (prefix caching is on by default). The single source of
    truth tools/check_metrics_docs.py checks the docs against."""
    from .prefix_cache import CacheStats
    return (tuple(_STATS_TEMPLATE)
            + ("dispatch_queue_depth", "queue_waiting",
               "sched_prefill_share", "sched_prefill_fill",
               "spec_acceptance_rate", "spec_tokens_per_step",
               "sched_cost_drift_ratio", "route_rows_per_assignment",
               "kv_tier_host_pages", "kv_restore_hit_rate",
               "kv_bytes_per_token", "index_bytes_per_token",
               "state_bytes", "slot_bytes", "prefix_cache_off", "uptime_s")
            + _BUILD_LOG_KEYS
            + tuple(CacheStats().snapshot()) + ("prefix_cache_pages",))


@functools.lru_cache(maxsize=64)
def _zeros_program(shape: tuple, dtype, placement):
    """Jitted ``zeros`` born at ``placement`` (a sharding, or a Format
    carrying a layout). Cached: every engine of one geometry — and each
    reset() — reuses one compiled program."""
    return jax.jit(functools.partial(jnp.zeros, shape, dtype),
                   out_shardings=placement)


# Share of the post-headroom free HBM the auto-sized pool may claim; the
# rest absorbs allocator fragmentation and what _headroom_bytes does not
# model. On a v5e the device's own accounting is exact and the headroom
# model sits above the compiler's temp need for the largest one-shot
# bucket in both KV modes (llama-2-7b int8, bucket 3072: 3.2 GB of 3.9
# modeled with a bf16 pool, 4.8 of 5.5 with an int8 pool), so one margin
# serves both. prewarm() serves a max-length request against the result,
# and a pool that still does not fit shrinks LOUDLY
# (stats["pool_shrinks"]).
_POOL_MARGIN = 0.9


class _StaleLoop(Exception):
    """Raised inside a loop thread that reset() has disowned — unwinds the
    whole _run() without touching the new generation's state."""


def weight_bytes_of(params, model_cfg: LlamaConfig) -> tuple[int, int]:
    """``(all, routed)``: the bytes of the parameter tree AS IT IS —
    every stack, a shared expert, a gate matrix, whatever the tree holds
    — and, of those, the dropless routed experts' (the stacks whose
    layers have a router): a decode step streams the rest whole and of
    these only the experts its rows reach (``_step_weight_bytes``).
    Leaves need a shape and a dtype only."""
    def nbytes(tree) -> int:
        return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(tree))

    routed = sum(nbytes(params[stack][name])
                 for stack in programs.routed_stacks(params, model_cfg)
                 for name in ("w_gate", "w_up", "w_down"))
    return nbytes(params), routed


@dataclass(frozen=True)
class EngineConfig:
    """Engine sizing. Limits mirror the reference's engine defaults
    (reference: model_server/__main__.py:81-92, config.pbtxt.j2:29)."""
    max_slots: int = 8                # concurrent decode requests
    max_input_length: int = 3000
    max_output_length: int = 512
    prefill_buckets: tuple[int, ...] = (128, 512, 1024, 2048, 3072)
    dtype: str = "bfloat16"
    seed: int = 0
    max_queue: int = 256
    # Paged KV pool. "auto" sizes the pool to the device's free HBM (so the
    # default geometry actually runs on one chip); None = full capacity
    # (max_slots x max cache extent); an int = pool size in tokens.
    page_size: int = 128
    kv_pool_tokens: Union[int, str, None] = "auto"
    # Decode pipelining: tokens generated per device dispatch, and how many
    # dispatches ride the device queue before the host blocks on results.
    steps_per_round: int = 8
    dispatch_depth: int = 2
    # Long-prompt serving: cap the largest compiled prefill bucket; prompts
    # beyond it stream through the paged pool in bucket-size chunks
    # (bounded prefill activations/cache for e.g. 32k-token prompts).
    # None = one-shot prefill up to max_input_length (the default; the
    # chunked path never runs).
    max_prefill_bucket: Optional[int] = None
    # KV-cache quantization: "" (pool in `dtype`) or "int8" (per-row
    # symmetric int8 pools + bf16 scale pools, ops/kv_quant.py) — halves
    # KV bytes per token, so the auto-sized pool holds ~2x the pages at
    # fixed HBM (the reference's batch-128 capacity rides the same
    # TRT-LLM lever; reference: config.pbtxt.j2:29).
    kv_quant: str = ""
    # Shared-prefix KV reuse (engine/prefix_cache.py): prompts are hashed
    # in page-sized blocks and admission maps the longest cached prefix
    # into the slot's page table read-only, so prefill starts at the
    # first uncached token — the repeat-turn/chat TTFT lever (vLLM
    # prefix caching / SGLang RadixAttention, adapted to this pool).
    # Retired requests' prompt pages stay resident at refcount 0 and are
    # reclaimed LRU under pool pressure; the pool remains the only
    # capacity budget. NOTE under kv_quant the reused prefix is read
    # back dequantized, so a warm request tracks (not bit-matches) the
    # cold trajectory — same caveat as chunked long-prompt admission.
    prefix_cache: bool = True
    # Token-budget continuous scheduler (engine/scheduler.py): per-round
    # prefill-token budget and per-request chunk cap. None = derive the
    # budget from the PROFILE_rNN step-cost model (prefill tokens whose
    # modeled cost equals one decode round) and let the chunk cap follow
    # the budget. SCHED_ROUND_BUDGET_TOKENS / SCHED_PREFILL_CHUNK_TOKENS
    # env vars override either (docs/configuration.md).
    sched_round_budget_tokens: Optional[int] = None
    sched_prefill_chunk_tokens: Optional[int] = None
    # Speculative decoding (engine/spec_decode.py): host-side prompt-
    # lookup drafting + one batched K+1-position verify forward per
    # round, emitting up to K+1 tokens per slot per model step. Exact:
    # greedy output is token-identical to the non-speculative engine,
    # temperature>0 preserves the output distribution via rejection
    # sampling. ENGINE_SPEC_DECODE env beats this field (0 restores the
    # plain decode path); SPEC_MAX_DRAFT_TOKENS env beats the field
    # below beats the default (docs/configuration.md). Works on
    # single-chip AND tp-sharded engines — the verify tail rides the
    # same (sharded) fused or materialized sampler path as decode.
    spec_decode: bool = False
    spec_max_draft_tokens: Optional[int] = None
    # Tiered KV store (engine/kv_tier.py): host-RAM budget, in tokens,
    # for refcount-0 prefix pages offloaded at eviction instead of
    # dropped (restored via priced H2D at admission; also the landing
    # zone for session resume and cross-replica page transfer). The
    # KV_HOST_POOL_TOKENS env var beats this field; None defers to it.
    # 0 (the default) disables the tier entirely — the engine then
    # byte-for-byte preserves the untiered eviction behavior.
    kv_host_pool_tokens: Optional[int] = None
    # Disaggregation role (docs/disaggregation.md): "unified" serves
    # everything (the default — a role-less fleet byte-for-byte
    # preserves today's behavior); "prefill" runs long prompts at full
    # mesh utilization with decode-bound admission DISABLED (submit
    # rejects requests wanting more than ROLE_PREFILL_MAX_TOKENS output
    # tokens with RoleMismatchError) and exports finished prefix pages
    # to decode siblings; "decode" advertises itself for short-prompt /
    # decode-bound placement (the router keeps long prompts off it when
    # a prefill sibling is placeable — advisory at the engine, enforced
    # at placement). The ENGINE_ROLE env var beats this field.
    role: str = "unified"

    def __post_init__(self) -> None:
        # Geometry validation lives on the config, not the engine — a bad
        # flag must fail in milliseconds at parse/build time, never after
        # minutes of checkpoint conversion (the reference rejects
        # impossible engine shapes up front, model_server/__init__.py:
        # 103-110). Prefill buckets scatter KV into whole pages, so the
        # cap must be a page multiple >= one page.
        if self.page_size <= 0:
            raise ConfigError(f"page_size={self.page_size} must be > 0")
        if self.kv_quant not in ("", "int8"):
            raise ConfigError(
                f"kv_quant={self.kv_quant!r} not supported; use '' or "
                f"'int8'")
        if self.max_prefill_bucket is not None and (
                self.max_prefill_bucket < self.page_size
                or self.max_prefill_bucket % self.page_size):
            raise ConfigError(
                f"max_prefill_bucket={self.max_prefill_bucket} must be a "
                f"multiple of page_size={self.page_size} (>= one page); "
                f"pass a smaller page_size to serve finer prefill caps")
        if self.kv_host_pool_tokens is not None \
                and self.kv_host_pool_tokens < 0:
            raise ConfigError(
                f"kv_host_pool_tokens={self.kv_host_pool_tokens} must "
                f"be >= 0 (0 disables the host KV tier)")
        if self.spec_max_draft_tokens is not None \
                and self.spec_max_draft_tokens < 1:
            raise ConfigError(
                f"spec_max_draft_tokens={self.spec_max_draft_tokens} "
                f"must be >= 1 (it sizes the verify round's K+1 "
                f"scoring positions)")
        if self.role not in ("unified", "prefill", "decode"):
            raise ConfigError(
                f"role={self.role!r} not supported; use 'unified', "
                f"'prefill', or 'decode' (docs/disaggregation.md)")

    @property
    def max_cache_len(self) -> int:
        return self.max_input_length + self.max_output_length


class TokenStream:
    """Thread-safe stream of text chunks for one request.

    ``request_id`` is the END-TO-END identity: the string minted (or
    adopted from ``X-Request-ID``/W3C traceparent) at the serving edge
    and stamped here by ``Engine.submit`` — the same ID names this
    request's flight-recorder timeline (``/debug/requests``), its
    slow-request log dump, and its replayed engine-stage spans.
    """

    def __init__(self, request_id: str):
        self.request_id = request_id
        # Flight-recorder hookup (set by Engine.submit): the timeline
        # this request's events land on, and the recorder that retires
        # it at the terminal transition below. owns_timeline is False
        # when the timeline was ADOPTED from a serving edge (the edge
        # completes it; this stream only contributes sub-call stats —
        # agent chains run several engine calls per request).
        self.timeline: Optional[obs_flight.Timeline] = None
        self.owns_timeline = True
        self._flight: Optional[obs_flight.FlightRecorder] = None
        # The request's place in its span tree (obs/flight.py Span): the
        # root, the state it is in now (exactly one is open between
        # submit and finish), and the newest engine round it was seen
        # under — what the terminal transition stamps.
        self.root: Optional[obs_flight.Span] = None
        self.state: Optional[obs_flight.Span] = None
        self.round_id = -1
        self._q: "queue.Queue[tuple[str, object]]" = queue.Queue()
        self._error: Optional[BaseException] = None
        self.finish_reason: Optional[str] = None
        self.token_ids: list[int] = []
        self.submit_time = time.monotonic()
        self.first_token_time: Optional[float] = None
        self.finish_time: Optional[float] = None
        self.cancelled = False
        # Fused-RAG requests: corpus row ids the on-device retrieval
        # picked (populated at first-token harvest).
        self.source_ids: list[int] = []

    def _put_chunk(self, text: str) -> None:
        if text:
            self._q.put(("chunk", text))

    def _enter(self, name: str, round_id: int = -1,
               t: Optional[float] = None, cause: Optional[str] = None,
               n: int = 0, m: int = 0) -> None:
        """One state transition of this request, stamped once by the
        thread that makes it: the state it was in ends and ``name``
        begins at ``t``, under round ``round_id``. A finished request
        (its root closed by whichever thread ended it) moves no more."""
        root = self.root
        if root is None or root.t1 is not None:
            return
        self.round_id = round_id
        self.state = self.timeline.enter(
            self.state, name, time.monotonic() if t is None else t,
            round_id, cause, n, m)

    def _record_done(self) -> None:
        """Retire the timeline on the FIRST terminal transition — every
        finish path (harvest finish, drain, fatal fan-out, reset) funnels
        through _finish/_fail, so no request can leak in /debug/requests'
        in-flight view. Idempotent via the recorder."""
        if self._flight is not None:
            self._flight.complete_stream(self)

    def _finish(self, reason: str) -> None:
        self.finish_reason = reason
        self.finish_time = time.monotonic()
        # Record into the timeline BEFORE the terminal sentinel goes
        # out: once the sentinel is consumed, the chain server's finally
        # races to complete() the timeline, and losing that race would
        # drop this stream's generated/ttft/finish annotations
        # (complete() is first-wins).
        self._record_done()
        self._q.put(("done", reason))

    def _fail(self, exc: BaseException) -> None:
        self._error = exc   # sticky: re-iteration re-raises, never hangs
        self.finish_reason = "error"
        self._record_done()  # before the sentinel — see _finish
        self._q.put(("error", exc))

    def cancel(self) -> None:
        """Abort generation (e.g. the HTTP client disconnected). The
        scheduler retires the request at the next harvested token."""
        self.cancelled = True

    def __iter__(self) -> Iterator[str]:
        """Yield chunks until the terminal event. The terminal state is
        STICKY: iterating a stream whose sentinel was already consumed
        (a second ``text()`` call, a retrying client) returns — or
        re-raises — immediately instead of blocking forever on the
        drained queue (found by the submit/cancel/reset stress test)."""
        while True:
            try:
                if self.finish_reason is not None and self._q.empty():
                    raise queue.Empty  # already finished: sticky path now
                # The timeout only bounds the idle wait for the sticky
                # re-check; a queued item is returned immediately, so the
                # streaming hot path pays nothing.
                kind, payload = self._q.get(timeout=0.25)
            except queue.Empty:
                if self.finish_reason is None:
                    continue
                # finish_reason is set BEFORE the terminal sentinel is
                # queued, and the retire path flushes tail chunks just
                # before that — drain them rather than truncating the
                # response of a slow-token stream that raced the finish.
                while True:
                    try:
                        kind, payload = self._q.get_nowait()
                    except queue.Empty:
                        break
                    if kind == "chunk":
                        yield payload  # type: ignore[misc]
                    elif kind == "error":
                        raise EngineError(
                            "engine failure") from payload  # type: ignore[arg-type]
                    else:
                        return
                if self._error is not None:
                    raise EngineError("engine failure") from self._error
                return
            if kind == "chunk":
                yield payload  # type: ignore[misc]
            elif kind == "error":
                raise EngineError("engine failure") from payload  # type: ignore[arg-type]
            else:
                return

    def text(self) -> str:
        """Block until completion, return the full generation."""
        return "".join(self)

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return (self.first_token_time - self.submit_time) * 1e3


@dataclass
class _Request:
    stream: TokenStream
    prompt_ids: list[int]
    params: SamplingParams
    detok: IncrementalDetokenizer
    stop: StopWordTrap
    eff_max: int = 0          # max_tokens clamped to the cache extent
    extent: int = 0           # prompt + eff_max (cache positions reserved)
    slot: int = -1
    pages: list[int] = field(default_factory=list)
    # Prefix-cache bookkeeping: block hashes this request holds a ref on
    # (matched prefix + blocks it registered), and which of req.pages are
    # cache property (retire must NOT return those to the free list —
    # they stay resident, warm for the next shared-prefix request).
    cache_refs: list = field(default_factory=list)
    cache_pages: set = field(default_factory=set)
    block_hashes: Optional[list] = None  # memoized across admission retries
    proj_pos: int = 0         # host upper bound on the device-side pos
    generated: int = 0
    greedy: bool = False      # top_k==1 / temp<=0: argmax fast path
    banned_ids: list[int] = field(default_factory=list)
    # Multi-token bad-words sequences (each a list of >=2 token ids):
    # banned on-device by matching the tail of generated tokens against the
    # sequence prefix and masking the completing token (the reference's
    # to_word_list_format sequences, preprocessing/1/model.py:211).
    bad_seqs: list[list[int]] = field(default_factory=list)
    # Device-ready renderings of the above, built ONCE at submit() on the
    # caller's thread so the serve loop's admission dispatch stays lean.
    banned_np: Optional[np.ndarray] = None
    bad_seq_np: Optional[np.ndarray] = None
    bad_len_np: Optional[np.ndarray] = None
    # Fused-RAG payload (q_llm (Sq,) int32, q_llm_len, q_enc (2, Se)):
    # admission runs the on-device retrieve+assemble+prefill program.
    rag: Optional[tuple] = None
    # Absolute (monotonic) deadline: queued past it → dropped before
    # prefill (finish deadline_queue); passed mid-decode → stopped at
    # the next harvested token (finish deadline).
    deadline_t: Optional[float] = None
    # Token-budget scheduler bookkeeping: arrival order (slack-sort
    # tiebreak), whether the slot is armed for decode (False while
    # prefill chunks are still in flight across rounds), the next
    # prompt token to prefill, and the admission-time dispatch context
    # (page row, window, masks, RNG key, prefix-cache seed) the chunk
    # dispatches share — built once at _begin_prefill.
    seq: int = 0
    prefill_done: bool = False
    pf_pos: int = 0
    pf: Optional[dict] = None
    # Pages the pool could give (free + evictable) when it last refused
    # this request's admission; None = never refused. Until the pool
    # can give more, _plan_round does not offer the request again.
    refused_avail: Optional[int] = None
    # Speculative decoding (spec on only): the request's prompt-lookup
    # drafter (host token index over prompt + generated), its adaptive
    # draft-length controller, and the prompt's device length (the rag
    # bucket for fused-RAG requests) — ``base_len + generated - 1`` is
    # the slot's exact device ``pos``, used to re-anchor ``proj_pos``
    # after each verify round's variable-length burst.
    drafter: Optional[PromptLookupDrafter] = None
    spec_ctrl: Optional[AdaptiveDraftController] = None
    base_len: int = 0
    # Failover resume (engine/resume.py): how many trailing prompt_ids
    # are REPLAYED generated tokens from a dead sibling's transcript.
    # None for ordinary requests. Pins the admission RNG key to
    # (seed, offset) instead of the global step counter, so a resumed
    # request with the same seed draws the same continuation stream.
    resume_offset: Optional[int] = None

    @property
    def done(self) -> bool:
        return self.stream.finish_reason is not None


class _Chunk(NamedTuple):
    """A grant cut into a request's next chunk (``Engine._next_chunk``)."""
    n: int              # tokens it computes: whole pages unless final
    padded: int         # the bucket they are padded to (0 where n is)
    final: bool         # reaches the prompt's end: arms the slot
    mode: str           # one-shot | first | middle | final (the span's)
    seen: str           # seen-mask handling: seed | replace | accum
    joins_rows: bool    # may run beside other prompts' in one program


class Engine:
    """Continuous-batching engine over one model + mesh."""

    # The device-side bad-words table's static caps (engine/programs.py).
    MAX_BAD_SEQS = programs.MAX_BAD_SEQS
    MAX_BAD_LEN = programs.MAX_BAD_LEN

    def __init__(self, params: llama.Params, model_cfg: LlamaConfig,
                 tokenizer: Tokenizer, cfg: EngineConfig = EngineConfig(),
                 mesh: Optional[Mesh] = None):
        compile_cache.install_build_log()  # count this engine's programs
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.mesh = mesh
        # Construction-time feature downgrades (observable, never
        # silent): populated by _note_downgrade as topology/geometry
        # gates resolve below, mirrored into the doc-fenced
        # ``engine_downgrades`` stat once the stats dict exists.
        self._downgrades: list[dict] = []
        self._dtype = jnp.dtype(cfg.dtype)
        self._kv_quant = bool(cfg.kv_quant)
        self._refuse_unsupported(model_cfg, cfg, mesh)
        B, page = cfg.max_slots, cfg.page_size
        self._pmax = _ceil_div(cfg.max_cache_len, page)

        if mesh is not None:
            params = shard_params(params, mesh, llama_param_specs(model_cfg, mesh))
        self.params = params

        # Effective prefill buckets: page multiples, clipped to the prompt
        # limit, so bucket KV scatters cleanly into whole pages. Computed
        # before pool sizing — the auto sizer reserves headroom for the
        # largest bucket's prefill cache.
        page_up = lambda n: _ceil_div(n, page) * page  # noqa: E731
        # max_prefill_bucket caps the one-shot prefill size; prompts past
        # the cap take the chunked paged-prefill admission instead of
        # compiling (and allocating) an arbitrarily large bucket. Cap
        # geometry (page multiple >= one page) is validated loudly in
        # EngineConfig.__post_init__.
        cap = min(cfg.max_prefill_bucket or cfg.max_input_length,
                  cfg.max_input_length)
        self._buckets = tuple(sorted(
            {page_up(min(b, cap)) for b in cfg.prefill_buckets}
            | {page_up(cap)}))

        # the rows a chunk program of several prompts may carry
        # (_execute_plan_inner holds such grants back for company)
        self._row_ladder = programs.row_ladder(model_cfg)

        # pp>1 serving is a validated REJECTION, not a silent fallback:
        # every decode round runs all layers in ONE program, so pipeline
        # stages would idle at a 1/pp duty cycle while adding a
        # cross-stage hop to the TTFT-critical dispatch. Serving shards
        # over tp/sp; pp stays a training-time axis
        # (parallel/pipeline.py GPipe). Rationale:
        # docs/api-reference.md "Pipeline-parallel serving: a validated
        # rejection".
        if mesh is not None and int(dict(mesh.shape).get("pp", 1)) > 1:
            raise ConfigError(
                f"serving requires pp == 1 "
                f"(mesh has pp={int(dict(mesh.shape)['pp'])}): the decode "
                f"engine dispatches all layers as one program per round, "
                f"so pipeline stages would idle 1/pp of every round; "
                f"shard serving over tp/sp instead (pp is training-only "
                f"— see docs/api-reference.md, 'Pipeline-parallel "
                f"serving')")

        # sp serving mesh: the ring-attention prefill shards each bucket
        # over sp, so invalid geometry must fail HERE, loudly, not as an
        # opaque trace-time fatal inside the serve loop on first submit.
        if mesh is not None and int(dict(mesh.shape).get("sp", 1)) > 1:
            for b in self._buckets:
                try:
                    llama.validate_sp_mesh(mesh, b, "sp serving prefill")
                except ValueError as exc:
                    raise ConfigError(str(exc)) from exc

        # What every device program is built from (engine/programs.py):
        # the kernel gates and the tail, resolved once, a gate below the
        # hardware's potential as a named downgrade — BEFORE pool
        # sizing: the auto sizer's headroom reserve depends on whether
        # the decode kernel's gather window ever materializes.
        # Speculative decoding (engine/spec_decode.py): host-side
        # prompt-lookup drafting + a batched verify round scoring
        # S = max_draft + 1 positions per slot in ONE model step. Runs
        # on single-chip AND tp-sharded engines: the verify tail rides
        # the same tail as the decode round, with identical
        # greedy-token / rejection-sampling distribution guarantees
        # (parity re-pinned on a sharded engine). ENGINE_SPEC_DECODE=0
        # restores the exact plain path.
        self._spec: Optional[SpecConfig] = None
        if spec_enabled(cfg.spec_decode):
            self._spec = SpecConfig.resolve(cfg.spec_max_draft_tokens)
        self._spec_S = (self._spec.max_draft_tokens + 1) if self._spec \
            else 0
        spec = programs.ProgramSpec.resolve(
            self.params, model_cfg, page_size=page, max_slots=B,
            pmax=self._pmax, dtype=self._dtype, mesh=mesh,
            eos_id=int(tokenizer.eos_id), spec_S=self._spec_S)
        for downgrade in spec.downgrades:
            self._note_downgrade(*downgrade)
        self.programs = programs.Programs(spec)
        self._use_kernel = spec.use_kernel

        # Page pool: physical page 0 is the trash page (never allocated);
        # the allocator hands out 1..n_pages-1.
        self._n_pages = 1 + self._resolve_pool_pages()
        self._free_pages = list(range(1, self._n_pages))
        # Shared-prefix page reuse over the pool above. Mutated only on
        # the serve-loop thread; reset() swaps in a fresh instance.
        # ... but not beside a recurrent state: a hit skips the chunks
        # that would have computed it (a snapshot a block is not kept),
        # so the cache is OFF for such a model, whatever the
        # configuration asks, and says so (``stats["prefix_cache_off"]``)
        self._prefix_cache_off = bool(cfg.prefix_cache
                                      and model_cfg.recurrent)
        self._prefix_cache = (
            PrefixCache(page)
            if cfg.prefix_cache and not self._prefix_cache_off else None)
        # Tiered KV store (engine/kv_tier.py): env beats config beats
        # the disabled default — with 0 the tier object never exists
        # and every tier code path below is skipped, preserving the
        # untiered engine byte-for-byte (pinned by the parity test).
        env_host = os.environ.get("KV_HOST_POOL_TOKENS", "")
        host_tokens = (int(env_host) if env_host
                       else (cfg.kv_host_pool_tokens or 0))
        self._kv_tier: Optional[KVTier] = None
        if self._prefix_cache is not None and host_tokens > 0:
            mcfg = self.model_cfg
            self._kv_tier = KVTier(
                page_size=page, host_pool_tokens=host_tokens,
                bytes_per_token=self._kv_bytes_per_token(),
                meta={"kv_quant": cfg.kv_quant,
                      "num_layers": mcfg.num_layers,
                      "num_kv_heads": mcfg.num_kv_heads,
                      "head_dim": mcfg.head_dim,
                      "dtype": cfg.dtype},
                transfer_max_pages=int(os.environ.get(
                    "KV_TRANSFER_MAX_PAGES", "32") or 32),
                transfer_timeout_s=float(os.environ.get(
                    "KV_TRANSFER_TIMEOUT_S", "5") or 5))
        # Disaggregation role: env beats config (the bench builds mixed
        # fleets via per-engine configs; deployments roll roles via
        # ENGINE_ROLE). "unified" changes nothing anywhere — the role
        # paths below are all gated on it. A prefill-role engine rejects
        # decode-bound requests at submit (more output tokens than the
        # ROLE_PREFILL_MAX_TOKENS cap): its whole mesh belongs to the
        # prefill wall; decode rounds stream from the decode pool.
        env_role = (os.environ.get("ENGINE_ROLE", "") or "").strip().lower()
        if env_role and env_role not in ("unified", "prefill", "decode"):
            raise ConfigError(
                f"ENGINE_ROLE={env_role!r} not supported; use 'unified', "
                f"'prefill', or 'decode' (docs/disaggregation.md)")
        self.role: str = env_role or cfg.role
        self._role_prefill_max_tokens = max(1, int(os.environ.get(
            "ROLE_PREFILL_MAX_TOKENS", "4") or 4))
        # Page gather/scatter programs for the tier (built lazily; jit
        # re-specializes per padded page-count rung automatically).
        # _io_rungs tracks scatter rungs already compiled: a rung's
        # FIRST dispatch pays jit compile inside the measured wall, and
        # feeding that into the h2d EWMA would price every later
        # restore as if it compiled too (observed: one cold 32-page
        # restore taught the calibrator 23 ms/page and the pricing
        # refused all restores thereafter).
        self._gather_fn = None
        self._scatter_fn = None
        self._io_rungs: set = set()
        # Control-op queue: suspend/resume/export mutate serve-loop-
        # owned structures (prefix cache, free pages, device state), so
        # callers funnel closures here; the loop executes them between
        # rounds. On a stopped engine they run inline (single-threaded).
        self._control: "queue.Queue[tuple]" = queue.Queue()
        self._state = self._init_device_state()
        self._base_key = jax.random.key(cfg.seed)
        self._step_counter = itertools.count()
        # Flight recorder override for per-request timelines (None = the
        # process-global obs_flight.RECORDER, resolved at USE time so a
        # swapped global never splits one request across two recorders);
        # tests install a private instance via the `flight` setter.
        self._flight_override: Optional[obs_flight.FlightRecorder] = None

        self._fused_rag = None           # set by enable_fused_rag()
        self._rag_jit = None
        self._slots: dict[int, _Request] = {}
        self._free_slots = list(range(B))
        self._pending: "queue.Queue[tuple[_Request, SamplingParams]]" = (
            queue.Queue(maxsize=cfg.max_queue))
        # Scheduler-owned admission backlog: _pull_pending drains the
        # thread-safe intake queue here (bounded by max_queue, so the
        # intake still sheds 429s under pressure) and the token-budget
        # scheduler orders it by deadline slack each round.
        self._backlog: list[tuple[_Request, SamplingParams]] = []
        self._arrival_seq = itertools.count()
        # Token-budget continuous scheduler (engine/scheduler.py): env
        # overrides beat the config fields beat the PROFILE-derived
        # default, mirroring the BENCH_* knob convention.
        env_budget = os.environ.get("SCHED_ROUND_BUDGET_TOKENS", "")
        env_chunk = os.environ.get("SCHED_PREFILL_CHUNK_TOKENS", "")
        # Online cost calibration (SCHED_ONLINE_CALIB, default on): the
        # artifact prior seeds the model; measured per-round costs from
        # the round recorder blend it toward this deployment's reality
        # and recalibrate() re-derives the budget between rounds.
        # =0 pins the static model — the pre-calibration behavior.
        # The prior is TOPOLOGY-KEYED: a tp-sharded engine loads the
        # artifact row measured at its own mesh shape
        # (tools/profile_decode.py --mesh), so the budget the first
        # rounds run under — before the calibrator has evidence — is
        # derived from the right hardware, not the single-chip row.
        # An artifact timed on another platform is no prior for this
        # device (a CPU timing of a toy model must not set a chip's
        # first prefill budgets): load() skips it.
        cost_prior = StepCostModel.load(
            topology=topology_key(
                dict(mesh.shape) if mesh is not None else None),
            platform=self._devices()[0].platform)
        log_event(logger, "engine_cost_prior", level=logging.INFO,
                  source=cost_prior.source, topology=cost_prior.topology,
                  decode_step_ms=cost_prior.decode_step_ms,
                  prefill_ms_per_token=cost_prior.prefill_ms_per_token)
        self._calib = (OnlineCalibrator(cost_prior)
                       if online_calib_enabled() else None)
        self._sched = TokenBudgetScheduler(
            cost_prior, page_size=page,
            steps_per_round=cfg.steps_per_round,
            round_budget_tokens=(int(env_budget) if env_budget
                                 else cfg.sched_round_budget_tokens),
            chunk_tokens=(int(env_chunk) if env_chunk
                          else cfg.sched_prefill_chunk_tokens),
            chunk_shapes=self._buckets,
            calibrator=self._calib)
        # Round telemetry (obs/rounds.py): per-round plan+execution
        # records behind GET /debug/rounds, the engine_round_* metric
        # surface, and the calibrator's evidence. Override-able like the
        # flight recorder (tests install private instances).
        self._rounds_override: Optional[obs_rounds.RoundRecorder] = None
        self._engine_tag = f"e{next(_ENGINE_TAGS)}"
        # Inputs of the per-round HBM-traffic estimate: weight bytes
        # streamed once per decode step, KV page bytes per touched page,
        # and the chip's peak bandwidth (0 on CPU — no roofline there).
        self._param_bytes, self._expert_bytes = weight_bytes_of(
            self.params, self.model_cfg)
        # Per-layer kinds of the model (models/configs.py): the share of
        # its layers that attend a window (the decode KERNEL starts their
        # page loop at the window's first page; the gather path masks
        # and skips nothing), and whether its experts are dropless (the
        # decode program then returns the experts its rows touched (and
        # hyper-connected streams' row defect); a step streams those).
        mc = self.model_cfg
        self._window_share = (
            sum(1 for w in mc.layer_windows if w) / mc.num_layers
            if self._use_kernel else 0.0)
        dev0 = self._devices()[0]
        self._hbm_peak = 0.0 if dev0.platform == "cpu" else peak_bw(dev0)
        # Model-vs-measured drift: EWMA of (round wall / modeled round
        # cost), updated per completed round on the harvest thread.
        # Tracked even with calibration pinned off — drift against a
        # deliberately static model is exactly the regression signal.
        self._drift_ratio: Optional[float] = None
        self._drift_dump_ratio = float(
            os.environ.get("ROUND_DRIFT_DUMP_RATIO", "8") or 0)
        self._slow_round_ms = float(
            os.environ.get("ROUND_SLOW_MS", "0") or 0)
        # Harvest pipeline: the scheduler enqueues each dispatched
        # program's output (first-token scalars, decode-round token
        # blocks, a non-final chunk's marker scalar) with its
        # ProgramRun onto ``_harvest_q`` in dispatch order; the harvest
        # worker blocks on the host copies there, OFF the scheduling
        # path, and posts finish decisions back on ``_completed`` for
        # the scheduler to retire (slot/page/device bookkeeping stays
        # single-threaded). FIFO order across both item kinds preserves
        # per-request token order. reset() swaps in fresh queues so a
        # disowned worker's stale mutations land on garbage.
        self._harvest_q: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._completed: "queue.Queue[tuple[_Request, str]]" = queue.Queue()
        self._inflight_rounds = 0   # decode rounds dispatched, unharvested
        self._pipe_lock = threading.Lock()
        self._wake = threading.Event()
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._harvest_thread: Optional[threading.Thread] = None
        # Liveness watchdog (docs/robustness.md): work queued/in-flight
        # while the progress counters stay frozen past the threshold
        # flips ``stalled`` (chains/server.py /health answers 503 on it)
        # and dumps thread stacks. 0 disables — the default, because a
        # legitimate first-time compile on a slow host looks exactly
        # like a stall to any timer.
        self._watchdog_stall_s = float(os.environ.get(
            "ENGINE_WATCHDOG_STALL_S", "0") or 0)
        self._watchdog_thread: Optional[threading.Thread] = None
        self._stalled = False
        self._fatal: Optional[BaseException] = None
        # Loop generation: reset() bumps it to disown wedged threads —
        # a stale loop drops its writes and exits when it unsticks.
        self._gen = 0

        self._stats_lock = threading.Lock()
        self._stats = dict(_STATS_TEMPLATE)  # keys doc-checked, see above
        # Construction instant for the uptime_s stat — mirrored as the
        # engine_uptime_s gauge so restarts are visible in /debug/history
        # (a counter reset joins an uptime drop in the same sample).
        self._created_monotonic = time.monotonic()
        self._stats["sched_round_budget_tokens"] = \
            self._sched.round_budget_tokens
        # Decode-attention page windows: power-of-two ladder up to the max.
        self._windows = _pow2_ladder(self._pmax)

        # Draft plan staged between _plan_round and _execute_plan
        # (serve-loop thread only): {slot: [draft token ids]}.
        self._draft_plan: Optional[dict] = None
        # 1 when the last _plan_round held backlog requests back because
        # the pool that refused one has not grown since (staged the same
        # way, for the round record's blocked_on_pages).
        self._held_on_pool = 0
        # What waited on the last plan (staged the same way, for the
        # round record): backlog requests by cause (slot, pages,
        # budget) and in-flight prefills the plan granted nothing.
        self._plan_waiting = (0, 0, 0, 0)
        # Id of the newest round begun — the one being dispatched, on
        # the scheduler thread: what a request's state stamps carry
        # (obs/flight.py Span.round_id0/1).
        self._round_seq = -1
        # Requests ever pulled into the backlog, and the count up to
        # which a plan without a free slot has stamped them ``slot``:
        # a saturated engine re-stamps nothing until one more arrives.
        self._pulled = 0
        self._slot_stamped = -1

    def _note_downgrade(self, feature: str, fallback: str,
                        reason: str) -> None:
        """Record a construction-time feature downgrade OBSERVABLY: one
        structured ``engine_feature_downgrade`` log event plus the
        doc-fenced ``engine_downgrades`` stat (derived from this list at
        read time). A downgraded engine still serves correctly, just
        below its hardware's potential — which used to hide in code
        comments (the PR-8/9 "mesh keeps the materialized tail" gates)
        instead of in telemetry."""
        self._downgrades.append(
            {"feature": feature, "fallback": fallback, "reason": reason})
        log_event(logger, "engine_feature_downgrade", feature=feature,
                  fallback=fallback, reason=reason)

    @property
    def downgrades(self) -> list[dict]:
        """Construction-time feature downgrades (copies)."""
        return [dict(d) for d in self._downgrades]

    def _init_device_state(self) -> dict:
        """Fresh device-side scheduler state (cache pool + slot arrays).
        Used at construction and by ``reset()`` after an abandoned loop —
        donated buffers from a wedged thread are unusable, so recovery
        means rebuilding, not reusing."""
        state = programs.slot_state(self.model_cfg.vocab_size,
                                    self.cfg.max_slots, self._pmax)
        if self.mesh is not None:
            state = {k: jax.device_put(v, NamedSharding(self.mesh, P()))
                     for k, v in state.items()}
        state["cache"] = self._alloc_pool()
        return state

    @staticmethod
    def _refuse_unsupported(mcfg: LlamaConfig, cfg: "EngineConfig",
                            mesh: Optional[Mesh]) -> None:
        """What cannot take a latent pool (``kv_lora_rank``), an expert
        share (``experts_held``), hyper-connection streams (``hc_mult``)
        or a recurrent state (``full_attention_interval``) yet says so by
        name, here, before anything is built (docs/support-matrix.md
        lists them)."""
        axes = {k: int(v) for k, v in
                (dict(mesh.shape) if mesh is not None else {}).items()}
        host = (os.environ.get("KV_HOST_POOL_TOKENS", "")
                or cfg.kv_host_pool_tokens or 0)
        role = (os.environ.get("ENGINE_ROLE", "") or cfg.role or "unified"
                ).strip().lower()
        tier = [
            (int(host) > 0, "the host KV tier (kv_host_pool_tokens / "
             "KV_HOST_POOL_TOKENS): engine/kv_tier.py keys and stacks host "
             "blobs on per-head K and V pages"),
            (role != "unified", f"role {role!r}: prefill/decode handoff "
             f"ships kv_tier blobs"),
        ]
        refused: dict[str, list] = {}
        if mcfg.kv_lora_rank:
            refused[f"a latent KV pool (kv_lora_rank={mcfg.kv_lora_rank})"] \
                = [
                (cfg.kv_quant, "an int8 KV pool (kv_quant): the latent "
                 "row is key and value at once and has no scale plane"),
                (axes.get("tp", 1) > 1, "a tp mesh: the latent is "
                 "common to all heads, so tp has nothing of the pool to "
                 "split and the decode kernel no shard rule"),
                (axes.get("sp", 1) > 1, "an sp mesh (ring attention "
                 "has no latent form)"),
            ] + tier
        if mcfg.experts_held:
            refused[f"an expert share (experts_held={mcfg.experts_held})"] \
                = [
                (axes.get("ep", 1) > 1 or axes.get("tp", 1) > 1,
                 "an expert share under an ep or tp mesh: the share IS "
                 "one device's experts"),
            ]
        if mcfg.hc_mult:
            refused[f"hyper-connection streams (hc_mult={mcfg.hc_mult})"] = [
                (axes.get("sp", 1) > 1, "hyper-connection streams "
                 "under an sp mesh: the ring-attention forwards scan the "
                 "raw layer tree and neither widen nor sum the stream"),
            ]
        if mcfg.recurrent:
            refused["a recurrent state (full_attention_interval="
                    f"{mcfg.full_attention_interval})"] = [
                (any(n > 1 for n in axes.values()), f"a mesh ({axes}): the "
                 f"state a slot has no sharding over tp, sp, ep or pp, and "
                 f"ring attention and the pipeline scan the raw layer tree"),
                (cfg.kv_quant, "an int8 KV pool (kv_quant) beside the "
                 "float32 state"),
                (spec_enabled(cfg.spec_decode), "speculative decoding "
                 "(spec_decode / ENGINE_SPEC_DECODE): a rejected draft is "
                 "rolled back by length, and a state that has consumed it "
                 "cannot be"),
            ] + [(hit, why + " and knows no state a slot: suspend, resume "
                  "and handoff would lose it") for hit, why in tier]
        for mechanism, cases in refused.items():
            for hit, why in cases:
                if hit:
                    raise ConfigError(
                        f"{mechanism} refuses: this model does not "
                        f"support {why}")

    def _alloc_pool(self) -> dict:
        """Zeroed pool leaves, each born in its final sharding and
        (kernel path) row-major layout. The pool is sized to nearly all
        free HBM, so it must be allocated ONCE: building it in the
        default layout and re-placing it afterwards would hold two
        pools for the length of the copy."""
        mcfg, mesh = self.model_cfg, self.mesh
        slots = ({"slots": self.cfg.max_slots}
                 if mcfg.recurrent else {})
        leaves = jax.eval_shape(lambda: llama.init_paged_kv_cache(
            mcfg, self._n_pages, self.cfg.page_size, self._dtype,
            quantized=self._kv_quant, **slots))
        if mesh is not None:
            specs = kv_cache_of(mcfg).pool_spec(mesh,
                                                quantized=self._kv_quant)
            shardings = {k: NamedSharding(mesh, specs[k]) for k in leaves}
        else:
            dev = SingleDeviceSharding(self._devices()[0])
            shardings = dict.fromkeys(leaves, dev)
        return {k: _zeros_program(
                    leaf.shape, leaf.dtype,
                    programs.cache_placement(shardings[k], leaf.ndim,
                                             self._use_kernel))()
                for k, leaf in leaves.items()}

    # -------------------------------------------------------------- sizing

    def _kv_bytes_per_token(self, pooled: bool = True) -> int:
        """KV bytes per cached token. ``pooled``: bytes in the page pool
        (int8 + bf16 scales under kv_quant); False: the DENSE bytes of
        prefill-bucket KV, which stays at the compute dtype — the
        quantization happens at insert, so sizing the prefill headroom
        with pooled bytes would under-reserve by ~2x in quant mode."""
        return kv_cache_of(self.model_cfg).model_token_bytes(
            self._dtype.itemsize, quantized=pooled and self._kv_quant)

    def _slot_bytes(self) -> int:
        """Bytes a sequence costs whatever its length: a recurrent
        model's state and convolution tail (models/kv_cache.py
        ``RecurrentKV.slot_bytes``); 0 for every other model."""
        if not self.model_cfg.recurrent:
            return 0
        return kv_cache_of(self.model_cfg).slot_bytes(self._dtype.itemsize)

    def _pool_shard_factor(self) -> int:
        """How many ways the page pool is actually split across devices —
        NOT the device count: pages replicate across dp, and KV heads only
        shard over tp when divisible (parallel/sharding.py:
        paged_kv_cache_spec P(pp, None, kv_tp, None, None))."""
        if self.mesh is None:
            return 1
        mcfg = self.model_cfg
        factor = 1
        if "tp" in self.mesh.shape:
            tp = self.mesh.shape["tp"]
            if tp > 1 and mcfg.num_kv_heads % tp == 0:
                factor *= tp
        if "pp" in self.mesh.shape:
            pp = self.mesh.shape["pp"]
            if pp > 1 and mcfg.num_layers % pp == 0:
                factor *= pp
        return factor

    def _devices(self) -> list:
        """The devices this engine's programs run on."""
        if self.mesh is not None:
            return list(self.mesh.devices.flat)
        return [jax.local_devices()[0]]

    def _free_hbm_bytes(self) -> int:
        """Free HBM on the tightest of this engine's devices, from the
        devices' own accounting (``memory_stats``: ``bytes_limit -
        bytes_in_use``). A device that keeps no such accounting cannot
        be auto-sized."""
        free = []
        for dev in self._devices():
            stats = dev.memory_stats()
            if not stats or "bytes_limit" not in stats:
                raise ConfigError(
                    f"{dev} reports no memory_stats, so "
                    f"kv_pool_tokens='auto' cannot size the KV pool; "
                    f"pin kv_pool_tokens to a token count")
            free.append(int(stats["bytes_limit"])
                        - int(stats.get("bytes_in_use", 0)))
        return min(free)

    def _headroom_bytes(self) -> int:
        """Peak transient bytes the engine needs beyond params + pool: the
        largest prefill bucket's contiguous KV — live THREE ways at the
        prefill->insert overlap (prefill output, insert's page-shaped
        relayout copies, the scatter in flight) — plus prefill
        logits/activations and the decode round's gathered page window.
        Prefill attention is chunked (ops/attention.py), so no S^2 score
        tensor appears here. Without this reserve the "auto" pool claims
        HBM the first dispatch then fights over (round-2 bench OOM)."""
        cfg, mcfg = self.cfg, self.model_cfg
        S = max(self._buckets)
        bucket_cache = S * self._kv_bytes_per_token(pooled=False)
        logits = S * mcfg.vocab_size * 4
        acts = S * mcfg.hidden_size * 64
        if mcfg.hc_mult:
            # Hyper-connections: the stream of the widest chunk program
            # (its rows x the bucket) is ``hc_mult`` copies wide, and a
            # sublayer's mix holds the stream it reads, the one it
            # writes and a float32 copy of one beside them.
            rows = max(self._row_ladder, default=1)
            acts += rows * S * mcfg.hc_mult * mcfg.hidden_size \
                * (2 * self._dtype.itemsize + 4)
        # The gathered page window only exists on the jnp fallback path;
        # the Pallas kernel streams pages through VMEM and never
        # materializes it — reserving for it there starves the pool
        # (the 16-slot throughput collapse, VERDICT r3 weak #2).
        # Learned sparse attention: a chunk program holds a full layer's
        # index scores and their selection, and the decode step over the
        # kernel still gathers the slots' windows of INDEX keys and
        # scores them (off the kernel they are in ``token_bytes``).
        kvc = kv_cache_of(mcfg)
        slots, keys = cfg.max_slots, self._pmax * cfg.page_size
        itemsize = self._dtype.itemsize
        gather = 0 if self._use_kernel \
            else slots * keys * kvc.token_bytes(itemsize)
        if mcfg.index_topk:
            gather += kvc.select_bytes(S, keys + S)
            if self._use_kernel:
                gather += kvc.index_window_bytes(slots, keys, itemsize)
        if mcfg.recurrent:
            # A recurrent state is allocated for every slot beside the
            # pages, whatever they hold: reserved here, BEFORE the pool
            # is sized from what is left. And the widest chunk program
            # holds a recurrent layer's q, k, v in float32 twice over
            # (as the convolution leaves them and cut into blocks) and
            # its blocks' 64 x 64 products.
            rows = max(self._row_ladder, default=1)
            heads = mcfg.linear_num_value_heads
            # (a decay a channel: the running decays and the keys and
            # queries that carry them into a block's products, too)
            acts += rows * S * heads * 4 * (
                4 * mcfg.linear_key_head_dim
                + 4 * mcfg.linear_value_head_dim + 6 * 64
                + 12 * mcfg.linear_key_head_dim
                * (mcfg.linear_decay == "channel"))
            gather += cfg.max_slots * self._slot_bytes()
        # int8-KV insert quantizes the bucket per-row; XLA sequences the
        # K and V transforms, so ~one bucket's f32 copy is live at once
        quant = bucket_cache if self._kv_quant else 0
        # 1.5x the bucket cache: the cache itself plus in-flight copy
        # slack at the prefill->insert overlap. (The former 3x model,
        # cross-checked against r5's measured serving ceilings, over-
        # reserved by ~2 GB at a 2048 bucket and floor-collapsed the
        # auto pool when an embedder shared the chip.)
        return int(1.5 * bucket_cache) + logits + acts + gather + quant \
            + (256 << 20)

    def _resolve_pool_pages(self) -> int:
        # The resolved pool is the ONLY capacity budget: the prefix
        # cache's warm (refcount-0) pages live inside it and are evicted
        # back to the free list under admission pressure, so no extra
        # headroom is reserved for caching (engine/prefix_cache.py).
        cfg = self.cfg
        full = cfg.max_slots * self._pmax
        spec = cfg.kv_pool_tokens
        if spec is None:
            return full
        if isinstance(spec, int):
            return min(full, max(self._pmax, _ceil_div(spec, cfg.page_size)))
        # "auto": fit the pool to free device memory after an explicit
        # headroom reserve (the reference sizes its paged pool via
        # kv_cache_free_gpu_mem_fraction; same idea, with the reserve made
        # explicit instead of a blanket fraction). Host memory is not
        # HBM: a CPU engine (tests, dev mode) takes full capacity.
        if self._devices()[0].platform == "cpu":
            return full
        # Per device: free memory less the transients every device
        # holds. The GLOBAL pool is that times the pool's shard factor —
        # not the device count: a pool replicated across dp must fit on
        # each replica.
        free, headroom = self._free_hbm_bytes(), self._headroom_bytes()
        budget = int((free - headroom) * _POOL_MARGIN) \
            * self._pool_shard_factor()
        pages = budget // (cfg.page_size * self._kv_bytes_per_token())
        pages = min(full, max(self._pmax, pages))
        log_event(logger, "engine_pool_sized", level=logging.INFO,
                  pages=pages, free_hbm_bytes=free,
                  headroom_bytes=headroom, source="memory_stats")
        return pages

    def prewarm(self, max_retries: int = 4) -> None:
        """Verify the pool sizing by actually SERVING a worst-case dummy
        request through the real loop (max-length prompt, full decode
        rounds, dispatch-ahead overlap). No synthetic pass reproduces
        the pipeline's true high-water mark, so the verification IS the
        serving path; it also compiles the programs that path uses.

        ``_headroom_bytes`` is a model, and a model can be short. On
        RESOURCE_EXHAUSTED the pool shrinks ~20% and the engine rebuilds
        — never silently: each shrink counts in ``stats["pool_shrinks"]``
        and logs an ``engine_pool_shrink`` event, and a healthy build
        reports 0. Call before serving; idempotent. Must not be called
        while the engine loop is running."""
        if self._thread is not None and self._thread.is_alive():
            raise EngineError("prewarm() requires a stopped engine")
        for attempt in range(max_retries + 1):
            try:
                if attempt:
                    # Rebuild at the shrunken size INSIDE the try: the
                    # rebuild's own allocations can OOM too, and that
                    # must consume a retry and shrink again, not abort
                    # the whole prewarm.
                    self.reset()
                    self._stopped.clear()
                self._verify_alloc()
                return
            except Exception as exc:  # noqa: BLE001 — filtered below
                if "RESOURCE_EXHAUSTED" not in str(exc) or \
                        attempt == max_retries:
                    raise
                new_pages = max(self._pmax + 1,
                                int((self._n_pages - 1) * 0.8) + 1)
                if new_pages >= self._n_pages:
                    raise
                self._bump("pool_shrinks")
                log_event(logger, "engine_pool_shrink",
                          pages=self._n_pages - 1,
                          retry_pages=new_pages - 1,
                          error=str(exc)[:400])
                # The caught exception's traceback frames pin device
                # arrays (prefill outputs, old state) — drop them before
                # the rebuild allocates the replacement pool.
                exc = None  # noqa: F841
                self._n_pages = new_pages

    def _verify_alloc(self) -> None:
        """Serve one worst-case request for real — max-length prompt,
        enough tokens for full decode rounds — while holding a slack
        allocation, so the accepted sizing has genuine headroom beyond
        the pipeline's measured peak."""
        slack = jnp.zeros(((256 << 20),), jnp.int8)
        jax.block_until_ready(slack)
        self.start()
        try:
            ids = [min(3, self.model_cfg.vocab_size - 1)
                   ] * self.cfg.max_input_length
            from .sampling_params import SamplingParams as _SP
            stream = self.submit(ids, _SP(
                max_tokens=min(self.cfg.max_output_length,
                               2 * self.cfg.steps_per_round + 1),
                top_k=1, ignore_eos=True),
                request_id="engine-prewarm")  # recognizable in /debug
            try:
                for _ in stream:
                    pass
            except EngineError as exc:
                # Unwrap: prewarm's caller matches on RESOURCE_EXHAUSTED,
                # which lives in the loop's fatal, not the stream wrapper.
                raise (self._fatal or exc) from exc
            if stream.finish_reason == "error":
                raise self._fatal or EngineError("prewarm serve failed")
            # Warm the FULL-WIDTH active-row rung through the real path:
            # the request above compiled the single-stream decode round
            # (ba rung 1); two short concurrent streams force a
            # multi-slot round so the first real occupancy crossing
            # doesn't pay that compile on the serve loop mid-traffic.
            dummies = 1
            if self.cfg.max_slots > 1 and self.programs.tail.gathers_rows:
                pair = [self.submit(
                    ids[:min(16, len(ids))], _SP(
                        max_tokens=self.cfg.steps_per_round + 1,
                        top_k=1, ignore_eos=True),
                    request_id=f"engine-prewarm-b{i}") for i in range(2)]
                dummies += 2
                for s in pair:
                    for _ in s:
                        pass
                    if s.finish_reason == "error":
                        raise self._fatal or EngineError(
                            "prewarm rung warm failed")
        finally:
            try:
                self.stop()
            except Exception:  # noqa: BLE001 — post-fatal cleanup only
                pass
            del slack
        # Scrub the dummies from served stats.
        with self._stats_lock:
            self._stats["requests"] -= dummies

    @property
    def flight(self) -> obs_flight.FlightRecorder:
        """Flight recorder in use: the process-global one unless a
        private instance was installed (tests). Resolved per access so
        the engine and the HTTP servers always agree on the recorder."""
        return self._flight_override or obs_flight.RECORDER

    @flight.setter
    def flight(self, recorder: obs_flight.FlightRecorder) -> None:
        self._flight_override = recorder

    @property
    def rounds(self) -> obs_rounds.RoundRecorder:
        """Round recorder in use: the process-global one unless a
        private instance was installed (tests) — same resolution rule
        as the flight recorder."""
        return self._rounds_override or obs_rounds.RECORDER

    @rounds.setter
    def rounds(self, recorder: obs_rounds.RoundRecorder) -> None:
        self._rounds_override = recorder

    @property
    def engine_tag(self) -> str:
        """This engine's tag on its round-telemetry records — the
        ``?engine=`` filter value for ``/debug/rounds`` in multi-engine
        processes, and what bench's per-engine aggregation scopes by."""
        return self._engine_tag

    @property
    def stats(self) -> dict[str, float]:
        with self._stats_lock:
            out = dict(self._stats)
        with self._pipe_lock:
            # Instantaneous device-queue depth: decode rounds dispatched
            # but not yet harvested. >0 during steady decode means the
            # device never goes idle waiting for the host.
            out["dispatch_queue_depth"] = self._inflight_rounds
        # Queued WORK awaiting admission: intake + scheduler backlog —
        # the leading congestion signal the router's load score and the
        # autoscaler's queue trigger read (dispatch_queue_depth alone
        # saturates at dispatch_depth and reads "2" on a replica
        # drowning in queued prefills). len()/qsize() are GIL-atomic;
        # this is a snapshot, not an admission decision.
        out["queue_waiting"] = len(self._backlog) + self._pending.qsize()
        # Scheduler mix: what share of the budgeted work was prefill.
        sched_total = out["sched_prefill_tokens"] + out["sched_decode_tokens"]
        out["sched_prefill_share"] = (
            round(out["sched_prefill_tokens"] / sched_total, 4)
            if sched_total else 0.0)
        # How full the chunk programs ran: tokens computed over the
        # tokens of the shapes they were padded to.
        out["sched_prefill_fill"] = (
            round(out["sched_prefill_tokens"]
                  / out["sched_prefill_padded_tokens"], 4)
            if out["sched_prefill_padded_tokens"] else 0.0)
        # Speculative decoding: acceptance rate over all drafted tokens,
        # and tokens emitted per verify slot-step (>1 = the speculative
        # multiplier is real; 0.0 until the first verify round runs).
        out["spec_acceptance_rate"] = (
            round(out["spec_accepted_tokens"]
                  / out["spec_draft_tokens"], 4)
            if out["spec_draft_tokens"] else 0.0)
        out["spec_tokens_per_step"] = (
            round(out["spec_verify_tokens"]
                  / out["spec_verify_slot_steps"], 4)
            if out["spec_verify_slot_steps"] else 0.0)
        # An expert share: padded rows the dispatch gather walked for
        # each assignment that fell on a held expert (0.0 without one).
        out["route_rows_per_assignment"] = (
            round(out["route_rows_read_sum"]
                  / out["local_assignments_sum"], 2)
            if out["local_assignments_sum"] else 0.0)
        # Construction-time feature downgrades — derived from the list
        # (written once at build, before any reader exists).
        out["downgrades"] = len(self._downgrades)
        out["tail_kernel"] = int(self.programs.tail.kernel)
        out["scan_kernel"] = int(self.programs.spec.scan_kernel)
        # Model-vs-measured drift over completed rounds: 1.0 = the
        # step-cost model predicts round time; >1 = rounds run slower
        # than planned (regression, or a stale artifact prior); 0.0
        # until the first round completes.
        drift = self._drift_ratio
        out["sched_cost_drift_ratio"] = (round(drift, 4)
                                         if drift is not None else 0.0)
        cache = self._prefix_cache
        if cache is not None:
            # Cache counters are written only on the serve-loop thread;
            # reading them here without its lock can tear between fields
            # by at most one in-flight admission — fine for metrics.
            out.update(cache.stats.snapshot())
            out["prefix_cache_pages"] = cache.cached_pages
        # KV tier (engine/kv_tier.py): live host-store occupancy and the
        # restore-hit rate — what fraction of prefix lookups the host
        # tier turned into restored pages instead of recompute.
        tier = self._kv_tier
        out["kv_tier_host_pages"] = tier.store.pages if tier else 0
        # what one cached token costs the pool, all layers: the
        # configuration's cache object says (models/kv_cache.py)
        out["kv_bytes_per_token"] = self._kv_bytes_per_token()
        # of which the indexer's keys (learned sparse attention; else 0)
        out["index_bytes_per_token"] = (
            kv_cache_of(self.model_cfg).index_token_bytes(
                self._dtype.itemsize) if self.model_cfg.index_topk else 0)
        # recurrent layers: what a sequence costs whatever its length,
        # what that reserved for all slots before the pool was sized,
        # and that the prefix cache is off for such a model
        out["slot_bytes"] = self._slot_bytes()
        out["state_bytes"] = self.cfg.max_slots * out["slot_bytes"]
        out["prefix_cache_off"] = int(self._prefix_cache_off)
        lookups = out.get("prefix_cache_lookups", 0)
        out["kv_restore_hit_rate"] = (
            round(out["kv_tier_restore_hits"] / lookups, 4)
            if lookups else 0.0)
        # Engine age: mirrored as engine_uptime_s — the restart marker
        # history/alert consumers join cumulative-counter resets against.
        out["uptime_s"] = round(
            time.monotonic() - self._created_monotonic, 3)
        out.update(compile_cache.build_log())
        return out

    def _bump(self, key: str, n: int = 1) -> None:
        with self._stats_lock:
            self._stats[key] += n

    # ------------------------------------------------------ device programs
    # live in engine/programs.py (``self.programs``). These are what
    # BENCHMARK.json's files still read off an engine by its private
    # name (ROADMAP D13): read-only forwards, nothing else.

    @property
    def _fused_tail(self) -> bool:
        return self.programs.tail.kind != "materialised"

    @property
    def _round_fns(self) -> dict:
        return self.programs.round_fns

    @property
    def _chunk_fns(self) -> dict:
        return self.programs.chunk_fns

    def _round_fn(self, window: int, steps: int, greedy: bool, ba: int):
        return self.programs.round_fn(window, steps, greedy, ba)

    def _chunk_extend_fn(self, window: int, mode: str):
        return self.programs.chunk_extend_fn(window, mode)

    # ------------------------------------------------------------- lifecycle

    def start(self) -> None:
        if self._thread is None:
            self._stopped.clear()  # allow restart after a stop()
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="engine-loop")
            self._thread._engine_gen = self._gen  # type: ignore[attr-defined]
            self._thread.start()
        if self._harvest_thread is None:
            self._harvest_thread = threading.Thread(
                target=self._harvest_worker, daemon=True,
                name="engine-harvest")
            self._harvest_thread._engine_gen = self._gen  # type: ignore[attr-defined]
            self._harvest_thread.start()
        if self._watchdog_thread is None and self._watchdog_stall_s > 0:
            self._watchdog_thread = threading.Thread(
                target=self._watchdog_loop, daemon=True,
                name="engine-watchdog")
            self._watchdog_thread._engine_gen = self._gen  # type: ignore[attr-defined]
            self._watchdog_thread.start()

    def stop(self) -> None:
        self._stopped.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                # Loop is wedged (e.g. a huge first-time compile). Keep the
                # handle so a later start() can't spawn a second loop racing
                # this one over the donated device state; reset() disowns
                # the thread and rebuilds.
                raise EngineError(
                    "engine loop did not stop within 30s; call reset() to "
                    "abandon it and rebuild the device state")
            self._thread = None
        if self._harvest_thread is not None:
            # The worker's longest block is one round's device execution
            # + host copy — bounded, unlike a first-time compile.
            self._harvest_thread.join(timeout=30)
            if self._harvest_thread.is_alive():
                raise EngineError(
                    "harvest worker did not stop within 30s; call reset() "
                    "to abandon it and rebuild the device state")
            self._harvest_thread = None
        if self._watchdog_thread is not None:
            self._watchdog_thread.join(timeout=5)
            self._watchdog_thread = None
            self._stalled = False
        self._drain_on_stop()

    # ------------------------------------------------------------- watchdog

    @property
    def stalled(self) -> bool:
        """Liveness-watchdog verdict: True while work is queued or in
        flight but no progress counter has moved for
        ``ENGINE_WATCHDOG_STALL_S`` (docs/robustness.md). The chain
        server's /health answers 503 on it — truthful readiness, so the
        fleet router places elsewhere — and it clears by itself the
        moment a round completes again."""
        return self._stalled

    def _progress_marks(self) -> tuple:
        """The counters any live engine moves: one frozen sweep of these
        with work pending is the stall signature."""
        with self._stats_lock:
            s = self._stats
            return (s["rounds_completed"], s["harvest_rounds"],
                    s["first_readbacks"], s["prefills"],
                    s["tokens_generated"])

    def _work_pending(self) -> bool:
        with self._pipe_lock:
            inflight = self._inflight_rounds
        return (inflight > 0 or len(self._backlog) > 0
                or self._pending.qsize() > 0)

    def _watchdog_loop(self) -> None:
        import sys
        import traceback

        gen = self._gen
        poll = max(0.05, min(1.0, self._watchdog_stall_s / 4.0))
        marks = self._progress_marks()
        last_move = time.monotonic()
        while not self._stopped.wait(poll):
            if gen != self._gen:
                return  # disowned by reset()
            now = time.monotonic()
            cur = self._progress_marks()
            if cur != marks or not self._work_pending():
                if self._stalled:
                    self._stalled = False
                    log_event(logger, "engine_watchdog_recovered",
                              stalled_s=round(now - last_move, 2))
                marks = cur
                last_move = now
                continue
            if self._stalled or now - last_move < self._watchdog_stall_s:
                continue
            # Stall declared: work is pending and nothing has moved for
            # the whole threshold. Dump every thread's stack + the last
            # round record — the post-mortem an operator needs when the
            # process is about to be killed — and flip readiness.
            self._stalled = True
            self._bump("watchdog_stalls")
            names = {t.ident: t.name for t in threading.enumerate()}
            stacks = {
                f"{names.get(tid, '?')}:{tid}":
                    "".join(traceback.format_stack(frame))[-2000:]
                for tid, frame in sys._current_frames().items()}
            try:
                last_round = self.rounds.snapshot(limit=1).get("records")
            except Exception:  # noqa: BLE001 — diagnostics must not throw
                last_round = None
            log_event(logger, "engine_watchdog_stall",
                      stall_s=round(now - last_move, 2),
                      threshold_s=self._watchdog_stall_s,
                      queue_waiting=(len(self._backlog)
                                     + self._pending.qsize()),
                      inflight_rounds=self._inflight_rounds,
                      last_round=last_round, stacks=stacks)

    def reset(self) -> None:
        """Recover from a wedged loop: disown the stuck threads (their
        writes are dropped via the generation check when they unstick),
        fail every live request, and rebuild the device state — serving
        restarts without process death (VERDICT r2 weak #10).

        Responsive threads are joined first, so reset() on a healthy
        engine degrades to stop-and-rebuild with no thread racing the
        rebuild; the disown path only covers threads actually stuck in a
        device call (the scheduler in a compile/dispatch, the harvest
        worker in a readback)."""
        self._stopped.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._harvest_thread is not None:
            self._harvest_thread.join(timeout=5)
        self._gen += 1
        self._thread = None
        self._harvest_thread = None
        self._watchdog_thread = None
        self._stalled = False
        exc = EngineError("engine was reset")
        for req in self._live_requests():
            if not req.done:
                req.stream._fail(exc)
        # Fresh queues, not .clear(): a disowned harvest worker may still
        # hold the old objects — its stale puts/gets must land on garbage,
        # never on the rebuilt pipeline. The depth counter is zeroed AFTER
        # the generation bump above, so a stale worker's guarded decrement
        # (see _harvest_worker) can never corrupt the new count.
        self._harvest_q = queue.Queue()
        self._completed = queue.Queue()
        # Control ops queued against the dead generation must fail NOW
        # (not hang out the 30 s wait) and must never execute against
        # the rebuilt state — a stale suspend would demote a fresh
        # cache. Fresh queue for the same disowned-thread reason as the
        # pipeline queues above.
        self._fail_control_ops("engine was reset")
        self._control = queue.Queue()
        with self._pipe_lock:
            self._inflight_rounds = 0
        self._slots.clear()
        self._free_slots = list(range(self.cfg.max_slots))
        self._free_pages = list(range(1, self._n_pages))
        if self._prefix_cache is not None:
            # Fresh instance, not .clear(): a disowned loop thread may
            # still hold the old object — its stale mutations must land
            # on garbage, never on the rebuilt pool's index.
            self._prefix_cache = PrefixCache(self.cfg.page_size)
        self._fatal = None
        # Drop the old pool BEFORE allocating the new one — holding both
        # across the rebuild doubles pool HBM exactly when recovering
        # from an OOM (prewarm's shrink-retry died re-allocating).
        self._state = None
        import gc
        gc.collect()
        self._state = self._init_device_state()

    def _loop_stale(self) -> bool:
        """True on a thread that reset() has disowned."""
        g = getattr(threading.current_thread(), "_engine_gen", None)
        return g is not None and g != self._gen

    def _guard_live(self) -> None:
        """Unwind a disowned loop thread entirely — a stale thread must
        not proceed to any later phase, where it would donate the rebuilt
        generation's device state into a jit call."""
        if self._loop_stale():
            raise _StaleLoop()

    def _live_requests(self) -> list[_Request]:
        """Every request the scheduler still knows about, across all of its
        staging structures (pending queue, head buffer, prefill-in-flight,
        slots). The single source of truth for both the fatal-error
        fan-out and the stop() drain — a request missed here would leave
        its consumer blocked forever. Requests whose first-token or round
        output still sits in the harvest queue are covered via ``_slots``:
        admission registers the slot BEFORE enqueueing the first-token
        item, and retirement (which removes the slot) only happens after
        the harvest worker finished their stream."""
        live: list[_Request] = []
        live += self._slots.values()
        live += [req for req, _ in self._backlog]
        self._backlog = []
        while not self._pending.empty():
            try:
                live.append(self._pending.get_nowait()[0])
            except queue.Empty:
                break
        return live

    def _drain_on_stop(self) -> None:
        """Retire everything still live so (a) consumers blocked on streams
        never hang forever and (b) no device slot stays active holding pages
        that a post-restart insert would reuse. Both worker threads are
        joined (or disowned) before this runs, so touching the pipeline
        structures and dispatching releases here is single-threaded."""
        # Unharvested device work is dropped; its requests stay visible
        # via _slots and are cancelled below.
        self._harvest_q = queue.Queue()
        with self._pipe_lock:
            self._inflight_rounds = 0
        # Queued control ops (suspend/export) will never run — fail
        # their waiters instead of leaving them to the wait timeout.
        self._fail_control_ops("engine stopped")
        # Deactivate every occupied device slot FIRST: a host-detected
        # finish pending in _completed never had its device release
        # dispatched, and retiring it below removes the slot from _slots
        # — a still-active device slot would keep writing KV into pages
        # the free list is about to hand to the next occupant.
        for slot in list(self._slots):
            # device-side deactivate: safe here, the loop thread is joined
            self._state = self.programs.release(self._state, jnp.int32(slot))
        # Slot/page bookkeeping for streams the harvest worker already
        # finished but the scheduler never got to retire.
        while True:
            try:
                req, finish = self._completed.get_nowait()
            except queue.Empty:
                break
            if self._slots.get(req.slot) is req:
                self._retire(req, finish)
        leftovers = self._live_requests()
        for req in leftovers:
            if self._slots.get(req.slot) is req:
                self._retire(req, "cancelled")
            elif not req.done:
                req.stream._finish("cancelled")

    def _fail_control_ops(self, reason: str) -> None:
        """Fail every queued control op's waiter (stop/reset paths —
        the ops will never run, and must neither hang their callers out
        the wait timeout nor execute later against rebuilt state)."""
        while True:
            try:
                _fn, box, ev = self._control.get_nowait()
            except queue.Empty:
                return
            box["error"] = EngineError(reason)
            ev.set()

    def __enter__(self) -> "Engine":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------------ API

    def _compile_bad_words(
            self, params: SamplingParams
    ) -> tuple[list[int], list[list[int]]]:
        """bad_words -> (single-token ids, multi-token sequences).

        Single-token spellings go on the static (V,) vocab mask; words
        that only exist as multi-token spellings become device-side
        sequence bans (the reference's word-list tensors,
        preprocessing/1/model.py:211 ``to_word_list_format``).
        """
        banned_ids: list[int] = []
        bad_seqs: list[list[int]] = []
        for word in params.bad_words:
            # Subword tokenizers give a word several single-token
            # spellings — word-initial (metaspace-prefixed, what encode
            # produces after its dummy prefix) and bare continuation —
            # ban every variant the vocab holds so none slips the mask.
            variants = set()
            seqs: list[list[int]] = []
            for text in (word, " " + word):
                ids = [int(i) for i in
                       self.tokenizer.encode(text, add_bos=False)]
                if len(ids) == 1:
                    variants.add(ids[0])
                elif ids and ids not in seqs:
                    seqs.append(ids)
            lookup = getattr(self.tokenizer, "piece_id", None)
            if lookup is not None:
                for piece in (word, "▁" + word):
                    pid = lookup(piece)
                    if pid is not None:
                        variants.add(int(pid))
            banned_ids.extend(sorted(variants))
            # A word is banned in EVERY spelling (the reference's word
            # list carries all of them): single-token variants go on the
            # vocab mask AND multi-token spellings become sequence bans —
            # a word with a one-piece " word" form can still surface via
            # its split bare form after a quote or newline.
            for seq in seqs:
                if any(t in variants for t in seq):
                    # spellings whose pieces include an already-banned
                    # variant can never complete anyway — and must not
                    # trip the length cap below (a word whose ' word'
                    # form is one banned piece stays servable however
                    # long its split spelling is)
                    continue
                if len(seq) > self.MAX_BAD_LEN:
                    raise EngineError(
                        f"bad_words entry {word!r} tokenizes to "
                        f"{len(seq)} tokens; the device-side sequence "
                        f"ban supports up to {self.MAX_BAD_LEN}")
                # dedupe across ALL entries, not just this word's
                # spellings — duplicate sequences would burn device table
                # slots and spuriously trip the MAX_BAD_SEQS cap
                if seq not in bad_seqs:
                    bad_seqs.append(seq)
            if not variants and not seqs:
                raise EngineError(
                    f"bad_words entry {word!r} produced no tokens")
        if len(bad_seqs) > self.MAX_BAD_SEQS:
            raise EngineError(
                f"{len(bad_seqs)} multi-token bad-word sequences; the "
                f"device table holds {self.MAX_BAD_SEQS}")
        return banned_ids, bad_seqs

    def _render_bad_words(self, banned_ids: list[int],
                          bad_seqs: list[list[int]]):
        """Device-ready numpy renderings, built on the SUBMITTING thread
        so the serve loop's admission dispatch does no mask assembly.
        The banned mask ships PACKED (uint32 bitfield, 32 tokens/word —
        ops/sampling.py): 1/8 the upload bytes and the exact layout the
        device state stores per slot."""
        banned_row = np.zeros((self.model_cfg.vocab_size,), bool)
        if banned_ids:
            banned_row[banned_ids] = True
        seq_tbl = np.full((self.MAX_BAD_SEQS, self.MAX_BAD_LEN), -1,
                          np.int32)
        seq_len = np.zeros((self.MAX_BAD_SEQS,), np.int32)
        for i, seq in enumerate(bad_seqs):
            seq_tbl[i, :len(seq)] = seq
            seq_len[i] = len(seq)
        return pack_mask_np(banned_row), seq_tbl, seq_len

    # -------------------------------------------------------- fused RAG

    def enable_fused_rag(self, enc_params, enc_cfg, spec) -> None:
        """Compile-in the on-device retrieve->assemble->prefill admission
        (engine/rag_fusion.py). ``spec``: FusedRagSpec. The corpus is
        uploaded separately via set_rag_corpus()."""
        from .rag_fusion import FusedRag
        if spec.bucket % self.cfg.page_size:
            raise EngineError("fused-RAG bucket must be a page multiple")
        if spec.bucket + 1 > self.cfg.max_cache_len:
            raise EngineError("fused-RAG bucket exceeds the cache extent")
        fused = FusedRag(enc_params, enc_cfg, spec)

        def rag_admit(state, params, enc_params, corpus, q_enc, q_llm,
                      q_llm_len, slot, row, temp, top_k, top_p, rep_pen,
                      banned, bad_seq, bad_len, key, remaining, eos_ok,
                      greedy: bool):
            tokens, length, top_ids = fused.assemble(
                enc_params, corpus, q_enc, q_llm, q_llm_len)
            new_state, first = self.programs.prefill_insert_raw(
                state, params, tokens[None, :], length, slot, row, temp,
                top_k, top_p, rep_pen, banned, bad_seq, bad_len, key,
                remaining, eos_ok, greedy)
            # One readback for everything the host needs: token, real
            # prompt length, retrieved corpus rows.
            aux = jnp.concatenate([
                first[None].astype(jnp.int32), length[None], top_ids])
            return new_state, aux

        self._fused_rag = fused
        self._rag_jit = jax.jit(rag_admit, static_argnums=(19,),
                                donate_argnums=(0,))

    @property
    def fused_rag_spec(self):
        """Spec of the compiled fused-RAG admission program, or None when
        fused RAG is not enabled (e.g. after an engine rebuild) — callers
        cache specs and must compare against the ENGINE's truth."""
        return self._fused_rag.spec if self._fused_rag is not None else None

    def set_rag_corpus(self, emb, toks, lens) -> None:
        """Upload/replace the device-resident retrieval corpus
        (rag_fusion.corpus_rows builds toks/lens from chunk texts)."""
        if self._fused_rag is None:
            raise EngineError("enable_fused_rag() first")
        self._fused_rag.set_corpus(emb, toks, lens)

    def _new_stream(self, request_id: Optional[str],
                    prompt_tokens: int, eff_max: int) -> TokenStream:
        """TokenStream + flight timeline for one submission. The request
        ID resolves in priority order: explicit argument, the ID bound on
        the calling context (the chain server's adopted X-Request-ID,
        visible here because the chain generator runs under a copied
        context), else a freshly minted one."""
        tl_ctx = obs_flight.current()
        if request_id is None and tl_ctx is not None:
            # The serving edge already opened this request's timeline —
            # pair by OBJECT identity (not by re-looking-up the rid,
            # which could collide with an unrelated in-flight request
            # reusing the same client-supplied ID). The edge owns its
            # completion; this stream only contributes sub-call stats.
            tl = tl_ctx
            owns = False
        else:
            # Direct submission (OpenAI surface, tests, prewarm): every
            # call is a new request — fresh disambiguates duplicate IDs.
            tl = self.flight.begin(
                request_id or obs_flight.mint_request_id(), fresh=True)
            owns = True
        stream = TokenStream(tl.request_id)
        stream.owns_timeline = owns
        tl.annotate(prompt_tokens=prompt_tokens, max_tokens=eff_max)
        stream.timeline = tl
        stream._flight = self.flight
        # The span tree's root and first state both start at the
        # stream's own submit stamp, so the states sum to finish -
        # submit and to the stream's ttft exactly.
        rid = self._round_seq
        stream.root = tl.enter(None, "request", stream.submit_time, rid)
        stream._enter("req_intake", rid, t=stream.submit_time)
        return stream

    def _resolve_deadline(self, stream: TokenStream,
                          deadline_t: Optional[float]) -> Optional[float]:
        """The request's effective deadline: the explicit argument (the
        OpenAI surface passes it — run_in_executor drops context), else
        whatever the serving edge armed on the adopted timeline. An
        explicit deadline is stamped back onto an unarmed timeline so
        /debug/requests shows the budget the request ran against."""
        tl = stream.timeline
        if deadline_t is None:
            return tl.deadline_t if tl is not None else None
        if tl is not None and tl.deadline_t is None:
            tl.set_deadline((deadline_t - tl.t_start) * 1e3)
            # set_deadline recomputes off t_start; pin the exact value
            tl.deadline_t = deadline_t
        return deadline_t

    def submit_rag(self, question_ids: Sequence[int],
                   question_enc_ids: Sequence[int],
                   params: Optional[SamplingParams] = None,
                   request_id: Optional[str] = None,
                   deadline_t: Optional[float] = None) -> TokenStream:
        """Enqueue a fused-RAG request: retrieval and prompt assembly
        happen on-device during admission; ``question_ids`` are the
        question's tokens in the LLM vocab (no BOS), ``question_enc_ids``
        in the encoder vocab (with any query prefix applied)."""
        if self._fatal is not None:
            raise EngineError("engine is dead") from self._fatal
        if self._fused_rag is None:
            raise EngineError("fused RAG is not enabled on this engine")
        params = params or SamplingParams()
        spec = self._fused_rag.spec
        ids = list(question_ids)
        if len(ids) > spec.q_bucket:
            # mirror submit()'s loud rejection — silently cutting the
            # question mid-sentence would answer a different question
            raise EngineError(
                f"question is {len(ids)} tokens but the fused-RAG "
                f"question bucket is {spec.q_bucket}; use the host "
                "retrieval path for long questions")
        q_llm = np.zeros((spec.q_bucket,), np.int32)
        q_llm[:len(ids)] = ids
        q_enc = np.zeros((2, spec.enc_bucket), np.int32)
        eids = list(question_enc_ids)[:spec.enc_bucket]
        q_enc[0, :len(eids)] = eids
        q_enc[1, :len(eids)] = 1
        eff_max = min(params.max_tokens,
                      self.cfg.max_cache_len - spec.bucket)
        if eff_max < 1:
            raise EngineError("fused-RAG bucket leaves no room to decode")
        need = _ceil_div(spec.bucket + eff_max, self.cfg.page_size)
        if need > self._n_pages - 1:
            # mirror submit(): an extent the pool can never hold must fail
            # here — enqueued, _admit would skip it forever (silent hang)
            raise EngineError(
                f"fused-RAG request needs {need} KV pages but the pool "
                f"only has {self._n_pages - 1} (kv_pool_tokens too small)")
        banned_ids, bad_seqs = self._compile_bad_words(params)
        banned_np, bad_seq_np, bad_len_np = self._render_bad_words(
            banned_ids, bad_seqs)
        stream = self._new_stream(request_id, len(ids), eff_max)
        req = _Request(stream=stream, prompt_ids=[], params=params,
                       eff_max=eff_max, extent=spec.bucket + eff_max,
                       detok=IncrementalDetokenizer(self.tokenizer),
                       stop=StopWordTrap(params.stop_words),
                       greedy=(params.top_k == 1 or params.temperature <= 0),
                       banned_ids=banned_ids, bad_seqs=bad_seqs,
                       banned_np=banned_np, bad_seq_np=bad_seq_np,
                       bad_len_np=bad_len_np,
                       rag=(q_llm, len(ids), q_enc),
                       deadline_t=self._resolve_deadline(stream, deadline_t),
                       seq=next(self._arrival_seq),
                       base_len=spec.bucket)
        if self._spec is not None:
            # The fused-RAG prompt is assembled on-device — the host
            # never sees its tokens, so the drafter indexes generated
            # tokens only (prompt-lookup still fires once the answer
            # starts repeating spans it generated).
            req.drafter = PromptLookupDrafter(
                ngram_max=self._spec.ngram_max,
                ngram_min=self._spec.ngram_min)
            req.spec_ctrl = AdaptiveDraftController(self._spec)
        self._enqueue(req, params, stream)
        if self._fatal is not None:
            stream._fail(self._fatal)
        self._bump("requests")
        self._wake.set()
        return stream

    def _enqueue(self, req: "_Request", params: SamplingParams,
                 stream: TokenStream) -> None:
        """Admission gate: ``max_queue`` bounds TOTAL queued work —
        intake queue plus the scheduler's backlog — so the PR-5 meaning
        of the knob (queued capacity before 429) survives the backlog
        refactor; without this check the backlog would silently double
        it. The combined read is approximate under concurrent
        submitters (``qsize``/``len`` race by design, like every
        queue-depth check), but the intake queue's own ``maxsize`` still
        hard-bounds any overshoot."""
        if len(self._backlog) + self._pending.qsize() >= self.cfg.max_queue:
            self._reject_full(stream)
        try:
            self._pending.put_nowait((req, params))
        except queue.Full:
            self._reject_full(stream)

    def _reject_full(self, stream: TokenStream) -> None:
        """Queue-full rejection: count the shed, retire the timeline
        (reason recorded, so rejected admissions show up in
        /debug/requests instead of leaking as forever-in-flight
        entries) — but only when this stream OWNS it; an edge-adopted
        timeline is completed by the edge, which turns this exception
        into a structured 429."""
        self._bump("rejected_full")
        # the stream never reaches its caller: end it where every other
        # stream ends, so its spans close and its reason is recorded
        stream.finish_reason = "rejected"
        self.flight.complete_stream(stream)
        raise SchedulerFullError(
            f"request queue full ({self.cfg.max_queue})") from None

    def submit(self, prompt_ids: Sequence[int],
               params: Optional[SamplingParams] = None,
               request_id: Optional[str] = None,
               deadline_t: Optional[float] = None) -> TokenStream:
        """Enqueue a request; returns its stream immediately.

        ``request_id``: the end-to-end request identity (see
        TokenStream). Omitted, it is adopted from the calling context
        (obs/flight.py contextvar — how the chain server's
        ``X-Request-ID`` reaches the engine without threading a parameter
        through every BaseExample chain) or minted fresh.

        ``deadline_t``: absolute ``time.monotonic`` deadline. Omitted,
        it is adopted from the same contextvar timeline (the chain
        server arms it from ``X-Deadline-Ms``). Expired in queue → the
        request is dropped before prefill (finish ``deadline_queue``);
        passed mid-decode → generation stops at the next harvested
        token (finish ``deadline``).

        The whole call is one ``engine_submit`` span on the CALLER's
        thread, carrying the request id the stream will bear."""
        adopted = None
        if request_id is None:
            adopted = obs_flight.current_request_id()
            if adopted is None:
                request_id = obs_flight.mint_request_id()
        with phase("engine_submit", request_id=request_id or adopted):
            return self._submit(prompt_ids, params, request_id, deadline_t)

    def _submit(self, prompt_ids: Sequence[int],
                params: Optional[SamplingParams],
                request_id: Optional[str],
                deadline_t: Optional[float]) -> TokenStream:
        if self._fatal is not None:
            raise EngineError("engine is dead") from self._fatal
        params = params or SamplingParams()
        prewarm_probe = bool(request_id) \
            and request_id.startswith("engine-prewarm")
        if self.role == "prefill" and not prewarm_probe \
                and params.max_tokens > self._role_prefill_max_tokens:
            # Role enforcement at admission: a prefill-role engine's
            # mesh belongs to the prefill wall — a decode-bound request
            # here would starve handoff exports behind its decode
            # rounds. Routing error, not capacity: edges map this to a
            # retryable 429 without tripping the breaker. Prewarm's own
            # worst-case calibration probes are exempt — they run
            # before the replica takes traffic and must exercise full
            # decode rounds regardless of role.
            raise RoleMismatchError(
                f"prefill-role engine refuses decode-bound request "
                f"(max_tokens={params.max_tokens} > role cap "
                f"{self._role_prefill_max_tokens}); route it to a "
                f"decode/unified replica")
        if len(prompt_ids) > self.cfg.max_input_length:
            raise EngineError(
                f"prompt length {len(prompt_ids)} exceeds max_input_length "
                f"{self.cfg.max_input_length}")
        if len(prompt_ids) == 0:
            raise EngineError("empty prompt")
        # Failover resume (engine/resume.py, docs/robustness.md): a
        # router-replayed continuation admits as prompt + generated-so-
        # far tokens. The replayed tokens are PROMPT from here on — the
        # prefix cache / host-tier restore / donor transfer make them
        # cheap, the rep-penalty seen mask covers them exactly like any
        # prefix-cache hit, and the stream emits only NEW tokens. The
        # max_input_length bound above applies to the ORIGINAL prompt:
        # the replayed tail was legitimately generated output.
        rz = engine_resume.current_resume()
        replay_ids = [int(t) for t in (rz or {}).get("ids", ())]
        full_ids = list(prompt_ids) + replay_ids
        eff_max = min(params.max_tokens - len(replay_ids),
                      self.cfg.max_cache_len - len(full_ids))
        if replay_ids and eff_max < 1:
            raise EngineError(
                f"resume replays {len(replay_ids)} tokens but the "
                f"request has no token budget left "
                f"(max_tokens={params.max_tokens})")
        need = _ceil_div(len(full_ids) + eff_max, self.cfg.page_size)
        if need > self._n_pages - 1:
            raise EngineError(
                f"request needs {need} KV pages but the pool only has "
                f"{self._n_pages - 1} (kv_pool_tokens too small)")
        banned_ids, bad_seqs = self._compile_bad_words(params)
        banned_np, bad_seq_np, bad_len_np = self._render_bad_words(
            banned_ids, bad_seqs)
        stream = self._new_stream(request_id, len(full_ids), eff_max)
        req = _Request(stream=stream, prompt_ids=full_ids,
                       params=params, eff_max=eff_max,
                       extent=len(full_ids) + eff_max,
                       detok=IncrementalDetokenizer(self.tokenizer),
                       stop=StopWordTrap(params.stop_words),
                       greedy=(params.top_k == 1 or params.temperature <= 0),
                       banned_ids=banned_ids, bad_seqs=bad_seqs,
                       banned_np=banned_np, bad_seq_np=bad_seq_np,
                       bad_len_np=bad_len_np,
                       deadline_t=self._resolve_deadline(stream, deadline_t),
                       seq=next(self._arrival_seq),
                       base_len=len(full_ids),
                       resume_offset=(len(replay_ids) if replay_ids
                                      else None))
        if replay_ids:
            # Fresh stop-word trap is CORRECT here: any held-back
            # stop-word prefix on the dead replica never reached the
            # router's transcript, so the replayed text ends before it
            # and the trap re-accumulates the straddle from the new
            # tokens. The detokenizer seeds the replayed tail as
            # already-emitted context so only new text streams.
            req.detok.prime(replay_ids)
            tl = stream.timeline
            if tl is not None:
                tl.annotate(resume_replayed=len(replay_ids),
                            resume_attempt=int((rz or {}).get("attempt",
                                                              1)))
                tl.event("resume_admit", {"replayed": len(replay_ids)})
        if self._spec is not None:
            # Prompt-lookup index built on the SUBMITTING thread (like
            # the bad-words masks): the serve loop only proposes. On a
            # resume, the replayed tokens index too — the uninterrupted
            # run would have indexed them as generated output.
            req.drafter = PromptLookupDrafter(
                full_ids, ngram_max=self._spec.ngram_max,
                ngram_min=self._spec.ngram_min)
            req.spec_ctrl = AdaptiveDraftController(self._spec)
        if self._kv_tier is not None:
            # Cross-replica prefix-page import (router placement-miss
            # hint): bounded network fetch on the CALLER's thread, so
            # the serve loop never does I/O; failures place cold.
            self._transfer_prefetch(req)
        self._enqueue(req, params, stream)
        if self._fatal is not None:
            # The loop may have died between the check above and the put;
            # fail the stream here so callers never block forever.
            stream._fail(self._fatal)
        self._bump("requests")
        self._wake.set()
        return stream

    def generate_text(self, prompt: str,
                      params: Optional[SamplingParams] = None,
                      request_id: Optional[str] = None) -> str:
        """Sync convenience: tokenize, generate, detokenize."""
        self.start()
        ids = self.tokenizer.encode(prompt)
        return self.submit(ids, params, request_id=request_id).text()

    def stream_text(self, prompt: str,
                    params: Optional[SamplingParams] = None,
                    request_id: Optional[str] = None,
                    deadline_t: Optional[float] = None) -> TokenStream:
        self.start()
        return self.submit(self.tokenizer.encode(prompt), params,
                           request_id=request_id, deadline_t=deadline_t)

    # ------------------------------------------------------------ scheduler

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    def _window_for(self, pages: int) -> int:
        for w in self._windows:
            if pages <= w:
                return w
        return self._pmax

    def _prefix_lookup(self, req: _Request):
        """Match the prompt's full page-sized blocks against the prefix
        cache and take refs on the usable prefix. Returns
        ``(hashes, k_use, pages)``: the prompt's block-chain hashes, how
        many leading blocks to map read-only, and their physical pages.

        ``usable_prefix_tokens`` caps a full-cover match one block short
        (COW demotion): the tail block the request must recompute — at
        least one token has to run through prefill for first-token
        logits — gets a PRIVATE page instead of the shared one, so the
        write never lands on cache property. Fused-RAG requests skip the
        cache: their prompt is assembled on-device and the host never
        sees its tokens."""
        if self._prefix_cache is None or req.rag is not None \
                or not req.prompt_ids:
            return [], 0, []
        page = self.cfg.page_size
        if req.block_hashes is None:  # backpressure retries re-enter here
            req.block_hashes = hash_blocks(req.prompt_ids, page)
        hashes = req.block_hashes
        matched = self._prefix_cache.match(hashes)
        k_use = usable_prefix_tokens(matched, len(req.prompt_ids),
                                     page) // page
        if k_use == 0:
            return hashes, 0, []
        return hashes, k_use, self._prefix_cache.acquire(hashes[:k_use])

    def _register_prefix(self, req: _Request, hashes: list,
                         k_use: int) -> None:
        """Hand the freshly prefilled full prompt blocks to the cache
        (they hold pure prompt KV: decode writes always land past the
        last full block, see prefix_cache.py). Blocks whose chain hash
        is already cached — e.g. the COW-demoted tail recomputed into a
        private page — keep their page private; it frees normally at
        retire."""
        if self._prefix_cache is None or req.rag is not None:
            return
        for i in range(k_use, len(hashes)):
            parent = hashes[i - 1] if i else None
            if self._prefix_cache.insert(hashes[i], parent, req.pages[i]):
                req.cache_refs.append(hashes[i])
                req.cache_pages.add(req.pages[i])

    # ---------------------------------------------------- tiered KV store

    def _page_io_fns(self):
        """Lazily-built page gather/scatter programs over the paged
        pool. Gather reads selected pages out of the live cache (the
        D2H offload source; non-donating — the pool stays valid);
        scatter writes page-shaped host data into selected pages (the
        H2D restore sink; donates the state like every other state
        transition). Both take a padded page-index vector (power-of-two
        rungs, padded with the trash page 0) so jit specializes per
        rung, not per count."""
        if self._gather_fn is None:
            def gather(cache, idx):
                return {k: v[:, idx] for k, v in cache.items()}

            def scatter(state, arrays, idx):
                cache = {k: v.at[:, idx].set(arrays[k].astype(v.dtype))
                         for k, v in state["cache"].items()}
                return dict(state,
                            cache=self.programs.spec.pin_cache(cache))

            self._gather_fn = jax.jit(gather)
            self._scatter_fn = jax.jit(scatter, donate_argnums=(0,))
        return self._gather_fn, self._scatter_fn

    @staticmethod
    def _pad_pages(pages) -> np.ndarray:
        """Pad a page-id list to the next power-of-two rung with the
        trash page (0): gathers of page 0 are discarded host-side,
        scatters into it land on the designated garbage page."""
        n = max(1, len(pages))
        m = 1
        while m < n:
            m *= 2
        return np.asarray(list(pages) + [0] * (m - len(pages)), np.int32)

    def _offload_victims(self, victims: list, rec=None) -> None:
        """Offload evicted refcount-0 prefix pages to the host tier:
        one gather dispatch over the victim pages (device FIFO order
        guarantees it reads the pages BEFORE any later dispatch of this
        or another admission overwrites them), async D2H started here,
        materialized into the host store by the harvest worker — the
        blocking copy never runs on the scheduling path. Any failure
        (including an injected ``kv.offload`` fault) degrades to the
        untiered behavior: the pages are simply dropped."""
        tier = self._kv_tier
        if tier is None or not victims:
            return
        try:
            faults.inject("kv.offload")
            fresh = [(h, par, pg) for h, par, pg in victims
                     if not tier.store.has(h)]
            if not fresh:
                return
            gather, _ = self._page_io_fns()
            idx = self._pad_pages([pg for _, _, pg in fresh])
            rung_warm = ("gather", len(idx)) in self._io_rungs
            self._guard_live()
            arrays = gather(self._state["cache"], jnp.asarray(idx))
            self._io_rungs.add(("gather", len(idx)))
            for a in arrays.values():
                try:
                    a.copy_to_host_async()
                except Exception:  # noqa: BLE001 — optional fast path
                    pass
            self._harvest_q.put((
                "offload", [(h, par) for h, par, _ in fresh], arrays,
                rung_warm))
            if rec is not None:
                # D2H traffic term: the offloaded pages cross HBM once.
                rec.hbm_bytes += len(fresh) * self.cfg.page_size \
                    * self._kv_bytes_per_token()
        except _StaleLoop:
            raise
        except Exception:  # noqa: BLE001 — offload is best-effort
            logger.debug("kv offload failed; pages dropped", exc_info=True)

    def _plan_restore(self, req: _Request, hashes: list,
                      k_use: int) -> list:
        """Host-tier half of admission lookup: the contiguous chain
        continuation ``hashes[k_use:]`` present in the host store,
        COW-capped like any other prefix match, and PRICED — the
        restore only happens when the step-cost model says uploading
        the pages beats recomputing their tokens (refusals are counted
        in ``kv_restore_skipped_cost``). Returns the block records to
        restore (possibly shorter than planned if the store's LRU raced
        us)."""
        tier = self._kv_tier
        page = self.cfg.page_size
        avail = tier.store.match_chain(hashes[k_use:])
        if not avail:
            return []
        usable = usable_prefix_tokens(k_use + avail, len(req.prompt_ids),
                                      page) // page
        r = usable - k_use
        if r <= 0:
            return []
        if not self._sched.cost.restore_cheaper(r, page):
            self._bump("kv_restore_skipped_cost")
            return []
        recs = []
        for h in hashes[k_use:k_use + r]:
            rec = tier.store.get(h)
            if rec is None:
                break  # LRU raced: restore the contiguous prefix we hold
            recs.append(rec)
        return recs

    def _restore_blocks(self, req: _Request, hashes: list, k_use: int,
                        recs: list, rec=None) -> int:
        """Upload host-tier blocks into this request's freshly
        allocated pages — ONE scatter dispatch, enqueued ahead of the
        scheduler's prefill-chunk grants (device FIFO), so by the time
        the first chunk's attention reads the prefix back it is
        resident. The restored blocks enter the prefix cache exactly
        like freshly prefilled ones (one ref held by this request)."""
        tier = self._kv_tier
        page = self.cfg.page_size
        r = len(recs)
        t0 = time.monotonic()
        faults.inject("kv.restore")
        arrays = tier.stack_blocks(recs)          # name -> (L, r, ...)
        pages = req.pages[k_use:k_use + r]
        idx = self._pad_pages(pages)
        pad = len(idx) - r
        if pad:
            arrays = {k: np.concatenate(
                [v, np.zeros(v.shape[:1] + (pad,) + v.shape[2:],
                             v.dtype)], axis=1)
                for k, v in arrays.items()}
        _, scatter = self._page_io_fns()
        rung_warm = ("scatter", len(idx)) in self._io_rungs
        self._guard_live()
        new_state = scatter(
            self._state, {k: jnp.asarray(v) for k, v in arrays.items()},
            jnp.asarray(idx))
        self._guard_live()
        self._state = new_state
        self._io_rungs.add(("scatter", len(idx)))
        dt = time.monotonic() - t0
        if self._calib is not None and rung_warm:
            # Host wall of build+upload dispatch per page: on async
            # backends this under-counts on-device copy time, but it IS
            # the serve-loop cost the admission decision trades against
            # recompute dispatch cost (docs/kv-tiering.md, pricing).
            # First-use rungs are excluded — their wall is dominated by
            # the one-time jit compile, not the transfer.
            self._calib.observe_h2d(r, dt * 1e3)
        record_stage("engine_kv_restore", dt)
        tl = req.stream.timeline
        if tl is not None:
            tl.stage("engine_kv_restore", dt)
        for i, pg in enumerate(pages):
            h = hashes[k_use + i]
            parent = hashes[k_use + i - 1] if (k_use + i) else None
            if self._prefix_cache.insert(h, parent, pg):
                req.cache_refs.append(h)
                req.cache_pages.add(pg)
        with self._stats_lock:
            self._stats["kv_tier_restore_pages"] += r
            self._stats["kv_tier_restore_hits"] += 1
        if rec is not None:
            rec.kv_restore_pages += r
            rec.hbm_bytes += r * page * self._kv_bytes_per_token()
        return r

    def _transfer_prefetch(self, req: _Request) -> None:
        """Cross-replica prefix-page import, on the SUBMITTING thread
        (like bad-words compilation — the serve loop never does network
        I/O): when the router hinted a donor via ``X-KV-Transfer-From``
        (bound to the request context by the chain server), fetch the
        prompt-head blocks missing from the host tier from the donor's
        ``/control/kv_pages``. Bounded + best-effort: any failure or
        timeout places cold."""
        tier = self._kv_tier
        src = kv_tier_mod.current_transfer_source()
        if tier is None or src is None or not req.prompt_ids:
            return
        if not kv_tier_mod.donor_allowed(src):
            # The hint header is client-suppliable on a directly-hit
            # replica: when KV_TRANSFER_ALLOW scopes donors, anything
            # outside it is ignored — no fetch, no SSRF surface.
            logger.warning("kv transfer: donor %s not in "
                           "KV_TRANSFER_ALLOW; ignoring hint", src)
            return
        if req.block_hashes is None:
            req.block_hashes = hash_blocks(req.prompt_ids,
                                           self.cfg.page_size)
        missing = [h for h in req.block_hashes[:tier.transfer_max_pages]
                   if not tier.store.has(h)]
        if not missing:
            return
        got = kv_tier_mod.fetch_blocks(
            src, missing, timeout_s=tier.transfer_timeout_s,
            max_pages=tier.transfer_max_pages,
            on_corrupt=lambda: self._bump("kv_restore_corrupt"))
        if not got:
            return
        meta, records = got
        if not tier.compatible(meta):
            logger.warning("kv transfer: donor %s pool geometry does not "
                           "match; ignoring payload", src)
            return
        # Only blocks we ASKED for may land: the content address is this
        # prompt's own hash chain, so an answer naming any other hash is
        # either a donor bug or an attempt to poison unrelated cached
        # prefixes through the shared host store — dropped either way.
        wanted = set(missing)
        n = sum(1 for record in records
                if record.hash in wanted and tier.store.put(record))
        if n:
            self._bump("kv_tier_transfer_pages", n)
            tl = req.stream.timeline
            if tl is not None:
                tl.annotate(kv_transfer_pages=n)

    # ------------------------------------------------ control operations

    def _drain_control(self) -> bool:
        """Execute queued control closures (suspend/export) on the serve
        loop, between rounds — they touch scheduler-owned structures
        (prefix cache, free pages, device state) that must never see a
        second thread."""
        did = False
        while True:
            try:
                fn, box, ev = self._control.get_nowait()
            except queue.Empty:
                return did
            did = True
            try:
                box["result"] = fn()
            except BaseException as exc:  # noqa: BLE001 — relayed to caller
                box["error"] = exc
            finally:
                ev.set()

    def _run_control(self, fn, timeout: float = 30.0):
        """Run ``fn`` on the serve loop (queued; bounded wait) — or
        inline when the loop is not running (construction-time and
        stopped engines are single-threaded by contract)."""
        thread = self._thread
        if thread is None or not thread.is_alive():
            return fn()
        box: dict = {}
        ev = threading.Event()
        self._control.put((fn, box, ev))
        self._wake.set()
        if not ev.wait(timeout):
            raise EngineError("engine control op timed out")
        if "error" in box:
            raise box["error"]
        return box.get("result")

    def _collect_blocks(self, hashes: list, start: int, stop: int,
                        into_store: bool = True) -> list:
        """Serve-loop body of export/suspend: walk the chain slice
        ``[start, stop)``, pulling each block from the host tier or
        gathering it out of HBM (one batched gather + blocking readback
        — a control op, off the token path). Callers BATCH long chains
        across control ops so decode rounds interleave between slices
        (one uncapped readback would stall every live stream). Stops at
        the first block resident in neither tier; chained hashes make a
        gapped chain useless anyway."""
        tier = self._kv_tier
        out: list = []
        gather_meta: list = []   # (out index, hash, parent, page)
        for i in range(start, min(stop, len(hashes))):
            h = hashes[i]
            rec = tier.store.peek(h)
            if rec is not None:
                out.append(rec)
                continue
            pg = (self._prefix_cache.page_of(h)
                  if self._prefix_cache is not None else None)
            if pg is None:
                break
            parent = hashes[i - 1] if i else None
            gather_meta.append((len(out), h, parent, pg))
            out.append(None)
        if gather_meta:
            gather, _ = self._page_io_fns()
            idx = self._pad_pages([pg for _, _, _, pg in gather_meta])
            arrays = gather(self._state["cache"], jnp.asarray(idx))
            host = {k: np.asarray(v) for k, v in arrays.items()}
            records = KVTier.split_pages(
                host, [(h, par) for _, h, par, _ in gather_meta])
            for (slot_i, _, _, _), record in zip(gather_meta, records):
                out[slot_i] = record
                if into_store:
                    # Exporting is free warming: the gathered block now
                    # also lives in the host tier.
                    tier.store.put(record)
        return [r for r in out if r is not None]

    def export_blob(self, hashes: Sequence[bytes],
                    max_blocks: Optional[int] = None
                    ) -> tuple[bytes, int]:
        """Serialize the leading cached blocks of a hash chain for a
        peer replica (the ``GET /control/kv_pages`` payload). Returns
        ``(blob, n_blocks)`` — n may be 0 (empty blob) when nothing of
        the chain is resident in either tier. Size-capped at the
        transfer page cap."""
        if self._kv_tier is None:
            raise EngineError(
                "KV tiering is disabled (KV_HOST_POOL_TOKENS=0)")
        tier = self._kv_tier
        cap = int(max_blocks or tier.transfer_max_pages)
        chain = list(hashes)
        recs = self._run_control(
            lambda: self._collect_blocks(chain, 0, cap))
        # Serialization happens HERE, on the caller's thread — the
        # serve loop only gathers.
        return kv_tier_mod.to_blob(recs, tier.meta), len(recs)

    def suspend_session(self, token_ids: Sequence[int]
                        ) -> Optional[bytes]:
        """Demote an idle conversation's full prefix chain out of BOTH
        tiers into a compact blob (engine/kv_tier.py wire format).
        HBM pages return to the free list; host copies are dropped.
        Blocks still referenced by live requests — or shared as
        interior blocks of another resident chain — stay put (they are
        exported into the blob regardless, so resume is complete).
        Returns None when nothing of the chain is cached."""
        if self._kv_tier is None:
            raise EngineError(
                "KV tiering is disabled (KV_HOST_POOL_TOKENS=0)")
        ids = list(token_ids)
        tier = self._kv_tier
        page = self.cfg.page_size
        hashes = hash_blocks(ids, page)
        # Collect in transfer-cap slices, one control op each: decode
        # rounds interleave between slices, so a long conversation's
        # suspend never stalls live streams for its whole readback.
        records: list = []
        step = max(1, tier.transfer_max_pages)
        for lo in range(0, len(hashes), step):
            batch = self._run_control(
                lambda lo=lo: self._collect_blocks(
                    hashes, lo, lo + step, into_store=False))
            records.extend(batch)
            if len(batch) < min(step, len(hashes) - lo):
                break   # chain ended mid-slice
        if not records:
            return None
        n = len(records)

        def demote():
            for h in reversed(hashes[:n]):   # leaf-first
                tier.store.pop(h)
                pg = self._prefix_cache.remove(h)
                if pg is not None:
                    self._free_pages.append(pg)
            with self._stats_lock:
                self._stats["kv_tier_suspended_blocks"] += n
        self._run_control(demote)
        # Blob assembly off the serve loop, on the caller's thread.
        return kv_tier_mod.to_blob(records, tier.meta)

    def resume_session(self, blob: bytes) -> int:
        """Re-seed a suspended session's blocks into the HOST tier (no
        device work — the next admission of the conversation restores
        them through the normal priced H2D path). Returns the number of
        blocks accepted. Raises EngineError on a geometry mismatch —
        silently loading another model's KV would serve garbage."""
        if self._kv_tier is None:
            raise EngineError(
                "KV tiering is disabled (KV_HOST_POOL_TOKENS=0)")
        try:
            meta, records = kv_tier_mod.from_blob(blob)
        except (ValueError, KeyError, TypeError) as exc:
            # Corrupt or malformed import (session resume, handoff
            # push): counted, then refused loudly — the sender's
            # fallback is recompute, never garbage pages in our pool.
            self._bump("kv_restore_corrupt")
            raise EngineError(f"malformed KV blob: {exc}") from exc
        if not self._kv_tier.compatible(meta):
            raise EngineError(
                f"KV blob geometry does not match this engine (blob "
                f"{meta!r} vs engine {self._kv_tier.meta!r})")
        n = sum(1 for rec in records if self._kv_tier.store.put(rec))
        self._bump("kv_tier_resumed_blocks", n)
        return n

    def export_handoff(self, token_ids: Sequence[int]
                       ) -> Optional[tuple[bytes, int]]:
        """Serialize a finished prompt's full prefix chain for
        push-on-completion handoff to a decode replica
        (docs/disaggregation.md). Unlike :meth:`suspend_session` the
        pages STAY resident here (the donor keeps serving pull-side
        ``/control/kv_pages`` fallbacks for the same prefix), and unlike
        :meth:`export_blob` the chain is NOT capped at the transfer page
        cap — it is collected in transfer-cap slices, one control op
        each, so decode rounds interleave between slices and the export
        overlaps them instead of stalling them. Returns ``(blob,
        n_blocks)`` or None when nothing of the chain is cached."""
        if self._kv_tier is None:
            raise EngineError(
                "KV tiering is disabled (KV_HOST_POOL_TOKENS=0)")
        tier = self._kv_tier
        hashes = hash_blocks(list(token_ids), self.cfg.page_size)
        records: list = []
        step = max(1, tier.transfer_max_pages)
        for lo in range(0, len(hashes), step):
            batch = self._run_control(
                lambda lo=lo: self._collect_blocks(
                    hashes, lo, lo + step))
            records.extend(batch)
            if len(batch) < min(step, len(hashes) - lo):
                break   # chain ended mid-slice
        if not records:
            return None
        self._bump("kv_tier_export_pages", len(records))
        # Blob assembly off the serve loop, on the caller's thread.
        return kv_tier_mod.to_blob(records, tier.meta), len(records)

    def _run(self) -> None:
        """Scheduler thread: retire completions, then execute ROUND PLANS
        from the token-budget scheduler — each iteration dispatches at
        most one decode round plus the prefill chunks that fit under the
        per-round budget (engine/scheduler.py), so a long prompt streams
        through in page-quantized chunks between decode rounds instead
        of monopolizing the loop until its prefill completes. NO device
        readback ever runs here — the harvest worker owns those — so the
        device queue stays >=1 round deep whenever there is work instead
        of draining behind a blocking np.asarray (the r5 ``loop_hround``
        ~285 ms serialization). Idle iterations park on ``_wake``, which
        submit(), cancel-capable emission, and every harvested item set —
        a completion-signalled pipeline, not a poll."""
        gen = self._gen
        try:
            while (not self._stopped.is_set() and self._gen == gen
                   and self._fatal is None):
                did_work = False
                if not self._completed.empty():
                    # Idle iterations stay out of the histogram and the
                    # trace: the span exists only when there is a
                    # completion to retire.
                    with phase("loop_drain"):
                        did_work = self._drain_completed()
                with phase("loop_plan") as planning:
                    self._pull_pending()
                    did_work |= self._drain_control()
                    did_work |= self._cull_backlog()
                    # Online calibration: fold any new measured-round
                    # evidence into the planning model BEFORE this round
                    # is planned (cheap version check; no-op when
                    # pinned).
                    if self._calib is not None \
                            and self._sched.recalibrate():
                        with self._stats_lock:
                            self._stats["sched_round_budget_tokens"] = \
                                self._sched.round_budget_tokens
                            self._stats["sched_budget_recalibrations"] += 1
                    plan = self._plan_round()
                did_work |= self._execute_plan(plan, planning.seconds)
                self._guard_live()
                if not did_work:
                    with phase("loop_idle"):
                        self._wake.wait(timeout=0.05)
                    self._wake.clear()
            if self._fatal is not None and self._gen == gen:
                # The harvest worker died: it set _fatal and woke us; fan
                # the failure out from HERE so _live_requests (which
                # mutates scheduler-owned structures) stays on this thread.
                for req in self._live_requests():
                    if not req.done:
                        req.stream._fail(self._fatal)
        except _StaleLoop:
            return  # disowned by reset(): its requests already failed
        except BaseException as exc:  # noqa: BLE001 - report to all streams
            if self._gen != gen:
                return  # disowned by reset(): its requests already failed
            self._fatal = exc
            for req in self._live_requests():
                if not req.done:
                    req.stream._fail(exc)

    def _queued_rounds(self) -> int:
        with self._pipe_lock:
            return self._inflight_rounds

    def _assert_harvestable(self, *arrays) -> None:
        """Sharded-serving harvest contract: every array headed for the
        harvest queue must materialize with ONE ``np.asarray`` and no
        implicit cross-host gather — per-round outputs are small
        REPLICATED arrays by construction (the sharded tail's out_specs
        replicate tokens/verdicts; scatters of replicated operands stay
        replicated). A violation means a dispatch returned
        device-SHARDED output the harvest thread would silently gather
        per round (cross-device always, cross-host on a multi-host
        slice): fail loudly at dispatch instead. Metadata check only —
        never a device sync."""
        if self.mesh is None:
            return
        for a in arrays:
            if not getattr(a, "is_fully_replicated", True):
                raise EngineError(
                    "round output is not replicated (sharding "
                    f"{getattr(a, 'sharding', None)!r}); harvest would "
                    "implicitly gather it every round — sharded round "
                    "outputs must be small replicated arrays")

    def _drain_completed(self) -> bool:
        """Scheduler-side half of request completion: the harvest worker
        finished these streams (terminal chunk + sentinel already
        delivered); dispatch the device release where the device still
        thinks the slot is live, then free slot/pages/cache refs."""
        did = False
        while True:
            try:
                req, finish = self._completed.get_nowait()
            except queue.Empty:
                return did
            did = True
            if self._slots.get(req.slot) is not req:
                continue  # already torn down by a reset/stop drain
            if finish not in ("eos", "length"):
                # Host-detected finish (stop word / cancel): the device
                # still thinks the slot is live — deactivate it before the
                # slot and its pages are reused. Commit the new state only
                # after a liveness re-check so a thread disowned mid-call
                # can't clobber the rebuilt generation.
                self._guard_live()
                new_state = self.programs.release(self._state, jnp.int32(req.slot))
                self._guard_live()
                self._state = new_state
            self._retire(req, finish)

    def _harvest_worker(self) -> None:
        """Harvest thread: consume dispatched programs' outputs in FIFO
        order, blocking on each host copy HERE so the scheduler never
        does. The async copy was started at dispatch, so by the time an
        item is popped its bytes are usually already in flight; the wait
        measured into ``harvest_wait_ms``/``first_readback_ms`` overlaps
        admission and dispatch on the scheduler thread.

        This thread touches NO device state and none of the scheduler's
        structures: it reads its items' own snapshots, feeds streams
        (detokenize/stop-check are host-only), and posts finish decisions
        to ``_completed``. Dispatch is asynchronous, so a program's
        execution error surfaces at its readback — it is caught here,
        recorded as _fatal, and fanned out by the scheduler."""
        gen = self._gen
        try:
            while (not self._stopped.is_set() and self._gen == gen
                   and self._fatal is None):
                try:
                    item = self._harvest_q.get(timeout=0.05)
                except queue.Empty:
                    continue
                faults.inject("engine.harvest")  # chaos: readback failure
                kind = item[0]
                t0 = time.monotonic()
                if kind == "mark":
                    # A non-final chunk program's completion marker: a
                    # scalar OUTPUT of the program (never part of the
                    # donated state), so its readback lands when THAT
                    # program has executed on the device — its
                    # ProgramRun, one part of its RoundRecord, completes
                    # here.
                    _, rec, run, marker = item
                    with phase("engine_harvest_wait", record=False,
                               round_id=-1 if rec is None else rec.round_id,
                               program="" if run is None else run.name) as ph:
                        np.asarray(marker)  # blocks off-thread
                    if self._gen != gen:
                        return
                    self.rounds.complete_part(
                        rec, harvest_wait_ms=ph.seconds * 1e3,
                        program=run, t_done=ph.t1)
                    self._wake.set()
                    continue
                if kind == "offload":
                    # Evicted prefix pages on their way to the host
                    # tier: materialize the gather's async D2H copies
                    # here, OFF the scheduling path, and park them in
                    # the content-addressed host store.
                    _, metas, dev_arrays, rung_warm = item
                    host = {k: np.asarray(v)
                            for k, v in dev_arrays.items()}
                    wait = time.monotonic() - t0
                    if self._gen != gen:
                        return
                    tier = self._kv_tier
                    if tier is not None:
                        for block in KVTier.split_pages(host, metas):
                            tier.store.put(block)
                        if self._calib is not None and rung_warm:
                            # First-use gather rungs are excluded like
                            # the scatter side: their wait is dominated
                            # by the one-time jit compile. (Steady
                            # state, the async copy often lands before
                            # the pop — the wait is a floor estimate.)
                            self._calib.observe_d2h(len(metas),
                                                    wait * 1e3)
                        self._bump("kv_tier_offload_pages", len(metas))
                    self._wake.set()
                    continue
                if kind == "first":
                    _, req, first_tok, rec, run = item
                    rid = -1 if rec is None else rec.round_id
                    with phase("engine_first_readback", round_id=rid) as ph:
                        arr = np.asarray(first_tok)  # blocks off-thread
                    wait, t_read = ph.seconds, ph.t1
                    self._bump("first_readback_ms", wait * 1e3)
                    self._bump("first_readbacks")
                    st = req.stream
                    if st.state is not None:
                        # the blocking wait, inside req_first_token
                        st.timeline.child(st.state, "req_readback", t0,
                                          t0 + wait, rid)
                        st.round_id = rid
                    if self._gen != gen:
                        return
                    emitted_first = not req.done
                    with phase("engine_emit", round_id=rid, tokens=1) as ph:
                        if not req.done:
                            if arr.ndim == 0:
                                self._emit_token(req, int(arr))
                            else:
                                # Fused-RAG aux row:
                                # [first_token, prompt_len, top_ids...]
                                req.stream.source_ids = [
                                    int(x) for x in arr[2:]]
                                self._emit_token(req, int(arr[0]))
                    # the final chunk program's own readback: its
                    # ProgramRun is done at t_read, before the emit
                    self.rounds.first_token(rec, wait_ms=wait * 1e3,
                                            counted=emitted_first,
                                            emit_ms=ph.seconds * 1e3,
                                            program=run, t_done=t_read)
                else:
                    rec, run = item[-2:]
                    rid = -1 if rec is None else rec.round_id
                    with phase("engine_harvest_wait", round_id=rid) as ph:
                        if kind == "verify":
                            _, members, toks_dev, acc_dev, drafted = item[:5]
                            accs = np.asarray(acc_dev)  # blocks off-thread
                            round_stats = {}
                        else:
                            _, members, toks_dev, round_stats = item[:4]
                            accs = drafted = None
                        # (K, B); blocks off-thread
                        toks = np.asarray(toks_dev)
                        if round_stats:
                            round_stats = {k: float(np.asarray(v))
                                           for k, v in round_stats.items()}
                            with self._stats_lock:
                                for k, v in round_stats.items():
                                    self._stats[f"{k}_sum"] += v
                                    self._stats[f"{k}_rounds"] += 1
                    wait, t_read = ph.seconds, ph.t1
                    self._bump("harvest_wait_ms", wait * 1e3)
                    self._bump("harvest_rounds")
                    if self._gen != gen:
                        return
                    emitted: dict[int, int] = {}
                    for req in members.values():
                        # what a finish inside this round is stamped with
                        req.stream.round_id = rid
                    with phase("engine_emit", round_id=rid,
                               tokens=int((toks[:, list(members)] >= 0)
                                          .sum())) as ph:
                        for k in range(toks.shape[0]):
                            row = toks[k]
                            for slot, req in members.items():
                                if req.done:
                                    # A host-detected finish (stop word
                                    # / cancel / deadline) mid-burst:
                                    # trailing device-accepted tokens
                                    # are DISCARDED — never streamed,
                                    # never counted, never fed to the
                                    # drafter (the slot retires, so the
                                    # device's advanced pos is moot).
                                    continue
                                tok = int(row[slot])
                                if tok < 0:
                                    continue  # inactive at this step
                                emitted[slot] = emitted.get(slot, 0) + 1
                                self._emit_token(req, tok)
                        # ONE timeline event per request per round
                        # (token count), never per token — the flight
                        # recorder's token-path budget. Ring appends
                        # are lock-free.
                        for slot, n in emitted.items():
                            st = members[slot].stream
                            if st.timeline is not None:
                                st.timeline.event("decode_round", n)
                                if st.state is not None:
                                    st.state.m += 1   # req_decode's rounds
                    accepted = 0
                    if kind == "verify":
                        accepted = self._finish_verify(members, accs,
                                                       drafted, emitted)
                    self.rounds.complete_part(
                        rec, tokens=sum(emitted.values()),
                        spec_accepted=accepted,
                        harvest_wait_ms=wait * 1e3,
                        emit_ms=ph.seconds * 1e3, program=run,
                        t_done=t_read, **round_stats)
                    with self._pipe_lock:
                        # Guarded by the generation check just above: a
                        # worker disowned during the readback must not
                        # decrement the rebuilt pipeline's fresh counter.
                        if self._gen == gen:
                            self._inflight_rounds -= 1
                self._wake.set()  # dispatch capacity / slots may be free
        except BaseException as exc:  # noqa: BLE001 — fan out via scheduler
            if self._gen != gen:
                return  # disowned by reset(): its requests already failed
            self._fatal = exc
            # Wake the scheduler: it notices _fatal, exits its loop, and
            # fails every live request (all of them reachable via _slots /
            # _pending, including this item's members).
            self._wake.set()

    def _finish_verify(self, members: dict, accs, drafted: dict,
                       emitted: dict) -> int:
        """Harvest-side bookkeeping of one verify round: speculative
        stats, per-request flight-recorder draft/accept counts, the
        adaptive-K controllers, and the ``proj_pos`` re-anchor (the
        dispatch bumped it by the full S upper bound; the burst may
        have consumed less — ``base_len + generated - 1`` is the exact
        device pos for any armed slot). Runs on the harvest thread;
        the scheduler only reads these fields after ``_queued_rounds``
        drops to 0, which happens strictly after this returns."""
        draft_total = sum(drafted.values())
        accept_total = 0
        for slot, req in members.items():
            k = drafted.get(slot, 0)
            a = min(int(accs[slot]), k)
            accept_total += a
            if k > 0 and req.spec_ctrl is not None:
                req.spec_ctrl.update(k, a)
            tl = req.stream.timeline
            if tl is not None and (k or emitted.get(slot)):
                tl.event("spec_drafted", k)
                tl.event("spec_accepted", a)
            if req.prefill_done and not req.done:
                req.proj_pos = min(req.extent,
                                   req.base_len + req.generated - 1)
        with self._stats_lock:
            self._stats["spec_draft_tokens"] += draft_total
            self._stats["spec_accepted_tokens"] += accept_total
            self._stats["spec_verify_tokens"] += sum(emitted.values())
            self._stats["spec_verify_slot_steps"] += len(emitted)
        return accept_total

    def _pull_pending(self) -> bool:
        """Drain the thread-safe intake queue into the scheduler's
        backlog. Pulls stop at ``max_queue`` backlog entries so the
        intake queue still fills — and still sheds 429s — under
        sustained overload; the backlog itself is scheduler-private and
        re-ordered by deadline slack every round."""
        moved = False
        while len(self._backlog) < self.cfg.max_queue:
            try:
                entry = self._pending.get_nowait()
            except queue.Empty:
                break
            self._backlog.append(entry)
            # req_intake ends: the wait's cause is the next plan's to say
            entry[0].stream._enter("req_backlog", self._round_seq)
            self._pulled += 1
            moved = True
        return moved

    def _cull_backlog(self) -> bool:
        """Shed cancelled and queue-expired backlog entries BEFORE any
        slot/page is touched — the PR-5 ``deadline_queue`` path, now run
        over the whole backlog every round instead of only at FIFO head
        pickup (a deep expired request no longer waits for the queue to
        drain past it before it is dropped)."""
        kept: list[tuple[_Request, SamplingParams]] = []
        did = False
        now = time.monotonic()
        for req, sp in self._backlog:
            if req.stream.cancelled:
                req.stream._finish("cancelled")
                did = True
                continue
            if req.deadline_t is not None and now > req.deadline_t:
                self._bump("deadline_queue_drops")
                # _finish closes the open req_backlog span: the queue
                # wait of a dropped request reads from it like any other
                req.stream._finish("deadline_queue")
                did = True
                continue
            kept.append((req, sp))
        self._backlog = kept
        return did

    def _plan_round(self):
        """Build this round's token-budget plan: the right-sized decode
        dispatch (power-of-two step ladder, unchanged from the pre-
        scheduler loop — a decode-only workload plans exactly the rounds
        it always got) plus the prefill jobs the scheduler may grant
        chunks to. In-flight prefills (slots mid-chunking) are offered
        first; backlog admissions are offered only when a slot is free
        and are slack-ordered inside plan_round."""
        armed = [r for r in self._slots.values() if r.prefill_done]
        need_steps = max((r.extent - r.proj_pos for r in armed), default=0)

        def ladder_steps() -> int:
            # Right-size the classic round against the power-of-two
            # step ladder — ONE definition, so spec-on and spec-off
            # engines can never drift apart in round shape.
            s = self.cfg.steps_per_round
            while s // 2 >= need_steps:
                s //= 2
            return s

        steps = 0
        verify_cost = None
        self._draft_plan = None
        if self._spec is not None:
            # Verify rounds require a DRAINED pipeline: the drafter
            # needs the previous round's tokens on the host, so
            # dispatch-ahead would draft blind — the up-to-S-tokens
            # multiplier pays for that lost overlap. Rounds that will
            # NOT draft gain nothing from the drain, so a workload with
            # no repetition in sight keeps the PR-8 dispatch-ahead
            # classic rounds instead of serializing for free.
            if need_steps > 0 and self._queued_rounds() == 0:
                self._draft_plan = self._plan_drafts(armed)
                if self._draft_plan is not None:
                    # One model step; priced as the S positions each
                    # armed slot actually computes, converted through
                    # the measured verify cost (StepCostModel).
                    steps = 1
                    verify_cost = self._sched.cost.verify_cost_tokens(
                        self._spec_S * len(armed))
                else:
                    # Nothing draftable at the drain point: classic
                    # multi-step round, the exact plain-decode program.
                    steps = ladder_steps()
            elif (need_steps > 0
                    and self._queued_rounds() < self.cfg.dispatch_depth
                    and not self._any_draftable(armed)):
                # Pipeline is non-empty and no armed slot shows a
                # draftable n-gram even on its (possibly stale) host
                # context — dispatch ahead as plain decode always did.
                # If a slot DOES look draftable, hold this round so the
                # pipeline drains and the next plan can verify.
                steps = ladder_steps()
        elif need_steps > 0 \
                and self._queued_rounds() < self.cfg.dispatch_depth:
            steps = ladder_steps()
        inflight = [
            PrefillJob(key=r, remaining=len(r.prompt_ids) - r.pf_pos,
                       deadline_t=r.deadline_t, seq=r.seq, started=True)
            for r in self._slots.values() if not r.prefill_done]
        backlog_jobs = []
        self._held_on_pool = 0
        now = time.monotonic()
        # What each backlog request waits on is said here, where it is
        # known, and stamped on its req_backlog span when it changes.
        n_slot = n_pages = 0
        if not self._free_slots:
            n_slot = len(self._backlog)
            if n_slot and self._slot_stamped != self._pulled:
                for req, _sp in self._backlog:
                    self._wait_on(req, "slot", now)
                self._slot_stamped = self._pulled
        else:
            self._slot_stamped = -1
            for req, _sp in self._backlog:
                # Pre-admission estimate: the full prompt (a prefix-cache
                # hit is only discovered at admission and can only SHRINK
                # the real chunk plan). Fused-RAG prompts are assembled
                # on-device at the spec's bucket size.
                remaining = (self._fused_rag.spec.bucket
                             if req.rag is not None
                             else len(req.prompt_ids))
                backlog_jobs.append(PrefillJob(
                    key=req, remaining=remaining,
                    deadline_t=req.deadline_t, seq=req.seq))
            # Pool backpressure is global: an admission the pool refused
            # stops admitting for the round (_execute_plan_inner), so a
            # request it refused, and whatever the planner would admit
            # after it, is not offered again until the pool can give
            # more pages than it could then. A grant for a request that
            # cannot start is a whole chunk program taken from the
            # prefills in flight.
            backlog_jobs = self._sched.order(backlog_jobs, now)
            if any(j.key.refused_avail is not None for j in backlog_jobs):
                avail = self._pool_avail_pages()
                for n, job in enumerate(backlog_jobs):
                    if job.key.refused_avail is not None \
                            and avail <= job.key.refused_avail:
                        self._held_on_pool = 1
                        n_pages = len(backlog_jobs) - n
                        for held in backlog_jobs[n:]:
                            self._wait_on(held.key, "pages", now)
                        del backlog_jobs[n:]
                        break
            # The planner is offered as many as can start (plan_round
            # would cut the ordered list at max_new itself): the rest
            # wait for a slot, not for the budget.
            free = len(self._free_slots)
            n_slot = max(0, len(backlog_jobs) - free)
            for late in backlog_jobs[free:]:
                self._wait_on(late.key, "slot", now)
            del backlog_jobs[free:]
        plan = self._sched.plan_round(
            decode_steps=steps, active_decodes=len(armed),
            inflight=inflight, backlog=backlog_jobs,
            now=now, max_new=len(self._free_slots),
            decode_cost_tokens=verify_cost)
        granted = {id(key) for key, _ in plan.chunks}
        n_budget = 0
        for job in backlog_jobs:
            if id(job.key) not in granted:
                n_budget += 1
                self._wait_on(job.key, "budget", now)
        self._plan_waiting = (
            n_slot, n_pages, n_budget,
            sum(1 for j in inflight if id(j.key) not in granted))
        return plan

    def _wait_on(self, req: _Request, cause: str, t: float) -> None:
        """Stamp what a backlog request waits on (scheduler thread): the
        first plan after the pull names the open ``req_backlog`` span, a
        CHANGE of cause opens a new one — one span a cause, not one a
        round."""
        st = req.stream
        sp = st.state
        if sp is not None and sp.cause != cause and sp.t1 is None \
                and sp.name == "req_backlog":
            st.state = st.timeline.recause(sp, cause, t, self._round_seq)

    def _any_draftable(self, armed) -> bool:
        """Cheap hint: could any armed slot propose >= 1 draft token
        right now? Used while rounds are still in flight — the host
        context may lag the device by the unharvested rounds, so this
        is a HINT for the pipeline-vs-drain decision, never the source
        of actual drafts (those are proposed only at a drained
        pipeline, where the context is exact). A stale positive just
        drains the pipeline one round earlier than necessary; a stale
        negative keeps one more round pipelined."""
        for req in armed:
            if req.drafter is None or req.spec_ctrl is None \
                    or not req.stream.token_ids:
                continue
            if min(req.spec_ctrl.k,
                   req.eff_max - req.generated - 1) <= 0:
                continue
            if req.drafter.propose(1):
                return True
        return False

    def _plan_drafts(self, armed) -> Optional[dict]:
        """Prompt-lookup proposals for this round: {slot: draft ids}.
        None when no armed slot can draft — the caller then dispatches a
        classic round instead (a verify round with zero drafts would
        emit one token per slot at multi-token-forward prices).

        Slots whose first token is still unharvested draft nothing (the
        host index would be behind the device's last token — proposals
        would verify against the wrong position's context); their rows
        still ride the verify round and emit exactly one token, so
        correctness never depends on the drafter's view."""
        plan: dict[int, list[int]] = {}
        total = 0
        for req in armed:
            if req.drafter is None or req.spec_ctrl is None \
                    or not req.stream.token_ids:
                continue
            # Never draft past the request's remaining output budget:
            # positions past it could write K/V beyond the allocated
            # extent (the device would truncate the emission anyway,
            # but the pages must stay in bounds).
            k = min(req.spec_ctrl.k, self._spec.max_draft_tokens,
                    req.eff_max - req.generated - 1)
            if k <= 0:
                continue
            proposal = req.drafter.propose(k)
            if proposal:
                plan[req.slot] = proposal
                total += len(proposal)
        return plan if total else None

    def _execute_plan(self, plan, plan_s: float = 0.0) -> bool:
        """Dispatch one round plan: the decode round first (the latency-
        critical work for every armed stream), then the granted prefill
        chunks. Stops admitting on pool backpressure; counts the round
        as interleaved when both kinds of work actually dispatched.

        Round telemetry: the plan opens a RoundRecord (scheduler-side
        half; ``plan_s`` is the host time the plan took), each dispatch
        fills its execution fields and appends its ProgramRun, and the
        harvest worker completes it a program at a time — a non-final
        chunk program puts a completion MARKER in the harvest queue (a
        scalar output of the program, so its readback lands exactly when
        its device work finishes), a final one its first token.
        The ``engine_round`` span covers begin to seal and carries the
        record's id, so trace and /debug/rounds join."""
        if not (plan.decode_steps or plan.chunks):
            return False
        used = self._pool_used_pages()
        with self._stats_lock:
            self._stats["pool_used_pages"] = used
        rec = self.rounds.begin(
            engine_tag=self._engine_tag,
            budget_tokens=plan.budget_tokens,
            decode_steps=plan.decode_steps,
            decode_cost_tokens=plan.decode_cost_tokens,
            active_decodes=plan.active_decodes,
            kind=("verify" if (plan.decode_steps
                               and self._draft_plan is not None)
                  else "decode" if plan.decode_steps else "prefill"),
            plan_ms=plan_s * 1e3, pool_used_pages=used,
            on_complete=self._on_round_complete)
        # Requests the planner was not offered because the pool that
        # refused them has not grown since wait on pages this round too.
        rec.blocked_on_pages = self._held_on_pool
        # What waited on this round when it was planned, and the decode
        # rounds queued on the device ahead of its first program.
        self._round_seq = rec.round_id
        (rec.waiting_slot, rec.waiting_pages, rec.waiting_budget,
         rec.prefill_ungranted) = self._plan_waiting
        rec.queued_ahead = self._queued_rounds()
        try:
            with phase("engine_round", round_id=rec.round_id,
                       kind=rec.kind, t_mono_ns=time.monotonic_ns()):
                return self._execute_plan_inner(plan, rec)
        except BaseException:
            # The round died mid-dispatch (fault injection, _StaleLoop
            # from a reset, a device error): an unsealed record would
            # sit in the ring as not-done debris forever — drop it. A
            # SEALED record's fate rides the harvest pipeline as usual.
            if not rec._sealed:
                self.rounds.discard(rec)
            raise

    def _pool_avail_pages(self) -> int:
        """KV pool pages an admission could get right now: free plus
        evictable prefix-cache pages (refcount 0: warm, but reclaimable
        the moment an admission needs them). O(1), scheduler thread."""
        avail = len(self._free_pages)
        cache = self._prefix_cache
        if cache is not None:
            avail += cache.cached_pages - cache.pinned_pages
        return avail

    def _pool_used_pages(self) -> int:
        """KV pool pages live requests hold right now: total less what
        an admission could get."""
        return self._n_pages - 1 - self._pool_avail_pages()

    def _execute_plan_inner(self, plan, rec) -> bool:
        did = False
        decoded = False
        rid = rec.round_id
        if plan.decode_steps:
            if self._draft_plan is not None:
                decoded = self._dispatch_verify(self._draft_plan, rec)
                self._draft_plan = None
            else:
                decoded = self._dispatch_round(plan.decode_steps, rec)
            if decoded:
                did = True
                self._bump("sched_decode_tokens", plan.decode_cost_tokens)
        prefilled = padded = programs = 0
        grants: list[tuple[str, int]] = []

        def ran(members, run, ph) -> None:
            """Account one dispatched chunk program: ``members`` the
            (request, tokens) it carried, ``run`` its record, ``ph`` the
            closed ``chunk_dispatch`` span around its launch."""
            nonlocal prefilled, padded, programs
            programs += 1
            if run is not None:
                run.t_launch1 = ph.t1
            # Prefill traffic estimate: a program streams the weights
            # once and writes its tokens' KV.
            rec.hbm_bytes += self._param_bytes
            for req, n in members:
                prefilled += n
                padded += self._bucket_for(n)
                grants.append((req.stream.request_id, n))
                rec.hbm_bytes += n * self._kv_bytes_per_token()

        def run_one(req: _Request, grant: int):
            """A prompt's own program, the prompt admitted inside its
            span if it is not yet; returns ``_begin_prefill``'s answer."""
            with phase("chunk_dispatch", round_id=rid,
                       request_id=req.stream.request_id,
                       **self._chunk_shape(req, grant)) as ch:
                ok = True if req.slot >= 0 else self._begin_prefill(req, rec)
                n, run = self._advance_prefill(req, grant, rec, ch) if ok \
                    else (0, None)
                ch.record = bool(n)
            if n:
                ran([(req, n)], run, ch)
            return ok

        def run_rows(held: list) -> None:
            """Dispatch the whole-bucket grants held back for company:
            as many as a rung of the ladder takes in ONE program, a
            straggler through its own."""
            C = self._buckets[-1]
            while held:
                r = next((r for r in self._row_ladder if r <= len(held)), 1)
                members, held[:r] = held[:r], []
                if r == 1:
                    run_one(members[0], C)
                else:
                    with phase("chunk_dispatch", round_id=rid,
                               request_id=members[0].stream.request_id,
                               tokens=r * C, padded=r * C, mode="rows",
                               rows=r) as ch:
                        run = self._advance_prefill_rows(members, rec, ch)
                    ran([(req, C) for req in members], run, ch)
                self._guard_live()

        if plan.chunks:
            with phase("loop_admit", round_id=rid) as ph:
                # a backlog request granted a chunk it could not start:
                # why, for it and for the grants behind it
                stopped_on = None
                # whole-bucket non-final grants wait here for the plan's
                # others of their kind (dispatch order among prompts is
                # free: a chunk touches its own slot and pages only) —
                # while NO stream is decoding. Beside a decoding batch a
                # closed loop turned the faster prefill into fuller
                # decode rounds: +6 % tokens a second and +1.3 % on the
                # median token gap of a 16-slot cell, over its bound
                # (chip, PR 39); when to trade the one for the other is
                # the planner's to decide, not the dispatch loop's.
                rows_ok = not any(r.prefill_done
                                  for r in self._slots.values())
                held: list[_Request] = []
                for at, (key, grant) in enumerate(plan.chunks):
                    req: _Request = key
                    if req.slot < 0 and not self._free_slots:
                        stopped_on = "slot"
                        break
                    ok = True
                    if rows_ok and req.slot < 0 \
                            and self._next_chunk(req, grant).joins_rows:
                        # admitted apart from any program's span: the
                        # program it joins is not this request's alone
                        ok = self._begin_prefill(req, rec)
                    if ok and rows_ok \
                            and self._next_chunk(req, grant).joins_rows:
                        if not self._prefill_aborted(req):
                            held.append(req)
                            if len(held) == self._row_ladder[0]:
                                run_rows(held)
                    elif ok:
                        ok = run_one(req, grant)
                    if ok is None:     # dropped (cancel raced the grant)
                        continue
                    if not ok:         # pool backpressure: stop admitting
                        rec.blocked_on_pages += 1
                        stopped_on = "pages"
                        break
                    self._guard_live()
                run_rows(held)
                if stopped_on is not None:
                    now = time.monotonic()
                    for key, _ in plan.chunks[at:]:
                        if key.slot < 0:
                            self._wait_on(key, stopped_on, now)
                ph.record = bool(prefilled)
        did = did or bool(prefilled)
        if prefilled:
            self._bump("sched_prefill_tokens", prefilled)
            self._bump("sched_prefill_padded_tokens", padded)
            self._bump("sched_chunk_programs", programs)
            if self.programs.spec.scan_kernel:
                self._bump("scan_kernel_chunks", programs)
            if decoded:
                self._bump("sched_interleaved_rounds")
        # one harvest-side completion a launched program
        parts = len(rec.programs)
        if parts == 0:
            self.rounds.discard(rec)
        else:
            if not decoded:
                rec.kind = "prefill"
            elif prefilled:
                rec.kind = "mixed" if rec.kind == "decode" \
                    else rec.kind
            if rec.blocked_on_pages:
                self._bump("pool_blocked_rounds")
            self.rounds.seal(
                rec, parts=parts, prefill_tokens=prefilled,
                prefill_padded_tokens=padded, grants=grants,
                modeled_ms=self._modeled_round_ms(
                    rec, plan.decode_steps if decoded else 0,
                    prefilled))
        return did

    def _modeled_round_ms(self, rec, decode_steps: int,
                          prefill_tokens: int) -> float:
        """What the live step-cost model predicts this round should
        take — the denominator of the drift ratio. Captured at seal
        time so a later recalibration cannot rewrite history."""
        cost = self._sched.cost
        modeled = 0.0
        if decode_steps:
            if rec.verify_positions:
                per = cost.verify_ms_per_token or cost.prefill_ms_per_token
                modeled += rec.verify_positions * per
            else:
                modeled += cost.decode_round_ms(decode_steps)
        modeled += prefill_tokens * cost.prefill_ms_per_token
        # In-flight H2D: restored pages ride the round's device queue
        # ahead of the chunk grants — priced so the drift gauge stays
        # truthful on restore-heavy rounds (0 until h2d is measured).
        if rec.kv_restore_pages:
            modeled += cost.restore_ms(rec.kv_restore_pages)
        return modeled

    def _on_round_complete(self, rec) -> None:
        """Harvest-thread completion callback for one round record:
        bandwidth estimate, drift accounting, calibrator feed, metric
        mirror, slow-round dump, and the retrospective OTel span.
        Observability — never raises into the harvest worker."""
        try:
            if self._hbm_peak > 0 and rec.device_ms > 0:
                rec.bw_util = rec.hbm_bytes / (rec.device_ms / 1e3) \
                    / self._hbm_peak
            ratio = (rec.round_ms / rec.modeled_ms
                     if rec.modeled_ms > 0 else 0.0)
            rec.drift_ratio = ratio
            if ratio > 0:
                prev = self._drift_ratio
                self._drift_ratio = (ratio if prev is None
                                     else prev + 0.2 * (ratio - prev))
            # Calibration: only PURE rounds are attributable (a mixed
            # round's device time cannot be split honestly).
            if self._calib is not None:
                if rec.kind == "decode" and not rec.prefill_tokens:
                    self._calib.observe_decode(rec.decode_steps,
                                               rec.device_ms)
                elif rec.kind == "verify" and not rec.prefill_tokens:
                    self._calib.observe_verify(rec.verify_positions,
                                               rec.device_ms)
                elif rec.kind == "prefill":
                    self._calib.observe_prefill(rec.prefill_tokens,
                                                rec.device_ms)
            self._bump("rounds_completed")
            obs_rounds.record_round_metrics(rec, self._drift_ratio)
            slow = (self._slow_round_ms
                    and rec.round_ms > self._slow_round_ms)
            drifted = (self._drift_dump_ratio and ratio
                       and ratio > self._drift_dump_ratio
                       # micro-rounds drift wildly on noise alone; only
                       # dump when the model predicted measurable work
                       and rec.modeled_ms >= 0.25)
            if slow or drifted:
                obs_rounds.count_slow_dump()
                log_event(logger, "slow_round",
                          reason=("slow" if slow else "drift"),
                          drift_ratio=round(ratio, 3),
                          drift_threshold=self._drift_dump_ratio,
                          slow_ms_threshold=self._slow_round_ms,
                          round=rec.to_dict())
            obs_rounds.emit_round_span(rec)
        except Exception:  # noqa: BLE001 — telemetry must never raise
            logger.debug("round completion accounting failed",
                         exc_info=True)

    def _next_chunk(self, req: _Request, grant: int) -> _Chunk:
        """How ``grant`` tokens become ``req``'s next chunk: worked out
        ONCE, here, for the span's arguments (``_chunk_shape``), the
        choice of the program of several (``joins_rows``) and the
        dispatch (``_advance_prefill``). For a request not yet admitted
        this is the plan's view, taken before the prefix lookup: a
        prefix-cache hit, found only then, shrinks the real chunk and
        may seed it (the round record's grants are exact)."""
        if req.rag is not None:
            bucket = self._fused_rag.spec.bucket
            return _Chunk(bucket, bucket, True, "one-shot", "replace", False)
        total, pos, pf = len(req.prompt_ids), req.pf_pos, req.pf
        top = self._buckets[-1]
        n = min(grant, total - pos, top)
        final = pos + n >= total
        if not final:
            n = (n // self.cfg.page_size) * self.cfg.page_size
        first = pf is None or pos == pf["start_tok"]
        # the first chunk after a prefix-cache hit: its seen mask is
        # seeded from the host's over the cached prefix
        seeding = pf is not None and first and pf["seed"] is not None
        mode = ("one-shot" if final and pos == 0 and total <= top
                else "final" if final else "first" if first else "middle")
        return _Chunk(
            n, self._bucket_for(n) if n > 0 else 0, final, mode,
            "seed" if seeding else "replace" if pos == 0 else "accum",
            # a whole largest bucket, not the prompt's last chunk, not
            # seeded: what engages the program of several
            # (programs.make_extend_rows) is then the plan itself, two
            # such grants
            bool(self._row_ladder) and grant >= top and total - pos > top
            and not seeding)

    def _chunk_shape(self, req: _Request, grant: int) -> dict:
        """Arguments of a ``chunk_dispatch`` span (``rows`` = the
        prompts the program carries: 1 here, ``_execute_plan_inner``
        names the program of several itself, mode ``rows``)."""
        chunk = self._next_chunk(req, grant)
        return {"tokens": chunk.n, "padded": chunk.padded,
                "mode": chunk.mode, "rows": 1}

    def _begin_prefill(self, req: _Request, rec=None):
        """Admission half 1: allocate the slot and pages, take prefix-
        cache refs, and build the dispatch context the chunk programs
        share. Returns True on success, False on pool backpressure (the
        request stays in the backlog; the caller stops admitting this
        round — pool pressure is global), None when the request was
        dropped instead of admitted. ``rec``: this round's telemetry
        record — KV-tier offload/restore traffic is attributed to it."""
        if req.stream.cancelled:
            self._backlog = [e for e in self._backlog if e[0] is not req]
            req.stream._finish("cancelled")
            return None
        sp = req.params
        n_alloc = _ceil_div(req.extent, self.cfg.page_size)
        # Shared-prefix match: map the longest cached block chain of
        # this prompt read-only (refs taken NOW so pool-pressure
        # eviction below can't reclaim it out from under us).
        hashes, k_use, hit_pages = self._prefix_lookup(req)
        # Host tier: plan the priced restore of the chain's continuation
        # BEFORE eviction (the records are materialized host-side now,
        # so this admission's own offloads can't LRU them away).
        restore_recs: list = []
        if self._kv_tier is not None and req.rag is None and hashes:
            restore_recs = self._plan_restore(req, hashes, k_use)
        need_new = n_alloc - k_use
        if need_new > len(self._free_pages):
            # Pool pressure: reclaim retired requests' warm prefix
            # pages (refcount 0, LRU leaf-first) before declaring
            # backpressure — the cache borrows pool pages, it never
            # shrinks serving capacity. With the host tier enabled the
            # victims are OFFLOADED (async D2H) instead of dropped.
            if self._prefix_cache is not None:
                victims: list = []
                sink = None
                if self._kv_tier is not None:
                    sink = (lambda h, e:
                            victims.append((h, e.parent, e.page)))
                self._free_pages.extend(self._prefix_cache.evict(
                    need_new - len(self._free_pages), sink=sink))
                self._offload_victims(victims, rec)
            if need_new > len(self._free_pages):
                if k_use:
                    self._prefix_cache.release(hashes[:k_use])
                req.refused_avail = self._pool_avail_pages()
                return False  # pool backpressure: wait for pages
        self._backlog = [e for e in self._backlog if e[0] is not req]
        slot = self._free_slots.pop()
        req.slot = slot
        req.pages = hit_pages + [self._free_pages.pop()
                                 for _ in range(need_new)]
        req.cache_refs = list(hashes[:k_use])
        req.cache_pages = set(hit_pages)
        restored = 0
        if restore_recs:
            try:
                restored = self._restore_blocks(req, hashes, k_use,
                                                restore_recs, rec)
            except _StaleLoop:
                raise
            except Exception:  # noqa: BLE001 — fall back to recompute
                # The allocated pages hold garbage at worst; prefill
                # recomputes straight over them from the HBM-hit
                # boundary — token-identical, just slower (pinned by
                # the kv.restore chaos test).
                logger.warning("kv restore failed; recomputing prefix",
                               exc_info=True)
                restored = 0
        start_tok = (k_use + restored) * self.cfg.page_size
        req.proj_pos = len(req.prompt_ids)
        req.pf_pos = start_tok
        row = np.zeros((self._pmax,), np.int32)
        row[:n_alloc] = req.pages
        if self._prefix_cache is not None and req.rag is None:
            st = self._prefix_cache.stats
            st.lookups += 1
            st.lookup_tokens += len(req.prompt_ids)
            if start_tok:
                st.hits += 1
                st.hit_tokens += start_tok

        now = time.monotonic()
        # The wait is over: req_backlog ends and req_prefill begins at
        # this one stamp (the timeline's engine_admit_pickup is read
        # off the spans; the stage histogram gets the same number).
        req.stream._enter("req_prefill", self._round_seq, t=now,
                          n=len(req.prompt_ids), m=start_tok)
        observe_stage("engine_admit_pickup", now - req.stream.submit_time)
        if req.deadline_t is not None:
            # Slack at admission: the headroom left after the modeled
            # prefill of the UNCACHED suffix. Clamped at 0 — the
            # histogram answers "how much margin do admitted requests
            # carry"; negative-slack admissions all land in the first
            # bucket (they are also the ones deadline_stops later
            # counts if the model was right).
            slack = (req.deadline_t - now) - self._sched.cost.prefill_s(
                len(req.prompt_ids) - start_tok)
            record_stage("sched_slack", max(slack, 0.0))
        tl = req.stream.timeline
        if tl is not None:
            # The slot and pages this request occupies, and how much of
            # the prompt the prefix cache already held.
            tl.annotate(slot=slot, pages_held=len(req.pages),
                        prefix_hit_tokens=start_tok)
        # Masks/tables were built at submit() on the caller's thread
        # (overlapped with the queue wait) — the serve loop only
        # uploads them, keeping admission dispatch lean.
        banned = jnp.asarray(req.banned_np)
        bad_seq = jnp.asarray(req.bad_seq_np)
        bad_len = jnp.asarray(req.bad_len_np)
        # uploaded; don't pin ~vocab-size bytes per request for the
        # rest of its lifetime (queue depth x 128k-vocab rows adds up)
        req.banned_np = req.bad_seq_np = req.bad_len_np = None
        if req.resume_offset is not None:
            # Failover resume (docs/robustness.md): the admission key
            # must be a pure function of (seed, replay offset) — the
            # global step counter would make the continuation's first
            # draw depend on unrelated admissions, breaking the "same
            # seed ⇒ same continuation" resume contract. The offset
            # salt keeps a resume at offset N distinct from both a
            # fresh request and a resume at a different boundary.
            key = jax.random.fold_in(
                self._base_key,
                ((req.resume_offset + 1) << 20) ^ sp.random_seed)
        else:
            key = jax.random.fold_in(
                self._base_key,
                next(self._step_counter) ^ sp.random_seed)
        # Chunk-window geometry (only the chunked path reads it): the
        # gather window must cover the PADDED chunk span, not just the
        # request extent — a chunk whose padding runs past the window
        # would make dynamic_update_slice/dynamic_slice CLAMP their
        # starts and silently relocate KV over the prompt's own pages.
        # Chunk pads come from the prefill-bucket ladder, so one extra
        # max-bucket of pages covers any final-chunk overhang; pages
        # past the extent map to the trash page 0.
        page = self.cfg.page_size
        span_pages = (start_tok // page
                      + _ceil_div(len(req.prompt_ids) - start_tok, page)
                      + self._buckets[-1] // page)
        window = max(self._window_for(_ceil_div(req.extent, page)),
                     span_pages)
        row_ext = np.zeros((window,), np.int32)
        row_ext[:min(len(row), window)] = row[:min(len(row), window)]
        seen0 = None
        if start_tok > 0:
            # Prefix-cache hit: the seen (repetition-penalty) mask over
            # the skipped prefix is rebuilt host-side from the prompt
            # itself and seeded into the first chunk's dispatch (packed,
            # same uint32 bitfield layout as the device state).
            V = self.model_cfg.vocab_size
            seen0 = np.zeros((V,), bool)
            ids = np.asarray(req.prompt_ids[:start_tok], np.int64)
            seen0[ids[(ids >= 0) & (ids < V)]] = True
            seen0 = pack_mask_np(seen0)
        req.pf = {
            "row": row, "row_win": jnp.asarray(row_ext[None, :]),
            "window": window, "start_tok": start_tok,
            "hashes": hashes, "k_use": k_use,
            "seed": None if seen0 is None else jnp.asarray(seen0),
            "banned": banned, "bad_seq": bad_seq, "bad_len": bad_len,
            "key": key, "dispatch_s": 0.0,
        }
        self._slots[slot] = req
        self._bump("prefills")
        return True

    def _abort_prefill(self, req: _Request, finish: str) -> None:
        """Retire a mid-prefill request (cancel / passed deadline). The
        slot was never armed on the device (``active`` stays False until
        the final chunk), so no device release is needed — just the
        slot/page/cache-ref bookkeeping."""
        req.pf = None
        self._retire(req, finish)

    def _prefill_aborted(self, req: _Request) -> bool:
        """Between-chunk aborts only: an admission that began keeps the
        PR-5 contract (its first dispatch runs and the harvest path
        notices cancellation/deadline at the first token) — but a
        MULTI-chunk prefill whose caller is gone stops sinking further
        rounds into an unwanted answer. True: the request was retired
        and dispatches nothing more."""
        if req.pf_pos <= req.pf["start_tok"]:
            return False
        if req.stream.cancelled:
            self._abort_prefill(req, "cancelled")
            return True
        if req.deadline_t is not None \
                and time.monotonic() > req.deadline_t:
            # Counted as a mid-flight deadline stop (the request DID
            # consume compute, unlike a deadline_queue drop).
            self._bump("deadline_stops")
            self._abort_prefill(req, "deadline")
            return True
        return False

    def _advance_prefill_rows(self, members: list, rec, ph):
        """``_advance_prefill`` for several prompts at once: each
        member's next whole largest-bucket chunk, non-final, in ONE
        program (``programs.make_extend_rows``). Returns its
        ProgramRun."""
        C = self._buckets[-1]
        faults.inject("engine.dispatch")  # chaos: slow/failed prefill
        t_chunk = time.monotonic()
        toks = np.asarray([r.prompt_ids[r.pf_pos:r.pf_pos + C]
                           for r in members], np.int32)
        start = np.asarray([r.pf_pos for r in members], np.int32)
        self._guard_live()
        new_state, marker = self.programs.chunk_rows_fn(len(members))(
            self._state, self.params, jnp.asarray(toks), jnp.asarray(start),
            jnp.asarray(np.asarray([r.slot for r in members], np.int32)),
            jnp.asarray(np.stack([r.pf["row"] for r in members])),
            jnp.asarray(start == 0))
        self._guard_live()
        self._state = new_state
        t_done = time.monotonic()
        for req in members:
            req.pf["dispatch_s"] += t_done - t_chunk
            self._chunk_span(req, t_chunk, t_done, C, C)
            req.pf_pos += C
        return self._launched_chunk(
            rec, ph, marker, "extend_rows", tokens=len(members) * C,
            padded=len(members) * C, rows=len(members))

    def _launched_chunk(self, rec, ph, marker, name: str, **what):
        """A chunk program is on the device queue: open its ProgramRun
        on the round (``ph`` the ``chunk_dispatch`` span still open
        around the launch) and, for a non-final one, hand its completion
        ``marker`` to the harvest worker (a final chunk's first token is
        ``_arm_slot``'s to hand over). Returns the run."""
        run = self.rounds.launch(rec, name, t_launch0=ph.t0, **what)
        if marker is not None:
            self._assert_harvestable(marker)
            self._harvest_q.put(("mark", rec, run, marker))
        return run

    def _advance_prefill(self, req: _Request, grant: int, rec,
                         ph) -> tuple[int, Optional[object]]:
        """Admission half 2, run once per round plan: dispatch ONE
        prefill chunk of up to ``grant`` tokens (bucket-shape padded)
        inside the ``chunk_dispatch`` span ``ph``. The final chunk arms
        the slot and hands the first token to the harvest worker; a
        non-final one hands it a marker, a device scalar that
        data-depends on the dispatched program (its readback times the
        program's end). Returns ``(tokens computed, the program's
        ProgramRun)``; ``(0, None)`` when nothing dispatched. Short cold
        prompts whose whole extent fits the grant keep the ONE-dispatch
        fused prefill+insert path — the TTFT-critical case is still a
        single program."""
        if req.rag is not None:
            return self._dispatch_rag(req, rec, ph)
        pf = req.pf
        if self._prefill_aborted(req):
            return 0, None
        chunk = self._next_chunk(req, grant)
        n, final = chunk.n, chunk.final
        if n <= 0:
            return 0, None
        faults.inject("engine.dispatch")  # chaos: slow/failed prefill
        t_chunk = time.monotonic()
        ids = req.prompt_ids[req.pf_pos:req.pf_pos + n] \
            + [0] * (chunk.padded - n)
        toks = jnp.asarray(np.asarray(ids, np.int32)[None, :])
        self._guard_live()
        if chunk.mode == "one-shot":
            # Whole cold prompt in one grant: the classic fused
            # prefill+sample+insert dispatch (one program boundary on
            # the TTFT path — see programs.make_prefill_insert).
            new_state, first_tok = self.programs.prefill_insert(
                self._state, self.params, toks, jnp.int32(n),
                jnp.int32(req.slot), jnp.asarray(pf["row"]),
                *self._sampling_args(req), req.greedy)
            marker, name, window = None, "prefill_insert", 0
        else:
            window = pf["window"]
            args = (self._state, self.params, toks, jnp.int32(req.pf_pos),
                    jnp.int32(req.pf_pos + n), jnp.int32(req.slot))
            seed = (pf["seed"],) if chunk.seen == "seed" else ()
            if not final:
                new_state, marker = self.programs.chunk_extend_fn(
                    window, chunk.seen)(*args, pf["row_win"], *seed)
                name = "extend"
            else:
                new_state, first_tok = self.programs.chunk_final_fn(
                    window, req.greedy, chunk.seen == "seed")(
                    *args, jnp.asarray(pf["row"]), pf["row_win"],
                    *self._sampling_args(req), *seed)
                marker, name = None, "final"
        self._guard_live()
        self._state = new_state
        t_done = time.monotonic()
        pf["dispatch_s"] += t_done - t_chunk
        self._chunk_span(req, t_chunk, t_done, n, chunk.padded)
        req.pf_pos += n
        run = self._launched_chunk(rec, ph, marker, name, tokens=n,
                                   padded=chunk.padded, rows=1,
                                   window=window)
        if final:
            self._arm_slot(req, first_tok, rec, run)
        return n, run

    @staticmethod
    def _sampling_args(req: _Request) -> tuple:
        """The sampling state an admission program arms a slot with, in
        the order ``prefill_insert`` and ``final`` take it."""
        sp, pf = req.params, req.pf
        return (jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                jnp.float32(sp.top_p), jnp.float32(sp.repetition_penalty),
                pf["banned"], pf["bad_seq"], pf["bad_len"], pf["key"],
                jnp.int32(req.eff_max - 1), jnp.bool_(not sp.ignore_eos))

    def _chunk_span(self, req: _Request, t0: float, t1: float,
                    tokens: int, padded: int) -> None:
        """One chunk's host dispatch (the device work is async) as a
        ``req_chunk`` child of the request's ``req_prefill`` span; the
        id of the round being dispatched joins it to the
        ``engine_round`` and ``chunk_dispatch`` spans of a trace."""
        st = req.stream
        if st.state is not None:
            st.timeline.child(st.state, "req_chunk", t0, t1,
                              self._round_seq, tokens, padded)

    def _arm_slot(self, req: _Request, first_tok, rec=None,
                  run=None) -> None:
        """Prefill complete: publish cache blocks, mark the slot armed
        for decode rounds, and hand the first-token readback to the
        harvest worker (its wait overlaps the decode rounds dispatched
        right after — FIFO order in the queue keeps it ahead of them).
        ``rec``: the round record of the ARMING round — the harvest
        worker attributes the first-token readback wait (and the first
        token itself) to it; ``run``: the ProgramRun of the program that
        computed the token, done when that readback returns."""
        pf = req.pf
        self._register_prefix(req, pf["hashes"], pf["k_use"])
        # Cumulative host dispatch time across every chunk of this
        # admission (the timeline reads the same sum off its req_chunk
        # spans); req_prefill ends and req_first_token begins.
        observe_stage("engine_admit_dispatch", pf["dispatch_s"])
        req.stream._enter("req_first_token", self._round_seq)
        try:
            # Start the device->host transfer of the first token now —
            # the harvest worker's np.asarray then finds the value
            # host-side (or at least in flight) instead of paying the
            # full readback RTT after the fact.
            first_tok.copy_to_host_async()
        except Exception:  # noqa: BLE001 — optional fast path
            pass
        req.pf = None
        req.prefill_done = True
        self._assert_harvestable(first_tok)
        self._harvest_q.put(("first", req, first_tok, rec, run))

    def _dispatch_rag(self, req: _Request, rec, ph
                      ) -> tuple[int, Optional[object]]:
        """Fused-RAG admission: retrieval + assembly + prefill happen in
        ONE device program, so the dispatch is atomic — the scheduler
        charges the whole assembled bucket against the round budget (a
        grant can't split an on-device assembly). Returns ``(tokens,
        ProgramRun)`` like ``_advance_prefill``."""
        pf = req.pf
        faults.inject("engine.dispatch")  # chaos: slow/failed prefill
        t0 = time.monotonic()
        q_llm, q_len, q_enc = req.rag
        fused = self._fused_rag
        req.proj_pos = fused.spec.bucket  # device pos upper bound
        self._guard_live()
        new_state, first_tok = self._rag_jit(
            self._state, self.params, fused.enc_params,
            fused.corpus, jnp.asarray(q_enc), jnp.asarray(q_llm),
            jnp.int32(q_len), jnp.int32(req.slot),
            jnp.asarray(pf["row"]), *self._sampling_args(req), req.greedy)
        self._guard_live()
        self._state = new_state
        t1 = time.monotonic()
        pf["dispatch_s"] += t1 - t0
        self._chunk_span(req, t0, t1, fused.spec.bucket, fused.spec.bucket)
        run = self._launched_chunk(rec, ph, None, "rag",
                                   tokens=fused.spec.bucket,
                                   padded=fused.spec.bucket, rows=1)
        self._arm_slot(req, first_tok, rec, run)
        return fused.spec.bucket, run

    def _dispatch_round(self, steps: int, rec=None) -> bool:
        """Dispatch one decode round of ``steps`` fused steps (the plan
        right-sized them against the power-of-two ladder), or decline
        (False) when no ARMED slot still needs tokens — slots mid-
        chunked-prefill are excluded: they are inactive on the device
        until their final chunk arms them, so a round over them would be
        pure masked work. ``rec``: this round's telemetry record; the
        dispatched program's harvest item carries it so the harvest
        worker can complete the execution half."""
        members = {s: r for s, r in self._slots.items() if r.prefill_done}
        need_steps = max((r.extent - r.proj_pos for r in
                          members.values()), default=0)
        if need_steps <= 0 or steps <= 0:
            return False
        faults.inject("engine.dispatch")  # chaos: slow/failed decode round
        need = max(min(r.proj_pos + steps, r.extent) + 1
                   for r in members.values())
        # Kernel path: pass the full table — the kernel's per-slot dynamic
        # loop bound already scales HBM reads with live context, so there
        # is exactly ONE compiled round per (steps, greedy) instead of a
        # whole window ladder. The jnp gather path still needs the window
        # sliced (its gather materializes window x page rows per slot).
        if self._use_kernel:
            window = self._pmax
        else:
            window = self._window_for(_ceil_div(need, self.cfg.page_size))
        greedy = all(r.greedy for r in members.values())
        # Active-slot compaction: the fused tail unembeds/samples only
        # the armed slots, padded to the smallest compiled rung (padding
        # indices == max_slots: gathers clamp, scatters drop). The
        # materialized tail (ENGINE_FUSED_SAMPLER=0 / downgraded
        # geometry) always runs full-width.
        ba = self.programs.ba_for(len(members))
        with phase("loop_dispatch",
                   round_id=-1 if rec is None else rec.round_id,
                   steps=steps, rows=len(members), ba=ba) as ph:
            run = self._launch_round(members, window, steps, greedy, ba,
                                     rec, ph)
        if run is not None:
            run.t_launch1 = ph.t1
        return True

    def _launch_round(self, members: dict, window: int, steps: int,
                      greedy: bool, ba: int, rec, ph):
        """The ``loop_dispatch`` span's body (``ph``): launch the round
        program, start its async readback, and hand it to the harvest
        worker with its ProgramRun, which it returns."""
        B = self.cfg.max_slots
        key = jax.random.fold_in(self._base_key, next(self._step_counter))
        act = np.full((ba,), B, np.int32)
        act[:len(members)] = sorted(members)
        new_state, toks = self.programs.round_fn(window, steps, greedy, ba)(
            self.params, self._state, key, jnp.asarray(act))
        round_stats = {}
        if self.programs.spec.round_stat_names(greedy):
            toks, round_stats = toks
        self._guard_live()  # reset() may have run while the round compiled
        self._state = new_state
        self._count_tail(ba, steps, self.programs.tail.kernel)
        try:
            # Async host copy: the harvest worker's np.asarray then finds
            # the round's tokens already on the host instead of paying a
            # blocking readback per round.
            toks.copy_to_host_async()
            for v in round_stats.values():
                v.copy_to_host_async()
        except Exception:  # noqa: BLE001 — optional fast path
            pass
        if rec is not None:
            # Execution estimate for the round record: live pages each
            # step must read (per-slot ceil(pos/page), pre-advance) and
            # the HBM traffic they plus the weight stream imply.
            page = self.cfg.page_size
            pages_per_step, skipped = self._pages_read(members)
            rec.decode_slots = len(members)
            rec.pages_touched += round(pages_per_step * steps)
            rec.kv_pages_skipped += skipped * steps
            rec.hbm_bytes += round(steps * (
                self._step_weight_bytes(len(members))
                + pages_per_step * page * self._kv_bytes_per_token()))
            if skipped:
                self._bump("kv_pages_skipped", skipped * steps)
            K = self.model_cfg.index_topk
            if K:       # what the selection chose, and what it scored
                ctx = [r.proj_pos + 1 for r in members.values()]
                indexed = sum(ctx)
                selected = sum(min(c, K) for c in ctx)
                # ... and what the read streamed to attend them: the
                # kernel walks a row's cached pages in whole blocks, the
                # gathered form takes every slot's whole window
                read = kv_cache_of(self.model_cfg).kernel_rows_read(
                    ctx, page) if self._use_kernel else B * window * page
                rec.kv_rows_selected += selected * steps
                rec.kv_rows_indexed += indexed * steps
                rec.kv_rows_read += read * steps
                rec.kv_selected_pct = 100.0 * selected / max(indexed, 1)
                rec.kv_read_per_selected = (
                    rec.kv_rows_read / max(rec.kv_rows_selected, 1))
                self._bump("kv_rows_selected", selected * steps)
                self._bump("kv_rows_indexed", indexed * steps)
                self._bump("kv_rows_read", read * steps)
            if self.programs.spec.state_step_kernel:
                # the recurrent layers' step kernel walks the live rows
                # of all B slots' state and moves no other
                rec.state_rows_idle_pct = 100.0 * (B - len(members)) / B
                self._bump("state_rows_idle", (B - len(members)) * steps)
                self._bump("state_rows_live", len(members) * steps)
        for req in members.values():
            req.proj_pos = min(req.proj_pos + steps, req.extent)
        self._count_inflight()
        self._assert_harvestable(toks)
        # counted BEFORE the hand-off: the harvest worker can end a
        # stream the moment it has the round, and whoever reads the
        # stats then must find the round that ended it
        self._bump("decode_steps", steps)
        run = self.rounds.launch(
            rec, "decode_round", tokens=len(members) * steps,
            padded=ba * steps, rows=len(members), steps=steps,
            t_launch0=ph.t0)
        self._harvest_q.put(("round", members, toks, round_stats, rec, run))
        return run

    def _count_tail(self, ba: int, rows_per_slot: int,
                    kernel: bool) -> None:
        """A dispatched round's tail: the rows it sampled and the slots'
        rows its compaction skipped — fused-tail occupancy
        (observability.md): the materialised tail leaves both at 0
        rather than masquerading as a full-occupancy fused engine — and
        whether a head kernel ran it."""
        if self.programs.tail.gathers_rows:
            self._bump("sampler_rows_sampled", ba * rows_per_slot)
            self._bump("sampler_rows_skipped",
                       (self.cfg.max_slots - ba) * rows_per_slot)
        if kernel:
            self._bump("tail_kernel_rounds")

    def _count_inflight(self) -> None:
        """One more round rides the device queue ahead of harvest."""
        with self._pipe_lock:
            self._inflight_rounds += 1
            depth = self._inflight_rounds
        with self._stats_lock:
            if depth > self._stats["dispatch_depth_peak"]:
                self._stats["dispatch_depth_peak"] = depth

    def _step_weight_bytes(self, rows: int) -> float:
        """Weight bytes one decode step of ``rows`` rows streams: all of
        them, but of dropless experts only those the rows are expected
        to reach (each row k of E at random) — the plan cannot know the
        routing; the round's ``experts_touched`` says what it was."""
        if not self._expert_bytes:
            return self._param_bytes
        mc = self.model_cfg
        idle = (1.0 - mc.num_experts_per_tok / mc.num_experts) ** rows
        return self._param_bytes - self._expert_bytes * idle

    def _pages_read(self, members: dict) -> tuple[float, float]:
        """Pages ONE decode step reads and pages it skips, summed over
        the rows and averaged over the layers: every layer reads a row's
        live pages but a window layer, which starts at the page of the
        first key its query attends (ops/paged_attention.py) — so a
        round's pages, bytes and bandwidth share follow
        ``min(context, window)`` in those layers."""
        page = self.cfg.page_size
        W, share = self.model_cfg.sliding_window, self._window_share
        read = skipped = 0.0
        for r in members.values():
            full = _ceil_div(max(1, r.proj_pos + 1), page)
            behind = share * (max(r.proj_pos - W + 1, 0) // page) \
                if share else 0.0
            read += full - behind
            skipped += behind
        return read, skipped

    def _dispatch_verify(self, drafts: dict, rec=None) -> bool:
        """Dispatch one speculative VERIFY round: every armed slot rides
        it (slots without proposals as plain 1-token rows), slots in
        ``drafts`` carry their prompt-lookup proposals. One model step,
        up to S tokens emitted per slot. Only called with the pipeline
        drained (``_queued_rounds() == 0``), so the host's per-request
        token lists — and therefore ``proj_pos`` — are exact."""
        members = {s: r for s, r in self._slots.items() if r.prefill_done}
        need_steps = max((r.extent - r.proj_pos
                          for r in members.values()), default=0)
        if need_steps <= 0 or not drafts:
            return False
        faults.inject("engine.dispatch")  # chaos: slow/failed decode round
        S = self._spec_S
        B = self.cfg.max_slots
        page = self.cfg.page_size
        # The gather window must cover every scored position (pos..
        # pos+S-1 in-register rows included); proj_pos is exact here.
        need = max(min(r.proj_pos + S, r.extent) + 1
                   for r in members.values())
        window = self._window_for(_ceil_div(need, page))
        greedy = all(r.greedy for r in members.values())
        ba = self.programs.ba_for(len(members))
        act = np.full((ba,), B, np.int32)
        act[:len(members)] = sorted(members)
        draft_np = np.zeros((B, S - 1), np.int32)
        n_np = np.zeros((B,), np.int32)
        drafted: dict[int, int] = {}
        for slot, toks in drafts.items():
            k = min(len(toks), S - 1)
            draft_np[slot, :k] = toks[:k]
            n_np[slot] = k
            drafted[slot] = k
        with phase("loop_dispatch",
                   round_id=-1 if rec is None else rec.round_id,
                   steps=1, rows=len(members), ba=ba) as ph:
            key = jax.random.fold_in(self._base_key, next(self._step_counter))
            t0 = time.monotonic()
            new_state, (toks, acc) = self.programs.verify_fn(
                window, greedy, ba)(
                self.params, self._state, key, jnp.asarray(act),
                jnp.asarray(draft_np), jnp.asarray(n_np))
            self._guard_live()  # reset() may have run while the round compiled
            self._state = new_state
            dt = time.monotonic() - t0
            # Speculative overhead attribution: host-side dispatch time of
            # the verify round, globally and on each member's timeline (one
            # stage event per round per slot — the decode_round budget).
            record_stage("engine_verify", dt)
            for req in members.values():
                tl = req.stream.timeline
                if tl is not None:
                    tl.stage("engine_verify", dt)
            # a sampled verify: the scan
            self._count_tail(ba, S, self.programs.tail.kernel and greedy)
            try:
                toks.copy_to_host_async()
                acc.copy_to_host_async()
            except Exception:  # noqa: BLE001 — optional fast path
                pass
            if rec is not None:
                # the verify forward gathers whole windows whatever the
                # layer (the jnp path masks, it does not skip)
                pages_per_step = sum(
                    _ceil_div(max(1, r.proj_pos + 1), page)
                    for r in members.values())
                rec.decode_slots = len(members)
                rec.spec_drafted = sum(drafted.values())
                rec.verify_positions = S * len(members)
                rec.pages_touched += pages_per_step
                rec.hbm_bytes += (
                    self._param_bytes
                    + pages_per_step * page * self._kv_bytes_per_token())
            for req in members.values():
                req.proj_pos = min(req.proj_pos + S, req.extent)
            self._count_inflight()
            self._assert_harvestable(toks, acc)
            self._bump("decode_steps")      # before the hand-off, as above
            self._bump("spec_verify_rounds")
            run = self.rounds.launch(
                rec, "verify_round", tokens=len(members), padded=ba * S,
                rows=len(members), steps=1, t_launch0=ph.t0)
            self._harvest_q.put(("verify", members, toks, acc, drafted, rec,
                                 run))
        if run is not None:
            run.t_launch1 = ph.t1
        return True

    def _emit_token(self, req: _Request, token: int) -> None:
        """Deliver one generated token (HARVEST-worker thread); finish the
        stream and post the completion for the scheduler to retire when
        the request ends. Finish logic mirrors the device-side termination
        exactly, so the host and device agree on each slot's last token.
        No device state is touched here — a host-detected finish's slot
        release is the scheduler's job (_drain_completed)."""
        req.generated += 1
        req.stream.token_ids.append(token)
        if req.drafter is not None:
            # Keep the prompt-lookup index in step with the stream (the
            # drafter only proposes between fully-harvested rounds, so
            # this index is never behind the device at proposal time).
            req.drafter.extend((token,))
        self._bump("tokens_generated")
        if req.stream.first_token_time is None:
            req.stream.first_token_time = time.monotonic()
            ttft = req.stream.first_token_time - req.stream.submit_time
            # Once per request, not per token. The single authoritative
            # engine_ttft record (EngineLLM deliberately does not
            # re-report it): req_first_token ends and req_decode begins
            # at the stream's own first-token stamp, and the stage
            # histogram gets the same number.
            req.stream._enter("req_decode", req.stream.round_id,
                              t=req.stream.first_token_time)
            observe_stage("engine_ttft", ttft)

        finish: Optional[str] = None
        if token == self.tokenizer.eos_id and not req.params.ignore_eos:
            finish = "eos"
        elif req.generated >= req.eff_max:
            finish = "length"

        if req.stream.cancelled and finish is None:
            finish = "cancelled"
        elif (finish is None and req.deadline_t is not None
                and time.monotonic() > req.deadline_t):
            # Deadline passed mid-generation: stop decoding now — the
            # tokens already emitted stand, but nobody is waiting for
            # more. Retired like a host-detected finish (the scheduler
            # releases the slot on the device).
            finish = "deadline"
            self._bump("deadline_stops")
        elif finish != "eos":  # eos token itself is not emitted as text
            chunk = req.stop.feed(req.detok.push(token))
            req.stream._put_chunk(chunk)
            if req.stop.stopped:
                finish = "stop"

        if finish is not None:
            if finish in ("eos", "length"):
                # Emit text still held back — both the detokenizer's
                # incomplete-fragment window and any potential stop-word
                # prefix in the stop checker.
                req.stream._put_chunk(req.stop.feed(req.detok.flush()))
                req.stream._put_chunk(req.stop.flush())
                if req.stop.stopped and finish == "length":
                    finish = "stop"  # stop word surfaced in the final flush
            # Terminal sentinel goes out NOW (consumer latency), before
            # the scheduler gets around to the slot/page bookkeeping.
            if not req.done:  # a failed stream keeps its "error" reason
                req.stream._finish(finish)
            self._completed.put((req, finish))
            self._wake.set()  # the freed slot may unblock an admission

    def _retire(self, req: _Request, finish: str) -> None:
        """Scheduler-side completion: return the slot and its non-cache
        pages, release prefix-cache refs. The stream is usually already
        finished by the harvest worker; the drain paths pass a terminal
        reason for requests that never got one."""
        del self._slots[req.slot]
        self._free_slots.append(req.slot)
        # Pages under cache control stay resident (warm for the next
        # shared-prefix request) instead of returning to the free list;
        # releasing the refs afterwards makes them reclaimable at LRU
        # order once no live request maps them.
        self._free_pages.extend(p for p in req.pages
                                if p not in req.cache_pages)
        if req.cache_refs:
            self._prefix_cache.release(req.cache_refs)
        req.pages = []
        req.cache_refs = []
        req.cache_pages = set()
        if not req.done:  # a failed stream keeps its "error" reason
            req.stream._finish(finish)
