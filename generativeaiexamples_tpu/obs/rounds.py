"""Engine-level round telemetry: what did the ENGINE do each round?

The flight recorder (``obs/flight.py``) answers "where did THIS
request's time go"; this module answers the question that remained
unobservable: *what did the engine do in each scheduler round, and did
it match the plan?* The token-budget scheduler (engine/scheduler.py)
makes per-round promises — decode never displaced, chunks sized to the
budget, verify rounds priced through the step-cost model — and those
are exactly per-round properties: without a per-round record they can
neither be audited in production nor used to calibrate the cost model
on real chips.

Every executed round gets a :class:`RoundRecord` in a bounded ring,
built under the same discipline as the flight ring:

- the **scheduler thread** appends the *plan* (``begin``: budget
  tokens, decode steps/slots, spec decisions) and *seals* the dispatch
  half (``seal``: prefill grants per job, host dispatch wall, modeled
  cost, estimated HBM traffic);
- the **harvest thread** completes the *execution* (``complete_part``:
  readback waits, tokens emitted, spec acceptances) — the record
  finalizes when its last outstanding device output has been harvested,
  which is when per-round device time can honestly be measured.

Appends never contend with the engine's token path: ``begin``/``seal``
run once per round on the scheduler thread, completion once per
harvested item on the harvest thread, and the recorder's lock guards
only the ring and the pipelined-completion clock — O(1) work per round,
nothing per token.

Exposure, three ways:

- ``GET /debug/rounds`` on the chain server and the model server: the
  last-N records plus rolling aggregates (``snapshot``);
- ``engine_round_*`` / ``sched_cost_drift_ratio`` metrics on
  ``/metrics``, declared in :data:`ROUND_METRICS` and doc-checked by
  ``tools/check_metrics_docs.py`` (the router-table contract);
- a retrospective OTel span per round (``emit_round_span``) when
  tracing is on — explicit timestamps, no SDK work on the serve loop.

Timing semantics (what the fields mean):

- ``dispatch_ms`` — host wall spent inside this round's device
  dispatches (compile + enqueue; the scheduler-thread cost).
- ``round_ms`` — plan start to last harvested output: the round's
  end-to-end wall, including host dispatch. This is what the drift
  gauge and the slow-round dump judge, so a host-side stall (a fault
  injection, a GC pause, a compile) is visible, not just device time.
- ``programs`` — one :class:`ProgramRun` a device program the round
  dispatched, in launch order: what it was and carried, when its launch
  began and ended (scheduler thread) and when its own readback returned
  (harvest thread). ``service_ms`` = done minus the later of (its launch
  end, the engine's PREVIOUS program's done): on a FIFO device that is
  never idle, the program's time on the chip. Under dispatch-ahead the
  raw launch→readback latency double-counts queue wait; this estimator
  does not.
- ``device_ms`` — the sum of the round's programs' ``service_ms``: the
  pipelined per-round device-time estimate the online cost calibrator
  feeds on.
- ``done_during_launch`` (on the program whose launch was the round's
  longest) — how many earlier programs' ``t_done`` fell inside that
  launch. A long launch with completions inside it waited for room in a
  queue the chip was draining; one with none saw the chip, the runtime
  or the machine stand still.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from ..utils.logging import get_logger

logger = get_logger(__name__)

#: ``t_done`` stamps kept an engine (the program clock): more than a
#: device queue holds, so a launch counts every completion inside it.
_DONE_STAMPS = 64

#: Tokens-per-round ladder: one decode round emits steps x slots tokens
#: (8..512 typical); prefill-heavy rounds grant up to a few pages.
ROUND_TOKEN_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024,
                       2048, 4096)

#: The round-telemetry metric surface, name -> (kind, help). Documented
#: in docs/observability.md between ``<!-- round-metrics:begin/end -->``
#: and enforced two-way by tools/check_metrics_docs.py, like the router
#: table. ``sched_cost_drift_ratio`` keeps its scheduler-facing name on
#: purpose: it is the model-vs-measured signal operators alert on.
ROUND_METRICS: dict[str, tuple[str, str]] = {
    "engine_rounds_total": (
        "counter",
        "engine rounds completed: plan sealed AND every device output "
        "of the round harvested"),
    "engine_round_seconds": (
        "histogram",
        "per-round wall time, plan start to last harvested output "
        "(includes host dispatch — the drift/dump signal)"),
    "engine_round_device_seconds": (
        "histogram",
        "pipelined per-round device service-time estimate (the sum of "
        "its programs' service times) — what the online cost calibrator "
        "feeds on"),
    "engine_program_seconds": (
        "histogram",
        "per-program device service-time estimate, labeled by program "
        "(decode_round, verify_round, prefill_insert, extend, "
        "extend_rows, final, rag): its own readback minus max(its "
        "launch end, the previous program's readback)"),
    "engine_round_tokens": (
        "histogram",
        "tokens per completed round: decode/verify tokens emitted + "
        "first tokens + prefill tokens granted"),
    "engine_round_bw_util": (
        "gauge",
        "last completed round's estimated HBM bandwidth-utilization "
        "fraction (estimated bytes moved / device time / chip peak; "
        "0 on CPU where no peak is defined)"),
    "engine_round_hbm_bytes_total": (
        "counter",
        "estimated HBM bytes moved by completed rounds (weight stream "
        "per step + live KV pages touched + prefill KV writes)"),
    "sched_cost_drift_ratio": (
        "gauge",
        "EWMA of measured round wall vs the step-cost model's "
        "prediction (1.0 = model matches reality; engine-level, "
        "mirrored per-engine as engine_sched_cost_drift_ratio)"),
    "engine_round_slow_dumps_total": (
        "counter",
        "slow-round structured dumps emitted (round drift or wall time "
        "breached ROUND_DRIFT_DUMP_RATIO / ROUND_SLOW_MS)"),
}


# Resolved metric handles, memoized: record_round_metrics runs on the
# harvest thread once per round — one dict hit beats a lock-guarded
# registry lookup per metric (the obs/metrics.py stage-children
# convention).
_metric_cache: dict[str, object] = {}
# ... and engine_program_seconds' children by program name
_program_children: dict[str, object] = {}


def _round_metric(name: str):
    """Resolve one declared round metric from the process registry
    (memoized; benign race — both writers cache the same object)."""
    m = _metric_cache.get(name)
    if m is not None:
        return m
    from . import metrics as obs_metrics
    kind, help_txt = ROUND_METRICS[name]
    reg = obs_metrics.REGISTRY
    if kind == "counter":
        m = reg.counter(name, help_txt)
    elif kind == "gauge":
        m = reg.gauge(name, help_txt)
    else:
        buckets = (ROUND_TOKEN_BUCKETS if name == "engine_round_tokens"
                   else obs_metrics.STAGE_BUCKETS)
        labels = (("program",) if name == "engine_program_seconds"
                  else ())
        m = reg.histogram(name, help_txt, buckets=buckets,
                          labelnames=labels)
    _metric_cache[name] = m
    return m


#: The device programs a round dispatches, under the names
#: ``engine/programs.py`` builds them: the label values of
#: ``engine_program_seconds``.
PROGRAM_NAMES = ("decode_round", "verify_round", "prefill_insert", "extend",
                 "extend_rows", "final", "rag")


class ProgramRun:
    """One dispatched device program, on the round that dispatched it.

    The scheduler thread writes what it was and the launch
    (``RoundRecorder.launch``; ``t_launch1`` once the span around the
    jitted call has closed), the harvest thread the completion
    (``t_done``: the instant the program's OWN readback returned — its
    marker scalar, its first token, its token block — before anything
    is detokenised or emitted; ``t_prev_done``: the engine's previous
    program's). ``time.monotonic()`` throughout, the clock of
    ``RoundRecord.t_start``."""

    __slots__ = ("name", "tokens", "padded", "rows", "steps", "window",
                 "t_launch0", "t_launch1", "t_done", "t_prev_done",
                 "done_during_launch")

    def __init__(self, name: str, tokens: int, padded: int, rows: int,
                 steps: int, window: int, t_launch0: float):
        self.name = name
        # real tokens a chunk program carried; rows x steps of a decode
        # or verify program
        self.tokens = tokens
        self.padded = padded        # tokens of the shape it ran in
        self.rows = rows
        self.steps = steps          # 0 for a chunk program
        self.window = window        # a chunk program's page window, else 0
        self.t_launch0 = t_launch0
        self.t_launch1 = t_launch0
        self.t_done = 0.0
        self.t_prev_done = 0.0
        # set when the round finalises, on its longest launch only
        self.done_during_launch: Optional[int] = None

    @property
    def launch_ms(self) -> float:
        return (self.t_launch1 - self.t_launch0) * 1e3

    @property
    def service_ms(self) -> float:
        """The program's time on the chip: its readback minus the later
        of its launch end and the previous program's readback (0 until
        it is done)."""
        if not self.t_done:
            return 0.0
        return max(0.0, self.t_done - max(self.t_launch1,
                                          self.t_prev_done)) * 1e3

    def to_dict(self, t_start: float) -> dict:
        """JSON-ready; instants in ms from the round's ``t_start``."""
        out = {
            "name": self.name, "tokens": self.tokens,
            "padded": self.padded, "rows": self.rows, "steps": self.steps,
            "window": self.window,
            "launch_at_ms": round((self.t_launch0 - t_start) * 1e3, 3),
            "launch_ms": round(self.launch_ms, 3),
            "done_at_ms": (round((self.t_done - t_start) * 1e3, 3)
                           if self.t_done else None),
            "service_ms": round(self.service_ms, 3),
        }
        if self.done_during_launch is not None:
            out["done_during_launch"] = self.done_during_launch
        return out


class RoundRecord:
    """One scheduler round: the plan, its dispatch, and its harvest.

    Written by exactly two threads in a strict phase order — scheduler
    (``begin``/``seal``), then harvest (completion) — with ``done`` set
    last, so a snapshot reader that observes ``done`` observes a fully
    written record (the no-torn-records contract the thread-safety test
    pins)."""

    __slots__ = (
        # identity / plan (scheduler thread, begin)
        "round_id", "engine_tag", "t_start", "wall_start", "kind",
        "budget_tokens", "decode_steps", "decode_cost_tokens",
        "active_decodes", "plan_ms", "pool_used_pages",
        "waiting_slot", "waiting_pages", "waiting_budget",
        "prefill_ungranted", "queued_ahead",
        # dispatch (scheduler thread, filled until seal)
        "decode_slots", "spec_drafted", "verify_positions",
        "prefill_tokens", "prefill_padded_tokens", "grants",
        "pages_touched", "hbm_bytes",
        "kv_restore_pages", "blocked_on_pages", "kv_pages_skipped",
        "kv_rows_selected", "kv_rows_indexed", "kv_selected_pct",
        "kv_rows_read", "kv_read_per_selected", "state_rows_idle_pct",
        "dispatch_ms", "modeled_ms", "t_dispatch_done",
        # execution (harvest thread)
        "harvest_wait_ms", "first_readback_ms", "emit_ms",
        "tokens_emitted", "first_tokens", "spec_accepted",
        "experts_touched", "tail_resort_pct", "local_assignments",
        "hc_row_defect", "route_groups_held_pct", "route_rows_read",
        "t_parts", "programs",
        # finalization
        "t_done", "device_ms", "round_ms", "bw_util", "drift_ratio", "done",
        # bookkeeping
        "_parts", "_done_parts", "_sealed", "_cb",
    )

    def __init__(self, round_id: int, engine_tag: str):
        self.round_id = round_id
        self.engine_tag = engine_tag
        self.t_start = time.monotonic()
        self.wall_start = time.time()
        self.kind = "decode"
        self.budget_tokens = 0
        self.decode_steps = 0
        self.decode_cost_tokens = 0
        self.active_decodes = 0
        # Host time of the planning phase that produced this round
        # (the loop_plan span: intake pull, control ops, backlog cull,
        # recalibration, _plan_round) — beside dispatch_ms and emit_ms
        # it is the host's split of a round without a profiler.
        self.plan_ms = 0.0
        # Pool occupancy when the round began: pages held by live
        # requests (total - free - evictable prefix-cache pages).
        self.pool_used_pages = 0
        # What waited on this round when it was planned (scheduler
        # thread, O(1) a round): backlog requests by the cause stamped
        # on their req_backlog span (obs/flight.py WAIT_CAUSES),
        # in-flight prefills the plan granted nothing, and the decode
        # rounds queued on the device when its first program went out.
        self.waiting_slot = 0
        self.waiting_pages = 0
        self.waiting_budget = 0
        self.prefill_ungranted = 0
        self.queued_ahead = 0
        self.decode_slots = 0
        self.spec_drafted = 0
        self.verify_positions = 0
        self.prefill_tokens = 0
        # Tokens of the compiled shapes this round's chunks ran in (each
        # grant padded to its bucket): prefill_tokens over this is how
        # full the round's chunk programs were.
        self.prefill_padded_tokens = 0
        self.grants: list[tuple[str, int]] = []
        self.pages_touched = 0
        self.hbm_bytes = 0
        # KV-tier H2D traffic: pages restored from host RAM ahead of
        # this round's chunk grants (engine/kv_tier.py) — their bytes
        # are folded into hbm_bytes; the count is kept separately so
        # the round record shows restore work explicitly.
        self.kv_restore_pages = 0
        # Requests kept out for want of pages this round (pool
        # backpressure): offered a chunk that _begin_prefill refused, or
        # held back from the plan since the pool last refused them.
        self.blocked_on_pages = 0
        # Pages of the live contexts this round's decode steps did not
        # read because they lie behind a window layer's window (whole
        # pages averaged over the layers, the unit of pages_touched;
        # from the plan, scheduler thread). 0 without window layers.
        self.kv_pages_skipped = 0.0
        # Learned sparse attention (from the plan, scheduler thread):
        # cached rows this round's decode steps' attention selected, a
        # layer (each row's min(context, index_topk), over rows and
        # steps); rows their indexer scored, a full layer (each row's
        # context); and the first as a share of the second, in percent:
        # what of a dense read the selection keeps. 0 without an indexer.
        self.kv_rows_selected = 0
        self.kv_rows_indexed = 0
        self.kv_selected_pct = 0.0
        # ... and the cached rows their attention STREAMED out of HBM, a
        # layer: over the decode kernel each live row's context rounded
        # up to whole blocks of the kernel's pages, gathered every
        # slot's whole window; and that over the rows selected: which
        # form ran, and how far a masked read is from a gathered one.
        self.kv_rows_read = 0
        self.kv_read_per_selected = 0.0
        # ``state_rows_idle_pct`` (from the plan, scheduler thread): of
        # the slots whose recurrent state the decode steps' kernel walks
        # (ops/ssd.py ``ssd_step_kernel``, ops/gated_delta.py
        # ``gated_delta_step_kernel``), the share, in percent, that held
        # no decoding sequence — rows the kernel neither fetched nor
        # wrote. NOT SET (no attribute) on every round of a model
        # without recurrent layers or whose step is XLA's, and on a
        # round that decoded nothing.
        self.dispatch_ms = 0.0
        self.modeled_ms = 0.0
        self.t_dispatch_done = self.t_start
        self.harvest_wait_ms = 0.0
        self.first_readback_ms = 0.0
        # Harvest-thread host time from readback done to the last token
        # of this round fed to its stream (the engine_emit span:
        # detokenize, stop check, stream feed).
        self.emit_ms = 0.0
        self.tokens_emitted = 0
        self.first_tokens = 0
        self.spec_accepted = 0
        # Mean distinct experts a layer's rows reached in a decode step
        # of this round: a scalar the decode program returns beside its
        # tokens (harvest thread). 0 without dropless experts.
        self.experts_touched = 0.0
        # Share, in percent, of a decode step's vocabulary tiles whose
        # candidate merge sorted the whole tile (ops/fused_sampler.py
        # _merge_tile), mean over the round's steps: a scalar a SAMPLED
        # round's program returns (harvest thread). 0 for a greedy one.
        self.tail_resort_pct = 0.0
        # Assignments a decode step's rows made to the experts THIS tree
        # holds (an expert share: models/configs.py ``experts_held``),
        # mean over the expert layers and the round's steps: a scalar
        # the decode program returns beside ``experts_touched``. 0
        # where the tree holds every expert.
        self.local_assignments = 0.0
        # What serving them walked: the padded rows a layer's dispatch
        # gather read for the held assignments (parallel/moe.py
        # ``route_sorted`` ``rows_read``: trips x a trip's rows), the
        # same mean (``route_rows_per_assignment`` is their ratio).
        self.route_rows_read = 0.0
        # Under a router limited to groups and an expert share: the
        # share (%) of the round's live rows whose kept groups include
        # one this tree holds experts of (parallel/moe.py), mean over
        # the expert layers and the round's steps. 0 elsewhere.
        self.route_groups_held_pct = 0.0
        # How far the rows of a layer's hyper-connection write-back
        # matrices were from summing to 1 (ops/hyper_connection.py
        # ``row_defect``), mean over the expert layers, their two
        # sublayers and the round's steps: a scalar the decode program
        # returns. ~1e-6 (``hc_eps``) where the coefficients are float32
        # and every normalisation ran; 0 on the plain residual path.
        self.hc_row_defect = 0.0
        # The harvest thread's stamp of the round's parts, in the
        # device's FIFO order: its decode output once emitted, then its
        # LAST chunk program's ``t_done`` (where a round that dispatched
        # both divides).
        self.t_parts: list[float] = []
        # One ProgramRun a dispatched device program, in launch order.
        self.programs: list[ProgramRun] = []
        # Stamp of the LAST part — the round's completion on the
        # program's own clock (t_start + round_ms says when the record
        # was finalized, which the scheduler thread may do later).
        self.t_done = 0.0
        self.device_ms = 0.0
        self.round_ms = 0.0
        self.bw_util = 0.0
        self.drift_ratio = 0.0
        self.done = False
        self._parts = 0
        self._done_parts = 0
        self._sealed = False
        self._cb: Optional[Callable[["RoundRecord"], None]] = None

    @property
    def route_rows_per_assignment(self) -> float:
        """Padded rows the dispatch gather read for each assignment that
        fell on a held expert: near the block height where every touched
        expert has few rows, larger by the walk's empty blocks. 0 where
        the tree holds every expert."""
        return (self.route_rows_read / self.local_assignments
                if self.local_assignments else 0.0)

    def to_dict(self) -> dict:
        """JSON-ready view for ``/debug/rounds`` and the slow-round
        dump."""
        return {
            "round_id": self.round_id,
            "engine": self.engine_tag,
            "started_unix_ms": int(self.wall_start * 1e3),
            "kind": self.kind,
            "done": self.done,
            "plan": {
                "budget_tokens": self.budget_tokens,
                "decode_steps": self.decode_steps,
                "decode_cost_tokens": self.decode_cost_tokens,
                "active_decodes": self.active_decodes,
                "plan_ms": round(self.plan_ms, 3),
                "pool_used_pages": self.pool_used_pages,
                "waiting_slot": self.waiting_slot,
                "waiting_pages": self.waiting_pages,
                "waiting_budget": self.waiting_budget,
                "prefill_ungranted": self.prefill_ungranted,
                "prefill_grants": [
                    {"request_id": rid, "tokens": n}
                    for rid, n in self.grants],
                "spec_draft_tokens": self.spec_drafted,
                "modeled_ms": round(self.modeled_ms, 3),
            },
            "execution": {
                "decode_slots": self.decode_slots,
                "prefill_tokens": self.prefill_tokens,
                "prefill_padded_tokens": self.prefill_padded_tokens,
                "dispatch_ms": round(self.dispatch_ms, 3),
                "queued_ahead": self.queued_ahead,
                "blocked_on_pages": self.blocked_on_pages,
                "harvest_wait_ms": round(self.harvest_wait_ms, 3),
                "first_readback_ms": round(self.first_readback_ms, 3),
                "emit_ms": round(self.emit_ms, 3),
                # the sum of the programs' service_ms
                "device_ms": round(self.device_ms, 3),
                "round_ms": round(self.round_ms, 3),
                "done_ms": (round((self.t_done - self.t_start) * 1e3, 3)
                            if self.t_done else None),
                "programs": [p.to_dict(self.t_start)
                             for p in self.programs],
            },
            "outcome": {
                "tokens_emitted": self.tokens_emitted,
                "first_tokens": self.first_tokens,
                "spec_accepted": self.spec_accepted,
                "pages_touched": self.pages_touched,
                "kv_pages_skipped": round(self.kv_pages_skipped, 2),
                "kv_rows_selected": self.kv_rows_selected,
                "kv_rows_indexed": self.kv_rows_indexed,
                "kv_selected_pct": round(self.kv_selected_pct, 2),
                "kv_rows_read": self.kv_rows_read,
                "kv_read_per_selected": round(self.kv_read_per_selected, 2),
                "state_rows_idle_pct": getattr(
                    self, "state_rows_idle_pct", None),
                "experts_touched": round(self.experts_touched, 2),
                "tail_resort_pct": round(self.tail_resort_pct, 2),
                "local_assignments": round(self.local_assignments, 2),
                "route_rows_per_assignment": round(
                    self.route_rows_per_assignment, 2),
                "hc_row_defect": self.hc_row_defect,
                "route_groups_held_pct": round(
                    self.route_groups_held_pct, 2),
                "kv_restore_pages": self.kv_restore_pages,
                "hbm_bytes_est": self.hbm_bytes,
                "bw_util": round(self.bw_util, 4),
                "drift_ratio": round(self.drift_ratio, 3),
            },
        }


class RoundRecorder:
    """Bounded ring of :class:`RoundRecord`, append-side lock-free for
    the engine's hot threads (the lock guards ring mutation and the
    pipelined-completion clock only; both are once-per-round)."""

    def __init__(self, cap: Optional[int] = None):
        self._cap = (cap if cap is not None
                     else int(os.environ.get("ROUND_RING_CAP", "512")))
        self._lock = threading.Lock()
        self._ring: "deque[RoundRecord]" = deque(maxlen=max(1, self._cap))
        # Monotone across reset(): a restarted engine's rounds continue
        # the sequence, so dashboards and tests can detect a reset as a
        # gap, never as a replayed id.
        self._ids = itertools.count()
        # The program clock PER ENGINE TAG: the ``t_done`` of an
        # engine's latest programs, newest last (a program's service
        # starts at the previous one's; a stalled launch counts those
        # that fell inside it). Multi-engine processes (fleet bench,
        # capacity sweeps) share this recorder, and engine A's
        # completion must not truncate engine B's device-time estimate
        # — that estimate feeds B's cost calibrator. Bounded: a launch
        # can see no more completions than the device queue held.
        self._program_done_t: "dict[str, deque[float]]" = {}

    # --------------------------------------------------- scheduler side

    def begin(self, *, engine_tag: str = "", budget_tokens: int = 0,
              decode_steps: int = 0, decode_cost_tokens: int = 0,
              active_decodes: int = 0, kind: str = "decode",
              plan_ms: float = 0.0, pool_used_pages: int = 0,
              on_complete: Optional[Callable[[RoundRecord], None]] = None
              ) -> RoundRecord:
        """Open this round's record (scheduler thread). The record is
        visible in ``/debug/rounds`` immediately, flagged not-done."""
        rec = RoundRecord(next(self._ids), engine_tag)
        rec.kind = kind
        rec.budget_tokens = int(budget_tokens)
        rec.decode_steps = int(decode_steps)
        rec.decode_cost_tokens = int(decode_cost_tokens)
        rec.active_decodes = int(active_decodes)
        rec.plan_ms = float(plan_ms)
        rec.pool_used_pages = int(pool_used_pages)
        rec._cb = on_complete
        with self._lock:
            self._ring.append(rec)
        return rec

    def discard(self, rec: RoundRecord) -> None:
        """Drop a record whose round dispatched nothing (the plan had
        work but every dispatch declined). Ids stay monotone — a gap is
        cheaper than a lie."""
        with self._lock:
            try:
                self._ring.remove(rec)
            except ValueError:
                pass  # already rotated out of the bounded ring

    def launch(self, rec: Optional[RoundRecord], name: str, *,
               tokens: int, padded: int, rows: int, steps: int = 0,
               window: int = 0, t_launch0: float) -> Optional[ProgramRun]:
        """One device program of this round is being launched (scheduler
        thread, inside the span around its jitted call: ``t_launch0`` is
        that span's begin, and the caller writes ``t_launch1`` when it
        has closed). Each launched program is one of the round's parts:
        hand its run to ``complete_part`` / ``first_token`` with its
        readback."""
        if rec is None:
            return None
        run = ProgramRun(name, int(tokens), int(padded), int(rows),
                         int(steps), int(window), t_launch0)
        rec.programs.append(run)
        return run

    def seal(self, rec: RoundRecord, *, parts: int,
             prefill_tokens: int = 0,
             prefill_padded_tokens: int = 0,
             grants: Optional[list] = None,
             modeled_ms: float = 0.0) -> None:
        """Close the dispatch half (scheduler thread): ``parts`` is how
        many harvest-side completion signals this round will produce
        (one a launched program: the decode/verify output, each chunk
        program's marker or first token). Finalizes immediately if the
        harvest thread already drained every part (it can outrun the
        scheduler on short rounds)."""
        rec.prefill_tokens = int(prefill_tokens)
        rec.prefill_padded_tokens = int(prefill_padded_tokens)
        if grants:
            rec.grants = list(grants)
        rec.modeled_ms = float(modeled_ms)
        rec.t_dispatch_done = time.monotonic()
        rec.dispatch_ms = (rec.t_dispatch_done - rec.t_start) * 1e3
        finalize = False
        with self._lock:
            rec._parts = int(parts)
            rec._sealed = True
            finalize = rec._done_parts >= rec._parts
        if finalize:
            self._finalize(rec)

    # ----------------------------------------------------- harvest side

    def complete_part(self, rec: Optional[RoundRecord], *,
                      tokens: int = 0, spec_accepted: int = 0,
                      harvest_wait_ms: float = 0.0,
                      emit_ms: float = 0.0,
                      experts_touched: float = 0.0,
                      tail_resort_pct: float = 0.0,
                      local_assignments: float = 0.0,
                      hc_row_defect: float = 0.0,
                      route_groups_held_pct: float = 0.0,
                      route_rows_read: float = 0.0,
                      program: Optional[ProgramRun] = None,
                      t_done: float = 0.0) -> None:
        """One harvested device output of this round (harvest thread):
        ``program`` the run it completes, ``t_done`` the instant its
        readback returned. The last part — once the scheduler has sealed
        the expected count — finalizes the record."""
        if rec is None:
            return
        rec.tokens_emitted += int(tokens)
        rec.spec_accepted += int(spec_accepted)
        rec.harvest_wait_ms += float(harvest_wait_ms)
        rec.emit_ms += float(emit_ms)
        if experts_touched:
            rec.experts_touched = float(experts_touched)
        if tail_resort_pct:
            rec.tail_resort_pct = float(tail_resort_pct)
        if local_assignments:
            rec.local_assignments = float(local_assignments)
        if hc_row_defect:
            rec.hc_row_defect = float(hc_row_defect)
        if route_groups_held_pct:
            rec.route_groups_held_pct = float(route_groups_held_pct)
        if route_rows_read:
            rec.route_rows_read = float(route_rows_read)
        if program is None or program.steps:
            # the decode output's part, stamped once emitted; the
            # chunks' part is their last program's t_done (_finalize)
            rec.t_parts.append(time.monotonic())
        self._part_done(rec, program, t_done)

    def first_token(self, rec: Optional[RoundRecord], *,
                    wait_ms: float = 0.0, counted: bool = True,
                    emit_ms: float = 0.0,
                    program: Optional[ProgramRun] = None,
                    t_done: float = 0.0) -> None:
        """A first-token readback attributed to the round that armed the
        request (harvest thread): the completion of the final chunk
        ``program`` that computed it, one of the round's parts."""
        if rec is None:
            return
        rec.first_readback_ms += float(wait_ms)
        rec.emit_ms += float(emit_ms)
        if counted:
            rec.first_tokens += 1
        if program is not None:
            self._part_done(rec, program, t_done)

    def _part_done(self, rec: RoundRecord, program: Optional[ProgramRun],
                   t_done: float) -> None:
        """Count one part; stamp its program on the engine's program
        clock (harvest thread, the device's FIFO order)."""
        finalize = False
        with self._lock:
            if program is not None:
                clock = self._program_done_t.get(rec.engine_tag)
                if clock is None:
                    clock = self._program_done_t[rec.engine_tag] = deque(
                        maxlen=_DONE_STAMPS)
                program.t_prev_done = clock[-1] if clock else 0.0
                program.t_done = t_done or time.monotonic()
                clock.append(program.t_done)
            rec._done_parts += 1
            finalize = rec._sealed and rec._done_parts >= rec._parts
        if finalize:
            self._finalize(rec)

    def _finalize(self, rec: RoundRecord) -> None:
        now = time.monotonic()
        runs = rec.programs
        last_chunk = next((p for p in reversed(runs) if not p.steps), None)
        if last_chunk is not None:
            rec.t_parts.append(last_chunk.t_done)
        rec.t_done = rec.t_parts[-1] if rec.t_parts else now
        rec.round_ms = (now - rec.t_start) * 1e3
        rec.device_ms = sum(p.service_ms for p in runs)
        if runs:
            # the round's longest launch: did the device go on
            # completing programs while the host was inside it?
            slow = max(runs, key=lambda p: p.launch_ms)
            with self._lock:
                slow.done_during_launch = sum(
                    1 for t in self._program_done_t.get(rec.engine_tag, ())
                    if slow.t_launch0 <= t <= slow.t_launch1
                    and t < slow.t_done)
        cb = rec._cb
        rec._cb = None
        if cb is not None:
            try:
                cb(rec)
            except Exception:  # noqa: BLE001 — observability never raises
                logger.debug("round completion callback failed",
                             exc_info=True)
        rec.done = True  # LAST write: a done record is fully written

    # --------------------------------------------------------- queries

    def reset(self) -> None:
        """Drop retained records; round ids keep counting (monotone
        across reset — pinned by the thread-safety test)."""
        with self._lock:
            self._ring.clear()
            self._program_done_t.clear()

    def records(self) -> list[RoundRecord]:
        with self._lock:
            return list(self._ring)

    def snapshot(self, limit: int = 50,
                 engine_tag: Optional[str] = None) -> dict:
        """JSON-ready view for ``GET /debug/rounds``: the ``limit`` most
        recent records plus rolling aggregates over every COMPLETED
        record still in the ring (the aggregation window is therefore
        the ring capacity, ``ROUND_RING_CAP``). ``engine_tag`` restricts
        both to one engine's rounds — multi-engine processes share this
        recorder, and an aggregate mixing two engines' geometries
        answers no question honestly (the bench's per-engine block
        filters here)."""
        recs = self.records()
        if engine_tag is not None:
            recs = [r for r in recs if r.engine_tag == engine_tag]
        complete = [r for r in recs if r.done]
        agg: dict[str, Any] = {"rounds_completed": len(complete)}
        if complete:
            n = len(complete)
            toks = sum(r.tokens_emitted + r.first_tokens for r in complete)
            prefill = sum(r.prefill_tokens for r in complete)
            wall_s = sum(r.round_ms for r in complete) / 1e3
            device_s = sum(r.device_ms for r in complete) / 1e3
            inter = sum(1 for r in complete
                        if r.decode_slots and r.prefill_tokens)
            by_ms = sorted(r.device_ms for r in complete)
            agg.update({
                "window_start_unix_ms": int(complete[0].wall_start * 1e3),
                "tokens_emitted": toks,
                "prefill_tokens": prefill,
                "avg_round_ms": round(1e3 * wall_s / n, 3),
                "avg_device_ms": round(1e3 * device_s / n, 3),
                "p50_device_ms": round(by_ms[n // 2], 3),
                "tokens_per_sec": (round(toks / device_s, 1)
                                   if device_s > 0 else 0.0),
                "interleaved_share": round(inter / n, 4),
                "avg_bw_util": round(
                    sum(r.bw_util for r in complete) / n, 4),
                "hbm_bytes_est": sum(r.hbm_bytes for r in complete),
                "avg_drift_ratio": round(
                    sum(r.drift_ratio for r in complete) / n, 3),
                "spec_drafted": sum(r.spec_drafted for r in complete),
                "spec_accepted": sum(r.spec_accepted for r in complete),
            })
            by_name: dict[str, list] = {}
            for r in complete:
                for p in r.programs:
                    by_name.setdefault(p.name, []).append(p.service_ms)
            # a line a program name: count, p50, p90 of service_ms
            agg["programs"] = {
                name: {"count": len(ms),
                       "p50_service_ms": round(ms[len(ms) // 2], 3),
                       "p90_service_ms": round(ms[len(ms) * 9 // 10], 3)}
                for name, ms in ((k, sorted(v))
                                 for k, v in sorted(by_name.items()))}
        limit = max(0, int(limit))
        recent = recs[-limit:] if limit else []
        return {
            "rounds": [r.to_dict() for r in reversed(recent)],
            "aggregates": agg,
            "ring_cap": self._cap,
            "retained": len(recs),
        }


def record_round_metrics(rec: RoundRecord,
                         drift_ewma: Optional[float] = None) -> None:
    """Mirror one completed round into the declared ``ROUND_METRICS``
    surface (called from the engine's completion callback — once per
    round, off the scheduler thread)."""
    _round_metric("engine_rounds_total").inc()
    _round_metric("engine_round_seconds").observe(rec.round_ms / 1e3)
    _round_metric("engine_round_device_seconds").observe(
        rec.device_ms / 1e3)
    for p in rec.programs:
        child = _program_children.get(p.name)
        if child is None:   # benign race, as _round_metric's
            child = _program_children[p.name] = _round_metric(
                "engine_program_seconds").labels(p.name)
        child.observe(p.service_ms / 1e3)
    _round_metric("engine_round_tokens").observe(
        rec.tokens_emitted + rec.first_tokens + rec.prefill_tokens)
    _round_metric("engine_round_bw_util").set(rec.bw_util)
    if rec.hbm_bytes:
        _round_metric("engine_round_hbm_bytes_total").inc(rec.hbm_bytes)
    if drift_ewma is not None:
        _round_metric("sched_cost_drift_ratio").set(drift_ewma)


def count_slow_dump() -> None:
    _round_metric("engine_round_slow_dumps_total").inc()


def emit_round_span(rec: RoundRecord) -> None:
    """Retrospective OTel span for one completed round (explicit
    timestamps — the serve loop never touches the SDK). No-op when
    tracing is off."""
    from . import tracing
    if not tracing.enabled():
        return
    try:
        tracer = tracing._get_tracer()
        if tracer is None:
            return
        start_ns = int(rec.wall_start * 1e9)
        end_ns = int((rec.wall_start + rec.round_ms / 1e3) * 1e9)
        span = tracer.start_span(
            "engine_round", start_time=start_ns,
            attributes={
                "round.id": rec.round_id,
                "round.kind": rec.kind,
                "round.engine": rec.engine_tag,
                "round.decode_steps": rec.decode_steps,
                "round.prefill_tokens": rec.prefill_tokens,
                "round.tokens_emitted": rec.tokens_emitted,
                "round.device_ms": round(rec.device_ms, 3),
                "round.drift_ratio": round(rec.drift_ratio, 3),
            })
        span.end(end_time=end_ns)
    except Exception:  # noqa: BLE001 — observability must never raise
        logger.debug("round span emit failed", exc_info=True)


# Process-wide default recorder: the engine(s) and both HTTP servers
# share this instance unless handed a private one (tests install their
# own via Engine.rounds). Multi-engine processes (the fleet bench)
# interleave here — records carry engine_tag to tell them apart.
RECORDER = RoundRecorder()


def debug_rounds_response(request,
                          recorder: Optional[RoundRecorder] = None):
    """The ``GET /debug/rounds`` aiohttp handler body, shared by the
    chain server and the model server so the endpoint contract
    (``limit``/``engine`` parsing, error shape, snapshot schema) cannot
    drift between them. ``?engine=<tag>`` scopes records and aggregates
    to one engine in multi-engine processes."""
    from aiohttp import web

    from .history import query_int
    limit = query_int(request, "limit", 50, minimum=0)
    engine_tag = request.query.get("engine") or None
    return web.json_response((recorder or RECORDER).snapshot(
        limit=limit, engine_tag=engine_tag))
