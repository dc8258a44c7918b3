"""Token-budget continuous scheduler: chunked-prefill / decode interleaving.

The prefill wall: prefill IS the TTFT budget, and the engine's former
run-prefill-to-completion admission let one long prompt monopolize the
serve loop while every occupied decode slot starved. The
cure is the Sarathi/Orca recipe adapted to this engine's multi-step
rounds: plan each engine round as a MIX of decode steps for armed slots
plus prefill *chunks* for admitted requests, sized so the whole round
stays under a per-round token budget derived from a measured step-cost
model — decode keeps flowing at its usual cadence while long prefills
progress in the gaps, a whole chunk-program shape at a time (a grant is
priced and sized in the shapes the engine's chunk programs are compiled
in, so no program runs mostly padding).

Division of labor:

- **This module is pure host-side policy** — no jax, no device state, no
  engine internals. It converts (decode work this round, prefill jobs
  waiting) into a :class:`RoundPlan` under the budget, and orders
  admission by DEADLINE SLACK (requests whose deadline minus estimated
  prefill time is smallest go first; ties by arrival). That keeps every
  decision unit-testable without an engine.
- **The engine** (engine.py ``_plan_round``/``_execute_plan``) owns
  resources: it offers only what slots/pages allow, executes chunk
  dispatches, and keeps the PR-5 deadline semantics (queue-expired
  requests shed via ``deadline_queue`` before any page is touched).

Cost model: :class:`StepCostModel` loads the committed
``PROFILE_rNN.json`` roofline artifact (``tools/profile_decode.py
--json`` regenerates it per deployment, now including a measured
``prefill_ms_per_token``) and falls back to conservative defaults when
the artifact or a field is missing. The derived default budget is the
number of prefill tokens whose modeled cost equals ONE decode round —
i.e. piggybacked prefill can at most ~double a round's latency, the
stall-free-batching knee. ``SCHED_ROUND_BUDGET_TOKENS`` /
``SCHED_PREFILL_CHUNK_TOKENS`` (env or EngineConfig) override it per
deployment (docs/configuration.md).
"""

from __future__ import annotations

import bisect
import glob
import json
import logging
import math
import os
import re
import threading
from dataclasses import dataclass, field, replace
from typing import Optional, Sequence

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def topology_key(mesh_shape: Optional[dict] = None) -> str:
    """Canonical topology label for cost-model rows: the mesh's
    non-trivial axes as ``axis=N`` pairs, sorted (``"tp=2"``,
    ``"sp=2,tp=2"``); a single chip — or a mesh of all-1 axes — is
    ``"tp=1"``. The same function labels ``tools/profile_decode.py
    --mesh`` artifacts and keys the engine's prior lookup, so the two
    can never drift apart. Takes a plain ``{axis: size}`` dict (this
    module stays jax-free): engines pass ``dict(mesh.shape)``."""
    if not mesh_shape:
        return "tp=1"
    parts = [f"{a}={int(s)}" for a, s in sorted(mesh_shape.items())
             if int(s) > 1]
    return ",".join(parts) if parts else "tp=1"


@dataclass(frozen=True)
class StepCostModel:
    """Per-deployment serving costs, in milliseconds.

    ``decode_step_ms`` is one fused decode step across ALL slots (the
    profile's ``full_ms_per_step``); ``prefill_ms_per_token`` is one
    prompt token through prefill. The ratio between them is what the
    budget derivation actually consumes: how many prefill tokens cost as
    much as a decode round.
    """

    decode_step_ms: float = 2.0
    prefill_ms_per_token: float = 0.125
    # One speculative-verification position (one token of a K+1-token
    # verify forward at decode occupancy, tools/profile_decode.py
    # ``verify_ms_per_token``). 0 = unmeasured: verification is then
    # priced 1:1 with prefill tokens (same forward math, the honest
    # default until the artifact carries the measurement).
    verify_ms_per_token: float = 0.0
    # KV-tier page migration (engine/kv_tier.py): milliseconds to move
    # one KV page host->device (restore) / device->host (offload).
    # 0 = unmeasured — the restore-vs-recompute decision then assumes
    # restore wins (on every real interconnect a page upload is far
    # cheaper than recomputing a page of prefill) until the online
    # calibrator has measured actual transfers.
    h2d_ms_per_page: float = 0.0
    d2h_ms_per_page: float = 0.0
    slots: int = 8
    source: str = "default"
    # The mesh shape these costs were measured at (``topology_key``
    # label; artifacts without one are single-chip measurements). A
    # tp-sharded engine must plan its FIRST rounds from the matching
    # row — a 2-chip decode step costs neither one chip's step nor
    # half of it, and the online calibrator only fixes the prior after
    # real traffic has already been (mis-)budgeted.
    topology: str = "tp=1"

    @classmethod
    def from_profile(cls, profile: dict, source: str = "profile",
                     topology: Optional[str] = None) -> "StepCostModel":
        decode = float(profile.get("full_ms_per_step") or 2.0)
        slots = int(profile.get("slots") or 8)
        prefill = profile.get("prefill_ms_per_token")
        if not prefill or prefill <= 0:
            # Older artifacts (≤ r06) predate the prefill measurement:
            # estimate a token's prefill cost from the decode step —
            # per-slot decode cost discounted by prefill's batching
            # efficiency (a whole bucket amortizes weight streaming the
            # way a decode step amortizes it over slots; 4x is the
            # conservative end of the measured 3-8x range).
            prefill = decode / max(1, slots) / 4.0
        verify = profile.get("verify_ms_per_token") or 0.0
        h2d = profile.get("h2d_ms_per_page") or 0.0
        d2h = profile.get("d2h_ms_per_page") or 0.0
        return cls(decode_step_ms=decode,
                   prefill_ms_per_token=float(prefill),
                   verify_ms_per_token=float(verify),
                   h2d_ms_per_page=float(h2d),
                   d2h_ms_per_page=float(d2h),
                   slots=slots, source=source,
                   topology=str(topology or profile.get("topology")
                                or "tp=1"))

    @classmethod
    def _from_artifact(cls, profile: dict, topology: Optional[str],
                       source: str) -> Optional["StepCostModel"]:
        """One artifact's topology-matched model, or None when it has no
        row for the requested topology. Artifacts carry their own
        ``topology`` label (absent == single-chip ``tp=1``) and may
        carry a ``topologies`` dict of per-mesh rows (each row's keys
        override the artifact's shared fields) — one sweep run can
        serve every rung."""
        own = str(profile.get("topology") or "tp=1")
        if topology is None or topology == own:
            return cls.from_profile(profile, source=source, topology=own)
        rows = profile.get("topologies")
        if isinstance(rows, dict) and isinstance(rows.get(topology),
                                                 dict):
            merged = {k: v for k, v in profile.items()
                      if k != "topologies"}
            merged.update(rows[topology])
            return cls.from_profile(merged,
                                    source=f"{source}@{topology}",
                                    topology=topology)
        return None

    @classmethod
    def load(cls, path: Optional[str] = None,
             topology: Optional[str] = None,
             platform: Optional[str] = None) -> "StepCostModel":
        """Resolve the deployment's cost model: explicit ``path``, else
        ``SCHED_PROFILE_JSON``, else the newest committed
        ``PROFILE_rNN.json`` at the repo root, else defaults. A missing
        or malformed artifact is skipped with a WARNING — the scheduler
        must never keep an engine from building, but it says so.

        ``platform``: the engine's device platform (``"tpu"``,
        ``"cpu"``). An artifact that records a different ``platform``
        was timed on another kind of device and is skipped: a rate
        measured elsewhere is not a prior, and the built-in defaults
        plus the online calibrator start closer than it does.

        ``topology``: the engine's mesh label (:func:`topology_key`).
        Precedence per docs/scheduler.md: an artifact whose own label or
        ``topologies`` row matches wins; with NO matching row anywhere,
        the newest parseable artifact is used as-is (its ``topology``
        field then records the mismatch) — a wrong-but-measured prior
        beats built-in defaults, and the online calibrator converges it."""
        candidates = []
        if path:
            candidates.append(path)
        env = os.environ.get("SCHED_PROFILE_JSON", "")
        if env:
            candidates.append(env)
        def _round_no(p: str) -> int:
            m = re.search(r"_r(\d+)\.json$", p)
            return int(m.group(1)) if m else -1
        # Numeric sort on the round number — lexicographic would pick
        # r99 over r100 (and r9 over r10) the day rounds grow a digit.
        candidates.extend(sorted(
            glob.glob(os.path.join(_REPO_ROOT, "PROFILE_r*.json")),
            key=_round_no, reverse=True))
        fallback: Optional["StepCostModel"] = None
        for cand in candidates:
            # Catch the full malformed-artifact surface, not just parse
            # errors: valid JSON that isn't an object of numbers (`[]`,
            # `{"prefill_ms_per_token": "fast"}`) raises Attribute/Type
            # errors out of from_profile — the fallback contract above
            # covers those the same as a missing file.
            try:
                with open(cand) as f:
                    profile = json.load(f)
                measured_on = profile.get("platform")
                if platform and measured_on and measured_on != platform:
                    logger.info(
                        "cost artifact %s skipped: measured on %s, "
                        "engine runs on %s", cand, measured_on, platform)
                    continue
                model = cls._from_artifact(profile, topology,
                                           os.path.basename(cand))
                if model is not None:
                    return model
                if fallback is None:
                    fallback = cls.from_profile(
                        profile, source=os.path.basename(cand))
            except (OSError, ValueError, TypeError, AttributeError,
                    KeyError) as exc:
                logger.warning("cost artifact %s unusable (%s: %s)",
                               cand, type(exc).__name__, exc)
                continue
        return fallback if fallback is not None else cls()

    def prefill_s(self, tokens: int) -> float:
        """Modeled wall seconds to prefill ``tokens`` prompt tokens."""
        return max(0, tokens) * self.prefill_ms_per_token / 1e3

    def decode_round_ms(self, steps: int) -> float:
        return steps * self.decode_step_ms

    def verify_cost_tokens(self, positions: int) -> int:
        """Price a speculative verify round against the token budget:
        ``positions`` scored positions (slots x S), converted to
        prefill-token units through the measured per-token costs. With
        no verify measurement the ratio is 1 — a verified position and
        a prefill token run the same multi-token forward math, so 1:1
        is the honest default rather than an optimistic discount."""
        if positions <= 0:
            return 0
        if self.verify_ms_per_token <= 0 or self.prefill_ms_per_token <= 0:
            return positions
        return max(1, math.ceil(
            positions * self.verify_ms_per_token
            / self.prefill_ms_per_token))

    def restore_ms(self, pages: int) -> float:
        """Modeled wall ms to restore ``pages`` KV pages host->device."""
        return max(0, pages) * self.h2d_ms_per_page

    def restore_cheaper(self, pages: int, page_size: int) -> bool:
        """The KV-tier admission decision: is restoring ``pages`` pages
        from host RAM priced cheaper than recomputing their tokens
        through prefill? Unmeasured H2D (0) answers True — restore is
        assumed to win until the online calibrator has real transfer
        measurements; once it does, the comparison is honest per
        deployment (engine counts the refusals as
        ``kv_restore_skipped_cost``)."""
        if pages <= 0:
            return False
        if self.h2d_ms_per_page <= 0:
            return True
        return self.restore_ms(pages) \
            < pages * page_size * self.prefill_ms_per_token

    def handoff_cheaper(self, pages: int, page_size: int) -> bool:
        """The disaggregation pricing rule: is shipping ``pages``
        finished prefix pages donor-device → host → wire → host →
        decode-device priced cheaper than the decode replica recomputing
        their tokens through prefill? The handoff pays BOTH transfer
        legs (``d2h`` on the donor, ``h2d`` on the receiver); unmeasured
        legs (0) answer True, mirroring :meth:`restore_cheaper` — the
        handoff is assumed to win until the calibrator has real
        transfer measurements."""
        if pages <= 0:
            return False
        per_page = self.d2h_ms_per_page + self.h2d_ms_per_page
        if per_page <= 0:
            return True
        return pages * per_page \
            < pages * page_size * self.prefill_ms_per_token


def derive_round_budget(model: StepCostModel, steps_per_round: int,
                        page_size: int) -> int:
    """Default per-round prefill-token budget: the tokens whose modeled
    prefill cost equals one full decode round. At that size a round that
    piggybacks a chunk takes at most ~2x a pure decode round — decoding
    streams keep flowing while prefill makes real progress. Quantized to
    whole pages (chunks scatter KV page-wise); floored at one page so a
    pathological cost model can never stall admission."""
    tokens = model.decode_round_ms(steps_per_round) / model.prefill_ms_per_token
    pages = max(1, int(tokens) // page_size)
    return pages * page_size


def online_calib_enabled(default: bool = True) -> bool:
    """``SCHED_ONLINE_CALIB`` gate for the online cost calibrator:
    ``0``/``false`` pins the static (artifact/env/default) model; any
    other value — and the unset default — enables calibration."""
    raw = os.environ.get("SCHED_ONLINE_CALIB", "").strip().lower()
    if not raw:
        return default
    return raw not in ("0", "false", "no", "off")


class OnlineCalibrator:
    """EWMA calibration of :class:`StepCostModel` from measured rounds.

    The committed ``PROFILE_rNN.json`` prior is a point measurement from
    whatever machine ran the profiler — the ROADMAP repeatedly flags the
    CPU-labeled artifacts as "regenerate on chip". This class closes the
    loop instead: the engine feeds it each completed round's *measured*
    per-token costs (round telemetry, ``obs/rounds.py``), it keeps an
    exponentially weighted moving average per cost component, and
    :meth:`current` returns the model the scheduler should plan with —
    the PRIOR blended toward the EWMA on a linear ramp
    (``weight = min(1, n / warmup)``): the first observations only
    nudge the model, and after ``warmup`` samples the measurement is
    fully trusted (the EWMA itself keeps absorbing noise) — a badly
    wrong artifact prior is fully displaced within a handful of rounds
    instead of lingering as a 1/n tail.

    Only *pure* rounds are attributable: a decode-only round measures
    ``decode_step_ms``, a prefill-only round ``prefill_ms_per_token``, a
    verify-only round ``verify_ms_per_token``. Mixed rounds are skipped
    (their time cannot be split honestly) — under real traffic pure
    rounds of every kind occur constantly, so the calibrator still sees
    a steady diet.

    Thread contract: ``observe_*`` run on the engine's harvest thread,
    ``current``/``drift`` on the scheduler thread (and scrapes); a small
    lock keeps each update atomic and the cached blended model
    consistent.
    """

    def __init__(self, prior: StepCostModel, *, alpha: float = 0.25,
                 warmup: int = 4):
        self.prior = prior
        self.alpha = float(alpha)
        self.warmup = max(1, int(warmup))
        self._lock = threading.Lock()
        self._ewma: dict[str, float] = {}
        self._n: dict[str, int] = {}
        self._cached: StepCostModel = prior
        self._dirty = False
        self.version = 0    # bumps per observation; recalibrate() keys off it

    def _observe(self, key: str, value: float) -> None:
        if value <= 0 or not math.isfinite(value):
            return
        with self._lock:
            prev = self._ewma.get(key)
            self._ewma[key] = (value if prev is None
                               else prev + self.alpha * (value - prev))
            self._n[key] = self._n.get(key, 0) + 1
            self._dirty = True
            self.version += 1

    def observe_decode(self, steps: int, device_ms: float) -> None:
        """A pure decode round of ``steps`` fused steps took
        ``device_ms`` of device time."""
        if steps > 0:
            self._observe("decode_step_ms", device_ms / steps)

    def observe_prefill(self, tokens: int, device_ms: float) -> None:
        """A prefill-only round computed ``tokens`` prompt tokens."""
        if tokens > 0:
            self._observe("prefill_ms_per_token", device_ms / tokens)

    def observe_verify(self, positions: int, device_ms: float) -> None:
        """A verify-only round scored ``positions`` slot-positions."""
        if positions > 0:
            self._observe("verify_ms_per_token", device_ms / positions)

    def observe_h2d(self, pages: int, wall_ms: float) -> None:
        """A KV-tier restore uploaded ``pages`` pages host->device
        (engine-measured dispatch wall — the restore pricing input)."""
        if pages > 0:
            self._observe("h2d_ms_per_page", wall_ms / pages)

    def observe_d2h(self, pages: int, wall_ms: float) -> None:
        """A KV-tier offload read ``pages`` pages back device->host
        (harvest-measured readback wait)."""
        if pages > 0:
            self._observe("d2h_ms_per_page", wall_ms / pages)

    def _blend(self, key: str, prior_value: float) -> float:
        ewma = self._ewma.get(key)
        if ewma is None:
            return prior_value
        w = min(1.0, self._n.get(key, 0) / self.warmup)
        return (1.0 - w) * prior_value + w * ewma

    def samples(self, key: str) -> int:
        with self._lock:
            return self._n.get(key, 0)

    def current(self) -> StepCostModel:
        """The blended model (cached; rebuilt only after new
        observations). Falls back to the prior field-by-field until a
        component has evidence."""
        with self._lock:
            if not self._dirty:
                return self._cached
            self._cached = replace(
                self.prior,
                decode_step_ms=self._blend("decode_step_ms",
                                           self.prior.decode_step_ms),
                prefill_ms_per_token=self._blend(
                    "prefill_ms_per_token",
                    self.prior.prefill_ms_per_token),
                verify_ms_per_token=self._blend(
                    "verify_ms_per_token",
                    self.prior.verify_ms_per_token),
                h2d_ms_per_page=self._blend(
                    "h2d_ms_per_page", self.prior.h2d_ms_per_page),
                d2h_ms_per_page=self._blend(
                    "d2h_ms_per_page", self.prior.d2h_ms_per_page),
                source=self.prior.source + "+online")
            self._dirty = False
            return self._cached


@dataclass
class PrefillJob:
    """One prefill the scheduler may advance this round.

    ``key`` is an opaque handle (the engine's ``_Request``) echoed back
    in the plan. ``remaining`` counts tokens still to COMPUTE: the
    prompt minus everything already prefilled minus any prefix-cache hit
    — a warm request's chunk plan shrinks by exactly its cached prefix
    (the PR-1 interaction; see docs/scheduler.md)."""

    key: object
    remaining: int
    deadline_t: Optional[float] = None
    seq: int = 0
    started: bool = False    # already holds a slot (in-flight chunks)


@dataclass
class RoundPlan:
    """One engine round: the decode dispatch (steps and how many armed
    slots ride it) plus the prefill chunks that fit under the budget."""

    decode_steps: int
    active_decodes: int
    chunks: list = field(default_factory=list)  # [(key, grant_tokens)]
    budget_tokens: int = 0
    # Explicit decode-work price for rounds whose cost is NOT steps x
    # slots — a speculative verify round scores S positions per slot in
    # one step (engine passes StepCostModel.verify_cost_tokens). None =
    # the classic normalization below.
    decode_cost_override: Optional[int] = None

    @property
    def decode_cost_tokens(self) -> int:
        if not self.decode_steps:
            return 0
        if self.decode_cost_override is not None:
            return self.decode_cost_override
        return self.decode_steps * max(1, self.active_decodes)

    @property
    def prefill_tokens(self) -> int:
        return sum(n for _, n in self.chunks)

    @property
    def interleaved(self) -> bool:
        return bool(self.decode_steps and self.chunks)


class TokenBudgetScheduler:
    """Plans rounds under a token budget; orders admission by slack.

    Token units: one prefill token costs 1; one decode step costs one
    token PER ACTIVE SLOT (each armed slot emits a token per step — the
    same normalization Sarathi/vLLM budgets use, and it makes the
    budget directly comparable to ``tokens_generated``).
    """

    def __init__(self, cost: StepCostModel, *, page_size: int,
                 steps_per_round: int,
                 round_budget_tokens: Optional[int] = None,
                 chunk_tokens: Optional[int] = None,
                 max_one_shot_tokens: Optional[int] = None,
                 chunk_shapes: Optional[Sequence[int]] = None,
                 calibrator: Optional[OnlineCalibrator] = None):
        self._static_cost = cost
        # Online calibration (``OnlineCalibrator``): when installed, the
        # scheduler plans with the measured-blended model instead of the
        # static artifact prior, and ``recalibrate()`` periodically
        # re-derives the round budget from it. Precedence (see
        # docs/scheduler.md): explicit env/config budget overrides are
        # PINNED — calibration then only refines slack estimates and
        # verify pricing, never the operator's chosen budget.
        self.calibrator = calibrator
        self._budget_pinned = round_budget_tokens is not None
        self._chunk_pinned = chunk_tokens is not None
        self.page_size = page_size
        self.steps_per_round = steps_per_round
        if round_budget_tokens is not None:
            budget = max(page_size, int(round_budget_tokens))
        else:
            budget = derive_round_budget(cost, steps_per_round, page_size)
        self.round_budget_tokens = budget
        # The shapes the engine's chunk programs are compiled in (its
        # prefill-bucket ladder, the sizes a dispatched chunk is padded
        # to). None — a scheduler built without an engine — means every
        # page multiple is a shape.
        self._ladder = (tuple(sorted({int(s) for s in chunk_shapes}))
                        if chunk_shapes else None)
        # Above this, a request is never one-shot even on an idle engine:
        # the largest compiled shape, which is also the largest single
        # DISPATCH the engine can execute — a grant beyond it would
        # deduct budget for tokens _advance_prefill clamps away.
        if self._ladder is not None:
            max_one_shot_tokens = self._ladder[-1]
        self.max_one_shot_tokens = max_one_shot_tokens
        # Per-chunk cap: a single request's grant within one round.
        # Defaults to the whole budget (the budget is already the round
        # latency bound); the knob exists to force finer interleaving.
        self._set_chunk_cap(int(chunk_tokens) if chunk_tokens else budget)
        # Fair-rotation cursor: when the leftover is too small for every
        # job to get a shape (one bucket a round is the common case),
        # WHO gets this round's shape rotates across rounds so a waiting
        # job's next grant is bounded by ~len(jobs) rounds.
        self._rr = 0
        self._calib_version = -1   # last calibrator version recalibrated at

    def _set_chunk_cap(self, cap: int) -> None:
        """Fix ``chunk_tokens`` (one grant's cap, never past the largest
        dispatchable shape) and the grant shapes under it, ascending.
        With a ladder whose smallest shape is over the cap, that shape is
        the one grant size: the device runs (and the budget is charged)
        no smaller program, so a finer grant would only be padding."""
        page = self.page_size
        cap = max(page, cap)
        if self.max_one_shot_tokens is not None:
            cap = min(cap, max(page, self.max_one_shot_tokens))
        self.chunk_tokens = cap
        if self._ladder is None:
            self._shapes = tuple(range(page, cap + 1, page))
        else:
            self._shapes = (tuple(s for s in self._ladder if s <= cap)
                            or self._ladder[:1])
        # A job that fits ONE program (a short prompt, or a long
        # prompt's tail) may still be cut at the page multiples under
        # the smallest shape, as before whole-shape grants: see
        # shapes_of(). Nothing under a ladder that starts at one page.
        self._fine_shapes = tuple(
            range(page, self._shapes[0], page)) + self._shapes

    def shapes_of(self, remaining: int) -> tuple:
        """The sizes a job with ``remaining`` tokens left can be granted
        (its final grant apart). A job that needs more than one largest
        program is granted whole programs: the ladder's shapes. A job
        that fits one keeps the page multiples under the smallest
        shape too — NOT because they are good grants (such a grant
        runs padded to the smallest shape): the benchmark's warm-up
        (benchmarks/harness/system.py ``missing``) reaches the chunk
        programs of small windows only through page-sized shared
        grants of short prompts, and retries for seconds when it
        cannot (docs/scheduler.md, PERF.md section 7)."""
        return (self._shapes if remaining > self._shapes[-1]
                else self._fine_shapes)

    @staticmethod
    def _within(shapes: tuple, room: int) -> int:
        """Largest of ``shapes`` that ``room`` tokens of budget pay for
        (0 when even the smallest does not fit)."""
        i = bisect.bisect_right(shapes, room)
        return shapes[i - 1] if i else 0

    @staticmethod
    def _charge(shapes: tuple, tokens: int) -> int:
        """What a grant of ``tokens`` takes off the budget: the smallest
        of ``shapes`` that covers it (0 for no grant)."""
        if tokens <= 0:
            return 0
        i = bisect.bisect_left(shapes, tokens)
        return shapes[min(i, len(shapes) - 1)]

    @property
    def cost(self) -> StepCostModel:
        """The model rounds are planned with: the calibrator's blended
        model when online calibration is on, the static artifact/env
        model otherwise."""
        if self.calibrator is not None:
            return self.calibrator.current()
        return self._static_cost

    def recalibrate(self) -> bool:
        """Re-derive the round budget from the current (blended) cost
        model. Called from the engine's scheduler thread between rounds;
        cheap no-op unless the calibrator saw new evidence since the
        last call. Explicitly pinned budgets (env/config) never move.
        Returns True when the budget actually changed."""
        if self.calibrator is None or self._budget_pinned:
            return False
        version = self.calibrator.version
        if version == self._calib_version:
            return False
        self._calib_version = version
        budget = derive_round_budget(self.cost, self.steps_per_round,
                                     self.page_size)
        if budget == self.round_budget_tokens:
            return False
        self.round_budget_tokens = budget
        if not self._chunk_pinned:
            # The chunk cap follows the budget (its documented default).
            self._set_chunk_cap(budget)
        return True

    # ------------------------------------------------------------ slack

    def slack_s(self, job: PrefillJob, now: float) -> float:
        """Deadline slack: seconds to spare if this job's prefill
        started NOW — (deadline - now) minus its modeled prefill time.
        No deadline → +inf (deadline'd traffic goes first; among
        unconstrained requests arrival order holds)."""
        if job.deadline_t is None:
            return math.inf
        return (job.deadline_t - now) - self.cost.prefill_s(job.remaining)

    def order(self, jobs: Sequence[PrefillJob], now: float
              ) -> list[PrefillJob]:
        """Admission order: smallest slack first, arrival order as the
        tiebreak (and the total order for no-deadline traffic). The
        engine sheds queue-EXPIRED requests before offering jobs here
        (PR-5 ``deadline_queue``); negative-slack-but-unexpired jobs
        sort first — their only chance of meeting the deadline is
        starting immediately."""
        return sorted(jobs, key=lambda j: (self.slack_s(j, now), j.seq))

    # ------------------------------------------------------------- plan

    def plan_round(self, *, decode_steps: int, active_decodes: int,
                   inflight: Sequence[PrefillJob] = (),
                   backlog: Sequence[PrefillJob] = (),
                   now: float = 0.0,
                   max_new: Optional[int] = None,
                   decode_cost_tokens: Optional[int] = None) -> RoundPlan:
        """Pack one round: decode first (decode is NEVER displaced —
        stall-free batching means ongoing generations keep their
        cadence), then prefill chunks into the leftover budget.

        ``inflight`` jobs (mid-prefill, already holding a slot) advance
        before new admissions — arming a half-done slot frees budget
        sooner than starting another prompt. ``backlog`` jobs are
        admission candidates ordered by slack here; ``max_new`` caps how
        many of them (slack-order first) may be granted this round — the
        engine passes its free-slot count, so budget is never split
        across jobs that cannot start and then wasted when the executor
        runs out of slots. ``decode_cost_tokens`` overrides the classic
        steps x slots decode price for rounds whose work is shaped
        differently — a speculative verify round scores S positions per
        slot in one step (StepCostModel.verify_cost_tokens).

        Grants come in the chunk programs' own SHAPES (the engine's
        bucket ladder; every page multiple when the scheduler was built
        without one): a job's FINAL grant is whatever remains (the
        final-chunk program takes any tail), every other grant is
        exactly a shape, and the budget is charged the shape a grant
        runs in — what the device pays — not the tokens it computes
        (:meth:`shapes_of` has the one exception, for a job that fits
        one program).
        Two liveness guarantees: if prefill work exists, at least one
        smallest shape is granted even when decode consumed the whole
        budget (a saturated decode fleet must not starve admission
        forever, and a decoding batch never cuts a lone prefill under a
        whole program), and on an IDLE engine (nothing decoding, nothing
        else waiting) a lone job up to 2x the round budget (and never
        past ``max_one_shot_tokens``, the largest compiled bucket) is
        granted whole — chunking a typical prompt would tax its TTFT
        with extra dispatches while protecting nobody, but an UNBOUNDED
        one-shot is un-preemptible once dispatched and would re-open
        the prefill wall for a request arriving moments later.
        """
        plan = RoundPlan(decode_steps=decode_steps,
                         active_decodes=active_decodes,
                         budget_tokens=self.round_budget_tokens,
                         decode_cost_override=decode_cost_tokens)
        admitted = self.order(backlog, now)
        if max_new is not None:
            admitted = admitted[:max(0, max_new)]
        jobs = list(inflight) + admitted
        if not jobs:
            return plan
        floor = self._shapes[0]
        # Liveness floor: decode saturation may never starve prefill,
        # nor shave the leftover under one whole program.
        leftover = max(self.round_budget_tokens - plan.decode_cost_tokens,
                       floor)
        # Idle engine, one waiter: whole-prompt grant (see docstring) —
        # but only up to 2x the round budget (and never past the largest
        # compiled bucket). A dispatched grant is un-preemptible, so an
        # unbounded one-shot would re-open the prefill wall for whoever
        # arrives a microsecond later: a lone 3072-token prompt would
        # monopolize the device for its whole prefill. 2x the budget
        # keeps the lone-prompt fast path for typical prompts while
        # bounding any later arrival's wait to ~2 extra round-times.
        one_shot_cap = 2 * self.round_budget_tokens
        if self.max_one_shot_tokens is not None:
            one_shot_cap = min(one_shot_cap, self.max_one_shot_tokens)
        if (decode_steps == 0 and active_decodes == 0 and len(jobs) == 1
                and not jobs[0].started
                and jobs[0].remaining <= one_shot_cap):
            plan.chunks.append((jobs[0].key, jobs[0].remaining))
            return plan
        # Two-phase packing. Phase 1 hands every job a FAIR SHARE: the
        # largest shape the leftover pays for each of them. A short
        # prompt behind a long in-flight prefill gets in within
        # ~len(jobs) plans instead of waiting out the whole long
        # prefill — strict priority order would starve it, which is the
        # head-of-line blocking this scheduler exists to kill. Phase 2
        # re-grants whatever the fair pass left unused (jobs smaller
        # than their share) to the highest-priority jobs, raising a
        # grant to the next shape the leftover still pays for.
        per_job = leftover // len(jobs)
        # Scarcity rotation: when the leftover cannot give every job a
        # shape (one bucket a round under several prefills), a fixed
        # packing order would hand the SAME job the shape every round —
        # strict head-of-line blocking in fair-share clothing — and a
        # share cut finer than a shape would run every job's program
        # mostly empty. Rotating who packs first across rounds bounds
        # any job's wait for its next grant to ~len(jobs) rounds.
        order_idx = list(range(len(jobs)))
        if per_job < floor:
            start = self._rr % len(jobs)
            order_idx = order_idx[start:] + order_idx[:start]
        self._rr += 1
        granted: dict[int, int] = {}      # job index -> tokens
        for fair in (True, False):
            for i in order_idx:
                shapes = self.shapes_of(jobs[i].remaining)
                have = granted.get(i, 0)
                paid = self._charge(shapes, have)
                room = leftover + paid
                if fair:
                    room = min(room, max(per_job, shapes[0]))
                # Final grant: the remainder, charged the shape that
                # covers it; otherwise exactly a shape.
                grant = min(jobs[i].remaining, self._within(shapes, room))
                if grant <= have:
                    continue
                granted[i] = grant
                leftover -= self._charge(shapes, grant) - paid
        plan.chunks.extend((job.key, granted[i])
                           for i, job in enumerate(jobs) if i in granted)
        return plan
