"""The engine's record of each device program it dispatched
(``obs/rounds.py`` ``ProgramRun``, on ``RoundRecord.programs``): what
the program was (``name``: ``decode_round``, ``verify_round``,
``prefill_insert``, ``extend``, ``extend_rows``, ``final``, ``rag``),
what it carried (``tokens`` real, ``padded`` of its shape, ``rows``,
``steps``), when its launch began and ended on the scheduler thread
(``t_launch0``, ``t_launch1``) and when its OWN readback returned on the
harvest thread (``t_done``), and ``service_ms`` = ``t_done`` minus the
later of ``t_launch1`` and the previous program's ``t_done``: on a FIFO
device that is never idle, the program's time on the chip. Read over
``ctx.rounds``: every program of the WHOLE window, traced or not, each
with its own tokens under it.

args: ``names`` (the program names that count; all where left out),
``where`` (plain keys a program must equal, e.g. ``{"rows": 1}``; the
value ``"largest"`` stands for the configuration's largest prefill
bucket), ``agg``
  "median_ms"    median ``service_ms``
  "ms_per_ktok"  sum of ``service_ms`` / sum of real ``tokens`` x 1000
  "ms_per_step"  sum of ``service_ms`` / sum of ``steps``
  "starved_pct"  100 x the time between one program's ``t_done`` and the
                 next one's ``t_launch1`` (nothing was queued: the chip
                 waited for the host), over consecutive programs, while
                 a request was live, / the window. A request was live
                 between two programs of one round, and before a round
                 planned with streams decoding, with requests waiting
                 (for a slot, pages, the budget, or in a prefill the
                 plan granted nothing) or with a grant to a prompt an
                 earlier round had granted already; not before a round
                 whose only work is a prompt's FIRST grant (it had just
                 arrived: the chip idled for want of a request).
Returns None where no record carries ``programs`` (a program without
them) or nothing matches.

Every call leaves, once a window, ``ctx.notes["program_runs"]``:
``by_name`` — per program name the count, p50 / p90 / max of
``service_ms`` and of the launch (``launch_ms``), the real and padded
tokens and the steps; ``longest_launch`` — the window's longest launch:
its program, round, ms and ``done_during_launch`` (how many earlier
programs completed inside it: many, the host waited for room in a queue
the chip was draining; none, the chip, the runtime or the machine stood
still); ``starved_ms``; and in a traced run ``closure`` — the device
plane's executions (but its first and its last, which the trace's edges
may cut) paired, module by module and in order, each with the first
program of its module whose ``t_done`` is not before its end (``t_done``
mapped onto the device's clock through the ``engine_round`` spans'
``t_mono_ns`` and ``host_spans``' offset, or where it gives none through
the host's profiler clock alone, a millisecond or two off):
per module the pairs, the sum of ``service_ms`` beside the sum of device
execution ms and their ratio, and how late the stamps were (``t_done``
minus the execution's end, p50 / p90 / max ms; ``late_stamps``: those
over 5 ms by name and round, latest first).
"""

from __future__ import annotations

import os

from benchmarks.harness import stats as st
from benchmarks.harness import trace
from benchmarks.harness.spec import REPO

#: the XLA module a program name runs as (``engine/programs.py``)
MODULES = {"decode_round": "jit_decode_round",
           "verify_round": "jit_verify_round",
           "prefill_insert": "jit_prefill_insert", "extend": "jit_extend",
           "extend_rows": "jit_extend", "final": "jit_final",
           "rag": "jit_rag_admit"}
#: a stamp may read this much EARLIER than its execution's end and still
#: be that execution's (the clock offset's residual, host_spans.align)
SLACK_NS = 2e6
#: a stamp later than this after its execution's end is listed by name
LATE_MS = 5.0


def runs_of(ctx) -> list | None:
    """[(round record, ProgramRun)] of the window in launch order, the
    completed ones; None where no record carries ``programs``."""
    recs = [r for r in ctx.rounds or [] if hasattr(r, "programs")]
    if not recs:
        return None
    return [(r, p) for r in sorted(recs, key=lambda r: r.round_id)
            for p in r.programs if p.t_done]


def largest_bucket(ctx, runs: list) -> int:
    engine = (getattr(ctx.cell, "config", None) or {}).get("engine", {})
    return int(engine.get("max_prefill_bucket")
               or max((p.padded // max(p.rows, 1) for _, p in runs
                       if not p.steps), default=0))


def select(ctx, runs: list, names=None, where=None) -> list:
    want = dict(where or {})
    for k, v in want.items():
        if v == "largest":
            want[k] = largest_bucket(ctx, runs)
    return [p for _, p in runs
            if (not names or p.name in names)
            and all(getattr(p, k) == v for k, v in want.items())]


def starved(runs: list) -> list:
    """[(ms, round id)]: each stretch in which nothing was queued though
    a request was live (see the module's docstring)."""
    out = []
    granted: set = set()
    prev_rec = prev = None
    for rec, p in runs:
        if rec is not prev_rec and prev_rec is not None:
            granted.update(rid for rid, _ in prev_rec.grants)
        if prev is not None:
            live = rec is prev_rec or rec.active_decodes > 0 or (
                rec.waiting_slot + rec.waiting_pages + rec.waiting_budget
                + rec.prefill_ungranted) > 0 or any(
                rid in granted for rid, _ in rec.grants)
            gap = (p.t_launch1 - prev.t_done) * 1e3
            if live and gap > 0:
                out.append((gap, rec.round_id))
        prev_rec, prev = rec, p
    return out


def _dist(vals: list) -> dict:
    return {"p50": st.percentile(vals, 0.5), "p90": st.percentile(vals, 0.9),
            "max": max(vals)}


def closure(ctx, runs: list) -> dict | None:
    """The traced interval: the device plane's executions beside the
    programs' own records of them (None without a trace or its host
    spans)."""
    if ctx.trace_t0 is None or ctx.trace_t1 is None:
        return None
    path = trace.find_xplane(os.path.join(REPO, ".bench_trace",
                                          ctx.cell.name))
    if path is None:
        return None
    from jax.profiler import ProfileData
    from benchmarks.readers import host_spans as hs
    summary = hs.summary(path)
    clock = summary["note"].get("clock_offset_ms")
    rounds = [s for s in summary["spans"].get(hs.ROUND, [])
              if "t_mono_ns" in s[2]]
    if not rounds:
        return None
    # monotonic seconds -> the device's clock, ns (request_life's
    # route). Where host_spans could pair nothing and gives no offset,
    # the host's profiler clock stands for the device's: the two lie a
    # millisecond or two apart, every stamp then reads that much later,
    # and a sum of service times does not move.
    shifts = sorted(s[0] - int(s[2]["t_mono_ns"]) for s in rounds)
    shift = shifts[len(shifts) // 2] - (clock["used"] * 1e6 if clock else 0)
    slack = SLACK_NS if clock else 5 * SLACK_NS
    device = hs.load_device(ProfileData.from_file(path))
    by_module: dict = {}
    for rec, p in runs:
        by_module.setdefault(MODULES.get(p.name), []).append((rec, p))
    # the plane's first and last execution may be CUT by the trace's
    # edges (the event of a program running when the profiler stopped
    # ends at the stop, 4 ms of an 85 ms round): left out
    mine = [m for m in device["modules"] if m[2] in by_module]
    edges = {mine[0][0], mine[-1][0]} if mine else set()
    out: dict = {}
    latest: list = []
    for module, progs in sorted((m, v) for m, v in by_module.items() if m):
        execs = [m for m in mine if m[2] == module and m[0] not in edges]
        i, service, dev, late, names = 0, 0.0, 0.0, [], {}
        for e0, e1, _ in execs:
            while i < len(progs) and \
                    progs[i][1].t_done * 1e9 + shift < e1 - slack:
                i += 1
            if i == len(progs):
                break
            rec, p = progs[i]
            i += 1
            service += p.service_ms
            dev += (e1 - e0) * 1e-6
            late.append((p.t_done * 1e9 + shift - e1) * 1e-6)
            names[p.name] = names.get(p.name, 0) + 1
            if late[-1] > LATE_MS:
                latest.append({"name": p.name, "round_id": rec.round_id,
                               "late_ms": late[-1],
                               "service_ms": p.service_ms,
                               "device_ms": (e1 - e0) * 1e-6})
        if late:
            out[module] = {
                "executions": len(execs), "pairs": len(late),
                "programs": names, "service_ms": service, "device_ms": dev,
                "service_over_device": service / dev if dev else None,
                "stamp_late_ms": _dist(late)}
    if not out:
        return None
    latest.sort(key=lambda x: -x["late_ms"])
    return {"clock_offset_residual_ms": clock["residual"] if clock else None,
            "modules": out,
            "late_stamps": {"over_ms": LATE_MS, "n": len(latest),
                            "latest": latest[:8]}}


def note(ctx, runs: list) -> dict:
    by_name: dict = {}
    for _, p in runs:
        by_name.setdefault(p.name, []).append(p)
    gaps = starved(runs)
    out = {
        "programs": len(runs),
        "by_name": {
            name: {"n": len(ps),
                   "service_ms": _dist([p.service_ms for p in ps]),
                   "launch_ms": _dist([p.launch_ms for p in ps]),
                   "tokens": sum(p.tokens for p in ps),
                   "padded": sum(p.padded for p in ps),
                   "steps": sum(p.steps for p in ps)}
            for name, ps in sorted(by_name.items())},
        "starved_ms": {"n": len(gaps), "sum": sum(g for g, _ in gaps),
                       "max": max(gaps, default=None)},
    }
    if runs:
        rec, p = max(runs, key=lambda rp: rp[1].launch_ms)
        # the count is kept on a ROUND's longest launch, which the
        # window's longest is
        out["longest_launch"] = {
            "name": p.name, "round_id": rec.round_id,
            "launch_ms": p.launch_ms, "service_ms": p.service_ms,
            "done_during_launch": p.done_during_launch,
            "in_window_s": p.t_launch0 - ctx.t0}
    traced = closure(ctx, runs)
    if traced is not None:
        out["closure"] = traced
    return out


def read(ctx, agg, names=None, where=None):
    runs = runs_of(ctx)
    if runs is None:
        return None
    if "program_runs" not in ctx.notes:
        ctx.notes["program_runs"] = note(ctx, runs)
    if agg == "starved_pct":
        if not runs or not ctx.window_s:
            return None
        return 100.0 * ctx.notes["program_runs"]["starved_ms"]["sum"] \
            / 1e3 / ctx.window_s
    chosen = select(ctx, runs, names, where)
    if not chosen:
        return None
    service = sum(p.service_ms for p in chosen)
    if agg == "median_ms":
        return st.percentile([p.service_ms for p in chosen], 0.5)
    if agg == "ms_per_ktok":
        tokens = sum(p.tokens for p in chosen)
        return service / tokens * 1000.0 if tokens else None
    if agg == "ms_per_step":
        steps = sum(p.steps for p in chosen)
        return service / steps if steps else None
    raise ValueError(f"program_runs does not know agg {agg!r}")
