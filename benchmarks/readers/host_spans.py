"""The program's host spans (``obs/tracing.py`` ``phase``: TraceMe
annotations) on the host plane of the cell's trace, beside the device
plane on the profiler's one clock. ``harness/trace.py`` keeps only
device planes, so this reader opens ``.bench_trace/<cell>`` itself.

args: ``spans`` (names), ``agg``
  "mean_ms"       mean duration of the named spans
  "count"         how many
  "ms_per_round"  their summed duration ÷ the number of ``engine_round``
                  spans (rounds that dispatched something)
Only spans that lie wholly inside the traced interval count. Returns
None where the trace holds none of the named spans (a program without
them).

A LAUNCH is a ``PjitFunction(<fn>)`` event of the runtime (the jitted
call itself, at ``host_tracer_level`` 1) for one of the engine's four
programs, taken with the ``loop_dispatch`` or ``chunk_dispatch`` span
around it: a span that launched nothing (an admission refused for
pages) pairs with no execution.

Every call also leaves, once a trace, ``ctx.notes["host_spans"]``:
``per_round_ms`` of every span name seen; ``calls_in_dispatch_ms`` —
per jitted function, the time per round spent inside its calls within
the dispatch spans (a call that blocks on a full device queue shows
here, under the name of the first call that met it: often a scalar's
``convert_element_type``); ``dispatch_ms_per_round`` — ``loop_dispatch``
+ ``loop_admit`` span time against the round records' ``dispatch_ms``
over the same rounds; ``clock_offset_ms`` — the
host-minus-device offset it assumed, with the two bounds it rests on
(``lower``: no program starts on the device before its launch began;
``upper``: no round ends on the device after the harvest thread held
its tokens) and the ``residual`` uncertainty; ``queue_ahead_ms`` — per
prefill program, host ``chunk_dispatch`` end to device start of the
matching ``jit_prefill_insert|jit_extend|jit_final`` execution, p50 and
p90; and ``idle_gaps`` — every device idle gap over 50 us put down to
the host span that covers most of it (``none`` where no span does;
``under_residual`` where the gap is shorter than the residual).
"""

from __future__ import annotations

import functools
import os
import re

from benchmarks.harness import stats as st
from benchmarks.harness import trace
from benchmarks.harness.spec import REPO

HOST_PLANE = "/host:CPU"
ROUND = "engine_round"
DECODE, PREFILL = r"^jit_decode_round$", r"^jit_(prefill_insert|extend|final)$"
CALL = "PjitFunction("
DISPATCH_SPANS = ("loop_dispatch", "chunk_dispatch")
GAP_MIN_NS = 50e3
#: bounds closer together than this pin the offset: their midpoint is
#: used and half their distance is the residual. Further apart, the
#: lower one is slack (the device queue was never empty, so no launch
#: found the device waiting): the upper one is used, and the residual is
#: taken to be RESIDUAL_NS — a readback's own latency is not measured.
TIGHT_NS = 2e6
RESIDUAL_NS = 1e6
#: spans a gap may be put down to, innermost first
_GAP_SPANS = ("chunk_dispatch", "loop_dispatch", "loop_admit", "loop_plan",
              "loop_drain", "loop_idle", ROUND)
_PREFIXES = ("loop_", "engine_", "chunk_", CALL)


def load_spans(profile) -> dict:
    """name -> [(start_ns, end_ns, stats dict)] sorted by start, for
    every host event of a ``ProfileData`` named as the program names
    its spans."""
    out: dict = {}
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(_PREFIXES):
                    continue
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    for name, v in out.items():
        v.sort(key=lambda s: (s[0], -s[1]))
        if name.startswith(CALL):
            # the runtime prints a jitted call twice, one inside the other
            kept: list = []
            for ev in v:
                if not kept or ev[0] >= kept[-1][1]:
                    kept.append(ev)
            out[name] = kept
    return out


def enclosing(spans: dict, names: tuple, start: float, end: float):
    """The span of ``names`` that holds [start, end), or None."""
    for name in names:
        for s in spans.get(name, []):
            if s[0] <= start and end <= s[1]:
                return s
    return None


def load_device(profile) -> dict:
    """First TPU plane of a ``ProfileData``: module executions
    [(start, end, module)] in order of start, and merged busy
    intervals."""
    lines: dict = {}
    for plane in profile.planes:
        if not re.search(trace.DEVICE_PLANE, plane.name):
            continue
        lines = {ln.name: [(e.name, e.start_ns, e.duration_ns)
                           for e in ln.events]
                 for ln in plane.lines
                 if ln.name in (trace.MODULES_LINE, trace.OPS_LINE)}
        if lines.get(trace.MODULES_LINE):
            break
    if not lines.get(trace.MODULES_LINE):
        return {"modules": [], "busy": []}
    mods = sorted((s, s + d, trace.module_name(n))
                  for n, s, d in lines[trace.MODULES_LINE])
    ev = lines.get(trace.OPS_LINE) or lines[trace.MODULES_LINE]
    return {"modules": mods,
            "busy": trace.merge((s, s + d) for _, s, d in ev)}


def pair_fifo(launches: list, execs: list, max_shift: int = 8):
    """The device stream is FIFO, so the k-th launch is the (k + c)-th
    execution, c the executions of the trace that were launched before
    it began. ``launches`` and ``execs`` are [(kind, item)]; c is the
    least shift under which every overlapping pair agrees in kind.
    Returns [(launch item, exec item)] (empty when no shift fits)."""
    for c in range(0, max_shift + 1):
        n = min(len(launches), len(execs) - c)
        if n <= 0:
            break
        if all(launches[i][0] == execs[i + c][0] for i in range(n)):
            return [(launches[i][1], execs[i + c][1]) for i in range(n)]
    return []


def align(spans: dict, device: dict) -> dict:
    """Clock offset, queue-ahead and the pairs they rest on."""
    launches = []
    for name, calls in spans.items():
        if not name.startswith(CALL):
            continue
        module = "jit_" + name[len(CALL):].rstrip(")")
        kind = ("decode" if re.search(DECODE, module) else
                "prefill" if re.search(PREFILL, module) else None)
        for c in calls:
            if kind:
                around = enclosing(spans, DISPATCH_SPANS, c[0], c[1])
                # (launch start, dispatch span end, the span's arguments)
                launches.append((kind, (c[0], (around or c)[1],
                                        (around or c)[2])))
    launches.sort(key=lambda x: x[1][0])
    execs = [("decode" if re.search(DECODE, m[2]) else "prefill", m)
             for m in device["modules"]
             if re.search(DECODE, m[2]) or re.search(PREFILL, m[2])]
    pairs = pair_fifo(launches, execs)
    out = {"pairs": len(pairs), "launches": len(launches),
           "executions": len(execs)}
    if not pairs:
        return dict(out, offset_ns=None)
    lower = max(h[0] - d[0] for h, d in pairs)
    by_round = {h[2].get("round_id"): d for h, d in pairs
                if re.search(DECODE, d[2])}
    ups = [w[1] - by_round[w[2].get("round_id")][1]
           for w in spans.get("engine_harvest_wait", [])
           if w[2].get("round_id") in by_round]
    upper = min(ups) if ups else None
    if upper is None or upper < lower:
        used, residual = lower, RESIDUAL_NS
    elif upper - lower <= TIGHT_NS:
        used, residual = (lower + upper) / 2, (upper - lower) / 2
    else:
        used, residual = upper, RESIDUAL_NS
    ahead = [(d[0] + used - h[1]) * 1e-6 for h, d in pairs
             if re.search(PREFILL, d[2])]
    return dict(out, offset_ns=used, clock_offset_ms={
        "used": used * 1e-6, "lower": lower * 1e-6,
        "upper": None if upper is None else upper * 1e-6,
        "residual": residual * 1e-6}, residual_ns=residual,
        queue_ahead_ms={"n": len(ahead), "p50": st.percentile(ahead, 0.5),
                        "p90": st.percentile(ahead, 0.9)})


def gaps_by_phase(spans: dict, device: dict, offset_ns: float,
                  residual_ns: float) -> dict:
    """Device idle gaps over 50 us, each put down to the host span (of
    ``_GAP_SPANS``, innermost first) that covers most of it."""
    out: dict = {}
    busy = device["busy"]
    for (_, g0), (g1, _) in zip(busy, busy[1:]):
        if g1 - g0 < GAP_MIN_NS:
            continue
        label, best = "none", 0.0
        if g1 - g0 < residual_ns:
            label = "under_residual"
        else:
            a, b = g0 + offset_ns, g1 + offset_ns   # on the host's clock
            for name in _GAP_SPANS:
                cover = max((min(b, e) - max(a, s)
                             for s, e, _ in spans.get(name, [])), default=0)
                if cover > best and cover >= 0.5 * (b - a):
                    label, best = name, cover
                    break
        n, s = out.get(label, (0, 0.0))
        out[label] = (n + 1, s + (g1 - g0) * 1e-6)
    return {k: {"n": n, "ms": ms} for k, (n, ms) in out.items()}


def calls_in_dispatch(spans: dict, rounds: int) -> dict:
    """Per jitted function: ms a round inside its calls within the
    dispatch spans."""
    out = {}
    for name, calls in spans.items():
        if name.startswith(CALL):
            ns = sum(c[1] - c[0] for c in calls
                     if enclosing(spans, DISPATCH_SPANS, c[0], c[1]))
            if ns:
                out[name[len(CALL):].rstrip(")")] = ns * 1e-6 / rounds
    return out


def summarise(profile) -> dict:
    """``{"spans": ..., "note": ...}`` of one ``ProfileData``."""
    spans, device = load_spans(profile), load_device(profile)
    rounds = len(spans.get(ROUND, []))
    note: dict = {"rounds": rounds}
    if rounds:
        note["per_round_ms"] = {
            n: sum(e - s for s, e, _ in spans[n]) * 1e-6 / rounds
            for n in sorted(spans) if not n.startswith(CALL)}
        note["calls_in_dispatch_ms"] = calls_in_dispatch(spans, rounds)
    al = align(spans, device)
    note["alignment"] = {k: al[k] for k in ("pairs", "launches",
                                            "executions")}
    if al["offset_ns"] is not None:
        note["clock_offset_ms"] = al["clock_offset_ms"]
        note["queue_ahead_ms"] = al["queue_ahead_ms"]
        note["idle_gaps"] = gaps_by_phase(spans, device, al["offset_ns"],
                                          al["residual_ns"])
    return {"spans": spans, "note": note}


def dispatch_against_records(spans: dict, records: list) -> dict | None:
    """``loop_dispatch`` + ``loop_admit`` span time a round against the
    round records' own ``dispatch_ms`` (plan done to seal), over the
    rounds that have both a whole ``engine_round`` span and a record."""
    by_id = {r.round_id: r for r in records if hasattr(r, "round_id")}
    ids = {s[2].get("round_id") for s in spans.get(ROUND, [])} & set(by_id)
    if not ids:
        return None
    ns = sum(e - b for n in ("loop_dispatch", "loop_admit")
             for b, e, st in spans.get(n, []) if st.get("round_id") in ids)
    return {"rounds": len(ids), "spans": ns * 1e-6 / len(ids),
            "records": sum(by_id[i].dispatch_ms for i in ids) / len(ids)}


@functools.lru_cache(maxsize=4)
def summary(path: str) -> dict:
    """``summarise`` of the trace file at ``path``, once a file."""
    from jax.profiler import ProfileData
    return summarise(ProfileData.from_file(path))


def read(ctx, spans, agg="mean_ms"):
    path = trace.find_xplane(os.path.join(REPO, ".bench_trace",
                                          ctx.cell.name))
    if path is None:
        return None
    s = summary(path)
    if s["spans"].get(ROUND):
        ctx.notes["host_spans"] = dict(
            s["note"], dispatch_ms_per_round=dispatch_against_records(
                s["spans"], ctx.trace_rounds or []))
    found = [x for n in spans for x in s["spans"].get(n, [])]
    if not found:
        return None
    if agg == "count":
        return float(len(found))
    total_ms = sum(e - b for b, e, _ in found) * 1e-6
    if agg == "mean_ms":
        return total_ms / len(found)
    if agg == "ms_per_round":
        rounds = len(s["spans"].get(ROUND, []))
        return total_ms / rounds if rounds else None
    raise ValueError(f"host_spans does not know agg {agg!r}")
