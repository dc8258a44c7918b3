"""What a request's time to first token and its token gap are made of:
the request's own span tree (``obs/flight.py`` ``Timeline.spans``, on
``row.stream.timeline``) laid over the rounds' service intervals
(``obs/rounds.py`` ``RoundRecord.t_done``), for the requests that
FINISHED CORRECTLY INSIDE the window (one that straddles its end is
left out: its life is not whole).

A request is in exactly one state at every instant between submit and
finish — ``req_intake``, ``req_backlog`` (one span a cause: ``slot``,
``pages``, ``budget``), ``req_prefill``, ``req_first_token``,
``req_decode`` — so the states sum to ``finish - submit``. The device
runs the rounds FIFO, so round k is in service over
``[t_done(k-1), t_done(k)]``, clipped at its own dispatch start where
the queue was empty (time no round covers is booked ``idle``). Each
state's overlap with a round is booked by what the round ran:

  ``decode``  its decode steps (no grant belongs to them)
  ``own``     a chunk its ``grants`` give THIS request
  ``chunks``  a chunk they give another request
  ``mixed``   a round that dispatched both and carries no stamp to
              divide it at (reported; should be nothing)

A round that dispatched decode steps and chunks is divided at the
harvest thread's stamp of its first part (``t_parts[0]``: the decode
output precedes the chunks' marker in the device's order); its chunks
share what is left in the order dispatched, each by the padded tokens
of its program (the request's ``req_chunk`` span; the grant's tokens
where the request is not among the rows).

args: ``quantity``
  "admit_wait_ms"             mean over requests of req_intake +
                              req_backlog
  "prefill_behind_decode_ms"  mean over requests of the part of
                              req_prefill + req_first_token booked
                              ``decode``: chunk programs waiting behind
                              the queued decode rounds
  "prefill_behind_chunks_ms"  the same interval's part booked
                              ``chunks``: the rotation among prompts
  "gap_behind_prefill_pct"    100 x the share of req_decode seconds NOT
                              booked ``decode``: what a token gap holds
                              beyond the request's own step
Returns None where no finished request carries spans or no round a
``t_done`` (a program without them), and where no request finished
inside the window.

Every call leaves ``ctx.notes["request_life"]`` (computed once a
window): ``states`` — per state, and per cause of req_backlog, p50 / p90
/ max ms over the requests that were in it and its share of all
request-seconds; ``booked_ms`` — mean ms a request by book, for the time
to first token (req_prefill + req_first_token) and for req_decode;
``ttft_ms`` rebuilt from the spans beside the window line's arithmetic
over the same requests; ``closure`` — the largest error of the states'
sum against ``finish - submit`` and of the first four against
``first_token - submit``, and the ``mixed`` and ``idle`` shares;
``own_step_ms`` — ``tpot`` p50 of these requests x the share of
req_decode booked ``decode``, to hold against ``decode_step_ms``, and
``window_step_ms`` — the whole window's decode service time a decode
step (what separates a step that differs outside the traced interval
from the steps a request wastes in its last round);
``traced`` — for the requests whose prefill lies wholly inside the
traced interval, the same split read off the DEVICE plane (the spans
mapped onto the profiler's clock through the ``engine_round`` spans'
``t_mono_ns``, onto the device's by ``host_spans.align``'s offset; a
request's own executions found by pairing launches with executions FIFO,
``host_spans.pair_fifo``, through the ``request_id`` of the
``chunk_dispatch`` span around each launch) and each ``req_chunk``'s wait
from the end of its host dispatch to its device start; and ``slowest`` —
the whole record of the window's slowest request to first token: its
spans with their round ids, and those rounds' records.
"""

from __future__ import annotations

import bisect
import os
import re

from benchmarks.harness import stats as st
from benchmarks.harness import trace
from benchmarks.harness.spec import REPO
from benchmarks.readers import host_spans as hs

STATES = ("req_intake", "req_backlog", "req_prefill", "req_first_token",
          "req_decode")
TTFT_STATES = STATES[:4]
SERVED = ("req_prefill", "req_first_token")     # booked for the ttft split
BOOKS = ("own", "chunks", "decode", "mixed", "idle")
SLOWEST_ROUNDS = 160      # round records printed with the slowest request


# ------------------------------------------------------------ the rounds


def service_pieces(rounds: list, padded: dict) -> list:
    """The rounds' service intervals as sorted, disjoint pieces
    ``(t0, t1, book, of)``: ``book`` is "decode" (``of`` its steps),
    "chunk" (``of`` the request id it was granted to) or "mixed"."""
    out: list = []
    prev = 0.0
    for rec in sorted((r for r in rounds
                       if getattr(r, "t_done", 0.0) and r.done),
                      key=lambda r: r.round_id):
        t0, t1 = max(prev, rec.t_start), rec.t_done
        prev = max(prev, t1)
        if t1 <= t0:
            continue
        grants = list(rec.grants or [])
        decoded = getattr(rec, "decode_slots", 0) > 0
        steps = getattr(rec, "decode_steps", 0)
        if decoded and grants:
            parts = getattr(rec, "t_parts", None) or []
            if len(parts) < 2 or not t0 <= parts[0] <= t1:
                out.append((t0, t1, "mixed", None))
                continue
            out.append((t0, parts[0], "decode", steps))
            t0 = parts[0]
        elif decoded or not grants:
            out.append((t0, t1, "decode", steps if decoded else 0))
            continue
        weights = [padded.get((rid, rec.round_id), n) or 1
                   for rid, n in grants]
        scale, at = (t1 - t0) / sum(weights), t0
        for (rid, _), w in zip(grants, weights):
            out.append((at, at + w * scale, "chunk", rid))
            at += w * scale
    return out


def book(pieces: list, starts: list, rid: str, a: float, b: float) -> dict:
    """Seconds of [a, b) by book, for request ``rid``."""
    out = dict.fromkeys(BOOKS, 0.0)
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(pieces) and pieces[i][0] < b:
        p0, p1, what, whose = pieces[i]
        lap = min(b, p1) - max(a, p0)
        if lap > 0:
            if what == "chunk":
                what = "own" if whose == rid else "chunks"
            out[what] += lap
        i += 1
    out["idle"] = max(0.0, (b - a) - sum(out.values()))
    return out


# ---------------------------------------------------------- the requests


def states_of(tl) -> dict:
    """A request's state spans by name (req_backlog: a list)."""
    out: dict = {s: [] for s in STATES}
    for sp in tl.spans:
        if sp.name in out and sp.t1 is not None:
            out[sp.name].append(sp)
    return out


def whole_rows(ctx) -> list:
    """Rows that finished correctly inside the window and carry a whole
    span tree (every span closed)."""
    rows = []
    for r in ctx.rows:
        tl = getattr(r.stream, "timeline", None)
        spans = getattr(tl, "spans", None)
        if not spans or not ctx.ok(r) or r.finish_t is None \
                or r.finish_t > ctx.t_end \
                or any(sp.t1 is None for sp in spans):
            continue
        rows.append(r)
    return rows


def _dist(ms: list, total_s: float) -> dict:
    return {"n": len(ms), "p50": st.percentile(ms, 0.5),
            "p90": st.percentile(ms, 0.9), "max": max(ms),
            "share_pct": 100.0 * sum(ms) / 1e3 / total_s}


def account(ctx) -> dict | None:
    """The four quantities and the note, or None where the program
    leaves no spans or no ``t_done``."""
    rows = whole_rows(ctx)
    rounds = [r for r in ctx.rounds or [] if getattr(r, "t_done", 0.0)]
    if not rows or not rounds:
        return None
    padded = {(r.stream.request_id, sp.round_id0): sp.m
              for r in ctx.rows
              for sp in (getattr(getattr(r.stream, "timeline", None),
                                 "spans", None) or [])
              if sp.name == "req_chunk"}
    pieces = service_pieces(rounds, padded)
    starts = [p[0] for p in pieces]
    by_state: dict = {}            # state or state:cause -> [ms a request]
    booked = {"ttft": dict.fromkeys(BOOKS, 0.0),
              "req_decode": dict.fromkeys(BOOKS, 0.0)}
    per_req = []
    admit, ttft, err_life, err_ttft = [], [], 0.0, 0.0
    total_s = 0.0
    for r in rows:
        s, rid = r.stream, r.stream.request_id
        states = states_of(s.timeline)
        life = sum(sp.t1 - sp.t0 for v in states.values() for sp in v)
        first4 = sum(sp.t1 - sp.t0 for n in TTFT_STATES for sp in states[n])
        total_s += life
        err_life = max(err_life, abs(life - (s.finish_time - s.submit_time)))
        err_ttft = max(err_ttft, abs(
            first4 - (s.first_token_time - s.submit_time)))
        ttft.append(first4 * 1e3)
        admit.append(sum(sp.t1 - sp.t0 for n in STATES[:2]
                         for sp in states[n]) * 1e3)
        mine = {"ttft": dict.fromkeys(BOOKS, 0.0),
                "req_decode": dict.fromkeys(BOOKS, 0.0)}
        for name, spans in states.items():
            keys: dict = {}
            for sp in spans:
                key = name if name != "req_backlog" \
                    else f"{name}:{sp.cause or 'none'}"
                keys[key] = keys.get(key, 0.0) + (sp.t1 - sp.t0) * 1e3
                group = ("ttft" if name in SERVED else
                         name if name == "req_decode" else None)
                if group:
                    for k, v in book(pieces, starts, rid, sp.t0,
                                     sp.t1).items():
                        mine[group][k] += v
            if name == "req_backlog" and spans:
                keys[name] = sum(keys.values())
            for key, ms in keys.items():
                by_state.setdefault(key, []).append(ms)
        for g in booked:
            for k in BOOKS:
                booked[g][k] += mine[g][k]
        per_req.append((r, states, mine))
    n = len(rows)
    dec_s = sum(booked["req_decode"].values())
    dec_share = booked["req_decode"]["decode"] / dec_s if dec_s else None
    metrics = {
        "admit_wait_ms": sum(admit) / n,
        "prefill_behind_decode_ms": booked["ttft"]["decode"] * 1e3 / n,
        "prefill_behind_chunks_ms": booked["ttft"]["chunks"] * 1e3 / n,
        "gap_behind_prefill_pct": (None if dec_share is None
                                   else 100.0 * (1.0 - dec_share)),
    }
    tpot = [v for v in (st.tpot_ms(r.first_token_t, r.finish_t, r.tokens)
                        for r in rows) if v is not None]
    tpot_p50 = st.percentile(tpot, 0.5)
    steps = sum(p[3] for p in pieces if p[2] == "decode")
    all_booked = {k: booked["ttft"][k] + booked["req_decode"][k]
                  for k in BOOKS}
    note = {
        "requests": n, "rounds": len(rounds), "request_seconds": total_s,
        "metrics": metrics,
        "states": {k: _dist(v, total_s) for k, v in sorted(by_state.items())},
        "booked_ms": {g: {k: v * 1e3 / n for k, v in b.items()}
                      for g, b in booked.items()},
        "ttft_ms": {
            "p50": st.percentile(ttft, 0.5), "p90": st.percentile(ttft, 0.9),
            "window_line_p50_same_requests": st.percentile(
                [(r.first_token_t - r.due_t) * 1e3 for r in rows], 0.5),
            "lateness_ms_max": max((r.stream.submit_time - r.due_t) * 1e3
                                   for r in rows)},
        "closure": {
            "life_err_ms_max": err_life * 1e3,
            "ttft_err_ms_max": err_ttft * 1e3,
            "mixed_pct": 100.0 * all_booked["mixed"] / total_s,
            "idle_pct": 100.0 * all_booked["idle"] / total_s},
        "own_step_ms": (None if tpot_p50 is None or dec_share is None
                        else tpot_p50 * dec_share),
        "tpot_p50_ms_same_requests": tpot_p50,
        "window_step_ms": (None if not steps else 1e3 * sum(
            p[1] - p[0] for p in pieces if p[2] == "decode") / steps),
    }
    traced = traced_check(ctx, per_req)
    if traced is not None:
        note["traced"] = traced
    note["slowest"] = slowest(ctx, rows)
    return note


def slowest(ctx, rows: list) -> dict:
    """The window's slowest request to first token: its spans (ms from
    its submit, with round ids) and the records of the rounds between
    its first and its arming stamp — the evidence of a stall."""
    r = max(rows, key=lambda r: r.first_token_t - r.stream.submit_time)
    s = r.stream
    tl = s.timeline
    lo = min((sp.round_id1 for sp in tl.spans if sp.round_id1 >= 0),
             default=-1)
    hi = max((sp.round_id0 for sp in tl.spans
              if sp.name == "req_decode"), default=lo)
    recs = sorted((x for x in ctx.rounds or []
                   if lo <= x.round_id <= hi), key=lambda x: x.round_id)
    omitted = max(0, len(recs) - SLOWEST_ROUNDS)
    if omitted:
        half = SLOWEST_ROUNDS // 2
        recs = recs[:half] + recs[-half:]
    fields = ("round_id", "kind", "decode_steps", "decode_slots",
              "queued_ahead", "waiting_slot", "waiting_pages",
              "waiting_budget", "prefill_ungranted", "blocked_on_pages")
    return {
        "request_id": s.request_id, "uid": r.request.uid,
        "prompt_tokens": len(r.request.prompt_ids), "tokens": r.tokens,
        "ttft_ms": (r.first_token_t - s.submit_time) * 1e3,
        "submit_in_window_s": s.submit_time - ctx.t0,
        "spans": tl.spans_dict(s.submit_time),
        "round_fields": list(fields) + [
            "start_ms", "done_ms", "dispatch_ms", "harvest_wait_ms",
            "grants", "own_tokens"],
        "rounds": [
            [getattr(x, f, None) for f in fields] + [
                round((x.t_start - s.submit_time) * 1e3, 3),
                round((getattr(x, "t_done", 0.0) - s.submit_time) * 1e3, 3),
                round(x.dispatch_ms, 3), round(x.harvest_wait_ms, 3),
                len(x.grants),
                sum(k for rid, k in x.grants if rid == s.request_id)]
            for x in recs],
        "rounds_omitted": omitted}


# ----------------------------------------------- beside the device plane


def _pairs(spans: dict, device: dict) -> list:
    """[(launch start ns, dispatch span end ns, the span's arguments),
    (start, end, module)] for the engine's programs: ``host_spans``'
    FIFO pairing, kept with each launch's ``chunk_dispatch`` arguments
    (``align`` returns only their count)."""
    launches = []
    for name, calls in spans.items():
        if not name.startswith(hs.CALL):
            continue
        module = "jit_" + name[len(hs.CALL):].rstrip(")")
        kind = ("decode" if re.search(hs.DECODE, module) else
                "prefill" if re.search(hs.PREFILL, module) else None)
        if kind:
            for c in calls:
                around = hs.enclosing(spans, hs.DISPATCH_SPANS, c[0], c[1])
                launches.append((kind, (c[0], (around or c)[1],
                                        (around or c)[2])))
    launches.sort(key=lambda x: x[1][0])
    execs = [("decode" if re.search(hs.DECODE, m[2]) else "prefill", m)
             for m in device["modules"]
             if re.search(hs.DECODE, m[2]) or re.search(hs.PREFILL, m[2])]
    return hs.pair_fifo(launches, execs)


def traced_check(ctx, per_req: list) -> dict | None:
    if ctx.trace_t0 is None or ctx.trace_t1 is None:
        return None
    path = trace.find_xplane(os.path.join(REPO, ".bench_trace",
                                          ctx.cell.name))
    if path is None:
        return None
    from jax.profiler import ProfileData
    summary = hs.summary(path)
    spans, clock = summary["spans"], summary["note"].get("clock_offset_ms")
    rounds = [s for s in spans.get(hs.ROUND, []) if "t_mono_ns" in s[2]]
    if not rounds or clock is None:
        return None
    offset = clock["used"] * 1e6        # align's host - device, ns
    device = hs.load_device(ProfileData.from_file(path))
    # monotonic seconds -> the device's clock, ns: the engine_round
    # spans carry both host clocks (exact), align gives host - device
    shifts = sorted(s[0] - int(s[2]["t_mono_ns"]) for s in rounds)
    shift = shifts[len(shifts) // 2] - offset
    mods = [(m[0], m[1], "decode" if re.search(hs.DECODE, m[2]) else "chunk")
            for m in device["modules"]
            if re.search(hs.DECODE, m[2]) or re.search(hs.PREFILL, m[2])]
    own: dict = {}
    ahead = []
    for launch, ex in _pairs(spans, device):
        rid = launch[2].get("request_id")
        if rid is not None and re.search(hs.PREFILL, ex[2]):
            own.setdefault(rid, set()).add(ex[0])
            ahead.append((ex[0] + offset - launch[1]) * 1e-6)
    by_rounds = dict.fromkeys(("own", "chunks", "decode"), 0.0)
    by_device = dict.fromkeys(("own", "chunks", "decode"), 0.0)
    n = 0
    for r, states, mine in per_req:
        served = [sp for name in SERVED for sp in states[name]]
        if not served or served[0].t0 < ctx.trace_t0 \
                or served[-1].t1 > ctx.trace_t1:
            continue
        n += 1
        a, b = served[0].t0 * 1e9 + shift, served[-1].t1 * 1e9 + shift
        mine_execs = own.get(r.stream.request_id, ())
        for m0, m1, what in mods:
            lap = min(b, m1) - max(a, m0)
            if lap > 0:
                if what == "chunk":
                    what = "own" if m0 in mine_execs else "chunks"
                by_device[what] += lap * 1e-6
        for k in by_rounds:
            by_rounds[k] += mine["ttft"][k] * 1e3
    if not n:
        return {"requests": 0}
    return {"requests": n, "by_rounds_ms": by_rounds,
            "by_device_ms": by_device,
            "disagree_pct": {
                k: (None if not by_device[k] else
                    100.0 * (by_rounds[k] - by_device[k]) / by_device[k])
                for k in by_rounds},
            "chunk_queue_ahead_ms": {
                "n": len(ahead), "p50": st.percentile(ahead, 0.5),
                "p90": st.percentile(ahead, 0.9)}}


def read(ctx, quantity):
    note = ctx.notes.get("request_life")
    if note is None:
        note = account(ctx)
        if note is None:
            return None
        ctx.notes["request_life"] = note
    return note["metrics"][quantity]
