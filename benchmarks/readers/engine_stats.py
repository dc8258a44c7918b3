"""The engine's own counters (``Engine.stats``), as the harness took
them at the window's start and end.

args: ``fields`` (stat names; their values are summed), ``at``
  "start"  the value at window start — what set-up cost
  "delta"  end minus start — what the window added
Returns None where the engine keeps none of the fields (a program
without them). Several fields leave their parts on the window line
(``ctx.notes["engine_stats"]``): the sum is the metric, the split says
which phase of a build to look at.
"""


def read(ctx, fields, at="start"):
    s0, s1 = ctx.stats0 or {}, ctx.stats1 or {}
    have = [f for f in fields if f in s0]
    if not have:
        return None
    if at == "start":
        parts = {f: s0[f] for f in have}
    elif at == "delta":
        parts = {f: s1.get(f, s0[f]) - s0[f] for f in have}
    else:
        raise ValueError(f"engine_stats does not know at {at!r}")
    if len(have) > 1:
        ctx.notes.setdefault("engine_stats", {}).update(parts)
    return float(sum(parts.values()))
