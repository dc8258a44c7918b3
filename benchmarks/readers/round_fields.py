"""Fields of the engine's per-round records (``obs/rounds.py``) that
``round_records`` does not list: the pool and the host's split of a
round, over the rounds that began inside the window.

args: ``field`` (a numeric RoundRecord attribute), ``agg``
  "mean"       mean over the window's rounds
  "share_pct"  100 x the share of rounds in which the field is > 0
Returns None where no record carries the field (a program without it).
"""


def read(ctx, field, agg="mean"):
    vals = [getattr(r, field) for r in ctx.rounds or []
            if hasattr(r, field)]
    if not vals:
        return None
    if agg == "mean":
        return sum(vals) / len(vals)
    if agg == "share_pct":
        return 100.0 * sum(1 for v in vals if v > 0) / len(vals)
    raise ValueError(f"round_fields does not know agg {agg!r}")
