"""The engine's per-round records (``obs/rounds.py``) of rounds that
began inside the window: counts and host times only (``device_ms``,
``bw_util`` and ``hbm_bytes`` are the program's estimates and are not
read).

args: ``field`` (a RoundRecord attribute; a list counts its entries),
``agg`` "mean" | "sum" | "per_counter" (sum over the window divided by
the delta of engine counter ``per_counter``), ``where`` (a field that
must be > 0 for the round to count), ``scale``.
"""

_ALLOWED = ("decode_steps", "active_decodes", "decode_slots", "grants",
            "prefill_tokens", "dispatch_ms", "round_ms", "harvest_wait_ms",
            "first_readback_ms", "tokens_emitted", "first_tokens",
            "budget_tokens")


def read(ctx, field, agg="mean", where=None, per_counter=None, scale=1.0):
    if field not in _ALLOWED or (where and where not in _ALLOWED):
        raise ValueError(f"round_records does not read {field!r}/{where!r}")
    vals = []
    for rec in ctx.rounds or []:
        if where and not getattr(rec, where):
            continue
        v = getattr(rec, field)
        vals.append(len(v) if isinstance(v, (list, tuple)) else v)
    if not vals:
        return None
    if agg == "mean":
        return sum(vals) / len(vals) * scale
    if agg == "sum":
        return sum(vals) * scale
    if agg == "per_counter":
        den = ctx.stats1.get(per_counter, 0) - ctx.stats0.get(per_counter, 0)
        return None if not den else sum(vals) / den * scale
    raise ValueError(f"unknown agg {agg!r}")
