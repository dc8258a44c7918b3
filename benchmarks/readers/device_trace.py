"""The profiler's device trace of the traced interval.

args: ``kind``
  "idle_pct"        100 x (1 - device busy / traced window)
  "module_ms_per"   device ms of the XLA modules matching ``modules`` (a
                    regular expression) per unit of work in the traced
                    interval. The unit is ``per``:
                      "step"   module executions x the mean of round
                               field ``steps_field`` over the rounds of
                               the interval that have it > 0 (the count
                               comes from the trace itself, so a round in
                               flight at either edge does not skew it)
                      "ktok"   thousand tokens of round field
                               ``tokens_field`` summed over the interval
                      "call"   module executions
"""


def read(ctx, kind, modules=None, per="call", steps_field="decode_steps",
         tokens_field="prefill_tokens"):
    tr = ctx.trace
    if tr is None:
        return None
    if kind == "idle_pct":
        return 100.0 * (1.0 - tr.busy_s / tr.window_s)
    if kind != "module_ms_per":
        raise ValueError(f"device_trace does not know kind {kind!r}")
    ms = tr.module_seconds(modules) * 1e3
    n = tr.module_count(modules)
    if not n:
        return None
    rounds = ctx.trace_rounds or []
    if per == "call":
        return ms / n
    if per == "step":
        steps = [getattr(r, steps_field) for r in rounds
                 if getattr(r, steps_field) > 0]
        return None if not steps else ms / (n * sum(steps) / len(steps))
    if per == "ktok":
        toks = sum(getattr(r, tokens_field) for r in rounds)
        return None if not toks else ms / (toks / 1000.0)
    raise ValueError(f"device_trace does not know per {per!r}")
