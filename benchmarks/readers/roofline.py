"""A decode step's share of its roofline: the least time the chip could
take for the step (``harness/costs.py``: the larger of bytes over peak
bytes/s and operations over peak FLOP/s, for the mean active rows and
the mean context tokens they attend over the traced interval — each
row's own context against each layer's window, ``attended_tokens`` —
both from the load generator's own stamps) over the step's device time
from the trace.

args: ``modules`` (regular expression of the decode round's module).
Leaves ``ctx.notes["roofline"]`` with the bound that binds.
"""

from benchmarks.harness import costs
from benchmarks.readers import device_trace


def read(ctx, modules):
    step_ms = device_trace.read(ctx, "module_ms_per", modules=modules,
                                per="step")
    model = ctx.cell.config["model"]
    occ = ctx.mean_occupancy(lambda c: costs.attended_tokens(model, c))
    if step_ms is None or occ is None:
        return None
    rows, kv_tokens = occ
    cost = costs.decode_step(model,
                             ctx.cell.config.get("weight_quant", ""),
                             rows, kv_tokens)
    least = costs.least_seconds(cost, ctx.peaks)
    ctx.notes["roofline"] = {
        "bound": least["bound"], "least_ms": least["seconds"] * 1e3,
        "t_bytes_ms": least["t_bytes"] * 1e3,
        "t_flops_ms": least["t_flops"] * 1e3, "step_ms": step_ms,
        "mean_rows": rows, "mean_kv_tokens": kv_tokens,
        "weight_bytes": cost["weight_bytes"], "kv_bytes": cost["kv_bytes"]}
    return 100.0 * least["seconds"] * 1e3 / step_ms
