"""ONE named stage's share of its roofline inside the decode step: the
least time the chip could take for the stage (``costs.decode_stage``:
its bytes and operations for the mean active rows and the mean context
tokens they attend, as ``roofline`` takes them) over the stage's device
time (``device_scope``: leaf operations under the program's scope).

A GUIDE, not a judge: stages overlap on the device (a stage's weights
stream while another computes), so a stage can read high while the step
does not; the whole step's share (``roofline``) is the judge. A reading
over 100 % all the same means the stage's count is too high or its
scope misses part of its operations.

args: ``stage`` (``costs.STAGES``), ``scope`` and ``modules`` as
``device_scope`` takes them. Leaves ``ctx.notes["stage_roofline"]``.
"""

from benchmarks.harness import costs
from benchmarks.readers import device_scope


def read(ctx, stage, scope, modules):
    stage_ms = device_scope.read(ctx, scope, modules, per="step")
    model = ctx.cell.config["model"]
    occ = ctx.mean_occupancy(lambda c: costs.attended_tokens(model, c))
    if not stage_ms or occ is None:
        return None
    rows, kv_tokens = occ
    cost = costs.decode_stage(model, ctx.cell.config.get("weight_quant", ""),
                              stage, rows, kv_tokens)
    least = costs.least_seconds(cost, ctx.peaks)
    ctx.notes.setdefault("stage_roofline", {})[stage] = {
        "bound": least["bound"], "least_ms": least["seconds"] * 1e3,
        "stage_ms": stage_ms, "bytes": cost["bytes"], "flops": cost["flops"]}
    return 100.0 * least["seconds"] * 1e3 / stage_ms
