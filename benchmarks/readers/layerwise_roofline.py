"""A decode step's, or ONE of its stages', share of its roofline with
the step counted layer by layer (``harness/costs_layerwise.py``: a
layer is dense or has experts, an expert has its own width, a shared
expert and a gate matrix are counted where the model group names them)
— ``roofline`` and ``stage_roofline`` for a model whose layers are not
all alike, over the same device times and the same mean rows and
attended tokens.

args: ``modules`` (regular expression of the decode round's module);
for a stage also ``stage`` (``costs.STAGES``) and ``scope`` as
``device_scope`` takes it. Leaves ``ctx.notes["layerwise_roofline"]``
(``step``, or the stage's name) with the bound that binds. A reading
over 100 % means the count is too high or the scope misses operations.
"""

from benchmarks.harness import costs, costs_layerwise
from benchmarks.readers import device_scope, device_trace


def read(ctx, modules, stage=None, scope=None):
    if stage is None:
        ms = device_trace.read(ctx, "module_ms_per", modules=modules,
                               per="step")
    else:
        ms = device_scope.read(ctx, scope, modules, per="step")
    model = ctx.cell.config["model"]
    occ = ctx.mean_occupancy(lambda c: costs.attended_tokens(model, c))
    if not ms or occ is None:
        return None
    rows, kv_tokens = occ
    quant = ctx.cell.config.get("weight_quant", "")
    if stage is None:
        cost = costs_layerwise.decode_step(model, quant, rows, kv_tokens)
    else:
        cost = costs_layerwise.decode_stage(model, quant, stage, rows,
                                            kv_tokens)
    least = costs.least_seconds(cost, ctx.peaks)
    ctx.notes.setdefault("layerwise_roofline", {})[stage or "step"] = {
        "bound": least["bound"], "least_ms": least["seconds"] * 1e3,
        "ms": ms, "mean_rows": rows, "mean_kv_tokens": kv_tokens, **cost}
    return 100.0 * least["seconds"] * 1e3 / ms
