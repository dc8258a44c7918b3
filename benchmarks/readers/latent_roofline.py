"""A decode step's, or ONE of its stages', share of its roofline for a
model with a latent cache and an expert share, counted from the
configuration file alone (``harness/costs_latent.py``) —
``layerwise_roofline`` over another count, the same device times and the
same mean rows and cached tokens.

args: ``modules`` (regular expression of the decode round's module);
for a stage also ``stage`` (``costs.STAGES``) and ``scope`` as
``device_scope`` takes it. Leaves ``ctx.notes["latent_roofline"]``
(``step``, or the stage's name) with the bound that binds. Returns None
where the configuration has no latent cache, where the trace holds no
such module or scope, or where no decode round ran. A reading over
100 % means the count is too high or the scope misses operations.
"""

from benchmarks.harness import costs, costs_latent
from benchmarks.readers import device_scope, device_trace


def read(ctx, modules, stage=None, scope=None):
    model = ctx.cell.config["model"]
    if not model.get("kv_lora_rank"):
        return None
    if stage is None:
        ms = device_trace.read(ctx, "module_ms_per", modules=modules,
                               per="step")
    else:
        ms = device_scope.read(ctx, scope, modules, per="step")
    occ = ctx.mean_occupancy(sum)        # no window: the contexts' sum
    if not ms or occ is None:
        return None
    rows, kv_tokens = occ
    quant = ctx.cell.config.get("weight_quant", "")
    if stage is None:
        cost = costs_latent.decode_step(model, quant, rows, kv_tokens)
    else:
        cost = costs_latent.decode_stage(model, quant, stage, rows,
                                         kv_tokens)
    least = costs.least_seconds(cost, ctx.peaks)
    ctx.notes.setdefault("latent_roofline", {})[stage or "step"] = {
        "bound": least["bound"], "least_ms": least["seconds"] * 1e3,
        "ms": ms, "mean_rows": rows, "mean_kv_tokens": kv_tokens, **cost}
    return 100.0 * least["seconds"] * 1e3 / ms
