"""A decode step's, or ONE of its stages', share of its roofline for a
model with a latent cache, an expert share and learned sparse attention,
counted from the configuration file alone
(``harness/costs_sparse_latent.py``) — ``latent_roofline`` over another
count and the same device times. The two context sums are taken from the
contexts themselves, instant by instant: their sum (what the indexer
scores) and the sum of min(context, index_topk) (what the attention
reads), never from a mean context.

args: ``modules`` (regular expression of the decode round's module);
for a stage also ``stage`` (``costs.STAGES``) and ``scope`` as
``device_scope`` takes it — the ``attn`` stage's scope names the
indexer's and the selection's scopes beside the read's. Leaves
``ctx.notes["sparse_latent_roofline"]`` (``step``, or the stage's name)
with the bound that binds. Returns None where the configuration has no
learned selection, where the trace holds no such module or scope (a
program without the scopes: the metric is left out), or where no decode
round ran. A reading over 100 % means the count is too high or the scope
misses operations.
"""

from benchmarks.harness import costs, costs_sparse_latent
from benchmarks.readers import device_scope, device_trace


def read(ctx, modules, stage=None, scope=None):
    model = ctx.cell.config["model"]
    if not (model.get("kv_lora_rank") and model.get("index_topk")):
        return None
    if stage is None:
        ms = device_trace.read(ctx, "module_ms_per", modules=modules,
                               per="step")
    else:
        ms = device_scope.read(ctx, scope, modules, per="step")
    occ = ctx.mean_occupancy(sum)
    chosen = ctx.mean_occupancy(
        lambda contexts: costs_sparse_latent.selected_tokens(model,
                                                             contexts))
    if not ms or occ is None or chosen is None:
        return None
    rows, indexed = occ
    selected = chosen[1]
    quant = ctx.cell.config.get("weight_quant", "")
    if stage is None:
        cost = costs_sparse_latent.decode_step(model, quant, rows, indexed,
                                               selected)
    else:
        cost = costs_sparse_latent.decode_stage(model, quant, stage, rows,
                                                indexed, selected)
    least = costs.least_seconds(cost, ctx.peaks)
    ctx.notes.setdefault("sparse_latent_roofline", {})[stage or "step"] = {
        "bound": least["bound"], "least_ms": least["seconds"] * 1e3,
        "ms": ms, "mean_rows": rows, "mean_indexed_tokens": indexed,
        "mean_selected_tokens": selected, **cost}
    return 100.0 * least["seconds"] * 1e3 / ms
