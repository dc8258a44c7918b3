"""Roofline shares of a model whose recurrent layers are state-space
layers beside attention layers, counted from the configuration file
alone (``harness/costs_ssd.py``).

args: ``modules`` (regular expression of the XLA modules), and

- without ``scope``: the whole decode STEP's share — ``latent_roofline``
  over ``costs_ssd.decode_step``, the same device times and the same
  mean rows and cached tokens;
- ``scope`` (as ``device_scope`` takes it) and ``of`` "step": the share
  of the operations under that scope in a decode step — the recurrence
  and its convolution — against ``costs_ssd.state_step`` of the mean
  LIVE rows: the work, whatever implements it (a program that also
  reads and writes idle slots' state reads lower);
- ``scope`` and ``of`` "chunks": the same scopes in the chunk programs
  against ``costs_ssd.state_chunks`` of the tokens and rows the traced
  interval's executions HOLD, as ``hyper_roofline.held`` takes them.

Leaves ``ctx.notes["ssd_roofline"]`` (``step``, or ``of``) with the
bound that binds. Returns None where the configuration has no
state-space layers (``linear_decay`` "ssd"), where the trace holds no
such module, scope or program shape — a program without the ``ssd_*``
scopes: the parent's —, or where no decode round ran. A reading over
100 % means the count is too high or the scope misses operations.
"""

from __future__ import annotations

from benchmarks.harness import costs, costs_ssd
from benchmarks.readers import device_scope, device_trace, hyper_roofline


def read(ctx, modules, scope=None, of="step"):
    model = ctx.cell.config["model"]
    if not (model.get("full_attention_interval")
            and model.get("linear_decay") == "ssd"):
        return None
    if scope is None or of == "step":
        occ = ctx.mean_occupancy(sum)
        if occ is None:
            return None
        rows, kv_tokens = occ
        extra = {"mean_rows": rows, "mean_kv_tokens": kv_tokens}
    if scope is None:
        ms = device_trace.read(ctx, "module_ms_per", modules=modules,
                               per="step")
        cost = costs_ssd.decode_step(
            model, ctx.cell.config.get("weight_quant", ""), rows, kv_tokens)
        key = "step"
    elif of == "step":
        ms = device_scope.read(ctx, scope, modules, per="step")
        cost, key = costs_ssd.state_step(model, rows), "state_step"
    elif of == "chunks":
        got = hyper_roofline.held(ctx, modules, scope)
        if not got:
            return None
        ms, extra = got
        cost = costs_ssd.state_chunks(model, extra["tokens"], extra["rows"])
        key = "state_chunks"
    else:
        raise ValueError(f"ssd_roofline does not know of {of!r}")
    if not ms:
        return None
    least = costs.least_seconds(cost, ctx.peaks)
    ctx.notes.setdefault("ssd_roofline", {})[key] = {
        "bound": least["bound"], "least_ms": least["seconds"] * 1e3,
        "ms": ms, **extra, **cost}
    return 100.0 * least["seconds"] * 1e3 / ms
