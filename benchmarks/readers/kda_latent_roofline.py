"""Roofline shares of a model whose recurrent layers decay a channel at
its own rate beside latent attention layers, counted from the
configuration file alone (``harness/costs_kda_latent.py``).

args: ``modules`` (regular expression of the XLA modules), and

- without ``scope``: the whole decode STEP's share — the modules' device
  time a step against ``costs_kda_latent.decode_step`` of the mean live
  rows and cached tokens;
- ``scope`` (as ``device_scope`` takes it) and ``of`` "step": the share
  of the operations under that scope in a decode step — the recurrence
  and its convolution — against ``costs_kda_latent.state_step`` of the
  mean LIVE rows: the work, whatever implements it (a program that also
  updates idle slots reads lower);
- ``scope`` and ``of`` "chunks": the same scopes in the chunk programs
  against ``costs_kda_latent.state_chunks`` of the tokens and rows the
  traced interval's executions HOLD, counted as
  ``hyper_roofline.program_tokens`` counts them.

Leaves ``ctx.notes["kda_latent_roofline"]`` (``step``, or ``of``) with
the bound that binds. Returns None where the configuration has no such
layers, where the trace holds no such module, scope or program shape (a
program without the ``kda_*`` scopes), or where no decode round ran. A
reading over 100 % means the count is too high or the scope misses
operations.
"""

from __future__ import annotations

import os
import re

from benchmarks.harness import costs, costs_kda_latent, trace
from benchmarks.harness.spec import REPO
from benchmarks.readers import device_scope, device_trace, hyper_roofline


def read(ctx, modules, scope=None, of="step"):
    model = ctx.cell.config["model"]
    if not (model.get("full_attention_interval")
            and model.get("linear_decay") == "channel"
            and model.get("kv_lora_rank")):
        return None
    if of not in ("step", "chunks"):
        raise ValueError(f"kda_latent_roofline does not know of {of!r}")
    if scope is None or of == "step":
        occ = ctx.mean_occupancy(sum)
        if occ is None:
            return None
        rows, kv_tokens = occ
        extra = {"mean_rows": rows, "mean_kv_tokens": kv_tokens}
    if scope is None:
        ms = device_trace.read(ctx, "module_ms_per", modules=modules,
                               per="step")
        cost = costs_kda_latent.decode_step(
            model, ctx.cell.config.get("weight_quant", ""), rows, kv_tokens)
        key = "step"
    elif of == "step":
        ms = device_scope.read(ctx, scope, modules, per="step")
        cost, key = costs_kda_latent.state_step(model, rows), "state_step"
    else:
        path = trace.find_xplane(os.path.join(REPO, ".bench_trace",
                                              ctx.cell.name))
        red = path and device_scope.reduce_scopes(path, modules)
        if not red:
            return None
        rx = re.compile(scope)
        hit = [s for p, s in red["by_path"].items() if rx.search(p)]
        buckets = tuple(ctx.cell.config["engine"].get("prefill_buckets", ()))
        held = hyper_roofline.program_tokens(path, modules, buckets)
        if not hit or held is None:
            return None
        ms = sum(hit) * 1e3
        # a row a prompt a program, each a whole bucket wide
        rows = held["tokens"] / max(buckets)
        cost = costs_kda_latent.state_chunks(model, held["tokens"], rows)
        extra, key = {**held, "rows": rows}, "state_chunks"
    if not ms:
        return None
    least = costs.least_seconds(cost, ctx.peaks)
    ctx.notes.setdefault("kda_latent_roofline", {})[key] = {
        "bound": least["bound"], "least_ms": least["seconds"] * 1e3,
        "ms": ms, **extra, **cost}
    return 100.0 * least["seconds"] * 1e3 / ms
