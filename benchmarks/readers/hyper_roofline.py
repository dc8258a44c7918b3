"""Roofline shares of a latent-cache expert model on hyper-connected
streams, counted from the configuration file alone
(``harness/costs_hyper.py``).

args: ``modules`` (regular expression of the XLA modules), and

- without ``scope``: the whole decode STEP's share — ``latent_roofline``
  over ``costs_hyper.decode_step`` (the block's count plus the residual
  path), the same device times and the same mean rows and cached tokens;
- with ``scope`` (as ``device_scope`` takes it): the share of the
  operations under that scope in those modules — the chunk programs'
  ``hc_pre`` + ``hc_post``; the ``H_pre`` mix is not among them where
  the compiler fuses it into the sublayer's pre-norm, whose fusion
  carries the sublayer's scope — against ``costs_hyper.hc_stage`` of the
  tokens the traced interval's executions HOLD. Each executed program
  carries rows x bucket tokens, read from the HLO the trace keeps of it
  (``program_tokens``): the rounds that BEGAN in the interval are other
  rounds than those whose programs ran in it (PERF.md section 7).

Leaves ``ctx.notes["hyper_roofline"]`` (``step``, or the scope) with the
bound that binds. Returns None where the configuration has no
``hc_mult``, where the trace holds no such module, scope or program
shape, or where no decode round ran. A reading over 100 % means the
count is too high or the scope misses operations.
"""

from __future__ import annotations

import os
import re

from benchmarks.harness import costs, costs_hyper, trace
from benchmarks.harness.spec import REPO
from benchmarks.readers import device_scope, device_trace
from benchmarks.readers.device_scope import _first, fields

S32 = 4             # xla_data.proto PrimitiveType


def _dims(shape) -> tuple:
    """ShapeProto.dimensions (3), packed or one by one."""
    out = []
    for f, wt, v in fields(shape):
        if f != 3:
            continue
        if wt == 0:
            out.append(v)
        else:
            i = 0
            while i < len(v):
                d, i = device_scope._varint(v, i)
                out.append(d)
    return tuple(out)


def program_params(path: str) -> dict:
    """module name as the device plane prints it -> [(element type,
    dims)] of the program's parameters, from the trace's
    ``/host:metadata`` plane: the walk of ``device_scope.scope_map``,
    then HloProto.hlo_module (1) > host_program_shape (4) > parameters
    (1) > element_type (2), dimensions (3)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for f, _, plane in fields(space):
        if f != 1:
            continue
        name = _first(plane, 2)
        if name is None or bytes(name) != b"/host:metadata":
            continue
        for g, _, entry in fields(plane):
            meta = _first(entry, 2) if g == 4 else None
            if meta is None:
                continue
            mod = _first(meta, 2)
            for h, _, v in fields(meta):
                blob = _first(v, 6) if h == 5 else None
                module = None if blob is None else _first(blob, 1)
                shape = None if module is None else _first(module, 4)
                if shape is None:
                    continue
                out[bytes(mod).decode()] = [
                    (_first(p, 2) or 0, _dims(p))
                    for k, _, p in fields(shape) if k == 1]
    return out


def program_tokens(path: str, modules: str, buckets) -> dict | None:
    """``{"tokens", "programs"}`` of the executions of modules matching
    ``modules`` on the first TPU plane: a chunk program's tokens are the
    shape of its int32 (rows, bucket) parameter, the only one whose
    second extent is a chunk bucket. None where an executed program shows
    no such parameter."""
    from jax.profiler import ProfileData
    rx, dev = re.compile(modules), re.compile(trace.DEVICE_PLANE)
    params = program_params(path)
    plane = next((p for p in ProfileData.from_file(path).planes
                  if dev.search(p.name)), None)
    if plane is None:
        return None
    tokens = programs = 0
    for line in plane.lines:
        if line.name != trace.MODULES_LINE:
            continue
        for e in line.events:
            if not rx.search(trace.module_name(e.name)):
                continue
            held = [d[0] * d[1] for t, d in params.get(e.name, ())
                    if t == S32 and len(d) == 2 and d[1] in buckets]
            if not held:
                return None
            tokens, programs = tokens + held[0], programs + 1
    return {"tokens": tokens, "programs": programs} if programs else None


def read(ctx, modules, scope=None):
    model = ctx.cell.config["model"]
    if not costs_hyper.streams(model):
        return None
    if scope is None:
        ms = device_trace.read(ctx, "module_ms_per", modules=modules,
                               per="step")
        occ = ctx.mean_occupancy(sum)
        if not ms or occ is None:
            return None
        rows, kv_tokens = occ
        cost = costs_hyper.decode_step(
            model, ctx.cell.config.get("weight_quant", ""), rows, kv_tokens)
        extra = {"mean_rows": rows, "mean_kv_tokens": kv_tokens}
        key = "step"
    else:
        path = trace.find_xplane(os.path.join(REPO, ".bench_trace",
                                              ctx.cell.name))
        red = path and device_scope.reduce_scopes(path, modules)
        if not red:
            return None
        rx = re.compile(scope)
        hit = [s for p, s in red["by_path"].items() if rx.search(p)]
        held = program_tokens(path, modules, tuple(
            ctx.cell.config["engine"].get("prefill_buckets", ())))
        if not hit or held is None:
            return None
        ms = sum(hit) * 1e3
        cost = costs_hyper.hc_stage(model, held["tokens"], held["programs"])
        extra, key = held, scope
    least = costs.least_seconds(cost, ctx.peaks)
    ctx.notes.setdefault("hyper_roofline", {})[key] = {
        "bound": least["bound"], "least_ms": least["seconds"] * 1e3,
        "ms": ms, **extra, **cost}
    return 100.0 * least["seconds"] * 1e3 / ms
