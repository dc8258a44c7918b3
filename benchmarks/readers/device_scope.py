"""Device time under a ``jax.named_scope`` of the program.

The trace names an operation by its HLO line (``%fusion.296 = ...``),
which says nothing of attention or FFN. The profiler also keeps, in
the plane ``/host:metadata`` of the same ``.xplane.pb``, the optimised
HLO of every module that ran (``run.py`` leaves ``enable_hlo_proto`` at
its default, on), keyed by the module's name as the device plane
prints it (``jit_decode_round(<id>)``). Each instruction there carries
``metadata.op_name`` = ``jit(decode_round)/.../attn/dot_general`` (a
fusion carries its root's): the scope path. ``jax.profiler.ProfileData``
does not hand out event metadata, so this reader walks the protobuf
wire format itself (field numbers of ``xplane.proto`` and ``hlo.proto``)
for that one plane, and takes the events from ``ProfileData``.

args: ``scope`` (regular expression searched in an operation's scope
path), ``modules`` (regular expression of the XLA module), ``per``
``"step"`` | ``"ktok"`` | ``"call"`` as ``device_trace`` divides.

The value is (device seconds of leaf operations under the scope ÷
device seconds of the matching modules) x what ``device_trace`` reads
for those modules, so the scopes of one module add up to its
``decode_step_ms``. Loops, branches and calls are wrappers around the
operations inside them and are left out, by their opcode. Leaves
``ctx.notes["device_scope"][modules]``: ms per unit of every stage in
``STAGES`` (an operation counts under the first stage on its path), of
operations under no stage (``unscoped``, with ``unscoped_top``: the
ends of the paths that make most of it), and of module time outside
any operation.
Returns None where the trace holds no operation under the scope (a
program without scopes).
"""

from __future__ import annotations

import bisect
import functools
import os
import re

from benchmarks.harness import trace
from benchmarks.harness.spec import REPO
from benchmarks.readers import device_trace

#: The program's stage names (``models/llama.py`` ``SCOPES``; a test
#: holds the two equal), outermost first.
STAGES = ("embed", "attn_proj", "attn", "mlp", "moe_route", "moe_experts",
          "tail")
_WRAPPERS = ("while", "conditional", "call")


# ------------------------------------------------------ protobuf, by hand

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: a varint as an
    int, a length-delimited field as a memoryview, fixed ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 1:
            val, i = None, i + 8
        elif wt == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, val


def _first(buf, num):
    return next((v for f, _, v in fields(buf) if f == num), None)


def _hlo_scopes(hlo_proto) -> dict:
    """instruction name -> (opcode, op_name) over every computation of
    HloProto.hlo_module (1): computations (3) > instructions (2) > name
    (1), opcode (2), metadata (7) > op_name (2)."""
    out = {}
    module = _first(hlo_proto, 1)
    if module is None:
        return out
    for f, _, comp in fields(module):
        if f != 3:
            continue
        for g, _, ins in fields(comp):
            if g != 2:
                continue
            name = opcode = path = ""
            for h, _, v in fields(ins):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 2:
                    opcode = bytes(v).decode()
                elif h == 7:
                    p = _first(v, 2)
                    path = "" if p is None else bytes(p).decode()
            out[name] = (opcode, path)
    return out


def scope_map(path: str) -> dict:
    """module name as the device plane prints it -> {instruction name:
    (opcode, scope path)}, from the trace's ``/host:metadata`` plane:
    XSpace.planes (1) > XPlane.name (2), event_metadata (4, a map entry:
    value 2) > XEventMetadata.name (2), stats (5) > XStat.bytes_value
    (6)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, _, plane in fields(space):
        if f != 1:
            continue
        name = _first(plane, 2)
        if name is None or bytes(name) != b"/host:metadata":
            continue
        for g, _, entry in fields(plane):
            if g != 4:
                continue
            meta = _first(entry, 2)
            if meta is None:
                continue
            mod, protos = "", []
            for h, _, v in fields(meta):
                if h == 2:
                    mod = bytes(v).decode()
                elif h == 5:
                    blob = _first(v, 6)
                    if blob is not None:
                        protos.append(blob)
            for blob in protos:
                out.setdefault(mod, {}).update(_hlo_scopes(blob))
    return out


# ------------------------------------------------------------ reduction

def stage_of(path: str) -> str:
    """The first of ``STAGES`` among the path's components, or ""."""
    for part in path.split("/"):
        if part in STAGES:
            return part
    return ""


def reduce_profile(profile, scopes: dict, modules: str) -> dict | None:
    """Over the executions of modules matching ``modules`` on the first
    TPU plane of a ``ProfileData``, with ``scopes`` as ``scope_map``
    gives it: ``module_s``, ``leaf_s``, and ``by_path`` (scope path ->
    seconds of leaf operations). None without a device plane."""
    rx, dev = re.compile(modules), re.compile(trace.DEVICE_PLANE)
    plane = next((p for p in profile.planes if dev.search(p.name)), None)
    if plane is None:
        return None
    mods, ops = [], []
    for line in plane.lines:
        if line.name == trace.MODULES_LINE:
            mods += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for e in line.events]
        elif line.name == trace.OPS_LINE:
            ops += [(e.start_ns, e.duration_ns, e.name) for e in line.events]
    mods = sorted(m for m in mods if rx.search(trace.module_name(m[2])))
    starts = [m[0] for m in mods]
    by_path: dict = {}
    leaf_ns = 0.0
    for start, dur, name in ops:
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= mods[i][1]:
            continue
        opcode, scope = scopes.get(mods[i][2], {}).get(
            trace.op_name(name), ("", ""))
        if opcode in _WRAPPERS:
            continue
        leaf_ns += dur
        by_path[scope] = by_path.get(scope, 0.0) + dur * 1e-9
    return {"module_s": sum(e - s for s, e, _ in mods) * 1e-9,
            "leaf_s": leaf_ns * 1e-9, "by_path": by_path}


@functools.lru_cache(maxsize=4)
def _load(path: str) -> tuple:
    from jax.profiler import ProfileData
    return ProfileData.from_file(path), scope_map(path)


@functools.lru_cache(maxsize=16)
def reduce_scopes(path: str, modules: str) -> dict | None:
    """``reduce_profile`` of the trace file at ``path``, once a file
    and pattern."""
    return reduce_profile(*_load(path), modules)


def read(ctx, scope, modules, per="step"):
    per_unit = device_trace.read(ctx, "module_ms_per", modules=modules,
                                 per=per)
    path = trace.find_xplane(os.path.join(REPO, ".bench_trace",
                                          ctx.cell.name))
    if per_unit is None or path is None:
        return None
    red = reduce_scopes(path, modules)
    if not red or not red["module_s"]:
        return None
    unit = per_unit / red["module_s"]        # ms per unit per device second
    stages: dict = {}
    loose: dict = {}
    for p, s in red["by_path"].items():
        k = stage_of(p) or "unscoped"
        stages[k] = stages.get(k, 0.0) + s * unit
        if k == "unscoped":
            # what it is: the last two components of its path
            tail = "/".join(p.split("/")[-2:]) or "(no metadata)"
            loose[tail] = loose.get(tail, 0.0) + s * unit
    stages["outside_operations"] = (red["module_s"] - red["leaf_s"]) * unit
    ctx.notes.setdefault("device_scope", {})[modules] = dict(
        stages, per=per, module_ms=per_unit, unscoped_top=sorted(
            ([k, v] for k, v in loose.items()), key=lambda kv: -kv[1])[:4])
    rx = re.compile(scope)
    hit = [s for p, s in red["by_path"].items() if rx.search(p)]
    return sum(hit) * unit if hit else None
