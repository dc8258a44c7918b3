"""From a profiler trace (``.xplane.pb``) to numbers: device busy time,
time per XLA module, time per device operation, idle gaps.

Read with ``jax.profiler.ProfileData`` alone. A TPU trace has one plane
per chip (``/device:TPU:<n>``) whose lines include ``XLA Modules`` (one
event per executed program, named ``jit_<fn>(<id>)``) and ``XLA Ops``
(one event per operation inside it). Busy time is the union of the
operation intervals (of the module intervals where a trace has no
operation line), so overlapping events are never counted twice.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE = r"^/device:TPU:\d+$"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_ID_SUFFIX = re.compile(r"\(\d+\)$")
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict          # line name -> [(event name, start_ns, dur_ns)]


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> list:
    """Every plane of the trace, events as plain tuples."""
    from jax.profiler import ProfileData
    return planes_of(ProfileData.from_file(path))


def planes_of(profile) -> list:
    out = []
    for plane in profile.planes:
        lines: dict = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
        out.append(Plane(plane.name, lines))
    return out


def describe(planes: Iterable[Plane]) -> list:
    """Plane and line names with event counts — printed on an earlier
    line of a traced run, so a reader can see what the trace holds."""
    return [{"plane": p.name,
             "lines": {n: len(ev) for n, ev in p.lines.items()}}
            for p in planes]


def module_name(event_name: str) -> str:
    """``jit_decode_round(123)`` -> ``jit_decode_round``."""
    return _ID_SUFFIX.sub("", event_name)


def op_name(event_name: str) -> str:
    """A TPU trace names an operation by its whole HLO line
    (``%fusion.3 = bf16[...] fusion(...)``): keep the name before the
    ``=``, as the trace prints it, without the ``%``."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:120]


def merge(intervals: Iterable[tuple]) -> list:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _spans(events: list) -> list:
    return [(s, s + d) for _, s, d in events]


@dataclasses.dataclass
class DeviceReduction:
    plane: str
    busy_s: float
    span_s: float                 # first event start to last event end
    module_s: dict                # module -> seconds
    module_n: dict                # module -> executions
    op_s: dict                    # operation -> seconds
    gaps: list                    # (label, seconds), longest first


def reduce_plane(plane: Plane) -> DeviceReduction:
    modules = plane.lines.get(MODULES_LINE, [])
    ops = plane.lines.get(OPS_LINE, [])
    busy = merge(_spans(ops or modules))
    busy_ns = sum(e - s for s, e in busy)
    span_ns = (busy[-1][1] - busy[0][0]) if busy else 0.0
    module_s: dict = {}
    module_n: dict = {}
    for name, _, dur in modules:
        key = module_name(name)
        module_s[key] = module_s.get(key, 0.0) + dur * 1e-9
        module_n[key] = module_n.get(key, 0) + 1
    op_s: dict = {}
    for name, _, dur in ops:
        key = op_name(name)
        op_s[key] = op_s.get(key, 0.0) + dur * 1e-9
    # idle gaps, labelled by the modules on either side
    mods = sorted((s, s + d, module_name(n)) for n, s, d in modules)
    starts = [m[0] for m in mods]

    def label(gap_start: float, gap_end: float) -> str:
        i = bisect.bisect_right(starts, gap_start) - 1
        if i >= 0 and mods[i][1] >= gap_end:
            return f"inside {mods[i][2]}"
        prev = mods[i][2] if i >= 0 else "start"
        nxt = mods[i + 1][2] if i + 1 < len(mods) else "end"
        return f"{prev}->{nxt}"

    gaps = [(label(a[1], b[0]), (b[0] - a[1]) * 1e-9)
            for a, b in zip(busy, busy[1:])]
    return DeviceReduction(plane.name, busy_ns * 1e-9, span_ns * 1e-9,
                           module_s, module_n, op_s,
                           sorted(gaps, key=lambda g: -g[1]))


@dataclasses.dataclass
class TraceReduction:
    devices: list                 # DeviceReduction per chip
    window_s: float               # the traced window, by the host clock

    @property
    def busy_s(self) -> float:
        """Mean over the chips used."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def module_seconds(self, pattern: str) -> float:
        """Device seconds of modules whose name matches ``pattern``
        (a regular expression, searched), mean over chips."""
        rx = re.compile(pattern)
        return sum(s for d in self.devices for m, s in d.module_s.items()
                   if rx.search(m)) / len(self.devices)

    def module_count(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(n for d in self.devices for m, n in d.module_n.items()
                   if rx.search(m)) / len(self.devices)

    def breakdown(self, top: int = 10) -> dict:
        """The contract's ``breakdown``: the operations that took most
        device time, and where the device sat idle longest (summed by
        the modules on either side of the gap), first chip."""
        d = self.devices[0]
        # a loop or branch is one event around the operations inside
        # it: list what it holds, not the wrapper
        leaves = {n: s for n, s in d.op_s.items()
                  if not n.startswith(_CONTAINERS)}
        ops = sorted(leaves.items(), key=lambda kv: -kv[1])[:top]
        if not ops:
            ops = sorted(d.module_s.items(), key=lambda kv: -kv[1])[:top]
        by_label: dict = {}
        for label, s in d.gaps:
            by_label[label] = by_label.get(label, 0.0) + s
        head = max(0.0, self.window_s - d.span_s)
        if head > 0:
            by_label["window edges (before first / after last op)"] = head
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce(planes: Iterable[Plane],
           window_s: Optional[float] = None) -> Optional[TraceReduction]:
    """None where the trace holds no device plane with events."""
    rx = re.compile(DEVICE_PLANE)
    devices = [reduce_plane(p) for p in planes if rx.search(p.name)]
    devices = [d for d in devices if d.busy_s > 0]
    if not devices:
        return None
    # the device keeps recording while the profiler starts and stops, so
    # the trace's own span can outlast the host's stamps: the traced
    # window is the longer of the two, and busy time never exceeds it
    span = max(d.span_s for d in devices)
    window_s = span if window_s is None else max(float(window_s), span)
    return TraceReduction(devices, float(window_s))
