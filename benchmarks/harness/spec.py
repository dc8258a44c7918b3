"""Where the benchmark's data lives and how a cell is put together.

``BENCHMARK.json`` names cells, configurations and metrics; everything
that belongs to one of them is a file of its own under the data
directory, found by that name:

    configs/<config>.json        the model and engine sizes as run
    traffic/<mix>.json           parameters of the one general generator
    workloads/<cell>.json        configuration + mix + rate or clients
    layer_metrics/<metric>.json  layer, unit, reader + arguments (which
                                 cells report it and what it ``moves``
                                 there are BENCHMARK.json's to say)

A later PR adds files and ``BENCHMARK.json`` entries; nothing here is
edited for a new cell.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """The benchmark's data files disagree or are missing."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing benchmark data file {path}") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # configs/<config>.json
    mix_name: str
    mix: dict               # traffic/<mix>.json
    workload: dict          # workloads/<cell>.json
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list         # layer_metrics/<name>.json, merged entries


def check_config(config: dict, path: str) -> None:
    """A configuration names its plain reference: a file under
    ``references/``, which decides ``correct``."""
    ref = config.get("reference")
    if not isinstance(ref, str) or not NAME_RE.match(ref):
        raise SpecError(f"{path}: a configuration names its plain "
                        f"reference (\"reference\": \"<name>\" loads "
                        f"benchmarks/references/<name>.py)")
    if not os.path.exists(os.path.join(HERE, "references", ref + ".py")):
        raise SpecError(f"{path}: no benchmarks/references/{ref}.py")


class Spec:
    def __init__(self, benchmark_json: str | None = None,
                 data_dir: str | None = None):
        self.path = benchmark_json or os.path.join(REPO, "BENCHMARK.json")
        self.data_dir = data_dir or HERE
        self.doc = load_json(self.path)

    def _data(self, kind: str, name: str) -> dict:
        if not NAME_RE.match(name):
            raise SpecError(f"bad name {name!r}")
        return load_json(os.path.join(self.data_dir, kind, name + ".json"))

    @staticmethod
    def _in_cell(metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def cell_names(self) -> list:
        return [w["name"] for w in self.doc["workloads"]]

    def layer_metric_file(self, name: str) -> str:
        """The file a per-layer metric is read by: its own name, or for
        ``<group>.<quantity>`` with no file of its own the quantity's.
        One quantity whose cells report different end-to-end metrics is
        several BENCHMARK.json entries (each with its own ``moves`` and
        ``workloads``) over one file."""
        own = os.path.join(self.data_dir, "layer_metrics", name + ".json")
        if "." in name and not os.path.exists(own):
            return name.split(".", 1)[1]
        return name

    def layer_metric(self, name: str) -> dict:
        return self._data("layer_metrics", self.layer_metric_file(name))

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.doc["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise SpecError(f"no cell {name!r} in {self.path}; known: "
                            f"{self.cell_names()}")
        cfg_entry = next((c for c in self.doc["configs"]
                          if c["name"] == entry["config"]), None)
        if cfg_entry is None:
            raise SpecError(f"cell {name!r} names unknown configuration "
                            f"{entry['config']!r}")
        config = load_json(os.path.join(os.path.dirname(self.path),
                                        cfg_entry["file"]))
        check_config(config, cfg_entry["file"])
        workload = self._data("workloads", name)
        if (workload["config"], workload["traffic"]) != (
                entry["config"], entry["traffic"]):
            raise SpecError(f"workloads/{name}.json and BENCHMARK.json "
                            f"disagree on configuration or traffic")
        per_layer = []
        for m in self.doc["per_layer"]:
            if self._in_cell(m, name):
                per_layer.append({**self.layer_metric(m["name"]), **m})
        return Cell(
            name=name, chips=int(entry["chips"]),
            config_name=entry["config"], config=config,
            mix_name=entry["traffic"],
            mix=self._data("traffic", entry["traffic"]),
            workload=workload,
            end_to_end=[m for m in self.doc["end_to_end"]
                        if self._in_cell(m, name)],
            per_layer=per_layer)
