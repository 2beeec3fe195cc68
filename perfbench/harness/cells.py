"""Finds everything a cell is made of by the names in BENCHMARK.json.

A later PR adds a cell, a configuration, a traffic mix or a per-layer metric
as new files and new entries; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys


class BenchmarkError(SystemExit):
    """A fault of the benchmark's own files or of the machine: the run ends
    with a non-zero code and prints no result."""

    def __init__(self, msg):
        print(f"perfbench: {msg}", file=sys.stderr)
        super().__init__(2)


def _json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchmarkError(f"cannot read {path}: {e}") from None


@dataclasses.dataclass
class Cell:
    root: str            # the checkout
    home: str            # <root>/perfbench
    name: str
    chips: int
    config_name: str
    config: dict         # the configuration as it is run
    traffic_name: str
    traffic: dict        # kind + parameters of the mix
    doc: dict            # why, who, limits of the comparison
    end_to_end: list     # this cell's entries of BENCHMARK.json
    per_layer: list
    run_seconds: int

    def module(self, kind, name):
        """``perfbench/<kind>/<name>.py``, loaded by its path."""
        path = os.path.join(self.home, kind, name + ".py")
        if not os.path.isfile(path):
            raise BenchmarkError(f"{kind} {name!r} has no file {path}")
        modname = f"perfbench_{kind}_{name.replace('.', '_').replace('-', '_')}"
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def peaks(self, device_kind):
        table = _json(os.path.join(self.home, "harness", "peaks.json"))
        if device_kind not in table["kinds"]:
            raise BenchmarkError(
                f"device_kind {device_kind!r} is not in harness/peaks.json "
                f"({sorted(table['kinds'])}): add its published peaks")
        return table["kinds"][device_kind]


def _mine(entries, cell):
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def load(root, workload):
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    home = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json "
                             f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchmarkError(f"workload {workload!r} names config "
                             f"{w['config']!r}, which BENCHMARK.json lacks")
    return Cell(
        root=root, home=home, name=workload, chips=int(w["chips"]),
        config_name=w["config"],
        config=_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic_name=w["traffic"],
        traffic=_json(os.path.join(home, "traffic", w["traffic"] + ".json")),
        doc=_json(os.path.join(home, "workloads", workload + ".json")),
        end_to_end=_mine(bench["end_to_end"], workload),
        per_layer=_mine(bench["per_layer"], workload),
        run_seconds=int(bench["run_seconds"]))
