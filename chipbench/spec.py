"""What a run is asked to do, read from data: ``BENCHMARK.json`` names the
cell, its configuration and its traffic mix; each of those is a file of
its own under ``chipbench/`` that the harness finds by that name.

    chipbench/configs/<config>.json    CNNs, boards, precision, limits
    chipbench/traffic/<traffic>.json   the mix: its kind and parameters
    chipbench/layer_metrics/<metric>.py  one per-layer reader each
"""
from __future__ import annotations

import importlib.util
import json
import os

#: the checkout: the directory that holds ``chipbench/``
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name: str, bench: dict | None = None,
                 root: str = ROOT):
        bench = benchmark(root) if bench is None else bench
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                           f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config = _json(os.path.join(
            root, "chipbench", "configs", f"{self.entry['config']}.json"))
        self.traffic = _json(os.path.join(
            root, "chipbench", "traffic", f"{self.entry['traffic']}.json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in moved)]
        self.root = root


def load_reader(name: str, root: str = ROOT):
    """The ``read`` function of ``chipbench/layer_metrics/<name>.py``."""
    path = os.path.join(root, "chipbench", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """Published peaks of one chip; a kind missing from the table is an
    error, never a default."""
    table = _json(os.path.join(root, "chipbench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"chipbench/peaks.json")
    return table["devices"][device_kind]
