"""The comparison that decides ``correct``: rows the timed path produced,
against the plain reference (``chipbench/reference/mccm.py``) on the same
designs.  For each metric the worst relative gap over the rows is read;
the configuration file names the ones compared, each with its limit."""
from __future__ import annotations

import math
import sys

from .reference import mccm

#: short name of each reference metric in the numbers read
NUMBERS = {"latency_s": "latency", "throughput_ips": "throughput",
           "buffer_bytes": "buffer", "access_bytes": "access"}


def layers_of(config: dict, net: str) -> list:
    return [mccm.Layer(d) for d in config["nets"][net]]


def board_of(config: dict, board: str) -> mccm.Board:
    return mccm.Board(config["boards"][board])


def gaps(rows, layers, board) -> dict:
    """``rows``: (seg_end, seg_pipe, seg_nce, inter_pipe, got) with ``got``
    the program's {metric: value}.  Returns each metric's relative gaps
    |got − ref| / |ref|, one per row; a value that is missing or not
    finite reads as infinite."""
    out = {k: [] for k in NUMBERS}
    for seg_end, seg_pipe, seg_nce, inter, got in rows:
        want = mccm.evaluate(layers, board, seg_end, seg_pipe, seg_nce,
                             bool(inter))
        for k in NUMBERS:
            g = got.get(k)
            g = math.inf if g is None else float(g)
            out[k].append(abs(g - want[k]) / max(abs(want[k]), 1e-300)
                          if math.isfinite(g) else math.inf)
    return out


def summary(per_row: dict) -> dict:
    """The numbers read from per-row gaps: ``<metric>_worst``."""
    import numpy as np

    out = {}
    for k, name in NUMBERS.items():
        v = np.asarray(per_row[k], np.float64)
        out[f"{name}_worst"] = float(v.max()) if v.size else 0.0
    return out


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {value, limit}}): the gaps that ``limits`` names,
    each within its limit (value <= limit), and every count the driver
    read (failures: non-finite rows) exactly 0."""
    checks, ok = {}, True
    counts = {k: 0 for k in numbers if not k.endswith("_worst")}
    for name, limit in {**limits, **counts}.items():
        value = numbers[name]
        checks[name] = {"value": value, "limit": limit}
        ok &= value <= limit
    return ok, checks


def report(checks: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
