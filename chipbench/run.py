"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are read from
``BENCHMARK.json`` and the files it names under ``chipbench/``.  The run
stands on TPU chips or not at all: with no TPU, or fewer chips than the
cell asks for, it exits nonzero and prints no result.  Set-up builds the
inputs from the seed, starts the system and warms every shape the window
uses; then ``--trace 0`` measures the cell's end-to-end metrics over
``--seconds``, and ``--trace 1`` profiles a shorter window of its own and
reads the cell's per-layer metrics from it.  Either way the rows the
window produced are then compared with the plain reference, each number
beside its limit.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), then ``checks``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def finite(x):
    """A number for the JSON line; not-finite reads as null."""
    x = float(x)
    return x if math.isfinite(x) else None


def per_layer(cell, work: dict, reduced: dict, peaks: dict) -> dict:
    """Each of the cell's per-layer metrics its reader finds something
    for; a reader's note goes to standard error."""
    from chipbench import spec

    reading = Reading(reduced, work, peaks)
    out = {}
    for m in cell.per_layer:
        got = spec.load_reader(m["name"])(reading)
        if isinstance(got, tuple):
            got, note = got
            print(f"{m['name']}: {note}", file=sys.stderr, flush=True)
        if got is not None:
            out[m["name"]] = {"value": float(got), "unit": m["unit"]}
    return out


class Reading:
    """What a per-layer reader gets: the reduced trace, the driver's work
    counts for the traced window, and the chip's published peaks."""

    def __init__(self, trace: dict, work: dict, peaks: dict):
        self.trace, self.work, self.peaks = trace, work, peaks


def run(cell, seed: int, seconds: float, trace: int,
        keep_trace: str | None = None, need_chip: bool = True) -> dict:
    """One run of ``cell``; returns the result line.  ``need_chip=False``
    skips the look for a chip (for the harness's own tests on the CPU)."""
    from chipbench import check, device, spec
    from chipbench import trace as tr
    from chipbench.drivers import driver_for

    if not os.path.isdir(os.path.join(cell.root, "src", "repro")):
        print(f"chipbench: no system under test at {cell.root}/src/repro",
              file=sys.stderr)
        raise SystemExit(2)
    import jax

    devices = jax.devices()
    info = device.guard(devices, cell.chips) if need_chip else {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}
    used = devices[:cell.chips]
    device.use_compile_cache(jax, cell.root)
    log = device.CompileLog(jax)
    drv = driver_for(cell.traffic["kind"])(cell, seed, seconds)
    try:
        drv.setup()
        setup_s = time.perf_counter() - T_START
        c0, j0 = log.snapshot(), drv.ses.compile_stats()["total"]
        if trace:
            tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
            try:
                with tr.record(tmp):
                    work = drv.traced()
                reduced = tr.reduce_xplane(tr.xplane_path(tmp))
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
            if keep_trace:
                tr.save(reduced, keep_trace)
            metrics = per_layer(cell, work, reduced,
                                spec.peaks(info["kind"]) if need_chip
                                else {"flops_per_s": 1.0,
                                      "hbm_bytes_per_s": 1.0})
            info["busy_s"] = tr.mean_busy_ns(reduced) / 1e9
            info["window_s"] = tr.window_ns(reduced) / 1e9
            breakdown = {"device_ops": tr.top_ops(reduced),
                         "idle_gaps": tr.idle_gaps(reduced)}
        else:
            got = drv.window(seconds)
            metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
            for m in cell.end_to_end:
                if m["name"] != "setup_s":
                    metrics[m["name"]] = {"value": finite(got[m["name"]]),
                                          "unit": m["unit"]}
        c1, j1 = log.snapshot(), drv.ses.compile_stats()["total"]
        print(f"compiles inside the window: {c1[0] - c0[0]} backend "
              f"compiles, {c1[1] - c0[1]} cache loads, {j1 - j0} new jit "
              f"entries (set-up {setup_s:.3f} s)", file=sys.stderr,
              flush=True)
        info["memory_peak_bytes"] = device.memory_peak(used)
        numbers = drv.numbers()
        attempted, failed = drv.counts()
    finally:
        drv.close()

    correct, checks = check.verdict(numbers, cell.config["limits"])
    check.report(checks)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": info}
    if trace:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                      for k, v in checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the reduced trace (gzip JSON) here")
    args = ap.parse_args(argv)

    from chipbench import spec

    cell = spec.Cell(args.workload)
    line = run(cell, args.seed, args.seconds, args.trace, args.keep_trace)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
