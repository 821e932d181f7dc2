"""Benchmark harness: one entry per paper table/figure plus the CPU
speed gates.  ``python -m benchmarks.run [--quick]``.

Prints ``name,seconds,checks`` CSV at the end; artifacts land in
``artifacts/bench/*.json``.
"""
from __future__ import annotations

import argparse
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduce the Fig10 DSE sample to 10k designs")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args(argv)

    from . import (eval_speed, fig5_fig8_fronts, fig6_fig7_breakdown,
                   fig9_fig10_dse, multinet_fronts, multinet_hybrid,
                   perf_gate, tab1_arch_comparison, tab4_accuracy,
                   tab5_best_arch)

    entries = [
        ("tab1_arch_comparison", tab1_arch_comparison.run, {}),
        ("tab4_accuracy", tab4_accuracy.run, {}),
        ("tab5_best_arch", tab5_best_arch.run, {}),
        ("fig5_fig8_fronts", fig5_fig8_fronts.run, {}),
        ("fig6_fig7_breakdown", fig6_fig7_breakdown.run, {}),
        ("fig9_fig10_dse", fig9_fig10_dse.run,
         {"n_sample": 10_000 if args.quick else 100_000}),
        ("multinet_fronts", multinet_fronts.run, {"quick": args.quick}),
        ("multinet_hybrid", multinet_hybrid.run, {"quick": args.quick}),
        ("eval_speed", eval_speed.run, {}),
        ("perf_gate", perf_gate.run, {"quick": args.quick}),
    ]
    if args.only:
        keep = set(args.only.split(","))
        entries = [e for e in entries if e[0] in keep]

    results = []
    failed = 0
    for name, fn, kw in entries:
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            out = fn(verbose=True, **kw)
            checks = out.get("checks", {})
            ok = all(checks.values()) if checks else True
        except Exception as e:  # noqa: BLE001
            print(f"[{name}] FAILED: {type(e).__name__}: {e}")
            ok, checks = False, {}
        dt = time.time() - t0
        failed += 0 if ok else 1
        results.append((name, dt, ok, checks))

    print("\nname,seconds,all_checks_pass")
    for name, dt, ok, _ in results:
        print(f"{name},{dt:.1f},{ok}")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
