"""Sharded-evaluator benchmark: one process per device count.

Measures the design-sharded hot path (``EvalMesh`` over shard_map) and
the island-model DSE against their single-device equivalents:

    python -m benchmarks.sharded_eval                 # this host's devices
    python -m benchmarks.sharded_eval --devices 4     # force 4 host devices
    python -m benchmarks.sharded_eval --devices 4 --json   # machine output

Device count must be fixed before jax initialises its backend, so
``main`` exports ``REPRO_MESH_DEVICES`` *first* and only then imports the
repro stack — the same single env-var path users follow (docs/perf.md).
That also means one process measures exactly one forced host-device
count; on the CPU ``perf_gate`` spawns this module as a subprocess per
point to build the weak-scaling curve.  On an accelerator, where a chip
belongs to one process, ``perf_gate`` calls :func:`run` in its own
process at one device and at every visible device.

On CPU, forced host devices are real XLA devices scheduled across cores:
aggregate designs/sec scales with ``min(ndevices, physical cores)`` and
no further.  Raw numbers are recorded either way; hardware-dependent
gates live in perf_gate and only arm when the cores exist.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

EVAL_B_FULL = 4096
EVAL_B_QUICK = 512
SEARCH_BUDGET_FULL = 4096
SEARCH_BUDGET_QUICK = 1024
SEARCH_POP = 256


def run(ndevices: int, *, b: int | None = None, quick: bool = False,
        verbose: bool = True) -> dict:
    """Measure sharded eval + island search at the current device count.

    Call only after ``REPRO_MESH_DEVICES`` is exported (see ``main``);
    importing anything jax-backed before that pins the backend to one
    device and the mesh silently clamps.
    """
    from repro.core import shard  # noqa: F401  (env bootstrap, pre-jax)
    import jax
    import numpy as np

    from repro.cnn.registry import get_cnn
    from repro.core.batch_eval import evaluate_batch, make_tables
    from repro.core.cache import enable_compilation_cache
    from repro.core.dse.samplers import sample_mixed
    from repro.core.dse.search import SearchConfig, search
    from repro.core.shard import EvalMesh, mesh_compile_counts
    from repro.fpga.boards import get_board

    enable_compilation_cache()
    B = b or (EVAL_B_QUICK if quick else EVAL_B_FULL)
    mesh = EvalMesh(ndevices)
    got = mesh.ndevices
    if got != ndevices and verbose:
        print(f"# requested {ndevices} devices, backend exposes {got}",
              file=sys.stderr)

    net, dev = get_cnn("xception"), get_board("vcu110")
    tables = make_tables(net)
    rng = np.random.default_rng(0)
    db = sample_mixed(rng, len(net), B)

    def _eval():
        r = evaluate_batch(db, tables, dev, mesh=mesh)
        jax.block_until_ready(r["latency_s"])
        return r

    t0 = time.time()
    _eval()
    first_s = time.time() - t0
    reps = 1 if quick else 3
    t0 = time.time()
    for _ in range(reps):
        _eval()
    steady_s = (time.time() - t0) / reps
    compiles = dict(mesh_compile_counts())

    # a tail batch in the same pad bucket must not trigger a recompile
    db_tail = sample_mixed(rng, len(net), B - 31)
    r = evaluate_batch(db_tail, tables, dev, mesh=mesh)
    jax.block_until_ready(r["latency_s"])
    recompiles = sum(mesh_compile_counts().values()) \
        - sum(compiles.values())

    # ---- island search vs the classic single-population loop at the
    # same evaluation budget (designs/sec is the honest comparison: the
    # island model pays migration + per-island archives for its
    # parallelism, so equal-budget throughput is what must win)
    budget = SEARCH_BUDGET_QUICK if quick else SEARCH_BUDGET_FULL
    scfg = dict(pop_size=SEARCH_POP, budget=budget, seed=0,
                migration_interval=2, migration_elites=8)

    def _timed_search(cfg, m):
        t0 = time.time()
        r = search(net, dev, cfg, mesh=m)
        return time.time() - t0, r

    island_cfg = SearchConfig(**scfg)           # islands = mesh devices
    single_cfg = SearchConfig(**scfg, n_islands=1)
    _timed_search(island_cfg, mesh)             # warm (compiles)
    isl_s, isl_r = _timed_search(island_cfg, mesh)
    _timed_search(single_cfg, None)
    sgl_s, sgl_r = _timed_search(single_cfg, None)

    payload = {
        "ndevices": got,
        "requested": ndevices,
        "cpu_count": os.cpu_count(),
        "quick": bool(quick),
        "jax": jax.__version__,
        "eval": {
            "B": B,
            "us_per_design": steady_s / B * 1e6,
            "designs_per_sec": B / steady_s,
            "steady_s": steady_s,
            "compile_s": max(first_s - steady_s, 0.0),
            "mesh_compiles": compiles,
            "recompiles_on_tail_reeval": int(recompiles),
        },
        "search": {
            "budget": budget,
            "pop_size": SEARCH_POP,
            "n_islands": got,
            "island_designs_per_sec": budget / isl_s,
            "single_designs_per_sec": budget / sgl_s,
            "island_seconds": isl_s,
            "single_seconds": sgl_s,
            "island_front": len(isl_r.front_idx),
            "single_front": len(sgl_r.front_idx),
        },
    }
    if verbose:
        e, s = payload["eval"], payload["search"]
        print(f"devices={got} eval B={B}: {e['us_per_design']:.1f} "
              f"us/design ({e['designs_per_sec']:.0f}/s), "
              f"recompiles={e['recompiles_on_tail_reeval']}")
        print(f"search budget={budget}: island {got}x "
              f"{s['island_designs_per_sec']:.0f}/s vs single "
              f"{s['single_designs_per_sec']:.0f}/s")
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=None,
                    help="host devices to force (default: REPRO_MESH_DEVICES"
                         " if set, else every visible device)")
    ap.add_argument("--b", type=int, default=None, help="eval batch size")
    ap.add_argument("--quick", action="store_true", help="CI smoke sizes")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line on stdout (for perf_gate)")
    args = ap.parse_args(argv)

    n = args.devices
    if n is None:
        n = int(os.environ.get("REPRO_MESH_DEVICES", "0") or 0) \
            or (os.cpu_count() or 1)
    # before ANY jax-touching import: this is the whole trick
    os.environ["REPRO_MESH_DEVICES"] = str(n)

    payload = run(n, b=args.b, quick=args.quick, verbose=not args.json)
    if args.json:
        print(json.dumps(payload))
    else:
        from .common import save
        save("BENCH_sharded", payload)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
