"""Perf gate: record the evaluate_batch hot-path trajectory.

Emits ``artifacts/bench/BENCH_eval.json`` with µs/design at the DSE batch
sizes, jit compile time and a peak-memory estimate, so every PR can be
checked against the recorded trajectory instead of folklore.

    python -m benchmarks.perf_gate            # full gate (B up to 65536)
    python -m benchmarks.perf_gate --quick    # CI smoke (small B)

The committed JSON is the trajectory; re-run and commit when the hot path
changes.  ``reference.pre_fusion_b4096_us`` pins the pre-fusion baseline
this PR replaced (measured on the same container) so speedups stay
auditable.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.cnn.registry import get_cnn
from repro.core import batch_eval
from repro.core.cache import enable_compilation_cache
from repro.core.batch_eval import (DEFAULT_TILE, evaluate_batch,
                                   make_device_tables, make_tables,
                                   padded_rows, pes_hint)
from repro.core.dse.samplers import sample_mixed
from repro.fpga.boards import get_board
from repro.kernels.mccm_eval import pair_tables, resolve_backend

from .common import fmt_table, save

FULL_SIZES = (32, 4096, 65536)
QUICK_SIZES = (32, 512)
MULTINET_B_FULL = 1024
MULTINET_B_QUICK = 128

#: pre-fusion evaluate_batch at B=4096 (xception × vcu110, this container),
#: measured at the commit preceding the fused/tiled hot path
PRE_FUSION_B4096_US = 348.6


def _peak_bytes_estimate(B: int, tables, dev) -> int:
    """Analytic live-set estimate of the tiled hot path (see docs/perf.md):
    ~3 (tile, L, P) parallelism-search blocks + the per-tile layer maps
    (CE one-hot, segment one-hot, scan temporaries), plus the (B,)-sized
    in/out arrays."""
    from repro.core.dse.encoding import NC, NS

    pairs = pair_tables(tables.candidates, pes_hint(dev.pes))
    P = len(pairs.pair_prod)
    tile = DEFAULT_TILE
    per_tile = 3 * tile * tables.max_L * P * 4 \
        + tile * tables.max_L * (NC + NS + 8) * 4
    io = B * (3 * NS + NC) * 4
    return per_tile + io


def run(verbose: bool = True, quick: bool = False,
        sizes=None) -> dict:
    enable_compilation_cache()
    backend = resolve_backend(None)
    net, dev = get_cnn("xception"), get_board("vcu110")
    tables = make_tables(net)
    rng = np.random.default_rng(0)
    sizes = sizes or (QUICK_SIZES if quick else FULL_SIZES)

    jax.clear_caches()
    table, points = [], {}
    for B in sizes:
        db = sample_mixed(rng, len(net), B)
        t0 = time.time()
        r = evaluate_batch(db, tables, dev)
        jax.block_until_ready(r["latency_s"])
        first_s = time.time() - t0
        reps = 1 if quick else 3
        t0 = time.time()
        for _ in range(reps):
            r = evaluate_batch(db, tables, dev)
            jax.block_until_ready(r["latency_s"])
        steady_s = (time.time() - t0) / reps
        # batches pad to a tile multiple: B=32 executes 128 rows.  Both
        # views are recorded — us_per_design is the user-facing cost of a
        # B-design call, us_per_row the per-executed-row throughput.
        rows = padded_rows(B)
        us = steady_s / B * 1e6
        peak = _peak_bytes_estimate(B, tables, dev)
        try:
            devt = make_device_tables(dev)
            mem = batch_eval._evaluate_jit.lower(
                db, tables, devt, backend=backend, tile=DEFAULT_TILE,
                fm_tile_rows=2, pes_hint_static=pes_hint(dev.pes),
                design_tile=16).compile().memory_analysis()
            xla_peak = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
        except Exception:  # noqa: BLE001 — backend without memory stats
            xla_peak = 0
        points[str(B)] = {
            "us_per_design": us,
            "us_per_row": steady_s / rows * 1e6,
            "rows_executed": rows,
            "steady_s": steady_s,
            "compile_s": max(first_s - steady_s, 0.0),
            "peak_bytes_estimate": peak,
            "xla_temp_bytes": xla_peak,
        }
        table.append([f"B={B}", f"{us:.1f}", f"{steady_s / rows * 1e6:.1f}",
                      str(rows), f"{max(first_s - steady_s, 0.0):.2f}",
                      f"{peak/1e6:.1f}"])

    # ---- multinet joint-eval point: µs/deployment at M=2 + compile count
    from repro.core.dse.encoding import stack_designs
    from repro.core.multinet import (DEFAULT_MAX_M, joint_evaluate,
                                     make_multi_tables, sample_shares)
    from repro.core.multinet import joint_eval as _je

    mb = MULTINET_B_QUICK if quick else MULTINET_B_FULL
    nets = [get_cnn("resnet50"), get_cnn("mobilenetv2")]
    mdev = get_board("zc706")
    mt = make_multi_tables(nets)
    md = stack_designs([sample_mixed(rng, len(n), mb) for n in nets],
                       DEFAULT_MAX_M)
    sh = [sample_shares(rng, mb, DEFAULT_MAX_M, 2) for _ in range(3)]
    misses0 = _je._joint_spatial_jit._cache_size()
    t0 = time.time()
    r = joint_evaluate(md, mt, mdev, pes_shares=sh[0], buf_shares=sh[1],
                       bw_shares=sh[2])
    jax.block_until_ready(r["worst_latency_s"])
    first_s = time.time() - t0
    reps = 1 if quick else 3
    t0 = time.time()
    for _ in range(reps):
        r = joint_evaluate(md, mt, mdev, pes_shares=sh[0],
                           buf_shares=sh[1], bw_shares=sh[2])
        jax.block_until_ready(r["worst_latency_s"])
    msteady = (time.time() - t0) / reps
    mcompiles = _je._joint_spatial_jit._cache_size() - misses0
    points["multinet_m2"] = {
        "B": mb,
        "max_m": DEFAULT_MAX_M,
        "us_per_deployment": msteady / mb * 1e6,
        "us_per_model_eval": msteady / (mb * 2) * 1e6,
        "steady_s": msteady,
        "compile_s": max(first_s - msteady, 0.0),
        "compile_count": mcompiles,
    }
    table.append([f"multinet M=2 B={mb}",
                  f"{msteady / mb * 1e6:.1f}",
                  f"{msteady / (mb * 2) * 1e6:.1f}", str(mb),
                  f"{max(first_s - msteady, 0.0):.2f}", "-"])

    # ---- hybrid joint-eval point: µs/deployment at M=3, mixed
    # spatial/shared assignments, single compile across assignment changes
    from repro.core.dse.encoding import sample_assign

    hnets = [get_cnn(n) for n in ("resnet50", "mobilenetv2",
                                  "densenet121")]
    hmt = make_multi_tables(hnets)
    hmd = stack_designs([sample_mixed(rng, len(n), mb) for n in hnets],
                        DEFAULT_MAX_M)
    hsh = [sample_shares(rng, mb, DEFAULT_MAX_M, 3) for _ in range(4)]
    asg = sample_assign(rng, mb, DEFAULT_MAX_M, 3)
    hmisses0 = _je._joint_hybrid_jit._cache_size()
    t0 = time.time()
    r = joint_evaluate(hmd, hmt, mdev, mode="hybrid", assign=asg,
                       pes_shares=hsh[0], buf_shares=hsh[1],
                       bw_shares=hsh[2], time_shares=hsh[3])
    jax.block_until_ready(r["worst_latency_s"])
    first_s = time.time() - t0
    # assignment changes (incl. the pure extremes) must reuse the compile
    asg2 = np.zeros_like(asg)
    asg3 = np.zeros_like(asg)
    asg3[:, :3] = 1.0
    assigns = [asg, asg2, asg3]
    t0 = time.time()
    for a in assigns:
        r = joint_evaluate(hmd, hmt, mdev, mode="hybrid", assign=a,
                           pes_shares=hsh[0], buf_shares=hsh[1],
                           bw_shares=hsh[2], time_shares=hsh[3])
        jax.block_until_ready(r["worst_latency_s"])
    hsteady = (time.time() - t0) / len(assigns)
    hcompiles = _je._joint_hybrid_jit._cache_size() - hmisses0
    points["multinet_hybrid_m3"] = {
        "B": mb,
        "max_m": DEFAULT_MAX_M,
        "us_per_deployment": hsteady / mb * 1e6,
        "us_per_model_eval": hsteady / (mb * 3) * 1e6,
        "steady_s": hsteady,
        "compile_s": max(first_s - hsteady, 0.0),
        "compile_count": hcompiles,
    }
    table.append([f"hybrid M=3 B={mb}",
                  f"{hsteady / mb * 1e6:.1f}",
                  f"{hsteady / (mb * 3) * 1e6:.1f}", str(mb),
                  f"{max(first_s - hsteady, 0.0):.2f}", "-"])

    # ---- session-cached re-evaluation: the Session front door at steady
    # state — memoized tables + shared compiles, so re-serving the same
    # net/board costs pure evaluation (table-build amortization made
    # visible in the trajectory)
    from repro.api import Session

    ses = Session(dev)
    sB = QUICK_SIZES[-1] if quick else 4096
    sdb = sample_mixed(rng, len(net), sB)
    r = ses.evaluate(sdb, net)                     # warmup (maybe compiles)
    jax.block_until_ready(r["latency_s"])
    sc0 = ses.compile_stats()["total"]
    t0 = time.time()
    r = ses.evaluate(sdb, net)
    jax.block_until_ready(r["latency_s"])
    first_s = time.time() - t0
    reps = 1 if quick else 3
    t0 = time.time()
    for _ in range(reps):
        r = ses.evaluate(sdb, net)
        jax.block_until_ready(r["latency_s"])
    ssteady = (time.time() - t0) / reps
    scompiles = ses.compile_stats()["total"] - sc0
    points["session_cached"] = {
        "B": sB,
        "us_per_design": ssteady / sB * 1e6,
        "steady_s": ssteady,
        "first_s_after_warmup": first_s,
        "compile_count_after_warmup": scompiles,
        "net_table_builds": ses.stats.net_table_builds,
        "net_table_hits": ses.stats.net_table_hits,
    }
    table.append([f"session B={sB}", f"{ssteady / sB * 1e6:.1f}",
                  f"{ssteady / sB * 1e6:.1f}", str(sB),
                  f"{max(first_s - ssteady, 0.0):.2f}", "-"])

    # ---- resilient session: the same steady-state call with the full
    # fault policy armed (deadline + admission control + retries +
    # breaker + per-call batch validation, docs/robustness.md) — the
    # policy is bookkeeping around the compiled call, gated to <5% of
    # session_cached
    rses = Session(dev, deadline_s=60.0, max_queue=256, max_retries=2,
                   fallback_backend="ref")
    r = rses.evaluate(sdb, net)                    # warmup (shares compiles)
    jax.block_until_ready(r["latency_s"])
    rc0 = rses.compile_stats()["total"]
    t0 = time.time()
    for _ in range(reps):
        r = rses.evaluate(sdb, net)
        jax.block_until_ready(r["latency_s"])
    rsteady = (time.time() - t0) / reps
    rcompiles = rses.compile_stats()["total"] - rc0
    resilient_overhead = rsteady / ssteady - 1.0
    points["resilient_session"] = {
        "B": sB,
        "us_per_design": rsteady / sB * 1e6,
        "steady_s": rsteady,
        "overhead_vs_session_cached": resilient_overhead,
        "compile_count_after_warmup": rcompiles,
        "degraded": rses.stats.degraded,
        "retried": rses.stats.retried,
    }
    table.append([f"resilient B={sB}", f"{rsteady / sB * 1e6:.1f}",
                  f"{rsteady / sB * 1e6:.1f}", str(sB),
                  f"{resilient_overhead * 100:+.1f}%", "-"])

    # ---- telemetry overhead: the same session_cached call with the
    # metrics registry + spans armed (in-process, no trace dir) against a
    # back-to-back disabled re-measure — the observability layer must be
    # a rounding error on the hot path (<3%, docs/observability.md)
    from repro import telemetry as _tele

    was_enabled = _tele.enabled()
    t0 = time.time()
    for _ in range(reps):
        r = ses.evaluate(sdb, net)
        jax.block_until_ready(r["latency_s"])
    toff = (time.time() - t0) / reps
    _tele.enable()                        # registry + spans, no JSONL sink
    t0 = time.time()
    for _ in range(reps):
        r = ses.evaluate(sdb, net)
        jax.block_until_ready(r["latency_s"])
    ton = (time.time() - t0) / reps
    if not was_enabled:
        _tele.disable()
    telemetry_overhead = ton / toff - 1.0
    points["telemetry_session"] = {
        "B": sB,
        "us_per_design_enabled": ton / sB * 1e6,
        "steady_s_enabled": ton,
        "steady_s_disabled": toff,
        "overhead_vs_disabled": telemetry_overhead,
    }
    table.append([f"telemetry B={sB}", f"{ton / sB * 1e6:.1f}",
                  f"{ton / sB * 1e6:.1f}", str(sB),
                  f"{telemetry_overhead * 100:+.1f}%", "-"])

    # ---- schedule search: the temporal-mapping refinement at batch
    # granularity (docs/schedule.md).  The candidate plane rides the
    # same ladder shapes as evaluate_batch, so the whole point costs one
    # compile cold and ZERO warm — and the structural never-worse
    # invariant (candidate 0 is the coarse mapping) holds on the batch
    from repro.schedule.search import _schedule_jit, schedule_batch

    sch0 = _schedule_jit._cache_size()
    t0 = time.time()
    r = schedule_batch(sdb, ses.tables(net), ses.device_tables(dev))
    jax.block_until_ready(r["ref_latency_s"])
    first_s = time.time() - t0
    sch_cold = _schedule_jit._cache_size() - sch0
    t0 = time.time()
    for _ in range(reps):
        r = schedule_batch(sdb, ses.tables(net), ses.device_tables(dev))
        jax.block_until_ready(r["ref_latency_s"])
    schsteady = (time.time() - t0) / reps
    sch_warm = _schedule_jit._cache_size() - sch0 - sch_cold
    sch_ok = bool(np.all(np.asarray(r["ref_latency_s"])
                         <= np.asarray(r["coarse_latency_s"])))
    points["schedule_search"] = {
        "B": sB,
        "us_per_design": schsteady / sB * 1e6,
        "steady_s": schsteady,
        "compile_s": max(first_s - schsteady, 0.0),
        "compile_count_cold": sch_cold,
        "compile_count_warm": sch_warm,
        "cost_vs_evaluate": schsteady / ssteady,
        "refined_leq_coarse": sch_ok,
    }
    table.append([f"schedule B={sB}", f"{schsteady / sB * 1e6:.1f}",
                  f"{schsteady / sB * 1e6:.1f}", str(sB),
                  f"{max(first_s - schsteady, 0.0):.2f}",
                  f"x{schsteady / ssteady:.1f} eval"])

    # ---- sharded weak-scaling.  On the CPU: one subprocess per forced
    # host-device count (the backend pins its device count at init).  On
    # an accelerator a chip belongs to one process, so this process
    # measures one device and every visible device itself
    from . import serve_load, sharded_eval

    cores = os.cpu_count() or 1
    if jax.default_backend() == "cpu":
        dev_counts = (1, 2) if quick else (1, 2, 4, 8)
        per_dev = {str(n): _forced_host_point(n, quick) for n in dev_counts}
    else:
        dev_counts = sorted({1, len(jax.devices())})
        per_dev = {str(n): sharded_eval.run(n, quick=quick, verbose=False)
                   for n in dev_counts}
    recompiles = 0
    for n, p in per_dev.items():
        recompiles += p["eval"]["recompiles_on_tail_reeval"]
        table.append([f"sharded n={n} B={p['eval']['B']}",
                      f"{p['eval']['us_per_design']:.1f}", "-",
                      f"{p['eval']['designs_per_sec']:.0f}/s",
                      f"{p['eval']['compile_s']:.2f}",
                      f"isl {p['search']['island_designs_per_sec']:.0f}/s"])
    base_dps = per_dev["1"]["eval"]["designs_per_sec"]
    scaling = {n: p["eval"]["designs_per_sec"]
               / (base_dps * min(int(n), cores))
               for n, p in per_dev.items()}
    # weak-scaling bounded by physical cores: on a 1-core host every
    # forced device multiplexes the same core, so the absolute-speedup
    # gate only arms when the silicon exists (docs/perf.md)
    session_dps = sB / ssteady
    gate_armed = cores >= 4 and "4" in per_dev and not quick
    speedup_vs_session = (per_dev.get("4", {}).get("eval", {})
                          .get("designs_per_sec", 0.0) / session_dps
                          if "4" in per_dev else None)
    points["sharded_eval"] = {
        "per_device_count": per_dev,
        "weak_scaling_efficiency": scaling,
        "cpu_count": cores,
        "aggregate_4dev_vs_session_cached": speedup_vs_session,
        "gate_2x_armed": gate_armed,
    }

    # ---- serving front: mixed CNN x board traffic over the socket
    # service, with a background DSE job on the batch lane.  The load
    # generator builds its own Session and server in this process
    sv = serve_load.run(seed=0, quick=quick, verbose=False)
    points["serve_load"] = sv
    table.append([f"serve n={sv['n_requests']}",
                  "-", "-", f"{sv['designs_per_s']:.0f}/s",
                  f"p50 {sv['latency_ms']['p50']:.1f}ms",
                  f"p99 {sv['latency_ms']['p99']:.1f}ms"])

    payload = {
        "benchmark": "evaluate_batch hot path (xception x vcu110)",
        "backend": backend,
        "tile": DEFAULT_TILE,
        "quick": bool(quick),
        "jax": jax.__version__,
        "cpu_count": os.cpu_count(),
        "created_unix": int(time.time()),
        "points": points,
        "reference": {"pre_fusion_b4096_us": PRE_FUSION_B4096_US,
                      "paper_us": 6300.0},
        "checks": {
            "speedup_2x_at_4096": (
                points["4096"]["us_per_design"] < PRE_FUSION_B4096_US / 2
                if "4096" in points else True),
            "multinet_single_compile": mcompiles == 1,
            "hybrid_single_compile_across_assignments": hcompiles == 1,
            "session_reeval_no_new_compiles": scompiles == 0,
            # the fault policy must stay out of the hot path: <5% over
            # session_cached at the same B, zero new compiles, nothing
            # degraded on a clean run (armed on full runs; quick CI
            # batches are too small to measure 5% reliably)
            "resilient_overhead_lt_5pct": (
                resilient_overhead < 0.05 if not quick else True),
            "resilient_no_new_compiles_no_degrade": (
                rcompiles == 0 and rses.stats.degraded == 0
                and rses.stats.retried == 0),
            # the observability layer must stay off the hot path: <3%
            # over the back-to-back disabled measure (armed on full runs;
            # quick CI batches are too noisy at this granularity)
            "telemetry_overhead_lt_3pct": (
                telemetry_overhead < 0.03 if not quick else True),
            # the schedule layer's compile policy + never-worse
            # invariant (docs/schedule.md): warm searches add zero
            # compiles, refined latency <= coarse on the whole batch
            "schedule_no_new_compiles_on_warm": sch_warm == 0,
            "schedule_refined_leq_coarse": sch_ok,
            "sharded_no_recompile_at_reeval": recompiles == 0,
            # scaled throughput: each in-cores device must hold >= 60%
            # of the single-device rate; vacuous on a 1-core host
            "sharded_weak_scaling_60pct": all(
                eff >= 0.6 for n, eff in scaling.items()
                if int(n) <= cores),
            # the ISSUE acceptance: >= 2x aggregate designs/sec over
            # session_cached with 4 devices — armed only when >= 4
            # physical cores exist (recorded raw either way)
            "sharded_2x_at_4dev": (speedup_vs_session >= 2.0
                                   if gate_armed else True),
            # serving front (docs/serving.md): request p99 stays under
            # 2s on the full mixed trace (armed on full runs — quick CI
            # hosts are too noisy for a latency bound), and an
            # interactive probe always lands inside its deadline while
            # the batch-lane DSE job runs
            "serve_p99_bounded": (
                sv["latency_ms"]["p99"] < 2000.0 if not quick else True),
            "serve_interactive_deadline": sv["interactive_under_dse"][
                "met"],
        },
    }
    if verbose:
        print(fmt_table(table, ["batch", "us/design", "us/row", "rows",
                                "compile_s", "peak_MB(est)"]))
        print("checks:", payload["checks"])
    save("BENCH_eval", payload)
    return payload


def _forced_host_point(n: int, quick: bool) -> dict:
    """``benchmarks.sharded_eval`` at ``n`` forced host devices, in a
    fresh interpreter (it exports REPRO_MESH_DEVICES before importing
    jax).  CPU only: on a chip host the child could not get the chip."""
    import json
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    env["REPRO_MESH_DEVICES"] = str(n)
    cmd = [sys.executable, "-m", "benchmarks.sharded_eval",
           "--devices", str(n), "--json"]
    if quick:
        cmd.append("--quick")
    out = subprocess.run(cmd, env=env, cwd=root, capture_output=True,
                         text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"sharded_eval --devices {n} failed:\n"
                           f"{out.stdout}\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small batches only (CI smoke)")
    args = ap.parse_args(argv)
    payload = run(quick=args.quick)
    return 0 if all(payload["checks"].values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
