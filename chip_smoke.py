"""Run MCCM's evaluation path on a TPU and check what comes out.

    python chip_smoke.py                # one chip: every phase of the main path
    python chip_smoke.py --four-chips   # four chips: the sharded evaluator
                                        # and the island search, nothing else

The script drives the system through the entry points a user calls —
``repro.api.Session``, ``repro.serve.EvalServer`` and ``ServeClient`` — at
the paper's deployment sizes: Table II boards, Table III CNNs, 65,536-design
sweeps and the 100k-design guided search of §V.  The session is pinned to
the compiled ``pallas`` backend with no fallback, so a kernel that fails on
the chip fails here instead of quietly running the pure-jnp ``ref`` path.

Each phase checks its outputs against the repository's own references: the
scalar evaluator (``core/evaluator.py``) at ``SCALAR_PARITY_RTOL``, the
``ref`` backend run bit for bit on the same chip, and, with four chips, the
one-chip program.  Every phase runs even after an earlier one failed, so a
single run reports every fault; any failure makes the exit code nonzero.
The last line of standard output is one JSON object naming the device, and
it is printed only when every phase passed.  Without a TPU the script exits
nonzero before running anything.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

SWEEP_B = 65_536              # designs per timed sweep
PARITY_B = 4096               # pallas == ref designs
SCALAR_B = 512                # of those, also checked against the scalar
SEARCH_BUDGET = 100_000       # the paper's §V DSE budget
SEARCH_CHECK_BUDGET = 16_384  # pallas == ref guided-search budget
DEPLOY_N = 4096               # joint deployments, resnet50 + mobilenetv2
SERVE_BATCH = 256             # designs in the served bulk request
FOUR_CHIP_B = 4 * 16_384      # sharded evaluate rows
ISLAND_POP = 4096
ISLAND_BUDGET = 4 * 4 * ISLAND_POP   # four generations of four islands


class SmokeFailure(Exception):
    """A check that did not hold."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def die(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(1)


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (a cache hit is timed as a compile that loaded from disk)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.programs, self.cache_hits


def compare_exact(got: dict, want: dict, what: str) -> None:
    """Every metric bit-identical; on a mismatch, say where and how far."""
    import numpy as np

    check(set(got) == set(want), f"{what}: metric sets differ")
    bad = []
    for k in sorted(want):
        a = np.asarray(got[k])
        b = np.asarray(want[k])
        check(a.shape == b.shape, f"{what} {k}: shape {a.shape} != {b.shape}")
        neq = ~((a == b) | (np.isnan(a) & np.isnan(b))) \
            if a.dtype.kind == "f" else a != b
        if neq.any():
            af = a.astype(np.float64)
            bf = b.astype(np.float64)
            rel = np.abs(af - bf) / np.maximum(np.abs(bf), 1e-300)
            bad.append(f"{k}: {int(neq.sum())}/{neq.size} rows differ, "
                       f"max rel {float(np.nanmax(rel[neq])):.3e}, first at "
                       f"row {int(np.argmax(neq.ravel()))}")
    check(not bad, f"{what} not bit-identical: " + "; ".join(bad))


def check_finite(out: dict, n: int, what: str) -> None:
    import numpy as np

    for k, v in out.items():
        v = np.asarray(v)
        check(v.shape == (n,), f"{what} {k}: shape {v.shape}, want ({n},)")
        check(bool(np.isfinite(v).all()), f"{what} {k}: non-finite values")


# --------------------------------------------------------------------------
# one-chip phases
# --------------------------------------------------------------------------
def one_chip_phases(ctx) -> list:
    import jax
    import numpy as np

    from repro.api import Session
    from repro.cnn.registry import CNN_NAMES, get_cnn
    from repro.core.batch_eval import SCALAR_PARITY_RTOL
    from repro.core.dse.encoding import decode_design
    from repro.core.dse.samplers import sample_mixed
    from repro.core.notation import format_spec
    from repro.fpga.archs import ARCH_NAMES, make_arch
    from repro.fpga.boards import get_board
    from repro.serve import EvalServer, ServeClient

    seed = ctx["seed"]
    vcu110 = get_board("vcu110")
    xception = get_cnn("xception")
    L = len(xception)
    ses = Session(vcu110, backend="pallas", fallback_backend=None)
    ses_ref = Session(vcu110, backend="ref", fallback_backend=None)
    ctx["sessions"] = [ses, ses_ref]
    state = {}

    def against_scalar(batch, specs, net, dev, worst, bad):
        """Row i of ``batch`` vs the scalar evaluator on ``specs[i]``."""
        for i, spec in enumerate(specs):
            m = ses.evaluate(spec, net, dev)
            want = {"latency_s": m.latency_s,
                    "throughput_ips": m.throughput_ips,
                    "buffer_bytes": float(m.buffer_bytes),
                    "access_bytes": m.access_bytes}
            for k, rtol in SCALAR_PARITY_RTOL.items():
                got = float(batch[k][i])
                rel = abs(got - want[k]) / max(abs(want[k]), 1e-300)
                worst[k] = max(worst[k], rel)
                if not abs(got - want[k]) <= rtol * abs(want[k]):
                    bad.append(f"{net.name} {spec.name} {k}: {got!r} vs "
                               f"scalar {want[k]!r} (rel {rel:.3e})")

    def worst_text(worst):
        return ", ".join(f"{k}={v:.2e}" for k, v in worst.items())

    def session():
        check(ses.config.backend == "pallas"
              and ses.config.fallback_backend is None,
              f"session resolved to {ses.config.backend!r} with fallback "
              f"{ses.config.fallback_backend!r}")
        return (f"backend={ses.config.backend} fallback=None "
                f"mesh={ses.mesh.ndevices}")

    def scalar_parity():
        dev = get_board("vcu108")
        worst = dict.fromkeys(SCALAR_PARITY_RTOL, 0.0)
        bad, n = [], 0
        for cnn in CNN_NAMES:
            net = get_cnn(cnn)
            specs = [make_arch(a, net, k) for a in ARCH_NAMES
                     for k in (2, 5, 9, 11)]
            against_scalar(ses.evaluate(specs, net, dev), specs, net, dev,
                           worst, bad)
            n += len(specs)
        check(not bad, f"{len(bad)} values outside SCALAR_PARITY_RTOL; "
                       f"first: {bad[:6]}")
        return (f"{n} designs ({len(CNN_NAMES)} CNNs x {len(ARCH_NAMES)} "
                f"archs x 4 CE counts) on vcu108, worst rel err "
                + worst_text(worst))

    def pallas_vs_ref():
        db = sample_mixed(np.random.default_rng(seed), L, PARITY_B)
        pal = ses.evaluate(db, xception)
        ref = ses_ref.evaluate(db, xception)
        check_finite(pal, PARITY_B, "pallas")
        worst = dict.fromkeys(SCALAR_PARITY_RTOL, 0.0)
        bad = []
        against_scalar(pal, [decode_design(db, i, L) for i in range(SCALAR_B)],
                       xception, vcu110, worst, bad)
        compare_exact(pal, ref, "pallas vs ref")
        check(not bad, f"{len(bad)} values outside SCALAR_PARITY_RTOL; "
                       f"first: {bad[:6]}")
        return (f"{PARITY_B} sample_mixed designs, xception x vcu110, "
                f"{len(pal)} metrics bit-identical; first {SCALAR_B} vs "
                f"scalar: worst rel err {worst_text(worst)}")

    def sweep():
        db = sample_mixed(np.random.default_rng(seed + 1), L, SWEEP_B)
        t0 = time.perf_counter()
        jax.block_until_ready(ses.evaluate(db, xception))
        warm_s = time.perf_counter() - t0
        before = ses.compile_stats()["total"]
        t0 = time.perf_counter()
        out = jax.block_until_ready(ses.evaluate(db, xception))
        dt = time.perf_counter() - t0
        compiles = ses.compile_stats()["total"] - before
        check_finite(out, SWEEP_B, "sweep")
        check(compiles == 0, f"{compiles} compiles inside the timed sweep")
        return (f"B={SWEEP_B} xception x vcu110: warm pass {warm_s:.3f}s, "
                f"timed pass {dt:.4f}s = {SWEEP_B / dt:.0f} designs/s, "
                f"compiles in timed pass {compiles}")

    def search():
        t0 = time.perf_counter()
        res = ses.explore(xception, n=SEARCH_BUDGET, strategy="search",
                          seed=seed)
        dt = time.perf_counter() - t0
        check(res.n_evals == SEARCH_BUDGET,
              f"search spent {res.n_evals} of {SEARCH_BUDGET} evaluations")
        check(res.front.size > 0, "empty Pareto front")
        front = {k: np.asarray(v)[res.front] for k, v in res.metrics.items()}
        check_finite(front, res.front.size, "search front")
        state["front_design"] = decode_design(res.batch, int(res.front[0]), L)
        a = ses.explore(xception, n=SEARCH_CHECK_BUDGET, strategy="search",
                        seed=seed)
        b = ses_ref.explore(xception, n=SEARCH_CHECK_BUDGET,
                            strategy="search", seed=seed)
        check(np.array_equal(a.front, b.front),
              f"pallas front {a.front.size} rows != ref front "
              f"{b.front.size} rows")
        compare_exact(a.metrics, b.metrics, "search pallas vs ref")
        compare_exact(dict(zip(("seg_end", "seg_pipe", "seg_nce",
                                "inter_pipe"), a.batch.to_numpy())),
                      dict(zip(("seg_end", "seg_pipe", "seg_nce",
                                "inter_pipe"), b.batch.to_numpy())),
                      "search designs pallas vs ref")
        return (f"budget {SEARCH_BUDGET}: {dt:.2f}s wall, front "
                f"{res.front.size}; budget {SEARCH_CHECK_BUDGET}: pallas "
                f"and ref fronts bit-identical ({a.front.size} rows)")

    def multinet():
        nets = [get_cnn("resnet50"), get_cnn("mobilenetv2")]
        zc706 = get_board("zc706")
        a = ses.deploy(nets, n=DEPLOY_N, dev=zc706, seed=seed)
        b = ses_ref.deploy(nets, n=DEPLOY_N, dev=zc706, seed=seed)
        check(a.mode == "spatial", f"deploy ran mode {a.mode!r}")
        check(a.n_evals == DEPLOY_N, f"deploy spent {a.n_evals} evaluations")
        check(a.front.size > 0, "empty joint front")
        check(np.array_equal(a.front, b.front), "pallas joint front != ref")
        compare_exact(a.metrics, b.metrics, "deploy pallas vs ref")
        check(all(np.isfinite(np.asarray(v)[a.front]).all()
                  for v in a.metrics.values()), "non-finite front metrics")
        return (f"resnet50 + mobilenetv2 spatial on zc706, {DEPLOY_N} "
                f"deployments, front {a.front.size}, bit-identical to ref")

    def schedule():
        check("front_design" in state, "no front design (search failed)")
        spec = state["front_design"]
        art = ses.schedule(spec, xception)
        scalar = ses.evaluate(spec, xception)
        check(math.isfinite(art.latency_s)
              and math.isfinite(art.coarse_latency_s),
              "non-finite schedule latency")
        check(art.latency_s <= art.coarse_latency_s,
              f"refined {art.latency_s} > coarse {art.coarse_latency_s}")
        rtol = SCALAR_PARITY_RTOL["latency_s"]
        check(abs(art.coarse_latency_s - scalar.latency_s)
              <= rtol * scalar.latency_s,
              f"coarse {art.coarse_latency_s} vs scalar {scalar.latency_s}")
        return (f"{format_spec(spec, L)}: coarse {art.coarse_latency_s:.6e}s "
                f"refined {art.latency_s:.6e}s over {art.n_candidates} "
                f"candidates")

    def served():
        rng = np.random.default_rng(seed + 2)
        resnet50 = get_cnn("resnet50")
        bulk = sample_mixed(rng, len(resnet50), SERVE_BATCH)
        bulk = [format_spec(decode_design(bulk, i, len(resnet50)),
                            len(resnet50)) for i in range(SERVE_BATCH)]
        reqs = [
            ("{L1-Last:CE1-CE4}", "resnet50", "zc706"),
            ("{L1-L20:CE1, L21-Last:CE2-CE5}", "mobilenetv2", "vcu110"),
            (["{L1-Last:CE1-CE2}", "{L1-L9:CE1-CE3, L10-Last:CE4}"],
             "resnet50", "vcu110"),
            (["{L1-L30:CE1, L31-Last:CE2-CE6}", "{L1-Last:CE1-CE8}",
              "{L1-L5:CE1, L6-L40:CE2-CE4, L41-Last:CE5}"],
             "mobilenetv2", "zc706"),
            (bulk, "resnet50", "zc706"),
            ("{L1-L8:CE1-CE2, L9-Last:CE3-CE4}", "resnet50", "vcu110"),
            (["{L1-Last:CE1-CE3}"] * 5, "mobilenetv2", "vcu110"),
            ("{L1-L12:CE1, L13-Last:CE2-CE3}", "mobilenetv2", "zc706"),
        ]
        srv = EvalServer(ses).start()
        try:
            with ServeClient(*srv.address) as cli:
                check(cli.ping() == {"pong": True}, "ping failed")
                futs = [cli.evaluate_async(d, net, board=board)
                        for d, net, board in reqs]
                answers = [f.result(timeout=600) for f in futs]
        finally:
            srv.stop()
        n = 0
        for (d, net, board), got in zip(reqs, answers):
            scalar = isinstance(d, str)
            want = ses.evaluate([d] if scalar else d, get_cnn(net),
                                get_board(board))
            if scalar:
                want = {k: v[:1] for k, v in want.items()}
                got = {k: [v] for k, v in got.items()}
            compare_exact({k: np.asarray(v, np.float64)
                           for k, v in got.items()},
                          {k: np.asarray(v, np.float64)
                           for k, v in want.items()},
                          f"served {net} x {board}")
            n += 1 if scalar else len(d)
        return (f"{len(reqs)} pipelined requests ({n} designs, 2 nets x 2 "
                f"boards) equal to Session.evaluate")

    return [("session", session), ("scalar parity", scalar_parity),
            ("pallas == ref", pallas_vs_ref), ("sweep", sweep),
            ("guided search", search), ("multinet", multinet),
            ("schedule", schedule), ("served", served)]


# --------------------------------------------------------------------------
# four-chip phases
# --------------------------------------------------------------------------
def four_chip_phases(ctx) -> list:
    import numpy as np

    from repro.api import Session
    from repro.cnn.registry import get_cnn
    from repro.core.dse.samplers import sample_mixed
    from repro.core.dse.search import SearchConfig
    from repro.fpga.boards import get_board

    seed = ctx["seed"]
    vcu110 = get_board("vcu110")
    xception = get_cnn("xception")
    ses4 = Session(vcu110, backend="pallas", fallback_backend=None)
    ses1 = Session(vcu110, backend="pallas", fallback_backend=None, mesh=1)
    ctx["sessions"] = [ses4, ses1]

    def sharded_evaluate():
        check(ses4.mesh.ndevices == 4,
              f"session mesh spans {ses4.mesh.ndevices} devices, want 4")
        db = sample_mixed(np.random.default_rng(seed), len(xception),
                          FOUR_CHIP_B)
        out4 = ses4.evaluate(db, xception)
        out1 = ses1.evaluate(db, xception)
        shards = out4["latency_s"].addressable_shards
        devices = {s.device for s in shards}
        rows = sorted(s.data.shape[0] for s in shards)
        check(len(devices) == 4 and rows == [FOUR_CHIP_B // 4] * 4,
              f"output spread over {len(devices)} devices, rows {rows}")
        check_finite(out4, FOUR_CHIP_B, "sharded")
        compare_exact(out4, out1, "4-chip vs 1-chip evaluate")
        return (f"B={FOUR_CHIP_B} over {len(devices)} devices "
                f"({FOUR_CHIP_B // 4} rows each), bit-identical to 1 chip")

    def island_search():
        cfg = SearchConfig(pop_size=ISLAND_POP, n_islands=4, seed=seed,
                           migration_interval=2)
        a = ses4.explore(xception, n=ISLAND_BUDGET, strategy="search",
                         config=cfg)
        b = ses1.explore(xception, n=ISLAND_BUDGET, strategy="search",
                         config=cfg)
        check(a.n_evals == ISLAND_BUDGET, f"spent {a.n_evals} evaluations")
        check(np.array_equal(a.front, b.front),
              f"sharded front {a.front.size} rows != serial "
              f"{b.front.size} rows")
        compare_exact(a.metrics, b.metrics, "island sharded vs serial")
        return (f"4 islands x pop {ISLAND_POP}, budget {ISLAND_BUDGET}: "
                f"sharded front ({a.front.size} rows) bit-identical to the "
                f"serial island run ({a.seconds:.2f}s vs {b.seconds:.2f}s)")

    return [("sharded evaluate", sharded_evaluate),
            ("island search", island_search)]


# --------------------------------------------------------------------------
def run(phases, compiles) -> list[str]:
    failed = []
    for name, fn in phases:
        c0 = compiles.snapshot()
        t0 = time.perf_counter()
        try:
            detail = fn()
        except Exception as e:  # noqa: BLE001 — reported, then exit 1
            traceback.print_exc()
            print(f"[{name}] FAIL {type(e).__name__}: {e}", flush=True)
            failed.append(name)
            continue
        c1 = compiles.snapshot()
        print(f"[{name}] ok in {time.perf_counter() - t0:.2f}s "
              f"(compile {c1[0] - c0[0]:.2f}s, {c1[1] - c0[1]} programs, "
              f"{c1[2] - c0[2]} cache hits): {detail}", flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded evaluate and island search "
                         "on four chips, against the one-chip program")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every sampled design set")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        die(f"no MCCM sources next to this script ({SRC}/repro is "
            f"missing): run it from a checkout of the repository")
    sys.path.insert(0, SRC)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        die(f"no TPU chip: JAX's devices are {platform!r} "
            f"({len(devices)} of them); this smoke runs only on a TPU")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        die(f"--four-chips needs 4 TPU chips, JAX sees {len(devices)}")
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: platform={platform} kind={device['kind']} "
          f"count={device['count']}", flush=True)

    from repro.core.cache import enable_compilation_cache

    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    compiles = CompileLog(jax)
    ctx = {"seed": args.seed}
    t0 = time.perf_counter()
    phases = (four_chip_phases if args.four_chips else one_chip_phases)(ctx)
    failed = run(phases, compiles)

    degraded = 0
    for ses in ctx.get("sessions", []):
        degraded += ses.stats.degraded
        if ses.breaker.trips or ses.breaker.is_open:
            failed.append(f"breaker opened ({ses.config.backend})")
        ses.close()
    if degraded:
        failed.append(f"{degraded} calls degraded to a fallback")
    secs, programs, hits = compiles.snapshot()
    print(f"end: {time.perf_counter() - t0:.1f}s, degraded={degraded}, "
          f"compile {secs:.1f}s over {programs} programs, "
          f"{hits} persistent-cache hits", flush=True)
    if failed:
        print(f"chip_smoke: FAILED: {', '.join(failed)}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
