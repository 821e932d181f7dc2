"""Sweep: ``Session.evaluate(DesignBatch)`` back to back on batches drawn
at set-up, each call's metrics pulled to the host, as a design-space
campaign does."""
from __future__ import annotations

import time

import numpy as np

from .. import check, generate
from .. import drivers
from . import live_ces, resolve


class Sweep:
    def __init__(self, cell, seed: int, seconds: float):
        self.cell, self.seed = cell, seed
        self.p = cell.traffic
        self.keys = [(n, b) for n in cell.config["nets"]
                     for b in cell.config["boards"]]
        self.results = []          # (batch index, {metric: host array})

    def setup(self) -> None:
        import jax
        from repro.api import Session
        from repro.core.dse.encoding import DesignBatch

        self.jax = jax
        nets, boards = resolve(self.cell.config)
        net0, board0 = self.keys[0]
        self.ses = Session(boards[board0], backend=drivers.BACKEND,
                           fallback_backend=None, mesh=self.cell.chips)
        B = int(self.p["batch"])
        self.batches = []
        for i in range(int(self.p["batches"])):
            net, board = self.keys[i % len(self.keys)]
            L = len(nets[net])
            rows = generate.designs(generate.rng_for(self.seed, 10, i), L, B,
                                    **self.p["sampler"])
            self.batches.append({
                "net": nets[net], "board": boards[board], "key": (net, board),
                "rows": rows, "db": DesignBatch.from_numpy(*rows),
                "live_ces": live_ces(rows[0], rows[1], rows[2], L)})
        for b in self.batches:           # compiles, or loads from the cache
            self._call(b)

    def _call(self, b) -> dict:
        TA = self.jax.profiler.TraceAnnotation
        with TA("chipbench.evaluate"):
            out = self.ses.evaluate(b["db"], b["net"], b["board"])
        with TA("chipbench.pull"):
            return {k: np.asarray(v) for k, v in out.items()}

    def _run(self, stop) -> tuple:
        t0 = time.perf_counter()
        i = 0
        while True:
            k = i % len(self.batches)
            self.results.append((k, self._call(self.batches[k])))
            i += 1
            if stop(i, time.perf_counter() - t0):
                return i, time.perf_counter() - t0

    def window(self, seconds: float) -> dict:
        calls, elapsed = self._run(lambda i, t: t >= seconds)
        designs = calls * int(self.p["batch"])
        return {"designs_per_s": designs / elapsed}

    def traced(self) -> dict:
        n = int(self.p["trace_calls"])
        self._run(lambda i, t: i >= n)
        used = [self.batches[i % len(self.batches)] for i in range(n)]
        b0 = used[0]
        return {"designs": n * int(self.p["batch"]), "calls": n,
                "live_ces": sum(b["live_ces"] for b in used),
                "layers": len(b0["net"]), "board_pes": b0["board"].pes}

    def numbers(self) -> dict:
        rng = generate.rng_for(self.seed, 20)
        B = int(self.p["batch"])
        picks = rng.choice(len(self.results) * B,
                           size=min(int(self.p["check_rows"]),
                                    len(self.results) * B), replace=False)
        per_row = {k: [] for k in check.NUMBERS}
        by_key: dict = {}
        for flat in sorted(int(x) for x in picks):
            k, host = self.results[flat // B]
            r = flat % B
            b = self.batches[k]
            seg_end, seg_pipe, seg_nce, inter = b["rows"]
            by_key.setdefault(b["key"], []).append(
                (seg_end[r], seg_pipe[r], seg_nce[r], inter[r],
                 {m: host[m][r] for m in check.NUMBERS}))
        for (net, board), rows in by_key.items():
            got = check.gaps(rows, check.layers_of(self.cell.config, net),
                             check.board_of(self.cell.config, board))
            for k, v in got.items():
                per_row[k] += v
        numbers = check.summary(per_row)
        numbers["nonfinite_rows"] = float(self.nonfinite_rows())
        return numbers

    def nonfinite_rows(self) -> int:
        """Rows of the window with a metric that is not finite."""
        return sum(int((~np.isfinite(np.stack(
            [host[m] for m in check.NUMBERS]))).any(0).sum())
            for _, host in self.results)

    def counts(self) -> tuple:
        return len(self.results) * int(self.p["batch"]), \
            self.nonfinite_rows()

    def close(self) -> None:
        self.ses.close()


Driver = Sweep
