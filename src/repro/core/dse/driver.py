"""End-to-end DSE drivers: random sampling and guided search behind one
``explore()`` call (paper §V-E, use case 3).

``explore(net, dev, n, strategy="random")`` reproduces the paper's blind
100k-sample sweep with the vectorized samplers; ``strategy="search"``
spends the same evaluation budget on the guided evolutionary loop and
returns the persistent Pareto archive as the front.  Both report the
whole evaluated sample so benchmarks can compare fronts side by side.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from .encoding import DesignBatch, concat_batches
from .pareto import dominates_matrix, pareto
from .samplers import sample_custom, sample_mixed
from .search import SearchConfig, SearchResult, orient, search

DEFAULT_OBJECTIVES = ("latency_s", "buffer_bytes")


@dataclass
class DSEResult:
    batch: DesignBatch
    metrics: dict[str, np.ndarray]
    seconds: float
    per_design_us: float
    strategy: str = "random"
    n_evals: int = 0
    objectives: tuple[str, ...] = DEFAULT_OBJECTIVES
    front: np.ndarray = field(default_factory=lambda: np.empty(0, np.intp))
    #: schedule-refined front metrics, front-aligned arrays — populated
    #: only by ``Session.explore(refine="schedule")`` (docs/schedule.md)
    refined: dict | None = None

    def front_points(self) -> np.ndarray:
        """Oriented (lower-better) objective points of the front rows."""
        return orient(self.metrics, self.objectives)[self.front]


def _explore(net, dev, n: int = 100_000, *,
             family: str = "custom", seed: int = 0, chunk: int = 4096,
             strategy: str = "random",
             objectives: tuple[str, ...] = DEFAULT_OBJECTIVES,
             config: SearchConfig | None = None,
             tables=None, backend: str | None = None,
             mesh=None) -> DSEResult:
    """Implementation behind ``Session.explore`` and the deprecated
    ``explore`` shim: evaluate ``n`` designs and return the sample plus
    its Pareto front.  ``mesh`` (a ``core.shard.EvalMesh``) shards the
    random sweep's design axis and turns the search into the island
    model; None keeps the single-device paths bit-identical.

    strategy="random": sample ``family`` ("custom" | "mixed" | "both") and
    evaluate, exactly the paper's use case;  strategy="search": run the
    guided multi-objective loop at the same evaluation budget, with
    ``family`` seeding the initial population/immigrants (the variation
    operators explore the full encoding space from there).  ``chunk``
    applies to the random strategy only — the search equivalent is
    ``config.pop_size``.

    A ``config``, when given, is authoritative for the search (only the
    budget comes from ``n``); the ``seed``/``objectives``/``family``
    keywords configure the search only when no config is passed.
    Caller-provided ``tables`` are used verbatim (never rebuilt); an
    explicit ``backend`` overrides the env-resolved kernel backend.
    """
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    if strategy == "search":
        if config is not None:
            cfg = SearchConfig(**{**config.__dict__, "budget": n})
        else:
            cfg = SearchConfig(budget=n, seed=seed,
                               objectives=tuple(objectives),
                               init_family=family)
        objectives = cfg.objectives
        res: SearchResult = search(net, dev, cfg, tables=tables,
                                   backend=backend, mesh=mesh)
        return DSEResult(
            batch=res.batch, metrics=res.metrics, seconds=res.seconds,
            per_design_us=res.seconds / max(res.n_evals, 1) * 1e6,
            strategy="search", n_evals=res.n_evals,
            objectives=tuple(objectives), front=res.front_idx)
    if strategy != "random":
        raise ValueError(f"unknown strategy {strategy!r}")

    import jax

    from ..batch_eval import _pad_rows, evaluate_batch, make_tables

    def sampler(rng, n_layers, b):
        if family == "custom":
            return sample_custom(rng, n_layers, b)
        if family == "mixed":
            return sample_mixed(rng, n_layers, b)
        if family == "both":
            half = b // 2
            return concat_batches([sample_custom(rng, n_layers, half),
                                   sample_mixed(rng, n_layers, b - half)])
        raise ValueError(f"unknown family {family!r}")

    rng = np.random.default_rng(seed)
    tables = make_tables(net) if tables is None else tables
    n_layers = tables.n_layers
    outs: list[dict] = []
    batches: list[DesignBatch] = []
    t0 = time.time()
    done = 0
    while done < n:
        b = min(chunk, n - done)
        batch = sampler(rng, n_layers, b)
        # pad the tail chunk to the full chunk size: a 100k-design sweep
        # compiles exactly once (padded rows are sliced off below)
        out = evaluate_batch(_pad_rows(batch, min(chunk, n)), tables, dev,
                             backend=backend, mesh=mesh)
        jax.block_until_ready(out["latency_s"])
        outs.append({k: np.asarray(v)[:b] for k, v in out.items()})
        batches.append(batch)
        done += b
    dt = time.time() - t0
    merged = concat_batches(batches)
    metrics = {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}
    front = pareto(orient(metrics, objectives))
    return DSEResult(batch=merged, metrics=metrics, seconds=dt,
                     per_design_us=dt / n * 1e6, strategy="random",
                     n_evals=n, objectives=tuple(objectives), front=front)


def explore(net, dev, n: int = 100_000, *,
            family: str = "custom", seed: int = 0, chunk: int = 4096,
            strategy: str = "random",
            objectives: tuple[str, ...] = DEFAULT_OBJECTIVES,
            config: SearchConfig | None = None,
            tables=None, backend: str | None = None) -> DSEResult:
    """Deprecated shim over :func:`_explore` — use
    :meth:`repro.api.Session.explore` (bit-identical results)."""
    from .._deprecation import warn_deprecated
    warn_deprecated("explore", "repro.api.Session.explore")
    return _explore(net, dev, n, family=family, seed=seed, chunk=chunk,
                    strategy=strategy, objectives=objectives, config=config,
                    tables=tables, backend=backend)


def best_scalar_index(metrics: dict[str, np.ndarray],
                      objectives: tuple[str, ...] = DEFAULT_OBJECTIVES,
                      weights=None) -> int:
    """Index of the best design under normalized weighted scalarization —
    the single 'best sample' a random sweep would report."""
    pts = orient(metrics, objectives)
    lo, hi = pts.min(0), pts.max(0)
    norm = (pts - lo) / np.maximum(hi - lo, 1e-30)
    w = np.ones(pts.shape[1]) if weights is None else np.asarray(weights)
    return int(np.argmin(norm @ (w / w.sum())))


def dominating_indices(points: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Indices of rows that strictly dominate ``ref`` (all <=, any <)."""
    points = np.asarray(points, np.float64)
    ref = np.asarray(ref, np.float64)
    return np.nonzero(dominates_matrix(points, ref[None, :])[:, 0])[0]
