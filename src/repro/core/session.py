"""The front door: one :class:`Session` serves scalar, batch, DSE and
multinet evaluation behind shared compiled programs.

MCCM's speed claim is per-call — microseconds per design once compiled.
What erodes it in practice is everything *around* the call: every entry
point (``evaluate_design``, ``evaluate_specs``, ``explore``,
``joint_explore``) rebuilding ``NetTables``/``DeviceTables`` unless the
caller threads ``tables=`` by hand, and each reading its own
``backend``/``tile``/``chunk`` kwargs and ``REPRO_*`` env vars.  A Session
is constructed once per process and owns all of it:

* **memoized tables** — ``NetTables`` keyed by ``(net, bucketed max_L)``,
  ``DeviceTables`` keyed by board, ``MultiNetTables`` keyed by the model
  set + weights/SLOs, so the one-compile-serves-all property of
  ``batch_eval``/``joint_eval`` is automatic instead of opt-in;
* **one config** — :class:`EvalConfig` resolves the kernel backend ONCE at
  session creation; every downstream call inherits it (no scattered env
  reads), and JAX's persistent compilation cache is switched on there
  (``core.cache.enable_compilation_cache``);
* **one surface** — :meth:`Session.evaluate` (scalar spec, spec list or
  ``DesignBatch``, dispatching on input), :meth:`Session.explore` (DSE),
  :meth:`Session.deploy` (multinet), and :meth:`Session.submit` → Future
  with a background drain loop that megabatches queued requests through
  one compiled program (the serve-many-users path).

The legacy free functions remain as deprecated shims over the same
implementations — bit-identical results (``tests/test_session.py``), one
``DeprecationWarning``.  Migration table: ``docs/api.md``.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, fields, replace

import numpy as np

from ..kernels.mccm_eval import resolve_backend
from . import telemetry
from .batch_eval import (DEFAULT_TILE, DeviceTables, NetTables,
                         _evaluate_specs, _evaluate_specs_multi,
                         bucket_max_L, evaluate_batch, make_device_tables,
                         make_tables)
from .cache import (DEFAULT_MAX_TABLES, TABLES_ENV, BoundedLRU,
                    enable_compilation_cache, env_bound)
from .coalesce import ArrivalEstimator, plan_megabatch
from .device import DeviceSpec
from .dse.driver import DEFAULT_OBJECTIVES
from .dse.encoding import NC, DesignBatch, count_invalid_jit
from .evaluator import _evaluate_design, build_design
from .notation import AcceleratorSpec, parse
from .resilience import (CircuitBreaker, EvalError, classify,
                         nonfinite_keys, retry_delay, wrap)
from .workload import Network


# --------------------------------------------------------------------------
# configuration, resolved once
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class EvalConfig:
    """Every evaluation knob in one place, resolved at session creation.

    ``backend=None`` reads ``REPRO_MCCM_BACKEND`` (falling back to auto:
    pallas on TPU, ref elsewhere) and pins the result.  The env var is
    consulted exactly once — at :class:`Session` construction — instead of
    per call.
    """

    #: parallelism-search kernel backend ("ref" | "pallas" |
    #: "pallas_interpret"); None resolves the env var / auto default
    backend: str | None = None
    #: design-tile width of the lax.map hot loop
    tile: int = DEFAULT_TILE
    #: feature-map tile rows of Eq. 4's double buffers.  Applies to the
    #: evaluate()/submit() paths; the explore()/deploy() search loops pin
    #: the engine default (2) so their compiled programs stay shared
    fm_tile_rows: int = 2
    #: VMEM design-tile width inside the fused kernel (same scope as
    #: ``fm_tile_rows``)
    design_tile: int = 16
    #: spec-list chunking of evaluate()/submit() (shapes pad per chunk)
    chunk: int = 2048
    #: model-axis padding of deploy()'s MultiNetTables; None = the
    #: multinet default (DEFAULT_MAX_M)
    max_m: int | None = None
    #: submit() megabatching window: how long the drain loop lingers after
    #: the first queued request before evaluating, so concurrent callers
    #: land in one compiled dispatch
    linger_s: float = 0.002
    #: design-axis mesh width (devices).  None resolves REPRO_MESH_DEVICES,
    #: else every visible device; 1 pins the single-device path.  The
    #: session builds one ``core.shard.EvalMesh`` from this and threads it
    #: through evaluate()/explore()/deploy()/submit() (docs/perf.md)
    mesh: int | None = None
    #: default per-request wall-clock deadline of submit(), in seconds.
    #: A request still queued (or whose result is not yet delivered) when
    #: its deadline passes fails with ``EvalError.DEADLINE_EXCEEDED``
    #: instead of hanging; None disables.  submit(deadline_s=...) wins
    #: per request (docs/robustness.md)
    deadline_s: float | None = None
    #: admission control: maximum queued submit() requests.  Further
    #: submits fail fast with ``EvalError.QUEUE_FULL`` instead of growing
    #: the queue without bound; None = unbounded
    max_queue: int | None = None
    #: transient-fault retries of the primary backend per call, with
    #: exponential backoff (``resilience.retry_delay``) between attempts
    max_retries: int = 0
    #: degraded-mode backend when the primary faults past its retries (and
    #: when the circuit breaker is open): the bit-tested pure-jnp "ref"
    #: path by default.  None disables fallback entirely
    fallback_backend: str | None = "ref"
    #: adaptive linger cap, in seconds.  None keeps the fixed ``linger_s``
    #: window; a value arms the arrival-rate-driven policy (the drain
    #: lingers ~2 observed inter-arrivals, never more than this cap) —
    #: what the serving front runs with (docs/serving.md)
    linger_max_s: float | None = None
    #: megabatch coalescing: merge tiny same-(net, board) requests into
    #: shared padded chunks and split oversized requests at the compiled
    #: chunk size.  Bit-identical results (evaluation is row-local) and
    #: never forks compiles; off reproduces the one-padded-chunk-per-
    #: request drain
    coalesce: bool = True
    #: bound of EACH memoized table cache (NetTables / DeviceTables /
    #: MultiNetTables), in entries.  None resolves REPRO_CACHE_TABLES
    #: (default 256); 0 disables eviction.  LRU past the bound, with
    #: eviction counters in observability() (docs/serving.md)
    max_cached_tables: int | None = None
    #: bound of the mesh's sharded-jit registry, in compiled programs.
    #: None resolves REPRO_CACHE_JITS (default 128); 0 disables eviction
    max_cached_jits: int | None = None

    def resolved(self) -> "EvalConfig":
        """Pin the env-dependent fields (backend, mesh, cache bounds) to
        concrete values — called once by :class:`Session`."""
        from .shard import env_mesh_devices
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, "
                             f"got {self.max_retries}")
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {self.deadline_s}")
        if self.linger_max_s is not None and self.linger_max_s < 0:
            raise ValueError(f"linger_max_s must be >= 0, "
                             f"got {self.linger_max_s}")
        return replace(
            self,
            backend=resolve_backend(self.backend),
            fallback_backend=None if self.fallback_backend is None
            else resolve_backend(self.fallback_backend),
            mesh=self.mesh if self.mesh is not None else env_mesh_devices(),
            max_cached_tables=env_bound(TABLES_ENV, DEFAULT_MAX_TABLES)
            if self.max_cached_tables is None else self.max_cached_tables,
            max_cached_jits=self.max_cached_jits)


@dataclass
class SessionStats:
    """Host-side counters of what a session reused vs rebuilt.

    Counters are mutated from BOTH the caller threads and the background
    drain thread (retries/degrades/deadlines happen on either side), so
    every mutation goes through :meth:`bump` under the stats lock —
    plain ``+=`` on the fields is a lost-update race
    (``tests/test_session.py::test_submit_hammer_counters_consistent``).
    """

    net_table_builds: int = 0
    net_table_hits: int = 0
    device_table_builds: int = 0
    device_table_hits: int = 0
    multi_table_builds: int = 0
    multi_table_hits: int = 0
    scalar_evals: int = 0
    batch_designs: int = 0
    explore_calls: int = 0
    deploy_calls: int = 0
    # schedule layer (docs/schedule.md)
    schedule_calls: int = 0
    schedule_builds: int = 0   # schedule searches actually run on device
    schedule_hits: int = 0     # artifacts served from the bounded memo
    submits: int = 0
    megabatches: int = 0
    megabatch_requests: int = 0
    # coalescing counters (docs/serving.md)
    coalesced_chunks: int = 0  # padded dispatch units planned
    coalesced_merges: int = 0  # requests that shared a chunk with another
    coalesced_splits: int = 0  # requests split at the compiled chunk size
    # priority-lane / search-job counters (docs/serving.md)
    search_jobs: int = 0       # submit_search() jobs accepted
    # cache-eviction counters (bounded table caches, docs/serving.md)
    net_table_evictions: int = 0
    device_table_evictions: int = 0
    multi_table_evictions: int = 0
    schedule_evictions: int = 0
    # resilience counters (docs/robustness.md)
    rejected: int = 0          # submits refused by admission control
    retried: int = 0           # primary-backend retry attempts
    degraded: int = 0          # calls served by the fallback backend
    deadline_missed: int = 0   # requests failed with DEADLINE_EXCEEDED

    def __post_init__(self):
        # not a dataclass field: stays out of fields()/as_dict()/repr
        self._lock = threading.Lock()

    def bump(self, name: str, n: int = 1) -> None:
        """Atomically increment counter ``name`` (and mirror it into the
        telemetry registry when enabled)."""
        with self._lock:
            setattr(self, name, getattr(self, name) + n)
        telemetry.count(f"session.{name}", n)

    def as_dict(self) -> dict[str, int]:
        return {f.name: getattr(self, f.name) for f in fields(self)}


#: submit() priority lanes, highest first.  The drain serves interactive
#: requests ahead of batch ones inside every megabatch, and search jobs
#: run on their own worker thread — bulk work can never starve a point
#: evaluation (docs/serving.md)
PRIORITIES = ("interactive", "batch")


class _Request:
    """One queued :meth:`Session.submit` unit of work."""

    __slots__ = ("specs", "net", "dev", "future", "scalar", "deadline",
                 "t_enq", "priority")

    def __init__(self, specs, net, dev, future, scalar, deadline=None,
                 priority="interactive"):
        self.specs = specs
        self.net = net
        self.dev = dev
        self.future = future
        self.scalar = scalar
        self.deadline = deadline   # absolute time.monotonic(), or None
        self.priority = priority
        self.t_enq = time.monotonic()   # queue-wait telemetry anchor


class _SearchJob:
    """One queued :meth:`Session.submit_search` long-running job (the
    batch lane's bulk work: explore/deploy searches)."""

    __slots__ = ("fn", "future", "deadline", "label", "t_enq")

    def __init__(self, fn, future, deadline=None, label="search"):
        self.fn = fn
        self.future = future
        self.deadline = deadline
        self.label = label
        self.t_enq = time.monotonic()


# --------------------------------------------------------------------------
# the session
# --------------------------------------------------------------------------
class Session:
    """One front door for every MCCM evaluation mode.

    Construct once per process (optionally with a default board) and call
    :meth:`evaluate`, :meth:`explore`, :meth:`deploy` or :meth:`submit`;
    tables and compiled programs are shared across all of them.

    >>> ses = Session(get_board("zc706"))
    >>> ses.evaluate("{L1-Last:CE1-CE4}", net)            # scalar Metrics
    >>> ses.evaluate([spec_a, spec_b], net)               # metric arrays
    >>> ses.explore(net, n=100_000, strategy="search")    # DSE front
    >>> ses.deploy([net_a, net_b], n=4096)                # multinet front
    >>> ses.submit(specs, net).result()                   # queued/megabatched
    """

    def __init__(self, dev: DeviceSpec | None = None, *,
                 config: EvalConfig | None = None, **overrides):
        base = config if config is not None else EvalConfig()
        if overrides:
            base = replace(base, **overrides)
        self.config = base.resolved()
        enable_compilation_cache()
        from .shard import EvalMesh
        #: the session's design-axis mesh; single-device meshes delegate
        #: to the exact single-device jits (zero extra compiles)
        self.mesh = EvalMesh(ndevices=self.config.mesh,
                             max_jits=self.config.max_cached_jits)
        self.default_device = dev
        self.stats = SessionStats()
        #: trips on repeated primary-backend faults; while open, calls
        #: degrade to ``fallback_backend`` with periodic recovery probes
        self.breaker = CircuitBreaker()
        # memoization has its own lock (held across check+build+count, so
        # the drain thread and callers can't race a duplicate build); the
        # condition variable below is the submit queue's only.  The table
        # memos are LRU-bounded (config.max_cached_tables per cache) so a
        # long-lived server under unbounded distinct keys stays
        # memory-bounded — evicted entries rebuild on next use,
        # bit-identically (tests/test_session_cache.py)
        self._table_lock = threading.Lock()
        bound = self.config.max_cached_tables
        self._net_tables = BoundedLRU(
            bound, on_evict=lambda *_:
            self.stats.bump("net_table_evictions"))
        self._dev_tables = BoundedLRU(
            bound, on_evict=lambda *_:
            self.stats.bump("device_table_evictions"))
        self._multi_tables = BoundedLRU(
            bound, on_evict=lambda *_:
            self.stats.bump("multi_table_evictions"))
        # schedule artifacts per (net, board, design-hash): small decoded
        # dataclasses, but keys churn with every distinct design — same
        # bound, same eviction-counter contract (docs/schedule.md)
        self._schedule_memo = BoundedLRU(
            bound, on_evict=lambda *_:
            self.stats.bump("schedule_evictions"))
        self._cv = threading.Condition()
        self._pending: list[_Request] = []
        self._worker: threading.Thread | None = None
        #: adaptive-linger arrival tracking (armed by config.linger_max_s)
        self._arrivals = ArrivalEstimator()
        # the batch lane's job queue: long searches run on their own
        # worker so the megabatch drain — the interactive lane — never
        # blocks behind a 100k-budget DSE (docs/serving.md)
        self._jobs: list[_SearchJob] = []
        self._job_cv = threading.Condition()
        self._job_worker: threading.Thread | None = None
        self._job_running = False
        self._closed = False

    # ---- lifecycle -------------------------------------------------------
    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Flush the submit queue and stop the background drain loop and
        the search-job worker.  Queued-but-unstarted search jobs are
        cancelled (``Future.cancel()``); a *running* job finishes — its
        checkpoint, when configured, is what makes killing the process
        instead lossless (docs/robustness.md).  Idempotent; the session's
        caches stay usable afterwards, only :meth:`submit` /
        :meth:`submit_search` are refused."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        with self._job_cv:
            cancelled, self._jobs = self._jobs, []
            self._job_cv.notify_all()
        for j in cancelled:
            j.future.cancel()
        if self._worker is not None:
            self._worker.join(timeout=60.0)
            self._worker = None
        if self._job_worker is not None:
            self._job_worker.join(timeout=600.0)
            self._job_worker = None
        self.drain()

    # ---- memoized tables -------------------------------------------------
    @staticmethod
    def _net_key(net: Network) -> tuple:
        # content fingerprint, not identity: two builds of the same zoo
        # entry share tables, while same-named custom nets don't collide.
        # The per-layer tuple is order-sensitive — layer order is
        # load-bearing for segmentation, so permuted nets must not alias.
        layers = hash(tuple((l.macs, l.weights_size, l.ifm_size,
                             l.ofm_size, l.residual) for l in net))
        return (net.name, len(net), net.total_macs, layers)

    def _device(self, dev: DeviceSpec | None) -> DeviceSpec:
        dev = dev if dev is not None else self.default_device
        if dev is None:
            raise ValueError("no device: pass dev= or construct the "
                             "Session with a default board")
        return dev

    def tables(self, net: Network, max_L: int | None = None) -> NetTables:
        """Memoized ``NetTables`` for ``net``, keyed by (net, bucketed
        max_L) — every evaluate/explore call on the same net reuses one
        traced pytree, so they also share one compiled program."""
        if isinstance(net, NetTables):
            return net
        L = len(net)
        bucket = bucket_max_L(L) if max_L is None \
            else (max_L if L <= max_L else bucket_max_L(L, base=max_L))
        key = self._net_key(net) + (bucket,)
        with self._table_lock:
            hit = self._net_tables.get(key)
            if hit is not None:
                self.stats.bump("net_table_hits")
                return hit
            with telemetry.span("session.net_table_build") as sp:
                sp.set_attr("net", net.name)
                sp.set_attr("max_L", bucket)
                built = make_tables(net, max_L=bucket)
            self._net_tables.put(key, built)
            self.stats.bump("net_table_builds")
            return built

    def device_tables(self, dev: DeviceSpec | None = None) -> DeviceTables:
        """Memoized ``DeviceTables`` for a board."""
        dev = self._device(dev)
        with self._table_lock:
            hit = self._dev_tables.get(dev)
            if hit is not None:
                self.stats.bump("device_table_hits")
                return hit
            with telemetry.span("session.device_table_build"):
                built = make_device_tables(dev)
            self._dev_tables.put(dev, built)
            self.stats.bump("device_table_builds")
            return built

    def multi_tables(self, nets, *, weights=None, slo_s=None,
                     max_m: int | None = None):
        """Memoized ``MultiNetTables`` for a model set (+ request weights
        and per-model SLOs) — what :meth:`deploy` evaluates against.  An
        explicit ``max_m`` wins over the config (deploy passes the search
        config's, matching the legacy joint_search semantics)."""
        from .multinet.joint_eval import make_multi_tables
        from .multinet.partition import DEFAULT_MAX_M

        if max_m is None:
            max_m = self.config.max_m or DEFAULT_MAX_M
        wkey = None if weights is None else tuple(
            float(w) for w in np.atleast_1d(np.asarray(weights, np.float64)))
        skey = None if slo_s is None else tuple(
            float(s) for s in np.atleast_1d(np.asarray(slo_s, np.float64)))
        key = (tuple(self._net_key(n) for n in nets), wkey, skey, max_m)
        with self._table_lock:
            hit = self._multi_tables.get(key)
            if hit is not None:
                self.stats.bump("multi_table_hits")
                return hit
            with telemetry.span("session.multi_table_build") as sp:
                sp.set_attr("models", len(list(nets)))
                built = make_multi_tables(list(nets), weights=weights,
                                          slo_s=slo_s, max_m=max_m)
            self._multi_tables.put(key, built)
            self.stats.bump("multi_table_builds")
            return built

    # ---- resilience ------------------------------------------------------
    def _resilient_call(self, call):
        """Run ``call(backend)`` under the session's fault policy:

        * input-shaped errors (parse/encode/shape problems) raise
          ``EvalError(INVALID_INPUT)`` immediately — retrying can't help;
        * backend faults retry the primary up to ``max_retries`` times
          with exponential backoff, feeding the circuit breaker;
        * past the retries (or with the breaker open, minus its periodic
          recovery probes) the call degrades to ``fallback_backend``.
        """
        cfg = self.config
        fallback = cfg.fallback_backend
        has_fallback = fallback is not None and fallback != cfg.backend
        if has_fallback and not self.breaker.allow_primary():
            self.stats.bump("degraded")
            telemetry.event("resilience.degrade",
                            {"reason": "breaker_open", "backend": fallback})
            return call(fallback)
        last = None
        for attempt in range(cfg.max_retries + 1):
            if attempt:
                self.stats.bump("retried")
                telemetry.event("resilience.retry", {"attempt": attempt})
                time.sleep(retry_delay(attempt))
            try:
                out = call(cfg.backend)
            except Exception as e:  # noqa: BLE001 — classified below
                if classify(e) != EvalError.BACKEND_FAULT:
                    raise wrap(e) from e
                self.breaker.record_failure()
                last = e
            else:
                self.breaker.record_success()
                return out
        if has_fallback:
            self.stats.bump("degraded")
            telemetry.event("resilience.degrade",
                            {"reason": "retries_exhausted",
                             "backend": fallback})
            try:
                return call(fallback)
            except Exception as e:  # noqa: BLE001
                raise wrap(e) from e
        raise wrap(last, EvalError.BACKEND_FAULT) from last

    def _search_backend(self) -> str:
        """Backend for the explore()/deploy() search loops: the primary,
        unless the breaker is open and a fallback exists (a whole search
        is too expensive to gamble on a recovery probe)."""
        cfg = self.config
        fb = cfg.fallback_backend
        if fb is not None and fb != cfg.backend and self.breaker.is_open:
            self.stats.bump("degraded")
            telemetry.event("resilience.degrade",
                            {"reason": "breaker_open_search",
                             "backend": fb})
            return fb
        return cfg.backend

    # ---- evaluation ------------------------------------------------------
    def _parse(self, design, net: Network,
               inter_segment_pipelining: bool) -> AcceleratorSpec:
        if isinstance(design, str):
            return parse(design, len(net),
                         inter_segment_pipelining=inter_segment_pipelining)
        return design

    def evaluate(self, designs, net: Network, dev: DeviceSpec | None = None,
                 *, inter_segment_pipelining: bool = True):
        """Evaluate design(s) of ``net`` on ``dev``, dispatching on input:

        * a single spec / notation string -> the scalar reference path,
          returning a full :class:`Metrics` (with per-segment detail) —
          bit-identical to the deprecated ``evaluate_design``;
        * a list/tuple of specs or strings -> the chunked batch path,
          returning ``{metric: np.ndarray}`` — bit-identical to the
          deprecated ``evaluate_specs``;
        * a ``DesignBatch`` -> the jitted hot path verbatim, returning
          ``{metric: jnp.ndarray}`` (arrays stay on device).

        ``inter_segment_pipelining`` applies to notation strings only
        (specs already carry the flag).
        """
        with telemetry.span("session.evaluate") as sp:
            out = self._evaluate(designs, net, dev,
                                 inter_segment_pipelining, sp)
        return out

    def _evaluate(self, designs, net, dev, inter_segment_pipelining, sp):
        dev = self._device(dev)
        if isinstance(designs, (str, AcceleratorSpec)):
            sp.set_attr("kind", "scalar")
            self.stats.bump("scalar_evals")
            try:
                m = _evaluate_design(
                    designs, net, dev,
                    inter_segment_pipelining=inter_segment_pipelining)
            except Exception as e:  # noqa: BLE001 — taxonomy boundary
                raise wrap(e) from e
            if not np.isfinite([m.latency_s, m.throughput_ips,
                                float(m.buffer_bytes)]).all():
                raise EvalError(EvalError.NONFINITE_METRICS,
                                "scalar evaluation produced non-finite "
                                "metrics")
            return m
        cfg = self.config
        if isinstance(designs, DesignBatch):
            with telemetry.span("session.validate"):
                # checked on the device: two scalars come back, not the batch
                try:
                    n_bad, first = (int(v) for v in np.asarray(
                        count_invalid_jit(designs, len(net), min_ces=1,
                                          max_ces=NC)))
                except Exception as e:  # noqa: BLE001 — malformed arrays
                    raise wrap(e, EvalError.INVALID_INPUT) from e
                if n_bad:
                    telemetry.count("session.rejected_rows", n_bad)
                    raise EvalError(
                        EvalError.INVALID_INPUT,
                        f"{n_bad} invalid DesignBatch row(s), first at "
                        f"index {first} (non-canonical segments or "
                        f"CE count outside [1, {NC}])")
            sp.set_attr("kind", "design_batch")
            sp.set_attr("designs", designs.batch)
            self.stats.bump("batch_designs", designs.batch)
            with telemetry.span("session.dispatch"):
                return self._resilient_call(lambda b: evaluate_batch(
                    designs, self.tables(net), self.device_tables(dev),
                    fm_tile_rows=cfg.fm_tile_rows, backend=b,
                    tile=cfg.tile, design_tile=cfg.design_tile,
                    mesh=self.mesh))
        with telemetry.span("session.validate"):
            try:
                specs = [self._parse(d, net, inter_segment_pipelining)
                         for d in designs]
            except Exception as e:  # noqa: BLE001
                raise wrap(e, EvalError.INVALID_INPUT) from e
            if not specs:
                raise EvalError(EvalError.INVALID_INPUT,
                                "no designs to evaluate (empty list)")
        sp.set_attr("kind", "spec_list")
        sp.set_attr("designs", len(specs))
        self.stats.bump("batch_designs", len(specs))
        out = self._resilient_call(lambda b: _evaluate_specs(
            specs, net, self.device_tables(dev),
            cfg.chunk, tables=self.tables(net),
            backend=b, tile=cfg.tile,
            fm_tile_rows=cfg.fm_tile_rows,
            design_tile=cfg.design_tile, mesh=self.mesh))
        bad = nonfinite_keys(out)
        if bad:
            raise EvalError(EvalError.NONFINITE_METRICS,
                            f"non-finite metrics {bad}")
        return out

    def build(self, design, net: Network, dev: DeviceSpec | None = None,
              *, opts=None, inter_segment_pipelining: bool = True):
        """Build the :class:`ConcreteAccelerator` for a design (the object
        ``evaluate`` scores — same parse flags, so they always agree)."""
        return build_design(design, net, self._device(dev), opts,
                            inter_segment_pipelining=inter_segment_pipelining)

    # ---- DSE -------------------------------------------------------------
    def explore(self, net: Network, n: int = 100_000,
                dev: DeviceSpec | None = None, *, strategy: str = "random",
                family: str = "custom", seed: int = 0, chunk: int = 4096,
                objectives: tuple[str, ...] = DEFAULT_OBJECTIVES,
                config=None, refine: str | None = None):
        """Single-model DSE (random sweep or guided search) through the
        session's cached tables — bit-identical to the deprecated
        ``explore`` free function at equal arguments.

        ``refine="schedule"`` re-scores the final Pareto front with the
        per-CE temporal-mapping search (``docs/schedule.md``): the sweep
        itself still runs on the coarse model (the refinement can only
        lower latency, never invalidate a front member), and the result
        gains a ``refined`` dict with schedule-refined latency/access
        arrays aligned with ``front``.
        """
        from .dse.driver import _explore

        if refine not in (None, "schedule"):
            raise EvalError(EvalError.INVALID_INPUT,
                            f"unknown refine mode {refine!r} "
                            "(expected None or 'schedule')")
        self.stats.bump("explore_calls")
        with telemetry.span("session.explore") as sp:
            sp.set_attr("n", n)
            sp.set_attr("strategy", strategy)
            res = _explore(net, self._device(dev), n, family=family,
                           seed=seed, chunk=chunk, strategy=strategy,
                           objectives=objectives, config=config,
                           tables=self.tables(net),
                           backend=self._search_backend(), mesh=self.mesh)
            if refine == "schedule" and res.front.size:
                res.refined = self._refine_front(res, net, dev, sp)
            return res

    def _refine_front(self, res, net: Network, dev, sp) -> dict:
        """Schedule-refine a DSE result's Pareto front: one batched
        schedule search over the front designs (padded to the ladder
        bucket — no compile forks), returning front-aligned arrays."""
        from ..schedule.search import schedule_batch
        from .batch_eval import _bucket, _pad_rows

        dev = self._device(dev)
        cfg = self.config
        front = res.batch.take(np.asarray(res.front))
        nf = int(res.front.size)
        padded = _pad_rows(front, _bucket(nf, cfg.tile))
        with telemetry.span("session.schedule_front") as fsp:
            fsp.set_attr("designs", nf)
            out = self._resilient_call(lambda b: schedule_batch(
                padded, self.tables(net), self.device_tables(dev),
                fm_tile_rows=cfg.fm_tile_rows, backend=b, tile=cfg.tile,
                design_tile=cfg.design_tile))
        lat = np.asarray(out["ref_latency_s"])[:nf]
        coarse = np.asarray(out["coarse_latency_s"])[:nf]
        telemetry.count("schedule.candidates",
                        int(np.asarray(out["valid_l"])[:nf].sum())
                        * self._ncand())
        sp.set_attr("refined_front", nf)
        return {
            "latency_s": lat,
            "coarse_latency_s": coarse,
            "throughput_ips": np.asarray(out["ref_throughput_ips"])[:nf],
            "access_bytes": np.asarray(out["ref_access_bytes"])[:nf],
            "coarse_access_bytes":
                np.asarray(out["coarse_access_bytes"])[:nf],
            "saving_frac": np.where(coarse > 0.0,
                                    1.0 - lat / np.maximum(coarse, 1e-30),
                                    0.0),
        }

    @staticmethod
    def _ncand() -> int:
        from ..kernels.schedule_score import NCAND
        return NCAND

    def deploy(self, nets, n: int = 4096, dev: DeviceSpec | None = None, *,
               strategy: str = "search", seed: int = 0, chunk: int = 512,
               objectives: tuple[str, ...] | None = None,
               objective: str = "serving", config=None, weights=None,
               slo_s=None):
        """Multi-CNN co-scheduling DSE (spatial / temporal / hybrid arms)
        through the session's cached ``MultiNetTables`` — bit-identical to
        the deprecated ``joint_explore`` at equal arguments."""
        from .multinet.driver import _joint_explore
        from .multinet.search import JOINT_OBJECTIVES

        # the tables must carry the same weights/SLOs/max_m the search
        # will use, whether they arrive via config or via the keywords
        w = config.weights if config is not None else weights
        s = config.slo_s if config is not None else slo_s
        mm = config.max_m if config is not None else None
        mt = self.multi_tables(nets, weights=w, slo_s=s, max_m=mm)
        self.stats.bump("deploy_calls")
        with telemetry.span("session.deploy") as sp:
            sp.set_attr("n", n)
            sp.set_attr("models", len(list(nets)))
            sp.set_attr("strategy", strategy)
            return _joint_explore(
                list(nets), self._device(dev), n, strategy=strategy,
                seed=seed, chunk=chunk,
                objectives=JOINT_OBJECTIVES if objectives is None
                else objectives,
                objective=objective, config=config, weights=weights,
                slo_s=slo_s, mtables=mt, backend=self._search_backend(),
                mesh=self.mesh)

    # ---- bottleneck attribution (paper use case 2) -----------------------
    def explain(self, design, net: Network, dev: DeviceSpec | None = None,
                *, inter_segment_pipelining: bool = True,
                refine: str | None = None) -> dict:
        """Rank where a single design's time and off-chip traffic go.

        Evaluates ``design`` through the exact scalar path (full
        per-segment / per-layer / per-CE detail) and returns the
        :func:`repro.telemetry.report.bottleneck_report` dict: segments
        ranked by occupancy with compute/memory bound verdicts, the
        busiest CE, Fig. 6's memory-bound layers + idle fraction and
        Fig. 7's weights-vs-FMs access split — bit-identical to
        ``benchmarks/fig6_fig7_breakdown.py``'s formulas
        (``docs/observability.md`` walks through the output).

        ``refine="schedule"`` additionally runs the per-CE temporal-
        mapping search (:meth:`schedule`) and attaches its refined
        per-segment costs as a ``"schedule"`` section — coarse vs
        refined cycles per segment and the headline latency saving
        (``docs/schedule.md``).
        """
        from ..telemetry.report import bottleneck_report

        if not isinstance(design, (str, AcceleratorSpec)):
            raise EvalError(
                EvalError.INVALID_INPUT,
                "explain() takes one design (notation string or "
                "AcceleratorSpec); use evaluate() for batches")
        if refine not in (None, "schedule"):
            raise EvalError(EvalError.INVALID_INPUT,
                            f"unknown refine mode {refine!r} "
                            "(expected None or 'schedule')")
        with telemetry.span("session.explain") as sp:
            m = self._evaluate(design, net, dev,
                               inter_segment_pipelining, sp)
            art = None
            if refine == "schedule":
                art = self.schedule(
                    design, net, dev,
                    inter_segment_pipelining=inter_segment_pipelining)
            return bottleneck_report(m, schedule=art)

    def schedule(self, design, net: Network, dev: DeviceSpec | None = None,
                 *, inter_segment_pipelining: bool = True):
        """Per-CE temporal-mapping search under one design: refine the
        coarse MCCM estimate by choosing each layer's loop order, tile
        size and buffering from an explicit candidate plane, scored in
        the same cost terms (``docs/schedule.md``).

        Returns the JSON-serializable
        :class:`~repro.schedule.ScheduleArtifact` — refined vs coarse
        latency/traffic/energy, per-layer chosen mappings, per-CE buffer
        plans and per-segment costs.  Refined latency never exceeds the
        coarse estimate (candidate 0 IS the coarse mapping).  Artifacts
        memoize per (net, board, design) in a bounded LRU; the device
        search rides the same bucket-ladder shapes as ``evaluate``, so
        warm calls add zero compiles.
        """
        from ..schedule import build_artifact
        from ..schedule.search import schedule_specs
        from .dse.encoding import encode_specs
        from .notation import format_spec

        if not isinstance(design, (str, AcceleratorSpec)):
            raise EvalError(
                EvalError.INVALID_INPUT,
                "schedule() takes one design (notation string or "
                "AcceleratorSpec)")
        dev = self._device(dev)
        self.stats.bump("schedule_calls")
        try:
            spec = self._parse(design, net, inter_segment_pipelining)
            spec.validate(len(net))
            enc = encode_specs([spec], len(net))
        except Exception as e:  # noqa: BLE001
            raise wrap(e, EvalError.INVALID_INPUT) from e
        key = (self._net_key(net), dev) + tuple(
            np.asarray(a).tobytes() for a in enc.to_numpy())
        with self._table_lock:
            hit = self._schedule_memo.get(key)
        if hit is not None:
            self.stats.bump("schedule_hits")
            return hit
        cfg = self.config
        with telemetry.span("session.schedule") as sp:
            sp.set_attr("net", net.name)
            sp.set_attr("board", dev.name)
            out = self._resilient_call(lambda b: schedule_specs(
                [spec], net, self.device_tables(dev),
                tables=self.tables(net), backend=b, tile=cfg.tile,
                fm_tile_rows=cfg.fm_tile_rows,
                design_tile=cfg.design_tile))
            if not np.isfinite([float(out["ref_latency_s"][0]),
                                float(out["coarse_latency_s"][0])]).all():
                raise EvalError(EvalError.NONFINITE_METRICS,
                                "schedule search produced non-finite "
                                "latency")
            art = build_artifact(
                out, 0, net=net, board_name=dev.name,
                design_repr=format_spec(spec, len(net)),
                wordbytes=dev.wordbytes)
            sp.set_attr("candidates", art.n_candidates)
            sp.set_attr("n_refined", art.meta.get("n_refined", 0))
        telemetry.count("schedule.candidates", art.n_candidates)
        telemetry.count("schedule.searches")
        with self._table_lock:
            self._schedule_memo.put(key, art)
        self.stats.bump("schedule_builds")
        return art

    # ---- queued requests (the serve-many-users path) ---------------------
    def submit(self, designs, net: Network,
               dev: DeviceSpec | None = None, *,
               inter_segment_pipelining: bool = True,
               deadline_s: float | None = None,
               priority: str = "interactive") -> Future:
        """Queue an evaluation request; returns a ``Future``.

        A background drain loop collects everything queued within the
        linger window (fixed ``linger_s``, or arrival-rate adaptive when
        ``linger_max_s`` is set), coalesces it — tiny same-(net, board)
        requests merge into shared padded chunks, oversized requests
        split at the compiled chunk size — and megabatches it through ONE
        compiled program (all chunks pad to a shared ladder shape, so
        mixed CNNs × boards still reuse the same compile).  The future
        resolves to ``{metric: np.ndarray}`` over the submitted specs; a
        single spec/string resolves to ``{metric: float}``.

        ``priority`` is the request's lane: ``"interactive"`` requests
        are planned and delivered ahead of ``"batch"`` ones in every
        drain, so bulk traffic cannot starve point evaluations
        (docs/serving.md).

        Failure semantics (docs/robustness.md): malformed designs raise
        ``EvalError(INVALID_INPUT)`` here, synchronously; with
        ``max_queue`` set, an over-full queue raises
        ``EvalError(QUEUE_FULL)``; ``deadline_s`` (defaulting to the
        config's) fails the future with ``EvalError(DEADLINE_EXCEEDED)``
        if the result can't be delivered in time — a request never hangs.
        """
        scalar = isinstance(designs, (str, AcceleratorSpec))
        raw = [designs] if scalar else list(designs)
        with telemetry.span("session.submit") as sp:
            sp.set_attr("designs", len(raw))
            sp.set_attr("priority", priority)
            return self._submit(raw, net, dev, scalar,
                                inter_segment_pipelining, deadline_s,
                                priority)

    def _submit(self, raw, net, dev, scalar, inter_segment_pipelining,
                deadline_s, priority="interactive") -> Future:
        if priority not in PRIORITIES:
            raise EvalError(EvalError.INVALID_INPUT,
                            f"unknown priority {priority!r}; "
                            f"known: {PRIORITIES}")
        try:
            specs = [self._parse(d, net, inter_segment_pipelining)
                     for d in raw]
        except Exception as e:  # noqa: BLE001 — taxonomy boundary
            raise wrap(e, EvalError.INVALID_INPUT) from e
        if not specs:
            # reject here: an empty job inside a megabatch would fail the
            # whole batch's futures, not just this one
            raise EvalError(EvalError.INVALID_INPUT,
                            "no designs to submit (empty list)")
        cfg = self.config
        if deadline_s is None:
            deadline_s = cfg.deadline_s
        deadline = None if deadline_s is None \
            else time.monotonic() + deadline_s
        req = _Request(specs, net, self._device(dev), Future(), scalar,
                       deadline, priority)
        with self._cv:
            if self._closed:
                raise RuntimeError(
                    "session closed: submit() is refused after close() "
                    "(the drain loop is stopped; synchronous evaluate() "
                    "still works)")
            if cfg.max_queue is not None \
                    and len(self._pending) + len(self._jobs) \
                    >= cfg.max_queue:
                self.stats.bump("rejected")
                telemetry.event("resilience.rejected",
                                {"queue": len(self._pending)})
                raise EvalError(
                    EvalError.QUEUE_FULL,
                    f"submit queue full ({cfg.max_queue} pending "
                    f"requests); retry after the queue drains")
            self._arrivals.observe(time.monotonic())
            self._pending.append(req)
            telemetry.gauge("session.queue_depth", len(self._pending))
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._drain_loop, name="repro-session-drain",
                    daemon=True)
                self._worker.start()
            self._cv.notify_all()
        self.stats.bump("submits")
        return req.future

    # ---- the batch lane: long search jobs --------------------------------
    def submit_search(self, nets, n: int = 100_000,
                      dev: DeviceSpec | None = None, *,
                      deadline_s: float | None = None,
                      checkpoint_path: str | None = None,
                      checkpoint_interval: int = 8,
                      **kw) -> Future:
        """Queue a long DSE job — :meth:`explore` for a single ``Network``,
        :meth:`deploy` for a list — on the batch lane; returns a
        ``Future`` resolving to the search result.

        Jobs run FIFO on a dedicated worker thread, so the interactive
        megabatch drain never blocks behind a 100k-budget search; the
        evaluations inside the job still flow through the session's
        cached tables and compiled programs.  ``checkpoint_path`` makes a
        ``strategy="search"`` job preemptible: the search snapshots every
        ``checkpoint_interval`` generations and a resubmitted job (or a
        restarted server) resumes bit-identically from the snapshot
        (docs/robustness.md).  Admission control (``max_queue``) counts
        queued jobs; a job whose ``deadline_s`` passes while queued fails
        with ``DEADLINE_EXCEEDED`` without spending any search budget.
        """
        from .workload import Network as _Network

        is_single = isinstance(nets, (_Network, NetTables))
        kind = "explore" if is_single else "deploy"
        if checkpoint_path is not None:
            if kw.get("strategy", "random" if is_single else "search") \
                    != "search":
                raise EvalError(
                    EvalError.INVALID_INPUT,
                    "checkpoint_path requires strategy='search' (the "
                    "random sweep has no loop state to snapshot)")
            config = kw.get("config")
            if config is None:
                from .dse.search import SearchConfig
                from .multinet.search import MultinetSearchConfig
                config = SearchConfig() if is_single \
                    else MultinetSearchConfig()
                if "seed" in kw:
                    config = replace(config, seed=kw["seed"])
            kw["config"] = replace(config,
                                   checkpoint_path=checkpoint_path,
                                   checkpoint_interval=checkpoint_interval,
                                   resume=True)

        def job():
            if kind == "explore":
                return self.explore(nets, n, dev, **kw)
            return self.deploy(nets, n, dev, **kw)

        cfg = self.config
        deadline = None if deadline_s is None \
            else time.monotonic() + deadline_s
        j = _SearchJob(job, Future(), deadline, label=kind)
        with self._job_cv:
            if self._closed:
                raise RuntimeError(
                    "session closed: submit_search() is refused after "
                    "close()")
            if cfg.max_queue is not None \
                    and len(self._jobs) + len(self._pending) \
                    >= cfg.max_queue:
                self.stats.bump("rejected")
                telemetry.event("resilience.rejected",
                                {"queue": len(self._jobs),
                                 "lane": "batch"})
                raise EvalError(
                    EvalError.QUEUE_FULL,
                    f"search-job queue full ({cfg.max_queue} pending); "
                    f"retry after the queue drains")
            self._jobs.append(j)
            telemetry.gauge("session.job_queue_depth", len(self._jobs))
            if self._job_worker is None:
                self._job_worker = threading.Thread(
                    target=self._job_loop, name="repro-session-jobs",
                    daemon=True)
                self._job_worker.start()
            self._job_cv.notify_all()
        self.stats.bump("search_jobs")
        return j.future

    def _job_loop(self) -> None:
        while True:
            with self._job_cv:
                while not self._jobs and not self._closed:
                    self._job_cv.wait()
                if not self._jobs:        # closed and drained
                    return
                j = self._jobs.pop(0)
                self._job_running = True
            try:
                self._run_job(j)
            finally:
                with self._job_cv:
                    self._job_running = False
                    self._job_cv.notify_all()

    def _run_job(self, j: _SearchJob) -> None:
        if not j.future.set_running_or_notify_cancel():
            return
        if j.deadline is not None and time.monotonic() > j.deadline:
            self.stats.bump("deadline_missed")
            telemetry.event("resilience.deadline_missed",
                            {"where": "job_queued"})
            j.future.set_exception(EvalError(
                EvalError.DEADLINE_EXCEEDED,
                "deadline passed while the search job was queued"))
            return
        with telemetry.span("session.search_job") as sp:
            sp.set_attr("kind", j.label)
            telemetry.observe("session.job_queue_wait_s",
                              time.monotonic() - j.t_enq)
            try:
                out = j.fn()
            except BaseException as e:  # noqa: BLE001 — job isolation
                j.future.set_exception(wrap(e))
                if not isinstance(e, Exception):
                    raise
            else:
                j.future.set_result(out)

    def drain(self) -> int:
        """Synchronously megabatch everything currently queued (also what
        the background loop runs); returns the number of requests served.
        Interactive-lane requests are planned and delivered ahead of
        batch-lane ones (stable within a lane)."""
        with self._cv:
            reqs, self._pending = self._pending, []
        if reqs:
            reqs.sort(key=lambda r: PRIORITIES.index(r.priority))
            self._run_megabatch(reqs)
        return len(reqs)

    def _linger(self) -> float:
        """The next drain's linger window: fixed ``linger_s``, or the
        arrival-rate-adaptive policy when ``linger_max_s`` is armed
        (~2 observed inter-arrivals, capped — docs/serving.md)."""
        cfg = self.config
        if cfg.linger_max_s is None:
            return cfg.linger_s
        return self._arrivals.linger(cfg.linger_max_s)

    def _drain_loop(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._closed:
                    self._cv.wait()
                if self._closed and not self._pending:
                    return
            # linger so concurrent submitters land in the same megabatch
            time.sleep(self._linger())
            self.drain()

    def _deliver(self, r: _Request, out: dict) -> None:
        if not r.future.set_running_or_notify_cancel():
            return
        if r.scalar:
            out = {k: float(v[0]) for k, v in out.items()}
        r.future.set_result(out)

    def _fail(self, r: _Request, exc: BaseException) -> None:
        if r.future.set_running_or_notify_cancel():
            r.future.set_exception(wrap(exc))

    def _expire(self, reqs: list[_Request]) -> list[_Request]:
        """Fail requests whose deadline already passed (DEADLINE_EXCEEDED)
        before spending any evaluation on them; returns the live rest."""
        now = time.monotonic()
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                self.stats.bump("deadline_missed")
                telemetry.event("resilience.deadline_missed",
                                {"where": "queued"})
                self._fail(r, EvalError(
                    EvalError.DEADLINE_EXCEEDED,
                    "deadline passed while the request was queued"))
            else:
                live.append(r)
        return live

    def _finish(self, r: _Request, out: dict) -> None:
        """Finite-guard + deadline-check one request's result, then
        deliver: NaN/Inf rows fail THEIR future, not the megabatch, and
        strict deadlines refuse late delivery."""
        bad = nonfinite_keys(out)
        if bad:
            self._fail(r, EvalError(EvalError.NONFINITE_METRICS,
                                    f"non-finite metrics {bad}"))
            return
        if r.deadline is not None and time.monotonic() > r.deadline:
            self.stats.bump("deadline_missed")
            telemetry.event("resilience.deadline_missed",
                            {"where": "evaluated"})
            self._fail(r, EvalError(EvalError.DEADLINE_EXCEEDED,
                                    "deadline passed during evaluation"))
            return
        self.stats.bump("megabatch_requests")
        telemetry.observe("session.request_latency_s",
                          time.monotonic() - r.t_enq)
        self._deliver(r, out)

    def _eval_one(self, r: _Request, backend: str | None = None) -> dict:
        cfg = self.config
        return _evaluate_specs(r.specs, r.net, self.device_tables(r.dev),
                               cfg.chunk, tables=self.tables(r.net),
                               backend=backend or cfg.backend,
                               tile=cfg.tile,
                               fm_tile_rows=cfg.fm_tile_rows,
                               design_tile=cfg.design_tile, mesh=self.mesh)

    def _run_megabatch(self, reqs: list[_Request]) -> None:
        # the outer net: whatever goes wrong below, every future resolves
        # — a drain must never leave callers hanging
        try:
            self._run_megabatch_inner(reqs)
        except BaseException as e:  # noqa: BLE001
            for r in reqs:
                if not r.future.done():
                    self._fail(r, e)
            if not isinstance(e, Exception):   # KeyboardInterrupt etc.
                raise

    def _run_megabatch_inner(self, reqs: list[_Request]) -> None:
        with telemetry.span("session.megabatch") as sp:
            sp.set_attr("requests", len(reqs))
            self._run_megabatch_spanned(reqs, sp)

    def _run_megabatch_spanned(self, reqs: list[_Request], sp) -> None:
        cfg = self.config
        reqs = self._expire(reqs)
        if not reqs:
            return
        if telemetry.enabled():
            # per-request queue wait + batch shape, measured at the top
            # of the drain (docs/observability.md metric catalog)
            now = time.monotonic()
            for r in reqs:
                telemetry.observe("session.queue_wait_s", now - r.t_enq)
            telemetry.observe("session.megabatch_fill",
                              len(reqs), bounds=tuple(
                                  float(2 ** i) for i in range(16)))
            telemetry.gauge("session.megabatch_size", len(reqs))
            telemetry.gauge("session.linger_s", cfg.linger_s)
        # memoized tables for BOTH axes, built per request under its own
        # guard: one request's broken net/board fails ITS future only,
        # the rest still megabatch together
        ready: list[tuple[_Request, object, object]] = []
        for r in reqs:
            try:
                tab = self.tables(r.net)
                dtab = self.device_tables(r.dev)
            except Exception as e:  # noqa: BLE001
                self._fail(r, wrap(e, EvalError.INVALID_INPUT))
            else:
                ready.append((r, tab, dtab))
        if not ready:
            return
        if cfg.coalesce:
            jobs, tabs, scatter = self._coalesce_jobs(ready, sp)
        else:
            # one padded chunk per request (the pre-coalescing drain)
            jobs = [(r.specs, r.net, dtab) for r, _, dtab in ready]
            tabs = [tab for _, tab, _ in ready]
            scatter = None
        try:
            results = self._resilient_call(
                lambda b: _evaluate_specs_multi(
                    jobs, cfg.chunk, backend=b,
                    tile=cfg.tile, tables=tabs,
                    fm_tile_rows=cfg.fm_tile_rows,
                    design_tile=cfg.design_tile, mesh=self.mesh))
        except Exception:  # noqa: BLE001 — isolate the bad job(s)
            # one malformed request must not poison its co-queued peers:
            # retry per request so each future gets ITS OWN result/error
            for r, _, _ in ready:
                try:
                    out = self._resilient_call(
                        lambda b, r=r: self._eval_one(r, b))
                except Exception as e:  # noqa: BLE001
                    self._fail(r, e)
                else:
                    self._finish(r, out)
            return
        self.stats.bump("megabatches")
        if scatter is None:
            for (r, _, _), out in zip(ready, results):
                self._finish(r, out)
            return
        scatter(results)

    def _coalesce_jobs(self, ready, sp):
        """Plan the coalesced megabatch: merge-compatible requests (same
        memoized ``NetTables`` object + same board) pack into shared
        chunks, oversized requests split at the compiled chunk size
        (``core.coalesce``).  Returns ``(jobs, tabs, scatter)`` where
        ``jobs`` holds one ``(specs, net, dev)`` triple per chunk and
        ``scatter(results)`` slices the per-chunk metric arrays back to
        each request's future — every request answered exactly once, in
        its own spec order, NaN rows still failing only their request."""
        cfg = self.config
        nd = self.mesh.ndevices if self.mesh.is_sharded else 1
        keyed = [((id(tab), id(dtab)), len(r.specs))
                 for r, tab, dtab in ready]
        plan = plan_megabatch(keyed, cfg.chunk, cfg.tile, nd)
        by_key = {}
        for i, (key, _) in enumerate(keyed):
            by_key.setdefault(key, i)
        jobs, tabs = [], []
        for c in plan.chunks:
            specs = []
            for p in c.parts:
                specs.extend(ready[p.req][0].specs[p.lo:p.hi])
            lead = ready[by_key[c.group]]
            jobs.append((specs, lead[0].net, lead[0].dev))
            tabs.append(lead[1])
        self.stats.bump("coalesced_chunks", len(plan.chunks))
        if plan.merges:
            self.stats.bump("coalesced_merges", plan.merges)
        if plan.splits:
            self.stats.bump("coalesced_splits", plan.splits)
        sp.set_attr("chunks", len(plan.chunks))
        sp.set_attr("shared_pad", plan.shared_pad)

        def scatter(results):
            pieces: dict[int, list] = {i: [] for i in range(len(ready))}
            for c, out in zip(plan.chunks, results):
                off = 0
                for p in c.parts:
                    n = len(p)
                    pieces[p.req].append(
                        (p.lo, {k: v[off:off + n]
                                for k, v in out.items()}))
                    off += n
            for i, (r, _, _) in enumerate(ready):
                parts = sorted(pieces[i], key=lambda t: t[0])
                outs = [d for _, d in parts]
                if len(outs) == 1:
                    self._finish(r, outs[0])
                else:
                    self._finish(r, {k: np.concatenate(
                        [o[k] for o in outs]) for k in outs[0]})

        return jobs, tabs, scatter

    # ---- observability ---------------------------------------------------
    def compile_stats(self) -> dict[str, int]:
        """Compiled-program counts of every jitted entry point the session
        drives.  ``total`` is the compile-miss counter the cache-reuse
        tests assert on: warm calls must not move it."""
        import importlib

        from . import batch_eval

        # the package re-exports a `search` FUNCTION, shadowing the
        # submodule attribute — resolve the module explicitly
        dse_search = importlib.import_module(".dse.search", __package__)
        counts = {
            "validate_batch": count_invalid_jit._cache_size(),
            "evaluate_batch": batch_eval._evaluate_jit._cache_size(),
            "dse_step": sum(f._cache_size()
                            for f in dse_search._STEP_CACHE.values()),
        }
        try:
            from .multinet import joint_eval as je
            counts["joint_spatial"] = je._joint_spatial_jit._cache_size()
            counts["joint_temporal"] = je._joint_temporal_jit._cache_size()
            counts["joint_hybrid"] = je._joint_hybrid_jit._cache_size()
        except ImportError:  # pragma: no cover — multinet always ships
            pass
        try:
            from ..schedule import search as sched
            counts["schedule_batch"] = sched._schedule_jit._cache_size()
            counts["schedule_plane"] = sched._plane_jit._cache_size()
        except ImportError:  # pragma: no cover — schedule always ships
            pass
        from .shard import mesh_compile_counts
        for name, n in mesh_compile_counts().items():
            counts[f"mesh_{name}"] = n
        counts["total"] = sum(v for k, v in counts.items() if k != "total")
        # resilience counters ride along for one-stop observability; they
        # are NOT compile counts, so they stay out of `total` (and are all
        # zero on a clean run — the warm-round equality tests still hold)
        counts["rejected"] = self.stats.rejected
        counts["retried"] = self.stats.retried
        counts["degraded"] = self.stats.degraded
        counts["deadline_missed"] = self.stats.deadline_missed
        return counts

    def cache_stats(self) -> dict[str, dict[str, int]]:
        """Size / bound / eviction counters of every bounded cache the
        session owns: the three table memos plus the mesh's sharded-jit
        registry (``docs/serving.md``).  A long-lived server's memory
        guarantee is exactly ``size <= maxsize`` here."""
        with self._table_lock:
            out = {
                "net_tables": self._net_tables.stats(),
                "device_tables": self._dev_tables.stats(),
                "multi_tables": self._multi_tables.stats(),
                "schedule_artifacts": self._schedule_memo.stats(),
            }
        out["mesh_jits"] = {"size": len(self.mesh._jits),
                            "maxsize": self.mesh.max_jits,
                            "evictions": self.mesh.jit_evictions}
        return out

    def observability(self) -> dict:
        """One-stop report: compile counts, session counters, bounded-
        cache occupancy/evictions, breaker state and — when telemetry is
        enabled — the full metrics registry snapshot (counters/gauges/
        histograms with p50/p90/p99/p999), merged into one dict
        (``docs/observability.md``)."""
        return {
            "compile": self.compile_stats(),
            "stats": self.stats.as_dict(),
            "caches": self.cache_stats(),
            "breaker": {"open": self.breaker.is_open,
                        "trips": self.breaker.trips},
            "telemetry": telemetry.snapshot(),
        }


# --------------------------------------------------------------------------
# the process-wide default session
# --------------------------------------------------------------------------
_DEFAULT_LOCK = threading.Lock()
_DEFAULT: Session | None = None


def default_session(**overrides) -> Session:
    """The process-wide shared session (what benchmarks and examples use).

    Created on first call; ``overrides`` (EvalConfig fields or ``dev=``)
    apply only then — asking for different settings once it exists is an
    error, construct a private :class:`Session` instead."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = Session(**overrides)
        elif overrides:
            raise ValueError(
                "the default session already exists; construct "
                "Session(...) directly for different settings")
        return _DEFAULT
