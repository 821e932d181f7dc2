"""Design-axis sharding: one mesh, many devices, same numbers.

``EvalMesh`` partitions the *design axis* of the evaluation programs
across devices with ``shard_map``: NetTables / DeviceTables are
replicated (small traced pytrees), ``DesignBatch`` rows are sharded, and
tails are padded to ``ndevices x tile`` so every shard sees identical
static shapes.  All evaluator arithmetic is row-local (reductions only
run *within* a design row), so the sharded program is bit-identical to
the single-device one — and on one device the mesh simply delegates to
the existing jits (zero extra compiles).

Device discovery honours ``REPRO_MESH_DEVICES`` (docs/perf.md).  For CPU
scaling runs the module force-splits the host platform into that many
devices, provided it is imported before jax initialises its backends —
the one supported path; callers never craft ``XLA_FLAGS`` by hand.
"""
from __future__ import annotations

import os
from functools import partial

import numpy as np

MESH_ENV = "REPRO_MESH_DEVICES"
MESH_AXIS = "designs"
_FORCE_FLAG = "--xla_force_host_platform_device_count"


def force_host_devices(n: int) -> bool:
    """Ask XLA for ``n`` host (CPU) devices.  Must run before jax
    initialises its backends; importing this module with
    ``REPRO_MESH_DEVICES`` set does it for you.  No-op (returns True)
    when a forced count is already in place; returns False for n < 2."""
    if n < 2:
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    if _FORCE_FLAG in flags:
        return True
    os.environ["XLA_FLAGS"] = f"{flags} {_FORCE_FLAG}={n}".strip()
    return True


def env_mesh_devices() -> int | None:
    """Parse ``REPRO_MESH_DEVICES`` (None when unset/empty)."""
    raw = os.environ.get(MESH_ENV)
    if not raw:
        return None
    n = int(raw)
    if n < 1:
        raise ValueError(f"{MESH_ENV} must be >= 1, got {raw!r}")
    return n


# Applied at import time so ``REPRO_MESH_DEVICES=4 python ...`` is the
# whole multi-device recipe on CPU hosts.  Harmless under real
# accelerator backends — the flag only affects the host platform.
_env_n = os.environ.get(MESH_ENV, "")
if _env_n.isdigit():
    force_host_devices(int(_env_n))

import jax                                           # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P    # noqa: E402

from . import telemetry                              # noqa: E402
from .batch_eval import (                            # noqa: E402
    DEFAULT_TILE, _pad_rows, evaluate_batch_traced, padded_rows)
from .cache import (                                 # noqa: E402
    DEFAULT_MAX_JITS, JITS_ENV, BoundedLRU, env_bound)

#: every *live* sharded jit (name, jitted fn) — Session.compile_stats
#: sums ``_cache_size()`` over this to count per-mesh compiles.
_REGISTRY: list[tuple[str, object]] = []
#: compile counts of evicted jits, folded in at eviction time so
#: ``mesh_compile_counts`` stays monotone across LRU turnover (a cache
#: that forgets a program must not forget that it was compiled).
_EVICTED_COUNTS: dict[str, int] = {}


def mesh_compile_counts() -> dict[str, int]:
    """Compiled-program count per sharded entry point, over all meshes —
    live jits plus everything evicted by the bounded registry (monotone:
    eviction frees the program, not its history)."""
    out: dict[str, int] = dict(_EVICTED_COUNTS)
    for name, fn in _REGISTRY:
        out[name] = out.get(name, 0) + fn._cache_size()
    return out


class EvalMesh:
    """A 1-D device mesh over the design axis.

    ``ndevices`` resolution order: explicit argument, then
    ``REPRO_MESH_DEVICES``, then every visible device.  A request beyond
    the visible device count clamps (recorded in ``requested``) — asking
    for 8 devices on a 1-device host lands on the single-device fallback,
    it is not an error.
    """

    def __init__(self, ndevices: int | None = None, *, devices=None,
                 max_jits: int | None = None):
        if devices is None:
            avail = jax.devices()
            want = ndevices if ndevices is not None else env_mesh_devices()
            want = len(avail) if want is None else want
            if want < 1:
                raise ValueError(f"ndevices must be >= 1, got {want}")
            self.requested = want
            devices = avail[:min(want, len(avail))]
        else:
            devices = list(devices)
            self.requested = len(devices)
        self.devices = tuple(devices)
        self._mesh: Mesh | None = None
        # bounded: a long-lived server cycling many (backend, tile, ...)
        # statics must not pin every sharded program forever.  Eviction
        # drops the program (a re-request recompiles) but folds its
        # compile count into _EVICTED_COUNTS so observability stays
        # monotone.  max_jits <= 0 disables eviction.
        if max_jits is None:
            max_jits = env_bound(JITS_ENV, DEFAULT_MAX_JITS)
        self._jits = BoundedLRU(max_jits, on_evict=self._on_evict_jit)

    @property
    def jit_evictions(self) -> int:
        """Sharded programs dropped by this mesh's bounded jit registry."""
        return self._jits.evictions

    @property
    def max_jits(self) -> int:
        return self._jits.maxsize

    def _on_evict_jit(self, key, jitted) -> None:
        name = key[0]
        _EVICTED_COUNTS[name] = _EVICTED_COUNTS.get(name, 0) \
            + jitted._cache_size()
        for i, (n, fn) in enumerate(_REGISTRY):
            if fn is jitted:
                del _REGISTRY[i]
                break
        telemetry.count("shard.jit_evictions")
        telemetry.event("shard.jit_evict",
                        {"name": name, "ndevices": self.ndevices})

    @property
    def ndevices(self) -> int:
        return len(self.devices)

    @property
    def is_sharded(self) -> bool:
        return self.ndevices > 1

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self._mesh = Mesh(np.asarray(self.devices), (MESH_AXIS,))
        return self._mesh

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (f"EvalMesh(ndevices={self.ndevices}, "
                f"requested={self.requested})")

    def padded_rows(self, B: int, tile: int = DEFAULT_TILE) -> int:
        """Rows actually executed for a B-design sharded call."""
        return padded_rows(B, tile, self.ndevices)

    # -- generic sharded-jit factory ------------------------------------
    def shard_jit(self, name: str, fn, *, replicated=(), static_kwargs=None,
                  donate_argnums=()):
        """``jit(shard_map(partial(fn, **static_kwargs)))`` with
        positional arg ``i`` replicated when ``i in replicated`` and
        row-sharded otherwise; memoised per (name, statics) so repeat
        calls reuse the compiled program."""
        statics = tuple(sorted((static_kwargs or {}).items()))
        key = (name, statics)
        cached = self._jits.get(key)      # refreshes LRU recency on a hit
        if cached is not None:
            return cached
        body = partial(fn, **dict(statics)) if statics else fn
        mesh = self.mesh
        repl = frozenset(replicated)

        def run(*args):
            specs = tuple(P() if i in repl else P(MESH_AXIS)
                          for i in range(len(args)))
            # check_vma=False: every row is computed on its own shard and
            # no collective runs, so the varying-axes check has nothing to
            # guard — and it rejects the Pallas kernel, whose out_shape
            # carries no vma, in compiled and interpret mode alike
            return jax.shard_map(body, mesh=mesh, in_specs=specs,
                                 out_specs=P(MESH_AXIS),
                                 check_vma=False)(*args)

        jitted = jax.jit(run, donate_argnums=donate_argnums)
        _REGISTRY.append((name, jitted))
        self._jits.put(key, jitted)
        telemetry.count("shard.jit_builds")
        telemetry.event("shard.jit_build",
                        {"name": name, "ndevices": self.ndevices})
        return jitted

    # -- the evaluator entry point --------------------------------------
    def evaluate_padded(self, design, tables, devt, *, backend, tile,
                        fm_tile_rows, pes_hint_static, design_tile):
        """Sharded ``evaluate_batch``: pad rows to ``ndevices x tile``,
        shard the design axis, slice the pad back off.  Each shard holds
        a whole number of ``lax.map`` tiles, so tile grouping — and hence
        every intermediate — matches the single-device program exactly."""
        B = design.batch
        run = self.shard_jit(
            "evaluate_batch", evaluate_batch_traced, replicated=(1, 2),
            static_kwargs=dict(backend=backend, tile=tile,
                               fm_tile_rows=fm_tile_rows,
                               pes_hint_static=pes_hint_static,
                               design_tile=design_tile))
        padded = _pad_rows(design, self.padded_rows(B, tile))
        out = run(padded, tables, devt)
        return {k: v[:B] for k, v in out.items()}
