"""Bounded LRU caches for the long-lived serving path, and the placement
of JAX's persistent compilation cache.

A :class:`Session` memoizes ``NetTables``/``DeviceTables``/
``MultiNetTables`` and the mesh's sharded jits.  In a notebook those
memos only ever hold a handful of entries; a long-lived *server* under
millions of distinct (net, board) keys would grow them without bound.
:class:`BoundedLRU` is the shared eviction policy: least-recently-used
entries fall out once ``maxsize`` is reached, with an eviction counter
(surfaced in ``Session.observability()``) and an optional ``on_evict``
callback so owners can fold evicted state into their own accounting
(``core.shard`` keeps compile counters monotone this way).

Thread safety is the *owner's* job — the Session holds its table lock
across get+put, exactly as it did over the plain dicts.  Bounds resolve
from the environment once, at session construction:
``REPRO_CACHE_TABLES`` / ``REPRO_CACHE_JITS`` (``docs/serving.md``).

Compiled programs persist on disk through JAX's own compilation cache
(:func:`enable_compilation_cache`, called by every ``Session``): where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and that directory is
used as it is; otherwise the cache lives at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from pathlib import Path
from typing import Callable

#: env knobs (read once, in ``EvalConfig.resolved()``)
TABLES_ENV = "REPRO_CACHE_TABLES"
JITS_ENV = "REPRO_CACHE_JITS"

#: defaults for a long-lived server: generous enough that interactive
#: sessions and the test suite never evict, small enough to bound memory
DEFAULT_MAX_TABLES = 256
DEFAULT_MAX_JITS = 128


#: where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset.  A fixed path: the directory is part of what a later process
#: looks entries up by, so a moving one would never hit.
DEFAULT_COMPILE_CACHE = str(Path(__file__).resolve().parents[3]
                            / ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  A directory JAX already has (from
    ``JAX_COMPILATION_CACHE_DIR`` or an earlier ``jax.config`` update) is
    kept as it is; only when there is none is ``DEFAULT_COMPILE_CACHE``
    set.  Idempotent."""
    import jax

    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = DEFAULT_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def env_bound(env: str, default: int) -> int:
    """Resolve a cache bound from the environment.  ``0`` (or a negative
    value) means *unbounded* — the cache never evicts."""
    raw = os.environ.get(env)
    if raw is None or raw == "":
        return default
    try:
        return int(raw)
    except ValueError as e:
        raise ValueError(f"{env} must be an integer, got {raw!r}") from e


class BoundedLRU:
    """An ordered mapping that evicts its least-recently-used entry past
    ``maxsize``.  ``maxsize <= 0`` disables eviction (plain memo dict).

    Not thread-safe by itself: callers hold their own lock across
    :meth:`get`/:meth:`put` (the Session's table lock already covers the
    check+build+insert sequence).
    """

    def __init__(self, maxsize: int = 0, *,
                 on_evict: Callable[[object, object], None] | None = None):
        self.maxsize = int(maxsize)
        self.on_evict = on_evict
        self.evictions = 0
        self._d: OrderedDict = OrderedDict()

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d

    def get(self, key, default=None):
        """Look up ``key``, refreshing its recency on a hit."""
        try:
            val = self._d[key]
        except KeyError:
            return default
        self._d.move_to_end(key)
        return val

    def put(self, key, value) -> None:
        """Insert (or refresh) ``key`` and evict LRU entries past the
        bound.  ``on_evict(key, value)`` runs for each victim — exceptions
        there propagate (the owner's accounting must not fail silently)."""
        self._d[key] = value
        self._d.move_to_end(key)
        if self.maxsize <= 0:
            return
        while len(self._d) > self.maxsize:
            k, v = self._d.popitem(last=False)
            self.evictions += 1
            if self.on_evict is not None:
                self.on_evict(k, v)

    def keys(self):
        return self._d.keys()

    def values(self):
        return self._d.values()

    def items(self):
        return self._d.items()

    def clear(self) -> None:
        self._d.clear()

    def stats(self) -> dict[str, int]:
        """Size / bound / eviction counters, as ``observability()``
        reports them."""
        return {"size": len(self._d), "maxsize": self.maxsize,
                "evictions": self.evictions}
