"""The chip a run stands on: the guard that refuses anything but enough
TPU chips, the compile counter, the compile cache and the memory peak."""
from __future__ import annotations

import os
import sys


class NoChip(SystemExit):
    """Raised (exit code 3) when the run cannot stand on a TPU."""


def guard(devices, chips: int) -> dict:
    """The device record of a run on ``devices``; exits nonzero, with a
    last line that names what JAX found, unless they are TPU chips and at
    least ``chips`` of them."""
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"chipbench: needs {chips} TPU chip(s); JAX found "
              f"platform={info['platform']} kind={info['kind']} "
              f"count={info['count']}", file=sys.stderr, flush=True)
        raise NoChip(3)
    return info


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self, jax):
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.programs, self.cache_hits


def use_compile_cache(jax, root: str) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, for
    every program however short its compile, so that only a checkout's
    first run compiles."""
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # no eviction: a cell's programs are tens of MB, and an evicted
    # program would compile again inside a later run's set-up
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
