"""The one traffic generator: every input of a run is a pure function of
``--seed`` and the parameters in the cell's traffic file.

* ``designs`` draws a batch of designs in the (B, 12) segment encoding
  the program takes (``DesignBatch``).  It is a copy of the program's
  ``sample_mixed`` (``src/repro/core/dse/samplers.py``): segments of
  random length, each single-CE or a pipelined block, CE counts drawn as
  balls into bins.
"""
from __future__ import annotations

import numpy as np

NS = 12          # segments per design, as the program encodes them
NC = 16          # CEs per design


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def _rand_partitions(rng, hi, n_parts, width):
    n = len(hi)
    hi = np.maximum(hi, 1)
    n_parts = np.clip(n_parts, 1, np.minimum(hi, width))
    max_cuts = int(min(width - 1, max(int(hi.max()) - 1, 0),
                       max(int(n_parts.max()) - 1, 1) if len(n_parts) else 1))
    if max_cuts == 0 or len(hi) == 0:
        return np.repeat(hi[:, None], width, axis=1).astype(np.int32)
    keys = rng.random((n, int(hi.max()) - 1), dtype=np.float32)
    if (hi != hi[0]).any():
        pos = np.arange(1, keys.shape[1] + 1)
        keys[pos[None, :] > (hi - 1)[:, None]] = np.inf
    if max_cuts < keys.shape[1]:
        part = np.argpartition(keys, max_cuts - 1, axis=1)[:, :max_cuts]
    else:
        part = np.broadcast_to(np.arange(max_cuts), (n, max_cuts))
    sel_keys = np.take_along_axis(keys, part, axis=1)
    order = np.take_along_axis(part, np.argsort(sel_keys, axis=1), axis=1)
    cuts = (order + 1).astype(np.int64)
    cuts = np.where(np.arange(max_cuts)[None, :] < (n_parts - 1)[:, None],
                    cuts, hi[:, None])
    cuts.sort(axis=1)
    ends = np.full((n, width), 0, np.int64)
    ends[:, :max_cuts] = cuts
    ends[:, max_cuts:] = hi[:, None]
    return ends.astype(np.int32)


def _balls_into_bins(rng, n_balls, n_bins, width):
    n = len(n_balls)
    m = int(n_balls.max()) if n else 0
    if n == 0 or m == 0:
        return np.zeros((n, width), np.int64)
    bins = rng.integers(0, np.maximum(n_bins, 1)[:, None], size=(n, m))
    live = np.arange(m)[None, :] < n_balls[:, None]
    flat = (np.arange(n)[:, None] * width + bins)[live]
    return np.bincount(flat, minlength=n * width).reshape(n, width)


def designs(rng: np.random.Generator, n_layers: int, n: int,
            min_ces: int = 2, max_ces: int = 11, max_segments: int = 6):
    """``n`` designs: (seg_end, seg_pipe, seg_nce, inter_pipe) arrays."""
    if not 1 <= min_ces <= max_ces <= NC:
        raise ValueError(f"need 1 <= min_ces <= max_ces <= {NC}")
    total = rng.integers(min_ces, max_ces + 1, size=n)
    cap = np.minimum(np.minimum(max_segments, total), min(n_layers, NS))
    n_seg = rng.integers(1, cap + 1)
    seg_end = _rand_partitions(rng, np.full(n, n_layers, np.int64), n_seg, NS)
    alloc = 1 + _balls_into_bins(rng, total - n_seg, n_seg, NS)
    active = np.arange(NS)[None, :] < n_seg[:, None]
    seg_nce = np.where(active, alloc, 1).astype(np.int32)
    seg_pipe = active & (seg_nce > 1)
    inter = (n_seg > 1) & (rng.integers(0, 2, size=n) > 0)
    return seg_end, seg_pipe, seg_nce, inter
