"""Shared design-encoding layer: fixed-shape arrays <-> AcceleratorSpec.

Every DSE component — the vectorized samplers, the guided search, the jitted
``batch_eval.evaluate_batch`` and the builder round-trip — speaks the same
(B, NS) encoding defined here:

* ``seg_end``   int32 (B, NS): exclusive end layer of each segment, sorted
  nondecreasing; padding columns repeat ``n_layers``.
* ``seg_pipe``  bool  (B, NS): segment is a pipelined block.
* ``seg_nce``   int32 (B, NS): CEs of the segment (1 for single-CE).
* ``inter_pipe`` bool (B,): coarse inter-segment pipelining.

Canonical form (what samplers/search produce and ``encode_specs`` emits):
segments are compact (no empty segment before a non-empty one), a valid
segment is pipelined iff ``seg_nce > 1``, and padding columns carry
``end == n_layers, nce == 1, pipe == False``.  ``validate_batch`` checks
exactly this plus the NS/NC CE-count bounds, and ``decode_design`` ->
``encode_specs`` round-trips any canonical row bit-exactly.

Multi-model deployments (``core.multinet``) extend the encoding along a
model axis: :class:`MultiDesignBatch` stacks M per-model design planes
into (B, M, NS) arrays, and a hybrid deployment adds one more gene — the
**assignment** plane, a float (B, M) array where ``assign[b, m] > 0.5``
places model m in deployment b's single time-multiplexed *shared slice*
and anything else gives it a dedicated spatial slice.  ``sample_assign``
draws random assignments; the traced evaluator canonicalizes them with a
plain ``> 0.5`` threshold (and masks padded model columns), so the search
mutates assignment genes as freely as resource shares without forking
compiles.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..notation import AcceleratorSpec, SegmentSpec

NS = 12          # max segments per design
NC = 16          # max CEs per design


@jax.tree_util.register_dataclass
@dataclass
class DesignBatch:
    """(B, NS) arrays; invalid segments have end == previous end."""

    seg_end: jnp.ndarray       # int32 (B, NS) exclusive end layer
    seg_pipe: jnp.ndarray      # bool  (B, NS)
    seg_nce: jnp.ndarray       # int32 (B, NS) >= 1
    inter_pipe: jnp.ndarray    # bool  (B,)

    @property
    def batch(self) -> int:
        """Number of designs in the batch."""
        return self.seg_end.shape[0]

    @classmethod
    def from_numpy(cls, seg_end, seg_pipe, seg_nce, inter_pipe) -> "DesignBatch":
        """Host arrays -> device DesignBatch with canonical dtypes."""
        return cls(jnp.asarray(seg_end, jnp.int32), jnp.asarray(seg_pipe, bool),
                   jnp.asarray(seg_nce, jnp.int32), jnp.asarray(inter_pipe, bool))

    def to_numpy(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """(seg_end, seg_pipe, seg_nce, inter_pipe) as host arrays."""
        return (np.asarray(self.seg_end), np.asarray(self.seg_pipe),
                np.asarray(self.seg_nce), np.asarray(self.inter_pipe))

    def take(self, idx) -> "DesignBatch":
        """Row subset (numpy/jnp fancy index)."""
        return DesignBatch(self.seg_end[idx], self.seg_pipe[idx],
                           self.seg_nce[idx], self.inter_pipe[idx])


def concat_batches(batches: list[DesignBatch]) -> DesignBatch:
    """Row-concatenate DesignBatches (all for the same n_layers)."""
    return DesignBatch(
        jnp.concatenate([b.seg_end for b in batches]),
        jnp.concatenate([b.seg_pipe for b in batches]),
        jnp.concatenate([b.seg_nce for b in batches]),
        jnp.concatenate([b.inter_pipe for b in batches]))


@jax.tree_util.register_dataclass
@dataclass
class MultiDesignBatch:
    """The model-axis extension of :class:`DesignBatch`: row b describes a
    *deployment* of ``n_models`` co-resident accelerators — model m of row
    b runs design ``(seg_end[b, m], ...)`` on its slice of the board.

    Segment arrays are (B, M, NS), ``inter_pipe`` is (B, M).  Each model's
    plane is a canonical DesignBatch for *that model's* layer count, so
    every single-model invariant (validate/repair/decode) applies
    per-plane via :meth:`model`.
    """

    seg_end: jnp.ndarray       # int32 (B, M, NS)
    seg_pipe: jnp.ndarray      # bool  (B, M, NS)
    seg_nce: jnp.ndarray       # int32 (B, M, NS)
    inter_pipe: jnp.ndarray    # bool  (B, M)

    @property
    def batch(self) -> int:
        """Number of deployment rows."""
        return self.seg_end.shape[0]

    @property
    def n_models(self) -> int:
        """Padded model-axis length (max_m)."""
        return self.seg_end.shape[1]

    def model(self, m: int) -> DesignBatch:
        """Model m's plane as a plain (B, NS) DesignBatch."""
        return DesignBatch(self.seg_end[:, m], self.seg_pipe[:, m],
                           self.seg_nce[:, m], self.inter_pipe[:, m])

    def take(self, idx) -> "MultiDesignBatch":
        """Row subset (numpy/jnp fancy index)."""
        return MultiDesignBatch(self.seg_end[idx], self.seg_pipe[idx],
                                self.seg_nce[idx], self.inter_pipe[idx])

    def to_numpy(self):
        """(seg_end, seg_pipe, seg_nce, inter_pipe) as host arrays."""
        return (np.asarray(self.seg_end), np.asarray(self.seg_pipe),
                np.asarray(self.seg_nce), np.asarray(self.inter_pipe))


def stack_designs(batches: list[DesignBatch],
                  max_m: int | None = None) -> MultiDesignBatch:
    """Stack per-model DesignBatches (equal B) into a MultiDesignBatch,
    padding the model axis to ``max_m`` by repeating the LAST entry — the
    same padding rule ``multinet.make_multi_tables`` applies to the stacked
    NetTables, so padded design planes always pair with matching tables.
    """
    if not batches:
        raise ValueError("stack_designs needs at least one DesignBatch")
    if len({b.batch for b in batches}) != 1:
        raise ValueError("all model DesignBatches must share one batch size")
    if max_m is None:
        max_m = len(batches)
    if len(batches) > max_m:
        raise ValueError(f"{len(batches)} models exceed max_m={max_m}")
    batches = list(batches) + [batches[-1]] * (max_m - len(batches))
    stack = lambda f: jnp.stack([getattr(b, f) for b in batches], axis=1)
    return MultiDesignBatch(stack("seg_end"), stack("seg_pipe"),
                            stack("seg_nce"), stack("inter_pipe"))


def sample_assign(rng: np.random.Generator, n: int, max_m: int,
                  n_models: int | None = None,
                  p_shared: float = 0.5) -> np.ndarray:
    """(n, max_m) random hybrid-deployment assignments: each real model is
    a shared-slice member with probability ``p_shared`` (1.0 on the gene ==
    member, 0.0 == dedicated spatial slice); padded columns stay 0.

    This is the assignment-gene twin of ``multinet.sample_shares`` — the
    raw genome the traced hybrid evaluator consumes (see
    ``multinet.partition.slice_masks`` for the canonicalization)."""
    m = max_m if n_models is None else n_models
    out = np.zeros((n, max_m), np.float32)
    out[:, :m] = (rng.random((n, m)) < p_shared).astype(np.float32)
    return out


def pad_plane(a, n: int):
    """Edge-pad one (B, ...) array to ``n`` rows by repeating the last row
    — how the share/assign planes ride along when their deployments are
    padded (``pad_deployments``) for tiling or mesh sharding."""
    pad = n - a.shape[0]
    if pad <= 0:
        return a
    return jnp.concatenate([a, jnp.repeat(a[-1:], pad, 0)], 0)


def pad_deployments(md: MultiDesignBatch, n: int) -> MultiDesignBatch:
    """Edge-pad a MultiDesignBatch to ``n`` rows (the model-axis analogue
    of ``batch_eval._pad_rows``; padded rows are evaluated and sliced off)."""
    if n <= md.batch:
        return md
    return MultiDesignBatch(pad_plane(md.seg_end, n),
                            pad_plane(md.seg_pipe, n),
                            pad_plane(md.seg_nce, n),
                            pad_plane(md.inter_pipe, n))


def encode_specs(specs: list[AcceleratorSpec], n_layers: int) -> DesignBatch:
    """AcceleratorSpecs -> one canonical (B, NS) DesignBatch (the inverse
    of :func:`decode_design`; round-trips bit-exactly)."""
    B = len(specs)
    seg_end = np.full((B, NS), n_layers, np.int32)
    seg_pipe = np.zeros((B, NS), bool)
    seg_nce = np.ones((B, NS), np.int32)
    inter = np.zeros((B,), bool)
    for b, spec in enumerate(specs):
        if len(spec.segments) > NS:
            raise ValueError(f"{spec.name}: more than {NS} segments")
        end = 0
        for s, seg in enumerate(spec.segments):
            end = seg.layer_hi + 1
            seg_end[b, s] = end
            seg_pipe[b, s] = seg.pipelined
            seg_nce[b, s] = seg.n_ces
        seg_end[b, len(spec.segments):] = end
        inter[b] = spec.inter_segment_pipelining
    return DesignBatch.from_numpy(seg_end, seg_pipe, seg_nce, inter)


def decode_design(batch: DesignBatch, i: int, n_layers: int) -> AcceleratorSpec:
    """Row i of a DesignBatch -> AcceleratorSpec (for the scalar evaluator
    or for pretty-printing in the paper's notation)."""
    seg_end = np.asarray(batch.seg_end[i])
    seg_pipe = np.asarray(batch.seg_pipe[i])
    seg_nce = np.asarray(batch.seg_nce[i])
    segs, lo, ce = [], 0, 0
    for s in range(NS):
        hi = int(seg_end[s])
        if hi <= lo:
            continue
        n = int(seg_nce[s]) if seg_pipe[s] else 1
        segs.append(SegmentSpec(lo, hi - 1, ce, ce + n - 1))
        ce += n
        lo = hi
        if hi >= n_layers:
            break
    return AcceleratorSpec(name=f"custom[{i}]", segments=tuple(segs),
                           inter_segment_pipelining=bool(batch.inter_pipe[i]))


def decode_batch(batch: DesignBatch, n_layers: int) -> list[AcceleratorSpec]:
    """Decode every row of a DesignBatch (see :func:`decode_design`)."""
    return [decode_design(batch, i, n_layers) for i in range(batch.batch)]


def validate_batch_jax(batch: DesignBatch, n_layers, *,
                       min_ces: int = 1, max_ces: int = NC) -> jnp.ndarray:
    """Traced twin of :func:`validate_batch` (``n_layers`` may be a traced
    scalar) — lets the guided search keep validity checking on device."""
    seg_end, seg_pipe, seg_nce = batch.seg_end, batch.seg_pipe, batch.seg_nce
    B = seg_end.shape[0]
    prev = jnp.concatenate(
        [jnp.zeros((B, 1), seg_end.dtype), seg_end[:, :-1]], axis=1)
    d = seg_end - prev
    active = d > 0
    ok = (d >= 0).all(1)
    ok &= (seg_end[:, -1] == n_layers) & (seg_end[:, 0] >= 1)
    ok &= (seg_end <= n_layers).all(1)
    # compact: once a segment is empty, all later ones are empty too
    prefix_active = jnp.cumprod(active.astype(jnp.int32), axis=1) > 0
    ok &= ~(active & ~prefix_active).any(1)
    ok &= (seg_nce >= 1).all(1)
    ok &= (seg_pipe == ((seg_nce > 1) & active)).all(1)
    ok &= (jnp.where(active, 1, seg_nce) == 1).all(1)   # padding nce == 1
    total = (seg_nce * active).sum(1)
    ok &= (total >= min_ces) & (total <= min(max_ces, NC))
    return ok


@partial(jax.jit, static_argnames=("min_ces", "max_ces"))
def count_invalid_jit(batch: DesignBatch, n_layers, *,
                      min_ces: int = 1, max_ces: int = NC) -> jnp.ndarray:
    """``[n_invalid, first_invalid_index]`` (int32, shape (2,)) of
    :func:`validate_batch_jax` — the check ``Session.evaluate`` runs on the
    device before dispatch, pulling 8 bytes instead of the batch.

    ``n_layers`` is traced, so one compile serves every CNN at a batch
    shape; the first index is 0 when every row is valid.
    """
    bad = ~validate_batch_jax(batch, n_layers, min_ces=min_ces,
                              max_ces=max_ces)
    first = jnp.argmax(bad) if bad.size else 0   # argmax needs a row
    return jnp.stack([bad.sum(dtype=jnp.int32),
                      jnp.asarray(first, jnp.int32)])


def repair_batch_jax(batch: DesignBatch, n_layers, *,
                     min_ces: int = 1, max_ces: int = NC) -> DesignBatch:
    """Traced constraint repair: canonicalize a batch and clamp its CE
    totals into [min_ces, min(max_ces, NC)].

    Bit-identity on already-canonical rows (sorting, compaction and both
    clamp loops are no-ops there), so the guided search can run it inside
    the jitted generation step as a safety net without perturbing the
    host-side breeding pipeline.  Deterministic (takes from the largest
    segment, gives to the first) where the host repair randomizes.

    Repair never merges segments: a row with more active segments than
    ``max_ces`` cannot reach the cap (each needs >= 1 CE) and stays
    invalid — the breeding pipeline already bounds segment counts by
    ``min(NS, max_ces)``, and ``validate_batch_jax`` screens the rest.
    """
    B = batch.batch
    end0 = jnp.clip(batch.seg_end, 0, n_layers)
    order = jnp.argsort(end0, axis=1, stable=True)
    end = jnp.take_along_axis(end0, order, axis=1)
    nce = jnp.take_along_axis(jnp.clip(batch.seg_nce, 1, NC), order, axis=1)
    end = end.at[:, -1].set(jnp.broadcast_to(n_layers, (B,)))
    prev = jnp.concatenate(
        [jnp.zeros((B, 1), end.dtype), end[:, :-1]], axis=1)
    active = end > prev
    # compaction: actives first (stable keeps ascending order), padding
    # columns forced to the canonical (n_layers, 1, False)
    corder = jnp.argsort(~active, axis=1, stable=True)
    active_s = jnp.take_along_axis(active, corder, axis=1)
    end = jnp.where(active_s, jnp.take_along_axis(end, corder, axis=1),
                    n_layers)
    nce = jnp.where(active_s, jnp.take_along_axis(nce, corder, axis=1), 1)
    prev = jnp.concatenate(
        [jnp.zeros((B, 1), end.dtype), end[:, :-1]], axis=1)
    active = end > prev

    cap = min(max_ces, NC)
    floor_ces = min(min_ces, cap)
    rows = jnp.arange(B)

    def shrink(_, nc):
        over = (nc * active).sum(1) > cap
        key = jnp.where(active & (nc > 1), nc, -1)
        col = jnp.argmax(key, axis=1)
        hit = over & (key.max(1) > 0)
        return nc.at[rows, col].add(-jnp.where(hit, 1, 0))

    def grow(_, nc):
        under = (nc * active).sum(1) < floor_ces
        col = jnp.argmax(active, axis=1)
        return nc.at[rows, col].add(jnp.where(under & active.any(1), 1, 0))

    # worst case needs NS*NC - cap decrements (all NS segments at nce=NC)
    nce = jax.lax.fori_loop(0, NS * NC, shrink, nce)
    nce = jax.lax.fori_loop(0, 2 * NC, grow, nce)
    nce = jnp.where(active, nce, 1)
    pipe = (nce > 1) & active
    return DesignBatch(end.astype(jnp.int32), pipe,
                       nce.astype(jnp.int32), batch.inter_pipe)


def validate_batch(batch: DesignBatch, n_layers: int, *,
                   min_ces: int = 1, max_ces: int = NC) -> np.ndarray:
    """Per-row canonical-form + constraint check -> bool mask (B,).

    A row is valid iff its segments are a compact, nondecreasing partition
    of [0, n_layers); ``pipe`` agrees with ``nce > 1`` on valid segments;
    padding carries (n_layers, 1, False); and the total CE count lies in
    [min_ces, min(max_ces, NC)].
    """
    seg_end, seg_pipe, seg_nce, _ = batch.to_numpy()
    prev = np.concatenate(
        [np.zeros((seg_end.shape[0], 1), seg_end.dtype), seg_end[:, :-1]],
        axis=1)
    d = seg_end - prev
    active = d > 0
    ok = (d >= 0).all(1)
    ok &= (seg_end[:, -1] == n_layers) & (seg_end[:, 0] >= 1)
    ok &= (seg_end <= n_layers).all(1)
    # compact: once a segment is empty, all later ones are empty too
    ok &= ~(active & ~np.logical_and.accumulate(active, axis=1)).any(1)
    ok &= (seg_nce >= 1).all(1)
    ok &= (seg_pipe == ((seg_nce > 1) & active)).all(1)
    ok &= (np.where(active, 1, seg_nce) == 1).all(1)   # padding nce == 1
    total = (seg_nce * active).sum(1)
    ok &= (total >= min_ces) & (total <= min(max_ces, NC))
    return ok
