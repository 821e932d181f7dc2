"""Vectorized MCCM: evaluate thousands of multiple-CE designs as ONE jitted
JAX program — recompile-free across CNNs, boards and sweep sizes.

The scalar path (``evaluator.evaluate_design``) walks Python objects at
~100 µs–1 ms per design; the paper's own C++/Python model reports 6.3 ms.
Here every design in a batch is encoded as fixed-shape arrays (segments
padded to ``NS``, CEs to ``NC``) and Eqs. 1–9 are evaluated with masked
tensor ops.

Exactness: this is the *same* model, not an approximation —
``tests/test_batch_eval.py`` asserts agreement with the scalar evaluator on
every baseline architecture × CNN × CE-count (largest-remainder PE
distribution, the discrete ⟨pf, ph, pw⟩ parallelism search, Eq. 6's two
buffered-access options, and the exact pipeline stage-sum via the
prefix/suffix-max identity all replicated in vector form).

Layout (see docs/perf.md for the why)
-------------------------------------
* ``NetTables``  — per-CNN arrays as a *traced pytree*, padded to a shared
  ``max_L`` with a layer-valid mask, so every CNN shares one compiled
  program.
* ``DeviceTables`` — the board as traced scalars, ditto for boards.
* ``DesignBatch`` — (B, NS) segment encoding (``core.dse.encoding``).
* ``evaluate_batch`` — jitted core.  Designs are processed in tiles of
  ``tile`` via ``lax.map``; per tile the ⟨pf, ph, pw⟩ search builds only a
  (tile, L, P) cost block (cache/VMEM-resident) instead of the old
  (B, L, 18, 18) HBM tensor, dispatched to ``kernels.mccm_eval`` (pure-jnp
  ref on CPU, the fused Pallas kernel on TPU, ``interpret=True`` under CI).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels.mccm_eval import pair_tables, parallelism_search, resolve_backend
from ..kernels.mccm_eval.ref import EXACT
from .blocks import CANDIDATES_DEFAULT
from .device import DeviceSpec
from .dse.encoding import NC, NS, DesignBatch, encode_specs  # noqa: F401
from .notation import AcceleratorSpec
from .workload import Network

NEG = -1.0e30

#: relative tolerance of batch (f32) vs scalar-evaluator (f64) agreement,
#: per metric — what the parity tests and ``chip_smoke.py`` hold the
#: evaluator to.  Off-chip access may flip Eq. 6's option at f32
#: thresholds, hence its wider bound.
SCALAR_PARITY_RTOL = {"latency_s": 1e-4, "throughput_ips": 1e-4,
                      "buffer_bytes": 1e-4, "access_bytes": 0.04}

#: base of the layer-axis padding ladder: covers the whole CNN zoo
#: (resnet152 = 155), so one compiled program serves every registered CNN.
DEFAULT_MAX_L = 160

#: bucket step above the base — larger nets pad to the next multiple, one
#: extra compile per new size bucket instead of one per net.
MAX_L_STEP = 32


def bucket_max_L(L: int, base: int = DEFAULT_MAX_L,
                 step: int = MAX_L_STEP) -> int:
    """Shared layer-padding bucket for an L-layer net.

    Every net at or under ``base`` layers shares the base bucket (one
    compile for the whole zoo); larger nets land on the next ``step``
    multiple, so two 200-ish-layer nets still share a compile instead of
    each minting its own shape.
    """
    if L <= base:
        return base
    return -(-L // step) * step


def shared_max_L(layer_counts) -> int:
    """The one bucket a set of nets must share to be stacked/megabatched
    (e.g. the model axis of ``core.multinet``): the max over their
    individual buckets."""
    counts = list(layer_counts)
    if not counts:
        return DEFAULT_MAX_L
    return max(bucket_max_L(int(c)) for c in counts)

#: design-tile width of the lax.map hot loop (the CPU analogue of the
#: Pallas kernel's VMEM design tile).
DEFAULT_TILE = 128

#: static PE-budget hints for pruning the ⟨pf, ph⟩ pair grid.  Every
#: registered board (<= 2520 DSPs) lands in the first bucket, keeping a
#: single compile across boards; exotic devices fall into coarser buckets.
PES_HINTS = (2520, 8192, 65536)


# --------------------------------------------------------------------------
# static-per-CNN tables, as a traced pytree
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class NetTables:
    """Per-network layer tables, padded to ``max_L`` (= ``F.shape[0]``).

    All array fields are pytree *data* — a NetTables is traced, never a
    static jit argument, so switching CNNs does not recompile.  Padded
    layers carry zeros and ``valid`` masks them out.
    """

    L: jnp.ndarray         # ()  i32 true layer count
    valid: jnp.ndarray     # (max_L,) f32 1.0 for real layers
    F: jnp.ndarray         # out channels
    CKK: jnp.ndarray       # c * kh * kw  (c=1 for depthwise)
    OH: jnp.ndarray
    OW: jnp.ndarray
    MACS: jnp.ndarray
    W: jnp.ndarray         # weights (elements)
    IFM: jnp.ndarray
    OFM: jnp.ndarray
    EXTRA: jnp.ndarray     # residual OFM copy (elements)
    BAND: jnp.ndarray      # in_ch * kh * iw  (IFM row band)
    OFM_ROW: jnp.ndarray   # out_ch * ow
    CEIL_F: jnp.ndarray    # (max_L, K) ceil(F / cand)
    CEIL_OH: jnp.ndarray
    CEIL_OW: jnp.ndarray
    CAND: jnp.ndarray      # (K,)
    candidates: tuple = CANDIDATES_DEFAULT   # static metadata

    @property
    def n_layers(self) -> int:
        """Concrete layer count (host-side use only)."""
        return int(self.L)

    @property
    def max_L(self) -> int:
        return self.F.shape[0]


jax.tree_util.register_dataclass(
    NetTables,
    data_fields=["L", "valid", "F", "CKK", "OH", "OW", "MACS", "W", "IFM",
                 "OFM", "EXTRA", "BAND", "OFM_ROW", "CEIL_F", "CEIL_OH",
                 "CEIL_OW", "CAND"],
    meta_fields=["candidates"],
)


def make_tables(net: Network, candidates=CANDIDATES_DEFAULT,
                max_L: int | None = None) -> NetTables:
    cand = np.asarray(candidates, np.float64)
    L = len(net)
    if max_L is None:
        max_L = bucket_max_L(L)
    elif L > max_L:
        max_L = bucket_max_L(L, base=max_L)
    dims = [l.dims() for l in net]

    def pad(vals):
        a = np.zeros(max_L, np.float64)
        a[:L] = vals
        return jnp.asarray(a, jnp.float32)

    F = np.array([d["f"] for d in dims], np.float64)
    OH = np.array([d["oh"] for d in dims], np.float64)
    OW = np.array([d["ow"] for d in dims], np.float64)

    def pad2(ceil_tab):
        a = np.zeros((max_L, len(cand)), np.float64)
        a[:L] = ceil_tab
        return jnp.asarray(a, jnp.float32)

    return NetTables(
        L=jnp.asarray(L, jnp.int32),
        valid=pad(np.ones(L)),
        F=pad(F),
        CKK=pad([d["c"] * d["kh"] * d["kw"] for d in dims]),
        OH=pad(OH), OW=pad(OW),
        MACS=pad([l.macs for l in net]),
        W=pad([l.weights_size for l in net]),
        IFM=pad([l.ifm_size for l in net]),
        OFM=pad([l.ofm_size for l in net]),
        EXTRA=pad([l.ofm_size if l.residual else 0 for l in net]),
        BAND=pad([l.in_ch * l.kh * l.iw for l in net]),
        OFM_ROW=pad([l.out_ch * l.ow for l in net]),
        CEIL_F=pad2(np.ceil(F[:, None] / cand[None, :])),
        CEIL_OH=pad2(np.ceil(OH[:, None] / cand[None, :])),
        CEIL_OW=pad2(np.ceil(OW[:, None] / cand[None, :])),
        CAND=jnp.asarray(cand, jnp.float32),
        candidates=tuple(candidates),
    )


# --------------------------------------------------------------------------
# the board, as traced scalars
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class DeviceTables:
    """DeviceSpec as a traced scalar struct — boards don't recompile."""

    pes: jnp.ndarray
    on_chip_bytes: jnp.ndarray
    bpc: jnp.ndarray           # off-chip bytes per cycle
    bps: jnp.ndarray           # off-chip bytes per second
    clock_hz: jnp.ndarray
    wordbytes: jnp.ndarray


jax.tree_util.register_dataclass(
    DeviceTables,
    data_fields=["pes", "on_chip_bytes", "bpc", "bps", "clock_hz",
                 "wordbytes"],
    meta_fields=[],
)


def make_device_tables(dev: DeviceSpec) -> DeviceTables:
    s = lambda x: jnp.asarray(x, jnp.float32)
    return DeviceTables(
        pes=s(dev.pes), on_chip_bytes=s(dev.on_chip_bytes),
        bpc=s(dev.off_chip_bytes_per_cycle), bps=s(dev.off_chip_gbps * 1e9),
        clock_hz=s(dev.clock_hz), wordbytes=s(dev.wordbytes))


def pes_hint(pes: float) -> int | None:
    """Static pair-pruning bucket for a concrete PE count (None = no
    pruning for devices beyond the ladder)."""
    for h in PES_HINTS:
        if pes <= h:
            return h
    return None


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------
def _largest_remainder(shares, total, valid):
    """Vectorized largest-remainder rounding (floor 1 per valid CE).

    shares: (B, NC) f32; total: scalar; valid: (B, NC) bool.
    Mirrors builder._largest_remainder including tie-breaking by index.
    """
    n = valid.sum(-1)                                  # (B,)
    s = jnp.where(shares.sum(-1) > 0, shares.sum(-1), 1.0)
    raw = jnp.maximum(shares / s[:, None] * total, 1.0)
    raw = jnp.where(valid, raw, 0.0)
    out = jnp.where(valid, jnp.maximum(jnp.floor(raw), 1.0), 0.0)
    rem = total - out.sum(-1)                          # (B,) can be +/-
    frac = jnp.where(valid, raw - jnp.floor(raw), -1.0)
    # positive remainder: +1 to the rem largest fractions (cyclically the
    # scalar hands out one each in frac order; rem < n in practice)
    order = jnp.argsort(-frac, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)    # rank in frac order
    give = rank < jnp.maximum(rem, 0)[:, None]
    out = out + jnp.where(valid & give, 1.0, 0.0)
    # negative remainder: take from the largest allocations (scalar loops;
    # one pass suffices when floors forced the overflow)
    deficit = jnp.maximum(-rem, 0.0)
    big_order = jnp.argsort(-out, axis=-1, stable=True)
    big_rank = jnp.argsort(big_order, axis=-1, stable=True)
    take = (big_rank < deficit[:, None]) & (out > 1.0)
    out = out - jnp.where(take, 1.0, 0.0)
    return out


def _seg_onehot(seg_of_layer, valid_layer):
    """(B, L, NS) one-hot of each layer's segment id."""
    oh = jax.nn.one_hot(seg_of_layer, NS, dtype=jnp.float32)
    return oh * valid_layer[..., None]


def _seg_sum(x, onehot):
    """sum of per-layer x (B, L) into segments -> (B, NS)."""
    return jnp.einsum("bl,bls->bs", x, onehot, precision=EXACT)


def _seg_max(x, onehot):
    big = jnp.where(onehot > 0, x[..., None], NEG)
    return big.max(axis=1)


def _of_layer(x, seg_of_layer):
    """``x[b, seg_of_layer[b, l]]`` for a per-segment ``x`` (B, NS).

    A select chain over the static segment axis, not a gather: on a TPU a
    (B, L) point gather costs far more than NS compares and selects, and a
    select moves each value as it is, so every dtype comes out bit for bit
    as ``take_along_axis`` gives it.  Indices must lie in [0, NS)."""
    out = jnp.broadcast_to(x[:, :1], seg_of_layer.shape)
    for s in range(1, x.shape[1]):
        out = jnp.where(seg_of_layer == s, x[:, s:s + 1], out)
    return out


def seg_scan_max(vals, start_flags, reverse=False):
    """Running max within groups delimited by start_flags (B, L).

    Associative, so log2(L) vector steps; a flagged element STARTS its own
    group.  With ``reverse=True`` the scan runs right-to-left (flags then
    mark group *ends*)."""
    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, jnp.maximum(va, vb))
    flags = start_flags[..., ::-1] if reverse else start_flags
    v = vals[..., ::-1] if reverse else vals
    _, out = jax.lax.associative_scan(combine, (flags, v), axis=1)
    return out[..., ::-1] if reverse else out


# --------------------------------------------------------------------------
# the traced core (works on any batch size; callers tile it)
# --------------------------------------------------------------------------
class _CEMaps(NamedTuple):
    seg_start: jnp.ndarray
    seg_len: jnp.ndarray
    seg_valid: jnp.ndarray
    n_seg: jnp.ndarray
    seg_of_layer: jnp.ndarray
    onehot: jnp.ndarray
    valid_b: jnp.ndarray        # (B, max_L) bool
    idx_in_seg: jnp.ndarray
    nce_of_layer: jnp.ndarray
    pipe_bool: jnp.ndarray      # (B, max_L) bool (masked to valid layers)
    slot_of_layer: jnp.ndarray
    round_of_layer: jnp.ndarray
    ce_base: jnp.ndarray
    ce_of_layer: jnp.ndarray    # clipped to [0, NC)
    ce_oh: jnp.ndarray
    pes_ce: jnp.ndarray
    ce_valid: jnp.ndarray


def _ce_maps(design: DesignBatch, t: NetTables, dev: DeviceTables) -> _CEMaps:
    """Layer -> segment / CE maps + the PE distribution (Eq. 1 prologue)."""
    B, max_L = design.batch, t.max_L
    layer_ix = jnp.arange(max_L)

    seg_end = design.seg_end                      # (B, NS)
    seg_start = jnp.concatenate(
        [jnp.zeros((B, 1), jnp.int32), seg_end[:, :-1]], axis=1)
    seg_len = seg_end - seg_start                 # (B, NS)
    seg_valid = seg_len > 0
    n_seg = seg_valid.sum(-1)                     # (B,)

    # seg of layer: first segment with end > l (padded layers clip to the
    # last column; the valid mask removes them from every reduction)
    seg_of_layer = jnp.minimum(jnp.sum(
        (layer_ix[None, :, None] >= seg_end[:, None, :]).astype(jnp.int32),
        axis=-1), NS - 1)                         # (B, max_L)
    valid_b = layer_ix[None, :] < t.L             # (B, max_L) bool
    valid_layer = valid_b.astype(jnp.float32) * t.valid[None, :]
    onehot = _seg_onehot(seg_of_layer, valid_layer)     # (B, max_L, NS)

    idx_in_seg = layer_ix[None, :] - _of_layer(seg_start, seg_of_layer)
    nce_of_layer = _of_layer(design.seg_nce, seg_of_layer)
    pipe_bool = _of_layer(design.seg_pipe, seg_of_layer) & valid_b
    slot_of_layer = idx_in_seg % jnp.maximum(nce_of_layer, 1)
    round_of_layer = idx_in_seg // jnp.maximum(nce_of_layer, 1)

    ce_base = jnp.cumsum(design.seg_nce * seg_valid, axis=-1) \
        - design.seg_nce * seg_valid
    ce_of_layer = _of_layer(ce_base, seg_of_layer) \
        + slot_of_layer                            # (B, max_L)
    # overflowing CEs (non-canonical rows) and padded layers map to a zero
    # one-hot row; clip keeps the ref path's gathers in bounds
    ce_oh = jax.nn.one_hot(ce_of_layer, NC, dtype=jnp.float32) \
        * valid_layer[..., None]
    ce_of_layer = jnp.clip(ce_of_layer, 0, NC - 1)

    # PE distribution (largest remainder over per-CE MACs)
    macs_ce = jnp.einsum("l,blc->bc", jnp.asarray(t.MACS), ce_oh,
                         precision=EXACT)
    ce_valid = jnp.einsum("blc->bc", ce_oh) > 0
    pes_ce = _largest_remainder(macs_ce, dev.pes, ce_valid)
    return _CEMaps(seg_start, seg_len, seg_valid, n_seg, seg_of_layer,
                   onehot, valid_b, idx_in_seg, nce_of_layer, pipe_bool,
                   slot_of_layer, round_of_layer, ce_base, ce_of_layer,
                   ce_oh, pes_ce, ce_valid)


def _pair_layer_tables(t: NetTables, pairs):
    """Per-(layer, pair) factor tables for the fused search."""
    pi = jnp.asarray(pairs.pair_i, jnp.int32)
    pj = jnp.asarray(pairs.pair_j, jnp.int32)
    fc_pair = t.CEIL_F[:, pi] * t.CKK[:, None]      # (max_L, P)
    coh_pair = t.CEIL_OH[:, pj]                     # (max_L, P)
    return fc_pair, coh_pair


class LayerState(NamedTuple):
    """Per-layer cost state between Eq. 1 and the Eq. 2–9 composition.

    ``layer_state`` computes it; ``compose_metrics`` reduces it to the
    metric dict.  The split exists for the schedule layer
    (``repro.schedule``): temporal-mapping search re-scores the
    per-layer fields (latency/busy/traffic) for its chosen mappings and
    re-runs the SAME composition, so coarse and schedule-refined costs
    stay in one currency — when every layer keeps the ideal mapping the
    result is bit-identical to ``_evaluate_core``.

    Per-layer arrays are (B, L); per-segment arrays are (B, NS).
    """

    # Eq. 1 compute + utilization
    comp: jnp.ndarray           # compute cycles
    util: jnp.ndarray
    # single-CE (Eq. 6) costs
    lat_single: jnp.ndarray     # max(comp, mem) — pre single_l masking
    acc_single: jnp.ndarray     # off-chip bytes
    wacc_single: jnp.ndarray
    facc_single: jnp.ndarray
    mem_cyc_single: jnp.ndarray
    # pipelined (Eq. 7) costs
    busy_pipe: jnp.ndarray      # max(comp, mem) per layer slot
    w_acc_pipe: jnp.ndarray
    mem_cyc_pipe: jnp.ndarray
    n_tiles_l: jnp.ndarray
    # mapping inputs the schedule search scores candidates against
    buf_l: jnp.ndarray          # single: the segment's buffer alloc
    ce_buf_l: jnp.ndarray       # pipelined: the layer's CE buffer slice
    wtile: jnp.ndarray          # streaming weight-tile bytes (pf rows)
    fm_tile2: jnp.ndarray       # double-buffered fm tile bytes
    ofm_res: jnp.ndarray        # OFM bytes held resident (Eq. 6)
    ofm_acc: jnp.ndarray        # OFM bytes streamed off-chip
    ideal: jnp.ndarray          # bool: whole working set fits
    ifm_onchip: jnp.ndarray     # bool: IFM left on chip by producer
    use_a: jnp.ndarray          # bool: Eq. 6 picked option A (IS) over B
    resident_l: jnp.ndarray     # bool: Eq. 5 whole-segment weight regime
    # per-segment allocations / boundaries
    alloc: jnp.ndarray
    desires: jnp.ndarray
    inter_onchip: jnp.ndarray   # bool
    bound_valid: jnp.ndarray    # bool
    is_pipe_seg: jnp.ndarray    # bool


def layer_state(design: DesignBatch, t: NetTables, dev: DeviceTables,
                m: _CEMaps, par, fm_tile_rows: int) -> LayerState:
    """Eqs. 1 + 4–7 given the CE maps and the ⟨pf, ph, pw⟩ winners:
    buffer allocation, per-layer compute/memory costs, residency regimes."""
    B, max_L = design.batch, t.max_L
    wb = dev.wordbytes
    bpc = dev.bpc
    pf_ce, ph_ce, pw_ce = par
    (seg_start, seg_len, seg_valid, n_seg, seg_of_layer, onehot, valid_b,
     idx_in_seg, nce_of_layer, pipe_bool, slot_of_layer, _round,
     ce_base, _ce_of_layer, ce_oh, _pes, ce_valid) = m
    valid_f = valid_b.astype(jnp.float32)
    seg_end = design.seg_end

    # ---- per-layer compute cycles & utilization --------------------------
    macs = jnp.asarray(t.MACS)
    ckk = jnp.asarray(t.CKK)
    pf_l = jnp.where(valid_b, jnp.einsum("bc,blc->bl", pf_ce, ce_oh,
                                         precision=EXACT), 1.0)
    ph_l = jnp.where(valid_b, jnp.einsum("bc,blc->bl", ph_ce, ce_oh,
                                         precision=EXACT), 1.0)
    pw_l = jnp.where(valid_b, jnp.einsum("bc,blc->bl", pw_ce, ce_oh,
                                         precision=EXACT), 1.0)
    F = jnp.asarray(t.F)
    OH = jnp.asarray(t.OH)
    OW = jnp.asarray(t.OW)
    comp = (jnp.ceil(F[None] / pf_l) * ckk[None]
            * jnp.ceil(OH[None] / ph_l) * jnp.ceil(OW[None] / pw_l))
    par_total = pf_l * ph_l * pw_l
    util = macs[None] / jnp.maximum(comp * par_total, 1.0)

    pipe_l = pipe_bool.astype(jnp.float32)
    single_l = (1.0 - pipe_l) * valid_f

    # ---- buffer floors / desires (Eq. 4 / 5) ------------------------------
    W = jnp.asarray(t.W)
    IFM = jnp.asarray(t.IFM)
    OFM = jnp.asarray(t.OFM)
    EXTRA = jnp.asarray(t.EXTRA)
    BAND = jnp.asarray(t.BAND)
    OFM_ROW = jnp.asarray(t.OFM_ROW)
    FMS = IFM + OFM + EXTRA

    wtile = jnp.minimum(pf_l, F[None]) * ckk[None] * wb  # (B, L)
    fm_tile2 = 2.0 * OFM_ROW[None] * fm_tile_rows * wb

    # pipelined: floor = sum(2*fm_tile + wtile); desire = sum(W + 2*fm_tile)
    floor_pipe = _seg_sum((fm_tile2 + wtile) * pipe_l, onehot)
    desire_pipe = _seg_sum((W[None] * wb + fm_tile2) * pipe_l, onehot)
    # single: floor = max(wtile + band + ofm_row); desire = max FMS + max wtile
    floor_single = _seg_max(
        jnp.where(single_l > 0, wtile + (BAND + OFM_ROW)[None] * wb, NEG),
        onehot)
    max_fms = _seg_max(jnp.where(single_l > 0, FMS[None] * wb, NEG), onehot)
    max_wtile = _seg_max(jnp.where(single_l > 0, wtile, NEG), onehot)
    desire_single = max_fms + max_wtile

    is_pipe_seg = design.seg_pipe & seg_valid
    floors = jnp.where(is_pipe_seg, floor_pipe,
                       jnp.where(seg_valid, jnp.maximum(floor_single, 0.0),
                                 0.0))
    desires = jnp.where(is_pipe_seg, desire_pipe,
                        jnp.where(seg_valid,
                                  jnp.maximum(desire_single, 0.0), 0.0))
    desires = jnp.maximum(desires, floors)

    budget_b = dev.on_chip_bytes
    alloc = floors
    over = alloc.sum(-1) > budget_b
    scale = jnp.where(over, budget_b / jnp.maximum(alloc.sum(-1), 1.0), 1.0)
    alloc = jnp.floor(alloc * scale[:, None])
    remaining = budget_b - alloc.sum(-1)                 # (B,)

    # ---- inter-segment double buffers, smallest-first ---------------------
    # boundary i lives after segment i (valid while i < n_seg - 1)
    b_ix = jnp.arange(NS)
    bound_valid = (b_ix[None, :] < (n_seg - 1)[:, None])
    last_of_seg = jnp.clip(seg_end - 1, 0, t.L - 1)      # (B, NS)
    bound_size = OFM[last_of_seg] * wb                   # (B, NS)
    bound_size = jnp.where(bound_valid, bound_size, jnp.inf)
    order = jnp.argsort(bound_size, axis=-1, stable=True)
    sorted_sz = jnp.take_along_axis(bound_size, order, axis=-1)
    csum = jnp.cumsum(jnp.where(jnp.isfinite(sorted_sz), 2 * sorted_sz, 0.0),
                      axis=-1)
    fit_sorted = (csum <= remaining[:, None]) & jnp.isfinite(sorted_sz)
    fit = jnp.zeros_like(fit_sorted).at[
        jnp.arange(B)[:, None], order].set(fit_sorted)
    inter_onchip = fit & bound_valid & design.inter_pipe[:, None]
    remaining = remaining - (2 * jnp.where(inter_onchip, OFM[last_of_seg]
                                           * wb, 0.0)).sum(-1)

    # ---- grant remaining toward minimum-access desires --------------------
    gaps = jnp.maximum(desires - alloc, 0.0)
    gap_sum = gaps.sum(-1)
    grant = jnp.minimum(jnp.maximum(remaining, 0.0), gap_sum)
    # a grant that covers every gap fills each exactly: on a TPU the f32
    # division is not correctly rounded, and floor(g * G / G) can land on
    # g - 1, one byte short of the weight-resident regime (Eq. 5)
    share = jnp.where(grant[:, None] >= gap_sum[:, None], gaps,
                      jnp.floor(grant[:, None] * gaps
                                / jnp.maximum(gap_sum[:, None], 1.0)))
    alloc = alloc + jnp.where(gap_sum[:, None] > 0, share, 0.0)

    # ---- pipelined per-CE buffer split (desire share within segment) ------
    ce_desire_l = (W[None] * wb + fm_tile2) * pipe_l     # (B, L)
    ce_desire = jnp.einsum("bl,blc->bc", ce_desire_l, ce_oh,
                           precision=EXACT)
    seg_of_ce_desire = _seg_sum(ce_desire_l, onehot)     # (B, NS)
    alloc_of_layer = _of_layer(alloc, seg_of_layer)
    segdes_of_layer = _of_layer(jnp.maximum(seg_of_ce_desire, 1.0),
                                seg_of_layer)
    cedes_of_layer = jnp.einsum("bc,blc->bl", ce_desire, ce_oh,
                                precision=EXACT)
    ce_buf_of_layer = jnp.floor(
        alloc_of_layer * cedes_of_layer / segdes_of_layer)

    # weights resident (Eq. 5 regime): alloc covers the Eq. 5 requirement
    resident_seg = (alloc >= desire_pipe) & is_pipe_seg
    resident_l = _of_layer(resident_seg, seg_of_layer)

    # n_tiles per layer: max OH over the layers of the same (seg, round).
    # Rounds are contiguous layer runs, so the group max is the combine of
    # a forward and a backward segmented max-scan — no (B, NS*rounds)
    # scatter map needed.
    is_round_start = slot_of_layer == 0
    is_round_last = (slot_of_layer == nce_of_layer - 1) | \
        (idx_in_seg == _of_layer(seg_len, seg_of_layer) - 1)
    OH_b = jnp.broadcast_to(OH[None], (B, max_L))
    n_tiles_l = jnp.maximum(
        jnp.maximum(seg_scan_max(OH_b, is_round_start),
                    seg_scan_max(OH_b, is_round_last, reverse=True)), 1.0)

    # ---- off-chip accesses ------------------------------------------------
    # pipelined (Eq. 7)
    w_bytes = W[None] * wb
    w_acc_pipe = jnp.where(
        resident_l, 0.0,
        jnp.where(ce_buf_of_layer >= w_bytes, w_bytes,
                  w_bytes * n_tiles_l))
    mem_cyc_pipe = w_acc_pipe / bpc

    # single (Eq. 6) — fully vectorized: the ifm_onchip "chain" has no true
    # recurrence (layer l's residency verdict doesn't depend on the carry),
    # so it's a shift-by-one within each segment, not a scan.
    buf = alloc_of_layer                                 # (B, L)
    wl = W[None] * wb
    ifml = IFM[None] * wb
    ofml = OFM[None] * wb
    extral = EXTRA[None] * wb
    ideal = ifml + ofml + extral + wtile <= buf          # (B, L)

    ifm_tile = jnp.minimum(ifml, BAND[None] * wb)
    ofm_on = ofml + extral + wtile + ifm_tile <= buf
    ofm_res = jnp.where(ofm_on, ofml + extral, 0.0)
    ofm_acc = jnp.where(ofm_on, 0.0, ofml)

    # layer l leaves its OFM on-chip for l+1 iff ideal or ofm_on
    next_on = jnp.where(ideal, True, ofm_on)             # (B, L)
    prev_on = jnp.concatenate(
        [jnp.zeros((B, 1), bool), next_on[:, :-1]], axis=1)
    is_seg_start = idx_in_seg == 0
    prev_boundary_onchip = _of_layer(
        inter_onchip, jnp.maximum(seg_of_layer - 1, 0)) \
        & (seg_of_layer > 0)
    ifm_onchip = jnp.where(is_seg_start, prev_boundary_onchip, prev_on)

    fm_ideal = jnp.where(ifm_onchip, 0.0, ifml)
    acc_prev_resident = ofm_acc + wl                     # ifm already on-chip
    ifm_buf = jnp.maximum(buf - ofm_res - wtile, ifm_tile)
    loads_a = jnp.where(ifm_buf < ifml,
                        wl * jnp.ceil(ifml / jnp.maximum(ifm_buf, 1.0))
                        + ifml,
                        wl + ifml)
    wacc_a = loads_a - ifml
    w_buf = jnp.maximum(buf - ofm_res - ifm_tile, wtile)
    loads_b = jnp.where(w_buf < wl,
                        ifml * jnp.ceil(wl / jnp.maximum(w_buf, 1.0)) + wl,
                        ifml + wl)
    facc_b = loads_b - wl
    use_a = loads_a <= loads_b
    acc_opt = ofm_acc + jnp.where(use_a, loads_a, loads_b)
    wacc_opt = jnp.where(use_a, wacc_a, wl)
    facc_opt = ofm_acc + jnp.where(use_a, ifml, facc_b)

    acc_single = jnp.where(ideal, wl + fm_ideal,
                           jnp.where(ifm_onchip, acc_prev_resident, acc_opt))
    wacc_single = jnp.where(ideal, wl,
                            jnp.where(ifm_onchip, wl, wacc_opt))
    facc_single = jnp.where(ideal, fm_ideal,
                            jnp.where(ifm_onchip, ofm_acc, facc_opt))
    mem_cyc_single = acc_single / bpc

    return LayerState(
        comp=comp, util=util,
        lat_single=jnp.maximum(comp, mem_cyc_single),
        acc_single=acc_single, wacc_single=wacc_single,
        facc_single=facc_single, mem_cyc_single=mem_cyc_single,
        busy_pipe=jnp.maximum(comp, mem_cyc_pipe),
        w_acc_pipe=w_acc_pipe, mem_cyc_pipe=mem_cyc_pipe,
        n_tiles_l=n_tiles_l,
        buf_l=buf, ce_buf_l=ce_buf_of_layer, wtile=wtile,
        fm_tile2=fm_tile2, ofm_res=ofm_res, ofm_acc=ofm_acc,
        ideal=ideal, ifm_onchip=ifm_onchip, use_a=use_a,
        resident_l=resident_l,
        alloc=alloc, desires=desires, inter_onchip=inter_onchip,
        bound_valid=bound_valid, is_pipe_seg=is_pipe_seg)


def compose_metrics(design: DesignBatch, t: NetTables, dev: DeviceTables,
                    m: _CEMaps, st: LayerState) -> dict[str, jnp.ndarray]:
    """Eqs. 2–3 + 8–9: per-layer costs -> design metrics.

    Monotone nondecreasing in every per-layer latency/busy/traffic field
    of ``st`` — the property the schedule layer's refined-≤-coarse
    guarantee rests on."""
    B, max_L = design.batch, t.max_L
    wb = dev.wordbytes
    (seg_start, seg_len, seg_valid, n_seg, seg_of_layer, onehot, valid_b,
     idx_in_seg, nce_of_layer, pipe_bool, slot_of_layer, _round,
     ce_base, _ce_of_layer, ce_oh, _pes, ce_valid) = m
    valid_f = valid_b.astype(jnp.float32)
    seg_end = design.seg_end
    pipe_l = pipe_bool.astype(jnp.float32)
    single_l = (1.0 - pipe_l) * valid_f
    is_round_start = slot_of_layer == 0
    is_round_last = (slot_of_layer == nce_of_layer - 1) | \
        (idx_in_seg == _of_layer(seg_len, seg_of_layer) - 1)
    last_of_seg = jnp.clip(seg_end - 1, 0, t.L - 1)      # (B, NS)
    OFM = jnp.asarray(t.OFM)
    IFM = jnp.asarray(t.IFM)
    macs = jnp.asarray(t.MACS)
    n_tiles_l = st.n_tiles_l
    inter_onchip = st.inter_onchip
    bound_valid = st.bound_valid
    is_pipe_seg = st.is_pipe_seg
    alloc, desires = st.alloc, st.desires

    # ---- latency / busy ---------------------------------------------------
    lat_l_single = st.lat_single * single_l
    seg_lat_single = _seg_sum(lat_l_single, onehot)      # (B, NS)

    # pipelined: tile lat per layer; exact stage-sum per round via the
    # prefix/suffix-max identity (segmented max-scans, log2(L) steps).
    tile_lat = st.busy_pipe / n_tiles_l                  # (B, L)
    pmax_seq = seg_scan_max(tile_lat, is_round_start)
    smax_seq = seg_scan_max(tile_lat, is_round_last, reverse=True)
    pipe_f = pipe_bool
    prefix_sum_all = jnp.where(pipe_f, pmax_seq, 0.0).sum(-1)
    suffix_sum_all = jnp.where(pipe_f, smax_seq, 0.0).sum(-1)
    gmax_l = jnp.where(pipe_f & is_round_last, pmax_seq, 0.0)

    # round latency = prefix_sum(0..n-1) + suffix_sum(0..n-1) - gmax
    #                 + (T - n) * gmax            [T = n_tiles, n = slots]
    slots_round = jnp.where(pipe_f & is_round_last,
                            slot_of_layer.astype(jnp.float32) + 1.0, 0.0)
    T_round = jnp.where(pipe_f & is_round_last, n_tiles_l, 0.0)
    lat_pipe_total = (prefix_sum_all + suffix_sum_all
                      + ((T_round - slots_round - 1.0) * gmax_l).sum(-1))

    # per-CE busy (Eq. 3 / throughput)
    busy_l = st.busy_pipe                                # pipelined layers
    busy_slot = jnp.einsum("bl,blc->bc", busy_l * pipe_l, ce_oh,
                           precision=EXACT)                   # (B, NC)
    # pipelined block busy = max over its slots; map back per segment:
    seg_of_ce = jnp.sum(
        (jnp.arange(NC)[None, :, None]
         >= (ce_base + design.seg_nce * seg_valid)[:, None, :]),
        axis=-1)                                         # (B, NC)
    seg_ce_oh = jax.nn.one_hot(seg_of_ce, NS, dtype=jnp.float32)
    busy_pipe_seg = jnp.where(
        is_pipe_seg,
        jnp.max(jnp.where(seg_ce_oh > 0, busy_slot[..., None], NEG), axis=1),
        0.0)
    busy_single_seg = jnp.where(~design.seg_pipe & seg_valid,
                                seg_lat_single, 0.0)

    # single-CE ids may serve multiple segments: busy adds per CE
    ce_first = ce_base                                   # (B, NS)
    add_single = jnp.zeros((B, NC)).at[
        jnp.arange(B)[:, None], ce_first].add(
        jnp.where(~design.seg_pipe & seg_valid, busy_single_seg, 0.0))
    add_pipe = jnp.zeros((B, NC)).at[
        jnp.arange(B)[:, None], ce_first].add(busy_pipe_seg)
    ce_busy = add_single + add_pipe

    # ---- interfaces: mandatory IO + Eq. 9 ---------------------------------
    access = (st.acc_single * single_l + st.w_acc_pipe * pipe_l).sum(-1)
    w_access = (st.wacc_single * single_l + st.w_acc_pipe * pipe_l).sum(-1)
    fm_access = (st.facc_single * single_l).sum(-1)
    mandatory = (IFM[0] + jnp.take(OFM, t.L - 1)) * wb
    access = access + mandatory
    fm_access = fm_access + mandatory

    bound_sz = jnp.where(bound_valid, OFM[last_of_seg] * wb, 0.0)
    spill = bound_valid & ~inter_onchip
    access = access + (2 * jnp.where(spill, bound_sz, 0.0)).sum(-1)
    fm_access = fm_access + (2 * jnp.where(spill, bound_sz, 0.0)).sum(-1)
    comm_cyc = ((jnp.where(spill, 2 * bound_sz, bound_sz) / dev.bps)
                * dev.clock_hz * bound_valid).sum(-1)

    latency_cyc = seg_lat_single.sum(-1) + lat_pipe_total + comm_cyc
    latency_s = latency_cyc / dev.clock_hz

    multi = (n_seg > 1) & design.inter_pipe
    bottleneck = jnp.where(multi, ce_busy.max(-1),
                           jnp.where(n_seg > 1, latency_cyc,
                                     jnp.maximum(ce_busy.max(-1), 1.0)))
    throughput = dev.clock_hz / jnp.maximum(bottleneck, 1.0)

    buffer_alloc = alloc.sum(-1) + (
        2 * jnp.where(inter_onchip, bound_sz, 0.0)).sum(-1)
    # Eq. 8 requirement (what the paper's buffer metric reports)
    buffer_req = desires.sum(-1) + jnp.where(
        design.inter_pipe, (2 * bound_sz).sum(-1), 0.0)

    util_avg = (st.util * macs[None]).sum(-1) / jnp.maximum(macs.sum(), 1.0)

    return {
        "latency_s": latency_s,
        "throughput_ips": throughput,
        "buffer_bytes": buffer_req,
        "buffer_alloc_bytes": buffer_alloc,
        "access_bytes": access,
        "weight_access_bytes": w_access,
        "fm_access_bytes": fm_access,
        "utilization": util_avg,
        "n_ces": ce_valid.sum(-1),
    }


def _evaluate_core(design: DesignBatch, t: NetTables, dev: DeviceTables,
                   m: _CEMaps, par, fm_tile_rows: int) -> dict:
    """Full MCCM evaluation: per-layer state then Eq. 2–9 composition."""
    with jax.named_scope("layer_state"):
        st = layer_state(design, t, dev, m, par, fm_tile_rows)
    with jax.named_scope("compose_metrics"):
        return compose_metrics(design, t, dev, m, st)


def _pad_rows(design: DesignBatch, n: int) -> DesignBatch:
    """Edge-pad a DesignBatch to ``n`` rows (padded rows are evaluated and
    discarded — keeping shapes static kills tail recompiles)."""
    pad = n - design.batch
    if pad <= 0:
        return design
    rep = lambda a: jnp.concatenate([a, jnp.repeat(a[-1:], pad, 0)], 0)
    return DesignBatch(rep(design.seg_end), rep(design.seg_pipe),
                       rep(design.seg_nce), rep(design.inter_pipe))


def padded_rows(B: int, tile: int = DEFAULT_TILE, ndevices: int = 1) -> int:
    """Rows actually executed for a B-design call (B padded to a multiple
    of ``ndevices x tile``) — the single source of the tiling policy for
    benchmarks and the mesh layer.  Rounding to the *device-count*
    multiple keeps every shard an identical whole number of tiles, so a
    B not divisible by the device count never reshards or recompiles."""
    unit = tile * max(int(ndevices), 1)
    return -(-B // unit) * unit


def eval_design_block(design: DesignBatch, tables: NetTables,
                      dev: DeviceTables, pairs, fc_pair, coh_pair, *,
                      backend: str = "ref", design_tile: int = 16,
                      fm_tile_rows: int = 2) -> dict[str, jnp.ndarray]:
    """Fully traced evaluation of one design block (no tiling/padding):
    CE maps -> fused ⟨pf, ph, pw⟩ search -> Eqs. 2–9.

    The shared building block: the ``lax.map`` hot loop below runs it per
    design tile, and ``core.multinet`` vmaps it across the model axis with
    per-row partitioned devices.  Each step runs under a named scope
    (``ce_maps``, ``parallelism_search``, ``layer_state``,
    ``compose_metrics``) that the profiler's trace shows on its ops."""
    with jax.named_scope("ce_maps"):
        m = _ce_maps(design, tables, dev)
    with jax.named_scope("parallelism_search"):
        pf, ph, pw, _cost = parallelism_search(
            m.pes_ce, m.ce_of_layer, m.ce_oh, fc_pair, coh_pair,
            tables.CEIL_OW, tables.OW[:, None], pairs, backend=backend,
            design_tile=design_tile)
    return _evaluate_core(design, tables, dev, m, (pf, ph, pw), fm_tile_rows)


def evaluate_batch_traced(design: DesignBatch, tables: NetTables,
                          dev: DeviceTables, *, backend: str = "ref",
                          tile: int = DEFAULT_TILE, fm_tile_rows: int = 2,
                          pes_hint_static: int | None = None,
                          design_tile: int = 16) -> dict[str, jnp.ndarray]:
    """The traced hot path (call under jit; ``evaluate_batch`` wraps it).

    Designs are processed in ``tile``-wide blocks through ``lax.map`` so
    every intermediate — most importantly the (tile, L, P) parallelism-
    search block — stays cache/VMEM-resident; per tile the search
    dispatches to the selected ``kernels.mccm_eval`` backend.

    ``pes_hint_static`` prunes the candidate-pair grid and is only sound
    when the device's PE total is <= the hint; the default (None) keeps
    every pair.  ``evaluate_batch``/``search`` pass the bucket computed
    from the concrete device.
    """
    B = design.batch
    pairs = pair_tables(tables.candidates, pes_hint_static)
    fc_pair, coh_pair = _pair_layer_tables(tables, pairs)

    nt = -(-B // tile)
    padded = _pad_rows(design, nt * tile)

    def one(args):
        return eval_design_block(
            DesignBatch(*args), tables, dev, pairs, fc_pair, coh_pair,
            backend=backend, design_tile=design_tile,
            fm_tile_rows=fm_tile_rows)

    out = jax.lax.map(one, (padded.seg_end.reshape(nt, tile, NS),
                            padded.seg_pipe.reshape(nt, tile, NS),
                            padded.seg_nce.reshape(nt, tile, NS),
                            padded.inter_pipe.reshape(nt, tile)))
    return {k: v.reshape(nt * tile)[:B] for k, v in out.items()}


@partial(jax.jit, static_argnames=("backend", "tile", "fm_tile_rows",
                                   "pes_hint_static", "design_tile"))
def _evaluate_jit(design, tables, dev, *, backend, tile, fm_tile_rows,
                  pes_hint_static, design_tile):
    return evaluate_batch_traced(
        design, tables, dev, backend=backend, tile=tile,
        fm_tile_rows=fm_tile_rows, pes_hint_static=pes_hint_static,
        design_tile=design_tile)


def evaluate_batch(design: DesignBatch, tables: NetTables,
                   dev: DeviceSpec | DeviceTables, fm_tile_rows: int = 2,
                   *, backend: str | None = None, tile: int = DEFAULT_TILE,
                   design_tile: int = 16, mesh=None) -> dict[str, jnp.ndarray]:
    """DesignBatch -> metric arrays, one jitted dispatch.

    One compiled program serves every CNN (tables are traced, padded to a
    shared ``max_L``) and every board (traced scalars); only the batch
    shape and the static knobs key the jit cache.

    ``mesh`` (a ``core.shard.EvalMesh``, duck-typed to avoid an import
    cycle) shards the design axis across its devices; a None or
    single-device mesh takes this unchanged single-device path.
    """
    backend = resolve_backend(backend)
    if isinstance(dev, DeviceSpec):
        hint = pes_hint(dev.pes)
        devt = make_device_tables(dev)
    else:
        devt = dev
        hint = pes_hint(float(dev.pes))
    if mesh is not None and getattr(mesh, "is_sharded", False):
        return mesh.evaluate_padded(
            design, tables, devt, backend=backend, tile=tile,
            fm_tile_rows=fm_tile_rows, pes_hint_static=hint,
            design_tile=design_tile)
    return _evaluate_jit(design, tables, devt, backend=backend, tile=tile,
                         fm_tile_rows=fm_tile_rows, pes_hint_static=hint,
                         design_tile=design_tile)


# --------------------------------------------------------------------------
# spec-list convenience wrappers (recompile-free chunking)
# --------------------------------------------------------------------------
def _bucket(b: int, tile: int, ndevices: int = 1) -> int:
    """Smallest power-of-two multiple of ``ndevices x tile`` holding ``b``
    designs — bounds the number of distinct compiled shapes to the ladder
    size, and keeps every bucket evenly shardable across the mesh."""
    n = tile * max(int(ndevices), 1)
    while n < b:
        n *= 2
    return n


def _evaluate_specs(specs: list[AcceleratorSpec], net: Network,
                    dev: DeviceSpec, chunk: int = 2048, *,
                    tables: NetTables | None = None,
                    backend: str | None = None,
                    tile: int = DEFAULT_TILE,
                    pad_to: int | None = None,
                    fm_tile_rows: int = 2,
                    design_tile: int = 16, mesh=None) -> dict[str, np.ndarray]:
    """Implementation behind ``Session.evaluate`` (spec lists) and the
    deprecated ``evaluate_specs`` shim: specs -> stacked metric arrays
    (chunked).

    Every chunk — including the tail — is padded to a static shape, so a
    100k-design sweep compiles exactly once (and shares that compile with
    every other CNN × board sweep at the same chunk size).  ``pad_to``
    overrides the bucket (``_evaluate_specs_multi`` uses it to share one
    shape across differently-sized jobs).  Under a sharded ``mesh`` the
    bucket rounds to a multiple of ``ndevices x tile`` so no B triggers a
    resharding recompile."""
    if not specs:
        raise ValueError("no specs to evaluate (empty design list)")
    tables = make_tables(net) if tables is None else tables
    nd = mesh.ndevices if mesh is not None and mesh.is_sharded else 1
    n_layers = len(net)
    outs: list[dict] = []
    n = len(specs)
    if pad_to is None:
        pad_to = chunk if n > chunk else _bucket(max(n, 1), tile, nd)
    for i in range(0, n, chunk):
        sub = specs[i:i + chunk]
        batch = _pad_rows(encode_specs(sub, n_layers), pad_to)
        out = evaluate_batch(batch, tables, dev, fm_tile_rows,
                             backend=backend, tile=tile,
                             design_tile=design_tile, mesh=mesh)
        outs.append({k: np.asarray(v)[:len(sub)] for k, v in out.items()})
    return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}


def evaluate_specs(specs: list[AcceleratorSpec], net: Network,
                   dev: DeviceSpec, chunk: int = 2048, *,
                   tables: NetTables | None = None,
                   backend: str | None = None,
                   tile: int = DEFAULT_TILE,
                   pad_to: int | None = None) -> dict[str, np.ndarray]:
    from ._deprecation import warn_deprecated
    warn_deprecated("evaluate_specs", "repro.api.Session.evaluate")
    return _evaluate_specs(specs, net, dev, chunk, tables=tables,
                           backend=backend, tile=tile, pad_to=pad_to)


def _evaluate_specs_multi(jobs, chunk: int = 2048, *,
                          backend: str | None = None,
                          tile: int = DEFAULT_TILE,
                          tables=None, fm_tile_rows: int = 2,
                          design_tile: int = 16, mesh=None) -> list[dict]:
    """Implementation behind ``Session.submit``'s drain loop and the
    deprecated ``evaluate_specs_multi`` shim: cross-(CNN × board)
    megabatch.  ``jobs`` is a sequence of ``(specs, net, dev)`` triples;
    returns one metric dict per job.  ``tables``, when given, is one
    prebuilt ``NetTables`` per job (the Session passes its memoized
    tables here).

    Because NetTables / DeviceTables are traced pytrees padded to shared
    shapes, and every job's chunks are padded to one shared bucket, the
    whole sweep runs through a single compiled program — the per-job work
    differs only in array *values*."""
    nd = mesh.ndevices if mesh is not None and mesh.is_sharded else 1
    sizes = [min(max(len(specs), 1), chunk) for specs, _, _ in jobs]
    pad_to = max((_bucket(s, tile, nd) for s in sizes), default=tile * nd)
    results = []
    for i, (specs, net, dev) in enumerate(jobs):
        results.append(_evaluate_specs(
            specs, net, dev, chunk,
            tables=None if tables is None else tables[i],
            backend=backend, tile=tile, pad_to=pad_to,
            fm_tile_rows=fm_tile_rows, design_tile=design_tile, mesh=mesh))
    return results


def evaluate_specs_multi(jobs, chunk: int = 2048, *,
                         backend: str | None = None,
                         tile: int = DEFAULT_TILE) -> list[dict]:
    from ._deprecation import warn_deprecated
    warn_deprecated("evaluate_specs_multi",
                    "repro.api.Session.submit (or Session.evaluate per job)")
    return _evaluate_specs_multi(jobs, chunk, backend=backend, tile=tile)
