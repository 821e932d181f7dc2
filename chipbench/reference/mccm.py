"""Plain MCCM reference: one design of one CNN on one board, in Python
floats and integers, one layer at a time.

This is the benchmark's own copy of the scalar evaluator that the program
keeps as its test reference (``src/repro/core/builder.py``,
``core/blocks.py`` and ``core/accelerator.py``): the Builder's resource
distribution (PEs by largest remainder over per-CE MACs, the ⟨pf, ph, pw⟩
search per CE, buffer floors, inter-segment double buffers and the grant
toward minimum-access sizes), then Eq. 1–9 composed over the segments.  It
imports nothing of the program and takes nothing the program made: the
layers and the board come from the configuration file, the design from
its segment arrays.

Two departures from a line-by-line copy: the ⟨pf, ph, pw⟩ search scores
all pairs of a CE at once with NumPy int64, visiting them in the scalar
loop's order and keeping the first minimum, so it picks the same vector;
and the buffer grant is exact (see ``evaluate``).
"""
from __future__ import annotations

import math

import numpy as np

#: ⟨pf, ph, pw⟩ candidate values (the Builder's default list)
CANDIDATES = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256,
              384, 512)
#: the metrics this reference computes, as the program names them
METRICS = ("latency_s", "throughput_ips", "buffer_bytes", "access_bytes")


# --------------------------------------------------------------------------
# layers and boards, from the configuration file
# --------------------------------------------------------------------------
class Layer:
    """One convolution: sizes in elements, as ``core/workload.py`` has
    them."""

    __slots__ = ("kind", "in_ch", "out_ch", "kh", "kw", "stride", "ih",
                 "iw", "residual", "oh", "ow", "c", "ifm", "ofm", "weights",
                 "macs")

    def __init__(self, d: dict):
        self.kind = d["kind"]
        self.in_ch, self.out_ch = int(d["in_ch"]), int(d["out_ch"])
        self.kh, self.kw = int(d["kh"]), int(d["kw"])
        self.stride = int(d["stride"])
        self.ih, self.iw = int(d["ih"]), int(d["iw"])
        self.residual = bool(d.get("residual", False))
        if d.get("padding", "same") == "same":
            self.oh = -(-self.ih // self.stride)
            self.ow = -(-self.iw // self.stride)
        else:
            self.oh = (self.ih - self.kh) // self.stride + 1
            self.ow = (self.iw - self.kw) // self.stride + 1
        self.c = 1 if self.kind == "dw" else self.in_ch
        self.ifm = self.in_ch * self.ih * self.iw
        self.ofm = self.out_ch * self.oh * self.ow
        self.weights = self.out_ch * self.c * self.kh * self.kw
        self.macs = self.weights * self.oh * self.ow

    @property
    def fms(self) -> int:
        return self.ifm + self.ofm + (self.ofm if self.residual else 0)


class Board:
    """Table II resources: PEs, on-chip bytes, off-chip GB/s, clock."""

    def __init__(self, d: dict):
        self.pes = int(d["pes"])
        self.on_chip_bytes = int(d["on_chip_bytes"])
        self.gbps = float(d["off_chip_gbps"])
        self.clock_hz = float(d["clock_hz"])
        self.wb = int(d["wordbytes"])
        self.bpc = self.gbps * 1e9 / self.clock_hz


def segments_of(seg_end, seg_pipe, seg_nce, n_layers: int) -> list:
    """Design row -> [(layer_lo, layer_hi, ce_lo, ce_hi)], 0-based and
    inclusive: a segment ends where ``seg_end`` says, is pipelined when
    ``seg_pipe`` is set, and takes the next ``seg_nce`` CEs if so, else
    one."""
    segs, lo, ce = [], 0, 0
    for s in range(len(seg_end)):
        hi = int(seg_end[s])
        if hi <= lo:
            continue
        n = int(seg_nce[s]) if bool(seg_pipe[s]) else 1
        segs.append((lo, hi - 1, ce, ce + n - 1))
        ce += n
        lo = hi
        if hi >= n_layers:
            break
    return segs


# --------------------------------------------------------------------------
# Builder
# --------------------------------------------------------------------------
def largest_remainder(shares: list, total: int, floor: int = 1) -> list:
    n = len(shares)
    total = max(total, n * floor)
    s = sum(shares) or 1.0
    raw = [max(x / s * total, floor) for x in shares]
    out = [max(int(r), floor) for r in raw]
    rem = total - sum(out)
    order = sorted(range(n), key=lambda i: raw[i] - int(raw[i]), reverse=True)
    i = 0
    while rem > 0 and n:
        out[order[i % n]] += 1
        rem -= 1
        i += 1
    while rem < 0 and n:
        j = max(range(n), key=lambda k: out[k])
        if out[j] > floor:
            out[j] -= 1
            rem += 1
        else:
            break
    return out


def best_parallelism(pes: int, layers: list) -> tuple:
    """⟨pf, ph, pw⟩ of least total Eq. 1 cycles over ``layers``: pf and ph
    from the candidates with pf·ph ≤ PEs, pw the largest candidate that
    still fits, first minimum in (pf, ph) order."""
    pes = max(pes, 1)
    pf, ph, pw = [], [], []
    for f in CANDIDATES:
        if f > pes:
            break
        for h in CANDIDATES:
            if f * h > pes:
                break
            w = 1
            for c in CANDIDATES:
                if f * h * c <= pes:
                    w = c
                else:
                    break
            pf.append(f)
            ph.append(h)
            pw.append(w)
    pf, ph, pw = (np.asarray(v, np.int64) for v in (pf, ph, pw))
    cost = np.zeros(len(pf), np.int64)
    for l in layers:
        cost += (-(-l.out_ch // pf) * (l.c * l.kh * l.kw)
                 * -(-l.oh // ph) * -(-l.ow // pw))
    i = int(np.argmin(cost))
    return int(pf[i]), int(ph[i]), int(pw[i])


def layer_cycles(l: Layer, par: tuple) -> int:
    """Eq. 1: product over the six loop dimensions of ⌈|d| / Par(d)⌉."""
    pf, ph, pw = par
    return (-(-l.out_ch // pf) * l.c * l.kh * l.kw * -(-l.oh // ph)
            * -(-l.ow // pw))


def weight_tile(l: Layer, pf: int) -> int:
    return min(pf, l.out_ch) * l.c * l.kh * l.kw


def single_min_buffer(layers: list, pf: int, wb: int) -> int:
    """Eq. 4."""
    return (max(l.fms for l in layers)
            + max(weight_tile(l, pf) for l in layers)) * wb


def pipelined_min_buffer(layers: list, wb: int, rows: int) -> int:
    """Eq. 5."""
    return sum(l.weights * wb + 2 * l.out_ch * l.ow * rows * wb
               for l in layers)


# --------------------------------------------------------------------------
# Eq. 1–7 per block
# --------------------------------------------------------------------------
def single_layer_access(l: Layer, buf: int, pf: int, wb: int,
                        ifm_onchip: bool) -> tuple:
    """Eq. 6: (access bytes, OFM stays on chip)."""
    w, ifm, ofm = l.weights * wb, l.ifm * wb, l.ofm * wb
    extra = l.ofm * wb if l.residual else 0
    wtile = weight_tile(l, pf) * wb
    if ifm + ofm + extra + wtile <= buf:
        return w + (0.0 if ifm_onchip else ifm), True
    ifm_tile = min(ifm, l.in_ch * l.kh * l.iw * wb)
    ofm_onchip = ofm + extra + wtile + ifm_tile <= buf
    ofm_resident = (ofm + extra) if ofm_onchip else 0
    ofm_acc = 0.0 if ofm_onchip else float(ofm)
    if ifm_onchip:
        return ofm_acc + w, ofm_onchip
    ifm_buf = max(buf - ofm_resident - wtile, ifm_tile)
    loads_a = w * math.ceil(ifm / ifm_buf) + ifm if ifm_buf < ifm else w + ifm
    w_buf = max(buf - ofm_resident - ifm_tile, wtile)
    loads_b = ifm * math.ceil(w / w_buf) + w if w_buf < w else ifm + w
    return ofm_acc + min(loads_a, loads_b), ofm_onchip


def eval_single(layers: list, par: tuple, buf: int, board: Board,
                ifm_onchip: bool) -> tuple:
    """Single-CE block: (latency cycles, busy cycles, access bytes)."""
    lat = acc = 0.0
    for l in layers:
        a, ofm_onchip = single_layer_access(l, buf, par[0], board.wb,
                                            ifm_onchip)
        lat += max(layer_cycles(l, par), a / board.bpc)
        acc += a
        ifm_onchip = ofm_onchip
    return lat, lat, acc


def stage_sum(tile_lats: list, n_tiles: int) -> float:
    """Eq. 2: over the pipeline's stages, the slowest active CE."""
    n = len(tile_lats)
    total = 0.0
    for s in range(n_tiles + n - 1):
        lo, hi = max(0, s - n_tiles + 1), min(n - 1, s)
        total += max(tile_lats[lo:hi + 1])
    return total


def eval_pipelined(layers: list, ces: list, board: Board,
                   resident: bool) -> tuple:
    """Pipelined-CEs block (Eq. 2, 3, 7): ``ces`` holds (par, buffer)."""
    wb, n = board.wb, len(ces)
    lat = acc = 0.0
    busy = [0.0] * n
    for r in range(-(-len(layers) // n)):
        rnd = range(r * n, min((r + 1) * n, len(layers)))
        n_tiles = max(layers[i].oh for i in rnd)
        tile_lats = []
        for slot, i in enumerate(rnd):
            l, (par, buf) = layers[i], ces[slot]
            w_bytes = l.weights * wb
            if resident:
                w_acc = 0.0
            elif buf >= w_bytes:
                w_acc = float(w_bytes)
            else:
                w_acc = float(w_bytes) * n_tiles
            cyc = max(layer_cycles(l, par), w_acc / board.bpc)
            tile_lats.append(cyc / n_tiles)
            busy[slot] += cyc
            acc += w_acc
        lat += stage_sum(tile_lats, n_tiles)
    return lat, max(busy), acc


# --------------------------------------------------------------------------
# the whole design
# --------------------------------------------------------------------------
def evaluate(layers: list, board: Board, seg_end, seg_pipe, seg_nce,
             inter_pipe: bool, fm_tile_rows: int = 2,
             float_grant: bool = False) -> dict:
    """Metrics of one design row: latency, throughput, Eq. 8 buffer
    requirement and off-chip access bytes.

    The grant toward minimum-access sizes is each segment's share of it,
    floored, in exact integer arithmetic, so a grant that covers every gap
    fills each gap exactly.  ``float_grant=True`` computes it as the
    program's scalar evaluator does, ``int(grant * (gap / gap_sum))`` in
    float64, which can land one byte short of a gap and out of the
    weight-resident regime (the program's batch path fills it exactly)."""
    wb, rows = board.wb, fm_tile_rows
    segs = segments_of(seg_end, seg_pipe, seg_nce, len(layers))

    # PEs in proportion to each live CE's MACs; the ⟨pf, ph, pw⟩ per CE
    assign: dict = {}
    for lo, hi, clo, chi in segs:
        n = chi - clo + 1
        for ce in range(clo, chi + 1):
            assign.setdefault(ce, [])
        for k, li in enumerate(range(lo, hi + 1)):
            assign[clo + k % n].append(layers[li])
    live = [c for c in sorted(assign) if assign[c]]
    pes = dict(zip(live, largest_remainder(
        [sum(l.macs for l in assign[c]) for c in live], board.pes)))
    par = {c: best_parallelism(pes[c], assign[c]) if assign[c]
           else (1, 1, 1) for c in assign}

    # buffer floors and minimum-access desires per segment
    floors, desires = [], []
    for lo, hi, clo, chi in segs:
        ls = layers[lo:hi + 1]
        if chi > clo:
            n = chi - clo + 1
            floor = sum(2 * l.out_ch * l.ow * rows * wb
                        + weight_tile(l, par[clo + k % n][0]) * wb
                        for k, l in enumerate(ls))
            desire = pipelined_min_buffer(ls, wb, rows)
        else:
            pf = par[clo][0]
            floor = max(weight_tile(l, pf) * wb + l.in_ch * l.kh * l.iw * wb
                        + l.out_ch * l.ow * wb for l in ls)
            desire = single_min_buffer(ls, pf, wb)
        floors.append(floor)
        desires.append(max(desire, floor))

    alloc = list(floors)
    if sum(alloc) > board.on_chip_bytes:
        scale = board.on_chip_bytes / sum(alloc)
        alloc = [int(a * scale) for a in alloc]
    remaining = board.on_chip_bytes - sum(alloc)

    n_bounds = len(segs) - 1
    inter_sizes = [layers[segs[i][1]].ofm * wb for i in range(n_bounds)]
    inter_onchip = [False] * n_bounds
    if inter_pipe:
        for i in sorted(range(n_bounds), key=lambda k: inter_sizes[k]):
            if 2 * inter_sizes[i] <= remaining:
                inter_onchip[i] = True
                remaining -= 2 * inter_sizes[i]

    gaps = [max(d - a, 0) for d, a in zip(desires, alloc)]
    gap_sum = sum(gaps)
    if gap_sum and remaining > 0:
        grant = min(remaining, gap_sum)
        for i, g in enumerate(gaps):
            alloc[i] += int(grant * (g / gap_sum)) if float_grant \
                else grant * g // gap_sum

    # Eq. 1–7 per segment, then Eq. 8–9 across them
    lat_cyc = 0.0
    access = 0.0
    buffer_req = 0
    ce_busy: dict = {}
    blocks_busy = []
    for i, (lo, hi, clo, chi) in enumerate(segs):
        ls = layers[lo:hi + 1]
        if chi > clo:
            n = chi - clo + 1
            d = [sum((l.weights + 2 * l.out_ch * l.ow * rows) * wb
                     for k, l in enumerate(ls) if k % n == slot)
                 for slot in range(n)]
            d_sum = sum(d) or 1
            ces = [(par[clo + s], int(alloc[i] * d[s] / d_sum))
                   for s in range(n)]
            lat, busy, acc = eval_pipelined(ls, ces, board,
                                            alloc[i] >= desires[i])
            buffer_req += pipelined_min_buffer(ls, wb, rows)
            for ce in range(clo, chi + 1):
                ce_busy[ce] = ce_busy.get(ce, 0.0)
            ce_busy[clo] = ce_busy.get(clo, 0.0) + busy
        else:
            prev_onchip = i > 0 and inter_onchip[i - 1]
            lat, busy, acc = eval_single(ls, par[clo], alloc[i], board,
                                         prev_onchip)
            buffer_req += single_min_buffer(ls, par[clo][0], wb)
            ce_busy[clo] = ce_busy.get(clo, 0.0) + busy
        # a segment's latency passes through seconds, as the program's
        # composition does
        lat_cyc += lat / board.clock_hz
        access += acc
        blocks_busy.append(busy)

    access += (layers[0].ifm + layers[-1].ofm) * wb
    bps = board.gbps * 1e9
    comm = 0.0
    for i in range(n_bounds):
        size = inter_sizes[i]
        if not inter_onchip[i]:
            access += 2 * size
            comm += 2 * size / bps * board.clock_hz
        else:
            comm += size / bps * board.clock_hz
    latency_cycles = lat_cyc * board.clock_hz + comm
    if inter_pipe and len(segs) > 1:
        bottleneck = max(ce_busy.values())
    else:
        bottleneck = max(blocks_busy)
        if len(segs) > 1:
            bottleneck = latency_cycles
    if inter_pipe:
        buffer_req += sum(2 * sz for sz in inter_sizes)
    return {
        "latency_s": latency_cycles / board.clock_hz,
        "throughput_ips": board.clock_hz / bottleneck if bottleneck
        else math.inf,
        "buffer_bytes": float(buffer_req),
        "access_bytes": float(access),
    }
