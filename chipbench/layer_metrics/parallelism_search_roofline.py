"""parallelism_search_roofline: the share of its roofline that the
⟨pf, ph, pw⟩ search kernel reached in the traced window.

The least time the chip could take for the search's work is the larger of
its operations over the chip's peak rate and its bytes over the HBM
bandwidth; the share is that least time over the kernel's device time in
the trace.  The work is the algorithm's, counted from shapes, the same
whatever implements it:

* for each design, each live CE and each ⟨pf, ph⟩ pair: the budget
  PEs / (pf·ph) and its floor (2 ops), the largest candidate pw within it
  (K compares), the feasibility test and the running first minimum
  (2 ops): K + 4 ops;
* for each design, each real layer and each pair: ⌈ow / pw⌉ (2 ops), times
  the layer's fc·coh for the pair (1), added to its CE's sum (1): 4 ops;
* bytes: per design, the CE of each real layer (4 B); per live CE, its
  PEs in (4 B) and pf, ph, pw and the cost out (16 B); per program call,
  the tables: fc·coh per (layer, pair), ow per layer, the K candidates and
  pf, ph and pf·ph per pair (4 B each).

Layers are counted at the net's real count (74 for Xception), not the
padded 160, and pairs after the PE-budget pruning (pairs whose pf·ph
exceeds the board's PE bucket can never be chosen).  So removing padding
or one-hot work shows as a gain, and the share cannot pass 100%.
"""
from chipbench import trace as tr

#: ⟨pf, ph, pw⟩ candidate values
CANDIDATES = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256,
              384, 512)
#: PE-count buckets of the pair pruning; a board beyond them keeps all
PES_BUCKETS = (2520, 8192, 65536)


def pairs(board_pes: int) -> int:
    """⟨pf, ph⟩ pairs left after pruning for a board of ``board_pes``."""
    bucket = next((b for b in PES_BUCKETS if board_pes <= b), None)
    n = sum(1 for f in CANDIDATES for h in CANDIDATES
            if bucket is None or f * h <= bucket)
    return max(n, 1)


def work(designs: int, calls: int, live_ces: int, layers: int,
         board_pes: int) -> tuple:
    """(operations, bytes) of the search over a window."""
    K, P = len(CANDIDATES), pairs(board_pes)
    ops = P * (live_ces * (K + 4) + 4 * layers * designs)
    nbytes = (4 * layers * designs + 20 * live_ces
              + calls * 4 * (layers * P + layers + K + 3 * P))
    return ops, nbytes


def read(r):
    ns = tr.kernel_ns(r.trace)
    w = r.work
    if not ns or not w.get("designs"):
        return None
    ops, nbytes = work(w["designs"], w["calls"], w["live_ces"], w["layers"],
                       w["board_pes"])
    t_ops = ops / r.peaks["flops_per_s"]
    t_bytes = nbytes / r.peaks["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_bytes else "memory"
    pct = 100.0 * max(t_ops, t_bytes) / (ns / 1e9)
    return pct, (f"{ops} ops, {nbytes} bytes, {ns / 1e6:.3f} ms of kernel; "
                 f"bound by {bound}")
