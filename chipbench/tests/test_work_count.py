"""The kernel's work count, tied to numbers worked by hand."""
import os

import numpy as np
import pytest

from chipbench import spec
from chipbench.drivers import live_ces



def _module():
    import importlib.util
    path = os.path.join(spec.HERE, "layer_metrics",
                        "parallelism_search_roofline.py")
    s = importlib.util.spec_from_file_location("roofline_under_test", path)
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


def test_pairs_after_pruning():
    m = _module()
    # 18 candidates: every board up to 2520 PEs keeps the pf·ph <= 2520
    # pairs, which is the 219 columns the kernel's tables have in a chip
    # trace (f32[160,219]); past the last bucket all 18 x 18 stay
    assert m.pairs(900) == m.pairs(2520) == 219
    assert m.pairs(10**6) == 18 * 18


def test_work_by_hand():
    m = _module()
    # two designs of a 5-layer net, one with 1 live CE and one with 2, in
    # one program call, on a 1800-PE board (219 pairs, 18 candidates):
    # ops   = 219 * (3 CEs * (18 + 4) + 4 * 5 layers * 2 designs)
    #       = 219 * (66 + 40) = 23,214
    # bytes = 4 * 5 * 2 (CE of each layer) + 20 * 3 (PEs in, 4 out per CE)
    #       + 4 * (5 * 219 + 5 + 18 + 3 * 219) (tables) = 100 + 7,100
    assert m.work(2, 1, 3, 5, 1800) == (23214, 7200)
    # without pruning: 324 pairs
    assert m.work(2, 1, 3, 5, 100000) == (324 * 106,
                                          100 + 4 * (5 * 324 + 5 + 18
                                                     + 3 * 324))


def test_live_ces_by_hand():
    # 5 layers: a pipelined block of 3 CEs over layers 0-1 (2 live), then
    # a single-CE segment over layers 2-4; padding columns end at 5
    seg_end = np.array([[2, 5, 5, 5]])
    seg_pipe = np.array([[True, False, False, False]])
    seg_nce = np.array([[3, 1, 1, 1]])
    assert live_ces(seg_end, seg_pipe, seg_nce, 5) == 3


def test_share_names_its_bound():
    m = _module()

    class R:
        trace = {"window": [0, 10**9], "devices": {"d": {
            "ops": [[0, 10**6, "kernel"]], "modules": []}}}
        work = {"designs": 2, "calls": 1, "live_ces": 3, "layers": 5,
                "board_pes": 1800}
        peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    pct, note = m.read(R)
    # 7,200 bytes / 819 GB/s = 8.79 ns > 23,214 ops / 197 TFLOP/s = 0.12 ns
    assert pct == pytest.approx(100 * 7200 / 819e9 / 1e-3)
    assert "memory" in note
