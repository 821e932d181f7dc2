"""The benchmark's reference agrees with the program's scalar evaluator,
which it copies, on the five Table III CNNs and the four Table II boards,
once its buffer grant is computed the scalar evaluator's way."""
import json
import os

import numpy as np
import pytest

from chipbench import check, generate, spec
from chipbench.reference import mccm

NETS = ("resnet152", "resnet50", "xception", "densenet121", "mobilenetv2")
BOARDS = ("zc706", "vcu108", "vcu110", "zcu102")


def _config(name):
    with open(os.path.join(spec.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def _zoo():
    """The Table III CNNs on the Table II boards, as the program has them,
    written the way a configuration file writes them."""
    from chipbench.drivers import program_board, program_layers
    from repro.cnn.registry import get_cnn
    from repro.fpga.boards import get_board

    return {"nets": {n: program_layers(get_cnn(n)) for n in NETS},
            "boards": {b: program_board(get_board(b)) for b in BOARDS}}


def test_configuration_is_the_programs():
    from chipbench.drivers import resolve
    resolve(_config("xception-vcu110"))   # raises on any difference


@pytest.mark.parametrize("net", NETS)
def test_reference_equals_scalar_evaluator(net):
    from repro.cnn.registry import get_cnn
    from repro.core.dse.encoding import DesignBatch, decode_design
    from repro.core.evaluator import _evaluate_design
    from repro.fpga.boards import get_board

    cfg = _zoo()
    layers = check.layers_of(cfg, net)
    program_net = get_cnn(net)
    L = len(layers)
    rows = generate.designs(generate.rng_for(17, L), L, 40)
    db = DesignBatch.from_numpy(*rows)
    for i in range(40):
        board = BOARDS[i % 4]
        want = _evaluate_design(decode_design(db, i, L), program_net,
                                get_board(board))
        got = mccm.evaluate(layers, check.board_of(cfg, board), rows[0][i],
                            rows[1][i], rows[2][i], bool(rows[3][i]),
                            float_grant=True)
        for k in mccm.METRICS:
            assert got[k] == pytest.approx(float(getattr(want, k)),
                                           rel=1e-12, abs=0), (i, k)


def test_gaps_read_a_missing_value_as_infinite():
    cfg = _config("xception-vcu110")
    layers = check.layers_of(cfg, "xception")
    board = check.board_of(cfg, "vcu110")
    rows = generate.designs(generate.rng_for(1), 74, 1)
    row = tuple(a[0] for a in rows)
    want = mccm.evaluate(layers, board, *row[:3], bool(row[3]))
    exact = check.summary(check.gaps([row + (want,)], layers, board))
    assert all(v == 0.0 for v in exact.values())
    off = dict(want, latency_s=want["latency_s"] * (1 + 1e-3),
               access_bytes=None)
    gaps = check.summary(check.gaps([row + (off,), row + (want,)], layers,
                                    board))
    assert gaps["latency_worst"] == pytest.approx(1e-3)
    assert gaps["access_worst"] == np.inf
    ok, checks = check.verdict(dict(gaps, unanswered=0.0),
                               {"buffer_worst": 1e-4, "latency_worst": 2e-3})
    assert ok and set(checks) == {"buffer_worst", "latency_worst",
                                  "unanswered"}
    ok, checks = check.verdict(dict(gaps, unanswered=1.0),
                               {"buffer_worst": 1e-4})
    assert not ok and checks["unanswered"] == {"value": 1.0, "limit": 0}


def test_exact_grant_fills_a_covered_gap():
    """densenet121 on vcu108: the grant covers every gap, and float64
    ``int(grant * (gap / gap_sum))`` leaves one segment a byte short of
    its Eq. 5 size, out of the weight-resident regime; the exact grant
    keeps it resident, as the program's batch path does."""
    cfg = _zoo()
    layers = check.layers_of(cfg, "densenet121")
    board = check.board_of(cfg, "vcu108")
    # {L1-L92:CE1-CE3, L93-L118:CE4-CE7, L119-Last:CE8-CE11}
    row = ([92, 118, 120] + [120] * 9, [True] * 3 + [False] * 9,
           [3, 4, 4] + [1] * 9)
    exact = mccm.evaluate(layers, board, *row, True)
    scalar = mccm.evaluate(layers, board, *row, True, float_grant=True)
    assert exact["access_bytes"] == 152096.0
    assert scalar["access_bytes"] == 5101280.0
