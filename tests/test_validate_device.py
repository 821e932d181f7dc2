"""``Session.evaluate(DesignBatch)`` checks the batch on the device: the
two scalars ``count_invalid_jit`` returns agree with the host
``validate_batch`` on every kind of fault, a refused batch never reaches
the evaluator, and the check pulls nothing but those two scalars."""
from __future__ import annotations

import numpy as np
import pytest

from repro.api import EvalError, Session
from repro.cnn.registry import get_cnn
from repro.core import batch_eval, telemetry
from repro.core import session as session_mod
from repro.core.dse import sample_mixed
from repro.core.dse.encoding import (NC, NS, DesignBatch, count_invalid_jit,
                                     validate_batch)
from repro.fpga.boards import get_board

NET = get_cnn("mobilenetv2")
L = len(NET)
B = 24          # one batch shape for the whole module: one compile
ROW = 5         # where a single bad row goes


def _good_row():
    """A canonical three-segment row: L1-5 on 1 CE, L6-10 pipelined on 4,
    the rest pipelined on 2 (7 CEs)."""
    end = np.full(NS, L, np.int32)
    end[:2] = (5, 10)
    nce = np.ones(NS, np.int32)
    nce[1:3] = (4, 2)
    pipe = np.zeros(NS, bool)
    pipe[1:3] = True
    return end, pipe, nce


def _decreasing(end, pipe, nce):
    end[1] = 3


def _last_not_l(end, pipe, nce):
    end[2:] = L - 1


def _first_zero(end, pipe, nce):
    end[0] = 0


def _end_over_l(end, pipe, nce):
    end[2:] = L + 3


def _gap(end, pipe, nce):
    # an empty segment before a later active one
    end[:4] = (5, 5, 10, L)
    nce[1:4] = (1, 4, 2)
    pipe[1:4] = (False, True, True)


def _nce_zero(end, pipe, nce):
    nce[0] = 0


def _pipe_mismatch(end, pipe, nce):
    pipe[0] = True


def _padding_nce(end, pipe, nce):
    nce[NS - 1] = 2


def _over_nc(end, pipe, nce):
    nce[1] = NC - 1          # 1 + 15 + 2 CEs


def _under_min(end, pipe, nce):
    # one segment on one CE: valid for the session (min 1), not at min 2
    end[:] = L
    nce[:] = 1
    pipe[:] = False


_FAULTS = {f.__name__.lstrip("_"): f for f in (
    _decreasing, _last_not_l, _first_zero, _end_over_l, _gap, _nce_zero,
    _pipe_mismatch, _padding_nce, _over_nc, _under_min)}

#: (case, rows to corrupt and how, min_ces of the direct check)
_CASES = ([("valid", {}, 1)]
          + [(k, {ROW: f}, 2 if k == "under_min" else 1)
             for k, f in _FAULTS.items()]
          + [("several", {3: _gap, 11: _over_nc, 17: _pipe_mismatch,
                          23: _end_over_l}, 1)])


def _batch(faults) -> DesignBatch:
    db = sample_mixed(np.random.default_rng(7), L, B, min_ces=1, max_ces=NC)
    se, sp, sn, ip = (np.array(a) for a in db.to_numpy())
    for row, fault in faults.items():
        se[row], sp[row], sn[row] = _good_row()
        fault(se[row], sp[row], sn[row])
    return DesignBatch.from_numpy(se, sp, sn, ip)


@pytest.fixture()
def counting():
    telemetry.disable()
    telemetry.reset()
    telemetry.enable()
    try:
        yield lambda: telemetry.snapshot()["counters"].get(
            "session.rejected_rows", 0)
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize("case,faults,min_ces", _CASES,
                         ids=[c[0] for c in _CASES])
def test_device_check_matches_host_check_and_refuses(case, faults, min_ces,
                                                     counting, monkeypatch):
    db = _batch(faults)
    # the direct check, at the case's bounds
    bad = np.nonzero(~validate_batch(db, L, min_ces=min_ces, max_ces=NC))[0]
    if faults:
        assert bad.size, f"{case}: the fault is not a fault"
    got = np.asarray(count_invalid_jit(db, L, min_ces=min_ces, max_ces=NC))
    assert got.dtype == np.int32 and got.shape == (2,)
    assert got.tolist() == [bad.size, int(bad[0]) if bad.size else 0]

    # the session, at its bounds [1, NC]
    ses = Session(get_board("zc706"))
    bad = np.nonzero(~validate_batch(db, L, min_ces=1, max_ces=NC))[0]
    if not bad.size:
        out = ses.evaluate(db, NET)
        assert np.isfinite(np.asarray(out["latency_s"])).all()
        assert counting() == 0
        return
    compiled = batch_eval._evaluate_jit._cache_size()
    dispatched = []
    monkeypatch.setattr(session_mod, "evaluate_batch",
                        lambda *a, **k: dispatched.append(a))
    with pytest.raises(EvalError) as ei:
        ses.evaluate(db, NET)
    assert ei.value.code == EvalError.INVALID_INPUT
    assert (f"{bad.size} invalid DesignBatch row(s), first at index "
            f"{int(bad[0])} (non-canonical segments or CE count outside "
            f"[1, {NC}])") in str(ei.value)
    assert not dispatched and \
        batch_eval._evaluate_jit._cache_size() == compiled, \
        "a refused batch reached the evaluator"
    assert counting() == bad.size


def test_malformed_batch_is_invalid_input():
    """Arrays whose shapes do not line up fail while the check traces,
    and that is the caller's fault: INVALID_INPUT."""
    db = _batch({})
    bad = DesignBatch(db.seg_end, db.seg_pipe, db.seg_nce[:, :NS - 1],
                      db.inter_pipe)
    with pytest.raises(EvalError) as ei:
        Session(get_board("zc706")).evaluate(bad, NET)
    assert ei.value.code == EvalError.INVALID_INPUT


def test_empty_batch_still_evaluates():
    """No row, no fault: the check reads [0, 0] and the session returns
    empty arrays, as the host check let it."""
    empty = _batch({}).take(np.arange(0))
    assert np.asarray(count_invalid_jit(empty, L)).tolist() == [0, 0]
    out = Session(get_board("zc706")).evaluate(empty, NET)
    assert np.asarray(out["latency_s"]).shape == (0,)


def test_evaluate_never_pulls_the_batch(monkeypatch):
    def no_pull(self):
        raise AssertionError("the batch was pulled to the host")

    db = _batch({})
    monkeypatch.setattr(DesignBatch, "to_numpy", no_pull)
    out = Session(get_board("zc706")).evaluate(db, NET)
    assert np.isfinite(np.asarray(out["latency_s"])).all()


def test_check_compiles_once_per_batch_shape():
    """``n_layers`` is traced: two CNNs at one batch shape, twice each,
    share one compiled check."""
    nets = (NET, get_cnn("resnet50"))
    dbs = [sample_mixed(np.random.default_rng(i), len(n), B, min_ces=1,
                        max_ces=NC) for i, n in enumerate(nets)]
    ses = Session(get_board("zc706"))
    count_invalid_jit.clear_cache()
    for _ in range(2):
        for net, db in zip(nets, dbs):
            ses.evaluate(db, net)
    assert ses.compile_stats()["validate_batch"] == 1
