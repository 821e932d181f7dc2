"""The reduction from a trace to busy, idle, kernel time, program time and
gaps: on a hand-made trace whose answers are worked out by hand, and on a
short trace recorded on a TPU v5e chip and committed beside this file."""
import os

import pytest

from chipbench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
#: 66 ms of a sweep's trace on one TPU v5e: the last 20 ms of one
#: ``_evaluate_jit`` program, the host's pull, 25 ms of the next; its
#: expected readings were worked out when it was cut, busy time on a 10 ns
#: timeline rather than by merging intervals
RECORDED = os.path.join(HERE, "data", "sweep_v5e.trace.json.gz")

#: window 0..100; device ops (start, duration): a program 10..40 holding
#: a kernel 20..25, a second program 60..90, a lone op 45..50, one op
#: sticking out of the window at 95..120
HAND = {"window": [0, 100], "host": [[0, 50, "chipbench.evaluate"],
                                     [50, 50, "chipbench.pull"]],
        "devices": {"/device:TPU:0": {
            "ops": [[10, 30, "%while.1"], [12, 5, "%fusion.1"],
                    [20, 5, "kernel"], [45, 5, "%copy.2"],
                    [60, 30, "%while.1"], [62, 3, "kernel"],
                    [95, 25, "%fusion.9"]],
            "modules": [[10, 30, "jit_step(1)"], [45, 5, "jit_add(2)"],
                        [60, 30, "jit_step(1)"], [95, 25, "jit_step(1)"]]}}}


def test_by_hand():
    assert tr.window_ns(HAND) == 100
    # busy: 10..40, 45..50, 60..90, 95..100 = 30 + 5 + 30 + 5
    assert tr.mean_busy_ns(HAND) == 70
    assert tr.idle_pct(HAND) == pytest.approx(30.0)
    assert tr.kernel_ns(HAND) == 8
    assert tr.module_ns(HAND, "step") == 65
    assert tr.top_ops(HAND, 2) == [["kernel", 8e-9], ["%fusion.1", 5e-9]]
    # idle stretches 0..10, 40..45, 50..60, 90..95, named by the host
    assert tr.idle_gaps(HAND, 2) == [["chipbench.evaluate", 1e-8],
                                     ["chipbench.pull", 1e-8]]


def test_union_of_nested_and_touching_spans():
    assert tr.union_ns([(0, 10), (2, 3), (10, 12), (20, 21)]) == 13
    assert tr.union_ns([]) == 0


def test_recorded_chip_trace():
    t = tr.load(RECORDED)
    assert list(t["devices"]) == ["/device:TPU:0"]
    expect = t["expect"]
    assert tr.window_ns(t) == pytest.approx(expect["window_ns"])
    # the expected busy time was counted on a 10 ns timeline
    assert tr.mean_busy_ns(t) == pytest.approx(expect["busy_ns"], rel=1e-5)
    assert tr.kernel_ns(t) == pytest.approx(expect["kernel_ns"])
    assert tr.module_ns(t, "_evaluate_jit") \
        == pytest.approx(expect["evaluate_ns"])
    assert 0 < tr.kernel_ns(t) < tr.module_ns(t, "_evaluate_jit")
