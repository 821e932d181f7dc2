"""The control of ``correct``: the program with its one-hot contractions
at matmul precision ``default`` (one bf16 pass) in place of the
``highest`` its configuration states must come out not correct, in every
cell.  (At ``high`` the batch evaluator reads the same as at ``highest``,
and the Pallas kernel has no ``high``.)  Lower precision only exists on
the TPU, so this test needs one; run it there with
``python -m pytest -s chipbench/tests/test_control.py``, which prints each
run's compared numbers beside their limits.  Each window is long enough
to compare as many rows as a run of the cell does."""
import json

import jax
import pytest

from chipbench import spec
from chipbench.run import run

WINDOW_S = {"xception-vcu110.sweep": 2.0}


@pytest.fixture
def lowered(monkeypatch):
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control needs a TPU: the CPU ignores precision")
    from repro.core import batch_eval
    from repro.kernels.mccm_eval import kernel

    monkeypatch.setattr(batch_eval, "EXACT", jax.lax.Precision.DEFAULT)
    monkeypatch.setattr(kernel, "EXACT", jax.lax.Precision.DEFAULT)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("seed", [2**31 + 101, 2**31 + 102, 2**31 + 103])
@pytest.mark.parametrize("workload", list(WINDOW_S))
def test_lower_precision_is_not_correct(lowered, workload, seed):
    line = run(spec.Cell(workload), seed, WINDOW_S[workload], 0)
    print("control", json.dumps({"workload": workload, "seed": seed,
                                 "attempted": line["attempted"],
                                 "checks": line["checks"]}))
    assert not line["correct"], line["checks"]
