"""A whole run on the CPU, the look for a chip skipped, small sizes: sound
it comes out correct; with the timed path broken underneath it comes out
not correct.  The fault each cell can have: an answer altered where it
is produced."""
import jax
import pytest

from chipbench import drivers, spec
from chipbench.run import run

SMALL = {
    "xception-vcu110.sweep": dict(batch=256, batches=2, check_rows=48),
}


@pytest.fixture
def cell(request, monkeypatch):
    monkeypatch.setattr(drivers, "BACKEND", "ref")
    c = spec.Cell(request.param)
    c.traffic.update(SMALL[request.param])
    return c


def _alter_latency(monkeypatch, module, name, rel=1e-2):
    """Wrap ``module.name`` so that every latency it produces is off by
    ``rel``."""
    inner = getattr(module, name)

    def altered(*a, **k):
        out = inner(*a, **k)
        return dict(out, latency_s=out["latency_s"] * (1 + rel))

    monkeypatch.setattr(module, name, altered)
    jax.clear_caches()


@pytest.mark.parametrize("cell", list(SMALL), indirect=True)
def test_sound_run_is_correct(cell):
    line = run(cell, 2**31 + 11, 1.0, 0, need_chip=False)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("cell", ["xception-vcu110.sweep"], indirect=True)
def test_sweep_answer_altered(cell, monkeypatch):
    from repro.core import session
    _alter_latency(monkeypatch, session, "evaluate_batch")
    line = run(cell, 5, 1.0, 0, need_chip=False)
    assert not line["correct"]
    assert line["checks"]["latency_worst"]["value"] > 5e-4

