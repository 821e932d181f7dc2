"""Without a TPU, or with fewer chips than the cell asks for, a run exits
nonzero, prints no result, and says what JAX found on its last line."""
import os
import subprocess
import sys

import pytest

from chipbench import device, spec


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload",
         "xception-vcu110.sweep", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=spec.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    last = p.stderr.strip().splitlines()[-1]
    assert "platform=cpu" in last and "kind=cpu" in last \
        and "count=" in last


class _Dev:
    def __init__(self, platform, kind="TPU v5 lite"):
        self.platform, self.device_kind = platform, kind


def test_fewer_chips_than_asked(capsys):
    with pytest.raises(SystemExit) as e:
        device.guard([_Dev("tpu")], 4)
    assert e.value.code != 0
    last = capsys.readouterr().err.strip().splitlines()[-1]
    assert "platform=tpu" in last and "kind=TPU v5 lite" in last \
        and "count=1" in last


def test_enough_chips():
    info = device.guard([_Dev("tpu")] * 4, 4)
    assert info == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_only_the_checked_in_files_is_not_enough(tmp_path):
    """A checkout that holds only BENCHMARK.json and chipbench/ has no
    system to run: nonzero, no result."""
    import shutil
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "xception-vcu110.sweep", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout
