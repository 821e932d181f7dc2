"""The documented entry points run: the examples README.md and
docs/index.md point users at, and the paper-reproduction scripts whose
``checks`` carry the paper's claims."""
from __future__ import annotations

import importlib
import os
import subprocess
import sys

import pytest

import benchmarks.common

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("example",
                         ["quickstart", "dse_explore", "multinet_deploy"])
def test_example_runs(example):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH")) if p)
    out = subprocess.run(
        [sys.executable, os.path.join("examples", f"{example}.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]


@pytest.mark.parametrize("script", ["tab1_arch_comparison", "tab4_accuracy",
                                    "fig5_fig8_fronts", "fig6_fig7_breakdown"])
def test_paper_reproduction_checks_hold(script, tmp_path, monkeypatch):
    monkeypatch.setattr(benchmarks.common, "ART", str(tmp_path))
    out = importlib.import_module(f"benchmarks.{script}").run(verbose=False)
    checks = out["checks"]
    assert checks
    assert all(checks.values()), {k: v for k, v in checks.items() if not v}
