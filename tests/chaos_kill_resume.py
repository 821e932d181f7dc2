"""SIGKILL-and-resume smoke: the checkpoint contract across real crashes.

The in-process chaos tests simulate crashes with a ``BaseException``; this
driver does it for real — a worker process runs the guided search with
checkpointing, ``SIGKILL``s itself right after its 2nd snapshot lands on
disk, and a fresh process resumes from the file.  The resumed front must
be bit-identical to an uninterrupted run, on BOTH search loops:

* ``serial``  — the single-population loop;
* ``island``  — the island model under ``REPRO_MESH_DEVICES=4`` (the env
  var is read before first jax use, so it only exists across a process
  boundary — the reason this file is a subprocess driver, not a test
  function).

Usage:
    python tests/chaos_kill_resume.py                 # driver: both modes
    python tests/chaos_kill_resume.py serial|island   # driver: one mode
    python tests/chaos_kill_resume.py worker <mode> <ckpt|-> <out.npz>

Workers honour ``REPRO_CHAOS_KILL_AFTER=N`` (die after the N-th snapshot)
and share one ``JAX_COMPILATION_CACHE_DIR`` so the three runs per mode compile
once.  Run by the ``chaos-smoke`` CI job and
``tests/test_chaos.py::test_sigkill_and_resume_subprocess``.
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")

#: per-mode search sizing: >= 5 generations each, so interval-2
#: checkpointing writes twice (gens 2 and 4) before the kill
SIZING = {
    "serial": dict(pop_size=32, budget=192, seed=3),
    "island": dict(pop_size=16, budget=320, seed=5,
                   migration_interval=2, migration_elites=4),
}
KILL_AFTER = 2
ISLAND_DEVICES = 4


# --------------------------------------------------------------------------
# worker: one search run (fresh or resumed), results to an .npz
# --------------------------------------------------------------------------
def worker(mode: str, ckpt: str, out_path: str) -> None:
    import numpy as np

    # import order is the point: shard first (reads REPRO_MESH_DEVICES and
    # force-splits the host platform), jax after — same bootstrap as
    # tests/shard_worker.py
    from repro.core import shard  # noqa: F401
    from repro.core import resilience
    from repro.core.dse.search import SearchConfig, search
    from repro.cnn.registry import get_cnn
    from repro.fpga.boards import get_board

    kill_after = int(os.environ.get("REPRO_CHAOS_KILL_AFTER", "0"))
    if kill_after:
        orig = resilience.save_checkpoint
        state = {"n": 0}

        def writer(path, kind, snap, meta=None):
            orig(path, kind, snap, meta=meta)
            state["n"] += 1
            if state["n"] >= kill_after:
                os.kill(os.getpid(), signal.SIGKILL)   # no cleanup, no exit
        resilience.save_checkpoint = writer

    mesh = None
    if mode == "island":
        from repro.core.shard import EvalMesh
        mesh = EvalMesh()
        assert mesh.is_sharded and mesh.ndevices == ISLAND_DEVICES, \
            f"mesh bootstrap failed: {mesh.ndevices} devices"
    cfg = SearchConfig(**SIZING[mode],
                       **({} if ckpt == "-" else
                          dict(checkpoint_path=ckpt, checkpoint_interval=2,
                               resume=True)))
    res = search(get_cnn("mobilenetv2"), get_board("zc706"), cfg, mesh=mesh)
    np.savez(out_path, front_idx=res.front_idx, points=res.points,
             latency=res.metrics["latency_s"],
             n_islands=len(res.island_fronts))
    print(f"WORKER_OK {mode} front={len(res.front_idx)}")


# --------------------------------------------------------------------------
# driver: reference run, killed run, resumed run; compare bit-exactly
# --------------------------------------------------------------------------
def _spawn(mode: str, ckpt: str, out: str, *, kill_after: int = 0,
           cache_dir: str) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["REPRO_MCCM_BACKEND"] = "ref"
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["REPRO_MESH_DEVICES"] = \
        str(ISLAND_DEVICES) if mode == "island" else "1"
    env.pop("REPRO_CHAOS_KILL_AFTER", None)
    if kill_after:
        env["REPRO_CHAOS_KILL_AFTER"] = str(kill_after)
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__), "worker", mode, ckpt,
         out], env=env, capture_output=True, text=True, timeout=900)


def drive(mode: str) -> None:
    import numpy as np

    with tempfile.TemporaryDirectory(prefix=f"chaos-{mode}-") as tmp:
        cache = os.path.join(tmp, "jit-cache")
        ckpt = os.path.join(tmp, "search.ckpt")
        ref_npz = os.path.join(tmp, "ref.npz")
        res_npz = os.path.join(tmp, "resumed.npz")

        ref = _spawn(mode, "-", ref_npz, cache_dir=cache)
        assert ref.returncode == 0, \
            f"reference worker failed:\n{ref.stdout}\n{ref.stderr}"

        killed = _spawn(mode, ckpt, os.path.join(tmp, "never.npz"),
                        kill_after=KILL_AFTER, cache_dir=cache)
        assert killed.returncode == -signal.SIGKILL, \
            f"worker survived its own SIGKILL (rc={killed.returncode}):" \
            f"\n{killed.stdout}\n{killed.stderr}"
        assert os.path.exists(ckpt), "no checkpoint survived the kill"

        resumed = _spawn(mode, ckpt, res_npz, cache_dir=cache)
        assert resumed.returncode == 0, \
            f"resume worker failed:\n{resumed.stdout}\n{resumed.stderr}"

        a, b = np.load(ref_npz), np.load(res_npz)
        for key in ("front_idx", "points", "latency", "n_islands"):
            np.testing.assert_array_equal(
                a[key], b[key],
                err_msg=f"{mode}: resumed {key} != uninterrupted")
        print(f"CHAOS_OK {mode} (front {len(a['front_idx'])}, "
              f"islands {int(a['n_islands'])})")


def main(argv: list[str]) -> None:
    if argv and argv[0] == "worker":
        worker(argv[1], argv[2], argv[3])
        return
    for mode in argv or ("serial", "island"):
        if mode not in SIZING:
            raise SystemExit(f"unknown mode {mode!r}; known: serial, island")
        drive(mode)


if __name__ == "__main__":
    main(sys.argv[1:])
