"""core/shard.py: design-axis sharding + the island-model search.

Two layers:

* in-process tests run on whatever this interpreter sees (usually one
  CPU device — conftest never sets XLA_FLAGS): padding math, env
  resolution, the single-device fallback, and the island model, which
  works on any device count (islands fall back to the serial loop).
* subprocess tests spawn tests/shard_worker.py with REPRO_MESH_DEVICES=8
  and deliberately WITHOUT XLA_FLAGS — proving the documented env-var
  path splits the host platform by itself — then assert bit-parity,
  cache stability and sharded-vs-serial island equality on real
  multi-device meshes.  CI's shard-smoke job additionally runs the
  ``needs_devices`` tests in-process under a forced 4-device host.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.batch_eval import padded_rows
from repro.core.shard import (EvalMesh, MESH_ENV, env_mesh_devices,
                              force_host_devices)

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")
WORKER = os.path.join(os.path.dirname(__file__), "shard_worker.py")


def _ndev():
    import jax
    return len(jax.devices())


needs_devices = pytest.mark.skipif(
    _ndev() < 2,
    reason="needs a multi-device backend (CI shard-smoke forces 4)")


# -------------------------------------------------------------------------
# padding math + env resolution (pure host-side, no devices involved)
# -------------------------------------------------------------------------
def test_padded_rows_rounds_to_device_tile_unit():
    assert padded_rows(100, 8) == 104          # single device: tile only
    assert padded_rows(100, 8, 1) == 104
    assert padded_rows(100, 8, 4) == 128       # unit = tile * ndevices
    assert padded_rows(1, 128, 8) == 1024
    assert padded_rows(1024, 128, 8) == 1024   # exact multiples untouched
    assert padded_rows(1025, 128, 8) == 2048


def test_env_mesh_devices(monkeypatch):
    monkeypatch.delenv(MESH_ENV, raising=False)
    assert env_mesh_devices() is None
    monkeypatch.setenv(MESH_ENV, "4")
    assert env_mesh_devices() == 4
    monkeypatch.setenv(MESH_ENV, "0")
    with pytest.raises(ValueError):
        env_mesh_devices()
    monkeypatch.setenv(MESH_ENV, "lots")
    with pytest.raises(ValueError):
        env_mesh_devices()


def test_force_host_devices_is_idempotent(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
    force_host_devices(4)
    first = os.environ["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=4" in first
    force_host_devices(8)                       # flag present: no-op
    assert os.environ["XLA_FLAGS"] == first
    monkeypatch.setenv("XLA_FLAGS", "")
    force_host_devices(1)                       # n < 2: no-op
    assert "device_count" not in os.environ["XLA_FLAGS"]


# -------------------------------------------------------------------------
# single-device fallback: mesh must be a bit-exact no-op
# -------------------------------------------------------------------------
def test_single_device_mesh_is_identity():
    from repro.cnn.registry import get_cnn
    from repro.core.batch_eval import encode_specs, evaluate_batch, \
        make_tables
    from repro.fpga.archs import ARCH_NAMES, make_arch
    from repro.fpga.boards import get_board

    mesh = EvalMesh(ndevices=1)
    assert not mesh.is_sharded
    assert mesh.padded_rows(100, 8) == padded_rows(100, 8)
    net = get_cnn("mobilenetv2")
    specs = [make_arch(a, net, n) for a in ARCH_NAMES for n in (2, 5)]
    batch = encode_specs(specs, len(net))
    tables = make_tables(net)
    dev = get_board("vcu108")
    plain = evaluate_batch(batch, tables, dev, tile=8)
    meshed = evaluate_batch(batch, tables, dev, tile=8, mesh=mesh)
    for k in plain:
        np.testing.assert_array_equal(np.asarray(plain[k]),
                                      np.asarray(meshed[k]))


def test_evalmesh_clamps_to_visible_devices():
    mesh = EvalMesh(ndevices=64)                # asks for more than exist
    assert mesh.requested == 64
    assert mesh.ndevices == _ndev()
    assert len(mesh.devices) == mesh.ndevices


# -------------------------------------------------------------------------
# island model (device-count independent: serial loop on one device)
# -------------------------------------------------------------------------
def _island_cfg(**kw):
    from repro.core.dse.search import SearchConfig
    base = dict(pop_size=64, budget=1300, seed=3, n_islands=4,
                migration_interval=2, migration_elites=4)
    base.update(kw)
    return SearchConfig(**base)


@pytest.fixture(scope="module")
def island_result():
    from repro.cnn.registry import get_cnn
    from repro.core.dse.search import search
    from repro.fpga.boards import get_board
    return search(get_cnn("mobilenetv2"), get_board(), _island_cfg())


def test_island_search_is_deterministic(island_result):
    from repro.cnn.registry import get_cnn
    from repro.core.dse.search import search
    from repro.fpga.boards import get_board
    again = search(get_cnn("mobilenetv2"), get_board(), _island_cfg())
    np.testing.assert_array_equal(island_result.front_idx, again.front_idx)
    np.testing.assert_array_equal(island_result.points, again.points)


def test_island_search_spends_exact_budget(island_result):
    cfg = _island_cfg()
    assert island_result.n_evals == cfg.budget
    assert len(island_result.batch.seg_end) == cfg.budget
    assert len(island_result.island_fronts) == cfg.n_islands


def test_migration_transfers_elites(island_result):
    migrated = [h["migrants"] for h in island_result.history]
    assert sum(migrated) > 0, "no generation exchanged elites"
    assert migrated[-1] == 0                    # final gen never breeds


def test_merged_front_dominates_island_fronts(island_result):
    merged = island_result.points[island_result.front_idx]
    for fi in island_result.island_fronts:
        assert len(fi) > 0
        for p in island_result.points[fi]:
            assert (merged <= p).all(axis=1).any(), \
                f"island point {p} beats the merged front"


def test_seed_changes_island_outcome(island_result):
    from repro.cnn.registry import get_cnn
    from repro.core.dse.search import search
    from repro.fpga.boards import get_board
    other = search(get_cnn("mobilenetv2"), get_board(),
                   _island_cfg(seed=4))
    assert not (other.points.shape == island_result.points.shape
                and np.array_equal(other.points, island_result.points))


# -------------------------------------------------------------------------
# in-process multi-device checks (CI shard-smoke: 4 forced host devices)
# -------------------------------------------------------------------------
@needs_devices
def test_sharded_parity_in_process():
    from repro.cnn.registry import get_cnn
    from repro.core.batch_eval import encode_specs, evaluate_batch, \
        make_tables
    from repro.fpga.archs import ARCH_NAMES, make_arch
    from repro.fpga.boards import get_board

    mesh = EvalMesh()
    assert mesh.is_sharded
    net = get_cnn("resnet50")
    specs = [make_arch(a, net, n) for a in ARCH_NAMES for n in (2, 5, 9)]
    batch = encode_specs(specs, len(net))
    tables = make_tables(net)
    dev = get_board("zc706")
    plain = evaluate_batch(batch, tables, dev, tile=8)
    sharded = evaluate_batch(batch, tables, dev, tile=8, mesh=mesh)
    for k in plain:
        np.testing.assert_array_equal(np.asarray(plain[k]),
                                      np.asarray(sharded[k]))


@needs_devices
def test_sharded_session_reuses_compiles():
    from repro.cnn.registry import get_cnn
    from repro.core.session import EvalConfig, Session
    from repro.fpga.boards import get_board

    ses = Session(get_board(), config=EvalConfig(tile=8))
    assert ses.mesh.is_sharded
    net = get_cnn("mobilenetv2")
    spec = "{L1-L20:CE1, L21-Last:CE2}"
    ses.evaluate([spec] * 100, net)
    warm = ses.compile_stats()
    assert warm["mesh_evaluate_batch"] >= 1
    ses.evaluate([spec] * 97, net)              # same pad bucket
    assert ses.compile_stats() == warm


# -------------------------------------------------------------------------
# subprocess: the documented env-var path, 8 devices, no manual XLA_FLAGS
# -------------------------------------------------------------------------
def _run_worker(job: str) -> str:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env[MESH_ENV] = "8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, WORKER, job], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, \
        f"worker {job} failed:\n{out.stdout}\n{out.stderr}"
    assert f"WORKER_OK {job}" in out.stdout
    return out.stdout


@pytest.mark.slow
def test_worker_parity_all_archs_all_cnns():
    _run_worker("parity")


@pytest.mark.slow
def test_worker_island_sharded_equals_serial():
    _run_worker("islands")


@pytest.mark.slow
def test_worker_session_cache_stability():
    _run_worker("cache")


@pytest.mark.slow
def test_worker_sharded_pallas_interpret_equals_one_device():
    _run_worker("pallas")
