"""Multi-device worker driven by tests/test_shard.py in a subprocess.

The parent sets ``REPRO_MESH_DEVICES`` (NOT ``XLA_FLAGS``) so this also
exercises the supported env-var path: importing ``repro.core.shard``
before first jax use must force-split the host platform by itself.

Usage: python tests/shard_worker.py <job> — jobs: parity | islands | cache |
pallas.
Prints ``WORKER_OK <job>`` on success; any assertion failure exits nonzero.
"""
from __future__ import annotations

import os
import sys

import numpy as np

# import order is the point: shard first (reads REPRO_MESH_DEVICES and
# sets the XLA flag), jax after
from repro.core import shard  # noqa: F401
import jax

from repro.cnn.registry import CNN_NAMES, get_cnn
from repro.core import batch_eval as be
from repro.core.session import EvalConfig, Session
from repro.core.shard import EvalMesh, mesh_compile_counts
from repro.fpga.archs import ARCH_NAMES, make_arch
from repro.fpga.boards import get_board

WANT = int(os.environ["REPRO_MESH_DEVICES"])
TILE = 8   # small tile so the ndevices x tile padding unit stays testable


def _eq(a, b, msg):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and np.array_equal(a, b, equal_nan=True), msg


def job_parity():
    """Sharded vs single-device bit-parity on every baseline arch x CNN."""
    assert len(jax.devices()) == WANT, \
        f"env bootstrap failed: {len(jax.devices())} devices, want {WANT}"
    mesh = EvalMesh()
    assert mesh.is_sharded and mesh.ndevices == WANT
    dev = get_board("vcu108")
    for cnn in CNN_NAMES:
        net = get_cnn(cnn)
        tables = be.make_tables(net)
        specs = [make_arch(a, net, n)
                 for a in ARCH_NAMES for n in (2, 5, 9, 11)]
        batch = be.encode_specs(specs, len(net))
        single = be.evaluate_batch(batch, tables, dev, tile=TILE)
        sharded = be.evaluate_batch(batch, tables, dev, tile=TILE,
                                    mesh=mesh)
        for k in single:
            _eq(single[k], sharded[k], f"{cnn} {k} diverges sharded")
    # one compiled program served all CNNs on each path
    counts = mesh_compile_counts()
    assert counts == {"evaluate_batch": 1}, counts
    assert be._evaluate_jit._cache_size() == 1
    print(f"WORKER_OK parity ({len(CNN_NAMES)} CNNs x {len(ARCH_NAMES)} "
          f"archs, {WANT} devices)")


def job_pallas():
    """The sharded Pallas kernel (interpret mode) equals the one-device
    program bit for bit, called directly and through a Session with no
    fallback — the path shard_map's varying-axes check used to reject."""
    from repro.core.dse.encoding import decode_design
    from repro.core.dse.samplers import sample_mixed

    net = get_cnn("resnet50")
    dev = get_board("zc706")
    mesh = EvalMesh()
    assert mesh.is_sharded and mesh.ndevices == WANT
    db = sample_mixed(np.random.default_rng(0), len(net), 64)
    tables = be.make_tables(net)
    kw = dict(tile=TILE, backend="pallas_interpret")
    single = be.evaluate_batch(db, tables, dev, **kw)
    sharded = be.evaluate_batch(db, tables, dev, mesh=mesh, **kw)
    for k in single:
        _eq(single[k], sharded[k], f"pallas {k} diverges sharded")
    specs = [decode_design(db, i, len(net)) for i in range(db.batch)]
    cfg = dict(tile=TILE, backend="pallas_interpret", fallback_backend=None)
    got = Session(dev, **cfg).evaluate(specs, net)
    want = Session(dev, mesh=1, **cfg).evaluate(specs, net)
    for k in want:
        _eq(got[k], want[k], f"session pallas {k} diverges sharded")
    print(f"WORKER_OK pallas ({WANT} devices, {db.batch} designs)")


def job_islands():
    """Sharded island search: deterministic, equal to the unsharded
    island model, and its merged front dominates every island front."""
    from repro.core.dse.search import SearchConfig, search

    net = get_cnn("mobilenetv2")
    dev = get_board()                      # the default board (vcu110)
    mesh = EvalMesh()
    # pop 32 x 8 islands = 256 evals/gen -> 5 generations on this budget,
    # so interval-2 migration fires twice before the final generation
    cfg = SearchConfig(pop_size=32, budget=1300, seed=3,
                       migration_interval=2, migration_elites=4)
    r1 = search(net, dev, cfg, mesh=mesh)  # islands = mesh devices
    r2 = search(net, dev, cfg, mesh=mesh)
    _eq(r1.front_idx, r2.front_idx, "sharded island search nondeterministic")
    _eq(r1.points, r2.points, "sharded island points nondeterministic")
    assert r1.n_evals == cfg.budget
    assert len(r1.island_fronts) == mesh.ndevices
    assert any(h.get("migrants", 0) > 0 for h in r1.history), \
        "migration never transferred elites"
    merged = r1.points[r1.front_idx]
    for i, fi in enumerate(r1.island_fronts):
        for p in r1.points[fi]:
            assert (merged <= p).all(1).any(), \
                f"island {i} point {p} not covered by the merged front"
    # the sharded step computes exactly what the serial island loop does
    r3 = search(net, dev,
                SearchConfig(**{**cfg.__dict__,
                                "n_islands": mesh.ndevices}))
    _eq(r1.front_idx, r3.front_idx, "sharded != serial island front")
    _eq(r1.points, r3.points, "sharded != serial island points")
    print(f"WORKER_OK islands ({mesh.ndevices} islands, "
          f"front {len(r1.front_idx)})")


def job_cache():
    """B not divisible by the device count never reshards/recompiles."""
    net = get_cnn("mobilenetv2")
    dev = get_board("zc706")
    ses = Session(dev, config=EvalConfig(tile=TILE))
    assert ses.mesh.is_sharded and ses.mesh.ndevices == WANT
    spec = "{L1-L20:CE1, L21-Last:CE2}"
    ses.evaluate([spec] * 100, net)        # 100 % WANT != 0
    warm = ses.compile_stats()
    assert warm[f"mesh_evaluate_batch"] == 1, warm
    for b in (97, 128, 65, 100):           # same pad bucket, awkward tails
        ses.evaluate([spec] * b, net)
    assert ses.compile_stats() == warm, \
        (warm, ses.compile_stats())
    # sharded joint evaluation shares the property
    res = ses.deploy([net, get_cnn("resnet50")], n=48, strategy="search",
                     seed=0)
    assert res.n_evals == 48
    joint_warm = ses.compile_stats()
    ses.deploy([net, get_cnn("resnet50")], n=48, strategy="search", seed=0)
    assert ses.compile_stats() == joint_warm
    print(f"WORKER_OK cache (stats {warm})")


if __name__ == "__main__":
    {"parity": job_parity, "islands": job_islands,
     "cache": job_cache, "pallas": job_pallas}[sys.argv[1]]()
