"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler is installed alongside jax, so every program of the
evaluation path can be compiled at its real shapes for a chip that is
described rather than attached: what Mosaic or XLA would refuse on the
chip (tiling, VMEM, partitioning, a sharded kernel) fails here for free.
Nothing runs, so these tests say nothing about results or times.

The topology is described only inside a module-scoped fixture: the TPU
library may be loaded by one process at a time, and describing it while
this module is imported would make every test worker try.  JAX's
persistent compilation cache is off around these tests — an executable
compiled for a described chip can be written to it but never read back.
"""
from __future__ import annotations

import os
import re
from functools import partial

import numpy as np
import pytest

#: v5e: 16 GB of HBM per chip (Google Cloud, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9
#: the shapes the chip runs: one lax.map tile, the zoo's layer bucket, the
#: CE cap, the pruned pair list of every registered board and the
#: candidate grid, at the session's design_tile
TILE, MAX_L, NC, K, DESIGN_TILE = 128, 160, 16, 18, 16
PES_HINT = 2520
EVAL_B = 4096
SWEEP_B = 65_536
POP = 4096
JOINT_B = 512


@pytest.fixture(scope="module")
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    """Every array leaf of ``tree`` as a ShapeDtypeStruct on ``sharding``."""
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


def _design_shapes(B, sharding):
    from repro.core.dse.encoding import NS, DesignBatch
    import jax
    import jax.numpy as jnp

    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return DesignBatch(s((B, NS), jnp.int32), s((B, NS), jnp.bool_),
                       s((B, NS), jnp.int32), s((B,), jnp.bool_))


def _tables(sharding):
    from repro.cnn.registry import get_cnn
    from repro.core.batch_eval import make_device_tables, make_tables
    from repro.fpga.boards import get_board

    return (_shapes(make_tables(get_cnn("xception")), sharding),
            _shapes(make_device_tables(get_board("vcu110")), sharding))


def _check(compiled, *, kernel=True):
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used


def test_parallelism_search_kernel_compiles(one_chip):
    import jax
    import jax.numpy as jnp

    from repro.core.blocks import CANDIDATES_DEFAULT
    from repro.kernels.mccm_eval import pair_tables
    from repro.kernels.mccm_eval.kernel import parallelism_search_call

    P = len(pair_tables(tuple(CANDIDATES_DEFAULT), PES_HINT).pair_prod)
    assert K == len(CANDIDATES_DEFAULT)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    fn = jax.jit(partial(parallelism_search_call, design_tile=DESIGN_TILE,
                         interpret=False))
    compiled = fn.lower(f32(TILE, NC), f32(TILE, MAX_L, NC), f32(MAX_L, P),
                        f32(MAX_L, P), f32(MAX_L, 1), f32(K), f32(P), f32(P),
                        f32(P)).compile()
    _check(compiled)


def test_evaluate_jit_pallas_compiles(one_chip):
    from repro.core.batch_eval import _evaluate_jit

    tables, devt = _tables(one_chip)
    compiled = _evaluate_jit.lower(
        _design_shapes(EVAL_B, one_chip), tables, devt, backend="pallas",
        tile=TILE, fm_tile_rows=2, pes_hint_static=PES_HINT,
        design_tile=DESIGN_TILE).compile()
    _check(compiled)
    # the kernel carries its own name, and the evaluator's stages theirs,
    # into the program the chip's profiler reports
    text = compiled.as_text()
    assert "mccm_parallelism_search" in text
    for scope in ("ce_maps", "parallelism_search", "layer_state",
                  "compose_metrics"):
        assert f"/{scope}/" in text, scope
    # each layer reads its segment's entries through a select chain: a
    # (tile, max_L) point gather costs a v5e ~170 us per tile apiece
    layer_gathers = re.findall(
        rf"\w+\[{TILE},{MAX_L}\]\S*\s+gather\(", text)
    assert not layer_gathers, layer_gathers
    # and its CE's ⟨pf, ph, pw⟩ and buffer desire through another: a v5e
    # runs the one-hot contraction as a multiply and a 16-lane reduce over
    # a (tile, max_L, NC) block, ~37 us per tile for the four of them
    assert "bc,blc->bl" not in text


@pytest.mark.parametrize("B", [EVAL_B, SWEEP_B])
def test_batch_check_compiles(one_chip, B):
    """The device check ``Session.evaluate`` runs before dispatch, at the
    evaluator's compile shape and at the sweep's batch."""
    import jax
    import jax.numpy as jnp

    from repro.core.dse.encoding import count_invalid_jit

    n_layers = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = count_invalid_jit.lower(_design_shapes(B, one_chip), n_layers,
                                       min_ces=1, max_ces=NC).compile()
    _check(compiled, kernel=False)
    out, = jax.tree.leaves(compiled.out_info)
    assert (out.shape, out.dtype) == ((2,), jnp.int32)


def test_sharded_evaluator_compiles_on_four_chips(topo):
    """The sharded Pallas evaluator over a 4-chip mesh — what failed the
    shard_map varying-axes check before ``EvalMesh.shard_jit`` turned it
    off."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.batch_eval import evaluate_batch_traced
    from repro.core.shard import MESH_AXIS, EvalMesh

    mesh = EvalMesh(devices=topo.devices)
    assert mesh.ndevices == 4
    run = mesh.shard_jit(
        "evaluate_batch", evaluate_batch_traced, replicated=(1, 2),
        static_kwargs=dict(backend="pallas", tile=TILE, fm_tile_rows=2,
                           pes_hint_static=PES_HINT,
                           design_tile=DESIGN_TILE))
    rows = NamedSharding(mesh.mesh, P(MESH_AXIS))
    tables, devt = _tables(NamedSharding(mesh.mesh, P()))
    compiled = run.lower(_design_shapes(4 * EVAL_B, rows), tables,
                         devt).compile()
    _check(compiled)


def test_search_step_with_donation_compiles(one_chip):
    import jax
    import jax.numpy as jnp

    from repro.core.dse.search import _jitted_step

    d = _design_shapes(POP, one_chip)
    tables, devt = _tables(one_chip)
    vec = lambda n: jax.ShapeDtypeStruct((n,), jnp.float32,
                                         sharding=one_chip)
    lowered = _jitted_step(donate=True).lower(
        d.seg_end, d.seg_pipe, d.seg_nce, d.inter_pipe, tables, devt,
        vec(2), vec(2), vec(2), objectives=("latency_s", "buffer_bytes"),
        min_ces=2, max_ces=11, backend="pallas", tile=TILE, hint=PES_HINT)
    # the donated population buffers alias the repaired designs out
    assert "tf.aliasing_output" in lowered.as_text()
    _check(lowered.compile())


def test_joint_spatial_compiles(one_chip):
    import jax.numpy as jnp

    from repro.cnn.registry import get_cnn
    from repro.core.batch_eval import make_device_tables
    from repro.core.dse.encoding import stack_designs
    from repro.core.dse.samplers import sample_mixed
    from repro.core.multinet.joint_eval import (DEFAULT_FLOORS, JOINT_TILE,
                                                _joint_spatial_jit,
                                                make_multi_tables)
    from repro.fpga.boards import get_board

    nets = [get_cnn("resnet50"), get_cnn("mobilenetv2")]
    mt = make_multi_tables(nets)
    rng = np.random.default_rng(0)
    md = stack_designs([sample_mixed(rng, len(n), JOINT_B, min_ces=1)
                        for n in nets], mt.max_m)
    share = np.ones((JOINT_B, mt.max_m), np.float32)
    compiled = _joint_spatial_jit.lower(
        _shapes(md, one_chip), _shapes(mt, one_chip),
        _shapes(make_device_tables(get_board("zc706")), one_chip),
        *(_shapes(jnp.asarray(share), one_chip) for _ in range(3)),
        backend="pallas", tile=JOINT_TILE, fm_tile_rows=2,
        pes_hint_static=PES_HINT, design_tile=DESIGN_TILE,
        floors=tuple(DEFAULT_FLOORS)).compile()
    _check(compiled)
