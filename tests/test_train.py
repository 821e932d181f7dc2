"""Training substrate: convergence, checkpoint fault tolerance, elastic
resharding, gradient compression."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.data.pipeline import synth_batch
from repro.models.registry import get_model
from repro.train import checkpoint as ckpt
from repro.train.optimizer import make_optimizer
from repro.train.train_step import (TrainState, init_residuals,
                                    make_compressed_train_step,
                                    make_train_step)

SHAPE = ShapeSpec("t", "train", 32, 8)


def _setup(arch="llama3.2-1b", rt=None, **opt_kw):
    cfg = get_config(arch).reduced()
    api = get_model(cfg)
    opt = make_optimizer("adamw", peak_lr=3e-3, warmup=5, total_steps=200,
                         **opt_kw)
    params = api.init(jax.random.key(0))
    state = TrainState(params=params, opt=opt.init(params),
                       step=jnp.zeros((), jnp.int32))
    return cfg, api, opt, state


def _run(step_fn, state, cfg, n, start=0):
    losses = []
    for i in range(start, start + n):
        batch = jax.tree.map(jnp.asarray, synth_batch(cfg, SHAPE, i))
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    return state, losses


def test_loss_decreases(local_rt):
    cfg, api, opt, state = _setup(rt=local_rt)
    step = jax.jit(make_train_step(api, local_rt, opt), donate_argnums=(0,))
    state, losses = _run(step, state, cfg, 30)
    assert losses[-1] < losses[0] * 0.9
    assert int(state.step) == 30


def test_accum_matches_bigbatch(local_rt):
    """2 microbatches of B/2 == one batch of B (same grads modulo fp)."""
    cfg, api, opt, state = _setup(rt=local_rt)
    s1 = jax.jit(make_train_step(api, local_rt, opt))
    s2 = jax.jit(make_train_step(api, local_rt, opt, accum=2))
    batch = jax.tree.map(jnp.asarray, synth_batch(cfg, SHAPE, 0))
    st1, m1 = s1(state, batch)
    st2, m2 = s2(state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-2)
    l1 = jax.tree.leaves(st1.params)[3]
    l2 = jax.tree.leaves(st2.params)[3]
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32), atol=2e-2)


def test_factored_no_momentum_state_is_smaller():
    _, api, opt_full, state_full = _setup()
    _, _, opt_fac, _ = _setup(factored=True, momentum=False,
                              state_dtype="bfloat16")
    params = state_full.params
    full = sum(l.size * l.dtype.itemsize
               for l in jax.tree.leaves(opt_full.init(params)))
    fac = sum(l.size * l.dtype.itemsize
              for l in jax.tree.leaves(opt_fac.init(params)))
    assert fac < full * 0.30   # momentum dropped + v factored + bf16


def test_checkpoint_crash_recovery(tmp_path, local_rt):
    cfg, api, opt, state = _setup(rt=local_rt)
    step = jax.jit(make_train_step(api, local_rt, opt))
    state, _ = _run(step, state, cfg, 10)
    ckpt.save(str(tmp_path), 10, state)
    state, _ = _run(step, state, cfg, 3, start=10)   # "lost" work
    # partial (uncommitted) write must be ignored
    os.makedirs(tmp_path / "step_00000013", exist_ok=True)
    (tmp_path / "step_00000013" / "arrays.npz").write_bytes(b"garbage")
    assert ckpt.latest_step(str(tmp_path)) == 10
    restored = ckpt.restore(str(tmp_path), jax.eval_shape(lambda: state))
    assert int(restored.step) == 10
    # bit-exact params restore (bf16 stored as raw bits)
    def eq(a, b):
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                      np.asarray(b).view(np.uint8))
    state10, _ = _run(step, restored, cfg, 0)


def test_checkpoint_elastic_reshard(tmp_path, local_rt, host_mesh):
    """Restore under a different sharding (elastic re-mesh)."""
    cfg, api, opt, state = _setup(rt=local_rt)
    ckpt.save(str(tmp_path), 1, state)
    sharding = jax.tree.map(
        lambda _: NamedSharding(host_mesh, P()), state)
    restored = ckpt.restore(str(tmp_path), jax.eval_shape(lambda: state),
                            shardings=sharding)
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_compressed_ddp_close_to_fp32(host_mesh):
    """int8 error-feedback DDP tracks the fp32 loss curve."""
    from repro.models.runtime import Runtime
    rt = Runtime(mesh=host_mesh, dp_axes=("data",))
    cfg, api, opt, state0 = _setup(rt=rt)

    plain = jax.jit(make_train_step(api, rt, opt))
    comp_raw = make_compressed_train_step(api, rt, opt, axis="data",
                                          n_shards=host_mesh.shape["data"])
    comp = jax.jit(jax.shard_map(
        comp_raw, mesh=host_mesh,
        in_specs=(P(), P(), P("data")),
        out_specs=(P(), P(), P()), check_vma=False))

    state_a = state0
    state_b = state0
    res = init_residuals(state0.params)
    la = lb = None
    for i in range(12):
        batch = jax.tree.map(jnp.asarray, synth_batch(cfg, SHAPE, i))
        state_a, ma = plain(state_a, batch)
        state_b, res, mb = comp(state_b, res, batch)
        la, lb = float(ma["loss"]), float(mb["loss"])
    assert lb < 6.0 and abs(la - lb) < 0.35


def test_compressed_wire_bytes_4x_smaller(host_mesh):
    """The compressed step's collective operand bytes are ~4x smaller than
    fp32 ring all-reduce of the same gradients (HLO-level check)."""
    from repro.models.runtime import Runtime
    from repro.tpu.hlo_walk import walk
    rt = Runtime(mesh=host_mesh, dp_axes=("data",))
    cfg, api, opt, state = _setup(rt=rt)
    comp_raw = make_compressed_train_step(api, rt, opt, axis="data",
                                          n_shards=host_mesh.shape["data"])
    comp = jax.jit(jax.shard_map(
        comp_raw, mesh=host_mesh,
        in_specs=(P(), P(), P("data")),
        out_specs=(P(), P(), P()), check_vma=False))
    res = init_residuals(state.params)
    batch = jax.tree.map(jnp.asarray, synth_batch(cfg, SHAPE, 0))
    txt = comp.lower(state, res, batch).compile().as_text()
    n_params = sum(l.size for l in jax.tree.leaves(state.params))
    costs = walk(txt)
    a2a = costs.coll_operand.get("all-to-all", 0.0)
    ag = costs.coll_operand.get("all-gather", 0.0)
    # int8 wire payload ≈ 2 B/param total vs 4 B/param fp32 operand
    assert 0 < a2a + ag < n_params * 3.0
