"""Vectorized MCCM vs the scalar reference — the central exactness claim."""
from __future__ import annotations

import numpy as np
import pytest

from repro.cnn.registry import CNN_NAMES, get_cnn
from repro.core.batch_eval import (SCALAR_PARITY_RTOL, encode_specs,
                                   evaluate_specs, make_tables)
from repro.core.dse import decode_design, explore, pareto, sample_mixed
from repro.core.evaluator import evaluate_design
from repro.fpga.archs import ARCH_NAMES, make_arch
from repro.fpga.boards import BOARD_NAMES, get_board

METRICS = tuple(SCALAR_PARITY_RTOL)
RTOL = SCALAR_PARITY_RTOL


def _scalar_vals(m):
    return {"latency_s": m.latency_s, "throughput_ips": m.throughput_ips,
            "buffer_bytes": float(m.buffer_bytes),
            "access_bytes": m.access_bytes}


@pytest.mark.parametrize("board", BOARD_NAMES)
@pytest.mark.parametrize("cnn", CNN_NAMES)
def test_matches_scalar_on_templates(cnn, board):
    net = get_cnn(cnn)
    dev = get_board(board)
    specs = [make_arch(a, net, n) for a in ARCH_NAMES for n in (2, 5, 9, 11)]
    scalar = [evaluate_design(s, net, dev) for s in specs]
    batch = evaluate_specs(specs, net, dev)
    for i, s in enumerate(scalar):
        sv = _scalar_vals(s)
        for k in METRICS:
            np.testing.assert_allclose(
                float(batch[k][i]), sv[k], rtol=RTOL[k],
                err_msg=f"{cnn} {specs[i].name} {k}")


# ROADMAP D12: on zcu102 the batch path's access_bytes for design 14 is
# 17.7% below evaluate_design's
_D12 = pytest.mark.xfail(strict=True, reason="ROADMAP D12: batch access_bytes "
                         "differs from evaluate_design on zcu102")


@pytest.mark.parametrize("board", [
    pytest.param(b, marks=_D12) if b == "zcu102" else b for b in BOARD_NAMES])
def test_matches_scalar_on_random_mixed_designs(board):
    net = get_cnn("resnet50")
    dev = get_board(board)
    rng = np.random.default_rng(7)
    db = sample_mixed(rng, len(net), 24)
    batch = {k: np.asarray(v) for k, v in
             evaluate_specs([decode_design(db, i, len(net))
                             for i in range(24)], net, dev).items()}
    for i in range(24):
        spec = decode_design(db, i, len(net))
        m = evaluate_design(spec, net, dev,
                            inter_segment_pipelining=bool(db.inter_pipe[i]))
        sv = _scalar_vals(m)
        for k in METRICS:
            np.testing.assert_allclose(
                float(batch[k][i]), sv[k], rtol=RTOL[k],
                err_msg=f"design {i} {k}")


def test_pareto_front_is_nondominated():
    pts = np.array([[1, 5], [2, 4], [3, 3], [2, 2], [5, 1], [4, 4]])
    idx = pareto(pts)
    front = pts[idx]
    for i, p in enumerate(front):
        for q in front:
            assert not (np.all(q <= p) and np.any(q < p))
    # (2,2) dominates (3,3) and (4,4)
    assert [2, 2] in front.tolist()
    assert [3, 3] not in front.tolist()


def test_explore_speed_and_consistency():
    net = get_cnn("resnet50")
    dev = get_board("vcu110")
    res = explore(net, dev, n=2048, family="custom", seed=3)
    assert res.per_design_us < 6300          # beat the paper's 6.3 ms
    m = res.metrics
    assert np.all(m["latency_s"] > 0)
    assert np.all(m["throughput_ips"] * m["latency_s"] >= 0.99)


@pytest.mark.parametrize("board", BOARD_NAMES)
def test_fused_path_backends_bit_identical(board):
    """The Pallas kernel (interpret mode — what TPU runs, on CPU) and the
    pure-jnp ref produce bit-identical metrics through evaluate_batch."""
    from repro.core.batch_eval import evaluate_batch, make_tables

    net = get_cnn("xception")
    dev = get_board(board)
    rng = np.random.default_rng(11)
    db = sample_mixed(rng, len(net), 48)
    tables = make_tables(net)
    ref = evaluate_batch(db, tables, dev, backend="ref")
    pal = evaluate_batch(db, tables, dev, backend="pallas_interpret")
    for k in ref:
        np.testing.assert_array_equal(
            np.asarray(ref[k]), np.asarray(pal[k]), err_msg=k)


@pytest.mark.parametrize("board", BOARD_NAMES)
def test_matches_scalar_through_pallas_interpret(board):
    """Scalar parity holds through the fused kernel path itself."""
    net = get_cnn("mobilenetv2")
    dev = get_board(board)
    specs = [make_arch(a, net, n) for a in ARCH_NAMES for n in (2, 9)]
    batch = evaluate_specs(specs, net, dev, backend="pallas_interpret")
    for i, s in enumerate(specs):
        sv = _scalar_vals(evaluate_design(s, net, dev))
        for k in METRICS:
            np.testing.assert_allclose(
                float(batch[k][i]), sv[k], rtol=RTOL[k],
                err_msg=f"{s.name} {k}")


def test_one_compile_serves_all_cnns_and_boards():
    """The recompile-free claim, asserted: NetTables / DeviceTables are
    traced pytrees padded to shared shapes, so ONE jit compile evaluates
    every registered CNN on every registered board."""
    import jax

    from repro.core import batch_eval
    from repro.core.batch_eval import evaluate_batch, make_tables

    jax.clear_caches()
    assert batch_eval._evaluate_jit._cache_size() == 0
    rng = np.random.default_rng(5)
    for cnn in CNN_NAMES:
        net = get_cnn(cnn)
        tables = make_tables(net)
        db = sample_mixed(rng, len(net), 64)
        for board in BOARD_NAMES:
            out = evaluate_batch(db, tables, get_board(board))
            assert np.isfinite(np.asarray(out["latency_s"])).all()
    assert batch_eval._evaluate_jit._cache_size() == 1


def test_evaluate_specs_multi_matches_single_jobs():
    """The cross-(CNN × board) megabatch returns exactly what per-job
    evaluation returns."""
    from repro.core.batch_eval import evaluate_specs_multi

    jobs = []
    for cnn, board in (("mobilenetv2", "zc706"), ("xception", "vcu110")):
        net = get_cnn(cnn)
        jobs.append(([make_arch(a, net, 4) for a in ARCH_NAMES], net,
                     get_board(board)))
    multi = evaluate_specs_multi(jobs)
    for (specs, net, dev), got in zip(jobs, multi):
        want = evaluate_specs(specs, net, dev)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_evaluate_specs_tail_padding_exact():
    """Chunked evaluation with a ragged tail equals unchunked evaluation
    (padded rows are sliced off, not leaked)."""
    net = get_cnn("mobilenetv2")
    dev = get_board("zc706")
    rng = np.random.default_rng(13)
    db = sample_mixed(rng, len(net), 37)
    specs = [decode_design(db, i, len(net)) for i in range(37)]
    whole = evaluate_specs(specs, net, dev, chunk=2048)
    ragged = evaluate_specs(specs, net, dev, chunk=16)
    for k in whole:
        np.testing.assert_array_equal(whole[k], ragged[k], err_msg=k)
        assert len(ragged[k]) == 37


def _per_segment(dtype, rng, B):
    from repro.core.batch_eval import NS
    if dtype == "int32":
        return rng.integers(-2**31, 2**31 - 1, (B, NS), dtype=np.int32)
    if dtype == "bool":
        return rng.random((B, NS)) < 0.5
    x = (rng.standard_normal((B, NS)) * 1e30).astype(np.float32)
    special = np.array([np.inf, -np.inf, -0.0, 0.0, 3.4e38, -3.4e38,
                        1e-45, np.nan], np.float32)
    x.flat[:special.size * 3] = np.tile(special, 3)
    return x


@pytest.mark.parametrize("shifted", [False, True], ids=["seg", "seg-1"])
@pytest.mark.parametrize("dtype", ["int32", "float32", "bool"])
def test_of_layer_equals_take_along_axis_bitwise(dtype, shifted):
    """The select chain reads each layer's segment entry bit for bit as
    ``take_along_axis`` does, eagerly and under jit, for every dtype the
    evaluator feeds it, at indices 0 and NS - 1 and at the shifted
    ``max(seg - 1, 0)`` of the boundary lookup."""
    import jax
    import jax.numpy as jnp

    from repro.core.batch_eval import NS, _of_layer

    rng = np.random.default_rng(5)
    B, L = 24, 160
    x = _per_segment(dtype, rng, B)
    seg = np.sort(rng.integers(0, NS, (B, L)), axis=1).astype(np.int32)
    seg[0], seg[1] = 0, NS - 1
    seg[2] = np.arange(L) % NS
    idx = np.maximum(seg - 1, 0) if shifted else seg
    assert idx.min() == 0 and idx.max() == (NS - 2 if shifted else NS - 1)

    want = np.asarray(jnp.take_along_axis(jnp.asarray(x), jnp.asarray(idx),
                                          axis=1))
    for fn in (_of_layer, jax.jit(_of_layer)):
        got = np.asarray(fn(jnp.asarray(x), jnp.asarray(idx)))
        assert got.dtype == want.dtype and got.shape == (B, L)
        np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


# ------------------------------------------ per-CE reads: select == one-hot
def _mixed_designs(n_layers, n, seed):
    """``sample_mixed`` rows up to NC CEs, then rows that break the
    canonical form: random CE counts per segment whose sum passes NC, so
    later layers index CEs past the last one."""
    from repro.core.batch_eval import NC
    from repro.core.dse.encoding import DesignBatch

    rng = np.random.default_rng(seed)
    db = sample_mixed(rng, n_layers, n, min_ces=1, max_ces=NC)
    seg_end, seg_pipe, seg_nce, inter = (a.copy() for a in db.to_numpy())
    odd = rng.random(n) < 0.5
    n_seg = (np.diff(np.concatenate([np.zeros((n, 1), seg_end.dtype),
                                     seg_end], 1), axis=1) > 0).sum(1)
    for i in np.flatnonzero(odd):
        seg_nce[i, :n_seg[i]] = rng.integers(1, NC + 1, n_seg[i])
        seg_pipe[i, :n_seg[i]] = seg_nce[i, :n_seg[i]] > 1
    # one pipelined block over every layer: CE l for layer l, up to NC - 1
    # where the net is that deep, and past it for the layers after
    seg_end[0], seg_nce[0], seg_pipe[0] = n_layers, 1, False
    seg_nce[0, 0], seg_pipe[0, 0] = n_layers, True
    return DesignBatch.from_numpy(seg_end, seg_pipe, seg_nce, inter)


def _maps(cnn, board, n, seed):
    from repro.core.batch_eval import _ce_maps, make_device_tables

    net = get_cnn(cnn)
    design = _mixed_designs(len(net), n, seed)
    tables = make_tables(net)
    devt = make_device_tables(get_board(board))
    return design, tables, devt, _ce_maps(design, tables, devt)


def _per_ce_values(kind, rng, shape):
    from repro.core.blocks import CANDIDATES_DEFAULT

    if kind == "parallelism":
        return rng.choice(np.asarray(CANDIDATES_DEFAULT, np.float32), shape)
    x = rng.uniform(2.0**24, 2.0**34, shape).astype(np.float32)
    x[rng.random(shape) < 0.2] = 0.0
    x.flat[:4] = [2.0**24 + 2, 2.0**31, 16_777_217.0, 3.0e9]
    return x


def _one_hot_read(x, ce_oh):
    """The per-layer read as the evaluator made it before the select
    chain: a contraction against the (B, L, NC) one-hot."""
    import jax
    import jax.numpy as jnp

    return jnp.einsum("bc,blc->bl", x, ce_oh,
                      precision=jax.lax.Precision.HIGHEST)


def _assert_of_ce_bitwise(kind):
    import jax
    import jax.numpy as jnp

    from repro.core.batch_eval import NC, _of_ce

    design, tables, _, m = _maps("xception", "vcu110", 48, 11)
    live = np.asarray(m.ce_live)
    valid = np.asarray(m.valid_b)
    ce = np.asarray(m.ce_of_layer)
    assert (ce[live] == 0).any() and (ce[live] == NC - 1).any()
    assert (valid & ~live).any(), "no row indexes a CE past NC"
    assert (~valid).any(), "no padded layers"
    np.testing.assert_array_equal(live, np.asarray(m.ce_oh).any(-1))

    x = jnp.asarray(_per_ce_values(kind, np.random.default_rng(3),
                                   (design.batch, NC)))
    want = np.asarray(_one_hot_read(x, m.ce_oh))
    want_jit = np.asarray(jax.jit(_one_hot_read)(x, m.ce_oh))
    np.testing.assert_array_equal(want_jit.view(np.uint8),
                                  want.view(np.uint8))
    for fn in (_of_ce, jax.jit(_of_ce)):
        got = np.asarray(fn(x, m))
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


def _layer_state_pair(cnn, board, backend):
    """The program's LayerState and the old one-hot formulas for the
    fields the per-CE reads feed, from the same maps and ⟨pf, ph, pw⟩."""
    import jax
    import jax.numpy as jnp

    from repro.core.batch_eval import (_ce_maps, _of_layer,
                                       _pair_layer_tables, _seg_sum,
                                       layer_state, pes_hint)
    from repro.kernels.mccm_eval import pair_tables, parallelism_search

    design, tables, devt, _ = _maps(cnn, board, 64, 17)
    pairs = pair_tables(tables.candidates, pes_hint(get_board(board).pes))
    fc_pair, coh_pair = _pair_layer_tables(tables, pairs)

    @jax.jit
    def both(design, t, dev):
        m = _ce_maps(design, t, dev)
        par = parallelism_search(m.pes_ce, m.ce_of_layer, m.ce_oh, fc_pair,
                                 coh_pair, t.CEIL_OW, t.OW[:, None], pairs,
                                 backend=backend)[:3]
        st = layer_state(design, t, dev, m, par, 2)

        read = lambda x: jnp.where(m.valid_b, _one_hot_read(x, m.ce_oh),
                                   1.0)
        pf_l, ph_l, pw_l = (read(x) for x in par)
        ckk = t.CKK[None]
        comp = (jnp.ceil(t.F[None] / pf_l) * ckk
                * jnp.ceil(t.OH[None] / ph_l) * jnp.ceil(t.OW[None] / pw_l))
        util = t.MACS[None] / jnp.maximum(comp * (pf_l * ph_l * pw_l), 1.0)
        wtile = jnp.minimum(pf_l, t.F[None]) * ckk * dev.wordbytes
        pipe_l = m.pipe_bool.astype(jnp.float32)
        ce_desire_l = (t.W[None] * dev.wordbytes + st.fm_tile2) * pipe_l
        ce_desire = jnp.einsum("bl,blc->bc", ce_desire_l, m.ce_oh,
                               precision=jax.lax.Precision.HIGHEST)
        segdes = _of_layer(jnp.maximum(_seg_sum(ce_desire_l, m.onehot), 1.0),
                           m.seg_of_layer)
        ce_buf_l = jnp.floor(_of_layer(st.alloc, m.seg_of_layer)
                             * _one_hot_read(ce_desire, m.ce_oh) / segdes)
        old = dict(comp=comp, util=util, wtile=wtile, ce_buf_l=ce_buf_l)
        return {k: getattr(st, k) for k in old}, old, par

    return both(design, tables, devt)


def _assert_layer_state_bitwise(cnn, board, backend):
    new, old, par = _layer_state_pair(cnn, board, backend)
    for x in par:
        assert np.isfinite(np.asarray(x)).all()
    for k in old:
        got, want = np.asarray(new[k]), np.asarray(old[k])
        np.testing.assert_array_equal(got.view(np.uint8),
                                      want.view(np.uint8),
                                      err_msg=f"{cnn} {board} {k}")


@pytest.mark.parametrize("kind", ["parallelism", "bytes"])
def test_of_ce_equals_one_hot_contraction_bitwise(kind):
    """Each layer's per-CE value read by the select chain and the live
    mask equals the HIGHEST one-hot contraction it replaced bit for bit,
    eagerly and under jit: at CE 0 and NC - 1, at CE indices past NC
    (non-canonical rows) and on padded layers, for parallelism factors and
    for byte counts above 2^24."""
    _assert_of_ce_bitwise(kind)


@pytest.mark.parametrize("board", ["vcu110", "zc706"])
@pytest.mark.parametrize("cnn", ["vgg16", "resnet152"])
def test_layer_state_per_ce_reads_bitwise(cnn, board):
    """``comp``, ``util``, ``wtile`` and ``ce_buf_l`` of the LayerState
    equal what the one-hot contractions gave, bit for bit, on random mixed
    designs with non-canonical rows, on a 13- and a 155-layer CNN."""
    _assert_layer_state_bitwise(cnn, board, "ref")


def test_of_ce_equals_one_hot_contraction_on_tpu():
    """On a TPU, where HIGHEST is emulated, the select read still equals
    the one-hot contraction bit for bit: the synthetic reads, and the
    LayerState fields behind the compiled Pallas kernel.  Run it there
    with ``python -m pytest tests/test_batch_eval.py -k on_tpu``."""
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("needs a TPU: the identity at stake is the chip's")
    for kind in ("parallelism", "bytes"):
        _assert_of_ce_bitwise(kind)
    for cnn in ("xception", "vgg16", "resnet152"):
        _assert_layer_state_bitwise(cnn, "vcu110", "pallas")
