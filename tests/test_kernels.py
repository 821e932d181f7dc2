"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv_ce.ops import conv_ce, grid_size, predicted_cycles
from repro.kernels.conv_ce.ref import conv_ref
from repro.kernels.mccm_eval.ops import mccm_latency
from repro.kernels.mccm_eval.ref import mccm_latency_ref


# ------------------------------------------------------------- conv_ce
@pytest.mark.parametrize("C,H,W,F,K,stride,par", [
    (3, 16, 16, 8, 3, 1, (4, 4, 4)),
    (4, 15, 15, 6, 3, 2, (4, 3, 5)),
    (1, 12, 12, 5, 1, 1, (2, 4, 4)),
    (8, 10, 10, 16, 5, 1, (16, 2, 3)),
    (2, 9, 9, 3, 3, 1, (2, 2, 2)),      # ragged everything
])
def test_conv_ce_vs_ref(C, H, W, F, K, stride, par):
    x = jax.random.normal(jax.random.key(0), (C, H, W), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (F, C, K, K), jnp.float32)
    out = conv_ce(x, w, stride=stride, par_f=par[0], par_oh=par[1],
                  par_ow=par[2])
    ref = conv_ref(x, w, stride=stride)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_conv_ce_grid_is_eq1():
    """The kernel's grid × inner trip count IS Eq. 1 — same ceil-divs as
    blocks.layer_cycles."""
    from repro.core.blocks import CE, layer_cycles
    from repro.core.workload import ConvLayer
    F, C, K, OH, OW = 6, 4, 3, 7, 7
    par = (4, 2, 2)
    cyc = predicted_cycles(F, C, K, K, OH, OW, *par)
    l = ConvLayer(index=0, name="l", kind="conv", in_ch=C, out_ch=F,
                  kh=K, kw=K, stride=1, ih=OH, iw=OW, padding="same")
    ce = CE("ce", pes=int(np.prod(par)),
            par={"f": par[0], "oh": par[1], "ow": par[2]})
    assert cyc == layer_cycles(l, ce)
    assert grid_size(F, OH, OW, *par) == \
        -(-F // par[0]) * -(-OH // par[1]) * -(-OW // par[2])


# ------------------------------------------------------------- mccm_eval
@pytest.mark.parametrize("B,L,blk", [(7, 53, 8), (64, 155, 64), (130, 74, 32)])
def test_mccm_latency_vs_ref(B, L, blk):
    rng = np.random.default_rng(0)
    dims = jnp.asarray(rng.integers(1, 512, (L, 4)), jnp.float32)
    par = jnp.asarray(rng.choice([1, 2, 4, 8, 16, 32], (B, L, 3)),
                      jnp.float32)
    tot, cyc = mccm_latency(dims, par, design_blk=blk)
    rtot, rcyc = mccm_latency_ref(dims, par)
    np.testing.assert_allclose(np.asarray(tot), np.asarray(rtot), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(cyc), np.asarray(rcyc), rtol=1e-6)


# ---------------------------------------------- fused parallelism search
def _search_inputs(cnn, board="vcu110"):
    """Baseline arch templates -> raw inputs of the fused search."""
    from repro.cnn.registry import get_cnn
    from repro.core.batch_eval import (_ce_maps, _pair_layer_tables,
                                       encode_specs, make_device_tables,
                                       make_tables, pes_hint)
    from repro.fpga.archs import ARCH_NAMES, make_arch
    from repro.fpga.boards import get_board
    from repro.kernels.mccm_eval import pair_tables

    net, dev = get_cnn(cnn), get_board(board)
    specs = [make_arch(a, net, n) for a in ARCH_NAMES for n in (2, 5, 9, 11)]
    tables = make_tables(net)
    maps = _ce_maps(encode_specs(specs, len(net)), tables,
                    make_device_tables(dev))
    pairs = pair_tables(tables.candidates, pes_hint(dev.pes))
    fc_pair, coh_pair = _pair_layer_tables(tables, pairs)
    return net, dev, specs, tables, maps, pairs, fc_pair, coh_pair


@pytest.mark.parametrize("cnn", ["resnet50", "xception", "mobilenetv2",
                                 "densenet121", "resnet152"])
def test_parallelism_search_kernel_vs_ref_vs_scalar(cnn):
    """Pallas kernel (interpret) == pure-jnp ref bit for bit, and both
    reproduce the scalar Builder's per-CE ⟨pf, ph, pw⟩ choice exactly, on
    every baseline arch template."""
    from repro.core.evaluator import build_design
    from repro.kernels.mccm_eval import parallelism_search

    net, dev, specs, tables, maps, pairs, fc, coh = _search_inputs(cnn)
    args = (maps.pes_ce, maps.ce_of_layer, maps.ce_oh, fc, coh,
            tables.CEIL_OW, tables.OW[:, None], pairs)
    ref = parallelism_search(*args, backend="ref")
    ker = parallelism_search(*args, backend="pallas_interpret",
                             design_tile=8)
    for name, r, k in zip(("pf", "ph", "pw", "cost"), ref, ker):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(k),
                                      err_msg=f"{cnn} {name}")

    pf, ph, pw, _ = (np.asarray(x) for x in ref)
    for b, spec in enumerate(specs):
        acc = build_design(spec, net, dev)
        ce_id = 0
        for seg, cseg in zip(spec.segments, acc.segments):
            n_layers_seg = seg.layer_hi - seg.layer_lo + 1
            for slot, ce in enumerate(cseg.ces):
                if slot < n_layers_seg:          # live CE (has layers)
                    got = (pf[b, ce_id], ph[b, ce_id], pw[b, ce_id])
                    want = (ce.par_of("f"), ce.par_of("oh"), ce.par_of("ow"))
                    assert got == want, \
                        f"{cnn} {spec.name} CE{ce_id}: {got} != {want}"
                ce_id += 1


def test_parallelism_search_infeasible_ce_degrades_to_unit():
    """A CE with 0 PEs (no layers) selects ⟨1, 1, 1⟩ in both backends."""
    from repro.core.batch_eval import make_tables, pes_hint
    from repro.cnn.registry import get_cnn
    from repro.kernels.mccm_eval import pair_tables, parallelism_search
    from repro.core.batch_eval import _pair_layer_tables

    tables = make_tables(get_cnn("mobilenetv2"))
    pairs = pair_tables(tables.candidates, pes_hint(900))
    fc, coh = _pair_layer_tables(tables, pairs)
    L = tables.max_L
    pes = jnp.zeros((2, 16), jnp.float32)
    cel = jnp.zeros((2, L), jnp.int32)
    ceoh = jnp.zeros((2, L, 16), jnp.float32)
    for backend in ("ref", "pallas_interpret"):
        pf, ph, pw, cost = parallelism_search(
            pes, cel, ceoh, fc, coh, tables.CEIL_OW, tables.OW[:, None],
            pairs, backend=backend)
        assert (np.asarray(pf) == 1).all() and (np.asarray(ph) == 1).all()
        assert (np.asarray(pw) == 1).all()
        assert np.isinf(np.asarray(cost)).all()
