"""Per-design temporal-mapping search — the device path.

For every evaluated design, each (valid) layer gets a ``(B, L, NCAND)``
plane of mapping candidates (loop order x tile fraction x buffering
choice, ``kernels.schedule_score``) scored in the SAME MCCM cost terms
the design search runs on: compute cycles, off-chip weight/feature-map
traffic, bandwidth contention.  An on-device argmin picks the winner per
layer; the chosen per-layer costs are substituted back into the
:class:`~repro.core.batch_eval.LayerState` and re-composed through the
exact Eq. 2–9 reduction — so refined and coarse metrics stay in one
currency, and because candidate 0 carries the coarse (ideal-mapping)
cost verbatim and the composition is monotone in every per-layer field,
**refined latency can never exceed the coarse estimate**.

Compile policy: the plane rides the bucket-ladder ``NetTables`` path
unchanged — candidates are a fixed trailing axis (NCAND) over the same
``(tile, max_L)`` block shapes, so schedule search never forks a
compile: one ``_schedule_jit`` program per ladder shape serves every
CNN x board x design (compile-miss-counter tested,
``tests/test_schedule.py``).
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..core.batch_eval import (DEFAULT_TILE, NEG, DesignBatch, DeviceSpec,
                               DeviceTables, LayerState, NetTables, _bucket,
                               _ce_maps, _pad_rows, _pair_layer_tables,
                               _seg_max, _seg_sum, compose_metrics,
                               layer_state, make_device_tables, pes_hint)
from ..core.dse.encoding import NS, encode_specs
from ..kernels.mccm_eval import pair_tables, parallelism_search
from ..kernels.mccm_eval import resolve_backend as resolve_eval_backend
from ..kernels.mccm_eval.ref import EXACT
from ..kernels.schedule_score import NCAND
from ..kernels.schedule_score.ops import score_plane_dispatch


def plane_inputs(xp, t: NetTables, dev: DeviceTables, st: LayerState,
                 pipe, valid) -> dict:
    """Assemble ``score_plane`` inputs from the per-layer state.

    Namespace-generic like the scorer itself: the device path passes
    ``jnp`` (traced), the reference path passes ``numpy`` with a
    host-materialized ``st`` — same statement sequence either way.
    """
    f32 = xp.float32
    wb = xp.asarray(dev.wordbytes, f32)
    W = xp.asarray(t.W, f32)[None]
    IFM = xp.asarray(t.IFM, f32)[None]
    OFM = xp.asarray(t.OFM, f32)[None]
    BAND = xp.asarray(t.BAND, f32)[None]
    ifml = IFM * wb
    return dict(
        comp=st.comp, wl=W * wb, ifml=ifml, ofml=OFM * wb,
        wtile=st.wtile, fm_tile2=st.fm_tile2,
        ifm_tile=xp.minimum(ifml, BAND * wb),
        buf=st.buf_l, ce_buf=st.ce_buf_l, n_tiles=st.n_tiles_l,
        ofm_res=st.ofm_res, ofm_acc=st.ofm_acc,
        lat_coarse=st.lat_single, acc_coarse=st.acc_single,
        wacc_coarse=st.wacc_single, facc_coarse=st.facc_single,
        busy_coarse=st.busy_pipe, wacc_pipe_coarse=st.w_acc_pipe,
        ideal=st.ideal, ifm_onchip=st.ifm_onchip, resident=st.resident_l,
        pipe=pipe, valid=valid, bpc=dev.bpc)


def _refine_state(st: LayerState, plane: dict, choice, bpc) -> LayerState:
    """Substitute each layer's chosen candidate costs into the state."""
    shape = plane["score"].shape

    def take(a):
        return jnp.take_along_axis(jnp.broadcast_to(a, shape),
                                   choice[..., None], axis=-1)[..., 0]

    acc = take(plane["acc_single"])
    wacc_p = take(plane["w_acc_pipe"])
    return st._replace(
        lat_single=take(plane["lat_single"]), acc_single=acc,
        wacc_single=take(plane["wacc_single"]),
        facc_single=take(plane["facc_single"]),
        mem_cyc_single=acc / bpc,
        busy_pipe=take(plane["busy_pipe"]), w_acc_pipe=wacc_p,
        mem_cyc_pipe=wacc_p / bpc)


def schedule_block(design: DesignBatch, t: NetTables, dev: DeviceTables,
                   pairs, fc_pair, coh_pair, *, backend: str = "ref",
                   design_tile: int = 16,
                   fm_tile_rows: int = 2) -> dict[str, jnp.ndarray]:
    """Fully traced schedule search of one design block: CE maps ->
    ⟨pf, ph, pw⟩ -> coarse layer state -> candidate plane -> argmin ->
    refined composition.  Returns refined + coarse metrics plus the
    per-layer/per-segment detail the artifact is decoded from."""
    m = _ce_maps(design, t, dev)
    pf, ph, pw, _cost = parallelism_search(
        m.pes_ce, m.ce_of_layer, m.ce_oh, fc_pair, coh_pair,
        t.CEIL_OW, t.OW[:, None], pairs, backend=backend,
        design_tile=design_tile)
    st = layer_state(design, t, dev, m, (pf, ph, pw), fm_tile_rows)
    coarse = compose_metrics(design, t, dev, m, st)

    pipe, valid = m.pipe_bool, m.valid_b
    plane = score_plane_dispatch(
        "device", **plane_inputs(jnp, t, dev, st, pipe, valid))
    choice = jnp.argmin(plane["score"], axis=-1).astype(jnp.int32)
    st2 = _refine_state(st, plane, choice, dev.bpc)
    refined = compose_metrics(design, t, dev, m, st2)

    shape = plane["score"].shape

    def take(a):
        return jnp.take_along_axis(jnp.broadcast_to(a, shape),
                                   choice[..., None], axis=-1)[..., 0]

    valid_f = valid.astype(jnp.float32)
    pipe_f = pipe.astype(jnp.float32)
    lat_ref_l = jnp.where(pipe, st2.busy_pipe, st2.lat_single) * valid_f
    lat_coarse_l = jnp.where(pipe, st.busy_pipe, st.lat_single) * valid_f
    acc_ref_l = jnp.where(pipe, st2.w_acc_pipe, st2.acc_single) * valid_f
    acc_coarse_l = jnp.where(pipe, st.w_acc_pipe, st.acc_single) * valid_f

    def seg_cyc(state):
        single = _seg_sum(state.lat_single * (1.0 - pipe_f) * valid_f,
                          m.onehot)
        busy = _seg_max(jnp.where(pipe & valid, state.busy_pipe, NEG),
                        m.onehot)
        return single + jnp.maximum(busy, 0.0)

    out = {f"ref_{k}": v for k, v in refined.items()}
    out.update({f"coarse_{k}": v for k, v in coarse.items()})
    out.update(
        choice=choice,
        phi=take(plane["phi"]),
        tile_bytes=take(plane["tile_bytes"]),
        companion_bytes=take(plane["companion_bytes"]),
        floor_bytes=take(plane["floor_bytes"]),
        budget_bytes=take(plane["budget_bytes"]),
        lat_ref_l=lat_ref_l, lat_coarse_l=lat_coarse_l,
        acc_ref_l=acc_ref_l, acc_coarse_l=acc_coarse_l,
        pf_l=jnp.einsum("bc,blc->bl", pf, m.ce_oh, precision=EXACT),
        ph_l=jnp.einsum("bc,blc->bl", ph, m.ce_oh, precision=EXACT),
        pw_l=jnp.einsum("bc,blc->bl", pw, m.ce_oh, precision=EXACT),
        ce_of_layer=m.ce_of_layer, seg_of_layer=m.seg_of_layer,
        pipe_l=pipe,
        valid_l=jnp.broadcast_to(valid, (design.batch, t.max_L)),
        n_tiles_l=st.n_tiles_l,
        ce_buf_l=st.ce_buf_l, buf_l=st.buf_l,
        alloc_seg=st.alloc, seg_valid=m.seg_valid,
        seg_cyc_ref=seg_cyc(st2), seg_cyc_coarse=seg_cyc(st))
    return out


def schedule_batch_traced(design: DesignBatch, tables: NetTables,
                          dev: DeviceTables, *, backend: str = "ref",
                          tile: int = DEFAULT_TILE, fm_tile_rows: int = 2,
                          pes_hint_static: int | None = None,
                          design_tile: int = 16) -> dict[str, jnp.ndarray]:
    """The traced schedule hot path — same tiling/lax.map structure as
    ``evaluate_batch_traced`` so the two share the ladder shape policy."""
    B = design.batch
    pairs = pair_tables(tables.candidates, pes_hint_static)
    fc_pair, coh_pair = _pair_layer_tables(tables, pairs)

    nt = -(-B // tile)
    padded = _pad_rows(design, nt * tile)

    def one(args):
        return schedule_block(
            DesignBatch(*args), tables, dev, pairs, fc_pair, coh_pair,
            backend=backend, design_tile=design_tile,
            fm_tile_rows=fm_tile_rows)

    out = jax.lax.map(one, (padded.seg_end.reshape(nt, tile, NS),
                            padded.seg_pipe.reshape(nt, tile, NS),
                            padded.seg_nce.reshape(nt, tile, NS),
                            padded.inter_pipe.reshape(nt, tile)))
    return {k: v.reshape((nt * tile,) + v.shape[2:])[:B]
            for k, v in out.items()}


@partial(jax.jit, static_argnames=("backend", "tile", "fm_tile_rows",
                                   "pes_hint_static", "design_tile"))
def _schedule_jit(design, tables, dev, *, backend, tile, fm_tile_rows,
                  pes_hint_static, design_tile):
    return schedule_batch_traced(
        design, tables, dev, backend=backend, tile=tile,
        fm_tile_rows=fm_tile_rows, pes_hint_static=pes_hint_static,
        design_tile=design_tile)


def schedule_batch(design: DesignBatch, tables: NetTables,
                   dev: DeviceSpec | DeviceTables, fm_tile_rows: int = 2,
                   *, backend: str | None = None, tile: int = DEFAULT_TILE,
                   design_tile: int = 16) -> dict[str, jnp.ndarray]:
    """DesignBatch -> refined + coarse metrics + per-layer schedule
    detail, one jitted dispatch (mirrors ``evaluate_batch``)."""
    backend = resolve_eval_backend(backend)
    if isinstance(dev, DeviceSpec):
        hint = pes_hint(dev.pes)
        devt = make_device_tables(dev)
    else:
        devt = dev
        hint = pes_hint(float(dev.pes))
    return _schedule_jit(design, tables, devt, backend=backend, tile=tile,
                         fm_tile_rows=fm_tile_rows, pes_hint_static=hint,
                         design_tile=design_tile)


def schedule_specs(specs, net, dev, *, tables: NetTables | None = None,
                   backend: str | None = None, tile: int = DEFAULT_TILE,
                   fm_tile_rows: int = 2, design_tile: int = 16,
                   pad_to: int | None = None) -> dict[str, np.ndarray]:
    """Spec list -> host metric/detail arrays (padded to the ladder
    bucket like ``_evaluate_specs``, so repeat calls share one compile)."""
    from ..core.batch_eval import make_tables
    if not specs:
        raise ValueError("no specs to schedule (empty design list)")
    tables = make_tables(net) if tables is None else tables
    n = len(specs)
    if pad_to is None:
        pad_to = _bucket(n, tile)
    batch = _pad_rows(encode_specs(list(specs), len(net)), pad_to)
    out = schedule_batch(batch, tables, dev, fm_tile_rows,
                         backend=backend, tile=tile, design_tile=design_tile)
    return {k: np.asarray(v)[:n] for k, v in out.items()}


def reference_plane(design: DesignBatch, t: NetTables,
                    dev: DeviceTables, *, backend: str = "ref",
                    design_tile: int = 16, fm_tile_rows: int = 2):
    """Pure-host reference scoring: the identical candidate plane and
    argmin computed in numpy (``xp=np``) from a host-materialized layer
    state — the bit-parity oracle of tests/test_schedule.py.  Returns
    ``(plane, choice, state_np)``."""
    m = _ce_maps(design, t, dev)
    pf, ph, pw = _reference_par(design, t, dev, m, backend, design_tile)
    st = layer_state(design, t, dev, m, (pf, ph, pw), fm_tile_rows)
    stn = LayerState(*[np.asarray(x) for x in st])
    plane = score_plane_dispatch(
        "ref", **plane_inputs(np, t, dev, stn,
                              np.asarray(m.pipe_bool),
                              np.asarray(m.valid_b)))
    choice = np.argmin(plane["score"], axis=-1).astype(np.int32)
    return plane, choice, stn


def _reference_par(design, t, dev, m, backend, design_tile):
    pairs = pair_tables(t.candidates, None)
    fc_pair, coh_pair = _pair_layer_tables(t, pairs)
    pf, ph, pw, _cost = parallelism_search(
        m.pes_ce, m.ce_of_layer, m.ce_oh, fc_pair, coh_pair,
        t.CEIL_OW, t.OW[:, None], pairs, backend=backend,
        design_tile=design_tile)
    return pf, ph, pw


@partial(jax.jit, static_argnames=("backend", "fm_tile_rows",
                                   "pes_hint_static", "design_tile"))
def _plane_jit(design, tables, dev, *, backend, fm_tile_rows,
               pes_hint_static, design_tile):
    """Device plane WITHOUT the argmin/compose reduction — what the
    bit-parity tests compare field-by-field against ``reference_plane``.
    Test-only; the production path is ``_schedule_jit``."""
    pairs = pair_tables(tables.candidates, pes_hint_static)
    fc_pair, coh_pair = _pair_layer_tables(tables, pairs)
    m = _ce_maps(design, tables, dev)
    pf, ph, pw, _cost = parallelism_search(
        m.pes_ce, m.ce_of_layer, m.ce_oh, fc_pair, coh_pair,
        tables.CEIL_OW, tables.OW[:, None], pairs, backend=backend,
        design_tile=design_tile)
    st = layer_state(design, tables, dev, m, (pf, ph, pw), fm_tile_rows)
    plane = score_plane_dispatch(
        "device", **plane_inputs(jnp, tables, dev, st, m.pipe_bool,
                                 m.valid_b))
    plane["choice"] = jnp.argmin(plane["score"], axis=-1).astype(jnp.int32)
    return plane


def device_plane(design: DesignBatch, t: NetTables,
                 dev: DeviceSpec | DeviceTables, *,
                 backend: str | None = None, design_tile: int = 16,
                 fm_tile_rows: int = 2) -> dict[str, np.ndarray]:
    """Host-materialized jitted plane (see ``_plane_jit``)."""
    backend = resolve_eval_backend(backend)
    if isinstance(dev, DeviceSpec):
        hint = pes_hint(dev.pes)
        devt = make_device_tables(dev)
    else:
        devt = dev
        hint = pes_hint(float(dev.pes))
    out = _plane_jit(design, t, devt, backend=backend,
                     fm_tile_rows=fm_tile_rows, pes_hint_static=hint,
                     design_tile=design_tile)
    return {k: np.asarray(v) for k, v in out.items()}
