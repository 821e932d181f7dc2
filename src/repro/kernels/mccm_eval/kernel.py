"""MCCM evaluation kernels in Pallas.

Two kernels:

* ``mccm_latency_call`` — the original Eq. 1 latency sweep (a given
  per-layer ⟨pf, ph, pw⟩, reduce to per-design totals).
* ``parallelism_search_call`` — the fused DSE hot path: for a tile of
  designs, search the best ⟨pf, ph, pw⟩ per CE.  Per design-tile the
  (tile, L, P) cycle-cost block is built in VMEM, contracted against the
  CE one-hot with the MXU, and arg-minimised — the full (B, L, 18, 18)
  cost tensor never exists in HBM.

VMEM budget of the search kernel (f32): the live set is ~3 × (tile, L, P)
blocks plus the (tile, L, NC) one-hot.  With L ≤ 160, P ≤ 324 and the
default ``design_tile = 16`` that is ≈ 8 MiB — comfortably under a
16 MiB/core VMEM with room for Mosaic's cross-step double buffering.
Raise ``design_tile`` on parts with more VMEM.

On CPU the kernels run under ``interpret=True`` (same code path, jnp
semantics); ``core.batch_eval`` selects the backend.  The compiled
program and the profiler's trace name them ``mccm_latency`` and
``mccm_parallelism_search``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import EXACT


# --------------------------------------------------------------------------
# Eq. 1 latency sweep (kept from the original toy kernel)
# --------------------------------------------------------------------------
def _mccm_kernel(dims_ref, par_ref, tot_ref, cyc_ref):
    dims = dims_ref[...]                        # (L, 4)
    par = par_ref[...]                          # (design_blk, L, 3)
    F, CKK = dims[:, 0], dims[:, 1]
    OH, OW = dims[:, 2], dims[:, 3]
    cyc = (jnp.ceil(F[None] / par[..., 0]) * CKK[None]
           * jnp.ceil(OH[None] / par[..., 1])
           * jnp.ceil(OW[None] / par[..., 2]))  # (design_blk, L)
    cyc_ref[...] = cyc
    tot_ref[...] = cyc.sum(-1)


def mccm_latency_call(dims, par, *, design_blk: int = 512,
                      interpret: bool = True):
    """dims: (L, 4) f32; par: (B, L, 3) f32 -> ((B,) totals, (B, L) cycles)."""
    B, L, _ = par.shape
    nb = -(-B // design_blk)
    pad = nb * design_blk - B
    if pad:
        par = jnp.pad(par, ((0, pad), (0, 0), (0, 0)),
                      constant_values=1.0)
    tot, cyc = pl.pallas_call(
        _mccm_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((L, 4), lambda i: (0, 0)),
            pl.BlockSpec((design_blk, L, 3), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((design_blk,), lambda i: (i,)),
            pl.BlockSpec((design_blk, L), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * design_blk,), jnp.float32),
            jax.ShapeDtypeStruct((nb * design_blk, L), jnp.float32),
        ],
        interpret=interpret,
        name="mccm_latency",
    )(dims, par)
    return tot[:B], cyc[:B]


# --------------------------------------------------------------------------
# fused per-CE parallelism search
# --------------------------------------------------------------------------
def _search_kernel(ncand: int):
    """Kernel body builder (``ncand`` fixed so the pw scan unrolls)."""

    def kern(pes_ref, ceoh_ref, fc_ref, coh_ref, ow_ref, cand_ref,
             prod_ref, pfv_ref, phv_ref, pf_out, ph_out, pw_out, cost_out):
        pes = pes_ref[...]                              # (T, NC)
        ce_oh = ceoh_ref[...]                           # (T, L, NC)
        cand = cand_ref[...][0]                         # (K,)
        prod = prod_ref[...][0]                         # (P,)
        P = prod.shape[0]

        budget = pes[:, :, None] / prod[None, None, :]  # (T, NC, P)
        feasible = budget >= 1.0
        flb = jnp.floor(budget)
        # largest candidate <= floor(budget): unrolled ascending scan keeps
        # the working set at one (T, NC, P) block instead of (T, NC, P, K)
        pwv = jnp.zeros_like(flb)
        for k in range(ncand):
            pwv = jnp.where(flb >= cand[k], cand[k], pwv)

        # per-layer pw of the layer's CE: one-hot contraction (MXU)
        pw_l = jax.lax.dot_general(
            ce_oh, pwv, (((2,), (1,)), ((0,), (0,))), precision=EXACT,
            preferred_element_type=jnp.float32)          # (T, L, P)
        cow = jnp.ceil(ow_ref[...][None] / jnp.maximum(pw_l, 1.0))
        cost_l = fc_ref[...][None] * coh_ref[...][None] * cow   # (T, L, P)
        cost_ce = jax.lax.dot_general(
            ce_oh, cost_l, (((1,), (1,)), ((0,), (0,))), precision=EXACT,
            preferred_element_type=jnp.float32)          # (T, NC, P)
        cost_ce = jnp.where(feasible, cost_ce, jnp.inf)

        # first minimum, spelled out: Mosaic's argmin does not return the
        # first of tied minima, and ties must resolve as the ref's argmin
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, P), 2)
        low = cost_ce.min(-1, keepdims=True)
        best = jnp.where(cost_ce == low, iota, P).min(-1)  # (T, NC)
        sel = best[..., None] == iota                    # (T, NC, P)
        self_f = sel.astype(jnp.float32)
        pf_out[...] = (pfv_ref[...][0][None, None, :] * self_f).sum(-1)
        ph_out[...] = (phv_ref[...][0][None, None, :] * self_f).sum(-1)
        pw_out[...] = jnp.maximum((pwv * self_f).sum(-1), 1.0)
        cost_out[...] = jnp.where(sel, cost_ce, 0.0).sum(-1)

    return kern


def parallelism_search_call(pes_ce, ce_oh, fc_pair, coh_pair, ow,
                            cand, pair_prod, pair_pf, pair_ph, *,
                            design_tile: int = 16, interpret: bool = True):
    """Fused ⟨pf, ph, pw⟩ search over a design batch.

    pes_ce (B, NC); ce_oh (B, L, NC); fc_pair / coh_pair (L, P);
    ow (L, 1) per-layer OW; cand (K,) ascending; pair_* (P,).
    Returns (pf, ph, pw, cost) each (B, NC) f32.  Semantics match
    ``ref.parallelism_search_ref`` bit for bit (same pair order, same
    first-minimum tie-breaking).
    """
    B, NC = pes_ce.shape
    L, P = fc_pair.shape
    K = int(cand.shape[0])
    nb = -(-B // design_tile)
    pad = nb * design_tile - B
    if pad:  # padded designs get pes 0 -> all-infeasible -> (1, 1, 1)
        pes_ce = jnp.pad(pes_ce, ((0, pad), (0, 0)))
        ce_oh = jnp.pad(ce_oh, ((0, pad), (0, 0), (0, 0)))
    row = lambda a: a.reshape(1, -1).astype(jnp.float32)
    outs = pl.pallas_call(
        _search_kernel(K),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((design_tile, NC), lambda i: (i, 0)),
            pl.BlockSpec((design_tile, L, NC), lambda i: (i, 0, 0)),
            pl.BlockSpec((L, P), lambda i: (0, 0)),
            pl.BlockSpec((L, P), lambda i: (0, 0)),
            pl.BlockSpec((L, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, P), lambda i: (0, 0)),
            pl.BlockSpec((1, P), lambda i: (0, 0)),
            pl.BlockSpec((1, P), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((design_tile, NC), lambda i: (i, 0))] * 4,
        out_shape=[jax.ShapeDtypeStruct((nb * design_tile, NC), jnp.float32)
                   ] * 4,
        interpret=interpret,
        name="mccm_parallelism_search",
    )(pes_ce.astype(jnp.float32), ce_oh.astype(jnp.float32),
      fc_pair.astype(jnp.float32), coh_pair.astype(jnp.float32),
      ow.astype(jnp.float32), row(cand), row(pair_prod), row(pair_pf),
      row(pair_ph))
    return tuple(o[:B] for o in outs)
