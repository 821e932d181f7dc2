"""Pure-jnp oracles for the MCCM evaluation kernels.

Two levels:

* ``mccm_latency_ref`` — the original Eq. 1 sweep (kept as the oracle of
  the simple latency kernel).
* ``parallelism_search_ref`` — the fused ⟨pf, ph, pw⟩ parallelism search
  that is the DSE hot path: for every design and CE, pick the candidate
  pair minimising the CE's total Eq. 1 cycles under its PE budget, with
  ``pw`` greedily maximised per pair.  This is the bit-exact reference the
  Pallas kernel (``kernel.parallelism_search_call``) and the tiled XLA
  path in ``core.batch_eval`` are tested against.

The search operates on a *static pair list* (see ``ops.pair_tables``):
the (i, j) candidate grid is flattened in row-major order with pairs
whose ``pf*ph`` product exceeds the device's PE budget hint pruned away.
Pruned pairs are infeasible for every CE (allocations never exceed the
device total), so selection is identical to an argmin over the full grid.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: precision of the one-hot contractions.  On a TPU an f32 contraction at
#: default precision rounds its operands to bf16 (~0.2% off cycle counts
#: and MACs); HIGHEST keeps them f32 (exact against one-hot operands)
EXACT = jax.lax.Precision.HIGHEST


def mccm_latency_ref(dims, par):
    """Eq. 1 over a design batch.

    dims: (L, 4) f32 — per-layer (F, CKK, OH, OW);
    par : (B, L, 3) f32 — per-design per-layer ⟨pf, ph, pw⟩ (already
          gathered from the layer's CE).
    Returns (B,) total cycles and (B, L) per-layer cycles.
    """
    F, CKK, OH, OW = dims[:, 0], dims[:, 1], dims[:, 2], dims[:, 3]
    cyc = (jnp.ceil(F[None] / par[..., 0]) * CKK[None]
           * jnp.ceil(OH[None] / par[..., 1])
           * jnp.ceil(OW[None] / par[..., 2]))
    return cyc.sum(-1), cyc


def parallelism_search_ref(pes_ce, ce_of_layer, ce_oh,
                           fc_pair, coh_pair, ceil_ow, cand,
                           pair_prod, pair_pf, pair_ph):
    """Fused per-CE parallelism search (the former (B, L, 18, 18) tensor).

    Arguments
    ---------
    pes_ce      (B, NC)    f32  PEs allocated to each CE.
    ce_of_layer (B, L)     i32  CE id of each layer, clipped to [0, NC).
    ce_oh       (B, L, NC) f32  one-hot of ``ce_of_layer`` (0-rows for
                                padded / unmapped layers).
    fc_pair     (L, P)     f32  ceil(F/pf) * CKK per (layer, pair).
    coh_pair    (L, P)     f32  ceil(OH/ph) per (layer, pair).
    ceil_ow     (L, K)     f32  ceil(OW/cand) table.
    cand        (K,)       f32  ascending parallelism candidates.
    pair_prod   (P,)       f32  pf*ph of each pair (row-major pair order).
    pair_pf/ph  (P,)       f32  pf / ph candidate values of each pair.

    Returns (pf, ph, pw, cost) each (B, NC) f32 — the per-CE winner and
    its total cycle cost (inf when no pair is feasible).
    """
    L = ce_of_layer.shape[1]
    ncand = cand.shape[0]
    budget = pes_ce[:, :, None] / pair_prod[None, None, :]      # (B, NC, P)
    feasible = budget >= 1.0
    # largest candidate with pf*ph*pw <= pes: searchsorted on the floor
    pw_idx = jnp.clip(
        jnp.searchsorted(cand, jnp.floor(budget), side="right") - 1,
        0, ncand - 1)                                           # (B, NC, P)
    pw_sel = jnp.take_along_axis(pw_idx, ce_of_layer[:, :, None], axis=1)
    cow = ceil_ow[jnp.arange(L)[None, :, None], pw_sel]         # (B, L, P)
    cost_l = fc_pair[None] * coh_pair[None] * cow               # (B, L, P)
    cost_ce = jnp.einsum("blp,blc->bcp", cost_l, ce_oh,
                         precision=EXACT)                       # (B, NC, P)
    cost_ce = jnp.where(feasible, cost_ce, jnp.inf)
    best = jnp.argmin(cost_ce, axis=-1)                         # (B, NC)
    pf = pair_pf[best]
    ph = pair_ph[best]
    pw = cand[jnp.take_along_axis(pw_idx, best[..., None], -1)[..., 0]]
    cost = jnp.take_along_axis(cost_ce, best[..., None], -1)[..., 0]
    return pf, ph, pw, cost
