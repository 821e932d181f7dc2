"""A "Compute Engine" as a Pallas TPU kernel: tiled direct convolution whose
grid IS the paper's Eq. 1.

The CE parallelism vector ⟨par_f, par_oh, par_ow⟩ becomes the output tile
shape; the pallas grid is then

    (ceil(F/par_f), ceil(OH/par_oh), ceil(OW/par_ow))

— the exact ceil-div product of Eq. 1, with MXU/VPU tile padding playing
the role of PE underutilisation (a tile smaller than the hardware lanes
wastes the remainder, exactly like idle PEs).  ``ops.predicted_cycles``
returns the Eq. 1 count; tests assert the kernel's grid agrees.

VMEM strategy: weights are blocked on F (the stationary operand — the
weight-stationary dataflow of §II-B); the input stays resident (validation
sizes; a production halo-exchange pipeline is noted in DESIGN.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _conv_kernel(x_ref, w_ref, o_ref, *, stride: int, par_f: int,
                 par_oh: int, par_ow: int, F: int, OH: int, OW: int):
    fi = pl.program_id(0)
    hi = pl.program_id(1)
    wi = pl.program_id(2)
    C, H, W = x_ref.shape
    KH, KW = w_ref.shape[2], w_ref.shape[3]

    w = w_ref[...].astype(jnp.float32)              # (par_f, C, KH, KW)
    wf = w.reshape(par_f, C * KH * KW)

    # gather the input patches for this (par_oh, par_ow) output tile
    oh0 = hi * par_oh
    ow0 = wi * par_ow

    def oh_body(dh, acc):
        def ow_body(dw, acc):
            patch = x_ref[:, pl.ds((oh0 + dh) * stride, KH),
                          pl.ds((ow0 + dw) * stride, KW)].astype(jnp.float32)
            col = patch.reshape(C * KH * KW)
            val = wf @ col                            # (par_f,) — MXU row
            return acc.at[:, dh, dw].set(val)
        return jax.lax.fori_loop(0, par_ow, ow_body, acc)

    acc = jnp.zeros((par_f, par_oh, par_ow), jnp.float32)
    acc = jax.lax.fori_loop(0, par_oh, oh_body, acc)

    # mask the ragged tails (ceil-div padding = idle PEs)
    f_abs = fi * par_f + jax.lax.broadcasted_iota(
        jnp.int32, (par_f, 1, 1), 0)
    h_abs = oh0 + jax.lax.broadcasted_iota(jnp.int32, (1, par_oh, 1), 1)
    w_abs = ow0 + jax.lax.broadcasted_iota(jnp.int32, (1, 1, par_ow), 2)
    valid = (f_abs < F) & (h_abs < OH) & (w_abs < OW)
    o_ref[...] = jnp.where(valid, acc, 0.0).astype(o_ref.dtype)


def conv_ce_call(x, w, *, stride: int = 1, par_f: int = 8, par_oh: int = 4,
                 par_ow: int = 4, interpret: bool = True):
    """x: (C, H, W); w: (F, C, KH, KW) -> (F, OH, OW) valid conv."""
    C, H, W = x.shape
    F, _, KH, KW = w.shape
    OH = (H - KH) // stride + 1
    OW = (W - KW) // stride + 1
    gf, gh, gw = -(-F // par_f), -(-OH // par_oh), -(-OW // par_ow)

    # pad weights on F so blocks divide evenly; input padded so every
    # in-bounds patch load is valid even for ragged output tiles
    wp = jnp.pad(w, ((0, gf * par_f - F), (0, 0), (0, 0), (0, 0)))
    pad_h = (gh * par_oh - 1) * stride + KH - H
    pad_w = (gw * par_ow - 1) * stride + KW - W
    xp = jnp.pad(x, ((0, 0), (0, max(pad_h, 0)), (0, max(pad_w, 0))))

    kern = functools.partial(_conv_kernel, stride=stride, par_f=par_f,
                             par_oh=par_oh, par_ow=par_ow, F=F, OH=OH, OW=OW)
    out = pl.pallas_call(
        kern,
        grid=(gf, gh, gw),
        in_specs=[
            pl.BlockSpec(xp.shape, lambda f, h, w_: (0, 0, 0)),
            pl.BlockSpec((par_f, C, KH, KW), lambda f, h, w_: (f, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((par_f, par_oh, par_ow),
                               lambda f, h, w_: (f, h, w_)),
        out_shape=jax.ShapeDtypeStruct((gf * par_f, gh * par_oh,
                                        gw * par_ow), x.dtype),
        interpret=interpret,
    )(xp, wp)
    return out[:F, :OH, :OW]
