"""FlashAttention forward as a Pallas TPU kernel.

Grid: (B*H, nq) — one program instance per (batch·head, q-block).  Each
instance streams the KV blocks for its q-block through VMEM with an
online-softmax recurrence; scores never leave VMEM (the HBM-traffic term
the pure-jnp twin pays, see EXPERIMENTS.md §Perf).

BlockSpecs (VMEM tiles):
    q   : (1, q_blk, D)     — this instance's query block
    k/v : (1, Sk, D)        — streamed; the kv loop is inside the kernel so
                              the (q_blk, kv_blk) score tile stays in VMEM
    o   : (1, q_blk, D)

Dims are MXU-aligned by the wrapper (q_blk, kv_blk multiples of 128; D is
the head dim, padded to 128 lanes by Mosaic).  Validated in interpret mode
against ``ref.attention_ref`` (CPU container; TPU is the target).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *,
                      causal: bool, window: int | None, scale: float,
                      kv_blk: int, sk_real: int, q_blk: int):
    qi = pl.program_id(1)
    Sk_pad = k_ref.shape[1]
    nk = Sk_pad // kv_blk
    D = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * scale

    q_abs = qi * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, 1), 0)

    def body(kj, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(kj * kv_blk, kv_blk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kj * kv_blk, kv_blk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_abs = kj * kv_blk + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_blk), 1)
        msk = k_abs < sk_real
        if causal:
            msk &= k_abs <= q_abs
        if window is not None:
            msk &= k_abs > q_abs - window
        s = jnp.where(msk, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(msk, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * alpha + p.sum(-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((q_blk, D), jnp.float32)
    m0 = jnp.full((q_blk, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((q_blk, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk, body, (acc0, m0, l0))
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def flash_fwd(q, k, v, *, causal: bool = True, window: int | None = None,
              scale: float | None = None, q_blk: int = 256,
              kv_blk: int = 256, interpret: bool = True):
    """q: (BH, Sq, D); k/v: (BH, Sk, D) -> (BH, Sq, D).

    The wrapper pads Sq/Sk to block multiples; padded KV positions are
    masked inside the kernel via ``sk_real``."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    q_blk = min(q_blk, max(Sq, 8))
    kv_blk = min(kv_blk, max(Sk, 8))
    nq = -(-Sq // q_blk)
    nk = -(-Sk // kv_blk)
    pq, pk = nq * q_blk - Sq, nk * kv_blk - Sk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))

    kern = functools.partial(
        _flash_fwd_kernel, causal=causal, window=window, scale=scale,
        kv_blk=kv_blk, sk_real=Sk, q_blk=q_blk)
    out = pl.pallas_call(
        kern,
        grid=(BH, nq),
        in_specs=[
            pl.BlockSpec((1, q_blk, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, nk * kv_blk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, nk * kv_blk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_blk, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, nq * q_blk, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq]
