"""Mixture-of-Experts layer with expert parallelism.

Two execution strategies with identical math (validated against each other):

* ``local``   — single-shard gather/scatter dispatch; runs anywhere under
  plain ``jit`` (CPU smoke tests, tiny configs).
* ``ep``      — ``jax.shard_map`` over the mesh: tokens are sharded over the
  data axes and *replicated* over the EP axis; experts are sharded over the
  EP axis.  Each EP shard locally selects the tokens routed to its own
  experts (no dispatch all-to-all needed because activations are already
  replicated across EP), computes them, scatters partial outputs, and one
  ``psum`` over the EP axis combines — the same collective footprint as a
  dense tensor-parallel MLP.  An all-to-all dispatch variant
  (``ep_a2a``) trades the psum for two all-to-alls; see
  EXPERIMENTS.md §Perf for when each wins.

Capacity-based dropless-ish routing: per-shard capacity
``C = ceil(top_k * n_tokens * cf / n_experts)``; overflow tokens are dropped
(standard GShard/Switch semantics), with an auxiliary load-balancing loss.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .layers import _dense_init

Params = dict[str, Any]


def init_moe(key, cfg) -> Params:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    dt = cfg.np_dtype
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, e), jnp.float32, scale=0.02),
        "wg": _dense_init(ks[1], (e, d, f), dt),
        "wu": _dense_init(ks[2], (e, d, f), dt),
        "wd": _dense_init(ks[3], (e, f, d), dt),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared"] = {
            "wg": _dense_init(ks[4], (d, fs), dt),
            "wu": _dense_init(jax.random.fold_in(ks[4], 1), (d, fs), dt),
            "wd": _dense_init(jax.random.fold_in(ks[4], 2), (fs, d), dt),
        }
    return p


def _route(xf, router_w, cfg):
    """Router: top-k expert ids + normalised gates + aux load-balance loss."""
    logits = (xf.astype(jnp.float32) @ router_w)            # (n, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, cfg.experts_per_token)    # (n, k)
    if cfg.norm_topk_prob:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
    # Switch-style aux loss: E * sum_e f_e * p_e
    e = cfg.n_experts
    chosen = jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32)  # top-1 counts
    f_e = chosen.mean(0)
    p_e = probs.mean(0)
    aux = e * jnp.sum(f_e * p_e)
    return eidx, gates, aux


def _expert_ffn(x_ecd, wg, wu, wd):
    """Grouped SwiGLU over (E, C, d) with per-expert weights (E, d, f)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_ecd, wg)) * jnp.einsum(
        "ecd,edf->ecf", x_ecd, wu)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _dispatch_compute_combine(xf, eidx, gates, wg, wu, wd, *, e0, e_local, cap):
    """Shared local dispatch kernel. xf:(n,d); experts [e0, e0+e_local)."""
    n, d = xf.shape
    k = eidx.shape[1]
    flat_e = eidx.reshape(-1) - e0                      # (n*k,)
    flat_g = gates.reshape(-1)
    tok = jnp.repeat(jnp.arange(n), k)
    local = (flat_e >= 0) & (flat_e < e_local)
    e_c = jnp.where(local, flat_e, e_local)             # park non-local
    # position within expert, computed over the flattened assignment order
    oh = jax.nn.one_hot(e_c, e_local, dtype=jnp.int32)  # (n*k, E_l)
    pos = jnp.take_along_axis(
        jnp.cumsum(oh, axis=0), jnp.clip(e_c, 0, e_local - 1)[:, None], axis=1
    )[:, 0] - 1
    keep = local & (pos < cap)
    # out-of-bounds scatter indices are dropped under jit -> park at e_local
    e_s = jnp.where(keep, e_c, e_local)
    x_disp = jnp.zeros((e_local, cap, d), xf.dtype).at[e_s, pos].set(xf[tok])
    y_ecd = _expert_ffn(x_disp, wg, wu, wd)
    # combine: gather each assignment's output, weight by its gate.  The gate
    # is cast *first* so the (n*k, d) gather stays in the activation dtype —
    # an f32 promotion here doubles the largest MoE buffer (§Perf).
    contrib = y_ecd[jnp.clip(e_s, 0, e_local - 1), pos]  # reads clip; masked below
    gate = (flat_g * keep).astype(contrib.dtype)
    contrib = contrib * gate[:, None]
    return jnp.zeros((n, d), xf.dtype).at[tok].add(contrib)


def _capacity(n_tokens: int, cfg) -> int:
    c = math.ceil(cfg.experts_per_token * n_tokens * cfg.capacity_factor
                  / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU lane alignment


def moe_local(params: Params, x, cfg):
    """Single-shard MoE. x: (B,S,D) -> (y, aux_loss)."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    eidx, gates, aux = _route(xf, params["router"], cfg)
    cap = _capacity(B * S, cfg)
    y = _dispatch_compute_combine(
        xf, eidx, gates, params["wg"], params["wu"], params["wd"],
        e0=0, e_local=cfg.n_experts, cap=cap)
    y = y.reshape(B, S, D)
    if cfg.n_shared_experts:
        sp = params["shared"]
        y = y + (jax.nn.silu(x @ sp["wg"]) * (x @ sp["wu"])) @ sp["wd"]
    return y, aux


def moe_ep(params: Params, x, cfg, mesh, *, ep_axis: str, dp_axes: tuple[str, ...]):
    """Expert-parallel MoE via shard_map (see module docstring)."""
    e_local = -(-cfg.n_experts // mesh.shape[ep_axis])

    def local_fn(x_l, router_w, wg, wu, wd):
        B, S, D = x_l.shape
        xf = x_l.reshape(B * S, D)
        eidx, gates, aux = _route(xf, router_w, cfg)
        cap = _capacity(B * S, cfg)
        e0 = lax.axis_index(ep_axis) * e_local
        y = _dispatch_compute_combine(
            xf, eidx, gates, wg, wu, wd, e0=e0, e_local=e_local, cap=cap)
        y = lax.psum(y, ep_axis)                 # combine expert partials
        aux = lax.pmean(aux, dp_axes) if dp_axes else aux
        return y.reshape(B, S, D), aux

    xs = P(*([dp_axes] + [None] * (x.ndim - 1)))
    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(xs, P(), P(ep_axis), P(ep_axis), P(ep_axis)),
        out_specs=(xs, P()),
    )(x, params["router"], params["wg"], params["wu"], params["wd"])
    if cfg.n_shared_experts:
        sp = params["shared"]
        y = y + (jax.nn.silu(x @ sp["wg"]) * (x @ sp["wu"])) @ sp["wd"]
    return y, aux


def moe_ep_a2a(params: Params, x, cfg, mesh, *, ep_axis: str,
               dp_axes: tuple[str, ...]):
    """All-to-all dispatch variant (DeepSpeed-MoE style).

    Tokens stay sharded over ``dp_axes`` *and* the EP axis (the EP axis acts
    as an extra data dimension pre-dispatch).  Each shard routes its own
    tokens, builds an (E, C_l, d) dispatch tensor, and two ``all_to_all``
    exchanges move token blocks to/from the shard owning each expert.
    Collective bytes per layer: 2 * k * cf * tokens_local * d  (vs. a full
    (n, d) psum for :func:`moe_ep`) — the beyond-paper optimisation logged in
    EXPERIMENTS.md §Perf.

    Tokens are sharded over ``dp_axes`` (batch) and ``ep_axis`` (sequence),
    so each shard routes only S/ep of the sequence before the exchange.
    """
    ep = mesh.shape[ep_axis]
    e_local = -(-cfg.n_experts // ep)

    def local_fn(x_l, router_w, wg, wu, wd):
        B, S, D = x_l.shape
        n = B * S
        xf = x_l.reshape(n, D)
        eidx, gates, aux = _route(xf, router_w, cfg)
        cap = _capacity(n, cfg)
        k = eidx.shape[1]
        flat_e = eidx.reshape(-1)
        flat_g = gates.reshape(-1)
        tok = jnp.repeat(jnp.arange(n), k)
        # position of each assignment within its (global) expert bucket
        oh = jax.nn.one_hot(flat_e, cfg.n_experts, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0), flat_e[:, None],
                                  axis=1)[:, 0] - 1
        keep = pos < cap
        e_s = jnp.where(keep, flat_e, cfg.n_experts)
        x_disp = jnp.zeros((cfg.n_experts, cap, D), xf.dtype).at[e_s, pos].set(
            xf[tok])
        # (E, C, d) = (ep, e_local, C, d); a2a over dim 0 sends each expert
        # block to the shard that owns it and gathers the ep source shards.
        x_disp = x_disp.reshape(ep, e_local, cap, D)
        x_recv = lax.all_to_all(x_disp, ep_axis, split_axis=0, concat_axis=0,
                                tiled=True)          # (ep, e_local, C, d)
        # my e_local experts each see ep*C candidate tokens
        x_mine = x_recv.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, D)
        y_mine = _expert_ffn(x_mine, wg, wu, wd)
        y_send = y_mine.reshape(e_local, ep, cap, D).transpose(1, 0, 2, 3)
        y_back = lax.all_to_all(y_send, ep_axis, split_axis=0, concat_axis=0,
                                tiled=True).reshape(cfg.n_experts, cap, D)
        contrib = y_back[jnp.clip(e_s, 0, cfg.n_experts - 1), pos]
        gate = (flat_g * keep).astype(contrib.dtype)
        contrib = contrib * gate[:, None]
        y = jnp.zeros((n, D), xf.dtype).at[tok].add(contrib)
        aux = lax.pmean(aux, dp_axes + (ep_axis,))
        return y.reshape(B, S, D), aux

    # tokens sharded over dp axes (batch) AND the EP axis (sequence): each
    # shard routes only its own S/ep slice, then a2a moves expert blocks.
    xs = P(dp_axes, ep_axis, None)
    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(xs, P(), P(ep_axis), P(ep_axis), P(ep_axis)),
        out_specs=(xs, P()),
    )(x, params["router"], params["wg"], params["wu"], params["wd"])
    if cfg.n_shared_experts:
        sp = params["shared"]
        y = y + (jax.nn.silu(x @ sp["wg"]) * (x @ sp["wu"])) @ sp["wd"]
    return y, aux


def moe_fwd(params: Params, x, cfg, rt):
    """Dispatch on the runtime's MoE implementation choice."""
    if rt.moe_impl == "local" or rt.mesh is None:
        return moe_local(params, x, cfg)
    ep = rt.mesh.shape.get(rt.ep_axis, 1) if rt.ep_axis else 1
    if rt.moe_impl == "ep_a2a" and x.shape[1] % max(ep, 1) == 0:
        return moe_ep_a2a(params, x, cfg, rt.mesh, ep_axis=rt.ep_axis,
                          dp_axes=rt.dp_axes)
    # psum variant — also the decode fallback (a2a needs S divisible by EP;
    # a one-token step can't sequence-shard)
    return moe_ep(params, x, cfg, rt.mesh, ep_axis=rt.ep_axis,
                  dp_axes=rt.dp_axes)
