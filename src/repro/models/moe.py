"""Mixture-of-Experts layer with expert parallelism.

Production dispatch path (DeepSpeed-MoE / MaxText style):

* experts are sharded over the ``data`` mesh axis (EP group = one pod's DP
  slice; experts replicate across pods so MoE all-to-alls never cross the
  slow pod links — gradients do, once per step);
* within each expert the FFN is tensor-sharded over ``model`` with an
  explicit psum on the down projection (``tp_einsum`` under ``manual_tp``
  — the region is manual over *every* mesh axis, because partial-manual
  shard_map does not compile on the image's jax; see
  docs/known_failures.md);
* routing is local, capacity-bounded (drops), dispatch/return via
  ``lax.all_to_all`` on the ``data`` axis.

The same math runs without a mesh (single-device smoke path) by skipping
the all-to-alls — ``ep_degree=1``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers
from .layers import ParamBuilder, Params


def moe_params(pb: ParamBuilder, prefix: str, cfg: ModelConfig, layers: Optional[int]):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    lead = () if layers is None else (layers,)
    llog = () if layers is None else ("layers",)
    pb.param(f"{prefix}.router", lead + (d, e), llog + ("embed", None))
    pb.param(f"{prefix}.w_gate", lead + (e, d, ff), llog + ("experts", "embed", "ff"))
    pb.param(f"{prefix}.w_up", lead + (e, d, ff), llog + ("experts", "embed", "ff"))
    pb.param(f"{prefix}.w_down", lead + (e, ff, d), llog + ("experts", "ff", "embed"))
    if cfg.dense_residual_ff:
        fr = cfg.dense_residual_ff
        pb.param(f"{prefix}.res_gate", lead + (d, fr), llog + ("embed", "ff"))
        pb.param(f"{prefix}.res_up", lead + (d, fr), llog + ("embed", "ff"))
        pb.param(f"{prefix}.res_down", lead + (fr, d), llog + ("ff", "embed"))


def _capacity(tokens_local: int, cfg: ModelConfig, ep: int) -> int:
    per_expert = tokens_local * cfg.experts_per_token / max(cfg.num_experts, 1)
    return max(1, int(per_expert * cfg.moe_capacity_factor + 0.999))


def _route_and_dispatch(x, router_w, cfg: ModelConfig, capacity: int):
    """Local routing: returns (gathered (E, C, d), combine metadata)."""
    t, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    logits = jnp.einsum("td,de->te", x, router_w).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    weights, sel = jax.lax.top_k(gates, k)                  # (t, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    flat_sel = sel.reshape(-1)                              # (t*k,)
    # position of each dispatch within its expert's queue
    order = jnp.argsort(flat_sel, stable=True)
    counts = jnp.bincount(flat_sel, length=e)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(t * k) - starts[flat_sel[order]]
    rank = jnp.zeros(t * k, jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

    keep = rank < capacity                                  # dropped beyond C
    slot = flat_sel * capacity + jnp.where(keep, rank, 0)   # (t*k,)
    token_id = jnp.repeat(jnp.arange(t), k)

    # scatter tokens into the (E*C, d) dispatch buffer; dropped dispatches
    # land in a trash row that is sliced off.
    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    buf = buf.at[jnp.where(keep, slot, e * capacity)].add(
        jnp.where(keep[:, None], x[token_id], 0).astype(x.dtype)
    )
    buf = buf[: e * capacity]
    meta = (token_id, slot, keep, weights.reshape(-1).astype(x.dtype))
    return buf.reshape(e, capacity, d), meta


def _combine(expert_out, meta, t: int):
    """Weighted scatter-add of expert outputs back to token order."""
    e, c, d = expert_out.shape
    token_id, slot, keep, w = meta
    vals = expert_out.reshape(e * c, d)[jnp.where(keep, slot, 0)]
    vals = jnp.where(keep[:, None], vals, 0) * w[:, None]
    return jnp.zeros((t, d), expert_out.dtype).at[token_id].add(vals)


def _expert_ffn(p: Params, prefix: str, xs: jax.Array, cfg=None) -> jax.Array:
    """xs: (E_local, C_total, d) -> same; per-expert SwiGLU."""
    from .layers import materialize_weight, tp_einsum
    g = jnp.einsum("ecd,edf->ecf", xs,
                   materialize_weight(p[f"{prefix}.w_gate"], xs.dtype))
    u = jnp.einsum("ecd,edf->ecf", xs,
                   materialize_weight(p[f"{prefix}.w_up"], xs.dtype))
    return tp_einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p[f"{prefix}.w_down"], cfg)


def moe_ffn_local(p: Params, prefix: str, cfg: ModelConfig, x: jax.Array,
                  ep_axis: Optional[str] = None) -> jax.Array:
    """MoE FFN over local tokens x: (T_local, d).

    With ``ep_axis`` set (inside shard_map), expert weights arrive sliced to
    E_local = E/ep on axis 0 and tokens are exchanged with two all-to-alls.
    """
    t = x.shape[0]
    ep = jax.lax.axis_size(ep_axis) if ep_axis else 1
    cap = _capacity(t, cfg, ep)
    dispatched, meta = _route_and_dispatch(x, p[f"{prefix}.router"], cfg, cap)

    if ep_axis:
        # (E, C, d) -> (E_local, ep*C, d): each shard keeps its own experts'
        # queues from every peer.
        dispatched = jax.lax.all_to_all(
            dispatched, ep_axis, split_axis=0, concat_axis=1, tiled=True
        )
        # named so remat_policy="save_coll" avoids re-running the all-to-all
        # during backward recompute (§Perf iteration, arctic cell)
        dispatched = jax.ad_checkpoint.checkpoint_name(dispatched, "moe_a2a")
    out = _expert_ffn(p, prefix, dispatched, cfg)
    if ep_axis:
        out = jax.lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0, tiled=True)
        out = jax.ad_checkpoint.checkpoint_name(out, "moe_a2a")
    return _combine(out, meta, t)


def moe_block(p: Params, prefix: str, cfg: ModelConfig, x: jax.Array,
              pctx=None) -> jax.Array:
    """x: (B, T, d).  Runs the EP path under a fully-manual shard_map when a
    mesh with a >1 EP axis is provided, else the single-shard path (same
    math).  Fully manual means every mesh axis is explicit here: experts
    shard over the EP axis, each expert's ff dim shards over the TP axis
    (when divisible) with :func:`~.layers.tp_einsum` psumming the down
    projection under :func:`~.layers.manual_tp`, and any remaining axes
    (pods) see replicated weights and tokens — nothing is left for GSPMD,
    which is what lets this compile on jax without partial-manual support
    (docs/known_failures.md)."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)

    # res_* (arctic's parallel dense MLP) runs outside the region, below
    moe_keys = [k for k in p if k.startswith(prefix + ".") and ".res_" not in k]
    sub = {k: p[k] for k in moe_keys}

    mesh = pctx.mesh if pctx is not None else None
    if (mesh is not None and pctx.ep_axis in mesh.axis_names
            and mesh.shape[pctx.ep_axis] > 1):
        P = jax.sharding.PartitionSpec
        ep_axis = pctx.ep_axis
        tp_axis = pctx.tp_axis if pctx.tp_axis in mesh.axis_names else None
        shard_ff = (tp_axis is not None and mesh.shape[tp_axis] > 1
                    and cfg.d_ff % mesh.shape[tp_axis] == 0)
        ff_ax = tp_axis if shard_ff else None

        def spec_for(k):
            if ".router" in k:
                return P()                      # replicated everywhere
            if ".w_down" in k:
                return P(ep_axis, ff_ax, None)  # (E, ff, d)
            return P(ep_axis, None, ff_ax)      # w_gate/w_up (E, d, ff)

        tp_deg = mesh.shape[tp_axis] if shard_ff else 1

        def body(sp, xl):
            with layers.manual_tp(ff_ax, tp_deg):
                return moe_ffn_local(sp, prefix, cfg, xl, ep_axis=ep_axis)

        out = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=({k: spec_for(k) for k in sub}, P(tuple(pctx.dp_axes))),
            out_specs=P(tuple(pctx.dp_axes)),
            check_vma=False,
        )(sub, flat)
    else:
        out = moe_ffn_local(sub, prefix, cfg, flat, ep_axis=None)

    out = out.reshape(b, t, d)
    if cfg.dense_residual_ff:
        from .layers import swiglu
        out = out + swiglu(x, p[f"{prefix}.res_gate"], p[f"{prefix}.res_up"],
                           p[f"{prefix}.res_down"], cfg)
    return out
