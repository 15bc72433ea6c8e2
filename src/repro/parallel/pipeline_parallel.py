"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Opt-in feature (the production dry-run uses DP x TP x EP, which fits every
assigned arch on v5e; PP is for deeper models on smaller-HBM parts — see
DESIGN.md §4).  Schedule: classic GPipe fill/steady/drain over
M microbatches and S stages (M + S - 1 ticks), expressed as a lax.scan of
ticks inside a shard_map that is manual over ``stage``; activations advance
between stages with ``lax.ppermute`` — the collective the roofline parser
accounts as pipeline traffic.  Backward is jax autodiff through the
schedule (ppermute transposes to the reverse rotation), giving GPipe's
fwd+bwd with recomputation when the layer_fn is checkpointed.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_apply(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,          # leaves with leading dim = n_stages
    microbatches: jax.Array,    # (M, mb, ...) input microbatches
    mesh: Mesh,
    *,
    stage_axis: str = "stage",
) -> jax.Array:
    """Run ``layer_fn`` per stage over microbatches; returns (M, mb, ...)."""
    n_stages = mesh.shape[stage_axis]

    def staged(params_local, xs):
        s = jax.lax.axis_index(stage_axis)
        m = xs.shape[0]
        ticks = m + n_stages - 1
        rot = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def apply_stage(x):
            # each stage holds L/S layers (leading local dim after sharding)
            nloc = jax.tree.leaves(params_local)[0].shape[0]
            for i in range(nloc):
                x = layer_fn(jax.tree.map(lambda a: a[i], params_local), x)
            return x

        def tick(carry, t):
            state, outs = carry
            # stage 0 injects microbatch t during the fill/steady phase
            inject = xs[jnp.clip(t, 0, m - 1)]
            state = jnp.where(s == 0, inject, state)
            out = apply_stage(state)
            # last stage emits microbatch t-(S-1)
            idx = t - (n_stages - 1)
            valid = (s == n_stages - 1) & (idx >= 0) & (idx < m)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, out.astype(outs.dtype), jnp.clip(idx, 0, m - 1), 0)
            outs = jnp.where(valid, upd, outs)
            state = jax.lax.ppermute(out, stage_axis, rot)
            return (state, outs), None

        state0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (state, outs), _ = jax.lax.scan(tick, (state0, outs0), jnp.arange(ticks))
        # only the last stage's buffer is real; replicate it via psum
        mask = (s == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, stage_axis)

    in_specs = (
        jax.tree.map(lambda _: P(stage_axis), stage_params,
                     is_leaf=lambda x: hasattr(x, "shape")),
        P(),
    )
    return jax.shard_map(
        staged, mesh=mesh, in_specs=in_specs, out_specs=P(),
        axis_names={stage_axis}, check_vma=False,
    )(stage_params, microbatches)
