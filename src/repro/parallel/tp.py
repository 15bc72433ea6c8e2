"""Tensor-parallel paged serving over a device mesh (ROADMAP item 1).

One :class:`~repro.serve.PagedServeEngine` drives N devices: attention
heads, MLP hidden dims, and (untied) lm_head vocab columns shard over a
1-D ``("model",)`` mesh, and every KV page pool shards over its kv-head
axis — *behind* the existing block-table contract, so the host allocator,
prefix cache, COW splits, and defrag in ``repro.serve.paged_cache`` stay
single-source: one block table drives N per-shard page pools in lockstep.

The decode/prefill step is a **fully-manual** ``shard_map`` region (manual
over every mesh axis) wrapping the unmodified
:func:`repro.models.lm.lm_decode_paged` with a *local* ModelConfig whose
head/ff/kv-head counts are divided by the TP degree.  The two collectives
are explicit:

* :func:`repro.models.layers.tp_einsum` psums its f32 partial sums over
  ``model`` (attention output and MLP/MoE down projections) — activated by
  the :func:`~repro.models.layers.manual_tp` context the region body
  enters, and
* ``_lm_head`` all_gathers vocab-sharded logit columns before masking.

Everything else is per-head / per-channel local math, bit-identical to the
corresponding slice of the 1-device computation — which is why mesh greedy
tokens match the 1-device engine token-for-token (CI-gated by
``benchmarks/bench_parallel.py`` and ``tests/test_engine_identity.py``).

Sharding rules (``plan_tp``):

* ``num_heads`` and ``d_ff`` must divide the TP degree (hard requirement:
  their tp_einsum contractions are unconditionally psummed);
* KV heads shard when divisible.  With fewer KV heads than shards
  (chatglm3-6b: 2 over 4) each KV head is **repeated** ``N / hkv`` times
  in the K/V projections and the page pools before sharding, so every
  shard holds the one KV head its query heads attend to (a shard's query
  heads always fall in one GQA group then);
* an untied lm_head vocab-shards when ``padded_vocab`` divides, else
  replicates; the embedding table always replicates (token gather stays
  local, and a tied head then emits full-width logits with no gather).

Dev/CI run on a simulated mesh::

    XLA_FLAGS=--xla_force_host_platform_device_count=4

See ``docs/parallel.md`` for the full guide.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sharding import ParallelContext


def make_tp_context(mesh: Mesh, tp_axis: str = "model") -> ParallelContext:
    """A serving ParallelContext for a tensor-parallel-only mesh: no data
    axes (one engine, one replica), every device a TP shard."""
    return ParallelContext(mesh=mesh, dp_axes=(), tp_axis=tp_axis)


def make_serving_mesh(n: int, tp_axis: str = "model") -> Mesh:
    """1-D TP mesh over the first ``n`` local devices."""
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"--mesh {n} needs {n} devices but only {len(devs)} are "
            "visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return Mesh(np.asarray(devs[:n]), (tp_axis,))


@dataclasses.dataclass(frozen=True)
class TPPlan:
    """How one model shards over a TP-only mesh (see :func:`plan_tp`)."""
    degree: int
    local_cfg: Any               # ModelConfig with divided head/ff counts
    kv_repeat: int               # copies of each KV head before sharding
    shard_vocab: bool            # untied lm_head vocab-sharded?


def plan_tp(cfg, degree: int) -> TPPlan:
    """Validate ``cfg`` against a TP degree and build the per-shard config.

    The local config keeps ``d_model`` and ``vocab_size`` (activations and
    logits are full-width at region boundaries) and pins ``head_dim`` so
    the divided head count cannot change the derived per-head dim.
    """
    if degree <= 1:
        return TPPlan(1, cfg, 1, False)
    h, hkv, ff = cfg.num_heads, cfg.num_kv_heads, cfg.d_ff
    if h % degree:
        raise ValueError(
            f"tensor parallelism needs num_heads % mesh == 0 "
            f"(got {h} heads over {degree} shards)")
    if ff % degree:
        raise ValueError(
            f"tensor parallelism needs d_ff % mesh == 0 "
            f"(got d_ff={ff} over {degree} shards)")
    if cfg.dense_residual_ff and cfg.dense_residual_ff % degree:
        raise ValueError(
            f"tensor parallelism needs dense_residual_ff % mesh == 0 "
            f"(got {cfg.dense_residual_ff} over {degree} shards)")
    if hkv % degree == 0:
        kv_repeat = 1
    elif degree % hkv == 0:
        kv_repeat = degree // hkv
    else:
        raise ValueError(
            f"tensor parallelism needs num_kv_heads and the mesh to divide "
            f"one another (got {hkv} KV heads over {degree} shards)")
    local_hkv = hkv * kv_repeat // degree
    if (h // degree) % local_hkv:
        raise ValueError(
            f"GQA layout unshardable: {h} query heads / {hkv} KV heads "
            f"over {degree} shards leaves {h // degree} local query heads "
            f"per {local_hkv} local KV heads (need a whole group per shard)")
    local_cfg = dataclasses.replace(
        cfg,
        num_heads=h // degree,
        num_kv_heads=local_hkv,
        head_dim=cfg.resolved_head_dim,
        d_ff=ff // degree,
        dense_residual_ff=cfg.dense_residual_ff // degree
        if cfg.dense_residual_ff else 0,
        name=f"{cfg.name}-tp{degree}",
    )
    shard_vocab = (not cfg.tie_embeddings) and cfg.padded_vocab % degree == 0
    return TPPlan(degree, local_cfg, kv_repeat, shard_vocab)


def repeat_kv_heads(params: Dict[str, Any], cache: Any,
                    logical: Dict[str, Tuple], plan: TPPlan):
    """``(params, cache)`` with every KV head repeated ``plan.kv_repeat``
    times, adjacent copies together, so sharding the KV-head axis hands
    shard ``s`` a copy of KV head ``s // kv_repeat`` — the one its query
    heads ``[s*h/N, (s+1)*h/N)`` attend to.  Identity when kv_repeat is 1.
    The K/V projections repeat along their (kv_heads x head_dim) output
    axis (int8 payload and scale alike), the page pools along axis 4."""
    r = plan.kv_repeat
    if r == 1:
        return params, cache
    hkv = plan.degree * plan.local_cfg.num_kv_heads // r

    def repeat(a, axis):
        a = jnp.asarray(a)
        split = a.shape[:axis] + (hkv, -1) + a.shape[axis + 1:]
        out = jnp.repeat(a.reshape(split), r, axis=axis)
        return out.reshape(a.shape[:axis] + (-1,) + a.shape[axis + 1:])

    params = dict(params)
    for name, log in logical.items():
        if "kv_heads" in log:
            axis = log.index("kv_heads")
            params[name] = jax.tree.map(lambda a: repeat(a, axis),
                                        params[name])
    cache = jax.tree.map(lambda a: repeat(a, _KV_HEAD_AXIS), cache)
    return params, cache


# ---------------------------------------------------------------------------
# PartitionSpec trees.
# ---------------------------------------------------------------------------

#: logical param axes that shard over the TP axis unconditionally (their
#: tp_einsum contractions are always psummed inside the manual region)
_ALWAYS_TP = ("heads", "kv_heads", "ff", "ssm_inner")


def tp_param_specs(params: Dict[str, Any], logical: Dict[str, Tuple],
                   plan: TPPlan, axis: str = "model") -> Dict[str, Any]:
    """Per-leaf PartitionSpecs for a param tree (plain arrays or int8
    :class:`~repro.quant.QuantizedTensor`s).

    Specs are resolved per *array leaf* so a QuantizedTensor's fp32 scale —
    same rank as its payload but with the contraction dim collapsed to 1 —
    replicates exactly the dims it cannot shard (a size-1 dim never
    shards) while staying aligned with the payload everywhere else.
    """
    specs: Dict[str, Any] = {}
    for name, val in params.items():
        log = logical[name]
        base = []
        for ax in log:
            if ax in _ALWAYS_TP:
                base.append(axis)
            elif ax == "vocab":
                # the embed table replicates (local token gather; tied head
                # emits full logits); only an untied lm_head vocab-shards
                base.append(axis if plan.shard_vocab and name != "embed"
                            else None)
            else:
                base.append(None)
        base_t = tuple(base)

        def leaf_spec(a, base_t=base_t, name=name):
            dims = []
            for i, ax in enumerate(base_t):
                if ax is None or a.shape[i] <= 1:
                    dims.append(None)
                    continue
                if a.shape[i] % plan.degree:
                    raise ValueError(
                        f"param {name!r} dim {i} ({a.shape[i]}) does not "
                        f"divide the TP degree {plan.degree}")
                dims.append(ax)
            return P(*dims)

        specs[name] = jax.tree.map(leaf_spec, val)
    return specs


#: axis index of the kv-head dim in every paged-cache leaf — payload pools
#: are (n_sb, me, pool_pages, page_size, hkv, dh) and int8 scale pools drop
#: only the trailing dh, so hkv sits at 4 in both
_KV_HEAD_AXIS = 4


def tp_cache_specs(cache: Dict[str, Any],
                   axis: str = "model") -> Dict[str, Any]:
    """PartitionSpecs for the KV page pools: sharded over the kv-head axis
    (after :func:`repeat_kv_heads` where the plan repeats KV heads)."""
    def spec(a):
        dims = [None] * a.ndim
        dims[_KV_HEAD_AXIS] = axis
        return P(*dims)
    return jax.tree.map(spec, cache)


def shard_tree(tree: Any, mesh: Mesh, specs: Any) -> Any:
    """device_put every leaf with its NamedSharding (specs is a matching
    tree of PartitionSpecs, zipped leaf-wise with is_leaf=P)."""
    leaves, treedef = jax.tree.flatten(tree)
    spec_leaves, _ = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves), (len(leaves), len(spec_leaves))
    out = [jax.device_put(a, NamedSharding(mesh, s))
           for a, s in zip(leaves, spec_leaves)]
    return jax.tree.unflatten(treedef, out)


def per_device_bytes(tree: Any) -> int:
    """Largest per-device byte footprint of a (possibly sharded) tree —
    the number BENCH_parallel.json reports per engine."""
    per: Dict[Any, int] = {}
    for a in jax.tree.leaves(tree):
        if hasattr(a, "addressable_shards") and a.addressable_shards:
            for sh in a.addressable_shards:
                per[sh.device] = per.get(sh.device, 0) + sh.data.nbytes
        else:
            per[None] = per.get(None, 0) + int(a.size) * a.dtype.itemsize
    return max(per.values()) if per else 0


# ---------------------------------------------------------------------------
# The TP decode/prefill step.
# ---------------------------------------------------------------------------


def make_tp_decode_paged(bundle, pctx: ParallelContext, plan: TPPlan,
                         param_specs, cache_specs):
    """Build the mesh variant of the engine's ``decode_paged`` entry point
    (same ``(params, cache, tokens, lengths, new_counts, block_tables)``
    contract, so decode T=1, chunked prefill T=chunk, and speculative
    verify T=K+1 all route through it unchanged).

    The body runs :func:`~repro.models.lm.lm_decode_paged` with the plan's
    *local* config under :func:`~repro.models.layers.manual_tp`; scalars,
    tokens, and block tables replicate (specs ``P()``), params and cache
    arrive pre-sliced per the spec trees.  The inner ParallelContext is
    mesh-free: inside a fully-manual region there is nothing left for
    GSPMD (or a nested shard_map) to do.
    """
    from ..models import lm
    from ..models.layers import manual_tp

    axis = pctx.tp_axis
    local_cfg = plan.local_cfg
    inner_pctx = ParallelContext(None)

    def body(params, cache, tokens, lengths, new_counts, block_tables):
        with manual_tp(axis, plan.degree):
            return lm.lm_decode_paged(params, local_cfg, inner_pctx, cache,
                                      tokens, lengths, new_counts,
                                      block_tables)

    return jax.shard_map(
        body, mesh=pctx.mesh,
        in_specs=(param_specs, cache_specs, P(), P(), P(), P()),
        out_specs=(P(), cache_specs),
        check_vma=False,
    )
