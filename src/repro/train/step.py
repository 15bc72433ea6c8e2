"""train_step / serve_step factories with full sharding assembly.

The train step is the function the multi-pod dry-run lowers: loss (masked
next-token CE), gradient accumulation over microbatches (lax.scan), optimizer
update (AdamW ZeRO-1 or Adafactor), all expressed at global shapes — GSPMD
inserts the grad all-reduce, ZeRO reduce-scatter/all-gather and TP
collectives from the in/out shardings.

Gradient compression (error-feedback int8) applies to the cross-pod stage
of the gradient reduction when enabled — see parallel/compression.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..configs.shapes import ShapeSpec
from ..models.registry import ModelBundle
from ..optim import (adafactor_init, adafactor_update, adamw_init,
                     adamw_update, warmup_cosine)
from ..parallel.compression import compressed_value_and_grad
from ..parallel.sharding import ParallelContext, param_specs, zero1_spec


@dataclasses.dataclass(frozen=True)
class TrainHyper:
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    num_microbatches: int = 1
    compress_cross_pod: bool = False


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Gather-free CE: with the vocab dim sharded over ``model``, both the
    logsumexp and the one-hot contraction reduce over the sharded axis via
    all-reduce — no (B,S,V) all-gather ever materialises."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    correct = jnp.einsum("btv,btv->bt", logits, onehot)
    ll = correct - lse
    if mask is not None:
        return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return -jnp.mean(ll)


def _loss_fn(bundle: ModelBundle, pctx: ParallelContext, params, batch):
    logits = bundle.forward(params, batch, pctx)
    mask = None
    if bundle.cfg.family == "vlm":  # vision prefix positions carry no labels
        s = logits.shape[1]
        mask = (jnp.arange(s) >= bundle.cfg.vision_tokens)[None, :].astype(jnp.float32)
        mask = jnp.broadcast_to(mask, logits.shape[:2])
    return cross_entropy_loss(logits, batch["labels"], mask)


def microbatches_for(cfg: ModelConfig, shape: ShapeSpec, pctx: ParallelContext) -> int:
    """Grad-accum depth: cap per-DP-shard microbatch at
    cfg.max_microbatch_tokens tokens."""
    local_batch = max(1, shape.global_batch // max(pctx.dp_degree, 1))
    tokens = local_batch * shape.seq_len
    mb = max(1, -(-tokens // cfg.max_microbatch_tokens))
    while local_batch % mb:
        mb += 1
    return min(mb, local_batch)


def init_optimizer(cfg: ModelConfig, params):
    if cfg.optimizer == "adafactor":
        return adafactor_init(params)
    return adamw_init(params)


def make_train_step(
    bundle: ModelBundle,
    pctx: ParallelContext,
    hyper: TrainHyper,
) -> Callable:
    """Returns train_step(params, opt_state, batch, step) ->
    (params, opt_state, metrics)."""
    cfg = bundle.cfg

    def train_step(params, opt_state, batch, step):
        lr = warmup_cosine(step, peak_lr=hyper.peak_lr, warmup=hyper.warmup,
                           total=hyper.total_steps)
        nmb = hyper.num_microbatches
        vg = functools.partial(
            compressed_value_and_grad,
            functools.partial(_loss_fn, bundle, pctx),
            pctx=pctx, enabled=hyper.compress_cross_pod,
        )
        if nmb <= 1:
            loss, grads = vg(params, batch)
        else:
            # split batch leading dim into (nmb, b/nmb, ...) and lax.scan
            def split(x):
                b = x.shape[0]
                return x.reshape(nmb, b // nmb, *x.shape[1:])
            mb_batch = {k: split(v) for k, v in batch.items()}

            def accum(carry, mb):
                loss, grads = vg(params, mb)
                acc_loss, acc_grads = carry
                return (acc_loss + loss,
                        jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                     acc_grads, grads)), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(accum, (jnp.zeros(()), zeros), mb_batch)
            loss = loss / nmb
            grads = jax.tree.map(lambda g: g / nmb, grads)

        if cfg.optimizer == "adafactor":
            new_params, new_opt = adafactor_update(grads, opt_state, params, lr)
        else:
            new_params, new_opt = adamw_update(grads, opt_state, lr)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        metrics = {"loss": loss, "lr": lr, "grad_norm": gnorm}
        return new_params, new_opt, metrics

    return train_step


# ---------------------------------------------------------------------------
# Sharding assembly for jit (params / optimizer / batch / cache specs).
# ---------------------------------------------------------------------------


def assemble_shardings(bundle: ModelBundle, pctx: ParallelContext):
    """Returns (param_spec_tree, opt_spec_fn, batch_spec_fn)."""
    cfg = bundle.cfg
    logical = bundle.logical_axes()
    pspecs = param_specs(logical, pctx, kv_heads=cfg.num_kv_heads,
                         fsdp=cfg.fsdp)
    shapes = {k: v.shape for k, v in bundle.abstract_params().items()}

    def opt_specs(opt_state):
        """Mirror param specs onto optimizer leaves, ZeRO-1 sharded."""
        def spec_for_leaf(path_params_spec, shape):
            return zero1_spec(path_params_spec, shape, pctx)

        if cfg.optimizer == "adafactor":
            vr = {k: P(*list(pspecs[k])[:-1]) if len(shapes[k]) >= 2 and
                  shapes[k][-1] > 1 and shapes[k][-2] > 1 else pspecs[k]
                  for k in pspecs}
            vc = {}
            for k in pspecs:
                if len(shapes[k]) >= 2 and shapes[k][-1] > 1 and shapes[k][-2] > 1:
                    entries = list(pspecs[k])
                    vc[k] = P(*(entries[:-2] + entries[-1:]))
                else:
                    vc[k] = P()
            return type(opt_state)(step=P(), vr=vr, vc=vc)
        m = {k: spec_for_leaf(pspecs[k], shapes[k]) for k in pspecs}
        return type(opt_state)(
            step=P(), m=dict(m), v=dict(m),
            master={k: spec_for_leaf(pspecs[k], shapes[k]) for k in pspecs},
        )

    def batch_specs(batch):
        return {k: P(tuple(pctx.dp_axes), *([None] * (v.ndim - 1)))
                for k, v in batch.items()}

    return pspecs, opt_specs, batch_specs


def cache_spec(cfg: ModelConfig, pctx: ParallelContext, cache_abstract):
    """KV-cache / state sharding for serving: batch over DP axes, the cache
    sequence axis over ``model`` (softmax reductions distribute — the
    sharded form of the APR online-softmax accumulator).  State tensors
    (ssm/wkv/conv) shard batch over DP and the inner dim over model."""
    tp = pctx.tp_axis
    dp = tuple(pctx.dp_axes)
    dp_deg = max(pctx.dp_degree, 1)
    tp_deg = max(pctx.tp_degree, 1)

    def spec_for(key: str, leaf) -> P:
        shape = leaf.shape
        nd = len(shape)
        if key in ("k", "v", "self_k", "self_v", "attn_k", "attn_v",
                   "cross_k", "cross_v"):
            # (..., B, S, Hkv, Dh): batch over dp, seq over model; if the
            # batch can't shard (long_500k b=1), the seq axis takes BOTH
            # mesh axis groups — distributed flash-decode over the cache.
            entries = [None] * nd
            b, s = shape[-4], shape[-3]
            if b % dp_deg == 0 and b >= dp_deg:
                entries[-4] = dp
                if s % tp_deg == 0:
                    entries[-3] = tp
            elif s % (dp_deg * tp_deg) == 0:
                entries[-3] = dp + (tp,)
            elif s % tp_deg == 0:
                entries[-3] = tp
            return P(*entries)

        def batch_or_none(idx=1):
            return dp if shape[idx] % dp_deg == 0 and shape[idx] >= dp_deg else None

        if key in ("wkv", "ssm"):   # (L, B, H, D/P, D/N)
            h = shape[2]
            return P(None, batch_or_none(), tp if h % tp_deg == 0 else None,
                     None, None)
        if key == "conv":           # (L, B, K-1, CH)
            ch = shape[-1]
            return P(None, batch_or_none(), None,
                     tp if ch % tp_deg == 0 else None)
        if key in ("tmix_x", "cmix_x"):  # (L, B, d)
            return P(None, batch_or_none(), None)
        return P()

    return {k: spec_for(k, v) for k, v in cache_abstract.items()}


def make_serve_steps(bundle: ModelBundle, pctx: ParallelContext):
    cfg = bundle.cfg

    def prefill_step(params, batch):
        return bundle.prefill(params, batch, pctx)

    def decode_step(params, cache, tokens, lengths):
        return bundle.decode_step(params, cache, tokens, lengths, pctx)

    return prefill_step, decode_step
