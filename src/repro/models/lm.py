"""Decoder-only LM covering the dense / moe / vlm families.

Layers are scanned (stacked params, one compiled body) in super-blocks of
``cfg.moe_every`` layers so MoE interleaving (llama4: every 2nd layer) stays
homogeneous under ``lax.scan``; the dry-run can also unroll
(``scan_layers=False``) for cost-analysis extrapolation.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..parallel.sharding import ParallelContext
from .layers import (ParamBuilder, Params, attention, attention_decode,
                     attention_decode_paged, attn_params, current_tp_axis,
                     mask_vocab_logits, materialize_weight, rms_norm, swiglu)
from .moe import moe_block, moe_params


def _lm_head(params: Params, rest: Params, cfg: ModelConfig,
             x: jax.Array) -> jax.Array:
    """Final projection; tied embeddings stay full precision (the embedding
    is gathered per token on the way in), an untied lm_head may be an int8
    :class:`~repro.quant.QuantizedTensor`.

    Inside a manual-TP region (repro.parallel.tp) an untied lm_head arrives
    vocab-sharded: each shard's einsum emits its own logit columns (no
    cross-shard reduction — vocab is an *output* dim, so the columns are
    bit-identical to the unsharded ones) and an all_gather reassembles the
    full vocab before padded-slot masking."""
    head = rest.get("lm_head")
    if head is None:
        head = params["embed"].T
    else:
        head = materialize_weight(head, x.dtype)
    logits = jnp.einsum("btd,dv->btv", x, head)
    axis = current_tp_axis()
    if axis is not None and logits.shape[-1] != cfg.padded_vocab:
        logits = jax.lax.all_gather(logits, axis, axis=-1, tiled=True)
    return mask_vocab_logits(logits, cfg.vocab_size)


def mlp_params(pb: ParamBuilder, prefix: str, cfg: ModelConfig, layers: Optional[int]):
    d, ff = cfg.d_model, cfg.d_ff
    lead = () if layers is None else (layers,)
    llog = () if layers is None else ("layers",)
    pb.param(f"{prefix}.w_gate", lead + (d, ff), llog + ("embed", "ff"))
    pb.param(f"{prefix}.w_up", lead + (d, ff), llog + ("embed", "ff"))
    pb.param(f"{prefix}.w_down", lead + (ff, d), llog + ("ff", "embed"))


def build_params(cfg: ModelConfig) -> ParamBuilder:
    pb = ParamBuilder(dtype=jnp.bfloat16)
    d = cfg.d_model
    pb.param("embed", (cfg.padded_vocab, d), ("vocab", "embed"), scale=0.02)
    me = max(cfg.moe_every, 1) if cfg.num_experts else 1
    n_sb = cfg.num_layers // me
    n_dense = me - 1 if cfg.num_experts else me
    # attention + norms for every layer: stacked (n_sb, me, ...)
    for j in range(me):
        attn_params(pb, f"blk.{j}.attn", cfg, n_sb)
        pb.param(f"blk.{j}.ln1", (n_sb, d), ("layers", None), scale=0.0)
        pb.param(f"blk.{j}.ln2", (n_sb, d), ("layers", None), scale=0.0)
        if cfg.num_experts and j == me - 1:
            moe_params(pb, f"blk.{j}.moe", cfg, n_sb)
        else:
            mlp_params(pb, f"blk.{j}.mlp", cfg, n_sb)
    pb.param("final_norm", (d,), (None,), scale=0.0)
    if not cfg.tie_embeddings:
        pb.param("lm_head", (d, cfg.padded_vocab), ("embed", "vocab"))
    return pb


def _split_block_params(p: Params) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    blk = {k: v for k, v in p.items() if k.startswith("blk.")}
    rest = {k: v for k, v in p.items() if not k.startswith("blk.")}
    return blk, rest


def _sub(p: Params, j: int, name: str) -> Params:
    pre = f"blk.{j}.{name}"
    return {k[len(f"blk.{j}."):]: v for k, v in p.items() if k.startswith(pre)}


def _super_block(cfg: ModelConfig, pctx: ParallelContext, x, blk_p, positions):
    """One scanned unit: ``moe_every`` transformer layers."""
    me = max(cfg.moe_every, 1) if cfg.num_experts else 1
    for j in range(me):
        lp = {k[len(f"blk.{j}."):]: v for k, v in blk_p.items()
              if k.startswith(f"blk.{j}.")}
        h = rms_norm(x, lp["ln1"] + 1.0, cfg.norm_eps)
        x = x + attention(lp, "attn", cfg, h, positions=positions, causal=True)
        h = rms_norm(x, lp["ln2"] + 1.0, cfg.norm_eps)
        if cfg.num_experts and j == me - 1:
            x = x + moe_block(lp, "moe", cfg, h, pctx)
        else:
            x = x + swiglu(h, lp["mlp.w_gate"], lp["mlp.w_up"], lp["mlp.w_down"], cfg)
    return x


def _remat_policy(cfg):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy == "save_coll":
        return jax.checkpoint_policies.save_only_these_names("moe_a2a")
    return jax.checkpoint_policies.nothing_saveable


def _run_blocks(cfg, pctx, x, blk, positions, *, scan_layers: bool, remat: bool):
    body = functools.partial(_super_block, cfg, pctx)
    if remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    me = max(cfg.moe_every, 1) if cfg.num_experts else 1
    n_sb = cfg.num_layers // me
    if scan_layers:
        def scan_body(carry, layer_p):
            return body(carry, layer_p, positions), None
        x, _ = jax.lax.scan(scan_body, x, blk)
    else:
        for i in range(n_sb):
            x = body(x, jax.tree.map(lambda a: a[i], blk), positions)
    return x


def lm_forward(
    params: Params,
    cfg: ModelConfig,
    pctx: ParallelContext,
    tokens: jax.Array,                       # (B, S_text)
    *,
    prefix_embeds: Optional[jax.Array] = None,  # (B, Nv, d) vlm/audio stubs
    scan_layers: bool = True,
) -> jax.Array:
    """Returns logits (B, S_total, V)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    blk, rest = _split_block_params(params)
    x = _run_blocks(cfg, pctx, x, blk, positions,
                    scan_layers=scan_layers, remat=cfg.remat)
    x = rms_norm(x, rest["final_norm"] + 1.0, cfg.norm_eps)
    return _lm_head(params, rest, cfg, x)


# ---------------------------------------------------------------------------
# Serving: prefill (build cache) + single-token decode.
# ---------------------------------------------------------------------------


def init_cache_abstract(cfg: ModelConfig, batch: int, max_seq: int):
    me = max(cfg.moe_every, 1) if cfg.num_experts else 1
    n_sb = cfg.num_layers // me
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (n_sb, me, batch, max_seq, hkv, dh)
    return {
        "k": jax.ShapeDtypeStruct(shape, jnp.bfloat16),
        "v": jax.ShapeDtypeStruct(shape, jnp.bfloat16),
    }


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        init_cache_abstract(cfg, batch, max_seq))


def lm_decode_step(
    params: Params,
    cfg: ModelConfig,
    pctx: ParallelContext,
    cache: Dict[str, jax.Array],
    tokens: jax.Array,        # (B, 1)
    lengths: jax.Array,       # (B,)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x = jnp.take(params["embed"], tokens, axis=0)
    blk, rest = _split_block_params(params)
    me = max(cfg.moe_every, 1) if cfg.num_experts else 1

    def scan_body(carry, xs):
        x = carry
        blk_p, kc_blk, vc_blk = xs
        new_k, new_v = [], []
        for j in range(me):
            lp = {k[len(f"blk.{j}."):]: v for k, v in blk_p.items()
                  if k.startswith(f"blk.{j}.")}
            h = rms_norm(x, lp["ln1"] + 1.0, cfg.norm_eps)
            attn_out, k_new, v_new = attention_decode(
                lp, "attn", cfg, h, kc_blk[j], vc_blk[j], lengths
            )
            new_k.append(k_new)
            new_v.append(v_new)
            x = x + attn_out
            h = rms_norm(x, lp["ln2"] + 1.0, cfg.norm_eps)
            if cfg.num_experts and j == me - 1:
                x = x + moe_block(lp, "moe", cfg, h, pctx)
            else:
                x = x + swiglu(h, lp["mlp.w_gate"], lp["mlp.w_up"], lp["mlp.w_down"], cfg)
        return x, (jnp.stack(new_k), jnp.stack(new_v))

    if cfg.scan_layers:
        x, (k_upd, v_upd) = jax.lax.scan(scan_body, x, (blk, cache["k"], cache["v"]))
    else:  # unrolled (cost-extrapolation dry-run compiles)
        n_sb = cfg.num_layers // me
        ys = []
        for i in range(n_sb):
            x, y = scan_body(x, jax.tree.map(lambda a: a[i],
                                             (blk, cache["k"], cache["v"])))
            ys.append(y)
        k_upd = jnp.stack([y[0] for y in ys])
        v_upd = jnp.stack([y[1] for y in ys])
    x = rms_norm(x, rest["final_norm"] + 1.0, cfg.norm_eps)
    logits = _lm_head(params, rest, cfg, x)
    return logits, {"k": k_upd, "v": v_upd}


# ---------------------------------------------------------------------------
# Paged serving: shared page pool + block tables instead of (B, max_seq).
# ---------------------------------------------------------------------------


def init_paged_cache_abstract(cfg: ModelConfig, pool_pages: int,
                              page_size: int, kv_dtype: str = "bfloat16"):
    """Per-layer KV page pools.  Unlike :func:`init_cache_abstract` there is
    no batch axis: slots own disjoint page subsets via block tables (one
    int32 table shared by every layer), so total KV memory scales with the
    *live* token count, not slots x max_seq.

    ``kv_dtype="int8"`` halves the pool footprint vs bf16: pages hold int8
    payloads and two extra fp32 scale pools carry one symmetric scale per
    (page slot, kv head) — written together with the payload so a slot is
    always self-consistent (see ``docs/quantization.md``)."""
    me = max(cfg.moe_every, 1) if cfg.num_experts else 1
    n_sb = cfg.num_layers // me
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (n_sb, me, pool_pages, page_size, hkv, dh)
    if kv_dtype == "int8":
        sshape = shape[:-1]
        return {
            "k": jax.ShapeDtypeStruct(shape, jnp.int8),
            "v": jax.ShapeDtypeStruct(shape, jnp.int8),
            "k_scale": jax.ShapeDtypeStruct(sshape, jnp.float32),
            "v_scale": jax.ShapeDtypeStruct(sshape, jnp.float32),
        }
    return {
        "k": jax.ShapeDtypeStruct(shape, jnp.dtype(kv_dtype)),
        "v": jax.ShapeDtypeStruct(shape, jnp.dtype(kv_dtype)),
    }


def init_paged_cache(cfg: ModelConfig, pool_pages: int, page_size: int,
                     kv_dtype: str = "bfloat16"):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        init_paged_cache_abstract(cfg, pool_pages, page_size,
                                                  kv_dtype))


def lm_decode_paged(
    params: Params,
    cfg: ModelConfig,
    pctx: ParallelContext,
    cache: Dict[str, jax.Array],
    tokens: jax.Array,        # (B, T) new tokens; T=1 decode, T=chunk prefill
    lengths: jax.Array,       # (B,) tokens already cached per slot
    new_counts: jax.Array,    # (B,) real new tokens this call (<= T)
    block_tables: jax.Array,  # (B, P_max)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Multi-token decode/prefill step over the paged cache.

    Returns logits ``(B, T, V)`` — the caller reads row ``new_counts[b]-1``
    of slot ``b`` for the next-token distribution and ignores padded rows.

    Three callers share this one contract: the decode tick (``T = 1``),
    chunked prefill (``T = prefill_chunk``), and speculative verification
    (``T = spec_k + 1``, ``repro.spec``) — a verify step is just a "prefill
    chunk" of candidate tokens whose logits are *all* read back (row ``i``
    is the target's next-token distribution after candidate ``i``), with the
    rejected suffix rolled back host-side via ``PagedKVCache.truncate``.
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    blk, rest = _split_block_params(params)
    me = max(cfg.moe_every, 1) if cfg.num_experts else 1
    quantized_kv = "k_scale" in cache  # int8 page pools carry scale pools

    def scan_body(carry, xs):
        x = carry
        if quantized_kv:
            blk_p, kc_blk, vc_blk, ks_blk, vs_blk = xs
        else:
            blk_p, kc_blk, vc_blk = xs
        new = []
        for j in range(me):
            lp = {k[len(f"blk.{j}."):]: v for k, v in blk_p.items()
                  if k.startswith(f"blk.{j}.")}
            scales = ({"k_scales": ks_blk[j], "v_scales": vs_blk[j]}
                      if quantized_kv else {})
            # named scopes mark the device trace's ops; code is unchanged
            with jax.named_scope("attn"):
                h = rms_norm(x, lp["ln1"] + 1.0, cfg.norm_eps)
                attn_out, *upd = attention_decode_paged(
                    lp, "attn", cfg, h, kc_blk[j], vc_blk[j],
                    lengths, new_counts, block_tables, **scales
                )
            new.append(upd)
            x = x + attn_out
            with jax.named_scope("mlp"):
                h = rms_norm(x, lp["ln2"] + 1.0, cfg.norm_eps)
                if cfg.num_experts and j == me - 1:
                    x = x + moe_block(lp, "moe", cfg, h, pctx)
                else:
                    x = x + swiglu(h, lp["mlp.w_gate"], lp["mlp.w_up"],
                                   lp["mlp.w_down"], cfg)
        # transpose [per-layer][field] -> per-field stacks (k, v[, ks, vs])
        return x, tuple(jnp.stack([u[f] for u in new])
                        for f in range(len(new[0])))

    if quantized_kv:
        xs = (blk, cache["k"], cache["v"], cache["k_scale"], cache["v_scale"])
    else:
        xs = (blk, cache["k"], cache["v"])
    if cfg.scan_layers:
        x, upd = jax.lax.scan(scan_body, x, xs)
    else:
        n_sb = cfg.num_layers // me
        ys = []
        for i in range(n_sb):
            x, y = scan_body(x, jax.tree.map(lambda a: a[i], xs))
            ys.append(y)
        upd = tuple(jnp.stack([y[f] for y in ys]) for f in range(len(ys[0])))
    with jax.named_scope("lm_head"):
        x = rms_norm(x, rest["final_norm"] + 1.0, cfg.norm_eps)
        logits = _lm_head(params, rest, cfg, x)
    new_cache = {"k": upd[0], "v": upd[1]}
    if quantized_kv:
        new_cache["k_scale"], new_cache["v_scale"] = upd[2], upd[3]
    return logits, new_cache


def lm_prefill(
    params: Params,
    cfg: ModelConfig,
    pctx: ParallelContext,
    tokens: jax.Array,         # (B, S)
    max_seq: Optional[int] = None,
    prefix_embeds: Optional[jax.Array] = None,
    scan_layers: bool = True,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Forward pass that also returns the populated KV cache."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    max_seq = max_seq or s
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    blk, rest = _split_block_params(params)
    me = max(cfg.moe_every, 1) if cfg.num_experts else 1

    from .layers import project_qkv, gqa_scores_attend, tp_einsum

    def scan_body(carry, blk_p):
        x = carry
        ks, vs = [], []
        for j in range(me):
            lp = {k[len(f"blk.{j}."):]: v for k, v in blk_p.items()
                  if k.startswith(f"blk.{j}.")}
            h = rms_norm(x, lp["ln1"] + 1.0, cfg.norm_eps)
            q, k, v = project_qkv(lp, "attn", cfg, h, positions)
            mask = jnp.tril(jnp.ones((s, s), bool))[None, None, None]
            o = gqa_scores_attend(q, k, v, mask)
            x = x + tp_einsum("btk,kd->btd", o, lp["attn.wo"])
            pad = max_seq - s
            ks.append(jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(jnp.bfloat16))
            vs.append(jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(jnp.bfloat16))
            h = rms_norm(x, lp["ln2"] + 1.0, cfg.norm_eps)
            if cfg.num_experts and j == me - 1:
                x = x + moe_block(lp, "moe", cfg, h, pctx)
            else:
                x = x + swiglu(h, lp["mlp.w_gate"], lp["mlp.w_up"], lp["mlp.w_down"], cfg)
        return x, (jnp.stack(ks), jnp.stack(vs))

    if scan_layers:
        x, (k_all, v_all) = jax.lax.scan(scan_body, x, blk)
    else:
        n_sb = cfg.num_layers // me
        outs = []
        for i in range(n_sb):
            x, o = scan_body(x, jax.tree.map(lambda a: a[i], blk))
            outs.append(o)
        k_all = jnp.stack([o[0] for o in outs])
        v_all = jnp.stack([o[1] for o in outs])
    x = rms_norm(x, rest["final_norm"] + 1.0, cfg.norm_eps)
    logits = _lm_head(params, rest, cfg, x[:, -1:])
    return logits, {"k": k_all, "v": v_all}
