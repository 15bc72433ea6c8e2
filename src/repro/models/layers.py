"""Shared transformer building blocks (functional, framework-free).

Parameters are plain dicts of jax.Arrays; every creation site registers a
logical-axis tuple alongside the shape so `repro.parallel.sharding` can map
the whole tree to PartitionSpecs without name guessing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..quant.quantize import QuantizedTensor, quantize_channelwise

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Manual tensor-parallel region marker.
#
# Fully-manual shard_map regions (see repro.parallel.tp) must place their
# own collectives: GSPMD never sees the region, so nobody inserts the all-reduce
# that completes a contraction whose reduced dim is sharded.  The region
# body wraps its model call in :func:`manual_tp`, and :func:`tp_einsum`
# (the one designated reduction site) psums over the named axis.  Because
# the context is entered inside the traced body, it is active on every
# (re)trace regardless of when jit decides to compile.
# ---------------------------------------------------------------------------

_TP_AXIS_STACK: list = [(None, 1)]


@contextlib.contextmanager
def manual_tp(axis: Optional[str], degree: int = 1):
    """Mark the enclosed trace as a manual-TP region: every
    :func:`tp_einsum` contraction all-reduces its partial sums over the
    ``degree``-sized mesh axis ``axis``.  The caller guarantees that *all*
    tp_einsum contraction dims in scope are actually sharded over ``axis``
    (see repro.parallel.tp).  ``manual_tp(None)`` masks any enclosing
    region (a fresh shard_map body with nothing sharded inside)."""
    _TP_AXIS_STACK.append((axis, degree) if axis is not None else (None, 1))
    try:
        yield
    finally:
        _TP_AXIS_STACK.pop()


def current_tp_axis() -> Optional[str]:
    """The active manual-TP mesh axis, or None outside any region."""
    return _TP_AXIS_STACK[-1][0]


def materialize_weight(w: Any, dtype) -> jax.Array:
    """Weight entries in a params dict are plain arrays or int8
    :class:`~repro.quant.QuantizedTensor`s (`repro.quant.quantize_params`);
    every einsum site goes through here so both load transparently.  XLA
    fuses the dequant multiply into the consumer, so the weight crosses HBM
    at 1 byte/element — the bandwidth win `docs/quantization.md` measures.
    """
    if isinstance(w, QuantizedTensor):
        return w.dequantize(dtype)
    return w


# ---------------------------------------------------------------------------
# Parameter builder: params pytree + parallel logical-axes pytree.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_param(key, shape, scale, dtype) -> jax.Array:
    if scale == 0.0:
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class ParamBuilder:
    """Collects (init, logical_axes) pairs; materialises with a key or
    abstractly (ShapeDtypeStruct) for the dry-run."""

    def __init__(self, dtype=jnp.bfloat16):
        self.dtype = dtype
        self._defs: Dict[str, Tuple[Tuple[int, ...], Tuple[Optional[str], ...], float]] = {}

    def param(self, name, shape, logical, scale=None):
        assert name not in self._defs, name
        assert len(shape) == len(logical), (name, shape, logical)
        if scale is None:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            scale = fan_in ** -0.5
        self._defs[name] = (tuple(shape), tuple(logical), float(scale))
        return name

    def build(self, key) -> Params:
        """Materialise every parameter on the default device.  Each one is
        drawn by its own jitted program, so the normal -> scale -> cast
        chain fuses and the float32 draw is never held whole: peak memory
        is the finished parameters (a 12.5 GB bf16 model inits on a 16 GB
        chip)."""
        out = {}
        names = sorted(self._defs)
        keys = jax.random.split(key, max(len(names), 1))
        for k, name in zip(keys, names):
            shape, _, scale = self._defs[name]
            out[name] = _init_param(k, shape, scale, self.dtype)
        return out

    def abstract(self) -> Params:
        return {
            name: jax.ShapeDtypeStruct(shape, self.dtype)
            for name, (shape, _, _) in self._defs.items()
        }

    def logical_axes(self) -> Dict[str, Tuple[Optional[str], ...]]:
        return {name: logical for name, (_, logical, _) in self._defs.items()}


# ---------------------------------------------------------------------------
# Primitive layers.
# ---------------------------------------------------------------------------


def mask_vocab_logits(logits: jax.Array, vocab_size: int) -> jax.Array:
    """Neutralise padded vocab slots (see ModelConfig.padded_vocab)."""
    if logits.shape[-1] == vocab_size:
        return logits
    live = jnp.arange(logits.shape[-1]) < vocab_size
    return jnp.where(live, logits, jnp.asarray(-1e30, logits.dtype))


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * w


def rope(
    x: jax.Array,            # (..., T, H, Dh)
    positions: jax.Array,    # (..., T)
    theta: float,
    fraction: float = 1.0,
) -> jax.Array:
    """Rotary embedding over the first ``fraction`` of the head dim
    (chatglm3's 2d-RoPE rotates half the dimensions)."""
    dh = x.shape[-1]
    rot = int(dh * fraction) // 2 * 2
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # positions: (..., T) -> angles (..., T, 1, half), broadcast over heads
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = xr[..., :half], xr[..., half:]
    xr = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([xr.astype(x.dtype), xp], axis=-1)


#: canonical split-K granularity for :func:`tp_einsum` — the contraction
#: dim is always cut into (up to) this many equal slices, 1 device or N
TP_CHUNKS = 4


def _tp_contract_axes(spec: str) -> Tuple[int, int]:
    """Axis of the (single) contracted letter in each einsum operand."""
    ins, out = spec.split("->")
    a, b = ins.split(",")
    shared = [c for c in a if c in b and c not in out]
    assert len(shared) == 1, spec
    return a.index(shared[0]), b.index(shared[0])


def _tp_chunks(k: int) -> int:
    """Canonical chunk count for a global contraction length ``k``."""
    for c in (TP_CHUNKS, 2):
        if k % c == 0:
            return c
    return 1


def _tree_sum(parts):
    """Balanced-binary-tree sum — the one canonical association order."""
    while len(parts) > 1:
        parts = [parts[i] + parts[i + 1] if i + 1 < len(parts) else parts[i]
                 for i in range(0, len(parts), 2)]
    return parts[0]


def tp_einsum(spec: str, x: jax.Array, w: jax.Array, cfg=None) -> jax.Array:
    """Einsum whose contraction dim is TP-sharded (partial sums cross the
    ``model`` axis).  With cfg.bf16_reduce the partials are bf16 so the
    all-reduce moves half the bytes (§Perf iteration 1); default keeps f32
    partials (paper-faithful baseline).

    The arithmetic is *canonical split-K*: the global contraction dim is
    cut into :data:`TP_CHUNKS` equal slices, each slice reduced by its own
    wide-accumulator gemm, and the per-slice partials combined by one
    balanced binary tree, rounding to ``x.dtype`` once at the end.  A
    1-device trace and a :func:`manual_tp` region at any degree dividing
    TP_CHUNKS execute the *same* gemm shapes in the *same* association
    order (the region all_gathers its slice partials in global order
    instead of summing a longer local contraction), so mesh outputs are
    bit-identical to 1-device outputs — the property the engine-identity
    suite and BENCH_parallel gate on.  Degrees not dividing TP_CHUNKS
    still reduce correctly (one gemm per shard, tree over N partials) but
    only match 1-device up to float associativity.

    The optimization_barrier pins both operands at their storage dtype:
    without it XLA's excess-precision pass may strip an upstream
    ``f32 -> bf16 -> f32`` round-trip (feeding the gemm *unrounded* f32
    activations) in one program but not the other, which breaks the
    bit-identity the canonical chunking otherwise guarantees."""
    w = materialize_weight(w, x.dtype)
    x, w = jax.lax.optimization_barrier((x, w))
    axis, degree = _TP_AXIS_STACK[-1]
    acc = (jnp.bfloat16 if cfg is not None and getattr(cfg, "bf16_reduce", False)
           else jnp.float32)
    xk, wk = _tp_contract_axes(spec)
    k_local = x.shape[xk]
    c_global = _tp_chunks(k_local * degree)
    c_local = c_global // degree if c_global % degree == 0 else 1
    step = k_local // c_local
    parts = [
        jnp.einsum(spec,
                   jax.lax.slice_in_dim(x, i * step, (i + 1) * step, axis=xk),
                   jax.lax.slice_in_dim(w, i * step, (i + 1) * step, axis=wk),
                   preferred_element_type=acc)
        for i in range(c_local)
    ]
    if axis is not None:
        # (c_local, ...) local partials -> (degree*c_local, ...) global
        # partials, in global slice order (shard i holds slices
        # [i*c_local, (i+1)*c_local) of the contraction dim)
        gathered = jax.lax.all_gather(jnp.stack(parts), axis, axis=0,
                                      tiled=True)
        parts = [gathered[i] for i in range(degree * c_local)]
    return _tree_sum(parts).astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
           cfg=None) -> jax.Array:
    g = jnp.einsum("btd,df->btf", x, materialize_weight(w_gate, x.dtype))
    u = jnp.einsum("btd,df->btf", x, materialize_weight(w_up, x.dtype))
    return tp_einsum("btf,fd->btd", jax.nn.silu(g) * u, w_down, cfg)


# ---------------------------------------------------------------------------
# GQA attention (full / causal / cached decode).
# ---------------------------------------------------------------------------


def attn_params(pb: ParamBuilder, prefix: str, cfg: ModelConfig, layers: Optional[int]):
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    lead = () if layers is None else (layers,)
    llog = () if layers is None else ("layers",)
    pb.param(f"{prefix}.wq", lead + (d, h * dh), llog + ("embed", "heads"))
    pb.param(f"{prefix}.wk", lead + (d, hkv * dh), llog + ("embed", "kv_heads"))
    pb.param(f"{prefix}.wv", lead + (d, hkv * dh), llog + ("embed", "kv_heads"))
    pb.param(f"{prefix}.wo", lead + (h * dh, d), llog + ("heads", "embed"))


def project_qkv(p: Params, prefix: str, cfg: ModelConfig, x: jax.Array,
                positions: Optional[jax.Array], apply_rope: bool = True):
    b, t, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    wq = materialize_weight(p[f"{prefix}.wq"], x.dtype)
    wk = materialize_weight(p[f"{prefix}.wk"], x.dtype)
    wv = materialize_weight(p[f"{prefix}.wv"], x.dtype)
    q = jnp.einsum("btd,dk->btk", x, wq).reshape(b, t, h, dh)
    k = jnp.einsum("btd,dk->btk", x, wk).reshape(b, t, hkv, dh)
    v = jnp.einsum("btd,dk->btk", x, wv).reshape(b, t, hkv, dh)
    if apply_rope and positions is not None:
        q = rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    return q, k, v


def gqa_scores_attend(q, k, v, mask) -> jax.Array:
    """q: (B,T,H,Dh); k/v: (B,S,Hkv,Dh); mask broadcastable to (B,H,T,S)."""
    b, t, h, dh = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, t, hkv, g, dh)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k).astype(jnp.float32)
    scores = scores * (dh ** -0.5)
    if mask is not None:  # mask broadcastable to (B, Hkv, G, T, S)
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, v)
    return out.reshape(b, t, h * dh)


def attention(
    p: Params,
    prefix: str,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
    apply_rope: bool = True,
) -> jax.Array:
    """Self (or cross, via kv_override) attention for train/prefill."""
    b, t, _ = x.shape
    q, k, v = project_qkv(p, prefix, cfg, x, positions, apply_rope)
    if kv_override is not None:
        k, v = kv_override
    mask = None
    if causal and kv_override is None:
        mask = jnp.tril(jnp.ones((t, t), bool))[None, None, None]
    out = gqa_scores_attend(q, k, v, mask)
    return tp_einsum("btk,kd->btd", out, p[f"{prefix}.wo"], cfg)


def attention_decode(
    p: Params,
    prefix: str,
    cfg: ModelConfig,
    x: jax.Array,              # (B, 1, d)
    k_cache: jax.Array,        # (B, S, Hkv, Dh) — may be seq-sharded
    v_cache: jax.Array,
    lengths: jax.Array,        # (B,) tokens already in cache
    *,
    apply_rope: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention against (and update of) the KV cache.

    The softmax runs over the cache's sequence axis; when that axis is
    sharded (decode_32k / long_500k shard it over "model"), GSPMD lowers the
    max/sum reductions to all-reduces — the distributed form of the APR
    online-softmax accumulator (see kernels/flash_decode for the TPU-kernel
    form the serving path uses on real hardware).
    """
    b = x.shape[0]
    s = k_cache.shape[1]
    q, k_new, v_new = project_qkv(p, prefix, cfg, x, lengths[:, None], apply_rope)
    idx = lengths  # scatter position per sequence
    bidx = jnp.arange(b)
    k_cache = k_cache.at[bidx, idx].set(k_new[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, idx].set(v_new[:, 0].astype(v_cache.dtype))
    mask = (jnp.arange(s)[None] <= lengths[:, None])[:, None, None, None, :]
    out = gqa_scores_attend(q, k_cache, v_cache, mask)
    return tp_einsum("btk,kd->btd", out, p[f"{prefix}.wo"], cfg), k_cache, v_cache


def attention_decode_paged(
    p: Params,
    prefix: str,
    cfg: ModelConfig,
    x: jax.Array,              # (B, T, d) — T new tokens per slot
    k_pages: jax.Array,        # (P_pool, page_size, Hkv, Dh) shared pool
    v_pages: jax.Array,
    lengths: jax.Array,        # (B,) tokens already in the cache per slot
    new_counts: jax.Array,     # (B,) real new tokens this call (<= T)
    block_tables: jax.Array,   # (B, P_max) physical page per logical page
    *,
    k_scales: Optional[jax.Array] = None,  # (P_pool, page_size, Hkv) fp32
    v_scales: Optional[jax.Array] = None,  # when the page pools are int8
    apply_rope: bool = True,
):
    """Multi-token attention against (and update of) a *paged* KV cache.

    One function covers both serve paths: ``T == 1`` is the decode step,
    ``T == chunk`` is one chunked-prefill step.  Token ``i`` of slot ``b``
    sits at logical position ``lengths[b] + i`` and is scattered to physical
    page ``block_tables[b, pos // page_size]``, offset ``pos % page_size``.
    Positions ``i >= new_counts[b]`` are padding (short final prefill chunk,
    or an idle slot with ``new_counts == 0``): their writes are routed to
    the reserved null page 0 so they can never corrupt a live page, and
    their query rows return garbage the caller must ignore.

    With ``k_scales``/``v_scales`` the page pools are **int8**: each new
    (token, head) vector is quantized symmetrically over the head dim at
    write time and its fp32 scale scattered to the matching page slot, so a
    page slot is always self-consistent (no requantization of old entries,
    writes stay idempotent); the gather dequantizes before the softmax.
    Returns ``(out, k_pages, v_pages)`` — plus the updated scale pools when
    quantized.
    """
    b, t, _ = x.shape
    ps = k_pages.shape[1]
    positions = lengths[:, None] + jnp.arange(t)[None]         # (B, T)
    q, k_new, v_new = project_qkv(p, prefix, cfg, x, positions, apply_rope)
    write = jnp.arange(t)[None] < new_counts[:, None]          # (B, T)
    page_idx = jnp.minimum(positions // ps, block_tables.shape[1] - 1)
    bidx = jnp.arange(b)[:, None]
    pids = jnp.where(write, block_tables[bidx, page_idx], 0)
    offs = jnp.where(write, positions % ps, 0)
    quantized = k_scales is not None
    if quantized:
        kq = quantize_channelwise(k_new, axis=-1)   # per (token, head)
        vq = quantize_channelwise(v_new, axis=-1)
        k_pages = k_pages.at[pids, offs].set(kq.q)
        v_pages = v_pages.at[pids, offs].set(vq.q)
        k_scales = k_scales.at[pids, offs].set(kq.scale[..., 0])
        v_scales = v_scales.at[pids, offs].set(vq.scale[..., 0])
    else:
        k_pages = k_pages.at[pids, offs].set(k_new.astype(k_pages.dtype))
        v_pages = v_pages.at[pids, offs].set(v_new.astype(v_pages.dtype))
    # logical contiguous view: (B, P_max * page_size, Hkv, Dh)
    k_all = jnp.take(k_pages, block_tables, axis=0).reshape(
        b, -1, *k_pages.shape[2:])
    v_all = jnp.take(v_pages, block_tables, axis=0).reshape(
        b, -1, *v_pages.shape[2:])
    if quantized:  # dequantize the gathered view before the softmax
        ks_all = jnp.take(k_scales, block_tables, axis=0).reshape(
            b, -1, k_scales.shape[2])
        vs_all = jnp.take(v_scales, block_tables, axis=0).reshape(
            b, -1, v_scales.shape[2])
        k_all = (k_all.astype(jnp.float32) * ks_all[..., None]).astype(x.dtype)
        v_all = (v_all.astype(jnp.float32) * vs_all[..., None]).astype(x.dtype)
    s = k_all.shape[1]
    # causal within the chunk: query i sees logical positions <= lengths + i
    mask = (jnp.arange(s)[None, None] <= positions[:, :, None])[:, None, None]
    out = gqa_scores_attend(q, k_all, v_all, mask)
    out = tp_einsum("btk,kd->btd", out, p[f"{prefix}.wo"], cfg)
    if quantized:
        return out, k_pages, v_pages, k_scales, v_scales
    return out, k_pages, v_pages
