"""The plain reference: float32 model math that imports nothing of the
program, and its lower-precision control.

A family module (``families/<family>.py``) lists its layers as
``(kind, {role: (leaf name, layer or None)})`` and gives one function per
kind.  :func:`hidden_states` runs them layer by layer over token
sequences, making each layer's weights again from the seed
(:mod:`chipbench.weights`), so only one layer's float32 weights are held at
a time.  Matrix products run at ``precision=HIGHEST``; with ``quant=True``
every product with a weight matrix runs in int8 instead (weights per
output channel, activations per row, int32 sums): the control, one step
below the bf16 the configurations state.

:func:`gaps` reads, at each served position, by how much the served token's
reference logit lies below the reference's best (and the same for the
token the control puts first).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import weights

HIGHEST = "highest"
#: query rows per block of the reference's attention
Q_BLOCK = 256


def mm(x, w, quant: bool):
    """``x @ w`` in float32, or in int8 with int32 sums for the control."""
    import jax
    import jax.numpy as jnp

    if not quant:
        return jnp.matmul(x, w, precision=HIGHEST)
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 127
    qx = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    qw = jnp.clip(jnp.round(w / sw), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(qx, qw, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def rms_norm(x, w, eps: float):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def rope(x, theta: float, fraction: float):
    """Rotary embedding of positions ``0..T-1`` over the first
    ``fraction`` of the head dim, its two halves rotated as pairs."""
    import jax.numpy as jnp

    t, dh = x.shape[1], x.shape[-1]
    rot = int(dh * fraction) // 2 * 2
    half = rot // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(t, dtype=np.float64)[:, None] * freqs      # (T, half)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def causal_attention(q, k, v):
    """q: (B,T,H,Dh); k, v: (B,T,Hkv,Dh); softmax over keys at or before
    each query, in blocks of ``Q_BLOCK`` queries."""
    import jax
    import jax.numpy as jnp

    b, t, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    outs = []
    for s in range(0, t, Q_BLOCK):
        e = min(s + Q_BLOCK, t)
        qb = q[:, s:e].reshape(b, e - s, hkv, g, dh)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k[:, :e],
                        precision=HIGHEST) / np.sqrt(dh)
        keep = np.arange(e)[None, :] <= np.arange(s, e)[:, None]
        sc = jnp.where(keep, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v[:, :e], precision=HIGHEST)
        outs.append(o.reshape(b, e - s, h * dh))
    return jnp.concatenate(outs, axis=1)


def _fetch(root, family, sizes, roles: Dict[str, tuple]) -> Dict[str, object]:
    specs = family.param_specs(sizes)
    out = {}
    for role, (name, layer) in roles.items():
        shape = specs[name][1:] if layer is not None else specs[name]
        out[role] = weights.leaf_from_seed(root, family, name, shape, layer)
    return out


@functools.lru_cache(maxsize=None)
def _jitted(fn, sizes_items: tuple, quant: bool):
    import jax

    sizes = dict(sizes_items)
    return jax.jit(lambda x, w: fn(x, w, sizes, quant))


def hidden_states(family, sizes: dict, seed: int, tokens: np.ndarray,
                  quants: Sequence[bool] = (False,)):
    """Final hidden states (before the final norm) of ``tokens`` (B, T),
    one array (B, T, d) per entry of ``quants``."""
    import jax.numpy as jnp

    root = weights.root_key(seed)
    emb = _fetch(root, family, sizes, {"embed": ("embed", None)})["embed"]
    x0 = jnp.take(emb, jnp.asarray(tokens), axis=0)
    del emb
    xs = [x0 for _ in quants]
    key = tuple(sorted(sizes.items()))
    for kind, roles in family.layers(sizes):
        w = _fetch(root, family, sizes, roles)
        fn = family.KINDS[kind]
        xs = [_jitted(fn, key, q)(x, w) for x, q in zip(xs, quants)]
        del w
    return xs


def _head(x, w, sizes, quant):
    """Final norm and output projection of rows ``x`` (N, d)."""
    h = rms_norm(x, w["final_norm"], sizes["norm_eps"])
    return mm(h, w["lm_head"], quant)[:, :sizes["vocab_size"]]


#: padded lengths the reference runs a sequence at (then multiples of the
#: last), so each cell compiles a handful of reference programs, all kept
#: in the persistent cache
BUCKETS = (256, 512, 1024, 1536, 2048, 3072, 4096, 6144, 8192)


def bucket(n: int) -> int:
    """The padded length of a sequence of ``n`` tokens."""
    for b in BUCKETS:
        if n <= b:
            return b
    return -(-n // BUCKETS[-1]) * BUCKETS[-1]


def gaps(family, sizes: dict, seed: int, prompts: List[List[int]],
         served: List[List[int]], control: bool = False
         ) -> Tuple[np.ndarray, np.ndarray]:
    """Per served token, the reference's best logit minus the served
    token's logit (``>= 0``).  With ``control``, also the same gap of the
    token the int8 control puts first at each position (else empty).
    One sequence at a time, padded to its :func:`bucket`; output rows in
    blocks of ``Q_BLOCK``."""
    import jax.numpy as jnp

    quants = (False, True) if control else (False,)
    root = weights.root_key(seed)
    w = _fetch(root, family, sizes, {"final_norm": ("final_norm", None),
                                     "lm_head": ("lm_head", None)})
    w["final_norm"] = w["final_norm"] + 1.0
    key = tuple(sorted(sizes.items()))
    ref_gap, ctl_gap = [], []
    for prompt, out in zip(prompts, served):
        seq = prompt + out
        tokens = np.zeros((1, bucket(len(seq))), np.int32)
        tokens[0, :len(seq)] = seq
        xs = [x[0] for x in hidden_states(family, sizes, seed, tokens, quants)]
        # the logits at position p score the token at p + 1
        pos = np.arange(len(prompt) - 1, len(seq) - 1)
        for s in range(0, len(pos), Q_BLOCK):
            n = min(Q_BLOCK, len(pos) - s)
            rows = np.pad(pos[s:s + n], (0, Q_BLOCK - n), mode="edge")
            want = np.pad(np.asarray(out[s:s + n]), (0, Q_BLOCK - n),
                          mode="edge")
            ref = _jitted(_head, key, False)(xs[0][rows], w)
            best = jnp.max(ref, axis=-1)
            got = jnp.take_along_axis(ref, jnp.asarray(want)[:, None],
                                      axis=-1)[:, 0]
            ref_gap.append(np.asarray(best - got)[:n])
            if control:
                pick = jnp.argmax(_jitted(_head, key, True)(xs[1][rows], w),
                                  axis=-1)
                ctl_gap.append(np.asarray(best - jnp.take_along_axis(
                    ref, pick[:, None], axis=-1)[:, 0])[:n])
    return (np.concatenate(ref_gap),
            np.concatenate(ctl_gap) if control else np.zeros(0))
