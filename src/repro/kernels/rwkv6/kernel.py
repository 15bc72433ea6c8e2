"""RWKV-6 (Finch) WKV kernel with the recurrent state as APR.

Per head of size D the recurrence is

    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T

with data-dependent per-channel decay ``w_t``.  ``S`` is a (D, D)
accumulator with *decay* — the paper's §I explicitly lists the P-extension
difference-accumulator as a target for the same APR mechanism; a decaying
accumulator is its continuous generalisation.  The kernel keeps S in VMEM
scratch across time-chunk grid steps; HBM sees only r/k/v/w chunk streams in
and y chunks out, never the O(D^2) state.

Grid: (B, H, T/chunk); the chunk loop inside the kernel is a fori_loop over
8-step slabs, unrolled within a slab (the sequential dependency is
fundamental, the state residency is what the APR buys).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: Rows per aligned load: Mosaic loads VMEM in (8, 128) tiles, so a time
#: step is read as one row of an 8-row slab rather than a one-row slice.
_SLAB = 8


def _rwkv6_kernel(
    r_ref,   # (chunk, D)
    k_ref,   # (chunk, D)
    v_ref,   # (chunk, D)
    w_ref,   # (chunk, D)  decay in (0,1)
    u_ref,   # (D, 1)      bonus, as a column
    o_ref,   # (chunk, D)
    s_ref,   # VMEM (D, D) APR: recurrent state
    *,
    chunk: int,
):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _reset():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[...].astype(jnp.float32)                 # (D, 1)
    g = math.gcd(chunk, _SLAB)

    def slab(i, state):
        rows = pl.ds(pl.multiple_of(i * g, g), g)
        v = v_ref[rows, :].astype(jnp.float32)         # (g, D)
        # columns of r/k/w index the state's rows
        r_t = r_ref[rows, :].astype(jnp.float32).T     # (D, g)
        k_t = k_ref[rows, :].astype(jnp.float32).T
        w_t = w_ref[rows, :].astype(jnp.float32).T
        row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        ys = jnp.zeros(v.shape, jnp.float32)
        for j in range(g):
            kv = k_t[:, j:j + 1] * v[j:j + 1, :]       # (D, D) rank-1 update
            y = jnp.sum((state + u * kv) * r_t[:, j:j + 1], axis=0,
                        keepdims=True)                 # (1, D)
            ys = jnp.where(row == j, y, ys)
            state = w_t[:, j:j + 1] * state + kv       # decay + accumulate
        o_ref[rows, :] = ys.astype(o_ref.dtype)
        return state

    s_ref[...] = jax.lax.fori_loop(0, chunk // g, slab, s_ref[...])


def rwkv6_call(
    r: jax.Array,  # (B, T, H, D)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decay in (0,1), same shape
    u: jax.Array,  # (H, D)
    *,
    chunk: int,  # required: chunk choice lives in repro.bench, not here
    interpret: bool = False,
) -> jax.Array:
    b, t, h, d = r.shape
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk

    def bhtd(x):
        return x.transpose(0, 2, 1, 3)  # (B, H, T, D)

    out = pl.pallas_call(
        functools.partial(_rwkv6_kernel, chunk=chunk),
        grid=(b, h, n_chunks),
        in_specs=[
            pl.BlockSpec((None, None, chunk, d), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((None, None, chunk, d), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((None, None, chunk, d), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((None, None, chunk, d), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((None, d, 1), lambda i, j, c: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, d), lambda i, j, c: (i, j, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), r.dtype),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
    )(bhtd(r), bhtd(k), bhtd(v), bhtd(w), u.reshape(h, d, 1))
    return out.transpose(0, 2, 1, 3)  # back to (B, T, H, D)
