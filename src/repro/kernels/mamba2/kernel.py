"""Mamba-2 (SSD) selective-state-space kernel with the SSM state as APR.

Per head (head dim P, state dim N) with scalar per-head decay:

    h_t = exp(a * dt_t) * h_{t-1} + dt_t * (x_t  B_t^T)     h: (P, N)
    y_t = h_t C_t + D * x_t

``h`` is a decaying accumulator of rank-1 updates — the APR pattern again.
The kernel keeps h in VMEM scratch across time-chunk grid steps; only the
x/B/C/dt chunk streams and y chunks touch HBM.  Time steps run in 8-step
slabs (one aligned load per stream), unrolled within a slab.

Grid: (B, H, T/chunk).
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


def _mamba2_kernel(
    x_ref,    # (chunk, P)
    b_ref,    # (chunk, N)
    c_ref,    # (chunk, N)
    dt_ref,   # (chunk, 1)
    a_ref,    # SMEM (H,)  per-head log-decay (negative)
    d_ref,    # SMEM (H,)  per-head skip
    o_ref,    # (chunk, P)
    h_ref,    # VMEM (N, P)  APR: SSM state, stored transposed
    *,
    chunk: int,
):
    head = pl.program_id(1)
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _reset():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[head]
    d_skip = d_ref[head]
    g = math.gcd(chunk, _SLAB)

    def slab(i, h):
        rows = pl.ds(pl.multiple_of(i * g, g), g)
        x = x_ref[rows, :].astype(jnp.float32)         # (g, P)
        b_t = b_ref[rows, :].astype(jnp.float32).T     # (N, g)
        c_t = c_ref[rows, :].astype(jnp.float32).T     # (N, g)
        # (g, 1) -> (g, P): Mosaic broadcasts one axis at a time, so widen
        # dt along lanes once per slab instead of per (1, 1) element
        dt = jnp.broadcast_to(dt_ref[rows, :].astype(jnp.float32), x.shape)
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        ys = jnp.zeros(x.shape, jnp.float32)
        for j in range(g):
            xj, dtj = x[j:j + 1, :], dt[j:j + 1, :]    # (1, P) each
            h = jnp.exp(a * dtj) * h + b_t[:, j:j + 1] * (dtj * xj)
            y = jnp.sum(h * c_t[:, j:j + 1], axis=0, keepdims=True) \
                + d_skip * xj                          # (1, P)
            ys = jnp.where(row == j, y, ys)
        o_ref[rows, :] = ys.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk // g, slab, h_ref[...])


def mamba2_call(
    x: jax.Array,    # (B, T, H, P)
    b: jax.Array,    # (B, T, N)    shared across heads (Mamba-2 style)
    c: jax.Array,    # (B, T, N)
    dt: jax.Array,   # (B, T, H)
    a: jax.Array,    # (H,)
    d: jax.Array,    # (H,)
    *,
    chunk: int,  # required: chunk choice lives in repro.bench, not here
    interpret: bool = False,
) -> jax.Array:
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    assert t % chunk == 0
    n_chunks = t // chunk

    xh = x.transpose(0, 2, 1, 3)                      # (B, H, T, P)
    bh = jnp.broadcast_to(b[:, None], (bsz, h, t, n))
    ch = jnp.broadcast_to(c[:, None], (bsz, h, t, n))
    dth = dt.transpose(0, 2, 1)[..., None]            # (B, H, T, 1)

    out = pl.pallas_call(
        functools.partial(_mamba2_kernel, chunk=chunk),
        grid=(bsz, h, n_chunks),
        in_specs=[
            pl.BlockSpec((None, None, chunk, p), lambda i, j, cc: (i, j, cc, 0)),
            pl.BlockSpec((None, None, chunk, n), lambda i, j, cc: (i, j, cc, 0)),
            pl.BlockSpec((None, None, chunk, n), lambda i, j, cc: (i, j, cc, 0)),
            pl.BlockSpec((None, None, chunk, 1), lambda i, j, cc: (i, j, cc, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, p), lambda i, j, cc: (i, j, cc, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, t, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xh, bh, ch, dth, a.astype(jnp.float32), d.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3)
