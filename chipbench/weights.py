"""Weights from the seed, made by the benchmark itself.

The reference may take nothing that the program made, so the benchmark
makes the weights: every leaf is a pure function of ``(seed, leaf name,
layer)``.  ``params_from_seed`` makes the whole tree on the device in one
jitted call, in bf16 as the model serves it; ``leaf_from_seed`` makes one
layer of one leaf again for the reference, bit for bit the same.

A value is ``offset + half * i / 2**(bits-1)`` for a uniform random
``bits``-bit integer ``i``, rounded once to bf16.  ``half`` is a power of
two, so every step before the rounding is exact and the values do not
depend on how XLA fuses the program.  The family module gives each leaf
its ``(offset, half, bits)`` and says which leaves stack their layers on
axis 0.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Callable, Dict, Tuple

Rule = Tuple[float, float, int]   # (offset, half-width, random bits)


def pow2(x: float) -> float:
    """The power of two nearest to ``x`` (on a log scale)."""
    return 2.0 ** round(math.log2(x))


def root_key(seed: int):
    """PRNG key of a seed of any size up to 62 bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _leaf_key(root, name: str):
    import jax

    return jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _draw(key, shape, rule: Rule):
    import jax
    import jax.numpy as jnp

    offset, half, bits = rule
    if half != pow2(half) or not 1 <= bits <= 16:
        raise ValueError(f"bad weight rule {rule}")
    i = jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, jnp.uint16), jnp.int16)
    if bits < 16:
        i = jax.lax.shift_right_arithmetic(i, jnp.int16(16 - bits))
    u = i.astype(jnp.float32) * jnp.float32(half / 2 ** (bits - 1))
    return (u + jnp.float32(offset)).astype(jnp.bfloat16)


def params_from_seed(specs: Dict[str, tuple], family, seed: int):
    """The whole parameter tree, in one jitted call on the default device."""
    import jax
    import jax.numpy as jnp

    plan = sorted(specs.items())

    def make(root):
        out = {}
        for name, shape in plan:
            key = _leaf_key(root, name)
            if family.stacked(name):
                rule = family.rule(name, shape[1:])
                out[name] = jax.vmap(
                    lambda i, k=key, s=shape[1:], r=rule:
                        _draw(jax.random.fold_in(k, i), s, r))(
                    jnp.arange(shape[0]))
            else:
                out[name] = _draw(key, shape, family.rule(name, shape))
        return out

    return jax.jit(make)(root_key(seed))


@functools.lru_cache(maxsize=None)
def _leaf_fn(shape: tuple, rule: Rule, stacked: bool) -> Callable:
    import jax

    def one(key, layer):
        if stacked:
            key = jax.random.fold_in(key, layer)
        return _draw(key, shape, rule)

    return jax.jit(one)


def leaf_from_seed(root, family, name: str, shape: tuple, layer=None):
    """Layer ``layer`` of leaf ``name`` (``shape`` without the stacked
    axis), as ``params_from_seed`` made it, upcast to float32."""
    import jax.numpy as jnp

    stacked = family.stacked(name)
    if stacked != (layer is not None):
        raise ValueError(f"{name}: stacked={stacked} but layer={layer}")
    fn = _leaf_fn(tuple(shape), family.rule(name, tuple(shape)), stacked)
    return fn(_leaf_key(root, name), jnp.int32(layer or 0)).astype(
        jnp.float32)
