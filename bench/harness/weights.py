"""Weights from the seed, made by the benchmark in one jitted call on
the device, in the type they are served in.

The program supplies only the shape of its parameter tree (and, under
a mesh, its placement rule); every value is the benchmark's, so the
plain reference can take the same arrays without taking anything the
program made.  A leaf's values depend on the seed and on its path
alone.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for any whole-number seed, beyond 32 bits too."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _leaf(key, name: str, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    x = jax.random.normal(key, shape, jnp.float32)
    if last == "scale":                     # norm gains around 1
        x = 1.0 + 0.1 * x
    elif last == "embed":
        x = 0.02 * x
    else:                                   # matrices: fan-in scaling
        x = x * jax.lax.rsqrt(jnp.float32(shape[-2]))
    return x.astype(dtype)


def make(shapes, seed: int, shardings=None):
    """A tree of arrays shaped like ``shapes`` (``ShapeDtypeStruct``
    leaves), from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path(p) for p, _ in flat]

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(jax.random.fold_in(key, zlib.crc32(n.encode()) & 0x7FFFFFFF),
                  n, s.shape, s.dtype)
            for n, (_, s) in zip(names, flat)])

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
