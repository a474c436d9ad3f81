"""Weights from a seed, made on the device in one jitted call.

The served layout is the program's parameter tree (stacked layers), as the
configuration's family lists its leaves (``leaf_specs``); the values come
from this file alone, so the reference can make the same weights again from
the seed without taking anything the program made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import families

EMBED_SCALE = 0.02
BIAS_SCALE = 0.1
NORM_SPREAD = 0.1


def key_for(seed: int):
    """A PRNG key for any whole seed up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


@functools.partial(jax.jit, static_argnums=0)
def _make(frozen: str, key):
    sizes = families.thaw(frozen)
    dtype = jnp.dtype(sizes["dtype"])
    tree: dict = {}
    specs = families.of(sizes).leaf_specs(sizes)
    for i, (path, shape, kind, scale) in enumerate(specs):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        value = (1.0 + scale * z) if kind == "norm" else scale * z
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = value.astype(dtype)
    return tree


def make(sizes: dict, seed: int):
    """The parameter tree for ``sizes`` (a configuration file's dict)."""
    return _make(families.freeze(sizes), key_for(seed))
