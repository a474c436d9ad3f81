"""Weights from a seed, made on the device in one jitted call.

The served layout is the program's parameter tree (stacked layers); the
values come from this file alone, so the reference can make the same
weights again from the seed without taking anything the program made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EMBED_SCALE = 0.02
BIAS_SCALE = 0.1
NORM_SPREAD = 0.1


def key_for(seed: int):
    """A PRNG key for any whole seed up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


def leaf_specs(sizes: dict) -> list[tuple[tuple[str, ...], tuple, str, float]]:
    """(path, shape, kind, scale) of every leaf, in a fixed order."""
    L, D = sizes["n_layers"], sizes["d_model"]
    H, G, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    F, V = sizes["d_ff"], sizes["vocab"]
    specs = [
        (("embedding",), (V, D), "normal", EMBED_SCALE),
        (("lm_head",), (V, D), "normal", EMBED_SCALE),
        (("final_norm",), (D,), "norm", NORM_SPREAD),
        (("layers", "norm1"), (L, D), "norm", NORM_SPREAD),
        (("layers", "norm2"), (L, D), "norm", NORM_SPREAD),
        (("layers", "attn", "wq"), (L, D, H, hd), "normal", D ** -0.5),
        (("layers", "attn", "wk"), (L, D, G, hd), "normal", D ** -0.5),
        (("layers", "attn", "wv"), (L, D, G, hd), "normal", D ** -0.5),
        (("layers", "attn", "wo"), (L, H, hd, D), "normal", D ** -0.5),
        (("layers", "mlp", "w_gate"), (L, D, F), "normal", D ** -0.5),
        (("layers", "mlp", "w_up"), (L, D, F), "normal", D ** -0.5),
        (("layers", "mlp", "w_down"), (L, F, D), "normal", F ** -0.5),
    ]
    if sizes["qkv_bias"]:
        specs += [
            (("layers", "attn", "bq"), (L, H, hd), "normal", BIAS_SCALE),
            (("layers", "attn", "bk"), (L, G, hd), "normal", BIAS_SCALE),
            (("layers", "attn", "bv"), (L, G, hd), "normal", BIAS_SCALE),
        ]
    return specs


def _freeze(sizes: dict) -> tuple:
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "qkv_bias", "dtype")
    return tuple((k, sizes[k]) for k in keys)


@functools.partial(jax.jit, static_argnums=0)
def _make(frozen: tuple, key):
    sizes = dict(frozen)
    dtype = jnp.dtype(sizes["dtype"])
    tree: dict = {}
    for i, (path, shape, kind, scale) in enumerate(leaf_specs(sizes)):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        value = (1.0 + scale * z) if kind == "norm" else scale * z
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = value.astype(dtype)
    return tree


def make(sizes: dict, seed: int):
    """The parameter tree for ``sizes`` (a configuration file's dict)."""
    return _make(_freeze(sizes), key_for(seed))
