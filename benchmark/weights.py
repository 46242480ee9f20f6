"""Seeded weights for the program and the reference alike.

Each leaf is drawn from its own key, folded from the seed and the leaf's
path, so the values do not depend on the order or the sharding of the tree:
one-dimensional leaves (norm scales) are zeros, ``embed`` is N(0, 0.02^2),
and every matrix is N(0, 1 / fan_in) with fan_in its second-to-last axis.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def path_str(path) -> str:
    """``layers/0/attn/wq`` for a ``tree_flatten_with_path`` key path."""
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
    return "/".join(parts)


def init_leaf(key, path: str, shape, dtype) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    if len(shape) == 1:
        return jnp.zeros(shape, dtype)
    x = jax.random.normal(k, shape, jnp.float32)
    if path.split("/")[-1] == "embed":
        return (x * 0.02).astype(dtype)
    return (x / jnp.sqrt(jnp.float32(shape[-2]))).astype(dtype)


def init_tree(seed32: int, shapes, dtype):
    """A tree congruent with ``shapes`` (leaves with ``.shape``), filled
    from ``seed32``.  Call under ``jax.jit`` to make it on the device."""
    key = jax.random.PRNGKey(seed32)
    return jax.tree_util.tree_map_with_path(
        lambda p, s: init_leaf(key, path_str(p), tuple(s.shape), dtype),
        shapes)
