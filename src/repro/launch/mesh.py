"""Production meshes.  Kept as functions so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init)."""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis Auto-sharded (the compiler
    partitions; shard_map bodies take their axes explicitly)."""
    types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many local devices exist (tests)."""
    return make_mesh((data, model), ("data", "model"))


def make_node_mesh(data: int = 1, node: int = 1, model: int = 1):
    """Debug mesh with a factored expert axis: ('data', 'node', 'model').

    The 'node' axis declares the slow (cross-node / DCN) tier of the
    bandwidth hierarchy; 'model' stays the fast intra-node (ICI/NVLink)
    tier.  Expert-parallel modes shard experts over the combined
    ``node x model`` axes, and ``moe_parallel='ep_a2a_hier'`` runs its
    intra-node hop over 'model' and its single cross-node hop over 'node'.
    """
    return make_mesh((data, node, model), ("data", "node", "model"))
