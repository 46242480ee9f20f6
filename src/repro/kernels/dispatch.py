"""Pallas dispatch-structure construction (paper §4.2), TPU rendering.

The paper's GPU pipeline is 3 atomic-free steps: dense token→expert bitmap,
per-expert lengths via warp reductions, then a location map from CTA-local
exclusive scans + global offsets.  On TPU the grid executes **sequentially**
per core, so a running per-expert counter carried in VMEM scratch across grid
steps *is* the exclusive scan — two single-pass kernels suffice:

  1. ``count`` — per-expert lengths (tile-local one-hot column sums,
     accumulated into the output across grid steps).
  2. ``route`` — per-slot destination = global offset (scalar input) +
     carried counter + tile-local exclusive scan, i.e. the flat
     ``token_index_map``.  The tile-local scan is a matmul against a strictly
     lower-triangular ones matrix (MXU work; exact, the counts are small
     integers).

``expert_token_indices`` is the inverse permutation of the destinations, one
XLA scatter outside the kernels.  Padding slots carry the sentinel expert id
``E`` and are masked everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core.routing import Dispatch


def _onehot(tei_ref, num_experts: int, bl: int):
    iota = jax.lax.broadcasted_iota(jnp.int32, (bl, num_experts), 1)
    return (tei_ref[...] == iota).astype(jnp.float32)        # sentinel E -> 0


def _count_kernel(tei_ref, len_ref, *, num_experts: int, bl: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        len_ref[...] = jnp.zeros_like(len_ref)

    onehot = _onehot(tei_ref, num_experts, bl)
    len_ref[...] += onehot.sum(axis=0, keepdims=True).astype(jnp.int32)


def _route_kernel(tei_ref, off_ref, dest_ref, counters,
                  *, num_experts: int, bl: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        counters[...] = jnp.zeros_like(counters)

    onehot = _onehot(tei_ref, num_experts, bl)                # (bl, E)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bl, bl), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bl, bl), 1)
    below = (cols < rows).astype(jnp.float32)
    local_excl = jnp.dot(below, onehot,
                         preferred_element_type=jnp.float32)  # tile-local scan
    cnt = counters[...]                                       # (1, E)
    base = off_ref[:, :num_experts] + cnt                     # (1, E)
    # One-hot contractions instead of vector gathers (VPU/MXU friendly).
    dest = jnp.sum(onehot * (base.astype(jnp.float32) + local_excl), axis=1,
                   keepdims=True)
    dest_ref[...] = dest.astype(jnp.int32)                    # pads -> 0
    counters[...] = cnt + onehot.sum(axis=0, keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_experts", "k", "bl"))
def build_dispatch_pallas(topk_experts: jax.Array, num_experts: int,
                          *, k: int | None = None, bl: int = 256) -> Dispatch:
    """Drop-in replacement for :func:`repro.core.routing.build_dispatch`."""
    L, kk = topk_experts.shape
    k = kk if k is None else k
    flat = topk_experts.reshape(L * k).astype(jnp.int32)
    n = L * k
    bl = min(bl, -(-n // 8) * 8)
    n_pad = -(-n // bl) * bl
    tei = jnp.pad(flat, (0, n_pad - n),
                  constant_values=num_experts).reshape(n_pad, 1)
    n_tiles = n_pad // bl
    E = num_experts

    lengths = pl.pallas_call(
        functools.partial(_count_kernel, num_experts=E, bl=bl),
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((bl, 1), lambda t: (t, 0))],
        out_specs=pl.BlockSpec((1, E), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, E), jnp.int32),
        interpret=kernels.interpret_mode(),
    )(tei)[0]

    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(lengths)]).astype(jnp.int32)

    dest = pl.pallas_call(
        functools.partial(_route_kernel, num_experts=E, bl=bl),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((bl, 1), lambda t: (t, 0)),
            pl.BlockSpec((1, E + 1), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bl, 1), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, E), jnp.int32)],
        interpret=kernels.interpret_mode(),
    )(tei, offsets.reshape(1, E + 1))[:n, 0]

    # expert_token_indices: slot dest[i] holds token i // k.
    eti = jnp.zeros((n,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32) // k)
    return Dispatch(
        expert_token_indices=eti,
        expert_token_offsets=offsets,
        token_expert_indices=flat,
        token_index_map=dest.reshape(L, k),
        expert_lengths=lengths,
    )
