"""Gating + the MoEBlaze dispatch data structures (paper §2.1, §4).

The four index structures (paper §4.1):

  expert_token_indices : (L*k,) int32 — token ids grouped by expert, within a
      group ordered by token id.  Expert ``e`` owns the slice
      ``[expert_token_offsets[e], expert_token_offsets[e+1])``.
  expert_token_offsets : (E+1,) int32 — exclusive prefix sums of counts.
  token_expert_indices : (L*k,) int32 — the chosen expert ids in token order
      (row-major flatten of the (L, k) top-k result).
  token_index_map      : (L, k) int32 — for each token, the positions of its k
      slots inside ``expert_token_indices`` (the inverse permutation).  Used by
      the combine step to *gather* its k partial outputs.

Two builders are provided:

  * :func:`build_dispatch` — the MoEBlaze **sort-free** build.  On GPU the
    paper replaces a radix sort with a 3-step atomic-free bitmap/scan pipeline
    (§4.2); the TPU-native analogue is a one-hot + cumulative-sum formulation
    that the VPU vectorizes directly (and `kernels/dispatch.py` provides the
    Pallas single-pass variant with a carried per-expert counter).
  * :func:`build_dispatch_sort` — the sort-based baseline the paper argues
    against (flatten → global stable sort by expert id → index recovery).

Both produce bit-identical structures (tested), so everything downstream is
agnostic to the builder.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import scoped


class Dispatch(NamedTuple):
    """The MoEBlaze routing metadata (paper Fig. 2)."""

    expert_token_indices: jax.Array  # (L*k,) int32
    expert_token_offsets: jax.Array  # (E+1,) int32
    token_expert_indices: jax.Array  # (L*k,) int32
    token_index_map: jax.Array       # (L, k) int32
    expert_lengths: jax.Array        # (E,)   int32

    @property
    def num_slots(self) -> int:
        return self.expert_token_indices.shape[0]


class GatingOut(NamedTuple):
    topk_experts: jax.Array  # (L, k) int32
    topk_weights: jax.Array  # (L, k) float — renormalized softmax scores
    router_probs: jax.Array  # (L, E) float — full softmax, for aux losses
    logits: jax.Array        # (L, E) float — for z-loss


@scoped("router")
def top_k_gating(x: jax.Array, w_gate: jax.Array, k: int,
                 *, renormalize: bool = True) -> GatingOut:
    """``TopK(softmax(W_g x))`` (paper §2.1).

    Args:
      x: (L, d) token activations.
      w_gate: (d, E) gating weights.
      k: experts per token.
    """
    logits = (x.astype(jnp.float32) @ w_gate.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topk_weights, topk_experts = jax.lax.top_k(probs, k)
    if renormalize:
        topk_weights = topk_weights / jnp.sum(topk_weights, -1, keepdims=True)
    return GatingOut(topk_experts.astype(jnp.int32), topk_weights, probs, logits)


def load_balance_loss(router_probs: jax.Array, topk_experts: jax.Array,
                      num_experts: int) -> jax.Array:
    """Switch/Mixtral-style auxiliary load-balance loss."""
    L = router_probs.shape[0]
    assign = jax.nn.one_hot(topk_experts, num_experts, dtype=jnp.float32)  # (L,k,E)
    frac_tokens = assign.sum(axis=(0, 1)) / (L * topk_experts.shape[1])
    frac_probs = router_probs.mean(axis=0)
    return num_experts * jnp.sum(frac_tokens * frac_probs)


def router_z_loss(logits: jax.Array) -> jax.Array:
    """ST-MoE z-loss: penalizes large router logits for stability."""
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)


@scoped("dispatch")
def build_dispatch(topk_experts: jax.Array, num_experts: int) -> Dispatch:
    """Sort-free dispatch-structure construction (paper §4.2, TPU rendering).

    Step 1 (dense token→expert map): one-hot encode the (L, k) assignments —
      the analogue of the paper's ``dense_token_map`` bitmap.
    Step 2 (expert lengths): column sums of the map + exclusive prefix sum —
      the analogue of the CTA-per-expert warp reductions.
    Step 3 (route indices to gates): within-expert ranks via an exclusive
      cumulative sum down the token axis (the paper's tile-level scans), added
      to the expert's global offset, yielding each slot's destination — then a
      single scatter writes ``expert_token_indices``.

    No sort is performed and no atomics are needed (TPU has none; XLA emits a
    vectorized cumsum).
    """
    L, k = topk_experts.shape
    flat = topk_experts.reshape(L * k)
    # (L*k, E) dense map.  int32 keeps the cumsum on the fast VPU path.
    onehot = jax.nn.one_hot(flat, num_experts, dtype=jnp.int32)
    # Step 2: per-expert totals and exclusive offsets.
    expert_lengths = onehot.sum(axis=0)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(expert_lengths)]
    ).astype(jnp.int32)
    # Step 3: rank of each slot within its expert = exclusive scan of the map.
    ranks_all = jnp.cumsum(onehot, axis=0) - onehot          # (L*k, E)
    rank = jnp.take_along_axis(ranks_all, flat[:, None], axis=1)[:, 0]
    dest = offsets[flat] + rank                               # (L*k,)
    token_ids = (jnp.arange(L * k, dtype=jnp.int32) // k)
    expert_token_indices = (
        jnp.zeros((L * k,), jnp.int32).at[dest].set(token_ids)
    )
    return Dispatch(
        expert_token_indices=expert_token_indices,
        expert_token_offsets=offsets,
        token_expert_indices=flat.astype(jnp.int32),
        token_index_map=dest.reshape(L, k).astype(jnp.int32),
        expert_lengths=expert_lengths.astype(jnp.int32),
    )


@scoped("dispatch")
def slice_dispatch(d: Dispatch, e_lo, e_hi, *,
                   count: int | None = None) -> Dispatch:
    """Compact a global :class:`Dispatch` to the expert range ``[e_lo, e_hi)``
    (the device-local view under expert parallelism).

    The slot space is *rotated*, not truncated: globally each of the ``L*k``
    slots has a unique destination in ``[0, L*k)``, and subtracting the
    range's first offset modulo ``L*k`` is a bijection, so

      * slots of the local experts land contiguously at ``[0, n_loc)``
        (``n_loc = offsets[e_hi] - offsets[e_lo]``) in global expert order —
        exactly the prefix a grouped GEMM with the rebased ``expert_lengths``
        consumes;
      * every *non-local* slot lands uniquely in the dead zone
        ``[n_loc, L*k)``.  Grouped-GEMM backends define rows past the
        group-size total as belonging to no group (output zero), so a combine
        gathering through the sliced ``token_index_map`` picks up exact zeros
        for non-local slots — summing the per-range outputs (one ``psum``)
        reassembles the global combine with no padding and no dense ``L×E``
        buffer.

    ``expert_token_offsets``/``expert_lengths`` are rebased to the local
    range; ``token_expert_indices`` is rebased by ``-e_lo`` (out-of-range
    values mark non-local slots).  ``e_lo``/``e_hi`` may be traced (e.g.
    ``axis_index * E_loc`` inside ``shard_map``); the local expert *count*
    must be static — pass ``count=`` when the bounds are traced.
    """
    if count is None:
        count = int(e_hi) - int(e_lo)
    if count <= 0:
        raise ValueError(f"empty expert range [{e_lo}, {e_hi})")
    e_lo = jnp.asarray(e_lo, jnp.int32)
    S = d.expert_token_indices.shape[0]
    off = jax.lax.dynamic_slice_in_dim(d.expert_token_offsets, e_lo, count + 1)
    lens = jax.lax.dynamic_slice_in_dim(d.expert_lengths, e_lo, count)
    start = off[0]
    # Rotate the slot axis so the local range starts at 0 (explicit gather —
    # works with a traced start index on every backend).
    src = (jnp.arange(S, dtype=jnp.int32) + start) % S
    return Dispatch(
        expert_token_indices=jnp.take(d.expert_token_indices, src, axis=0),
        expert_token_offsets=(off - start).astype(jnp.int32),
        token_expert_indices=(d.token_expert_indices - e_lo).astype(jnp.int32),
        token_index_map=((d.token_index_map - start) % S).astype(jnp.int32),
        expert_lengths=lens.astype(jnp.int32),
    )


def build_dispatch_sort(topk_experts: jax.Array, num_experts: int) -> Dispatch:
    """Sort-based baseline (paper §4.2's strawman): global stable sort by
    expert id, then index recovery.  Produces identical structures."""
    L, k = topk_experts.shape
    flat = topk_experts.reshape(L * k).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)   # (L*k,)
    token_ids = (jnp.arange(L * k, dtype=jnp.int32) // k)
    expert_token_indices = token_ids[order]
    # index recovery: dest[slot] = position of `slot` in `order`
    dest = jnp.zeros((L * k,), jnp.int32).at[order].set(
        jnp.arange(L * k, dtype=jnp.int32))
    expert_lengths = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(expert_lengths)]
    ).astype(jnp.int32)
    return Dispatch(
        expert_token_indices=expert_token_indices,
        expert_token_offsets=offsets,
        token_expert_indices=flat,
        token_index_map=dest.reshape(L, k),
        expert_lengths=expert_lengths,
    )
