"""Gather-GMM: grouped expert GEMMs with on-the-fly token gather (paper §3.1
+ §5.2), as a Pallas TPU kernel — plus the fully fused
dispatch→GEMM→combine MoE kernels built on the same work-item grid.

This is the kernel rendering of the paper's central claim: the expert MLPs
consume **non-materialized** routed tokens.  The `(L·k, d)` routed buffer
never exists in HBM; instead the kernel is driven by the scalar-prefetched
``expert_token_indices`` and DMA-gathers the needed rows of the *unpermuted*
``x`` (kept in HBM, ``memory_space=pl.ANY``) per work item, streams them
through the expert's projections (optionally both SwiGLU branches at once,
sharing the single read of the gathered rows), and applies the SiLU·gate
epilogue in VMEM.

:func:`fused_moe_fwd` / :func:`fused_moe_bwd` take the fusion end to end
(SonicMoE-style IO-aware epilogue fusion): the second grouped GEMM
(``y_swi @ w3[e]``) runs in the same grid pass, and each slot's gated partial
is scatter-accumulated straight into the `(L, d)` output in HBM through the
same index metadata (row DMAs: read, add, write back) — the gather-of-partials
combine of ``kernels/combine.py`` becomes the kernel's epilogue, so neither
the `(L·k, h)` SwiGLU product nor the `(L·k, d)` partials ever exist in HBM.
The backward replays the gather in-kernel and produces dx / dgates / dw1 /
dw2 / dw3 from one grid sweep, again with no `(L·k, ·)` residual.

Every kernel's VMEM is bounded by its tile sizes, never by ``L`` or ``S``:
token rows move one DMA per row (``kernels/common.py``), and weight and
weight-gradient blocks are tiled over the hidden (and, for ``gmm_dw``, the
model) width.

Group-crossing tiles are handled MegaBlocks-style: the wrapper precomputes a
static work-item list (one item per (row-tile × overlapping expert); at most
``n_tiles + E`` items) whose metadata — tile id, expert id, row range inside
the tile, first-visit flags — is scalar-prefetched so that the weight
BlockSpec's ``index_map`` can select ``w[expert]`` per work item.

Work-item contracts (see :func:`make_work_items`):

  * items are ordered so that both the tile id and the expert id are
    non-decreasing: every row-tiled and every per-expert output block is
    visited in ONE run of consecutive grid steps.  A compiled TPU grid writes
    an output block back when its index changes and never reads it back in,
    so a later revisit would overwrite a finished block with stale VMEM;
  * every output row tile is zero-initialized in-kernel (``first`` marks the
    first item of each tile's run) — tiles no expert touches get an
    empty-range filler item, so trailing dead rows are exact zeros;
  * every expert's weight-gradient block is zero-initialized in-kernel
    (``efirst``) — empty experts get an empty-range item in their place in
    the expert order, so callers never mask ``gmm_dw_pallas`` outputs;
  * the all-empty case (``n_valid == 0``, e.g. an ``ep_a2a`` shard whose
    tokens were all dropped) degenerates to pure no-op items that still
    zero-initialize every output block.

Tile sizes: ``bl``/``bh`` are *requests*; ``bh`` is clamped by
:func:`repro.kernels.common.lane_tile` (a multiple of 128 dividing ``h``, or
all of ``h``) and ``bl`` to the padded row count.  Callers that want
hardware-informed sizes ask ``repro.roofline.select_moe_tiles``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels.common import (compiler_params, dma_rows, lane_tile,
                                  row_words, rows_from_words)


def _silu(a):
    return a * jax.nn.sigmoid(a)


def _dsilu(a):
    s = jax.nn.sigmoid(a)
    return s * (1.0 + a * (1.0 - s))


def _row_tiles(S: int, bl: int) -> tuple[int, int]:
    """(bl, S_pad): the row-tile request clamped to the row count."""
    bl = min(bl, max(S, 8))
    return bl, -(-S // bl) * bl


def make_work_items(offsets: jax.Array, n_tiles: int, bl: int,
                    num_experts: int):
    """Static-shape (tile × expert) work-item metadata.

    Returns int32 arrays of length ``W = n_tiles + num_experts``:
      (tile, expert, lo, hi, first, efirst) — ``[lo, hi)`` is the row range
    of ``expert`` inside ``tile``; ``first`` marks the first item of each
    tile's run and ``efirst`` the first item of each expert's run.

    The order is expert-major: expert ``e``'s items cover the tiles its row
    segment ``[offsets[e], offsets[e+1])`` spans, in tile order; an empty
    expert gets one empty-range item on the tile where its (empty) segment
    sits.  Segments are contiguous and sorted, so the tile id never
    decreases either, and every tile and every expert is visited in one run
    of consecutive items.  Tiles past the last routed row follow, one
    empty-range item each (on expert ``E-1``, the last run); remaining items
    repeat the last tile with an empty range.

    Why ``W`` items always suffice: consecutive non-empty experts share at
    most one tile, so the expert-major items number at most
    ``last_tile + 1 + E - 1``, and the trailing tiles ``n_tiles - 1 -
    last_tile`` more — ``n_tiles + E - 1 < W`` in all, including the
    all-empty case (``E`` items on tile 0, then tiles ``1..n_tiles-1``).
    """
    E = num_experts
    W = n_tiles + E
    off = offsets.astype(jnp.int32)
    start, end = off[:E], off[1:E + 1]
    t0 = jnp.minimum(start // bl, n_tiles - 1)
    t1 = jnp.where(end > start, (end - 1) // bl, t0)
    count = t1 - t0 + 1                                  # items per expert
    ends = jnp.cumsum(count)
    n_main = ends[-1]
    w = jnp.arange(W, dtype=jnp.int32)
    e = jnp.minimum((ends[None, :] <= w[:, None]).sum(axis=1), E - 1)
    e = e.astype(jnp.int32)
    in_main = w < n_main
    tile = jnp.where(in_main, t0[e] + w - (ends[e] - count[e]),
                     jnp.minimum(t1[E - 1] + 1 + w - n_main, n_tiles - 1))
    expert = jnp.where(in_main, e, E - 1)
    lo = jnp.where(in_main, jnp.clip(start[e] - tile * bl, 0, bl), 0)
    hi = jnp.where(in_main, jnp.clip(end[e] - tile * bl, 0, bl), 0)

    def run_starts(v):
        return (v != jnp.concatenate([jnp.full((1,), -1, v.dtype), v[:-1]])
                ).astype(jnp.int32)

    return (tile.astype(jnp.int32), expert, lo.astype(jnp.int32),
            hi.astype(jnp.int32), run_starts(tile), run_starts(expert))


def _accumulate(ref, val, init):
    """``ref = val`` on the first visit of the block, ``ref += val`` after."""
    @pl.when(init)
    def _init():
        ref[...] = val.astype(ref.dtype)

    @pl.when(jnp.logical_not(init))
    def _acc():
        ref[...] += val.astype(ref.dtype)


def _row_mask(lo, hi, bl: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (bl, 1), 0)
    return (rows >= lo) & (rows < hi)


# ---------------------------------------------------------------------------
# Gather-GMM (the ``pallas`` grouped-GEMM backend and the unfused layer)
# ---------------------------------------------------------------------------


def _gmm_kernel(*refs, bl: int, dual: bool, epilogue: bool, n_out: int,
                gather: bool, dtype):
    tile_ref, _, lo_ref, hi_ref, first_ref = refs[:5]
    i = 5
    if gather:
        idx_ref = refs[i]
        i += 1
    x_ref, w1_ref = refs[i:i + 2]
    i += 2
    w2_ref = None
    if dual:
        w2_ref = refs[i]
        i += 1
    outs = refs[i:i + n_out]
    wi = pl.program_id(1)
    lo, hi = lo_ref[wi], hi_ref[wi]
    if gather:
        # On-the-fly gather: exactly this item's rows, one DMA each.
        xt_ref, sem = refs[i + n_out:]
        base = tile_ref[wi] * bl
        dma_rows(x_ref, xt_ref, sem, lo, hi, lambda r: idx_ref[base + r],
                 lambda r: r)
        xt = rows_from_words(xt_ref[...], dtype)
    else:
        xt = x_ref[...]
    # Rows of the tile outside [lo, hi) belong to other experts (or hold a
    # previous item's gather): zero them so the full-tile dot is exact.
    xt = jnp.where(_row_mask(lo, hi, bl), xt, jnp.zeros((), xt.dtype))
    a = jnp.dot(xt, w1_ref[0], preferred_element_type=jnp.float32)
    b = None
    y = a
    if dual:
        b = jnp.dot(xt, w2_ref[0], preferred_element_type=jnp.float32)
        if epilogue:
            y = _silu(a) * b
    first = first_ref[wi] == 1
    for ref, val in zip(outs, (y, a, b)):
        _accumulate(ref, val, first)


@functools.partial(jax.jit, static_argnames=("bl", "bh", "epilogue",
                                             "save_ab"))
def gather_gmm(x: jax.Array, idx: jax.Array | None, offsets: jax.Array,
               w1: jax.Array, w2: jax.Array | None = None,
               *, bl: int = 128, bh: int = 512, epilogue: bool = True,
               save_ab: bool = False):
    """Grouped matmul over gathered rows.

    Args:
      x: (L, d) unpermuted activations.
      idx: (S,) row ids grouped by expert (``expert_token_indices``), or
        None when ``x`` is already in expert order (``L == S``; the rows
        then stream as plain blocks, no gather).
      offsets: (E+1,) exclusive prefix sums (``expert_token_offsets``).
      w1: (E, d, h); w2: optional (E, d, h) SwiGLU gate branch.
      epilogue: apply ``silu(a)·b`` (requires w2).
      save_ab: also return the checkpointed GEMM outputs a (and b).
      bl/bh: row/hidden tile-size *requests* (see the module docstring).

    Returns ``y`` of shape (S, h) — or ``(y, a[, b])`` when ``save_ab``.
    Output rows past ``offsets[-1]`` belong to no group and are exact zeros.
    """
    gather = idx is not None
    L, d = x.shape
    S = idx.shape[0] if gather else L
    E, _, h = w1.shape
    dual = w2 is not None
    bl, S_pad = _row_tiles(S, bl)
    bh = lane_tile(h, bh)
    n_tiles, nh = S_pad // bl, h // bh
    items = make_work_items(offsets, n_tiles, bl, E)
    W = items[0].shape[0]
    scalars = list(items[:5])
    it = x.dtype.itemsize
    if gather:
        scalars.append(jnp.pad(jnp.clip(idx.astype(jnp.int32), 0, L - 1),
                               (0, S_pad - S)))
        xin = row_words(x)
        x_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((bl, 1, xin.shape[-1]), xin.dtype),
                   pltpu.SemaphoreType.DMA(())]
        x_vmem = bl * d * it
    else:
        xin = jnp.pad(x, ((0, S_pad - S), (0, 0)))
        x_spec = pl.BlockSpec((bl, d), lambda hh, wi, *s: (s[0][wi], 0))
        scratch = []
        x_vmem = 2 * bl * d * it
    n_out = 1 + (1 if save_ab else 0) + (1 if (save_ab and dual) else 0)
    w_spec = pl.BlockSpec((1, d, bh), lambda hh, wi, *s: (s[1][wi], 0, hh))
    out_spec = pl.BlockSpec((bl, bh), lambda hh, wi, *s: (s[0][wi], hh))
    n_w = 2 if dual else 1
    vmem = (x_vmem + 2 * n_w * d * bh * w1.dtype.itemsize
            + 2 * n_out * bl * bh * it + 4 * bl * bh * 4 + bl * d * 4)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, bl=bl, dual=dual,
                          epilogue=epilogue and dual, n_out=n_out,
                          gather=gather, dtype=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(nh, W),
            in_specs=[x_spec] + [w_spec] * n_w,
            out_specs=[out_spec] * n_out,
            scratch_shapes=scratch,
        ),
        out_shape=[jax.ShapeDtypeStruct((S_pad, h), x.dtype)] * n_out,
        compiler_params=compiler_params("gather_gmm", vmem),
        interpret=kernels.interpret_mode(),
    )(*scalars, xin, w1, *([w2] if dual else []))
    if n_out == 1:
        return out[0][:S]
    return tuple(o[:S] for o in out)


# ---------------------------------------------------------------------------
# Fully fused dispatch -> grouped GEMMs -> combine (forward)
# ---------------------------------------------------------------------------


def _fused_kernel(tile_ref, expert_ref, lo_ref, hi_ref, idx_ref,
                  x_ref, g_ref, w1_ref, w2_ref, w3_ref, y0_ref, y_ref,
                  xt_ref, pacc_ref, ybuf_ref, sem, *, bl: int, nh: int,
                  dtype):
    del expert_ref, y0_ref           # y0 is y's initial (aliased) buffer
    wi = pl.program_id(0)
    hh = pl.program_id(1)
    lo, hi = lo_ref[wi], hi_ref[wi]
    base = tile_ref[wi] * bl

    def tok(r):
        return idx_ref[base + r]

    def slot(r):
        return r

    @pl.when(hh == 0)
    def _gather():
        # On-the-fly dispatch: this item's rows, gathered once per work item
        # (the scratch persists across the sequential hh steps).
        dma_rows(x_ref, xt_ref, sem, lo, hi, tok, slot)

    xt = jnp.where(_row_mask(lo, hi, bl), rows_from_words(xt_ref[...], dtype),
                   jnp.zeros((), dtype))
    a = jnp.dot(xt, w1_ref[0], preferred_element_type=jnp.float32)
    b = jnp.dot(xt, w2_ref[0], preferred_element_type=jnp.float32)
    y_swi = _silu(a) * b                       # (bl, bh), VMEM-only
    # Round to the I/O dtype at the GEMM boundary — the same place the
    # unfused path materializes y_swi — so fused-vs-unfused stays within
    # reduction-order noise even in bf16 (identity in f32).
    y_swi = y_swi.astype(dtype).astype(jnp.float32)
    # Second grouped GEMM, this h-block's contribution: (bl, bh) @ (bh, d).
    p = jnp.dot(y_swi, w3_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)

    @pl.when(hh == 0)
    def _p_init():
        pacc_ref[...] = p

    @pl.when(hh > 0)
    def _p_acc():
        pacc_ref[...] += p

    @pl.when(hh == nh - 1)
    def _combine():
        # Fused combine epilogue: once the h-contraction is complete, add
        # each slot's gated partial into y[token] in HBM (read the rows, add,
        # write them back).  An item's slots are one expert's, so its tokens
        # are distinct; the grid runs items one after another.
        dma_rows(y_ref, ybuf_ref, sem, lo, hi, tok, slot)
        gated = g_ref[...].astype(jnp.float32) * pacc_ref[...]
        ybuf_ref[...] = ybuf_ref[...] + gated.reshape(ybuf_ref.shape)
        dma_rows(ybuf_ref, y_ref, sem, lo, hi, slot, tok)


@functools.partial(jax.jit, static_argnames=("bl", "bh"))
def fused_moe_fwd(x: jax.Array, g_slot: jax.Array, idx: jax.Array,
                  offsets: jax.Array, w1: jax.Array, w2: jax.Array,
                  w3: jax.Array, *, bl: int = 128,
                  bh: int = 128) -> jax.Array:
    """Fused dispatch→GEMM→combine SwiGLU MoE forward.

    One grid pass over the work items computes, per (row tile × expert ×
    h-block): the on-the-fly gather of ``x`` rows, both first-layer GEMMs,
    the SiLU·gate epilogue, the second grouped GEMM, and the gated
    scatter-accumulate of each slot's partial into the ``(L, d)`` output —
    no ``(L·k, h)`` or ``(L·k, d)`` intermediate is ever written to HBM.

    Args:
      x: (L, d) unpermuted activations.
      g_slot: (S,) per-slot gate weights in expert order (the (L, k) gates
        scattered through ``token_index_map``).
      idx: (S,) ``expert_token_indices``; offsets: (E+1,) prefix sums.
      w1, w2: (E, d, h); w3: (E, h, d).
      bl/bh: tile requests; ask ``repro.roofline.select_moe_tiles`` for
        hardware-informed sizes.

    Returns the combined (L, d) output in fp32 (full-precision accumulation
    across h-blocks and the k slots; cast at the call site).
    """
    S, = idx.shape
    L, d = x.shape
    E, _, h = w1.shape
    bl, S_pad = _row_tiles(S, bl)
    bh = lane_tile(h, bh)
    n_tiles, nh = S_pad // bl, h // bh
    tile, expert, lo, hi, _, _ = make_work_items(offsets, n_tiles, bl, E)
    W = tile.shape[0]
    idx_p = jnp.pad(jnp.clip(idx.astype(jnp.int32), 0, L - 1), (0, S_pad - S))
    g_pad = jnp.pad(g_slot.astype(jnp.float32), (0, S_pad - S)
                    ).reshape(S_pad, 1)
    xw = row_words(x)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    it, wit = x.dtype.itemsize, w1.dtype.itemsize
    vmem = (bl * d * (it + 4 + 4) + 2 * 3 * d * bh * wit + 2 * bl * 128 * 4
            + 3 * bl * bh * 4 + bl * d * 4)
    y = pl.pallas_call(
        functools.partial(_fused_kernel, bl=bl, nh=nh, dtype=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(W, nh),
            in_specs=[
                any_spec,
                pl.BlockSpec((bl, 1), lambda wi, hh, *s: (s[0][wi], 0)),
                pl.BlockSpec((1, d, bh), lambda wi, hh, *s: (s[1][wi], 0, hh)),
                pl.BlockSpec((1, d, bh), lambda wi, hh, *s: (s[1][wi], 0, hh)),
                pl.BlockSpec((1, bh, d), lambda wi, hh, *s: (s[1][wi], hh, 0)),
                any_spec,
            ],
            out_specs=any_spec,
            scratch_shapes=[pltpu.VMEM((bl, 1, xw.shape[-1]), xw.dtype),
                            pltpu.VMEM((bl, d), jnp.float32),
                            pltpu.VMEM((bl, 1, d), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((L, 1, d), jnp.float32),
        input_output_aliases={10: 0},
        compiler_params=compiler_params("fused_moe_fwd", vmem),
        interpret=kernels.interpret_mode(),
    )(tile, expert, lo, hi, idx_p, xw, g_pad, w1, w2, w3,
      jnp.zeros((L, 1, d), jnp.float32))
    return y.reshape(L, d)


# ---------------------------------------------------------------------------
# Fully fused backward: replay the gather in-kernel, produce every gradient
# ---------------------------------------------------------------------------


def _fused_bwd_kernel(tile_ref, expert_ref, lo_ref, hi_ref, first_ref,
                      efirst_ref, idx_ref,
                      x_ref, dy_ref, g_ref, w1_ref, w2_ref, w3_ref, dx0_ref,
                      dx_ref, dg_ref, dw1_ref, dw2_ref, dw3_ref,
                      xt_ref, dyt_ref, dxbuf_ref, sem, *, bl: int, dtype):
    del expert_ref, dx0_ref          # dx0 is dx's initial (aliased) buffer
    wi = pl.program_id(1)
    lo, hi = lo_ref[wi], hi_ref[wi]
    first = first_ref[wi] == 1
    efirst = efirst_ref[wi] == 1
    base = tile_ref[wi] * bl

    def tok(r):
        return idx_ref[base + r]

    def slot(r):
        return r

    # Replay the dispatch gather for x AND expand the (L, d) output grads to
    # this item's slots — neither buffer was saved.
    dma_rows(x_ref, xt_ref, sem, lo, hi, tok, slot)
    dma_rows(dy_ref, dyt_ref, sem, lo, hi, tok, slot)
    mask = _row_mask(lo, hi, bl)
    xt = jnp.where(mask, rows_from_words(xt_ref[...], dtype),
                   jnp.zeros((), dtype))
    dyt = jnp.where(mask, rows_from_words(dyt_ref[...], dtype),
                    jnp.zeros((), dtype)).astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)               # (bl, 1)
    # Recompute A, B, SiLU for this h-block (Algorithm 1's smart checkpoint,
    # taken to its deepest point: nothing but x and the weights was saved).
    a = jnp.dot(xt, w1_ref[0], preferred_element_type=jnp.float32)
    b = jnp.dot(xt, w2_ref[0], preferred_element_type=jnp.float32)
    sa = _silu(a)
    # Recomputed y_swi and the cotangent dyu are rounded to the I/O dtype,
    # matching the buffers the unfused backward reads (identity in f32).
    y_swi = (sa * b).astype(dtype).astype(jnp.float32)
    # dY_swi through the transposed third GEMM: (bl, d) x (bh, d) -> (bl, bh)
    dyu = jax.lax.dot_general(dyt, w3_ref[0].astype(jnp.float32),
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dyu = dyu.astype(dtype).astype(jnp.float32)
    dy_swi = dyu * g
    da = dy_swi * b * _dsilu(a)
    db = dy_swi * sa

    # dgates, in slot order, this h-block's share (summed over h-blocks by
    # the wrapper): rows outside [lo, hi) contribute exact zeros.
    _accumulate(dg_ref, jnp.sum(y_swi * dyu, axis=1, keepdims=True)[None],
                first)
    rows_t = (((0,), (0,)), ((), ()))
    xt32 = xt.astype(jnp.float32)
    _accumulate(dw1_ref, jax.lax.dot_general(
        xt32, da, rows_t, preferred_element_type=jnp.float32)[None], efirst)
    _accumulate(dw2_ref, jax.lax.dot_general(
        xt32, db, rows_t, preferred_element_type=jnp.float32)[None], efirst)
    _accumulate(dw3_ref, jax.lax.dot_general(
        y_swi * g, dyt, rows_t, preferred_element_type=jnp.float32)[None],
        efirst)

    # Token gradients, this h-block's share: added into dx[token] in HBM.
    dxg = (jax.lax.dot_general(da, w1_ref[0].astype(jnp.float32),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + jax.lax.dot_general(db, w2_ref[0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32))
    dma_rows(dx_ref, dxbuf_ref, sem, lo, hi, tok, slot)
    dxbuf_ref[...] = dxbuf_ref[...] + dxg.reshape(dxbuf_ref.shape)
    dma_rows(dxbuf_ref, dx_ref, sem, lo, hi, slot, tok)


@functools.partial(jax.jit, static_argnames=("bl", "bh"))
def fused_moe_bwd(x: jax.Array, dy: jax.Array, g_slot: jax.Array,
                  idx: jax.Array, offsets: jax.Array, w1: jax.Array,
                  w2: jax.Array, w3: jax.Array, *, bl: int = 128,
                  bh: int = 128):
    """Backward of :func:`fused_moe_fwd` in one grid sweep.

    Replays the dispatch gather in-kernel (both ``x`` rows and the slot
    expansion of ``dy``), recomputes A/B/SiLU per h-block, and accumulates
    all five gradients — no ``(L·k, ·)`` buffer is read from or written to
    HBM.  The h-blocks are the outer grid axis, so each expert's
    ``(d, bh)`` weight-gradient blocks are finished in one run of items;
    ``dx`` collects each h-block's share by row DMA.  Empty experts' dw
    blocks and dead row tiles are zero-initialized by the work items.

    Returns ``(dx (L, d), dgates_slot (S,), dw1, dw2, dw3)`` in fp32.
    """
    S, = idx.shape
    L, d = x.shape
    E, _, h = w1.shape
    bl, S_pad = _row_tiles(S, bl)
    bh = lane_tile(h, bh)
    n_tiles, nh = S_pad // bl, h // bh
    items = make_work_items(offsets, n_tiles, bl, E)
    W = items[0].shape[0]
    idx_p = jnp.pad(jnp.clip(idx.astype(jnp.int32), 0, L - 1), (0, S_pad - S))
    g_pad = jnp.pad(g_slot.astype(jnp.float32), (0, S_pad - S)
                    ).reshape(S_pad, 1)
    xw, dyw = row_words(x), row_words(dy.astype(x.dtype))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    def w12_map(hh, wi, *s):
        return (s[1][wi], 0, hh)

    def w3_map(hh, wi, *s):
        return (s[1][wi], hh, 0)

    it, wit = x.dtype.itemsize, w1.dtype.itemsize
    vmem = (bl * d * (2 * it + 4) + 2 * 3 * d * bh * (wit + 4)
            + 4 * bl * 128 * 4 + 6 * bl * bh * 4 + 2 * bl * d * 4)
    dx, dg, dw1, dw2, dw3 = pl.pallas_call(
        functools.partial(_fused_bwd_kernel, bl=bl, dtype=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(nh, W),
            in_specs=[
                any_spec, any_spec,
                pl.BlockSpec((bl, 1), lambda hh, wi, *s: (s[0][wi], 0)),
                pl.BlockSpec((1, d, bh), w12_map),
                pl.BlockSpec((1, d, bh), w12_map),
                pl.BlockSpec((1, bh, d), w3_map),
                any_spec,
            ],
            out_specs=[
                any_spec,
                pl.BlockSpec((1, bl, 1), lambda hh, wi, *s: (hh, s[0][wi], 0)),
                pl.BlockSpec((1, d, bh), w12_map),
                pl.BlockSpec((1, d, bh), w12_map),
                pl.BlockSpec((1, bh, d), w3_map),
            ],
            scratch_shapes=[pltpu.VMEM((bl, 1, xw.shape[-1]), xw.dtype),
                            pltpu.VMEM((bl, 1, dyw.shape[-1]), dyw.dtype),
                            pltpu.VMEM((bl, 1, d), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((L, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((nh, S_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((E, d, h), jnp.float32),
            jax.ShapeDtypeStruct((E, d, h), jnp.float32),
            jax.ShapeDtypeStruct((E, h, d), jnp.float32),
        ],
        input_output_aliases={13: 0},
        compiler_params=compiler_params("fused_moe_bwd", vmem),
        interpret=kernels.interpret_mode(),
    )(*items, idx_p, xw, dyw, g_pad, w1, w2, w3,
      jnp.zeros((L, 1, d), jnp.float32))
    return dx.reshape(L, d), dg.sum(axis=0)[:S, 0], dw1, dw2, dw3


# ---------------------------------------------------------------------------
# Row gather (the a2a send-buffer builder)
# ---------------------------------------------------------------------------


def _gather_rows_kernel(rows_ref, ids_ref, src_ref, out_ref, buf_ref, sem,
                        *, bl: int, dtype):
    t = pl.program_id(0)

    def each_valid(fn):
        def body(r, c):
            rid = rows_ref[t * bl + r]

            @pl.when(rid >= 0)
            def _():
                fn(r, rid)
            return c
        jax.lax.fori_loop(0, bl, body, 0)

    each_valid(lambda r, rid: pltpu.make_async_copy(
        src_ref.at[pl.ds(rid, 1)], buf_ref.at[pl.ds(r, 1)], sem).start())
    each_valid(lambda r, rid: pltpu.make_async_copy(
        src_ref.at[pl.ds(0, 1)], buf_ref.at[pl.ds(0, 1)], sem).wait())
    out_ref[...] = jnp.where(ids_ref[...] >= 0,
                             rows_from_words(buf_ref[...], dtype),
                             jnp.zeros((), dtype))


@functools.partial(jax.jit, static_argnames=("bl",))
def gather_rows_pallas(src: jax.Array, row_ids: jax.Array, *,
                       bl: int = 128) -> jax.Array:
    """Build an (N, d) row buffer straight from ``src`` rows: ``out[i] =
    src[row_ids[i]]``, with ``row_ids[i] < 0`` producing an exact zero row.

    This is the ``ep_a2a`` send-buffer builder: the buffer is filled from
    the dispatch metadata inside the kernel — no intermediate (L·k, d)
    gathered copy is materialized before the scatter into rank order.
    """
    N, = row_ids.shape
    L, d = src.shape
    bl, N_pad = _row_tiles(N, bl)
    rows_p = jnp.pad(jnp.minimum(row_ids.astype(jnp.int32), L - 1),
                     (0, N_pad - N), constant_values=-1)
    sw = row_words(src)
    it = src.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_gather_rows_kernel, bl=bl, dtype=src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N_pad // bl,),
            in_specs=[pl.BlockSpec((bl, 1), lambda t, *s: (t, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((bl, d), lambda t, *s: (t, 0)),
            scratch_shapes=[pltpu.VMEM((bl, 1, sw.shape[-1]), sw.dtype),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((N_pad, d), src.dtype),
        compiler_params=compiler_params(
            "gather_rows_pallas", bl * d * (it + 2 * it) + 2 * bl * 128 * 4),
        interpret=kernels.interpret_mode(),
    )(rows_p, rows_p.reshape(N_pad, 1), sw)
    return out[:N]


# ---------------------------------------------------------------------------
# Grouped weight gradient on the same work-item machinery
# ---------------------------------------------------------------------------


def _dw_kernel(tile_ref, expert_ref, lo_ref, hi_ref, efirst_ref,
               x_ref, g_ref, dw_ref, *, bl: int):
    del tile_ref, expert_ref
    wi = pl.program_id(2)
    mask = _row_mask(lo_ref[wi], hi_ref[wi], bl)
    xt = jnp.where(mask, x_ref[...], 0).astype(jnp.float32)
    # Contract the row axis: (bl, bd), (bl, bh) -> (bd, bh).  Rows outside
    # this item's range are zeroed in xt, so the full-tile dot is exact.
    dwt = jax.lax.dot_general(xt, g_ref[...].astype(jnp.float32),
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    _accumulate(dw_ref, dwt[None], efirst_ref[wi] == 1)


@functools.partial(jax.jit, static_argnames=("bl", "bd", "bh"))
def gmm_dw_pallas(lhs: jax.Array, dout: jax.Array, offsets: jax.Array,
                  *, bl: int = 128, bd: int = 512,
                  bh: int = 512) -> jax.Array:
    """Per-group weight gradient (S, d), (S, h) -> (E, d, h) on the
    work-item grid.

    ``lhs``/``dout`` rows are already in expert order; each work item masks
    its expert's row range inside the tile and accumulates ``x_tile^T @
    dout_tile`` into the ``(bd, bh)`` block of ``dw[expert]``.  The grid is
    ``(d-blocks, h-blocks, items)``: an expert's items are consecutive, so
    each output block is finished in one run (the accumulation pattern TPU
    grids require).  Cross-tile partials genuinely overlap (unlike the
    forward's disjoint row ranges), so the output is fp32 and cast to
    ``lhs.dtype`` only at the end — the backend contract's fp32
    accumulation.  Blocks of *empty* experts are zero-initialized in-kernel.
    """
    S, d = lhs.shape
    h = dout.shape[1]
    E = offsets.shape[0] - 1
    bl, S_pad = _row_tiles(S, bl)
    bd, bh = lane_tile(d, bd), lane_tile(h, bh)
    lhs_p = jnp.pad(lhs, ((0, S_pad - S), (0, 0)))
    dout_p = jnp.pad(dout, ((0, S_pad - S), (0, 0)))
    tile, expert, lo, hi, _, efirst = make_work_items(
        offsets, S_pad // bl, bl, E)
    W = tile.shape[0]
    it = lhs.dtype.itemsize
    vmem = (2 * bl * (bd + bh) * it + 2 * bd * bh * 4
            + bl * (bd + bh) * 4 + bd * bh * 4)
    out = pl.pallas_call(
        functools.partial(_dw_kernel, bl=bl),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(d // bd, h // bh, W),
            in_specs=[
                pl.BlockSpec((bl, bd), lambda dd, hh, wi, *s: (s[0][wi], dd)),
                pl.BlockSpec((bl, bh), lambda dd, hh, wi, *s: (s[0][wi], hh)),
            ],
            out_specs=pl.BlockSpec(
                (1, bd, bh), lambda dd, hh, wi, *s: (s[1][wi], dd, hh)),
        ),
        out_shape=jax.ShapeDtypeStruct((E, d, h), jnp.float32),
        compiler_params=compiler_params("gmm_dw_pallas", vmem),
        interpret=kernels.interpret_mode(),
    )(tile, expert, lo, hi, efirst, lhs_p, dout_p)
    return out.astype(lhs.dtype)
