"""Helpers shared by the Pallas kernels: row-granular HBM DMA (gather and
scatter of single token rows), tile clamps and the VMEM guard.

A TPU DMA moves whole tiles of the second-minor dimension: a ``(1, d)``
slice of an ``(L, d)`` array is refused ("slice shape along dimension 0 must
be aligned to tiling").  Rows therefore travel as ``(L, 1, w)`` arrays, one
row per tile, of 32-bit words: float32 rows as they are, bfloat16 rows with
column ``j`` packed beside column ``j + d/2`` in one word (packed dtypes
share a tile between two rows, so a single bf16 row is never DMA-able on its
own).  :func:`rows_from_words` undoes the packing in VMEM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hardware, kernels


def largest_divisor_tile(n: int, b: int) -> int:
    """Largest divisor of ``n`` that is ``<= b`` (static Python ints).

    ``largest_divisor_tile(192, 128) == 96``.  Always >= 1, so any positive
    ``n`` has a valid tiling.
    """
    b = max(1, min(int(b), int(n)))
    while n % b:
        b -= 1
    return b


def lane_tile(n: int, b: int) -> int:
    """A tile for a lane (minor) dimension of width ``n``, at most ``b``
    where possible: the largest multiple of 128 dividing ``n``, or ``n``
    itself (a full-width block) when ``n`` is not a multiple of 128 — the
    two block widths Mosaic accepts."""
    if n % 128:
        return n
    return 128 * largest_divisor_tile(n // 128, max(b // 128, 1))


def row_words(x: jax.Array) -> jax.Array:
    """``(L, d)`` float32/bfloat16 rows -> ``(L, 1, w)`` 32-bit words, the
    DMA-able row layout (see the module docstring)."""
    L, d = x.shape
    if x.dtype.itemsize == 4:
        return x.reshape(L, 1, d)
    if x.dtype != jnp.bfloat16 or d % 2:
        raise ValueError(f"row DMA supports float32 and even-width bfloat16 "
                         f"rows, got {x.dtype} of width {d}")
    half = d // 2
    w = jax.lax.bitcast_convert_type(
        jnp.stack([x[:, :half], x[:, half:]], axis=-1), jnp.uint32)
    return w.reshape(L, 1, half)


def rows_from_words(w: jax.Array, dtype) -> jax.Array:
    """In-kernel inverse of :func:`row_words`: ``(n, 1, w)`` words ->
    ``(n, d)`` rows of ``dtype``.  A bfloat16 value is the top half of the
    float32 with the same bits, so each word unpacks by a shift and a
    bitcast."""
    n = w.shape[0]
    w = w.reshape(n, w.shape[-1])
    if jnp.dtype(dtype).itemsize == 4:
        return w
    lo = jax.lax.bitcast_convert_type(w << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return jnp.concatenate([lo, hi], axis=1).astype(dtype)


def dma_rows(src, dst, sem, lo, hi, src_row, dst_row):
    """Copy rows ``r`` in ``[lo, hi)``: ``dst[dst_row(r)] <- src[src_row(r)]``
    (each a ``(1, 1, w)`` slice of an ``(n, 1, w)`` ref), all in flight at
    once, then wait for every one."""
    def start(r, c):
        pltpu.make_async_copy(src.at[pl.ds(src_row(r), 1)],
                              dst.at[pl.ds(dst_row(r), 1)], sem).start()
        return c

    def wait(r, c):
        pltpu.make_async_copy(src.at[pl.ds(0, 1)], dst.at[pl.ds(0, 1)],
                              sem).wait()
        return c

    jax.lax.fori_loop(lo, hi, start, 0)
    jax.lax.fori_loop(lo, hi, wait, 0)


def compiler_params(name: str, vmem_bytes: int):
    """Mosaic parameters for a kernel whose VMEM working set at its tile
    sizes is ``vmem_bytes``.  Compiled, a set larger than the chip's VMEM
    raises here, before lowering, naming the need and the limit; under the
    interpreter VMEM does not exist and nothing is checked."""
    if kernels.interpret_mode():
        return None
    limit = hardware.peaks().vmem_bytes
    if vmem_bytes > limit:
        raise ValueError(
            f"{name} needs {vmem_bytes / 2**20:.1f} MiB of VMEM at these tile "
            f"sizes; the chip has {limit / 2**20:.0f} MiB")
    # Double-buffering slack and Mosaic's own temporaries.
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(limit, vmem_bytes * 3 // 2 + 8 * 2**20)))
