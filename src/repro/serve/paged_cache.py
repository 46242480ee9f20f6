"""Block-paged KV storage for the serving engine (SHARK-Engine direction).

The seed engine held one dense ``(B, capacity, Hkv, Dh)`` slab per slot —
decode memory scaled with ``slots x capacity`` whether or not a request ever
reached ``capacity`` tokens, and a short request pinned its whole slab until
the longest request in the batch finished.  This module replaces the slab
with a pool of fixed-size *pages* shared by every request:

* :class:`PagedKV` — one layer's page pool, ``(P, page_size, Hkv, Dh)`` in
  the model dtype, or int8 values + f16 per-(position, head) scales when
  quantized (the ``serve/kv_quant`` symmetric scheme, applied at write time);
* per-request *page tables* ``(B, pages_per_seq)`` map logical token
  positions to physical pages.  Unused table entries point at the reserved
  **trash page** (physical page 0): writes to padded positions land there and
  reads from it are always masked, so scatter/gather never needs bounds
  branches;
* :class:`PagePool` — the host-side free-list allocator, now *refcounted*:
  a physical page may be mapped read-only into several requests' page tables
  (prefix sharing) and only returns to the free list when its last reference
  drops.  Guards are O(1) (a membership set rides alongside the LIFO list);
* :class:`PrefixCache` — a trie over full-page prompt chunks.  A finishing
  request donates its full prompt pages; a later request whose prompt shares
  a page-aligned prefix maps the cached pages read-only and prefills only
  the unshared suffix.  The first write into a shared page is forked by the
  engine into a private copy (copy-on-write) — the trash-page idiom already
  makes the page-table remap branch-free.

Masking is by per-request *prefix length*: a gathered slot at logical
position ``t`` is attended iff ``t <= pos_b`` (and inside the sliding window
when one applies).  Right-padded prompts therefore never leak pad keys into
another request's attention — the batched-vs-solo parity gate in
``bench/serving.py`` holds by construction.

Decode attention has two registered implementations (the
``core/gmm_backend`` capability-detection pattern): ``dense`` — the
jnp gather reference below — and ``pallas`` —
``kernels/paged_attention.py``, which walks the page table inside the
kernel via scalar prefetch and reads only pages up to each request's
position.  ``resolve_paged_attn`` applies the arg > env (``REPRO_PAGED_ATTN``)
> auto chain; ``pallas`` is never auto-selected.
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.serve.kv_quant import quantize

NEG_INF = -1e30

#: physical page 0 is never allocated: page-table entries beyond a request's
#: reservation point here, so padded-position writes have a harmless target
#: and gathered trash is masked by the prefix-length test.
TRASH_PAGE = 0


class PagedKV(NamedTuple):
    """One attention layer's page pool.  ``k``/``v`` are ``(P, page_size,
    Hkv, Dh)`` in the storage dtype; int8 storage carries f16 per-vector
    scales ``(P, page_size, Hkv, 1)`` (``None`` otherwise — the pytree
    structure is the static quantization flag)."""

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array | None
    v_scale: jax.Array | None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def page_size(self) -> int:
        return self.k.shape[1]


def init_paged_kv(num_pages: int, page_size: int, n_kv: int, head_dim: int,
                  dtype, *, quantized: bool = False) -> PagedKV:
    shape = (num_pages, page_size, n_kv, head_dim)
    if quantized:
        return PagedKV(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       k_scale=jnp.zeros(shape[:-1] + (1,), jnp.float16),
                       v_scale=jnp.zeros(shape[:-1] + (1,), jnp.float16))
    return PagedKV(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   k_scale=None, v_scale=None)


# ---------------------------------------------------------------------------
# writes
# ---------------------------------------------------------------------------


def _store(x, quantized: bool, dtype):
    """(values, scales|None) in the pool's storage layout."""
    if quantized:
        return quantize(x)
    return x.astype(dtype), None


def _scatter(pages: PagedKV, k, v, phys, off) -> PagedKV:
    """Write flattened k/v rows at ``(phys, off)`` page coordinates."""
    kq, ks = _store(k, pages.quantized, pages.k.dtype)
    vq, vs = _store(v, pages.quantized, pages.v.dtype)
    return PagedKV(
        k=pages.k.at[phys, off].set(kq),
        v=pages.v.at[phys, off].set(vq),
        k_scale=None if ks is None else pages.k_scale.at[phys, off].set(ks),
        v_scale=None if vs is None else pages.v_scale.at[phys, off].set(vs),
    )


def write_prefill(pages: PagedKV, k: jax.Array, v: jax.Array,
                  page_table: jax.Array) -> PagedKV:
    """Scatter a whole right-padded prompt's k/v ``(B, S, Hkv, Dh)`` through
    ``page_table`` ``(B, pages_per_seq)``: logical position ``t`` of request
    ``b`` lands in ``page_table[b, t // page_size]`` at offset
    ``t % page_size``.  Positions past a request's reservation map to the
    trash page (never attended), so the padded tail needs no branch.

    ``S`` may exceed the table's logical width ``pages_per_seq * page_size``
    (callers bucket prompts to power-of-two lengths): columns past the table
    are routed to the trash page explicitly.  Without that routing, JAX's
    clamping gather would alias them onto the LAST table column and the pad
    tail would scatter over the request's own final page — silently
    corrupting valid prompt KV whenever the bucket overshoots the table."""
    B, S = k.shape[:2]
    return write_prefill_offset(pages, k, v, page_table,
                                jnp.zeros((B,), jnp.int32))


def write_prefill_offset(pages: PagedKV, k: jax.Array, v: jax.Array,
                         page_table: jax.Array,
                         offsets: jax.Array) -> PagedKV:
    """:func:`write_prefill` generalized to per-request start positions:
    row ``t`` of request ``b`` lands at *absolute* position
    ``offsets[b] + t`` (prefix sharing prefills only the unshared suffix —
    the shared pages already hold the prefix KV).  Columns past the table
    width are routed to the trash page exactly like :func:`write_prefill`
    (the pow2 bucket may overshoot both the suffix and the table)."""
    B, S = k.shape[:2]
    ps = pages.page_size
    t_abs = offsets[:, None].astype(jnp.int32) + jnp.arange(S)     # (B, S)
    col = t_abs // ps
    ncols = page_table.shape[1]
    phys = jnp.where(
        col < ncols,
        jnp.take_along_axis(page_table, jnp.minimum(col, ncols - 1), axis=1),
        TRASH_PAGE).reshape(-1)                                    # (B*S,)
    off = (t_abs % ps).reshape(-1)
    flat = lambda x: x.reshape((B * S,) + x.shape[2:])
    return _scatter(pages, flat(k), flat(v), phys, off)


def write_decode(pages: PagedKV, k: jax.Array, v: jax.Array,
                 page_table: jax.Array, positions: jax.Array) -> PagedKV:
    """Scatter one token per request: ``k``/``v`` ``(B, 1, Hkv, Dh)`` at
    per-request absolute ``positions`` ``(B,)``."""
    B = k.shape[0]
    ps = pages.page_size
    phys = page_table[jnp.arange(B), positions // ps]     # (B,)
    off = positions % ps
    return _scatter(pages, k[:, 0], v[:, 0], phys, off)


def copy_page(pages: PagedKV, src: jax.Array, dst: jax.Array) -> PagedKV:
    """Device-side page fork: copy physical page ``src``'s contents into
    ``dst`` (the copy-on-write primitive — the writer's page table is then
    remapped host-side to ``dst`` and the shared ``src`` keeps serving its
    other readers untouched)."""
    cp = lambda a: None if a is None else a.at[dst].set(a[src])
    return PagedKV(k=cp(pages.k), v=cp(pages.v),
                   k_scale=cp(pages.k_scale), v_scale=cp(pages.v_scale))


# ---------------------------------------------------------------------------
# attend
# ---------------------------------------------------------------------------


def paged_gather_attention(q: jax.Array, pages: PagedKV,
                           page_table: jax.Array, pos_q: jax.Array, *,
                           window: int = 0, cap: float = 0.0) -> jax.Array:
    """Attention of ``Sq`` query tokens per request against that request's
    gathered pages (the dense reference path).

    q: ``(B, Sq, Hq, Dh)``; ``pos_q`` ``(B, Sq)`` is each query's absolute
    position — its k/v must already be written, and it attends every
    gathered slot ``t <= pos_q`` (window-restricted when one applies).
    ``Sq == 1`` is the decode step; ``Sq > 1`` is the prefix-sharing suffix
    prefill, where the shared prefix is read from cached pages instead of
    being recomputed.  For int8 pools the per-vector scales are applied to
    the score/value rows rather than to the storage: the RESIDENT pool is
    never dequantized, though the gathered per-step view is upcast to f32
    for the dots (transient, proportional to one step's working set, not to
    the pool)."""
    B, Sq, Hq, Dh = q.shape
    ps = pages.page_size
    T = page_table.shape[1] * ps
    Hkv = pages.k.shape[2]
    G = Hq // Hkv
    gather = lambda a: a[page_table].reshape((B, T) + a.shape[2:])
    kg, vg = gather(pages.k), gather(pages.v)
    qf = q.reshape(B, Sq, Hkv, G, Dh) * Dh**-0.5

    if pages.quantized:
        s = jnp.einsum("bqhgd,bthd->bqhgt", qf.astype(jnp.float32),
                       kg.astype(jnp.float32))
        s = s * gather(pages.k_scale)[..., 0].astype(jnp.float32).transpose(
            0, 2, 1)[:, None, :, None, :]
    else:
        s = jnp.einsum("bqhgd,bthd->bqhgt", qf.astype(kg.dtype), kg,
                       preferred_element_type=jnp.float32)
    if cap:
        s = cap * jnp.tanh(s / cap)
    t_ids = jnp.arange(T)
    valid = t_ids[None, None, :] <= pos_q[:, :, None]          # (B, Sq, T)
    if window:
        valid &= t_ids[None, None, :] > pos_q[:, :, None] - window
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if pages.quantized:
        pv = p * gather(pages.v_scale)[..., 0].astype(jnp.float32).transpose(
            0, 2, 1)[:, None, :, None, :]
        out = jnp.einsum("bqhgt,bthd->bqhgd", pv, vg.astype(jnp.float32))
    else:
        out = jnp.einsum("bqhgt,bthd->bqhgd", p.astype(vg.dtype), vg,
                         preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, Hq, Dh).astype(q.dtype)


def paged_attention(q: jax.Array, pages: PagedKV, page_table: jax.Array,
                    positions: jax.Array, *, window: int = 0,
                    cap: float = 0.0, impl: str = "dense") -> jax.Array:
    """One-token attention against the paged cache.

    q: ``(B, 1, Hq, Dh)``; ``positions`` ``(B,)`` is each request's current
    (already written) token position.  ``impl`` selects the registered
    implementation: ``dense`` gathers the request's pages to a
    ``(B, pages_per_seq * page_size, Hkv, Dh)`` view; ``pallas`` walks the
    page table inside the kernel and reads only pages up to each request's
    position (no full-reservation copy materializes)."""
    if impl == "pallas":
        from repro.kernels.paged_attention import paged_attention_pallas
        return paged_attention_pallas(
            q, pages.k, pages.v, pages.k_scale, pages.v_scale,
            page_table, positions, window=window, cap=cap)
    if impl != "dense":
        raise ValueError(f"unknown paged-attention impl {impl!r}; "
                         f"known: {paged_attn_names()}")
    return paged_gather_attention(q, pages, page_table, positions[:, None],
                                  window=window, cap=cap)


# ---------------------------------------------------------------------------
# paged-attention implementation registry (the gmm_backend pattern)
# ---------------------------------------------------------------------------

PAGED_ATTN_ENV = "REPRO_PAGED_ATTN"


class DensePagedAttn:
    """The jnp gather reference above — available everywhere, and the
    numerical oracle the kernel parity tests compare against."""

    name = "dense"

    @staticmethod
    def available() -> bool:
        return True


class PallasPagedAttn:
    """``kernels/paged_attention.py``: page-table walk via scalar prefetch,
    online softmax across page steps, f32 accumulate, int8 scale-on-scores.
    Compiled on a TPU, interpreted on the CPU; never auto-selected (no chip
    measurement has shown it faster yet)."""

    name = "pallas"

    @staticmethod
    def available() -> bool:
        try:
            import repro.kernels.paged_attention  # noqa: F401
        except Exception:  # pragma: no cover - import guard
            return False
        return True


_ATTN_REGISTRY: dict[str, object] = {
    b.name: b for b in (DensePagedAttn, PallasPagedAttn)
}
#: auto order: the XLA gather path only — ``pallas`` is requested explicitly
#: until a chip measurement shows it faster.
_ATTN_AUTO = ("dense",)


def paged_attn_names() -> list[str]:
    return list(_ATTN_REGISTRY)


def available_paged_attn() -> list[str]:
    return [n for n, b in _ATTN_REGISTRY.items() if b.available()]


@dataclass(frozen=True)
class ResolvedPagedAttn:
    """A validated paged-attention implementation choice with provenance
    (mirrors ``gmm_backend.ResolvedBackend``: ``source`` records which
    precedence slot won)."""

    name: str
    source: str
    jax_version: str

    def __str__(self) -> str:                   # pragma: no cover - trivial
        return self.name


def _validate_attn(name: str) -> str:
    if name not in _ATTN_REGISTRY:
        raise ValueError(f"unknown paged-attention impl {name!r}; "
                         f"known: {paged_attn_names()}")
    if not _ATTN_REGISTRY[name].available():
        raise RuntimeError(
            f"paged-attention impl {name!r} is not available on jax "
            f"{jax.__version__}; available: {available_paged_attn()}")
    return name


def resolve_paged_attn(impl: str | ResolvedPagedAttn | None = None, *,
                       config: str | None = None) -> ResolvedPagedAttn:
    """arg > config > ``REPRO_PAGED_ATTN`` env > auto (``dense``)."""
    if isinstance(impl, ResolvedPagedAttn):
        return impl
    chain = (("arg", impl), ("config", config),
             ("env", os.environ.get(PAGED_ATTN_ENV, "").strip() or None))
    for source, cand in chain:
        if cand not in (None, "", "auto"):
            return ResolvedPagedAttn(_validate_attn(cand), source,
                                     jax.__version__)
    for cand in _ATTN_AUTO:
        if _ATTN_REGISTRY[cand].available():
            return ResolvedPagedAttn(cand, "auto", jax.__version__)
    raise RuntimeError("no paged-attention impl available")  # pragma: no cover


# ---------------------------------------------------------------------------
# host-side page allocator (refcounted)
# ---------------------------------------------------------------------------


class PagePool:
    """Refcounted free-list page allocator (host side; pages are ints).

    Page ``TRASH_PAGE`` is reserved at construction.  Frees push onto the
    list tail and allocs pop from it (LIFO), so a request admitted right
    after another finishes reuses the same physical pages — the property the
    page-table-reuse regression test pins down.

    Prefix sharing maps one physical page into several page tables:
    :meth:`share` takes an extra reference and :meth:`release` drops one;
    the page only rejoins the free list when its count reaches zero.
    Guards are O(1): a membership set mirrors the LIFO list (the old
    ``p in self._free`` scan was O(P) per page, O(P²) per batch of frees),
    and the refcount array catches double frees and invalid pages."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (one is the reserved trash page)")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, TRASH_PAGE, -1))
        self._free_set = set(self._free)
        self._refs = [0] * num_pages
        self.min_free = len(self._free)       # low-water mark (stats)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        return self._refs[page]

    def alloc(self, n: int) -> list[int]:
        """Pop ``n`` pages (each born with one reference); raises if the
        pool cannot satisfy the request (callers check :attr:`free_pages`
        first — admission control)."""
        if n > len(self._free):
            raise RuntimeError(f"page pool exhausted: want {n}, "
                               f"have {len(self._free)}")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._free_set.discard(p)
            self._refs[p] = 1
        self.min_free = min(self.min_free, len(self._free))
        return pages

    def _check_allocated(self, p: int) -> None:
        if p == TRASH_PAGE or not (0 < p < self.num_pages):
            raise ValueError(f"freeing invalid page {p}")
        if p in self._free_set or self._refs[p] < 1:
            raise ValueError(f"double free of page {p}")

    def share(self, page: int) -> int:
        """Take an extra reference on an allocated page (map it read-only
        into another page table).  Returns the new count."""
        self._check_allocated(page)
        self._refs[page] += 1
        return self._refs[page]

    def release(self, page: int) -> int:
        """Drop one reference; the page rejoins the free list (LIFO tail)
        when the count reaches zero.  Returns the remaining count."""
        self._check_allocated(page)
        self._refs[page] -= 1
        if self._refs[page] == 0:
            self._free.append(page)
            self._free_set.add(page)
        return self._refs[page]

    def free(self, pages: list[int]) -> None:
        """Drop one reference on each page.  The whole batch is validated
        before any mutation (a bad page never half-applies the free): a page
        appearing k times in the batch needs refcount >= k, else the batch
        would drive its count negative mid-apply.  Pages reaching zero
        rejoin in reversed order — preserving the exact LIFO reuse order of
        the pre-refcount allocator."""
        occurrences: dict[int, int] = {}
        for p in pages:
            self._check_allocated(p)
            occurrences[p] = occurrences.get(p, 0) + 1
            if occurrences[p] > self._refs[p]:
                raise ValueError(
                    f"double free of page {p}: batch frees it "
                    f"{occurrences[p]} times but refcount is {self._refs[p]}")
        for p in reversed(pages):
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
                self._free_set.add(p)


def pages_needed(n_tokens: int, page_size: int) -> int:
    return -(-max(n_tokens, 1) // page_size)


# ---------------------------------------------------------------------------
# prefix-trie page cache (copy-on-write prefix sharing)
# ---------------------------------------------------------------------------


def page_keys(prompt, page_size: int) -> list[bytes]:
    """Content keys of a prompt's FULL pages: one ``bytes`` per complete
    ``page_size`` chunk (the partial tail page is never shared — its page
    will be written by the owner's decode stream)."""
    import numpy as np
    p = np.asarray(prompt, np.int32)
    return [p[i * page_size:(i + 1) * page_size].tobytes()
            for i in range(p.size // page_size)]


class _TrieNode:
    __slots__ = ("page", "children", "last_use")

    def __init__(self, page: int, tick: int):
        self.page = page
        self.children: dict[bytes, _TrieNode] = {}
        self.last_use = tick


class PrefixCache:
    """Trie keyed by full-page prompt content, each node pinning one
    physical page of prompt KV (the cache holds one pool reference per
    node).  ``lookup`` walks the longest cached chain; ``insert`` adopts a
    finished request's full prompt pages (transferring the caller's
    reference); ``evict`` drops least-recently-used *leaf* nodes whose page
    no live request still maps — interior nodes are never evicted before
    their children, so every cached chain stays reachable from the root."""

    def __init__(self):
        self._root: dict[bytes, _TrieNode] = {}
        self._tick = 0
        self._n_pages = 0

    def __len__(self) -> int:
        return self._n_pages

    def lookup(self, keys: list[bytes]) -> list[int]:
        """Longest cached page chain matching ``keys`` front-to-back."""
        self._tick += 1
        out: list[int] = []
        level = self._root
        for key in keys:
            node = level.get(key)
            if node is None:
                break
            node.last_use = self._tick
            out.append(node.page)
            level = node.children
        return out

    def insert(self, keys: list[bytes], pages: list[int]) -> set[int]:
        """Register ``pages`` along the ``keys`` path.  Returns the set of
        pages the cache ADOPTED (it now owns the caller's reference on
        those); pages whose key already had a node are not adopted — the
        caller still owns its reference and should release it."""
        self._tick += 1
        adopted: set[int] = set()
        level = self._root
        for key, page in zip(keys, pages):
            node = level.get(key)
            if node is None:
                node = _TrieNode(page, self._tick)
                level[key] = node
                adopted.add(page)
                self._n_pages += 1
            else:
                node.last_use = self._tick
            level = node.children
        return adopted

    def evict(self, pool: PagePool, n: int) -> int:
        """Release up to ``n`` cached pages back to ``pool``, least recently
        used leaves first (a node is evictable only when it has no children
        and no live request shares its page, i.e. the cache holds the sole
        reference).  Returns the number of pages actually evicted."""
        evicted = 0
        while evicted < n:
            # collect current leaves with their parents
            leaves: list[tuple[dict, bytes, _TrieNode]] = []
            stack = [(self._root, key, node) for key, node in
                     self._root.items()]
            while stack:
                level, key, node = stack.pop()
                if node.children:
                    stack.extend((node.children, k, c)
                                 for k, c in node.children.items())
                else:
                    leaves.append((level, key, node))
            leaves = [(lv, k, nd) for lv, k, nd in leaves
                      if pool.refcount(nd.page) == 1]
            if not leaves:
                break
            leaves.sort(key=lambda t: t[2].last_use)
            level, key, node = leaves[0]
            del level[key]
            self._n_pages -= 1
            pool.release(node.page)
            evicted += 1
        return evicted
