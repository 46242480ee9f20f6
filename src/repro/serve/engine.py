"""Paged, continuously-batched serving engine (SHARK-Engine architecture).

Requests enter a queue (``enqueue`` / ``run``) or come as a batch
(``generate``), and the scheduler runs them through two jitted entry
families:

* **prefill** — ONE whole-prompt forward per admitted batch (bucketed to
  power-of-two ``(batch, seq)`` shapes so the jit cache stays bounded) that
  scatters every prompt position's k/v through per-request *page tables*
  into a block-paged KV pool (``serve/paged_cache``).  Prompts are
  right-padded and masked by per-request prefix length, so batched output ==
  solo output (the left-pad parity gate).
* **decode** — a single-token step over the full slot array with every
  request at its OWN position (``T.paged_decode_step``).  Inactive slots
  point at the reserved trash page and cost no correctness.  The gather can
  run as the dense jnp reference or the Pallas page-walk kernel
  (``paged_kernel=`` / ``REPRO_PAGED_ATTN``, resolved at construction like
  the grouped-GEMM backend).

Sampling is FOLDED INTO the jitted steps: only ``(slots,)`` token ids cross
the host boundary each step, never ``(slots, vocab)`` logits.  Greedy
argmaxes in-graph; ``greedy=False`` temperature-samples with a per-request
PRNG key — ``fold_in(fold_in(seed_key, request_id), token_index)`` — so a
request's token stream depends only on its own id and seed, NEVER on how
requests were batched or scheduled.  That schedule-independence is what
makes the async runtime (``serve/runtime``) token-identical to this
synchronous path under a fixed seed (the pipeline parity gate).

**Prefix sharing (``prefix_cache=True``)**: the engine keeps a persistent
:class:`~repro.serve.paged_cache.PrefixCache` — a trie over full-page
prompt chunks.  A finishing request donates its full prompt pages; a later
request whose prompt shares a page-aligned prefix maps the cached pages
read-only (one pool refcount each) and prefills ONLY the unshared suffix
through the offset-prefill path.  When the prompt is exactly covered by
shared pages, the last prompt token is re-fed and its target page is forked
first — copy-on-write: the writer gets a private device-side copy
(``paged_cache.copy_page``), the page table is remapped (branch-free, the
trash-page idiom), and the sharer's page is never mutated.  Cache pages are
evicted LRU-leaf-first when admission needs their space.

Scheduling is continuous and split into three stages — **admission**
(validation, prefix lookup, slot/page allocation, COW forks), **device**
(jitted prefill/decode dispatch; everything stays on device, including each
step's sampled tokens feeding the next step), and **sampling/emission**
(the only host sync: token ids to Python, ``on_token`` callbacks, EOS/limit
finish decisions).  The synchronous engine chains the stages inline;
``serve/runtime.AsyncServeRuntime`` runs them in pipelined threads
connected by ``WorkQueue``s.  A request's slot and pages return to the pool
the moment it finishes; admission is under a page budget with FIFO blocking
(``stats['blocked_admissions']``).

``kv_dtype='int8'`` stores the pool quantized via ``serve/kv_quant``'s
symmetric per-(position, head) scheme.

Grouped-GEMM backend selection is context-scoped (DESIGN: mixed fleets share
one config while each host/engine picks its fastest available backend): the
engine resolves once at construction (engine argument > ``use_backend``
scope > ``cfg.gmm_backend`` > env > auto) and holds the
``ResolvedBackend``; each ``Request`` may carry its own override, validated
at enqueue time; ``generate`` groups slots by resolved backend.  Steps are
jitted per backend name inside ``use_backend`` so an ambient scope at
first-trace time cannot leak into the cached computation.
"""

from __future__ import annotations

import itertools

from collections import deque
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import checkpoint as CK
from repro.core import gmm_backend as GB
from repro.models import transformer as T
from repro.serve import paged_cache as PC


@dataclass
class Request:
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 32
    eos_id: int = 2
    gmm_backend: str | None = None  # per-request override of the engine default
    on_token: Callable[[int], None] | None = None   # streaming: per token
    on_finish: Callable[[str], None] | None = None  # terminal event (reason)
    out_tokens: list = field(default_factory=list)
    done: bool = False
    finish_reason: str | None = None    # "eos" | "length" | "error"
    rid: int | None = None              # engine-assigned id (PRNG lane)


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _emit_token(r: Request, tok: int) -> None:
    r.out_tokens.append(tok)
    if r.on_token is not None:
        r.on_token(tok)


def _finish_request(r: Request, reason: str) -> None:
    r.done = True
    if r.finish_reason is None:
        r.finish_reason = reason
    if r.on_finish is not None:
        r.on_finish(reason)


class ServeEngine:
    def __init__(self, cfg, params, *, batch_slots: int = 4,
                 capacity: int = 512, page_size: int = 16,
                 num_pages: int | None = None, kv_dtype: str | None = None,
                 greedy: bool = True, temperature: float = 1.0,
                 seed: int = 0, gmm_backend: str | None = None,
                 prefix_cache: bool = False, paged_kernel: str | None = None,
                 remat_policy=None, mesh=None):
        # Snapshot the backend resolution at construction: precedence is the
        # explicit engine argument > active use_backend scope >
        # cfg.gmm_backend > env > auto, frozen into a ResolvedBackend.
        self.backend = GB.resolve(gmm_backend, config=cfg.gmm_backend)
        # The paged-attention implementation resolves with the same
        # discipline (arg > REPRO_PAGED_ATTN env > auto) and is baked into
        # the jitted steps — an unknown/unavailable kernel raises HERE.
        self.paged_attn = PC.resolve_paged_attn(paged_kernel)
        # Same for the checkpoint plan: the engine argument wins over
        # cfg.remat_policy; an unparseable spec raises HERE, never
        # mid-generate.  Decode never runs a backward, so the plan is
        # provenance + config hygiene.
        self.remat_plan = CK.resolve_plan(remat_policy,
                                          config=cfg.remat_policy)
        self.cfg = cfg.replace(gmm_backend=self.backend.name,
                               remat_policy=self.remat_plan.spec)
        if not T.paged_supported(cfg):
            raise ValueError(
                f"ServeEngine pages attention KV; {cfg.name} has "
                f"block pattern {cfg.block_pattern} (SSM carries are O(1) "
                f"per-slot state — serve those via T.decode_step directly)")
        if kv_dtype not in (None, "model", "int8"):
            raise ValueError(f"kv_dtype must be None|'model'|'int8', "
                             f"got {kv_dtype!r}")
        if not greedy and temperature <= 0:
            raise ValueError("temperature must be > 0 for sampling")
        if cfg.is_moe:
            # Eagerly validate the plan's moe-scoped residual decisions
            # (coupled-FFN_A/B or save-Y_swi-under-recompute-A/B raise).
            CK.moe_residual_mode(self.cfg)
        # Validate the MoE distribution mode for this (cfg, mesh) pairing at
        # construction — decode steps run it via shard_map when a mesh is
        # given, and a bad pairing must not surface mid-generate.  The token
        # exchanges are degenerate for decode (single-token slabs rarely
        # divide the expert axes, and there is nothing to exchange at S=1),
        # so an explicit ep_a2a / ep_a2a_hier falls back to plain EP:
        # numerically identical, same expert-sharded weight layout.  'auto'
        # stays 'auto' — the cost model resolves it per decode slab, and its
        # live-bytes tie-break lands on EP for decode-sized token counts.
        if cfg.is_moe:
            from repro.models.moe_block import resolve_moe_parallel
            if self.cfg.moe_parallel in ("ep_a2a", "ep_a2a_hier"):
                self.cfg = self.cfg.replace(moe_parallel="ep")
            resolve_moe_parallel(self.cfg, mesh)
        self.mesh = mesh
        self.params = params
        self.slots = batch_slots
        self.capacity = capacity
        self.page_size = page_size
        self.quantized = kv_dtype == "int8"
        self.pages_per_seq = PC.pages_needed(capacity, page_size)
        # Default budget: full occupancy at max capacity, plus the trash page.
        self.num_pages = (num_pages if num_pages is not None
                          else 1 + batch_slots * self.pages_per_seq)
        if self.num_pages < 2:
            raise ValueError("num_pages must be >= 2 (one is the trash page)")
        self.greedy = greedy
        self.temperature = temperature
        self._base_key = jax.random.PRNGKey(seed)
        self.pending: list[Request] = []
        # itertools.count: a single next() is atomic, so concurrent
        # submit() threads (async runtime) never mint duplicate rids.
        self._rid_counter = itertools.count()
        # Persistent device state: the page pool, the paged KV cache, and
        # the prefix trie live for the engine's life (prefix hits span
        # generate() calls), lazily created at first use.
        self._pool: PC.PagePool | None = None
        self._cache = None
        self._prefix = PC.PrefixCache() if prefix_cache else None
        self._decode_fns: dict[str, object] = {}
        self._prefill_fns: dict[tuple, object] = {}
        # last_tok scatter for admitted slots (shape-specialized by jit).
        self._merge_fn = jax.jit(
            lambda lt, tk, idx: lt.at[idx, 0].set(tk[:idx.shape[0]]))
        # COW fork: copy one physical page across every layer's pools
        # (leaves are (num_groups, P, page_size, ...) — page axis is 1).
        self._copy_page_fn = jax.jit(
            lambda c, src, dst: jax.tree.map(
                lambda a: a.at[:, dst].set(a[:, src]), c),
            donate_argnums=(0,))
        self.stats = {"prefill_calls": 0, "prefill_tokens": 0,
                      "decode_steps": 0, "decode_slot_tokens": 0,
                      "generated_tokens": 0, "blocked_admissions": 0,
                      "truncated_budgets": 0, "peak_pages_used": 0,
                      "prefix_hits": 0, "prefix_misses": 0,
                      "shared_pages_mapped": 0, "cow_forks": 0,
                      "prefix_evictions": 0}

    # -- persistent device state --------------------------------------------

    def _ensure_state(self) -> None:
        if self._pool is None:
            self._pool = PC.PagePool(self.num_pages)
            self._cache = T.init_paged_cache(self.cfg, self.num_pages,
                                             self.page_size,
                                             quantized=self.quantized)

    # -- jitted entry points ------------------------------------------------

    def _sample_traced(self, logits, rid, gidx):
        """In-graph sampling: (B, vocab) logits -> (B,) int32 token ids.
        Greedy argmaxes; otherwise each row samples with its own
        ``fold_in(fold_in(seed, rid), token_index)`` key — schedule- and
        batch-independent, the property both parity gates lean on."""
        if self.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        base = self._base_key

        def one(r, g, row):
            key = jax.random.fold_in(jax.random.fold_in(base, r), g)
            return jax.random.categorical(key, row / self.temperature)

        return jax.vmap(one)(rid, gidx, logits).astype(jnp.int32)

    def _decode_for(self, backend_name: str):
        """The jitted single-token decode step specialized to one backend —
        full slot array, per-request positions, sampling fused in (only the
        ``(slots,)`` token ids ever reach the host)."""
        fn = self._decode_fns.get(backend_name)
        if fn is None:
            cfg = self.cfg.replace(gmm_backend=backend_name)
            impl = self.paged_attn.name

            def step(p, c, tok, lens, pt, rid, gidx):
                logits, c2 = T.paged_decode_step(p, c, tok, lens, pt, cfg,
                                                 mesh=self.mesh,
                                                 attn_impl=impl)
                return self._sample_traced(logits, rid, gidx), c2

            fn = jax.jit(step, donate_argnums=(1,))   # cache updated in place
            self._decode_fns[backend_name] = fn
        return fn

    def _prefill_for(self, backend_name: str, bs: int, seq: int,
                     prefix: bool):
        """The jitted whole-prompt (or unshared-suffix) prefill for one
        (backend, batch-bucket, seq-bucket, prefix-path) — the SHARK
        per-batch-size entry-point family, with power-of-two bucketing
        keeping the family finite.  Returns sampled tokens, not logits."""
        key = (backend_name, bs, seq, prefix)
        fn = self._prefill_fns.get(key)
        if fn is None:
            cfg = self.cfg.replace(gmm_backend=backend_name)
            impl = self.paged_attn.name

            def pf(p, c, tok, lens, pt, offs, rid):
                logits, c2 = T.prefill(
                    p, tok, lens, c, pt, cfg, mesh=self.mesh,
                    offsets=offs if prefix else None, attn_impl=impl)
                gidx = jnp.zeros_like(rid)
                return self._sample_traced(logits, rid, gidx), c2

            fn = jax.jit(pf, donate_argnums=(1,))
            self._prefill_fns[key] = fn
        return fn

    # -- validation ---------------------------------------------------------

    def resolve_request(self, request: Request) -> GB.ResolvedBackend:
        """The backend a request will decode with: its own override at the
        call-site slot, falling back to the engine's construction-time
        snapshot.  Raises on unknown/unavailable names."""
        if request.gmm_backend in (None, "", "auto"):
            return self.backend
        return GB.resolve(request.gmm_backend, config=self.cfg.gmm_backend)

    def _limit(self, request: Request) -> int:
        """Effective new-token budget: the cache holds ``prompt + (T - 1)``
        written tokens for T generated, bounded by ``capacity``."""
        return min(request.max_new_tokens,
                   self.capacity - request.prompt.size + 1)

    def _validate(self, request: Request) -> None:
        self.resolve_request(request)
        if request.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {request.max_new_tokens} "
                f"(prefill always samples one token)")
        if request.prompt.size > self.capacity:
            raise ValueError(
                f"prompt of {request.prompt.size} tokens exceeds engine "
                f"capacity {self.capacity}")
        need = PC.pages_needed(
            request.prompt.size + self._limit(request) - 1, self.page_size)
        if need > self.num_pages - 1:
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{self.num_pages - 1} allocatable pages")
        if request.rid is None:
            request.rid = next(self._rid_counter)

    # -- queue API ----------------------------------------------------------

    def enqueue(self, request: Request) -> Request:
        """Admit a request to the pending queue.  Backend + budget
        validation happens HERE — an unknown ``gmm_backend`` or an
        impossible-to-schedule request raises at enqueue, never mid-generate
        with other requests' tokens in flight."""
        self._validate(request)
        self.pending.append(request)
        return request

    def run(self) -> list[Request]:
        """Drain the pending queue.  The scheduler batches continuously, so
        the whole queue goes in at once — slots refill as requests finish."""
        batch = self.pending
        self.pending = []
        return self.generate(batch)

    # -- batched generation -------------------------------------------------

    def generate(self, requests: list[Request]) -> list[Request]:
        # Validate every request up front (raises before any decode work),
        # then group by resolved backend — one batch may mix overrides.
        for r in requests:
            self._validate(r)
        resolved = [self.resolve_request(r) for r in requests]
        groups: dict[str, list[int]] = {}
        for i, rb in enumerate(resolved):
            groups.setdefault(rb.name, []).append(i)
        for name, idxs in groups.items():
            self._serve_group([requests[i] for i in idxs], name)
        return requests

    def _serve_group(self, requests: list[Request], backend_name: str):
        """Continuously serve one group of requests sharing a backend: the
        three pipeline stages chained inline (the async runtime runs the
        same :class:`_GroupScheduler` stages across threads)."""
        sched = _GroupScheduler(self, requests, backend_name)
        # The use_backend scope pins trace-time resolution to this group's
        # backend even if the caller holds an ambient scope of their own.
        with GB.use_backend(backend_name):
            try:
                while sched.has_work():
                    admit = sched.try_admit()             # admission stage
                    if admit:
                        snap = [(s, sched.owner[s]) for s in admit]
                        ptoks = sched.dispatch_prefill(admit)   # device
                        for s in sched.emit_prefill(snap, np.asarray(ptoks)):
                            sched.release(s)              # emission stage
                    out = sched.dispatch_decode()         # device stage
                    if out is None:
                        continue
                    toks, snap = out
                    for s in sched.emit_decode(snap, np.asarray(toks)):
                        sched.release(s)                  # emission stage
            except Exception:
                for r in sched.in_flight() + list(sched.waiting):
                    if not r.done:
                        _finish_request(r, "error")
                raise
        self.stats["peak_pages_used"] = max(
            self.stats["peak_pages_used"],
            self.num_pages - 1 - self._pool.min_free)


class _GroupScheduler:
    """The old ``_serve_group`` monolith split into its three stages.

    * **admission** — :meth:`try_admit`: FIFO under the page budget, prefix
      trie lookup, shared-page mapping (refcounts), COW forks, LRU cache
      eviction under pressure;
    * **device** — :meth:`dispatch_prefill` / :meth:`dispatch_decode`: build
      host staging buffers, issue the jitted steps, keep the sampled-token
      array device-resident (each step's output feeds the next step's input
      without a host round-trip);
    * **sampling/emission** — :meth:`emit_prefill` / :meth:`emit_decode`:
      the only host sync; append tokens, fire streaming callbacks, decide
      EOS/limit finishes.  :meth:`release` returns a finished slot's pages
      (donating full prompt pages to the prefix cache).

    The synchronous engine calls the stages back-to-back; the async runtime
    (``serve/runtime``) calls admission+device on its device thread and
    emit_* on its emission thread, connected by ``WorkQueue``s.  Because
    sampling keys are per-request (never per-step-of-the-engine), tokens do
    not depend on which stage interleaving ran them.
    """

    def __init__(self, eng: ServeEngine, requests: list[Request],
                 backend_name: str):
        eng._ensure_state()
        self.eng = eng
        self.backend_name = backend_name
        self.pool = eng._pool
        self.ps = eng.page_size
        self.pps = eng.pages_per_seq
        n = eng.slots
        self.waiting: deque[Request] = deque(requests)
        self.free_slots = list(range(n - 1, -1, -1))
        self.owner: list[Request | None] = [None] * n
        self.mapped_pages: list[list[int] | None] = [None] * n
        self.shared_cols: list[dict | None] = [None] * n
        self.suffix_start = [0] * n
        self.cap_of = np.zeros(n, np.int32)     # max tokens ever written
        self.page_table = np.full((n, self.pps), PC.TRASH_PAGE, np.int32)
        self.lengths = np.zeros(n, np.int32)    # tokens in cache
        self.gen_count = np.zeros(n, np.int32)  # tokens produced (PRNG lane)
        self.rid = np.zeros(n, np.int32)
        self.last_tok = jnp.zeros((n, 1), jnp.int32)   # device-resident
        self.decode_fn = eng._decode_for(backend_name)

    def has_work(self) -> bool:
        return bool(self.waiting) or any(o is not None for o in self.owner)

    def in_flight(self) -> list[Request]:
        return [o for o in self.owner if o is not None]

    # -- admission stage ----------------------------------------------------

    def try_admit(self) -> list[int]:
        """Admit pending requests while slots + pages allow, preserving FIFO
        order under the page budget.  With the prefix cache enabled, each
        prompt's full-page chain is looked up first: hits map the cached
        pages read-only (share refs) and shrink the private-page need to the
        unshared suffix; a fully-covered prompt re-feeds its last token into
        a copy-on-write fork of the final shared page."""
        eng = self.eng
        st = eng.stats
        admit: list[int] = []
        while self.waiting and self.free_slots:
            r = self.waiting[0]
            plen = int(r.prompt.size)
            limit = eng._limit(r)
            total_need = PC.pages_needed(plen + limit - 1, self.ps)
            def plan(shared):
                # A prompt exactly covered by shared pages still needs one
                # forward token for its first logits: re-feed the last
                # prompt token (its write forks the final shared page —
                # COW).
                n_shared = len(shared)
                refeed = n_shared > 0 and n_shared * self.ps >= plen
                sstart = plen - 1 if refeed else n_shared * self.ps
                need_private = total_need - n_shared + (1 if refeed else 0)
                return n_shared, refeed, sstart, need_private

            shared: list[int] = []
            if eng._prefix is not None:
                shared = eng._prefix.lookup(PC.page_keys(r.prompt, self.ps))
                # Pin the looked-up chain BEFORE any eviction: share()
                # lifts each page's refcount above 1, so evict() (which
                # only frees sole-owner leaves) can never reclaim the
                # pages this request is about to map.
                for pg in shared:
                    self.pool.share(pg)
            n_shared, refeed, sstart, need_private = plan(shared)
            if need_private > self.pool.free_pages and eng._prefix is not None:
                st["prefix_evictions"] += eng._prefix.evict(
                    self.pool, need_private - self.pool.free_pages)
                if need_private > self.pool.free_pages and shared:
                    # Not enough evictable OUTSIDE the pinned chain: trade
                    # sharing for capacity.  Unpin, evict again (the chain
                    # was just touched, so LRU takes everything else
                    # first), and re-plan on whatever chain survived.
                    for pg in shared:
                        self.pool.release(pg)
                    st["prefix_evictions"] += eng._prefix.evict(
                        self.pool, total_need - self.pool.free_pages)
                    shared = eng._prefix.lookup(
                        PC.page_keys(r.prompt, self.ps))
                    for pg in shared:
                        self.pool.share(pg)
                    n_shared, refeed, sstart, need_private = plan(shared)
            if need_private > self.pool.free_pages:
                # FIFO under the page budget: the head waits (and is
                # accounted), later requests do not jump it.  Unpin the
                # chain — the cache keeps its own reference.
                for pg in shared:
                    self.pool.release(pg)
                st["blocked_admissions"] += 1
                break
            self.waiting.popleft()
            if limit < r.max_new_tokens:
                # Capacity silently bounds the budget; surface it.
                st["truncated_budgets"] += 1
            if eng._prefix is not None:
                st["prefix_hits" if n_shared else "prefix_misses"] += 1
                st["shared_pages_mapped"] += n_shared
            slot = self.free_slots.pop()
            priv = self.pool.alloc(need_private)
            row = np.full(self.pps, PC.TRASH_PAGE, np.int32)
            row[:n_shared] = shared
            n_tail = total_need - n_shared
            if n_tail:
                row[n_shared:total_need] = priv[:n_tail]
            self.owner[slot] = r
            self.mapped_pages[slot] = shared + priv
            self.shared_cols[slot] = {c: shared[c] for c in range(n_shared)}
            self.suffix_start[slot] = sstart
            self.cap_of[slot] = plen + limit - 1
            self.lengths[slot] = 0
            self.gen_count[slot] = 0
            self.rid[slot] = r.rid
            if refeed:
                self._fork(slot, n_shared - 1, priv[n_tail], row)
            self.page_table[slot] = row
            admit.append(slot)
        return admit

    def _fork(self, slot: int, col: int, new_page: int, row) -> None:
        """Copy-on-write: fork shared column ``col`` into ``new_page`` (a
        device-side page copy), remap the writer's table, and drop the
        writer's reference on the shared original — the sharer's page is
        never written."""
        eng = self.eng
        old = self.shared_cols[slot].pop(col)
        eng._cache = eng._copy_page_fn(eng._cache, old, new_page)
        row[col] = new_page
        self.pool.release(old)
        self.mapped_pages[slot].remove(old)
        eng.stats["cow_forks"] += 1

    # -- device stage -------------------------------------------------------

    def dispatch_prefill(self, admit: list[int]):
        """One jitted prefill over the admitted batch (suffixes only when
        prefix sharing applies).  Returns the sampled-token device array;
        the slots' ``last_tok`` lanes are updated device-side."""
        eng = self.eng
        use_prefix = eng._prefix is not None
        sufs = [self.owner[s].prompt.size - self.suffix_start[s]
                for s in admit]
        # Clamp the pow2 seq bucket to the page table's logical width: a
        # wider bucket would make the prefill pad tail spill past the table
        # (routed to the trash page, but the clamp keeps the prefill shape
        # honest and the jit-cache family within the table).
        sb = min(_pow2(max(sufs)), self.pps * self.ps)
        bb = _pow2(len(admit))
        toks = np.zeros((bb, sb), np.int32)
        lens = np.zeros(bb, np.int32)
        offs = np.zeros(bb, np.int32)
        rid = np.zeros(bb, np.int32)
        pt = np.full((bb, self.pps), PC.TRASH_PAGE, np.int32)
        for i, s in enumerate(admit):
            r = self.owner[s]
            suf = r.prompt[self.suffix_start[s]:]
            toks[i, :suf.size] = suf
            lens[i] = suf.size
            offs[i] = self.suffix_start[s]
            rid[i] = self.rid[s]
            pt[i] = self.page_table[s]
        pf = eng._prefill_for(self.backend_name, bb, sb, use_prefix)
        ptoks, eng._cache = pf(eng.params, eng._cache, jnp.asarray(toks),
                               jnp.asarray(lens), jnp.asarray(pt),
                               jnp.asarray(offs), jnp.asarray(rid))
        eng.stats["prefill_calls"] += 1
        eng.stats["prefill_tokens"] += int(lens[:len(admit)].sum())
        self.last_tok = eng._merge_fn(
            self.last_tok, ptoks,
            jnp.asarray(np.asarray(admit, np.int32)))
        for s in admit:
            self.lengths[s] = self.owner[s].prompt.size
            self.gen_count[s] = 1
        return ptoks

    def dispatch_decode(self):
        """One decode step over the full slot array.  Slots that already
        wrote their last reserved position ("frozen": the async runtime may
        run ahead of finish notifications) are routed to the trash page so
        they cannot touch live pages.  Returns ``(token device array,
        [(slot, request, token_index), ...])`` for the emission stage, or
        ``None`` when nothing is live."""
        eng = self.eng
        live = [s for s in range(eng.slots)
                if self.owner[s] is not None
                and self.lengths[s] < self.cap_of[s]]
        if not live:
            return None
        frozen = [s for s in range(eng.slots)
                  if self.owner[s] is not None and s not in live]
        # The step reads copies: a host array handed to the device may be
        # read until its transfer completes (zero-copy on the CPU), and
        # lengths / gen_count change right below, before the step has run.
        lens_step = self.lengths.copy()
        pt_step = self.page_table.copy()
        for s in frozen:
            lens_step[s] = 0
            pt_step[s] = PC.TRASH_PAGE
        toks, eng._cache = self.decode_fn(
            eng.params, eng._cache, self.last_tok, jnp.asarray(lens_step),
            jnp.asarray(pt_step), jnp.asarray(self.rid.copy()),
            jnp.asarray(self.gen_count.copy()))
        self.last_tok = toks[:, None]
        eng.stats["decode_steps"] += 1
        eng.stats["decode_slot_tokens"] += len(live)
        snap = [(s, self.owner[s], int(self.gen_count[s])) for s in live]
        for s in live:
            self.lengths[s] += 1
            self.gen_count[s] += 1
        return toks, snap

    # -- sampling/emission stage --------------------------------------------

    def _emit_one(self, r: Request, tok: int) -> bool:
        """Append + stream one token; returns True when the request is now
        finished (EOS or budget)."""
        eng = self.eng
        _emit_token(r, tok)
        eng.stats["generated_tokens"] += 1
        if tok == r.eos_id:
            _finish_request(r, "eos")
        elif len(r.out_tokens) >= eng._limit(r):
            _finish_request(r, "length")
        return r.done

    def emit_prefill(self, snap: list[tuple[int, Request]],
                     np_toks) -> list[int]:
        """Emit each admitted request's first token; returns slots to
        release."""
        finished = []
        for i, (s, r) in enumerate(snap):
            if r.done:       # async run-ahead: already terminal
                continue
            if self._emit_one(r, int(np_toks[i])):
                finished.append(s)
        return finished

    def emit_decode(self, snap: list[tuple[int, Request, int]],
                    np_toks) -> list[int]:
        """Emit one decode step's tokens; returns slots to release.  Tokens
        for requests that finished since dispatch (async run-ahead) are
        dropped — the synchronous path never produces them."""
        finished = []
        for s, r, _tidx in snap:
            if r.done:
                continue
            if self._emit_one(r, int(np_toks[s])):
                finished.append(s)
        return finished

    def release(self, slot: int) -> None:
        """Return a finished slot's pages.  With the prefix cache, the
        request's FULL prompt pages are donated to the trie first (the
        cache adopts one reference per newly cached page); every other
        reference is dropped in a single batch so the pre-refcount LIFO
        reuse order is preserved exactly."""
        eng = self.eng
        r = self.owner[slot]
        pages = self.mapped_pages[slot]
        adopted: set[int] = set()
        if eng._prefix is not None:
            n_full = r.prompt.size // self.ps
            chain = [int(self.page_table[slot, c]) for c in range(n_full)]
            adopted = eng._prefix.insert(
                PC.page_keys(r.prompt, self.ps), chain)
        self.pool.free([p for p in pages if p not in adopted])
        self.owner[slot] = None
        self.mapped_pages[slot] = None
        self.shared_cols[slot] = None
        self.page_table[slot, :] = PC.TRASH_PAGE   # stale entries must not
        self.lengths[slot] = 0                     # alias freshly reused pages
        self.cap_of[slot] = 0
        self.free_slots.append(slot)
