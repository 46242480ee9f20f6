"""Paged serving engine tests: batched-vs-solo parity (the left-pad
regression), model-level prefill/decode vs full forward, continuous slot
release, page-budget admission, page reuse, int8 cache parity, and
fixed-seed sampling determinism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serve import kv_quant as KQ
from repro.serve import paged_cache as PC
from repro.serve.engine import Request, ServeEngine

CFG = get_config("yi_6b").reduced().replace(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
    d_ff=128, vocab_size=64, attn_chunk=16)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def _mixed_prompts(vocab, lens=(1, 4, 7, 3)):
    rng = np.random.default_rng(0)
    return [rng.integers(1, vocab, size=L).astype(np.int32) for L in lens]


def _reqs(prompts, max_new=5, eos_id=None, **kw):
    # default eos outside the vocab: runs always reach max_new
    return [Request(prompt=p, max_new_tokens=max_new,
                    eos_id=CFG.vocab_size if eos_id is None else eos_id,
                    **kw)
            for p in prompts]


# ---------------------------------------------------------------------------
# the left-pad regression: batched output must not depend on batch-mates
# ---------------------------------------------------------------------------


def test_batched_matches_solo_mixed_lengths(params):
    """Mixed prompt lengths in one batch give exactly the tokens each
    request gets alone.  The seed engine failed this: left-padding
    teacher-forced token-id-0 keys at VALID positions, so short prompts
    attended to pad garbage whenever batched with longer ones."""
    prompts = _mixed_prompts(CFG.vocab_size)
    eng = ServeEngine(CFG, params, batch_slots=4, capacity=32, page_size=8)
    batched = eng.generate(_reqs(prompts))
    for p, r in zip(prompts, batched):
        solo = ServeEngine(CFG, params, batch_slots=1, capacity=32,
                           page_size=8)
        ref = solo.generate(_reqs([p]))[0]
        assert r.out_tokens == ref.out_tokens, (p.size, r.out_tokens,
                                                ref.out_tokens)


def test_prefill_decode_match_full_forward(params):
    """Model-level: one jitted prefill + per-request-position decode steps
    reproduce the full forward's greedy continuation for every request of a
    right-padded mixed-length batch."""
    lens = np.array([2, 6, 4])
    B, S, max_new, ps = 3, 8, 4, 4
    rng = np.random.default_rng(1)
    toks = np.zeros((B, S), np.int32)
    for b in range(B):
        toks[b, :lens[b]] = rng.integers(1, CFG.vocab_size, lens[b])
    pool = PC.PagePool(32)
    pps = PC.pages_needed(S + max_new, ps)
    pt = np.full((B, pps), PC.TRASH_PAGE, np.int32)
    for b in range(B):
        n = PC.pages_needed(int(lens[b]) + max_new, ps)
        pt[b, :n] = pool.alloc(n)
    cache = T.init_paged_cache(CFG, 32, ps)
    logits, cache = T.prefill(params, jnp.asarray(toks), jnp.asarray(lens),
                              cache, jnp.asarray(pt), CFG)
    cur = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
    seqs = [list(toks[b, :lens[b]]) + [int(cur[b])] for b in range(B)]
    pos = lens.copy()
    for _ in range(max_new - 1):
        logits, cache = T.paged_decode_step(
            params, cache, jnp.asarray(cur[:, None]), jnp.asarray(pos),
            jnp.asarray(pt), CFG)
        cur = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
        pos += 1
        for b in range(B):
            seqs[b].append(int(cur[b]))
    for b in range(B):
        ref, _ = T.forward(params, {"tokens": jnp.asarray([seqs[b][:-1]])},
                           CFG)
        ref_greedy = np.argmax(np.asarray(ref[0]), axis=-1)
        assert seqs[b][lens[b]:] == list(ref_greedy[lens[b] - 1:]), b


def test_init_paged_cache_rejects_ssm_patterns():
    ssm_cfg = get_config("xlstm_1_3b").reduced().replace(
        num_layers=2, d_model=64, num_heads=2, vocab_size=64)
    with pytest.raises(ValueError, match="attention block pattern"):
        T.init_paged_cache(ssm_cfg, 8, 4)
    with pytest.raises(ValueError, match="block pattern"):
        ServeEngine(ssm_cfg, {}, batch_slots=1)


def test_write_prefill_tail_past_table_goes_to_trash():
    """S beyond the page table's logical width must spill to the trash page,
    never alias onto the last real page.  Regression: JAX's clamping gather
    sent out-of-range columns to the LAST table column, so a pow2 prefill
    bucket wider than the table scattered pad garbage over the request's own
    final page of valid prompt KV."""
    B, ps, n_pages = 2, 4, 5
    Hkv, Dh = 2, 8
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)   # width 2 -> T = 8
    S = 12                                          # 4 positions past the table
    k = jax.random.normal(jax.random.PRNGKey(0), (B, S, Hkv, Dh))
    v = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, Dh))
    pool = PC.write_prefill(PC.init_paged_kv(n_pages, ps, Hkv, Dh,
                                             jnp.float32), k, v, pt)
    for b in range(B):
        for t in range(8):                          # in-table positions exact
            np.testing.assert_array_equal(
                np.asarray(pool.k[int(pt[b, t // ps]), t % ps]),
                np.asarray(k[b, t]))


def test_engine_nonaligned_capacity_matches_aligned(params):
    """A capacity that is not pow2-aligned to the page grid (48 = 3 pages of
    16, but _pow2(40) = 64) must generate the same tokens as an aligned one.
    Regression: the prefill bucket overshot the page table and the pad tail
    overwrote the prompt's last real page — silent wrong tokens on exactly
    the configs the parity bench never exercised."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, CFG.vocab_size, size=40).astype(np.int32)
    mis = ServeEngine(CFG, params, batch_slots=1, capacity=48, page_size=16)
    ali = ServeEngine(CFG, params, batch_slots=1, capacity=64, page_size=16)
    got = mis.generate(_reqs([prompt], max_new=4))[0]
    ref = ali.generate(_reqs([prompt], max_new=4))[0]
    assert got.out_tokens == ref.out_tokens


def test_zero_budget_rejected_and_truncation_accounted(params):
    """max_new_tokens < 1 raises at validation (prefill always samples one
    token, so a 0 budget cannot be honored), and a budget silently bounded
    by capacity is surfaced in stats['truncated_budgets']."""
    eng = ServeEngine(CFG, params, batch_slots=1, capacity=16, page_size=8)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.enqueue(Request(prompt=np.asarray([1, 2], np.int32),
                            max_new_tokens=0))
    prompt = np.arange(1, 13, dtype=np.int32)       # 12 + 64 > capacity 16
    done = eng.generate(_reqs([prompt], max_new=64))[0]
    assert eng.stats["truncated_budgets"] == 1
    assert len(done.out_tokens) == 16 - 12 + 1


# ---------------------------------------------------------------------------
# continuous scheduler
# ---------------------------------------------------------------------------


def test_finished_requests_release_slots(params):
    """Total decode slot-tokens == sum(T_r - 1): a finished request's slot
    stops decoding immediately (the seed engine decoded every slot until the
    LAST request finished — batch x max(T) slot-steps)."""
    prompts = _mixed_prompts(CFG.vocab_size, lens=(2, 3, 5, 2))
    eng = ServeEngine(CFG, params, batch_slots=2, capacity=32, page_size=8)
    reqs = [Request(prompt=p, max_new_tokens=m, eos_id=CFG.vocab_size)
            for p, m in zip(prompts, (1, 3, 7, 2))]
    eng.generate(reqs)
    for r, m in zip(reqs, (1, 3, 7, 2)):
        assert len(r.out_tokens) == m
    assert eng.stats["decode_slot_tokens"] == sum((1, 3, 7, 2)) - len(reqs)
    # with 2 slots the longest request alone lower-bounds the step count
    assert eng.stats["decode_steps"] >= 7 - 1


def test_eos_frees_slot_early(params):
    """A request that samples EOS stops immediately and its tokens end at
    the EOS; the engine keeps serving the others."""
    prompts = _mixed_prompts(CFG.vocab_size, lens=(3, 4))
    eng = ServeEngine(CFG, params, batch_slots=2, capacity=32, page_size=8)
    probe = eng.generate(_reqs(prompts, max_new=8))
    eos = probe[0].out_tokens[2]          # force EOS at the 3rd token
    eng2 = ServeEngine(CFG, params, batch_slots=2, capacity=32, page_size=8)
    reqs = _reqs(prompts, max_new=8, eos_id=int(eos))
    eng2.generate(reqs)
    assert reqs[0].done and reqs[0].out_tokens[-1] == eos
    assert len(reqs[0].out_tokens) <= 3
    assert len(reqs[1].out_tokens) >= len(reqs[0].out_tokens)


def test_admission_order_under_page_budget(params):
    """FIFO admission under a page budget: with pages for only one resident
    request, requests run one at a time in arrival order — every request's
    output equals its solo run, the pool never holds more than one
    request's pages, and the blocked head is accounted."""
    prompts = _mixed_prompts(CFG.vocab_size, lens=(4, 4, 4))
    # each request writes 4 + 3 - 1 = 6 tokens -> 1 page of 8; a pool of 2
    # (1 allocatable past the trash page) admits exactly one at a time even
    # though two slots are free
    eng = ServeEngine(CFG, params, batch_slots=2, capacity=16, page_size=8,
                      num_pages=2)
    for r in _reqs(prompts, max_new=3):
        eng.enqueue(r)
    done = eng.run()
    assert eng.stats["blocked_admissions"] >= 1
    assert eng.stats["peak_pages_used"] == 1
    for p, r in zip(prompts, done):
        solo = ServeEngine(CFG, params, batch_slots=1, capacity=16,
                           page_size=8)
        ref = solo.generate(_reqs([p], max_new=3))[0]
        assert r.out_tokens == ref.out_tokens
    # an impossible request (more pages than the pool will ever have)
    # raises at enqueue, not mid-run
    with pytest.raises(ValueError, match="pages"):
        eng.enqueue(Request(prompt=np.arange(1, 12, dtype=np.int32),
                            max_new_tokens=6, eos_id=CFG.vocab_size))


def test_sampling_deterministic_under_fixed_seed(params):
    """greedy=False samples in-graph with per-(request, token-index)
    fold_in keys: same seed => same tokens, different seed => (almost
    surely) different — and tokens never depend on batching/scheduling."""
    prompts = _mixed_prompts(CFG.vocab_size, lens=(3, 5))
    outs = []
    for seed in (7, 7, 8):
        eng = ServeEngine(CFG, params, batch_slots=2, capacity=32,
                          page_size=8, greedy=False, temperature=1.0,
                          seed=seed)
        rs = eng.generate(_reqs(prompts, max_new=6))
        outs.append(tuple(tuple(r.out_tokens) for r in rs))
    assert outs[0] == outs[1]
    assert outs[0] != outs[2]
    with pytest.raises(ValueError, match="temperature"):
        ServeEngine(CFG, params, greedy=False, temperature=0.0)


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------


def test_page_pool_reuse_after_eviction():
    pool = PC.PagePool(8)
    a = pool.alloc(3)
    assert PC.TRASH_PAGE not in a
    pool.free(a)
    b = pool.alloc(3)
    assert b == a                        # LIFO: freed pages reused first
    assert pool.free_pages == 4
    assert pool.min_free == 4
    pool.free(b)
    with pytest.raises(ValueError, match="double free"):
        pool.free(b[:1])
    with pytest.raises(ValueError, match="invalid page"):
        pool.free([PC.TRASH_PAGE])
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(8)


def test_engine_page_reuse(params):
    """Pages freed by a finished request are immediately reused by the next
    admitted one — the peak page usage of a one-at-a-time run equals ONE
    request's footprint, not the sum."""
    prompts = _mixed_prompts(CFG.vocab_size, lens=(4, 4, 4))
    eng = ServeEngine(CFG, params, batch_slots=1, capacity=16, page_size=8)
    eng.generate(_reqs(prompts, max_new=3))
    assert eng.stats["peak_pages_used"] == PC.pages_needed(4 + 3 - 1, 8)


# ---------------------------------------------------------------------------
# int8 paged cache
# ---------------------------------------------------------------------------


def test_int8_engine_cache_bytes_and_tolerance(params):
    """The int8 paged pool measures ~2x fewer bytes than a same-shape model-
    dtype pool, and the int8 engine's greedy tokens stay close to the f32
    engine's (identical on this config — attention outputs agree to the
    quantization tolerance)."""
    f32_pool = T.init_paged_cache(CFG, 16, 8)
    i8_pool = T.init_paged_cache(CFG, 16, 8, quantized=True)
    assert KQ.cache_bytes(i8_pool) < 0.55 * KQ.cache_bytes(f32_pool)

    prompts = _mixed_prompts(CFG.vocab_size, lens=(3, 6))
    base = ServeEngine(CFG, params, batch_slots=2, capacity=32, page_size=8)
    int8 = ServeEngine(CFG, params, batch_slots=2, capacity=32, page_size=8,
                       kv_dtype="int8")
    b = base.generate(_reqs(prompts, max_new=5))
    q = int8.generate(_reqs(prompts, max_new=5))
    for rb, rq in zip(b, q):
        assert rb.out_tokens == rq.out_tokens


def test_paged_attention_int8_matches_fp():
    """serve.paged_cache.paged_attention against an int8 pool tracks the fp
    pool within the kv_quant tolerance."""
    B, ps, n_pages, Hkv, Hq, Dh = 2, 4, 9, 2, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, Dh))
    k = jax.random.normal(ks[1], (B, 12, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, 12, Hkv, Dh))
    pt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    pos = jnp.asarray([11, 7])
    fp = PC.write_prefill(PC.init_paged_kv(n_pages, ps, Hkv, Dh,
                                           jnp.float32), k, v, pt)
    i8 = PC.write_prefill(PC.init_paged_kv(n_pages, ps, Hkv, Dh,
                                           jnp.float32, quantized=True),
                          k, v, pt)
    ref = PC.paged_attention(q, fp, pt, pos)
    out = PC.paged_attention(q, i8, pt, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# serving bench gates
# ---------------------------------------------------------------------------


def test_serving_gate_failures_pairing():
    from repro.bench.record import entry
    from repro.bench.serving import serving_gate_failures

    def fam(par, got, want, i8, bf16, noshare=48, shared=32, pmis=0,
            amis=0):
        return [entry("serving/parity/mismatched_tokens", par,
                      kind="serving"),
                entry("serving/sched/decode_slot_tokens", got,
                      kind="serving"),
                entry("serving/sched/expected_slot_tokens", want,
                      kind="serving"),
                entry("serving/kv/int8_paged_bytes_per_token", i8,
                      kind="serving"),
                entry("serving/kv/bf16_dense_bytes_per_token", bf16,
                      kind="serving"),
                entry("serving/prefix/prefill_tokens_nosharing", noshare,
                      kind="serving"),
                entry("serving/prefix/prefill_tokens_shared", shared,
                      kind="serving", page_size=8),
                entry("serving/prefix/mismatched_tokens", pmis,
                      kind="serving"),
                entry("serving/pipeline/async_sync_mismatches", amis,
                      kind="serving")]

    assert serving_gate_failures([]) == []            # legacy record
    assert serving_gate_failures(fam(0, 16, 16, 100, 200)) == []
    assert any("parity" in f for f in
               serving_gate_failures(fam(2, 16, 16, 100, 200)))
    assert any("slot" in f for f in
               serving_gate_failures(fam(0, 20, 16, 100, 200)))
    assert any("kv bytes" in f for f in
               serving_gate_failures(fam(0, 16, 16, 150, 200)))
    # prefix pair must save >= one full page of prefill tokens ...
    assert any("full page" in f for f in
               serving_gate_failures(fam(0, 16, 16, 100, 200,
                                         noshare=48, shared=41)))
    # ... without changing a single token.
    assert any("COW" in f for f in
               serving_gate_failures(fam(0, 16, 16, 100, 200, pmis=1)))
    assert any("pipeline" in f for f in
               serving_gate_failures(fam(0, 16, 16, 100, 200, amis=3)))
    assert any("incomplete" in f for f in
               serving_gate_failures(fam(0, 16, 16, 100, 200)[:2]))


def test_decode_inputs_do_not_alias_host_state(params):
    """Regression: the decode step was handed ``jnp.asarray`` views of the
    scheduler's lengths / gen_count arrays, which it then mutated before the
    step had run — zero-copy on the CPU, so tokens depended on timing.
    Every host array a decode step receives must keep the value it had at
    dispatch."""
    eng = ServeEngine(CFG, params, batch_slots=4, capacity=32, page_size=8)
    seen = []
    make = eng._decode_for

    def recording(name):
        fn = make(name)

        def call(p, c, tok, *host):
            seen.append([(a, np.array(a)) for a in host])
            return fn(p, c, tok, *host)
        return call

    eng._decode_for = recording
    eng.generate(_reqs(_mixed_prompts(CFG.vocab_size), max_new=6))
    assert seen
    for step in seen:
        for arr, at_dispatch in step:
            np.testing.assert_array_equal(np.asarray(arr), at_dispatch)
