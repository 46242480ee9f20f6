"""Serving-engine tests: decode equals full forward; batched generation;
context-scoped grouped-GEMM backend selection (engine default, per-Request
override, enqueue-time validation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine

CFG = get_config("yi_6b").reduced().replace(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
    d_ff=128, vocab_size=64, attn_chunk=16)

# A config with grouped GEMMs in the decode path, so backend choice is real.
MOE_CFG = get_config("qwen3_moe_30b_a3b").reduced().replace(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
    num_experts=4, top_k=2, moe_d_ff=64, vocab_size=64, dtype="float32",
    attn_chunk=16)


def _two_backends():
    """Two distinct backends: the XLA path and the pure-jnp oracle."""
    return "ragged", "segment"


def test_decode_matches_forward_logits():
    """Token-by-token decode reproduces the full-forward last logits."""
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              CFG.vocab_size)
    full_logits, _ = T.forward(params, {"tokens": toks}, CFG)
    cache = T.init_cache(CFG, 2, 32)
    for t in range(16):
        logits, cache = T.decode_step(params, cache,
                                      {"tokens": toks[:, t:t + 1]},
                                      jnp.array(t), CFG)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits[:, t]),
                                   atol=2e-3, rtol=1e-3)


def test_engine_batched_generation():
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    eng = ServeEngine(CFG, params, batch_slots=3, capacity=64)
    reqs = [Request(prompt=np.array([1, 4, 9], np.int32), max_new_tokens=6),
            Request(prompt=np.array([1, 7], np.int32), max_new_tokens=4),
            Request(prompt=np.array([1], np.int32), max_new_tokens=5)]
    out = eng.generate(reqs)
    for r in out:
        assert 1 <= len(r.out_tokens) <= r.max_new_tokens
        assert all(0 <= t < CFG.vocab_size for t in r.out_tokens)


def test_engine_greedy_deterministic():
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    eng = ServeEngine(CFG, params, batch_slots=1, capacity=64)
    outs = []
    for _ in range(2):
        r = eng.generate([Request(prompt=np.array([1, 2, 3], np.int32),
                                  max_new_tokens=5)])[0]
        outs.append(tuple(r.out_tokens))
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Context-scoped backend selection
# ---------------------------------------------------------------------------


def _gen_tokens(eng, prompt=(1, 2, 3), max_new=4, **req_kw):
    r = eng.generate([Request(prompt=np.array(prompt, np.int32),
                              max_new_tokens=max_new, **req_kw)])[0]
    return tuple(r.out_tokens)


def test_two_engines_different_backends_identical_tokens():
    """Two engines in ONE process, same params, different grouped-GEMM
    backends: each holds its own resolution (per-run, not per-process — the
    MegaBlocks/Megatron-Core property) and greedy tokens agree exactly."""
    b1, b2 = _two_backends()
    params = T.init_params(jax.random.PRNGKey(0), MOE_CFG)
    eng1 = ServeEngine(MOE_CFG, params, batch_slots=1, capacity=16,
                       gmm_backend=b1)
    eng2 = ServeEngine(MOE_CFG, params, batch_slots=1, capacity=16,
                       gmm_backend=b2)
    assert eng1.backend.name == b1 and eng2.backend.name == b2
    assert eng1.backend.jax_version == jax.__version__
    t1, t2 = _gen_tokens(eng1), _gen_tokens(eng2)
    assert t1 == t2
    # Each engine jitted its own backend's decode — no shared specialization.
    assert set(eng1._decode_fns) == {b1}
    assert set(eng2._decode_fns) == {b2}


def test_request_override_beats_engine_default():
    """A per-Request ``gmm_backend`` outranks the engine default (call-site
    slot of the precedence chain) and produces the same greedy tokens."""
    b_default, b_override = _two_backends()
    params = T.init_params(jax.random.PRNGKey(0), MOE_CFG)
    eng = ServeEngine(MOE_CFG, params, batch_slots=2, capacity=16,
                      gmm_backend=b_default)

    req = Request(prompt=np.array([1, 2, 3], np.int32), max_new_tokens=4,
                  gmm_backend=b_override)
    assert eng.resolve_request(req).name == b_override
    assert eng.resolve_request(req).source == "arg"

    base = _gen_tokens(eng)                         # engine default
    over = _gen_tokens(eng, gmm_backend=b_override)
    assert base == over
    assert b_override in eng._decode_fns            # override really ran

    # Mixed batch: slots grouped by resolved backend, both decode fine.
    reqs = [Request(prompt=np.array([1, 2, 3], np.int32), max_new_tokens=3),
            Request(prompt=np.array([1, 2, 3], np.int32), max_new_tokens=3,
                    gmm_backend=b_override)]
    out = eng.generate(reqs)
    assert tuple(out[0].out_tokens) == tuple(out[1].out_tokens)


def test_unknown_backend_raises_at_enqueue_not_mid_generate():
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    eng = ServeEngine(CFG, params, batch_slots=2, capacity=16)

    with pytest.raises(ValueError, match="unknown gmm backend"):
        eng.enqueue(Request(prompt=np.array([1], np.int32),
                            gmm_backend="cuda"))
    assert eng.pending == []                        # nothing was admitted

    # generate() also validates every slot before any decode work
    good = Request(prompt=np.array([1, 2], np.int32), max_new_tokens=2)
    bad = Request(prompt=np.array([1, 2], np.int32), gmm_backend="cuda")
    with pytest.raises(ValueError, match="unknown gmm backend"):
        eng.generate([good, bad])
    assert good.out_tokens == []                    # no tokens in flight


def test_engine_queue_drains_in_slot_batches():
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    eng = ServeEngine(CFG, params, batch_slots=2, capacity=32)
    for i in range(3):
        eng.enqueue(Request(prompt=np.array([1 + i, 2], np.int32),
                            max_new_tokens=3))
    done = eng.run()
    assert eng.pending == []
    assert len(done) == 3
    for r in done:
        assert 1 <= len(r.out_tokens) <= 3


def test_engine_construction_snapshots_config_backend():
    """ModelConfig.gmm_backend feeds the engine's config slot; the explicit
    engine argument beats it."""
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    eng = ServeEngine(CFG.replace(gmm_backend="segment"), params,
                      batch_slots=1, capacity=16)
    assert eng.backend.name == "segment"
    assert eng.backend.source == "config"
    eng2 = ServeEngine(CFG.replace(gmm_backend="segment"), params,
                       batch_slots=1, capacity=16, gmm_backend="pallas")
    assert eng2.backend.name == "pallas"
    assert eng2.backend.source == "arg"
