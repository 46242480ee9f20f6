"""Training-substrate tests: optimizer math, microbatch equivalence, loss
descent, checkpoint roundtrip."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.data.pipeline import PackedBatches, PipelineConfig
from repro.models import transformer as T
from repro.train.checkpointing import restore_checkpoint, save_checkpoint
from repro.train.loop import make_train_step, train
from repro.train.optimizer import (adamw_update, clip_by_global_norm,
                                   cosine_schedule, global_norm, init_adamw)

CFG = get_config("yi_6b").reduced().replace(
    num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
    d_ff=128, vocab_size=128)


def _batch(B=4, S=32, seed=0):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                              CFG.vocab_size)
    return {"tokens": toks, "labels": toks}


def test_adamw_matches_reference_step():
    p = {"w": jnp.array([1.0, -2.0, 3.0])}
    g = {"w": jnp.array([0.1, 0.2, -0.3])}
    st = init_adamw(p)
    newp, st2 = adamw_update(g, st, p, lr=0.1, b1=0.9, b2=0.999,
                             eps=1e-8, weight_decay=0.0)
    # bias-corrected first step: delta == lr * sign-ish formula
    m = 0.1 * np.array([0.1, 0.2, -0.3])
    v = 0.001 * np.array([0.1, 0.2, -0.3]) ** 2
    mhat, vhat = m / 0.1, v / 0.001
    ref = np.array([1.0, -2.0, 3.0]) - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(newp["w"]), ref, rtol=1e-5)
    assert int(st2.step) == 1


def test_grad_clipping():
    g = {"a": jnp.ones((10,)) * 3.0}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-5)
    np.testing.assert_allclose(float(norm), np.sqrt(90.0), rtol=1e-5)


def test_cosine_schedule_shape():
    lr0 = cosine_schedule(jnp.array(0), peak_lr=1e-3, warmup=10, total=100)
    lr_w = cosine_schedule(jnp.array(10), peak_lr=1e-3, warmup=10, total=100)
    lr_end = cosine_schedule(jnp.array(100), peak_lr=1e-3, warmup=10,
                             total=100)
    assert float(lr0) == 0.0
    np.testing.assert_allclose(float(lr_w), 1e-3, rtol=1e-5)
    np.testing.assert_allclose(float(lr_end), 1e-4, rtol=1e-3)


def test_microbatch_invariance():
    """Gradient accumulation is invariant in the microbatch count: on one
    fixed batch, ``num_microbatches`` in {1, 2, 4} produce the same loss,
    grad norm, and updated params (guards the f32 accumulation path in
    ``train/loop.py``)."""
    params = T.init_params(jax.random.PRNGKey(0), CFG)
    batch = _batch(B=8)
    outs = {}
    for M in (1, 2, 4):
        tcfg = TrainConfig(num_microbatches=M, learning_rate=1e-3)
        step = jax.jit(make_train_step(CFG, tcfg))
        p2, _, metrics = step(params, init_adamw(params), batch)
        outs[M] = (p2, metrics)
    for M in (2, 4):
        # CE/loss means over microbatches of equal size == full-batch mean
        for key in ("ce", "loss", "grad_norm"):
            np.testing.assert_allclose(
                float(outs[1][1][key]), float(outs[M][1][key]), rtol=1e-4,
                err_msg=f"M={M} metric={key}")
        for a, b in zip(jax.tree.leaves(outs[1][0]),
                        jax.tree.leaves(outs[M][0])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, err_msg=f"M={M}")


def test_loss_decreases_end_to_end():
    tcfg = TrainConfig(total_steps=25, batch_size=4, seq_len=64,
                       learning_rate=2e-3, log_every=5)
    _, _, hist = train(CFG, tcfg, log=lambda *_: None)
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_checkpoint_roundtrip(tmp_path):
    params = T.init_params(jax.random.PRNGKey(1), CFG)
    opt = init_adamw(params)
    save_checkpoint(str(tmp_path / "ck"), 7, params, opt)
    step, p2, o2 = restore_checkpoint(str(tmp_path / "ck"), params, opt)
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), b)
    for a, b in zip(jax.tree.leaves(opt), jax.tree.leaves(o2)):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_step_hook_reports_backend_and_context_flips_one_step():
    """``step_hook`` metrics carry the step's resolved grouped-GEMM backend,
    and entering a ``use_backend("segment")`` scope between steps flips
    exactly the next step — with loss parity against the uninterrupted auto
    run (backends are numerically interchangeable)."""
    from repro.core import gmm_backend as GB
    moe_cfg = get_config("qwen3_moe_30b_a3b").reduced().replace(
        num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        num_experts=4, top_k=2, moe_d_ff=64, vocab_size=64, dtype="float32")
    auto = GB.resolve(None).name
    tcfg = TrainConfig(total_steps=3, batch_size=2, seq_len=16,
                       learning_rate=1e-3, log_every=1)

    # Reference: plain auto run (same seed -> identical batches).
    _, _, hist_ref = train(moe_cfg, tcfg, log=lambda *_: None)
    assert [h["gmm_backend"] for h in hist_ref] == [auto] * 3

    # Flip step 1 only, via a scope entered/exited inside the step hook.
    scope = GB.use_backend("segment")
    seen = []

    def hook(step, metrics):
        seen.append(metrics["gmm_backend"])
        assert metrics["step_s"] > 0
        if step == 0:
            scope.__enter__()
        elif step == 1:
            scope.__exit__(None, None, None)

    _, _, hist = train(moe_cfg, tcfg, log=lambda *_: None, step_hook=hook)
    assert seen == [auto, "segment", auto]
    for h_ref, h in zip(hist_ref, hist):
        np.testing.assert_allclose(h_ref["loss"], h["loss"], rtol=1e-4,
                                   err_msg=f"step {h['step']}")


def test_data_pipeline_deterministic_and_packed():
    pc = PipelineConfig(vocab_size=64, seq_len=32, batch_size=2, seed=3)
    it1, it2 = iter(PackedBatches(pc)), iter(PackedBatches(pc))
    b1, b2 = next(it1), next(it2)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (2, 32)
    assert b1["tokens"].max() < 64


def test_compile_cache_placement(monkeypatch, tmp_path):
    """The entry points' compile cache: a ``JAX_COMPILATION_CACHE_DIR``
    setting stands untouched; without it the cache goes to the fixed
    ``.jax_cache/`` at the repository root."""
    from repro.launch import cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.use_compile_cache() == str(tmp_path)
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = cache.use_compile_cache()
    assert path == str(cache.REPO_ROOT / ".jax_cache")
    assert (cache.REPO_ROOT / "chip_smoke.py").is_file()
    assert updates == [("jax_compilation_cache_dir", path)]
