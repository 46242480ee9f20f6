"""End-to-end behaviour tests for the MoEBlaze reproduction."""

import jax

from repro.configs import PAPER_CONFS, get_config
from repro.configs.base import TrainConfig
from repro.core.checkpoint import POLICIES
from repro.train.loop import train


def test_moe_training_learns_bigram_structure():
    """A small MoEBlaze model trains end to end and the loss drops."""
    cfg = get_config("mixtral_8x7b").reduced().replace(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        num_experts=4, top_k=2, moe_d_ff=96, vocab_size=128,
        sliding_window=32, attn_chunk=32)
    tcfg = TrainConfig(total_steps=40, batch_size=4, seq_len=64,
                       learning_rate=3e-3, log_every=10)
    _, _, hist = train(cfg, tcfg, log=lambda *_: None)
    assert hist[-1]["loss"] < hist[0]["loss"] - 0.3, hist


def test_paper_conf_registry():
    assert len(PAPER_CONFS) == 7
    c4 = PAPER_CONFS["paper_conf4"]
    assert (c4.d_model, c4.num_experts, c4.top_k) == (2048, 16, 4)
    assert c4.moe_d_ff == 4 * c4.d_model


def test_checkpoint_policy_memory_ordering():
    """More aggressive policies save fewer residual bytes:
    none <= paper_min <= paper <= full."""
    from repro.core.checkpoint import saved_residual_nbytes
    from repro.core.checkpoint import FFN_A, FFN_B, FFN_YSWI, tag

    L, d, h = 256, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (L, d))
    w1 = jax.random.normal(ks[1], (d, h)) * 0.1
    w2 = jax.random.normal(ks[2], (d, h)) * 0.1

    def layer(x):
        a = tag(x @ w1, FFN_A)
        b = tag(x @ w2, FFN_B)
        y = tag(jax.nn.silu(a) * b, FFN_YSWI)
        return y @ w1.T

    sizes = {}
    for pol in ("none", "paper_min", "paper", "full"):
        f = jax.checkpoint(layer, policy=POLICIES[pol]) \
            if pol != "full" else layer
        sizes[pol] = saved_residual_nbytes(lambda x: f(x).sum(), x)
    assert sizes["none"] <= sizes["paper_min"] <= sizes["paper"] \
        <= sizes["full"]
    # In this single-layer toy, partial-eval may pick an equivalent-size
    # residual set for paper vs paper_min; the strict win shows up at MoE
    # layer level (test_memory_claim_moeblaze_vs_megablocks / benchmarks).
    assert sizes["none"] < sizes["full"]


def test_memory_claim_moeblaze_vs_megablocks():
    """Paper validation at test scale: MoEBlaze saves >=1.8x activation
    memory vs the materialized baseline on a SwiGLU MoE layer."""
    from repro.bench.paper_tables import residual_bytes
    conf = (256, 8, 2, 4, 512)          # d, E, k, B, S (scaled conf2)
    blaze = residual_bytes(conf, "blaze", "swiglu")
    mega = residual_bytes(conf, "megablocks", "swiglu")
    assert mega / blaze >= 1.8, (blaze, mega)
    silu_ratio = (residual_bytes(conf, "megablocks", "silu") /
                  residual_bytes(conf, "blaze", "silu"))
    assert silu_ratio >= 2.5, silu_ratio


def test_dispatch_sortfree_faster_than_sort():
    """The paper's headline dispatch claim, on this backend."""
    from repro.bench.paper_tables import dispatch_build_us
    conf = (512, 16, 4, 8, 1024)
    t_free = dispatch_build_us(conf, "sortfree", iters=3)
    t_sort = dispatch_build_us(conf, "sort", iters=3)
    # sort-based does strictly more passes; allow generous slack for noise
    assert t_free < t_sort * 1.2, (t_free, t_sort)
