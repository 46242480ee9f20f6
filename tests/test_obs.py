"""The program's own tracing (``repro.obs``): host spans of the training
loop, the step it keeps for lowering, the named scopes of the compiled train
step, and the contract of ``train``'s frame that a step hook may read."""

import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.train.loop import make_train_step, train
from repro.train.optimizer import init_adamw
from repro.models import transformer as T

CFG = get_config("mixtral_8x7b").reduced().replace(
    num_layers=1, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
    vocab_size=128, num_experts=4, top_k=2, moe_d_ff=64, sliding_window=0)
TCFG = TrainConfig(batch_size=2, seq_len=32, total_steps=3, log_every=2,
                   checkpoint_every=0)
STEP_SPANS = ["train.next_batch", "train.to_device", "train.resolve",
              "train.dispatch", "train.sync", "train.hook"]


def _batches():
    rng = np.random.default_rng(0)
    while True:
        t = rng.integers(0, CFG.vocab_size, (TCFG.batch_size, TCFG.seq_len))
        yield {"tokens": t.astype(np.int32), "labels": t.astype(np.int32)}


@pytest.fixture(scope="module")
def run():
    """One 3-step ``train`` whose hook keeps what it saw of the caller."""
    seen = []

    def hook(step, metrics):
        f = sys._getframe(1)
        loc = f.f_locals
        seen.append({"caller": f.f_code.co_name,
                     "names": {k for k in ("params", "opt_state", "batch",
                                           "step_fn") if k in loc},
                     "lowers": hasattr(loc.get("step_fn"), "lower"),
                     "step_s": metrics["step_s"]})

    start = time.perf_counter_ns()
    _, _, history = train(CFG, TCFG, batch_iterator=_batches(),
                          step_hook=hook, log=lambda s: None)
    return {"seen": seen, "history": history,
            "spans": [sp for sp in obs.spans() if sp[1] >= start]}


def _steps(spans):
    """The spans of each step, in start order, from the enclosing
    ``train`` span of each step."""
    outer = sorted((s, e) for n, s, e in spans if n == "train")
    return [sorted((s, n, e) for n, s, e in spans
                   if n != "train" and lo <= s and e <= hi)
            for lo, hi in outer]


def test_each_span_once_per_step_nested_in_order(run):
    steps = _steps(run["spans"])
    assert len(steps) == TCFG.total_steps
    n_inner = sum(n != "train" for n, _, _ in run["spans"])
    assert n_inner == sum(len(s) for s in steps)      # all nest in a step
    for i, inner in enumerate(steps):
        names = [n for _, n, _ in inner]
        logged = i % TCFG.log_every == 0 or i == TCFG.total_steps - 1
        assert names == STEP_SPANS + (["train.log"] if logged else []), i
        for (s0, _, e0), (s1, _, e1) in zip(inner, inner[1:]):
            assert s0 <= e0 <= s1 <= e1                # disjoint, in order


def test_record_is_bounded_and_holds_no_array():
    for i in range(obs.RECORD_LEN + 10):
        with obs.span("test.fill"):
            pass
    rec = obs.spans()
    assert len(rec) == obs.RECORD_LEN
    assert all(type(n) is str and type(s) is int and type(e) is int
               and s <= e for n, s, e in rec)
    rec.clear()                                        # a copy
    assert len(obs.spans()) == obs.RECORD_LEN


def test_span_recorded_when_body_raises():
    with pytest.raises(KeyError):
        with obs.span("test.raises"):
            raise KeyError
    assert obs.spans()[-1][0] == "test.raises"


def test_last_step_keeps_shapes_not_arrays(run):
    fn, args = obs.last_step()
    assert hasattr(fn, "lower")
    leaves = jax.tree.leaves(args)
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct)
                          and not isinstance(x, jax.Array) for x in leaves)
    assert all(x.sharding is not None for x in leaves)
    # enough to lower the same step again
    assert "HloModule" in fn.lower(*args).as_text(dialect="hlo")


def test_step_hook_sees_train_frame(run):
    """The frame contract a step hook (the benchmark's) reads."""
    assert len(run["seen"]) == TCFG.total_steps
    for s in run["seen"]:
        assert s["caller"] == "train"
        assert s["names"] == {"params", "opt_state", "batch", "step_fn"}
        assert s["lowers"]


def test_step_s_is_dispatch_to_sync(run):
    """``step_s`` is still the host wall time from the step's dispatch to
    the end of its sync."""
    steps = _steps(run["spans"])
    for s, inner in zip(run["seen"], steps):
        t = {n: (a, b) for a, n, b in inner}
        lo = t["train.dispatch"][0]
        hi = t["train.sync"][1]
        assert (hi - lo) / 1e9 <= s["step_s"] <= (hi - lo) / 1e9 + 1e-3
    logged = [h["step_s"] for h in run["history"]]
    assert logged == [run["seen"][i]["step_s"] for i in (0, 2)]


SCOPES_WITH_BACKWARD = [s for s in obs.SCOPES if s != "optimizer"]


@pytest.fixture(scope="module")
def op_names():
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), CFG))
    opt = jax.eval_shape(init_adamw, params)
    tok = jax.ShapeDtypeStruct((TCFG.batch_size, TCFG.seq_len), jnp.int32)
    step = jax.jit(make_train_step(CFG, TCFG))
    text = step.lower(params, opt, {"tokens": tok, "labels": tok}) \
        .compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def _in_scope(op_name, scope):
    return re.search(rf"(^|[/(]){scope}([/)]|$)", op_name) is not None


@pytest.mark.parametrize("scope", obs.SCOPES)
def test_compiled_step_names_scope_forward(op_names, scope):
    assert any(_in_scope(o, scope) and "transpose(" not in o
               for o in op_names)


@pytest.mark.parametrize("scope", SCOPES_WITH_BACKWARD)
def test_compiled_step_names_scope_backward(op_names, scope):
    assert any(_in_scope(o, scope) and "transpose(" in o for o in op_names)


def test_scopes_leave_compiled_program_unchanged():
    """A named scope changes an instruction's metadata and nothing else."""
    def f(w, x):
        h = jnp.tanh(x @ w)
        return (jnp.exp(h) @ w).sum()

    def g(w, x):
        with jax.named_scope("attention"):
            h = obs.scoped("experts")(lambda: jnp.tanh(x @ w))()
        with jax.named_scope("combine"):
            return (jnp.exp(h) @ w).sum()

    def text(fn):
        w, x = jnp.ones((8, 8)), jnp.ones((4, 8))
        t = jax.jit(jax.grad(fn)).lower(w, x).compile().as_text()
        t = re.sub(r", metadata=\{[^}]*\}", "", t)
        return [line for line in t.splitlines()
                if " = " in line and "HloModule" not in line]

    assert text(f) == text(g)
