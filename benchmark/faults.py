"""Faults planted under the timed path, to show that the check of outputs
catches them.  Each is a context manager that patches the program for the
runs made inside it; ``control.py`` reads them on the chip at a cell's own
size and ``tests/test_faults.py`` at a small size on the CPU.

* ``frozen``: the step returns its state unchanged (AdamW makes no update).
* ``half_batch``: the loss is the mean over the first half of the batch,
  the rest left out.
* ``no_exchange``: the MoE layer's combine across chips (its ``psum``) is
  left out, so each chip keeps its own experts' part.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def frozen():
    from repro.train import loop
    orig = loop.adamw_update

    def unchanged(grads, state, params, **kw):
        return params, state

    loop.adamw_update = unchanged
    try:
        yield
    finally:
        loop.adamw_update = orig


@contextlib.contextmanager
def half_batch():
    from repro.models import transformer as T
    orig = T.train_loss

    def half(params, batch, cfg, *, mesh=None):
        n = batch["tokens"].shape[0] // 2
        return orig(params, {k: v[:n] for k, v in batch.items()}, cfg,
                    mesh=mesh)

    T.train_loss = half
    try:
        yield
    finally:
        T.train_loss = orig


@contextlib.contextmanager
def no_exchange():
    import jax
    from repro.models import moe_block
    orig = moe_block.jax.lax.psum

    def local(x, axis_name, **kw):
        return x

    jax.lax.psum = local
    try:
        yield
    finally:
        jax.lax.psum = orig


PLANTS = {"frozen": frozen, "half_batch": half_batch,
          "no_exchange": no_exchange}
