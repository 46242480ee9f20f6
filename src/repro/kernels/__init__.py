"""Pallas TPU kernels for the MoEBlaze hot spots (``ops.py`` holds the
differentiable entry points, ``ref.py`` the pure-jnp oracles)."""

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run through the interpreter.

    The one place that decides it: True only when JAX's default backend is
    the CPU (tests and the CPU rehearsal).  On a TPU every kernel is compiled
    by Mosaic; nothing falls back to the interpreter or to another backend.
    """
    return jax.default_backend() == "cpu"
