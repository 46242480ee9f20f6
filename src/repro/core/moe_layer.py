"""The MoEBlaze expert layer (paper §3, §5, Algorithm 1).

Forward (paper §3.1): tokens are *never* permuted into per-expert buffers.
The expert GEMMs consume rows gathered on the fly through
``dispatch.expert_token_indices``; the SwiGLU epilogue is applied to the
grouped GEMM outputs; the combine step *gathers* each token's k partial
outputs through ``dispatch.token_index_map`` and contracts them with the gate
weights (the TPU-idiomatic rendering of the paper's on-the-fly reduction —
see DESIGN.md §2).

Backward (paper §3.2 + Algorithm 1): a custom VJP that
  1. expands the (L, d) output gradient to the (L·k, d) slot gradients via the
     same index metadata (no materialized forward buffer is needed for this),
  2. **recomputes SiLU(A)** instead of saving it (paper's smart checkpoint),
  3. recomputes the input gather ``x[expert_token_indices]`` instead of saving
     the (L·k, d) routed buffer,
  4. accumulates token gradients with a scatter-add over the index list.

The residual set is a per-plan decision (``repro.core.checkpoint``
``moe``-scoped tags), expressed as one of three modes:

  * ``"ab_yswi"`` — save ``A``, ``B`` (the two first-layer GEMM outputs)
    and, faithful to Algorithm 1 line 11, ``Y_swi``;
  * ``"ab"``      — recompute ``Y_swi = SiLU(A)·B`` in the backward as well,
    trading one elementwise multiply for an (L·k, h) buffer (the legacy
    ``save_yswi=False``);
  * ``"x"``       — save neither: the backward re-runs the two first-layer
    grouped GEMMs from the (recomputed) input gather, trading two grouped
    GEMMs for *both* (L·k, h) buffers — the deepest-recompute point a
    ``moe:recompute=ffn_a,ffn_b`` plan can ask for.

The grouped GEMMs go through the pluggable backend registry in
``repro.core.gmm_backend`` (``ragged`` = ``jax.lax.ragged_dot[_general]``,
the auto choice, ``segment`` = pure-jnp oracle, ``pallas`` = the
``repro.kernels`` work-item kernels); select per call via ``backend=`` or
globally via ``REPRO_GMM_BACKEND``.  The ``pallas_fused`` backend short-
circuits the whole SwiGLU layer into the fused dispatch→GEMM→combine kernel
pair (``repro.kernels.ops.moe_ffn_blaze_fused``) — no ``(L·k, ·)``
intermediate exists in HBM in either direction.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.checkpoint import MOE_RESIDUAL_MODES
from repro.core.gmm_backend import ResolvedBackend, gmm, gmm_dw, resolve
from repro.core.routing import Dispatch
from repro.obs import scoped

__all__ = ["moe_ffn_blaze", "gmm", "gmm_dw"]


def _silu(a):
    return a * jax.nn.sigmoid(a)


def _dsilu(a):
    s = jax.nn.sigmoid(a)
    return s * (1.0 + a * (1.0 - s))


_ACTS = {
    "silu": (_silu, _dsilu),
    "relu": (jax.nn.relu, lambda a: (a > 0).astype(a.dtype)),
    "gelu": (jax.nn.gelu,
             lambda a: jax.vmap(jax.grad(lambda t: jax.nn.gelu(t)))(
                 a.reshape(-1)).reshape(a.shape)),
}


@scoped("combine")
def _gate_per_slot(gates: jax.Array, token_index_map: jax.Array,
                   num_slots: int) -> jax.Array:
    """Scatter the (L, k) gate weights into expert-order slots (L*k,)."""
    return jnp.zeros((num_slots,), gates.dtype).at[
        token_index_map.reshape(-1)].set(gates.reshape(-1))


@scoped("combine")
def _combine(p_out, gates, tim):
    """Gather each token's k partials (L*k, d) through ``token_index_map``
    and contract them with its gates."""
    L, k = tim.shape
    parts = jnp.take(p_out, tim.reshape(-1), axis=0).reshape(L, k, -1)
    return jnp.einsum("lk,lkd->ld", gates.astype(parts.dtype), parts)


@scoped("combine")
def _combine_dgates(act, dyu, tim, gates):
    """The gates' gradient: each slot's activation against its output
    gradient, gathered back to token order."""
    dgates_slot = jnp.sum(act * dyu, axis=-1)          # (L*k,)
    dgates = jnp.take(dgates_slot, tim.reshape(-1)).reshape(gates.shape)
    return dgates.astype(gates.dtype)


# ---------------------------------------------------------------------------
# MoEBlaze SwiGLU layer — custom VJP (Algorithm 1)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _moe_swiglu(residuals: str, backend: str, x, w1, w2, w3, gates,
                eti, off, tim, lens):
    y, _ = _moe_swiglu_fwd(residuals, backend, x, w1, w2, w3, gates,
                           eti, off, tim, lens)
    return y


def _moe_swiglu_fwd(residuals, backend, x, w1, w2, w3, gates,
                    eti, off, tim, lens):
    del off
    L = x.shape[0]
    k = tim.shape[1]
    # On-the-fly gather from the *unpermuted* activations (transient).
    xg = jnp.take(x, eti, axis=0)                     # (L*k, d)
    a = gmm(xg, w1, lens, backend=backend)             # (L*k, h)
    b = gmm(xg, w2, lens, backend=backend)             # (L*k, h)
    y_swi = _silu(a) * b                               # (L*k, h)
    g_slot = _gate_per_slot(gates, tim, L * k)
    p_out = gmm(y_swi, w3, lens, backend=backend)      # (L*k, d) partials
    y = _combine(p_out, gates, tim)
    save_ab = residuals != "x"
    res = (x, w1, w2, w3, gates, eti, tim, lens, g_slot,
           a if save_ab else None, b if save_ab else None,
           y_swi if residuals == "ab_yswi" else None)
    return y, res


def _moe_swiglu_bwd(residuals, backend, res, dy):
    del residuals                   # the residual tuple itself encodes it
    (x, w1, w2, w3, gates, eti, tim, lens, g_slot, a, b, y_swi) = res
    if a is None:
        # Deepest recompute ("x"): re-run the two first-layer grouped GEMMs
        # from the recomputed input gather (Algorithm 1 with lines 9-10
        # replayed in backward).
        xg0 = jnp.take(x, eti, axis=0)
        a = gmm(xg0, w1, lens, backend=backend)
        b = gmm(xg0, w2, lens, backend=backend)
    if y_swi is None:
        y_swi = _silu(a) * b                           # beyond-paper recompute
    # 1. Expert-summation backward: expand (L, d) grads to the slots via the
    #    index metadata (paper §3.2 step 1) — gather, no materialized buffer.
    with jax.named_scope("combine"):
        dyg = jnp.take(dy, eti, axis=0)                # (L*k, d), unscaled
    # 2. Final-projection grads (Algorithm 1 lines 18-20).
    dw3 = gmm_dw(y_swi * g_slot[:, None].astype(y_swi.dtype), dyg, lens,
                 backend=backend)
    dyu = gmm(dyg, jnp.swapaxes(w3, 1, 2), lens, backend=backend)
    dgates = _combine_dgates(y_swi, dyu, tim, gates)
    dy_swi = dyu * g_slot[:, None].astype(dyu.dtype)
    # 3. SwiGLU backward with SiLU *recomputed* (Algorithm 1 lines 23-28).
    da = dy_swi * b * _dsilu(a)
    db = dy_swi * _silu(a)
    # 4. First-layer grads; the routed-token gather is recomputed, not saved.
    xg = jnp.take(x, eti, axis=0)
    dw1 = gmm_dw(xg, da, lens, backend=backend)
    dw2 = gmm_dw(xg, db, lens, backend=backend)
    dxg = gmm(da, jnp.swapaxes(w1, 1, 2), lens, backend=backend) + \
        gmm(db, jnp.swapaxes(w2, 1, 2), lens, backend=backend)
    # 5. Token-gradient accumulation (paper §3.2 step 3).
    dx = jnp.zeros_like(x).at[eti].add(dxg.astype(x.dtype))
    return dx, dw1, dw2, dw3, dgates, None, None, None, None


_moe_swiglu.defvjp(_moe_swiglu_fwd, _moe_swiglu_bwd)


# ---------------------------------------------------------------------------
# MoEBlaze plain-MLP layer (SiLU / ReLU / GELU) — paper §6.3 benchmarks
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _moe_mlp(act: str, backend: str, residuals: str,
             x, w1, w3, gates, eti, off, tim, lens):
    y, _ = _moe_mlp_fwd(act, backend, residuals, x, w1, w3, gates,
                        eti, off, tim, lens)
    return y


def _moe_mlp_fwd(act, backend, residuals, x, w1, w3, gates,
                 eti, off, tim, lens):
    del off
    f, _ = _ACTS[act]
    L, k = tim.shape[0], tim.shape[1]
    xg = jnp.take(x, eti, axis=0)
    a = gmm(xg, w1, lens, backend=backend)
    g_slot = _gate_per_slot(gates, tim, L * k)
    p_out = gmm(f(a), w3, lens, backend=backend)
    y = _combine(p_out, gates, tim)
    # Smart checkpoint: save only the GEMM output `a` (or, under a
    # moe:recompute=ffn_a plan, not even that); act(a) is always recomputed.
    return y, (x, w1, w3, gates, eti, tim, lens, g_slot,
               a if residuals != "x" else None)


def _moe_mlp_bwd(act, backend, residuals, res, dy):
    del residuals
    f, df = _ACTS[act]
    (x, w1, w3, gates, eti, tim, lens, g_slot, a) = res
    if a is None:                   # "x": replay the first-layer grouped GEMM
        a = gmm(jnp.take(x, eti, axis=0), w1, lens, backend=backend)
    fa = f(a)                                          # recompute (paper §5.2)
    with jax.named_scope("combine"):
        dyg = jnp.take(dy, eti, axis=0)
    dw3 = gmm_dw(fa * g_slot[:, None].astype(fa.dtype), dyg, lens,
                 backend=backend)
    dyu = gmm(dyg, jnp.swapaxes(w3, 1, 2), lens, backend=backend)
    dgates = _combine_dgates(fa, dyu, tim, gates)
    da = dyu * g_slot[:, None].astype(dyu.dtype) * df(a)
    xg = jnp.take(x, eti, axis=0)
    dw1 = gmm_dw(xg, da, lens, backend=backend)
    dxg = gmm(da, jnp.swapaxes(w1, 1, 2), lens, backend=backend)
    dx = jnp.zeros_like(x).at[eti].add(dxg.astype(x.dtype))
    return dx, dw1, dw3, dgates, None, None, None, None


_moe_mlp.defvjp(_moe_mlp_fwd, _moe_mlp_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


#: custom-VJP residual modes (see module docstring) — the single source of
#: truth lives next to the plan logic in ``repro.core.checkpoint``.
RESIDUAL_MODES = MOE_RESIDUAL_MODES


def moe_ffn_blaze(x: jax.Array, gates: jax.Array, dispatch: Dispatch,
                  w1: jax.Array, w3: jax.Array, w2: jax.Array | None = None,
                  *, activation: str = "swiglu",
                  save_yswi: bool = True,
                  residuals: str | None = None,
                  backend: str | ResolvedBackend | None = None) -> jax.Array:
    """MoEBlaze expert FFN.

    Args:
      x: (L, d) unpermuted token activations.
      gates: (L, k) gate weights for the chosen experts.
      dispatch: index metadata from :func:`repro.core.routing.build_dispatch`.
      w1: (E, d, h) first projection (the SiLU branch for SwiGLU).
      w2: (E, d, h) gate-branch projection (SwiGLU only).
      w3: (E, h, d) down projection.
      activation: "swiglu" | "silu" | "relu" | "gelu".
      save_yswi: deprecated bool alias — paper-faithful (True) saves Y_swi;
        ignored when ``residuals`` is given.
      residuals: custom-VJP residual mode, "ab_yswi" | "ab" | "x" — usually
        derived from the checkpoint plan via
        ``repro.core.checkpoint.moe_residual_mode(cfg)``.  None falls back
        to the ``save_yswi`` alias.
      backend: grouped-GEMM backend — a name ("ragged" | "segment" |
        "pallas"), an upstream ``ResolvedBackend``, or None/"auto" to walk
        the full precedence chain (``use_backend`` context, then
        ``REPRO_GMM_BACKEND``, then auto).
    """
    if residuals is None:
        residuals = "ab_yswi" if save_yswi else "ab"
    if residuals not in RESIDUAL_MODES:
        raise ValueError(f"unknown residual mode {residuals!r}; "
                         f"known: {RESIDUAL_MODES}")
    # Resolve to a concrete name here so the custom-VJP static arg is a
    # stable hashable and the precedence chain is walked at trace time.
    backend = resolve(backend).name
    d = dispatch
    if activation == "swiglu":
        assert w2 is not None
        from repro.core.gmm_backend import get_backend
        if getattr(get_backend(backend), "fused_moe", False):
            # Fused dispatch→GEMM→combine kernel pair: the backward replays
            # the gather and recomputes A/B/SiLU in-kernel, so its residual
            # set (x + weights + gates) is strictly below even the "x" mode —
            # every requested mode is satisfied a fortiori.
            from repro.kernels.ops import moe_ffn_blaze_fused
            return moe_ffn_blaze_fused(x, gates, d, w1, w3, w2)
        return _moe_swiglu(residuals, backend, x, w1, w2, w3, gates,
                           d.expert_token_indices, d.expert_token_offsets,
                           d.token_index_map, d.expert_lengths)
    assert w2 is None or activation == "swiglu"
    return _moe_mlp(activation, backend, residuals, x, w1, w3, gates,
                    d.expert_token_indices, d.expert_token_offsets,
                    d.token_index_map, d.expert_lengths)
