"""Pluggable grouped-GEMM (gmm) backend registry.

Every grouped GEMM in the MoEBlaze core funnels through two primitives:

  * ``gmm(lhs, rhs, group_sizes)``    — (S, d) @ (E, d, h) -> (S, h), rows of
    ``lhs`` grouped by expert (``group_sizes`` sums to <= S; trailing rows
    belong to no group and produce zeros);
  * ``gmm_dw(lhs, dout, group_sizes)``— (S, d), (S, h) -> (E, d, h), the
    per-group weight gradient (contract the grouped row axis).

Both accumulate in fp32 and return ``lhs.dtype``.  The ``ragged`` backend
hands the GEMM bfloat16 copies of float32 operands wherever the product is one
bfloat16 pass anyway: where the computation is compiled for a TPU
(``jax.lax.platform_dependent``) and no higher
``jax.default_matmul_precision`` than the default is in force at trace time.
XLA's TPU kernel reads its operand tiles from HBM at the width it is given
and rounds them itself, so rounding first halves the bytes and leaves the
result as it was.  Compiled for the CPU, under a higher precision, and for
operands already narrower than float32 nothing is rounded.  The registry
makes the primitive swappable (MegaBlocks-style):

  * ``ragged``  — ``jax.lax.ragged_dot`` / ``ragged_dot_general``, the XLA
    grouped GEMM (on a TPU, XLA's own grouped-matmul kernel).  The auto
    choice.
  * ``segment`` — pure-``jnp`` rendering: per-group row mask + dense dot
    with fp32 accumulation.  Compute is O(E·S·d·h); it is the exact oracle
    the parity tests compare every other backend against, not a fast path.
  * ``pallas``  — the ``kernels/gather_gmm.py`` work-item kernels (rows
    already in expert order), compiled by Mosaic on a TPU and interpreted on
    the CPU (``repro.kernels.interpret_mode``).
  * ``pallas_fused`` — same kernels as a backend, plus the ``fused_moe``
    capability flag: ``moe_ffn_blaze`` routes whole SwiGLU layers through
    the fused dispatch→GEMM→combine kernel pair (no ``(L·k, ·)``
    intermediates in HBM, forward or backward).

Selection precedence (``resolve``):

  1. explicit ``backend=`` call-site argument,
  2. the active :func:`use_backend` context,
  3. a config field (``ModelConfig.gmm_backend`` / ``TrainConfig.gmm_backend``,
     passed via ``resolve(..., config=...)``),
  4. the ``REPRO_GMM_BACKEND`` environment variable,
  5. auto: ``ragged``.

``pallas`` / ``pallas_fused`` are never auto-selected: no chip measurement
has yet shown them faster than ``ragged``; they are requested explicitly.

    REPRO_GMM_BACKEND=segment python -m pytest -q          # force portable
    gmm(lhs, rhs, sizes, backend="ragged")                  # force fast path
    with use_backend("segment"):                            # scope, not env
        y = moe_ffn_blaze(...)

Resolution happens at *trace time* (inside jit it runs while the Python
function is being traced, so the chosen backend is baked into the jaxpr) and
is recorded in a :class:`ResolvedBackend` carrying the name plus jax-version
provenance.  Long-lived objects (``ServeEngine``, train steps) resolve once
at construction and hold the ``ResolvedBackend`` — mutating the environment
afterwards cannot retarget them.
"""

from __future__ import annotations

import contextlib
import os
from contextvars import ContextVar
from dataclasses import dataclass

import jax
import jax.numpy as jnp

ENV_VAR = "REPRO_GMM_BACKEND"

#: the auto choice: the XLA grouped GEMM.
_AUTO = "ragged"

#: the innermost active ``use_backend`` scope (None when outside any scope).
_ACTIVE: ContextVar[str | None] = ContextVar("repro_gmm_backend", default=None)


def _offsets_of(group_sizes: jax.Array) -> jax.Array:
    """(E,) group sizes -> (E+1,) exclusive prefix-sum offsets."""
    gs = group_sizes.astype(jnp.int32)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(gs)])


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------


#: ``jax_default_matmul_precision`` settings under which a TPU product of
#: float32 operands is one bfloat16 pass (None is JAX's default).
_ONE_BF16_PASS = (None, "default", "bfloat16", "BF16_BF16_F32")


def _by_platform(plain, rounded, *args):
    """The one place the grouped GEMM looks at the platform: ``rounded``
    where the computation is compiled for a TPU, ``plain`` elsewhere."""
    return jax.lax.platform_dependent(*args, tpu=rounded, default=plain)


def _to_bf16(x):
    return x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x


def _one_pass(product, lhs, rhs):
    """``product(lhs, rhs)``, on bfloat16 copies of float32 operands where
    the product is one bfloat16 pass anyway: compiled for a TPU, with no
    higher default matmul precision in force at trace time."""
    if (jnp.float32 not in (lhs.dtype, rhs.dtype)
            or jax.config.jax_default_matmul_precision not in _ONE_BF16_PASS):
        return product(lhs, rhs)
    return _by_platform(product,
                        lambda l, r: product(_to_bf16(l), _to_bf16(r)),
                        lhs, rhs)


def _ragged_gmm_impl(lhs, rhs, group_sizes):
    gs = group_sizes.astype(jnp.int32)
    out = _one_pass(lambda l, r: jax.lax.ragged_dot(
        l, r, gs, preferred_element_type=jnp.float32), lhs, rhs)
    # Rows past the group total belong to no group.  XLA's TPU kernel
    # leaves them unwritten (whatever the buffer held, NaN included); the
    # contract — and the dead zone of a sliced dispatch — needs zeros.
    rows = jnp.arange(lhs.shape[0], dtype=jnp.int32)[:, None]
    return jnp.where(rows < gs.sum(), out, 0).astype(lhs.dtype)


def _ragged_dw_impl(lhs, dout, group_sizes):
    gs = group_sizes.astype(jnp.int32)
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),  # contract rows
        lhs_ragged_dimensions=[0],
        rhs_group_dimensions=[])
    out = _one_pass(lambda l, d: jax.lax.ragged_dot_general(
        l, d, gs, dims, preferred_element_type=jnp.float32), lhs, dout)
    return out.astype(lhs.dtype)


class SegmentBackend:
    """Portable pure-``jnp`` grouped GEMM: per-group mask + dense dot.

    A ``fori_loop`` over experts keeps the lowered program O(1) in E; each
    step masks the rows of the current group and runs one dense fp32 GEMM.
    Mathematically exact (no approximation), so it doubles as the oracle the
    parity tests compare every other backend against.
    """

    name = "segment"

    @staticmethod
    def gmm(lhs, rhs, group_sizes):
        S = lhs.shape[0]
        E, _, h = rhs.shape
        off = _offsets_of(group_sizes)
        rows = jnp.arange(S, dtype=jnp.int32)[:, None]

        def body(e, acc):
            w = jax.lax.dynamic_index_in_dim(rhs, e, 0, keepdims=False)
            mask = (rows >= off[e]) & (rows < off[e + 1])
            xm = jnp.where(mask, lhs, 0).astype(jnp.float32)
            return acc + xm @ w.astype(jnp.float32)

        acc = jnp.zeros((S, h), jnp.float32)
        return jax.lax.fori_loop(0, E, body, acc).astype(lhs.dtype)

    @staticmethod
    def gmm_dw(lhs, dout, group_sizes):
        E = group_sizes.shape[0]
        d, h = lhs.shape[1], dout.shape[1]
        off = _offsets_of(group_sizes)
        rows = jnp.arange(lhs.shape[0], dtype=jnp.int32)[:, None]

        def body(e, acc):
            mask = (rows >= off[e]) & (rows < off[e + 1])
            xm = jnp.where(mask, lhs, 0).astype(jnp.float32)
            dw = xm.T @ dout.astype(jnp.float32)
            return acc.at[e].set(dw)

        acc = jnp.zeros((E, d, h), jnp.float32)
        return jax.lax.fori_loop(0, E, body, acc).astype(lhs.dtype)


def _pallas_gmm_impl(lhs, rhs, group_sizes):
    from repro.kernels.gather_gmm import gather_gmm
    # Backend contract: rows past the group-size total belong to no group and
    # are exact zeros.  The kernel guarantees this itself: rows inside a
    # visited tile are zeroed by the in-tile row mask, and tiles past the
    # total are zero-initialized by make_work_items' filler items.
    return gather_gmm(lhs, None, _offsets_of(group_sizes), rhs,
                      epilogue=False)


def _pallas_dw_impl(lhs, dout, group_sizes):
    from repro.kernels.gather_gmm import gmm_dw_pallas
    # Empty experts' (1, d, h) blocks are zero-initialized in-kernel (each
    # empty expert gets a dedicated efirst filler item) — no caller-side
    # masking needed.
    return gmm_dw_pallas(lhs, dout, _offsets_of(group_sizes))


# The grouped GEMM is linear: d_lhs flows through the transposed weights and
# d_rhs is exactly the grouped weight gradient.  Each backend's pair of
# kernels is wrapped in custom VJPs built from each other, which keeps the
# backend contract uniform — every backend is differentiable by plain
# autodiff, not just inside the MoE layer's hand-written VJP.  ``pallas_call``
# has no JVP rule; ``ragged_dot``'s own rule would hand back bfloat16
# cotangents for the rounded operands, where these keep float32.


def _linear_pair(gmm_impl, dw_impl):
    @jax.custom_vjp
    def gmm_fn(lhs, rhs, group_sizes):
        return gmm_impl(lhs, rhs, group_sizes)

    def gmm_fwd(lhs, rhs, group_sizes):
        return gmm_impl(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def gmm_bwd(res, dout):
        lhs, rhs, gs = res
        dlhs = gmm_impl(dout, jnp.swapaxes(rhs, 1, 2), gs)
        drhs = dw_impl(lhs, dout, gs)
        return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), None

    @jax.custom_vjp
    def dw_fn(lhs, dout, group_sizes):
        return dw_impl(lhs, dout, group_sizes)

    def dw_fwd(lhs, dout, group_sizes):
        return dw_impl(lhs, dout, group_sizes), (lhs, dout, group_sizes)

    def dw_bwd(res, ddw):
        lhs, dout, gs = res
        dlhs = gmm_impl(dout, jnp.swapaxes(ddw, 1, 2), gs)
        ddout = gmm_impl(lhs, ddw, gs)
        return dlhs.astype(lhs.dtype), ddout.astype(dout.dtype), None

    gmm_fn.defvjp(gmm_fwd, gmm_bwd)
    dw_fn.defvjp(dw_fwd, dw_bwd)
    return gmm_fn, dw_fn


_ragged_gmm, _ragged_dw = _linear_pair(_ragged_gmm_impl, _ragged_dw_impl)
_pallas_gmm, _pallas_dw = _linear_pair(_pallas_gmm_impl, _pallas_dw_impl)


class RaggedBackend:
    """``jax.lax.ragged_dot[_general]`` — the XLA grouped-GEMM fast path."""

    name = "ragged"

    @staticmethod
    def gmm(lhs, rhs, group_sizes):
        return _ragged_gmm(lhs, rhs, group_sizes)

    @staticmethod
    def gmm_dw(lhs, dout, group_sizes):
        return _ragged_dw(lhs, dout, group_sizes)


class PallasBackend:
    """The ``kernels/gather_gmm.py`` work-item kernels on rows already in
    expert order — compiled on a TPU, interpreted on the CPU."""

    name = "pallas"

    @staticmethod
    def gmm(lhs, rhs, group_sizes):
        return _pallas_gmm(lhs, rhs, group_sizes)

    @staticmethod
    def gmm_dw(lhs, dout, group_sizes):
        return _pallas_dw(lhs, dout, group_sizes)


class PallasFusedBackend(PallasBackend):
    """Fully fused dispatch→GEMM→combine Pallas path (SonicMoE-style).

    As a grouped-GEMM backend it behaves exactly like ``pallas`` (same
    work-item kernels — the parity suite covers it for free); the extra
    ``fused_moe`` capability flag makes ``moe_ffn_blaze`` route SwiGLU
    layers to ``kernels.ops.moe_ffn_blaze_fused``, where the second grouped
    GEMM and the gated combine run inside the same grid pass and the
    backward replays the gather in-kernel — no ``(L·k, h)`` / ``(L·k, d)``
    intermediate exists in HBM in either direction.  Tile sizes come from
    ``repro.roofline.select_moe_tiles``.  Never auto-selected; request it
    explicitly like ``pallas``.
    """

    name = "pallas_fused"

    #: capability flag: ``moe_ffn_blaze`` routes whole SwiGLU MoE layers
    #: through the fused kernel pair instead of composing gmm/gmm_dw calls.
    fused_moe = True


# ---------------------------------------------------------------------------
# Registry + selection
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, object] = {
    b.name: b for b in (RaggedBackend, SegmentBackend, PallasBackend,
                        PallasFusedBackend)
}


def backend_names() -> list[str]:
    """All registered backend names."""
    return list(_REGISTRY)


@dataclass(frozen=True)
class ResolvedBackend:
    """A concrete, validated backend choice with provenance.

    ``name`` is always a registered backend; ``source`` records
    which precedence slot won (``arg`` | ``context`` | ``config`` | ``env`` |
    ``auto``); ``jax_version`` is the install the resolution was made on —
    together they make a BENCH record / step metric self-describing in mixed
    fleets where two hosts resolve the same config differently.  Frozen and
    hashable, so it can ride through jit static arguments unchanged."""

    name: str
    source: str
    jax_version: str

    def __str__(self) -> str:                   # pragma: no cover - trivial
        return self.name


def _unset(name) -> bool:
    """True when a precedence slot holds no explicit choice."""
    return name in (None, "", "auto")


def _validate(name: str) -> str:
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown gmm backend {name!r}; known: {backend_names()}")
    return name


@contextlib.contextmanager
def use_backend(name: str | None):
    """Scope the grouped-GEMM backend for everything traced inside the block.

    Sits between the call-site argument and config fields in the precedence
    chain, so ``with use_backend("segment"):`` retargets a whole train step /
    engine batch without touching configs or the process environment.  The
    name is validated eagerly (entering the scope raises on an unknown
    backend); ``None``/"auto" makes the scope fully transparent —
    it neither selects nor masks an enclosing scope, so helpers can forward
    an optional pin via ``with use_backend(maybe_none):`` safely.  Scopes
    nest — the innermost non-transparent one wins."""
    if _unset(name):
        yield                       # transparent: inherit enclosing scope
        return
    _validate(name)
    token = _ACTIVE.set(name)
    try:
        yield
    finally:
        _ACTIVE.reset(token)


def active_backend() -> str | None:
    """The innermost ``use_backend`` scope's name, or None outside any."""
    return _ACTIVE.get()


def resolve(backend: str | ResolvedBackend | None = None, *,
            config: str | None = None) -> ResolvedBackend:
    """Resolve a backend request to a concrete :class:`ResolvedBackend`.

    Precedence: ``backend`` call-site argument > active :func:`use_backend`
    context > ``config`` (a ``gmm_backend`` config field) > the
    ``REPRO_GMM_BACKEND`` environment variable > auto (``ragged``).  A
    ``ResolvedBackend`` passed as ``backend`` is returned unchanged (already
    resolved upstream — threading it is free of re-resolution surprises)."""
    if isinstance(backend, ResolvedBackend):
        return backend
    chain = (("arg", backend),
             ("context", _ACTIVE.get()),
             ("config", config),
             ("env", os.environ.get(ENV_VAR, "").strip() or None))
    for source, cand in chain:
        if not _unset(cand):
            return ResolvedBackend(_validate(cand), source, jax.__version__)
    return ResolvedBackend(_AUTO, "auto", jax.__version__)


def resolve_backend_name(name: str | ResolvedBackend | None = None, *,
                         config: str | None = None) -> str:
    """Resolve to a concrete backend *name* (:func:`resolve`
    without the provenance — kept for call sites that only need the str)."""
    return resolve(name, config=config).name


def get_backend(name: str | ResolvedBackend | None = None):
    """Return the backend object for ``name`` (or the resolved default)."""
    return _REGISTRY[resolve(name).name]


def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
        *, backend: str | ResolvedBackend | None = None) -> jax.Array:
    """Grouped matmul: rows of ``lhs`` (grouped by ``group_sizes``) times the
    matching ``rhs[g]``.  (S, d) @ (E, d, h) -> (S, h)."""
    return get_backend(backend).gmm(lhs, rhs, group_sizes)


def gmm_dw(lhs: jax.Array, dout: jax.Array, group_sizes: jax.Array,
           *, backend: str | ResolvedBackend | None = None) -> jax.Array:
    """Per-group weight gradient: (S, d), (S, h) -> (E, d, h)."""
    return get_backend(backend).gmm_dw(lhs, dout, group_sizes)
