"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md
§Roofline).

Three terms per (arch x shape x mesh), in seconds:
  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_bytes_per_device / ICI link bw

``cost_analysis()`` runs on the *partitioned per-device* module, so its flops
and bytes are already per-chip.  Collective bytes are not in cost_analysis —
we parse the compiled HLO and sum operand sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.

The same intensity model also drives kernel tiling:
:func:`select_moe_tiles` picks the ``bl``/``bh`` work-item tile sizes for
the gather-GMM / fused-MoE kernels from the ridge point instead of
hard-coded 128s.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

from repro.hardware import DCN_BW, peaks

# The modeled chip (TPU v5e): every analytic term below is charged at its
# peaks, from the one table in ``repro.hardware``.
_CHIP = peaks()
PEAK_FLOPS_BF16 = _CHIP.bf16_flops
HBM_BW = _CHIP.hbm_bw
HBM_BYTES = _CHIP.hbm_bytes
ICI_BW_PER_LINK = _CHIP.ici_bw_per_link


def axis_bandwidth(axis: str) -> float:
    """Bytes/s the collective cost model charges for traffic over ``axis``:
    'node'/'pod' cross the data-center network, everything else rides the
    chip-to-chip interconnect (one link)."""
    return DCN_BW if axis in ("node", "pod") else ICI_BW_PER_LINK


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_stats(hlo_text: str) -> dict:
    """Sum tensor bytes per collective kind from HLO text.

    HLO operands are ``%ref``s without inline shapes, so we use the *result*
    shape (tuples summed) — the full-tensor size, which is the standard
    per-device ring-transfer proxy (~1x tensor bytes for AG/RS, ~2x for AR;
    we count 1x uniformly and note it in EXPERIMENTS.md).

    NOTE: ops inside a ``while`` body (layer scan) appear once in the text;
    callers must apply the trip-count extrapolation (see dryrun.run_one).
    """
    stats = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(
            r"(?:ROOT\s+)?%[\w.-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*)"
            r"\s+([a-z-]+)\(", line)
        if not m or m.group(2) not in _COLLECTIVES:
            continue
        kind = m.group(2)
        counts[kind] += 1
        for dt, dims in _SHAPE_RE.findall(m.group(1)):
            if dt in _DTYPE_BYTES:
                stats[kind] += _shape_bytes(dt, dims)
    return {"bytes": stats, "counts": counts,
            "total_bytes": sum(stats.values()),
            "total_count": sum(counts.values())}


def model_flops(cfg, shape) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE) — the useful-compute yardstick."""
    import jax
    from repro.launch.specs import params_shapes
    ps = params_shapes(cfg)
    flat = jax.tree_util.tree_flatten_with_path(ps)[0]
    n_total = 0
    n_expert = 0
    for path, leaf in flat:
        keys = "/".join(
            str(getattr(k, "key", getattr(k, "idx", ""))) for k in path)
        if keys == "embed":
            continue  # embedding lookup is a gather, not a matmul
        size = 1
        for s in leaf.shape:
            size *= s
        if cfg.is_moe and "/moe/" in f"/{keys}/".replace("//", "/"):
            n_expert += size
        else:
            n_total += size
    if cfg.is_moe and cfg.num_experts:
        n_active = n_total + n_expert * cfg.top_k / cfg.num_experts
    else:
        n_active = n_total
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 3.0 if shape.kind == "train" else 1.0   # fwd + 2x bwd
    return 2.0 * n_active * tokens * mult


def analyze_compiled(compiled, cfg, shape, *, n_chips: int) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    hlo_bytes = float(cost.get("bytes accessed", 0.0))
    mem = compiled.memory_analysis()
    arg_b = getattr(mem, "argument_size_in_bytes", 0)
    out_b = getattr(mem, "output_size_in_bytes", 0)
    tmp_b = getattr(mem, "temp_size_in_bytes", 0)
    alias_b = getattr(mem, "alias_size_in_bytes", 0)
    peak = arg_b + out_b + tmp_b - alias_b
    coll = collective_stats(compiled.as_text())
    mf = model_flops(cfg, shape)
    t_compute = flops / PEAK_FLOPS_BF16
    t_memory = hlo_bytes / HBM_BW
    t_coll = coll["total_bytes"] / ICI_BW_PER_LINK
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    return {
        "flops_per_dev": flops,
        "hlo_bytes_per_dev": hlo_bytes,
        "collective_bytes": coll["total_bytes"],
        "collective_counts": coll["counts"],
        "collective_bytes_by_kind": coll["bytes"],
        "arg_bytes": arg_b, "out_bytes": out_b, "temp_bytes": tmp_b,
        "peak_bytes": peak, "fits_hbm": bool(peak <= HBM_BYTES),
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops_global": mf,
        "useful_flops_ratio": mf / max(flops * n_chips, 1.0),
        "n_chips": n_chips,
    }


# ---------------------------------------------------------------------------
# MoE-parallelism collective cost model (README "Distribution modes")
# ---------------------------------------------------------------------------
#
# ``moe_parallel="auto"`` is resolved by ranking the candidate modes with the
# same three-term roofline used for compiled modules, evaluated analytically
# per mode on ONE MoE layer at the per-device token slab:
#
#   compute    = grouped-GEMM + gating + dispatch-build flops / peak FLOP/s
#   memory     = working-set HBM traffic (2x the dispatch/GEMM buffers, one
#                read of the local weight bank) / HBM bw
#   collective = bytes-on-wire per axis / that axis's bandwidth — a psum ring
#                moves 2*(n-1)/n of the tensor per device; an a2a hop moves
#                (n-1)/n of each capacity buffer each way.  'node'/'pod' axes
#                are charged at DCN bandwidth, 'model' at ICI (the two tiers
#                the hierarchical two-hop a2a is built around).
#
# Buffer row counts come from ``core.memsim`` so the predictor and the peak
# simulator can never disagree about what a mode allocates.  The measured
# half of the loop is ``collective_stats`` below: dryrun parses the compiled
# HLO and prints predicted-vs-measured bytes per collective kind.

#: modes the optimizer ranks, in deterministic tie-break preference order
#: (earlier wins when predicted costs tie).
MOE_MODE_ORDER = ("ep", "ep_a2a_hier", "ep_a2a", "tp")

#: a mode within this fraction of the fastest predicted time is a candidate;
#: among candidates the lowest per-device live bytes wins (the memory wall
#: is the binding constraint the paper optimizes).
AUTO_TIME_SLACK = 0.10

#: live-bytes spread below this is noise — prefer the faster/earlier mode
#: instead (keeps tiny decode slabs on ``ep`` where a2a latency dominates).
AUTO_LIVE_EPS = 8 * 1024 * 1024

#: per-device slab used to rank modes when the caller has no token count yet
#: (construction-time resolution; trace-time calls pass the real slab).
DEFAULT_AUTO_TOKENS = 4096

#: int ops per routing slot per pass of the sort-free one-hot/cumsum
#: dispatch build, charged as flops (one-hot + cumsum + offset gather).
_DISPATCH_PASSES = 3.0


@dataclass(frozen=True)
class ParallelCost:
    """One row of the ``auto`` decision table: predicted per-layer cost of
    running the MoE sublayer under ``mode`` on this config x mesh."""

    mode: str
    feasible: bool
    why: str                    # infeasibility reason ("" when feasible)
    t_compute_s: float
    t_memory_s: float
    t_collective_s: float
    t_total_s: float
    live_bytes: int             # per-device transient working set + buffers
    a2a_bytes: int              # predicted bytes-on-wire, all_to_all
    psum_bytes: int             # predicted bytes-on-wire, psum combine
    chosen: bool = False

    def row(self) -> dict:
        """JSON-ready record row (dryrun decision table)."""
        return {
            "mode": self.mode, "feasible": self.feasible, "why": self.why,
            "t_compute_s": self.t_compute_s, "t_memory_s": self.t_memory_s,
            "t_collective_s": self.t_collective_s,
            "t_total_s": self.t_total_s, "live_bytes": self.live_bytes,
            "a2a_bytes": self.a2a_bytes, "psum_bytes": self.psum_bytes,
            "chosen": self.chosen,
        }


@dataclass(frozen=True)
class ParallelDecision:
    """Resolved MoE distribution with provenance (mirrors
    ``gmm_backend.ResolvedBackend``): the concrete mode, where it came from
    (``config`` = forced, ``auto`` = cost model, ``single`` = no mesh or a
    1-way expert axis), and the full predicted-cost table it was ranked
    from."""

    mode: str                   # single | ep | ep_a2a | ep_a2a_hier | tp
    source: str                 # "config" | "auto" | "single"
    table: tuple            # ParallelCost rows, MOE_MODE_ORDER order
    n_tokens: int               # per-device slab the table was ranked at
    mesh_axes: tuple        # ((axis, size), ...) of the mesh ranked against

    def table_rows(self) -> list:
        return [c.row() for c in self.table]


def _psum_cost(n_tokens: int, d: int, it: int, axes) -> tuple[int, float]:
    """(bytes-on-wire, seconds) of psum-combining a (L, d) partial over the
    given ``(axis_name, size)`` pairs: ring all-reduce per axis, the slow
    (cross-node) axis charged at DCN bandwidth."""
    bytes_total, t = 0, 0.0
    for axis, n in axes:
        if n <= 1:
            continue
        b = int(2 * (n - 1) / n * n_tokens * d * it)
        bytes_total += b
        t += b / axis_bandwidth(axis)
    return bytes_total, t


def _a2a_hop_cost(rows: int, n: int, d: int, it: int, axis: str
                  ) -> tuple[int, float]:
    """(bytes-on-wire, seconds) of one capacity-bounded token exchange over
    ``axis``: ``rows`` buffer rows of width d cross the wire twice (x out,
    y back), (n-1)/n of them leaving the device."""
    if n <= 1:
        return 0, 0.0
    b = int(2 * rows * (n - 1) / n * d * it)
    return b, b / axis_bandwidth(axis)


def moe_parallel_costs(cfg, *, n_model: int, n_node: int = 1,
                       n_tokens: int) -> tuple:
    """Predicted :class:`ParallelCost` rows for every rankable mode of
    (cfg, expert axes, per-device slab).  Pure arithmetic — no jax."""
    from repro.core import memsim

    E, k, d, h = cfg.num_experts, cfg.top_k, cfg.d_model, cfg.moe_d_ff
    it = memsim._itemsize(cfg.dtype)
    n_exp = max(n_model, 1) * max(n_node, 1)
    L = max(int(n_tokens), 1)
    n_mat = 3 if cfg.ffn_act == "swiglu" else 2
    chunks = max(int(getattr(cfg, "moe_a2a_chunks", 1)), 1)

    def tile_pen(width: float) -> float:
        """MXU lane quantization (same 128-lane alignment that drives
        :func:`select_moe_tiles`): a GEMM whose minor dim is ``width`` pads
        to the next 128 multiple and runs at ``width / pad`` of peak."""
        if width <= 0:
            return 1.0
        return float(-(-int(width) // 128) * 128) / float(width)

    def gemm_time(h_eff: float) -> float:
        """Per-device grouped-GEMM seconds: ep/a2a split rows expert-wise at
        full matrix widths, tp keeps every row but slices the expert hidden
        dim to ``h_eff`` — sub-tile slivers burn MXU lanes, which is what
        makes tp lose to expert parallelism at small per-device h."""
        base = 2.0 * n_mat * L * k * d * h / n_exp
        pen = ((n_mat - 1) * tile_pen(h_eff) + tile_pen(d)) / n_mat
        return base * pen / PEAK_FLOPS_BF16

    w_bytes = n_mat * E * d * h * it / n_exp       # one local-bank read

    def feas(mode: str) -> str:
        if n_exp <= 1 and mode != "tp":
            return "expert axes are 1-way"
        if mode in ("ep", "ep_a2a", "ep_a2a_hier"):
            if E % n_exp:
                return f"E={E} not divisible by {n_exp} expert ways"
        if mode in ("ep_a2a", "ep_a2a_hier") and L % n_exp:
            return f"{L} tokens/device not divisible by {n_exp} ranks"
        if mode == "ep_a2a" and n_node > 1:
            return "flat a2a on a node mesh (use ep_a2a_hier)"
        if mode == "ep_a2a_hier" and n_node <= 1:
            return "mesh declares no 'node' axis"
        if mode == "tp" and n_model > 1 and h % n_model:
            return f"moe_d_ff={h} not divisible by n_model={n_model}"
        return ""

    rows_out = []
    for mode in MOE_MODE_ORDER:
        why = feas(mode)
        t_gemm = gemm_time(h / n_model if mode == "tp" else h)
        s = memsim.moe_layer_sizes(cfg, L, mode=mode, n_model=n_model,
                                   n_node=n_node)
        # tokens this device gates/routes, and dispatch-build work
        if mode in ("ep_a2a", "ep_a2a_hier"):
            tm = max(L // n_exp, 1)
        else:
            tm = L
        if mode == "ep_a2a":
            rows = memsim._a2a_rows(cfg, L, n_exp)
            disp_ops = tm * k * n_exp + rows * (E // max(n_exp, 1) + 1)
            a2a_b, t_a2a = _a2a_hop_cost(rows, n_exp, d, it, "model")
        elif mode == "ep_a2a_hier":
            r1, r2 = memsim._a2a_hier_rows(cfg, L, n_node, n_model)
            rows = r2
            disp_ops = (tm * k * n_model + r1 * (n_node + 1)
                        + r2 * (E // max(n_exp, 1) + 1))
            b1, t1 = _a2a_hop_cost(r1, n_model, d, it, "model")
            b2, t2 = _a2a_hop_cost(r2, n_node, d, it, "node")
            a2a_b, t_a2a = b1 + b2, t1 + t2
        else:
            rows = L * k
            disp_ops = tm * k * E
            a2a_b, t_a2a = 0, 0.0
        flops_other = 2.0 * tm * d * E + _DISPATCH_PASSES * disp_ops
        # psum axes: expert modes combine over every expert axis; tp's
        # hidden-sharded partials combine over 'model' only (node replicas,
        # when present, already agree).
        if mode in ("ep", "ep_a2a", "ep_a2a_hier"):
            psum_axes = (("node", n_node), ("model", n_model))
        else:
            psum_axes = (("model", n_model),)
        psum_b, t_psum = _psum_cost(L, d, it, psum_axes)
        hbm = 2.0 * (s.moe_other + s.moe_vjp) + w_bytes
        t_compute = t_gemm + flops_other / PEAK_FLOPS_BF16
        t_memory = hbm / HBM_BW
        t_coll = t_a2a + t_psum
        if mode == "ep_a2a" and chunks > 1:
            # Double-buffered chunks let chunk i's exchange ride under
            # chunk i-1's grouped GEMM: only the pipeline-fill fraction of
            # the smaller of the two stays exposed.
            overlapped = min(t_a2a, t_gemm)
            t_total = (t_compute + t_memory + t_psum
                       + max(t_a2a, t_gemm) - t_gemm
                       + overlapped / chunks)
        else:
            t_total = t_compute + t_memory + t_coll
        live = s.moe_other + s.moe_vjp + s.moe_x + s.collective
        rows_out.append(ParallelCost(
            mode=mode, feasible=not why, why=why,
            t_compute_s=t_compute, t_memory_s=t_memory,
            t_collective_s=t_coll, t_total_s=t_total,
            live_bytes=int(live), a2a_bytes=a2a_b, psum_bytes=psum_b))
    return tuple(rows_out)


def select_moe_parallel(cfg, mesh, n_tokens: int | None = None
                        ) -> ParallelDecision:
    """Rank the MoE distribution modes for (cfg, mesh, per-device slab) and
    resolve ``cfg.moe_parallel`` to a concrete mode with provenance.

    ``auto`` picks the fastest predicted mode, except that any feasible mode
    within :data:`AUTO_TIME_SLACK` of the fastest whose per-device live
    bytes are *materially* lower (> :data:`AUTO_LIVE_EPS` spread) wins the
    tie — predicted step cost first, memory wall second, exactly the
    ordering the paper's measurements justify.  Forced modes are passed
    through (validation lives in ``resolve_moe_parallel``) with the same
    table attached for provenance.
    """
    if mesh is None or not getattr(cfg, "is_moe", False):
        return ParallelDecision(mode="single", source="single", table=(),
                                n_tokens=int(n_tokens or 0), mesh_axes=())
    n_model = mesh.shape.get("model", 1)
    n_node = mesh.shape.get("node", 1)
    L = int(n_tokens) if n_tokens else DEFAULT_AUTO_TOKENS
    table = moe_parallel_costs(cfg, n_model=n_model, n_node=n_node,
                               n_tokens=L)
    mesh_axes = tuple((a, mesh.shape[a]) for a in mesh.axis_names)
    if cfg.moe_parallel != "auto":
        mode, source = cfg.moe_parallel, "config"
    else:
        source = "auto"
        feasible = [c for c in table if c.feasible]
        ep_like = [c for c in feasible if c.mode != "tp"]
        if not ep_like and n_model * n_node > 1:
            mode = "tp"           # legacy fallback: E doesn't divide -> tp
        elif not feasible:
            mode = "tp"
        else:
            t0 = min(c.t_total_s for c in feasible)
            cands = [c for c in feasible
                     if c.t_total_s <= t0 * (1.0 + AUTO_TIME_SLACK)]
            spread = (max(c.live_bytes for c in cands)
                      - min(c.live_bytes for c in cands))
            if spread > AUTO_LIVE_EPS:
                mode = min(cands, key=lambda c: c.live_bytes).mode
            else:
                # Sub-slack, sub-material differences are noise: take the
                # earliest candidate in MOE_MODE_ORDER (ep before the a2a
                # variants — no exchange machinery for no measurable win).
                order = {m: i for i, m in enumerate(MOE_MODE_ORDER)}
                mode = min(cands, key=lambda c: order[c.mode]).mode
    import dataclasses
    table = tuple(dataclasses.replace(c, chosen=c.mode == mode)
                  for c in table)
    return ParallelDecision(mode=mode, source=source, table=table,
                            n_tokens=L, mesh_axes=mesh_axes)


def select_moe_tiles(n_rows: int, d: int, h: int, *, dtype_bytes: int = 2,
                     num_experts: int | None = None,
                     vmem_limit_bytes: int = 8 * 1024 * 1024
                     ) -> tuple[int, int]:
    """Arithmetic-intensity-driven ``(bl, bh)`` tile selection for the
    gather-GMM / fused-MoE work-item kernels.

    A work-item step multiplies a ``(bl, d)`` row tile against ``(d, bh)``
    weight blocks (plus the ``(bh, d)`` down-projection in the fused path).
    Its arithmetic intensity is

        AI(bl, bh) = 2·bl·bh·d / ((bl·d + 2·d·bh + bh·d)·dtype_bytes)

    and the kernel stops being HBM-bound once AI exceeds the hardware ridge
    point ``PEAK_FLOPS_BF16 / HBM_BW`` (~240 flops/byte for the modeled
    chip).  We scan MXU-aligned candidates (multiples of 128, largest first
    per axis so ties break toward squarer tiles), keep those whose per-step
    VMEM footprint — gathered rows + three weight blocks + the fp32 partial
    accumulator and elementwise temps — fits ``vmem_limit_bytes``, and pick
    the *smallest* tile pair that reaches the ridge (beyond it, bigger tiles
    only add VMEM pressure and tail waste).  If nothing reaches the ridge
    (small ``d``), pick the max-AI candidate that fits.  The kernels still
    clamp: ``bh`` to the largest divisor of ``h``, ``bl`` to the padded row
    count — the returned pair is a *request*, exactly like the literals it
    replaces.

    When ``num_experts`` is given and the active JAX backend is CPU (the
    interpret-mode CI), ``bl`` is additionally shrunk for expert-boundary
    fragmentation — see the inline comment.
    """
    ridge = PEAK_FLOPS_BF16 / HBM_BW
    cands = []
    for bl in (128, 256, 512):
        for bh in (128, 256, 512):
            vmem = ((bl * d + 2 * d * bh + bh * d) * dtype_bytes
                    + bl * d * 4          # fp32 partial accumulator
                    + 3 * bl * bh * 4)    # a / b / y_swi fp32 temps
            if vmem > vmem_limit_bytes:
                continue
            ai = (2.0 * bl * bh * d
                  / ((bl * d + 2 * d * bh + bh * d) * dtype_bytes))
            cands.append((ai, bl, bh, bl * bh))
    if not cands:
        return 128, min(128, max(8, h))
    reaching = [c for c in cands if c[0] >= ridge]
    if reaching:
        _, bl, bh, _ = min(reaching, key=lambda c: (c[3], c[1]))
    else:
        _, bl, bh, _ = max(cands, key=lambda c: (c[0], -c[3]))
    # No point tiling beyond the problem: shrink toward the actual extents
    # (the kernel would clamp anyway; doing it here keeps the request honest).
    while bl > 128 and bl // 2 >= n_rows:
        bl //= 2
    while bh > 128 and bh // 2 >= h:
        bh //= 2
    # Expert-boundary fragmentation: the work-item scheme runs one full
    # (bl, ·) tile per expert boundary even when that item covers a handful
    # of slots, so total GEMM work scales like ``n_rows + E·bl``.  On TPU
    # the memory side (weight restreaming ∝ n_tiles + E) rewards big tiles
    # regardless, but under the CPU interpreter wall time tracks flops —
    # shrink ``bl`` until the masked-tile waste stops dominating the real
    # rows.  TPU tile selection is unchanged.
    import jax                     # deferred: roofline stays importable fast
    if num_experts and jax.default_backend() == "cpu":
        while bl > 32 and num_experts * bl >= 2 * n_rows:
            bl //= 2
    return bl, bh


def bench_entries(analysis: dict, prefix: str) -> list:
    """Project an ``analyze_compiled`` dict into ``repro.bench.record``
    entries so roofline-model numbers and measured numbers land in the same
    tracked report (``BENCH_memory.json``)."""
    from repro.bench.record import entry

    meta = {"dominant": analysis["dominant"], "n_chips": analysis["n_chips"]}
    return [
        entry(f"{prefix}/flops", analysis["flops_per_dev"],
              kind="flops", unit="flop", tolerance_pct=20.0, **meta),
        entry(f"{prefix}/hlo_bytes", analysis["hlo_bytes_per_dev"],
              kind="bytes_accessed", unit="bytes", tolerance_pct=100.0),
        entry(f"{prefix}/peak_bytes", analysis["peak_bytes"],
              kind="peak_bytes", unit="bytes", tolerance_pct=100.0),
        entry(f"{prefix}/t_compute", analysis["t_compute_s"],
              kind="roofline_s", unit="s"),
        entry(f"{prefix}/t_memory", analysis["t_memory_s"],
              kind="roofline_s", unit="s"),
        entry(f"{prefix}/t_collective", analysis["t_collective_s"],
              kind="roofline_s", unit="s"),
    ]
