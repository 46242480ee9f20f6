"""MoE transformer sublayer: router + MoEBlaze expert FFN, with padding-free
distributed execution.

One *Dispatch-driven* path (paper §4.1) serves every expert placement — the
compact index structures from ``core/routing.py`` are built once and either
used whole (single device, TP) or compacted to a device-local expert range
(``routing.slice_dispatch``), so the fused-SwiGLU ``custom_vjp``, the
paper's residual policy, the ``checkpoint.tag`` remat tags and the resolved
grouped-GEMM backend apply identically on one device and under a mesh.

Distribution modes (``cfg.moe_parallel``, README "Distribution modes"):

  * ``ep``     — experts sharded over 'model' (weights never gathered).  Each
    device slices the global Dispatch to its expert range and runs the SAME
    ``moe_ffn_blaze`` on its local tokens; one ``psum`` combines partials.
    Non-local slots rotate into the sliced structure's dead zone, where the
    grouped GEMM produces exact zeros — no capacity padding, no dense L×E.
  * ``ep_a2a`` — tokens sharded over 'model' as well: each device routes its
    L/n chunk, groups slots by destination rank with the same sort-free
    dispatch build, and exchanges capacity-bounded row buffers with
    ``jax.lax.all_to_all`` (counts first; overflow is accounted and surfaced
    as a stat, never silently padded).  With ``cfg.moe_a2a_chunks > 1`` the
    exchange is split into double-buffered chunks so chunk i's all_to_all
    overlaps chunk i-1's grouped GEMM (the overlap knob).
  * ``ep_a2a_hier`` — two-hop hierarchical exchange for meshes that declare
    a 'node' axis (X-MoE style): a node-local hop over the fast 'model'
    axis aligns rows with their destination *lane*, then ONE cross-node
    hop over 'node' delivers them — cross-node (DCN) traffic carries only
    the rows that must actually change nodes.
  * ``tp``     — every expert's hidden dim tensor-sharded over 'model'; the
    unmodified single-device algorithm runs per shard.
  * ``auto``   — resolved by ``roofline.select_moe_parallel``: the analytic
    collective cost model ranks the feasible modes by predicted step cost
    (compute + HBM traffic + bytes-on-wire over each mesh axis's bandwidth
    tier) and breaks near-ties toward lower per-device live bytes.  The
    full decision table travels with the resolution
    (:func:`resolve_moe_parallel_ex`, mirroring ``ResolvedBackend``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import gmm_backend as GB
from repro.core import routing
from repro.core.baseline import moe_ffn_dense, moe_ffn_megablocks
from repro.core.checkpoint import MOE_GATES, moe_residual_mode, tag
from repro.core.moe_layer import moe_ffn_blaze
from repro.models.common import dense_init

MOE_PARALLEL_MODES = ("auto", "ep", "ep_a2a", "ep_a2a_hier", "tp")


def init_moe_params(key, cfg, d: int) -> dict:
    E, h = cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    pd = jnp.dtype(cfg.param_dtype)
    p = {
        "wg": dense_init(ks[0], (d, E), 0, pd),
        "w1": dense_init(ks[1], (E, d, h), 1, pd),
        "w3": dense_init(ks[3], (E, h, d), 1, pd),
    }
    if cfg.ffn_act == "swiglu":
        p["w2"] = dense_init(ks[2], (E, d, h), 1, pd)
    return p


def resolve_moe_parallel(cfg, mesh, n_tokens: int | None = None) -> str:
    """Concrete distribution mode for (cfg, mesh): ``single`` | ``tp`` |
    ``ep`` | ``ep_a2a`` | ``ep_a2a_hier`` — the string half of
    :func:`resolve_moe_parallel_ex`."""
    return resolve_moe_parallel_ex(cfg, mesh, n_tokens).mode


def resolve_moe_parallel_ex(cfg, mesh, n_tokens: int | None = None):
    """Resolve ``cfg.moe_parallel`` against a mesh, with provenance.

    Returns a ``roofline.ParallelDecision`` (mirroring the grouped-GEMM
    registry's ``ResolvedBackend``): the concrete mode, its source
    (``config`` forced / ``auto`` cost model / ``single``) and the full
    predicted-cost table the ``auto`` optimizer ranked.  ``n_tokens`` is the
    per-device token slab when the caller knows it (trace time, train-step
    construction); ``auto`` only ever selects a mode that is *feasible* at
    that slab.

    Validates forced modes at entry — bad factorizations raise HERE, not
    mid-trace: expert parallelism with ``E`` not divisible by the combined
    expert axes would silently drop experts; flat ``ep_a2a`` on a node mesh
    would route cross-node rows over the flat exchange; ``ep_a2a_hier``
    without a 'node' axis has no second hop to run.
    """
    from repro import roofline

    if cfg.moe_parallel not in MOE_PARALLEL_MODES:
        raise ValueError(
            f"unknown moe_parallel {cfg.moe_parallel!r}; "
            f"known: {MOE_PARALLEL_MODES}")
    decision = roofline.select_moe_parallel(cfg, mesh, n_tokens)
    if decision.mode == "single":
        return decision
    n_model = mesh.shape.get("model", 1)
    n_node = mesh.shape.get("node", 1)
    n_exp = max(n_model, 1) * max(n_node, 1)
    mode = decision.mode
    if mode in ("ep", "ep_a2a", "ep_a2a_hier") and n_exp > 1 \
            and cfg.num_experts % n_exp != 0:
        raise ValueError(
            f"moe_parallel={mode!r} requires num_experts divisible by the "
            f"expert axes, got E={cfg.num_experts} % "
            f"n_exp={n_exp} (node x model) != 0 — E_loc = E // n_exp would "
            "silently drop experts.  Use moe_parallel='tp' or resize the "
            "mesh.")
    if mode == "ep_a2a" and n_node > 1:
        raise ValueError(
            "moe_parallel='ep_a2a' is the flat single-hop exchange; this "
            f"mesh declares a 'node' axis (n_node={n_node}) — use "
            "moe_parallel='ep_a2a_hier' (two-hop) or 'ep'.")
    if mode == "ep_a2a_hier" and n_node <= 1:
        raise ValueError(
            "moe_parallel='ep_a2a_hier' needs a factored 'model' axis: the "
            "mesh must declare a 'node' axis (see "
            "launch.mesh.make_node_mesh); this mesh has none.  Use "
            "moe_parallel='ep_a2a' on flat meshes.")
    return decision


def _aux_of(g, cfg):
    return (cfg.aux_loss_weight *
            routing.load_balance_loss(g.router_probs, g.topk_experts,
                                      cfg.num_experts)
            + cfg.z_loss_weight * routing.router_z_loss(g.logits))


def _moe_dispatch(xf: jax.Array, p: dict, cfg, g, disp, rb, *,
                  sliced: bool = False):
    """The shared Dispatch-driven expert compute: gate tagging + the chosen
    implementation over an (already global or already sliced) dispatch.

    Under a sliced dispatch the fused-Pallas composition (``blaze_pallas``)
    and the GShard ``dense`` oracle fall through to ``moe_ffn_blaze`` — the
    fused kernels are a single-device composition (``cfg.use_pallas``
    contract) and the dense oracle has no dispatch to slice; the resolved
    backend still selects the grouped-GEMM kernels inside.
    """
    gates = tag(g.topk_weights.astype(xf.dtype), MOE_GATES)
    if cfg.moe_impl == "megablocks":
        return moe_ffn_megablocks(xf, gates, disp, p["w1"], p["w3"],
                                  p.get("w2"), activation=cfg.ffn_act,
                                  backend=rb)
    if cfg.moe_impl == "blaze_pallas" and not sliced:
        # The fused-Pallas composition has a fixed residual set; a plan
        # whose moe-scoped overrides ask for a different one must fail
        # loudly here, not be silently ignored.
        mode = moe_residual_mode(cfg)
        if mode != ("ab_yswi" if cfg.save_yswi else "ab"):
            raise ValueError(
                f"moe_impl='blaze_pallas' cannot honor the checkpoint "
                f"plan's moe-scoped residual mode {mode!r} (the fused "
                "kernels manage a fixed residual set); use "
                "moe_impl='blaze' or drop the moe-scoped overrides")
        from repro.kernels.ops import moe_ffn_blaze_pallas
        return moe_ffn_blaze_pallas(xf, gates, disp, p["w1"], p["w3"],
                                    p["w2"], backend=rb)
    # Residual set from the checkpoint plan's moe scope (the deprecated
    # cfg.save_yswi bool is the fallback when the plan leaves it open).
    return moe_ffn_blaze(xf, gates, disp, p["w1"], p["w3"], p.get("w2"),
                         activation=cfg.ffn_act,
                         residuals=moe_residual_mode(cfg), backend=rb)


def _moe_local(xf: jax.Array, p: dict, cfg, backend=None):
    """Single-device / tensor-parallel MoEBlaze path on a (L, d) token slab."""
    E, k = cfg.num_experts, cfg.top_k
    g = routing.top_k_gating(xf, p["wg"].astype(xf.dtype), k)
    if cfg.moe_impl == "proxy_gmm":
        # COST-MODEL STAND-IN, dry-run probes only (never executed): XLA's
        # CPU decomposition of ragged_dot is dense-per-group (E x FLOPs /
        # temps), which misrepresents the TPU gmm lowering.  This proxy has
        # the gmm's exact useful FLOPs (L·k rows through d->h->d) and reads
        # the full expert weight bank once (the .sum(0) reductions), but is
        # NOT numerically the MoE.  See EXPERIMENTS.md §Roofline.
        disp = routing.build_dispatch(g.topk_experts, E)   # keep build cost
        gates = g.topk_weights.astype(xf.dtype)
        xg = jnp.take(xf, disp.expert_token_indices, axis=0)
        w1e = p["w1"].sum(0).astype(xf.dtype)
        w3e = p["w3"].sum(0).astype(xf.dtype)
        a = xg @ w1e
        if "w2" in p:
            y_act = jax.nn.silu(a) * (xg @ p["w2"].sum(0).astype(xf.dtype))
        else:
            y_act = jax.nn.silu(a)
        p_out = y_act @ w3e
        L = xf.shape[0]
        parts = jnp.take(p_out, disp.token_index_map.reshape(-1),
                         axis=0).reshape(L, k, -1)
        y = jnp.einsum("lk,lkd->ld", gates, parts)
        return y, _aux_of(g, cfg)
    if cfg.moe_impl == "dense":
        y = moe_ffn_dense(xf, g.router_probs, g.topk_experts,
                          g.topk_weights.astype(xf.dtype),
                          p["w1"], p["w3"], p.get("w2"),
                          activation=cfg.ffn_act)
        return y, _aux_of(g, cfg)
    if cfg.moe_impl == "blaze_pallas":
        from repro.kernels.dispatch import build_dispatch_pallas
        disp = build_dispatch_pallas(g.topk_experts, E)
    else:
        disp = routing.build_dispatch(g.topk_experts, E)
    # cfg.gmm_backend enters the precedence chain at the *config* slot: an
    # explicit call-site choice or an active use_backend() scope wins,
    # env/auto fill in when the config says "auto".
    rb = GB.resolve(backend, config=cfg.gmm_backend)
    y = _moe_dispatch(xf, p, cfg, g, disp, rb)
    return y, _aux_of(g, cfg)


def _moe_proxy_ep(xf: jax.Array, p: dict, cfg, n_model: int):
    """gmm cost model under EP: ~L·k/n_model rows through one d->h->d, plus
    one read of the local expert bank.  NOT numerically the MoE."""
    k = cfg.top_k
    L = xf.shape[0]
    g = routing.top_k_gating(xf, p["wg"].astype(xf.dtype), k)
    rows = max(L * k // n_model, 1)
    xg = jnp.take(xf, jnp.arange(rows) % L, axis=0)
    a = xg @ p["w1"].sum(0).astype(xf.dtype)
    y_act = jax.nn.silu(a)
    if "w2" in p:
        y_act = y_act * (xg @ p["w2"].sum(0).astype(xf.dtype))
    p_out = y_act @ p["w3"].sum(0).astype(xf.dtype)
    y = jnp.zeros_like(xf).at[jnp.arange(rows) % L].add(p_out)
    gm = g.topk_weights.astype(xf.dtype).mean()
    return y * gm, _aux_of(g, cfg)


def _moe_ep(xf: jax.Array, p: dict, cfg, n_exp: int, rb, idx=None):
    """Expert-parallel shard body: this device owns ``E_loc = E / n_exp``
    experts (weights arrive local via in_specs — no gather).  ``n_exp`` is
    the combined expert-axis size (``n_node * n_model`` on a node mesh) and
    ``idx`` this device's flattened expert-axis index (defaults to the
    'model' axis index on flat meshes).

    Full gating + the sort-free global dispatch build run on the (expert-axis
    replicated) token slab; ``routing.slice_dispatch`` compacts the result to
    this device's expert range, and the SAME ``moe_ffn_blaze`` path runs on
    it — the custom-VJP recompute, the plan-driven residual mode and the
    resolved grouped-GEMM backend all apply under EP.  ``psum`` over the
    expert axes (outside) combines expert contributions.
    """
    E, k = cfg.num_experts, cfg.top_k
    E_loc = E // max(n_exp, 1)
    g = routing.top_k_gating(xf, p["wg"].astype(xf.dtype), k)
    disp = routing.build_dispatch(g.topk_experts, E)
    if idx is None:
        idx = jax.lax.axis_index("model")
    loc = routing.slice_dispatch(disp, idx * E_loc, (idx + 1) * E_loc,
                                 count=E_loc)
    y = _moe_dispatch(xf, p, cfg, g, loc, rb, sliced=True)
    return y, _aux_of(g, cfg)


def _a2a_capacity(cfg, n_tokens: int, k: int, n_model: int) -> int:
    """Static per-destination-rank slot capacity of the flat exchange —
    delegates to the simulator's arithmetic so predictor, peak accounting
    and the traced path can never disagree."""
    from repro.core.memsim import _a2a_capacity as cap
    return cap(cfg, n_tokens * k, n_model)


def _a2a_pack(ids: jax.Array, G: int, C: int):
    """Slot bookkeeping of one capacity-bounded exchange hop.

    ``ids`` (R,) int32 destination group per routing slot, in ``[0, G]`` —
    id ``G`` is the trash group (rows that must not travel, e.g. hop-1 pads
    regrouped in hop 2).  The same sort-free dispatch build as routing
    (group members keep ascending row order) yields a bidirectional
    slot<->buffer mapping:

      ``src_of_slot`` (G*C,)  source row per buffer slot (-1 for pads),
      ``slot_ok``     (G*C,)  buffer-slot occupancy,
      ``buf_idx``     (R,)    destination buffer slot per row (G*C = dropped),
      ``valid``       (R,)    row made it under the capacity bound,
      ``sent``        (G,)    rows packed per destination,
      ``dropped``     ()      rows lost to the capacity bound.
    """
    R = ids.shape[0]
    dr = routing.build_dispatch(ids[:, None], G + 1)
    pos = dr.token_index_map.reshape(-1) - dr.expert_token_offsets[ids]
    valid = (ids < G) & (pos < C)
    buf_idx = jnp.where(valid, ids * C + pos, G * C)
    slot_rank = jnp.repeat(jnp.arange(G, dtype=jnp.int32), C)
    slot_pos = jnp.tile(jnp.arange(C, dtype=jnp.int32), G)
    lens = dr.expert_lengths[:G]
    sent = jnp.minimum(lens, C)
    slot_ok = slot_pos < sent[slot_rank]
    src_slot = jnp.minimum(dr.expert_token_offsets[slot_rank] + slot_pos,
                           R - 1)
    src_of_slot = jnp.where(slot_ok, dr.expert_token_indices[src_slot], -1)
    dropped = (lens - sent).sum()
    return src_of_slot, slot_ok, buf_idx, valid, sent, dropped


def _a2a_gather_x(xc, src_of_slot, slot_ok, k: int, rb):
    """Fill the send buffer's x rows: buffer slot <- token ``src//k``.
    Under a Pallas backend the rows stream through the ``gather_rows``
    kernel; the jnp path is the same gather expressed as a masked take."""
    row_ids = jnp.where(slot_ok, src_of_slot // k, -1)
    if rb.name in ("pallas", "pallas_fused"):
        from repro.kernels.ops import gather_rows
        return gather_rows(xc, row_ids)
    return jnp.where(slot_ok[:, None],
                     jnp.take(xc, jnp.maximum(row_ids, 0), axis=0),
                     jnp.zeros((), xc.dtype))


def _a2a_gather(vals, src_of_slot, slot_ok, fill):
    """Fill a per-slot send buffer (gates / expert ids) by the same
    slot<->buffer gather; pad slots carry ``fill``."""
    picked = jnp.take(vals, jnp.maximum(src_of_slot, 0), axis=0)
    return jnp.where(slot_ok, picked, jnp.asarray(fill, vals.dtype))


def _a2a_unpack(back, buf_idx, valid, n_rows: int):
    """Inverse of the send-buffer build: gather each routing slot's output
    row back out of the returned buffer (dropped slots contribute zeros)."""
    parts = jnp.take(back, jnp.minimum(buf_idx, n_rows - 1), axis=0)
    return jnp.where(valid[:, None], parts, jnp.zeros((), back.dtype))


def _local_expert_ffn(rx, rg, re, E_loc: int, p: dict, cfg, rb):
    """Run received k=1 slots against the local expert bank: build over
    ``E_loc + 1`` experts (the extra one collects pads/overflow) and slice
    the real range — trash slots rotate into the dead zone where the
    grouped GEMM produces exact zeros."""
    full = routing.build_dispatch(re[:, None], E_loc + 1)
    loc = routing.slice_dispatch(full, 0, E_loc)
    return moe_ffn_blaze(rx, rg[:, None], loc, p["w1"], p["w3"],
                         p.get("w2"), activation=cfg.ffn_act,
                         residuals=moe_residual_mode(cfg), backend=rb)


def _moe_ep_a2a(xf: jax.Array, p: dict, cfg, n_model: int, rb):
    """Token-exchanged expert parallelism (the X-MoE-style padding-free
    cross-device design, capacity-bounded).

    The local (data-shard) token slab is split over 'model': each rank routes
    its ``L/n`` chunk, groups slots by destination rank with the SAME
    sort-free dispatch build (destination rank = expert // E_loc), and
    exchanges fixed-capacity row buffers with ``jax.lax.all_to_all`` — counts
    first, then rows; slots beyond a destination's capacity are dropped and
    *accounted* (returned as an overflow fraction), never padded to a dense
    ``L×E`` buffer.  Received rows (k=1 slots) run through ``moe_ffn_blaze``
    against the local expert bank — pad rows carry a trash expert id that
    ``slice_dispatch`` rotates into the dead zone — and outputs return to
    their source rank over the same all_to_all pattern.

    With ``cfg.moe_a2a_chunks > 1`` the capacity buffers are split into
    double-buffered chunks: chunk ``j+1``'s exchange is issued before chunk
    ``j``'s grouped GEMM, so the two have no data dependency and XLA's async
    collectives overlap the wire time with the dense compute.  The slot
    bookkeeping, the overflow stat and the custom-VJP residual contract are
    chunk-local but otherwise identical to the unchunked path.
    """
    E, k = cfg.num_experts, cfg.top_k
    n = max(n_model, 1)
    E_loc = E // n
    L, d = xf.shape
    Lc = L // n
    chunks = max(int(getattr(cfg, "moe_a2a_chunks", 1)), 1)
    idx = jax.lax.axis_index("model")
    xc = jax.lax.dynamic_slice_in_dim(xf, idx * Lc, Lc, axis=0)
    g = routing.top_k_gating(xc, p["wg"].astype(xc.dtype), k)
    gates = tag(g.topk_weights.astype(xc.dtype), MOE_GATES)
    # Group this chunk's slots by destination rank (sort-free build reused).
    dest_rank = (g.topk_experts // E_loc).reshape(-1).astype(jnp.int32)
    C = _a2a_capacity(cfg, Lc, k, n)
    if chunks > 1:
        C = -(-C // chunks) * chunks          # pad to a chunk multiple
    src, slot_ok, buf_idx, valid, sent, dropped = _a2a_pack(dest_rank, n, C)
    send_x = _a2a_gather_x(xc, src, slot_ok, k, rb)
    send_g = _a2a_gather(gates.reshape(-1), src, slot_ok, 0)
    e_local = (g.topk_experts % E_loc).reshape(-1).astype(jnp.int32)
    send_e = _a2a_gather(e_local, src, slot_ok, E_loc)
    # Counts first: each rank learns how many rows every peer sent it ...
    recv_cnt = jax.lax.all_to_all(
        sent.reshape(n, 1), "model", 0, 0).reshape(n)
    # ... then the (cheap) slot metadata.
    recv_g = jax.lax.all_to_all(
        send_g.reshape(n, C), "model", 0, 0).reshape(n * C)
    recv_e = jax.lax.all_to_all(
        send_e.reshape(n, C), "model", 0, 0).reshape(n * C)
    # Mask rows past each source's announced count to the trash expert
    # (belt over the sender-side pad fill).
    row_valid = (jnp.arange(C, dtype=jnp.int32)[None, :]
                 < recv_cnt[:, None]).reshape(n * C)
    recv_e = jnp.where(row_valid, recv_e, E_loc)
    recv_g = jnp.where(row_valid, recv_g, jnp.zeros((), recv_g.dtype))
    if chunks == 1:
        recv_x = jax.lax.all_to_all(
            send_x.reshape(n, C, d), "model", 0, 0).reshape(n * C, d)
        y_rows = _local_expert_ffn(recv_x, recv_g, recv_e, E_loc, p, cfg, rb)
        # Return outputs to their source rank (all_to_all is its own inverse
        # under this split/concat pattern), gather back into (Lc, k) slots.
        back = jax.lax.all_to_all(
            y_rows.reshape(n, C, d), "model", 0, 0).reshape(n * C, d)
    else:
        # Double-buffered chunked exchange: buffer position j*Cc..(j+1)*Cc
        # of every rank is chunk j, so each chunk is its own complete
        # (n, Cc) exchange and chunk j+1's all_to_all has no dependency on
        # chunk j's GEMM — issued ahead, it overlaps the compute.
        Cc = C // chunks
        sx = send_x.reshape(n, chunks, Cc, d)
        ge = recv_g.reshape(n, chunks, Cc)
        ee = recv_e.reshape(n, chunks, Cc)

        def exch(j):
            return jax.lax.all_to_all(sx[:, j], "model", 0, 0)

        cur = exch(0)
        backs = []
        for j in range(chunks):
            nxt = exch(j + 1) if j + 1 < chunks else None
            y_j = _local_expert_ffn(cur.reshape(n * Cc, d),
                                    ge[:, j].reshape(-1),
                                    ee[:, j].reshape(-1), E_loc, p, cfg, rb)
            backs.append(jax.lax.all_to_all(
                y_j.reshape(n, Cc, d), "model", 0, 0))
            cur = nxt
        back = jnp.stack(backs, axis=1).reshape(n * C, d)
    parts = _a2a_unpack(back, buf_idx, valid, n * C).reshape(Lc, k, d)
    yc = parts.sum(axis=1).astype(xf.dtype)
    y = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros_like(xf), yc, idx * Lc, axis=0)
    overflow = dropped.astype(jnp.float32) / float(Lc * k)
    return y, _aux_of(g, cfg), overflow


def _moe_ep_a2a_hier(xf: jax.Array, p: dict, cfg, n_node: int, n_model: int,
                     rb):
    """Two-hop hierarchical token exchange for node meshes (X-MoE style).

    Device ``(i, l)`` on the ('node', 'model') expert axes owns experts
    ``[g*E_loc, (g+1)*E_loc)`` with ``g = i*n_model + l``.  Each device
    routes its ``L/n`` token chunk, then:

      hop 1 (node-local, fast axis): slots regroup by destination *lane*
        ``(e // E_loc) % n_model`` and exchange over 'model' — after this
        hop every row sits on the lane of its target expert, inside its
        source node;
      hop 2 (one cross-node exchange): received rows regroup by destination
        node ``e // (E_loc * n_model)`` and exchange over 'node' — the only
        DCN traffic is rows that genuinely change nodes.

    Both hops reuse the flat path's capacity/overflow accounting
    (``_a2a_pack``); hop-1 pad rows carry the global sentinel expert ``E``,
    which lands in hop 2's trash group by construction.  Compute and the
    return path mirror the flat exchange: the local grouped GEMM runs over
    ``slice_dispatch``'s dead-zone rotation, then the two hops invert in
    reverse order (all_to_all is its own inverse under this pattern).
    """
    E, k = cfg.num_experts, cfg.top_k
    nn, nl = max(n_node, 1), max(n_model, 1)
    n = nn * nl
    E_loc = E // n
    L, d = xf.shape
    Lc = L // n
    gdev = jax.lax.axis_index("node") * nl + jax.lax.axis_index("model")
    xc = jax.lax.dynamic_slice_in_dim(xf, gdev * Lc, Lc, axis=0)
    g = routing.top_k_gating(xc, p["wg"].astype(xc.dtype), k)
    gates = tag(g.topk_weights.astype(xc.dtype), MOE_GATES)
    eg = g.topk_experts.reshape(-1).astype(jnp.int32)   # global expert ids
    # --- hop 1: align rows with their destination lane, inside the node.
    dest_lane = (eg // E_loc) % nl
    from repro.core.memsim import _a2a_capacity as _cap
    C1 = _cap(cfg, Lc * k, nl)
    R1 = nl * C1
    src1, ok1, buf1, valid1, sent1, drop1 = _a2a_pack(dest_lane, nl, C1)
    s1x = _a2a_gather_x(xc, src1, ok1, k, rb)
    s1g = _a2a_gather(gates.reshape(-1), src1, ok1, 0)
    s1e = _a2a_gather(eg, src1, ok1, E)                 # sentinel: global E
    cnt1 = jax.lax.all_to_all(
        sent1.reshape(nl, 1), "model", 0, 0).reshape(nl)
    r1x = jax.lax.all_to_all(
        s1x.reshape(nl, C1, d), "model", 0, 0).reshape(R1, d)
    r1g = jax.lax.all_to_all(
        s1g.reshape(nl, C1), "model", 0, 0).reshape(R1)
    r1e = jax.lax.all_to_all(
        s1e.reshape(nl, C1), "model", 0, 0).reshape(R1)
    rv1 = (jnp.arange(C1, dtype=jnp.int32)[None, :]
           < cnt1[:, None]).reshape(R1)
    r1e = jnp.where(rv1, r1e, E)
    r1g = jnp.where(rv1, r1g, jnp.zeros((), r1g.dtype))
    # --- hop 2: one cross-node exchange per node pair, on the slow axis.
    # Pad rows (e == E) regroup into the trash group nn automatically:
    # E // (E_loc * nl) == nn.
    dest_node = jnp.minimum(r1e // (E_loc * nl), nn)
    C2 = _cap(cfg, Lc * k, nn, clamp=R1)
    R2 = nn * C2
    src2, ok2, buf2, valid2, sent2, drop2 = _a2a_pack(dest_node, nn, C2)
    s2x = jnp.where(ok2[:, None],
                    jnp.take(r1x, jnp.maximum(src2, 0), axis=0),
                    jnp.zeros((), r1x.dtype))
    s2g = _a2a_gather(r1g, src2, ok2, 0)
    s2e = _a2a_gather(r1e, src2, ok2, E)
    cnt2 = jax.lax.all_to_all(
        sent2.reshape(nn, 1), "node", 0, 0).reshape(nn)
    r2x = jax.lax.all_to_all(
        s2x.reshape(nn, C2, d), "node", 0, 0).reshape(R2, d)
    r2g = jax.lax.all_to_all(
        s2g.reshape(nn, C2), "node", 0, 0).reshape(R2)
    r2e = jax.lax.all_to_all(
        s2e.reshape(nn, C2), "node", 0, 0).reshape(R2)
    rv2 = (jnp.arange(C2, dtype=jnp.int32)[None, :]
           < cnt2[:, None]).reshape(R2)
    r2e = jnp.where(rv2, r2e, E)
    r2g = jnp.where(rv2, r2g, jnp.zeros((), r2g.dtype))
    # --- compute against the local bank (global ids -> local range; any
    # row not owned here — pads only, by construction — hits the dead zone).
    lo = gdev * E_loc
    el = jnp.where((r2e >= lo) & (r2e < lo + E_loc), r2e - lo,
                   E_loc).astype(jnp.int32)
    y2 = _local_expert_ffn(r2x, r2g, el, E_loc, p, cfg, rb)
    # --- inverse hop 2, then inverse hop 1.
    b2 = jax.lax.all_to_all(
        y2.reshape(nn, C2, d), "node", 0, 0).reshape(R2, d)
    y1 = _a2a_unpack(b2, buf2, valid2, R2)              # (R1, d)
    b1 = jax.lax.all_to_all(
        y1.reshape(nl, C1, d), "model", 0, 0).reshape(R1, d)
    parts = _a2a_unpack(b1, buf1, valid1, R1).reshape(Lc, k, d)
    yc = parts.sum(axis=1).astype(xf.dtype)
    y = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros_like(xf), yc, gdev * Lc, axis=0)
    # Every dropped row is counted exactly once — at its source (hop 1) or
    # its relay (hop 2); the pmean outside turns this into the global
    # dropped fraction, same accounting as the flat path.
    overflow = (drop1 + drop2).astype(jnp.float32) / float(Lc * k)
    return y, _aux_of(g, cfg), overflow


def moe_sublayer(x: jax.Array, p: dict, cfg, *, mesh=None,
                 dp_axes=("pod", "data"), with_stats: bool = False):
    """(B, S, d) -> ((B, S, d), aux_loss) — plus a stats dict when
    ``with_stats=True`` (``a2a_overflow``: fraction of routed slots dropped
    by the ``ep_a2a`` / ``ep_a2a_hier`` capacity bounds; 0.0 in every other
    mode).

    Distribution is selected by :func:`resolve_moe_parallel` (validated at
    entry) and executed by one Dispatch-driven path — see the module
    docstring and README "Distribution modes".
    """
    B, S, d = x.shape
    if mesh is not None:
        dp_axes = tuple(a for a in dp_axes if a in mesh.axis_names)
        n_dp = 1
        for a in dp_axes:
            n_dp *= mesh.shape[a]
        batch_axes = dp_axes if (B % max(n_dp, 1) == 0 and n_dp > 1) else ()
        tokens_per_dev = (B // n_dp if batch_axes else B) * S
    else:
        tokens_per_dev = B * S
    mode = resolve_moe_parallel(cfg, mesh, tokens_per_dev)

    if mode == "single":
        y, aux = _moe_local(x.reshape(B * S, d), p, cfg)
        y = y.reshape(B, S, d)
        if with_stats:
            return y, aux, {"a2a_overflow": jnp.zeros((), jnp.float32)}
        return y, aux

    n_model = mesh.shape.get("model", 1)
    n_node = mesh.shape.get("node", 1)
    n_exp = max(n_model, 1) * max(n_node, 1)
    # Resolve the grouped-GEMM backend HERE, at trace time outside the
    # shard_map, and thread the ResolvedBackend into the body: use_backend
    # scopes and config pins reach the distributed path exactly like the
    # single-device one.
    rb = GB.resolve(None, config=cfg.gmm_backend)
    if mode in ("ep_a2a", "ep_a2a_hier"):
        if tokens_per_dev % n_exp != 0:
            raise ValueError(
                f"moe_parallel={mode!r} splits the per-device token slab "
                f"over the expert axes: {tokens_per_dev} tokens/device % "
                f"n_exp={n_exp} != 0.  Pad the batch/sequence or use "
                "moe_parallel='ep'.")
    x_spec = P(batch_axes if batch_axes else None, None, None)
    # On a node mesh, expert banks shard over the combined (node, model)
    # axes — node-major blocks, matching gdev = node_i * n_model + lane_i.
    ep_w = ("node", "model") if n_node > 1 else "model"
    if mode in ("ep", "ep_a2a", "ep_a2a_hier"):
        p_specs = {"wg": P(None, None), "w1": P(ep_w, None, None),
                   "w2": P(ep_w, None, None), "w3": P(ep_w, None, None)}
    else:
        p_specs = {"wg": P(None, None), "w1": P(None, None, "model"),
                   "w2": P(None, None, "model"), "w3": P(None, "model", None)}
    p_specs = {k_: v for k_, v in p_specs.items() if k_ in p}
    all_axes = tuple(mesh.axis_names)
    # Partials combine over every expert axis; 'tp' shards the hidden dim
    # over 'model' only (node ranks hold identical replicas — no psum).
    psum_axes = (("node", "model") if n_node > 1 else ("model",)) \
        if mode in ("ep", "ep_a2a", "ep_a2a_hier") else ("model",)

    def body(xl, pl_):
        Bl, Sl, _ = xl.shape
        xf = xl.reshape(Bl * Sl, d)
        overflow = jnp.zeros((), jnp.float32)
        if (mode in ("ep", "ep_a2a", "ep_a2a_hier")
                and cfg.moe_impl == "proxy_gmm"):
            y, aux = _moe_proxy_ep(xf, pl_, cfg, n_exp)
        elif mode == "ep":
            idx = None
            if n_node > 1:
                idx = (jax.lax.axis_index("node") * n_model
                       + jax.lax.axis_index("model"))
            y, aux = _moe_ep(xf, pl_, cfg, n_exp, rb, idx=idx)
        elif mode == "ep_a2a":
            y, aux, overflow = _moe_ep_a2a(xf, pl_, cfg, n_model, rb)
        elif mode == "ep_a2a_hier":
            y, aux, overflow = _moe_ep_a2a_hier(xf, pl_, cfg, n_node,
                                                n_model, rb)
        else:
            y, aux = _moe_local(xf, pl_, cfg, backend=rb)
        # The one collective the MoE layer adds: combine partials.
        y = jax.lax.psum(y, psum_axes)
        aux = jax.lax.pmean(aux, all_axes)
        overflow = jax.lax.pmean(overflow, all_axes)
        return y.reshape(Bl, Sl, d), aux, overflow

    y, aux, overflow = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, p_specs),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(x, p)
    if with_stats:
        return y, aux, {"a2a_overflow": overflow}
    return y, aux
