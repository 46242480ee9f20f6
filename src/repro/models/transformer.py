"""Model assembly: block patterns, layer-scan, embeddings, train/prefill/
decode entry points, and cache management for all ten assigned architectures.

Layers are stacked per *pattern group* and iterated with ``jax.lax.scan``
(MaxText-style) so HLO size and compile time stay bounded for 46–62-layer
configs; alternating patterns (gemma2 local/global, xLSTM mLSTM/sLSTM) scan
over groups of ``cfg.pattern_period`` sublayers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from typing import NamedTuple

from repro.core import checkpoint as CK
from repro.models import ssm
from repro.models.attention import (attention_sublayer, init_attn_params,
                                    init_kv_cache, paged_attention_sublayer)
from repro.models.common import dense_init, rms_norm, softcap
from repro.models.ffn import ffn_sublayer, init_ffn_params
from repro.models.moe_block import init_moe_params, moe_sublayer
from repro.obs import scoped

ATTN_KINDS = {"attn_ffn", "attn_local_ffn", "attn_moe", "attn_local_moe"}


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_sublayer(key, kind: str, cfg) -> dict:
    d = cfg.d_model
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    zeros = lambda: jnp.zeros((d,), pd)
    if kind in ATTN_KINDS:
        p = {"ln1": zeros(), "ln2": zeros(),
             "attn": init_attn_params(ks[0], cfg, d)}
        if cfg.post_norms:
            p["ln1_post"] = zeros()
            p["ln2_post"] = zeros()
        if kind.endswith("moe"):
            p["moe"] = init_moe_params(ks[1], cfg, d)
        else:
            p["ffn"] = init_ffn_params(ks[1], cfg, d, cfg.d_ff)
        return p
    if kind == "mlstm":
        return {"ln1": zeros(), "mlstm": ssm.init_mlstm_params(ks[0], cfg, d)}
    if kind == "slstm":
        return {"ln1": zeros(), "slstm": ssm.init_slstm_params(ks[0], cfg, d)}
    if kind == "hymba":
        return {"ln1": zeros(), "ln2": zeros(),
                "attn": init_attn_params(ks[0], cfg, d),
                "mamba": ssm.init_mamba_params(ks[1], cfg, d),
                "ffn": init_ffn_params(ks[2], cfg, d, cfg.d_ff)}
    raise ValueError(kind)


def init_params(key, cfg) -> dict:
    d = cfg.d_model
    pd = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, cfg.num_groups + 4)
    pattern = cfg.block_pattern
    assert len(pattern) == cfg.pattern_period

    def init_group(k):
        sks = jax.random.split(k, len(pattern))
        return tuple(_init_sublayer(sk, kind, cfg)
                     for sk, kind in zip(sks, pattern))

    groups = [init_group(keys[i]) for i in range(cfg.num_groups)]
    layers = jax.tree.map(lambda *ls: jnp.stack(ls), *groups)
    params = {"layers": layers,
              "final_norm": jnp.zeros((d,), pd),
              "unembed": dense_init(keys[-1], (d, cfg.vocab_size), 0, pd)}
    if cfg.input_kind in ("tokens", "mixed"):
        params["embed"] = (jax.random.normal(keys[-2], (cfg.vocab_size, d))
                           * 0.02).astype(pd)
    if cfg.input_kind == "frames":
        params["frontend_proj"] = dense_init(keys[-3], (d, d), 0, pd)
    if cfg.input_kind == "mixed":
        params["img_proj"] = dense_init(keys[-3], (d, d), 0, pd)
    return params


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _apply_sublayer(x, p, kind: str, cfg, *, mesh, positions, cache,
                    paged=None):
    """Returns (x, aux, stats, new_cache) — ``aux`` the scalar aux loss,
    ``stats`` the scalar ``ep_a2a`` routing-overflow fraction.  ``paged``
    (a :class:`PagedCtx`) switches the attention sublayers onto the
    block-paged cache path; ``cache`` then holds each sublayer's
    :class:`~repro.serve.paged_cache.PagedKV` pool."""
    aux = jnp.zeros((), jnp.float32)
    stats = jnp.zeros((), jnp.float32)
    if kind in ATTN_KINDS:
        is_local = "local" in kind and cfg.sliding_window > 0
        with jax.named_scope("attention"):
            h = rms_norm(x, p["ln1"])
            if paged is not None:
                h, new_kv = paged_attention_sublayer(
                    h, p["attn"], cfg, is_local=is_local,
                    positions=positions, pages=cache[0],
                    page_table=paged.page_table, prefill=paged.prefill,
                    offsets=paged.offsets, attn_impl=paged.attn_impl)
            else:
                h, new_kv = attention_sublayer(
                    h, p["attn"], cfg, is_local=is_local,
                    positions=positions,
                    cache=cache[0] if cache is not None else None)
            if cfg.post_norms:
                h = rms_norm(h, p["ln1_post"])
            x = x + h
        if kind.endswith("moe"):
            with jax.named_scope("router"):
                h = rms_norm(x, p["ln2"])
            h, aux, mstats = moe_sublayer(h, p["moe"], cfg, mesh=mesh,
                                          with_stats=True)
            stats = mstats["a2a_overflow"]
        else:
            h = ffn_sublayer(rms_norm(x, p["ln2"]), p["ffn"], cfg)
        if cfg.post_norms:
            h = rms_norm(h, p["ln2_post"])
        return x + h, aux, stats, (new_kv,)
    if kind == "mlstm":
        h, st = ssm.mlstm_sublayer(
            rms_norm(x, p["ln1"]), p["mlstm"], cfg,
            state=cache[0] if cache is not None else None)
        return x + h, aux, stats, (st,)
    if kind == "slstm":
        h, st = ssm.slstm_sublayer(
            rms_norm(x, p["ln1"]), p["slstm"], cfg,
            state=cache[0] if cache is not None else None)
        return x + h, aux, stats, (st,)
    if kind == "hymba":
        h = rms_norm(x, p["ln1"])
        ha, new_kv = attention_sublayer(
            h, p["attn"], cfg, is_local=cfg.sliding_window > 0,
            positions=positions, cache=cache[0] if cache is not None else None)
        hm, st = ssm.mamba_sublayer(
            h, p["mamba"], cfg,
            state=cache[1] if cache is not None else None)
        x = x + 0.5 * (ha + hm)            # parallel heads, mean-fused
        h = ffn_sublayer(rms_norm(x, p["ln2"]), p["ffn"], cfg)
        return x + h, aux, stats, (new_kv, st)
    raise ValueError(kind)


def _apply_group(x, gp, cfg, *, mesh, positions, cache_group,
                 sub_policies=None, paged=None):
    """Apply one pattern group.  ``sub_policies`` (kind -> jax.checkpoint
    policy) engages per-block-kind remat: the plan scopes some shared tag
    differently across the kinds of this pattern, so each sublayer is
    checkpointed with its own scoped policy (training path only — the
    group-level wrap in ``forward`` handles the uniform case)."""
    auxes = []
    stats = []
    new_caches = []
    for j, kind in enumerate(cfg.block_pattern):
        c = cache_group[j] if cache_group is not None else None
        if sub_policies is not None and c is None:
            sub = jax.checkpoint(
                lambda x_, p_, kind=kind: _apply_sublayer(
                    x_, p_, kind, cfg, mesh=mesh, positions=positions,
                    cache=None),
                policy=sub_policies[kind], prevent_cse=False)
            x, aux, st, nc = sub(x, gp[j])
        else:
            x, aux, st, nc = _apply_sublayer(x, gp[j], kind, cfg, mesh=mesh,
                                             positions=positions, cache=c,
                                             paged=paged)
        auxes.append(aux)
        stats.append(st)
        new_caches.append(nc)
    return x, sum(auxes), sum(stats), tuple(new_caches)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


@scoped("embed")
def _embed_inputs(params, batch, cfg):
    dt = jnp.dtype(cfg.dtype)
    if cfg.input_kind == "tokens":
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dt)
    elif cfg.input_kind == "frames":
        x = (batch["features"].astype(dt) @
             params["frontend_proj"].astype(dt))
    elif cfg.input_kind == "mixed":
        img = (batch["image_embeds"].astype(dt) @
               params["img_proj"].astype(dt))
        tok = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dt)
        x = jnp.concatenate([img, tok], axis=1)
    else:
        raise ValueError(cfg.input_kind)
    return x * (cfg.d_model ** 0.5)


def _act_constraint(x, mesh):
    """Anchor activations batch-sharded on the data axes — without this GSPMD
    can propagate the FSDP weight shardings into batch-replicated activations
    (observed: 16x activation blow-up on prefill)."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if n_dp <= 1 or x.shape[0] % n_dp:
        return x
    spec = P(dp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def forward(params, batch, cfg, *, mesh=None, last_only: bool = False,
            with_stats: bool = False):
    """Full-sequence forward (training / prefill).  Returns (logits, aux) —
    plus a stats dict (``moe_overflow``: layer-summed ``ep_a2a`` routing
    overflow fraction) when ``with_stats=True``.  ``last_only`` emits logits
    for the final position only (prefill)."""
    x = _embed_inputs(params, batch, cfg)
    B, S, _ = x.shape
    positions = jnp.arange(S)
    x = _act_constraint(x, mesh)

    # Resolve the checkpoint plan (cfg.remat_policy: registry name or spec)
    # and pick how to apply it: one group-level jax.checkpoint when the
    # decisions are uniform across the pattern's kinds (bit-identical to the
    # legacy string path for named plans), per-sublayer policies when the
    # plan scopes a shared tag differently per block kind.
    plan = CK.resolve_plan(config=cfg.remat_policy).plan
    mode, payload = CK.plan_policies(plan, cfg.block_pattern)
    sub_policies = payload if mode == "per_kind" else None

    def group_fn(carry, gp):
        x, aux, ov = carry
        x, a, o, _ = _apply_group(x, gp, cfg, mesh=mesh, positions=positions,
                                  cache_group=None,
                                  sub_policies=sub_policies)
        return (_act_constraint(x, mesh), aux + a, ov + o), None

    if mode == "group":
        # prevent_cse: a one-group scan is unrolled, and XLA would otherwise
        # share values between the forward and the recompute, keeping them
        # (the GEMMs' bfloat16 weight copies among them) live across the loss.
        group_fn = jax.checkpoint(group_fn, policy=payload, prevent_cse=True)

    zero = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:
        (x, aux, ov), _ = jax.lax.scan(group_fn, (x, zero, zero),
                                       params["layers"])
    else:
        aux, ov = zero, zero
        for i in range(cfg.num_groups):
            gp = jax.tree.map(lambda l: l[i], params["layers"])
            (x, aux, ov), _ = group_fn((x, aux, ov), gp)

    with jax.named_scope("unembed_loss"):
        if last_only:
            x = x[:, -1:]
        x = rms_norm(x, params["final_norm"])
        logits = x @ params["unembed"].astype(x.dtype)
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if with_stats:
        return logits, aux, {"moe_overflow": ov}
    return logits, aux


def init_cache(cfg, batch: int, capacity: int):
    """Decode cache pytree, stacked over layer groups."""
    dt = jnp.dtype(cfg.dtype)
    dh = cfg.resolved_head_dim

    def sub_cache(kind):
        if kind in ATTN_KINDS:
            cap = capacity
            if "local" in kind and cfg.sliding_window:
                cap = min(cfg.sliding_window, capacity)
            return (init_kv_cache(batch, cap, cfg.num_kv_heads, dh, dt),)
        if kind == "mlstm":
            H = cfg.num_heads
            dhh = 2 * cfg.d_model // H
            return ((jnp.zeros((batch, H, dhh, dhh), jnp.float32),
                     jnp.zeros((batch, H, dhh), jnp.float32),
                     jnp.full((batch, H), -1e30, jnp.float32)),)
        if kind == "slstm":
            d = cfg.d_model
            return ((jnp.zeros((batch, d), jnp.float32),
                     jnp.zeros((batch, d), jnp.float32),
                     jnp.full((batch, d), -1e30, jnp.float32)),)
        if kind == "hymba":
            cap = min(cfg.sliding_window, capacity) if cfg.sliding_window \
                else capacity
            return (init_kv_cache(batch, cap, cfg.num_kv_heads, dh, dt),
                    jnp.zeros((batch, cfg.ssm_heads, dh, cfg.ssm_state),
                              jnp.float32))
        raise ValueError(kind)

    one_group = tuple(sub_cache(k) for k in cfg.block_pattern)
    return jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (cfg.num_groups,) + l.shape),
        one_group)


def decode_step(params, cache, batch, pos, cfg, *, mesh=None):
    """One-token decode.  batch['tokens']: (B, 1); pos: scalar absolute
    position.  Returns (logits (B, vocab), new_cache)."""
    dt = jnp.dtype(cfg.dtype)
    if cfg.input_kind == "frames":
        raise ValueError("encoder-only architectures do not decode")
    x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dt)
    x = x * (cfg.d_model ** 0.5)
    positions = jnp.full((1,), pos)

    def group_fn(x, scan_in):
        gp, cache_group = scan_in
        x, _, _, nc = _apply_group(x, gp, cfg, mesh=mesh, positions=positions,
                                   cache_group=cache_group)
        return x, nc

    if cfg.scan_layers:
        x, new_cache = jax.lax.scan(group_fn, x, (params["layers"], cache))
    else:
        ncs = []
        for i in range(cfg.num_groups):
            gp = jax.tree.map(lambda l: l[i], params["layers"])
            cg = jax.tree.map(lambda l: l[i], cache)
            x, nc = group_fn(x, (gp, cg))
            ncs.append(nc)
        new_cache = jax.tree.map(lambda *ls: jnp.stack(ls), *ncs)

    x = rms_norm(x, params["final_norm"])
    logits = x[:, 0] @ params["unembed"].astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged serving entry points (block-paged KV cache; see serve/paged_cache)
# ---------------------------------------------------------------------------


class PagedCtx(NamedTuple):
    """Static+dynamic context threaded to the paged attention sublayers.
    ``prefill`` is a Python bool (trace-static): it selects the whole-prompt
    scatter+flash path vs the single-token append+gather path.
    ``offsets`` (``(B,)``, prefill only) switches prefill to the
    prefix-sharing suffix path: tokens scatter at ``offsets[b] + t`` and
    attention gathers cached pages instead of running flash on in-flight
    k/v.  ``attn_impl`` (trace-static str) picks the registered decode
    attention implementation (``dense`` | ``pallas``)."""

    page_table: jax.Array       # (B, pages_per_seq) int32 physical pages
    prefill: bool
    offsets: jax.Array | None = None
    attn_impl: str = "dense"


def paged_supported(cfg) -> bool:
    """Paged serving covers attention block patterns (SSM carries are O(1)
    per-slot state — nothing to page)."""
    return all(k in ATTN_KINDS for k in cfg.block_pattern)


def init_paged_cache(cfg, num_pages: int, page_size: int, *,
                     quantized: bool = False):
    """Paged decode cache: per attention sublayer one
    :class:`~repro.serve.paged_cache.PagedKV` pool of ``num_pages`` pages
    (physical page 0 reserved as the trash page), stacked over layer groups
    like :func:`init_cache`.  ``quantized`` stores int8 values + f16
    per-(position, head) scales — the ``serve/kv_quant`` scheme applied at
    append time."""
    from repro.serve.paged_cache import init_paged_kv
    if not paged_supported(cfg):
        raise ValueError(
            f"paged serving needs an attention block pattern; "
            f"{cfg.name} has {cfg.block_pattern} (use T.decode_step)")
    dt = jnp.dtype(cfg.dtype)
    one_group = tuple(
        (init_paged_kv(num_pages, page_size, cfg.num_kv_heads,
                       cfg.resolved_head_dim, dt, quantized=quantized),)
        for _ in cfg.block_pattern)
    return jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (cfg.num_groups,) + l.shape),
        one_group)


def prefill(params, tokens, lengths, cache, page_table, cfg, *, mesh=None,
            offsets=None, attn_impl: str = "dense"):
    """Whole-prompt forward that fills the paged cache in ONE call.

    tokens: (B, S) right-padded prompts; lengths: (B,) true prompt lengths;
    page_table: (B, pages_per_seq).  Every position 0..S-1 is written
    through the page table (padded tails land on the trash page or in slots
    the request will overwrite during decode — both unobservable, because
    attention masks by per-request prefix length), and attention over the
    prompt itself is causal flash on the in-flight k/v.  Returns
    ``(logits (B, vocab) at each request's last prompt token, new_cache)``.

    With ``offsets`` ``(B,)`` (prefix sharing), ``tokens`` holds only each
    request's unshared SUFFIX (``lengths`` = suffix lengths): rows write at
    absolute ``offsets[b] + t`` and attend through the page table, reading
    the shared prefix KV from cache instead of recomputing it.  The logits
    row is still each request's last real token (relative index
    ``lengths - 1``)."""
    dt = jnp.dtype(cfg.dtype)
    if cfg.input_kind != "tokens":
        raise ValueError("paged serving decodes token streams")
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    x = x * (cfg.d_model ** 0.5)
    if offsets is None:
        positions = jnp.arange(S)
    else:
        positions = offsets[:, None] + jnp.arange(S)[None, :]   # (B, S)
    paged = PagedCtx(page_table, True, offsets, attn_impl)

    def group_fn(x, scan_in):
        gp, cache_group = scan_in
        x, _, _, nc = _apply_group(x, gp, cfg, mesh=mesh, positions=positions,
                                   cache_group=cache_group, paged=paged)
        return x, nc

    if cfg.scan_layers:
        x, new_cache = jax.lax.scan(group_fn, x, (params["layers"], cache))
    else:
        ncs = []
        for i in range(cfg.num_groups):
            gp = jax.tree.map(lambda l: l[i], params["layers"])
            cg = jax.tree.map(lambda l: l[i], cache)
            x, nc = group_fn(x, (gp, cg))
            ncs.append(nc)
        new_cache = jax.tree.map(lambda *ls: jnp.stack(ls), *ncs)

    x = rms_norm(x, params["final_norm"])
    idx = jnp.clip(lengths - 1, 0, S - 1)
    x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    logits = x_last @ params["unembed"].astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, new_cache


def paged_decode_step(params, cache, tokens, lengths, page_table, cfg, *,
                      mesh=None, attn_impl: str = "dense"):
    """One decode step with every request at its OWN position.

    tokens: (B, 1) the last sampled token per request; lengths: (B,) the
    absolute position that token is written at (== the request's current
    token count).  ``attn_impl`` picks the paged-attention implementation
    (``dense`` gather or the Pallas page-walk kernel).  Returns
    (logits (B, vocab), new_cache)."""
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    x = x * (cfg.d_model ** 0.5)
    paged = PagedCtx(page_table, False, None, attn_impl)

    def group_fn(x, scan_in):
        gp, cache_group = scan_in
        x, _, _, nc = _apply_group(x, gp, cfg, mesh=mesh, positions=lengths,
                                   cache_group=cache_group, paged=paged)
        return x, nc

    if cfg.scan_layers:
        x, new_cache = jax.lax.scan(group_fn, x, (params["layers"], cache))
    else:
        ncs = []
        for i in range(cfg.num_groups):
            gp = jax.tree.map(lambda l: l[i], params["layers"])
            cg = jax.tree.map(lambda l: l[i], cache)
            x, nc = group_fn(x, (gp, cg))
            ncs.append(nc)
        new_cache = jax.tree.map(lambda *ls: jnp.stack(ls), *ncs)

    x = rms_norm(x, params["final_norm"])
    logits = x[:, 0] @ params["unembed"].astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def train_loss(params, batch, cfg, *, mesh=None):
    logits, aux, stats = forward(params, batch, cfg, mesh=mesh,
                                 with_stats=True)
    with jax.named_scope("unembed_loss"):
        labels = batch["labels"]
        if cfg.input_kind == "mixed":
            # image positions carry no next-token loss
            n_img = batch["image_embeds"].shape[1]
            logits = logits[:, n_img:]
        if cfg.causal:
            logits = logits[:, :-1]
            labels = labels[:, 1:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return loss + aux, {"ce": loss, "aux": aux,
                            "moe_overflow": stats["moe_overflow"]}
