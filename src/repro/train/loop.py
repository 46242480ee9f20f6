"""Training loop: jitted train step (loss -> grads -> clip -> AdamW),
metrics, periodic checkpointing.  Works single-device (examples, smoke) and
under a mesh (launch/train.py passes shardings).

Grouped-GEMM backend selection is context-scoped: ``make_train_step``
resolves once at construction (``tcfg.gmm_backend`` over ``cfg.gmm_backend``
at the config slot of ``repro.core.gmm_backend.resolve``) and bakes the
concrete name into the step — mutating ``REPRO_GMM_BACKEND`` afterwards
cannot retarget an already-made step.  ``train`` re-resolves **per step**, so
an ambient ``use_backend`` scope entered mid-run (e.g. from a ``step_hook``)
flips the very next step; steps are jitted per backend name and cached."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import checkpoint as CK
from repro.core import gmm_backend as GB
from repro.core import memsim
from repro.data.pipeline import make_batch_iterator
from repro.models import transformer as T
from repro.train import checkpointing
from repro.train.optimizer import (AdamWState, adamw_update,
                                   clip_by_global_norm, cosine_schedule,
                                   init_adamw)


def _config_backend(cfg, tcfg) -> str:
    """The config-precedence slot for the train path: the train config's
    choice wins over the model config's (more specific beats more general)."""
    if tcfg.gmm_backend not in (None, "", "auto"):
        return tcfg.gmm_backend
    return cfg.gmm_backend


def _dp_shards(mesh) -> int:
    """Data-parallel shard count of a mesh (activations are batch-sharded
    over these axes, so per-device residuals divide by it)."""
    if mesh is None:
        return 1
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return max(n, 1)


def make_train_step(cfg, tcfg, *, mesh=None, backend=None,
                    remat_policy=None, hbm_budget=None):
    """Returns ``step_fn(params, opt_state, batch) -> (params, opt, metrics)``.

    The grouped-GEMM backend is resolved HERE, once: ``backend`` (call-site)
    > active ``use_backend`` scope > ``tcfg.gmm_backend`` > ``cfg.gmm_backend``
    > env > auto.  The resolution is exposed as ``step_fn.resolved_backend``
    (a ``ResolvedBackend``) and baked into the traced config, so the step is
    immune to later environment mutation.

    The activation-checkpoint plan follows the same discipline:
    ``remat_policy`` (call-site name/spec/plan) > ``cfg.remat_policy`` >
    default, exposed as ``step_fn.resolved_plan`` (a ``ResolvedPlan``) and
    baked into the traced config as the canonical spec.  ``hbm_budget``
    (bytes, *per device*) engages :meth:`CheckpointPlan.fit` instead: the
    cheapest-recompute registry plan whose estimated residuals fit the
    budget is selected (an explicit ``remat_policy`` becomes the preferred
    candidate).  The estimate is taken at the residual set actually live on
    one device: the global batch divided by the mesh's data-parallel shards
    and by ``tcfg.num_microbatches`` (gradient accumulation bounds the live
    set to one microbatch).

    With ``tcfg.num_microbatches > 1`` the global batch is split along its
    leading axis and gradients are accumulated in f32 across a ``lax.scan``
    (gradient accumulation — bounds activation memory to one microbatch)."""
    resolved = GB.resolve(backend, config=_config_backend(cfg, tcfg))
    n_model = 1 if mesh is None else max(mesh.shape.get("model", 1), 1)
    n_node = 1 if mesh is None else max(mesh.shape.get("node", 1), 1)
    b_live = max(tcfg.batch_size // max(tcfg.num_microbatches, 1)
                 // _dp_shards(mesh), 1)
    moe_mode = None
    if cfg.is_moe:
        # Fail at construction, not at trace time inside shard_map: an
        # invalid (moe_parallel, mesh) pairing — e.g. forced 'ep' with
        # E % n_model != 0 — raises here with a clear message.  'auto'
        # resolves through the roofline cost model at the live per-shard
        # token count, so this resolution matches what moe_sublayer traces.
        # The resolved mode also feeds the budget fit / peak simulation
        # below (a2a capacity buffers only exist under the a2a modes).
        from repro.models.moe_block import resolve_moe_parallel
        moe_mode = resolve_moe_parallel(cfg, mesh, b_live * tcfg.seq_len)
    if hbm_budget is not None:
        prefer = CK.get_plan(remat_policy) if remat_policy is not None \
            else None
        resolved_plan = CK.CheckpointPlan.fit(
            cfg, b_live * tcfg.seq_len, hbm_budget, batch=b_live,
            prefer=prefer, mode=moe_mode, n_model=n_model,
            n_node=n_node).resolved
    else:
        resolved_plan = CK.resolve_plan(remat_policy,
                                        config=cfg.remat_policy)
    cfg = cfg.replace(gmm_backend=resolved.name,
                      remat_policy=resolved_plan.spec)

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: T.train_loss(p, batch, cfg, mesh=mesh),
            has_aux=True)(params)

    def accumulate(params, batch):
        M = tcfg.num_microbatches
        if M <= 1:
            return grads_of(params, batch)
        mb = jax.tree.map(
            lambda x: x.reshape(M, x.shape[0] // M, *x.shape[1:]), batch)

        def body(acc, one):
            (loss, metrics), g = grads_of(params, one)
            acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32) / M, acc, g)
            return acc, (loss, metrics)

        zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        grads, (losses, mets) = jax.lax.scan(body, zero, mb)
        return (losses.mean(),
                jax.tree.map(lambda m: m.mean(), mets)), grads

    def step_fn(params, opt_state: AdamWState, batch):
        # Pin trace-time resolution to the construction-time snapshot: an
        # ambient use_backend scope active when jit first traces this step
        # must not outrank the backend this step was made with (the scope is
        # a trace-time no-op once compiled).
        with GB.use_backend(resolved.name):
            (loss, metrics), grads = accumulate(params, batch)
            with jax.named_scope("optimizer"):
                grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
                lr = cosine_schedule(
                    opt_state.step, peak_lr=tcfg.learning_rate,
                    warmup=tcfg.warmup_steps, total=tcfg.total_steps)
                params, opt_state = adamw_update(
                    grads, opt_state, params, lr=lr, b1=tcfg.b1, b2=tcfg.b2,
                    eps=tcfg.eps, weight_decay=tcfg.weight_decay)
            metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
            return params, opt_state, metrics

    step_fn.resolved_backend = resolved
    step_fn.resolved_plan = resolved_plan
    step_fn.peak_sim_bytes = _sim_peak(cfg, tcfg, mesh, resolved_plan.plan)
    return step_fn


def _sim_peak(cfg, tcfg, mesh, plan) -> int:
    """Simulated per-device train-step peak (params + grads + AdamW state +
    the activation timeline) at the live set of one microbatch on one
    data-parallel shard — the same accounting slot the budget fit uses."""
    n_model = 1 if mesh is None else max(mesh.shape.get("model", 1), 1)
    n_node = 1 if mesh is None else max(mesh.shape.get("node", 1), 1)
    b = max(tcfg.batch_size // max(tcfg.num_microbatches, 1)
            // _dp_shards(mesh), 1)
    moe_mode = None
    if cfg.is_moe:
        from repro.models.moe_block import resolve_moe_parallel
        moe_mode = resolve_moe_parallel(cfg, mesh, b * tcfg.seq_len)
    return memsim.simulate_peak(cfg, b * tcfg.seq_len, batch=b, plan=plan,
                                mode=moe_mode, n_model=n_model,
                                n_node=n_node, base="train")


def compiled_step_memory(cfg, tcfg, *, mesh=None, backend=None) -> dict:
    """Memory/cost hook: abstractly lower + compile one train step and return
    its XLA memory analysis (no arrays allocated, no step executed).  This is
    the per-step memory axis the bench harness (``repro.bench.memory``)
    regresses against.  ``gmm_backend`` in the result is the step's resolved
    backend name — stamped from the resolution, not re-read from the env."""
    key = jax.random.PRNGKey(tcfg.seed)
    params = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
    opt_state = jax.eval_shape(init_adamw, params)
    sds = jax.ShapeDtypeStruct
    tok = sds((tcfg.batch_size, tcfg.seq_len), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    step_fn = make_train_step(cfg, tcfg, mesh=mesh, backend=backend)
    compiled = jax.jit(step_fn).lower(params, opt_state, batch).compile()
    mem = compiled.memory_analysis()
    return {
        "arg_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "out_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
        "gmm_backend": step_fn.resolved_backend.name,
        "remat_plan": step_fn.resolved_plan.spec,
        "compiled": compiled,
    }


def train(cfg, tcfg, *, mesh=None, params=None, log=print,
          batch_iterator=None, step_hook=None):
    """End-to-end training driver.  Returns (params, opt_state, history).

    ``step_hook(step, metrics)`` — if given — fires after every step with the
    raw (device) metrics plus ``step_s`` (the step's host wall time),
    ``gmm_backend`` (the step's resolved grouped-GEMM backend name),
    ``remat_plan`` (the canonical spec of the step's resolved checkpoint
    plan) and ``peak_sim_bytes`` (the simulated per-device train-step peak
    from :mod:`repro.core.memsim`); the same fields land in ``history`` so
    callers can track per-step timing and provenance without wrapping the
    loop.

    The backend is re-resolved at the top of every step: entering a
    ``use_backend`` scope between steps (e.g. inside ``step_hook``) retargets
    the next step — jitted steps are cached per backend name, so flipping
    back and forth does not recompile.

    Each step runs in ``repro.obs`` host spans: ``train`` (a step trace
    annotation) around ``train.next_batch``, ``train.to_device``,
    ``train.resolve``, ``train.dispatch``, ``train.sync``, ``train.hook``,
    ``train.log`` and ``train.checkpoint``; ``obs.note_step`` keeps each new
    step function with its argument shapes.  A step hook is called from this
    function's own frame, with ``params``, ``opt_state``, ``batch`` and
    ``step_fn`` (the ``jax.jit`` object) among its locals: a benchmark reads
    them there."""
    key = jax.random.PRNGKey(tcfg.seed)
    if params is None:
        params = T.init_params(key, cfg)
    opt_state = init_adamw(params)
    resolved_plan = CK.resolve_plan(config=cfg.remat_policy)
    peak_sim_bytes = _sim_peak(cfg, tcfg, mesh, resolved_plan.plan)
    step_fns: dict[str, object] = {}

    def step_fn_for(name: str):
        fn = step_fns.get(name)
        if fn is None:
            fn = jax.jit(make_train_step(cfg, tcfg, mesh=mesh, backend=name),
                         donate_argnums=(0, 1))
            step_fns[name] = fn
        return fn

    if batch_iterator is None:
        batch_iterator = make_batch_iterator(
            cfg.vocab_size, tcfg.seq_len, tcfg.batch_size, tcfg.seed)

    history = []
    noted = None
    t0 = time.perf_counter()
    for step in range(tcfg.total_steps):
        with obs.span("train", step_num=step):
            with obs.span("train.next_batch"):
                host_batch = next(batch_iterator)
            with obs.span("train.to_device"):
                batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
            with obs.span("train.resolve"):
                resolved = GB.resolve(None, config=_config_backend(cfg, tcfg))
                step_fn = step_fn_for(resolved.name)
            if step_fn is not noted:
                obs.note_step(step_fn, (params, opt_state, batch))
                noted = step_fn
            ts = time.perf_counter()
            # (No scope needed here: the backend is pinned at the arg slot via
            # make_train_step(backend=...) and again inside step_fn's own
            # trace-time use_backend scope.)
            with obs.span("train.dispatch"):
                params, opt_state, metrics = step_fn(params, opt_state, batch)
            if step_hook is not None:
                with obs.span("train.sync"):
                    jax.block_until_ready(metrics)
                metrics = dict(metrics, step_s=time.perf_counter() - ts,
                               gmm_backend=resolved.name,
                               remat_plan=resolved_plan.spec,
                               peak_sim_bytes=peak_sim_bytes)
                with obs.span("train.hook"):
                    step_hook(step, metrics)
            if step % tcfg.log_every == 0 or step == tcfg.total_steps - 1:
                with obs.span("train.log"):
                    m = {k: float(v) for k, v in metrics.items()
                         if not isinstance(v, str)}
                    m["step"] = step
                    m.setdefault("step_s", time.perf_counter() - ts)
                    m["wall_s"] = time.perf_counter() - t0
                    m["gmm_backend"] = resolved.name
                    m["remat_plan"] = resolved_plan.spec
                    m["peak_sim_bytes"] = peak_sim_bytes
                    history.append(m)
                    log(f"step {step:5d} loss {m['loss']:.4f} "
                        f"ce {m['ce']:.4f} gnorm {m['grad_norm']:.3f} "
                        f"lr {m['lr']:.2e} ({m['wall_s']:.1f}s)")
            if (tcfg.checkpoint_every and step
                    and step % tcfg.checkpoint_every == 0):
                with obs.span("train.checkpoint"):
                    checkpointing.save_checkpoint(
                        f"{tcfg.checkpoint_dir}/step_{step}", step, params,
                        opt_state)
    return params, opt_state, history
