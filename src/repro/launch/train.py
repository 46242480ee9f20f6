"""Distributed training launcher.

On a TPU slice this builds the production mesh, shards params/optimizer
FSDP x TP per `repro.sharding`, and runs the training loop.  ``--mesh DxM``
lays a debug mesh over the local devices (TPU chips, or host CPU devices,
which the flag creates):

    PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b \
        --reduced --steps 20 --mesh 2x4

The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache/`` at the repository root.

    # production (TPU pod):
    python -m repro.launch.train --arch qwen3-moe-30b-a3b --production-mesh
"""

from __future__ import annotations

import argparse
import os
import sys

# Debug meshes on CPU need fake host devices; this must precede jax init.
if "--mesh" in sys.argv and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    _dm = sys.argv[sys.argv.index("--mesh") + 1]
    _n = 1
    for _t in _dm.split("x"):
        _n *= int(_t)
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp

from repro import sharding as shd
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.data.pipeline import make_batch_iterator
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import transformer as T
from repro.train import checkpointing
from repro.train.loop import make_train_step
from repro.train.optimizer import init_adamw


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default=None,
                    help="DxM debug mesh over host devices, e.g. 2x4")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainConfig(total_steps=args.steps, batch_size=args.batch,
                       seq_len=args.seq, learning_rate=args.lr,
                       num_microbatches=args.microbatches,
                       log_every=args.log_every,
                       checkpoint_every=args.steps // 2 if args.ckpt_dir else 0,
                       checkpoint_dir=args.ckpt_dir or "/tmp/repro_ckpt")

    mesh = None
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    elif args.mesh:
        d, m = (int(t) for t in args.mesh.split("x"))
        mesh = make_debug_mesh(d, m)

    params = T.init_params(jax.random.PRNGKey(tcfg.seed), cfg)
    opt = init_adamw(params)
    step_fn = make_train_step(cfg, tcfg, mesh=mesh)

    if mesh is not None:
        pspecs = shd.param_specs(params, mesh)
        shardings = shd.to_shardings(mesh, (pspecs, shd.opt_specs(pspecs)))
        params = jax.device_put(params, shardings[0])
        opt = jax.device_put(opt, shardings[1])
        step = jax.jit(step_fn, donate_argnums=(0, 1))
    else:
        step = jax.jit(step_fn, donate_argnums=(0, 1))

    it = make_batch_iterator(cfg.vocab_size, tcfg.seq_len, tcfg.batch_size,
                             tcfg.seed)
    ctx = mesh or _nullcontext()
    with ctx:
        for i in range(tcfg.total_steps):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            params, opt, metrics = step(params, opt, batch)
            if i % tcfg.log_every == 0 or i == tcfg.total_steps - 1:
                print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}")
            if tcfg.checkpoint_every and i and i % tcfg.checkpoint_every == 0:
                checkpointing.save_checkpoint(
                    f"{tcfg.checkpoint_dir}/step_{i}", i, params, opt)
    print("done")


class _nullcontext:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
