"""Serving launcher: loads (or initializes) a model and serves batched
greedy-decode requests through the engine — synchronously, or through the
pipelined async runtime with live token streaming.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --reduced \
        --prompts 4 --max-new 16 [--ckpt path] \
        [--stream] [--prefix-cache] [--paged-kernel dense|pallas] [--out f]

Every run emits a JSON run record (stdout, or appended JSONL via ``--out``)
stamping the RESOLVED choices — grouped-GEMM backend, paged-attention
kernel (name + where it was decided), prefix cache, streaming mode — plus
the engine stats, so a perf number can always be traced back to exactly
what served it.
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.cache import use_compile_cache
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine
from repro.train.checkpointing import restore_checkpoint


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kv-dtype", default=None, choices=["int8", "model"],
                    help="int8: quantized paged KV pool (~2x fewer bytes)")
    ap.add_argument("--stream", action="store_true",
                    help="serve through the pipelined async runtime "
                         "(serve.runtime) and print tokens as they emit")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable copy-on-write prefix sharing: full prompt "
                         "pages of finished requests are cached and mapped "
                         "read-only by later page-aligned-prefix matches")
    ap.add_argument("--paged-kernel", default=None,
                    choices=["dense", "pallas"],
                    help="paged-attention decode implementation (default: "
                         "REPRO_PAGED_ATTN env, else the dense jnp gather; "
                         "pallas walks the page table in-kernel)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--out", default=None, help="append the JSON run record "
                                                "here instead of stdout")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if not cfg.causal or cfg.input_kind == "frames":
        raise SystemExit(f"{cfg.name} is encoder-only; nothing to decode")

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    if args.ckpt:
        _, params = restore_checkpoint(args.ckpt, params)

    eng = ServeEngine(cfg, params, batch_slots=args.prompts,
                      capacity=args.capacity, page_size=args.page_size,
                      kv_dtype=args.kv_dtype,
                      prefix_cache=args.prefix_cache,
                      paged_kernel=args.paged_kernel)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(
        3, cfg.vocab_size, size=int(rng.integers(2, 9))).astype(np.int32),
        max_new_tokens=args.max_new) for _ in range(args.prompts)]

    if args.stream:
        from repro.serve.runtime import AsyncServeRuntime
        for i, r in enumerate(reqs):
            r.on_token = (lambda tok, i=i:
                          print(f"req[{i}] token: {tok}", flush=True))
            r.on_finish = (lambda reason, i=i:
                           print(f"req[{i}] finished: {reason}", flush=True))
        with AsyncServeRuntime(eng) as rt:
            rt.run(reqs)
    else:
        eng.generate(reqs)
    for i, r in enumerate(reqs):
        print(f"req[{i}]: prompt={r.prompt.tolist()} -> {r.out_tokens} "
              f"[{r.finish_reason}]")

    rec = {
        "arch": cfg.name,
        "mode": "async-stream" if args.stream else "sync",
        "gmm_backend": eng.backend.name,
        "gmm_backend_source": eng.backend.source,
        "paged_kernel": eng.paged_attn.name,
        "paged_kernel_source": eng.paged_attn.source,
        "prefix_cache": args.prefix_cache,
        "kv_dtype": args.kv_dtype or "model",
        "capacity": args.capacity,
        "page_size": args.page_size,
        "stats": dict(eng.stats),
    }
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    else:
        print(f"run-record: {json.dumps(rec)}")


if __name__ == "__main__":
    main()
