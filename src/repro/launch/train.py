"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1_1b \
        --steps 200 --batch 8 --seq 256 --reduced --elitekv --ckpt-dir /tmp/ck

On this CPU container use ``--reduced`` (tiny same-family config); on a real
TPU slice drop it and point ``--mesh`` at the production mesh.  The loop is
fault-tolerant: checkpoints are committed atomically and a restart resumes
from the newest committed step with a deterministic data stream.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config
from repro.core.convert import pick_dims
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch import compile_cache
from repro.models import lm
from repro.optim import schedule as sched_lib
from repro.optim.adamw import AdamWConfig
from repro.runtime import train_loop


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1_1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="constant", choices=["constant", "cosine", "wsd"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--elitekv", action="store_true")
    ap.add_argument("--cache-ratio", type=float, default=0.25)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--moe-impl", default="ragged")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.elitekv and cfg.n_attn_layers:
        cfg = dataclasses.replace(cfg, elitekv=pick_dims(cfg, args.cache_ratio, align=16))

    key = jax.random.PRNGKey(args.seed)
    params, buffers = lm.init(key, cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"elitekv={cfg.elitekv.enabled} "
          f"cache/token/layer={cfg.elitekv.cache_per_token_per_layer(cfg.n_kv_heads, cfg.head_dim)}")

    if args.schedule == "constant":
        sched = sched_lib.constant(args.lr)
    elif args.schedule == "cosine":
        sched = sched_lib.cosine(args.lr, warmup=args.steps // 20 + 1, total=args.steps)
    else:
        sched = sched_lib.wsd(args.lr, warmup=args.steps // 20 + 1,
                              stable=args.steps // 2, decay=args.steps // 3 + 1)

    tc = train_loop.TrainConfig(
        optimizer=AdamWConfig(), lr=args.lr, schedule=sched,
        grad_accum=args.grad_accum, moe_impl=args.moe_impl)
    data = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                    batch_size=args.batch, seed=args.seed))
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None

    t0 = time.time()

    def cb(step, metrics):
        if step % args.log_every == 0:
            print(f"step {step:5d}  loss {float(metrics['loss']):.4f}  "
                  f"lr {float(metrics['lr']):.2e}  gnorm {float(metrics['grad_norm']):.2f}  "
                  f"({time.time() - t0:.0f}s)", flush=True)

    params, opt_state, history = train_loop.train(
        params, buffers, cfg, tc, iter(data), args.steps,
        checkpointer=ckpt, ckpt_every=args.ckpt_every, callback=cb)
    print(f"final loss: {history[-1][1]:.4f}  ({args.steps} steps, "
          f"{time.time() - t0:.0f}s)")
    return history


if __name__ == "__main__":
    compile_cache.enable()
    main()
