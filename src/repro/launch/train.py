"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 100 --batch 8 --seq 128 [--reduced] [--mesh data,model] \
        [--checkpoint-dir ckpt] [--resume]

On a real TPU slice this runs under `jax.distributed.initialize()` (one
process per host); on CPU it runs single-device (use --reduced).  The loop is
the fault-tolerant one from repro/train/elastic.py: async checkpoints,
crash-restart, straggler-tolerant prefetch.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, reduce_config
from repro.distributed import sharding as shd
from repro.distributed.ctx import TRAIN_RULES_1POD, dp_rules, use_sharding
from repro.launch.mesh import auto_mesh
from repro.models import zoo
from repro.train.checkpoint import CheckpointManager
from repro.train.data import PrefetchPipeline, synthetic_token_batches
from repro.train.elastic import LoopConfig, recoverable_train_loop
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size config (CPU)")
    ap.add_argument("--mesh", default="", help="e.g. 2,4 for (data,model)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    print(f"arch={cfg.name} params={cfg.param_count() / 1e6:.1f}M "
          f"devices={jax.device_count()}")

    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        mesh = auto_mesh(dims, ("data", "model")[: len(dims)])

    params = zoo.init_model(cfg, jax.random.key(0))
    opt = init_opt_state(params)
    if mesh is not None:
        mode = shd.choose_policy(cfg, mesh, "train")
        p_shard = shd.param_shardings(params, cfg, mesh, mode=mode)
        params = jax.device_put(params, p_shard)
        opt = jax.device_put(opt, {
            "m": p_shard, "v": p_shard, "master": p_shard,
            "step": jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())}
            if "master" in opt else
            {"m": p_shard, "v": p_shard,
             "step": jax.sharding.NamedSharding(
                 mesh, jax.sharding.PartitionSpec())})
        rules = (dp_rules(tuple(mesh.axis_names)) if mode == "dp_train"
                 else TRAIN_RULES_1POD)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                          total_steps=args.steps)
    raw = make_train_step(cfg, opt_cfg, microbatches=args.microbatches)

    def jit_step():
        if mesh is None:
            return jax.jit(raw)
        return jax.jit(raw)

    step = jit_step()

    def step_fn(state, batch):
        params, opt = state
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if mesh is not None:
            batch = jax.device_put(batch, shd.batch_shardings(batch, mesh))
            with use_sharding(rules, mesh):
                params, opt, metrics = step(params, opt, batch)
        else:
            params, opt, metrics = step(params, opt, batch)
        return (params, opt), metrics

    pipe = PrefetchPipeline(
        synthetic_token_batches(cfg.vocab, args.batch, args.seq,
                                n_batches=args.steps * 2),
        depth=4, deadline_s=10.0)

    import tempfile

    ckdir = args.checkpoint_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    ckpt = CheckpointManager(ckdir, keep=2)
    state = (params, opt)
    start = 0
    if args.resume and ckpt.latest_step() is not None:
        state, extra = ckpt.restore(state)
        start = extra.get("step", 0)
        print(f"resumed from step {start}")

    def on_metrics(s, m):
        if s % 10 == 0 or s == args.steps:
            print(f"step {s:5d} loss {float(m['loss']):.4f} "
                  f"gnorm {float(m.get('grad_norm', 0)):.2f}", flush=True)

    state, steps, restarts = recoverable_train_loop(
        state, pipe, step_fn, ckpt=ckpt,
        cfg=LoopConfig(total_steps=args.steps,
                       checkpoint_every=args.checkpoint_every),
        start_step=start, on_metrics=on_metrics)
    print(f"done: {steps} steps, restarts={restarts}, checkpoints in {ckdir}")


if __name__ == "__main__":
    main()
