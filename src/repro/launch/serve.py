"""Serving launcher: batched prefill + decode driver.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --reduced --batch 8 --prompt-len 32 --max-new 32 [--mesh 2,2]

Uses the same serve_step the 512-chip dry-run lowers; on a mesh it applies
the TP serve shardings (KV-head replication / seq-sharded / int8 cache per
flags).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, make_serve_config, reduce_config
from repro.distributed import sharding as shd
from repro.distributed.ctx import SERVE_RULES_1POD, use_sharding
from repro.launch.mesh import auto_mesh
from repro.models import zoo
from repro.serve.serve_step import make_decode_step, make_prefill_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--mesh", default="", help="e.g. 2,2 for (data,model)")
    ap.add_argument("--kv-quant", action="store_true", help="int8 KV cache")
    ap.add_argument("--kv-shard", default="heads", choices=["heads", "seq"])
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    mesh = None
    model_axis = 1
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        mesh = auto_mesh(dims, ("data", "model")[: len(dims)])
        model_axis = mesh.shape.get("model", 1)
    cfg = make_serve_config(cfg, model_axis)
    cfg = dataclasses.replace(cfg, kv_cache_quant=args.kv_quant,
                              kv_cache_shard=args.kv_shard)
    print(f"serving {cfg.name}: kv_repeat={cfg.kv_repeat} "
          f"quant={cfg.kv_cache_quant} shard={cfg.kv_cache_shard}")

    params = zoo.init_model(cfg, jax.random.key(0))
    max_len = args.prompt_len + args.max_new + 8
    caches = zoo.init_cache(cfg, args.batch, max_len)
    if mesh is not None:
        params = jax.device_put(
            params, shd.param_shardings(params, cfg, mesh, mode="serve"))
        caches = jax.device_put(caches, shd.cache_shardings(caches, cfg, mesh))

    prefill = make_prefill_step(cfg, max_len)
    decode = make_decode_step(cfg)

    def run():
        prompts = jax.random.randint(jax.random.key(1),
                                     (args.batch, args.prompt_len), 0, cfg.vocab)
        jp = jax.jit(lambda p, b: zoo.decode_step(
            p, cfg, b, caches, cache_index=jnp.int32(0)))
        jd = jax.jit(decode, donate_argnums=(1,))
        t0 = time.time()
        logits, c = jp(params, {"tokens": prompts})
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0
        tok = jnp.argmax(logits[:, -1:], -1)
        t0 = time.time()
        for i in range(args.max_new):
            logits, c = jd(params, c, {"tokens": tok},
                           jnp.int32(args.prompt_len + i))
            tok = jnp.argmax(logits[:, -1:], -1)
        jax.block_until_ready(logits)
        t_dec = time.time() - t0
        print(f"prefill {args.batch}x{args.prompt_len} in {t_prefill:.2f}s; "
              f"decode {args.max_new} steps: "
              f"{args.batch * args.max_new / t_dec:.0f} tok/s")

    if mesh is not None:
        with use_sharding(SERVE_RULES_1POD, mesh):
            run()
    else:
        run()


if __name__ == "__main__":
    main()
