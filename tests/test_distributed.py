"""Multi-device tests.  Each runs in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count set, because the main pytest
process must keep seeing 1 device (smoke tests)."""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_sub(code: str, devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_sharded_train_step_runs():
    """FSDP+TP train step on a 2x4 host mesh: runs, loss finite, params
    sharded as specified."""
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config, reduce_config
    from repro.distributed import sharding as shd
    from repro.distributed.ctx import TRAIN_RULES_1POD, use_sharding
    from repro.launch.mesh import auto_mesh
    from repro.models import zoo
    from repro.train.optimizer import init_opt_state
    from repro.train.train_step import AdamWConfig, make_train_step

    cfg = reduce_config(get_config("olmo-1b"))
    mesh = auto_mesh((2, 4), ("data", "model"))
    params = zoo.init_model(cfg, jax.random.key(0))
    p_shard = shd.param_shardings(params, cfg, mesh, mode="train")
    params = jax.device_put(params, p_shard)
    opt = init_opt_state(params)
    o_shard = {"m": p_shard, "v": p_shard, "step": NamedSharding(mesh, P())}
    opt = jax.device_put(opt, o_shard)
    batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
             "targets": jnp.zeros((8, 32), jnp.int32)}
    batch = jax.device_put(batch, shd.batch_shardings(batch, mesh))
    step = make_train_step(cfg, AdamWConfig())
    with use_sharding(TRAIN_RULES_1POD, mesh):
        jstep = jax.jit(step, in_shardings=(p_shard, o_shard,
                                            shd.batch_shardings(batch, mesh)),
                        donate_argnums=(0, 1))
        params, opt, m = jstep(params, opt, batch)
    assert np.isfinite(float(m["loss"])), m
    # spot-check a sharded leaf
    w = params["layers"]["mlp"]["gate"]["w"]
    assert len(w.sharding.device_set) == 8
    print("OK", float(m["loss"]))
    """)


def test_moe_dist_equals_local():
    run_sub("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config, reduce_config
    from repro.models.moe import init_moe, moe_apply
    from repro.distributed.ctx import ShardingRules, use_sharding

    cfg = reduce_config(get_config("qwen3-moe-30b-a3b"))
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=100.0))  # no drops: exact equality regime
    p = init_moe(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (4, 16, cfg.d_model))
    out_local = moe_apply(p, x, cfg)
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    rules = ShardingRules(rules={"batch": "data", "experts": "model"})
    with use_sharding(rules, mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        ps = jax.device_put(p, NamedSharding(mesh, P()))
        out_dist = jax.jit(lambda pp, xx: moe_apply(pp, xx, cfg))(ps, xs)
    np.testing.assert_allclose(np.asarray(out_local, np.float32),
                               np.asarray(out_dist, np.float32), atol=3e-2)
    print("OK")
    """)


def test_compressed_grad_sync_converges():
    """int8 error-feedback DP grad sync: quadratic converges ~like fp32."""
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.compression import make_dp_compressed_grad_fn

    mesh = jax.make_mesh((8,), ("data",))
    target = jnp.arange(32.0) / 32.0

    def loss_fn(params, batch):
        pred = batch @ params["w"]
        return jnp.mean((pred - batch @ target) ** 2)

    grad_fn = jax.jit(make_dp_compressed_grad_fn(loss_fn, mesh))
    params = {"w": jnp.zeros((32,))}
    residuals = {"w": jnp.zeros((32,))}
    rng = np.random.default_rng(0)
    losses = []
    for i in range(60):
        batch = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
        loss, grads, residuals = grad_fn(params, batch, residuals)
        params = jax.tree.map(lambda p, g: p - 0.3 * g, params, grads)
        losses.append(float(loss))
    assert losses[-1] < 1e-3 * losses[0], (losses[0], losses[-1])
    print("OK", losses[0], losses[-1])
    """)


def test_checkpoint_elastic_reshard():
    """Save on an 8-device mesh, restore onto a 4-device mesh (node loss)."""
    run_sub("""
    import tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.train.checkpoint import CheckpointManager

    devs = jax.devices()
    mesh8 = Mesh(np.array(devs).reshape(8), ("data",))
    mesh4 = Mesh(np.array(devs[:4]).reshape(4), ("data",))
    state = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                                 NamedSharding(mesh8, P("data", None)))}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, state)
        target = {"w": jnp.zeros((8, 8))}
        shardings = {"w": NamedSharding(mesh4, P("data", None))}
        got, _ = mgr.restore(target, shardings=shardings)
        assert len(got["w"].sharding.device_set) == 4
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.arange(64.0).reshape(8, 8))
    print("OK")
    """)


def test_decode_step_sharded():
    """TP serving decode on a host mesh with kv-head sharding + cache donation."""
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config, make_serve_config, reduce_config
    from repro.distributed import sharding as shd
    from repro.distributed.ctx import SERVE_RULES_1POD, use_sharding
    from repro.launch.mesh import auto_mesh
    from repro.models import zoo
    from repro.serve.serve_step import make_decode_step

    cfg = reduce_config(get_config("qwen2-72b"))
    mesh = auto_mesh((2, 2), ("data", "model"))
    scfg = make_serve_config(cfg, 2)
    params = zoo.init_model(scfg, jax.random.key(0))
    params = jax.device_put(params, shd.param_shardings(params, scfg, mesh,
                                                        mode="serve"))
    caches = zoo.init_cache(scfg, 4, 32)
    caches = jax.device_put(caches, shd.cache_shardings(caches, scfg, mesh))
    batch = {"tokens": jnp.zeros((4, 1), jnp.int32)}
    step = make_decode_step(scfg)
    with use_sharding(SERVE_RULES_1POD, mesh):
        jd = jax.jit(step, donate_argnums=(1,))
        logits, caches = jd(params, caches, batch, jnp.int32(3))
    assert logits.shape == (4, 1, scfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    print("OK")
    """)
