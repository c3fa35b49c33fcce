"""Launch-shaped example: build the production mesh, shard a full assigned
architecture, and run the ColA train step (on the 512 fake host devices —
the same code path a real TPU pod launch uses, minus the hardware).

    PYTHONPATH=src python examples/multipod_launch.py --arch smollm-135m
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=64")

import argparse

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import ColaConfig
from repro.core import gl
from repro.distributed import sharding as sh
from repro.distributed import steps
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.utils import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--data", type=int, default=4)
    ap.add_argument("--model", type=int, default=4)
    ap.add_argument("--pods", type=int, default=2)
    args = ap.parse_args()

    mesh = make_mesh(args.data, args.model, pods=args.pods)
    print(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} devices")

    cfg = registry.reduced_config(args.arch)
    cc = ColaConfig(mode="fused_fit", family="lowrank", rank=8, taps="qv")
    key = jax.random.PRNGKey(0)
    params = M.init(cfg, key)
    adapters = gl.init_adapters(cfg, cc, key)
    B, S = args.pods * args.data * 2, 64
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}

    with mesh:
        fn, (ps, ash, _), _ = steps.make_train_step(cfg, cc, mesh)
        bs = sh.batch_shardings(mesh, jax.eval_shape(lambda: batch))
        jitted = jax.jit(fn, in_shardings=(ps, ash, bs))
        params = jax.device_put(params, ps)
        adapters = jax.device_put(adapters, ash)
        from repro.optim import optimizers as opt
        optimizer = opt.sgd(0.1)
        opt_state = optimizer.init(adapters)
        for step in range(3):
            loss, grads = jitted(params, adapters, batch)
            updates, opt_state = optimizer.update(grads, opt_state, adapters)
            adapters = opt.apply_updates(adapters, updates)
            print(f"step {step}: loss={float(loss):.4f} "
                  f"(grads sharded: "
                  f"{jax.tree.leaves(grads)[0].sharding.spec})")


if __name__ == "__main__":
    main()
