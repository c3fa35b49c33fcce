"""End-to-end driver: train a ~100M-param LM with ColA for a few hundred steps
through the full fault-tolerant runtime (checkpointing, watchdog, metrics,
restart-resume).

    PYTHONPATH=src python examples/train_cola_lm.py --steps 300
    # kill it mid-run and re-run: it resumes from the last checkpoint.

Note: ~100M params on this CPU container is slow; --small trains a reduced
model through the identical code path (default). Pass --full for the real
smollm-135m config.
"""
import argparse
import os

import jax

from repro.configs import registry
from repro.configs.base import ColaConfig
from repro.core.session import ColaSession
from repro.data.pipeline import SyntheticLM
from repro.models import model as M
from repro.optim import optimizers as opt
from repro.optim import schedules
from repro.runtime.train_loop import TrainLoop
from repro.utils import (REPO_ROOT, enable_compile_cache, human_count,
                         tree_count)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--full", action="store_true",
                    help="use the full smollm-135m (~135M params; slow on CPU)")
    ap.add_argument("--workdir",
                    default=os.path.join(REPO_ROOT, "runs", "cola_lm"))
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mode", default="faithful_offload",
                    choices=["faithful_offload", "fused_fit", "lora", "ft"])
    args = ap.parse_args()

    if args.full:
        cfg = registry.get_config("smollm-135m").replace(
            param_dtype="float32", compute_dtype="float32", remat="none")
    else:
        cfg = registry.reduced_config("smollm-135m").replace(
            n_layers=4, d_model=256, n_heads=8, n_kv_heads=4, d_head=32,
            d_ff=512, vocab_size=4096)

    key = jax.random.PRNGKey(0)
    params = M.init(cfg, key)
    print(f"model: {cfg.name} ({human_count(tree_count(params))} params)")

    lr = schedules.linear_warmup_decay(3e-3, args.steps)
    cc = ColaConfig(mode=args.mode, family="lowrank", rank=8, taps="qv",
                    merged=(args.mode == "faithful_offload"), interval=2)
    session = ColaSession(cfg, cc, params, key, optimizer=opt.adamw(lr))
    data = SyntheticLM(cfg, batch=args.batch, seq=args.seq, seed=0)

    loop = TrainLoop(session, data, args.workdir, ckpt_every=50, log_every=10)
    stats = loop.run(args.steps, resume=True)
    print("run stats:", stats)
    print(f"metrics: {loop.metrics_path}")


if __name__ == "__main__":
    main()
