"""Fault-tolerant FTaaS demo: collaborative training over an unreliable
offload transport, with per-user quarantine and validated hot-swaps into the
serving engine.

Two users fine-tune one merged base model (paper §3.2). User 1's channel is
deliberately terrible — payloads get dropped, delayed and NaN-poisoned — while
user 0's is clean. The `OffloadChannel` retries/dedups transit faults,
validates every returned adapter bank, rolls back bad rounds and quarantines
the user if rounds keep failing; `publish_banks` then installs only validated
version bumps into the `ServeEngine` (stale/quarantined users keep serving
their last-good adapters).

    PYTHONPATH=src python examples/chaos_train.py
    PYTHONPATH=src python examples/chaos_train.py --fault nan --rate 1.0
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.configs.base import ColaConfig
from repro.core.collab import CollabSession
from repro.data.pipeline import SyntheticLM
from repro.models import model as M
from repro.optim import optimizers as opt
from repro.runtime.faults import FaultInjector, FaultProfile, RetryPolicy
from repro.runtime.serve_loop import Request, ServeEngine, publish_banks
from repro.utils import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", default="mixed",
                    choices=["drop", "delay", "corrupt", "duplicate", "nan",
                             "mixed"])
    ap.add_argument("--rate", type=float, default=0.4)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.fault == "mixed":
        profile = FaultProfile(drop=args.rate / 2, delay=args.rate / 2,
                               delay_ticks=1, nan=args.rate / 2)
    else:
        profile = FaultProfile(**{args.fault: args.rate})
    injector = FaultInjector({1: profile}, seed=args.seed)
    policy = RetryPolicy(max_attempts=6, timeout_ticks=2,
                         backoff_base=1e-4, sleep=lambda s: None)

    cfg = registry.reduced_config("smollm-135m").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, vocab_size=128)
    key = jax.random.PRNGKey(0)
    params = M.init(cfg, key)
    cc = ColaConfig(mode="faithful_offload", family="lowrank", taps="qv",
                    rank=4, merged=True, users=2)
    collab = CollabSession(cfg, cc, params, key, optimizer=opt.sgd(0.1),
                           injector=injector, policy=policy)
    data = SyntheticLM(cfg, batch=4, seq=16, seed=2, users=2)

    print(f"user 1 fault profile: {args.fault} @ {args.rate}  "
          f"(user 0 clean)\n")
    for t in range(args.steps):
        b = data.batch_at(t)
        uid = jnp.asarray(b.pop("user_id"))
        loss = collab.train_step({k: jnp.asarray(v) for k, v in b.items()},
                                 uid)
        print(f"step {t:2d}  loss {loss:.4f}  "
              f"bank versions {collab.bank_versions()}")

    print("\nchannel health:")
    for k, h in collab.channel_health().items():
        flags = " QUARANTINED" if h["quarantined"] else ""
        print(f"  user {k}: v{h['version']}  retries={h['send_retries']} "
              f"rollbacks={h['rollbacks']} dead_letters={h['dead_letter_count']}"
              f"{flags}")
    print(f"injected faults: {injector.injected}")

    # train -> serve hot-swap: only validated version bumps install
    init_banks = [jax.tree.map(np.asarray, ch.last_good)
                  for ch in collab.channels]
    eng = ServeEngine(cfg, params, slots=2, max_len=64,
                      user_adapters=init_banks)
    eng.bank_versions[:] = 0
    n = publish_banks(eng, collab.channels)
    print(f"\nserve engine: installed {n} validated bank(s); "
          f"versions now {eng.bank_versions.tolist()} "
          f"(rejected: {eng.stats['bank_rejected']})")
    for user in range(2):
        req = Request(rid=user, user=user,
                      prompt=np.arange(8) % cfg.vocab_size, max_new=8)
        eng.submit(req)
    eng.run_until_idle()
    for req in eng.finished:
        print(f"user {req.user} -> {req.out}  ({req.status})")


if __name__ == "__main__":
    main()
