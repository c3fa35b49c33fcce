"""FTaaS serving: one frozen base model, K users' adapters, continuous
batching with per-request adapter routing (the multi_lora kernel's job).

    PYTHONPATH=src python examples/serve_multi_user.py
"""
import jax
import numpy as np

from repro.configs import registry
from repro.configs.base import ColaConfig
from repro.core import gl
from repro.core.session import ColaSession
from repro.data.pipeline import SyntheticLM
from repro.models import model as M
from repro.optim import optimizers as opt
from repro.runtime.serve_loop import Request, ServeEngine
from repro.utils import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = registry.reduced_config("smollm-135m").replace(n_layers=2)
    key = jax.random.PRNGKey(0)
    params = M.init(cfg, key)

    # fine-tune two users' adapters on different data (FTaaS training half)
    banks = []
    for user in range(2):
        cc = ColaConfig(mode="faithful_offload", family="lowrank", rank=8,
                        taps="qv", merged=True)
        sess = ColaSession(cfg, cc, params, jax.random.fold_in(key, user),
                           optimizer=opt.adamw(3e-3))
        data = SyntheticLM(cfg, batch=8, seq=64, seed=100 + user)
        for t in range(10):
            sess.step(data.batch_at(t))
        banks.append(sess.adapters)
        print(f"user {user}: trained adapter bank")

    # serving half: both users share one engine + one base model. Admission
    # drains waiting requests into free slots and prefills them as one padded
    # batch (submit -> admit -> batched prefill -> decode ticks).
    eng = ServeEngine(cfg, params, slots=4, max_len=128, user_adapters=banks)
    rng = np.random.default_rng(0)
    for rid in range(6):
        eng.submit(Request(rid=rid, user=rid % 2,
                           prompt=rng.integers(0, cfg.vocab_size, size=32),
                           max_new=8))
    eng.run_until_idle()
    print(f"served {eng.stats['completed']} requests, "
          f"{eng.stats['tokens']} tokens in {eng.stats['ticks']} ticks, "
          f"{eng.stats['prefill_tokens']} prompt tokens in "
          f"{eng.stats['prefill_calls']} batched prefills "
          f"(continuous batching, per-token adapter routing)")
    th = eng.throughput()
    print(f"decode {th['decode_tok_per_s']:.0f} tok/s, "
          f"prefill {th['prefill_tok_per_s']:.0f} tok/s, "
          f"mean TTFT {th['mean_ttft']*1e3:.1f} ms")
    for r in eng.request_stats():
        print(f"  rid={r['rid']} user={r['user']} prompt={r['prompt_len']} "
              f"new={r['new_tokens']} ttft={r['ttft']*1e3:.1f}ms "
              f"latency={r['latency']*1e3:.1f}ms")


if __name__ == "__main__":
    main()
