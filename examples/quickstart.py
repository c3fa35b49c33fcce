"""Quickstart: fine-tune a small LM with ColA (Gradient Learning) in 30 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.configs import registry
from repro.configs.base import ColaConfig
from repro.core.session import ColaSession
from repro.data.pipeline import SyntheticLM
from repro.models import model as M
from repro.optim import optimizers as opt
from repro.utils import enable_compile_cache


def main():
    enable_compile_cache()
    # a reduced smollm-family model that trains on CPU in seconds
    cfg = registry.reduced_config("smollm-135m").replace(n_layers=2)
    key = jax.random.PRNGKey(0)
    params = M.init(cfg, key)

    # ColA, paper-faithful: merged server pass + offloaded quadratic fit
    cc = ColaConfig(mode="faithful_offload", family="lowrank", rank=8,
                    taps="qv", merged=True, interval=2)
    session = ColaSession(cfg, cc, params, key, optimizer=opt.adamw(3e-3))

    data = SyntheticLM(cfg, batch=8, seq=64, seed=0)
    for step in range(30):
        loss = session.step(data.batch_at(step))
        if step % 5 == 0:
            print(f"step {step:3d}  loss {loss:.4f}")

    print("\nadapters live on the offload device; server held only the "
          "frozen (merged) base model — paper Table 1, ColA (merged) row.")
    merged = session.inference_params()
    logits, _ = M.forward(cfg, merged, data.batch_at(999))
    print("merged-for-inference logits:", logits.shape)


if __name__ == "__main__":
    main()
