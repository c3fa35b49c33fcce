"""ColA's required operations per trained token (forward, backward through
the activations, adapter gradients; no remat recompute) times the traced
run's trained tokens per second, over the chip's peak, in %."""
from bench import readers


def read(run):
    if run["kind"] != "train" or not run["window_s"] or not run.get("peak"):
        return None
    mix = run["traffic"]
    per_tok = readers.counts(run).train_flops_per_token(
        run["config"], mix["seq"], mix["rank"])
    rate = run["window_tokens"] / run["window_s"]
    return 100.0 * per_tok * rate / run["peak"]["bf16_flops"]
