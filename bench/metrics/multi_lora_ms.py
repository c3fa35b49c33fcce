"""Device time of the ``multi_lora`` Pallas kernel (the adapters' q and v
projections, plain and grouped dispatch) per engine tick, found by the name
the kernel carries in the trace."""
from bench import phases


def read(run):
    return phases.kernel_ms_per_tick(run, "multi_lora")
