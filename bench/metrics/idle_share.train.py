"""Share of the traced training window in which no operation ran on the
device, in %."""
from bench import readers


def read(run):
    return readers.idle_share(run) if run["kind"] == "train" else None
