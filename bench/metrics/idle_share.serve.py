"""Share of the traced serving window in which no operation ran on the
device, in %."""
from bench import readers


def read(run):
    return readers.idle_share(run) if run["kind"] == "serve" else None
