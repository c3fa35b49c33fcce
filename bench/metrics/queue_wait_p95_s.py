"""95th percentile of the wait from a request's due time to its admission
into a slot (engine: ``Request.t_admit``)."""
from bench import core, readers


def read(run):
    if run["kind"] != "serve":
        return None
    return core.pct([r["admit"] - r["due"] for r in readers.served(run)], 95)
