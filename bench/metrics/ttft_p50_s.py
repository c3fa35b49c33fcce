"""Median time to first token over every request due in the window, from
when it was due."""
from bench import core, readers


def read(run):
    return core.pct(readers.ttfts(run), 50) if run["kind"] == "serve" else None
