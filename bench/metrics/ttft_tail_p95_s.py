"""95th percentile of the time to first token over every request due in the
traced window, from when it was due. A per-layer reading: over a 50 s
window of bursty traffic near the knee it is set by the few requests that
queue behind the largest burst, and a stall of the host for a second or
more moves it by half, so it is not bounded end to end."""
from bench import core, readers


def read(run):
    return core.pct(readers.ttfts(run), 95) if run["kind"] == "serve" else None
