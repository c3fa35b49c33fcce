"""99th percentile of every gap between consecutive output tokens of every
request due in the window, as the host sees the tick that made each return.
(The 99th and not the 95th: at these loads a few per cent of the gaps span a
chunk round, so the 95th percentile falls on one mode or the other from run
to run; the 99th always lies in the chunk-round mode.)"""
from bench import core, readers


def read(run):
    return core.pct(readers.itl_gaps(run), 99) if run["kind"] == "serve" else None
