"""Host wall of the engine's ``serve.admit`` phase per engine tick
(``serve.tick``), both spans the program's own (``runtime/serve_loop.py``)."""
from bench import phases


def read(run):
    return phases.span_ms_per_tick(run, "serve.admit")
