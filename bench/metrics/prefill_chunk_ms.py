"""Device time per run of the chunk-round program (``ServeEngine._chunk_fn``)."""
from bench import readers


def read(run):
    return readers.per_call_ms(run, "_chunk_fn")
