"""Device time per run of the decode program (``ServeEngine._decode_fn``)."""
from bench import readers


def read(run):
    return readers.per_call_ms(run, "_decode_fn")
