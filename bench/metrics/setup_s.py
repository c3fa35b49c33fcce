"""Process start to the first timed step: making the weights, building the
engine or session, compiling (or loading from the cache) and warming up."""


def read(run):
    return run["setup_s"]
