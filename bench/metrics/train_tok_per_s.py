"""Tokens trained in the window over the window's time: every step that
ran in it, and all of its time."""


def read(run):
    if run["kind"] != "train" or not run["window_s"]:
        return None
    return run["window_tokens"] / run["window_s"]
