"""Helpers the metric readers (``bench/metrics/<name>.py``) share. A run
record is a dict; see ``serve.run`` and ``train.run`` for its keys."""
from __future__ import annotations

import collections

from bench import core, seeded
from bench import trace as tr


def served(run: dict) -> list[dict]:
    """Requests due in the window that were answered."""
    return [r for r in run["requests"] if r["ok"]]


def ttfts(run: dict) -> list[float]:
    return [r["first"] - r["due"] for r in served(run)]


def itl_gaps(run: dict) -> list[float]:
    return [b - a for r in served(run)
            for a, b in zip(r["times"], r["times"][1:])]


def counts(run: dict):
    return core.module("roofline", run["config"]["family"], run["root"])


def causal_pairs(pos: int, n: int, window: int | None = None) -> int:
    """(query, key) pairs of n causal queries at positions pos .. pos+n-1,
    each reading at most ``window`` keys (itself included) where a window is
    given, as the program masks it (key > query - window)."""
    if window is None:
        return n * pos + n * (n + 1) // 2
    # the query at q reads min(q + 1, window) keys; j = q + 1 runs over
    # pos+1 .. pos+n: j itself up to the window, the window past it
    top = min(pos + n, window)
    inside = top * (top + 1) // 2 - pos * (pos + 1) // 2 if top > pos else 0
    return inside + window * max(0, pos + n - max(window, pos))


def per_call_ms(run: dict, fn: str) -> float | None:
    t = run.get("trace") and tr.program_time(run["trace"], fn)
    return 1e3 * t[0] / t[1] if t else None


def share(useful_s: float, spent_s: float | None) -> float | None:
    """A share of a peak in %, or None where there was nothing to time."""
    if not spent_s or useful_s <= 0:
        return None
    return 100.0 * useful_s / spent_s


def traced_ticks(run: dict) -> list[dict]:
    return run.get("ticks") or []


def dims(run: dict) -> dict:
    return seeded.dims(run["config"])


def windows(run: dict) -> collections.Counter:
    """How many layers read each window of keys (None: the whole context),
    from the family's counts (``layer_windows``)."""
    return collections.Counter(counts(run).layer_windows(run["config"]))


def least_s(run: dict, cost, rows: list, layers: collections.Counter) -> float:
    """Least time of a kernel called once per layer on ``rows``: each
    layer's max(operations / peak FLOP/s, bytes / peak bandwidth) with its
    window (``layers``, from ``windows``), added up over the layers."""
    m, pk = dims(run), run["peak"]
    return sum(n * max(f / pk["bf16_flops"], b / pk["hbm_bw"])
               for w, n in layers.items()
               for f, b in [cost(rows, m["H"], m["K"], m["Dh"], window=w)])


def idle_share(run: dict) -> float | None:
    t = run.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
