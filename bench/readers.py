"""Helpers the metric readers (``bench/metrics/<name>.py``) share. A run
record is a dict; see ``serve.run`` and ``train.run`` for its keys."""
from __future__ import annotations

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


def idle_share(run: dict) -> float | None:
    t = run.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
