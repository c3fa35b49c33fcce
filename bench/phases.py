"""What the readers of the program's own trace share: the engine's tick
phases (``serve.*`` spans the program writes through ``telemetry.span``
while profiler annotations are armed) and the Pallas kernels' names (each
kernel's ``pl.pallas_call(..., metadata={"kernel": <name>})``, carried in
the ``kernel_metadata`` attribute of its op in the trace).

A program without these spans or names (one older than them) gives the
readers nothing to read: each returns None."""
from __future__ import annotations

import re

from bench import trace as tr

TICK = "serve.tick"


def ticks(run: dict) -> int | None:
    """Engine ticks inside the traced window."""
    t = run.get("trace")
    s = t and t["spans"].get(TICK)
    return s["count"] if s and s["count"] else None


def span_ms_per_tick(run: dict, name: str) -> float | None:
    """Wall of the ``name`` spans inside the window, per engine tick."""
    n = ticks(run)
    s = n and run["trace"]["spans"].get(name)
    return 1e3 * s["total_s"] / n if s else None


def kernel(name: str) -> str:
    """The ops of the Pallas kernel named ``name``: custom calls only (the
    layout copies beside a kernel carry its ``kernel_metadata`` too)."""
    return (r' custom-call\(.*kernel_metadata=\{\s*\\?"kernel\\?":\s*\\?"'
            + re.escape(name) + r'\\?"\s*\}')


def kernel_ms_per_tick(run: dict, name: str) -> float | None:
    """Device time of the kernel named ``name``, per engine tick."""
    n = ticks(run)
    hit = n and tr.op_time(run["trace"], kernel(name))
    return 1e3 * hit[0] / n if hit else None
