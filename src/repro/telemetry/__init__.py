"""Unified telemetry for the FTaaS stack: metric registry, span tracing and
the flight recorder behind one facade.

Three pillars (ISSUE 10):

- **Metrics** (`metrics.MetricRegistry`): counters/gauges/fixed-bucket
  histograms under namespaced names (``serve.*``, ``store.*``, ``channel.*``,
  ``pager.*``, ``train.*``) with one ``snapshot()``, a JSONL streamer and a
  Prometheus text exporter. The five legacy stat dicts keep working and are
  absorbed into the registry.
- **Tracing** (``span``, `tracing.Tracer`): one span helper at every site.
  It writes a Chrome-trace-event (Perfetto-loadable) span when a tracer is
  attached — per-tick serve spans and per-user offload-round spans carrying
  the channel's seq ids in their args, read back with
  ``python -m repro.trace_summary`` — and a ``jax.profiler.TraceAnnotation``
  of the same name when ``enable_jax_annotations(True)`` armed them, so the
  spans land in the profiler's trace beside the device's ops.
- **Flight recorder** (`recorder.FlightRecorder`): bounded per-user/per-slot
  rings of recent events, frozen into postmortem files on quarantine,
  validation rollback, PagerError or a watchdog straggler.

Usage: build one ``Telemetry`` and hand it to the components you want
observed (``ServeEngine(telemetry=tm)``, ``ColaSession(telemetry=tm)``,
``TrainLoop(telemetry=tm)``, ...). Components accept ``telemetry=None``
(the default): the disabled path is one attribute check per site and MUST
stay a no-op — generated tokens are bit-identical telemetry-on vs. off
because telemetry only ever *reads* host-side values and never touches a
jitted computation (guarded by tests/test_telemetry.py).
"""
from __future__ import annotations

import contextlib

from repro.telemetry.metrics import (DEFAULT_TIME_BUCKETS, MetricRegistry,
                                     percentiles)
from repro.telemetry.recorder import FlightRecorder
from repro.telemetry.tracing import Tracer, validate_trace

__all__ = ["Telemetry", "MetricRegistry", "Tracer", "FlightRecorder",
           "validate_trace", "percentiles", "span", "enable_jax_annotations",
           "NULL_CONTEXT", "DEFAULT_TIME_BUCKETS"]

# one shared reusable no-op context: the entire cost of a disabled span
NULL_CONTEXT = contextlib.nullcontext()

# ``jax.profiler.TraceAnnotation`` while profiler annotations are armed
_ANNOTATION = None


def enable_jax_annotations(on: bool) -> None:
    """Arm (or disarm) profiler annotations for every ``span`` in the
    process, whether or not a ``Telemetry`` is attached to the component."""
    global _ANNOTATION
    if on:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    else:
        _ANNOTATION = None


@contextlib.contextmanager
def _nested(outer, inner):
    with outer, inner:
        yield


def span(name: str, tracer: Tracer | None = None, *, cat: str = "serve",
         tid: int = 0, **args):
    """The stack's one span. Armed profiler annotations write a
    ``jax.profiler.TraceAnnotation`` named ``name`` (on the profiler's
    clock, read back from its trace); a ``tracer`` (``Telemetry.tracer``)
    writes a Chrome-trace event with ``args`` on lane ``tid``; both when
    both are on. With neither it returns the shared ``NULL_CONTEXT`` after
    two checks."""
    ann = _ANNOTATION
    if ann is None:
        if tracer is None:
            return NULL_CONTEXT
        return tracer.span(name, cat=cat, tid=tid, **args)
    if tracer is None:
        return ann(name)
    return _nested(ann(name), tracer.span(name, cat=cat, tid=tid, **args))


class Telemetry:
    """Facade tying the registry, tracer and flight recorder together.

    Parameters
    ----------
    enabled           : master switch. ``Telemetry(enabled=False)`` is
                        indistinguishable from passing ``telemetry=None``.
    trace             : collect Chrome-trace spans (off by default — spans
                        accumulate in memory until ``export_trace``).
    recorder_capacity : events retained per flight-recorder key.
    out_dir           : where postmortem files land (None = in-memory only).

    Profiler annotations are armed for the whole process, not per facade:
    ``enable_jax_annotations(True)``.
    """

    def __init__(self, *, enabled: bool = True, trace: bool = False,
                 recorder_capacity: int = 64, out_dir: str | None = None):
        self.enabled = bool(enabled)
        self.registry = MetricRegistry(enabled=self.enabled)
        self.tracer = Tracer() if (self.enabled and trace) else None
        self.recorder = (FlightRecorder(capacity=recorder_capacity,
                                        out_dir=out_dir)
                         if self.enabled else None)

    def __bool__(self) -> bool:
        return self.enabled

    # -- tracing -----------------------------------------------------------
    def span(self, name: str, cat: str = "serve", tid: int = 0, **args):
        return span(name, self.tracer, cat=cat, tid=tid, **args)

    def name_thread(self, tid: int, name: str) -> None:
        if self.tracer is not None:
            self.tracer.name_thread(tid, name)

    def export_trace(self, path: str) -> str | None:
        return self.tracer.export(path) if self.tracer is not None else None

    # -- flight recorder ---------------------------------------------------
    def record(self, scope: str, key, kind: str, **fields) -> None:
        if self.recorder is not None:
            self.recorder.record(scope, key, kind, **fields)

    def dump(self, scope: str, key, reason: str) -> dict | None:
        if self.recorder is not None:
            return self.recorder.dump(scope, key, reason)
        return None

    # -- metrics -----------------------------------------------------------
    def snapshot(self) -> dict:
        return self.registry.snapshot()
