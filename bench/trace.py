"""The profiler's trace of a run, reduced to what the per-layer metrics read.

A traced run runs ``jax.profiler`` across its window (a serving run from
before the window opens until its drain is over), marks the traced part with
the ``bench.traced`` span, and wraps each call into the program in a host
span (``jax.profiler.TraceAnnotation`` named ``bench.<what>``). The reduction reads the ``.xplane.pb`` the profiler wrote:

- device events: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane (one
  event per executed HLO op or Pallas kernel) and its ``XLA Modules`` line
  (one event per run of a jitted program);
- host programs: the union of the op intervals of each jitted program run
  on the host's own XLA device (lines ``tf_XLA*``), by module name;
- busy time: the union of the device-op intervals inside the traced window,
  averaged over the chips used;
- time per program and per op, by name (module names lose their ``(id)``);
  a program run counts where it starts inside the traced window;
- for each host span name: its count, total time and the device busy time
  inside it;
- idle gaps: the stretches of the window with no device op, each labelled by
  the host span that covers most of it ("none" where no span is open).

Run as ``python3 bench/trace.py <file.xplane.pb>`` to print the planes, lines
and most frequent event names of a trace.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import re
import sys

# host spans: the benchmark's own, and the program's TraceAnnotations
SPAN_PREFIX = ("bench.", "serve.", "offload.")
WINDOW_SPAN = "bench.traced"
_ID = re.compile(r"\(\d+\)$")
# An XLA Ops event is named by its HLO text, "%name = type kind(operands),
# attributes". Control flow (while, conditional, call) encloses the ops of
# its body, so it counts for busy time but not as an op of its own.
_HLO = re.compile(r"%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def short_name(hlo: str) -> str:
    """"fusion.163 fusion -> f32[64,9,128]..." from an op's HLO text."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:120]
    return f"{m[1]} {m[3]} -> {re.sub(r'{[^}]*}', '', m[2])[:80]}"


@contextlib.contextmanager
def profiled(out_dir: str, window: bool = True):
    """Trace the body; the ``bench.traced`` span marks the traced window on
    the profiler's own clock, around the whole body, or where the caller
    opens and closes it (``window=False``; see ``Window``). Yields a
    one-item list that holds the path of the ``.xplane.pb`` once the block
    has closed."""
    import jax
    os.makedirs(out_dir, exist_ok=True)
    before = set(glob.glob(os.path.join(out_dir, "plugins/profile/*/*.xplane.pb")))
    box: list = []
    jax.profiler.start_trace(out_dir)
    try:
        with (jax.profiler.TraceAnnotation(WINDOW_SPAN) if window
              else contextlib.nullcontext()):
            yield box
    finally:
        jax.profiler.stop_trace()
    new = sorted(set(glob.glob(os.path.join(
        out_dir, "plugins/profile/*/*.xplane.pb"))) - before)
    if new:
        box.append(new[-1])


class Window:
    """The ``bench.traced`` span, opened and closed by hand inside a
    ``profiled(..., window=False)`` block, so that the profiler can start
    before the traced window and stop (a synchronous write of the trace)
    once nothing is being timed."""

    def __init__(self):
        self._ann = None
        self.closed = False

    def open(self) -> None:
        import jax
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()

    def close(self) -> None:
        if self._ann is not None and not self.closed:
            self._ann.__exit__(None, None, None)
            self.closed = True

    @property
    def is_open(self) -> bool:
        return self._ann is not None and not self.closed


def _union(iv: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(merged: list[tuple[int, int]], starts: list[int], s: int,
             e: int) -> int:
    """Length of [s, e) covered by the sorted disjoint intervals ``merged``."""
    tot = 0
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(merged) and merged[i][0] < e:
        a, b = merged[i]
        tot += max(0, min(b, e) - max(a, s))
        i += 1
    return tot


def reduce(path: str, n_gaps: int = 10, n_ops: int = 10) -> dict:
    """Reduce one ``.xplane.pb``. Times are in seconds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev_ops: dict[str, list[tuple[int, int, str]]] = {}
    module_runs: list[tuple[int, int, str]] = []
    spans: dict[str, list[tuple[int, int]]] = collections.defaultdict(list)
    host_ops: dict[str, list[tuple[int, int]]] = collections.defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            ops = dev_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((int(e.start_ns), int(e.end_ns), e.name)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    module_runs.extend((int(e.start_ns), int(e.end_ns),
                                        _ID.sub("", e.name))
                                       for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                # programs run on the host's own XLA device (Mode A's fit)
                xla = line.name.startswith("tf_XLA")
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans[e.name].append((int(e.start_ns), int(e.end_ns)))
                    elif xla and e.duration_ns:
                        for k, v in e.stats:
                            if k == "hlo_module":
                                host_ops[v].append((int(e.start_ns),
                                                    int(e.end_ns)))
    if WINDOW_SPAN not in spans:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    w0, w1 = spans.pop(WINDOW_SPAN)[0]
    n_chips = max(len(dev_ops), 1)
    # a program run counts for the window it starts in
    modules: collections.Counter = collections.Counter()
    module_n: collections.Counter = collections.Counter()
    for s, e, name in module_runs:
        if w0 <= s < w1:
            modules[name] += e - s
            module_n[name] += 1
    op_time: collections.Counter = collections.Counter()
    op_n: collections.Counter = collections.Counter()
    busy = 0
    merged_all = []
    for ops in dev_ops.values():
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                  if e > w0 and s < w1]
        for s, e, n in inside:
            m = _HLO.match(n)
            if not (m and m[3] in CONTAINERS):
                op_time[n] += e - s
                op_n[n] += 1
        merged = _union([(s, e) for s, e, _ in inside])
        busy += sum(e - s for s, e in merged)
        merged_all.append(merged)
    # spans and gaps are read against the first chip's timeline
    merged = merged_all[0] if merged_all else []
    starts = [s for s, _ in merged]
    span_out = {}
    for name, iv in spans.items():
        iv = [(max(s, w0), min(e, w1)) for s, e in iv if e > w0 and s < w1]
        span_out[name] = {
            "count": len(iv), "total_s": sum(e - s for s, e in iv) * 1e-9,
            "device_busy_s": sum(_overlap(merged, starts, s, e)
                                 for s, e in iv) * 1e-9}
    gaps = []
    prev = w0
    for s, e in merged + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in gaps[:n_gaps]:
        # the span that covers most of the gap; among equals the innermost
        best, label = (0, 0), "none"
        for name, iv in spans.items():
            for a, b in iv:
                cov = max(0, min(b, e) - max(a, s))
                if cov and (cov, a - b) > best:
                    best, label = (cov, a - b), name
        labelled.append([label, (e - s) * 1e-9])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n_chips * 1e-9,
        "chips": len(dev_ops),
        "programs": {n: {"total_s": t * 1e-9, "count": module_n[n]}
                     for n, t in modules.items()},
        "ops": {n: {"total_s": t * 1e-9, "count": op_n[n]}
                for n, t in op_time.items()},
        "spans": span_out,
        "host_programs": {
            m: {"busy_s": sum(e - s for s, e in _union(
                [(max(s, w0), min(e, w1)) for s, e in iv
                 if e > w0 and s < w1])) * 1e-9}
            for m, iv in host_ops.items()},
        "idle_gaps": labelled,
        "device_ops": [[short_name(n), t * 1e-9]
                       for n, t in op_time.most_common(n_ops)],
    }


def program_time(red: dict, fn_name: str) -> tuple[float, int] | None:
    """(total seconds, runs) of the jitted programs whose module name holds
    ``fn_name`` (e.g. ``_chunk_fn`` for ``jit__chunk_fn``)."""
    hits = [v for n, v in red["programs"].items() if fn_name in n]
    if not hits:
        return None
    return (sum(v["total_s"] for v in hits), sum(v["count"] for v in hits))


def op_time(red: dict, pattern: str) -> tuple[float, int] | None:
    """(total seconds, calls) of the device ops whose HLO text matches
    ``pattern``. A Pallas kernel shows in the trace as a ``tpu_custom_call``
    without its name, so a pattern picks it by its operands."""
    rx = re.compile(pattern)
    hits = [v for n, v in red["ops"].items() if rx.search(n)]
    if not hits:
        return None
    return (sum(v["total_s"] for v in hits), sum(v["count"] for v in hits))


def dump(path: str, top: int = 40) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            print(f"  line {line.name!r}: {sum(names.values())} events")
            for n, c in names.most_common(top):
                print(f"    {c:6d} x {n}")
            for e in list(line.events)[:2]:
                print(f"    e.g. {e.name}: {dict(e.stats)}")


if __name__ == "__main__":
    dump(sys.argv[1])
