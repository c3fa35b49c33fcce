"""The engine's tick phases and their readers on a traced run of the tiny
serving cell on the CPU: the profiler's host trace carries the program's
``serve.*`` spans, and the per-layer readers of the phases find them. The
CPU runs no Pallas kernel on a device, so ``multi_lora_ms`` has nothing to
read there."""
import glob
import json

import pytest

from bench import trace as tr
from bench.tests.test_rehearsal import execute

PHASES = ("serve.admit", "serve.prep", "serve.dispatch", "serve.sync",
          "serve.emit")
PHASE_METRICS = ("tick_admit_ms", "tick_prep_ms", "tick_dispatch_ms",
                 "tick_emit_ms")


@pytest.fixture
def disarm():
    """A traced run arms the process-wide annotation switch; disarm it."""
    from repro import telemetry
    yield
    telemetry.enable_jax_annotations(False)


def test_traced_tiny_serve_reads_the_tick_phases(checkout, disarm):
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    listed = {m["name"]: m["workloads"] for m in bench["per_layer"]}
    for name in PHASE_METRICS + ("multi_lora_ms",):
        assert "tiny.serve" in listed[name]
    out = execute(checkout, "tiny.serve", trace=1)
    assert out["correct"] is True, out["compared"]
    for name in PHASE_METRICS:
        assert out["metrics"][name]["value"] > 0, name
    assert "multi_lora_ms" not in out["metrics"]

    (path,) = glob.glob(str(checkout / "bench_out" / "trace" / "tiny.serve"
                            / "plugins" / "profile" / "*" / "*.xplane.pb"))
    spans = tr.reduce(path)["spans"]
    assert {"serve.tick", "serve.prefill_chunk", "serve.decode",
            *PHASES} <= set(spans)
    runs = {spans[n]["count"] for n in ("serve.prep", "serve.dispatch",
                                        "serve.sync")}
    assert len(runs) == 1 and runs.pop() >= 2
