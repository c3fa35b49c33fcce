"""The program's own spans and kernel names in a trace recorded on one v5e:
one second of ``smollm.serve.chat`` under ``--trace 1`` (``python3
bench/run.py --workload smollm.serve.chat --seed 3700015001 --seconds 1
--trace 1``), gzipped. The trace of the same run before the program wrote
these names is ``smollm_chat_1s.xplane.pb.gz`` (``test_trace.py``)."""
import bisect
import collections
import gzip
import os
import re
import shutil

import pytest

from bench import core, phases
from bench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "smollm_chat_phases_1s.xplane.pb.gz")
PHASES = ("serve.admit", "serve.prep", "serve.dispatch", "serve.sync",
          "serve.emit")
LAYERS = 30


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture(scope="module")
def raw(path):
    """Host spans by name, and the chip's op and program events."""
    from jax.profiler import ProfileData
    spans = collections.defaultdict(list)
    ops, programs = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    programs = [(e.start_ns, e.end_ns, e.name)
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        spans[e.name].append((e.start_ns, e.end_ns))
    return {"spans": spans, "ops": ops, "programs": programs}


def test_every_phase_nests_inside_a_tick(raw):
    ticks = sorted(raw["spans"]["serve.tick"])
    assert ticks
    for name in PHASES + ("serve.prefill_chunk", "serve.decode"):
        assert raw["spans"][name], name
        for s, e in raw["spans"][name]:
            i = bisect.bisect_right(ticks, (s, float("inf"))) - 1
            assert i >= 0 and ticks[i][0] <= s and e <= ticks[i][1], name


def test_multi_lora_found_by_name_per_program_run(raw):
    rx = re.compile(phases.kernel("multi_lora"))
    calls = sorted(s for s, _, n in raw["ops"] if rx.search(n))
    runs = [(s, e) for s, e, n in raw["programs"]
            if "_chunk_fn" in n or "_decode_fn" in n]
    assert runs
    for s, e in runs:
        # the q and v projections of each of the 30 layers
        n = bisect.bisect_right(calls, e) - bisect.bisect_left(calls, s)
        assert n == 2 * LAYERS
    # the layout copies beside a kernel carry its name too; only the
    # custom calls count
    named = [n for _, _, n in raw["ops"]
             if re.search(r'"kernel\\?":\s*\\?"multi_lora\\?"', n)]
    assert len(named) > len(calls)
    assert all(" custom-call(" in n for _, _, n in raw["ops"] if rx.search(n))


def test_readers_of_the_phases_and_the_kernel(path):
    run = {"trace": tr.reduce(path)}
    for name in ("tick_admit_ms", "tick_prep_ms", "tick_dispatch_ms",
                 "tick_emit_ms", "multi_lora_ms"):
        assert core.module("metrics", name).read(run) > 0, name
    spans = run["trace"]["spans"]
    assert (spans["serve.prep"]["count"] == spans["serve.dispatch"]["count"]
            == spans["serve.sync"]["count"])
