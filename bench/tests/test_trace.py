"""The trace reduction on a small trace recorded on one v5e: one second of
``smollm.serve.chat`` under ``--trace 1`` (``python3 bench/run.py
--workload smollm.serve.chat --seconds 1 --trace 1``), gzipped."""
import gzip
import os
import shutil

import pytest

from bench import core
from bench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "smollm_chat_1s.xplane.pb.gz")


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.reduce(str(path))


def test_window_and_busy(red):
    assert red["chips"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]


def test_programs_by_name(red):
    chunk = tr.program_time(red, "_chunk_fn")
    decode = tr.program_time(red, "_decode_fn")
    assert chunk and decode
    assert chunk[1] >= 1 and decode[1] >= 1
    assert tr.program_time(red, "no_such_fn") is None


def test_paged_kernel_found_by_its_operands(red):
    pat = core.module("metrics", "decode_attention_paged_roofline").KERNEL
    t, calls = tr.op_time(red, pat)
    # one call per layer (30) per decode tick
    assert calls == 30 * tr.program_time(red, "_decode_fn")[1]
    assert t > 0


def test_control_flow_is_not_an_op(red):
    assert not any(" while -> " in n for n, _ in red["device_ops"])
    assert all(len(n) <= 200 for n, _ in red["device_ops"])


def test_spans_and_gaps(red):
    tick = red["spans"]["bench.tick"]
    assert tick["count"] >= 1
    assert 0 < tick["device_busy_s"] <= tick["total_s"]
    assert red["idle_gaps"] and all(g[1] > 0 for g in red["idle_gaps"])
    assert {g[0] for g in red["idle_gaps"]} <= set(red["spans"]) | {"none"}
