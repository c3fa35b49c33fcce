"""Each cell's harness path at a tiny size on the CPU (Pallas kernels in
interpret mode), in a checkout where the tiny cells and a new metric were
added as files and entries only, and the device check."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import run
from bench.tests.conftest import ROOT


def execute(checkout, workload, trace=0, seed=2**31 + 99, seconds=2):
    from repro.kernels import ops
    ops.set_backend("pallas_interpret")
    try:
        args = run.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
        return run.execute(args, require_tpu=False, root=str(checkout),
                           t_start=time.perf_counter(), cache=False)
    finally:
        ops.set_backend(None)


@pytest.mark.parametrize("workload,metric", [
    ("tiny.serve", "itl_p99_s"), ("tiny.fused", "train_tok_per_s"),
    ("tiny.offload", "train_tok_per_s")])
def test_cell_from_added_files_runs_and_is_correct(checkout, workload, metric):
    out = execute(checkout, workload)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert {"setup_s", metric} <= set(out["metrics"])
    assert list(out)[-1] == "compared"


def test_metric_file_adds_a_metric(checkout):
    (checkout / "bench" / "metrics" / "served_requests.py").write_text(
        "def read(run):\n"
        "    return len(run['requests']) if run['kind'] == 'serve' else None\n")
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "served_requests", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "engine", "moves": "itl_p99_s",
        "workloads": ["tiny.serve"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    out = execute(checkout, "tiny.serve", trace=1)
    assert out["metrics"]["served_requests"]["value"] == out["attempted"]
    assert "busy_s" in out["device"] and "breakdown" in out


def _run_cli(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script, "--workload", "smollm.serve.chat", "--seed",
         "5", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run_cli(ROOT, os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_exit_nonzero(checkout):
    p = _run_cli(checkout, os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
