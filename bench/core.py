"""What every run shares: finding a cell's files by name, the device check,
the per-layer and end-to-end metric readers, and the result line.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix; each is a file found by its name:

- ``bench/configs/<config>.json``: the published sizes, what was cut, and the
  family whose builder (``bench/families/<family>.py``), reference
  (``bench/reference/<family>.py``) and counts (``bench/roofline/<family>.py``)
  apply (``program.py`` states what each provides);
- ``bench/traffic/<traffic>.json``: the mix, read by ``traffic.py``, whose
  ``kind`` picks the driver (``bench/<kind>.py``);
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``, which
  returns a number or None when the run has nothing for it to read.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have "
                     f"{[w['name'] for w in bench['workloads']]})")


def config(name: str, root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "bench", "configs", f"{name}.json"))


def traffic(name: str, root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "bench", "traffic", f"{name}.json"))


def module(kind: str, name: str, root: str = ROOT):
    """``<root>/bench/<kind>/<name>.py`` loaded by path (names may hold
    dots); ``root`` is the checkout whose files define the cell."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, workload: str, traced: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: the end-to-end ones with
    ``--trace 0``, the per-layer ones with ``--trace 1``."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def read_metrics(specs: list[dict], run: dict) -> dict:
    out = {}
    for m in specs:
        v = module("metrics", m["name"], run["root"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def pct(x, q: float) -> float | None:
    x = np.asarray(list(x), np.float64)
    return float(np.percentile(x, q)) if x.size else None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int) -> dict:
    """Platform, kind and count of the devices JAX sees. Exits with code 3
    and no result when there is no TPU or fewer chips than the cell needs."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        log(f"[device] no run: JAX sees {info['count']} {info['platform']} "
            f"device(s) ({info['kind']}); this cell needs {chips} TPU chip(s)")
        raise SystemExit(3)
    return info


def compile_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path in the
    checkout (``$JAX_COMPILATION_CACHE_DIR`` where that is set), holding
    every program however quickly it compiled, so that only a cell's first
    run in a checkout compiles."""
    import jax
    from repro.utils import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def emit(result: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output,
    with ``compared`` as its last key."""
    compared = result.pop("compared")
    for k, v in compared.items():
        log(f"[check] {k} = {v['value']!r} (limit {v['limit']!r})")
    result["compared"] = compared
    print(json.dumps(result), flush=True)
