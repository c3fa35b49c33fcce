"""Run one benchmark cell once and print one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: find the cell's files (``BENCHMARK.json``, its configuration and
traffic mix), check the device (no TPU, or fewer chips than the cell needs:
exit 3, no result), make the weights on the device from the seed, warm up the
cell's own shapes with JAX's persistent compilation cache at
``<checkout>/.jax_cache``, measure for ``--seconds``, check correctness
against the plain reference, and print the result as the last line of
standard output. ``--trace 1`` reports the per-layer metrics instead of the
end-to-end ones and traces part of the window with the profiler.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Ctx:
    workload: str
    seed: int
    seconds: float
    trace: bool
    config: dict
    traffic: dict
    t_start: float
    trace_dir: str
    root: str


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(args, *, require_tpu: bool = True, t_start: float = T_START,
            root: str = ROOT, cache: bool = True):
    """Everything after argument parsing; returns the result (without
    printing it). ``root`` is the checkout whose ``BENCHMARK.json`` and
    ``bench/`` files define the cell; ``require_tpu=False`` is for rehearsals
    on the CPU, ``cache=False`` leaves JAX's compilation cache off."""
    from bench import core, peaks
    bench = core.benchmark(root)
    w = core.cell(bench, args.workload)
    cfg = core.config(w["config"], root)
    mix = core.traffic(w["traffic"], root)
    if require_tpu:
        dev = core.device_info(w["chips"])
    else:
        import jax
        d = jax.devices()
        dev = {"platform": d[0].platform, "kind": d[0].device_kind,
               "count": len(d)}
    if cache:
        core.log(f"[device] {dev}; compile cache {core.compile_cache()}")
    ctx = Ctx(workload=args.workload, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), config=cfg, traffic=mix,
              t_start=t_start,
              trace_dir=os.path.join(root, "bench_out", "trace",
                                     args.workload), root=root)
    import importlib
    driver = importlib.import_module(f"bench.{mix['kind']}")
    res = driver.run(ctx)
    rec = res.pop("record")
    rec.update(config=cfg, traffic=mix, workload=args.workload, root=root,
               peak=peaks.peaks(dev["kind"]) if dev["platform"] == "tpu"
               else None)
    specs = core.metrics_for(bench, args.workload, ctx.trace)
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"],
           "metrics": core.read_metrics(specs, rec),
           "device": dict(dev, memory_peak_bytes=res["memory_peak"])}
    if ctx.trace and rec.get("trace"):
        t = rec["trace"]
        out["device"].update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["compared"] = res["compared"]
    return out


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"no ColA checkout around {__file__}: src/repro is missing",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench_out",
                                                      "tpu_logs"))
    from bench import core
    core.emit(execute(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())
