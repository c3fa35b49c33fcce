"""Benchmark harness: one module per paper table/figure + kernel benches +
dry-run roofline summary. Prints CSV-ish blocks; ``python -m benchmarks.run``.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.utils import enable_compile_cache

SUITES = [
    ("table1_memory", "paper Table 1: computation-space complexity"),
    ("equivalence", "paper Tables 2/3/6: method equivalence"),
    ("from_scratch", "paper C.3/Table 9: learning from scratch"),
    ("interval", "paper C.4: adaptation interval ablation"),
    ("collaboration", "paper Table 4: K-user collaboration"),
    ("compute_eval", "paper Tables 10-18: computation evaluation"),
    ("serve_throughput", "FTaaS serving: batched vs single-row prefill"),
    ("kernels_bench", "kernel micro-benchmarks"),
    ("roofline_summary", "dry-run roofline table (reads dryrun_*.jsonl)"),
]


def main(argv=None) -> int:
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None, help="comma-separated suite names")
    args = p.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    failures = []
    for name, desc in SUITES:
        if only and name not in only:
            continue
        print(f"\n===== {name}: {desc} =====", flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run(lambda *a: print(*a, flush=True))
            print(f"[{name}] done in {time.time()-t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print("\nFAILED suites:", failures)
        return 1
    print("\nall benchmark suites passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
