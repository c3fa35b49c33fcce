"""Find a serving cell's knee on the chip: one set-up, then one window of
``--seconds`` at each offered rate, in order.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.5 1 2 ...

Per rate it prints a JSON line: requests due, answered within the window and
after it, TTFT p50/p95, ITL p99, and the backlog (requests queued or in a
slot) sampled after every tick, with its growth over the window's second half
(the least-squares slope times that half's length). The knee is the highest
rate at which the median TTFT stays under twice the median TTFT at the first
(lowest) rate swept and every request is answered before the drain limit:
past the engine's capacity requests queue for slots, and the median request,
due mid-window, waits for the excess that arrived before it. (The backlog's
growth is printed too, but over the few tens of requests that a nemo window
holds it swings too far to decide.) The sweep stops at the first rate past
the knee. The cell's rate (about 0.8 x the knee) is then written into its
traffic file by hand; nothing reads these lines.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np
    from bench import core, run, serve, traffic
    bench = core.benchmark()
    w = core.cell(bench, args.workload)
    core.device_info(w["chips"])
    core.compile_cache()
    ctx = run.Ctx(workload=args.workload, seed=args.seed,
                  seconds=args.seconds, trace=False,
                  config=core.config(w["config"]),
                  traffic=core.traffic(w["traffic"]),
                  t_start=time.perf_counter(), trace_dir="", root=ROOT)
    b = serve.build(ctx)
    base = None
    for rate in args.rates:
        mix = json.loads(json.dumps(ctx.traffic))
        mix["arrivals"]["rate_per_s"] = rate
        c2 = dataclasses.replace(ctx, traffic=mix)
        sched = traffic.serve_schedule(mix, args.seed, args.seconds,
                                       b["cfg"].vocab_size)
        out = serve.window(c2, b["engine"], sched)
        bl = np.array([(t, n) for t, n in out["backlog"]
                       if t >= args.seconds / 2 and t <= args.seconds])
        slope = (float(np.polyfit(bl[:, 0], bl[:, 1], 1)[0])
                 if len(bl) > 2 else 0.0)
        recs = out["recs"]
        ok = [r for r in recs if r["req"].status == "done"]
        in_win = [r for r in ok if r["req"].t_done <= out["t_end"]]
        ttft = [r["req"].t_first - r["due"] for r in ok]
        gaps = [b_ - a for r in ok for a, b_ in zip(r["times"], r["times"][1:])]
        p50 = core.pct(ttft, 50)
        base = p50 if base is None else base
        lost = serve.unanswered(out)
        past = lost > 0 or p50 is None or p50 >= 2 * base
        print(json.dumps({
            "rate": rate, "due": out["n"], "answered_in_window": len(in_win),
            "answered": len(ok), "unanswered": lost, "ttft_p50_s": p50,
            "ttft_p95_s": core.pct(ttft, 95), "itl_p99_s": core.pct(gaps, 99),
            "backlog_mean": float(np.mean([n for _, n in out["backlog"]]))
            if out["backlog"] else 0.0,
            "backlog_growth_2nd_half": slope * args.seconds / 2,
            "past_knee": past,
            "late_max_s": max(r["late"] for r in recs) if recs else None}),
            flush=True)
        if past:
            break       # what is left in flight would spill into the next
    return 0


if __name__ == "__main__":
    sys.exit(main())
