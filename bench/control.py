"""Readings that a cell's correctness limits are set from (on the chip, not
part of a benchmark run), one JSON line per seed, all seeds in one process.

Serving cells: the cell is built, a window runs at the cell's own load, and
on a seeded sample of the requests it finished it reads

- ``token_gap``: the program's widest gap below the reference's best logit
  (the number ``run.py`` compares with its limit), and
- ``control_gap``: the gap of the token that the control puts first, at
  the same served positions of the same prompts and tokens, the control
  being the reference computed in float8 e4m3 (``weights="fp8"``), the
  precision below the configuration's bfloat16; ``control_correct`` is what
  ``serve.verdict`` makes of it in the program's place, which has to be
  false.

Training cells: the session is built and driven through its first three
steps (no window), and the numbers of ``train.gaps`` are read for the
program, for the control, and for the half-batch fault planted in the
reference (each step's mean taken over the first half of its rows);
``--program-only`` reads the program alone.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 ...

Each limit lies between the largest sound reading and the smallest upper
one (``PERF.md`` gives the readings and the limits).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-only", action="store_true",
                    help="training: read the program alone, no control or "
                         "fault")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import core, run, serve, train
    bench = core.benchmark()
    w = core.cell(bench, args.workload)
    core.device_info(w["chips"])
    core.compile_cache()
    for seed in args.seeds:
        ctx = run.Ctx(workload=args.workload, seed=seed, seconds=args.seconds,
                      trace=False, config=core.config(w["config"]),
                      traffic=core.traffic(w["traffic"]),
                      t_start=time.perf_counter(), trace_dir="", root=ROOT)
        if ctx.traffic["kind"] == "train":
            print(json.dumps(dict(seed=seed, **train_readings(
                ctx, train, args.program_only))), flush=True)
            continue
        b = serve.build(ctx)
        out = serve.window(ctx, b["engine"], b["sched"])
        del b
        gc.collect()
        lost = serve.unanswered(out)
        chk = serve.check(ctx, out["recs"], control=True)
        print(json.dumps({
            "seed": seed, "token_gap": chk["token_gap"],
            "control_gap": chk["control_gap"],
            "correct": serve.verdict(ctx, lost, chk)[0],
            "control_correct": serve.verdict(ctx, lost, chk,
                                             "control_gap")[0],
            "tokens": chk["tokens"], "requests": len(chk["sample"]),
            "unanswered": lost}), flush=True)
    return 0


def train_readings(ctx, train, program_only: bool = False) -> dict:
    import numpy as np
    b = train.build(ctx)
    prog = {k: b[k] for k in ("losses", "first_grad", "change")}
    data = [{k: np.asarray(v) for k, v in d.items()} for d in b["data"]]
    del b
    gc.collect()
    ref = train.reference(ctx, data)
    if program_only:
        return {"program": train.gaps(prog, ref)}
    ctl = train.reference(ctx, data, weights="fp8")
    half = train.reference(ctx, [{k: v[: len(v) // 2] for k, v in d.items()}
                                 for d in data])
    return {"program": train.gaps(prog, ref),
            "control": train.gaps(ctl, ref),
            "half_batch": train.gaps(half, ref),
            "losses": prog["losses"], "ref_losses": ref["losses"]}


if __name__ == "__main__":
    sys.exit(main())
