"""Driver of the training cells: ``ColaSession.step`` in a loop.

Set-up makes the weights from the seed, builds one ``ColaSession`` (Mode A
``faithful_offload`` or Mode B ``fused_fit``, low-rank qv adapters, AdamW on
the offload device) and drives that same object through its first three
steps on the mix's first three batches: they compile every program and are
what the reference follows. The window then calls ``step`` on the batches in
turn until ``--seconds`` have passed; each step ends on ``float(loss)``, so
the host clock covers all of its device work.

``correct`` compares the first three steps with the plain reference
(``bench/reference/<family>.py``: float32, AdamW as the program runs it):
each step's loss, the first gradient as the optimizer received it (Adam's
first moment after one step, over 1 - beta1), and the adapters' change after
three steps. Norms are compared leaf by leaf: the gap between the program's
norm of a leaf and the reference's, over the larger of the reference's norm
of that leaf and the median leaf's; the worst leaf counts. A leaf whose
reference gradient is under a thousandth of the median leaf's (A at the
first step: LoRA starts with B = 0) has no first gradient to compare.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from bench import core, program, seeded, traffic
from bench import trace as tr

WARM_STEPS = 3
TINY = 1e-3


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def optimizer(mix: dict) -> dict:
    o = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.0}
    o.update(mix["optimizer"])
    return o


def _leaves(tree: dict) -> dict:
    """{"q.A": array, ...} from the program's {"layers.attn.q": {"A"}} or
    the reference's {"q": {"A"}}."""
    return {f"{tap.rsplit('.', 1)[-1]}.{n}": np.asarray(a, np.float64)
            for tap, leaves in tree.items() for n, a in leaves.items()}


def build(ctx) -> dict:
    import jax
    from repro.configs.base import ColaConfig
    from repro.core.session import ColaSession
    from repro.optim import optimizers as optim
    c, mix = ctx.config, ctx.traffic
    fam = program.family(c, ctx.root)
    cfg = fam.model_config(c, **mix.get("program", {}))
    params = fam.params(c, ctx.seed)
    data = [jax.device_put(b) for b in traffic.train_batches(
        mix, ctx.seed, cfg.vocab_size)]
    o = optimizer(mix)
    cc = ColaConfig(mode=mix["mode"], family="lowrank", taps="qv",
                    rank=mix["rank"])
    sess = ColaSession(cfg, cc, params, seeded.session_key(ctx.seed),
                       optimizer=optim.adamw(o["lr"], b1=o["b1"], b2=o["b2"],
                                             eps=o["eps"],
                                             weight_decay=o["weight_decay"]))
    start = _leaves(jax.device_get(sess.adapters))
    losses, first = [], None
    for k in range(WARM_STEPS):
        losses.append(sess.step(data[k]))
        if k == 0:
            m = jax.device_get(sess.offloader.opt_state["m"])
            first = {n: a / (1 - o["b1"]) for n, a in _leaves(m).items()}
    after = _leaves(jax.device_get(sess.adapters))
    return {"session": sess, "data": data, "losses": losses,
            "first_grad": first,
            "change": {n: after[n] - start[n] for n in start}}


def window(ctx, sess, data) -> dict:
    mix = ctx.traffic
    tokens_per_step = mix["batch"] * mix["seq"]
    trace_s = min(mix["trace_seconds"], ctx.seconds)
    stack, box, tracing = contextlib.ExitStack(), None, False
    steps, k = [], WARM_STEPS
    t0 = time.perf_counter()
    t_end = t0 + ctx.seconds
    t_tr0 = t0 + (ctx.seconds - trace_s) / 2 if ctx.trace else float("inf")
    with stack:
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if not tracing and box is None and now >= t_tr0:
                box = stack.enter_context(tr.profiled(ctx.trace_dir))
                tracing = True
            elif tracing and now >= t_tr0 + trace_s:
                stack.close()
                tracing = False
            with _span("bench.step"):
                loss = sess.step(data[k % len(data)])
            steps.append((now, time.perf_counter(), loss))
            k += 1
    t_last = steps[-1][1] if steps else t0
    return {"window_s": t_last - t0, "tokens": len(steps) * tokens_per_step,
            "steps": steps, "trace": tr.reduce(box[0]) if box else None}


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared with the reference, each by the worst leaf and
    against the larger of the reference's norm of that leaf and of the
    median leaf: the gap between the norms of the first gradient
    (``grad_gap``) and of the change after the steps (``change_gap``), and
    the norm of the first gradient's difference (``grad_err``); and the
    worst relative gap of a step's loss (``loss_gap``)."""
    def flat(tree: dict) -> dict:
        nested = any(isinstance(v, dict) for v in tree.values())
        return _leaves(tree) if nested else tree

    def worst(p: dict, r: dict, skip_tiny: bool, diff: bool) -> float:
        rn = {n: float(np.linalg.norm(a)) for n, a in r.items()}
        med = float(np.median(list(rn.values())))
        keep = [n for n in rn if not (skip_tiny and rn[n] < TINY * med)]
        num = {n: (float(np.linalg.norm(p[n] - r[n])) if diff else
                   abs(float(np.linalg.norm(p[n])) - rn[n])) for n in keep}
        return max(num[n] / max(rn[n], med) for n in keep)

    pg, rg = flat(prog["first_grad"]), flat(ref["first_grad"])
    pc, rc = flat(prog["change"]), flat(ref["change"])
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": worst(pg, rg, True, False),
            "grad_err": worst(pg, rg, True, True),
            "change_gap": worst(pc, rc, False, False)}


def reference(ctx, data, weights: str = "as_stated") -> dict:
    c, mix = ctx.config, ctx.traffic
    ref = core.module("reference", c["family"], ctx.root)
    host = [{k: np.asarray(v) for k, v in b.items()}
            for b in data[:WARM_STEPS]]
    with _span("bench.check"):
        return ref.train_steps(c, ctx.seed, seeded.session_key(ctx.seed),
                               mix["rank"], host, optimizer(mix),
                               weights=weights)


def run(ctx) -> dict:
    if ctx.trace:
        from repro import telemetry
        telemetry.enable_jax_annotations(True)   # the program's offload.fit
    b = build(ctx)
    setup_s = time.perf_counter() - ctx.t_start
    core.log(f"[train] set-up {setup_s:.3f} s; first losses {b['losses']}")
    w = window(ctx, b["session"], b["data"])
    mem = core.memory_peak()
    core.log(f"[train] window: {len(w['steps'])} steps, {w['tokens']} tokens "
             f"in {w['window_s']:.3f} s")
    prog = {k: b[k] for k in ("losses", "first_grad", "change")}
    data = [{k: np.asarray(v) for k, v in d.items()} for d in b["data"]]
    finite = bool(np.isfinite([s[2] for s in w["steps"]]).all())
    del b
    gc.collect()
    t = time.perf_counter()
    g = gaps(prog, reference(ctx, data))
    core.log(f"[check] reference, {WARM_STEPS} steps: "
             f"{time.perf_counter() - t:.3f} s; {g}")
    # the numbers with a limit in the mix's file are compared (PERF.md gives
    # the readings each limit was set from, and why a number has none)
    lim = ctx.traffic["check"]
    compared = {k: {"value": g[k], "limit": lim[k]} for k in lim}
    compared["nonfinite_losses"] = {"value": int(not finite), "limit": 0}
    correct = finite and all(g[k] <= lim[k] for k in lim)
    rec = {"kind": "train", "setup_s": setup_s, "seconds": ctx.seconds,
           "window_s": w["window_s"], "window_tokens": w["tokens"],
           "trace": w["trace"]}
    return {"correct": bool(correct), "attempted": len(w["steps"]),
            "failed": 0 if finite else len(w["steps"]), "record": rec,
            "memory_peak": mem, "compared": compared}
