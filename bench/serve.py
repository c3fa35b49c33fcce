"""Driver of the serving cells: an open loop around ``ServeEngine``.

Set-up makes the weights and every user's adapters from the seed, builds the
engine (paged KV, chunked prefill) and runs one warm-up request, which
compiles the only two programs a tick runs: the chunk round (slots x chunk)
and the decode step (slots x 1). Then the window opens: each request is
submitted when it is due (``traffic.serve_schedule``), the engine ticks while
it has work, and the loop sleeps until the next request is due when it has
none. Arrivals stop when the window closes; the requests still in flight are
finished, and one unfinished ``drain_s`` seconds after the close has failed.

Times are taken on the host: a request's first token when the engine
records it (``Request.t_first``, after the chunk round's result reached the
host), every later token when the tick that made it returns. TTFT is timed
from when the request was due, so a stall counts for every request behind it.

``correct`` compares what the window served with the plain reference: a
sample of the finished requests drawn from the seed, the longest among them,
is run through ``bench/reference/<family>.py`` over each prompt and its
served tokens, and the widest gap by which a served (greedy) token's logit
lies below the reference's best at that position must stay under the limit.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from bench import core, program, traffic
from bench import trace as tr


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def build(ctx) -> dict:
    """Weights, adapters, engine and schedule; warm-up included."""
    import jax
    from repro.runtime.serve_loop import Request, ServeEngine
    c, mix = ctx.config, ctx.traffic
    fam = program.family(c, ctx.root)
    cfg = fam.model_config(c)
    params = fam.params(c, ctx.seed)
    users = mix["users"]
    banks = fam.user_adapters(c, ctx.seed, users["count"], users["rank"])
    jax.block_until_ready((params, banks))
    e = mix["engine"]
    eng = ServeEngine(cfg, params, slots=e["slots"],
                      max_len=traffic.horizon(mix), user_adapters=banks,
                      prefill_chunk=e["prefill_chunk"], kv_layout="paged",
                      kv_block=e["kv_block"], kv_blocks=e.get("kv_blocks"))
    # one request through a chunk round and two decode ticks compiles every
    # shape the window uses: a tick runs a (slots, chunk) round and a
    # (slots, 1) decode, whatever the lengths of the prompts
    warm = np.arange(e["prefill_chunk"] + 1, dtype=np.int32) % cfg.vocab_size
    eng.submit(Request(rid=-1, user=0, prompt=warm, max_new=3))
    eng.run_until_idle()
    if eng.finished[-1].status != "done":
        raise RuntimeError(f"warm-up request: {eng.finished[-1].status}")
    sched = traffic.serve_schedule(mix, ctx.seed, ctx.seconds, cfg.vocab_size)
    return {"engine": eng, "sched": sched, "cfg": cfg}


def window(ctx, eng, sched) -> dict:
    """Run the open loop; returns the per-request records and, in a traced
    run, the reduced trace and the ticks it covers."""
    from repro.runtime.serve_loop import Request
    mix = ctx.traffic
    n = len(sched["offsets"])
    recs, inflight = [], {}
    ticks: list[dict] = []
    backlog: list[tuple[float, int]] = []
    # the profiler runs from before the window opens until every request
    # has finished, so that neither its start nor its stop (which writes the
    # trace synchronously) stalls a timed request; the ``bench.traced`` span
    # marks the traced part of the window
    prof = (tr.profiled(ctx.trace_dir, window=False) if ctx.trace
            else contextlib.nullcontext())
    win = tr.Window()
    i = 0
    with prof as box:
        t0 = time.perf_counter()
        due = t0 + sched["offsets"]
        t_end, deadline = t0 + ctx.seconds, t0 + ctx.seconds + mix["drain_s"]
        trace_s = min(mix["trace_seconds"], ctx.seconds)
        t_tr0 = t0 + (ctx.seconds - trace_s) / 2 if ctx.trace else float("inf")
        while True:
            now = time.perf_counter()
            if not win.closed and not win.is_open and now >= t_tr0:
                win.open()
            elif win.is_open and now >= t_tr0 + trace_s:
                win.close()
            tracing = win.is_open
            while i < n and due[i] <= now:
                r = Request(rid=i, user=int(sched["users"][i]),
                            prompt=sched["prompts"][i],
                            max_new=int(sched["max_new"][i]))
                with _span("bench.submit"):
                    eng.submit(r)
                rec = {"req": r, "due": float(due[i]), "late": now - due[i],
                       "times": [], "consumed": 0}
                recs.append(rec)
                if not r.done:
                    inflight[i] = rec
                i += 1
            if not inflight:
                if i >= n:
                    break
                with _span("bench.wait"):
                    time.sleep(max(0.0, due[i] - time.perf_counter()))
                continue
            if now > deadline:
                break
            with _span("bench.tick"):
                eng.tick()
            t = time.perf_counter()
            backlog.append((t - t0, len(eng.queue) + sum(
                a is not None for a in eng.active)))
            tick = {"chunk": [], "decode": []} if tracing else None
            for k in list(inflight):
                rec = inflight[k]
                r = rec["req"]
                P = len(r.prompt)
                had = len(rec["times"])
                for j in range(had, len(r.out)):
                    rec["times"].append(r.t_first if j == 0 else t)
                if tick is not None:
                    # prompt tokens in the cache: the engine's own count,
                    # set on the request at admission (0 before it)
                    cons = getattr(r, "_consumed", 0)
                    if cons > rec["consumed"]:
                        tick["chunk"].append(
                            (rec["consumed"], cons - rec["consumed"], cons == P))
                    dec = len(r.out) - had - (1 if had == 0 and r.out else 0)
                    if dec > 0:
                        tick["decode"].append(P + len(r.out) - 2)
                rec["consumed"] = getattr(r, "_consumed", 0)
                if r.done:
                    del inflight[k]
            if tick is not None:
                ticks.append(tick)
        win.close()
    red = tr.reduce(box[0]) if box else None
    return {"t0": t0, "t_end": t_end, "recs": recs, "ticks": ticks,
            "trace": red, "submitted": i, "n": n, "backlog": backlog}


def check(ctx, recs: list[dict], control: bool = False) -> dict:
    """Compare a seeded sample of finished requests with the reference.
    ``control=True`` also reads the control (the reference computed in
    float8) at the same served positions of the same prompts and tokens:
    the gap of the token it puts first, which ``verdict`` judges as it
    judges the program's."""
    c, mix = ctx.config, ctx.traffic
    chk = mix["check"]
    ref = core.module("reference", c["family"], ctx.root)
    done = [r["req"] for r in recs if r["req"].status == "done"]
    V = c["vocab_size"]
    bad = [r.rid for r in done
           if len(r.out) != r.max_new or min(r.out) < 0 or max(r.out) >= V]
    # the longest request and others drawn from the seed, max_requests in all
    rng = np.random.default_rng([ctx.seed, 1])
    longest = max(range(len(done)), key=lambda k: len(done[k].prompt)
                  + len(done[k].out))
    pick = [longest] + [int(k) for k in rng.permutation(len(done))
                        if k != longest][:chk["max_requests"] - 1]
    tokens = sum(len(done[k].out) for k in pick)
    sample = [done[k] for k in pick]
    # each prompt with its served tokens; position p predicts seq[p + 1],
    # and the served tokens sit at positions P - 1 .. P + n - 2
    seqs = [np.concatenate([r.prompt, np.asarray(r.out, np.int32)])[:-1]
            for r in sample]
    nxt = [np.concatenate([r.prompt[1:], np.asarray(r.out, np.int32)])
           for r in sample]
    users = [r.user for r in sample]
    rank = mix["users"]["rank"]
    t = time.perf_counter()
    with _span("bench.check"):
        kw = dict(rows=chk["max_requests"], multiple=chk["pad_to"])
        if control:
            xc = ref.hidden(c, ctx.seed, seqs, users, rank, weights="fp8",
                            **kw)
            _, ctl_tok, _ = ref.head_stats(c, ctx.seed, xc, seqs,
                                           [s[:, None] for s in nxt],
                                           weights="fp8")
            del xc
        xs = ref.hidden(c, ctx.seed, seqs, users, rank, **kw)
        gather = ([np.stack([a, b], 1) for a, b in zip(nxt, ctl_tok)]
                  if control else [s[:, None] for s in nxt])
        best, _, got = ref.head_stats(c, ctx.seed, xs, seqs, gather)
    def widest(col: int) -> float:
        # the served positions only: P - 1 .. P + n - 2
        return max(float(np.max((b - g[:, col])[len(r.prompt) - 1:]))
                   for b, g, r in zip(best, got, sample))

    gap = widest(0)
    core.log(f"[check] reference over {len(sample)} requests, {tokens} "
             f"served tokens, longest {len(seqs[0]) + 1} positions: "
             f"{time.perf_counter() - t:.3f} s")
    out = {"token_gap": gap, "malformed": len(bad), "sample": pick,
           "tokens": tokens}
    if control:
        out["control_gap"] = widest(1)
    return out


def verdict(ctx, lost: int, chk: dict | None,
            gap: str = "token_gap") -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit, for a
    run with ``lost`` requests unanswered; ``gap`` names the reading of ``check`` that is judged (``control_gap`` puts the
    control in the program's place)."""
    lim = ctx.traffic["check"]["token_gap_limit"]
    compared = {
        "token_gap": {"value": chk[gap] if chk else None, "limit": lim},
        "unanswered": {"value": lost, "limit": 0},
        "malformed": {"value": chk["malformed"] if chk else None,
                      "limit": 0}}
    correct = (chk is not None and lost == 0 and chk["malformed"] == 0
               and chk[gap] <= lim)
    return bool(correct), compared


def unanswered(w: dict) -> int:
    """Requests due in the window that were never submitted or never
    finished."""
    return sum(1 for r in w["recs"] if r["req"].status != "done") + (
        w["n"] - w["submitted"])


def run(ctx) -> dict:
    if ctx.trace:
        from repro import telemetry
        telemetry.enable_jax_annotations(True)   # the program's serve.decode
    b = build(ctx)
    setup_s = time.perf_counter() - ctx.t_start
    core.log(f"[serve] set-up {setup_s:.3f} s")
    w = window(ctx, b["engine"], b["sched"])
    mem = core.memory_peak()
    recs = w["recs"]
    lost = unanswered(w)
    late = [r["late"] for r in recs]
    core.log(f"[serve] {len(recs)} of {w['n']} requests submitted, "
             f"{lost} unanswered; generator late p50 "
             f"{core.pct(late, 50)} s, max {max(late) if late else None} s; "
             f"engine stats {b['engine'].stats}")
    del b
    gc.collect()
    chk = check(ctx, recs) if lost < len(recs) else None
    correct, compared = verdict(ctx, lost, chk)
    rec = {"kind": "serve", "setup_s": setup_s, "seconds": ctx.seconds,
           "requests": [{
               "due": r["due"], "late": r["late"],
               "admit": r["req"].t_admit, "first": r["req"].t_first,
               "done": r["req"].t_done, "times": r["times"],
               "prompt": len(r["req"].prompt), "new": len(r["req"].out),
               "ok": r["req"].status == "done"} for r in recs],
           "ticks": w["ticks"], "trace": w["trace"]}
    return {"correct": correct, "attempted": w["n"],
            "failed": lost, "record": rec, "memory_peak": mem,
            "compared": compared}
