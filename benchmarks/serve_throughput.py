"""Serving throughput: batched vs single-row (reference) prefill.

Reports time-to-first-token (TTFT) and decode/prefill tokens/sec across
prompt lengths, slot counts and user counts — the FTaaS serving hot path
(ColA §3.2: one base model, many users' adapters, continuous batching).

    PYTHONPATH=src python benchmarks/serve_throughput.py
or as part of the harness:
    PYTHONPATH=src:. python -m benchmarks.run --only serve_throughput

Perf trajectory: ``--baseline`` writes ``BENCH_serve.json`` at the repo root
(decode/prefill tokens/sec, burst on and off); ``--check`` diffs a fresh run
against the committed baseline (non-blocking CI job; see
benchmarks/perf_baseline.py).

``--chunked-sweep`` runs the chunked-prefill + paged-KV acceptance sweep:
decode tok/s while a long prefill drains (chunked vs whole-prompt) and
allocated cache bytes vs slot count (paged pool vs dense horizon).
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import bench_cfg, fmt_row  # noqa: E402
from repro.configs.base import ColaConfig  # noqa: E402
from repro.core import gl  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.runtime.serve_loop import Request, ServeEngine  # noqa: E402
from repro.telemetry.metrics import percentiles  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402


def _reset(eng, cfg, slots, max_len):
    """Reset serving state but keep the engine's compiled jit callables (and,
    with a tiered store, its warmed residency — steady-state, not cold-start)."""
    eng.cache = M.init_cache(cfg, slots, max_len)
    eng.finished = []
    eng.queue = []
    eng.active = [None] * slots
    eng.positions[:] = 0
    for k, v in eng.stats.items():
        eng.stats[k] = 0 if isinstance(v, int) else 0.0
    eng._decode_tick_s.clear()
    eng._prefill_s.clear()
    if eng.store is not None:
        eng.store.reset_counters()


def _run_once(eng, prompts, users, max_new):
    """Submit all requests, run to idle; returns (mean_ttft, wall)."""
    reqs = [Request(rid=i, user=users[i], prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    wall = time.perf_counter() - t0
    ttfts = [r.ttft for r in reqs if r.ttft is not None]
    return float(np.mean(ttfts)), wall


def bench(prompt_len=64, slots=4, n_users=2, n_requests=8, max_new=8, seed=0,
          **engine_kw):
    cfg = bench_cfg("smollm-135m")
    max_len = max(2 * prompt_len, prompt_len + max_new + 8)
    key = jax.random.PRNGKey(seed)
    params = M.init(cfg, key)
    cc = ColaConfig(mode="lora", family="lowrank", taps="qv", rank=4)
    banks = [gl.init_adapters(cfg, cc, jax.random.fold_in(key, u))
             for u in range(n_users)] if n_users else None
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len)
               for _ in range(n_requests)]
    users = [i % max(n_users, 1) for i in range(n_requests)]

    out = {}
    for mode in ("batched", "reference"):
        eng = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                          user_adapters=banks, prefill_mode=mode, **engine_kw)
        # warmup: compile decode + prefill for the shapes under test
        _run_once(eng, prompts[:slots], users[:slots], max_new)
        _reset(eng, cfg, slots, max_len)
        ttft, wall = _run_once(eng, prompts, users, max_new)
        tp = eng.throughput()
        # throughput() carries the percentile summaries under "ttft"/"latency";
        # keep the run-level mean TTFT as the scalar and expose the tails as
        # ttft_pct so existing consumers of r["ttft"] stay scalar-valued
        out[mode] = {k: v for k, v in tp.items() if k != "ttft"}
        out[mode].update(ttft=ttft, ttft_pct=tp["ttft"], wall=wall)
    return out


def _store_trace(n_users, n_requests, rng):
    """Request trace over a large user population: half the requests follow a
    zipf-ish popularity (a few hot users), half stride through the cold tail —
    so an R-row residency cache sees both reuse (hits) and churn (evictions)."""
    w = 1.0 / np.arange(1, n_users + 1)
    hot = rng.choice(n_users, size=n_requests, p=w / w.sum())
    users = []
    for i in range(n_requests):
        users.append(int(hot[i]) if i % 2 == 0 else (37 * i) % n_users)
    return users


def bench_store(n_users=256, resident=32, slots=8, n_requests=48,
                prompt_len=32, max_new=8, seed=0, check_identity=False,
                **engine_kw):
    """Tiered-store serving over U users with an R-row resident cache.

    Returns hit/eviction/byte metrics and decode throughput; with
    ``check_identity`` the emitted tokens are also asserted bit-identical to
    an all-resident (dense U-user bank) engine on the same trace."""
    cfg = bench_cfg("smollm-135m")
    max_len = max(2 * prompt_len, prompt_len + max_new + 8)
    key = jax.random.PRNGKey(seed)
    params = M.init(cfg, key)
    cc = ColaConfig(mode="lora", family="lowrank", taps="qv", rank=4)
    banks = [gl.init_adapters(cfg, cc, jax.random.fold_in(key, u))
             for u in range(n_users)]
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len)
               for _ in range(n_requests)]
    users = _store_trace(n_users, n_requests, rng)

    def trace(eng):
        reqs = [Request(rid=i, user=users[i], prompt=p, max_new=max_new)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        eng.run_until_idle()
        return [r.out for r in reqs], time.perf_counter() - t0

    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                      user_adapters=banks, resident_slots=resident,
                      **engine_kw)
    _run_once(eng, prompts[:slots], users[:slots], max_new)   # warmup/compile
    _reset(eng, cfg, slots, max_len)
    outs, wall = trace(eng)
    tp = eng.throughput()
    sm = tp["store"]
    out = {"wall": wall, "decode_tok_per_s": tp["decode_tok_per_s"],
           "hit_rate": sm["hit_rate"], "evictions": sm["evictions"],
           "fetch_time": sm["fetch_time"],
           "resident_bytes": sm["resident_bytes"],
           "host_bytes": sm["host_bytes"]}
    if check_identity:
        ref = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                          user_adapters=banks,
                          **{k: v for k, v in engine_kw.items()
                             if k != "cluster_threshold"})
        ref_outs, _ = trace(ref)
        assert outs == ref_outs, (
            f"resident-store serving (R={resident}) diverged from the "
            f"all-resident engine on a U={n_users} trace")
        out["identical_to_all_resident"] = True
        dense_bytes = sum(int(l.nbytes) for l in jax.tree.leaves(ref.bank))
        out["dense_bytes"] = dense_bytes
    return out


def store_sweep(report):
    """U >> R residency sweep: hit rate / evictions / device bytes, plus the
    acceptance trace (U=1024, R=32) checked bit-identical to all-resident."""
    report("# Tiered adapter store: U users through an R-row resident cache")
    report(fmt_row("users", "resident", "store", "hit_rate", "evictions",
                   "resident_MB", "host_MB", "decode_tok_s", "wall_s"))
    for n_users, resident, bank_store in ((256, 16, "f32"), (256, 64, "f32"),
                                          (256, 32, "int8")):
        r = bench_store(n_users=n_users, resident=resident,
                        bank_store=bank_store)
        report(fmt_row(n_users, resident, bank_store, f"{r['hit_rate']:.3f}",
                       r["evictions"], f"{r['resident_bytes'] / 2**20:.2f}",
                       f"{r['host_bytes'] / 2**20:.2f}",
                       f"{r['decode_tok_per_s']:.1f}", f"{r['wall']:.3f}"))
    # acceptance: 1024-user trace, 32 resident rows, bit-identical tokens
    r = bench_store(n_users=1024, resident=32, n_requests=64,
                    check_identity=True)
    report(fmt_row(1024, 32, "f32", f"{r['hit_rate']:.3f}", r["evictions"],
                   f"{r['resident_bytes'] / 2**20:.2f}",
                   f"{r['host_bytes'] / 2**20:.2f}",
                   f"{r['decode_tok_per_s']:.1f}", f"{r['wall']:.3f}"))
    report(f"# U=1024 R=32: bit-identical to all-resident engine; device "
           f"adapter bytes {r['resident_bytes']} vs dense {r['dense_bytes']} "
           f"({r['dense_bytes'] / max(r['resident_bytes'], 1):.0f}x), "
           f"hit rate {r['hit_rate']:.3f}, {r['evictions']} evictions, "
           f"fetch time {r['fetch_time'] * 1e3:.1f}ms")
    assert r["evictions"] > 0, "acceptance trace must exercise eviction"


def bench_interference(chunk=None, prompt_long=1024, slots=4, seed=0,
                       steady_ticks=30):
    """Decode throughput with a long-prompt prefill draining concurrently.

    ``slots - 1`` victim slots decode continuously; after a steady-state
    measurement a ``prompt_long`` request is submitted and decode throughput
    is re-measured until its prefill completes. ``chunk=None`` runs the
    legacy whole-prompt prefill (decode stalls for the full prompt);
    ``chunk=C`` runs chunked prefill over the paged KV layout (one C-token
    chunk per tick, decode interleaved). Returns steady/drain decode tok/s
    and per-tick stall percentiles (p50/p99) for each phase — the stall
    claim is stated on p99, not the mean, because the whole point of
    chunking is bounding the tail."""
    cfg = bench_cfg("smollm-135m")
    max_len = prompt_long + 64
    params = M.init(cfg, jax.random.PRNGKey(seed))
    kw = {}
    if chunk is not None:
        kw = dict(prefill_chunk=chunk, kv_layout="paged", kv_block=16)
    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len, **kw)
    rng = np.random.default_rng(seed)
    victims = [Request(rid=i, user=0,
                       prompt=rng.integers(0, cfg.vocab_size, size=16),
                       max_new=max_len - 24) for i in range(slots - 1)]
    for r in victims:
        eng.submit(r)
    while any(r.t_first is None for r in victims):
        eng.tick()

    def long_req(rid):
        return Request(rid=rid, user=0,
                       prompt=rng.integers(0, cfg.vocab_size,
                                           size=prompt_long), max_new=1)

    # warmup: compile the long-prompt prefill/chunk path off the clock
    warm = long_req(100)
    eng.submit(warm)
    while not warm.done:
        eng.tick()

    def phase(stop):
        d0, t0, gaps = eng.stats["decode_tokens"], time.perf_counter(), []
        while not stop(len(gaps)):
            t1 = time.perf_counter()
            eng.tick()
            gaps.append(time.perf_counter() - t1)
        dt = time.perf_counter() - t0
        return ((eng.stats["decode_tokens"] - d0) / dt, percentiles(gaps),
                len(gaps))

    base, base_pct, _ = phase(lambda n: n >= steady_ticks)
    probe = long_req(101)
    eng.submit(probe)
    drain, drain_pct, drain_ticks = phase(lambda n: probe.t_first is not None)
    return {"base": base, "drain": drain, "ratio": drain / max(base, 1e-9),
            "base_stall": base_pct["p99"], "drain_stall": drain_pct["p99"],
            "base_stall_pct": base_pct, "drain_stall_pct": drain_pct,
            "drain_ticks": drain_ticks}


def _layout_bytes(cfg, slots, max_len, kv_blocks, kv_block=16):
    """Allocated decode-cache bytes per layout, from cache_specs shapes (no
    device allocation — slots=4096 dense would be GBs)."""
    def total(specs):
        return sum(int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
                   for s in jax.tree.leaves(specs))
    dense = total(M.cache_specs(cfg, slots, max_len))
    ring_len = None
    if M.layer_plan(cfg)[0] == "pairs":
        ring_len = (cfg.local_window or max_len) + kv_block - 1
    paged = total(M.cache_specs(cfg, slots, max_len, kv_layout="paged",
                                kv_blocks=kv_blocks, kv_block=kv_block,
                                ring_len=ring_len))
    paged += slots * (-(-max_len // kv_block)) * 4      # block table
    return dense, paged


def chunked_sweep(report):
    """Chunked prefill + paged KV acceptance sweep (ISSUE 9)."""
    report("# Chunked prefill: decode tok/s while a 1024-token prefill drains")
    report("# (stall columns are per-tick decode-gap percentiles; the claim "
           "is on p99, not the mean)")
    report(fmt_row("mode", "steady_tok_s", "drain_tok_s", "retained",
                   "stall_p50_ms", "stall_p95_ms", "stall_p99_ms",
                   "drain_ticks"))
    rows = {}
    for label, chunk in (("unchunked", None), ("chunk=16", 16),
                         ("chunk=32", 32)):
        r = bench_interference(chunk=chunk)
        rows[label] = r
        p = r["drain_stall_pct"]
        report(fmt_row(label, f"{r['base']:.1f}", f"{r['drain']:.1f}",
                       f"{r['ratio']:.2f}", f"{p['p50'] * 1e3:.1f}",
                       f"{p['p95'] * 1e3:.1f}", f"{p['p99'] * 1e3:.1f}",
                       r["drain_ticks"]))
    un, ch = rows["unchunked"], rows["chunk=16"]
    report(f"# unchunked stalls decode for the whole prompt "
           f"(p99 {un['drain_stall'] * 1e3:.0f}ms, one tick); chunked bounds "
           f"the p99 stall at one chunk round "
           f"({ch['drain_stall'] * 1e3:.0f}ms) "
           f"(target: drain tok/s within 15% of steady on accelerator-class "
           f"decode batches; CPU ticks are dispatch-bound so the retained "
           f"fraction here is dominated by the extra chunk dispatch)")
    assert ch["ratio"] > 2 * un["ratio"], \
        "chunked prefill must retain more decode throughput under drain"
    assert un["drain_stall"] > 3 * ch["drain_stall"], \
        "chunked prefill must bound the p99 decode stall below the " \
        "full-prompt stall"

    report("")
    report("# Paged KV: allocated cache bytes vs slot count (max_len=256, "
           "pool fixed at 1024x16 positions = tokens in flight, not horizon)")
    report(fmt_row("slots", "dense_MB", "paged_MB", "dense/paged"))
    cfg = bench_cfg("smollm-135m")
    sizes = {}
    for slots in (8, 64, 512, 4096):
        dense, paged = _layout_bytes(cfg, slots, max_len=256, kv_blocks=1024)
        sizes[slots] = (dense, paged)
        report(fmt_row(slots, f"{dense / 2**20:.2f}", f"{paged / 2**20:.2f}",
                       f"{dense / paged:.1f}x"))
    # dense scales with slots * max_len; paged only grows by the block table
    assert sizes[4096][0] == 512 * sizes[8][0]
    assert sizes[4096][1] < 2 * sizes[8][1]
    report(f"# 4096 slots: dense {sizes[4096][0] / 2**20:.0f}MB vs paged "
           f"{sizes[4096][1] / 2**20:.1f}MB with a 16k-position pool "
           f"({sizes[4096][0] / sizes[4096][1]:.0f}x)")


def run(report):
    report("# FTaaS serving: batched vs single-row prefill "
           "(TTFT from submit, all requests submitted up front)")
    report(fmt_row("prompt_len", "slots", "users", "mode", "mean_ttft_s",
                   "ttft_p50", "ttft_p95", "ttft_p99", "wall_s",
                   "decode_tok_s", "prefill_tok_s"))
    speedups = {}
    for prompt_len in (16, 64, 128):
        for slots, n_users in ((2, 0), (4, 2), (8, 4)):
            res = bench(prompt_len=prompt_len, slots=slots, n_users=n_users)
            for mode in ("batched", "reference"):
                r = res[mode]
                p = r["ttft_pct"] or {}
                report(fmt_row(prompt_len, slots, n_users, mode,
                               f"{r['ttft']:.4f}",
                               f"{p.get('p50', float('nan')):.4f}",
                               f"{p.get('p95', float('nan')):.4f}",
                               f"{p.get('p99', float('nan')):.4f}",
                               f"{r['wall']:.3f}",
                               f"{r['decode_tok_per_s']:.1f}",
                               f"{r['prefill_tok_per_s']:.1f}"))
            speedups[(prompt_len, slots, n_users)] = (
                res["reference"]["ttft"] / max(res["batched"]["ttft"], 1e-9))
    report("")
    for k, s in speedups.items():
        report(f"# prompt_len={k[0]} slots={k[1]} users={k[2]}: "
               f"batched prefill TTFT speedup {s:.2f}x")
    assert all(s > 1.0 for k, s in speedups.items() if k[0] >= 64), \
        "batched prefill must beat single-row TTFT at prompt length >= 64"
    report("")
    store_sweep(report)


# ---------------------------------------------------------------------------
# telemetry artifact export (--telemetry-out DIR)
# ---------------------------------------------------------------------------

def telemetry_run(out_dir, report=print, prompt_len=48, slots=4, n_users=3,
                  n_requests=8, max_new=8, seed=0):
    """Run a short chunked+paged serve trace with telemetry enabled and export
    the artifacts CI uploads: a Chrome trace-event JSON (load in Perfetto /
    chrome://tracing) and a metric-registry snapshot. The trace is validated
    before writing — a malformed artifact fails the job, not the viewer."""
    from repro.telemetry import Telemetry
    from repro.telemetry.tracing import validate_trace

    os.makedirs(out_dir, exist_ok=True)
    cfg = bench_cfg("smollm-135m")
    max_len = max(2 * prompt_len, prompt_len + max_new + 8)
    key = jax.random.PRNGKey(seed)
    params = M.init(cfg, key)
    cc = ColaConfig(mode="lora", family="lowrank", taps="qv", rank=4)
    banks = [gl.init_adapters(cfg, cc, jax.random.fold_in(key, u))
             for u in range(n_users)]
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len)
               for _ in range(n_requests)]
    users = [i % n_users for i in range(n_requests)]

    tm = Telemetry(trace=True, out_dir=out_dir)
    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                      user_adapters=banks, prefill_chunk=16,
                      kv_layout="paged", kv_block=16, telemetry=tm)
    _run_once(eng, prompts, users, max_new)

    doc = tm.tracer.to_doc()
    errors = validate_trace(doc)
    assert not errors, f"exported trace failed validation: {errors}"
    trace_path = os.path.join(out_dir, "serve_trace.json")
    tm.export_trace(trace_path)
    snap_path = os.path.join(out_dir, "serve_metrics.json")
    with open(snap_path, "w") as f:
        json.dump(eng.telemetry_snapshot(), f, indent=1, sort_keys=True)
        f.write("\n")
    spans = sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
    report(f"# telemetry artifacts: {trace_path} ({spans} spans, valid "
           f"trace-event JSON), {snap_path}")
    tp = eng.throughput()
    for k in ("ttft", "decode_tick"):
        p = tp[k]
        if p:
            report(f"# {k}: p50={p['p50'] * 1e3:.1f}ms "
                   f"p95={p['p95'] * 1e3:.1f}ms p99={p['p99'] * 1e3:.1f}ms "
                   f"(n={p['count']})")
    return 0


# ---------------------------------------------------------------------------
# per-PR perf baseline (BENCH_serve.json)
# ---------------------------------------------------------------------------

def _engine_tokens_per_s(max_new=32, **kw):
    """Decode tokens/sec of a warmed engine on a fixed request mix."""
    res = bench(prompt_len=64, slots=4, n_users=2, n_requests=8,
                max_new=max_new, **kw)["batched"]
    return res["decode_tok_per_s"], res["prefill_tok_per_s"]


def collect() -> list[dict]:
    from benchmarks import perf_baseline as pb
    entries = []
    dec1, pre = _engine_tokens_per_s(decode_burst=1)
    entries.append(pb.entry("serve_decode", "slots=4,users=2,burst=1",
                            tokens_per_s=dec1))
    dec8, _ = _engine_tokens_per_s(decode_burst=8)
    entries.append(pb.entry("serve_decode", "slots=4,users=2,burst=8",
                            tokens_per_s=dec8))
    decq8, _ = _engine_tokens_per_s(decode_burst=8, bank_store="int8")
    entries.append(pb.entry("serve_decode", "slots=4,users=2,burst=8,int8",
                            tokens_per_s=decq8))
    entries.append(pb.entry("serve_prefill", "slots=4,users=2,prompt=64",
                            tokens_per_s=pre))
    st = bench_store(n_users=256, resident=32)
    entries.append(pb.entry("serve_store", "users=256,resident=32,slots=8",
                            tokens_per_s=st["decode_tok_per_s"],
                            hit_rate=st["hit_rate"]))
    st8 = bench_store(n_users=256, resident=32, bank_store="int8")
    entries.append(pb.entry("serve_store",
                            "users=256,resident=32,slots=8,int8",
                            tokens_per_s=st8["decode_tok_per_s"],
                            hit_rate=st8["hit_rate"]))
    # chunked prefill + paged KV: steady paged decode and decode-under-drain
    itf = bench_interference(chunk=16)
    entries.append(pb.entry("serve_paged_decode",
                            "slots=4,chunk=16,kv_block=16,steady",
                            tokens_per_s=itf["base"]))
    entries.append(pb.entry("serve_paged_decode",
                            "slots=4,chunk=16,kv_block=16,drain1024",
                            tokens_per_s=itf["drain"]))
    return entries


def main(argv=None) -> int:
    enable_compile_cache()
    from benchmarks import perf_baseline as pb
    import jax as _jax
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--telemetry-out" in argv:
        i = argv.index("--telemetry-out")
        return telemetry_run(argv[i + 1], lambda *a: print(*a, flush=True))
    if "--store-sweep" in argv:
        store_sweep(lambda *a: print(*a, flush=True))
        return 0
    if "--chunked-sweep" in argv:
        chunked_sweep(lambda *a: print(*a, flush=True))
        return 0
    return pb.run_cli(argv, collect=collect, baseline_name="BENCH_serve.json",
                      meta={"suite": "serve_throughput",
                            "device": _jax.devices()[0].platform})


if __name__ == "__main__":
    if len(sys.argv) > 1:
        sys.exit(main())
    run(lambda *a: print(*a, flush=True))
