"""Kernel micro-benchmarks: oracle (jnp) wall time on this host + roofline
byte/flop accounting for the TPU target (the kernels themselves require TPU;
interpret mode is correctness-only).

Perf trajectory:
    PYTHONPATH=src:. python benchmarks/kernels_bench.py --baseline
writes ``BENCH_kernels.json`` at the repo root (median/p90 wall per op);
``--check`` diffs a fresh run against the committed baseline and flags
regressions (non-blocking CI job; see benchmarks/perf_baseline.py).
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import perf_baseline as pb  # noqa: E402
from benchmarks.common import fmt_row, timed  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels import multi_lora as ml  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402


def run(report):
    report("# kernel micro-bench: jnp-oracle wall time (CPU) + TPU-side "
           "analytic bytes/flops per call")
    report(fmt_row("kernel", "shape", "cpu_ms", "flops", "hbm_bytes_flash",
                   "hbm_bytes_naive"))
    key = jax.random.PRNGKey(0)

    # flash attention: naive materialises S^2 scores; flash streams kv blocks
    for S in (512, 1024):
        H, K, D = 8, 8, 64
        q = jax.random.normal(key, (1, S, H, D), jnp.bfloat16)
        k = jax.random.normal(key, (1, S, K, D), jnp.bfloat16)
        v = jax.random.normal(key, (1, S, K, D), jnp.bfloat16)
        pos = jnp.arange(S)[None]
        f = jax.jit(lambda q, k, v: ref.sdpa(q, k, v, q_positions=pos,
                                             kv_positions=pos))
        t = timed(f, q, k, v, iters=3)
        flops = 4 * S * S * H * D  # QK^T + PV
        flash_bytes = 2 * (3 * S * H * D + S * H * D)      # q,k,v in + o out
        naive_bytes = flash_bytes + 2 * 4 * H * S * S      # + scores rt f32
        report(fmt_row("flash_attention", f"S={S},H={H},D={D}",
                       f"{t*1e3:.2f}", flops, flash_bytes, naive_bytes))

    # cola_fit: fused vs two-pass (materialising xa in HBM)
    for T in (4096, 16384):
        d, r = 1024, 16
        x = jax.random.normal(key, (T, d), jnp.bfloat16)
        g = jax.random.normal(key, (T, d), jnp.bfloat16)
        A = jax.random.normal(key, (d, r))
        Bm = jax.random.normal(key, (r, d))
        f = jax.jit(lambda x, g: ref.cola_fit_lowrank(x, g, A, Bm))
        t = timed(f, x, g, iters=3)
        flops = 2 * T * d * r * 3
        fused = 2 * (2 * T * d) + 4 * (2 * d * r)
        twopass = fused + 2 * 4 * T * r
        report(fmt_row("cola_fit", f"T={T},d={d},r={r}", f"{t*1e3:.2f}",
                       flops, fused, twopass))

    # multi_lora dense-over-users cost model
    for U in (4, 16):
        T, d, r = 1024, 1024, 16
        x = jax.random.normal(key, (T, d), jnp.bfloat16)
        A = jax.random.normal(key, (U, d, r))
        Bm = jax.random.normal(key, (U, r, d))
        idx = jax.random.randint(key, (T,), 0, U)
        f = jax.jit(lambda x, idx: ref.multi_lora(x, A, Bm, idx))
        t = timed(f, x, idx, iters=3)
        flops = 2 * T * d * r * 2 * U   # TPU kernel: dense over users
        gather_flops = 2 * T * d * r * 2
        report(fmt_row("multi_lora", f"T={T},U={U},r={r}", f"{t*1e3:.2f}",
                       flops, gather_flops, "-"))


# ---------------------------------------------------------------------------
# per-PR perf baseline (BENCH_kernels.json)
# ---------------------------------------------------------------------------

def collect() -> list[dict]:
    """Decode-hot-path op timings on this host (jnp oracles under jit — the
    code the CPU serve path actually runs; Pallas kernels need a TPU)."""
    key = jax.random.PRNGKey(0)
    entries = []

    # single-query decode attention against a slot cache (serving hot path)
    for B, Smax, H, K, D in ((8, 512, 8, 2, 64), (16, 1024, 8, 8, 64)):
        q = jax.random.normal(key, (B, 1, H, D))
        kc = jax.random.normal(jax.random.fold_in(key, 1), (B, Smax, K, D))
        vc = jax.random.normal(jax.random.fold_in(key, 2), (B, Smax, K, D))
        pos = jax.random.randint(jax.random.fold_in(key, 3), (B,), 0, Smax)
        live = jnp.ones((B,), bool)
        f = jax.jit(lambda q, kc, vc, pos, live: ref.sdpa_decode(
            q, kc, vc, pos, live=live))
        entries.append(pb.entry(
            "sdpa_decode", f"B={B},Smax={Smax},H={H},K={K},D={D}",
            **pb.timed_stats(f, q, kc, vc, pos, live)))

    # multi-LoRA decode dispatch: dense-over-users vs grouped (big bank)
    T, d, r = 16, 512, 8
    for U in (16, 256):
        x = jax.random.normal(key, (T, d))
        A = jax.random.normal(jax.random.fold_in(key, 1), (U, d, r))
        Bm = jax.random.normal(jax.random.fold_in(key, 2), (U, r, d))
        idx = jax.random.randint(jax.random.fold_in(key, 3), (T,), 0, U)
        f = jax.jit(lambda x, idx: ref.multi_lora(x, A, Bm, idx))
        entries.append(pb.entry("multi_lora", f"T={T},U={U},d={d},r={r}",
                                **pb.timed_stats(f, x, idx)))

    # int8-stored bank apply (dequant-on-load oracle)
    U = 16
    A = jax.random.normal(jax.random.fold_in(key, 4), (U, d, r))
    Bm = jax.random.normal(jax.random.fold_in(key, 5), (U, r, d))
    A_q, A_s = ml.quant_rows(A)
    B_q, B_s = ml.quant_rows(Bm)
    idx = jax.random.randint(jax.random.fold_in(key, 6), (T,), 0, U)
    x = jax.random.normal(key, (T, d))
    f = jax.jit(lambda x, idx: ref.multi_lora_q8(x, A_q, A_s, B_q, B_s, idx))
    entries.append(pb.entry("multi_lora_q8", f"T={T},U={U},d={d},r={r}",
                            **pb.timed_stats(f, x, idx)))

    # chunked SSD scan (prefill path for ssm archs)
    b, S, H, P, N = 2, 512, 4, 16, 8
    xs = jax.random.normal(key, (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.1)
    Bs = jax.random.normal(jax.random.fold_in(key, 3), (b, S, N))
    Cs = jax.random.normal(jax.random.fold_in(key, 4), (b, S, N))
    Dv = jnp.ones((H,))
    f = jax.jit(lambda xs, dt, Bs, Cs: ops.ssd(xs, dt, a, Bs, Cs, Dv,
                                               chunk=128)[0])
    entries.append(pb.entry("ssd_chunked", f"S={S},H={H},P={P},N={N}",
                            **pb.timed_stats(f, xs, dt, Bs, Cs, iters=10)))
    return entries


def telemetry_run(out_dir, report=print):
    """Export CI telemetry artifacts for the kernel suite: one Chrome-trace
    span around the baseline collection with an instant marker per entry, and
    a metric-registry snapshot of every entry's timing/throughput numbers."""
    import json

    from repro.telemetry import Telemetry
    from repro.telemetry.tracing import validate_trace

    os.makedirs(out_dir, exist_ok=True)
    tm = Telemetry(trace=True, out_dir=out_dir)
    tm.name_thread(0, "kernels")
    with tm.span("kernels.collect", cat="bench", tid=0):
        entries = collect()
    for e in entries:
        tm.tracer.instant(f"{e['op']}[{e['shape']}]", cat="bench", tid=0,
                          **e["metrics"])
        tm.registry.absorb(f"bench.{e['op']}.{e['shape']}", e["metrics"])
    doc = tm.tracer.to_doc()
    errors = validate_trace(doc)
    assert not errors, f"exported trace failed validation: {errors}"
    trace_path = os.path.join(out_dir, "kernels_trace.json")
    tm.export_trace(trace_path)
    snap_path = os.path.join(out_dir, "kernels_metrics.json")
    with open(snap_path, "w") as f:
        json.dump(tm.snapshot(), f, indent=1, sort_keys=True)
        f.write("\n")
    report(f"# telemetry artifacts: {trace_path} ({len(entries)} entries), "
           f"{snap_path}")
    return 0


def main(argv=None) -> int:
    enable_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--telemetry-out" in argv:
        i = argv.index("--telemetry-out")
        return telemetry_run(argv[i + 1])
    return pb.run_cli(argv, collect=collect, baseline_name="BENCH_kernels.json",
                      meta={"suite": "kernels_bench", "device":
                            jax.devices()[0].platform})


if __name__ == "__main__":
    sys.exit(main())
