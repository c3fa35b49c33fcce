"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell on
the production meshes and record memory/cost analysis + collective bytes.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single --out out.json
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

The XLA_FLAGS lines below MUST run before any other import (jax locks the
device count on first init); nothing else in the package sets it.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis import roofline
from repro.configs import registry
from repro.configs.base import ColaConfig
from repro.distributed import sharding as sh
from repro.distributed import steps
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib

# the chip the dry-run's roofline terms are priced against (roofline.PEAKS)
TARGET_KIND = "TPU v5 lite"


def _compile_cell(cfg, spec, mesh, cc):
    if spec.kind == "train":
        batch = registry.batch_specs(cfg, spec.batch, spec.seq)
        bs = sh.batch_shardings(mesh, batch, policy=cfg.shard_policy)
        params = steps.shaped_params(cfg)
        if cc.mode == "ft":
            fn, (ps, _), _ = steps.make_train_step(cfg, cc, mesh)
            jitted = jax.jit(fn, in_shardings=(ps, bs))
            lowered = jitted.lower(params, batch)
        else:
            fn, (ps, ash, _), _ = steps.make_train_step(cfg, cc, mesh)
            adapters = steps.shaped_adapters(cfg, cc)
            jitted = jax.jit(fn, in_shardings=(ps, ash, bs))
            lowered = jitted.lower(params, adapters, batch)
    elif spec.kind == "prefill":
        fn, ps = steps.make_prefill_step(cfg, mesh)
        batch = registry.batch_specs(cfg, spec.batch, spec.seq)
        bs = sh.batch_shardings(mesh, batch)
        params = steps.shaped_params(cfg)
        outs = steps.prefill_out_shardings(cfg, mesh, spec.batch, spec.seq)
        jitted = jax.jit(fn, in_shardings=(ps, bs), out_shardings=outs)
        lowered = jitted.lower(params, batch)
    else:  # decode
        fn, ps = steps.make_serve_step(cfg, mesh)
        cache = model_lib.cache_specs(cfg, spec.batch, spec.seq)
        cache_sh, tok_sh = steps.serve_shardings(cfg, mesh, spec.batch,
                                                 spec.seq)
        batch = registry.decode_token_specs(cfg, spec.batch)
        params = steps.shaped_params(cfg)
        # out_shardings must match the donated cache input for buffer aliasing
        out_tok = sh.batch_shardings(
            mesh, jax.eval_shape(
                lambda: jnp.zeros((spec.batch, 1)
                                  + ((cfg.n_codebooks,) if cfg.n_codebooks
                                     else ()), jnp.int32)))
        jitted = jax.jit(fn, in_shardings=(ps, cache_sh, tok_sh),
                         out_shardings=(out_tok, cache_sh),
                         donate_argnums=(1,))
        lowered = jitted.lower(params, cache, batch)
    return lowered.compile()


def _extrapolated_costs(cfg, spec, mesh, cc):
    """Exact HLO cost totals via two-point layer extrapolation.

    XLA's cost_analysis counts a while-loop body ONCE (calibrated), and fully
    unrolling the production configs is prohibitively slow to compile. Layer
    stacks are homogeneous, so costs are affine in the layer count: compile
    the cell at n1 and n2 layers with every *inner* scan unrolled (cheap at
    1-2 layers), and extrapolate  total = f(n1) + (units-1) * (f(n2)-f(n1)).
    Microbatching is disabled for the cost compile (same total FLOPs; the
    accumulation adds are negligible). loss_chunk likewise.
    """
    from repro import flags as repro_flags
    plan = model_lib.layer_plan(cfg)
    if plan[0] == "pairs":
        n1, n2, units = 2, 4, cfg.n_layers / 2
    elif plan[0] == "hybrid":
        e = cfg.shared_attn_every
        n1, n2, units = e, 2 * e, cfg.n_layers / e
    else:
        n1, n2, units = 1, 2, cfg.n_layers

    # The cost compile runs in f32: XLA CPU emulates bf16 dots via hoisted f32
    # converts, which would pollute byte/collective counts with traffic that
    # does not exist on TPU. f32 is native on CPU; bytes and collective bytes
    # are then halved to model bf16 TPU execution. FLOPs are dtype-independent.
    dt = cfg.compute_dtype
    scale_bytes = 0.5 if dt in ("bfloat16", "bf16", "float16") else 1.0
    keys = ("flops", "bytes accessed", "collective")

    def costs_at(n_layers: int, seq: int) -> dict:
        c = cfg.replace(n_layers=n_layers, microbatches=1, loss_chunk=0,
                        param_dtype="float32", compute_dtype="float32")
        s = dataclasses.replace(spec, seq=seq)
        with repro_flags.override(unroll_scans=True), mesh:
            comp = _compile_cell(c, s, mesh, cc)
        ca = comp.cost_analysis()
        return {
            "flops": ca.get("flops", 0.0),
            "bytes accessed": scale_bytes * ca.get("bytes accessed", 0.0),
            "collective": scale_bytes * roofline.collective_bytes(
                comp.as_text()),
        }

    def layer_extrapolated(seq: int) -> dict:
        f1, f2 = costs_at(n1, seq), costs_at(n2, seq)
        return {k: f1[k] + (units - 1.0) * (f2[k] - f1[k]) for k in keys}

    # Every cost is a polynomial of degree <=2 in the sequence length
    # (attention S^2; SSD chunks, conv, projections, dispatch: linear).
    # Unrolling inner scans at long S explodes compile time, so long-seq (and
    # SSD-heavy) cells are fit with a polynomial in S and evaluated at the
    # target — exact for polynomial scaling. Local-window attention changes
    # the polynomial at S=window, so the fit points sit above the window.
    # Pure-SSM archs are exactly linear in S; decode is linear in cache len.
    if cfg.family == "ssm":
        deg, pts = 1, [512, 1024]
    elif cfg.family == "hybrid":
        deg, pts = 2, [512, 768, 1024]
    elif spec.kind == "decode":
        deg, pts = 1, [2048, 4096]
    else:
        base = 2048
        if cfg.attn_pattern == "local_global":
            base = max(base, 2 * cfg.local_window)
        deg, pts = 2, [base, base + base // 2, 2 * base]
    if spec.seq <= max(pts) or (spec.seq <= 8192 and cfg.family not in
                                ("ssm", "hybrid")):
        out = layer_extrapolated(spec.seq)
        return out, out.pop("collective")
    vals = [layer_extrapolated(s) for s in pts]
    import numpy as _np
    out = {}
    for k in keys:
        coef = _np.polyfit(_np.array(pts, float),
                           _np.array([v[k] for v in vals], float), deg)
        out[k] = float(_np.polyval(coef, float(spec.seq)))
    return out, out.pop("collective")


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               cola_mode: str = "fused_fit", overrides: dict | None = None,
               verbose: bool = True, cost_pass: bool = True) -> dict:
    """Lower+compile one (arch, shape) cell; return the §Dry-run/§Roofline record.

    Two compiles per cell:
    - memory pass: scans rolled (realistic schedule) -> memory_analysis.
    - cost pass: scans unrolled -> exact HLO_FLOPs / bytes / collective totals
      (XLA cost_analysis counts loop bodies once; see repro.flags).
    """
    cfg = registry.get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    spec = registry.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cc = ColaConfig(mode=cola_mode, family="lowrank", taps="qv", rank=16)

    t0 = time.time()
    with mesh:
        compiled = _compile_cell(cfg, spec, mesh, cc)
    t1 = time.time()
    mem = compiled.memory_analysis()
    # XLA *CPU* emulates bf16 dots by hoisting f32 converts of the bf16
    # operands (weight stacks, KV caches) out of the layer loop — persistent
    # f32 shadow copies that do not exist on TPU (native bf16 MXU). The shadow
    # is 2x the bf16 argument bytes; report a TPU-representative corrected
    # peak alongside the raw CPU number. (Verified against the buffer
    # assignment: e.g. decode_32k mistral-large carries two
    # f32[88,8,2048,8,128] copies of the bf16 KV cache.)
    emu = 2 * int(getattr(mem, "argument_size_in_bytes", 0) or 0)

    if cost_pass:
        cost, coll = _extrapolated_costs(cfg, spec, mesh, cc)
    else:
        cost = compiled.cost_analysis()
        coll = roofline.collective_bytes(compiled.as_text())
    t2 = time.time()

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "mode": cola_mode,
        "kind": spec.kind,
        "compile_s": round(t1 - t0, 1),
        "cost_compile_s": round(t2 - t1, 1),
        "memory": roofline.memory_record(mem),
        "cpu_bf16_emulation_bytes": emu,
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": coll,
        "devices": mesh.devices.size,
        "exact_costs": bool(cost_pass),
    }
    peak = rec["memory"].get("peak_bytes_per_device", 0)
    rec["memory"]["peak_corrected_tpu"] = max(0, peak - emu)
    rec.update(roofline.roofline_terms(rec, TARGET_KIND))
    rec["model_flops"] = roofline.model_flops(cfg, spec)
    # cost_analysis flops are per-device; model_flops is global
    rec["useful_ratio"] = (rec["model_flops"] / (rec["flops"] * rec["devices"])
                           if rec["flops"] else 0.0)
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} ({rec['mesh']}, {cola_mode}) "
              f"compiled in {rec['compile_s']}s")
        print("  memory_analysis:", json.dumps(rec["memory"]))
        print(f"  flops={rec['flops']:.3e} bytes={rec['bytes_accessed']:.3e} "
              f"collective={rec['collective_bytes']:.3e}")
        print(f"  terms(s): compute={rec['t_compute']:.4e} "
              f"memory={rec['t_memory']:.4e} collective={rec['t_collective']:.4e}"
              f" -> bottleneck={rec['bottleneck']}")
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    p.add_argument("--mode", default="fused_fit",
                   choices=["fused_fit", "faithful_offload", "ft", "frozen"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--out", default=None, help="append JSON records to file")
    p.add_argument("--no-cost-pass", action="store_true",
                   help="skip the unrolled cost compile (fast; approx costs)")
    p.add_argument("--override", default=None,
                   help="comma k=v model-config overrides (ints/floats/strs)")
    p.add_argument("--skip-done", action="store_true",
                   help="skip cells already present in --out")
    args = p.parse_args(argv)

    overrides = {}
    if args.override:
        for kv in args.override.split(","):
            k, v = kv.split("=")
            try:
                overrides[k] = int(v)
            except ValueError:
                try:
                    overrides[k] = float(v)
                except ValueError:
                    overrides[k] = v

    cells: list[tuple[str, str]]
    if args.all:
        cells = registry.all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    done = set()
    if args.skip_done and args.out and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass
    records, failures = [], []
    for arch, shape in cells:
        for mp in meshes:
            if (arch, shape, "pod2x16x16" if mp else "pod16x16") in done:
                continue
            try:
                rec = lower_cell(arch, shape, multi_pod=mp, cola_mode=args.mode,
                                 overrides=overrides or None,
                                 cost_pass=not args.no_cost_pass)
                records.append(rec)
                if args.out:   # flush per cell (crash-safe)
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
            except Exception as e:  # noqa: BLE001 — report every cell
                traceback.print_exc()
                failures.append({"arch": arch, "shape": shape,
                                 "multi_pod": mp, "error": repr(e)})
    if args.out and failures:
        with open(args.out + ".failures", "a") as f:
            for r in failures:
                f.write(json.dumps(r) + "\n")
    print(f"\n[dryrun] {len(records)} cells OK, {len(failures)} failed")
    for f_ in failures:
        print("  FAILED:", f_)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
