"""Smoke run of ColA's main path on one TPU chip, at smollm-135m's full
published width and depth (30 layers, d_model 576, 9 heads over 3 KV heads of
64, d_ff 1536, vocab 49152, bf16) with random weights made from ``--seed``.

Phases, in order; each prints its own lines and any failed check stops the
run with a non-zero exit:

  device  platform, kind and count. Anything but a TPU stops the run.
  serve   ``ServeEngine`` with 4 users' adapters, chunked prefill and paged
          KV answers 16 seeded requests (prompts of 64-512 tokens, 32 new
          tokens each). Checked against a plain float32 forward on the chip.
  train   ``ColaSession`` in faithful_offload (Mode A) and fused_fit (Mode B),
          a few steps each through ``TrainLoop``; Mode A matches Mode B on
          the first step (Prop. 1).
  kernels every op of those paths ran its Pallas kernel: a fallback to the
          jnp oracle fails the run, except the known gap below.

With ``--four-chips`` only this phase runs instead: the sharded ColA train
step (``distributed/steps.py``) on a 2x2 mesh of four chips, against
``gl.train_step_b`` on one chip, on loss and gradients, both in float32.

Set-up time (compilation, warm-up) is printed apart from the steady time.
The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` if set, else
``<repo>/.jax_cache``. Everything the run writes goes under ``--out``. The
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py [--out DIR] [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# --- tolerances ------------------------------------------------------------
# Serving vs the float32 reference: the system stores weights, activations and
# the KV cache in bf16 (unit roundoff 2^-8) through 30 residual layers, so
# its logits carry a few percent of the logit range in rounding. A wrong
# kernel (masking, head mapping, adapter routing) is off by the whole range.
LOGIT_TOL = 0.05          # max |bf16 logits - f32 logits| / max |f32 logits|
# An engine token is consistent with the reference when its reference logit
# is within 2 * LOGIT_TOL of the row max: the engine's argmax over logits that
# are each within LOGIT_TOL of the reference can lose at most that much.
# Mode A vs Mode B (Prop. 1): the same bf16 math, reassociated (Mode A's fit
# runs in float32 on the host from the server's bf16 activations); a wrong
# transpose, scale or tap is off by O(1).
LOSS_RTOL = 1e-2          # |loss_a - loss_b| / |loss_b|
GRAD_RTOL = 5e-2          # ||g_a - g_b|| / ||g_b|| per adapter leaf
# Sharded vs one chip runs in float32 with float32 matmuls, so that only the
# order of the cross-chip sums differs: distribution must not change the
# math, and bf16 rounding would hide a sharding error of a few percent.
SHARD_LOSS_RTOL = 1e-4
SHARD_GRAD_RTOL = 1e-3


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, phase: str, msg: str) -> None:
    """Print the check and stop the run with exit code 1 when it fails."""
    log(phase, f"{'ok' if ok else 'FAIL'}: {msg}")
    if not ok:
        sys.exit(1)


def rel_l2(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def perturbed_adapters(cfg, key, sigma=0.01):
    """One user's qv low-rank adapters with B drawn from N(0, sigma^2)
    instead of LoRA's zeros, so the adapter path changes the outputs and
    both of its gradients are non-zero."""
    import jax
    from repro.configs.base import ColaConfig
    from repro.core import gl
    cc = ColaConfig(mode="lora", family="lowrank", taps="qv", rank=8)
    ad = gl.init_adapters(cfg, cc, key)
    kb = jax.random.fold_in(key, 1)
    return {tap: {"A": w["A"], "B": sigma * jax.random.normal(
        kb, w["B"].shape, w["B"].dtype)} for tap, w in ad.items()}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_phase(cfg, params, *, seed: int, n_requests: int = 16,
                users: int = 4, prompt_lens=(64, 512), max_new: int = 32,
                slots: int = 16, chunk: int = 128) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.models import model as M
    from repro.runtime.serve_loop import (Request, ServeEngine,
                                          stack_user_adapters)
    P = "serve"
    key = jax.random.PRNGKey(seed)
    banks = [perturbed_adapters(cfg, jax.random.fold_in(key, 100 + u))
             for u in range(users)]
    max_len = prompt_lens[1] + max_new + chunk
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, size=n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n), dtype=np.int32)
               for n in lens]

    t0 = time.perf_counter()
    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                      user_adapters=banks, prefill_chunk=chunk,
                      kv_layout="paged")
    # warm-up: one request through a chunk round and decode ticks compiles
    # every shape the engine uses (chunk width and decode batch are fixed)
    eng.submit(Request(rid=-1, user=0, prompt=prompts[0][:chunk + 1],
                       max_new=2))
    eng.run_until_idle()
    setup_s = time.perf_counter() - t0
    log(P, f"set-up (engine build + compile + warm-up): {setup_s:.3f} s")

    reqs = [Request(rid=i, user=i % users, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    steady_s = time.perf_counter() - t0
    n_tok = sum(len(r.out) for r in reqs)
    log(P, f"steady: {n_requests} requests, prompts {int(lens.min())}-"
           f"{int(lens.max())} tokens ({int(lens.sum())} in all), {n_tok} "
           f"new tokens in {steady_s:.3f} s; engine stats: ticks="
           f"{eng.stats['ticks']} chunk_rounds={eng.stats['chunk_rounds']} "
           f"kv_blocks_peak={eng.stats['kv_blocks_peak']}")
    check(all(r.status == "done" and len(r.out) == max_new for r in reqs), P,
          f"all {n_requests} requests completed with {max_new} tokens")
    toks = np.array([r.out for r in reqs])
    check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()), P,
          f"every token is in the vocabulary [0, {cfg.vocab_size})")

    # Teacher-forced forward over prompt + generated tokens: the logits at
    # positions P-1 .. P+max_new-2 predict the engine's max_new tokens. Rows
    # are padded to one width (causal: the tail does not reach them).
    width = -(-(int(lens.max()) + max_new) // 64) * 64
    seqs = np.zeros((n_requests, width), np.int32)
    gather = np.zeros((n_requests, max_new), np.int32)
    for i, r in enumerate(reqs):
        s = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        seqs[i, :len(s)] = s
        gather[i] = len(r.prompt) - 1 + np.arange(max_new)
    bank = stack_user_adapters(banks)
    user_ids = jnp.asarray([r.user for r in reqs], jnp.int32)

    def logits_at(cfg_, params_, bank_, tokens, users_, gather_):
        vars_ = {tap: dict(leaves, idx=jnp.broadcast_to(
            users_, (leaves["A"].shape[0],) + users_.shape))
            for tap, leaves in bank_.items()}
        h, _ = M.hidden_states(cfg_, params_, {"tokens": tokens}, eng.spec,
                               {"adapters": vars_})
        h = jnp.take_along_axis(h, gather_[..., None], axis=1)
        return M.head_logits(cfg_, params_, h).astype(jnp.float32)

    args = (jnp.asarray(seqs), user_ids, jnp.asarray(gather))
    # the system's own forward: bf16, kernels chosen by the platform
    sys_logits = jax.jit(functools.partial(logits_at, cfg))(params, bank,
                                                            *args)
    # the plain reference: float32 weights and math, jnp oracles only
    ref_cfg = cfg.replace(param_dtype="float32", compute_dtype="float32")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    ops.set_backend("ref")
    try:
        with jax.default_matmul_precision("highest"):
            ref_logits = jax.jit(functools.partial(logits_at, ref_cfg))(
                params32, bank, *args)
            ref_logits.block_until_ready()
    finally:
        ops.set_backend(None)
    sys_l, ref_l = np.asarray(sys_logits), np.asarray(ref_logits)
    check(bool(np.isfinite(sys_l).all()), P, "forward logits are finite")
    scale = float(np.abs(ref_l).max())
    err = float(np.abs(sys_l - ref_l).max()) / scale
    check(err <= LOGIT_TOL, P,
          f"bf16 forward vs f32 reference: max |dlogit| / max |logit| = "
          f"{err:.6f} <= {LOGIT_TOL} (max |logit| {scale:.4f})")
    picked = np.take_along_axis(ref_l, toks[..., None], axis=-1)[..., 0]
    regret = (ref_l.max(axis=-1) - picked) / scale
    agree0 = int((ref_l[:, 0].argmax(-1) == toks[:, 0]).sum())
    agree = float((ref_l.argmax(-1) == toks).mean())
    check(float(regret[:, 0].max()) <= 2 * LOGIT_TOL, P,
          f"engine first tokens vs f32 reference logits: max regret "
          f"{float(regret[:, 0].max()):.6f} <= {2 * LOGIT_TOL} of the range "
          f"(argmax agrees on {agree0}/{n_requests})")
    check(float(regret.max()) <= 2 * LOGIT_TOL, P,
          f"all {n_tok} engine tokens vs f32 reference: max regret "
          f"{float(regret.max()):.6f} (argmax agrees on {agree:.4f})")
    return {"setup_s": setup_s, "steady_s": steady_s, "requests": n_requests,
            "prompt_tokens": int(lens.sum()), "new_tokens": n_tok,
            "logit_err": err, "max_regret": float(regret.max()),
            "first_token_argmax_agree": agree0, "argmax_agree": agree}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _where(tree) -> str:
    import jax
    return ",".join(sorted({d.platform for leaf in jax.tree.leaves(tree)
                            for d in leaf.devices()}))


def train_phase(cfg, params, *, seed: int, out_dir: str, batch: int = 4,
                seq: int = 512, steps: int = 3) -> dict:
    import jax
    import numpy as np
    from repro.configs.base import ColaConfig
    from repro.core import gl
    from repro.core.session import ColaSession
    from repro.data.pipeline import SyntheticLM
    from repro.optim import optimizers as optim
    from repro.runtime.train_loop import TrainLoop
    P = "train"
    data = SyntheticLM(cfg, batch=batch, seq=seq, seed=seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    out: dict = {}
    sessions = {}
    for mode in ("faithful_offload", "fused_fit"):
        cc = ColaConfig(mode=mode, family="lowrank", rank=8, taps="qv")
        sess = ColaSession(cfg, cc, params, key, optimizer=optim.sgd(1e-2))
        sessions[mode] = sess
        workdir = os.path.join(out_dir, f"train_{mode}")
        shutil.rmtree(workdir, ignore_errors=True)
        loop = TrainLoop(sess, data, workdir, ckpt_every=steps, log_every=1)
        loop.run(steps, resume=False)
        dts = list(loop.watchdog.durations)   # per step, ends on float(loss)
        setup_s, steady_s = dts[0], sum(dts[1:])
        log(P, f"{mode}: set-up (compile + step 1) {setup_s:.6f} s; steps "
               f"2-{steps} {steady_s:.6f} s ({batch * seq * (steps - 1)} "
               f"tokens); losses {loop.losses}")
        server = _where((sess.base_params, sess.adapters))
        where = (f"server step on {server} (weights and adapters); adapter "
                 f"fit and optimizer on {sess.offloader.device.platform} "
                 "(the offload device, by design)")
        log(P, f"{mode}: {where}")
        home = jax.devices()[0].platform
        check(server == home, P, f"{mode}: after {steps} offload rounds the "
              f"server step's weights and adapters are still on {home}")
        check(len(loop.losses) == steps and bool(np.isfinite(
            loop.losses).all()), P, f"{mode}: {steps} finite losses")
        out[mode] = {"setup_s": setup_s, "step_s": dts[1:],
                     "losses": loop.losses, "where": where}

    # Prop. 1 on the first step, from adapters whose B is not zero (so both
    # gradients are non-trivial): Mode A's offloaded fit of the server's
    # (x, grad_h) equals Mode B's in-graph adapter gradient.
    sa, sb = sessions["faithful_offload"], sessions["fused_fit"]
    ad0 = perturbed_adapters(cfg, key)
    b0 = data.batch_at(0)
    loss_a, adapt, _ = jax.jit(functools.partial(
        gl.server_step_a, cfg, sa.server_spec))(params, ad0, b0)
    off = sa.offloader.device
    grads_a = jax.jit(functools.partial(gl.fit_grads, sa.adapter_spec))(
        jax.device_put(ad0, off), jax.device_put(adapt, off))
    loss_b, grads_b, _ = jax.jit(functools.partial(
        gl.train_step_b, cfg, sb.server_spec))(params, ad0, b0)
    dl = abs(float(loss_a) - float(loss_b)) / abs(float(loss_b))
    check(dl <= LOSS_RTOL, P, f"Prop. 1 first step: loss A {float(loss_a):.6f}"
          f" vs B {float(loss_b):.6f}, rel {dl:.2e} <= {LOSS_RTOL}")
    errs = {f"{tap}.{n}": rel_l2(grads_a[tap][n], grads_b[tap][n])
            for tap in grads_b for n in grads_b[tap]}
    worst = max(errs, key=errs.get)
    check(errs[worst] <= GRAD_RTOL, P,
          f"Prop. 1 first step: adapter grads A (fit on {off.platform}) vs B "
          f"(in-graph on {_where(grads_b)}), worst rel-L2 {errs[worst]:.2e} "
          f"({worst}) <= {GRAD_RTOL}")
    out["prop1"] = {"loss_rel": dl, "grad_rel_l2": errs}
    return out


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chip_phase(cfg, params, *, seed: int, batch: int = 8,
                    seq: int = 512) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ColaConfig
    from repro.core import gl
    from repro.data.pipeline import SyntheticLM
    from repro.distributed import sharding as sh
    from repro.distributed import steps
    from repro.launch.mesh import make_mesh
    P = "four-chips"
    devs = jax.devices()
    check(len(devs) == 4, P, f"{len(devs)} devices (need 4)")
    cfg = cfg.replace(param_dtype="float32", compute_dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    cc = ColaConfig(mode="fused_fit", family="lowrank", rank=8, taps="qv")
    spec = gl.make_spec(cfg, cc)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    ad0 = perturbed_adapters(cfg, key)
    b0 = SyntheticLM(cfg, batch=batch, seq=seq, seed=seed).batch_at(0)
    log(P, f"float32 weights and matmuls; batch {batch} x {seq} tokens")

    with jax.default_matmul_precision("highest"):
        one = jax.device_put((params, ad0, b0), devs[0])
        t0 = time.perf_counter()
        loss1, g1, _ = jax.jit(functools.partial(
            gl.train_step_b, cfg, spec))(*one)
        loss1.block_until_ready()
        log(P, f"one chip ({devs[0]}): gl.train_step_b loss "
               f"{float(loss1):.6f} ({time.perf_counter() - t0:.3f} s with "
               "compile)")
        mesh = make_mesh(2, 2)
        with mesh:
            fn, (ps, ash, _), _ = steps.make_train_step(cfg, cc, mesh)
            bs = sh.batch_shardings(mesh, b0)
            placed = (jax.device_put(params, ps), jax.device_put(ad0, ash),
                      jax.device_put(b0, bs))
            t0 = time.perf_counter()
            loss4, g4 = jax.jit(fn, in_shardings=(ps, ash, bs))(*placed)
            loss4.block_until_ready()
    log(P, f"mesh {dict(mesh.shape)}: sharded train step loss "
           f"{float(loss4):.6f} ({time.perf_counter() - t0:.3f} s with "
           "compile)")
    dl = abs(float(loss4) - float(loss1)) / abs(float(loss1))
    check(dl <= SHARD_LOSS_RTOL, P, f"loss rel {dl:.2e} <= {SHARD_LOSS_RTOL}")
    errs = {f"{tap}.{n}": rel_l2(g4[tap][n], g1[tap][n])
            for tap in g1 for n in g1[tap]}
    worst = max(errs, key=errs.get)
    check(errs[worst] <= SHARD_GRAD_RTOL, P, f"adapter grads worst rel-L2 "
          f"{errs[worst]:.2e} ({worst}) <= {SHARD_GRAD_RTOL}")
    return {"loss_one_chip": float(loss1), "loss_sharded": float(loss4),
            "loss_rel": dl, "grad_rel_l2": errs}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernel_phase() -> dict:
    """Fail on any kernel-to-oracle fallback the run traced, except the known
    gap: a prefill chunk (Sq > 1) over the dense cache layout has no kernel
    (the paged layout's has one)."""
    from repro.kernels import ops
    P = "kernels"
    known, unexpected = [], []
    for (op, shapes), n in sorted(ops.fallbacks().items()):
        chunk = op == "decode_attention" and shapes[0][1][1] > 1
        (known if chunk else unexpected).append(f"{op} {shapes} x{n}")
    for k in known:
        log(P, f"known gap (dense-cache chunk attention has no kernel): {k}")
    for u in unexpected:
        log(P, f"unexpected fallback: {u}")
    check(not unexpected, P, f"{len(unexpected)} unexpected kernel fallbacks")
    return {"known_gaps": known, "unexpected": unexpected}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "smoke_out"),
                    help="directory for everything the run writes")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on four chips")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"[device] FAIL: no ColA checkout next to {__file__} "
              "(src/repro is missing)", flush=True)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.makedirs(args.out, exist_ok=True)
    # the TPU runtime's own logs go with the run's output, not to /tmp
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(args.out, "tpu_logs"))
    import jax
    from repro.kernels import ops
    from repro.models import model as M
    from repro.configs import registry
    from repro.utils import enable_compile_cache

    t_start = time.perf_counter()
    cache = enable_compile_cache()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log("device", f"platform={dev['platform']} kind={dev['kind']} "
                  f"count={dev['count']}; compile cache {cache}; kernels "
                  f"chosen by {ops.get_backend()}")
    if dev["platform"] != "tpu":
        log("device", f"FAIL: no TPU found (JAX sees {dev['platform']})")
        return 1

    cfg = registry.get_config("smollm-135m")
    log("device", f"config {cfg.name}: {cfg.n_layers}L d_model={cfg.d_model} "
                  f"{cfg.n_heads}H/{cfg.n_kv_heads}KV d_head={cfg.d_head} "
                  f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
                  f"{cfg.param_dtype}; random weights, seed {args.seed}")
    t0 = time.perf_counter()
    params = jax.jit(functools.partial(M.init, cfg))(
        jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    log("device", f"weights made in {time.perf_counter() - t0:.3f} s")

    summary: dict = {"device": dev, "seed": args.seed, "config": cfg.name}
    if args.four_chips:
        summary["four_chips"] = four_chip_phase(cfg, params, seed=args.seed)
    else:
        summary["serve"] = serve_phase(cfg, params, seed=args.seed)
        summary["train"] = train_phase(cfg, params, seed=args.seed,
                                       out_dir=args.out)
        summary["kernels"] = kernel_phase()
    summary["wall_s"] = time.perf_counter() - t_start
    name = "four_chips.json" if args.four_chips else "smoke.json"
    with open(os.path.join(args.out, name), "w") as f:
        json.dump(summary, f, indent=1, default=float)
    log("done", f"wall {summary['wall_s']:.3f} s; summary in "
                f"{os.path.join(args.out, name)}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
