"""Distribution layer tests. Multi-device cases run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (jax locks the device count
at first init, so the main test process stays single-device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """The pjit train step on a (2,4) mesh computes the same loss/grads as the
    single-device step — distribution never changes semantics."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import registry
        from repro.configs.base import ColaConfig
        from repro.core import gl
        from repro.distributed import sharding as sh, steps
        from repro.launch.mesh import make_mesh
        from repro.models import model as M

        cfg = registry.reduced_config('smollm-135m').replace(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
            d_ff=128, vocab_size=128)
        key = jax.random.PRNGKey(0)
        params = M.init(cfg, key)
        cc = ColaConfig(mode='fused_fit', family='lowrank', taps='qv', rank=4)
        adapters = gl.init_adapters(cfg, cc, key)
        batch = {'tokens': jax.random.randint(key, (8, 16), 0, 128),
                 'labels': jax.random.randint(key, (8, 16), 0, 128)}
        spec = gl.make_spec(cfg, cc)
        loss1, g1, _ = gl.train_step_b(cfg, spec, params, adapters, batch)

        mesh = make_mesh(2, 4)
        with mesh:
            fn, (ps, ash, _), _ = steps.make_train_step(cfg, cc, mesh)
            bs = sh.batch_shardings(mesh, jax.eval_shape(lambda: batch))
            jitted = jax.jit(fn, in_shardings=(ps, ash, bs))
            loss2, g2 = jitted(params, adapters, batch)
        assert np.allclose(float(loss1), float(loss2), rtol=1e-5), (loss1, loss2)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=1e-5)
        print('OK devices=', len(jax.devices()))
    """)
    assert "OK devices= 8" in out


def test_multipod_mesh_and_serve_step():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import registry
        from repro.distributed import sharding as sh, steps
        from repro.launch.mesh import make_mesh
        from repro.models import model as M
        mesh = make_mesh(2, 2, pods=2)
        cfg = registry.reduced_config('mistral-nemo-12b').replace(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
            d_ff=128, vocab_size=128)
        params = M.init(cfg, jax.random.PRNGKey(0))
        B, Smax = 8, 32
        cache = M.init_cache(cfg, B, Smax)
        with mesh:
            fn, ps = steps.make_serve_step(cfg, mesh)
            cache_sh, tok_sh = steps.serve_shardings(cfg, mesh, B, Smax)
            jitted = jax.jit(fn, in_shardings=(ps, cache_sh, tok_sh))
            batch = {'tokens': jnp.zeros((B, 1), jnp.int32),
                     'positions': jnp.zeros((B,), jnp.int32)}
            toks, cache2 = jitted(params, cache, batch)
        # single device reference
        logits, cache_ref = M.decode_step(cfg, params, batch, cache)
        ref_toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(ref_toks))
        print('OK multipod')
    """)
    assert "OK multipod" in out


def test_param_shardings_divisibility():
    """Every assigned arch's param sharding rules produce valid shardings on
    the production mesh shape (divisibility-guarded)."""
    out = run_sub("""
        import jax
        from repro.configs import registry
        from repro.distributed import sharding as sh, steps
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(2, 4)
        for arch in registry.ASSIGNED:
            cfg = registry.get_config(arch)
            shapes = steps.shaped_params(cfg)
            shards = sh.params_shardings(mesh, shapes)
            def check(leaf, s):
                for dim, spec in zip(leaf.shape, s.spec):
                    if spec is None:
                        continue
                    axes = (spec,) if isinstance(spec, str) else spec
                    n = 1
                    for a in axes:
                        n *= mesh.shape[a]
                    assert dim % n == 0, (arch, leaf.shape, s.spec)
            jax.tree.map(check, shapes, shards)
        print('OK shardings')
    """)
    assert "OK shardings" in out
