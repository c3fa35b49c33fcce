"""Runtime substrate: checkpoint/restore (atomic, async, elastic), train-loop
restart-resume, watchdog straggler detection, serving engine (continuous
batching + multi-LoRA)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ColaConfig
from repro.core import gl
from repro.core.session import ColaSession
from repro.data.pipeline import ByteCorpus, SyntheticLM
from repro.models import model as M
from repro.optim import optimizers as opt
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.serve_loop import Request, ServeEngine, stack_user_adapters
from repro.runtime.train_loop import TrainLoop
from repro.runtime.watchdog import Watchdog


def _tiny():
    cfg = registry.reduced_config("smollm-135m").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, vocab_size=128)
    key = jax.random.PRNGKey(0)
    return cfg, M.init(cfg, key), key


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_retention(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": {"w": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3)},
            "step": jnp.asarray(7)}
    for s in (1, 2, 3):
        cm.save(s, tree)
    assert cm.steps() == [2, 3]
    step, back = cm.restore()
    assert step == 3
    np.testing.assert_array_equal(np.asarray(back["a"]["w"], np.float32),
                                  np.asarray(tree["a"]["w"], np.float32))
    assert back["a"]["w"].dtype == np.dtype("bfloat16")


def test_checkpoint_async_and_atomic(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=3)
    tree = {"w": jnp.ones((128, 128))}
    cm.save_async(10, tree)
    cm.wait()
    assert cm.latest_step() == 10
    assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]


def test_train_loop_restart_resumes(tmp_path):
    cfg, params, key = _tiny()
    data = SyntheticLM(cfg, batch=4, seq=16, seed=3)

    def fresh_session():
        return ColaSession(cfg, ColaConfig(mode="lora", family="lowrank",
                                           taps="qv", rank=4),
                           params, key, optimizer=opt.sgd(0.05))

    # uninterrupted run to 8 steps
    full = TrainLoop(fresh_session(), data, str(tmp_path / "a"), ckpt_every=2)
    full.run(8, resume=False)
    ref_adapters = full.session.adapters

    # interrupted run: 4 steps, then a new process resumes to 8
    loop1 = TrainLoop(fresh_session(), data, str(tmp_path / "b"), ckpt_every=2)
    loop1.run(4, resume=False)
    loop2 = TrainLoop(fresh_session(), data, str(tmp_path / "b"), ckpt_every=2)
    out = loop2.run(8, resume=True)
    assert loop2.session.step_count == 8
    for a, b in zip(jax.tree.leaves(ref_adapters),
                    jax.tree.leaves(loop2.session.adapters)):
        # trajectories agree to optimizer-noise level (XLA CPU reductions are
        # not bitwise deterministic across separate jit instances)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_elastic_restore_new_topology(tmp_path):
    """Checkpoints are topology-free: arrays restore under any sharding."""
    cm = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(64.0).reshape(8, 8)}
    cm.save(1, tree)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(1, 1)
    sh = {"w": NamedSharding(mesh, P("data", "model"))}
    _, back = cm.restore(shardings=sh)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_flags_stragglers():
    events = []
    wd = Watchdog(threshold=3.0, on_straggler=lambda *a: events.append(a))
    import time
    for step in range(12):
        wd.start_step()
        time.sleep(0.001)
        wd.end_step(step)
    wd.start_step()
    time.sleep(0.05)
    wd.end_step(99)
    assert wd.stragglers and wd.stragglers[-1][0] == 99
    assert events


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_restartable():
    cfg, _, _ = _tiny()
    d1 = SyntheticLM(cfg, batch=4, seq=16, seed=5)
    d2 = SyntheticLM(cfg, batch=4, seq=16, seed=5)
    b1, b2 = d1.batch_at(17), d2.batch_at(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = d1.batch_at(18)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_byte_corpus(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_bytes(b"hello world, this is a tiny corpus for byte-level lm " * 20)
    d = ByteCorpus(str(p), batch=2, seq=32, seed=0)
    b = d.batch_at(0)
    assert b["tokens"].shape == (2, 32)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def test_serve_engine_continuous_batching():
    cfg, params, key = _tiny()
    eng = ServeEngine(cfg, params, slots=2, max_len=64)
    for rid in range(4):
        eng.submit(Request(rid=rid, user=0,
                           prompt=np.arange(3 + rid) % cfg.vocab_size,
                           max_new=4))
    eng.run_until_idle()
    assert eng.stats["completed"] == 4
    assert eng.stats["tokens"] >= 16


def test_serve_engine_multi_user_adapters_route_correctly():
    """Two users with very different adapters must get different outputs, and
    each must match the single-user merged model."""
    cfg, params, key = _tiny()
    cc = ColaConfig(mode="lora", family="lowrank", taps="qv", rank=4)
    ad0 = gl.init_adapters(cfg, cc, jax.random.fold_in(key, 1))
    ad1 = gl.init_adapters(cfg, cc, jax.random.fold_in(key, 2))
    ad1 = jax.tree.map(lambda a: a + 0.5 * jax.random.normal(
        jax.random.fold_in(key, 3), a.shape), ad1)

    prompt = np.arange(8) % cfg.vocab_size
    outs = {}
    for user, _ in enumerate((ad0, ad1)):
        eng = ServeEngine(cfg, params, slots=2, max_len=64,
                          user_adapters=[ad0, ad1])
        eng.submit(Request(rid=0, user=user, prompt=prompt, max_new=6))
        eng.run_until_idle()
        outs[user] = eng.stats and eng  # keep engine
    # compare against per-user dedicated engines using merged weights
    from repro.core import merge as merge_lib
    spec = gl.make_spec(cfg, cc)
    for user, ad in enumerate((ad0, ad1)):
        merged = merge_lib.merged_params(cfg, params, dict(spec.families), ad,
                                         1.0)
        ref_eng = ServeEngine(cfg, merged, slots=2, max_len=64)
        r = Request(rid=0, user=0, prompt=prompt, max_new=6)
        ref_eng.submit(r)
        ref_eng.run_until_idle()
        ml_eng = ServeEngine(cfg, params, slots=2, max_len=64,
                             user_adapters=[ad0, ad1])
        r2 = Request(rid=0, user=user, prompt=prompt, max_new=6)
        ml_eng.submit(r2)
        ml_eng.run_until_idle()
        assert r2.out == r.out, f"user {user}: multi-lora != merged"


def test_watchdog_end_step_without_start_raises():
    from repro.runtime.watchdog import WatchdogError
    wd = Watchdog()
    with pytest.raises(WatchdogError, match="without a matching start_step"):
        wd.end_step(0)
    # and the error is not an AssertionError (must survive python -O)
    assert not issubclass(WatchdogError, AssertionError)


def test_watchdog_heartbeat_survives_disk_errors(tmp_path):
    """A missed heartbeat (full/read-only/vanished disk) is an observability
    gap, not a training failure: end_step must still return and count the
    failure in stats."""
    good = Watchdog(heartbeat_path=str(tmp_path / "hb.json"))
    good.start_step()
    good.end_step(0)
    assert good.stats == {"steps": 1, "heartbeats": 1,
                          "heartbeat_failures": 0}

    bad = Watchdog(heartbeat_path=str(tmp_path / "no_such_dir" / "hb.json"))
    for step in range(3):
        bad.start_step()
        dt = bad.end_step(step)
        assert dt >= 0.0
    assert bad.stats == {"steps": 3, "heartbeats": 0,
                         "heartbeat_failures": 3}


# ---------------------------------------------------------------------------
# roofline peak table
# ---------------------------------------------------------------------------

def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the table by JAX's device_kind; an unknown kind is an
    error, never a silent v5e default."""
    from repro.analysis import roofline
    pk = roofline.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bw"] == 819e9
    terms = roofline.roofline_terms(
        {"flops": 197e12, "bytes_accessed": 819e9 / 2, "collective_bytes": 0},
        "TPU v5 lite")
    assert terms["t_compute"] == 1.0 and terms["bottleneck"] == "compute"
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("cpu")
