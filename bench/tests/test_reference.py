"""The plain reference agrees with the program's own forward at a reduced
size, with both in float32, while importing nothing of the program."""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench import core, program
from bench.tests.conftest import BENCH, TINY


def tiny_cfg(dtype="float32"):
    c = core.config("smollm-135m")
    c.update(TINY, name="tiny", dtype=dtype, tie_word_embeddings=False)
    return c


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "reference", "dense_gqa.py")
    tree = ast.parse(open(path).read())
    mods = {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    mods |= {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    assert not any(m and m.startswith("repro") for m in mods), mods


def test_reference_matches_program_forward():
    from repro.core import taps as taps_lib
    from repro.kernels import ops
    from repro.models import model as M
    c = tiny_cfg()
    fam = program.family(c)
    cfg = fam.model_config(c)
    params = fam.params(c, 11)
    ad = fam.user_adapters(c, 11, 3, 4)[2]
    spec = taps_lib.make_spec(family="lowrank", taps=tuple(sorted(ad)))
    toks = np.random.default_rng(0).integers(0, 128, size=(1, 40))
    ops.set_backend("ref")
    try:
        with jax.default_matmul_precision("highest"):
            got, _ = M.forward(cfg, params, {"tokens": jnp.asarray(toks)},
                               spec, {"adapters": ad})
    finally:
        ops.set_backend(None)
    ref = core.module("reference", "dense_gqa")
    want = ref.logits(c, 11, [toks[0]], [np.arange(40)], [2], 4)[0]
    got = np.asarray(got[0], np.float64)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_training_reference_matches_program_gradient():
    from repro.configs.base import ColaConfig
    from repro.core import gl
    from repro.kernels import ops
    from bench import seeded
    c = tiny_cfg()
    fam = program.family(c)
    cfg = fam.model_config(c)
    params = fam.params(c, 5)
    cc = ColaConfig(mode="fused_fit", family="lowrank", taps="qv", rank=4)
    ad = gl.init_adapters(cfg, cc, seeded.session_key(5))
    ref = core.module("reference", "dense_gqa")
    want = ref.lora_init(c, seeded.session_key(5), 4)
    for tap in ("q", "v"):
        for n in ("A", "B"):
            assert np.array_equal(np.asarray(ad[f"layers.attn.{tap}"][n]),
                                  np.asarray(want[tap][n]))
    # nonzero B so that both leaves have a gradient
    ad = jax.tree.map(lambda a: a + 0.01, ad)
    rng = np.random.default_rng(1)
    t = rng.integers(0, 128, size=(2, 33))
    batch = {"tokens": jnp.asarray(t[:, :-1]), "labels": jnp.asarray(t[:, 1:])}
    ops.set_backend("ref")
    try:
        with jax.default_matmul_precision("highest"):
            loss, g, _ = gl.train_step_b(cfg, gl.make_spec(cfg, cc), params,
                                         ad, batch)
    finally:
        ops.set_backend(None)
    rad = {tap[-1]: v for tap, v in ad.items()}
    rloss, rg = ref._loss_and_grads(ref._frozen(c), seeded.root_key(5),
                                    "as_stated", rad, batch["tokens"],
                                    batch["labels"])
    assert abs(float(loss) - float(rloss)) <= 1e-5 * abs(float(rloss))
    for tap in ("q", "v"):
        for n in ("A", "B"):
            a = np.asarray(g[f"layers.attn.{tap}"][n])
            b = np.asarray(rg[tap][n])
            assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b)
