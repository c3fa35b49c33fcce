"""A configuration is built through the files its ``family`` names: the
dense GQA family builds what it built before its builder moved into
``bench/families/``, an unknown family and a clash of leaf ids fail with a
clear message, and a family added as new files only runs a serving cell and
reports its counts."""
import hashlib
import json
import os
import re
import time

import jax
import numpy as np
import pytest

from bench import core, program, run, seeded
from bench import trace as tr
from bench.tests.conftest import TINY

SEED, USERS, RANK = 2**31 + 17, 3, 4


def digests(tree) -> dict:
    """{leaf path: "dtype[shape] <first 16 hex digits of its SHA-256>"}."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(a)
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        out[key] = (f"{a.dtype}{list(a.shape)} "
                    + hashlib.sha256(a.tobytes()).hexdigest()[:16])
    return out


# Recorded at commit 538c2c6, where bench/program.py built the dense GQA
# family itself, on the CPU at conftest.py's TINY cut: the ModelConfig (its
# fields that differ from the class's defaults) and every leaf of
# params(c, SEED) and of user_adapters(c, SEED, USERS, RANK), by ``digests``.
PARENT = {
    "mistral-nemo-12b-10l": {
        "model_config": dict(name="mistral-nemo-12b-10l", family="dense",
                             n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
                             d_head=128, d_ff=256, vocab_size=512,
                             rope_theta=1000000.0, tie_embeddings=False),
        "params": {
            "embed/emb": "bfloat16[512, 128] 3b1b2ea8ce036a7b",
            "final_norm/scale": "bfloat16[128] 352320887a5e2e7d",
            "layers/attn/k/w": "bfloat16[4, 128, 256] 4fb0292957e4d993",
            "layers/attn/o/w": "bfloat16[4, 512, 128] ac82d1935b157860",
            "layers/attn/q/w": "bfloat16[4, 128, 512] 5ba85406d32bcdf9",
            "layers/attn/v/w": "bfloat16[4, 128, 256] 442708084baff4db",
            "layers/ln1/scale": "bfloat16[4, 128] cb91fbb36bb65efe",
            "layers/ln2/scale": "bfloat16[4, 128] 85c38a7d5ed7fa67",
            "layers/mlp/down/w": "bfloat16[4, 256, 128] d995a15455c758b1",
            "layers/mlp/gate/w": "bfloat16[4, 128, 256] 204c3e6a315a44f9",
            "layers/mlp/up/w": "bfloat16[4, 128, 256] 43cb835f80ee3fc3",
            "lm_head/w": "bfloat16[128, 512] 10da90776843cc6d",
        },
        "adapters": {
            "0/layers.attn.q/A": "float32[4, 128, 4] 4f70de7c16d64631",
            "0/layers.attn.q/B": "float32[4, 4, 512] d66e1fd22e141ad3",
            "0/layers.attn.v/A": "float32[4, 128, 4] 7dddd3e3e8fc3627",
            "0/layers.attn.v/B": "float32[4, 4, 256] ca7a9488defaaf5e",
            "1/layers.attn.q/A": "float32[4, 128, 4] cb9cb617153b0b11",
            "1/layers.attn.q/B": "float32[4, 4, 512] 4763c4f246e80142",
            "1/layers.attn.v/A": "float32[4, 128, 4] 344418b1d94cf6c3",
            "1/layers.attn.v/B": "float32[4, 4, 256] 12c2381cbbaac1c7",
            "2/layers.attn.q/A": "float32[4, 128, 4] 4eb4113c3c11f211",
            "2/layers.attn.q/B": "float32[4, 4, 512] a9deb82f48311995",
            "2/layers.attn.v/A": "float32[4, 128, 4] 1b5aa2be47fcffae",
            "2/layers.attn.v/B": "float32[4, 4, 256] a63a8ead9b9b757b",
        },
    },
    "smollm-135m": {
        "model_config": dict(name="smollm-135m", family="dense", n_layers=4,
                             d_model=128, n_heads=4, n_kv_heads=2, d_head=32,
                             d_ff=256, vocab_size=512),
        "params": {
            "embed/emb": "bfloat16[512, 128] 3b1b2ea8ce036a7b",
            "final_norm/scale": "bfloat16[128] 352320887a5e2e7d",
            "layers/attn/k/w": "bfloat16[4, 128, 64] 360f548fb832c246",
            "layers/attn/o/w": "bfloat16[4, 128, 128] d9d7e1d9b1244c7f",
            "layers/attn/q/w": "bfloat16[4, 128, 128] c386477d5aff46bf",
            "layers/attn/v/w": "bfloat16[4, 128, 64] ddff6a55d91a6b9d",
            "layers/ln1/scale": "bfloat16[4, 128] cb91fbb36bb65efe",
            "layers/ln2/scale": "bfloat16[4, 128] 85c38a7d5ed7fa67",
            "layers/mlp/down/w": "bfloat16[4, 256, 128] d995a15455c758b1",
            "layers/mlp/gate/w": "bfloat16[4, 128, 256] 204c3e6a315a44f9",
            "layers/mlp/up/w": "bfloat16[4, 128, 256] 43cb835f80ee3fc3",
        },
        "adapters": {
            "0/layers.attn.q/A": "float32[4, 128, 4] 4f70de7c16d64631",
            "0/layers.attn.q/B": "float32[4, 4, 128] cdc55c26fff00229",
            "0/layers.attn.v/A": "float32[4, 128, 4] 7dddd3e3e8fc3627",
            "0/layers.attn.v/B": "float32[4, 4, 64] 7acee49c5a1c07d5",
            "1/layers.attn.q/A": "float32[4, 128, 4] cb9cb617153b0b11",
            "1/layers.attn.q/B": "float32[4, 4, 128] e9299d028180b4bc",
            "1/layers.attn.v/A": "float32[4, 128, 4] 344418b1d94cf6c3",
            "1/layers.attn.v/B": "float32[4, 4, 64] ff2d478e61d26928",
            "2/layers.attn.q/A": "float32[4, 128, 4] 4eb4113c3c11f211",
            "2/layers.attn.q/B": "float32[4, 4, 128] 4062c9954b0f6eab",
            "2/layers.attn.v/A": "float32[4, 128, 4] 1b5aa2be47fcffae",
            "2/layers.attn.v/B": "float32[4, 4, 64] 8bae4a3ac1b32545",
        },
    },
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_dense_build_is_as_before(name):
    from repro.configs.base import ModelConfig
    c = core.config(name)
    c.update(TINY)
    fam = program.family(c)
    assert fam.model_config(c) == ModelConfig(**PARENT[name]["model_config"])
    assert digests(fam.params(c, SEED)) == PARENT[name]["params"]
    assert (digests(fam.user_adapters(c, SEED, USERS, RANK))
            == PARENT[name]["adapters"])


def test_family_leaves_take_ids_of_their_own():
    ids = seeded.leaves({"router": 1000, "expert_up": 1001})
    assert ids["router"] == 1000 and ids["q"] == seeded.LEAF["q"]
    key = seeded.root_key(SEED)
    a = seeded.normal(key, ids["router"], 2, (8,), 1.0)
    assert not np.array_equal(a, seeded.normal(key, ids["expert_up"], 2, (8,),
                                               1.0))
    assert np.array_equal(seeded.normal(key, ids["q"], 2, (8,), 1.0),
                          seeded._normal(key, "q", 2, (8,), 1.0))
    with pytest.raises(ValueError, match="'router' and 'expert_up' share id"):
        seeded.leaves({"router": 1000, "expert_up": 1000})
    with pytest.raises(ValueError, match="not a whole number from 1000 up"):
        seeded.leaves({"router": 12})
    with pytest.raises(ValueError, match="already the dense block's"):
        seeded.leaves({"q": 1002})


FAMILY, CONFIG, CELL = "dense_gqa_files", "tiny-files", "tiny_files.serve"
# A family of its own that re-exports the dense GQA files, as a later change
# would add a new one: four new files and one new cell.
FILES = {
    "families": "model_config, params, user_adapters",
    "reference": "hidden, head_stats, logits, lora_init, train_steps",
    "roofline": "layer_windows, forward_flops, train_flops_per_token",
}


def _snapshot(root) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def _add_cell(checkout, config: dict) -> None:
    """The configuration's file and the cell's entries in BENCHMARK.json:
    the cell, and its name in every metric that lists ``tiny.serve``."""
    (checkout / "bench" / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": CELL, "config": config["name"],
                               "traffic": "tiny.serve", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))


def _execute(checkout, trace: int, require_tpu: bool = False):
    from repro.kernels import ops
    ops.set_backend("pallas_interpret")
    try:
        args = run.parse(["--workload", CELL, "--seed", str(2**31 + 71),
                          "--seconds", "2", "--trace", str(trace)])
        return run.execute(args, require_tpu=require_tpu, root=str(checkout),
                           t_start=time.perf_counter(), cache=False)
    finally:
        ops.set_backend(None)


def test_unknown_family_fails_naming_its_file(checkout):
    _add_cell(checkout, dict(core.config("tiny", str(checkout)),
                             name=CONFIG, family="no_such_family"))
    want = os.path.join("bench", "families", "no_such_family.py")
    with pytest.raises(FileNotFoundError, match=re.escape(want)):
        _execute(checkout, 0)


def test_family_added_as_files_only_runs(checkout, monkeypatch):
    """The CPU has no device trace, so the readers are handed a v5e's peak
    table and the programs' runs on the host's own XLA device as the
    device's: what is checked is that every lookup reaches the new files,
    not the numbers."""
    had = _snapshot(checkout)
    old = json.loads((checkout / "BENCHMARK.json").read_text())
    for kind, names in FILES.items():
        (checkout / "bench" / kind / f"{FAMILY}.py").write_text(
            f"from bench.{kind}.dense_gqa import {names}  # noqa: F401\n")
    _add_cell(checkout, dict(core.config("tiny", str(checkout)),
                             name=CONFIG, family=FAMILY))
    loaded, module = [], core.module

    def spy(kind, name, root=core.ROOT):
        loaded.append((kind, name))
        return module(kind, name, root)

    def host_program_time(red, fn_name):
        hits = [v["busy_s"] for n, v in red["host_programs"].items()
                if fn_name in n]
        return (sum(hits), len(hits)) if hits else None

    monkeypatch.setattr(core, "module", spy)
    monkeypatch.setattr(tr, "program_time", host_program_time)
    monkeypatch.setattr(core, "device_info", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    out = _execute(checkout, 1, require_tpu=True)
    assert out["correct"] is True, out["compared"]
    for name in ("mfu.prefill", "mfu.decode"):
        assert out["metrics"][name]["value"] > 0, name
    assert {(kind, FAMILY) for kind in FILES} <= set(loaded)
    now = _snapshot(checkout)
    changed = [p for p, h in had.items() if now.get(p) != h]
    assert changed == ["BENCHMARK.json"]
    # ... and BENCHMARK.json only gained the cell's entries
    new = json.loads((checkout / "BENCHMARK.json").read_text())
    new["workloads"] = [w for w in new["workloads"] if w["name"] != CELL]
    for m in new["end_to_end"] + new["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w != CELL]
    assert new == old
