"""Shared fixtures of the benchmark's CPU tests: a copy of the benchmark
in a temporary checkout, with a tiny configuration and tiny mixes added as
files of their own (the way a later change adds a cell)."""
from __future__ import annotations

import json
import os
import shutil

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY = {"hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 4, "vocab_size": 512}


# Deep enough that the float8 control's errors grow as they do at a cell's
# size (the control test reads it against the nemo cell's limit).
WIDE = dict(TINY, hidden_size=256, intermediate_size=512,
            num_hidden_layers=12, vocab_size=4096)


def tiny_serve_mix() -> dict:
    with open(os.path.join(BENCH, "traffic", "serve.chat.json")) as f:
        t = json.load(f)
    t["arrivals"]["rate_per_s"] = 10.0
    t["prompt"].update(max=96)
    t["output"].update(median=6, max=12)
    t["engine"].update(slots=4, prefill_chunk=16)
    t["users"]["count"] = 4
    t["check"].update(max_requests=8, pad_to=128)
    return t


def tiny_train_mix(mode: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"train.{mode}.json")) as f:
        t = json.load(f)
    t.update(batch=2, seq=32)
    t.pop("program", None)
    return t


def make_checkout(root, dims: dict, serve_mix: dict):
    """A checkout holding BENCHMARK.json and bench/, plus tiny cells
    ``tiny.serve``, ``tiny.fused`` and ``tiny.offload`` added as new files
    and new entries only."""
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(root / "bench" / "configs" / "smollm-135m.json") as f:
        cfg = json.load(f)
    cfg.update(dims, name="tiny")
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mixes = {"tiny.serve": serve_mix,
             "tiny.fused": tiny_train_mix("fused"),
             "tiny.offload": tiny_train_mix("offload")}
    for name, mix in mixes.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": name, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += list(mixes)
    if not any(m["name"] == "train_tok_per_s" for m in bench["end_to_end"]):
        bench["end_to_end"].append({
            "name": "train_tok_per_s", "unit": "tokens/s", "better": "higher",
            "bound": 0.25, "source": "host_clock",
            "workloads": ["tiny.fused", "tiny.offload"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture()
def checkout(tmp_path):
    return make_checkout(tmp_path / "checkout", TINY, tiny_serve_mix())


@pytest.fixture()
def control_checkout(tmp_path):
    """WIDE dims, longer prompts, outputs long enough that the sample holds
    some hundreds of served tokens (the control is read at the served
    positions only), and the nemo serving cell's limit."""
    mix = tiny_serve_mix()
    mix["prompt"].update(max=192)
    mix["output"].update(median=24, max=48)
    with open(os.path.join(BENCH, "traffic", "serve.tenants.json")) as f:
        mix["check"]["token_gap_limit"] = json.load(f)["check"][
            "token_gap_limit"]
    return make_checkout(tmp_path / "checkout", WIDE, mix)
