"""Counts that know each layer: the kernels' counts with a window of keys
against hand-worked values, the five readers that take their counts from a
configuration's family against the values they read before the counts
knew each layer, and a family whose layers differ by window summed layer by
layer."""
import gzip
import json
import os
import shutil

import pytest

from bench import core, peaks
from bench import trace as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
V5E = peaks.peaks("TPU v5 lite")
READERS = ("mfu.prefill", "mfu.decode", "mfu.train",
           "decode_attention_paged_roofline",
           "prefill_attention_paged_roofline")

# Chunk rounds ((p, c, last) of each prefilling row) and decode ticks (each
# live row's query position), as a traced serving run records them.
TICKS = [
    {"chunk": [(0, 256, False), (512, 100, True), (0, 37, True)],
     "decode": [40, 1000, 4095]},
    {"chunk": [], "decode": [5, 300, 2047, 9]},
    {"chunk": [(256, 256, False), (1536, 256, False)], "decode": [41, 1001]},
]
PREFILL_OP = ('%prefill_attention_paged.3 = bf16[16,8,1024,128] custom-call('
              's32[16] %a, s32[16,289] %b), custom_call_target='
              '"tpu_custom_call", frontend_attributes={kernel_metadata={'
              '\n"kernel":"prefill_attention_paged"\n}}')
DECODE_OP = ('%decode_attention_paged.10 = bf16[16,8,4,128] custom-call('
             's32[16] %a, s32[16] %b, s32[16,289] %c, bf16[16,8,4,128] %q, '
             'bf16[2048,16,1024] %k, bf16[2048,16,1024] %v), '
             'frontend_attributes={kernel_metadata={'
             '\n"kernel":"decode_attention_paged"\n}}')


def cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _reduced(name, tmp):
    path = tmp / f"{name}.xplane.pb"
    with gzip.open(os.path.join(DATA, f"{name}.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.reduce(str(path))


# A synthetic trace of nemo's chunk rounds and decode ticks: both paged
# kernels and both programs.
NEMO_TRACE = {
    "ops": {PREFILL_OP: {"total_s": 0.0125, "count": 20},
            DECODE_OP: {"total_s": 0.0731, "count": 30}},
    "programs": {"jit__chunk_fn": {"total_s": 0.3829, "count": 2},
                 "jit__decode_fn": {"total_s": 0.0726, "count": 3}}}


def serve_run(trace, config: str, traffic: str) -> dict:
    return {"kind": "serve", "trace": trace, "config": cfg(config),
            "traffic": mix(traffic), "ticks": TICKS, "peak": V5E,
            "root": core.ROOT}


def train_run(config: str, traffic: str, seconds: float, tokens: int):
    return {"kind": "train", "config": cfg(config), "traffic": mix(traffic),
            "peak": V5E, "window_s": seconds, "window_tokens": tokens,
            "trace": None, "root": core.ROOT}


def runs(traces: dict) -> dict:
    """Run records as the readers get them: the two committed v5e traces
    of ``smollm.serve.chat`` under the ticks above, the synthetic nemo
    trace, and two training windows."""
    return {
        "smollm_chat_1s": serve_run(traces["smollm_chat_1s"], "smollm-135m",
                                    "serve.chat"),
        "smollm_chat_phases_1s": serve_run(traces["smollm_chat_phases_1s"],
                                           "smollm-135m", "serve.chat"),
        "nemo_synthetic": serve_run(NEMO_TRACE, "mistral-nemo-12b-10l",
                                    "serve.tenants"),
        "nemo_train_fused": train_run("mistral-nemo-12b-10l", "train.fused",
                                      29.731, 172_032),
        "smollm_train_offload": train_run("smollm-135m", "train.offload",
                                          30.52, 18_432),
    }


def read_all(traces: dict) -> dict:
    return {name: {m: core.module("metrics", m).read(run) for m in READERS}
            for name, run in runs(traces).items()}


# Read at commit 538c2c6, before the counts knew each layer, from the runs
# above by ``read_all``.
PARENT = {
    "smollm_chat_1s": {
        "mfu.prefill": 0.1386218573725331,
        "mfu.decode": 0.006910225815758251,
        "mfu.train": None,
        "decode_attention_paged_roofline": 0.37652649205286043,
        "prefill_attention_paged_roofline": None,
    },
    "smollm_chat_phases_1s": {
        "mfu.prefill": 0.13873620840655548,
        "mfu.decode": 0.004648885948849512,
        "mfu.train": None,
        "decode_attention_paged_roofline": 0.25909061066821354,
        "prefill_attention_paged_roofline": None,
    },
    "nemo_synthetic": {
        "mfu.prefill": 6.681800907156577,
        "mfu.decode": 0.4375229768846751,
        "mfu.train": None,
        "decode_attention_paged_roofline": 0.5872158666686711,
        "prefill_attention_paged_roofline": 4.481043535733933,
    },
    "nemo_train_fused": {
        "mfu.prefill": None,
        "mfu.decode": None,
        "mfu.train": 42.89426862640526,
        "decode_attention_paged_roofline": None,
        "prefill_attention_paged_roofline": None,
    },
    "smollm_train_offload": {
        "mfu.prefill": None,
        "mfu.decode": None,
        "mfu.train": 0.18206004655840224,
        "decode_attention_paged_roofline": None,
        "prefill_attention_paged_roofline": None,
    },
}


@pytest.fixture(scope="module")
def traces(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("traces")
    return {n: _reduced(n, tmp)
            for n in ("smollm_chat_1s", "smollm_chat_phases_1s")}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_reader_reads_as_before(traces, name):
    got = read_all(traces)[name]
    for m in READERS:
        want = PARENT[name][m]
        if want is None:
            assert got[m] is None, m
        else:
            assert got[m] == pytest.approx(want, rel=1e-9, abs=0), m


def test_decode_attention_paged_counts_with_a_window():
    cost = core.module("roofline", "decode_attention_paged").cost
    unit = lambda keys, rows: (  # noqa: E731
        4 * 32 * 128 * keys, (2 * 8 * 128 * keys + 2 * 32 * 128 * rows) * 2)
    # inside the window: the query at 100 reads its 101 keys
    assert cost([100], H=32, K=8, Dh=128, window=1024) == unit(101, 1)
    # at its edge and past it: the last 1024 keys
    assert cost([1023], H=32, K=8, Dh=128, window=1024) == unit(1024, 1)
    assert cost([4095], H=32, K=8, Dh=128, window=1024) == unit(1024, 1)
    assert cost([100, 4095], H=32, K=8, Dh=128) == unit(101 + 4096, 2)


def test_prefill_attention_paged_counts_with_a_window():
    cost = core.module("roofline", "prefill_attention_paged").cost
    unit = lambda pairs, keys, queries: (  # noqa: E731
        4 * 32 * 128 * pairs,
        (2 * 8 * 128 * keys + 2 * 32 * 128 * queries) * 2)
    # a first chunk inside the window: every causal pair, keys 0 .. 255
    assert cost([(0, 256)], 32, 8, 128, window=1024) == unit(
        256 * 257 // 2, 256, 256)
    # a chunk past it: each query reads 1024 keys, 3073 .. 4351 are read
    assert cost([(4096, 256)], 32, 8, 128, window=1024) == unit(
        256 * 1024, 4351 - 3073 + 1, 256)
    # a chunk straddling its edge: queries 900 .. 1155; those up to 1023
    # read 901 .. 1024 keys, the 132 from 1024 on read 1024; keys 0 .. 1155
    straddle = (901 + 1024) * 124 // 2 + 132 * 1024
    assert cost([(900, 256)], 32, 8, 128, window=1024) == unit(
        straddle, 1156, 256)
    assert cost([(0, 256), (4096, 256), (900, 256)], 32, 8, 128,
                window=1024) == unit(256 * 257 // 2 + 256 * 1024 + straddle,
                                     256 + 1279 + 1156, 768)


def test_kernel_readers_add_up_each_layers_window(checkout):
    """A family whose layers differ by window (three sliding layers of 1024
    keys to one full one, as Mellum2 has them): each kernel reader adds up
    every layer's least time with that layer's window."""
    (checkout / "bench" / "roofline" / "windowed.py").write_text(
        "def layer_windows(c):\n"
        "    return [None if l % 4 == 3 else 1024\n"
        "            for l in range(c['num_hidden_layers'])]\n")
    run = serve_run(NEMO_TRACE, "mistral-nemo-12b-10l", "serve.tenants")
    # nemo's 10 layers: 8 with a window, 2 full
    run.update(root=str(checkout), config=dict(run["config"],
                                               family="windowed"))
    for name, key, rows, op in (
            ("decode_attention_paged", "decode", lambda t: t["decode"],
             DECODE_OP),
            ("prefill_attention_paged", "chunk",
             lambda t: [(p, c) for p, c, _ in t["chunk"]], PREFILL_OP)):
        cost = core.module("roofline", name).cost
        least = 0.0
        for tick in TICKS:
            for w, n in ((1024, 8), (None, 2)):
                if tick[key]:
                    f, b = cost(rows(tick), 32, 8, 128, window=w)
                    least += n * max(f / V5E["bf16_flops"],
                                     b / V5E["hbm_bw"])
        got = core.module("metrics", f"{name}_roofline").read(run)
        want = 100 * least / NEMO_TRACE["ops"][op]["total_s"]
        assert got == pytest.approx(want, rel=1e-12)
        assert got < PARENT["nemo_synthetic"][f"{name}_roofline"]
