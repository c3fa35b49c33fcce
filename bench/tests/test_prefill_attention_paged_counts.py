"""The paged prefill attention kernel's operation and byte counts against
hand-worked values, and its roofline reader on synthetic traces."""
import json
import os

import pytest

from bench import core, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_prefill_attention_paged_counts_causal_chunks():
    cost = core.module("roofline", "prefill_attention_paged").cost
    # nemo heads: a first chunk (p=0, c=256) and a tail (p=512, c=100)
    f, b = cost([(0, 256), (512, 100)], H=32, K=8, Dh=128)
    pairs = 256 * 257 // 2 + (100 * 512 + 100 * 101 // 2)
    assert pairs == 32_896 + 56_250
    assert f == 4 * 32 * 128 * pairs
    keys, queries = 256 + 612, 356
    assert b == (2 * 8 * 128 * keys + 2 * 32 * 128 * queries) * 2


def _chunk_run(ops: dict) -> dict:
    """A traced serving run reduced to the readers' inputs: one chunk round
    of two prefilling rows at nemo's sizes, and the given device ops."""
    c = cfg("mistral-nemo-12b-10l")
    return {"trace": {"ops": ops}, "config": c, "root": core.ROOT,
            "peak": peaks.peaks("TPU v5 lite"),
            "ticks": [{"chunk": [(0, 256, False), (512, 100, True)],
                       "decode": []}, {"chunk": [], "decode": [40]}]}


def test_prefill_attention_paged_roofline_reads_its_kernel():
    read = core.module("metrics", "prefill_attention_paged_roofline").read
    named = ('%prefill_attention_paged.3 = bf16[16,8,1024,128] custom-call('
             's32[16] %a, s32[16,289] %b), custom_call_target='
             '"tpu_custom_call", frontend_attributes={kernel_metadata={'
             '\n"kernel":"prefill_attention_paged"\n}}')
    f, b = core.module("roofline", "prefill_attention_paged").cost(
        [(0, 256), (512, 100)], H=32, K=8, Dh=128)
    pk = peaks.peaks("TPU v5 lite")
    least = 10 * max(f / pk["bf16_flops"], b / pk["hbm_bw"])
    got = read(_chunk_run({named: {"total_s": 2 * least, "count": 10}}))
    assert got == pytest.approx(50.0)
    # a trace without the kernel (the parent's, or chunks that fell back to
    # the oracle) reads nothing
    decode = ('%decode_attention_paged.10 = bf16[16,8,4,128] custom-call('
              's32[16] %a, s32[16] %b, s32[16,289] %c), frontend_attributes='
              '{kernel_metadata={\n"kernel":"decode_attention_paged"\n}}')
    assert read(_chunk_run({decode: {"total_s": 1.0, "count": 10}})) is None
    assert read({"trace": None}) is None
