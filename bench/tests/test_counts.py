"""Operation and byte counts, and the peak table, against hand-worked
values."""
import json
import os

import pytest

from bench import core, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_layer_params_hand_worked():
    dg = core.module("roofline", "dense_gqa")
    # nemo: q 5120x4096, k and v 5120x1024, o 4096x5120, 3 MLP 5120x14336
    assert dg.layer_params(cfg("mistral-nemo-12b-10l")) == 272_629_760
    # smollm: 30 layers of 3,538,944 = 106.2 M without the embedding
    assert dg.layer_params(cfg("smollm-135m")) * 30 == 106_168_320


def test_attention_pairs_are_causal():
    dg = core.module("roofline", "dense_gqa")
    assert dg.attn_pairs(0, 4) == 1 + 2 + 3 + 4
    assert dg.attn_pairs(10, 2) == 11 + 12


def test_decode_attention_paged_counts_gqa():
    cost = core.module("roofline", "decode_attention_paged").cost
    # one row at position 15: 16 keys; H=32 query heads read K=8 KV heads
    f, b = cost([15], H=32, K=8, Dh=128)
    assert f == 4 * 32 * 128 * 16
    assert b == (2 * 8 * 128 * 16 + 2 * 32 * 128) * 2


def test_flash_attention_counts_causal_and_backward():
    cost = core.module("roofline", "flash_attention").cost
    fwd, _ = cost("fwd", 1, 4, 2, 1, 8)
    dq, _ = cost("bwd_dq", 1, 4, 2, 1, 8)
    dkv, _ = cost("bwd_dkv", 1, 4, 2, 1, 8)
    unit = 2 * 1 * 2 * 8 * 10            # 10 causal pairs of S = 4
    assert (fwd, dq, dkv) == (2 * unit, 3 * unit, 4 * unit)


def test_cola_training_count_is_about_four_n():
    """Forward 2N plus backward through the activations 2N per token,
    with no weight gradients; attention and adapters add a little."""
    c = cfg("mistral-nemo-12b-10l")
    dg = core.module("roofline", "dense_gqa")
    n = 10 * dg.layer_params(c) + 5120 * 131072     # blocks + head
    per_tok = dg.train_flops_per_token(c, 4096, 8)
    assert 4 * n < per_tok < 4.5 * n


def test_peaks_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError):
        peaks.peaks("TPU v99")
