"""Operation counts of a dense GQA decoder (``bench/reference/dense_gqa.py``)
from its configuration file. Counts are of the work the algorithm needs: a
multiply-add is 2 operations, padding rows and recomputation are not work.
"""
from __future__ import annotations

from bench import seeded


def layer_params(c: dict) -> int:
    """Weights of the seven projections of one block."""
    m = seeded.dims(c)
    hd, kd = m["H"] * m["Dh"], m["K"] * m["Dh"]
    return m["d"] * hd + 2 * m["d"] * kd + hd * m["d"] + 3 * m["d"] * m["F"]


def adapter_params(c: dict, rank: int) -> int:
    """q and v low-rank adapters of one block."""
    m = seeded.dims(c)
    return rank * (m["d"] + m["H"] * m["Dh"]) + rank * (m["d"] + m["K"] * m["Dh"])


def attn_pairs(pos: int, n: int) -> int:
    """(query, key) pairs of n causal queries at positions pos .. pos+n-1."""
    return n * pos + n * (n + 1) // 2


def forward_flops(c: dict, n: int, pairs: int, rank: int, heads: int) -> float:
    """n tokens through every block (with adapters of ``rank``), ``pairs``
    causal (query, key) pairs per layer, and ``heads`` rows of the output
    head."""
    m = seeded.dims(c)
    per_tok = 2 * (layer_params(c) + adapter_params(c, rank))
    attn = 4 * m["H"] * m["Dh"] * pairs
    return m["L"] * (n * per_tok + attn) + heads * 2 * m["d"] * m["V"]


def train_flops_per_token(c: dict, seq: int, rank: int) -> float:
    """ColA's required work per trained token: the forward pass, and the
    backward pass through the activations only (the base weights are frozen,
    so no weight gradients), plus the adapters' own gradients. Causal
    attention backward is twice its forward. Remat recompute is not counted.
    """
    m = seeded.dims(c)
    pairs = attn_pairs(0, seq) / seq                   # per token
    fwd = forward_flops(c, 1, 0, rank, 1)               # without attention
    attn_fwd = m["L"] * 4 * m["H"] * m["Dh"] * pairs
    bwd_x = 2 * m["L"] * (layer_params(c) + adapter_params(c, rank)) \
        + 2 * m["d"] * m["V"]
    bwd_adapter = 2 * m["L"] * adapter_params(c, rank)
    return fwd + attn_fwd + bwd_x + 2 * attn_fwd + bwd_adapter
