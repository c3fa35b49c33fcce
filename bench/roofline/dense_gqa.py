"""Operation counts of a dense GQA decoder (``bench/reference/dense_gqa.py``)
from its configuration file. Counts are of the work the algorithm needs: a
multiply-add is 2 operations, padding rows and recomputation are not work.
``bench/program.py`` states what a family's counts file provides.
"""
from __future__ import annotations

from bench import readers, seeded


def layer_params(c: dict) -> int:
    """Weights of the seven projections of one block."""
    m = seeded.dims(c)
    hd, kd = m["H"] * m["Dh"], m["K"] * m["Dh"]
    return m["d"] * hd + 2 * m["d"] * kd + hd * m["d"] + 3 * m["d"] * m["F"]


def adapter_params(c: dict, rank: int) -> int:
    """q and v low-rank adapters of one block."""
    m = seeded.dims(c)
    return rank * (m["d"] + m["H"] * m["Dh"]) + rank * (m["d"] + m["K"] * m["Dh"])


def layer_windows(c: dict) -> list[int | None]:
    """Every layer attends to the whole context: the family's builder and
    reference set no window."""
    return [None] * seeded.dims(c)["L"]


def attn_pairs(pos: int, n: int, window: int | None = None) -> int:
    """(query, key) pairs of n causal queries at positions pos .. pos+n-1."""
    return readers.causal_pairs(pos, n, window)


def layer_pairs(c: dict, pos: int, n: int) -> list[int]:
    """Each layer's (query, key) pairs for n queries at pos .. pos+n-1."""
    return [attn_pairs(pos, n, w) for w in layer_windows(c)]


def _blocks(c: dict, rank: int) -> int:
    """One token through every block's projections and adapters."""
    per_layer = layer_params(c) + adapter_params(c, rank)
    return 2 * seeded.dims(c)["L"] * per_layer


def _attn(c: dict, pos: int, n: int) -> int:
    """QK and PV of n queries at pos .. pos+n-1, summed over the layers."""
    m = seeded.dims(c)
    return 4 * m["H"] * m["Dh"] * sum(layer_pairs(c, pos, n))


def forward_flops(c: dict, pos: int, n: int, rank: int, heads: int) -> float:
    """n causal queries at positions pos .. pos+n-1 through every block
    (with adapters of ``rank``, each layer's attention over the keys it
    reads), and ``heads`` rows of the output head."""
    m = seeded.dims(c)
    head = heads * 2 * m["d"] * m["V"]
    return n * _blocks(c, rank) + _attn(c, pos, n) + head


def train_flops_per_token(c: dict, seq: int, rank: int) -> float:
    """ColA's required work per trained token: the forward pass, and the
    backward pass through the activations only (the base weights are frozen,
    so no weight gradients), plus the adapters' own gradients. Causal
    attention backward is twice its forward. Remat recompute is not counted.
    """
    m = seeded.dims(c)
    head = 2 * m["d"] * m["V"]
    attn_fwd = _attn(c, 0, seq) / seq
    bwd_adapter = 2 * m["L"] * adapter_params(c, rank)
    fwd = _blocks(c, rank) + head + attn_fwd
    bwd_x = _blocks(c, rank) + head
    return fwd + bwd_x + 2 * attn_fwd + bwd_adapter
