"""Work of one call of ``kernels/decode_attention.py``'s chunked-prefill
kernel (``prefill_attention_paged``): each prefilling row's c new queries,
causal over its p cached positions and each other. Operations: QK and PV
products, 2 x 2 x H x Dh per (query, key) pair. Bytes: the row's K and V up
to its last query at the cache dtype, its queries and its outputs; padded
rows and the pool pages a row does not own are not needed."""
from __future__ import annotations


def cost(rows: list[tuple[int, int]], H: int, K: int, Dh: int,
         itemsize: int = 2) -> tuple[float, float]:
    """``rows``: (p, c) of each prefilling row, c queries at positions
    p .. p + c - 1. Returns (operations, bytes)."""
    pairs = sum(c * p + c * (c + 1) // 2 for p, c in rows)
    keys = sum(p + c for p, c in rows)
    queries = sum(c for _, c in rows)
    flops = 4.0 * H * Dh * pairs
    nbytes = (2.0 * K * Dh * keys + 2.0 * H * Dh * queries) * itemsize
    return flops, nbytes
