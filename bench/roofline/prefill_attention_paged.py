"""Work of one call of ``kernels/decode_attention.py``'s chunked-prefill
kernel (``prefill_attention_paged``): each prefilling row's c new queries,
causal over its p cached positions and each other. Operations: QK and PV
products, 2 x 2 x H x Dh per (query, key) pair. Bytes: the keys and values
the row's queries read at the cache dtype, its queries and its outputs;
padded rows, the pool pages a row does not own and, in a layer with a
window, the keys below the first query's window are not needed."""
from __future__ import annotations

from bench import readers


def cost(rows: list[tuple[int, int]], H: int, K: int, Dh: int,
         itemsize: int = 2, window: int | None = None) -> tuple[float, float]:
    """``rows``: (p, c) of each prefilling row, c queries at positions
    p .. p + c - 1, each reading at most its last ``window`` positions
    where a window is given. Returns (operations, bytes)."""
    pairs = sum(readers.causal_pairs(p, c, window) for p, c in rows)
    # the keys max(0, p - window + 1) .. p + c - 1
    keys = sum(p + c if window is None else min(p + c, c + window - 1)
               for p, c in rows)
    queries = sum(c for _, c in rows)
    flops = 4.0 * H * Dh * pairs
    nbytes = (2.0 * K * Dh * keys + 2.0 * H * Dh * queries) * itemsize
    return flops, nbytes
