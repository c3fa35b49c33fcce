"""Work of one call of ``kernels/decode_attention.py``'s paged decode kernel
(``_kernel_paged``): one query per live row against its cached positions.
Operations: QK and PV products, 2 x 2 x H x Dh per cached position read.
Bytes: the row's cached K and V that it reads at the cache dtype, its query
and its output; the pool pages a row does not own are not needed, nor, in a
layer with a window, the keys below it."""
from __future__ import annotations


def cost(positions: list[int], H: int, K: int, Dh: int,
         itemsize: int = 2, window: int | None = None) -> tuple[float, float]:
    """``positions``: the query position of each live row (it attends to
    positions 0 .. p, or to the last ``window`` of them where a window is
    given). Returns (operations, bytes)."""
    keys = sum(p + 1 if window is None else min(p + 1, window)
               for p in positions)
    flops = 4.0 * H * Dh * keys
    nbytes = (2.0 * K * Dh * keys + 2.0 * H * Dh * len(positions)) * itemsize
    return flops, nbytes
