"""Work of ``kernels/flash_attention.py``'s three kernels on one causal
self-attention call of B sequences of S tokens, H query heads over K KV
heads of Dh. A product over the causal (query, key) pairs costs
2 x B x H x Dh x S(S+1)/2 operations; blocks above the diagonal are not work.

- ``_fwd_kernel``: QK and PV, 2 products; reads Q, K, V, writes O and the
  log-sum-exp.
- ``_bwd_dq_kernel``: recomputes QK, forms dP = dO V^T and dQ = dS K:
  3 products; reads Q, K, V, dO, the statistics, writes dQ.
- ``_bwd_dkv_kernel``: recomputes QK, forms dP, dV = P^T dO, dK = dS^T Q:
  4 products; reads Q, K, V, dO, the statistics, writes dK, dV.
"""
from __future__ import annotations

PRODUCTS = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}


def cost(part: str, B: int, S: int, H: int, K: int, Dh: int,
         itemsize: int = 2) -> tuple[float, float]:
    q = B * S * H * Dh * itemsize
    kv = B * S * K * Dh * itemsize
    stats = B * H * S * 4
    flops = PRODUCTS[part] * 2.0 * B * H * Dh * S * (S + 1) / 2
    nbytes = {"fwd": q + 2 * kv + q + stats,
              "bwd_dq": 3 * q + 2 * kv + 2 * stats,
              "bwd_dkv": 2 * q + 4 * kv + 2 * stats}[part]
    return flops, float(nbytes)
