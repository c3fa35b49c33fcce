"""Fused single-query flash-attention decode kernel (Pallas, TPU target).

The serving decode hot path: every live slot attends one new query against its
KV cache row. The kernel streams the cache in (block_k x K*d_head) VMEM tiles
with the online-softmax running stats in scratch — the decode analogue of the
prefill flash kernel — with three decode-specific twists:

- **GQA layout**: the cache is viewed as (B, Smax, K*Dh) (a free reshape of
  (B, Smax, K, Dh)), so one grid step loads the tile of *every* KV head and
  the TPU block rule (last two block dims multiples of (8, 128) or the whole
  axis) holds for any K and Dh. Inside the step a static loop over the K
  groups slices head h's lanes; the G query heads of group h form the rows of
  its (G, Dh) q tile, so each KV tile is read once per step and the
  (G, block_k) score tile is real MXU work even though Sq == 1.
- **Per-slot positions via scalar prefetch**: each row's current position (and
  its ``live`` bit) arrives in SMEM before the grid runs; KV blocks entirely
  above the position (or entirely below the local-window floor) are skipped
  with ``pl.when`` — continuous batching means rows at wildly different
  positions share one launch.
- **Live-slot semantics**: dead/padding slots produce exact zeros (not
  attention over a stale cache), matching ``ref.sdpa_decode``.

Oracle: ``repro.kernels.ref.sdpa_decode``. Tests sweep GQA group counts,
window/softcap variants and live-mask patterns in interpret mode;
``tests/test_tpu_compile.py`` compiles both kernels for a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30


def supported(q, k_cache, v_cache) -> bool:
    B, Sq, H, Dh = q.shape
    _, Sk, K, _ = k_cache.shape
    if Sq != 1 or Dh not in (64, 128, 256):
        return False
    if H % K != 0:
        return False
    return Sk % _block_k(Sk, K * Dh * k_cache.dtype.itemsize) == 0


def supported_paged(q, k_pool, v_pool, block_table) -> bool:
    B, Sq, H, Dh = q.shape
    _, bs, K, _ = k_pool.shape
    if Sq != 1 or Dh not in (64, 128, 256):
        return False
    if H % K != 0:
        return False
    return bs % 8 == 0 and bs * K * Dh * k_pool.dtype.itemsize <= _TILE_BYTES


# one (block_k, K*Dh) k or v tile; four of them are in flight (double-buffered
# k and v), well inside the TPU's default scoped VMEM
_TILE_BYTES = 1 << 20


def _block_k(sk: int, row_bytes: int) -> int:
    for b in (512, 256, 128, 64, 32, 16, 8):
        if sk % b == 0 and b * row_bytes <= _TILE_BYTES:
            return b
    return sk


def _kernel(pos_ref, live_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, *, scale, window, softcap, block_k, n_kv, d_head):
    bi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[bi]
    start = ki * block_k
    # block intersects the valid kv range [max(0, pos - window + 1), pos]?
    in_range = start <= pos
    if window is not None and window > 0:
        in_range = in_range & (start + block_k > pos - window + 1)

    @pl.when(in_range)
    def _compute():
        n_groups = q_ref.shape[2]
        kpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (n_groups, block_k), 1)
        mask = kpos <= pos
        if window is not None and window > 0:
            mask = mask & (kpos > pos - window)
        for h in range(n_kv):          # static: one KV group per iteration
            lanes = slice(h * d_head, (h + 1) * d_head)
            q = q_ref[0, h].astype(jnp.float32)            # (G, Dh)
            k = k_ref[0, :, lanes].astype(jnp.float32)     # (Bk, Dh)
            v = v_ref[0, :, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
            if softcap is not None and softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1)
            acc_ref[h] = acc_ref[h] * corr[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            m_ref[h] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _final():
        l = l_ref[...]
        safe_l = jnp.where(l > 0, l, 1.0)
        out = acc_ref[...] / safe_l[..., None]
        out = out * (live_ref[bi] > 0).astype(jnp.float32)
        o_ref[0] = out.astype(o_ref.dtype)


def _scratch(K, G, Dh):
    return [pltpu.VMEM((K, G, Dh), jnp.float32),
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G), jnp.float32)]


def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     positions: Array, *, live: Array | None = None,
                     window: int | None = None, softcap: float | None = None,
                     scale: float | None = None,
                     interpret: bool = False) -> Array:
    """q: (B, 1, H, Dh); caches: (B, Smax, K, Dh); positions: (B,) int32;
    live: (B,) bool or None (all live). Returns (B, 1, H, Dh)."""
    B, Sq, H, Dh = q.shape
    _, Sk, K, _ = k_cache.shape
    G = H // K
    if scale is None:
        scale = Dh ** -0.5
    bk = _block_k(Sk, K * Dh * k_cache.dtype.itemsize)
    if live is None:
        live = jnp.ones((B,), bool)
    qg = q.reshape(B, K, G, Dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, K, G, Dh), lambda b, ki, pos, live: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, K * Dh), lambda b, ki, pos, live: (b, ki, 0)),
            pl.BlockSpec((1, bk, K * Dh), lambda b, ki, pos, live: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, K, G, Dh), lambda b, ki, pos, live: (b, 0, 0, 0)),
        scratch_shapes=_scratch(K, G, Dh),
    )
    o = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, softcap=softcap,
                          block_k=bk, n_kv=K, d_head=Dh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, Dh), q.dtype),
        interpret=interpret,
        name="decode_attention",
        metadata={"kernel": "decode_attention"},
    )(positions.astype(jnp.int32), live.astype(jnp.int32), qg,
      k_cache.reshape(B, Sk, K * Dh), v_cache.reshape(B, Sk, K * Dh))
    return o.reshape(B, 1, H, Dh)


def _kernel_paged(pos_ref, live_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, **kw):
    # Identical online-softmax math to the dense kernel: grid axis 1 walks the
    # row's block *table* slots in position order, so ki * block_k is still the
    # absolute kv position of the tile — only the BlockSpec index maps differ
    # (the tile is fetched from pool row table[b, ki] instead of (b, ki)).
    _kernel(pos_ref, live_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, **kw)


def decode_attention_paged(q: Array, k_pool: Array, v_pool: Array,
                           positions: Array, block_table: Array, *,
                           live: Array | None = None,
                           window: int | None = None,
                           softcap: float | None = None,
                           scale: float | None = None,
                           interpret: bool = False) -> Array:
    """Fused single-query decode attention over a paged KV pool.

    q: (B, 1, H, Dh); pools: (n_blocks, block, K, Dh) shared across slots;
    block_table: (B, max_blocks) int32 mapping (slot, position // block) to a
    pool block id. The table rides in as a third scalar-prefetch operand (next
    to positions/live): the k/v BlockSpec index maps dereference
    ``table[b, ki]`` so each grid step DMAs its (block, K*Dh) tile straight
    out of the pool — no gathered dense copy of the cache ever exists. Blocks
    past a row's position (including unallocated table entries, which point
    at block 0) are skipped by the same ``pl.when`` position test as the dense
    kernel. Oracle: ``ref.sdpa_decode_paged``.
    """
    B, Sq, H, Dh = q.shape
    n_blocks, bs, K, _ = k_pool.shape
    nb = block_table.shape[1]
    G = H // K
    if scale is None:
        scale = Dh ** -0.5
    if live is None:
        live = jnp.ones((B,), bool)
    qg = q.reshape(B, K, G, Dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, K, G, Dh),
                         lambda b, ki, pos, live, tbl: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, K * Dh),
                         lambda b, ki, pos, live, tbl: (tbl[b, ki], 0, 0)),
            pl.BlockSpec((1, bs, K * Dh),
                         lambda b, ki, pos, live, tbl: (tbl[b, ki], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, K, G, Dh), lambda b, ki, pos, live, tbl: (b, 0, 0, 0)),
        scratch_shapes=_scratch(K, G, Dh),
    )
    o = pl.pallas_call(
        functools.partial(_kernel_paged, scale=scale, window=window,
                          softcap=softcap, block_k=bs, n_kv=K, d_head=Dh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, Dh), q.dtype),
        interpret=interpret,
        name="decode_attention_paged",
        metadata={"kernel": "decode_attention_paged"},
    )(positions.astype(jnp.int32), live.astype(jnp.int32),
      block_table.astype(jnp.int32), qg,
      k_pool.reshape(n_blocks, bs, K * Dh), v_pool.reshape(n_blocks, bs, K * Dh))
    return o.reshape(B, 1, H, Dh)
