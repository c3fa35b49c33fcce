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

A chunk of a chunked prefill (Sq > 1) over the paged pool has its own
kernel, ``prefill_attention_paged``: one grid step per row, whose pages it
gathers into VMEM tiles with its own DMAs, up to its last query only.

Oracles: ``repro.kernels.ref.sdpa_decode`` and ``ref.sdpa_decode_paged``.
Tests sweep GQA group counts, window/softcap variants and live-mask
patterns in interpret mode; ``tests/test_tpu_compile.py`` compiles the
kernels for a v5e.
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


def supported_prefill_paged(q, k_pool, v_pool, block_table) -> bool:
    B, Sq, H, Dh = q.shape
    _, bs, K, _ = k_pool.shape
    if Sq == 1 or Dh not in (64, 128, 256) or H % K != 0:
        return False
    # a page lands at a row offset of the VMEM tile that is a whole number
    # of (packed) sublane tiles
    if (bs * k_pool.dtype.itemsize) % 32 != 0:
        return False
    return _prefill_vmem_bytes(q, k_pool) <= _PREFILL_VMEM_LIMIT


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


# ---------------------------------------------------------------------------
# chunked prefill over the paged pool (Sq > 1)
# ---------------------------------------------------------------------------

# the kernel holds a whole row's chunk (query, output, f32 accumulator) in
# VMEM: about 21 MiB at nemo's chunk of 256; v5e has 128 MiB of VMEM, and
# the default scoped limit is 16 MiB
_PREFILL_VMEM_LIMIT = 64 << 20
# KV positions per tile: up to this many, in whole pages
_PREFILL_TILE_K = 512


def _prefill_pages_per_tile(bs: int, row_bytes: int) -> int:
    """Pages gathered into one VMEM KV tile: the most (a power of two) that
    keeps the tile within _PREFILL_TILE_K positions and _TILE_BYTES / 2."""
    tp = 1
    while (2 * tp * bs <= _PREFILL_TILE_K
           and 2 * tp * bs * row_bytes <= _TILE_BYTES // 2):
        tp *= 2
    return tp


def _prefill_vmem_bytes(q, k_pool) -> int:
    B, Sq, H, Dh = q.shape
    _, bs, K, _ = k_pool.shape
    lanes = -(-Dh // 128) * 128
    rows = Sq * (H // K)
    isz = k_pool.dtype.itemsize
    tk = _prefill_pages_per_tile(bs, K * lanes * isz) * bs
    sub = 32 // isz                                      # packed sublanes
    qo = 2 * 2 * K * rows * lanes * q.dtype.itemsize     # q, out: 2 buffers
    acc = K * rows * lanes * 4 + 2 * K * rows * 4        # acc, m, l
    pages = 2 * 2 * tk * -(-K // sub) * sub * lanes * isz   # as DMA'd
    heads = 2 * K * tk * lanes * isz                     # head-major k, v
    work = 4 * rows * max(tk, 128) * 4                   # s, p and temps
    return qo + acc + pages + heads + work


def _prefill_kernel(pos_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf,
                    vbuf, khead, vhead, sem, acc_ref, m_ref, l_ref, *, scale,
                    window, softcap, group, sq, pages, n_kv):
    b = pl.program_id(0)
    pos = pos_ref[b]                   # first query position; -1: dead row
    nb = table_ref.shape[1]
    bs = k_hbm.shape[1]
    tk = pages * bs
    # the row's causal horizon: pages 0 .. n_pages - 1 hold every position
    # up to its last query (the table ends the horizon earlier if it must)
    n_pages = jnp.minimum((pos + sq + bs - 1) // bs, nb)
    n_tiles = (n_pages + pages - 1) // pages
    first = 0
    if window is not None and window > 0:
        first = jnp.maximum(pos - window + 1, 0) // tk

    def page_copies(t, slot, j):
        # a tile's pages past the horizon repeat the row's last page: every
        # DMA reads real pool rows, and their positions are masked
        blk = table_ref[b, jnp.minimum(t * pages + j, n_pages - 1)]
        return [pltpu.make_async_copy(src.at[blk], dst.at[slot, j],
                                      sem.at[slot])
                for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf))]

    def start(t, slot):
        @pl.loop(0, pages)
        def _(j):
            for c in page_copies(t, slot, j):
                c.start()

    def wait(t, slot):
        @pl.loop(0, pages)
        def _(j):
            for c in page_copies(t, slot, j):
                c.wait()

    def update(mask):
        """Online-softmax update of every KV group with one KV tile; ``mask``
        None for a tile every query sees whole."""
        @pl.loop(0, n_kv)
        def _group(h):                 # the G queries of KV head h, as rows
            v = vhead[h]                                       # (tk, Dh)
            s = jax.lax.dot_general(
                q_ref[0, h], khead[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if softcap is not None and softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            if mask is not None:       # a row with no key yet: m_new = -inf
                p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1)
            acc_ref[h] = acc_ref[h] * corr[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(pos < 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(pos >= 0)
    def _live():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        start(first, first % 2)
        shape = (q_ref.shape[2], tk)
        qpos = pos + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
        kcol = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

        @pl.loop(first, n_tiles)
        def _tile(t):
            slot = t % 2

            @pl.when(t + 1 < n_tiles)
            def _prefetch():
                start(t + 1, 1 - slot)

            wait(t, slot)
            # the pages arrive position-major, (pages, block, K, Dh) as the
            # pool holds them; lay the tile out head-major in VMEM
            for h in range(n_kv):
                khead[h] = kbuf[slot, :, :, h, :].reshape(tk, -1)
                vhead[h] = vbuf[slot, :, :, h, :].reshape(tk, -1)
            # a tile wholly below the chunk's first query (and inside every
            # query's window) needs no mask
            inner = (t + 1) * tk <= pos + 1
            if window is not None and window > 0:
                inner = inner & (t * tk > pos + sq - 1 - window)

            @pl.when(inner)
            def _inner():
                update(None)

            @pl.when(jnp.logical_not(inner))
            def _edge():
                kpos = t * tk + kcol
                mask = (kpos <= qpos) & (kpos < n_pages * bs)
                if window is not None and window > 0:
                    mask = mask & (kpos > qpos - window)
                update(mask)

        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)[..., None]
        o_ref[0] = out.astype(o_ref.dtype)


def prefill_attention_paged(q: Array, k_pool: Array, v_pool: Array,
                            positions: Array, block_table: Array, *,
                            live: Array | None = None,
                            window: int | None = None,
                            softcap: float | None = None,
                            scale: float | None = None,
                            interpret: bool = False) -> Array:
    """Chunked-prefill attention over a paged KV pool: each row's Sq > 1
    queries at positions ``positions[b] + i`` against its cached positions.

    q: (B, Sq, H, Dh); pools: (n_blocks, block, K, Dh); block_table:
    (B, max_blocks). One grid step per row. A live row reads only its pages
    ``0 .. ceil((positions[b] + Sq) / block) - 1``: the table rides in SMEM
    (scalar prefetch) and the kernel gathers several whole pages at a time
    into one VMEM tile with its own double-buffered DMAs from the pool,
    which stays in HBM in its own layout; no dense copy of a row's cache
    exists. A dead row issues no DMA and writes exact zeros. For each KV
    head h the Sq*G queries of its group are the rows of one (Sq*G, Dh)
    tile against the head's (block_k, Dh) K and V: bf16 products with f32
    accumulation, an f32 online softmax, and P cast to the value dtype for
    P.V, as in ``ref.sdpa``. Dead rows are marked by a position of -1, so
    the operands are (positions, table, q, pools): a signature apart from
    the single-query kernel's. Oracle: ``ref.sdpa_decode_paged``.
    """
    B, Sq, H, Dh = q.shape
    bs, K = k_pool.shape[1], k_pool.shape[2]
    G = H // K
    if scale is None:
        scale = Dh ** -0.5
    pos = positions.astype(jnp.int32)
    if live is not None:
        pos = jnp.where(live, pos, -1)
    # a DMA may not cut a padded minor dimension: Dh is padded to whole
    # 128-lane rows and K to the pool's (packed) sublane tiling, no-ops at
    # K = 8, Dh = 128; zero lanes add nothing to Q.K or P.V. The queries
    # are laid out head-major, (B, K, Sq * G, Dhp)
    Dhp = -(-Dh // 128) * 128
    Kp = (max(1 << (K - 1).bit_length(), 4 // k_pool.dtype.itemsize)
          if K < 8 else -(-K // 8) * 8)
    qg = jnp.pad(q.reshape(B, Sq, K, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, K, Sq * G, Dh), ((0, 0),) * 3 + ((0, Dhp - Dh),))
    pad = ((0, 0), (0, 0), (0, Kp - K), (0, Dhp - Dh))
    kp, vp = jnp.pad(k_pool, pad), jnp.pad(v_pool, pad)
    pages = _prefill_pages_per_tile(bs, K * Dhp * k_pool.dtype.itemsize)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, K, Sq * G, Dhp),
                         lambda b, pos, tbl: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, K, Sq * G, Dhp),
                               lambda b, pos, tbl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs, Kp, Dhp), k_pool.dtype),
            pltpu.VMEM((2, pages, bs, Kp, Dhp), v_pool.dtype),
            pltpu.VMEM((K, pages * bs, Dhp), k_pool.dtype),
            pltpu.VMEM((K, pages * bs, Dhp), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((K, Sq * G, Dhp), jnp.float32),
            pltpu.VMEM((K, Sq * G), jnp.float32),
            pltpu.VMEM((K, Sq * G), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, window=window,
                          softcap=softcap, group=G, sq=Sq, pages=pages,
                          n_kv=K),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, Sq * G, Dhp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT),
        interpret=interpret,
        name="prefill_attention_paged",
        metadata={"kernel": "prefill_attention_paged"},
    )(pos, block_table.astype(jnp.int32), qg, kp, vp)
    return o[..., :Dh].reshape(B, K, Sq, G, Dh).transpose(
        0, 2, 1, 3, 4).reshape(B, Sq, H, Dh)
