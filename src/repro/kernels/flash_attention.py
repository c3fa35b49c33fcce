"""Flash attention for TPU (Pallas): block-tiled online-softmax with GQA,
causal + local-window masking and gemma2 logit softcap. Forward and backward
kernels with a custom_vjp wrapper.

TPU adaptation (vs the CUDA flash-attention the literature assumes):
- tiles are (block_q x d_head) / (block_k x d_head) VMEM blocks, MXU-aligned
  (block sizes multiples of 128; d_head 64/128/256);
- the kv-block loop is the innermost sequential grid dimension, with the
  online-softmax running stats (m, l) and the output accumulator living in
  VMEM scratch across iterations — the systolic analogue of warp-level
  accumulation;
- GQA is handled by the index_map (q-head h reads kv-head h // G), so kv tiles
  are never physically repeated;
- the kernels run head-major: the wrapper transposes (B, S, H, Dh) to
  (B, H, S, Dh) so every block's last two dims are (seq tile, Dh), which the
  TPU block rule (multiples of (8, 128) or the whole axis) accepts for any
  head count. The per-row softmax statistics travel as (B, H, S, 1) columns
  for the same reason.

Oracle: repro.kernels.ref.sdpa (uniform positions). Tests sweep shapes/dtypes
in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30


def supported(q, k, v, *, q_positions=None, causal=True) -> bool:
    B, Sq, H, Dh = q.shape
    _, Sk, K, _ = k.shape
    if Dh not in (64, 128, 256):
        return False
    if H % K != 0:
        return False
    if Sq % _block_q(Sq) or Sk % _block_k(Sk):
        return False
    return True


def _block_q(sq: int) -> int:
    for b in (256, 128, 64, 32, 16, 8):
        if sq % b == 0 and b <= sq:
            return b
    return sq


def _block_k(sk: int) -> int:
    for b in (512, 256, 128, 64, 32, 16, 8):
        if sk % b == 0 and b <= sk:
            return b
    return sk


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale, causal, window, softcap, block_q, block_k, n_kv,
                q_offset):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (Bq, Dh)
    k = k_ref[0, 0].astype(jnp.float32)                 # (Bk, Dh)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (Bq, Bk)
    if softcap is not None and softcap > 0:
        s = jnp.tanh(s / softcap) * softcap

    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None and window > 0:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    # guard fully-masked rows (m == NEG_INF): exp underflows to 0 anyway
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _final():
        l = l_ref[...]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l > 0, m_ref[...] + jnp.log(safe_l), NEG_INF)[:, None]


def _fwd(q, k, v, *, scale, causal, window, softcap, q_offset, interpret):
    """Head-major operands: q (B, H, Sq, Dh), k/v (B, K, Sk, Dh). Returns o
    (B, H, Sq, Dh) and the row log-sum-exp (B, H, Sq, 1)."""
    B, H, Sq, Dh = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    bq, bk = _block_q(Sq), _block_k(Sk)
    grid = (B, H, Sq // bq, Sk // bk)

    out_shapes = [
        jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
        jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, block_q=bq,
                          block_k=bk, n_kv=K, q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((bq, Dh), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
        metadata={"kernel": "flash_attention_fwd"},
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, window, softcap, block_q,
                   block_k, q_offset):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, 0]
    delta = delta_ref[0, 0][:, 0]

    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if softcap is not None and softcap > 0:
        t = jnp.tanh(s_raw / softcap)
        s = t * softcap
        dcap = 1.0 - t * t
    else:
        s = s_raw
        dcap = None

    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None and window > 0:
        mask = mask & (kpos > qpos - window)

    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta[:, None])
    if dcap is not None:
        ds = ds * dcap
    acc_ref[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ()))) * scale

    @pl.when(ki == pl.num_programs(3) - 1)
    def _final():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, window,
                    softcap, block_q, block_k, q_offset):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, 0]
    delta = delta_ref[0, 0][:, 0]

    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if softcap is not None and softcap > 0:
        t = jnp.tanh(s_raw / softcap)
        s = t * softcap
        dcap = 1.0 - t * t
    else:
        s = s_raw
        dcap = None

    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None and window > 0:
        mask = mask & (kpos > qpos - window)

    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)      # (Bq, Bk)
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta[:, None])
    if dcap is not None:
        ds = ds * dcap
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ()))) * scale

    @pl.when(qi == pl.num_programs(3) - 1)
    def _final():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(res, g, *, scale, causal, window, softcap, q_offset, interpret):
    q, k, v, o, lse = res
    do = g
    B, H, Sq, Dh = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    bq, bk = _block_q(Sq), _block_k(Sk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                       # (B, H, Sq, 1)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, block_q=bq,
                          block_k=bk, q_offset=q_offset),
        grid=(B, H, Sq // bq, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dh), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, Dh), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
        metadata={"kernel": "flash_attention_bwd_dq"},
    )(q, k, v, do, lse, delta)

    # dk/dv accumulate over q-heads within each kv group: run per q-head and
    # sum the group afterwards (keeps the kernel simple; the sum is tiny).
    dkh, dvh = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, block_q=bq,
                          block_k=bk, q_offset=q_offset),
        grid=(B, H, Sk // bk, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, ki, qi: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, ki, qi: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, ki, qi: (b, h, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Sk, Dh), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, Dh), jnp.float32),
            pltpu.VMEM((bk, Dh), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
        metadata={"kernel": "flash_attention_bwd_dkv"},
    )(q, k, v, do, lse, delta)
    dk = dkh.reshape(B, K, G, Sk, Dh).sum(axis=2).astype(k.dtype)
    dv = dvh.reshape(B, K, G, Sk, Dh).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, window, softcap, q_offset, interpret):
    o, _ = _fwd(q, k, v, scale=scale, causal=causal, window=window,
                softcap=softcap, q_offset=q_offset, interpret=interpret)
    return o


def _flash_fwd(q, k, v, scale, causal, window, softcap, q_offset, interpret):
    o, lse = _fwd(q, k, v, scale=scale, causal=causal, window=window,
                  softcap=softcap, q_offset=q_offset, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, window, softcap, q_offset, interpret, res, g):
    return _bwd(res, g, scale=scale, causal=causal, window=window,
                softcap=softcap, q_offset=q_offset, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: Array, k: Array, v: Array, *, q_positions=None,
                    kv_positions=None, causal: bool = True,
                    window: int | None = None, softcap: float | None = None,
                    scale: float | None = None, q_offset: int = 0,
                    interpret: bool = False) -> Array:
    """q: (B, Sq, H, Dh), k/v: (B, Sk, K, Dh) -> (B, Sq, H, Dh). Positions are
    assumed uniform (q starts at q_offset, kv at 0); the ref oracle handles
    arbitrary per-row positions (continuous batching decode)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))   # head-major
    o = _flash(q, k, v, scale, causal, window, softcap, q_offset, interpret)
    return o.transpose(0, 2, 1, 3)
