"""Multi-LoRA kernel (Pallas): per-token adapter-indexed low-rank apply — the
FTaaS serving hot-spot (K users' adapters inside one decode batch; the BGMV
problem from Punica/S-LoRA, adapted to TPU).

TPU adaptation: instead of CUDA's per-warp gather of adapter weights, the grid
iterates (token-block x user); each user's (A_u, B_u) tile is a clean VMEM
block (index_map on the user axis), the token block computes the full low-rank
product on the MXU and masks rows that do not belong to user u before
accumulating. For K ~ tens of users this trades U-fold MXU passes (cheap,
r << d) for zero irregular memory access (expensive on TPU).

Oracle: repro.kernels.ref.multi_lora.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def supported(x, A, B, idx, *, grouped: bool = False) -> bool:
    T, d_in = x.shape
    U, _, r = A.shape
    d_out = B.shape[-1]
    # grouped dispatch compacts the bank to the resident set, so the kernel's
    # user grid is min(U, T) regardless of bank size.
    eff_users = min(U, T) if grouped else U
    if d_in > 8192 or d_out > 8192 or r > 256 or eff_users > 64:
        return False
    return T % _block_t(T) == 0 and _block_t(T) <= 256


def _block_t(t: int) -> int:
    for b in (256, 128, 64, 32, 16, 8):
        if t % b == 0 and b <= t:
            return b
    return t


PAD_ALIGN = 128


def pad_tokens(x: Array, idx: Array, align: int = PAD_ALIGN):
    """Pad the token axis to a kernel-friendly multiple (prefill batches are
    J*P tokens and rarely align). Padding rows carry user id -1, which matches
    no user block in the kernel mask and therefore contributes zeros; callers
    slice the output back to the original T. Returns None when already aligned.
    """
    from repro.utils import round_up
    T = x.shape[0]
    t2 = round_up(T, align)
    if t2 == T:
        return None
    xp = jnp.pad(x, ((0, t2 - T), (0, 0)))
    ip = jnp.pad(idx.astype(jnp.int32), (0, t2 - T), constant_values=-1)
    return xp, ip


def _column(idx: Array) -> Array:
    """Token -> user ids as a (T, 1) column: its (block_t, 1) tiles meet the
    TPU block rule, and the in-kernel row mask broadcasts across lanes."""
    return idx.astype(jnp.int32)[:, None]


def _kernel(x_ref, a_ref, b_ref, idx_ref, y_ref, acc_ref, *, scale, block_t):
    ui = pl.program_id(1)

    @pl.when(ui == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (Bt, d_in)
    a = a_ref[0].astype(jnp.float32)            # (d_in, r)
    b = b_ref[0].astype(jnp.float32)            # (r, d_out)
    idx = idx_ref[...]                          # (Bt, 1)

    xa = jax.lax.dot_general(x, a, (((1,), (0,)), ((), ())))
    y = jax.lax.dot_general(xa, b, (((1,), (0,)), ((), ())))
    m = (idx == ui).astype(jnp.float32)
    acc_ref[...] += y * m

    @pl.when(ui == pl.num_programs(1) - 1)
    def _final():
        y_ref[...] = (scale * acc_ref[...]).astype(y_ref.dtype)


def multi_lora(x: Array, A: Array, B: Array, idx: Array, *, scale: float = 1.0,
               interpret: bool = False) -> Array:
    T, d_in = x.shape
    U, _, r = A.shape
    d_out = B.shape[-1]
    bt = _block_t(T)
    y = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_t=bt),
        grid=(T // bt, U),
        in_specs=[
            pl.BlockSpec((bt, d_in), lambda t, u: (t, 0)),
            pl.BlockSpec((1, d_in, r), lambda t, u: (u, 0, 0)),
            pl.BlockSpec((1, r, d_out), lambda t, u: (u, 0, 0)),
            pl.BlockSpec((bt, 1), lambda t, u: (t, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d_out), lambda t, u: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d_out), jnp.float32)],
        interpret=interpret,
        name="multi_lora",
        metadata={"kernel": "multi_lora"},
    )(x, A, B, _column(idx))
    return y


# ---------------------------------------------------------------------------
# grouped decode dispatch (Punica/S-LoRA BGMV idiom)
# ---------------------------------------------------------------------------

def compact_resident(idx: Array, n_users: int, max_groups: int | None = None
                     ) -> tuple[Array, Array]:
    """Compact a decode batch's adapter ids to its *resident set*.

    A decode batch of T token rows references at most min(U, T) distinct
    adapters, while the kernel's user grid (and the dense-over-users cost)
    scales with the bank size U. Sort the ids, mark the distinct ones, and
    remap every row into the compacted id space — the kernel then iterates one
    grouped matmul per *resident* (A, B) pair instead of per bank entry.

    Returns (resident_ids (G,), remapped_idx (T,)): ``resident_ids`` is the
    sorted distinct ids padded with ``n_users``; rows with idx < 0 (padding)
    stay -1 in ``remapped_idx``.
    """
    T = idx.shape[0]
    G = min(n_users, T) if max_groups is None else max_groups
    idx = idx.astype(jnp.int32)
    s = jnp.sort(idx)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    distinct = first & (s >= 0)
    resident = jnp.sort(jnp.where(distinct, s, n_users))[:G]
    remapped = jnp.searchsorted(resident, idx).astype(jnp.int32)
    remapped = jnp.where(idx < 0, -1, remapped)
    return resident, remapped


def multi_lora_grouped(x: Array, A: Array, B: Array, idx: Array, *,
                       scale: float = 1.0, interpret: bool = False) -> Array:
    """Grouped-GEMM decode dispatch: compact the bank to the resident adapter
    set before launching the kernel, so cost scales with min(U, T) rather than
    U. When the bank holds a single adapter (U == 1) the compaction is skipped
    entirely — one grouped matmul pair, rows with idx != 0 masked in-kernel."""
    U = A.shape[0]
    if U == 1:
        return multi_lora(x, A, B, idx, scale=scale, interpret=interpret)
    resident, remapped = compact_resident(idx, U)
    safe = jnp.clip(resident, 0, U - 1)        # pad entries gather arbitrarily;
    A_c, B_c = A[safe], B[safe]                # no row maps to them
    return multi_lora(x, A_c, B_c, remapped, scale=scale, interpret=interpret)


# ---------------------------------------------------------------------------
# int8-stored banks: fused dequant-on-load
# ---------------------------------------------------------------------------

def quant_rows(w: Array) -> tuple[Array, Array]:
    """Per-row (last-dim) symmetric int8 quantisation of an adapter leaf.
    Matches the offload channel's transfer compression (core/offload.py)."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequant_rows(q: Array, scale: Array, dtype=jnp.float32) -> Array:
    """Inverse of ``quant_rows`` — the host-side decode used when an
    int8-stored bank entry must be read back as f32 (similarity vectors,
    cluster merging, tests). The serving path never calls this: kernels
    dequantise on tile load."""
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def _q8_kernel(x_ref, aq_ref, as_ref, bq_ref, bs_ref, idx_ref, y_ref, acc_ref,
               *, scale, block_t):
    ui = pl.program_id(1)

    @pl.when(ui == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                        # (Bt, d_in)
    # dequant-on-load: the int8 tiles + row scales are what crosses HBM->VMEM;
    # the f32 view exists only as this block's VMEM working set.
    a = aq_ref[0].astype(jnp.float32) * as_ref[0].astype(jnp.float32)
    b = bq_ref[0].astype(jnp.float32) * bs_ref[0].astype(jnp.float32)
    idx = idx_ref[...]

    xa = jax.lax.dot_general(x, a, (((1,), (0,)), ((), ())))
    y = jax.lax.dot_general(xa, b, (((1,), (0,)), ((), ())))
    m = (idx == ui).astype(jnp.float32)
    acc_ref[...] += y * m

    @pl.when(ui == pl.num_programs(1) - 1)
    def _final():
        y_ref[...] = (scale * acc_ref[...]).astype(y_ref.dtype)


def multi_lora_q8(x: Array, A_q: Array, A_scale: Array, B_q: Array,
                  B_scale: Array, idx: Array, *, scale: float = 1.0,
                  interpret: bool = False) -> Array:
    """int8-stored multi-LoRA: A_q (U, d_in, r) int8 with A_scale (U, d_in, 1)
    per-row scales (likewise B). The bank stays int8 in HBM; dequant happens on
    tile load inside the kernel, so no f32 copy of the bank is ever
    materialised. Oracle: ref.multi_lora_q8."""
    T, d_in = x.shape
    U, _, r = A_q.shape
    d_out = B_q.shape[-1]
    bt = _block_t(T)
    y = pl.pallas_call(
        functools.partial(_q8_kernel, scale=scale, block_t=bt),
        grid=(T // bt, U),
        in_specs=[
            pl.BlockSpec((bt, d_in), lambda t, u: (t, 0)),
            pl.BlockSpec((1, d_in, r), lambda t, u: (u, 0, 0)),
            pl.BlockSpec((1, d_in, 1), lambda t, u: (u, 0, 0)),
            pl.BlockSpec((1, r, d_out), lambda t, u: (u, 0, 0)),
            pl.BlockSpec((1, r, 1), lambda t, u: (u, 0, 0)),
            pl.BlockSpec((bt, 1), lambda t, u: (t, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d_out), lambda t, u: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d_out), jnp.float32)],
        interpret=interpret,
        name="multi_lora_q8",
        metadata={"kernel": "multi_lora_q8"},
    )(x, A_q, A_scale, B_q, B_scale, _column(idx))
    return y
