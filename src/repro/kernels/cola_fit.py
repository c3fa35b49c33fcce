"""ColA fit kernel (Pallas): the offloaded Gradient-Learning step for the
low-rank family, fused so the (T, r) intermediate never round-trips to HBM.

  dB = (x @ A)^T @ grad_h        dA = x^T @ (grad_h @ B^T)

The token axis T (= I * B * S rows after interval buffering) is the streaming
grid dimension; dA/dB accumulate in VMEM scratch. This is ColA's own compute
hot-spot: at interval I the offload device processes I*B*S rows per tap.

Oracle: repro.kernels.ref.cola_fit_lowrank.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def supported(x, grad_h, A, B) -> bool:
    T, d_in = x.shape
    d_out = grad_h.shape[-1]
    r = A.shape[-1]
    if d_in > 8192 or d_out > 8192 or r > 256:
        return False          # VMEM budget for the unblocked feature dims
    return T % _block_t(T) == 0


def _block_t(t: int) -> int:
    for b in (512, 256, 128, 64, 32, 16, 8):
        if t % b == 0 and b <= t:
            return b
    return t


def _kernel(x_ref, g_ref, a_ref, b_ref, da_ref, db_ref, da_acc, db_acc, *,
            scale):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        da_acc[...] = jnp.zeros_like(da_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    x = x_ref[...].astype(jnp.float32)       # (Bt, d_in)
    g = g_ref[...].astype(jnp.float32)       # (Bt, d_out)
    a = a_ref[...].astype(jnp.float32)       # (d_in, r)
    b = b_ref[...].astype(jnp.float32)       # (r, d_out)

    xa = jax.lax.dot_general(x, a, (((1,), (0,)), ((), ())))       # (Bt, r)
    db_acc[...] += jax.lax.dot_general(xa, g, (((0,), (0,)), ((), ())))
    gb = jax.lax.dot_general(g, b, (((1,), (1,)), ((), ())))       # (Bt, r)
    da_acc[...] += jax.lax.dot_general(x, gb, (((0,), (0,)), ((), ())))

    @pl.when(ti == pl.num_programs(0) - 1)
    def _final():
        da_ref[...] = (scale * da_acc[...]).astype(da_ref.dtype)
        db_ref[...] = (scale * db_acc[...]).astype(db_ref.dtype)


def cola_fit_lowrank(x: Array, grad_h: Array, A: Array, B: Array, *,
                     scale: float = 1.0, interpret: bool = False
                     ) -> tuple[Array, Array]:
    T, d_in = x.shape
    d_out = grad_h.shape[-1]
    r = A.shape[-1]
    bt = _block_t(T)
    grid = (T // bt,)
    with jax.named_scope("cola_fit_lowrank"):
        dA, dB = _pallas_fit(x, grad_h, A, B, scale=scale, bt=bt, grid=grid,
                             d_in=d_in, d_out=d_out, r=r, interpret=interpret)
    return dA, dB


def _pallas_fit(x, grad_h, A, B, *, scale, bt, grid, d_in, d_out, r,
                interpret):
    dA, dB = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, d_in), lambda t: (t, 0)),
            pl.BlockSpec((bt, d_out), lambda t: (t, 0)),
            pl.BlockSpec((d_in, r), lambda t: (0, 0)),
            pl.BlockSpec((r, d_out), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d_in, r), lambda t: (0, 0)),
            pl.BlockSpec((r, d_out), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_in, r), jnp.float32),
            jax.ShapeDtypeStruct((r, d_out), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d_in, r), jnp.float32),
            pltpu.VMEM((r, d_out), jnp.float32),
        ],
        interpret=interpret,
        name="cola_fit",
        metadata={"kernel": "cola_fit"},
    )(x, grad_h, A, B)
    return dA, dB
