"""Chunked SSD scan (Mamba2 "state-space duality", linear time).

The sequence is split into chunks of length ``chunk``; each chunk applies the
quadratic masked form (``ref.ssd``) locally and carries the (H, P, N) state
across chunks with ``lax.scan``. On TPU the per-chunk quadratic form is dense
MXU work; the scan carries only the small state in registers/VMEM. There is
no Pallas kernel for it: this jnp path runs on every platform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import flags
from repro.kernels import ref

Array = jax.Array


def ssd_chunked(x: Array, dt: Array, a: Array, B: Array, C: Array, D: Array,
                init_state: Array | None = None, *, chunk: int = 128
                ) -> tuple[Array, Array]:
    """Linear-time chunked scan; exact for any S.

    A non-divisible tail is handled as one exact-length ``ref.ssd`` call seeded
    with the scanned carry rather than by zero-padding the last chunk: padded
    positions with dt == 0 happen to be state-preserving *only* because this
    parameterisation multiplies both the decay exponent and the input by dt —
    any other discretisation would silently corrupt the returned final state.
    With the tail sliced exactly, the returned state is provably the state at
    position S (tests assert it equals the step-by-step decode state).
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    nc, tail = divmod(S, chunk)
    state = (jnp.zeros((b, H, P, N), jnp.float32) if init_state is None
             else init_state.astype(jnp.float32))
    if nc == 0:
        return ref.ssd(x, dt, a, B, C, D, init_state=state)

    head = nc * chunk

    def to_chunks(t):
        return t.reshape(t.shape[0], nc, chunk, *t.shape[2:]).swapaxes(0, 1)

    xs = (to_chunks(x[:, :head]), to_chunks(dt[:, :head]),
          to_chunks(B[:, :head]), to_chunks(C[:, :head]))

    def body(state, inp):
        xc, dtc, Bc, Cc = inp
        yc, state = ref.ssd(xc, dtc, a, Bc, Cc, D, init_state=state)
        return state, yc

    state, ys = jax.lax.scan(body, state, xs, unroll=flags.scan_unroll())
    y = ys.swapaxes(0, 1).reshape(b, head, H, P)
    if tail:
        y_tail, state = ref.ssd(x[:, head:], dt[:, head:], a, B[:, head:],
                                C[:, head:], D, init_state=state)
        y = jnp.concatenate([y, y_tail], axis=1)
    return y, state
