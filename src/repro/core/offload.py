"""Gradient Offloading (paper Fig. 1): host-side buffers, the adaptation
interval I, int8 transfer compression, and the offloaded fit+optimizer.

The Offloader owns everything the paper moves off the server device:
- the adaptation-data buffers (accumulate I batches -> effective batch B*I),
- the adapter parameters between rounds,
- the adapter optimizer and its state (as in ZeRO-Offload, cited by the paper).

On a real pod the buffers live in host RAM of each worker (or a low-end
accelerator); here ``device`` defaults to the CPU device. Transfers are
asynchronous: ``push`` only enqueues; blocking happens inside ``maybe_fit``.
"""
from __future__ import annotations

import collections
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gl
from repro.core.taps import ColaSpec
from repro.optim import optimizers as optim_lib
from repro.telemetry import span

Array = jax.Array


# ---------------------------------------------------------------------------
# int8 row-scaled transfer compression (beyond-paper; §Perf)
# ---------------------------------------------------------------------------

def quant_int8(x: Array) -> tuple[Array, Array]:
    """Per-row (last-dim) symmetric int8 quantisation."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequant_int8(q: Array, scale: Array, dtype=jnp.float32) -> Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


class Offloader:
    """Buffers + offloaded fit for one adapter bank.

    Parameters
    ----------
    spec        : ColaSpec whose ``families`` describe the adapters to fit
                  (use the *adapter* spec even when the server runs merged).
    adapters    : initial adapter pytree {tap: w}.
    optimizer   : repro.optim Optimizer (state lives with the offloader).
    interval    : adaptation interval I (fit every I pushed batches).
    compress    : "none" | "int8" — compress (x, grad_h) for the transfer.
    """

    def __init__(self, spec: ColaSpec, adapters: dict, optimizer, *,
                 interval: int = 1, compress: str = "none", device=None):
        self.spec = spec
        self.optimizer = optimizer
        self.interval = int(interval)
        self.compress = compress
        self.device = device if device is not None else jax.devices("cpu")[0]
        self.adapters = jax.device_put(adapters, self.device)
        self.opt_state = jax.jit(optimizer.init)(self.adapters)
        self.buffers: dict[str, list] = collections.defaultdict(list)
        self._pushes = 0
        self.stats = {"pushed_bytes": 0, "fits": 0}

        def _fit(adapters, opt_state, data):
            grads = gl.fit_grads(self.spec, adapters, data)
            # average over the I buffered batches (effective batch B*I)
            grads = jax.tree.map(lambda g: g / float(self.interval), grads)
            updates, opt_state = optimizer.update(grads, opt_state, adapters)
            return optim_lib.apply_updates(adapters, updates), opt_state, grads

        self._fit = jax.jit(_fit)

    # -- transfer ----------------------------------------------------------
    def push(self, data: dict[str, tuple]) -> None:
        """Enqueue one batch of adaptation data {tap: (x, grad_h)}."""
        for tap, (x, gh) in data.items():
            if self.compress == "int8":
                payload = (quant_int8(x), quant_int8(gh))
                nbytes = sum(int(np.prod(p[0].shape)) + 4 * int(np.prod(p[1].shape))
                             for p in payload)
            else:
                payload = (x, gh)
                nbytes = x.size * x.dtype.itemsize + gh.size * gh.dtype.itemsize
            # device -> offload-device transfer (async under jax dispatch)
            payload = jax.device_put(payload, self.device)
            self.buffers[tap].append(payload)
            self.stats["pushed_bytes"] += nbytes
        self._pushes += 1

    def _materialise(self) -> dict[str, tuple]:
        out = {}
        for tap, items in self.buffers.items():
            xs, ghs = [], []
            for item in items:
                if self.compress == "int8":
                    (qx, sx), (qg, sg) = item
                    xs.append(dequant_int8(qx, sx))
                    ghs.append(dequant_int8(qg, sg))
                else:
                    xs.append(item[0])
                    ghs.append(item[1])
            axis = xs[0].ndim - 3  # batch axis: (L?, B, S, d)
            out[tap] = (jnp.concatenate(xs, axis=axis),
                        jnp.concatenate(ghs, axis=axis))
        return out

    @property
    def ready(self) -> bool:
        """True when I batches have accumulated and a fit is due."""
        return (self._pushes > 0 and self._pushes % self.interval == 0
                and bool(self.buffers))

    # -- fit ----------------------------------------------------------------
    def maybe_fit(self) -> dict | None:
        """Run the offloaded fit if I batches have accumulated. Returns the new
        adapters (to be sent back to the server / merged) or None."""
        if not self.ready:
            return None
        data = self._materialise()
        with span("offload.fit"):
            self.adapters, self.opt_state, _ = self._fit(
                self.adapters, self.opt_state, data)
        self.buffers.clear()
        self.stats["fits"] += 1
        return self.adapters

    def force_fit(self) -> dict | None:
        if not self.buffers:
            return None
        data = self._materialise()
        n = len(next(iter(self.buffers.values())))
        grads = gl.fit_grads(self.spec, self.adapters, data)
        grads = jax.tree.map(lambda g: g / float(n), grads)
        updates, self.opt_state = self.optimizer.update(
            grads, self.opt_state, self.adapters)
        self.adapters = optim_lib.apply_updates(self.adapters, updates)
        self.buffers.clear()
        self.stats["fits"] += 1
        return self.adapters
