"""Public jit'd entry points for the kernel layer.

Kernels are chosen by platform. In a process whose default device is a TPU,
every op that has a Pallas kernel lowers through
``jax.lax.platform_dependent``: the compiled kernel where the computation is
lowered for the TPU, the pure-jnp oracle in ``ref`` where it is lowered for
the host CPU (the offload fit). Elsewhere the oracles run. Nobody has to
switch anything on for the chip.

``set_backend`` overrides that choice for tests and rehearsals:
- "ref"              : pure-jnp oracles on every platform;
- "pallas"           : compiled Pallas TPU kernels, even when tracing on a CPU
                       host (to compile a step for a described, unattached
                       chip);
- "pallas_interpret" : Pallas kernels executed in interpret mode (CPU-correctness);
- None               : back to the platform's choice.

A kernel whose ``supported()`` gate refuses a shape falls back to its oracle.
While the default device is a TPU, each such fallback is counted per op and
per traced shape in ``fallbacks()``, so a run on the chip can show that its
hot path really ran the kernels. Models call these wrappers; nothing below
the ops layer knows about the backend.
"""
from __future__ import annotations

import collections
from typing import Callable, Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref

Array = jax.Array
_BACKEND: str | None = None
_FALLBACKS: collections.Counter = collections.Counter()


def set_backend(
        name: Literal["ref", "pallas", "pallas_interpret"] | None) -> None:
    global _BACKEND
    assert name in (None, "ref", "pallas", "pallas_interpret"), name
    _BACKEND = name


def get_backend() -> str:
    """The override in force, or "platform" when the platform decides."""
    return _BACKEND or "platform"


def fallbacks() -> dict[tuple, int]:
    """{(op, ((dtype, shape), ...)): traces} of the kernel-to-oracle
    fallbacks seen on a TPU, one entry per op and operand shapes."""
    return dict(_FALLBACKS)


def reset_fallbacks() -> None:
    _FALLBACKS.clear()


def _shapes(*arrays) -> tuple:
    return tuple((str(a.dtype), tuple(a.shape)) for a in arrays)


def _route(op: str, ok: bool, kernel: Callable, oracle: Callable,
           args: tuple, split: tuple[bool, ...] | None = None) -> Array:
    """Run ``kernel(*args, interpret=...)`` or ``oracle()`` for one op call.

    ``ok`` is the kernel's support gate for these shapes. ``split`` marks the
    operands whose leading axis is the batch: inside a multi-device program
    the kernel then runs per batch shard (see ``_per_shard``); an op without
    it falls back there."""
    ok = ok and (split is not None or _mesh_rules() is None)
    if _BACKEND is not None:
        if _BACKEND == "ref" or not ok:
            return oracle()
        return _per_shard(kernel, args, split,
                          interpret=_BACKEND == "pallas_interpret")
    if jax.default_backend() != "tpu":
        return oracle()
    if not ok:
        _FALLBACKS[(op, _shapes(*args))] += 1
        return oracle()
    # a TPU host still lowers some programs for its CPU (the offload fit):
    # the platform each one is lowered for picks the branch
    return jax.lax.platform_dependent(
        tpu=lambda: _per_shard(kernel, args, split, interpret=False),
        default=oracle)


def _mesh_rules():
    """The activation rules of the multi-device program being traced, if any
    (``distributed.sharding.activation_rules``)."""
    from repro.distributed import sharding
    rules = sharding.current_rules()
    return rules if rules is not None and rules.mesh.size > 1 else None


def _per_shard(kernel: Callable, args: tuple, split, *, interpret: bool):
    """Pallas TPU kernels are not partitioned automatically: in a
    multi-device program, run the kernel under a ``shard_map`` over every
    mesh axis. The ``split`` operands are cut along their leading (batch)
    axis over the rules' batch axes, the rest are replicated; a batch the
    batch axes do not divide is replicated whole."""
    rules = _mesh_rules()
    if rules is None:
        return kernel(*args, interpret=interpret)
    from jax.sharding import PartitionSpec as P
    axes = rules.batch_axes or None
    if axes and args[0].shape[0] % rules.axis_size(axes):
        axes = None
    specs = tuple(P(axes) if s else P() for s in split)
    return jax.shard_map(
        lambda *a: kernel(*a, interpret=interpret), mesh=rules.mesh,
        in_specs=specs, out_specs=P(axes), check_vma=False)(*args)


# ---------------------------------------------------------------------------
# sdpa / flash attention
# ---------------------------------------------------------------------------

def sdpa(q: Array, k: Array, v: Array, *, q_positions: Array, kv_positions: Array,
         causal: bool = True, window: int | None = None,
         softcap: float | None = None, scale: float | None = None) -> Array:
    """Attention entry point used by the model zoo (see ref.sdpa for semantics)."""
    from repro.kernels import flash_attention as fa
    return _route(
        "flash_attention",
        fa.supported(q, k, v, q_positions=q_positions, causal=causal),
        lambda q, k, v, interpret: fa.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, interpret=interpret),
        lambda: ref.sdpa(q, k, v, q_positions=q_positions,
                         kv_positions=kv_positions, causal=causal,
                         window=window, softcap=softcap, scale=scale),
        (q, k, v), split=(True, True, True))


def sdpa_decode(q: Array, k_cache: Array, v_cache: Array, positions: Array, *,
                live: Array | None = None, window: int | None = None,
                softcap: float | None = None, scale: float | None = None) -> Array:
    """Incremental attention against a dense slot KV cache (serving hot path):
    per-row positions, per-slot live mask; Sq == 1 is the decode tick, Sq > 1 a
    prefill chunk. Routes the single-query case to the fused flash-decode
    kernel; a chunk (Sq > 1) has no kernel yet and runs the oracle. See
    ref.sdpa_decode for semantics."""
    from repro.kernels import decode_attention as da
    return _route(
        "decode_attention", da.supported(q, k_cache, v_cache),
        lambda q, k, v, pos, live, interpret: da.decode_attention(
            q, k, v, pos, live=live, window=window, softcap=softcap,
            scale=scale, interpret=interpret),
        lambda: ref.sdpa_decode(q, k_cache, v_cache, positions, live=live,
                                window=window, softcap=softcap, scale=scale),
        (q, k_cache, v_cache, positions, _live(live, q)),
        split=(True,) * 5)


def sdpa_decode_paged(q: Array, k_pool: Array, v_pool: Array, positions: Array,
                      block_table: Array, *, live: Array | None = None,
                      window: int | None = None, softcap: float | None = None,
                      scale: float | None = None) -> Array:
    """Paged-KV incremental attention: the cache is a shared block pool
    (n_blocks, block, K, Dh) addressed through a per-slot ``block_table``
    (B, max_blocks). Both kernels scalar-prefetch the table and read pool
    blocks directly (no gather): the query's length picks the single-query
    decode kernel (Sq == 1) or the chunked-prefill kernel (Sq > 1); the ref
    path gathers a dense per-slot view. See ref.sdpa_decode_paged for
    semantics."""
    from repro.kernels import decode_attention as da
    if q.shape[1] == 1:
        op, ok, fn = ("decode_attention_paged",
                      da.supported_paged(q, k_pool, v_pool, block_table),
                      da.decode_attention_paged)
    else:
        op, ok, fn = ("prefill_attention_paged",
                      da.supported_prefill_paged(q, k_pool, v_pool,
                                                 block_table),
                      da.prefill_attention_paged)
    return _route(
        op, ok,
        lambda q, kp, vp, pos, tbl, live, interpret: fn(
            q, kp, vp, pos, tbl, live=live, window=window, softcap=softcap,
            scale=scale, interpret=interpret),
        lambda: ref.sdpa_decode_paged(q, k_pool, v_pool, positions,
                                      block_table, live=live, window=window,
                                      softcap=softcap, scale=scale),
        (q, k_pool, v_pool, positions, block_table, _live(live, q)),
        split=(True, False, False, True, True, True))


def _live(live: Array | None, q: Array) -> Array:
    return jnp.ones((q.shape[0],), bool) if live is None else live


def sdpa_decode_ring(q: Array, k_ring: Array, v_ring: Array, positions: Array,
                     *, live: Array | None = None, window: int | None = None,
                     softcap: float | None = None,
                     scale: float | None = None) -> Array:
    """Rolling-window (ring) incremental attention for local-window layers
    under the paged layout. The ring is window-sized, so there is no long
    cache to stream — the position-ordered gather + dense math in
    ref.sdpa_decode_ring is the implementation on every backend."""
    return ref.sdpa_decode_ring(q, k_ring, v_ring, positions, live=live,
                                window=window, softcap=softcap, scale=scale)


# ---------------------------------------------------------------------------
# cola_fit
# ---------------------------------------------------------------------------

def cola_fit_lowrank(x: Array, grad_h: Array, A: Array, B: Array,
                     scale: float = 1.0) -> tuple[Array, Array]:
    from repro.kernels import cola_fit as ck
    return _route(
        "cola_fit_lowrank", ck.supported(x, grad_h, A, B),
        lambda x, g, A, B, interpret: ck.cola_fit_lowrank(
            x, g, A, B, scale=scale, interpret=interpret),
        lambda: ref.cola_fit_lowrank(x, grad_h, A, B, scale=scale),
        (x, grad_h, A, B))


# ---------------------------------------------------------------------------
# multi_lora
# ---------------------------------------------------------------------------

def _multi_lora_route(op: str, fn: Callable, oracle: Callable, x: Array,
                      idx: Array, bank: tuple, gate: Callable) -> Array:
    """Run a multi-LoRA kernel ``fn(x, *bank, idx, interpret)`` at a token
    count it supports: as is, or padded with no-user rows (idx == -1
    contributes zeros) and sliced back — a (J, P) prefill batch flattens to
    J*P tokens, which rarely aligns with the kernel's token blocking."""
    from repro.kernels import multi_lora as ml
    if gate(x, idx):
        return _route(op, True, fn, oracle, (x, *bank, idx))
    padded = ml.pad_tokens(x, idx)
    ok = padded is not None and gate(*padded)
    args = (padded[0], *bank, padded[1]) if ok else (x, *bank, idx)
    return _route(
        op, ok, lambda *a, interpret: fn(*a, interpret)[:x.shape[0]],
        oracle, args)


def multi_lora(x: Array, A: Array, B: Array, idx: Array, scale: float = 1.0) -> Array:
    from repro.kernels import multi_lora as ml
    # decode-shaped dispatch (BGMV idiom): when the bank is larger than the
    # token batch, compact to the resident adapter set first so the kernel's
    # user grid scales with min(U, T) instead of U.
    grouped = A.shape[0] > x.shape[0]
    kern = ml.multi_lora_grouped if grouped else ml.multi_lora
    return _multi_lora_route(
        "multi_lora_grouped" if grouped else "multi_lora",
        lambda xx, A, B, ii, interpret: kern(xx, A, B, ii, scale=scale,
                                             interpret=interpret),
        lambda: ref.multi_lora(x, A, B, idx, scale=scale), x, idx, (A, B),
        lambda xx, ii: ml.supported(xx, A, B, ii, grouped=grouped))


def multi_lora_q8(x: Array, A_q: Array, A_scale: Array, B_q: Array,
                  B_scale: Array, idx: Array, scale: float = 1.0) -> Array:
    """int8-stored bank apply with fused dequant-on-load (see ref.multi_lora_q8
    for the oracle semantics; the serve path never materialises a f32 bank)."""
    from repro.kernels import multi_lora as ml
    return _multi_lora_route(
        "multi_lora_q8",
        lambda xx, aq, as_, bq, bs, ii, interpret: ml.multi_lora_q8(
            xx, aq, as_, bq, bs, ii, scale=scale, interpret=interpret),
        lambda: ref.multi_lora_q8(x, A_q, A_scale, B_q, B_scale, idx,
                                  scale=scale), x, idx,
        (A_q, A_scale, B_q, B_scale),
        lambda xx, ii: ml.supported(xx, A_q, B_q, ii))


# ---------------------------------------------------------------------------
# ssd (mamba2) — chunked jnp implementation on every platform
# ---------------------------------------------------------------------------

def ssd(x: Array, dt: Array, a: Array, B: Array, C: Array, D: Array,
        init_state: Array | None = None, *, chunk: int = 128) -> tuple[Array, Array]:
    """Chunked SSD scan (linear-time). Falls back to ref on tiny sequences."""
    S = x.shape[1]
    if S <= chunk:
        return ref.ssd(x, dt, a, B, C, D, init_state)
    from repro.kernels import ssd_scan
    return ssd_scan.ssd_chunked(x, dt, a, B, C, D, init_state, chunk=chunk)


def ssd_decode_step(x: Array, dt: Array, a: Array, B: Array, C: Array, D: Array,
                    state: Array) -> tuple[Array, Array]:
    return ref.ssd_decode_step(x, dt, a, B, C, D, state)
