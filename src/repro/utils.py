"""Shared utilities: pytree helpers, rng, dtype handling, shape math."""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Callable, Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


# ---------------------------------------------------------------------------
# persistent compilation cache (entry points only)
# ---------------------------------------------------------------------------

def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a script's process and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    already uses that directory and nothing is set here. Otherwise the cache
    lives at the fixed in-checkout path ``<repo>/.jax_cache``, so a later run
    of the same checkout finds what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# dtypes
# ---------------------------------------------------------------------------

def canonical_dtype(name: str | jnp.dtype) -> jnp.dtype:
    if isinstance(name, str):
        return jnp.dtype({
            "bf16": jnp.bfloat16,
            "bfloat16": jnp.bfloat16,
            "f32": jnp.float32,
            "float32": jnp.float32,
            "f16": jnp.float16,
        }[name])
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# rng helpers
# ---------------------------------------------------------------------------

def split_like(key: jax.Array, names: Iterable[str]) -> dict[str, jax.Array]:
    names = list(names)
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))


def fold_in_str(key: jax.Array, s: str) -> jax.Array:
    h = np.uint32(abs(hash(s)) % (2**31 - 1))
    return jax.random.fold_in(key, h)


# ---------------------------------------------------------------------------
# pytree helpers
# ---------------------------------------------------------------------------

def tree_size_bytes(tree: PyTree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_count(tree: PyTree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_scale(a: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, a)


def tree_cast(tree: PyTree, dtype) -> PyTree:
    dtype = canonical_dtype(dtype)
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )


def tree_allclose(a: PyTree, b: PyTree, *, rtol=1e-5, atol=1e-6) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    return all(np.allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)
               for x, y in zip(la, lb))


def flatten_dict(d: Mapping, prefix: str = "", sep: str = ".") -> dict[str, Any]:
    out: dict[str, Any] = {}
    for k, v in d.items():
        kk = f"{prefix}{sep}{k}" if prefix else str(k)
        if isinstance(v, Mapping):
            out.update(flatten_dict(v, kk, sep))
        else:
            out[kk] = v
    return out


def unflatten_dict(d: Mapping[str, Any], sep: str = ".") -> dict:
    out: dict = {}
    for k, v in d.items():
        parts = k.split(sep)
        cur = out
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = v
    return out


# ---------------------------------------------------------------------------
# shape math
# ---------------------------------------------------------------------------

def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def human_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB", "PB"):
        if abs(n) < 1024:
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} EB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000:
            return f"{n:.2f}{unit}" if unit else f"{n:.0f}"
        n /= 1000
    return f"{n:.2f}Q"


def asdict_shallow(dc) -> dict:
    return {f.name: getattr(dc, f.name) for f in dataclasses.fields(dc)}
