"""The dense GQA family (Llama / Mistral block) as the benchmark builds it:
the program's ``ModelConfig`` from a configuration file, and the seeded
weights and adapters (``seeded.py``) placed in the program's own parameter
layout, on the device, each in one jitted call. ``bench/program.py`` states
what a family file provides."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import seeded

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(c: dict, **program):
    """``repro.configs.base.ModelConfig`` of a dense GQA configuration file.
    ``program`` carries the program's own settings (``loss_chunk``...)."""
    from repro.configs.base import ModelConfig
    m = seeded.dims(c)
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{c['name']}: only silu gated MLPs are supported")
    return ModelConfig(
        name=c["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["K"], d_head=m["Dh"], d_ff=m["F"],
        vocab_size=m["V"], rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        norm_eps=float(c["rms_norm_eps"]), param_dtype=c["dtype"],
        compute_dtype=c["dtype"], **program)


@functools.partial(jax.jit, static_argnums=(0,))
def _params(cj: tuple, key: jax.Array) -> dict:
    c = dict(cj)
    dt = DTYPES[c["dtype"]]
    L = seeded.dims(c)["L"]
    w = jax.vmap(lambda l: seeded.layer_weights(c, key, l))(jnp.arange(L))
    w = jax.tree.map(lambda a: a.astype(dt), w)
    p = {"embed": {"emb": seeded.embedding(c, key).astype(dt)},
         "layers": {"ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
                    "attn": {n: {"w": w[n]} for n in ("q", "k", "v", "o")},
                    "mlp": {n: {"w": w[n]} for n in ("gate", "up", "down")}},
         "final_norm": {"scale": seeded.final_norm(c, key).astype(dt)}}
    if not c["tie_word_embeddings"]:
        p["lm_head"] = {"w": seeded.head(c, key).astype(dt)}
    return p


def _frozen(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


def params(c: dict, seed: int) -> dict:
    """The program's parameter tree, in the configuration's dtype."""
    return _params(_frozen(c), seeded.root_key(seed))


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _bank(cj: tuple, key: jax.Array, users: int, rank: int) -> dict:
    c = dict(cj)
    L = seeded.dims(c)["L"]
    per = jax.vmap(lambda u: jax.vmap(
        lambda l: seeded.adapter_layer(c, key, u, l, rank))(jnp.arange(L)))(
        jnp.arange(users))                       # leaves (U, L, ...)
    return {f"layers.attn.{tap}": {n: jnp.moveaxis(a, 0, 1)
                                   for n, a in leaves.items()}
            for tap, leaves in per.items()}      # leaves (L, U, ...)


def user_adapters(c: dict, seed: int, users: int, rank: int) -> list[dict]:
    """One adapter pytree per user, as ``ServeEngine(user_adapters=)`` takes
    them: {tap: {"A": (L, d, r), "B": (L, r, d_out)}} in float32."""
    bank = _bank(_frozen(c), seeded.root_key(seed), users, rank)
    return [{tap: {n: a[:, u] for n, a in leaves.items()}
             for tap, leaves in bank.items()} for u in range(users)]
