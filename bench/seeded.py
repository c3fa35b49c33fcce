"""Values made from ``--seed``: the base weights of a dense GQA model, one
layer at a time, per-user low-rank adapters, and the per-leaf normal that
a family's own leaves are made from (``normal``, ``leaves``).

Both the harness (which hands the weights to the program in its own layout)
and the plain reference (which rebuilds them layer by layer in float32) call
these functions, so the two see the same numbers without the reference ever
taking an array from the program. Every value is a function of the seed, the
leaf and the layer alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# A fixed id per leaf: the key of a leaf never depends on which other leaves
# a model has. These are the dense block's; a family's own leaves take ids
# from FAMILY_LEAVES up (``leaves``).
LEAF = {"embed": 1, "lm_head": 2, "q": 3, "k": 4, "v": 5, "o": 6, "gate": 7,
        "up": 8, "down": 9, "ln1": 10, "ln2": 11, "final_norm": 12,
        "adapter": 100}
FAMILY_LEAVES = 1000


def leaves(own: dict[str, int]) -> dict[str, int]:
    """``LEAF`` with a family's own leaves added, as its family file
    declares them: {name: id}, each id a whole number from FAMILY_LEAVES
    up, no two alike, and no name of ``LEAF``'s. Raises on a clash."""
    taken = {i: n for n, i in LEAF.items()}
    for name, i in own.items():
        if name in LEAF:
            raise ValueError(f"leaf {name!r} is already the dense block's "
                             f"(id {LEAF[name]})")
        if not isinstance(i, int) or i < FAMILY_LEAVES:
            raise ValueError(f"leaf {name!r}: id {i!r} is not a whole number "
                             f"from {FAMILY_LEAVES} up")
        if i in taken:
            raise ValueError(f"leaves {taken[i]!r} and {name!r} share id {i}")
        taken[i] = name
    return {**LEAF, **own}


def root_key(seed: int) -> jax.Array:
    """A key from a seed of up to 64 bits (the driver's seeds pass 2**31)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, jnp.uint32(seed >> 32))


def dims(c: dict) -> dict:
    """The sizes these functions need, from a configuration file's keys."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "H": h, "K": c["num_key_value_heads"],
            "Dh": c.get("head_dim") or d // h, "F": c["intermediate_size"],
            "V": c["vocab_size"], "L": c["num_hidden_layers"]}


def normal(key, leaf: int, layer, shape, scale):
    """float32 N(0, scale^2) entries of the leaf with id ``leaf`` at
    ``layer`` (which may be traced)."""
    k = jax.random.fold_in(jax.random.fold_in(key, leaf), layer)
    return jax.random.normal(k, shape, jnp.float32) * scale


def _normal(key, leaf: str, layer, shape, scale):
    return normal(key, LEAF[leaf], layer, shape, scale)


def layer_weights(c: dict, key: jax.Array, layer) -> dict:
    """float32 weights of one block; ``layer`` may be traced. Dense weights
    are (d_in, d_out) with N(0, 1/d_in) entries, norm scales 1 + N(0, 0.01)."""
    m = dims(c)
    d, hd, kd, f = m["d"], m["H"] * m["Dh"], m["K"] * m["Dh"], m["F"]
    n = lambda leaf, shape, scale: _normal(key, leaf, layer, shape, scale)
    return {"ln1": 1.0 + n("ln1", (d,), 0.1),
            "q": n("q", (d, hd), d ** -0.5), "k": n("k", (d, kd), d ** -0.5),
            "v": n("v", (d, kd), d ** -0.5), "o": n("o", (hd, d), hd ** -0.5),
            "ln2": 1.0 + n("ln2", (d,), 0.1),
            "gate": n("gate", (d, f), d ** -0.5),
            "up": n("up", (d, f), d ** -0.5),
            "down": n("down", (f, d), f ** -0.5)}


def embedding(c: dict, key: jax.Array) -> jax.Array:
    m = dims(c)
    return _normal(key, "embed", 0, (m["V"], m["d"]), 0.02)


def head(c: dict, key: jax.Array) -> jax.Array:
    """(d, V) output head; the transposed embedding when the model ties them.
    With rms-normed inputs its logits are about N(0, 1)."""
    m = dims(c)
    if c["tie_word_embeddings"]:
        return embedding(c, key).T
    return _normal(key, "lm_head", 0, (m["d"], m["V"]), m["d"] ** -0.5)


def final_norm(c: dict, key: jax.Array) -> jax.Array:
    return 1.0 + _normal(key, "final_norm", 0, (dims(c)["d"],), 0.1)


# Per-user adapters on the q and v projections: y += (x @ A) @ B with
# A ~ N(0, 1/d) and B ~ N(0, sigma^2), so that on rms-normed inputs each adds
# about sqrt(rank) * sigma to a projection whose entries are about 1: a user's
# adapter moves the logits, and routing a row to the wrong user shows.
ADAPTER_TAPS = ("q", "v")
ADAPTER_SIGMA = 0.1


def adapter_layer(c: dict, key: jax.Array, user, layer, rank: int) -> dict:
    """{tap: {"A": (d, r), "B": (r, d_out)}} of one user at one layer."""
    m = dims(c)
    out_dim = {"q": m["H"] * m["Dh"], "v": m["K"] * m["Dh"]}
    ku = jax.random.fold_in(jax.random.fold_in(key, LEAF["adapter"]), user)
    out = {}
    for t, tap in enumerate(ADAPTER_TAPS):
        ka = jax.random.fold_in(jax.random.fold_in(ku, 2 * t), layer)
        kb = jax.random.fold_in(jax.random.fold_in(ku, 2 * t + 1), layer)
        out[tap] = {
            "A": jax.random.normal(ka, (m["d"], rank)) * m["d"] ** -0.5,
            "B": jax.random.normal(kb, (rank, out_dim[tap])) * ADAPTER_SIGMA}
    return out


def session_key(seed: int) -> jax.Array:
    """The key a training session starts its adapters from."""
    return jax.random.fold_in(root_key(seed), LEAF["adapter"] + 1)
