"""Plain float32 reference of a dense GQA decoder (Llama / Mistral block):
pre-norm attention with rotary positions over grouped KV heads, a SiLU-gated
MLP, RMSNorm, and per-user low-rank adapters on the q and v projections.

It imports nothing of the program and takes no array from it: weights and
adapters are rebuilt from the seed (``bench/seeded.py``), one layer at a time,
and rounded to the dtype the configuration states, so that no float32 copy of
the whole model is ever held. Every matrix product runs at HIGHEST precision.

``weights="fp8"`` is the control: the same computation in float8 e4m3, the
precision below the configuration's bfloat16. Both operands of every matrix
product are rounded to it: the projections and the head with one scale per
output channel, the activations (the normed inputs, q, k, v, the attention
probabilities and output, the MLP's hidden layer, the head's input) with
one scale per row. Norms, the embedding, softmax and the residual stream
stay in float32, as in an fp8 serving path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeded

HI = jax.lax.Precision.HIGHEST
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
PROJ = ("q", "k", "v", "o", "gate", "up", "down")
Q_BLOCK = 512


def _round(w, dtype: str, weights: str, channel_axis: int = -1):
    """A weight as served: the configuration's dtype, or float8 e4m3 with a
    per-output-channel scale for the control."""
    w = w.astype(DTYPES[dtype]).astype(jnp.float32)
    if weights == "fp8":
        red = tuple(a for a in range(w.ndim) if a != channel_axis % w.ndim)
        s = jnp.max(jnp.abs(w), axis=red, keepdims=True) / 448.0
        s = jnp.where(s > 0, s, 1.0)
        w = (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return w


def _q8(x, on: bool):
    """x as a float8 e4m3 matmul operand with one scale per row (last
    axis); x itself when ``on`` is false. The rounding passes gradients
    through unchanged (as fp8 training does with its float32 master
    gradients), so that a cotangent never underflows in float8."""
    if not on:
        return x
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
    s = jax.lax.stop_gradient(jnp.where(s > 0, s, 1.0))
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, n, Dh); rotates the two halves of each head (rotate_half)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, fp8: bool = False):
    """Causal GQA softmax attention. q: (S, H, Dh); k, v: (S, K, Dh); query
    head h reads KV head h // (H // K). Query blocks, each recomputed in the
    backward pass, bound the size of the scores."""
    S, H, Dh = q.shape
    G = H // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def blk(qb, s0):
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * Dh ** -0.5
        mask = kpos[None, :] <= (s0 + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _q8(p, fp8), v, precision=HI)

    return jnp.concatenate([blk(q[s0:s0 + Q_BLOCK], s0)
                            for s0 in range(0, S, Q_BLOCK)], 0)


def block(c: dict, w: dict, x, adapter: dict | None, fp8: bool = False):
    """One decoder block on x: (S, d) float32; ``fp8`` rounds every matrix
    product's activation operand (the control)."""
    m = seeded.dims(c)
    eps, S = float(c["rms_norm_eps"]), x.shape[0]
    dot = functools.partial(jnp.dot, precision=HI)
    h = _q8(_rms(x, w["ln1"], eps), fp8)
    proj = {n: dot(h, w[n]) for n in ("q", "k", "v")}
    if adapter is not None:
        for tap, ab in adapter.items():
            proj[tap] = proj[tap] + dot(dot(h, ab["A"]), ab["B"])
    pos = jnp.arange(S)
    q = _rope(proj["q"].reshape(S, m["H"], m["Dh"]), pos, c["rope_theta"])
    k = _rope(proj["k"].reshape(S, m["K"], m["Dh"]), pos, c["rope_theta"])
    v = proj["v"].reshape(S, m["K"], m["Dh"])
    o = _attention(_q8(q, fp8), _q8(k, fp8), _q8(v, fp8), fp8)
    x = x + dot(_q8(o.reshape(S, -1), fp8), w["o"])
    h = _q8(_rms(x, w["ln2"], eps), fp8)
    a = _q8(jax.nn.silu(dot(h, w["gate"])) * dot(h, w["up"]), fp8)
    return x + dot(a, w["down"])


def layer(c: dict, key, l, weights: str) -> dict:
    w = seeded.layer_weights(c, key, l)
    return {n: (_round(a, c["dtype"], weights) if n in PROJ
                else _round(a, c["dtype"], "as_stated"))
            for n, a in w.items()}


def _frozen(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _layer_apply(cj, key, l, xs, users, rank, weights):
    """One layer on a stack of sequences xs: (n, S, d); users: (n,) or None."""
    c = dict(cj)
    w = layer(c, key, l, weights)

    def one(x, u):
        ad = (None if rank == 0 else seeded.adapter_layer(c, key, u, l, rank))
        return block(c, w, x, ad, weights == "fp8")

    return jax.lax.map(lambda xu: one(*xu), (xs, users))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _embed(cj, key, weights, toks):
    c = dict(cj)
    return _round(seeded.embedding(c, key), c["dtype"], "as_stated")[toks]


@functools.partial(jax.jit, static_argnums=(0, 2))
def _head(cj, key, weights, h):
    """h: (n, P, d) final hidden rows -> (n, P, V) logits."""
    c = dict(cj)
    h = _rms(h, _round(seeded.final_norm(c, key), c["dtype"], "as_stated"),
             float(c["rms_norm_eps"]))
    return jnp.einsum("npd,dv->npv", _q8(h, weights == "fp8"),
                      _round(seeded.head(c, key), c["dtype"], weights),
                      precision=HI)


HEAD_CHUNK = 512


@functools.partial(jax.jit, static_argnums=(0, 2))
def _head_stats(cj, key, weights, xs, gather):
    """xs: (n, S, d) last hidden states; gather: (n, S, G) token ids. Per
    position: the best logit, its token, and the logits of the G tokens,
    in chunks of positions so that no (S, V) block is held."""
    def one(x, g):
        outs = []
        for s0 in range(0, x.shape[0], HEAD_CHUNK):
            lg = _head(cj, key, weights, x[None, s0:s0 + HEAD_CHUNK])[0]
            outs.append((lg.max(-1), lg.argmax(-1).astype(jnp.int32),
                         jnp.take_along_axis(lg, g[s0:s0 + HEAD_CHUNK], -1)))
        return tuple(jnp.concatenate(o, 0) for o in zip(*outs))
    return jax.lax.map(lambda a: one(*a), (xs, gather))


def hidden(c: dict, seed: int, seqs: list, users: list, rank: int, *,
           weights: str = "as_stated", rows: int = 0,
           multiple: int = 1024):
    """Last hidden states (before the final norm) of token sequences
    ``seqs``, user ``users[i]``'s adapters of rank ``rank`` (0: none), as
    one (n, S, d) array. Sequences are padded to one length, a multiple of
    ``multiple``, and their number to ``rows``, so that a run finds each
    layer's program in the compile cache; the padding sits after every real
    position, which causality keeps unseen."""
    cj, key = _frozen(c), seeded.root_key(seed)
    n = max(rows, len(seqs))
    S = -(-max(len(s) for s in seqs) // multiple) * multiple
    toks = np.zeros((n, S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    u = np.zeros(n, np.int32)
    u[:len(users)] = users
    xs = _embed(cj, key, weights, jnp.asarray(toks))
    for l in range(seeded.dims(c)["L"]):
        xs = _layer_apply(cj, key, jnp.int32(l), xs, jnp.asarray(u), rank,
                          weights)
    return xs


def head_stats(c: dict, seed: int, xs, seqs: list, gather: list, *,
               weights: str = "as_stated"):
    """For each sequence i and each of its positions p: the best logit, the
    token that has it, and the logits of the tokens ``gather[i][p]``
    (shape (len, G)). Returns three lists of numpy arrays."""
    n, S = xs.shape[0], xs.shape[1]
    G = gather[0].shape[1]
    g = np.zeros((n, S, G), np.int32)
    for i, t in enumerate(gather):
        g[i, :len(t)] = t
    mx, am, gl = _head_stats(_frozen(c), seeded.root_key(seed), weights, xs,
                             jnp.asarray(g))
    mx, am, gl = np.asarray(mx), np.asarray(am), np.asarray(gl)
    return ([mx[i, :len(s)] for i, s in enumerate(seqs)],
            [am[i, :len(s)] for i, s in enumerate(seqs)],
            [gl[i, :len(s)] for i, s in enumerate(seqs)])


def logits(c: dict, seed: int, seqs: list, reads: list, users: list,
           rank: int, *, weights: str = "as_stated") -> list[np.ndarray]:
    """Full logits at positions ``reads[i]`` of ``seqs[i]`` (small sizes)."""
    xs = hidden(c, seed, seqs, users, rank, weights=weights, multiple=128)
    P = max(len(r) for r in reads)
    idx = np.zeros((xs.shape[0], P), np.int32)
    for i, r in enumerate(reads):
        idx[i, :len(r)] = r
    h = jnp.take_along_axis(xs, jnp.asarray(idx)[..., None], axis=1)
    out = np.asarray(_head(_frozen(c), seeded.root_key(seed), weights, h))
    return [out[i, :len(r)] for i, r in enumerate(reads)]


# ---------------------------------------------------------------------------
# training: ColA's loss and the adapters' gradients, AdamW as the program runs
# it (repro.optim.optimizers.adamw: bias-corrected moments, eps outside the
# square root, decoupled weight decay)
# ---------------------------------------------------------------------------

CE_CHUNK = 512


def lora_init(c: dict, key, rank: int) -> dict:
    """The adapters ColA starts from (LoRA's rule, as the program states it
    in core/adapters.py): for the i-th tap in name order, a key
    fold_in(key, i) split over the layers, A ~ N(0, 1/rank) per layer and
    B = 0. Rebuilt here from the same key, not taken from the program."""
    m = seeded.dims(c)
    out = {}
    for i, tap in enumerate(seeded.ADAPTER_TAPS):     # "q" < "v": name order
        d_out = m["H"] * m["Dh"] if tap == "q" else m["K"] * m["Dh"]
        ks = jax.random.split(jax.random.fold_in(key, i), m["L"])
        a = jax.vmap(lambda kk: jax.random.normal(kk, (m["d"], rank))
                     / jnp.sqrt(jnp.float32(rank)))(ks)
        out[tap] = {"A": a, "B": jnp.zeros((m["L"], rank, d_out))}
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _loss_and_grads(cj, key, weights, adapters, tokens, labels):
    c = dict(cj)

    def loss(ad):
        x = _round(seeded.embedding(c, key), c["dtype"], "as_stated")[tokens]

        @jax.checkpoint
        def body(x, xs):
            l, ad_l = xs
            w = layer(c, key, l, weights)
            return jax.vmap(lambda xb: block(c, w, xb, ad_l,
                                             weights == "fp8"))(x), None

        L = seeded.dims(c)["L"]
        x, _ = jax.lax.scan(body, x, (jnp.arange(L), ad))
        h = _rms(x, _round(seeded.final_norm(c, key), c["dtype"],
                           "as_stated"), float(c["rms_norm_eps"]))
        wh = _round(seeded.head(c, key), c["dtype"], weights)

        @jax.checkpoint
        def ce(hc, yc):
            lg = jnp.einsum("bsd,dv->bsv", _q8(hc, weights == "fp8"), wh,
                            precision=HI)
            ll = jnp.take_along_axis(lg, yc[..., None], -1)[..., 0]
            return jnp.sum(jax.scipy.special.logsumexp(lg, -1) - ll)

        S = tokens.shape[1]
        tot = sum(ce(h[:, s0:s0 + CE_CHUNK], labels[:, s0:s0 + CE_CHUNK])
                  for s0 in range(0, S, CE_CHUNK))
        return tot / labels.size

    return jax.value_and_grad(loss)(adapters)


def train_steps(c: dict, seed: int, adapter_key, rank: int, batches: list,
                opt: dict, *, weights: str = "as_stated") -> dict:
    """ColA steps on ``batches`` from the LoRA start: each step's loss, the
    first step's gradient and the adapters' change after all the steps.
    Trees are {tap: {"A", "B"}} with the layer axis first."""
    cj, key = _frozen(c), seeded.root_key(seed)
    ad = lora_init(c, adapter_key, rank)
    start = ad
    m = jax.tree.map(jnp.zeros_like, ad)
    v = jax.tree.map(jnp.zeros_like, ad)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    losses, first = [], None
    for t, b in enumerate(batches, start=1):
        loss, g = _loss_and_grads(cj, key, weights, ad,
                                  jnp.asarray(b["tokens"]),
                                  jnp.asarray(b["labels"]))
        losses.append(float(loss))
        first = g if first is None else first
        m = jax.tree.map(lambda a, b_: b1 * a + (1 - b1) * b_, m, g)
        v = jax.tree.map(lambda a, b_: b2 * a + (1 - b2) * b_ * b_, v, g)
        ad = jax.tree.map(
            lambda p, m_, v_: p - opt["lr"] * (
                (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
                + wd * p), ad, m, v)
    return {"losses": losses,
            "first_grad": jax.tree.map(np.asarray, first),
            "change": jax.tree.map(lambda a, b_: np.asarray(a - b_), ad,
                                   start)}
