"""The one generator of traffic: it reads a mix's data file
(``bench/traffic/<mix>.json``) and makes the run's inputs from ``--seed``.

Every seed gets the same work in another order: prompt and output lengths,
inter-arrival gaps and users are taken at the quantiles (i + 1/2) / n of
their distributions. The arrival times are the same for every seed (the gaps
in one fixed shuffled order, so bursts keep their sizes); the seed shuffles
which request, of which lengths and user, arrives at each of them, and draws
the token ids. So the spread between runs with different seeds is the
system's, not the draw's.

Serving mixes (``"kind": "serve"``) give an open loop: request i is due at
``offsets[i]`` seconds after the window opens, whatever the system does.
Training mixes (``"kind": "train"``) give fixed batches that the window
cycles through.
"""
from __future__ import annotations

import statistics

import numpy as np

# A fixed base sample for distributions without a closed-form quantile (the
# gamma of bursty arrivals): its quantiles depend on no run's seed.
_BASE = 1 << 16


def _mids(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``median * exp(sigma * z)`` at the n mid-quantiles, clipped."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(u) for u in _mids(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def gaps(spec: dict, n: int, seconds: float) -> np.ndarray:
    """n inter-arrival gaps summing to ``seconds``: exponential (Poisson
    arrivals) or gamma with the given coefficient of variation (bursts)."""
    u = _mids(n)
    if spec["process"] == "poisson":
        g = -np.log1p(-u)
    elif spec["process"] == "gamma":
        k = 1.0 / spec["cv"] ** 2
        base = np.sort(np.random.default_rng(0).gamma(k, 1.0, _BASE))
        g = base[np.minimum((u * _BASE).astype(np.int64), _BASE - 1)]
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return g * (seconds / g.sum())


def zipf_users(spec: dict, n: int) -> np.ndarray:
    p = 1.0 / np.arange(1, spec["count"] + 1) ** spec["zipf_s"]
    cdf = np.cumsum(p / p.sum())
    return np.minimum(np.searchsorted(cdf, _mids(n)), spec["count"] - 1)


def serve_schedule(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    """The window's requests: due offsets (s), prompts, output lengths and
    users. ``round(rate * seconds)`` requests are due inside the window."""
    n = max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))
    g = np.random.default_rng(0).permutation(gaps(mix["arrivals"], n,
                                                  seconds))
    rng = np.random.default_rng(seed)
    offsets = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    plen = rng.permutation(lognormal_lengths(mix["prompt"], n))
    olen = rng.permutation(lognormal_lengths(mix["output"], n))
    users = rng.permutation(zipf_users(mix["users"], n))
    prompts = [rng.integers(0, vocab, size=int(p), dtype=np.int32)
               for p in plen]
    return {"offsets": offsets, "prompts": prompts, "max_new": olen,
            "users": users}


def horizon(mix: dict) -> int:
    """Positions a slot must hold: the longest prompt and output, plus the
    position the last token would be fed at."""
    return int(mix["prompt"]["max"] + mix["output"]["max"] + 1)


def train_batches(mix: dict, seed: int, vocab: int) -> list[dict]:
    """``mix["distinct"]`` batches of (batch, seq) next-token rows, all rows
    different; labels are the tokens shifted by one."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(mix["distinct"]):
        t = rng.integers(0, vocab, size=(mix["batch"], mix["seq"] + 1),
                         dtype=np.int32)
        out.append({"tokens": t[:, :-1], "labels": t[:, 1:]})
    return out
