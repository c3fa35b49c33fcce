"""The generator: the same seed gives the same inputs, another seed the
same sizes and gaps in another order."""
import numpy as np
import pytest

from bench import core, traffic
from bench.tests.conftest import tiny_serve_mix

MIXES = ["serve.chat", "serve.tenants"]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = core.traffic(name)
    a = traffic.serve_schedule(mix, 2**31 + 7, 10, 1000)
    b = traffic.serve_schedule(mix, 2**31 + 7, 10, 1000)
    assert np.array_equal(a["offsets"], b["offsets"])
    assert np.array_equal(a["max_new"], b["max_new"])
    assert all(np.array_equal(x, y) for x, y in zip(a["prompts"], b["prompts"]))


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_work_other_order(name):
    mix = core.traffic(name)
    a = traffic.serve_schedule(mix, 1, 30, 1000)
    b = traffic.serve_schedule(mix, 2, 30, 1000)
    n = int(round(mix["arrivals"]["rate_per_s"] * 30))
    assert len(a["offsets"]) == len(b["offsets"]) == n
    # the same arrival times, other requests at them
    assert np.array_equal(a["offsets"], b["offsets"])
    assert a["offsets"].max() < 30
    assert not np.array_equal(a["max_new"], b["max_new"])
    assert [len(p) for p in a["prompts"]] != [len(p) for p in b["prompts"]]
    for k in ("max_new", "users"):
        assert np.array_equal(np.sort(a[k]), np.sort(b[k]))
    assert sorted(map(len, a["prompts"])) == sorted(map(len, b["prompts"]))


def test_bursty_gaps_have_the_stated_spread():
    mix = tiny_serve_mix()
    g = traffic.gaps(mix["arrivals"], 4000, 100.0)
    assert abs(g.sum() - 100.0) < 1e-9
    assert 1.8 < g.std() / g.mean() < 2.2


def test_lengths_stay_in_their_clip():
    spec = {"median": 512, "sigma": 1.0, "min": 32, "max": 4096}
    x = traffic.lognormal_lengths(spec, 1000)
    assert x.min() >= 32 and x.max() == 4096
    assert abs(np.median(x) - 512) <= 2


def test_train_batches_rows_all_differ():
    mix = {"batch": 2, "seq": 16, "distinct": 3}
    bs = traffic.train_batches(mix, 5, 1000)
    rows = {tuple(r) for b in bs for r in b["tokens"]}
    assert len(rows) == 6
    assert np.array_equal(bs[0]["labels"][:, :-1], bs[0]["tokens"][:, 1:])
