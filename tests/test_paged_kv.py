"""Chunked prefill + paged KV cache (ISSUE 9).

Acceptance invariants:
- chunked prefill (``prefill_chunk=C``) emits exactly the tokens of unchunked
  prefill on every layer plan — logits equal up to float32 reassociation
  for attention plans, token-exact (argmax) with tight logit tolerance for
  recurrent/moe plans, including prompts with an ``S % C != 0`` tail chunk;
- the paged KV layout (``kv_layout="paged"``) is bit-identical to the dense
  chunked run on every plan whose layers read a cache of the same length
  (pool + block table is a relayout, not a renumeration), and equal up to
  float32 reassociation where a local layer reads its ring; the fused paged
  Pallas kernel matches its gather oracle;
- HBM accounting: ``kv_cache_bytes()`` under the paged layout scales with
  blocks actually in use, not the horizon, and a paged engine admits prompts
  longer than the dense engine's old ``max_len`` ceiling with a small pool;
- pool safety: reservation-backed admission, refcounted frees, double frees
  raise, and a drained engine always returns the pool whole
  (``assert_empty``).  Churn-under-faults lives in tests/test_faults.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ColaConfig
from repro.core import gl
from repro.kernels import decode_attention as da
from repro.kernels import ref
from repro.models import model as M
from repro.runtime.kv_pager import BlockPager, PagerError
from repro.runtime.serve_loop import Request, ServeEngine


def _tiny(name="smollm-135m", **over):
    cfg = registry.reduced_config(name)
    base = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
                d_ff=128, vocab_size=128)
    base.update(over)
    return cfg.replace(**{k: v for k, v in base.items() if hasattr(cfg, k)})


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=p) for p in lens]


# one case per layer plan: uniform attn, moe, local/global pairs, uniform ssm,
# hybrid (shared attn over ssm backbone). P is chosen so P % C != 0 — the tail
# chunk is narrower than C and exercises the exact-width recurrent grouping
# and the per-row logit gather of the padded attention group.
PLAN_CASES = {
    "smollm-135m": dict(C=4, P=11, over={}, exact=True),
    # drop-free capacity so chunked routing can't change expert drops; the
    # residual difference is shape-dependent matmul blocking noise
    "qwen3-moe-30b-a3b": dict(C=4, P=9, over=dict(capacity_factor=8.0),
                              exact=False),
    "gemma2-9b": dict(C=4, P=13, over=dict(local_window=6), exact=True),
    "mamba2-370m": dict(C=4, P=11, over=dict(ssm_headdim=16, ssm_state=16),
                        exact=True),
    "zamba2-7b": dict(C=4, P=11, over=dict(ssm_headdim=16, ssm_state=16),
                      exact=True),
}


# ---------------------------------------------------------------------------
# pager unit tests
# ---------------------------------------------------------------------------

def test_pager_reserve_ensure_release_roundtrip():
    pg = BlockPager(n_blocks=8, block_size=4, slots=2, max_len=32)
    assert pg.max_blocks == 8 and pg.blocks_for(9) == 3 and pg.blocks_for(0) == 0
    assert pg.reserve(0, 9)                      # 3 blocks promised
    assert pg.free_unreserved() == 5
    assert pg.ensure(0, 6)                       # pos 0..6 -> 2 blocks
    assert pg.capacity(0) == 8 and pg.blocks_in_use() == 2
    assert pg.free_unreserved() == 5             # drawn from the reservation
    # table maps position // block -> the owned pool block, in order
    assert list(pg.table[0, :2]) == list(pg.owned(0))
    assert pg.ensure(0, 6)                       # idempotent, no new blocks
    assert pg.stats["allocs"] == 2
    pg.release(0)
    assert pg.blocks_in_use() == 0 and pg.capacity(0) == 0
    assert np.all(pg.table[0] == 0)
    pg.assert_empty()
    assert pg.stats["allocs"] == pg.stats["frees"] == 2


def test_pager_reserve_fails_clean_when_pool_promised():
    pg = BlockPager(n_blocks=4, block_size=4, slots=3, max_len=16)
    assert pg.reserve(0, 12)                     # 3 of 4 blocks
    assert not pg.reserve(1, 8)                  # would need 2, only 1 left
    assert pg.stats["reserve_failures"] == 1
    assert pg.free_unreserved() == 1             # failed reserve claims nothing
    assert pg.reserve(1, 4)
    # every free block is now promised: a slot with no reservation cannot
    # allocate even one block, while slot 1 can draw down its own promise
    assert not pg.ensure(2, 0)
    assert pg.ensure(1, 3)
    assert not pg.ensure(1, 4)                   # beyond its reservation
    pg.release(0)
    pg.release(1)
    pg.assert_empty()


def test_pager_release_is_refcounted_and_double_free_raises():
    pg = BlockPager(n_blocks=4, block_size=4, slots=2, max_len=16)
    assert pg.ensure(0, 5)
    blk = pg.owned(0)[0]
    pg.release(0)
    pg._owned[0] = [blk]                         # simulate a corrupted retire
    with pytest.raises(PagerError, match="double free"):
        pg.release(0)


def test_pager_assert_empty_detects_leak():
    pg = BlockPager(n_blocks=4, block_size=4, slots=2, max_len=16)
    assert pg.ensure(1, 0)
    with pytest.raises(PagerError, match="leaked"):
        pg.assert_empty()
    pg.release(1)
    pg.assert_empty()


# ---------------------------------------------------------------------------
# fused paged kernel vs gather oracle; ring oracle vs dense window
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,softcap", [(None, None), (9, 10.0)])
def test_paged_kernel_matches_oracle(window, softcap):
    """decode_attention_paged (interpret) == ref.sdpa_decode_paged with rows
    at scattered positions, a shuffled block assignment, and a dead slot."""
    rng = np.random.default_rng(0)
    B, H, K, Dh = 4, 8, 2, 64
    bs, nb_pool, nb_tab = 8, 16, 6
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(nb_pool, bs, K, Dh)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(nb_pool, bs, K, Dh)), jnp.float32)
    positions = np.array([3, 10, 21, 40], np.int32)
    table = np.zeros((B, nb_tab), np.int32)
    it = iter(rng.permutation(nb_pool))
    for b in range(B):
        for j in range(positions[b] // bs + 1):
            table[b, j] = next(it)
    table = jnp.asarray(table)
    positions = jnp.asarray(positions)
    live = jnp.asarray([True, True, False, True])
    assert da.supported_paged(q, k_pool, v_pool, table)

    o_ref = ref.sdpa_decode_paged(q, k_pool, v_pool, positions, table,
                                  live=live, window=window, softcap=softcap)
    o_pal = da.decode_attention_paged(q, k_pool, v_pool, positions, table,
                                      live=live, window=window,
                                      softcap=softcap, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=1e-5, rtol=1e-5)
    # dead slot is exact zeros, not stale-cache attention
    assert np.all(np.asarray(o_pal)[2] == 0)


def test_ring_oracle_bit_identical_to_dense_window():
    """The rolling ring cache (pairs local layers under the paged layout) with
    position p at ring index p % W_ring reads back the dense windowed
    oracle's values — including positions that have wrapped the ring."""
    rng = np.random.default_rng(0)
    B, K, Dh, Smax = 4, 2, 64, 32
    W, C = 5, 3
    Wr = W + C - 1
    q = jnp.asarray(rng.normal(size=(B, 1, 2 * K, Dh)), jnp.float32)
    kd = jnp.asarray(rng.normal(size=(B, Smax, K, Dh)), jnp.float32)
    vd = jnp.asarray(rng.normal(size=(B, Smax, K, Dh)), jnp.float32)
    kr = jnp.zeros((B, Wr, K, Dh), jnp.float32)
    vr = jnp.zeros((B, Wr, K, Dh), jnp.float32)
    pos = np.array([2, 7, 13, 25], np.int32)     # 13, 25 have wrapped (> Wr)
    for b in range(B):
        for p in range(pos[b] + 1):
            kr = kr.at[b, p % Wr].set(kd[b, p])
            vr = vr.at[b, p % Wr].set(vd[b, p])
    live = jnp.asarray([True, True, False, True])
    o_dense = ref.sdpa_decode(q, kd, vd, jnp.asarray(pos), live=live, window=W)
    o_ring = ref.sdpa_decode_ring(q, kr, vr, jnp.asarray(pos), live=live,
                                  window=W)
    # the same keys, values, masks, maxima and softmax denominators either
    # way; XLA:CPU blocks the P.V product by the key axis's length (W_ring
    # = 7 here, Smax = 32 dense), so its float32 sums reassociate: 245 of
    # the 1024 outputs differ, by at most 1.8e-7 absolute
    np.testing.assert_allclose(np.asarray(o_ring), np.asarray(o_dense),
                               rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# model level: chunked == full prefill; paged == dense (every layer plan)
# ---------------------------------------------------------------------------

def _chunk_run(cfg, params, prompt, cache, *, C, slot, slots, recurrent,
               pager=None):
    """Drive decode_step chunk-by-chunk the way the engine does: recurrent
    plans get exact-width tails, attention plans a padded width-C group with
    the per-row logit gather. Returns the last real token's logits."""
    P = len(prompt)
    consumed, lg_last = 0, None
    while consumed < P:
        c = min(C, P - consumed)
        width = c if recurrent else C
        toks = np.zeros((slots, width), np.int32)
        toks[slot, :c] = prompt[consumed:consumed + c]
        if pager is not None:
            assert pager.ensure(slot, consumed + width - 1)
        pos = np.zeros((slots,), np.int32)
        pos[slot] = consumed
        live = np.zeros((slots,), bool)
        live[slot] = True
        kw = ({"block_table": jnp.asarray(pager.table)}
              if pager is not None else {})
        lg, cache = M.decode_step(
            cfg, params, {"tokens": jnp.asarray(toks),
                          "positions": jnp.asarray(pos)},
            cache, live=jnp.asarray(live), **kw)
        lg_last = np.asarray(lg[slot, c - 1])
        consumed += c
    return lg_last


@pytest.mark.parametrize("name", sorted(PLAN_CASES))
def test_chunked_matches_prefill_and_paged_matches_dense(name):
    case = PLAN_CASES[name]
    C, P = case["C"], case["P"]
    assert P % C != 0                            # tail chunk narrower than C
    cfg = _tiny(name, **case["over"])
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompt = _prompts(cfg, (P,), seed=1)[0]
    slots, max_len, s = 3, 32, 1
    recurrent = M.has_recurrent_state(cfg)

    lg_full, _ = M.prefill(cfg, params, {"tokens": jnp.asarray(prompt[None, :])})
    lg_full = np.asarray(lg_full[0, 0])

    lg_d = _chunk_run(cfg, params, prompt, M.init_cache(cfg, slots, max_len),
                      C=C, slot=s, slots=slots, recurrent=recurrent)
    assert int(np.argmax(lg_d)) == int(np.argmax(lg_full))
    if case["exact"] and not recurrent:
        # the same attention, but XLA:CPU blocks the Q.K and P.V products by
        # their shapes (a 4-query chunk against the Smax = 32 cache, or the
        # whole prompt against itself), so float32 sums reassociate: at most
        # 4.9e-7 (gemma2-9b) and 3.7e-7 (smollm-135m) apart
        np.testing.assert_allclose(lg_d, lg_full, rtol=0, atol=4e-6)
    else:
        np.testing.assert_allclose(lg_d, lg_full, atol=1e-3)

    # paged relayout: bit-identical to the dense chunked run wherever both
    # read a cache of the same length; a local layer of the pairs plan reads
    # its W_ring ring, which sums as the ring test above does (gemma2-9b: at
    # most 6.6e-7 apart)
    plan = M.layer_plan(cfg)
    ring_len = cfg.local_window + C - 1 if plan[0] == "pairs" else None
    pager = BlockPager(n_blocks=16, block_size=8, slots=slots, max_len=max_len)
    assert pager.reserve(s, P)
    cache_p = M.init_cache(cfg, slots, max_len, kv_layout="paged",
                           kv_blocks=16, kv_block=8, ring_len=ring_len)
    lg_p = _chunk_run(cfg, params, prompt, cache_p, C=C, slot=s, slots=slots,
                      recurrent=recurrent, pager=pager)
    if ring_len is None:
        np.testing.assert_array_equal(lg_p, lg_d)
    else:
        assert int(np.argmax(lg_p)) == int(np.argmax(lg_d))
        np.testing.assert_allclose(lg_p, lg_d, rtol=0, atol=5e-6)


# ---------------------------------------------------------------------------
# engine level: every serving mode emits identical tokens
# ---------------------------------------------------------------------------

def _run_modes(cfg, params, prompts, banks=None, max_new=5, slots=4,
               max_len=64, **extra_modes):
    def run(**kw):
        eng = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                          user_adapters=banks, **kw)
        reqs = [Request(rid=i, user=(i % 2 if banks else 0), prompt=p,
                        max_new=max_new) for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_idle()
        assert all(r.done and r.status == "done" for r in reqs)
        return [r.out for r in reqs], eng
    return run


def test_engine_modes_token_identical():
    """batched / reference / chunked / paged / burst / paged+burst all emit
    the same tokens (prompt lens include 1 and a chunk-straddling 21)."""
    cfg = _tiny()
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg, (1, 5, 9, 13, 21))
    run = _run_modes(cfg, params, prompts, max_new=6)
    base, _ = run(prefill_mode="batched")
    modes = {
        "reference": dict(prefill_mode="reference"),
        "chunked": dict(prefill_chunk=4),
        "paged": dict(prefill_chunk=4, kv_layout="paged", kv_block=8),
        "burst": dict(decode_burst=4),
        "paged_burst": dict(prefill_chunk=4, kv_layout="paged", kv_block=8,
                            decode_burst=4),
    }
    for mode, kw in modes.items():
        out, eng = run(**kw)
        assert out == base, f"{mode} != batched"
        if eng.pager is not None:
            eng.pager.assert_empty()
            assert eng.stats["kv_allocs"] == eng.stats["kv_frees"]


@pytest.mark.parametrize("name", sorted(PLAN_CASES))
def test_engine_chunked_and_paged_match_unchunked(name):
    case = PLAN_CASES[name]
    cfg = _tiny(name, **case["over"])
    params = M.init(cfg, jax.random.PRNGKey(0))
    banks = None
    if name == "smollm-135m":                    # adapters ride along once
        cc = ColaConfig(mode="lora", family="lowrank", taps="qv", rank=4)
        banks = [gl.init_adapters(cfg, cc,
                                  jax.random.fold_in(jax.random.PRNGKey(7), u))
                 for u in range(2)]
    prompts = _prompts(cfg, (1, 5, 9, 14))
    run = _run_modes(cfg, params, prompts, banks=banks)
    base, _ = run(prefill_mode="batched")
    chk, _ = run(prefill_chunk=case["C"])
    assert chk == base, f"{name}: chunked != unchunked"
    pg, eng = run(prefill_chunk=case["C"], kv_layout="paged", kv_block=8)
    assert pg == base, f"{name}: paged != dense"
    eng.pager.assert_empty()


# ---------------------------------------------------------------------------
# capacity: virtual horizon, max_prompt, HBM proportional to used blocks
# ---------------------------------------------------------------------------

def test_paged_engine_admits_prompt_beyond_dense_horizon():
    """With a 40-block pool the paged engine serves a 97-token prompt under a
    max_len=256 virtual horizon — a prompt the dense max_len=64 engine
    rejects outright — while peak pool use stays far below the horizon."""
    cfg = _tiny()
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompt = _prompts(cfg, (97,), seed=3)[0]

    dense = ServeEngine(cfg, params, slots=4, max_len=64)
    rej = Request(rid=0, user=0, prompt=prompt, max_new=4)
    dense.submit(rej)
    assert rej.done and "prompt length 97" in rej.status

    eng = ServeEngine(cfg, params, slots=4, max_len=256, prefill_chunk=8,
                      kv_layout="paged", kv_block=8, kv_blocks=40)
    r = Request(rid=1, user=0, prompt=prompt, max_new=4)
    eng.submit(r)
    eng.run_until_idle()
    assert r.status == "done" and len(r.out) == 4
    eng.pager.assert_empty()
    # pool sized for the request, not slots * horizon (= 128 blocks)
    assert eng.stats["kv_blocks_peak"] <= eng.pager.blocks_for(97 + 8)


def test_max_prompt_boundary_and_rejection_reason():
    cfg = _tiny()
    params = M.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, max_prompt=20)
    ok = Request(rid=0, user=0, prompt=_prompts(cfg, (20,))[0], max_new=2)
    bad = Request(rid=1, user=0, prompt=_prompts(cfg, (21,))[0], max_new=2)
    eng.submit(ok)
    eng.submit(bad)
    assert not ok.done
    assert bad.done and bad.status.startswith("rejected: ")
    assert "prompt length 21 > max_prompt 20" in bad.status
    assert "max_len=64" in bad.status
    eng.run_until_idle()
    assert ok.status == "done"
    # default max_prompt remains the dense-compatible max_len - 1
    assert ServeEngine(cfg, params, slots=2, max_len=64).max_prompt == 63


def test_paged_cache_bytes_proportional_to_blocks_in_use():
    """kv_cache_bytes under the paged layout is affine in blocks_in_use (the
    non-pool leaves are a fixed intercept) and far below the dense layout's
    horizon-scaled footprint at the same max_len."""
    cfg = _tiny()
    params = M.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, slots=4, max_len=256, prefill_chunk=8,
                      kv_layout="paged", kv_block=8, kv_blocks=64)
    dense = ServeEngine(cfg, params, slots=4, max_len=256)
    assert eng.kv_cache_bytes() < dense.kv_cache_bytes() / 100

    r = Request(rid=0, user=0, prompt=_prompts(cfg, (33,), seed=3)[0],
                max_new=8)
    eng.submit(r)
    samples = []
    while not r.done:
        eng.tick()
        samples.append((eng.stats["kv_blocks_in_use"], eng.kv_cache_bytes()))
    counts = sorted({c for c, _ in samples})
    # KV is written for the prompt plus every generated token except the last
    # (never fed back): P + max_new - 1 positions
    assert len(counts) >= 2 and counts[-1] == eng.pager.blocks_for(33 + 8 - 1)
    by_count = dict(samples)
    slope = ((by_count[counts[-1]] - by_count[counts[0]])
             / (counts[-1] - counts[0]))
    assert slope > 0
    for c, b in samples:
        assert b == by_count[counts[0]] + (c - counts[0]) * slope
    eng.pager.assert_empty()


def test_queued_request_waits_for_pool_capacity():
    """When the pool can't cover a second request's worst case, admission
    leaves it queued (reserve fails clean) until the first retires."""
    cfg = _tiny()
    params = M.init(cfg, jax.random.PRNGKey(0))
    # 6 blocks x 8 = 48 positions; each request reserves ceil(28/4)*4 = 28
    # positions = 4 blocks, so only one fits at a time
    eng = ServeEngine(cfg, params, slots=2, max_len=64, prefill_chunk=4,
                      kv_layout="paged", kv_block=8, kv_blocks=6)
    reqs = [Request(rid=i, user=0, prompt=_prompts(cfg, (26,), seed=i)[0],
                    max_new=2) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.tick()
    assert sum(r is not None for r in eng.active) == 1 and len(eng.queue) == 1
    assert eng.stats["kv_reserve_failures"] >= 1
    eng.run_until_idle()
    assert all(r.status == "done" and len(r.out) == 2 for r in reqs)
    eng.pager.assert_empty()
