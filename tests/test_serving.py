"""Serving engine: batched prefill == single-row reference (logits and
tokens, with and without per-user adapters), slot-mask isolation (admission
must not perturb live slots), and engine stats consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ColaConfig
from repro.core import gl
from repro.models import model as M
from repro.runtime.serve_loop import Request, ServeEngine, _bucket


def _tiny():
    cfg = registry.reduced_config("smollm-135m").replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, vocab_size=128)
    key = jax.random.PRNGKey(0)
    return cfg, M.init(cfg, key), key


def _banks(cfg, key):
    cc = ColaConfig(mode="lora", family="lowrank", taps="qv", rank=4)
    ad0 = gl.init_adapters(cfg, cc, jax.random.fold_in(key, 1))
    ad1 = gl.init_adapters(cfg, cc, jax.random.fold_in(key, 2))
    ad1 = jax.tree.map(lambda a: a + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 3), a.shape), ad1)
    return [ad0, ad1]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=p) for p in lens]


# ---------------------------------------------------------------------------
# batched prefill == token-by-token reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_adapters", [False, True])
def test_batched_prefill_matches_reference_tokens(with_adapters):
    """Per-slot generated tokens identical between the one-shot padded batched
    prefill and the token-by-token single-row reference, across mixed prompt
    lengths (including length-1 prompts, which skip prefill entirely)."""
    cfg, params, key = _tiny()
    banks = _banks(cfg, key) if with_adapters else None
    prompts = _prompts(cfg, (1, 5, 9, 13))
    outs = {}
    for mode in ("batched", "reference"):
        eng = ServeEngine(cfg, params, slots=4, max_len=64,
                          user_adapters=banks, prefill_mode=mode)
        reqs = [Request(rid=i, user=i % 2, prompt=p, max_new=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_idle()
        outs[mode] = [r.out for r in reqs]
    assert outs["batched"] == outs["reference"]


def test_batched_prefill_matches_reference_logits():
    """Model-level: scatter-prefill into a slot cache, then one decode step —
    logits match feeding the prompt token-by-token through the live-masked
    decode path (the engine's two prefill modes, minus the engine)."""
    cfg, params, key = _tiny()
    slots, max_len = 3, 32
    prompts = _prompts(cfg, (7, 4))
    slot_ids = np.array([0, 2], np.int32)

    # reference: per-token decode with a single-slot live mask
    cache_ref = M.init_cache(cfg, slots, max_len)
    for j, prompt in enumerate(prompts):
        s = slot_ids[j]
        for t, tok in enumerate(prompt[:-1]):
            toks = np.zeros((slots, 1), np.int32)
            toks[s, 0] = tok
            pos = np.zeros((slots,), np.int32)
            pos[s] = t
            live = np.zeros((slots,), bool)
            live[s] = True
            _, cache_ref = M.decode_step(
                cfg, params, {"tokens": jnp.asarray(toks),
                              "positions": jnp.asarray(pos)}, cache_ref,
                live=jnp.asarray(live))

    # batched: one padded prefill scattered into the slot cache
    pmax = max(len(p) for p in prompts) - 1
    toks = np.zeros((len(prompts), pmax), np.int32)
    for j, p in enumerate(prompts):
        toks[j, :len(p) - 1] = p[:-1]
    _, pre = M.prefill(cfg, params, {"tokens": jnp.asarray(toks)})
    cache_bat = M.scatter_prefill_cache(M.init_cache(cfg, slots, max_len),
                                        pre, jnp.asarray(slot_ids))

    # decode the last prompt token for both slots at once; compare logits
    toks = np.zeros((slots, 1), np.int32)
    pos = np.zeros((slots,), np.int32)
    live = np.zeros((slots,), bool)
    for j, p in enumerate(prompts):
        toks[slot_ids[j], 0] = p[-1]
        pos[slot_ids[j]] = len(p) - 1
        live[slot_ids[j]] = True
    batch = {"tokens": jnp.asarray(toks), "positions": jnp.asarray(pos)}
    lg_ref, _ = M.decode_step(cfg, params, batch, cache_ref,
                              live=jnp.asarray(live))
    lg_bat, _ = M.decode_step(cfg, params, batch, cache_bat,
                              live=jnp.asarray(live))
    np.testing.assert_allclose(np.asarray(lg_bat[slot_ids]),
                               np.asarray(lg_ref[slot_ids]),
                               rtol=2e-4, atol=2e-5)


def test_prefill_lengths_gathers_per_row_logits():
    """prefill(lengths=...) on a right-padded batch returns each row's
    unpadded last-token logits."""
    cfg, params, key = _tiny()
    prompts = _prompts(cfg, (4, 7))
    pmax = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), pmax), np.int32)
    for j, p in enumerate(prompts):
        toks[j, :len(p)] = p
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    lg, _ = M.prefill(cfg, params, {"tokens": jnp.asarray(toks)},
                      lengths=lengths)
    for j, p in enumerate(prompts):
        lg_solo, _ = M.prefill(cfg, params,
                               {"tokens": jnp.asarray(p[None, :])})
        np.testing.assert_allclose(np.asarray(lg[j, 0]),
                                   np.asarray(lg_solo[0, 0]),
                                   rtol=2e-4, atol=2e-5)


def test_batched_prefill_matches_reference_ssm():
    """Recurrent-state models must prefill each row at its exact length (a
    right-padded batch would fold pad tokens into the final ssm/conv state).
    Regression: batched == reference tokens on an SSM config with mixed
    prompt lengths that would otherwise hit different pad buckets."""
    cfg = registry.reduced_config("mamba2-370m").replace(
        n_layers=2, d_model=64, vocab_size=128)
    params = M.init(cfg, jax.random.PRNGKey(0))
    assert M.has_recurrent_state(cfg)
    prompts = _prompts(cfg, (3, 6, 11))
    outs = {}
    for mode in ("batched", "reference"):
        eng = ServeEngine(cfg, params, slots=3, max_len=32, prefill_mode=mode)
        reqs = [Request(rid=i, user=0, prompt=p, max_new=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_idle()
        outs[mode] = [r.out for r in reqs]
    assert outs["batched"] == outs["reference"]


def test_prefill_bucket_capped_at_max_len():
    """A prompt whose pad bucket exceeds max_len must still prefill (the
    bucket is clamped to the cache's sequence axis)."""
    cfg, params, key = _tiny()
    eng = ServeEngine(cfg, params, slots=2, max_len=100)
    prompt = _prompts(cfg, (70,))[0]
    req = Request(rid=0, user=0, prompt=prompt, max_new=3)
    eng.submit(req)
    eng.run_until_idle()
    assert req.done and len(req.out) == 3


# ---------------------------------------------------------------------------
# slot isolation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["batched", "reference"])
def test_admission_mid_flight_leaves_live_slots_bit_identical(mode):
    """Admitting a request while others decode must not change their output."""
    cfg, params, key = _tiny()
    prompts = _prompts(cfg, (9, 6))

    def run(second_request: bool):
        eng = ServeEngine(cfg, params, slots=2, max_len=64, prefill_mode=mode,
                          user_adapters=_banks(cfg, key))
        r0 = Request(rid=0, user=0, prompt=prompts[0], max_new=10)
        eng.submit(r0)
        for _ in range(3):
            eng.tick()
        if second_request:
            eng.submit(Request(rid=1, user=1, prompt=prompts[1], max_new=4))
        eng.run_until_idle()
        return r0.out

    assert run(False) == run(True)


def test_feed_does_not_clobber_other_slots():
    """The single-row reference prefill must only write its target slot's
    cache row (regression: the unmasked version wrote token 0 at position 0
    of every other slot)."""
    cfg, params, key = _tiny()
    eng = ServeEngine(cfg, params, slots=3, max_len=32,
                      prefill_mode="reference")
    for t in range(4):
        eng._feed(1, 5 + t, t)
    k = np.asarray(eng.cache["layers"]["k"])   # (L, slots, max_len, K, Dh)
    assert np.all(k[:, 0] == 0) and np.all(k[:, 2] == 0), \
        "non-target slot cache rows were written"
    assert np.any(k[:, 1, :4] != 0), "target slot cache row was not written"


def test_scatter_prefill_cache_drops_out_of_range_rows():
    """Padding rows of a bucketed prefill batch carry slot id == slots and
    must be dropped, not wrapped or clamped onto a real slot."""
    cfg, params, key = _tiny()
    slots, max_len = 2, 32
    cache = M.init_cache(cfg, slots, max_len)
    toks = jnp.asarray(_prompts(cfg, (8,))[0][None, :].astype(np.int32))
    _, pre = M.prefill(cfg, params, {"tokens": toks})
    out = M.scatter_prefill_cache(cache, pre,
                                  jnp.asarray(np.array([slots], np.int32)))
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# stats / admission batching
# ---------------------------------------------------------------------------

def test_engine_stats_consistency():
    cfg, params, key = _tiny()
    prompts = _prompts(cfg, (5, 8, 3, 6, 4))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, admit_batch=2)
    reqs = [Request(rid=i, user=0, prompt=p, max_new=4)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    assert eng.stats["completed"] == len(prompts)
    assert eng.stats["admitted"] == len(prompts)
    assert eng.stats["tokens"] == sum(len(r.out) for r in reqs) == 4 * len(prompts)
    # the full prompt goes through prefill; the first generated token is
    # emitted from the prefill logits, the rest from decode ticks
    assert eng.stats["prefill_tokens"] == sum(len(p) for p in prompts)
    assert eng.stats["decode_tokens"] == eng.stats["tokens"] - len(prompts)
    # 2 slots, 5 requests x 3 decode tokens (the first of the 4 comes from
    # prefill) -> at least ceil(15/2) decode ticks
    assert eng.stats["ticks"] >= 8
    stats = eng.request_stats()
    assert len(stats) == len(prompts)
    for s in stats:
        assert s["new_tokens"] == 4
        assert s["ttft"] is not None and s["latency"] is not None
        assert 0 <= s["ttft"] <= s["latency"]
    th = eng.throughput()
    assert th["decode_tok_per_s"] > 0 and th["prefill_tok_per_s"] > 0
    assert th["completed"] == len(prompts)


def test_admit_batch_caps_admission():
    cfg, params, key = _tiny()
    eng = ServeEngine(cfg, params, slots=4, max_len=64, admit_batch=1)
    for i, p in enumerate(_prompts(cfg, (4, 4, 4))):
        eng.submit(Request(rid=i, user=0, prompt=p, max_new=6))
    eng.tick()
    assert sum(r is not None for r in eng.active) == 1
    eng.tick()
    assert sum(r is not None for r in eng.active) == 2
    eng.run_until_idle()
    assert eng.stats["completed"] == 3


def test_bucket_rounds_up_to_power_of_two():
    assert _bucket(1) == 8 and _bucket(8) == 8 and _bucket(9) == 16
    assert _bucket(100) == 128 and _bucket(3, floor=1) == 4


# ---------------------------------------------------------------------------
# admission-time request validation
# ---------------------------------------------------------------------------

def test_submit_rejects_invalid_requests():
    """Bad requests get a terminal rejected status without ever occupying a
    slot or crashing a tick; valid ones are unaffected."""
    cfg, params, key = _tiny()
    eng = ServeEngine(cfg, params, slots=2, max_len=16,
                      user_adapters=_banks(cfg, key))
    bad = [
        Request(rid=0, user=0, prompt=np.array([], np.int32), max_new=4),
        Request(rid=1, user=0, prompt=np.arange(16) % cfg.vocab_size,
                max_new=4),                                    # > max_len - 1
        Request(rid=2, user=0, prompt=np.arange(4), max_new=0),
        Request(rid=3, user=0, prompt=np.arange(4), max_new=-2),
        Request(rid=4, user=7, prompt=np.arange(4), max_new=4),  # unknown user
    ]
    for r in bad:
        eng.submit(r)
    assert not eng.queue and all(r is None for r in eng.active)
    assert eng.stats["rejected"] == len(bad)
    assert len(eng.finished) == len(bad)
    for r in bad:
        assert r.done and r.status.startswith("rejected: ")
        assert r.out == [] and r.latency is not None
    assert "empty prompt" in bad[0].status
    assert "prompt length" in bad[1].status
    assert "max_new" in bad[2].status and "max_new" in bad[3].status
    assert "unknown user" in bad[4].status

    ok = Request(rid=5, user=1, prompt=np.arange(5) % cfg.vocab_size, max_new=3)
    eng.submit(ok)
    eng.run_until_idle()
    assert ok.status == "done" and len(ok.out) == 3
    assert eng.stats["completed"] == 1


def test_submit_without_bank_accepts_any_user_id():
    """With no adapter bank configured there is no user routing to validate."""
    cfg, params, _ = _tiny()
    eng = ServeEngine(cfg, params, slots=1, max_len=32)
    r = Request(rid=0, user=99, prompt=np.arange(4) % cfg.vocab_size, max_new=2)
    eng.submit(r)
    eng.run_until_idle()
    assert r.status == "done" and eng.stats["rejected"] == 0


# ---------------------------------------------------------------------------
# burst decoding
# ---------------------------------------------------------------------------

def _run_engine(cfg, params, banks, prompts, max_new, **kw):
    eng = ServeEngine(cfg, params, slots=len(prompts), max_len=64,
                      user_adapters=banks, **kw)
    reqs = [Request(rid=i, user=i % 2 if banks else 0, prompt=p,
                    max_new=max_new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    return [r.out for r in reqs], eng


@pytest.mark.parametrize("with_adapters", [False, True])
def test_burst_decode_tokens_bit_identical(with_adapters):
    """decode_burst=N fuses ticks into one lax.scan; emitted tokens must be
    bit-identical to tick-at-a-time decoding (max_new=17 forces uneven burst
    splits: 8+4+2+1 plus the TTFT-protected first tick)."""
    cfg, params, key = _tiny()
    banks = _banks(cfg, key) if with_adapters else None
    prompts = _prompts(cfg, (5, 9, 13))
    o1, e1 = _run_engine(cfg, params, banks, prompts, max_new=17)
    o2, e2 = _run_engine(cfg, params, banks, prompts, max_new=17,
                         decode_burst=8)
    assert o1 == o2
    assert e2.stats["tokens"] == e1.stats["tokens"]
    assert all(len(o) == 17 for o in o2)


def test_burst_decode_staggered_completion():
    """Mixed max_new across slots: bursts must shrink to the soonest
    completion so no slot ever overruns its budget."""
    cfg, params, key = _tiny()
    prompts = _prompts(cfg, (5, 9))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, decode_burst=16)
    r0 = Request(rid=0, user=0, prompt=prompts[0], max_new=3)
    r1 = Request(rid=1, user=0, prompt=prompts[1], max_new=21)
    eng.submit(r0)
    eng.submit(r1)
    eng.run_until_idle()
    assert len(r0.out) == 3 and len(r1.out) == 21
    ref_eng = ServeEngine(cfg, params, slots=2, max_len=64)
    q0 = Request(rid=0, user=0, prompt=prompts[0], max_new=3)
    q1 = Request(rid=1, user=0, prompt=prompts[1], max_new=21)
    ref_eng.submit(q0)
    ref_eng.submit(q1)
    ref_eng.run_until_idle()
    assert r0.out == q0.out and r1.out == q1.out


# ---------------------------------------------------------------------------
# int8-stored adapter banks
# ---------------------------------------------------------------------------

def _dequant_banks(banks):
    from repro.kernels.multi_lora import quant_rows
    out = []
    for a in banks:
        d = {}
        for tap, leaves in a.items():
            d[tap] = {}
            for n, leaf in leaves.items():
                q, s = quant_rows(leaf)
                d[tap][n] = (q.astype(jnp.float32) * s).astype(leaf.dtype)
        out.append(d)
    return out


def test_int8_bank_matches_dequantized_f32_serving():
    """bank_store="int8" must emit exactly the tokens of serving the
    explicitly round-tripped (dequantised) f32 bank — the int8 path changes
    storage and load, never math."""
    cfg, params, key = _tiny()
    banks = _banks(cfg, key)
    prompts = _prompts(cfg, (5, 9, 13))
    o_q8, e_q8 = _run_engine(cfg, params, banks, prompts, max_new=8,
                             bank_store="int8")
    o_f32, _ = _run_engine(cfg, params, _dequant_banks(banks), prompts,
                           max_new=8)
    assert o_q8 == o_f32
    # the stored bank is int8 codes + f32 scales, never f32 weights
    for tap, leaves in e_q8.bank.items():
        assert set(n.rsplit("_", 1)[-1] for n in leaves) == {"q", "scale"}
        for n, leaf in leaves.items():
            if n.endswith("_q"):
                assert leaf.dtype == jnp.int8


def test_int8_bank_install_adapters_quantizes_incoming():
    """Hot-swapping f32 adapters into an int8 bank quantises on install and
    the swap actually changes served tokens for that user only."""
    cfg, params, key = _tiny()
    banks = _banks(cfg, key)
    prompts = _prompts(cfg, (6, 6))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, user_adapters=banks,
                      bank_store="int8")
    from repro.core import gl
    from repro.configs.base import ColaConfig
    cc = ColaConfig(mode="lora", family="lowrank", taps="qv", rank=4)
    new = gl.init_adapters(cfg, cc, jax.random.fold_in(key, 7))
    new = jax.tree.map(lambda a: a + 0.5, new)
    assert eng.install_adapters(1, new, version=1)
    assert eng.stats["bank_installs"] == 1
    for tap, leaves in eng.bank.items():
        for n, leaf in leaves.items():
            if n.endswith("_q"):
                assert leaf.dtype == jnp.int8
    # stale version is still rejected on the q8 path
    assert not eng.install_adapters(1, new, version=1)
    assert eng.stats["bank_rejected"] == 1


# ---------------------------------------------------------------------------
# decode kernel switch (ref backend vs fused interpret kernels)
# ---------------------------------------------------------------------------

def test_decode_tokens_identical_across_kernel_backends():
    """End-to-end engine regression for the fused decode kernels: tokens under
    the pallas_interpret backend (fused decode attention + grouped multi-LoRA)
    match the jnp reference backend exactly. Uses d_head=64 so the decode
    attention kernel's support gate engages."""
    from repro.kernels import ops
    cfg, params, key = _tiny()
    cfg = cfg.replace(n_heads=2, n_kv_heads=1, d_head=64)
    params = M.init(cfg, jax.random.PRNGKey(0))
    banks = _banks(cfg, key)
    prompts = _prompts(cfg, (5, 9))
    o_ref, _ = _run_engine(cfg, params, banks, prompts, max_new=5)
    ops.set_backend("pallas_interpret")
    try:
        o_int, _ = _run_engine(cfg, params, banks, prompts, max_new=5)
    finally:
        ops.set_backend(None)
    assert o_ref == o_int
