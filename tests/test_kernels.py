"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import cola_fit as ck
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import multi_lora as ml
from repro.kernels import ops, ref, ssd_scan


def _tol(dt):
    return dict(rtol=2e-2, atol=5e-2) if dt == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("S,H,K,D", [(128, 4, 4, 64), (256, 4, 2, 64),
                                     (256, 8, 2, 128), (128, 6, 3, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_fwd_sweep(S, H, K, D, dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, S, H, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, S, K, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, S, K, D), dtype)
    pos = jnp.arange(S)[None]
    o_ref = ref.sdpa(q, k, v, q_positions=pos, kv_positions=pos)
    o = fa.flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window,softcap", [(None, None), (64, None),
                                            (None, 30.0), (64, 30.0)])
def test_flash_attention_masking_variants(window, softcap):
    key = jax.random.PRNGKey(1)
    S, H, K, D = 256, 4, 2, 64
    q = jax.random.normal(key, (1, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, S, K, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, S, K, D))
    pos = jnp.arange(S)[None]
    o_ref = ref.sdpa(q, k, v, q_positions=pos, kv_positions=pos,
                     window=window, softcap=softcap)
    o = fa.flash_attention(q, k, v, window=window, softcap=softcap,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_backward():
    key = jax.random.PRNGKey(2)
    S, H, K, D = 128, 4, 2, 64
    q = jax.random.normal(key, (1, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, S, K, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, S, K, D))
    pos = jnp.arange(S)[None]

    def loss_ref(q, k, v):
        return jnp.sum(ref.sdpa(q, k, v, q_positions=pos,
                                kv_positions=pos) ** 2)

    def loss_ker(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, interpret=True) ** 2)

    g1 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ker, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=2e-4)


@pytest.mark.parametrize("T,din,dout,r", [(256, 128, 128, 8), (512, 192, 96, 16),
                                          (128, 64, 256, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cola_fit_sweep(T, din, dout, r, dtype):
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (T, din), dtype)
    g = jax.random.normal(jax.random.fold_in(key, 1), (T, dout), dtype) * 0.01
    A = jax.random.normal(jax.random.fold_in(key, 2), (din, r), jnp.float32)
    B = jax.random.normal(jax.random.fold_in(key, 3), (r, dout), jnp.float32)
    dA1, dB1 = ref.cola_fit_lowrank(x, g, A, B, scale=1.0)
    dA2, dB2 = ck.cola_fit_lowrank(x, g, A, B, scale=1.0, interpret=True)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dA1), np.asarray(dA2), **tol)
    np.testing.assert_allclose(np.asarray(dB1), np.asarray(dB2), **tol)


# ---------------------------------------------------------------------------
# fused single-query decode attention (serving hot path)
# ---------------------------------------------------------------------------

def _decode_case(key, B, Smax, H, K, D, dtype=jnp.float32, seed_positions=None):
    q = jax.random.normal(key, (B, 1, H, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Smax, K, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Smax, K, D), dtype)
    if seed_positions is None:
        positions = jax.random.randint(jax.random.fold_in(key, 3), (B,),
                                       0, Smax)
    else:
        positions = jnp.asarray(seed_positions, jnp.int32)
    return q, k, v, positions


@pytest.mark.parametrize("H,K,D", [(4, 4, 64), (4, 2, 64), (8, 2, 128),
                                   (6, 1, 64), (4, 4, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_gqa_sweep(H, K, D, dtype):
    """Continuous-batching shapes: per-row positions scattered over the cache,
    every GQA group-count flavor (MHA, grouped, MQA)."""
    key = jax.random.PRNGKey(10)
    q, k, v, pos = _decode_case(key, B=4, Smax=128, H=H, K=K, D=D, dtype=dtype)
    o_ref = ref.sdpa_decode(q, k, v, pos)
    o = da.decode_attention(q, k, v, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window,softcap", [(64, None), (None, 30.0),
                                            (64, 30.0), (16, 10.0)])
def test_decode_attention_masking_variants(window, softcap):
    key = jax.random.PRNGKey(11)
    q, k, v, pos = _decode_case(key, B=4, Smax=256, H=4, K=2, D=64,
                                seed_positions=[0, 17, 100, 255])
    o_ref = ref.sdpa_decode(q, k, v, pos, window=window, softcap=softcap)
    o = da.decode_attention(q, k, v, pos, window=window, softcap=softcap,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-5)


def test_decode_attention_live_mask_zeroes_dead_rows():
    """Dead slots produce exact zeros; live rows are untouched by the mask."""
    key = jax.random.PRNGKey(12)
    q, k, v, pos = _decode_case(key, B=6, Smax=128, H=4, K=2, D=64)
    live = jnp.asarray([True, False, True, False, False, True])
    o_ref = ref.sdpa_decode(q, k, v, pos, live=live)
    o = da.decode_attention(q, k, v, pos, live=live, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-5)
    assert np.all(np.asarray(o)[~np.asarray(live)] == 0.0)
    o_all = da.decode_attention(q, k, v, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(o)[np.asarray(live)],
                                  np.asarray(o_all)[np.asarray(live)])


def test_decode_attention_position_zero_and_full_cache():
    """Boundary positions: a row attending to a single KV entry (pos 0) and a
    row at the last cache position both match the oracle."""
    key = jax.random.PRNGKey(13)
    q, k, v, pos = _decode_case(key, B=2, Smax=64, H=4, K=2, D=64,
                                seed_positions=[0, 63])
    o_ref = ref.sdpa_decode(q, k, v, pos)
    o = da.decode_attention(q, k, v, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-5)


def test_ops_sdpa_decode_backend_switch():
    key = jax.random.PRNGKey(14)
    q, k, v, pos = _decode_case(key, B=3, Smax=128, H=4, K=2, D=64)
    a = ops.sdpa_decode(q, k, v, pos)
    ops.set_backend("pallas_interpret")
    try:
        b = ops.sdpa_decode(q, k, v, pos)
    finally:
        ops.set_backend(None)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# chunked prefill over the paged KV pool (Sq > 1)
# ---------------------------------------------------------------------------

SMOLLM_HEADS = dict(H=9, K=3, D=64)
NEMO_HEADS = dict(H=32, K=8, D=128)


def _paged_chunk_case(seed, Sq, H, K, D, positions, dtype=jnp.float32,
                      bs=16, n_blocks=64, max_blocks=24):
    """One chunk round over a shared pool: each row owns distinct, shuffled
    pool blocks for its positions 0 .. pos + Sq - 1; the rest of its table
    points at block 0 (foreign contents), as the pager leaves unallocated
    entries."""
    rng = np.random.default_rng(seed)
    B = len(positions)
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), dtype)
    k_pool = jnp.asarray(rng.normal(size=(n_blocks, bs, K, D)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(n_blocks, bs, K, D)), dtype)
    table = np.zeros((B, max_blocks), np.int32)
    free = iter(rng.permutation(np.arange(1, n_blocks)))
    for b, p in enumerate(positions):
        for j in range(min(max_blocks, -(-(p + Sq) // bs))):
            table[b, j] = next(free)
    return (q, k_pool, v_pool, jnp.asarray(positions, jnp.int32),
            jnp.asarray(table))


@pytest.fixture
def prefill_spy(monkeypatch):
    """Calls of each paged kernel made through ``ops`` in interpret mode
    (a refused shape would fall back to the oracle silently there)."""
    calls = {"prefill": 0, "decode": 0}
    for name, fn in (("prefill", da.prefill_attention_paged),
                     ("decode", da.decode_attention_paged)):
        def spy(*a, _fn=fn, _name=name, **kw):
            calls[_name] += 1
            return _fn(*a, **kw)
        monkeypatch.setattr(da, f"{name}_attention_paged", spy)
    ops.set_backend("pallas_interpret")
    yield calls
    ops.set_backend(None)


@pytest.mark.parametrize("heads", [SMOLLM_HEADS, NEMO_HEADS],
                         ids=["smollm", "nemo"])
@pytest.mark.parametrize("Sq", [16, 5], ids=["chunk", "tail"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefill_attention_paged_matches_oracle(prefill_spy, heads, Sq,
                                                dtype):
    """Rows at position 0, mid-page and across many pages (several KV tiles)
    of a shuffled, non-contiguous block table, against the gather oracle."""
    q, kp, vp, pos, table = _paged_chunk_case(
        16, Sq, heads["H"], heads["K"], heads["D"], [0, 21, 300, 7],
        dtype=dtype)
    assert da.supported_prefill_paged(q, kp, vp, table)
    o = ops.sdpa_decode_paged(q, kp, vp, pos, table)
    assert prefill_spy == {"prefill": 1, "decode": 0}
    o_ref = ref.sdpa_decode_paged(q, kp, vp, pos, table)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window,softcap", [(None, 30.0), (40, None)])
def test_prefill_attention_paged_dead_rows_softcap_window(prefill_spy,
                                                          window, softcap):
    """Dead rows come back as exact zeros; softcap (Gemma-2's global
    layers) and a local window mask as the oracle does."""
    q, kp, vp, pos, table = _paged_chunk_case(
        17, 16, NEMO_HEADS["H"], NEMO_HEADS["K"], NEMO_HEADS["D"],
        [130, 0, 57, 250])
    live = jnp.asarray([True, False, True, False])
    o = ops.sdpa_decode_paged(q, kp, vp, pos, table, live=live,
                              window=window, softcap=softcap)
    assert prefill_spy["prefill"] == 1
    o_ref = ref.sdpa_decode_paged(q, kp, vp, pos, table, live=live,
                                  window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               **_tol(jnp.float32))
    assert np.all(np.asarray(o)[~np.asarray(live)] == 0.0)


def test_paged_kernel_chosen_by_query_length(prefill_spy):
    """Sq == 1 still runs the single-query decode kernel; Sq > 1 runs the
    chunked-prefill kernel."""
    q, kp, vp, pos, table = _paged_chunk_case(18, 1, 4, 2, 64, [3, 40])
    ops.sdpa_decode_paged(q, kp, vp, pos, table)
    assert prefill_spy == {"prefill": 0, "decode": 1}
    q2 = jnp.concatenate([q, q], axis=1)
    ops.sdpa_decode_paged(q2, kp, vp, pos, table)
    assert prefill_spy == {"prefill": 1, "decode": 1}


def test_paged_kernels_gate_the_cells_shapes():
    """Both serving cells' chunk rounds and decode ticks are accepted by
    their kernel's gate, and by no other."""
    S, bf, i32 = jax.ShapeDtypeStruct, jnp.bfloat16, jnp.int32
    for B, C, H, K, D, n_blocks, max_blocks in (
            (16, 256, 32, 8, 128, 2048, 289),      # nemo10.serve.tenants
            (64, 128, 9, 3, 64, 5184, 81)):        # smollm.serve.chat
        pool = S((n_blocks, 16, K, D), bf)
        table = S((B, max_blocks), i32)
        chunk, tick = S((B, C, H, D), bf), S((B, 1, H, D), bf)
        assert da.supported_prefill_paged(chunk, pool, pool, table)
        assert not da.supported_paged(chunk, pool, pool, table)
        assert da.supported_paged(tick, pool, pool, table)
        assert not da.supported_prefill_paged(tick, pool, pool, table)


def test_platform_routing_counts_no_paged_fallback(monkeypatch):
    """On a TPU process a paged chunk and a paged decode tick both have a
    kernel: no fallback is counted (the CPU lowering here is the oracle)."""
    q, kp, vp, pos, table = _paged_chunk_case(19, 16, 4, 2, 64, [0, 35])
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    ops.reset_fallbacks()
    try:
        chunk = jax.jit(ops.sdpa_decode_paged)(q, kp, vp, pos, table)
        tick = jax.jit(ops.sdpa_decode_paged)(q[:, :1], kp, vp, pos, table)
        assert ops.fallbacks() == {}
    finally:
        ops.reset_fallbacks()
    np.testing.assert_allclose(
        np.asarray(chunk), np.asarray(ref.sdpa_decode_paged(q, kp, vp, pos,
                                                            table)),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(tick), np.asarray(ref.sdpa_decode_paged(q[:, :1], kp, vp,
                                                           pos, table)),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("T,U,din,dout,r", [(128, 2, 64, 64, 4),
                                            (256, 8, 128, 96, 8),
                                            (64, 3, 192, 128, 16)])
def test_multi_lora_sweep(T, U, din, dout, r):
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (T, din))
    A = jax.random.normal(jax.random.fold_in(key, 1), (U, din, r))
    B = jax.random.normal(jax.random.fold_in(key, 2), (U, r, dout))
    idx = jax.random.randint(jax.random.fold_in(key, 3), (T,), 0, U)
    y1 = ref.multi_lora(x, A, B, idx, scale=0.5)
    y2 = ml.multi_lora(x, A, B, idx, scale=0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# grouped decode dispatch + int8-stored banks
# ---------------------------------------------------------------------------

def _lora_bank(key, U, din, r, dout):
    A = jax.random.normal(jax.random.fold_in(key, 1), (U, din, r))
    B = jax.random.normal(jax.random.fold_in(key, 2), (U, r, dout))
    return A, B


def test_compact_resident_remaps_and_pads():
    idx = jnp.asarray([7, 3, 7, -1, 42, 3], jnp.int32)
    resident, remapped = ml.compact_resident(idx, n_users=100)
    res = np.asarray(resident)
    assert list(res[:3]) == [3, 7, 42]
    assert np.all(res[3:] == 100)              # padded with the sentinel
    np.testing.assert_array_equal(np.asarray(remapped), [1, 0, 1, -1, 2, 0])


@pytest.mark.parametrize("dist", ["skewed", "uniform", "single"])
def test_multi_lora_grouped_big_bank(dist):
    """Bank far larger than the decode batch (the BGMV regime): compaction to
    the resident set must be exact across adapter distributions, including
    idx == -1 padding rows."""
    key = jax.random.PRNGKey(15)
    T, U, din, r, dout = 64, 300, 64, 8, 96
    x = jax.random.normal(key, (T, din))
    A, B = _lora_bank(key, U, din, r, dout)
    rng = np.random.default_rng(0)
    if dist == "skewed":      # most rows on 3 adapters + padding rows
        idx = rng.choice([5, 191, 250], size=T).astype(np.int32)
        idx[::9] = -1
    elif dist == "uniform":
        idx = rng.integers(0, U, size=T).astype(np.int32)
    else:                     # every row on one adapter
        idx = np.full(T, 123, np.int32)
    idx = jnp.asarray(idx)
    y1 = ref.multi_lora(x, A, B, idx, scale=0.5)
    y2 = ml.multi_lora_grouped(x, A, B, idx, scale=0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


def test_multi_lora_grouped_single_adapter_fast_path():
    """U == 1 skips compaction entirely; idx != 0 rows still mask to zero."""
    key = jax.random.PRNGKey(16)
    T, din, r, dout = 64, 64, 4, 64
    x = jax.random.normal(key, (T, din))
    A, B = _lora_bank(key, 1, din, r, dout)
    idx = jnp.asarray(([0] * 60 + [-1] * 4), jnp.int32)
    y1 = ref.multi_lora(x, A, B, idx)
    y2 = ml.multi_lora_grouped(x, A, B, idx, scale=1.0, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


def test_ops_multi_lora_routes_grouped_when_bank_exceeds_batch():
    """ops.multi_lora must produce oracle results through the grouped path
    (U > T) under the interpret backend, including unsupported-shape fallback."""
    key = jax.random.PRNGKey(17)
    T, U, din, r, dout = 32, 100, 64, 8, 64
    x = jax.random.normal(key, (T, din))
    A, B = _lora_bank(key, U, din, r, dout)
    idx = jnp.asarray(np.random.default_rng(1).integers(-1, U, T), jnp.int32)
    want = ref.multi_lora(x, A, B, idx)
    ops.set_backend("pallas_interpret")
    try:
        got = ops.multi_lora(x, A, B, idx)
    finally:
        ops.set_backend(None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_multi_lora_idx_minus_one_rows_are_exact_zero():
    key = jax.random.PRNGKey(18)
    x = jax.random.normal(key, (64, 64))
    A, B = _lora_bank(key, 4, 64, 4, 64)
    idx = jnp.asarray([-1] * 64, jnp.int32)
    for y in (ref.multi_lora(x, A, B, idx),
              ml.multi_lora(x, A, B, idx, interpret=True),
              ml.multi_lora_grouped(x, A, B, idx, scale=1.0, interpret=True)):
        assert np.all(np.asarray(y) == 0.0)


def test_quant_rows_roundtrip_error_bound():
    """Per-row symmetric int8: reconstruction error bounded by scale/2 per
    element (half a quantisation step)."""
    key = jax.random.PRNGKey(19)
    w = jax.random.normal(key, (4, 32, 8)) * 3.0
    q, s = ml.quant_rows(w)
    assert q.dtype == jnp.int8 and s.shape == (4, 32, 1)
    recon = q.astype(jnp.float32) * s
    assert float(jnp.max(jnp.abs(recon - w) / s)) <= 0.5 + 1e-6


@pytest.mark.parametrize("T,U,din,dout,r", [(128, 4, 64, 64, 4),
                                            (64, 8, 128, 96, 8)])
def test_multi_lora_q8_matches_oracle(T, U, din, dout, r):
    key = jax.random.PRNGKey(20)
    x = jax.random.normal(key, (T, din))
    A, B = _lora_bank(key, U, din, r, dout)
    A_q, A_s = ml.quant_rows(A)
    B_q, B_s = ml.quant_rows(B)
    idx = np.random.default_rng(2).integers(0, U, T).astype(np.int32)
    idx[::13] = -1
    idx = jnp.asarray(idx)
    y1 = ref.multi_lora_q8(x, A_q, A_s, B_q, B_s, idx, scale=0.5)
    y2 = ml.multi_lora_q8(x, A_q, A_s, B_q, B_s, idx, scale=0.5,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    # quantisation itself stays within ~1% of the f32 bank apply
    truth = ref.multi_lora(x, A, B, idx, scale=0.5)
    denom = float(jnp.abs(truth).max()) + 1e-9
    assert float(jnp.abs(np.asarray(y1) - np.asarray(truth)).max()) / denom < 0.02


@pytest.mark.parametrize("S,chunk", [(256, 64), (96, 32), (512, 128),
                                     (200, 64), (130, 128)])
def test_ssd_chunked_matches_quadratic(S, chunk):
    key = jax.random.PRNGKey(5)
    b, H, P, N = 2, 4, 16, 8
    x = jax.random.normal(key, (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.1)
    B = jax.random.normal(jax.random.fold_in(key, 3), (b, S, N))
    C = jax.random.normal(jax.random.fold_in(key, 4), (b, S, N))
    D = jnp.ones((H,))
    y1, s1 = ref.ssd(x, dt, a, B, C, D)
    y2, s2 = ssd_scan.ssd_chunked(x, dt, a, B, C, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S,chunk", [(200, 64), (37, 32), (300, 128)])
def test_ssd_chunked_tail_state_matches_decode(S, chunk):
    """Non-divisible lengths: the state returned by the chunked scan must be
    exactly the state after position S (the tail chunk is sliced at its true
    length, never padded), as produced by the step-by-step decode recurrence."""
    key = jax.random.PRNGKey(9)
    b, H, P, N = 2, 3, 8, 4
    x = jax.random.normal(key, (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.1)
    B = jax.random.normal(jax.random.fold_in(key, 3), (b, S, N))
    C = jax.random.normal(jax.random.fold_in(key, 4), (b, S, N))
    D = jnp.ones((H,))
    y_chunked, s_chunked = ssd_scan.ssd_chunked(x, dt, a, B, C, D, chunk=chunk)
    state = jnp.zeros((b, H, P, N))
    ys = []
    for t in range(S):
        y, state = ref.ssd_decode_step(x[:, t], dt[:, t], a, B[:, t], C[:, t],
                                       D, state)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(s_chunked), np.asarray(state),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_chunked),
                               np.asarray(jnp.stack(ys, axis=1)),
                               rtol=1e-4, atol=1e-4)


def test_ssd_decode_matches_sequence():
    """Step-by-step recurrence == full-sequence SSD."""
    key = jax.random.PRNGKey(6)
    b, S, H, P, N = 1, 8, 2, 4, 8
    x = jax.random.normal(key, (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, S, H)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.1)
    B = jax.random.normal(jax.random.fold_in(key, 3), (b, S, N))
    C = jax.random.normal(jax.random.fold_in(key, 4), (b, S, N))
    D = jnp.zeros((H,))
    y_full, state_full = ref.ssd(x, dt, a, B, C, D)
    state = jnp.zeros((b, H, P, N))
    ys = []
    for t in range(S):
        y, state = ref.ssd_decode_step(x[:, t], dt[:, t], a, B[:, t], C[:, t],
                                       D, state)
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_seq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state_full), np.asarray(state),
                               rtol=1e-4, atol=1e-4)


def test_blocked_sdpa_equals_dense():
    from repro import flags
    key = jax.random.PRNGKey(7)
    S, H, K, D = 2048, 2, 2, 64
    q = jax.random.normal(key, (1, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, S, K, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, S, K, D))
    pos = jnp.arange(S)[None]
    blocked = ref.sdpa(q, k, v, q_positions=pos, kv_positions=pos)
    with flags.override(dense_sdpa=True):
        dense = ref.sdpa(q, k, v, q_positions=pos, kv_positions=pos)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


def test_ops_backend_switch():
    key = jax.random.PRNGKey(8)
    q = jax.random.normal(key, (1, 128, 4, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 128, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 128, 2, 64))
    pos = jnp.arange(128)[None]
    a = ops.sdpa(q, k, v, q_positions=pos, kv_positions=pos)
    ops.set_backend("pallas_interpret")
    try:
        b = ops.sdpa(q, k, v, q_positions=pos, kv_positions=pos)
    finally:
        ops.set_backend(None)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)


def test_platform_routing_counts_fallbacks(monkeypatch):
    """With no override, a process whose default device is a TPU routes a
    supported shape through ``platform_dependent`` (whose CPU branch is the
    oracle, so a CPU lowering here must equal ref) and counts every refused
    shape once per trace, by op and shape."""
    key = jax.random.PRNGKey(15)
    q, k, v, pos = _decode_case(key, B=3, Smax=128, H=4, K=2, D=64)
    want = ref.sdpa_decode(q, k, v, pos)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    ops.reset_fallbacks()
    try:
        got = jax.jit(ops.sdpa_decode)(q, k, v, pos)
        assert ops.fallbacks() == {}
        # a 2-row chunk (Sq > 1) has no decode kernel: counted, then oracle
        q2 = jnp.concatenate([q, q], axis=1)
        chunk = jax.jit(ops.sdpa_decode)(q2, k, v, pos)
        fb = ops.fallbacks()
    finally:
        ops.reset_fallbacks()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(chunk),
                               np.asarray(ref.sdpa_decode(q2, k, v, pos)),
                               rtol=1e-6, atol=1e-6)
    assert list(fb.values()) == [1]
    (op, shapes), = fb
    assert op == "decode_attention"
    assert shapes[:2] == (("float32", (3, 2, 4, 64)),
                          ("float32", (3, 128, 2, 64)))
