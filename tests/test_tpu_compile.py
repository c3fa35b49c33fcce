"""Compile-only checks of the Pallas kernels for a TPU v5e that is described,
not attached: the TPU compiler is installed with JAX, so a refused block
layout or VMEM overrun shows up here without a chip. Shapes are smollm-135m's
serving and training widths (H=9, K=3, Dh=64, d_model=576, rank 8), plus one
attention case at Dh=128, K=8 (mistral-nemo-12b's heads).

Only one process may load the TPU library at a time, so the topology is
described inside a module fixture (never at import), and every test of this
kind lives in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import cola_fit as ck
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import multi_lora as ml

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOLLM = dict(H=9, K=3, Dh=64)
NEMO = dict(H=32, K=8, Dh=128)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes, kernels: tuple[str, ...]):
    """Compile ``fn`` for the described chip; each of ``kernels`` must be
    named in the program (the Pallas call's ``name`` and its
    ``kernel_metadata``, which the profiler's trace carries)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in kernels:
        assert f'"kernel":"{name}"' in text, f"{name} is not named"
    return compiled


@pytest.mark.parametrize("heads", [SMOLLM, NEMO], ids=["smollm", "nemo"])
def test_flash_attention_compiles(one_chip, heads):
    B, S, H, K, Dh = 2, 512, heads["H"], heads["K"], heads["Dh"]
    bf = jnp.bfloat16

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, interpret=False)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    shapes = [((B, S, H, Dh), bf), ((B, S, K, Dh), bf), ((B, S, K, Dh), bf)]
    _compile(lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
             one_chip, *shapes, kernels=("flash_attention_fwd",))
    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *shapes,
             kernels=("flash_attention_fwd", "flash_attention_bwd_dq",
                      "flash_attention_bwd_dkv"))


@pytest.mark.parametrize("heads", [SMOLLM, NEMO], ids=["smollm", "nemo"])
def test_decode_attention_compiles(one_chip, heads):
    B, Smax, H, K, Dh = 16, 1024, heads["H"], heads["K"], heads["Dh"]
    bf = jnp.bfloat16
    _compile(lambda q, k, v, pos, live: da.decode_attention(
        q, k, v, pos, live=live, interpret=False), one_chip,
        ((B, 1, H, Dh), bf), ((B, Smax, K, Dh), bf), ((B, Smax, K, Dh), bf),
        ((B,), jnp.int32), ((B,), jnp.bool_), kernels=("decode_attention",))


def test_decode_attention_paged_compiles(one_chip):
    B, H, K, Dh = 16, SMOLLM["H"], SMOLLM["K"], SMOLLM["Dh"]
    n_blocks, bs, nb = 1024, 16, 64
    bf = jnp.bfloat16
    _compile(lambda q, k, v, pos, tbl, live: da.decode_attention_paged(
        q, k, v, pos, tbl, live=live, interpret=False), one_chip,
        ((B, 1, H, Dh), bf), ((n_blocks, bs, K, Dh), bf),
        ((n_blocks, bs, K, Dh), bf), ((B,), jnp.int32), ((B, nb), jnp.int32),
        ((B,), jnp.bool_), kernels=("decode_attention_paged",))


def _hlo_with_operand_shapes(compiled) -> str:
    """The optimized HLO as the profiler's trace names an op: operands
    printed with their shapes (without the printer's ``/*index=N*/``
    comments, which the trace does not carry)."""
    from jax._src.lib import xla_client as xc
    opts = xc._xla.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_percent = True
    text = compiled._executable.xla_extension_executable().hlo_modules()[
        0].to_string(opts)
    return re.sub(r"/\*index=\d+\*/", "", text)


def _decode_roofline_pattern() -> str:
    """``KERNEL`` of the benchmark's decode-roofline reader, read from its
    file (the benchmark is not a package of the program)."""
    import ast
    path = os.path.join(ROOT, "bench", "metrics",
                        "decode_attention_paged_roofline.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    return next(ast.literal_eval(node.value) for node in tree.body
                if isinstance(node, ast.Assign)
                and [t.id for t in node.targets] == ["KERNEL"])


# both serving cells' chunk rounds: (slots, chunk, H, K, Dh, pool blocks,
# table width), pages of 16 positions
PREFILL_CELLS = {"nemo10": (16, 256, 32, 8, 128, 2048, 289),
                 "smollm": (64, 128, 9, 3, 64, 5184, 81)}


@pytest.mark.parametrize("cell", sorted(PREFILL_CELLS))
def test_prefill_attention_paged_compiles(one_chip, cell):
    """The chunked-prefill kernel at each serving cell's chunk shapes, in
    one program with the paged decode kernel: the decode roofline's operand
    pattern finds the decode kernel's custom call and not the new one."""
    B, C, H, K, Dh, n_blocks, nb = PREFILL_CELLS[cell]
    bf = jnp.bfloat16

    def both(qc, qd, k, v, pos, tbl, live):
        return (da.prefill_attention_paged(qc, k, v, pos, tbl, live=live),
                da.decode_attention_paged(qd, k, v, pos, tbl, live=live))

    compiled = _compile(
        both, one_chip, ((B, C, H, Dh), bf), ((B, 1, H, Dh), bf),
        ((n_blocks, 16, K, Dh), bf), ((n_blocks, 16, K, Dh), bf),
        ((B,), jnp.int32), ((B, nb), jnp.int32), ((B,), jnp.bool_),
        kernels=("prefill_attention_paged", "decode_attention_paged"))
    decode_pat = _decode_roofline_pattern()
    calls = {}     # each custom call, by the instruction name it is given
    for line in _hlo_with_operand_shapes(compiled).splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*%(\w+)\.\d+ = ", line).group(1)
            calls[name] = bool(re.search(decode_pat, line))
    assert calls == {"prefill_attention_paged": False,
                     "decode_attention_paged": True}


# decode batch (16 slots), one prefill chunk row (256 tokens) and a chunk
# round over 16 slots (2048 tokens); q (576 out) and v (192 out) projections
# of smollm-135m
@pytest.mark.parametrize("T", [16, 256, 2048])
@pytest.mark.parametrize("d_out", [576, 192])
def test_multi_lora_compiles(one_chip, T, d_out):
    U, d_in, r = 4, 576, 8
    bf, f32 = jnp.bfloat16, jnp.float32
    x, idx = ((T, d_in), bf), ((T,), jnp.int32)
    _compile(lambda x, A, B, i: ml.multi_lora(x, A, B, i, interpret=False),
             one_chip, x, ((U, d_in, r), f32), ((U, r, d_out), f32), idx,
             kernels=("multi_lora",))
    _compile(lambda x, A, B, i: ml.multi_lora_grouped(x, A, B, i,
                                                      interpret=False),
             one_chip, x, ((64, d_in, r), f32), ((64, r, d_out), f32), idx,
             kernels=("multi_lora",))
    _compile(lambda x, aq, as_, bq, bs, i: ml.multi_lora_q8(
        x, aq, as_, bq, bs, i, interpret=False), one_chip, x,
        ((U, d_in, r), jnp.int8), ((U, d_in, 1), f32),
        ((U, r, d_out), jnp.int8), ((U, r, 1), f32), idx,
        kernels=("multi_lora_q8",))


def test_cola_fit_lowrank_compiles(one_chip):
    T, d_in, d_out, r = 2048, 576, 192, 8
    f32 = jnp.float32
    _compile(lambda x, g, A, B: ck.cola_fit_lowrank(x, g, A, B,
                                                    interpret=False),
             one_chip, ((T, d_in), f32), ((T, d_out), f32), ((d_in, r), f32),
             ((r, d_out), f32), kernels=("cola_fit",))


# ---------------------------------------------------------------------------
# whole steps: a kernel can compile alone and still be refused inside a
# program, where XLA picks its operands' layouts (multi_lora's user ids were)
# ---------------------------------------------------------------------------

@pytest.fixture
def pallas_backend():
    from repro.kernels import ops
    ops.set_backend("pallas")
    yield
    ops.set_backend(None)


def _smollm(n_layers=2):
    from repro.configs import registry
    return registry.get_config("smollm-135m").replace(n_layers=n_layers)


def _placed(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("width", [128, 1], ids=["chunk", "decode"])
def test_serve_step_compiles(one_chip, pallas_backend, width):
    """The engine's paged chunk round and decode tick with 4 users'
    adapters, at smollm-135m's widths (depth cut to 2 layers)."""
    from repro.configs.base import ColaConfig
    from repro.core import gl
    from repro.core import taps as taps_lib
    from repro.models import model as M
    from repro.runtime.serve_loop import stack_user_adapters
    cfg = _smollm()
    slots, max_len, kv_block = 16, 1024, 16
    cc = ColaConfig(mode="lora", family="lowrank", taps="qv", rank=8)
    bank = jax.eval_shape(lambda: stack_user_adapters(
        [gl.init_adapters(cfg, cc, jax.random.PRNGKey(u)) for u in range(4)]))
    spec = taps_lib.make_spec(family="multi_lowrank",
                              taps=gl.select_taps(cfg, "qv"))
    params = jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0)))
    cache = M.cache_specs(cfg, slots, max_len, kv_layout="paged",
                          kv_block=kv_block)

    def step(params, bank, cache, table, tokens, positions, users, live):
        vars_ = {tap: dict(w, idx=jnp.broadcast_to(
            users, (w["A"].shape[0],) + users.shape))
            for tap, w in bank.items()}
        logits, cache = M.decode_step(
            cfg, params, {"tokens": tokens, "positions": positions}, cache,
            spec, {"adapters": vars_}, live=live, block_table=table)
        return logits[:, -1], cache

    i32 = jnp.int32
    small = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((slots, max_len // kv_block), i32), ((slots, width), i32),
        ((slots,), i32), ((slots,), i32), ((slots,), jnp.bool_))]
    compiled = jax.jit(step).lower(
        _placed(params, one_chip), _placed(bank, one_chip),
        _placed(cache, one_chip), *small).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["fused_fit", "faithful_offload"])
def test_train_step_compiles(one_chip, pallas_backend, mode):
    """Mode B's train step and Mode A's server step (flash attention forward
    and backward) at smollm-135m's widths, depth cut to 2 layers."""
    from repro.configs.base import ColaConfig
    from repro.core import gl
    from repro.models import model as M
    import functools
    cfg = _smollm()
    cc = ColaConfig(mode=mode, family="lowrank", taps="qv", rank=8)
    params = jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0)))
    ad = jax.eval_shape(lambda: gl.init_adapters(cfg, cc,
                                                 jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((4, 512), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    fn = gl.train_step_b if mode == "fused_fit" else gl.server_step_a
    compiled = jax.jit(functools.partial(fn, cfg, gl.make_spec(cfg, cc))).lower(
        _placed(params, one_chip), _placed(ad, one_chip), batch).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_train_step_compiles_on_four_chips(one_chip, pallas_backend):
    """distributed/steps.py's ColA train step on a 2x2 mesh: the flash
    kernels run per batch shard under shard_map (a Mosaic kernel is not
    partitioned automatically)."""
    from jax.experimental import topologies
    from repro.configs.base import ColaConfig
    from repro.core import gl
    from repro.distributed import sharding as sh
    from repro.distributed import steps
    from repro.launch.mesh import make_mesh
    cfg = _smollm()
    cc = ColaConfig(mode="fused_fit", family="lowrank", taps="qv", rank=8)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_mesh(2, 2, devices=topo.devices)
    batch = {k: jax.ShapeDtypeStruct((8, 512), jnp.int32)
             for k in ("tokens", "labels")}
    with mesh:
        fn, (ps, ash, _), _ = steps.make_train_step(cfg, cc, mesh)
        compiled = jax.jit(fn, in_shardings=(
            ps, ash, sh.batch_shardings(mesh, batch))).lower(
            steps.shaped_params(cfg), steps.shaped_adapters(cfg, cc),
            batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
