"""Roofline analysis from the compiled dry-run artifact (no real hardware).

Terms per (arch, mesh):
    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

Hardware constants come from ``PEAKS``, keyed by the ``device_kind`` JAX
reports; a kind missing from the table is an error, never a default.

``collective_bytes`` is parsed from the HLO text: the summed operand sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute ops.
"""
from __future__ import annotations

import re
from typing import Any

# Published peaks of one chip, keyed by ``jax.devices()[i].device_kind``.
# "TPU v5 lite" is the TPU v5e. Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect per chip over 4 links (50 GB/s per link).
PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    """The chip's peak table entry; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r}"
                         f" (known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4,
    "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2,
    "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """bytes of one 'dtype[d0,d1,...]' string."""
    m = _SHAPE_RE.match(shape_str.strip())
    if not m:
        return 0
    dtype, dims = m.groups()
    nbytes = _DTYPE_BYTES.get(dtype)
    if nbytes is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nbytes


_COLL_RE = re.compile(
    r"=\s*(?P<shapes>\(?[\w\[\],{}/:\. ]*?\)?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<suffix>-start)?\(")


def collective_bytes(hlo_text: str, per_op: dict | None = None) -> float:
    """Sum of result-shape bytes of every collective op in the (per-device)
    partitioned HLO. Counted per device: an all-gather's per-device result is
    the full gathered size, which matches the bytes a ring all-gather moves
    through each chip's links; all-reduce moves ~2x its size (reduce-scatter +
    all-gather), folded in with a factor of 2.
    """
    total = 0.0
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        nbytes = 0
        for dtype, dims in _SHAPE_RE.findall(m.group("shapes")):
            b = _DTYPE_BYTES.get(dtype, 0)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * b
        factor = 2.0 if op == "all-reduce" else 1.0
        contrib = factor * nbytes
        total += contrib
        if per_op is not None:
            per_op[op] = per_op.get(op, 0.0) + contrib
    return float(total)


_CONVERT_RE = re.compile(
    r"= f32\[([\d,]+)\][^)]*? convert\((%[\w.\-]+)\)")


def cpu_bf16_emulation_bytes(hlo_text: str, min_bytes: int = 32 << 20) -> int:
    """XLA *CPU* lowers bf16 dots by converting operands to f32; loop-invariant
    code motion hoists those converts, so whole bf16 weight stacks / KV caches
    get persistent f32 shadow copies that would NOT exist on TPU (native bf16
    MXU). This counts the big (>=min_bytes) f32 convert results whose operand
    is a parameter/loop-carried value — the dry-run subtracts them to report a
    TPU-representative peak alongside the raw CPU number."""
    total = 0
    for m in _CONVERT_RE.finditer(hlo_text):
        dims, operand = m.groups()
        if "param" not in operand:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        size = n * 4
        if size >= min_bytes:
            total += size
    return total


def memory_record(mem) -> dict:
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    try:
        out["peak_bytes_per_device"] = int(
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
    except Exception:
        pass
    return out


def roofline_terms(rec: dict[str, Any], device_kind: str) -> dict[str, float]:
    """rec carries flops / bytes_accessed / collective_bytes of the PARTITIONED
    per-device module (verified by calibration: cost_analysis of the compiled
    SPMD executable reports one device's program; a 1024^3 matmul reports
    exactly 2*M*N*K). So the terms below are already per-chip — no division by
    the chip count."""
    pk = peaks(device_kind)
    t_compute = rec["flops"] / pk["bf16_flops"]
    t_memory = rec["bytes_accessed"] / pk["hbm_bw"]
    t_coll = rec["collective_bytes"] / pk["link_bw"]
    terms = {"t_compute": t_compute, "t_memory": t_memory,
             "t_collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    total = max(terms.values())
    return {**terms,
            "bottleneck": bottleneck.replace("t_", ""),
            "roofline_s": total,
            "roofline_fraction": (t_compute / total) if total > 0 else 0.0}


# ---------------------------------------------------------------------------
# MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE); decode uses D=new tokens
# ---------------------------------------------------------------------------

def param_count(cfg) -> tuple[int, int]:
    """(total, active) parameter counts, analytic."""
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab_size
    emb = V * d * (cfg.n_codebooks or 1)
    head = 0 if cfg.tie_embeddings and not cfg.n_codebooks else (
        d * V * (cfg.n_codebooks or 1))
    per_attn = d * (cfg.n_heads * cfg.d_head) * 2 + \
        d * (cfg.n_kv_heads * cfg.d_head) * 2 if cfg.n_heads else 0
    per_mlp = 3 * d * cfg.d_ff if cfg.d_ff else 0
    per_moe_total = per_moe_active = 0
    if cfg.n_experts:
        per_e = 3 * d * cfg.d_expert
        per_moe_total = cfg.n_experts * per_e + d * cfg.n_experts
        per_moe_active = cfg.moe_top_k * per_e + d * cfg.n_experts
    per_ssm = 0
    if cfg.ssm_state:
        di = cfg.ssm_expand * d
        nh = di // cfg.ssm_headdim
        d_in_proj = 2 * di + 2 * cfg.ssm_state + nh
        per_ssm = d * d_in_proj + di * d

    if cfg.family == "ssm":
        body_t = body_a = L * per_ssm
    elif cfg.family == "hybrid":
        n_seg = len(range(0, L, cfg.shared_attn_every))
        shared = per_attn + per_mlp
        body_t = L * per_ssm + shared
        body_a = L * per_ssm + n_seg * shared   # shared block runs n_seg times
    elif cfg.n_experts:
        body_t = L * (per_attn + per_moe_total)
        body_a = L * (per_attn + per_moe_active)
    else:
        body_t = body_a = L * (per_attn + per_mlp)
    return emb + head + body_t, emb + head + body_a


def model_flops(cfg, shape_spec) -> float:
    """Useful model FLOPs for the cell: 6*N_active*tokens for train (fwd+bwd),
    2*N_active*tokens for prefill/decode (fwd only)."""
    _, active = param_count(cfg)
    if shape_spec.kind == "train":
        tokens = shape_spec.batch * shape_spec.seq
        return 6.0 * active * tokens
    if shape_spec.kind == "prefill":
        tokens = shape_spec.batch * shape_spec.seq
        return 2.0 * active * tokens
    tokens = shape_spec.batch  # one new token per row
    return 2.0 * active * tokens
