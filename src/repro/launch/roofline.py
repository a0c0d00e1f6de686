"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), priced at one chip's published
peaks (``PEAKS``, keyed by ``jax.Device.device_kind``):
  compute    = HLO_FLOPs_per_device / peak_FLOPs          [s]
  memory     = HLO_bytes_per_device / HBM_bw              [s]
  collective = collective_bytes_per_device / link_bw      [s]

cost_analysis() reports post-SPMD per-device numbers, so no further division
by chip count is needed. collective bytes are parsed from the compiled HLO:
sum of operand sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops (also per-device shapes).
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    link_bw: float    # bytes/s per ICI link
    source: str


# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.
PEAKS = {
    # 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interconnect over
    # four ICI links (50 GB/s each)
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}
# the chip the dry-run cells are lowered for (launch/dryrun.py)
TARGET_KIND = "TPU v5 lite"


def device_peaks(kind: str) -> DevicePeaks:
    """Peaks of one chip of ``kind``; a kind missing from ``PEAKS`` is an
    error, never a default."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {kind!r}; add them to "
            f"repro.launch.roofline.PEAKS with their source"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_OP_RE = re.compile(
    r"=\s+(.*?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes per collective kind (output-shape sized, HLO-text
    parse; shapes after SPMD partitioning are already per-device)."""
    out: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        out[kind] = out.get(kind, 0.0) + _shape_bytes(shape_str)
    return out


def loop_factor(arch_id: str, shape_name: str) -> float:
    """XLA's cost analysis counts while-loop bodies ONCE; scale by the
    dominant loop's static trip count (layer scan x grad-accum scan for LM,
    edge-chunk scan for huge-graph equivariant cells)."""
    from repro.configs.registry import get_arch
    from repro.configs.shapes import GNN_SHAPES, LM_SHAPES

    arch = get_arch(arch_id)
    if arch.family == "lm":
        cfg = arch.make_config()
        layers = max(cfg.n_scan_layers, 1)
        if LM_SHAPES[shape_name].kind == "train":
            return layers * max(cfg.grad_accum, 1)
        return layers
    if arch.family == "gnn" and arch.arch_id in ("nequip", "mace"):
        shape = GNN_SHAPES[shape_name]
        if shape.kind == "full_graph" and shape.n_edges > 4_000_000:
            chunk = 524_288
            return -(-shape.n_edges // chunk)
    return 1.0


def roofline_terms(cost: dict, hlo_text: str, factor: float = 1.0,
                   device_kind: str = TARGET_KIND) -> dict:
    peaks = device_peaks(device_kind)
    flops = float(cost.get("flops", 0.0) or 0.0) * factor
    bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0) * factor
    coll = {k: v * factor for k, v in collective_bytes(hlo_text).items()}
    coll_total = sum(coll.values())
    compute_s = flops / peaks.flops
    memory_s = bytes_accessed / peaks.hbm_bw
    collective_s = coll_total / peaks.link_bw
    terms = {
        "loop_factor": factor,
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll_total,
        "collective_breakdown": coll,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dominant = max(
        ("compute", compute_s), ("memory", memory_s),
        ("collective", collective_s), key=lambda kv: kv[1],
    )[0]
    terms["dominant"] = dominant
    bound = max(compute_s, memory_s, collective_s)
    terms["roofline_fraction"] = compute_s / bound if bound > 0 else 0.0
    return terms


def model_flops(arch_id: str, shape_name: str) -> float | None:
    """MODEL_FLOPS = 6 N D (dense) or 6 N_active D (MoE), D = tokens.

    Returns the *global* useful flops for LM train cells (3x fwd for the
    backward pass included via the factor 6); serve cells use 2 N D.
    None for non-LM families (no standard closed form)."""
    from repro.configs.registry import get_arch
    from repro.configs.shapes import LM_SHAPES

    arch = get_arch(arch_id)
    if arch.family != "lm":
        return None
    cfg = arch.make_config()
    shape = LM_SHAPES[shape_name]
    d, L, v = cfg.d_model, cfg.n_layers, cfg.padded_vocab

    attn = 2 * d * (cfg.n_heads * cfg.d_head) * 2  # qo
    if cfg.attn_type == "gqa":
        attn += 2 * d * (cfg.n_kv_heads * cfg.d_head) * 2  # kv
    else:
        dqk = cfg.d_nope + cfg.d_rope
        attn = 2 * d * (cfg.q_lora or d) + 2 * (cfg.q_lora or d) * cfg.n_heads * dqk
        attn += 2 * d * (cfg.kv_lora + cfg.d_rope)
        attn += 2 * cfg.kv_lora * cfg.n_heads * (cfg.d_nope + cfg.d_v)
        attn += 2 * cfg.n_heads * cfg.d_v * d
    if cfg.moe:
        ffn_active = 2 * d * cfg.d_ff_expert * 3 * (cfg.top_k + cfg.n_shared)
        dense_ffn = 2 * d * cfg.d_ff * 3
        per_tok = (
            cfg.first_k_dense * (attn + dense_ffn)
            + cfg.n_scan_layers * (attn + ffn_active)
        )
    else:
        per_tok = L * (attn + 2 * d * cfg.d_ff * 3)
    per_tok += 2 * d * v  # lm head
    n_active = per_tok / 2  # params touched per token ~ flops/2

    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence + attention over the cache
    tokens = shape.global_batch
    cache_read = (
        2 * shape.global_batch * shape.seq_len
        * cfg.n_heads * cfg.d_head * 2 * L
    )
    return 2.0 * n_active * tokens + cache_read
