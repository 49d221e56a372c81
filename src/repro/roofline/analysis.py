"""Roofline-term extraction from compiled XLA artifacts (no hardware).

Per (arch x shape x mesh) we derive the three terms of EXPERIMENTS.md
SSRoofline from the dry-run's compiled module:

  compute   = HLO_FLOPs / peak_FLOPs            (per chip)
  memory    = HLO_bytes / HBM_bw                (per chip)
  collective= collective_bytes / (links * link_bw)  (per chip)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (which reports
the per-partition SPMD program — i.e. per-chip numbers).  Collective bytes
are NOT in cost_analysis, so we parse the optimized HLO text and sum operand
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops.

Hardware constants come from the published-peak table keyed by device kind
(`roofline_hw`); collectives are credited 3 usable link-pairs per chip on a
2D torus mesh slice (conservative 3 x link bandwidth aggregate).
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "roofline_hw",
    "collective_bytes",
    "roofline_terms",
    "train_gemm_roofline_terms",
    "model_flops",
]

ICI_LINKS = 3  # usable links per chip credited for collectives


def roofline_hw(device_kind: str) -> Dict[str, float]:
    """Roofline constants of one chip, from the published-peak table
    `core.perf_model.DEVICE_PEAKS` (raises `KeyError` for a kind it does
    not hold)."""
    from repro.core.perf_model import device_peaks

    peaks = device_peaks(device_kind)
    return {
        "peak_flops": peaks.peak_flops,
        "hbm_bw": peaks.mem_bw,
        "ici_link_bw": 1.0 / peaks.ici_beta,
        "ici_links": ICI_LINKS,
    }


_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1,
    "u4": 1,
    "s8": 1,
    "u8": 1,
    "s16": 2,
    "u16": 2,
    "bf16": 2,
    "f16": 2,
    "s32": 4,
    "u32": 4,
    "f32": 4,
    "s64": 8,
    "u64": 8,
    "f64": 8,
    "c64": 8,
    "c128": 16,
    "f8e4m3fn": 1,
    "f8e5m2": 1,
    "f8e4m3b11fnuz": 1,
    "token": 0,
}

# `bf16[8,128,1024]{2,1,0}` or `f32[]` style shapes
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def _shape_bytes(shape_str: str) -> int:
    m = _SHAPE_RE.match(shape_str.strip())
    if not m:
        return 0
    dt, dims = m.groups()
    nbytes = _DTYPE_BYTES.get(dt)
    if nbytes is None:
        return 0
    if not dims:
        return nbytes
    n = 1
    for d in dims.split(","):
        n *= int(d)
    return n * nbytes


def _line_output_bytes(line: str) -> int:
    """Bytes of the op's OUTPUT shape(s): `%x = bf16[..] op(...)` or a tuple
    `%x = (bf16[..], bf16[..]) op(...)`."""
    m = re.search(r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s", line)
    if not m:
        return 0
    return sum(_shape_bytes(f"{dt}[{dims}]") for dt, dims in _SHAPE_RE.findall(m.group(1)))


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output-shape bytes of every collective op in the optimized HLO.

    Output bytes are the right payload proxy: all-gather output = full
    gathered panel, all-reduce output = reduced tensor, reduce-scatter
    output = shard (x world-1 factor differences are absorbed into the
    link-count constant; we report raw sums + per-op breakdown).
    """
    per_op: Dict[str, float] = {op: 0.0 for op in _COLLECTIVE_OPS}
    counts: Dict[str, int] = {op: 0 for op in _COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.search(r"=\s*(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+([a-z0-9-]+)", ls)
        if not m:
            continue
        op = m.group(1)
        for cop in _COLLECTIVE_OPS:
            if op == cop or op.startswith(cop + "-"):
                b = _line_output_bytes(ls)
                per_op[cop] += b
                counts[cop] += 1
                break
    total = sum(per_op.values())
    return {"total_bytes": total, "per_op_bytes": per_op, "per_op_counts": counts}


def roofline_terms(
    cost: Dict[str, float],
    coll: Dict[str, float],
    *,
    n_chips: int,
    hw: Dict[str, float],
) -> Dict[str, float]:
    """The three §Roofline terms, in seconds (per chip / per step)."""
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    cbytes = float(coll["total_bytes"])
    t_compute = flops / hw["peak_flops"]
    t_memory = bytes_accessed / hw["hbm_bw"]
    t_collective = cbytes / (hw["ici_links"] * hw["ici_link_bw"])
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_collective),
        key=lambda kv: kv[1],
    )[0]
    bound = max(t_compute, t_memory, t_collective)
    return {
        "hlo_flops": flops,
        "hlo_bytes": bytes_accessed,
        "collective_bytes": cbytes,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "bound_s": bound,
        # fraction of the roofline-bound step spent on useful compute
        "roofline_fraction": (t_compute / bound) if bound > 0 else 0.0,
    }


def train_gemm_roofline_terms(
    M: int,
    N: int,
    K: int,
    *,
    dtype_bytes: int = 2,
    hw: Dict[str, float],
) -> Dict[str, float]:
    """Per-chip roofline terms for one projection's *train* step: the
    forward GEMM plus both backward GEMMs (dA = dC·Bᵀ, dB = Aᵀ·dC).

    Backward traffic is not 2x forward: each backward GEMM re-reads one
    saved forward operand and the (M, N) cotangent and writes a gradient
    the size of the other operand, so the byte mix shifts with the shape's
    aspect — tall-skinny projections (the LM head, d_ff up-projections) go
    memory-bound in the backward before they do in the forward."""
    flops = {"fwd": 2.0 * M * N * K, "nt": 2.0 * M * N * K, "tn": 2.0 * M * N * K}
    bytes_ = {
        # operands read + output written, once each (compulsory traffic)
        "fwd": (M * K + K * N + M * N) * dtype_bytes,
        "nt": (M * N + K * N + M * K) * dtype_bytes,
        "tn": (M * K + M * N + K * N) * dtype_bytes,
    }
    out: Dict[str, float] = {}
    t_total = 0.0
    for phase in ("fwd", "nt", "tn"):
        t_c = flops[phase] / hw["peak_flops"]
        t_m = bytes_[phase] / hw["hbm_bw"]
        out[f"{phase}_compute_s"] = t_c
        out[f"{phase}_memory_s"] = t_m
        out[f"{phase}_bound_s"] = max(t_c, t_m)
        out[f"{phase}_dominant"] = "compute" if t_c >= t_m else "memory"
        t_total += max(t_c, t_m)
    out["total_s"] = t_total
    out["bwd_to_fwd"] = (
        (out["nt_bound_s"] + out["tn_bound_s"]) / out["fwd_bound_s"]
        if out["fwd_bound_s"] > 0
        else 0.0
    )
    return out


def model_flops(cfg, shape, n_layers_active: Optional[int] = None) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) for training;
    2·N·D for inference steps.  N counted from the config."""
    d, L, ff, V = cfg.d_model, cfg.n_layers, cfg.d_ff, cfg.vocab
    hd = cfg.head_dim_ * cfg.n_heads
    kvd = cfg.head_dim_ * cfg.kv_heads
    attn = d * hd + 2 * d * kvd + hd * d
    if cfg.n_experts:
        mlp_active = cfg.moe_top_k * 3 * d * ff + d * cfg.n_experts
    elif ff:
        mlp_active = (3 if cfg.gated_mlp else 2) * d * ff
    else:
        mlp_active = 0
    if cfg.family == "ssm":  # xLSTM blocks
        d_inner = 2 * d
        attn = 2 * d * d_inner + 3 * d_inner * d_inner + d_inner * d  # mLSTM proj
        mlp_active = 0
    if cfg.family == "hybrid":
        d_inner = cfg.ssm_expand * d
        n_attn = L // cfg.attn_every
        mamba = d * (2 * d_inner + 2 * cfg.ssm_state + d_inner // cfg.ssm_head_dim) + d_inner * d
        attn_blk = attn + 3 * d * ff
        n_active = L * mamba + n_attn * attn_blk
        per_layer_total = n_active
        L_eff = 1
    else:
        per_layer_total = attn + mlp_active
        L_eff = L
    if cfg.is_encoder_decoder:
        L_eff = L + cfg.encoder_layers
        per_layer_total = per_layer_total * 1.5  # cross-attention on decoder side
    n_params_active = L_eff * per_layer_total + 2 * V * d  # + embed/head
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    mult = 6.0 if shape.mode == "train" else 2.0
    return mult * n_params_active * tokens
