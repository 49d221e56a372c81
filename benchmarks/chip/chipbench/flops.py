"""Operations and bytes the benchmark's work needs, from shapes alone.

Kept with the benchmark so that no change to the program can change how its
work is counted.  FLOPs count a multiply and an add as two; bytes are those
of bf16 operands (2 bytes) read once and results written once, the least
traffic the work allows.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict, List, NamedTuple

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")
BF16 = 2


def device_peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


class Gemm(NamedTuple):
    """C (m, n) = A (m, k) @ B (k, n), with ``n_b`` B panels sharing A (a
    gated up-projection reads two weights and writes one product)."""
    m: int
    n: int
    k: int
    n_b: int = 1

    @property
    def flops(self) -> float:
        return 2.0 * self.m * self.n * self.k * self.n_b

    @property
    def bytes(self) -> float:
        return BF16 * (self.m * self.k + self.n_b * self.k * self.n
                       + self.m * self.n)

    def least_s(self, peaks: Dict[str, float]) -> float:
        return max(self.flops / peaks["bf16_flops"],
                   self.bytes / peaks["hbm_bytes_per_s"])


def layer_gemms(m: int, model: dict) -> List[Gemm]:
    """The projection GEMMs of one decoder layer over ``m`` token rows."""
    d, h, kv = model["d_model"], model["n_heads"], model["kv_heads"]
    hd = model.get("head_dim") or d // h
    ff = model["d_ff"]
    return [
        Gemm(m, h * hd, d),    # q
        Gemm(m, kv * hd, d),   # k
        Gemm(m, kv * hd, d),   # v
        Gemm(m, d, h * hd),    # o
        Gemm(m, ff, d, n_b=2),  # gate and up
        Gemm(m, d, ff),        # down
    ]


def step_gemms(rows: int, head_rows: int, model: dict) -> List[Gemm]:
    """Every projection GEMM of one prefill or decode step: each layer over
    ``rows`` token rows, the LM head over ``head_rows``."""
    return (layer_gemms(rows, model) * model["n_layers"]
            + [Gemm(head_rows, model["vocab"], model["d_model"])])


def attention_flops(q_rows: int, keys: float, model: dict) -> float:
    """Scores and weighted values of every layer: ``q_rows`` queries, each
    against ``keys`` keys on average."""
    h = model["n_heads"]
    hd = model.get("head_dim") or model["d_model"] // h
    return 4.0 * q_rows * keys * h * hd * model["n_layers"]


def prefill_work(batch: int, prompt_len: int, model: dict) -> Dict[str, float]:
    """Model FLOPs and GEMM list of one prefill of ``batch`` prompts; the
    head runs on the last position only, as the served result needs."""
    gemms = step_gemms(batch * prompt_len, batch, model)
    causal_keys = (prompt_len + 1) / 2
    flops = (sum(g.flops for g in gemms)
             + attention_flops(batch * prompt_len, causal_keys, model))
    return {"flops": flops, "gemms": gemms}


def decode_work(batch: int, context: int, model: dict) -> Dict[str, float]:
    """One decode step of ``batch`` rows whose new token sits at position
    ``context`` (so it attends to ``context + 1`` keys)."""
    gemms = step_gemms(batch, batch, model)
    flops = (sum(g.flops for g in gemms)
             + attention_flops(batch, context + 1, model))
    return {"flops": flops, "gemms": gemms}


def round_work(batch: int, prompt_len: int, max_new: int, model: dict,
               peaks: Dict[str, float]) -> Dict[str, float]:
    """One closed-loop round: the prefill, then ``max_new - 1`` decode steps.
    Returns its model FLOPs and the least time its GEMMs can take."""
    parts = [prefill_work(batch, prompt_len, model)]
    parts += [decode_work(batch, prompt_len + i, model)
              for i in range(max_new - 1)]
    return {
        "flops": sum(p["flops"] for p in parts),
        "gemm_least_s": sum(g.least_s(peaks) for p in parts
                            for g in p["gemms"]),
    }
