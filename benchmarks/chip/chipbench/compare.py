"""The numbers that decide ``correct``, and how they are reported.

A served token is judged by the reference's own logits: its *gap* is how far
its logit lies below the reference's best at that position (0 where the
program chose the reference's argmax).  Logits and GEMM outputs are also
compared whole, as a relative Frobenius error.  Each number has its limit
in ``limits/<cell>.json``; a run is correct when no number exceeds it.
"""

from __future__ import annotations

import json
import pathlib
import sys
from typing import Dict

import jax.numpy as jnp

LIMITS_DIR = pathlib.Path(__file__).resolve().parents[1] / "limits"


def limits(cell: str) -> Dict[str, float]:
    return json.loads((LIMITS_DIR / f"{cell}.json").read_text())["limits"]


def token_gaps(ref_logits, tokens):
    """ref max minus ref logit of ``tokens``; ref (..., V), tokens (...)."""
    ref = jnp.asarray(ref_logits, jnp.float32)
    picked = jnp.take_along_axis(ref, jnp.asarray(tokens)[..., None], -1)[..., 0]
    return jnp.max(ref, -1) - picked


def rel_err(got, want) -> float:
    """||got - want||_F / ||want||_F, in float32 on the device."""
    g = jnp.asarray(got, jnp.float32)
    w = jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))


def judge(readings: Dict[str, float], cell: str) -> Dict[str, dict]:
    """{name: {"value", "limit"}} for every number the cell compares."""
    lim = limits(cell)
    missing = set(lim) - set(readings)
    if missing:
        raise KeyError(f"{cell}: no reading for {sorted(missing)}")
    return {k: {"value": float(readings[k]), "limit": float(lim[k])}
            for k in lim}


def is_correct(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def report(checks: Dict[str, dict]) -> None:
    """The compared numbers beside their limits, as the last lines on
    standard error."""
    for k, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
