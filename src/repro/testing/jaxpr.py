"""Jaxpr walking for the structural (census) tests.

The tests gate properties of traced programs — "the train step launches no
`dot_general` outside the SFC kernels", "the fused optimizer leaves no
elementwise pass at a routed weight's shape" — by visiting every equation,
including those of the sub-jaxprs held in equation params (scan, cond,
custom_vjp, pjit bodies).
"""

from __future__ import annotations

from typing import Any, Dict, Iterator

from jax.extend import core as jex_core

__all__ = ["walk_eqns", "op_census"]


def walk_eqns(jaxpr: jex_core.Jaxpr) -> Iterator[jex_core.JaxprEqn]:
    """Yield every equation of ``jaxpr`` and of its nested sub-jaxprs,
    depth first.  A ``pallas_call`` is yielded but its kernel body is not
    entered: on the TPU that body is Mosaic code, so the dots inside it are
    the SFC kernel itself, not a fallback."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            yield from _param_eqns(val)


def op_census(jaxpr: jex_core.Jaxpr) -> Dict[str, Any]:
    """Count ``pallas_call`` launches and the ``dot_general`` equations
    outside them (with their operand shapes)."""
    counts: Dict[str, Any] = {"dot": 0, "pallas": 0, "dot_shapes": []}
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            counts["pallas"] += 1
        elif eqn.primitive.name == "dot_general":
            counts["dot"] += 1
            counts["dot_shapes"].append(
                tuple(tuple(v.aval.shape) for v in eqn.invars)
            )
    return counts


def _param_eqns(val: Any) -> Iterator[jex_core.JaxprEqn]:
    if isinstance(val, jex_core.ClosedJaxpr):
        yield from walk_eqns(val.jaxpr)
    elif isinstance(val, jex_core.Jaxpr):
        yield from walk_eqns(val)
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _param_eqns(v)
