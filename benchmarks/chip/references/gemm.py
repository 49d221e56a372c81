"""Plain reference of a GEMM: C = A @ B in float32 at "highest" precision.

Computed on the same devices as the program's output, with XLA's own
partitioning of one `jnp.dot`, and laid out as that output is, so that the
whole sharded result is compared.  ``precision="fp8"`` is the control: A
and B rounded to float8 e4m3 (scaled per row of A and per column of B)
before the float32 product, the step a faster program would be tempted to
take from bf16.  It imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.lru_cache(maxsize=None)
def _program(sharding, precision):
    def product(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if precision == "fp8":
            a, b = _fp8(a, 1), _fp8(b, 0)
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)

    return jax.jit(product, out_shardings=sharding)


def product(a, b, sharding, *, precision="f32"):
    """A @ B in float32, laid out by ``sharding``."""
    return _program(sharding, precision)(a, b)
