"""Distributed 2.5D Communication-Avoiding matmul on a JAX mesh.

This is the inter-chip instantiation of the paper (DESIGN.md §2.2): the T
cores with private L2 become T chips with private HBM, "words from slow
memory" become bytes over ICI, and the blockwise-SFC worker grid becomes an
explicit mesh factorization chosen by `sfc_grid_factorization` (the curve's
"patch vote").  `K_layers` is realised as a mesh axis (`kl_axis`) holding
replicated C copies that are combined with a `psum`/`psum_scatter` — the
distributed `add_reduce_tpp`.

Three entry points:

  ca_matmul         stationary-C 2.5D: inputs pre-sharded so the GEMM phase
                    is communication-free; one reduction over kl_axis.
  summa_ca_matmul   ring-SUMMA within each layer: A/B fully sharded, panels
                    rotate via `ppermute` with compute/comm overlap
                    (beyond-paper collective schedule, used in §Perf).
  sfc_plan_mesh     turn a flat device count + GEMM shape into the
                    (tm, tn, c) logical grid the blockwise SFC partition
                    implies, plus the analytical-model K_layers choice.

The local per-chip GEMM backend is pluggable: "xla" (jnp.dot — used by the
512-device dry-runs), "sfc_pallas" (the Pallas kernel; TPU or interpret) or
"sfc_reference" (Listing-1 oracle).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.decomposition import sfc_grid_factorization
from repro.core.perf_model import TPU_V5E, HardwareModel, roofline_best_time

__all__ = [
    "CAPlan",
    "sfc_plan_mesh",
    "local_matmul",
    "ca_matmul",
    "summa_ca_matmul",
]


@dataclasses.dataclass(frozen=True)
class CAPlan:
    """Logical (tm, tn, c) grid for a GEMM on T devices + modeled time."""

    tm: int
    tn: int
    k_layers: int
    modeled_time_s: float

    @property
    def n_devices(self) -> int:
        return self.tm * self.tn * self.k_layers


def sfc_plan_mesh(
    n_devices: int,
    M: int,
    N: int,
    K: int,
    *,
    bm: int = 256,
    bn: int = 256,
    hw: HardwareModel = TPU_V5E,
    max_c: int = 8,
) -> CAPlan:
    """Choose (tm, tn, c): c from the analytical roofline sweep (paper §III-C
    method 2), (tm, tn) from the SFC patch vote on the per-layer team (paper
    §II-D "implicit" decomposition).  Works for any device count, including
    non-powers of two (the CARMA limitation the paper calls out)."""
    t_best, (_, _, c) = roofline_best_time(M, N, K, n_devices, hw=hw, max_c=max_c)
    per_layer = n_devices // c
    tm, tn = sfc_grid_factorization(per_layer, max(M // bm, 1), max(N // bn, 1))
    return CAPlan(tm=tm, tn=tn, k_layers=c, modeled_time_s=t_best)


def local_matmul(backend: str = "xla") -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Per-chip GEMM used inside shard_map bodies."""
    if backend == "xla":
        return lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32).astype(
            a.dtype
        )
    if backend == "sfc_pallas":
        from repro.kernels.ops import sfc_matmul

        return lambda a, b: sfc_matmul(a, b)
    if backend == "sfc_reference":
        from repro.core.sfc_gemm import sfc_ca_gemm_reference

        def _ref(a, b):
            def blk(dim):
                for c in (32, 16, 8, 4, 2, 1):
                    if dim % c == 0:
                        return c
                return dim
            return sfc_ca_gemm_reference(
                a, b, bm=blk(a.shape[0]), bn=blk(b.shape[1]), bk=blk(a.shape[1])
            )

        return _ref
    raise ValueError(f"unknown matmul backend: {backend}")


def ca_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    tm_axis: str,
    tn_axis: str,
    kl_axis: Optional[str] = None,
    backend: str = "xla",
    reduce: str = "psum",
) -> jax.Array:
    """Stationary-C 2.5D CA matmul.

    Sharding contract (the 2.5D data placement):
      A (M, K): M over tm_axis, K over kl_axis, replicated over tn_axis
      B (K, N): K over kl_axis, N over tn_axis, replicated over tm_axis
      C (M, N): M over tm_axis, N over tn_axis
                (+ N additionally over kl_axis when reduce="psum_scatter")

    Each (tm, tn) chip in layer `l` contracts the l-th K/c slab into its own
    C copy with *zero* communication, then the copies are add-reduced over
    kl_axis — communication per chip = (c-1)/c · MN/(tm·tn) for psum_scatter,
    matching §II-C's low-order reduction term.
    """
    lm = local_matmul(backend)

    a_spec = P(tm_axis, kl_axis)
    b_spec = P(kl_axis, tn_axis)
    if kl_axis is None:
        out_spec = P(tm_axis, tn_axis)

        def body2d(a_loc: jax.Array, b_loc: jax.Array) -> jax.Array:
            return lm(a_loc, b_loc)

        return jax.shard_map(
            body2d,
            mesh=mesh,
            in_specs=(a_spec, b_spec),
            out_specs=out_spec,
            check_vma=False,
        )(a, b)

    if reduce == "psum":
        out_spec = P(tm_axis, tn_axis)
    elif reduce == "psum_scatter":
        # scatter splits each tn shard kl-ways -> kl is the minor axis on N
        out_spec = P(tm_axis, (tn_axis, kl_axis))
    else:
        raise ValueError(f"reduce must be psum|psum_scatter, got {reduce}")

    def body(a_loc: jax.Array, b_loc: jax.Array) -> jax.Array:
        c_copy = lm(a_loc, b_loc)  # this layer's partial C (Listing 1 GEMM phase)
        if reduce == "psum":
            return lax.psum(c_copy, kl_axis)  # add_reduce (lines 26-35)
        return lax.psum_scatter(
            c_copy, kl_axis, scatter_dimension=1, tiled=True
        )

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(a_spec, b_spec),
        out_specs=out_spec,
        check_vma=False,
    )(a, b)


def summa_ca_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    tm_axis: str,
    tn_axis: str,
    kl_axis: Optional[str] = None,
    backend: str = "xla",
) -> jax.Array:
    """Ring-SUMMA (stationary C) with compute/comm overlap inside each layer.

    Sharding contract:
      A (M, K): M over tm_axis, K over (kl_axis, tn_axis) — fully distributed
      B (K, N): K over kl_axis, N over tn_axis, replicated over tm_axis
                (stationary operand — for NN layers this is the weight,
                whose placement cost is paid once, not per step)
      C (M, N): M over tm_axis, N over tn_axis  (psum over kl_axis)

    Within a layer, each device's K/(c·tn) chunk of A rotates around the
    tn-axis ring with `ppermute`; at step s, the arriving chunk multiplies
    the matching K-rows of the resident B slab while the next chunk is in
    flight — the overlap schedule the paper delegates to COSMA/MPI, written
    jax-natively.  Total A bytes moved per chip equal one all-gather, but in
    tn pipelined pieces (beyond-paper: overlap; used in §Perf).
    """
    lm = local_matmul(backend)

    a_spec = P(tm_axis, (kl_axis, tn_axis) if kl_axis else tn_axis)
    b_spec = P(kl_axis, tn_axis) if kl_axis else P(None, tn_axis)
    out_spec = P(tm_axis, tn_axis)

    n_steps = mesh.shape[tn_axis]
    perm = [(i, (i + 1) % n_steps) for i in range(n_steps)]

    def body(a_loc: jax.Array, b_loc: jax.Array) -> jax.Array:
        my_col = lax.axis_index(tn_axis)
        k_chunk = a_loc.shape[1]  # = K/(c·tn)

        def step(carry, s):
            a_cur, acc = carry
            # perm (i -> i+1) means we receive from i-1: at step s we hold the
            # chunk that started at col (my_col - s) — those K rows of B
            src = (my_col - s) % n_steps
            b_rows = lax.dynamic_slice_in_dim(b_loc, src * k_chunk, k_chunk, axis=0)
            a_nxt = lax.ppermute(a_cur, tn_axis, perm)  # in flight during dot
            acc = acc + lm(a_cur, b_rows).astype(jnp.float32)
            return (a_nxt, acc), None

        acc0 = jnp.zeros((a_loc.shape[0], b_loc.shape[1]), jnp.float32)
        (_, acc), _ = lax.scan(step, (a_loc, acc0), jnp.arange(n_steps))
        if kl_axis:
            acc = lax.psum(acc, kl_axis)
        return acc.astype(a_loc.dtype)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(a_spec, b_spec),
        out_specs=out_spec,
        check_vma=False,
    )(a, b)
