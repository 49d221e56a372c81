"""Pallas TPU kernel: SFC-ordered Communication-Avoiding GEMM.

TPU adaptation of paper Listing 1 (see DESIGN.md §2.1).  The Pallas grid *is*
the paper's fused task loop: one grid step per (SFC-tile, K-layer, K-chunk)
task, visited in exactly the Listing-1 order, with the (im, in) tile
coordinates coming from a scalar-prefetched SFC table (the TPU analogue of
`map_sfc_index`).  Because Mosaic only re-fetches a block whose `index_map`
output changed between consecutive sequential grid steps, the gilbert-order
traversal realises the paper's BRGEMM taxonomy in hardware:

  * consecutive tiles share `im`  -> the A panel stays in VMEM (BRGEMM₂)
  * consecutive tiles share `in`  -> the B panel stays in VMEM (BRGEMM₁)
  * both change (quadrant hops)   -> BRGEMM₀, only O(√(Mb·Nb)) times.

Two families of kernels live here:

**Fused (layer-inner) forms** — `sfc_gemm_fused`, `sfc_gemm_batched_fused`,
`sfc_gemm_grouped`.  On a single TensorCore the 2.5D algorithm's replicated
C copies buy nothing: there is no second worker to hand a partial copy to,
so the grid is `(n_sfc_tasks, K_layers, n_k_chunks)` with the *layer as an
inner dimension*.  The f32 VMEM accumulator carries the full-K reduction
across layers — `add_reduce_tpp` degenerates into the accumulator itself —
and C is written to HBM exactly once.  No `(K_layers, M, N)` intermediate,
no second launch.  The flush step optionally applies a **fused epilogue**
(bias add, silu/gelu/relu activation, output scale, residual add) and a
**dual-B GLU form** (two B panels share one A traversal; flush writes
`act(acc_gate) * acc_val`) so gated-MLP projections never round-trip the
`(M, N)` output through HBM between the GEMM and its elementwise tail.

**Replicated (2.5D) forms** — `sfc_gemm_pallas`, `sfc_gemm_batched`, each
returning the `(K_layers, M, N)` C copies reduced by `add_reduce_pallas`.
These remain for the *distributed* `ca_matmul` path, where K_layers is a
mesh axis and the copies are combined with a psum (the true
`add_reduce_tpp`), and as the fallback when the fused accumulator footprint
does not fit VMEM (`ops.sfc_matmul` decides).

**Backward (NT/TN) forms** — `sfc_gemm_nt`, `sfc_gemm_tn` and their grouped
companions serve the training backward pass (`dA = dC·Bᵀ`, `dB = Aᵀ·dC`):
the same SFC task tables traversed with swapped operand roles, the
transposition expressed as `dot_general` dimension numbers on VMEM panels —
`Aᵀ`/`Bᵀ` never materialize in HBM.  See the section comment below.

`k_block_factor` chunks each layer's K range so the A/B panels fit VMEM
(paper §II-E: the k' constant), accumulating in an f32 VMEM scratch.
VMEM budget per step: bm*kc*(1+n_B) panels (+double-buffering) + bm*bn*4
per f32 accumulator — `ops.py` picks the knobs so this fits, using the same
analytical model the paper uses for its L2-capacity heuristic.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import (
    compile_schedule,
    gemm_spec,
    grouped_gemm_spec,
    grouped_tn_spec,
)
from repro.optim.adamw import (
    HYP_B1,
    HYP_B1C,
    HYP_B2,
    HYP_B2C,
    HYP_EPS,
    HYP_LR,
    HYP_SALT,
    HYP_SCALE,
    HYP_SEED,
    HYP_WD,
    HYP_1MB1,
    HYP_1MB2,
    seed_from_lane,
)

# Mosaic's scoped-VMEM limit, set explicitly on every GEMM launch and used
# by `ops.fused_path_fits_vmem` as the planning budget, so the knob planner
# and the compiler hold a kernel to the same number.
VMEM_LIMIT_BYTES = 16 * 2**20

# bumped when a kernel change invalidates measured knobs / calibration
# constants; `repro.tune.cache` stamps persisted entries with it and
# drops stale generations on mismatch
KERNEL_VERSION = 2  # v2: ABFT checksum lane in the fused/TN flush paths

__all__ = [
    "KERNEL_VERSION",
    "VMEM_LIMIT_BYTES",
    "sfc_gemm_pallas",
    "sfc_gemm_batched",
    "sfc_gemm_fused",
    "sfc_gemm_batched_fused",
    "sfc_gemm_grouped",
    "sfc_gemm_nt",
    "sfc_gemm_tn",
    "sfc_gemm_grouped_nt",
    "sfc_gemm_grouped_tn",
    "add_reduce_pallas",
    "build_task_table",
    "build_grouped_task_table",
    "build_grouped_tn_task_table",
    "activation_fn",
    "stochastic_round_to",
    "tile_random_bits",
    "ACTIVATIONS",
]


def build_task_table(mb: int, nb: int, k_layers: int) -> np.ndarray:
    """(3, K_layers*Mb*Nb) int32: rows = (im, in, layer) per task, in
    Listing-1 task order (layer-major, gilbert order within each layer).

    Thin compatibility wrapper: the table is emitted by the unified
    schedule compiler (`repro.core.schedule`); kernels consume the
    `Schedule` artifact directly."""
    return compile_schedule(gemm_spec(mb, nb, k_layers)).table


def build_grouped_task_table(
    row_blocks: Tuple[int, ...], nb: int
) -> np.ndarray:
    """(3, sum_e row_blocks[e]*nb) int32 task table for the grouped kernel.

    Rows = (im_global, in, expert): each expert e owns its own ``row_blocks[e]
    x nb`` tile grid, walked in gilbert order (one SFC map per expert), with
    ``im_global`` offset by the padded row blocks of the experts before it.
    Experts with zero rows contribute no tasks.  Compatibility wrapper over
    the unified schedule compiler (`repro.core.schedule`)."""
    return compile_schedule(grouped_gemm_spec(tuple(row_blocks), nb)).table


# ---------------------------------------------------------------------------
# fused epilogues
# ---------------------------------------------------------------------------

ACTIVATIONS = ("silu", "gelu", "relu")


def activation_fn(name: Optional[str]):
    """f32 -> f32 elementwise activation used in the kernel flush step."""
    if name is None:
        return lambda x: x
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu":
        return lambda x: jnp.maximum(x, 0.0)
    raise ValueError(f"unknown activation {name!r}; pick from {ACTIVATIONS}")


@dataclasses.dataclass(frozen=True)
class _FusedSpec:
    """Static layout/epilogue description for one fused-kernel build."""

    mode: str  # "plain" | "batched" | "grouped"
    glu: bool
    has_bias: bool
    has_gate_bias: bool
    has_residual: bool
    b_batched: bool
    n_layers: int
    n_k_chunks: int
    activation: Optional[str]
    out_scale: Optional[float]
    out_dtype: Any
    # training-forward mode: instead of the activated epilogue, flush the two
    # GLU pre-activations (value+bias, gate+gate_bias) as separate outputs —
    # the residuals `jax.custom_vjp` needs, still from one A traversal.
    preact_out: bool = False
    # ABFT checksum lane: a launch-resident (1, 1) f32 output accumulating
    # sum(raw accumulator) across every flush — pre-epilogue, so it equals
    # the operand checksum (eᵀA)·(Be) up to roundoff (repro.robust.abft).
    abft: bool = False


def _fused_kernel(*refs, spec: _FusedSpec):
    """Shared body for all three fused kernels.

    Ref order: tab, A, B_val, [B_gate], [bias], [gate_bias], [residual],
    O, acc, [acc_gate].  The zero step runs at the first (layer, k-chunk)
    of each C tile, the accumulate step on every grid step, and the flush —
    epilogue included — exactly once, at the last (layer, k-chunk): C and
    the epilogue operands touch HBM once per output tile.
    """
    it = iter(refs)
    next(it)  # tab: consumed by the index maps
    a_ref = next(it)
    b_ref = next(it)
    bg_ref = next(it) if spec.glu else None
    bias_ref = next(it) if spec.has_bias else None
    gbias_ref = next(it) if spec.has_gate_bias else None
    res_ref = next(it) if spec.has_residual else None
    o_ref = next(it)
    og_ref = next(it) if (spec.glu and spec.preact_out) else None
    chk_ref = next(it) if spec.abft else None
    acc_ref = next(it)
    accg_ref = next(it) if spec.glu else None

    if spec.mode == "plain":
        lyr, kc = pl.program_id(1), pl.program_id(2)
    elif spec.mode == "batched":
        lyr, kc = pl.program_id(2), pl.program_id(3)
    else:  # grouped: no 2.5D layer dimension
        lyr, kc = None, pl.program_id(1)

    first = kc == 0 if lyr is None else (lyr == 0) & (kc == 0)
    last = kc == spec.n_k_chunks - 1
    if lyr is not None:
        last = (lyr == spec.n_layers - 1) & last

    if spec.abft:
        # the checksum output is launch-resident (every grid step maps to
        # block (0, 0)): zero it exactly once, at the global first step
        launch_start = first
        for d in range(2 if spec.mode == "batched" else 1):
            launch_start = (pl.program_id(d) == 0) & launch_start

        @pl.when(launch_start)
        def _zero_chk():
            chk_ref[...] = jnp.zeros_like(chk_ref)

    @pl.when(first)
    def _zero():  # zero_tpp (Listing 1 line 16) — once per C tile
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if spec.glu:
            accg_ref[...] = jnp.zeros_like(accg_ref)

    a = a_ref[0] if spec.mode == "batched" else a_ref[...]
    if spec.mode == "grouped" or (spec.mode == "batched" and spec.b_batched):
        b = b_ref[0]
    else:
        b = b_ref[...]
    # brgemm_tpp: one stride-based batch-reduce step on the MXU
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)
    if spec.glu:
        bg = bg_ref[0] if spec.mode == "grouped" else bg_ref[...]
        accg_ref[...] += jnp.dot(a, bg, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _flush():
        if spec.abft:
            # checksum the *raw* accumulator(s): epilogues (bias, activation,
            # residual) are nonlinear in sum(C) and would break the identity
            chk = jnp.sum(acc_ref[...])
            if spec.glu:
                chk = chk + jnp.sum(accg_ref[...])
            chk_ref[0, 0] += chk
        acc = acc_ref[...]
        if spec.has_bias:
            bias = bias_ref[0] if spec.mode == "grouped" else bias_ref[...]
            acc = acc + bias.astype(jnp.float32)
        if spec.glu and spec.preact_out:
            # training forward: both biased pre-activations leave the kernel
            # (the VJP residuals); the activated product is formed outside.
            g = accg_ref[...]
            if spec.has_gate_bias:
                gb = gbias_ref[0] if spec.mode == "grouped" else gbias_ref[...]
                g = g + gb.astype(jnp.float32)
            if spec.mode == "batched":
                o_ref[0, ...] = acc.astype(spec.out_dtype)
                og_ref[0, ...] = g.astype(spec.out_dtype)
            else:
                o_ref[...] = acc.astype(spec.out_dtype)
                og_ref[...] = g.astype(spec.out_dtype)
            return
        if spec.glu:
            g = accg_ref[...]
            if spec.has_gate_bias:
                gb = gbias_ref[0] if spec.mode == "grouped" else gbias_ref[...]
                g = g + gb.astype(jnp.float32)
            y = activation_fn(spec.activation)(g) * acc
        elif spec.activation is not None:
            y = activation_fn(spec.activation)(acc)
        else:
            y = acc
        if spec.out_scale is not None:
            y = y * spec.out_scale
        if spec.has_residual:
            r = res_ref[0] if spec.mode == "batched" else res_ref[...]
            y = y + r.astype(jnp.float32)
        out = y.astype(spec.out_dtype)
        if spec.mode == "batched":
            o_ref[0, ...] = out
        else:
            o_ref[...] = out


def _fused_call(
    *,
    spec: _FusedSpec,
    tab: jax.Array,
    grid: Tuple[int, ...],
    inputs: list,
    in_specs: list,
    out_spec: pl.BlockSpec,
    out_shape: jax.ShapeDtypeStruct,
    bm: int,
    bn: int,
    interpret: bool,
):
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if spec.glu:
        scratch.append(pltpu.VMEM((bm, bn), jnp.float32))
    out_specs: Any = out_spec
    out_shapes: Any = out_shape
    if spec.glu and spec.preact_out:
        # second output: the gate pre-activation, same tiling as the value
        out_specs = [out_spec, out_spec]
        out_shapes = [out_shape, out_shape]
    if spec.abft:
        # trailing launch-resident checksum scalar (block (0, 0) at every
        # grid step — stays in VMEM, one 4-byte write at launch end)
        if not isinstance(out_specs, list):
            out_specs, out_shapes = [out_specs], [out_shapes]
        out_specs = out_specs + [pl.BlockSpec((1, 1), lambda *args: (0, 0))]
        out_shapes = out_shapes + [jax.ShapeDtypeStruct((1, 1), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_fused_kernel, spec=spec),
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(tab, *inputs)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm",
        "bn",
        "k_layers",
        "k_block_factor",
        "activation",
        "out_scale",
        "interpret",
        "out_dtype",
        "preact_out",
        "abft",
    ),
)
def sfc_gemm_fused(
    a: jax.Array,  # (M, K)
    b: jax.Array,  # (K, N)
    b_gate: Optional[jax.Array] = None,  # (K, N) GLU gate weights
    bias: Optional[jax.Array] = None,  # (1, N)
    gate_bias: Optional[jax.Array] = None,  # (1, N)
    residual: Optional[jax.Array] = None,  # (M, N)
    *,
    activation: Optional[str] = None,
    out_scale: Optional[float] = None,
    bm: int = 256,
    bn: int = 256,
    k_layers: int = 1,
    k_block_factor: int = 1,
    interpret: bool = False,
    out_dtype=None,
    preact_out: bool = False,
    abft: bool = False,
) -> jax.Array:
    """Single-launch SFC GEMM with in-kernel 2.5D reduction + fused epilogue.

    Grid `(Mb*Nb, K_layers, k_block_factor)`: layer is an *inner* dimension,
    so the f32 accumulator carries the full-K contraction and C = epilogue(
    A @ B) is written to HBM exactly once — the `(K_layers, M, N)` copies of
    the replicated form never materialize.  With ``b_gate`` the kernel runs
    the dual-B GLU form: one A traversal feeds two accumulators and the
    flush writes ``activation(A@b_gate [+gate_bias]) * (A@b [+bias])``.

    Epilogue order: ``y = act(acc + bias) [* act-gate] * out_scale +
    residual``; everything is applied to the f32 accumulator before the
    single cast to ``out_dtype``.

    Requires M % bm == N % bn == 0 and K % (k_layers * k_block_factor) == 0
    (`ops.sfc_matmul` pads arbitrary shapes).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if m % bm or n % bn:
        raise ValueError(f"(M,N)=({m},{n}) not divisible by (bm,bn)=({bm},{bn})")
    if k % (k_layers * k_block_factor):
        raise ValueError(f"K={k} vs k_layers*kbf={k_layers * k_block_factor}")
    out_dtype = out_dtype or a.dtype

    if preact_out and b_gate is None:
        raise ValueError("preact_out is the dual-B (GLU) training-forward mode")

    mb_cnt, nb_cnt = m // bm, n // bn
    k_chunk = k // (k_layers * k_block_factor)
    n_k_chunks = k_block_factor

    sched = compile_schedule(gemm_spec(mb_cnt, nb_cnt, 1))
    tab = jnp.asarray(sched.table)
    maj, mnr = sched.selector("major"), sched.selector("minor")
    spec = _FusedSpec(
        mode="plain",
        glu=b_gate is not None,
        has_bias=bias is not None,
        has_gate_bias=gate_bias is not None,
        has_residual=residual is not None,
        b_batched=False,
        n_layers=k_layers,
        n_k_chunks=n_k_chunks,
        activation=activation,
        out_scale=out_scale,
        out_dtype=out_dtype,
        preact_out=preact_out,
        abft=abft,
    )

    # Block index maps (units of blocks).  `t` walks the compiled schedule
    # order; layer `l` then chunk `kc` are innermost, so the C tile (and
    # both epilogue operands) are resident across the whole contraction.
    def a_map(t, l, kc, tab):
        return (maj(tab, t), l * n_k_chunks + kc)

    def b_map(t, l, kc, tab):
        return (l * n_k_chunks + kc, mnr(tab, t))

    def o_map(t, l, kc, tab):
        return (maj(tab, t), mnr(tab, t))

    def col_map(t, l, kc, tab):  # (1, N) epilogue vectors
        return (0, mnr(tab, t))

    inputs = [a, b]
    in_specs = [
        pl.BlockSpec((bm, k_chunk), a_map),
        pl.BlockSpec((k_chunk, bn), b_map),
    ]
    if b_gate is not None:
        inputs.append(b_gate)
        in_specs.append(pl.BlockSpec((k_chunk, bn), b_map))
    if bias is not None:
        inputs.append(bias)
        in_specs.append(pl.BlockSpec((1, bn), col_map))
    if gate_bias is not None:
        inputs.append(gate_bias)
        in_specs.append(pl.BlockSpec((1, bn), col_map))
    if residual is not None:
        inputs.append(residual)
        in_specs.append(pl.BlockSpec((bm, bn), o_map))

    out = _fused_call(
        spec=spec,
        tab=tab,
        grid=(mb_cnt * nb_cnt, k_layers, n_k_chunks),
        inputs=inputs,
        in_specs=in_specs,
        out_spec=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        bm=bm,
        bn=bn,
        interpret=interpret,
    )
    if abft:
        # (..., chk): trailing scalar checksum joins the regular output(s)
        return (*out[:-1], out[-1][0, 0])
    return out


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm",
        "bn",
        "k_layers",
        "k_block_factor",
        "activation",
        "out_scale",
        "interpret",
        "out_dtype",
        "preact_out",
        "abft",
    ),
)
def sfc_gemm_batched_fused(
    a: jax.Array,  # (B, M, K)
    b: jax.Array,  # (K, N) shared weights, or (B, K, N) per-batch
    b_gate: Optional[jax.Array] = None,  # (K, N) shared GLU gate weights
    bias: Optional[jax.Array] = None,  # (1, N)
    gate_bias: Optional[jax.Array] = None,  # (1, N)
    residual: Optional[jax.Array] = None,  # (B, M, N)
    *,
    activation: Optional[str] = None,
    out_scale: Optional[float] = None,
    bm: int = 256,
    bn: int = 256,
    k_layers: int = 1,
    k_block_factor: int = 1,
    interpret: bool = False,
    out_dtype=None,
    preact_out: bool = False,
    abft: bool = False,
) -> jax.Array:
    """Batched fused form: (B, M, N) written once, no replicated copies.

    The batch index is the outermost grid dimension; every batch element
    replays the same scalar-prefetched SFC task table.  With shared 2-D
    ``b`` (and ``b_gate``) the weight-panel index maps do not depend on the
    batch coordinate, so panels stay resident across batch boundaries.  The
    GLU form requires shared 2-D gate weights (projection weights are shared
    across the batch in every model call site).
    """
    bsz, m, k = a.shape
    b_batched = b.ndim == 3
    if b_batched:
        b2, k2, n = b.shape
        assert b2 == bsz, (a.shape, b.shape)
        assert b_gate is None, "GLU form requires shared 2-D weights"
    else:
        k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if m % bm or n % bn:
        raise ValueError(f"(M,N)=({m},{n}) not divisible by (bm,bn)=({bm},{bn})")
    if k % (k_layers * k_block_factor):
        raise ValueError(f"K={k} vs k_layers*kbf={k_layers * k_block_factor}")
    out_dtype = out_dtype or a.dtype

    if preact_out and b_gate is None:
        raise ValueError("preact_out is the dual-B (GLU) training-forward mode")

    mb_cnt, nb_cnt = m // bm, n // bn
    k_chunk = k // (k_layers * k_block_factor)
    n_k_chunks = k_block_factor

    sched = compile_schedule(gemm_spec(mb_cnt, nb_cnt, 1))
    tab = jnp.asarray(sched.table)
    maj, mnr = sched.selector("major"), sched.selector("minor")
    spec = _FusedSpec(
        mode="batched",
        glu=b_gate is not None,
        has_bias=bias is not None,
        has_gate_bias=gate_bias is not None,
        has_residual=residual is not None,
        b_batched=b_batched,
        n_layers=k_layers,
        n_k_chunks=n_k_chunks,
        activation=activation,
        out_scale=out_scale,
        out_dtype=out_dtype,
        preact_out=preact_out,
        abft=abft,
    )

    def a_map(bi, t, l, kc, tab):
        return (bi, maj(tab, t), l * n_k_chunks + kc)

    def o_map(bi, t, l, kc, tab):
        return (bi, maj(tab, t), mnr(tab, t))

    def col_map(bi, t, l, kc, tab):
        return (0, mnr(tab, t))

    if b_batched:
        def b_map(bi, t, l, kc, tab):
            return (bi, l * n_k_chunks + kc, mnr(tab, t))

        b_spec = pl.BlockSpec((1, k_chunk, bn), b_map)
    else:
        def b_map(bi, t, l, kc, tab):
            return (l * n_k_chunks + kc, mnr(tab, t))

        b_spec = pl.BlockSpec((k_chunk, bn), b_map)

    inputs = [a, b]
    in_specs = [pl.BlockSpec((1, bm, k_chunk), a_map), b_spec]
    if b_gate is not None:
        inputs.append(b_gate)
        in_specs.append(pl.BlockSpec((k_chunk, bn), b_map))
    if bias is not None:
        inputs.append(bias)
        in_specs.append(pl.BlockSpec((1, bn), col_map))
    if gate_bias is not None:
        inputs.append(gate_bias)
        in_specs.append(pl.BlockSpec((1, bn), col_map))
    if residual is not None:
        inputs.append(residual)
        in_specs.append(pl.BlockSpec((1, bm, bn), o_map))

    out = _fused_call(
        spec=spec,
        tab=tab,
        grid=(bsz, mb_cnt * nb_cnt, k_layers, n_k_chunks),
        inputs=inputs,
        in_specs=in_specs,
        out_spec=pl.BlockSpec((1, bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((bsz, m, n), out_dtype),
        bm=bm,
        bn=bn,
        interpret=interpret,
    )
    if abft:
        return (*out[:-1], out[-1][0, 0])
    return out


# ---------------------------------------------------------------------------
# replicated (2.5D) forms — kept for the distributed psum path and as the
# fallback when the fused accumulator footprint does not fit VMEM
# ---------------------------------------------------------------------------


def _sfc_gemm_kernel(
    tab_ref,  # scalar-prefetch: (3, n_tasks) SFC task table
    a_ref,  # (bm, k_chunk) A panel in VMEM
    b_ref,  # (k_chunk, bn) B panel in VMEM
    o_ref,  # (1, bm, bn) C-copy tile in VMEM
    acc_ref,  # (bm, bn) f32 scratch accumulator
    *,
    n_k_chunks: int,
    out_dtype,
):
    del tab_ref  # consumed by the index maps
    kc = pl.program_id(1)

    @pl.when(kc == 0)
    def _zero():  # zero_tpp (Listing 1 line 16)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # brgemm_tpp: one stride-based batch-reduce step on the MXU
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(kc == n_k_chunks - 1)
    def _flush():
        o_ref[0, ...] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm",
        "bn",
        "k_layers",
        "k_block_factor",
        "interpret",
        "out_dtype",
    ),
)
def sfc_gemm_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    k_layers: int = 1,
    k_block_factor: int = 1,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Partial-product stage: returns the (K_layers, M, N) replicated C copies
    (reduce with `add_reduce_pallas`).  Kept for the distributed `ca_matmul`
    psum path; single-core callers want `sfc_gemm_fused`.

    Requires M % bm == N % bn == 0 and K % (k_layers * k_block_factor) == 0.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if m % bm or n % bn:
        raise ValueError(f"(M,N)=({m},{n}) not divisible by (bm,bn)=({bm},{bn})")
    if k % (k_layers * k_block_factor):
        raise ValueError(f"K={k} vs k_layers*kbf={k_layers * k_block_factor}")
    out_dtype = out_dtype or a.dtype

    mb_cnt, nb_cnt = m // bm, n // bn
    k_per_layer = k // k_layers
    k_chunk = k_per_layer // k_block_factor
    n_k_chunks = k_block_factor
    n_tasks = k_layers * mb_cnt * nb_cnt

    sched = compile_schedule(gemm_spec(mb_cnt, nb_cnt, k_layers))
    tab = jnp.asarray(sched.table)
    maj, mnr, lyr = (
        sched.selector("major"), sched.selector("minor"),
        sched.selector("layer"),
    )

    # Block index maps (units of blocks).  `t` walks Listing-1 task order;
    # `kc` is the K-chunk (innermost, so the C tile is revisited/resident).
    kc_per_layer = k_per_layer // k_chunk

    def a_map(t, kc, tab):
        return (maj(tab, t), lyr(tab, t) * kc_per_layer + kc)

    def b_map(t, kc, tab):
        return (lyr(tab, t) * kc_per_layer + kc, mnr(tab, t))

    def o_map(t, kc, tab):
        return (lyr(tab, t), maj(tab, t), mnr(tab, t))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tasks, n_k_chunks),
        in_specs=[
            pl.BlockSpec((bm, k_chunk), a_map),
            pl.BlockSpec((k_chunk, bn), b_map),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), o_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )

    kernel = functools.partial(
        _sfc_gemm_kernel, n_k_chunks=n_k_chunks, out_dtype=out_dtype
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k_layers, m, n), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(tab, a, b)


def _sfc_gemm_batched_kernel(
    tab_ref,  # scalar-prefetch: (3, n_tasks) SFC task table (shared by batch)
    a_ref,  # (1, bm, k_chunk) A panel in VMEM
    b_ref,  # (k_chunk, bn) or (1, k_chunk, bn) B panel in VMEM
    o_ref,  # (1, 1, bm, bn) C-copy tile in VMEM
    acc_ref,  # (bm, bn) f32 scratch accumulator
    *,
    n_k_chunks: int,
    out_dtype,
    b_batched: bool,
):
    del tab_ref
    kc = pl.program_id(2)

    @pl.when(kc == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    b_panel = b_ref[0] if b_batched else b_ref[...]
    acc_ref[...] += jnp.dot(
        a_ref[0], b_panel, preferred_element_type=jnp.float32
    )

    @pl.when(kc == n_k_chunks - 1)
    def _flush():
        o_ref[0, 0, ...] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm",
        "bn",
        "k_layers",
        "k_block_factor",
        "interpret",
        "out_dtype",
    ),
)
def sfc_gemm_batched(
    a: jax.Array,  # (B, M, K)
    b: jax.Array,  # (K, N) shared weights, or (B, K, N) per-batch
    *,
    bm: int = 256,
    bn: int = 256,
    k_layers: int = 1,
    k_block_factor: int = 1,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Batched partial-product stage: (B, K_layers, M, N) replicated C copies.

    The batch index is the outermost grid dimension; every batch element
    replays the same scalar-prefetched SFC task table, so the table (and the
    Mosaic index-map machinery) is built once for the whole batch.  With a
    shared 2-D ``b`` the B-panel index map does not depend on the batch
    coordinate — the weight panel that ends one batch element's traversal
    stays resident into the next element's first task.

    Requires M % bm == N % bn == 0 and K % (k_layers * k_block_factor) == 0
    (``ops.sfc_matmul`` pads arbitrary shapes).
    """
    bsz, m, k = a.shape
    b_batched = b.ndim == 3
    if b_batched:
        b2, k2, n = b.shape
        assert b2 == bsz, (a.shape, b.shape)
    else:
        k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if m % bm or n % bn:
        raise ValueError(f"(M,N)=({m},{n}) not divisible by (bm,bn)=({bm},{bn})")
    if k % (k_layers * k_block_factor):
        raise ValueError(f"K={k} vs k_layers*kbf={k_layers * k_block_factor}")
    out_dtype = out_dtype or a.dtype

    mb_cnt, nb_cnt = m // bm, n // bn
    k_per_layer = k // k_layers
    k_chunk = k_per_layer // k_block_factor
    n_k_chunks = k_block_factor
    n_tasks = k_layers * mb_cnt * nb_cnt
    kc_per_layer = k_per_layer // k_chunk

    sched = compile_schedule(gemm_spec(mb_cnt, nb_cnt, k_layers))
    tab = jnp.asarray(sched.table)
    maj, mnr, lyr = (
        sched.selector("major"), sched.selector("minor"),
        sched.selector("layer"),
    )

    def a_map(bi, t, kc, tab):
        return (bi, maj(tab, t), lyr(tab, t) * kc_per_layer + kc)

    def o_map(bi, t, kc, tab):
        return (bi, lyr(tab, t), maj(tab, t), mnr(tab, t))

    if b_batched:
        def b_map(bi, t, kc, tab):
            return (bi, lyr(tab, t) * kc_per_layer + kc, mnr(tab, t))

        b_spec = pl.BlockSpec((1, k_chunk, bn), b_map)
    else:
        def b_map(bi, t, kc, tab):
            return (lyr(tab, t) * kc_per_layer + kc, mnr(tab, t))

        b_spec = pl.BlockSpec((k_chunk, bn), b_map)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, n_tasks, n_k_chunks),
        in_specs=[
            pl.BlockSpec((1, bm, k_chunk), a_map),
            b_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, bm, bn), o_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )

    kernel = functools.partial(
        _sfc_gemm_batched_kernel,
        n_k_chunks=n_k_chunks,
        out_dtype=out_dtype,
        b_batched=b_batched,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, k_layers, m, n), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(tab, a, b)


@functools.partial(
    jax.jit,
    static_argnames=(
        "row_blocks",
        "bm",
        "bn",
        "k_block_factor",
        "activation",
        "out_scale",
        "interpret",
        "out_dtype",
        "preact_out",
        "abft",
    ),
)
def sfc_gemm_grouped(
    a: jax.Array,  # (sum_e row_blocks[e]*bm, K) expert-grouped, padded rows
    b: jax.Array,  # (E, K, N) per-expert weights
    b_gate: Optional[jax.Array] = None,  # (E, K, N) per-expert gate weights
    bias: Optional[jax.Array] = None,  # (E, 1, N) per-expert bias
    gate_bias: Optional[jax.Array] = None,  # (E, 1, N)
    *,
    row_blocks: Tuple[int, ...],
    activation: Optional[str] = None,
    out_scale: Optional[float] = None,
    bm: int = 128,
    bn: int = 128,
    k_block_factor: int = 1,
    interpret: bool = False,
    out_dtype=None,
    preact_out: bool = False,
    abft: bool = False,
) -> jax.Array:
    """Grouped (ragged) SFC GEMM: per-expert row slabs against per-expert
    weights, one SFC map per expert tile grid (paper's shape-obliviousness
    applied to MoE expert GEMMs), with the same fused epilogue / dual-B GLU
    flush as `sfc_gemm_fused` — the SwiGLU expert MLP reads each dispatched
    row slab from HBM once.

    ``a`` holds the experts' rows concatenated, each expert's slab padded to
    ``row_blocks[e] * bm`` rows; the task table walks expert e's
    ``row_blocks[e] x (N/bn)`` grid in gilbert order before moving to e+1, so
    B panels of one expert are fully consumed before the next expert's are
    touched.  Returns the (sum_rows, N) padded product (callers slice the
    per-expert valid rows back out).
    """
    m_total, k = a.shape
    e_cnt, k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert len(row_blocks) == e_cnt, (row_blocks, e_cnt)
    if m_total != sum(row_blocks) * bm:
        raise ValueError(
            f"A rows {m_total} != sum(row_blocks)*bm = {sum(row_blocks)}*{bm}"
        )
    if n % bn:
        raise ValueError(f"N={n} not divisible by bn={bn}")
    if k % k_block_factor:
        raise ValueError(f"K={k} vs k_block_factor={k_block_factor}")
    out_dtype = out_dtype or a.dtype

    if preact_out and b_gate is None:
        raise ValueError("preact_out is the dual-B (GLU) training-forward mode")

    nb_cnt = n // bn
    k_chunk = k // k_block_factor
    n_k_chunks = k_block_factor

    sched = compile_schedule(grouped_gemm_spec(tuple(row_blocks), nb_cnt))
    n_tasks = sched.num_tasks
    if n_tasks == 0:
        zero = jnp.zeros((m_total, n), out_dtype)
        outs = (zero, zero) if preact_out else (zero,)
        if abft:
            outs = (*outs, jnp.float32(0.0))
        return outs if len(outs) > 1 else outs[0]
    tab = jnp.asarray(sched.table)
    maj, mnr, grp = (
        sched.selector("major"), sched.selector("minor"),
        sched.selector("group"),
    )
    spec = _FusedSpec(
        mode="grouped",
        glu=b_gate is not None,
        has_bias=bias is not None,
        has_gate_bias=gate_bias is not None,
        has_residual=False,
        b_batched=False,
        n_layers=1,
        n_k_chunks=n_k_chunks,
        activation=activation,
        out_scale=out_scale,
        out_dtype=out_dtype,
        preact_out=preact_out,
        abft=abft,
    )

    def a_map(t, kc, tab):
        return (maj(tab, t), kc)

    def b_map(t, kc, tab):
        return (grp(tab, t), kc, mnr(tab, t))

    def o_map(t, kc, tab):
        return (maj(tab, t), mnr(tab, t))

    def col_map(t, kc, tab):  # (E, 1, N) per-expert epilogue vectors
        return (grp(tab, t), 0, mnr(tab, t))

    inputs = [a, b]
    in_specs = [
        pl.BlockSpec((bm, k_chunk), a_map),
        pl.BlockSpec((1, k_chunk, bn), b_map),
    ]
    if b_gate is not None:
        inputs.append(b_gate)
        in_specs.append(pl.BlockSpec((1, k_chunk, bn), b_map))
    if bias is not None:
        inputs.append(bias)
        in_specs.append(pl.BlockSpec((1, 1, bn), col_map))
    if gate_bias is not None:
        inputs.append(gate_bias)
        in_specs.append(pl.BlockSpec((1, 1, bn), col_map))

    out = _fused_call(
        spec=spec,
        tab=tab,
        grid=(n_tasks, n_k_chunks),
        inputs=inputs,
        in_specs=in_specs,
        out_spec=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((m_total, n), out_dtype),
        bm=bm,
        bn=bn,
        interpret=interpret,
    )
    if abft:
        return (*out[:-1], out[-1][0, 0])
    return out


# ---------------------------------------------------------------------------
# stochastic rounding + the TN grad-and-update flush
#
# The fused-optimizer flush casts the updated f32 master weight to the
# param dtype inside the kernel; for bf16 the cast rounds *stochastically*
# (the standard low-precision-training trick: E[round(x)] == x, so update
# increments smaller than one bf16 ulp are preserved in expectation instead
# of being swallowed by round-to-nearest).  Random bits come from the TPU
# per-core PRNG (`pltpu.prng_seed` / `pltpu.prng_random_bits`) on real
# Mosaic lowering, and from a counter-based integer hash in interpret mode
# (the TPU PRNG has no CPU lowering); both are seeded deterministically per
# (step, output tile), so a fixed step re-runs bit-identically per backend.
# ---------------------------------------------------------------------------


def _hash_u32(x: jax.Array) -> jax.Array:
    """32-bit finalizer (murmur3-style avalanche) over uint32 lanes."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def tile_random_bits(shape, seed: jax.Array, *, hw_rng: bool) -> jax.Array:
    """(shape) uint32 random bits from an int32/uint32 scalar seed.

    ``hw_rng=True`` (real TPU lowering) uses the per-core Mosaic PRNG;
    otherwise a counter-based hash over the tile's (row, col) grid — the
    interpret-mode path, also the reference for determinism tests."""
    if hw_rng:
        pltpu.prng_seed(seed.astype(jnp.int32))
        return pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    i = lax.broadcasted_iota(jnp.uint32, shape, 0)
    j = lax.broadcasted_iota(jnp.uint32, shape, 1)
    x = (
        seed.astype(jnp.uint32)
        ^ (i * jnp.uint32(0x9E3779B1))
        ^ (j * jnp.uint32(0x85EBCA77))
    )
    return _hash_u32(x)


def stochastic_round_to(x: jax.Array, bits: jax.Array, dtype) -> jax.Array:
    """Stochastically round f32 ``x`` to ``dtype`` using uint32 ``bits``.

    bf16 shares f32's exponent/sign layout, so adding a uniform 16-bit
    offset to the f32 significand and truncating the low 16 bits rounds up
    with probability equal to the truncated fraction — exactly unbiased.
    Non-bf16 targets fall back to round-to-nearest (nothing to dither: f32
    is the master dtype).  Non-finite values pass through untouched."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return x.astype(dtype)
    xf = x.astype(jnp.float32)
    xu = lax.bitcast_convert_type(xf, jnp.uint32)
    xu = (xu + (bits & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
    rounded = lax.bitcast_convert_type(xu, jnp.float32)
    return jnp.where(jnp.isfinite(xf), rounded, xf).astype(jnp.bfloat16)


@dataclasses.dataclass(frozen=True)
class _TnUpdate:
    """Static description of the TN kernel's grad-and-update flush."""

    param_dtype: Any  # dtype of the W_new output (bf16 -> SR eligible)
    stochastic_round: bool
    hw_rng: bool  # Mosaic PRNG vs interpret-mode hash bits


def _tile_seed(hyp_ref, *salts) -> jax.Array:
    """Deterministic per-(step, leaf, tile) uint32 seed: the int32 step
    (bitcast out of the seed lane) mixed with the per-leaf/per-layer salt
    lane and the tile coordinates (and expert id) — no two routed weights,
    layers or tiles share a dither stream."""
    s = seed_from_lane(hyp_ref[HYP_SEED]).astype(jnp.uint32)
    h = _hash_u32(s ^ jnp.uint32(0x2545F491))
    h = _hash_u32(
        h ^ seed_from_lane(hyp_ref[HYP_SALT]).astype(jnp.uint32)
        * jnp.uint32(0x85EBCA77)
    )
    for salt in salts:
        h = _hash_u32(h ^ salt.astype(jnp.uint32) * jnp.uint32(0x9E3779B1))
    return h


def _apply_update_flush(
    acc: jax.Array,  # (bm, bn) f32 raw dW accumulator
    mst_ref,
    mu_ref,
    nu_ref,
    w_out,
    mst_out,
    mu_out,
    nu_out,
    hyp_ref,
    seed: jax.Array,
    upd: _TnUpdate,
    *,
    out_index=...,
) -> jax.Array:
    """AdamW on the f32 accumulator (the `optim.adamw.adamw_leaf_update`
    program, scalars from the SMEM hyper vector); writes W/master/mu/nu
    tiles back and returns ``sum(dW^2)`` (pre-clip, for the global norm).

    ``scale == 0`` is the reserved skip-update sentinel (a finite grad
    norm never clips to exactly 0): moments and master are written back
    *unchanged* and W is the deterministic cast of the unchanged master
    — stochastic rounding is bypassed so the skip is reproducible.  For
    f32 (and bf16 without SR) params that cast is bitwise the previous
    W; under bf16+SR it can differ by one ulp from the last dithered
    write (the kernel has no old-W input to echo)."""
    ix = out_index
    sq = jnp.sum(acc * acc)
    skip = hyp_ref[HYP_SCALE] == 0.0
    g = acc * hyp_ref[HYP_SCALE]
    mu, nu, mst = mu_ref[ix], nu_ref[ix], mst_ref[ix]
    mu_n = hyp_ref[HYP_B1] * mu + hyp_ref[HYP_1MB1] * g
    nu_n = hyp_ref[HYP_B2] * nu + hyp_ref[HYP_1MB2] * jnp.square(g)
    mhat = mu_n / hyp_ref[HYP_B1C]
    nhat = nu_n / hyp_ref[HYP_B2C]
    step_v = mhat / (jnp.sqrt(nhat) + hyp_ref[HYP_EPS]) + hyp_ref[HYP_WD] * mst
    mst_n = mst - hyp_ref[HYP_LR] * step_v
    # select (not multiply) so a NaN/Inf accumulator cannot leak through
    mu_n = jnp.where(skip, mu, mu_n)
    nu_n = jnp.where(skip, nu, nu_n)
    mst_n = jnp.where(skip, mst, mst_n)
    mu_out[ix] = mu_n
    nu_out[ix] = nu_n
    mst_out[ix] = mst_n
    if upd.stochastic_round:
        bits = tile_random_bits(mst_n.shape, seed, hw_rng=upd.hw_rng)
        w_out[ix] = jnp.where(
            skip,
            mst_n.astype(upd.param_dtype),
            stochastic_round_to(mst_n, bits, upd.param_dtype),
        )
    else:
        w_out[ix] = mst_n.astype(upd.param_dtype)
    return sq


# ---------------------------------------------------------------------------
# NT / TN backward-pass kernels
#
# The training backward GEMMs — dA = dC·Bᵀ (NT) and dB = Aᵀ·dC (TN) — are
# exactly the shape-oblivious case the SFC traversal is built for: the task
# table walks the *gradient's* output tile grid in gilbert order while the
# index maps read the stored operands with swapped roles, so Aᵀ/Bᵀ are never
# materialized in HBM; the transposition happens inside the MXU contraction
# (`dot_general` dimension numbers) on VMEM-resident panels.  Both carry the
# same layer-inner 2.5D contraction chunking as the fused forward kernels.
#
# The dual forms mirror the forward GLU fusion: one NT launch accumulates
# ``a@bᵀ + a2@b2ᵀ`` (the GLU dA = dg·Wgᵀ + dh·Wvᵀ in a single traversal),
# and one TN launch streams A once to flush both ``aᵀ@b`` and ``aᵀ@b2``
# (dWv and dWg share the activation traversal).
# ---------------------------------------------------------------------------


def _nt_kernel(
    tab_ref,  # scalar-prefetch SFC task table (2+, n_tasks)
    *refs,
    n_layers: int,
    n_k_chunks: int,
    dual: bool,
    out_dtype,
):
    """out[t] += a[im] @ b[in]ᵀ (+ a2[im] @ b2[in]ᵀ): contraction over the
    operands' shared *last* dim, no transposed copy."""
    del tab_ref
    it = iter(refs)
    a_ref = next(it)
    b_ref = next(it)
    a2_ref = next(it) if dual else None
    b2_ref = next(it) if dual else None
    o_ref = next(it)
    acc_ref = next(it)

    lyr, kc = pl.program_id(1), pl.program_id(2)

    @pl.when((lyr == 0) & (kc == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    nt_dims = (((1,), (1,)), ((), ()))  # contract last-with-last: a @ bᵀ
    acc_ref[...] += lax.dot_general(
        a_ref[...], b_ref[...], nt_dims, preferred_element_type=jnp.float32
    )
    if dual:
        acc_ref[...] += lax.dot_general(
            a2_ref[...], b2_ref[...], nt_dims,
            preferred_element_type=jnp.float32,
        )

    @pl.when((lyr == n_layers - 1) & (kc == n_k_chunks - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm",
        "bn",
        "k_layers",
        "k_block_factor",
        "interpret",
        "out_dtype",
    ),
)
def sfc_gemm_nt(
    a: jax.Array,  # (M, K)
    b: jax.Array,  # (N, K) — consumed as bᵀ, never transposed in HBM
    a2: Optional[jax.Array] = None,  # (M, K) second addend (GLU dA)
    b2: Optional[jax.Array] = None,  # (N, K)
    *,
    bm: int = 256,
    bn: int = 256,
    k_layers: int = 1,
    k_block_factor: int = 1,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """C = A @ Bᵀ (+ A2 @ B2ᵀ) via the SFC traversal of C's tile grid.

    Grid ``(Mb*Nb, k_layers, k_block_factor)`` exactly like the fused
    forward kernel; the B panel is a ``(bn, k_chunk)`` row slab of the
    *untransposed* (N, K) operand, and the in-kernel `dot_general` contracts
    both operands' last dims.  This is the dA backward kernel: A = dC,
    B = the forward weights as stored.

    Requires M % bm == N % bn == 0 and K % (k_layers * k_block_factor) == 0
    (`ops.sfc_matmul_nt` pads arbitrary shapes).
    """
    m, k = a.shape
    n, k2 = b.shape
    assert k == k2, (a.shape, b.shape)
    dual = a2 is not None
    if dual:
        assert b2 is not None and a2.shape == (m, k) and b2.shape == (n, k), (
            a2.shape,
            b2.shape,
        )
    if m % bm or n % bn:
        raise ValueError(f"(M,N)=({m},{n}) not divisible by (bm,bn)=({bm},{bn})")
    if k % (k_layers * k_block_factor):
        raise ValueError(f"K={k} vs k_layers*kbf={k_layers * k_block_factor}")
    out_dtype = out_dtype or a.dtype

    mb_cnt, nb_cnt = m // bm, n // bn
    k_chunk = k // (k_layers * k_block_factor)
    n_k_chunks = k_block_factor
    sched = compile_schedule(gemm_spec(mb_cnt, nb_cnt, 1))
    tab = jnp.asarray(sched.table)
    maj, mnr = sched.selector("major"), sched.selector("minor")

    def a_map(t, l, kc, tab):
        return (maj(tab, t), l * n_k_chunks + kc)

    def b_map(t, l, kc, tab):  # row slab of the (N, K) operand
        return (mnr(tab, t), l * n_k_chunks + kc)

    def o_map(t, l, kc, tab):
        return (maj(tab, t), mnr(tab, t))

    inputs = [a, b]
    in_specs = [
        pl.BlockSpec((bm, k_chunk), a_map),
        pl.BlockSpec((bn, k_chunk), b_map),
    ]
    if dual:
        inputs += [a2, b2]
        in_specs += [
            pl.BlockSpec((bm, k_chunk), a_map),
            pl.BlockSpec((bn, k_chunk), b_map),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mb_cnt * nb_cnt, k_layers, n_k_chunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), o_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(
        _nt_kernel,
        n_layers=k_layers,
        n_k_chunks=n_k_chunks,
        dual=dual,
        out_dtype=out_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(tab, *inputs)


def _tn_kernel(
    *prefetch_and_refs,
    n_layers: int,
    n_k_chunks: int,
    dual: bool,
    out_dtype,
    update: Optional[_TnUpdate] = None,
    abft: bool = False,
):
    """out[t] += aᵀ-slab @ b-slab (+ second output for b2): contraction over
    the operands' shared *first* (row) dim.

    With ``update`` the flush is the grad-and-update step: instead of
    writing dW, it runs AdamW on the f32 accumulator against the resident
    (master, mu, nu) tiles, writes back (W_new, master', mu', nu') and
    accumulates ``sum(dW^2)`` into a scalar norm output — the raw weight
    gradient never leaves VMEM."""
    it = iter(prefetch_and_refs)
    tab_ref = next(it)
    hyp_ref = next(it) if update is not None else None
    a_ref = next(it)
    b_ref = next(it)
    b2_ref = next(it) if dual else None
    if update is not None:
        mst_ref = next(it)
        mu_ref = next(it)
        nu_ref = next(it)
        if dual:
            mst2_ref = next(it)
            mu2_ref = next(it)
            nu2_ref = next(it)
        w_o = next(it)
        mst_o = next(it)
        mu_o = next(it)
        nu_o = next(it)
        if dual:
            w2_o = next(it)
            mst2_o = next(it)
            mu2_o = next(it)
            nu2_o = next(it)
        norm_o = next(it)
    else:
        o_ref = next(it)
        o2_ref = next(it) if dual else None
    chk_o = next(it) if abft else None
    acc_ref = next(it)
    acc2_ref = next(it) if dual else None

    t, lyr, kc = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    if update is not None or abft:

        @pl.when((t == 0) & (lyr == 0) & (kc == 0))
        def _zero_norm():  # once per launch; the blocks are launch-resident
            if update is not None:
                norm_o[...] = jnp.zeros_like(norm_o)
            if abft:
                chk_o[...] = jnp.zeros_like(chk_o)

    @pl.when((lyr == 0) & (kc == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if dual:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)

    tn_dims = (((0,), (0,)), ((), ()))  # contract rows-with-rows: aᵀ @ b
    a_pan = a_ref[...]
    acc_ref[...] += lax.dot_general(
        a_pan, b_ref[...], tn_dims, preferred_element_type=jnp.float32
    )
    if dual:
        acc2_ref[...] += lax.dot_general(
            a_pan, b2_ref[...], tn_dims, preferred_element_type=jnp.float32
        )

    @pl.when((lyr == n_layers - 1) & (kc == n_k_chunks - 1))
    def _flush():
        if abft:
            # checksum the raw dW accumulator(s) before the optimizer (or
            # the cast) touches them — one per operand set
            chk_o[0, 0] += jnp.sum(acc_ref[...])
            if dual:
                chk_o[1, 0] += jnp.sum(acc2_ref[...])
        if update is None:
            o_ref[...] = acc_ref[...].astype(out_dtype)
            if dual:
                o2_ref[...] = acc2_ref[...].astype(out_dtype)
            return
        im, in_ = tab_ref[0, t], tab_ref[1, t]
        norm_o[0, 0] += _apply_update_flush(
            acc_ref[...], mst_ref, mu_ref, nu_ref,
            w_o, mst_o, mu_o, nu_o,
            hyp_ref, _tile_seed(hyp_ref, im, in_), update,
        )
        if dual:
            norm_o[1, 0] += _apply_update_flush(
                acc2_ref[...], mst2_ref, mu2_ref, nu2_ref,
                w2_o, mst2_o, mu2_o, nu2_o,
                hyp_ref,
                _tile_seed(hyp_ref, im, in_, jnp.int32(1)),
                update,
            )


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm",
        "bn",
        "k_layers",
        "k_block_factor",
        "interpret",
        "out_dtype",
        "update_dtype",
        "stochastic_round",
        "abft",
    ),
)
def sfc_gemm_tn(
    a: jax.Array,  # (M, K) — consumed as aᵀ, never transposed in HBM
    b: jax.Array,  # (M, N)
    b2: Optional[jax.Array] = None,  # (M, N) second operand (GLU dWg)
    master: Optional[jax.Array] = None,  # (K, N) f32 — enables update mode
    mu: Optional[jax.Array] = None,  # (K, N) f32 first moment
    nu: Optional[jax.Array] = None,  # (K, N) f32 second moment
    master2: Optional[jax.Array] = None,  # (K, N) f32 (dual update)
    mu2: Optional[jax.Array] = None,
    nu2: Optional[jax.Array] = None,
    hyper: Optional[jax.Array] = None,  # (12,) f32 AdamW scalars (SMEM)
    *,
    bm: int = 256,
    bn: int = 256,
    k_layers: int = 1,
    k_block_factor: int = 1,
    interpret: bool = False,
    out_dtype=None,
    update_dtype=None,  # W_new output dtype (the param dtype)
    stochastic_round: bool = False,
    abft: bool = False,
):
    """C = Aᵀ @ B (and Aᵀ @ B2) via the SFC traversal of the (K, N) output.

    The contraction runs over the shared row dim M in layer-inner chunks;
    each grid step contracts an ``(m_chunk, bm)`` column slab of the stored
    (M, K) operand against an ``(m_chunk, bn)`` slab of B.  This is the dW
    backward kernel: A = the forward activations, B = dC.  With ``b2`` the
    A slab is streamed once for both weight grads (returns a tuple).

    **Update (grad-and-update) flush**: passing ``master``/``mu``/``nu``
    (+ the (12,) ``hyper`` AdamW scalar vector, second scalar-prefetch
    operand) switches the flush to the fused AdamW step — dW stays in the
    f32 accumulator, the moments update in place, decoupled weight decay
    applies against the master weight, and the outputs are
    ``(W_new, master', mu', nu', norm)`` (dual: both weight sets then a
    (2, 1) norm) where ``W_new`` is cast to ``update_dtype`` — with
    stochastic rounding when bf16 and ``stochastic_round`` — and ``norm``
    accumulates ``sum(dW^2)`` pre-clip.  The raw gradient never exists in
    HBM.

    Requires K % bm == N % bn == 0 and M % (k_layers * k_block_factor) == 0
    (`ops.sfc_matmul_tn` pads arbitrary shapes).
    """
    m, k = a.shape
    m2, n = b.shape
    assert m == m2, (a.shape, b.shape)
    dual = b2 is not None
    if dual:
        assert b2.shape == (m, n), (b2.shape, b.shape)
    if k % bm or n % bn:
        raise ValueError(f"(K,N)=({k},{n}) not divisible by (bm,bn)=({bm},{bn})")
    if m % (k_layers * k_block_factor):
        raise ValueError(f"M={m} vs k_layers*kbf={k_layers * k_block_factor}")
    out_dtype = out_dtype or a.dtype

    update_mode = master is not None
    if update_mode:
        assert mu is not None and nu is not None and hyper is not None
        for t_ in (master, mu, nu):
            assert t_.shape == (k, n), (t_.shape, (k, n))
        if dual:
            assert master2 is not None and mu2 is not None and nu2 is not None
        update = _TnUpdate(
            param_dtype=jnp.dtype(update_dtype or out_dtype),
            stochastic_round=stochastic_round,
            hw_rng=not interpret,
        )
    else:
        update = None

    kb_cnt, nb_cnt = k // bm, n // bn
    m_chunk = m // (k_layers * k_block_factor)
    n_k_chunks = k_block_factor
    sched = compile_schedule(gemm_spec(kb_cnt, nb_cnt, 1))
    tab = jnp.asarray(sched.table)
    maj, mnr = sched.selector("major"), sched.selector("minor")

    def a_map(t, l, kc, tab, *_):  # column slab of the (M, K) operand
        return (l * n_k_chunks + kc, maj(tab, t))

    def b_map(t, l, kc, tab, *_):
        return (l * n_k_chunks + kc, mnr(tab, t))

    def o_map(t, l, kc, tab, *_):
        return (maj(tab, t), mnr(tab, t))

    def norm_map(t, l, kc, tab, *_):
        return (0, 0)

    inputs = [a, b]
    in_specs = [
        pl.BlockSpec((m_chunk, bm), a_map),
        pl.BlockSpec((m_chunk, bn), b_map),
    ]
    if dual:
        inputs.append(b2)
        in_specs.append(pl.BlockSpec((m_chunk, bn), b_map))

    out_spec = pl.BlockSpec((bm, bn), o_map)
    out_shape = jax.ShapeDtypeStruct((k, n), out_dtype)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if dual:
        scratch.append(pltpu.VMEM((bm, bn), jnp.float32))

    if update_mode:
        tile_spec = pl.BlockSpec((bm, bn), o_map)
        moments = [master, mu, nu]
        if dual:
            moments += [master2, mu2, nu2]
        inputs += moments
        in_specs += [tile_spec] * len(moments)
        f32_shape = jax.ShapeDtypeStruct((k, n), jnp.float32)
        w_shape = jax.ShapeDtypeStruct((k, n), update.param_dtype)
        n_sets = 2 if dual else 1
        out_specs = [tile_spec] * (4 * n_sets) + [
            pl.BlockSpec((n_sets, 1), norm_map)
        ]
        out_shapes = [w_shape, f32_shape, f32_shape, f32_shape] * n_sets + [
            jax.ShapeDtypeStruct((n_sets, 1), jnp.float32)
        ]
        prefetch = (tab, hyper)
        n_prefetch = 2
    else:
        out_specs = [out_spec, out_spec] if dual else out_spec
        out_shapes = [out_shape, out_shape] if dual else out_shape
        prefetch = (tab,)
        n_prefetch = 1
    if abft:
        # trailing launch-resident checksum: sum of the raw accumulator(s)
        # per operand set, pre-update/pre-cast (repro.robust.abft)
        n_sets_chk = 2 if dual else 1
        if not isinstance(out_specs, list):
            out_specs, out_shapes = [out_specs], [out_shapes]
        out_specs = out_specs + [pl.BlockSpec((n_sets_chk, 1), norm_map)]
        out_shapes = out_shapes + [
            jax.ShapeDtypeStruct((n_sets_chk, 1), jnp.float32)
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(kb_cnt * nb_cnt, k_layers, n_k_chunks),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _tn_kernel,
        n_layers=k_layers,
        n_k_chunks=n_k_chunks,
        dual=dual,
        out_dtype=out_dtype,
        update=update,
        abft=abft,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(*prefetch, *inputs)


def _grouped_nt_kernel(
    tab_ref,
    *refs,
    n_k_chunks: int,
    dual: bool,
    out_dtype,
):
    del tab_ref
    it = iter(refs)
    a_ref = next(it)
    b_ref = next(it)
    a2_ref = next(it) if dual else None
    b2_ref = next(it) if dual else None
    o_ref = next(it)
    acc_ref = next(it)

    kc = pl.program_id(1)

    @pl.when(kc == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    nt_dims = (((1,), (1,)), ((), ()))
    acc_ref[...] += lax.dot_general(
        a_ref[...], b_ref[0], nt_dims, preferred_element_type=jnp.float32
    )
    if dual:
        acc_ref[...] += lax.dot_general(
            a2_ref[...], b2_ref[0], nt_dims, preferred_element_type=jnp.float32
        )

    @pl.when(kc == n_k_chunks - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "row_blocks",
        "bm",
        "bn",
        "k_block_factor",
        "interpret",
        "out_dtype",
    ),
)
def sfc_gemm_grouped_nt(
    a: jax.Array,  # (sum_e row_blocks[e]*bm, K) grouped rows (e.g. dC slabs)
    b: jax.Array,  # (E, N, K) per-expert operand, consumed as b[e]ᵀ
    a2: Optional[jax.Array] = None,  # (sum_rows, K) second addend (GLU dA)
    b2: Optional[jax.Array] = None,  # (E, N, K)
    *,
    row_blocks: Tuple[int, ...],
    bm: int = 128,
    bn: int = 128,
    k_block_factor: int = 1,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Grouped NT: out[rows of e] = a[rows of e] @ b[e]ᵀ (+ a2 @ b2[e]ᵀ).

    The dA kernel of the grouped (MoE expert) backward: same per-expert SFC
    task table as the forward grouped kernel, per-expert weights read as
    stored (E, N, K) row slabs — contraction over the shared last dim.
    """
    m_total, k = a.shape
    e_cnt, n, k2 = b.shape
    assert k == k2, (a.shape, b.shape)
    assert len(row_blocks) == e_cnt, (row_blocks, e_cnt)
    dual = a2 is not None
    if dual:
        assert b2 is not None and a2.shape == a.shape and b2.shape == b.shape
    if m_total != sum(row_blocks) * bm:
        raise ValueError(
            f"A rows {m_total} != sum(row_blocks)*bm = {sum(row_blocks)}*{bm}"
        )
    if n % bn:
        raise ValueError(f"N={n} not divisible by bn={bn}")
    if k % k_block_factor:
        raise ValueError(f"K={k} vs k_block_factor={k_block_factor}")
    out_dtype = out_dtype or a.dtype

    nb_cnt = n // bn
    k_chunk = k // k_block_factor
    n_k_chunks = k_block_factor

    sched = compile_schedule(grouped_gemm_spec(tuple(row_blocks), nb_cnt))
    n_tasks = sched.num_tasks
    if n_tasks == 0:
        return jnp.zeros((m_total, n), out_dtype)
    tab = jnp.asarray(sched.table)
    maj, mnr, grp = (
        sched.selector("major"), sched.selector("minor"),
        sched.selector("group"),
    )

    def a_map(t, kc, tab):
        return (maj(tab, t), kc)

    def b_map(t, kc, tab):  # (expert, row-of-bᵀ, k-chunk)
        return (grp(tab, t), mnr(tab, t), kc)

    def o_map(t, kc, tab):
        return (maj(tab, t), mnr(tab, t))

    inputs = [a, b]
    in_specs = [
        pl.BlockSpec((bm, k_chunk), a_map),
        pl.BlockSpec((1, bn, k_chunk), b_map),
    ]
    if dual:
        inputs += [a2, b2]
        in_specs += [
            pl.BlockSpec((bm, k_chunk), a_map),
            pl.BlockSpec((1, bn, k_chunk), b_map),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tasks, n_k_chunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), o_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(
        _grouped_nt_kernel,
        n_k_chunks=n_k_chunks,
        dual=dual,
        out_dtype=out_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_total, n), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(tab, *inputs)


def build_grouped_tn_task_table(
    row_blocks: Tuple[int, ...], kb: int, nb: int
) -> np.ndarray:
    """(5, E*kb*nb) int32 table for the grouped TN kernel.

    Rows = (ik, in, expert, row_off_blocks, rb): every expert owns the same
    ``kb x nb`` weight-grad tile grid, walked in gilbert order, plus the
    block offset/extent of its row slab in the packed activation buffer so
    the kernel can bound the ragged contraction.  Compatibility wrapper over
    the unified schedule compiler (`repro.core.schedule`)."""
    return compile_schedule(
        grouped_tn_spec(tuple(row_blocks), kb, nb)
    ).table


def _grouped_tn_kernel(
    *prefetch_and_refs,
    n_chunks: int,
    dual: bool,
    out_dtype,
    update: Optional[_TnUpdate] = None,
):
    it = iter(prefetch_and_refs)
    tab_ref = next(it)
    hyp_ref = next(it) if update is not None else None
    a_ref = next(it)
    b_ref = next(it)
    b2_ref = next(it) if dual else None
    if update is not None:
        mst_ref = next(it)
        mu_ref = next(it)
        nu_ref = next(it)
        if dual:
            mst2_ref = next(it)
            mu2_ref = next(it)
            nu2_ref = next(it)
        w_o = next(it)
        mst_o = next(it)
        mu_o = next(it)
        nu_o = next(it)
        if dual:
            w2_o = next(it)
            mst2_o = next(it)
            mu2_o = next(it)
            nu2_o = next(it)
        norm_o = next(it)
    else:
        o_ref = next(it)
        o2_ref = next(it) if dual else None
    acc_ref = next(it)
    acc2_ref = next(it) if dual else None

    t, kc = pl.program_id(0), pl.program_id(1)
    rb = tab_ref[4, t]  # this expert's row-slab extent in blocks

    if update is not None:

        @pl.when((t == 0) & (kc == 0))
        def _zero_norm():
            norm_o[...] = jnp.zeros_like(norm_o)

    @pl.when(kc == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if dual:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)

    tn_dims = (((0,), (0,)), ((), ()))

    @pl.when(kc < rb)  # chunks past the expert's rows contribute nothing
    def _accumulate():
        a_pan = a_ref[...]
        acc_ref[...] += lax.dot_general(
            a_pan, b_ref[...], tn_dims, preferred_element_type=jnp.float32
        )
        if dual:
            acc2_ref[...] += lax.dot_general(
                a_pan, b2_ref[...], tn_dims, preferred_element_type=jnp.float32
            )

    @pl.when(kc == n_chunks - 1)
    def _flush():
        if update is None:
            o_ref[0, ...] = acc_ref[...].astype(out_dtype)
            if dual:
                o2_ref[0, ...] = acc2_ref[...].astype(out_dtype)
            return
        # empty experts flush a zero accumulator: AdamW with g == 0 still
        # decays the moments and applies weight decay — exactly the unfused
        # semantics for a zero expert gradient
        im, in_, exp = tab_ref[0, t], tab_ref[1, t], tab_ref[2, t]
        salt = exp * jnp.int32(2) + jnp.int32(0)
        norm_o[0, 0] += _apply_update_flush(
            acc_ref[...], mst_ref, mu_ref, nu_ref,
            w_o, mst_o, mu_o, nu_o,
            hyp_ref, _tile_seed(hyp_ref, im, in_, salt), update,
            out_index=0,
        )
        if dual:
            norm_o[1, 0] += _apply_update_flush(
                acc2_ref[...], mst2_ref, mu2_ref, nu2_ref,
                w2_o, mst2_o, mu2_o, nu2_o,
                hyp_ref,
                _tile_seed(hyp_ref, im, in_, salt + jnp.int32(1)),
                update,
                out_index=0,
            )


@functools.partial(
    jax.jit,
    static_argnames=(
        "row_blocks",
        "row_block",
        "bm",
        "bn",
        "interpret",
        "out_dtype",
        "update_dtype",
        "stochastic_round",
    ),
)
def sfc_gemm_grouped_tn(
    a: jax.Array,  # (sum_e row_blocks[e]*row_block, K) grouped activations
    b: jax.Array,  # (sum_rows, N) grouped dC slabs (same row packing)
    b2: Optional[jax.Array] = None,  # (sum_rows, N) second dC (GLU dg)
    master: Optional[jax.Array] = None,  # (E, K, N) f32 — update mode
    mu: Optional[jax.Array] = None,
    nu: Optional[jax.Array] = None,
    master2: Optional[jax.Array] = None,
    mu2: Optional[jax.Array] = None,
    nu2: Optional[jax.Array] = None,
    hyper: Optional[jax.Array] = None,  # (12,) f32 AdamW scalars
    *,
    row_blocks: Tuple[int, ...],
    row_block: int,  # rows per contraction chunk (the slab padding unit)
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
    out_dtype=None,
    update_dtype=None,
    stochastic_round: bool = False,
):
    """Grouped TN: dW[e] = a[rows of e]ᵀ @ b[rows of e] per expert, one
    launch for the whole (E, K, N) weight-grad stack.

    Every expert shares the same (K/bm) x (N/bn) output grid (one gilbert
    map, replayed per expert); the ragged contraction over each expert's
    row slab is bounded by the prefetched ``rb`` column of the task table —
    chunks beyond an expert's rows are predicated off, so empty experts
    flush exact zeros.  With ``b2`` the activation slab streams once for
    both weight-grad stacks (returns a tuple).

    The ``master``/``mu``/``nu`` (+ ``hyper``) operands switch the flush to
    the grad-and-update mode exactly as in `sfc_gemm_tn`: per-expert AdamW
    on the f32 accumulator, outputs ``(W_new, master', mu', nu', norm)``
    stacks (dual: both sets), the (E, K, N) weight-grad stack never written.
    Empty experts run the g = 0 update (moment decay + weight decay).
    """
    m_total, k = a.shape
    m2, n = b.shape
    assert m_total == m2, (a.shape, b.shape)
    dual = b2 is not None
    if dual:
        assert b2.shape == b.shape, (b2.shape, b.shape)
    e_cnt = len(row_blocks)
    if m_total != sum(row_blocks) * row_block:
        raise ValueError(
            f"rows {m_total} != sum(row_blocks)*row_block = "
            f"{sum(row_blocks)}*{row_block}"
        )
    if k % bm or n % bn:
        raise ValueError(f"(K,N)=({k},{n}) not divisible by (bm,bn)=({bm},{bn})")
    out_dtype = out_dtype or a.dtype

    update_mode = master is not None
    if update_mode:
        assert mu is not None and nu is not None and hyper is not None
        for t_ in (master, mu, nu):
            assert t_.shape == (e_cnt, k, n), (t_.shape, (e_cnt, k, n))
        if dual:
            assert master2 is not None and mu2 is not None and nu2 is not None
        update = _TnUpdate(
            param_dtype=jnp.dtype(update_dtype or out_dtype),
            stochastic_round=stochastic_round,
            hw_rng=not interpret,
        )
    else:
        update = None

    kb_cnt, nb_cnt = k // bm, n // bn
    max_rb = max(row_blocks) if row_blocks else 0
    out_shape = jax.ShapeDtypeStruct((e_cnt, k, n), out_dtype)
    if max_rb == 0 or m_total == 0:
        zero = jnp.zeros(out_shape.shape, out_dtype)
        return (zero, zero) if dual else zero
    total_blocks = m_total // row_block

    sched = compile_schedule(
        grouped_tn_spec(tuple(row_blocks), kb_cnt, nb_cnt)
    )
    tab = jnp.asarray(sched.table)
    maj, mnr, grp, goff, glen = (
        sched.selector("major"), sched.selector("minor"),
        sched.selector("group"), sched.selector("group_off"),
        sched.selector("group_len"),
    )

    def row_idx(t, kc, tab):
        # clamp into the expert's slab (and the buffer) — out-of-extent
        # chunks are predicated off in the kernel, the fetch just needs a
        # legal address
        rb = glen(tab, t)
        kc_c = jnp.minimum(kc, jnp.maximum(rb - 1, 0))
        return jnp.minimum(goff(tab, t) + kc_c, total_blocks - 1)

    def a_map(t, kc, tab, *_):
        return (row_idx(t, kc, tab), maj(tab, t))

    def b_map(t, kc, tab, *_):
        return (row_idx(t, kc, tab), mnr(tab, t))

    def o_map(t, kc, tab, *_):
        return (grp(tab, t), maj(tab, t), mnr(tab, t))

    def norm_map(t, kc, tab, *_):
        return (0, 0)

    inputs = [a, b]
    in_specs = [
        pl.BlockSpec((row_block, bm), a_map),
        pl.BlockSpec((row_block, bn), b_map),
    ]
    if dual:
        inputs.append(b2)
        in_specs.append(pl.BlockSpec((row_block, bn), b_map))

    out_spec = pl.BlockSpec((1, bm, bn), o_map)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if dual:
        scratch.append(pltpu.VMEM((bm, bn), jnp.float32))

    if update_mode:
        tile_spec = pl.BlockSpec((1, bm, bn), o_map)
        moments = [master, mu, nu]
        if dual:
            moments += [master2, mu2, nu2]
        inputs += moments
        in_specs += [tile_spec] * len(moments)
        f32_shape = jax.ShapeDtypeStruct((e_cnt, k, n), jnp.float32)
        w_shape = jax.ShapeDtypeStruct((e_cnt, k, n), update.param_dtype)
        n_sets = 2 if dual else 1
        out_specs = [tile_spec] * (4 * n_sets) + [
            pl.BlockSpec((n_sets, 1), norm_map)
        ]
        out_shapes = [w_shape, f32_shape, f32_shape, f32_shape] * n_sets + [
            jax.ShapeDtypeStruct((n_sets, 1), jnp.float32)
        ]
        prefetch = (tab, hyper)
        n_prefetch = 2
    else:
        out_specs = [out_spec, out_spec] if dual else out_spec
        out_shapes = [out_shape, out_shape] if dual else out_shape
        prefetch = (tab,)
        n_prefetch = 1

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(tab.shape[1], max_rb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _grouped_tn_kernel,
        n_chunks=max_rb,
        dual=dual,
        out_dtype=out_dtype,
        update=update,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(*prefetch, *inputs)


def _add_reduce_kernel(c_ref, o_ref, *, acc_dtype):
    # add_reduce_tpp: accumulate K_layers strided tiles (Listing 1 line 34)
    o_ref[...] = c_ref[...].astype(acc_dtype).sum(axis=0).astype(o_ref.dtype)


def _add_reduce_batched_kernel(c_ref, o_ref, *, acc_dtype):
    # (1, K_layers, bm, bn) -> (1, bm, bn): reduce per batch element, no
    # HBM transpose/reshape of the copies
    o_ref[0, ...] = c_ref[0].astype(acc_dtype).sum(axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def add_reduce_pallas(
    c_copies: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """(K_layers, M, N) -> (M, N) layer reduction (paper lines 26-35), or
    (B, K_layers, M, N) -> (B, M, N) with the batch as an outer grid axis —
    the batched form reads each element's copies in place instead of first
    folding the batch into M via an HBM transpose+reshape copy."""
    if c_copies.ndim == 4:
        bsz, kl, m, n = c_copies.shape
        bm = min(bm, m)
        bn = min(bn, n)
        if m % bm or n % bn:
            raise ValueError(
                f"(M,N)=({m},{n}) not divisible by (bm,bn)=({bm},{bn})"
            )
        kernel = functools.partial(
            _add_reduce_batched_kernel, acc_dtype=jnp.float32
        )
        return pl.pallas_call(
            kernel,
            grid=(bsz, m // bm, n // bn),
            in_specs=[
                pl.BlockSpec((1, kl, bm, bn), lambda b, i, j: (b, 0, i, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j)),
            out_shape=jax.ShapeDtypeStruct((bsz, m, n), c_copies.dtype),
            interpret=interpret,
        )(c_copies)
    kl, m, n = c_copies.shape
    bm = min(bm, m)
    bn = min(bn, n)
    if m % bm or n % bn:
        raise ValueError(f"(M,N)=({m},{n}) not divisible by (bm,bn)=({bm},{bn})")
    kernel = functools.partial(_add_reduce_kernel, acc_dtype=jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((kl, bm, bn), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), c_copies.dtype),
        interpret=interpret,
    )(c_copies)
