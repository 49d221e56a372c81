"""Jit'd public wrappers around the Pallas SFC-CA GEMM kernels.

`sfc_matmul` is the user-facing entry point: it accepts arbitrary-rank
operands — ``(M, K) @ (K, N)``, ``(..., M, K) @ (K, N)`` (shared weights)
and ``(..., M, K) @ (..., K, N)`` — pads to block multiples, fills knobs
from the persistent empirical tune cache (`repro.tune`) when a measured
winner exists for the shape bucket and from the paper's analytical model
otherwise, and launches **one fused-epilogue SFC kernel**: the 2.5D layer
reduction happens inside the kernel's f32 accumulator (layer-inner grid)
and the optional epilogue — ``bias``, ``activation`` (silu/gelu/relu),
``out_scale``, ``residual`` — is applied in the flush step, so C touches
HBM exactly once.  `sfc_glu_matmul` is the dual-B gated form (one A
traversal feeds gate and value accumulators; flush writes
``act(A@Wg) * (A@Wv)``).

The replicated `(K_layers, M, N)` + `add_reduce_pallas` two-launch pipeline
survives as a fallback (``fuse=False``, or automatically when the fused
VMEM footprint exceeds the budget) and for the distributed `ca_matmul`
psum path; the fallback applies the same epilogue with jnp ops after the
reduction.

`sfc_grouped_matmul` / `sfc_grouped_glu_matmul` are the ragged companions
for MoE expert GEMMs: rows grouped by expert against per-expert weight
slabs, one SFC map per expert tile grid, same fused epilogue.

**Training**: every entry point carries a `jax.custom_vjp` whose backward
pass is itself SFC GEMMs — `sfc_matmul_nt` (dA = dC·Bᵀ) and
`sfc_matmul_tn` (dB = Aᵀ·dC), plus their grouped companions — so
`jax.value_and_grad` under `gemm_backend("sfc_pallas")` never falls back
to `dot_general` in either direction.  Backward shapes resolve knobs from
their own ``op="nt"`` / ``op="tn"`` tune-cache namespaces.

On non-TPU backends everything transparently switches to interpret mode so
the same call sites work in tests/CPU containers.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.namespaces import (
    NS_GEMM,
    NS_GLU,
    NS_GROUPED,
    NS_GROUPED_GLU,
    NS_GROUPED_NT,
    NS_GROUPED_TN,
    NS_GROUPED_TN_UPDATE,
    NS_NT,
    NS_NT_DUAL,
    NS_TN,
    NS_TN_DUAL,
    NS_TN_UPDATE,
    NS_TN_UPDATE_DUAL,
    RUNG_SFC_PALLAS,
    RUNG_XLA,
)
from repro.core.perf_model import TPU_V5E, choose_knobs_analytical
from repro.kernels.sfc_gemm import (
    VMEM_LIMIT_BYTES,
    activation_fn,
    add_reduce_pallas,
    sfc_gemm_batched,
    sfc_gemm_batched_fused,
    sfc_gemm_fused,
    sfc_gemm_grouped,
    sfc_gemm_grouped_nt,
    sfc_gemm_grouped_tn,
    sfc_gemm_nt,
    sfc_gemm_pallas,
    sfc_gemm_tn,
)
from repro.robust import abft as _abft

__all__ = [
    "sfc_matmul",
    "sfc_glu_matmul",
    "sfc_grouped_matmul",
    "sfc_grouped_glu_matmul",
    "sfc_matmul_nt",
    "sfc_matmul_tn",
    "sfc_matmul_tn_update",
    "sfc_grouped_matmul_nt",
    "sfc_grouped_matmul_tn",
    "sfc_grouped_matmul_tn_update",
    "fused_update_matmul",
    "fused_update_glu_matmul",
    "fused_update_grouped_matmul",
    "fused_update_grouped_glu_matmul",
    "default_interpret",
    "pick_blocks",
    "resolve_knobs",
    "reference_knobs",
    "fused_path_fits_vmem",
    "chunk_gemm_plan",
]

# dual-B namespaces: two weight panels stream per grid step
_DUAL_B_NAMESPACES = (NS_GLU, NS_NT_DUAL, NS_TN_DUAL, NS_TN_UPDATE_DUAL)

# Mosaic's minor (lane) tile: a block's last dimension must be a multiple of
# it or span the whole array dimension.
_LANE = 128


def default_interpret() -> bool:
    """Pallas->Mosaic requires a real TPU; everywhere else, interpret."""
    return jax.default_backend() != "tpu"


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pad_contract(k: int, split: int) -> int:
    """Contraction length padded to ``split`` equal chunks.  A chunk at
    least one lane wide is kept a lane multiple: the chunk is the minor
    dimension of the streamed A panel, and Mosaic refuses other widths."""
    if split > 1 and k >= split * _LANE:
        return _round_up(k, split * _LANE)
    return _round_up(k, split)


def pick_blocks(m: int, n: int, k: int) -> Tuple[int, int, int]:
    """MXU-aligned (bm, bn, bk): multiples of 128 when the problem allows,
    small powers of two otherwise (tests use tiny shapes)."""

    def pick(dim: int) -> int:
        for cand in (256, 128, 64, 32, 16, 8):
            if dim % cand == 0:
                return cand
        return dim

    return pick(m), pick(n), pick(k)


def _resolve_knobs(
    m: int,
    n: int,
    k: int,
    dtype,
    bm: Optional[int],
    bn: Optional[int],
    k_layers: Optional[int],
    k_block_factor: Optional[int],
    op: str = NS_GEMM,
) -> Tuple[int, int, int, int]:
    """Fill unspecified knobs: measured tune-cache winner first (paper §III-C
    method (1)), analytical model + MXU alignment rules as the fallback.
    ``op`` selects the tune-cache namespace ("gemm" or the dual-B "glu").

    A ``k_block_factor`` the caller left open is then raised until one
    fused grid step fits `VMEM_LIMIT_BYTES` (`_bump_kbf_to_fit`), so the
    plan Mosaic compiles never exceeds its scoped limit."""
    auto_kbf = k_block_factor is None
    if None in (bm, bn, k_layers, k_block_factor):
        cached = None
        try:
            from repro.tune import lookup_knobs

            cached = lookup_knobs(m, n, k, dtype, op=op)
        except Exception:
            cached = None
        if cached is not None:
            bm = bm or cached.bm
            bn = bn or cached.bn
            k_layers = k_layers or cached.k_layers
            k_block_factor = k_block_factor or cached.k_block_factor
    if bm is None or bn is None:
        pbm, pbn, _ = pick_blocks(m, n, k)
        bm = bm or pbm
        bn = bn or pbn
    if k_layers is None or k_block_factor is None:
        # worker count 1: the kernel runs on one TensorCore; K_layers here
        # trades VMEM-residency of panels against the copy reduction.
        c, kbf = choose_knobs_analytical(
            max(m, bm), max(n, bn), max(k, 1), 1, bm=bm, bn=bn, hw=TPU_V5E
        )
        k_layers = k_layers or c
        k_block_factor = k_block_factor or kbf
    if auto_kbf:
        # residual tile counted: the caller's epilogue is not known here
        k_block_factor = _bump_kbf_to_fit(
            bm, bn, k, k_layers, k_block_factor, dtype, dtype,
            dual=op in _DUAL_B_NAMESPACES, has_residual=True,
        )
    return bm, bn, k_layers, k_block_factor


def resolve_knobs(
    m: int,
    n: int,
    k: int,
    dtype,
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    k_layers: Optional[int] = None,
    k_block_factor: Optional[int] = None,
    op: str = NS_GEMM,
) -> Tuple[int, int, int, int]:
    """Public knob resolution: tune cache -> analytical model -> alignment.

    The single source of truth every backend path (Pallas kernels, the
    Listing-1 reference, the tuner's candidate seeding) consults, so a
    measured winner applies everywhere."""
    return _resolve_knobs(m, n, k, dtype, bm, bn, k_layers, k_block_factor, op)


def chunk_gemm_plan(m: int, n: int, k: int, dtype):
    """Tune namespace + knobs for one batched intra-chunk GEMM (the
    chunked-recurrence einsums routed through `core.gemm_backend.chunk_einsum`).

    The schedule compiler is the identity: knobs resolved from the base
    "gemm" namespace fix the padded tile grid, and the compiled
    `ScheduleSpec` key of that grid qualifies the namespace
    (``"gemm@<key>"`` via `namespaces.schedule_namespace`) — so a chunked
    xLSTM qk block and a plain projection with the same padded shape tune
    into *distinct* buckets, and the fallback ladder quarantines them
    per-schedule.  Knobs then re-resolve under the qualified namespace so
    a measured winner in the schedule's own bucket overrides the base
    choice (the spec key itself stays canonical: it names the tile space,
    not the winning knobs).

    Returns ``(namespace, knobs)`` with ``knobs`` the explicit
    bm/bn/k_layers/k_block_factor kwargs for `sfc_matmul`.
    """
    from repro.core.namespaces import schedule_namespace
    from repro.core.schedule import compile_schedule, gemm_spec

    bm, bn, kl, kbf = _resolve_knobs(
        m, n, k, dtype, None, None, None, None, NS_GEMM
    )
    mb_cnt = _round_up(m, bm) // bm
    nb_cnt = _round_up(n, bn) // bn
    sched = compile_schedule(gemm_spec(mb_cnt, nb_cnt, kl))
    namespace = schedule_namespace(NS_GEMM, sched.key)
    bm, bn, kl, kbf = _resolve_knobs(
        m, n, k, dtype, None, None, None, None, namespace
    )
    return namespace, dict(
        bm=bm, bn=bn, k_layers=kl, k_block_factor=kbf
    )


def _divisor_block(dim: int, cap: int) -> int:
    """Largest aligned block <= cap that divides dim, else the dim itself —
    the reference implementation does not pad, and one whole-extent block
    beats a degenerate unit block."""
    for cand in (256, 128, 64, 32, 16, 8):
        if cand <= cap and dim % cand == 0:
            return cand
    return dim


def reference_knobs(
    m: int, n: int, k: int, dtype, op: str = NS_GEMM
) -> Tuple[int, int, int, int, int]:
    """(bm, bn, bk, k_layers, k_block_factor) for `sfc_ca_gemm_reference`.

    Resolves through the same tune-cache/analytical pipeline as the Pallas
    path, then clips each block to a divisor of its extent (the reference
    implementation does not pad) and drops the K knobs to (1, 1) when K's
    block count cannot accommodate them."""
    bm, bn, k_layers, k_block_factor = _resolve_knobs(
        m, n, k, dtype, None, None, None, None, op
    )
    bm = _divisor_block(m, bm)
    bn = _divisor_block(n, bn)
    _, _, bk = pick_blocks(m, n, k)
    kb_cnt = max(k // bk, 1)
    if kb_cnt % (k_layers * k_block_factor):
        k_layers = k_block_factor = 1
    return bm, bn, bk, k_layers, k_block_factor


def fused_path_fits_vmem(
    bm: int,
    bn: int,
    k_chunk: int,
    dtype_bytes: int,
    out_bytes: int,
    *,
    glu: bool = False,
    has_residual: bool = False,
    opt_tile_sets: int = 0,
) -> bool:
    """Does one fused grid step's working set fit `VMEM_LIMIT_BYTES`?

    Counts what Mosaic allocates against its scoped limit: every blocked
    operand double-buffered — the A and B (x2 for GLU) panels, the output
    tile, any resident epilogue operands — plus one f32 accumulator scratch
    per B.  Rows are padded to the 16-row bf16 sublane tile.
    ``opt_tile_sets`` counts grad-and-update flush sets: each adds 3
    resident f32 input tiles (master/mu/nu) and 4 output tiles (W_new +
    three f32 states) — this is why the update flush owns its own
    ``op="tn_update"`` tune namespace."""
    n_b = 2 if glu else 1
    rows = _round_up(bm, 16)
    blocks = (rows * k_chunk + n_b * k_chunk * bn) * dtype_bytes
    blocks += rows * bn * out_bytes
    if has_residual:
        blocks += rows * bn * dtype_bytes
    blocks += 2 * 16 * bn * dtype_bytes  # bias / gate-bias rows
    if opt_tile_sets:
        blocks += opt_tile_sets * rows * bn * (3 * 4 + 3 * 4 + out_bytes)
    accs = rows * bn * 4 * n_b
    return 2 * blocks + accs <= VMEM_LIMIT_BYTES


def ensure_fused_fits(
    m: int,
    n: int,
    k: int,
    dtype,
    out_dtype=None,
    *,
    glu: bool = False,
    has_residual: bool = False,
) -> None:
    """Raise `robust.VmemBudgetError` when the fused plan overflows VMEM.

    The planning check the *fused rung* of the fallback ladder runs
    before launching: on CPU interpret mode nothing would physically
    overflow, so raising on the plan is what keeps rung selection
    platform-faithful — the ladder (not a local shrink loop) degrades
    to the replicated fuse=False rung.  Knobs resolve through the same
    `_resolve_knobs` pipeline the launch itself uses."""
    from repro.robust import VmemBudgetError

    op = NS_GLU if glu else NS_GEMM
    bm, bn, k_layers, k_block_factor = _resolve_knobs(
        m, n, k, jnp.dtype(dtype), None, None, None, None, op
    )
    kp = _pad_contract(k, k_layers * k_block_factor)
    out_dtype = out_dtype or dtype
    if not fused_path_fits_vmem(
        bm,
        bn,
        kp // (k_layers * k_block_factor),
        jnp.dtype(dtype).itemsize,
        jnp.dtype(out_dtype).itemsize,
        glu=glu,
        has_residual=has_residual,
    ):
        raise VmemBudgetError(
            f"fused {op} plan ({m}x{n}x{k}, bm={bm}, bn={bn}, "
            f"k_layers={k_layers}, kbf={k_block_factor}) exceeds the "
            f"{VMEM_LIMIT_BYTES >> 20} MiB VMEM budget"
        )


def _epilogue_jnp(
    y: jax.Array,
    *,
    gate: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    gate_bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    out_scale: Optional[float] = None,
    residual: Optional[jax.Array] = None,
    out_dtype=None,
) -> jax.Array:
    """The fallback path's epilogue: same math as the kernel flush (f32)."""
    acc = y.astype(jnp.float32)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if gate is not None:
        g = gate.astype(jnp.float32)
        if gate_bias is not None:
            g = g + gate_bias.astype(jnp.float32)
        acc = activation_fn(activation)(g) * acc
    elif activation is not None:
        acc = activation_fn(activation)(acc)
    if out_scale is not None:
        acc = acc * out_scale
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    return acc.astype(out_dtype or y.dtype)


def _matmul_impl(
    a: jax.Array,
    b: jax.Array,
    b_gate: Optional[jax.Array],
    *,
    bias: Optional[jax.Array],
    gate_bias: Optional[jax.Array],
    residual: Optional[jax.Array],
    activation: Optional[str],
    out_scale: Optional[float],
    bm: Optional[int],
    bn: Optional[int],
    k_layers: Optional[int],
    k_block_factor: Optional[int],
    interpret: Optional[bool],
    out_dtype,
    fuse: Optional[bool],
    preact: bool = False,
    abft: Optional[str] = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    if a.ndim < 2 or b.ndim < 2:
        raise ValueError(f"sfc_matmul needs matrices, got {a.shape} @ {b.shape}")
    if preact:
        # training-forward GLU mode: return both biased pre-activations
        # (value, gate) instead of the activated epilogue
        assert b_gate is not None and activation is None and residual is None
        assert out_scale is None

    glu = b_gate is not None
    lead = a.shape[:-2]
    m, k = a.shape[-2:]
    k2, n = b.shape[-2:]
    assert k == k2, (a.shape, b.shape)
    b_batched = b.ndim > 2
    if b_batched and b.shape[:-2] != lead:
        raise ValueError(f"batch dims mismatch: {a.shape} @ {b.shape}")
    if glu:
        if b_gate.ndim != 2 or b_gate.shape != b.shape[-2:]:
            raise ValueError(
                f"GLU gate weights must be (K, N)={b.shape[-2:]}, "
                f"got {b_gate.shape}"
            )
        if b_batched:
            raise ValueError("GLU form requires shared 2-D value weights")
    for name, vec in (("bias", bias), ("gate_bias", gate_bias)):
        if vec is not None and vec.shape not in ((n,), (1, n)):
            raise ValueError(f"{name} must be (N,) or (1, N) with N={n}, got {vec.shape}")
    if residual is not None and residual.shape != (*lead, m, n):
        raise ValueError(
            f"residual shape {residual.shape} != output {(*lead, m, n)}"
        )
    out_dtype = out_dtype or a.dtype

    op = NS_GLU if glu else NS_GEMM
    abft_mode = abft if abft is not None else _abft.current_mode(op)
    abft_on = abft_mode != "off"
    bm, bn, k_layers, k_block_factor = _resolve_knobs(
        m, n, k, a.dtype, bm, bn, k_layers, k_block_factor, op
    )

    def _verify(out, chk, cast_dtype=None):
        ref, mag = _abft.gemm_checksum_ref(a, b, b_gate)
        return _abft.verify(
            op, out, chk, ref, mag,
            contract_dim=k, mode=abft_mode, cast_dtype=cast_dtype,
        )

    mp = _round_up(m, bm)
    np_ = _round_up(n, bn)
    kp = _pad_contract(k, k_layers * k_block_factor)

    if fuse is None:
        fuse = fused_path_fits_vmem(
            bm,
            bn,
            kp // (k_layers * k_block_factor),
            jnp.dtype(a.dtype).itemsize,
            jnp.dtype(out_dtype).itemsize,
            glu=glu,
            has_residual=residual is not None,
        )
    if not fuse and glu:
        # unfused GLU: two independent products + jnp epilogue (each inner
        # product carries its own ABFT check under the gemm namespace)
        val = _matmul_impl(
            a, b, None,
            bias=None, gate_bias=None, residual=None,
            activation=None, out_scale=None,
            bm=bm, bn=bn, k_layers=k_layers, k_block_factor=k_block_factor,
            interpret=interpret, out_dtype=jnp.float32, fuse=False,
            abft=abft_mode,
        )
        gate = _matmul_impl(
            a, b_gate, None,
            bias=None, gate_bias=None, residual=None,
            activation=None, out_scale=None,
            bm=bm, bn=bn, k_layers=k_layers, k_block_factor=k_block_factor,
            interpret=interpret, out_dtype=jnp.float32, fuse=False,
            abft=abft_mode,
        )
        if preact:
            if bias is not None:
                val = val + bias.reshape(1, n).astype(jnp.float32)
            if gate_bias is not None:
                gate = gate + gate_bias.reshape(1, n).astype(jnp.float32)
            return val.astype(out_dtype), gate.astype(out_dtype)
        return _epilogue_jnp(
            val, gate=gate, bias=bias, gate_bias=gate_bias,
            activation=activation, out_scale=out_scale, residual=residual,
            out_dtype=out_dtype,
        )

    # pad operands to block multiples (curve still covers the padded grid;
    # padding contributes zeros to the contraction and is sliced back off)
    bias_p = gate_bias_p = None
    if fuse:
        if bias is not None:
            bias_p = jnp.pad(bias.reshape(1, n), ((0, 0), (0, np_ - n)))
        if gate_bias is not None:
            gate_bias_p = jnp.pad(
                gate_bias.reshape(1, n), ((0, 0), (0, np_ - n))
            )
    b_gate_p = None
    if glu and (kp != k or np_ != n):
        b_gate_p = jnp.pad(b_gate, ((0, kp - k), (0, np_ - n)))
    elif glu:
        b_gate_p = b_gate

    if not lead:
        a_p = jnp.pad(a, ((0, mp - m), (0, kp - k))) if (mp != m or kp != k) else a
        b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n))) if (kp != k or np_ != n) else b
        if fuse:
            res_p = None
            if residual is not None:
                res_p = jnp.pad(residual, ((0, mp - m), (0, np_ - n)))
            out = sfc_gemm_fused(
                a_p, b_p, b_gate_p, bias_p, gate_bias_p, res_p,
                activation=activation, out_scale=out_scale,
                bm=bm, bn=bn,
                k_layers=k_layers, k_block_factor=k_block_factor,
                interpret=interpret, out_dtype=out_dtype,
                preact_out=preact, abft=abft_on,
            )
            chk = None
            if abft_on:
                *rest, chk = out
                c_full = tuple(rest) if preact else rest[0]
            else:
                c_full = out
            if preact:
                h_full, g_full = c_full
                res = (h_full[:m, :n], g_full[:m, :n])
            else:
                res = c_full[:m, :n]
            return _verify(res, chk) if abft_on else res
        copies = sfc_gemm_pallas(
            a_p, b_p,
            bm=bm, bn=bn,
            k_layers=k_layers, k_block_factor=k_block_factor,
            interpret=interpret, out_dtype=out_dtype,
        )
        if k_layers > 1:
            c_full = add_reduce_pallas(copies, bm=bm, bn=bn, interpret=interpret)
        else:
            c_full = copies[0]
        res = _epilogue_jnp(
            c_full[:m, :n], bias=bias, activation=activation,
            out_scale=out_scale, residual=residual, out_dtype=out_dtype,
        )
        if abft_on:
            # op-level check: the replicated output is the raw (cast)
            # accumulator, pre-epilogue — its sum is the checksum
            chk = jnp.sum(c_full, dtype=jnp.float32)
            res = _verify(res, chk, cast_dtype=out_dtype)
        return res

    # batched path: fold leading dims into one batch axis for the kernel grid
    bsz = 1
    for d in lead:
        bsz *= d
    a3 = a.reshape(bsz, m, k)
    if mp != m or kp != k:
        a3 = jnp.pad(a3, ((0, 0), (0, mp - m), (0, kp - k)))
    if b_batched:
        b3 = b.reshape(bsz, k, n)
        if kp != k or np_ != n:
            b3 = jnp.pad(b3, ((0, 0), (0, kp - k), (0, np_ - n)))
    else:
        b3 = jnp.pad(b, ((0, kp - k), (0, np_ - n))) if (kp != k or np_ != n) else b

    if fuse:
        res_p = None
        if residual is not None:
            res_p = jnp.pad(
                residual.reshape(bsz, m, n),
                ((0, 0), (0, mp - m), (0, np_ - n)),
            )
        out = sfc_gemm_batched_fused(
            a3, b3, b_gate_p, bias_p, gate_bias_p, res_p,
            activation=activation, out_scale=out_scale,
            bm=bm, bn=bn,
            k_layers=k_layers, k_block_factor=k_block_factor,
            interpret=interpret, out_dtype=out_dtype,
            preact_out=preact, abft=abft_on,
        )  # (B, Mp, Np)
        chk = None
        if abft_on:
            *rest, chk = out
            c_full = tuple(rest) if preact else rest[0]
        else:
            c_full = out
        if preact:
            h_full, g_full = c_full
            res = (
                h_full[:, :m, :n].reshape(*lead, m, n),
                g_full[:, :m, :n].reshape(*lead, m, n),
            )
        else:
            res = c_full[:, :m, :n].reshape(*lead, m, n)
        return _verify(res, chk) if abft_on else res

    copies = sfc_gemm_batched(
        a3, b3,
        bm=bm, bn=bn,
        k_layers=k_layers, k_block_factor=k_block_factor,
        interpret=interpret, out_dtype=out_dtype,
    )  # (B, K_layers, Mp, Np)
    if k_layers > 1:
        # reduce per batch element in place — no transpose+reshape HBM copy
        c_full = add_reduce_pallas(copies, bm=bm, bn=bn, interpret=interpret)
    else:
        c_full = copies[:, 0]
    out = c_full[:, :m, :n].reshape(*lead, m, n)
    res = _epilogue_jnp(
        out, bias=bias, activation=activation,
        out_scale=out_scale, residual=residual, out_dtype=out_dtype,
    )
    if abft_on:
        chk = jnp.sum(c_full, dtype=jnp.float32)
        res = _verify(res, chk, cast_dtype=out_dtype)
    return res


# ---------------------------------------------------------------------------
# backward (NT / TN) entry points
# ---------------------------------------------------------------------------


def _bump_kbf_to_fit(
    bm: int,
    bn: int,
    contract: int,
    k_layers: int,
    kbf: int,
    dtype,
    out_dtype,
    *,
    dual: bool,
    has_residual: bool = False,
    opt_tile_sets: int = 0,
) -> int:
    """Chunk the contraction harder until one grid step's working set fits
    `VMEM_LIMIT_BYTES`: the smallest power-of-two multiple of ``kbf`` that
    fits, chunks padded as the launch pads them (`_pad_contract`)."""
    dtype_bytes = jnp.dtype(dtype).itemsize
    out_bytes = jnp.dtype(out_dtype).itemsize
    while kbf < max(contract, 1) and not fused_path_fits_vmem(
        bm, bn, _pad_contract(contract, k_layers * kbf) // (k_layers * kbf),
        dtype_bytes, out_bytes, glu=dual, has_residual=has_residual,
        opt_tile_sets=opt_tile_sets,
    ):
        kbf *= 2
    return kbf


def sfc_matmul_nt(
    a: jax.Array,  # (..., M, K)
    b: jax.Array,  # (N, K) — consumed as bᵀ without an HBM transpose
    a2: Optional[jax.Array] = None,  # (..., M, K) second addend
    b2: Optional[jax.Array] = None,  # (N, K)
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    k_layers: Optional[int] = None,
    k_block_factor: Optional[int] = None,
    interpret: Optional[bool] = None,
    out_dtype=None,
    abft: Optional[str] = None,
) -> jax.Array:
    """C = A @ Bᵀ (+ A2 @ B2ᵀ) via the SFC NT kernel — the dA backward GEMM
    (``dA = dC @ Wᵀ``; the dual form is the GLU ``dg·Wgᵀ + dh·Wvᵀ`` in one
    traversal).  Leading batch dims of ``a`` fold into M (the (N, K) operand
    is shared), and arbitrary shapes are zero-padded.

    Knobs left as None resolve through the ``op="nt"`` tune-cache namespace
    (``"nt_dual"`` for the dual form — two extra streamed panels change the
    knob landscape, mirroring the forward gemm/glu split): backward shapes
    differ from forward and deserve their own winners.
    """
    if interpret is None:
        interpret = default_interpret()
    lead = a.shape[:-2]
    a2d = a.reshape(-1, a.shape[-1])
    a22d = a2.reshape(-1, a2.shape[-1]) if a2 is not None else None
    m, k = a2d.shape
    n, k2 = b.shape
    assert k == k2, (a.shape, b.shape)
    dual = a2 is not None
    out_dtype = out_dtype or a.dtype

    bm, bn, k_layers, k_block_factor = _resolve_knobs(
        m, n, k, a.dtype, bm, bn, k_layers, k_block_factor,
        NS_NT_DUAL if dual else NS_NT,
    )

    mp = _round_up(m, bm)
    np_ = _round_up(n, bn)
    kp = _pad_contract(k, k_layers * k_block_factor)

    def pad2(x, rows, cols):
        r, c = x.shape
        if r != rows or c != cols:
            return jnp.pad(x, ((0, rows - r), (0, cols - c)))
        return x

    out = sfc_gemm_nt(
        pad2(a2d, mp, kp),
        pad2(b, np_, kp),
        pad2(a22d, mp, kp) if dual else None,
        pad2(b2, np_, kp) if dual else None,
        bm=bm, bn=bn,
        k_layers=k_layers, k_block_factor=k_block_factor,
        interpret=interpret, out_dtype=out_dtype,
    )
    ns = NS_NT_DUAL if dual else NS_NT
    mode = abft if abft is not None else _abft.current_mode(ns)
    res = out[:m, :n].reshape(*lead, a.shape[-2], n)
    if mode != "off":
        # op-level check: the NT output *is* the raw accumulator cast to
        # out_dtype (no epilogue), so its sum is the checksum
        chk = jnp.sum(out, dtype=jnp.float32)
        ref, mag = _abft.nt_checksum_ref(a2d, b)
        if dual:
            r2, m2_ = _abft.nt_checksum_ref(a22d, b2)
            ref, mag = ref + r2, mag + m2_
        res = _abft.verify(
            ns, res, chk, ref, mag,
            contract_dim=k, mode=mode, cast_dtype=out_dtype,
        )
    return res


def sfc_matmul_tn(
    a: jax.Array,  # (..., M, K) — consumed as aᵀ without an HBM transpose
    b: jax.Array,  # (..., M, N)
    b2: Optional[jax.Array] = None,  # (..., M, N) second operand
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    k_layers: Optional[int] = None,
    k_block_factor: Optional[int] = None,
    interpret: Optional[bool] = None,
    out_dtype=None,
    abft: Optional[str] = None,
):
    """C = Aᵀ @ B (and Aᵀ @ B2) via the SFC TN kernel — the dW backward GEMM
    (``dW = Aᵀ @ dC``); with ``b2`` one activation traversal flushes both
    weight grads (the GLU dWv/dWg pair).  Leading batch dims fold into the
    contraction (the weight grad sums over them); arbitrary shapes are
    zero-padded.  Knobs resolve through the ``op="tn"`` namespace
    (``"tn_dual"`` for the dual form).
    """
    if interpret is None:
        interpret = default_interpret()
    a2d = a.reshape(-1, a.shape[-1])
    b2d = b.reshape(-1, b.shape[-1])
    b22d = b2.reshape(-1, b2.shape[-1]) if b2 is not None else None
    m, k = a2d.shape
    m2, n = b2d.shape
    assert m == m2, (a.shape, b.shape)
    dual = b2 is not None
    out_dtype = out_dtype or a.dtype

    # the output is (K, N); the contraction runs over M
    bm, bn, k_layers, k_block_factor = _resolve_knobs(
        k, n, m, a.dtype, bm, bn, k_layers, k_block_factor,
        NS_TN_DUAL if dual else NS_TN,
    )

    kp = _round_up(k, bm)
    np_ = _round_up(n, bn)
    mp = _pad_contract(m, k_layers * k_block_factor)

    def pad2(x, rows, cols):
        r, c = x.shape
        if r != rows or c != cols:
            return jnp.pad(x, ((0, rows - r), (0, cols - c)))
        return x

    ns = NS_TN_DUAL if dual else NS_TN
    mode = abft if abft is not None else _abft.current_mode(ns)
    out = sfc_gemm_tn(
        pad2(a2d, mp, kp),
        pad2(b2d, mp, np_),
        pad2(b22d, mp, np_) if dual else None,
        bm=bm, bn=bn,
        k_layers=k_layers, k_block_factor=k_block_factor,
        interpret=interpret, out_dtype=out_dtype,
        abft=mode != "off",
    )
    if mode != "off":
        *outs, chk = out
        res = (outs[0][:k, :n], outs[1][:k, :n]) if dual else outs[0][:k, :n]
        ref, mag = _abft.tn_checksum_ref(a2d, b2d)
        res = _abft.verify(
            ns, res, chk[0, 0], ref, mag, contract_dim=m, mode=mode
        )
        if dual:
            r2, m2_ = _abft.tn_checksum_ref(a2d, b22d)
            res = _abft.verify(
                ns, res, chk[1, 0], r2, m2_, contract_dim=m, mode=mode
            )
        return res
    if dual:
        return out[0][:k, :n], out[1][:k, :n]
    return out[:k, :n]


# ---------------------------------------------------------------------------
# grad-and-update (fused optimizer) entry points
# ---------------------------------------------------------------------------


def _pad_state(x: jax.Array, rows: int, cols: int) -> jax.Array:
    """Zero-pad the trailing (K, N) dims of a weight/moment tensor.  Zero
    padding is closed under the update: g = 0 there, so every padded state
    element maps 0 -> 0 and the slice-back is exact."""
    pad = [(0, 0)] * (x.ndim - 2) + [
        (0, rows - x.shape[-2]),
        (0, cols - x.shape[-1]),
    ]
    if any(p != (0, 0) for p in pad):
        return jnp.pad(x, pad)
    return x


def _jnp_update(dw, master, mu, nu, hyper, *, param_dtype, stochastic_round):
    """Host-side (non-Pallas) AdamW step from the packed hyper vector — the
    empty-input fallback for the grouped update and the semantics oracle
    pieces share this."""
    from repro.kernels.sfc_gemm import stochastic_round_to, tile_random_bits
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
        adamw_leaf_update,
        seed_from_lane,
    )

    g0 = dw.astype(jnp.float32)
    sq = jnp.sum(g0 * g0)
    # the one shared AdamW leaf program, scalars from the hyper lanes
    mu_n, nu_n, mst_n = adamw_leaf_update(
        g0, mu, nu, master,
        lr=hyper[HYP_LR], b1=hyper[HYP_B1], b2=hyper[HYP_B2],
        eps=hyper[HYP_EPS], weight_decay=hyper[HYP_WD],
        b1c=hyper[HYP_B1C], b2c=hyper[HYP_B2C], scale=hyper[HYP_SCALE],
    )
    if stochastic_round and jnp.dtype(param_dtype) == jnp.dtype(jnp.bfloat16):
        flat = mst_n.reshape(-1, mst_n.shape[-1])
        seed = seed_from_lane(hyper[HYP_SEED]) ^ (
            seed_from_lane(hyper[HYP_SALT]) * jnp.int32(0x85EB)
        )
        bits = tile_random_bits(flat.shape, seed, hw_rng=False)
        w_sr = stochastic_round_to(flat, bits, param_dtype).reshape(mst_n.shape)
        # scale==0 skip sentinel: bypass the dither and write the
        # deterministic cast of the (unchanged) master — mirrors the
        # kernel flush's skip path
        w_n = jnp.where(
            hyper[HYP_SCALE] == 0.0, mst_n.astype(param_dtype), w_sr
        )
    else:
        w_n = mst_n.astype(param_dtype)
    return w_n, mst_n, mu_n, nu_n, sq


def sfc_matmul_tn_update(
    a: jax.Array,  # (..., M, K) forward activations (leading dims fold)
    dy: jax.Array,  # (..., M, N) output cotangent
    master: jax.Array,  # (K, N) f32 master weights
    mu: jax.Array,  # (K, N) f32
    nu: jax.Array,  # (K, N) f32
    hyper: jax.Array,  # (12,) f32 `optim.adamw.pack_adamw_hyper` vector
    dy2: Optional[jax.Array] = None,  # (..., M, N) second cotangent (GLU)
    master2: Optional[jax.Array] = None,
    mu2: Optional[jax.Array] = None,
    nu2: Optional[jax.Array] = None,
    *,
    param_dtype=None,
    stochastic_round: bool = False,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    k_layers: Optional[int] = None,
    k_block_factor: Optional[int] = None,
    interpret: Optional[bool] = None,
    abft: Optional[str] = None,
):
    """Fused dW-and-AdamW: one TN launch computes ``dW = Aᵀ @ dY`` in the
    f32 accumulator and applies the update in the flush — returns
    ``(W_new, master', mu', nu', sum(dW^2))`` (dual: one tuple per weight
    set plus a pair of norms).  The raw gradient never touches HBM.

    Knobs resolve through the ``op="tn_update"`` namespace (dual:
    ``"tn_update_dual"``) — the flush's extra resident state tiles change
    the VMEM footprint, so TN winners do not transfer.
    """
    if interpret is None:
        interpret = default_interpret()
    a2d = a.reshape(-1, a.shape[-1])
    b2d = dy.reshape(-1, dy.shape[-1])
    b22d = dy2.reshape(-1, dy2.shape[-1]) if dy2 is not None else None
    m, k = a2d.shape
    m2, n = b2d.shape
    assert m == m2, (a.shape, dy.shape)
    assert master.shape == (k, n), (master.shape, (k, n))
    dual = dy2 is not None
    param_dtype = jnp.dtype(param_dtype or a.dtype)

    auto_kbf = k_block_factor is None
    opt_sets = 2 if dual else 1
    bm, bn, k_layers, k_block_factor = _resolve_knobs(
        k, n, m, a.dtype, bm, bn, k_layers, k_block_factor,
        NS_TN_UPDATE_DUAL if dual else NS_TN_UPDATE,
    )
    if auto_kbf:
        k_block_factor = _bump_kbf_to_fit(
            bm, bn, m, k_layers, k_block_factor, a.dtype, jnp.float32,
            dual=dual, opt_tile_sets=opt_sets,
        )

    kp = _round_up(k, bm)
    np_ = _round_up(n, bn)
    mp = _pad_contract(m, k_layers * k_block_factor)

    def pad2(x, rows, cols):
        if x is None:
            return None
        r, c = x.shape
        if r != rows or c != cols:
            return jnp.pad(x, ((0, rows - r), (0, cols - c)))
        return x

    ns = NS_TN_UPDATE_DUAL if dual else NS_TN_UPDATE
    mode = abft if abft is not None else _abft.current_mode(ns)
    f32 = jnp.float32
    out = sfc_gemm_tn(
        pad2(a2d, mp, kp),
        pad2(b2d, mp, np_),
        pad2(b22d, mp, np_),
        _pad_state(master.astype(f32), kp, np_),
        _pad_state(mu.astype(f32), kp, np_),
        _pad_state(nu.astype(f32), kp, np_),
        _pad_state(master2.astype(f32), kp, np_) if dual else None,
        _pad_state(mu2.astype(f32), kp, np_) if dual else None,
        _pad_state(nu2.astype(f32), kp, np_) if dual else None,
        hyper.astype(f32),
        bm=bm, bn=bn,
        k_layers=k_layers, k_block_factor=k_block_factor,
        interpret=interpret, out_dtype=f32,
        update_dtype=param_dtype, stochastic_round=stochastic_round,
        abft=mode != "off",
    )
    chk = None
    if mode != "off":
        *out, chk = out

    def crop(set_):
        w_n, mst_n, mu_n, nu_n = set_
        return (
            w_n[:k, :n],
            mst_n[:k, :n],
            mu_n[:k, :n],
            nu_n[:k, :n],
        )

    if dual:
        norm = out[8]
        res = (
            (*crop(out[0:4]), norm[0, 0]),
            (*crop(out[4:8]), norm[1, 0]),
        )
    else:
        res = (*crop(out[0:4]), out[4][0, 0])
    if mode != "off":
        # the checksum is the raw dW accumulator, caught *before* the
        # in-flush AdamW consumes it — a flip in the gradient contraction
        # is detected even though dW itself never reaches HBM
        ref, mag = _abft.tn_checksum_ref(a2d, b2d)
        res = _abft.verify(
            ns, res, chk[0, 0], ref, mag, contract_dim=m, mode=mode
        )
        if dual:
            r2, m2_ = _abft.tn_checksum_ref(a2d, b22d)
            res = _abft.verify(
                ns, res, chk[1, 0], r2, m2_, contract_dim=m, mode=mode
            )
    return res


def sfc_grouped_matmul_tn_update(
    a: jax.Array,  # (T, K) rows sorted by group (forward activations)
    dy: jax.Array,  # (T, N) rows sorted by group (output cotangent)
    group_sizes: Sequence[int],
    master: jax.Array,  # (E, K, N) f32
    mu: jax.Array,
    nu: jax.Array,
    hyper: jax.Array,  # (12,) f32
    dy2: Optional[jax.Array] = None,
    master2: Optional[jax.Array] = None,
    mu2: Optional[jax.Array] = None,
    nu2: Optional[jax.Array] = None,
    *,
    param_dtype=None,
    stochastic_round: bool = False,
    row_block: Optional[int] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Grouped grad-and-update: per-expert ``dW[e] = a[rows of e]ᵀ @
    dy[rows of e]`` fused with the AdamW step over the (E, K, N) stacks —
    the expert weight-grad stack never materializes.  Empty dispatch
    (no rows at all) falls back to the elementwise g = 0 update."""
    if interpret is None:
        interpret = default_interpret()
    t, k = a.shape
    t2, n = dy.shape
    assert t == t2, (a.shape, dy.shape)
    dual = dy2 is not None
    group_sizes = tuple(int(g) for g in group_sizes)
    e_cnt = len(group_sizes)
    assert master.shape == (e_cnt, k, n), (master.shape, (e_cnt, k, n))
    param_dtype = jnp.dtype(param_dtype or a.dtype)
    f32 = jnp.float32

    def empty_update(mst, m_, v_):
        dw = jnp.zeros((e_cnt, k, n), f32)
        return _jnp_update(
            dw, mst.astype(f32), m_.astype(f32), v_.astype(f32), hyper,
            param_dtype=param_dtype, stochastic_round=stochastic_round,
        )

    if bm is None or bn is None:
        pbm, pbn, _ = pick_blocks(k, n, max(t, 1))
        bm = bm or min(pbm, 128)
        bn = bn or min(pbn, 128)
    if row_block is None:
        max_g = max(group_sizes) if group_sizes else 1
        row_block = min(128, _round_up(max(max_g, 8), 8))
        dtype_bytes = jnp.dtype(a.dtype).itemsize
        while row_block > 8 and not fused_path_fits_vmem(
            bm, bn, row_block, dtype_bytes, 4, glu=dual,
            opt_tile_sets=2 if dual else 1,
        ):
            row_block //= 2

    kp = _round_up(k, bm)
    np_ = _round_up(n, bn)
    a_p, row_blocks = _grouped_row_pad(a, group_sizes, row_block, kp)
    if a_p is None:
        one = empty_update(master, mu, nu)
        if dual:
            return one, empty_update(master2, mu2, nu2)
        return one
    b_p, _ = _grouped_row_pad(dy, group_sizes, row_block, np_)
    b2_p = None
    if dual:
        b2_p, _ = _grouped_row_pad(dy2, group_sizes, row_block, np_)

    out = sfc_gemm_grouped_tn(
        a_p, b_p, b2_p,
        _pad_state(master.astype(f32), kp, np_),
        _pad_state(mu.astype(f32), kp, np_),
        _pad_state(nu.astype(f32), kp, np_),
        _pad_state(master2.astype(f32), kp, np_) if dual else None,
        _pad_state(mu2.astype(f32), kp, np_) if dual else None,
        _pad_state(nu2.astype(f32), kp, np_) if dual else None,
        hyper.astype(f32),
        row_blocks=row_blocks, row_block=row_block,
        bm=bm, bn=bn, interpret=interpret, out_dtype=f32,
        update_dtype=param_dtype, stochastic_round=stochastic_round,
    )

    def crop(set_):
        w_n, mst_n, mu_n, nu_n = set_
        return (
            w_n[:, :k, :n],
            mst_n[:, :k, :n],
            mu_n[:, :k, :n],
            nu_n[:, :k, :n],
        )

    if dual:
        norm = out[8]
        return (
            (*crop(out[0:4]), norm[0, 0]),
            (*crop(out[4:8]), norm[1, 0]),
        )
    return (*crop(out[0:4]), out[4][0, 0])


def _grouped_row_pad(
    a: jax.Array, group_sizes: Tuple[int, ...], unit: int, kp: int
):
    """Pad each group's rows to a ``unit`` multiple (and K to ``kp``) and
    concatenate — the packing every grouped kernel consumes."""
    k = a.shape[1]
    row_blocks = tuple(_round_up(g, unit) // unit for g in group_sizes)
    slabs = []
    off = 0
    for g, rb in zip(group_sizes, row_blocks):
        if rb == 0:
            continue
        slab = a[off : off + g]
        pad_rows = rb * unit - g
        if pad_rows or kp != k:
            slab = jnp.pad(slab, ((0, pad_rows), (0, kp - k)))
        slabs.append(slab)
        off += g
    if not slabs:
        return None, row_blocks
    return (jnp.concatenate(slabs) if len(slabs) > 1 else slabs[0]), row_blocks


def _grouped_row_unpad(out_p, group_sizes, row_blocks, unit: int, n: int):
    outs = []
    poff = 0
    for g, rb in zip(group_sizes, row_blocks):
        outs.append(out_p[poff : poff + g, :n])
        poff += rb * unit
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


def sfc_grouped_matmul_nt(
    a: jax.Array,  # (T, Kc) rows sorted by group (e.g. the dC rows)
    b: jax.Array,  # (E, N, Kc) per-group operand, consumed as b[e]ᵀ
    group_sizes: Sequence[int],
    a2: Optional[jax.Array] = None,
    b2: Optional[jax.Array] = None,
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    k_block_factor: Optional[int] = None,
    interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """Grouped NT: ``out[rows of e] = a[rows of e] @ b[e]ᵀ`` — the grouped
    dA backward (per-expert weights read as stored).  Same ragged-row
    contract as `sfc_grouped_matmul`."""
    if interpret is None:
        interpret = default_interpret()
    t, k = a.shape
    e_cnt, n, k2 = b.shape
    assert k == k2, (a.shape, b.shape)
    dual = a2 is not None
    group_sizes = tuple(int(g) for g in group_sizes)
    assert sum(group_sizes) == t, (group_sizes, t)
    out_dtype = out_dtype or a.dtype

    max_g = max(group_sizes) if group_sizes else 1
    pbm, pbn, _ = pick_blocks(max(max_g, 1), n, k)
    bm = bm or min(pbm, 128)
    bn = bn or pbn
    if k_block_factor is None:
        _, k_block_factor = choose_knobs_analytical(
            max(max_g, bm), max(n, bn), max(k, 1), 1, bm=bm, bn=bn, hw=TPU_V5E
        )
        k_block_factor = _bump_kbf_to_fit(
            bm, bn, k, 1, k_block_factor, a.dtype, out_dtype, dual=dual
        )

    kp = _pad_contract(k, k_block_factor)
    np_ = _round_up(n, bn)
    a_p, row_blocks = _grouped_row_pad(a, group_sizes, bm, kp)
    if a_p is None:
        return jnp.zeros((0, n), out_dtype)
    a2_p = None
    if dual:
        a2_p, _ = _grouped_row_pad(a2, group_sizes, bm, kp)

    def pad_w(w):
        if w is None:
            return None
        if kp != k or np_ != n:
            return jnp.pad(w, ((0, 0), (0, np_ - n), (0, kp - k)))
        return w

    out_p = sfc_gemm_grouped_nt(
        a_p, pad_w(b), a2_p, pad_w(b2),
        row_blocks=row_blocks,
        bm=bm, bn=bn, k_block_factor=k_block_factor,
        interpret=interpret, out_dtype=out_dtype,
    )
    return _grouped_row_unpad(out_p, group_sizes, row_blocks, bm, n)


def sfc_grouped_matmul_tn(
    a: jax.Array,  # (T, K) rows sorted by group (the forward activations)
    b: jax.Array,  # (T, N) rows sorted by group (the dC rows)
    group_sizes: Sequence[int],
    b2: Optional[jax.Array] = None,  # (T, N) second dC (GLU gate grad)
    *,
    row_block: Optional[int] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    interpret: Optional[bool] = None,
    out_dtype=None,
):
    """Grouped TN: ``dW[e] = a[rows of e]ᵀ @ b[rows of e]`` for every group
    in one launch — the grouped dW backward.  With ``b2`` the activation
    slab streams once for both weight-grad stacks."""
    if interpret is None:
        interpret = default_interpret()
    t, k = a.shape
    t2, n = b.shape
    assert t == t2, (a.shape, b.shape)
    dual = b2 is not None
    group_sizes = tuple(int(g) for g in group_sizes)
    e_cnt = len(group_sizes)
    assert sum(group_sizes) == t, (group_sizes, t)
    out_dtype = out_dtype or a.dtype

    if bm is None or bn is None:
        pbm, pbn, _ = pick_blocks(k, n, max(t, 1))
        bm = bm or min(pbm, 128)
        bn = bn or min(pbn, 128)
    if row_block is None:
        max_g = max(group_sizes) if group_sizes else 1
        row_block = min(128, _round_up(max(max_g, 8), 8))
        dtype_bytes = jnp.dtype(a.dtype).itemsize
        out_bytes = jnp.dtype(out_dtype).itemsize
        while row_block > 8 and not fused_path_fits_vmem(
            bm, bn, row_block, dtype_bytes, out_bytes, glu=dual,
        ):
            row_block //= 2

    kp = _round_up(k, bm)
    np_ = _round_up(n, bn)
    a_p, row_blocks = _grouped_row_pad(a, group_sizes, row_block, kp)
    if a_p is None:
        zero = jnp.zeros((e_cnt, k, n), out_dtype)
        return (zero, zero) if dual else zero
    b_p, _ = _grouped_row_pad(b, group_sizes, row_block, np_)
    b2_p = None
    if dual:
        b2_p, _ = _grouped_row_pad(b2, group_sizes, row_block, np_)

    out = sfc_gemm_grouped_tn(
        a_p, b_p, b2_p,
        row_blocks=row_blocks, row_block=row_block,
        bm=bm, bn=bn,
        interpret=interpret, out_dtype=out_dtype,
    )
    if dual:
        return out[0][:, :k, :n], out[1][:, :k, :n]
    return out[:, :k, :n]


# ---------------------------------------------------------------------------
# custom VJPs: the backward pass is itself SFC GEMMs
#
# `jax.value_and_grad` through `sfc_matmul`/`sfc_glu_matmul` (and the
# grouped forms) routes both backward GEMMs — dA = dC·Bᵀ and dB = Aᵀ·dC —
# through the NT/TN kernels above, with their own tune-cache namespaces.
# The epilogue derivatives (activation', the GLU gating terms, bias/residual
# reductions) are cheap elementwise/reduce ops computed once on dC before
# the kernels consume it: precomputing dZ in HBM costs one write + one read,
# while fusing act'(z) into the NT/TN panel loads would re-stream the saved
# pre-activation once per tile revisit — strictly more traffic.
#
# Training forward differs from inference forward only for the activated
# forms: the kernel flushes the biased *pre-activation* (for GLU, both
# accumulators via `preact_out` — still one A traversal) and the activation
# runs outside, because the backward needs act'(z) and recomputing z would
# double the backward GEMM count.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _VjpCfg:
    glu: bool
    activation: Optional[str]
    out_scale: Optional[float]
    bm: Optional[int]
    bn: Optional[int]
    k_layers: Optional[int]
    k_block_factor: Optional[int]
    interpret: Optional[bool]
    out_dtype: Any
    fuse: Optional[bool]
    abft: Optional[str] = None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _matmul_core(cfg, a, b, b_gate, bias, gate_bias, residual):
    return _matmul_impl(
        a, b, b_gate,
        bias=bias, gate_bias=gate_bias, residual=residual,
        activation=cfg.activation, out_scale=cfg.out_scale,
        bm=cfg.bm, bn=cfg.bn,
        k_layers=cfg.k_layers, k_block_factor=cfg.k_block_factor,
        interpret=cfg.interpret, out_dtype=cfg.out_dtype, fuse=cfg.fuse,
        abft=cfg.abft,
    )


def _matmul_core_fwd(cfg, a, b, b_gate, bias, gate_bias, residual):
    out_dtype = cfg.out_dtype or a.dtype
    kw = dict(
        bm=cfg.bm, bn=cfg.bn,
        k_layers=cfg.k_layers, k_block_factor=cfg.k_block_factor,
        interpret=cfg.interpret, fuse=cfg.fuse, abft=cfg.abft,
    )
    h_pre = g_pre = None
    if cfg.glu:
        h_pre, g_pre = _matmul_impl(
            a, b, b_gate, bias=bias, gate_bias=gate_bias, residual=None,
            activation=None, out_scale=None, out_dtype=None, preact=True, **kw,
        )
        y = activation_fn(cfg.activation)(g_pre.astype(jnp.float32)) * (
            h_pre.astype(jnp.float32)
        )
    elif cfg.activation is not None:
        h_pre = _matmul_impl(
            a, b, None, bias=bias, gate_bias=None, residual=None,
            activation=None, out_scale=None, out_dtype=None, **kw,
        )
        y = activation_fn(cfg.activation)(h_pre.astype(jnp.float32))
    else:
        # linear epilogue: the fully fused primal path is the training
        # forward too (no pre-activation residual needed)
        out = _matmul_impl(
            a, b, None, bias=bias, gate_bias=None, residual=residual,
            activation=None, out_scale=cfg.out_scale, out_dtype=cfg.out_dtype,
            **kw,
        )
        y = None
    if y is not None:
        if cfg.out_scale is not None:
            y = y * cfg.out_scale
        if residual is not None:
            y = y + residual.astype(jnp.float32)
        out = y.astype(out_dtype)
    res_meta = (
        jnp.zeros((), residual.dtype) if residual is not None else None
    )
    return out, (a, b, b_gate, h_pre, g_pre, bias, gate_bias, res_meta)


def _epilogue_cotangents(glu, activation, out_scale, h_pre, g_pre, dy):
    """(dh, dg) f32 cotangents of the biased pre-activations given dy —
    the epilogue-derivative prelude shared by every backward path."""
    dyf = dy.astype(jnp.float32)
    if out_scale is not None:
        dyf = dyf * out_scale
    if glu:
        act = activation_fn(activation)
        ag, act_vjp = jax.vjp(act, g_pre.astype(jnp.float32))
        dh = dyf * ag
        dg = act_vjp(dyf * h_pre.astype(jnp.float32))[0]
    elif activation is not None:
        act = activation_fn(activation)
        _, act_vjp = jax.vjp(act, h_pre.astype(jnp.float32))
        dh = act_vjp(dyf)[0]
        dg = None
    else:
        dh, dg = dyf, None
    return dh, dg


# ---------------------------------------------------------------------------
# backward self-healing — fallback-ladder rungs for the NT/TN launches
#
# The backward kernels run at grad-trace time, far from the forward ladder
# in `core.gemm_backend`: a Mosaic/VMEM failure here must degrade *here*.
# Each launch gets a two-rung ladder — the SFC kernel, then a plain-jnp
# contraction with an f32 accumulator (`preferred_element_type`), which is
# exactly the math the kernel performs.  The jnp rungs introduce
# dot_general into the jaxpr, so they only ever appear in a trace where
# the Pallas rung actually failed or is quarantined — the healthy-path
# structure gates (zero dot_general) are unaffected.
# ---------------------------------------------------------------------------


def _bwd_shape_key(m: int, n: int, k: int, dtype) -> str:
    from repro.tune.cache import shape_bucket

    bm_, bn_, bk_ = shape_bucket(max(m, 1), max(n, 1), max(k, 1))
    return f"{bm_}x{bn_}x{bk_}|{jnp.dtype(dtype).name}"


def _jnp_nt(dh, b, dg=None, b_gate=None):
    """jnp rung for `sfc_matmul_nt`: dh(...,M,N) @ b(K,N)ᵀ (+ dual)."""
    out = jnp.einsum(
        "...mn,kn->...mk", dh, b, preferred_element_type=jnp.float32
    )
    if dg is not None:
        out = out + jnp.einsum(
            "...mn,kn->...mk", dg, b_gate, preferred_element_type=jnp.float32
        )
    return out


def _jnp_tn(a2d, dh2, dg2=None):
    """jnp rung for `sfc_matmul_tn`: a(M,K)ᵀ @ dh(M,N) (dual: a pair)."""
    db = jnp.einsum("mk,mn->kn", a2d, dh2, preferred_element_type=jnp.float32)
    if dg2 is None:
        return db
    return db, jnp.einsum(
        "mk,mn->kn", a2d, dg2, preferred_element_type=jnp.float32
    )


def _jnp_grouped_nt(dh, b, group_sizes, dg=None, b_gate=None):
    """jnp rung for `sfc_grouped_matmul_nt` (per-expert row slabs)."""
    parts = []
    off = 0
    for ei, g in enumerate(group_sizes):
        slab = _jnp_nt(
            dh[off : off + g],
            b[ei],
            dg[off : off + g] if dg is not None else None,
            b_gate[ei] if dg is not None else None,
        )
        parts.append(slab)
        off += g
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _jnp_grouped_tn(a, dh, group_sizes, dg=None):
    """jnp rung for `sfc_grouped_matmul_tn`: (E, K, N) dW stack(s)."""
    dbs, dgs = [], []
    off = 0
    for g in group_sizes:
        dbs.append(_jnp_tn(a[off : off + g], dh[off : off + g]))
        if dg is not None:
            dgs.append(_jnp_tn(a[off : off + g], dg[off : off + g]))
        off += g
    db = jnp.stack(dbs)
    if dg is None:
        return db
    return db, jnp.stack(dgs)


def _nt_with_fallback(dh_c, b, dg_c, b_gate, *, interpret):
    from repro.robust import run_with_fallback

    def kernel():
        return sfc_matmul_nt(
            dh_c, b, dg_c, b_gate, interpret=interpret,
            out_dtype=jnp.float32,
        )

    m = int(np.prod(dh_c.shape[:-1]))
    return run_with_fallback(
        NS_NT,
        ((RUNG_SFC_PALLAS, kernel), (RUNG_XLA, lambda: _jnp_nt(dh_c, b, dg_c, b_gate))),
        shape_key=_bwd_shape_key(m, b.shape[0], dh_c.shape[-1], dh_c.dtype),
    )


def _tn_with_fallback(a2d, dh2, dg2, *, interpret):
    from repro.robust import run_with_fallback

    def kernel():
        if dg2 is not None:
            return sfc_matmul_tn(
                a2d, dh2, dg2, interpret=interpret, out_dtype=jnp.float32
            )
        return sfc_matmul_tn(
            a2d, dh2, interpret=interpret, out_dtype=jnp.float32
        )

    return run_with_fallback(
        NS_TN,
        ((RUNG_SFC_PALLAS, kernel), (RUNG_XLA, lambda: _jnp_tn(a2d, dh2, dg2))),
        shape_key=_bwd_shape_key(
            a2d.shape[-1], dh2.shape[-1], a2d.shape[0], a2d.dtype
        ),
    )


def _grouped_nt_with_fallback(dh_c, b, gs, dg_c, b_gate, *, interpret):
    from repro.robust import run_with_fallback

    def kernel():
        return sfc_grouped_matmul_nt(
            dh_c, b, gs, dg_c, b_gate, interpret=interpret,
            out_dtype=jnp.float32,
        )

    return run_with_fallback(
        NS_GROUPED_NT,
        (
            (RUNG_SFC_PALLAS, kernel),
            (RUNG_XLA, lambda: _jnp_grouped_nt(dh_c, b, gs, dg_c, b_gate)),
        ),
        shape_key=_bwd_shape_key(
            dh_c.shape[0], b.shape[-2], dh_c.shape[-1], dh_c.dtype
        ),
    )


def _grouped_tn_with_fallback(a, dh_c, gs, dg_c, *, interpret):
    from repro.robust import run_with_fallback

    def kernel():
        if dg_c is not None:
            return sfc_grouped_matmul_tn(
                a, dh_c, gs, dg_c, interpret=interpret, out_dtype=jnp.float32
            )
        return sfc_grouped_matmul_tn(
            a, dh_c, gs, interpret=interpret, out_dtype=jnp.float32
        )

    return run_with_fallback(
        NS_GROUPED_TN,
        ((RUNG_SFC_PALLAS, kernel), (RUNG_XLA, lambda: _jnp_grouped_tn(a, dh_c, gs, dg_c))),
        shape_key=_bwd_shape_key(
            a.shape[-1], dh_c.shape[-1], a.shape[0], a.dtype
        ),
    )


def _matmul_core_bwd(cfg, saved, dy):
    a, b, b_gate, h_pre, g_pre, bias, gate_bias, res_meta = saved
    interp = cfg.interpret
    dres = dy.astype(res_meta.dtype) if res_meta is not None else None
    dh, dg = _epilogue_cotangents(
        cfg.glu, cfg.activation, cfg.out_scale, h_pre, g_pre, dy
    )

    cdt = a.dtype  # backward kernels run in the forward compute dtype
    dh_c = dh.astype(cdt)
    dg_c = dg.astype(cdt) if dg is not None else None

    if b.ndim > 2:
        # per-batch weights (no model call site; GLU excluded by the fwd
        # validation): backward through the forward kernels on materialized
        # transposes — still the SFC path, one extra HBM copy each
        da = sfc_matmul(
            dh_c, jnp.swapaxes(b, -1, -2), interpret=interp,
            out_dtype=jnp.float32,
        )
        db = sfc_matmul(
            jnp.swapaxes(a, -1, -2), dh_c, interpret=interp,
            out_dtype=jnp.float32,
        )
        dbg = None
    else:
        da = _nt_with_fallback(
            dh_c, b,
            dg_c, b_gate if dg_c is not None else None,
            interpret=interp,
        )
        n = b.shape[-1]
        a2d = a.reshape(-1, a.shape[-1])
        if dg_c is not None:
            db, dbg = _tn_with_fallback(
                a2d, dh_c.reshape(-1, n), dg_c.reshape(-1, n),
                interpret=interp,
            )
        else:
            db = _tn_with_fallback(
                a2d, dh_c.reshape(-1, n), None, interpret=interp
            )
            dbg = None

    lead_axes = tuple(range(dh.ndim - 1))
    dbias = None
    if bias is not None:
        dbias = dh.sum(axis=lead_axes).reshape(bias.shape).astype(bias.dtype)
    dgbias = None
    if gate_bias is not None:
        dgbias = (
            dg.sum(axis=lead_axes).reshape(gate_bias.shape)
            .astype(gate_bias.dtype)
        )
    return (
        da.astype(a.dtype),
        db.astype(b.dtype),
        dbg.astype(b_gate.dtype) if b_gate is not None else None,
        dbias,
        dgbias,
        dres,
    )


_matmul_core.defvjp(_matmul_core_fwd, _matmul_core_bwd)


# ---------------------------------------------------------------------------
# fused-optimizer custom VJPs: the update runs inside the backward pass
#
# A routed weight's "cotangent" is not its gradient — it is the *applied
# AdamW update*: the bwd rule launches the TN grad-and-update kernel and
# returns (W_new, master', mu', nu', sum(dW^2)) through the cotangent slots
# of the `optim.fused.FusedParam` children.  `jax.grad` of the loss then
# hands the train step the updated state directly; no standalone optimizer
# pass exists for routed weights and dW never touches HBM.
#
# ``fused=False`` (the "xla"/"sfc_reference" backends) is the semantics
# oracle: plain-autodiff backward GEMMs composed with the same packed-hyper
# elementwise update — the unfused composition differential tests compare
# the kernel flush against.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _UpdateVjpCfg:
    base: _VjpCfg
    fused: bool  # sfc_pallas NT/TN-update kernels vs the jnp oracle
    stochastic_round: bool


def _oracle_primal_parts(cfg, a, b, b_gate, bias, gate_bias):
    """(callable, args) for the plain-jnp primal of the unfused oracle."""
    glu = cfg.base.glu
    have_bias = bias is not None
    have_gbias = gate_bias is not None

    def prim(*args):
        it = iter(args)
        a_ = next(it)
        b_ = next(it)
        bg_ = next(it) if glu else None
        bi_ = next(it) if have_bias else None
        gb_ = next(it) if have_gbias else None
        h = a_ @ b_
        if bi_ is not None:
            h = h + bi_
        if glu:
            g = a_ @ bg_
            if gb_ is not None:
                g = g + gb_
            return activation_fn(cfg.base.activation)(g) * h
        if cfg.base.activation is not None:
            return activation_fn(cfg.base.activation)(h)
        return h

    args = [a, b]
    if glu:
        args.append(b_gate)
    if have_bias:
        args.append(bias)
    if have_gbias:
        args.append(gate_bias)
    return prim, args


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _update_core(cfg, a, b, b_gate, bias, gate_bias, opt, hyper, token):
    del opt, hyper, token  # consumed by the backward rule only
    if not cfg.fused:
        prim, args = _oracle_primal_parts(cfg, a, b, b_gate, bias, gate_bias)
        return prim(*args)
    return _matmul_impl(
        a, b, b_gate,
        bias=bias, gate_bias=gate_bias, residual=None,
        activation=cfg.base.activation, out_scale=None,
        bm=cfg.base.bm, bn=cfg.base.bn,
        k_layers=cfg.base.k_layers, k_block_factor=cfg.base.k_block_factor,
        interpret=cfg.base.interpret, out_dtype=cfg.base.out_dtype,
        fuse=cfg.base.fuse,
    )


def _update_core_fwd(cfg, a, b, b_gate, bias, gate_bias, opt, hyper, token):
    del token
    if not cfg.fused:
        prim, args = _oracle_primal_parts(cfg, a, b, b_gate, bias, gate_bias)
        y, f_vjp = jax.vjp(prim, *args)
        return y, (f_vjp, a, b, b_gate, bias, gate_bias, opt, hyper)
    out, saved = _matmul_core_fwd(cfg.base, a, b, b_gate, bias, gate_bias, None)
    a_, b_, bg_, h_pre, g_pre, bias_, gbias_, _ = saved
    return out, (a_, b_, bg_, h_pre, g_pre, bias_, gbias_, opt, hyper)


def _run_tn_update(cfg, a2d, dh_c, dg_c, b, b_gate, opt, hyper):
    """Dispatch the (possibly dual) fused TN update; returns the cotangent
    pieces (w_cots, opt_cots, token_cots) in primal argument structure.

    Self-healing: the grad-and-update flush is the deepest Pallas launch
    in the train step, so it carries its own ladder rung — on a
    classified failure the update falls back to the jnp oracle (`_jnp_tn`
    dW + `_jnp_update`), which is the same AdamW program the flush runs."""
    from repro.robust import run_with_fallback

    interp = cfg.base.interpret
    n = b.shape[-1]

    def kernel():
        if dg_c is not None:
            if b_gate.dtype != b.dtype:
                # one _TnUpdate.param_dtype serves both flush sets — a
                # silent cast would round the gate weights through the
                # value dtype; the ladder degrades this to the oracle,
                # which keeps per-weight dtypes
                raise NotImplementedError(
                    f"fused GLU update requires matching weight dtypes, got "
                    f"value={b.dtype} gate={b_gate.dtype}"
                )
            (ov, og) = opt
            set_v, set_g = sfc_matmul_tn_update(
                a2d, dh_c.reshape(-1, n), ov[0], ov[1], ov[2], hyper,
                dg_c.reshape(-1, n), og[0], og[1], og[2],
                param_dtype=b.dtype, stochastic_round=cfg.stochastic_round,
                interpret=interp,
            )
            wv, mv, muv, nuv, sqv = set_v
            wg, mg, mug, nug, sqg = set_g
            return (
                (wv, wg),
                ((mv, muv, nuv), (mg, mug, nug)),
                (sqv, sqg),
            )
        (mst, mu, nu) = opt
        w_n, mst_n, mu_n, nu_n, sq = sfc_matmul_tn_update(
            a2d, dh_c.reshape(-1, n), mst, mu, nu, hyper,
            param_dtype=b.dtype, stochastic_round=cfg.stochastic_round,
            interpret=interp,
        )
        return ((w_n, None), (mst_n, mu_n, nu_n), sq)

    def oracle():
        if dg_c is not None:
            dw, dwg = _jnp_tn(a2d, dh_c.reshape(-1, n), dg_c.reshape(-1, n))
            ov, og = opt
            w_v, opt_v, sq_v = _oracle_update(cfg, dw, ov, b.dtype, hyper)
            w_g, opt_g, sq_g = _oracle_update(cfg, dwg, og, b_gate.dtype, hyper)
            return ((w_v, w_g), (opt_v, opt_g), (sq_v, sq_g))
        dw = _jnp_tn(a2d, dh_c.reshape(-1, n), None)
        w_n, opt_n, sq = _oracle_update(cfg, dw, opt, b.dtype, hyper)
        return ((w_n, None), opt_n, sq)

    return run_with_fallback(
        NS_TN_UPDATE,
        ((RUNG_SFC_PALLAS, kernel), (RUNG_XLA, oracle)),
        shape_key=_bwd_shape_key(
            a2d.shape[-1], n, a2d.shape[0], a2d.dtype
        ),
    )


def _oracle_update(cfg, dw, opt_leaf, param_dtype, hyper):
    w_n, mst_n, mu_n, nu_n, sq = _jnp_update(
        dw, opt_leaf[0], opt_leaf[1], opt_leaf[2], hyper,
        param_dtype=param_dtype, stochastic_round=cfg.stochastic_round,
    )
    return w_n, (mst_n, mu_n, nu_n), sq


def _update_core_bwd(cfg, saved, dy):
    glu = cfg.base.glu
    if not cfg.fused:
        f_vjp, a, b, b_gate, bias, gate_bias, opt, hyper = saved
        cots = list(f_vjp(dy))
        da = cots.pop(0)
        dw = cots.pop(0)
        dwg = cots.pop(0) if glu else None
        dbias = cots.pop(0) if bias is not None else None
        dgbias = cots.pop(0) if gate_bias is not None else None
        if glu:
            ov, og = opt
            w_v, opt_v, sq_v = _oracle_update(cfg, dw, ov, b.dtype, hyper)
            w_g, opt_g, sq_g = _oracle_update(
                cfg, dwg, og, b_gate.dtype, hyper
            )
            return (
                da, w_v, w_g, dbias, dgbias,
                (opt_v, opt_g), jnp.zeros_like(hyper), (sq_v, sq_g),
            )
        w_n, opt_n, sq = _oracle_update(cfg, dw, opt, b.dtype, hyper)
        return (
            da, w_n, None, dbias, dgbias,
            opt_n, jnp.zeros_like(hyper), sq,
        )

    a, b, b_gate, h_pre, g_pre, bias, gate_bias, opt, hyper = saved
    interp = cfg.base.interpret
    dh, dg = _epilogue_cotangents(glu, cfg.base.activation, None, h_pre, g_pre, dy)
    cdt = a.dtype  # backward kernels run in the forward compute dtype
    dh_c = dh.astype(cdt)
    dg_c = dg.astype(cdt) if dg is not None else None

    da = _nt_with_fallback(
        dh_c, b,
        dg_c, b_gate if dg_c is not None else None,
        interpret=interp,
    )
    a2d = a.reshape(-1, a.shape[-1])
    (w_v, w_g), opt_cots, token_cots = _run_tn_update(
        cfg, a2d, dh_c, dg_c, b, b_gate, opt, hyper
    )

    lead_axes = tuple(range(dh.ndim - 1))
    dbias = None
    if bias is not None:
        dbias = dh.sum(axis=lead_axes).reshape(bias.shape).astype(bias.dtype)
    dgbias = None
    if gate_bias is not None:
        dgbias = (
            dg.sum(axis=lead_axes).reshape(gate_bias.shape)
            .astype(gate_bias.dtype)
        )
    return (
        da.astype(a.dtype), w_v, w_g, dbias, dgbias,
        opt_cots, jnp.zeros_like(hyper), token_cots,
    )


_update_core.defvjp(_update_core_fwd, _update_core_bwd)


def fused_update_matmul(
    x: jax.Array,
    w: jax.Array,
    master: jax.Array,
    mu: jax.Array,
    nu: jax.Array,
    hyper: jax.Array,
    token: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    backend: str = RUNG_SFC_PALLAS,
    stochastic_round: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Projection whose backward applies AdamW in the TN flush.

    Forward: ``epilogue(x @ w)`` exactly like `sfc_matmul` (or the plain
    jnp program under the non-Pallas oracle backends).  Backward: dA flows
    on as usual, while the cotangents of (w, master, mu, nu, token) carry
    (W_new, master', mu', nu', sum(dW^2)) — see `optim.fused`."""
    cfg = _UpdateVjpCfg(
        base=_VjpCfg(
            glu=False, activation=activation, out_scale=None,
            bm=None, bn=None, k_layers=None, k_block_factor=None,
            interpret=interpret, out_dtype=None, fuse=None,
        ),
        fused=backend == RUNG_SFC_PALLAS,
        stochastic_round=stochastic_round,
    )
    return _update_core(
        cfg, x, w, None, bias, None, (master, mu, nu), hyper, token
    )


def fused_update_glu_matmul(
    x: jax.Array,
    w_gate: jax.Array,
    w_val: jax.Array,
    opt_gate: Tuple[jax.Array, jax.Array, jax.Array],
    opt_val: Tuple[jax.Array, jax.Array, jax.Array],
    hyper: jax.Array,
    tokens: Tuple[jax.Array, jax.Array],  # (token_val, token_gate)
    *,
    activation: str = "silu",
    bias: Optional[jax.Array] = None,
    gate_bias: Optional[jax.Array] = None,
    backend: str = RUNG_SFC_PALLAS,
    stochastic_round: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Gated projection with both weight updates fused into one dual TN
    flush: the activation slab streams once for (dWv, dWg) and both AdamW
    updates; cotangent slots return both updated weight sets."""
    cfg = _UpdateVjpCfg(
        base=_VjpCfg(
            glu=True, activation=activation, out_scale=None,
            bm=None, bn=None, k_layers=None, k_block_factor=None,
            interpret=interpret, out_dtype=None, fuse=None,
        ),
        fused=backend == RUNG_SFC_PALLAS,
        stochastic_round=stochastic_round,
    )
    return _update_core(
        cfg, x, w_val, w_gate, bias, gate_bias,
        (opt_val, opt_gate), hyper, tokens,
    )


def sfc_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    out_scale: Optional[float] = None,
    residual: Optional[jax.Array] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    k_layers: Optional[int] = None,
    k_block_factor: Optional[int] = None,
    interpret: Optional[bool] = None,
    out_dtype=None,
    fuse: Optional[bool] = None,
    abft: Optional[str] = None,
) -> jax.Array:
    """C = epilogue(A @ B) via the SFC-CA Pallas kernel, any leading batch
    dims on A.

    ``a``: (..., M, K); ``b``: (K, N) shared across the batch, or
    (..., K, N) with leading dims matching ``a``'s.  The epilogue —
    ``bias`` (N,), ``activation`` in {"silu", "gelu", "relu"},
    ``out_scale`` (python float) and ``residual`` (..., M, N) — is fused
    into the kernel flush: ``C = act(A@B + bias) * out_scale + residual``
    computed on the f32 accumulator, one HBM write.

    Knobs left as None are filled from the empirical tune cache when
    present, else by the paper's analytical model (K_layers,
    k_block_factor) and MXU alignment rules (bm, bn).  ``fuse=None`` (auto)
    uses the single-launch layer-inner kernel whenever its VMEM working set
    fits; ``fuse=False`` forces the replicated (K_layers, M, N) +
    `add_reduce_pallas` two-launch fallback with a jnp epilogue.  Arbitrary
    M/N/K are handled by zero padding (curve still covers the padded grid;
    padding contributes zeros to the contraction).

    Differentiable end-to-end on the SFC backend: a `jax.custom_vjp` routes
    the backward GEMMs through `sfc_matmul_nt`/`sfc_matmul_tn` (transposes
    stay in VMEM, knobs from the "nt"/"tn" tune namespaces).

    ``abft``: "off" | "detect" | "strict" checksum verification of the
    forward launch (`repro.robust.abft`); None defers to the ambient
    `abft_mode` context (backward launches always resolve from the
    context — the cfg only pins the forward).
    """
    cfg = _VjpCfg(
        glu=False, activation=activation, out_scale=out_scale,
        bm=bm, bn=bn, k_layers=k_layers, k_block_factor=k_block_factor,
        interpret=interpret, out_dtype=out_dtype, fuse=fuse, abft=abft,
    )
    return _matmul_core(cfg, a, b, None, bias, None, residual)


def sfc_glu_matmul(
    a: jax.Array,
    b_gate: jax.Array,
    b_val: jax.Array,
    *,
    activation: str = "silu",
    bias: Optional[jax.Array] = None,
    gate_bias: Optional[jax.Array] = None,
    out_scale: Optional[float] = None,
    residual: Optional[jax.Array] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    k_layers: Optional[int] = None,
    k_block_factor: Optional[int] = None,
    interpret: Optional[bool] = None,
    out_dtype=None,
    fuse: Optional[bool] = None,
    abft: Optional[str] = None,
) -> jax.Array:
    """Gated-MLP projection: ``act(A@Wg + gate_bias) * (A@Wv + bias)`` in
    one SFC traversal of A (dual-B kernel: two weight panels, two f32
    accumulators, one C write).  ``a``: (..., M, K); weights are shared 2-D
    (K, N).  Same knob resolution/padding contract as `sfc_matmul`; the GLU
    variant has its own tune-cache namespace (op="glu").

    Differentiable: the VJP computes dA = dg·Wgᵀ + dh·Wvᵀ in one dual NT
    launch and (dWv, dWg) in one dual TN launch — four backward GEMMs, two
    SFC traversals, no transposed HBM copies."""
    cfg = _VjpCfg(
        glu=True, activation=activation, out_scale=out_scale,
        bm=bm, bn=bn, k_layers=k_layers, k_block_factor=k_block_factor,
        interpret=interpret, out_dtype=out_dtype, fuse=fuse, abft=abft,
    )
    return _matmul_core(cfg, a, b_val, b_gate, bias, gate_bias, residual)


def _grouped_impl(
    a: jax.Array,  # (T, K) rows sorted by group
    b: jax.Array,  # (E, K, N) per-group weights
    b_gate: Optional[jax.Array],  # (E, K, N) per-group gate weights
    group_sizes: Sequence[int],
    *,
    bias: Optional[jax.Array],
    gate_bias: Optional[jax.Array],
    activation: Optional[str],
    out_scale: Optional[float],
    bm: Optional[int],
    bn: Optional[int],
    k_block_factor: Optional[int],
    interpret: Optional[bool],
    out_dtype,
    preact: bool = False,
    abft: Optional[str] = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    glu = b_gate is not None
    if preact:
        assert glu and activation is None and out_scale is None
    t, k = a.shape
    e_cnt, k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if glu and b_gate.shape != b.shape:
        raise ValueError(f"gate weights {b_gate.shape} != {b.shape}")
    group_sizes = tuple(int(g) for g in group_sizes)
    if len(group_sizes) != e_cnt:
        raise ValueError(f"{len(group_sizes)} group sizes for {e_cnt} groups")
    if sum(group_sizes) != t:
        raise ValueError(f"group_sizes sum {sum(group_sizes)} != rows {t}")
    for name, vec in (("bias", bias), ("gate_bias", gate_bias)):
        if vec is not None and vec.shape != (e_cnt, n):
            raise ValueError(f"{name} must be (E, N)=({e_cnt},{n}), got {vec.shape}")
    out_dtype = out_dtype or a.dtype

    max_g = max(group_sizes) if group_sizes else 1
    pbm, pbn, _ = pick_blocks(max(max_g, 1), n, k)
    bm = bm or min(pbm, 128)
    bn = bn or pbn
    if k_block_factor is None:
        # capacity heuristic only (no 2.5D layers for the ragged form)
        _, k_block_factor = choose_knobs_analytical(
            max(max_g, bm), max(n, bn), max(k, 1), 1, bm=bm, bn=bn, hw=TPU_V5E
        )
        # the grouped form has no replicated fallback — if the (possibly
        # dual-B) working set overflows the VMEM budget, shrink the K chunk.
        # Only auto-resolved knobs are adjusted; explicit ones are honored.
        dtype_bytes = jnp.dtype(a.dtype).itemsize
        out_bytes = jnp.dtype(out_dtype).itemsize
        while k_block_factor < max(k, 1) and not fused_path_fits_vmem(
            bm, bn, _pad_contract(k, k_block_factor) // k_block_factor,
            dtype_bytes, out_bytes, glu=glu,
        ):
            k_block_factor *= 2

    kp = _pad_contract(k, k_block_factor)
    np_ = _round_up(n, bn)

    # pad each group's rows to a bm multiple and concatenate (host loop:
    # group_sizes are static, so this unrolls into slices under jit)
    a_p, row_blocks = _grouped_row_pad(a, group_sizes, bm, kp)
    if a_p is None:
        zero = jnp.zeros((0, n), out_dtype)
        return (zero, zero) if preact else zero

    def pad_w(w):
        if kp != k or np_ != n:
            return jnp.pad(w, ((0, 0), (0, kp - k), (0, np_ - n)))
        return w

    b_p = pad_w(b)
    bg_p = pad_w(b_gate) if glu else None

    def pad_vec(v):
        if v is None:
            return None
        return jnp.pad(v.reshape(e_cnt, 1, n), ((0, 0), (0, 0), (0, np_ - n)))

    ns = NS_GROUPED_GLU if glu else NS_GROUPED
    mode = abft if abft is not None else _abft.current_mode(ns)
    out_p = sfc_gemm_grouped(
        a_p, b_p, bg_p, pad_vec(bias), pad_vec(gate_bias),
        row_blocks=row_blocks,
        activation=activation, out_scale=out_scale,
        bm=bm, bn=bn,
        k_block_factor=k_block_factor,
        interpret=interpret, out_dtype=out_dtype,
        preact_out=preact, abft=mode != "off",
    )  # (sum(row_blocks)*bm, Np), or the (value, gate) preact pair
    chk = None
    if mode != "off":
        *out_p, chk = out_p
    elif not isinstance(out_p, tuple):
        out_p = (out_p,)

    # slice the valid rows of each group back out
    def unpad(full):
        return _grouped_row_unpad(full, group_sizes, row_blocks, bm, n)

    if preact:
        res = (unpad(out_p[0]), unpad(out_p[1]))
    else:
        res = unpad(out_p[0])
    if mode != "off":
        # per-expert operand checksums: each group contracts its own rows
        # against its own weight slab
        ref = mag = jnp.float32(0.0)
        off = 0
        for ei, g in enumerate(group_sizes):
            if g == 0:
                continue
            r, mg = _abft.gemm_checksum_ref(
                a[off:off + g], b[ei],
                b_gate[ei] if glu else None,
            )
            ref, mag = ref + r, mag + mg
            off += g
        res = _abft.verify(
            ns, res, chk, ref, mag, contract_dim=k, mode=mode
        )
    return res


@dataclasses.dataclass(frozen=True)
class _GroupedVjpCfg:
    group_sizes: Tuple[int, ...]
    glu: bool
    activation: Optional[str]
    out_scale: Optional[float]
    bm: Optional[int]
    bn: Optional[int]
    k_block_factor: Optional[int]
    interpret: Optional[bool]
    out_dtype: Any


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_core(cfg, a, b, b_gate, bias, gate_bias):
    return _grouped_impl(
        a, b, b_gate, cfg.group_sizes,
        bias=bias, gate_bias=gate_bias,
        activation=cfg.activation, out_scale=cfg.out_scale,
        bm=cfg.bm, bn=cfg.bn, k_block_factor=cfg.k_block_factor,
        interpret=cfg.interpret, out_dtype=cfg.out_dtype,
    )


def _grouped_core_fwd(cfg, a, b, b_gate, bias, gate_bias):
    out_dtype = cfg.out_dtype or a.dtype
    kw = dict(
        bm=cfg.bm, bn=cfg.bn, k_block_factor=cfg.k_block_factor,
        interpret=cfg.interpret,
    )
    # per-expert bias enters the kernel as (E, N); the preact paths fold it
    h_pre = g_pre = None
    if cfg.glu:
        h_pre, g_pre = _grouped_impl(
            a, b, b_gate, cfg.group_sizes,
            bias=bias, gate_bias=gate_bias,
            activation=None, out_scale=None, out_dtype=None, preact=True, **kw,
        )
        y = activation_fn(cfg.activation)(g_pre.astype(jnp.float32)) * (
            h_pre.astype(jnp.float32)
        )
    elif cfg.activation is not None:
        h_pre = _grouped_impl(
            a, b, None, cfg.group_sizes,
            bias=bias, gate_bias=None,
            activation=None, out_scale=None, out_dtype=None, **kw,
        )
        y = activation_fn(cfg.activation)(h_pre.astype(jnp.float32))
    else:
        out = _grouped_impl(
            a, b, None, cfg.group_sizes,
            bias=bias, gate_bias=None,
            activation=None, out_scale=cfg.out_scale,
            out_dtype=cfg.out_dtype, **kw,
        )
        y = None
    if y is not None:
        if cfg.out_scale is not None:
            y = y * cfg.out_scale
        out = y.astype(out_dtype)
    return out, (a, b, b_gate, h_pre, g_pre, bias, gate_bias)


def _grouped_core_bwd(cfg, saved, dy):
    a, b, b_gate, h_pre, g_pre, bias, gate_bias = saved
    interp = cfg.interpret
    gs = cfg.group_sizes
    dh, dg = _epilogue_cotangents(
        cfg.glu, cfg.activation, cfg.out_scale, h_pre, g_pre, dy
    )

    cdt = a.dtype
    dh_c = dh.astype(cdt)
    dg_c = dg.astype(cdt) if dg is not None else None

    da = _grouped_nt_with_fallback(
        dh_c, b, gs,
        dg_c, b_gate if dg_c is not None else None,
        interpret=interp,
    )
    if dg_c is not None:
        db, dbg = _grouped_tn_with_fallback(
            a, dh_c, gs, dg_c, interpret=interp
        )
    else:
        db = _grouped_tn_with_fallback(a, dh_c, gs, None, interpret=interp)
        dbg = None

    e_cnt = len(gs)
    seg = jnp.asarray(np.repeat(np.arange(e_cnt), gs), jnp.int32)
    dbias = None
    if bias is not None:
        dbias = jax.ops.segment_sum(dh, seg, num_segments=e_cnt).astype(
            bias.dtype
        )
    dgbias = None
    if gate_bias is not None:
        dgbias = jax.ops.segment_sum(dg, seg, num_segments=e_cnt).astype(
            gate_bias.dtype
        )
    return (
        da.astype(a.dtype),
        db.astype(b.dtype),
        dbg.astype(b_gate.dtype) if b_gate is not None else None,
        dbias,
        dgbias,
    )


_grouped_core.defvjp(_grouped_core_fwd, _grouped_core_bwd)


# ---------------------------------------------------------------------------
# grouped (MoE expert-stack) fused-optimizer VJPs — the ROADMAP "MoE
# fused-optimizer routing" item: a FusedParam-wrapped (E, K, N) expert stack
# routes here from `gemm_backend.grouped_matmul`/`grouped_glu_matmul`, and
# the backward runs `sfc_grouped_matmul_tn_update` — per-expert dW computed
# and AdamW-applied in one launch, the (E, K, N) weight-grad stack never
# written to HBM; empty experts run the g = 0 update in the same flush.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _GroupedUpdateVjpCfg:
    base: _GroupedVjpCfg
    fused: bool  # sfc_pallas grouped kernels vs the jnp oracle
    stochastic_round: bool


def _grouped_oracle_parts(cfg, a, b, b_gate, bias, gate_bias):
    """(callable, args) plain-jnp grouped primal for the unfused oracle."""
    glu = cfg.base.glu
    gs = cfg.base.group_sizes
    have_bias = bias is not None
    have_gbias = gate_bias is not None

    def one(ei, a_, w, vec):
        off = sum(gs[:ei])
        h = a_[off : off + gs[ei]] @ w[ei]
        if vec is not None:
            h = h + vec[ei]
        return h

    def prim(*args):
        it = iter(args)
        a_ = next(it)
        b_ = next(it)
        bg_ = next(it) if glu else None
        bi_ = next(it) if have_bias else None
        gb_ = next(it) if have_gbias else None
        parts = []
        for ei in range(len(gs)):
            h = one(ei, a_, b_, bi_)
            if glu:
                g = one(ei, a_, bg_, gb_)
                h = activation_fn(cfg.base.activation)(g) * h
            elif cfg.base.activation is not None:
                h = activation_fn(cfg.base.activation)(h)
            parts.append(h)
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    args = [a, b]
    if glu:
        args.append(b_gate)
    if have_bias:
        args.append(bias)
    if have_gbias:
        args.append(gate_bias)
    return prim, args


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_update_core(cfg, a, b, b_gate, bias, gate_bias, opt, hyper, token):
    del opt, hyper, token  # consumed by the backward rule only
    if not cfg.fused:
        prim, args = _grouped_oracle_parts(cfg, a, b, b_gate, bias, gate_bias)
        return prim(*args)
    return _grouped_impl(
        a, b, b_gate, cfg.base.group_sizes,
        bias=bias, gate_bias=gate_bias,
        activation=cfg.base.activation, out_scale=None,
        bm=cfg.base.bm, bn=cfg.base.bn,
        k_block_factor=cfg.base.k_block_factor,
        interpret=cfg.base.interpret, out_dtype=cfg.base.out_dtype,
    )


def _grouped_update_core_fwd(cfg, a, b, b_gate, bias, gate_bias, opt, hyper, token):
    del token
    if not cfg.fused:
        prim, args = _grouped_oracle_parts(cfg, a, b, b_gate, bias, gate_bias)
        y, f_vjp = jax.vjp(prim, *args)
        return y, (f_vjp, a, b, b_gate, bias, gate_bias, opt, hyper)
    out, saved = _grouped_core_fwd(cfg.base, a, b, b_gate, bias, gate_bias)
    a_, b_, bg_, h_pre, g_pre, bias_, gbias_ = saved
    return out, (a_, b_, bg_, h_pre, g_pre, bias_, gbias_, opt, hyper)


def _grouped_update_core_bwd(cfg, saved, dy):
    glu = cfg.base.glu
    gs = cfg.base.group_sizes
    if not cfg.fused:
        f_vjp, a, b, b_gate, bias, gate_bias, opt, hyper = saved
        cots = list(f_vjp(dy))
        da = cots.pop(0)
        dw = cots.pop(0)
        dwg = cots.pop(0) if glu else None
        dbias = cots.pop(0) if bias is not None else None
        dgbias = cots.pop(0) if gate_bias is not None else None
        if glu:
            ov, og = opt
            w_v, opt_v, sq_v = _oracle_update(cfg, dw, ov, b.dtype, hyper)
            w_g, opt_g, sq_g = _oracle_update(cfg, dwg, og, b_gate.dtype, hyper)
            return (
                da, w_v, w_g, dbias, dgbias,
                (opt_v, opt_g), jnp.zeros_like(hyper), (sq_v, sq_g),
            )
        w_n, opt_n, sq = _oracle_update(cfg, dw, opt, b.dtype, hyper)
        return da, w_n, None, dbias, dgbias, opt_n, jnp.zeros_like(hyper), sq

    a, b, b_gate, h_pre, g_pre, bias, gate_bias, opt, hyper = saved
    interp = cfg.base.interpret
    dh, dg = _epilogue_cotangents(
        glu, cfg.base.activation, None, h_pre, g_pre, dy
    )
    cdt = a.dtype
    dh_c = dh.astype(cdt)
    dg_c = dg.astype(cdt) if dg is not None else None

    da = _grouped_nt_with_fallback(
        dh_c, b, gs,
        dg_c, b_gate if dg_c is not None else None,
        interpret=interp,
    )

    def kernel():
        if dg_c is not None:
            if b_gate.dtype != b.dtype:
                # ladder degrades this config to the oracle, which keeps
                # per-weight dtypes instead of silently casting the gate
                raise NotImplementedError(
                    f"fused grouped GLU update requires matching weight "
                    f"dtypes, got value={b.dtype} gate={b_gate.dtype}"
                )
            (ov, og) = opt
            set_v, set_g = sfc_grouped_matmul_tn_update(
                a, dh_c, gs, ov[0], ov[1], ov[2], hyper,
                dg_c, og[0], og[1], og[2],
                param_dtype=b.dtype, stochastic_round=cfg.stochastic_round,
                interpret=interp,
            )
            wv, mv, muv, nuv, sqv = set_v
            wg, mg, mug, nug, sqg = set_g
            return (
                (wv, wg),
                ((mv, muv, nuv), (mg, mug, nug)),
                (sqv, sqg),
            )
        (mst, mu, nu) = opt
        w_n, mst_n, mu_n, nu_n, sq = sfc_grouped_matmul_tn_update(
            a, dh_c, gs, mst, mu, nu, hyper,
            param_dtype=b.dtype, stochastic_round=cfg.stochastic_round,
            interpret=interp,
        )
        return ((w_n, None), (mst_n, mu_n, nu_n), sq)

    def oracle():
        if dg_c is not None:
            dw, dwg = _jnp_grouped_tn(a, dh_c, gs, dg_c)
            ov, og = opt
            w_v, opt_v, sq_v = _oracle_update(cfg, dw, ov, b.dtype, hyper)
            w_g, opt_g, sq_g = _oracle_update(cfg, dwg, og, b_gate.dtype, hyper)
            return ((w_v, w_g), (opt_v, opt_g), (sq_v, sq_g))
        dw = _jnp_grouped_tn(a, dh_c, gs, None)
        w_n, opt_n, sq = _oracle_update(cfg, dw, opt, b.dtype, hyper)
        return ((w_n, None), opt_n, sq)

    from repro.robust import run_with_fallback

    w_cots, opt_cots, token_cots = run_with_fallback(
        NS_GROUPED_TN_UPDATE,
        ((RUNG_SFC_PALLAS, kernel), (RUNG_XLA, oracle)),
        shape_key=_bwd_shape_key(
            a.shape[-1], dh_c.shape[-1], a.shape[0], a.dtype
        ),
    )

    e_cnt = len(gs)
    seg = jnp.asarray(np.repeat(np.arange(e_cnt), gs), jnp.int32)
    dbias = None
    if bias is not None:
        dbias = jax.ops.segment_sum(dh, seg, num_segments=e_cnt).astype(
            bias.dtype
        )
    dgbias = None
    if gate_bias is not None:
        dgbias = jax.ops.segment_sum(dg, seg, num_segments=e_cnt).astype(
            gate_bias.dtype
        )
    return (
        da.astype(a.dtype), w_cots[0], w_cots[1], dbias, dgbias,
        opt_cots, jnp.zeros_like(hyper), token_cots,
    )


_grouped_update_core.defvjp(_grouped_update_core_fwd, _grouped_update_core_bwd)


def fused_update_grouped_matmul(
    x: jax.Array,  # (T, K) rows sorted by group
    w: jax.Array,  # (E, K, N) expert stack
    master: jax.Array,  # (E, K, N) f32
    mu: jax.Array,
    nu: jax.Array,
    hyper: jax.Array,  # (12,) f32
    token: jax.Array,
    group_sizes: Sequence[int],
    *,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    backend: str = RUNG_SFC_PALLAS,
    stochastic_round: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Grouped expert projection whose backward applies AdamW per expert in
    the grouped-TN flush: forward exactly like `sfc_grouped_matmul`, the
    cotangents of (w, master, mu, nu, token) carry the applied update —
    the (E, K, N) dW stack never exists in HBM, empty experts run the
    g = 0 update in the same launch."""
    cfg = _GroupedUpdateVjpCfg(
        base=_GroupedVjpCfg(
            group_sizes=tuple(int(g) for g in group_sizes),
            glu=False, activation=activation, out_scale=None,
            bm=None, bn=None, k_block_factor=None,
            interpret=interpret, out_dtype=None,
        ),
        fused=backend == RUNG_SFC_PALLAS,
        stochastic_round=stochastic_round,
    )
    return _grouped_update_core(
        cfg, x, w, None, bias, None, (master, mu, nu), hyper, token
    )


def fused_update_grouped_glu_matmul(
    x: jax.Array,  # (T, K) rows sorted by group
    w_gate: jax.Array,  # (E, K, N)
    w_val: jax.Array,  # (E, K, N)
    opt_gate: Tuple[jax.Array, jax.Array, jax.Array],
    opt_val: Tuple[jax.Array, jax.Array, jax.Array],
    hyper: jax.Array,
    tokens: Tuple[jax.Array, jax.Array],  # (token_val, token_gate)
    group_sizes: Sequence[int],
    *,
    activation: str = "silu",
    bias: Optional[jax.Array] = None,
    gate_bias: Optional[jax.Array] = None,
    backend: str = RUNG_SFC_PALLAS,
    stochastic_round: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Grouped gated expert MLP with both expert stacks' updates fused into
    one dual grouped-TN flush — the dispatched rows stream once for
    (dWv, dWg) and both AdamW updates."""
    cfg = _GroupedUpdateVjpCfg(
        base=_GroupedVjpCfg(
            group_sizes=tuple(int(g) for g in group_sizes),
            glu=True, activation=activation, out_scale=None,
            bm=None, bn=None, k_block_factor=None,
            interpret=interpret, out_dtype=None,
        ),
        fused=backend == RUNG_SFC_PALLAS,
        stochastic_round=stochastic_round,
    )
    return _grouped_update_core(
        cfg, x, w_val, w_gate, bias, gate_bias,
        (opt_val, opt_gate), hyper, tokens,
    )


def sfc_grouped_matmul(
    a: jax.Array,  # (T, K) rows sorted by group
    b: jax.Array,  # (E, K, N) per-group weights
    group_sizes: Sequence[int],
    *,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    out_scale: Optional[float] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    k_block_factor: Optional[int] = None,
    interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """Ragged grouped GEMM: ``out[rows of group e] = epilogue(a[rows of e] @
    b[e])``.

    ``group_sizes`` are *static* per-group row counts summing to ``a``'s row
    count (MoE callers know them at trace time: group×capacity).  Each
    group's rows are zero-padded to a ``bm`` multiple, the groups' tile
    grids are concatenated into one SFC task table (one gilbert map per
    group) and a single Pallas launch computes every expert's product —
    epilogue (per-expert ``bias`` (E, N), ``activation``, ``out_scale``)
    included; the valid rows are sliced back out.  Groups with zero rows
    are legal.

    Differentiable: the VJP runs the grouped NT/TN kernels (per-expert
    dA/dW in one launch each, ragged rows included).
    """
    cfg = _GroupedVjpCfg(
        group_sizes=tuple(int(g) for g in group_sizes),
        glu=False, activation=activation, out_scale=out_scale,
        bm=bm, bn=bn, k_block_factor=k_block_factor,
        interpret=interpret, out_dtype=out_dtype,
    )
    return _grouped_core(cfg, a, b, None, bias, None)


def sfc_grouped_glu_matmul(
    a: jax.Array,  # (T, K) rows sorted by group
    b_gate: jax.Array,  # (E, K, N) per-group gate weights
    b_val: jax.Array,  # (E, K, N) per-group value weights
    group_sizes: Sequence[int],
    *,
    activation: str = "silu",
    bias: Optional[jax.Array] = None,
    gate_bias: Optional[jax.Array] = None,
    out_scale: Optional[float] = None,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    k_block_factor: Optional[int] = None,
    interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """Ragged grouped gated-MLP: ``act(a@b_gate[e]) * (a@b_val[e])`` per
    group, one SFC traversal of the dispatched rows (dual-B grouped kernel).
    The MoE expert SwiGLU reads each row slab from HBM once instead of
    twice.  Differentiable via the dual grouped NT/TN backward kernels."""
    cfg = _GroupedVjpCfg(
        group_sizes=tuple(int(g) for g in group_sizes),
        glu=True, activation=activation, out_scale=out_scale,
        bm=bm, bn=bn, k_block_factor=k_block_factor,
        interpret=interpret, out_dtype=out_dtype,
    )
    return _grouped_core(cfg, a, b_val, b_gate, bias, gate_bias)
