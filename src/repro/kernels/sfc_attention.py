"""Pallas TPU kernels: SFC-scheduled flash attention (fwd/bwd) + decode.

The attention analogue of the SFC-CA GEMM stack (`kernels/sfc_gemm.py`):
every kernel here walks a **band task table** compiled by the unified
schedule compiler (`core.schedule.attention_spec` →
`compile_schedule`) through a scalar-prefetched grid, so

  * masked (q, k) tile pairs of the causal band are dropped from the task
    list entirely — no grid step, no copy, no predicated-off MXU slot
    (`kernels/flash_attention.py` keeps the dense grid and `pl.when`s the
    compute away; its copies still stream);
  * consecutive tasks share panels: within a band row the q (or k) panel
    is revisited task after task, and the boustrophedon row turns share
    one k (or q) panel — the BRGEMM₁/₂ structure of the GEMM traversal;
  * operands are read in the model's native ``(B, S, H, D)`` layout,
    viewed as ``(B, S, H·D)`` so that one head is one ``(chunk, D)`` lane
    block (`_heads_on_lanes`) — no head transpose, and GQA is resolved by
    the index maps too (a q head reads kv head ``h // group``), so grouped
    K/V are never `jnp.repeat`-expanded in HBM.

Three kernel families:

**Forward** — `sfc_flash_fwd`: online-softmax flash forward over the band,
q-row-major, emitting the output *and* the per-row logsumexp — the residual
the backward needs, which the forward-only legacy kernel throws away.

**Backward** — `sfc_flash_bwd_dq` / `sfc_flash_bwd_dkv`: the two
transpose-routed passes of the standard flash backward.  dQ walks the same
q-major band; dK/dV walks the *transposed* band (k-row-major, the NT/TN
move applied to attention) with the GQA group as an inner grid dimension so
a kv head's dK/dV tile accumulates over its group's q heads without ever
materializing per-q-head copies.  Sᵀ/Pᵀ never exist in HBM: the
transpositions are `dot_general` dimension numbers on resident (qc, kc)
tiles, exactly like `sfc_gemm_nt`/`sfc_gemm_tn` — and the (S, S) score
matrix never exists anywhere.

**Decode** — `sfc_decode_attention_pallas`: one batched launch for the
cached-KV GEMV-like contraction of a decode step.  Grid (B·Hkv, k-chunks)
with a **valid-length scalar-prefetch bound**: chunks past a sequence's
live cache length are predicated off and their fetches clamped to a legal
address — the same ragged-bound trick as the grouped-TN expert kernel.
The q rows of one kv head's whole GQA group form the tile's M extent, so
the per-head einsum fan-out of `models.layers.decode_attention` collapses
into a single `pallas_call`.

Knobs (q_chunk, k_chunk) resolve in `core.attention_backend` from the
``op="attn_fwd"/"attn_bwd"/"attn_decode"`` tune-cache namespaces.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import attention_spec, compile_schedule

__all__ = [
    "build_attention_task_table",
    "sfc_flash_fwd",
    "sfc_flash_bwd_dq",
    "sfc_flash_bwd_dkv",
    "sfc_decode_attention_pallas",
]

NEG = -1e30
_TINY = 1e-30


def build_attention_task_table(
    nq: int,
    nk: int,
    *,
    causal: bool,
    q_chunk: int,
    k_chunk: int,
    transpose: bool = False,
    q_offset: int = 0,
) -> np.ndarray:
    """(4, T) band task table for the (nq, nk) attention tile grid.

    Thin front-end over the unified schedule compiler
    (`repro.core.schedule.attention_spec`); kept so callers and tests can
    grab the raw table without building a spec by hand.

    ``causal`` bounds each q row's k extent at the diagonal (start-aligned
    convention: global q position ``q_offset + i`` attends k[0..q_offset+i],
    matching `ref.flash_attention_ref`); with ``transpose`` the table is
    k-row-major — rows (ik, iq, first, last), each k tile's band of
    contributing q tiles walked contiguously (the dK/dV traversal)."""
    spec = attention_spec(
        nq,
        nk,
        causal=causal,
        q_chunk=q_chunk,
        k_chunk=k_chunk,
        transpose=transpose,
        q_offset=q_offset,
    )
    return compile_schedule(spec).table


def _tile_mask(
    iq,
    ik,
    q_chunk: int,
    k_chunk: int,
    seq_q: int,
    seq_k: int,
    causal: bool,
    q_offset: int = 0,
):
    """(q_chunk, k_chunk) bool validity of one tile (padding + causal).

    ``q_offset`` shifts local q positions to global ones for the causal
    comparison (chunked prefill against a KV cache): local row i sits at
    global position ``q_offset + i`` and attends k[0..q_offset+i]."""
    qpos = iq * q_chunk + lax.broadcasted_iota(
        jnp.int32, (q_chunk, k_chunk), 0
    )
    kpos = ik * k_chunk + lax.broadcasted_iota(
        jnp.int32, (q_chunk, k_chunk), 1
    )
    valid = (kpos < seq_k) & (qpos < seq_q)
    if causal:
        valid = valid & (kpos <= qpos + q_offset)
    return valid


def _heads_on_lanes(x: jax.Array) -> jax.Array:
    """(B, S, H, D) -> (B, S, H*D), a free view of the native layout.

    Mosaic tiles the last two dimensions of a block, so a one-head block of
    (B, S, H, D) — second-minor extent 1 against H — is refused.  On this
    view one head is the lane block (1, chunk, D) at block index h."""
    b, s, h, d = x.shape
    return x.reshape(b, s, h * d)


def _stats_by_head(x: jax.Array) -> jax.Array:
    """(B, S, H, 1) row statistics -> (B, H, S, 1), so that one head's
    (chunk, 1) column is a legal block; the inverse is the same swap."""
    return jnp.swapaxes(x, 1, 2)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(
    tab_ref,  # (4, T) band task table
    q_ref,  # (1, qc, D)
    k_ref,  # (1, kc, D)
    v_ref,  # (1, kc, D)
    o_ref,  # (1, qc, D)
    lse_ref,  # (1, 1, qc, 1) f32
    acc_ref,  # (qc, D) f32
    m_ref,  # (qc, 1) f32
    l_ref,  # (qc, 1) f32
    *,
    scale: float,
    causal: bool,
    q_chunk: int,
    k_chunk: int,
    seq_q: int,
    seq_k: int,
    q_offset: int,
):
    t = pl.program_id(1)
    iq, ik = tab_ref[0, t], tab_ref[1, t]

    @pl.when(tab_ref[2, t] == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (qc, kc)
    valid = _tile_mask(
        iq, ik, q_chunk, k_chunk, seq_q, seq_k, causal, q_offset
    )
    s = jnp.where(valid, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)

    @pl.when(tab_ref[3, t] == 1)
    def _flush():
        l = jnp.maximum(l_ref[...], _TINY)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "seq_q", "seq_k", "q_chunk", "k_chunk", "q_offset",
        "interpret",
    ),
)
def sfc_flash_fwd(
    q: jax.Array,  # (B, Sq_p, H, D)
    k: jax.Array,  # (B, Sk_p, Hkv, D)
    v: jax.Array,  # (B, Sk_p, Hkv, D)
    *,
    causal: bool,
    seq_q: int,
    seq_k: int,
    q_chunk: int,
    k_chunk: int,
    q_offset: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Band-scheduled flash forward: returns (o, lse).

    ``lse`` is (B, Sq_p, H, 1) f32 — the logsumexp residual the custom VJP
    saves.  Padded rows (>= seq_q) carry a harmless sentinel; the backward
    masks them explicitly.  Requires Sq_p % q_chunk == Sk_p % k_chunk == 0
    (`core.attention_backend` pads).  ``q_offset`` shifts the causal band
    by a KV-cache offset (chunked prefill): local q row i is global row
    ``q_offset + i``."""
    b, sq_p, h, d = q.shape
    _, sk_p, hkv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    groups = h // hkv
    assert sq_p % q_chunk == 0 and sk_p % k_chunk == 0

    nq, nk = sq_p // q_chunk, sk_p // k_chunk
    sched = compile_schedule(
        attention_spec(
            nq, nk, causal=causal, q_chunk=q_chunk, k_chunk=k_chunk,
            q_offset=q_offset,
        )
    )
    tab = jnp.asarray(sched.table)
    maj, mnr = sched.selector("major"), sched.selector("minor")
    kernel = functools.partial(
        _flash_fwd_kernel,
        scale=1.0 / float(np.sqrt(d)),
        causal=causal,
        q_chunk=q_chunk,
        k_chunk=k_chunk,
        seq_q=seq_q,
        seq_k=seq_k,
        q_offset=q_offset,
    )

    def q_map(i, t, tab):
        return (i // h, maj(tab, t), i % h)

    def kv_map(i, t, tab):
        return (i // h, mnr(tab, t), (i % h) // groups)

    def stat_map(i, t, tab):
        return (i // h, i % h, maj(tab, t), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, tab.shape[1]),
        in_specs=[
            pl.BlockSpec((1, q_chunk, d), q_map),
            pl.BlockSpec((1, k_chunk, d), kv_map),
            pl.BlockSpec((1, k_chunk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, q_chunk, d), q_map),
            pl.BlockSpec((1, 1, q_chunk, 1), stat_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_chunk, d), jnp.float32),
            pltpu.VMEM((q_chunk, 1), jnp.float32),
            pltpu.VMEM((q_chunk, 1), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, sq_p, h * d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_p, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(tab, _heads_on_lanes(q), _heads_on_lanes(k), _heads_on_lanes(v))
    return o.reshape(b, sq_p, h, d), _stats_by_head(lse)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_p_ds(q, k, v, do, lse, delta, valid, *, scale: float):
    """Shared (p, ds) prelude of both backward kernels, all f32 in VMEM.

    p  = exp(scale·qkᵀ − lse) masked to the band (padded q rows carry a
         sentinel lse, so the mask — not the sentinel — zeroes them);
    ds = p ⊙ (do·vᵀ − delta), the score cotangent."""
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    dp = lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    return p, ds


def _flash_bwd_dq_kernel(
    tab_ref,
    q_ref,  # (1, qc, D)
    k_ref,  # (1, kc, D)
    v_ref,  # (1, kc, D)
    do_ref,  # (1, qc, D)
    lse_ref,  # (1, 1, qc, 1)
    delta_ref,  # (1, 1, qc, 1)
    dq_ref,  # (1, qc, D) f32
    acc_ref,  # (qc, D) f32
    *,
    scale: float,
    causal: bool,
    q_chunk: int,
    k_chunk: int,
    seq_q: int,
    seq_k: int,
    q_offset: int,
):
    t = pl.program_id(1)
    iq, ik = tab_ref[0, t], tab_ref[1, t]

    @pl.when(tab_ref[2, t] == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = _tile_mask(
        iq, ik, q_chunk, k_chunk, seq_q, seq_k, causal, q_offset
    )
    k = k_ref[0].astype(jnp.float32)
    _, ds = _bwd_p_ds(
        q_ref[0].astype(jnp.float32),
        k,
        v_ref[0].astype(jnp.float32),
        do_ref[0].astype(jnp.float32),
        lse_ref[0, 0],
        delta_ref[0, 0],
        valid,
        scale=scale,
    )
    acc_ref[...] += scale * lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(tab_ref[3, t] == 1)
    def _flush():
        dq_ref[0] = acc_ref[...]


def _flash_bwd_dkv_kernel(
    tab_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dk_ref,  # (1, kc, D) f32
    dv_ref,  # (1, kc, D) f32
    dk_acc,  # (kc, D) f32
    dv_acc,  # (kc, D) f32
    *,
    scale: float,
    causal: bool,
    groups: int,
    q_chunk: int,
    k_chunk: int,
    seq_q: int,
    seq_k: int,
    q_offset: int,
):
    t, g = pl.program_id(1), pl.program_id(2)
    ik, iq = tab_ref[0, t], tab_ref[1, t]

    @pl.when((tab_ref[2, t] == 1) & (g == 0))
    def _zero():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    valid = _tile_mask(
        iq, ik, q_chunk, k_chunk, seq_q, seq_k, causal, q_offset
    )
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    p, ds = _bwd_p_ds(
        q,
        k_ref[0].astype(jnp.float32),
        v_ref[0].astype(jnp.float32),
        do,
        lse_ref[0, 0],
        delta_ref[0, 0],
        valid,
        scale=scale,
    )
    # Pᵀ·dO and dSᵀ·Q as first-dim contractions on the resident (qc, kc)
    # tiles — the TN move; no transposed tile exists anywhere
    tn = (((0,), (0,)), ((), ()))
    dv_acc[...] += lax.dot_general(
        p, do, tn, preferred_element_type=jnp.float32
    )
    dk_acc[...] += scale * lax.dot_general(
        ds, q, tn, preferred_element_type=jnp.float32
    )

    @pl.when((tab_ref[3, t] == 1) & (g == groups - 1))
    def _flush():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "seq_q", "seq_k", "q_chunk", "k_chunk", "q_offset",
        "interpret",
    ),
)
def sfc_flash_bwd_dq(
    q: jax.Array,  # (B, Sq_p, H, D)
    k: jax.Array,  # (B, Sk_p, Hkv, D)
    v: jax.Array,
    do: jax.Array,  # (B, Sq_p, H, D)
    lse: jax.Array,  # (B, Sq_p, H, 1) f32
    delta: jax.Array,  # (B, Sq_p, H, 1) f32 rowsum(dO ⊙ O)
    *,
    causal: bool,
    seq_q: int,
    seq_k: int,
    q_chunk: int,
    k_chunk: int,
    q_offset: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """dQ over the q-major band table; returns (B, Sq_p, H, D) f32."""
    b, sq_p, h, d = q.shape
    _, sk_p, hkv, _ = k.shape
    groups = h // hkv
    nq, nk = sq_p // q_chunk, sk_p // k_chunk
    sched = compile_schedule(
        attention_spec(
            nq, nk, causal=causal, q_chunk=q_chunk, k_chunk=k_chunk,
            q_offset=q_offset,
        )
    )
    tab = jnp.asarray(sched.table)
    maj, mnr = sched.selector("major"), sched.selector("minor")
    kernel = functools.partial(
        _flash_bwd_dq_kernel,
        scale=1.0 / float(np.sqrt(d)),
        causal=causal,
        q_chunk=q_chunk,
        k_chunk=k_chunk,
        seq_q=seq_q,
        seq_k=seq_k,
        q_offset=q_offset,
    )

    def q_map(i, t, tab):
        return (i // h, maj(tab, t), i % h)

    def kv_map(i, t, tab):
        return (i // h, mnr(tab, t), (i % h) // groups)

    def stat_map(i, t, tab):
        return (i // h, i % h, maj(tab, t), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, tab.shape[1]),
        in_specs=[
            pl.BlockSpec((1, q_chunk, d), q_map),
            pl.BlockSpec((1, k_chunk, d), kv_map),
            pl.BlockSpec((1, k_chunk, d), kv_map),
            pl.BlockSpec((1, q_chunk, d), q_map),
            pl.BlockSpec((1, 1, q_chunk, 1), stat_map),
            pl.BlockSpec((1, 1, q_chunk, 1), stat_map),
        ],
        out_specs=pl.BlockSpec((1, q_chunk, d), q_map),
        scratch_shapes=[pltpu.VMEM((q_chunk, d), jnp.float32)],
    )
    dq = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, sq_p, h * d), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(
        tab, *map(_heads_on_lanes, (q, k, v, do)),
        _stats_by_head(lse), _stats_by_head(delta),
    )
    return dq.reshape(b, sq_p, h, d)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "seq_q", "seq_k", "q_chunk", "k_chunk", "q_offset",
        "interpret",
    ),
)
def sfc_flash_bwd_dkv(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    do: jax.Array,
    lse: jax.Array,
    delta: jax.Array,
    *,
    causal: bool,
    seq_q: int,
    seq_k: int,
    q_chunk: int,
    k_chunk: int,
    q_offset: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(dK, dV) over the k-major (transposed) band table.

    The GQA group is the innermost grid dimension: one kv head's (kc, D)
    accumulators stay resident while its ``groups`` q heads stream through,
    so dK/dV land in (B, Sk_p, Hkv, D) directly — no per-q-head dK copies,
    no reduction pass."""
    b, sq_p, h, d = q.shape
    _, sk_p, hkv, _ = k.shape
    groups = h // hkv
    nq, nk = sq_p // q_chunk, sk_p // k_chunk
    sched = compile_schedule(
        attention_spec(
            nq, nk, causal=causal, q_chunk=q_chunk, k_chunk=k_chunk,
            transpose=True, q_offset=q_offset,
        )
    )
    tab = jnp.asarray(sched.table)
    # transpose table: major = k tile, minor = q tile
    maj, mnr = sched.selector("major"), sched.selector("minor")
    kernel = functools.partial(
        _flash_bwd_dkv_kernel,
        scale=1.0 / float(np.sqrt(d)),
        causal=causal,
        groups=groups,
        q_chunk=q_chunk,
        k_chunk=k_chunk,
        seq_q=seq_q,
        seq_k=seq_k,
        q_offset=q_offset,
    )

    def q_map(i, t, g, tab):
        return (i // hkv, mnr(tab, t), (i % hkv) * groups + g)

    def kv_map(i, t, g, tab):
        return (i // hkv, maj(tab, t), i % hkv)

    def stat_map(i, t, g, tab):
        return (i // hkv, (i % hkv) * groups + g, mnr(tab, t), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, tab.shape[1], groups),
        in_specs=[
            pl.BlockSpec((1, q_chunk, d), q_map),
            pl.BlockSpec((1, k_chunk, d), kv_map),
            pl.BlockSpec((1, k_chunk, d), kv_map),
            pl.BlockSpec((1, q_chunk, d), q_map),
            pl.BlockSpec((1, 1, q_chunk, 1), stat_map),
            pl.BlockSpec((1, 1, q_chunk, 1), stat_map),
        ],
        out_specs=[
            pl.BlockSpec((1, k_chunk, d), kv_map),
            pl.BlockSpec((1, k_chunk, d), kv_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((k_chunk, d), jnp.float32),
            pltpu.VMEM((k_chunk, d), jnp.float32),
        ],
    )
    dk, dv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, sk_p, hkv * d), jnp.float32),
            jax.ShapeDtypeStruct((b, sk_p, hkv * d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )(
        tab, *map(_heads_on_lanes, (q, k, v, do)),
        _stats_by_head(lse), _stats_by_head(delta),
    )
    return dk.reshape(b, sk_p, hkv, d), dv.reshape(b, sk_p, hkv, d)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _decode_kernel(
    valid_ref,  # (B,) int32 live cache lengths (scalar prefetch)
    q_ref,  # (1, 1, Gp, D)
    k_ref,  # (1, kc, D)
    v_ref,  # (1, kc, D)
    o_ref,  # (1, 1, Gp, D)
    acc_ref,  # (Gp, D) f32
    m_ref,  # (Gp, 1) f32
    l_ref,  # (Gp, 1) f32
    *,
    scale: float,
    hkv: int,
    k_chunk: int,
    n_k: int,
    g_rows: int,
):
    i, kc = pl.program_id(0), pl.program_id(1)
    valid = valid_ref[i // hkv]

    @pl.when(kc == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # chunks past this sequence's live cache contribute nothing: the fetch
    # address is clamped in the index maps, the work predicated off here —
    # the grouped-TN ragged-bound trick applied to the KV cache
    @pl.when(kc * k_chunk < valid)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (Gp, D)
        k = k_ref[0].astype(jnp.float32)  # (kc, D)
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (Gp, kc)
        kpos = kc * k_chunk + lax.broadcasted_iota(
            jnp.int32, (g_rows, k_chunk), 1
        )
        s = jnp.where(kpos < valid, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)

    @pl.when(kc == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], _TINY)
        o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k_chunk", "interpret"))
def sfc_decode_attention_pallas(
    q: jax.Array,  # (B, Hkv, Gp, D) — GQA group rows per kv head, padded
    k: jax.Array,  # (B, T_p, Hkv, D) KV cache, cache layout as stored
    v: jax.Array,  # (B, T_p, Hkv, D)
    valid_len: jax.Array,  # (B,) int32 live lengths
    *,
    k_chunk: int,
    interpret: bool = False,
) -> jax.Array:
    """Single-launch decode attention against the cache.

    One grid row per (batch, kv head); the kv head's GQA group occupies the
    q tile's rows, and the cache is read *in its stored (B, T, Hkv, D)
    layout* through the index maps — no head expansion, no cache
    transpose.  Returns (B, Hkv, Gp, D)."""
    b, hkv, gp, d = q.shape
    _, t_p, _, _ = k.shape
    assert t_p % k_chunk == 0, (t_p, k_chunk)
    n_k = t_p // k_chunk

    def q_map(i, kc, valid):
        return (i // hkv, i % hkv, 0, 0)

    def kv_map(i, kc, valid):
        vb = valid[i // hkv]
        kmax = jnp.maximum((vb + k_chunk - 1) // k_chunk, 1)
        return (i // hkv, jnp.minimum(kc, kmax - 1), i % hkv)

    kernel = functools.partial(
        _decode_kernel,
        scale=1.0 / float(np.sqrt(d)),
        hkv=hkv,
        k_chunk=k_chunk,
        n_k=n_k,
        g_rows=gp,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, gp, d), q_map),
            pl.BlockSpec((1, k_chunk, d), kv_map),
            pl.BlockSpec((1, k_chunk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((gp, d), jnp.float32),
            pltpu.VMEM((gp, 1), jnp.float32),
            pltpu.VMEM((gp, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(valid_len.astype(jnp.int32), q, _heads_on_lanes(k), _heads_on_lanes(v))
