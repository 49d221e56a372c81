"""Pallas TPU flash attention — the kernel behind the `vmem_fused_attention`
regions declared in `models/layers.py` (scores/softmax never leave VMEM).

Grid: (batch*heads, q_chunks, k_chunks) with the k dimension innermost so the
(qc, D) f32 accumulator and the (qc, 1) online-softmax stats stay resident in
VMEM scratch across k steps.  Causal band skip: fully-masked k chunks are
`pl.when`-ed out (their copies still stream, but the MXU work is skipped —
the pure-JAX pair-list variant in models/layers.py removes even the copies).

ops-layer entry point: `flash_attention` (GQA expansion + padding + layout).
Oracle: `ref.flash_attention_ref`.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_pallas", "flash_attention"]

NEG = -1e30


def _flash_kernel(
    q_ref,  # (1, qc, D)
    k_ref,  # (1, kc, D)
    v_ref,  # (1, kc, D)
    o_ref,  # (1, qc, D)
    acc_ref,  # (qc, D) f32 scratch
    m_ref,  # (qc, 1) f32 scratch
    l_ref,  # (qc, 1) f32 scratch
    *,
    scale: float,
    causal: bool,
    q_chunk: int,
    k_chunk: int,
    n_k: int,
    seq_q: int,
    seq_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (qc, kc)
        qpos = qi * q_chunk + jax.lax.broadcasted_iota(jnp.int32, (q_chunk, k_chunk), 0)
        kpos = ki * k_chunk + jax.lax.broadcasted_iota(jnp.int32, (q_chunk, k_chunk), 1)
        valid = kpos < seq_k
        if causal:
            valid = valid & (kpos <= qpos)
        s = jnp.where(valid, s, NEG)

        m_prev = m_ref[...]  # (qc, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        l_cur = jnp.sum(p, axis=1, keepdims=True)
        alpha = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + l_cur

    # causal band: a k chunk fully above the diagonal contributes nothing —
    # it is needed iff its first k position <= the chunk's last q position.
    # (A previous revision computed this predicate into a dead local that
    # was always True; the band skip only worked by the accident of the
    # if/else below.  The predicate now *is* the guard.)
    if causal:
        needed = ki * k_chunk <= qi * q_chunk + q_chunk - 1
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "q_chunk", "k_chunk", "interpret")
)
def flash_attention_pallas(
    q: jax.Array,  # (BH, Sq, D)
    k: jax.Array,  # (BH, Sk, D)
    v: jax.Array,  # (BH, Sk, D)
    *,
    causal: bool = True,
    q_chunk: int = 128,
    k_chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, sk)
    nq = (sq + q_chunk - 1) // q_chunk
    nk = (sk + k_chunk - 1) // k_chunk
    sq_p, sk_p = nq * q_chunk, nk * k_chunk
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0)))

    kernel = functools.partial(
        _flash_kernel,
        scale=1.0 / math.sqrt(d),
        causal=causal,
        q_chunk=q_chunk,
        k_chunk=k_chunk,
        n_k=nk,
        seq_q=sq,
        seq_k=sk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_chunk, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, k_chunk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, k_chunk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_chunk, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_chunk, d), jnp.float32),
            pltpu.VMEM((q_chunk, 1), jnp.float32),
            pltpu.VMEM((q_chunk, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )(q, k, v)
    return out[:, :sq]


def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, T, Hkv, D)
    v: jax.Array,  # (B, T, Hkv, D)
    *,
    causal: bool = True,
    q_chunk: int = 128,
    k_chunk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """User-level wrapper: GQA head expansion + (B,S,H,D) layout."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, d = q.shape
    _, t, hkv, _ = k.shape
    groups = h // hkv
    kk = jnp.repeat(k, groups, axis=2)
    vv = jnp.repeat(v, groups, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = kk.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vf = vv.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    o = flash_attention_pallas(
        qf, kf, vf, causal=causal, q_chunk=q_chunk, k_chunk=k_chunk,
        interpret=interpret,
    )
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)
