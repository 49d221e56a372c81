"""Plain reference of a dense decoder LM (Llama / Yi / Qwen3 layout).

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * norm1
                q, k, v = h Wq, h Wk, h Wv        (split into heads)
                q, k = rmsnorm(q) * q_norm, rmsnorm(k) * k_norm   (Qwen3 only)
                q, k = rope(q), rope(k)           (rotate-half, theta)
                a = softmax(q k^T / sqrt(hd) + causal) v   (kv head h // (H/Hkv))
                x = x + a Wo
                h = rmsnorm(x) * norm2
                x = x + (silu(h Wgate) * (h Win)) Wout
    logits = (rmsnorm(x) * final_norm) Whead

Everything in float32, matmuls at "highest" precision, computed layer by
layer so that it fits beside the served weights.  It imports nothing of the
program.  ``precision="fp8"`` is the control: every projection and head
matmul takes its operands rounded to float8 e4m3 (scaled per row of the
activations and per column of the weights, accumulation in float32), the
step a faster program would be tempted to take from bf16.

The configuration's weights (``params``) and work counts (``round_work``)
are the harness's dense ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, weights

params = weights.decoder_params
round_work = flops.round_work

HI = jax.lax.Precision.HIGHEST
RMS_EPS_DEFAULT = 1e-6
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(a, w, precision):
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HI)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """Rotate-half rotary embedding at positions 0..S-1; x (B, S, H, D)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("model", "precision"))
def _layer(x, layers, index, *, model, precision):
    m = dict(model)
    d, h, kv = m["d_model"], m["n_heads"], m["kv_heads"]
    hd = m.get("head_dim") or d // h
    eps = m.get("rms_norm_eps", RMS_EPS_DEFAULT)
    p = jax.tree.map(lambda a: a[index], layers)
    b, s, _ = x.shape

    hn = _rmsnorm(x, p["norm1"]["scale"], eps)
    q = _matmul(hn, p["attn"]["wq"], precision).reshape(b, s, h, hd)
    k = _matmul(hn, p["attn"]["wk"], precision).reshape(b, s, kv, hd)
    v = _matmul(hn, p["attn"]["wv"], precision).reshape(b, s, kv, hd)
    if "q_norm" in p["attn"]:
        q = _rmsnorm(q, p["attn"]["q_norm"]["scale"], eps)
        k = _rmsnorm(k, p["attn"]["k_norm"]["scale"], eps)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HI)
    x = x + _matmul(a.reshape(b, s, h * hd), p["attn"]["wo"], precision)

    hn = _rmsnorm(x, p["norm2"]["scale"], eps)
    gate = _matmul(hn, p["mlp"]["w_gate"], precision)
    up = _matmul(hn, p["mlp"]["w_in"], precision)
    return x + _matmul(jax.nn.silu(gate) * up, p["mlp"]["w_out"], precision)


@functools.partial(jax.jit, static_argnames=("model", "precision"))
def _head(x, final_norm, head, positions, *, model, precision):
    eps = dict(model).get("rms_norm_eps", RMS_EPS_DEFAULT)
    x = _rmsnorm(x[:, positions], final_norm, eps)
    return _matmul(x, head, precision)


def logits(params, model: dict, tokens, positions, *, precision="f32"):
    """Logits (B, P, V) in float32 at ``positions`` (P,) of ``tokens``
    (B, S), from one full causal pass over each row."""
    frozen = tuple(sorted((k, v) for k, v in model.items()
                          if isinstance(v, (int, float, str, bool))))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(model["n_layers"]):
        x = _layer(x, params["layers"], i, model=frozen, precision=precision)
    return _head(x, params["final_norm"]["scale"], params["head"],
                 jnp.asarray(positions), model=frozen, precision=precision)
