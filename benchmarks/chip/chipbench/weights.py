"""Weights and operands made on the device from ``--seed``.

The benchmark makes the weights itself, so that its reference takes nothing
the program made.  A configuration's reference module names the function
that makes them (``params``); they are laid out as the program's decoder
expects them (`serve.Cell` checks the tree against the program's own
``init``) and made in one jitted call, layer by layer inside it
(`lax.map`), in the dtype they are served in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# projection weights ~ N(0, 0.02^2), the common initializer range of the
# published decoders; norm scales ~ 1 + N(0, 0.1^2), so that a norm whose
# scale is dropped or misread changes the result
WEIGHT_STD = 0.02
NORM_STD = 0.1


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any whole ``seed`` up to 2**62: its low 31 bits seed
    the key and the bits above are folded in."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 62:
        raise ValueError(f"--seed must lie in [0, 2**62), got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31), seed >> 31)


def host_rng(seed: int, stream: str) -> np.random.Generator:
    """A NumPy generator for one named use of ``seed`` (traffic, sampling)."""
    words = [int(seed) & 0xFFFFFFFF, int(seed) >> 32] + [ord(c) for c in stream]
    return np.random.default_rng(words)


def _normal(key, shape, dtype, std=WEIGHT_STD, mean=0.0):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def decoder_params(model: dict, seed: int):
    """The parameters of a dense decoder LM of sizes ``model``."""
    d, h, kv = model["d_model"], model["n_heads"], model["kv_heads"]
    hd = model.get("head_dim") or d // h
    ff, vocab, n_layers = model["d_ff"], model["vocab"], model["n_layers"]
    dt = jnp.dtype(model.get("param_dtype", "bfloat16"))
    qk_norm = bool(model.get("qk_norm"))

    def layer(key):
        ks = iter(jax.random.split(key, 11))
        attn = {
            "wq": _normal(next(ks), (d, h * hd), dt),
            "wk": _normal(next(ks), (d, kv * hd), dt),
            "wv": _normal(next(ks), (d, kv * hd), dt),
            "wo": _normal(next(ks), (h * hd, d), dt),
        }
        if qk_norm:
            attn["q_norm"] = {"scale": _normal(next(ks), (hd,), dt, NORM_STD, 1.0)}
            attn["k_norm"] = {"scale": _normal(next(ks), (hd,), dt, NORM_STD, 1.0)}
        else:
            next(ks), next(ks)
        return {
            "attn": attn,
            "norm1": {"scale": _normal(next(ks), (d,), dt, NORM_STD, 1.0)},
            "norm2": {"scale": _normal(next(ks), (d,), dt, NORM_STD, 1.0)},
            "mlp": {
                "w_in": _normal(next(ks), (d, ff), dt),
                "w_out": _normal(next(ks), (ff, d), dt),
                "w_gate": _normal(next(ks), (d, ff), dt),
            },
        }

    def make(key):
        k_emb, k_head, k_norm, k_layers = jax.random.split(key, 4)
        return {
            "embed": _normal(k_emb, (vocab, d), dt),
            "layers": jax.lax.map(layer, jax.random.split(k_layers, n_layers)),
            "final_norm": {"scale": _normal(k_norm, (d,), dt, NORM_STD, 1.0)},
            "head": _normal(k_head, (d, vocab), dt),
        }

    return jax.jit(make)(root_key(seed))
