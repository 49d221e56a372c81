"""Reference module of a tiny MoE decoder, for the test that a second
architecture enters the benchmark by files alone.

The program's ``family="moe"`` `DecoderLM`: the dense decoder's attention,
and in place of its MLP a router over ``n_experts`` gated experts, of which
each token takes the ``moe_top_k`` most probable (softmax, the chosen
probabilities renormalised).  The reference computes every expert on every
token and weighs each by its gate, 0 for those not chosen: dropless by
construction, so the configuration sets ``capacity_factor`` to ``n_experts /
moe_top_k``, at which the program drops no token either.
"""

from __future__ import annotations

import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, load_module, weights

dense = load_module(pathlib.Path(__file__).resolve().parents[2] / "references"
                    / "dense_decoder.py", "reference_dense_for_moe")
HI = dense.HI


def params(model: dict, seed: int):
    """Seeded weights, laid out as the program's MoE ``init``."""
    d, h, kv = model["d_model"], model["n_heads"], model["kv_heads"]
    hd, ff, e = model["head_dim"], model["d_ff"], model["n_experts"]
    dt = jnp.dtype(model["param_dtype"])

    def normal(key, shape, std=weights.WEIGHT_STD, mean=0.0):
        return (mean + std * jax.random.normal(key, shape)).astype(dt)

    def layer(key):
        ks = iter(jax.random.split(key, 12))
        return {
            "attn": {"wq": normal(next(ks), (d, h * hd)),
                     "wk": normal(next(ks), (d, kv * hd)),
                     "wv": normal(next(ks), (d, kv * hd)),
                     "wo": normal(next(ks), (h * hd, d)),
                     "q_norm": {"scale": normal(next(ks), (hd,), weights.NORM_STD, 1.0)},
                     "k_norm": {"scale": normal(next(ks), (hd,), weights.NORM_STD, 1.0)}},
            "norm1": {"scale": normal(next(ks), (d,), weights.NORM_STD, 1.0)},
            "norm2": {"scale": normal(next(ks), (d,), weights.NORM_STD, 1.0)},
            "moe": {"router": normal(next(ks), (d, e)),
                    "w_in": normal(next(ks), (e, d, ff)),
                    "w_gate": normal(next(ks), (e, d, ff)),
                    "w_out": normal(next(ks), (e, ff, d))},
        }

    def make(key):
        k_emb, k_head, k_norm, k_layers = jax.random.split(key, 4)
        return {"embed": normal(k_emb, (model["vocab"], d)),
                "layers": jax.lax.map(layer, jax.random.split(
                    k_layers, model["n_layers"])),
                "final_norm": {"scale": normal(k_norm, (d,), weights.NORM_STD, 1.0)},
                "head": normal(k_head, (d, model["vocab"]))}

    return jax.jit(make)(weights.root_key(seed))


@functools.partial(jax.jit, static_argnames=("model", "precision"))
def _layer(x, layers, index, *, model, precision):
    m = dict(model)
    h, kv, hd, k = m["n_heads"], m["kv_heads"], m["head_dim"], m["moe_top_k"]
    eps, mm = m["rms_norm_eps"], functools.partial(dense._matmul, precision=precision)
    p = jax.tree.map(lambda a: a[index], layers)
    b, s, _ = x.shape

    hn = dense._rmsnorm(x, p["norm1"]["scale"], eps)
    q = dense._rmsnorm(mm(hn, p["attn"]["wq"]).reshape(b, s, h, hd),
                       p["attn"]["q_norm"]["scale"], eps)
    kk = dense._rmsnorm(mm(hn, p["attn"]["wk"]).reshape(b, s, kv, hd),
                        p["attn"]["k_norm"]["scale"], eps)
    v = mm(hn, p["attn"]["wv"]).reshape(b, s, kv, hd)
    q, kk = dense._rope(q, m["rope_theta"]), dense._rope(kk, m["rope_theta"])
    kk, v = jnp.repeat(kk, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision=HI) / np.sqrt(hd)
    scores = jnp.where(np.tril(np.ones((s, s), bool)), scores, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, precision=HI)
    x = x + mm(a.reshape(b, s, h * hd), p["attn"]["wo"])

    hn = dense._rmsnorm(x, p["norm2"]["scale"], eps)
    probs = jax.nn.softmax(mm(hn, p["moe"]["router"]), -1)
    top, chosen = jax.lax.top_k(probs, k)
    gates = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1])
                    * (top / top.sum(-1, keepdims=True))[..., None], -2)
    experts = [mm(jax.nn.silu(mm(hn, p["moe"]["w_gate"][e])) * mm(hn, p["moe"]["w_in"][e]),
                  p["moe"]["w_out"][e]) for e in range(probs.shape[-1])]
    return x + jnp.einsum("bse,ebsd->bsd", gates, jnp.stack(experts), precision=HI)


def logits(params, model: dict, tokens, positions, *, precision="f32"):
    """Logits (B, P, V) in float32 at ``positions`` of ``tokens`` (B, S)."""
    frozen = tuple(sorted((k, v) for k, v in model.items()
                          if isinstance(v, (int, float, str, bool))))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(model["n_layers"]):
        x = _layer(x, params["layers"], i, model=frozen, precision=precision)
    return dense._head(x, params["final_norm"]["scale"], params["head"],
                       jnp.asarray(positions), model=frozen, precision=precision)


def _step(rows: int, head_rows: int, keys: float, model: dict, peaks) -> dict:
    """One prefill or decode step over ``rows`` token rows."""
    d, h, kv, hd = (model["d_model"], model["n_heads"], model["kv_heads"],
                    model["head_dim"])
    ff, e, k = model["d_ff"], model["n_experts"], model["moe_top_k"]
    routed = rows * k  # one row for each token and expert it chose
    dense_gemms = [flops.Gemm(rows, h * hd, d), flops.Gemm(rows, kv * hd, d),
                   flops.Gemm(rows, kv * hd, d), flops.Gemm(rows, d, h * hd),
                   flops.Gemm(rows, e, d)]
    up, down = flops.Gemm(routed, ff, d, n_b=2), flops.Gemm(routed, d, ff)
    # the grouped GEMMs read the weights of every expert that gets a row
    weight_bytes = (min(e, routed) - 1) * flops.BF16 * 3 * d * ff
    expert_s = max((up.flops + down.flops) / peaks["bf16_flops"],
                   (up.bytes + down.bytes + weight_bytes) / peaks["hbm_bytes_per_s"])
    head = flops.Gemm(head_rows, model["vocab"], d)
    n = model["n_layers"]
    return {
        "flops": (n * (sum(g.flops for g in dense_gemms) + up.flops + down.flops)
                  + head.flops + flops.attention_flops(rows, keys, model)),
        "gemm_least_s": (n * (sum(g.least_s(peaks) for g in dense_gemms) + expert_s)
                         + head.least_s(peaks)),
        "expert_rows": n * routed,
    }


def round_work(batch: int, prompt_len: int, max_new: int, model: dict,
               peaks) -> dict:
    """One closed-loop round: the prefill, then ``max_new - 1`` decode steps;
    the expert GEMMs counted per routed row."""
    steps = [_step(batch * prompt_len, batch, (prompt_len + 1) / 2, model, peaks)]
    steps += [_step(batch, batch, prompt_len + i + 1, model, peaks)
              for i in range(max_new - 1)]
    return {key: sum(s[key] for s in steps) for key in steps[0]}
