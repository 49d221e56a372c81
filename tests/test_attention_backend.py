"""SFC-native attention backend: differential + structural suite.

Differential: the band-scheduled flash forward against
`ref.flash_attention_ref`, its custom-VJP grads against XLA autodiff
(rtol 1e-4 at f32), the single-launch decode kernel against
`models.layers.decode_attention` — across causal/non-causal, GQA head
ratios, ragged/padded sequence lengths and bf16 inputs.

Structural: with ``attn_impl="sfc"`` and the sfc_pallas GEMM backend a
full train step's forward+backward jaxpr contains **zero** dot_general
(the attention extension of PR 3's projection gate); a decode step's
attention runs in exactly one Pallas launch; the kernels consult the
``attn_fwd``/``attn_bwd``/``attn_decode`` tune namespaces.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import attention_backend as ab
from repro.kernels.ref import flash_attention_ref
from repro.kernels.sfc_attention import build_attention_task_table
from repro.models.layers import decode_attention as decode_ref
from repro.testing.jaxpr import op_census


def _rand(*shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng([seed, *[int(s) for s in shape]])
    return jnp.asarray(rng.normal(size=shape), dtype)


def _qkv(b, s, t, h, hkv, d, dtype=jnp.float32, seed=0):
    return (
        _rand(b, s, h, d, dtype=dtype, seed=seed),
        _rand(b, t, hkv, d, dtype=dtype, seed=seed + 1),
        _rand(b, t, hkv, d, dtype=dtype, seed=seed + 2),
    )


def _count(fn, *args):
    return op_census(jax.make_jaxpr(fn)(*args).jaxpr)


# ---------------------------------------------------------------------------
# task table
# ---------------------------------------------------------------------------


def test_band_table_drops_masked_tiles():
    """Causal tiles strictly above the diagonal are absent from the table —
    not pl.when-skipped — and each q row's tasks are contiguous with
    correct first/last flags."""
    tab = build_attention_task_table(
        4, 4, causal=True, q_chunk=16, k_chunk=16
    )
    # band row i has i+1 tiles -> 1+2+3+4 tasks, not 16
    assert tab.shape[1] == 10
    for t in range(tab.shape[1]):
        iq, ik = tab[0, t], tab[1, t]
        assert ik <= iq  # nothing above the diagonal
    # row-contiguity + flags
    rows = tab[0]
    changes = np.nonzero(np.diff(rows))[0]
    assert (np.sort(np.unique(rows)) == np.arange(4)).all()
    assert tab[2, 0] == 1 and tab[3, -1] == 1
    for c in changes:
        assert tab[3, c] == 1 and tab[2, c + 1] == 1


def test_band_table_serpentine_shares_boundary_panels():
    """Consecutive rows walk k in alternating directions, so at least one
    row boundary reuses the k panel of the previous task's neighbourhood
    (the boustrophedon quadrant-hop)."""
    tab = build_attention_task_table(
        4, 4, causal=False, q_chunk=16, k_chunk=16
    )
    assert tab.shape[1] == 16
    ks = tab[1].reshape(4, 4)
    assert (ks[0] == np.arange(4)).all()
    assert (ks[1] == np.arange(4)[::-1]).all()  # flipped row
    # boundary: last k of row 0 == first k of row 1
    assert ks[0, -1] == ks[1, 0]


def test_transpose_table_covers_causal_band():
    fwd = build_attention_task_table(3, 5, causal=True, q_chunk=32, k_chunk=16)
    bwd = build_attention_task_table(
        3, 5, causal=True, q_chunk=32, k_chunk=16, transpose=True
    )
    pairs_f = {(int(tab_q), int(tab_k)) for tab_q, tab_k in zip(fwd[0], fwd[1])}
    pairs_b = {(int(tab_q), int(tab_k)) for tab_k, tab_q in zip(bwd[0], bwd[1])}
    assert pairs_f == pairs_b


# ---------------------------------------------------------------------------
# forward differential
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "b,s,t,h,hkv,d",
    [
        (2, 32, 32, 4, 4, 16),  # MHA, chunk-aligned
        (2, 33, 33, 4, 2, 16),  # GQA 2:1, ragged seq
        (1, 16, 48, 8, 2, 8),   # GQA 4:1, cross-shaped (Sq != Sk)
        (1, 40, 24, 6, 6, 32),  # q longer than k, non-pow2 heads
    ],
)
def test_flash_fwd_matches_ref(causal, b, s, t, h, hkv, d):
    q, k, v = _qkv(b, s, t, h, hkv, d)
    got = ab.flash_attention(q, k, v, causal=causal, q_chunk=16, k_chunk=16)
    want = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_flash_fwd_bf16():
    q, k, v = _qkv(2, 33, 33, 4, 2, 16, dtype=jnp.bfloat16)
    got = ab.flash_attention(q, k, v, causal=True, q_chunk=16, k_chunk=16)
    want = flash_attention_ref(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2,
    )


# ---------------------------------------------------------------------------
# backward differential
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "b,s,t,h,hkv,d",
    [
        (2, 32, 32, 4, 4, 16),
        (2, 33, 33, 4, 2, 16),
        (1, 16, 48, 8, 2, 8),
    ],
)
def test_flash_grads_match_xla(causal, b, s, t, h, hkv, d):
    """custom-VJP dQ/dK/dV kernels vs XLA autodiff of the dense reference
    at f32 rtol 1e-4 — GQA included (dK/dV sum over the head group)."""
    q, k, v = _qkv(b, s, t, h, hkv, d)
    w = _rand(b, s, h, d, seed=9)

    def f_sfc(q, k, v):
        o = ab.flash_attention(q, k, v, causal=causal, q_chunk=16, k_chunk=16)
        return jnp.sum(o.astype(jnp.float32) * w)

    def f_ref(q, k, v):
        o = flash_attention_ref(q, k, v, causal=causal)
        return jnp.sum(o.astype(jnp.float32) * w)

    gs = jax.grad(f_sfc, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(gs, gx, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5,
            err_msg=f"d{name} causal={causal}",
        )


def test_flash_grad_is_three_pallas_launches_no_dots():
    """grad(flash) = fwd + dQ + dK/dV launches, zero dot_general — the
    attention analogue of the NT/TN structural gate."""
    q, k, v = _qkv(1, 32, 32, 4, 2, 16)
    c = _count(
        lambda q, k, v: ab.flash_attention(
            q, k, v, causal=True, q_chunk=16, k_chunk=16
        ).sum(),
        q, k, v,
    )
    assert c["pallas"] == 1 and c["dot"] == 0
    c = _count(
        jax.grad(
            lambda q, k, v: ab.flash_attention(
                q, k, v, causal=True, q_chunk=16, k_chunk=16
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ),
        q, k, v,
    )
    assert c["dot"] == 0, f"attention backward fell back: {c['dot_shapes']}"
    assert c["pallas"] == 3, f"expected fwd+dQ+dKV launches, saw {c['pallas']}"


# ---------------------------------------------------------------------------
# chunked prefill (q_offset)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("off", [16, 40])
def test_flash_fwd_q_offset_matches_full_causal(off):
    """A q block at global rows [off, off+s) with q_offset=off reproduces
    the matching slice of full-sequence causal attention — the chunked
    prefill identity."""
    b, t, h, hkv, d, s = 2, 96, 4, 2, 32, 32
    q_full, k, v = _qkv(b, t, t, h, hkv, d)
    want = flash_attention_ref(q_full, k, v, causal=True)
    got = ab.flash_attention(
        q_full[:, off : off + s], k, v, causal=True,
        q_chunk=16, k_chunk=16, q_offset=off,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want[:, off : off + s]),
        rtol=1e-5, atol=1e-5,
    )


def test_flash_fwd_q_offset_zero_is_identity():
    q, k, v = _qkv(1, 33, 33, 4, 2, 16)
    base = ab.flash_attention(q, k, v, causal=True, q_chunk=16, k_chunk=16)
    off0 = ab.flash_attention(
        q, k, v, causal=True, q_chunk=16, k_chunk=16, q_offset=0
    )
    np.testing.assert_array_equal(np.asarray(base), np.asarray(off0))


def test_flash_q_offset_grads_match_xla():
    """custom-VJP grads at a nonzero KV-cache offset vs XLA autodiff of an
    offset-masked dense reference, f32 rtol 1e-4."""
    b, s, t, h, hkv, d, off = 1, 32, 80, 4, 2, 16, 40
    q, k, v = _qkv(b, s, t, h, hkv, d)
    w = _rand(b, s, h, d, seed=9)

    def f_sfc(q, k, v):
        o = ab.flash_attention(
            q, k, v, causal=True, q_chunk=16, k_chunk=16, q_offset=off
        )
        return jnp.sum(o.astype(jnp.float32) * w)

    def f_ref(q, k, v):
        kr = jnp.repeat(k, h // hkv, axis=2)
        vr = jnp.repeat(v, h // hkv, axis=2)
        sc = jnp.einsum(
            "bqhd,bkhd->bhqk", q, kr, preferred_element_type=jnp.float32
        ) / np.sqrt(d)
        mask = (
            jnp.arange(t)[None, :] <= jnp.arange(s)[:, None] + off
        )
        sc = jnp.where(mask[None, None], sc, -1e30)
        o = jnp.einsum(
            "bhqk,bkhd->bqhd",
            jax.nn.softmax(sc, axis=-1),
            vr.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return jnp.sum(o * w)

    gs = jax.grad(f_sfc, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(gs, gx, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5,
            err_msg=f"d{name} q_offset={off}",
        )


def test_flash_q_offset_negative_rejected():
    q, k, v = _qkv(1, 16, 16, 2, 2, 8)
    with pytest.raises(ValueError, match="q_offset"):
        ab.flash_attention(q, k, v, causal=True, q_offset=-1)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,t,h,hkv,d,valids",
    [
        (3, 40, 8, 2, 16, (1, 17, 40)),   # ragged live lengths
        (2, 32, 4, 4, 8, (32, 5)),        # MHA
        (1, 64, 16, 2, 32, (33,)),        # deep GQA 8:1
    ],
)
def test_decode_matches_ref(b, t, h, hkv, d, valids):
    q = _rand(b, 1, h, d)
    k = _rand(b, t, hkv, d, seed=1)
    v = _rand(b, t, hkv, d, seed=2)
    valid = jnp.asarray(valids, jnp.int32)
    got = ab.decode_attention(q, k, v, valid)
    want = decode_ref(q, k, v, valid)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_decode_is_single_pallas_launch():
    """The whole (batch, head) decode fan-out is ONE pallas_call — no
    per-head einsum fan-out, no dot_general."""
    q = _rand(2, 1, 8, 16)
    k = _rand(2, 32, 2, 16, seed=1)
    v = _rand(2, 32, 2, 16, seed=2)
    valid = jnp.asarray([5, 32], jnp.int32)
    c = _count(lambda q, k, v: ab.decode_attention(q, k, v, valid), q, k, v)
    assert c["pallas"] == 1, f"decode used {c['pallas']} launches"
    assert c["dot"] == 0, f"decode fell back to dot_general: {c['dot_shapes']}"


def test_model_decode_step_single_attention_launch_per_layer():
    """`attention_decode` under attn_impl='sfc' launches exactly one Pallas
    kernel for the attention math (projections pinned to xla here so the
    count isolates attention)."""
    from repro.models import attention as attn

    cfg = _tiny_cfg()
    p = attn.attention_init(
        jax.random.PRNGKey(0), d_model=cfg.d_model, n_heads=cfg.n_heads,
        kv_heads=cfg.kv_heads, head_dim=cfg.head_dim_,
    )
    x = _rand(2, 1, cfg.d_model)
    cache = {
        "k": jnp.zeros((2, 32, cfg.kv_heads, cfg.head_dim_)),
        "v": jnp.zeros((2, 32, cfg.kv_heads, cfg.head_dim_)),
    }
    idx = jnp.asarray(3, jnp.int32)

    def step(x, cache):
        o, _ = attn.attention_decode(
            p, x, cache, idx,
            n_heads=cfg.n_heads, kv_heads=cfg.kv_heads, attn_impl="sfc",
        )
        return o

    c = _count(step, x, cache)
    assert c["pallas"] == 1
    # remaining dots are the xla projections (rank-2 weights) only
    for shp in c["dot_shapes"]:
        assert any(len(op) == 2 for op in shp), shp


# ---------------------------------------------------------------------------
# full-model structural gates
# ---------------------------------------------------------------------------


def _tiny_cfg(**kw):
    return ArchConfig(
        name="tiny_sfc_attn", family="dense", n_layers=2, d_model=32,
        n_heads=4, kv_heads=2, d_ff=48, vocab=64, head_dim=8,
        param_dtype="float32", q_chunk=16, k_chunk=16, attn_impl="sfc",
        **kw,
    )


def test_train_step_jaxpr_is_dot_general_free():
    """Acceptance: with attn_impl='sfc' + the sfc_pallas GEMM backend, the
    FULL forward+backward train-step jaxpr contains zero dot_general —
    attention scores included (PR 3 only gated rank-2 projections)."""
    from repro.models.registry import build_model
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.train.step import BackendConfig, make_train_step

    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
    }
    step = make_train_step(
        model, AdamWConfig(lr=1e-3), remat="none", backend=BackendConfig(gemm_backend="sfc_pallas"))
    jx = jax.make_jaxpr(step)(params, adamw_init(params), batch)
    c = op_census(jx.jaxpr)
    assert c["pallas"] > 0
    assert c["dot"] == 0, (
        f"dot_general survived the SFC train step: {c['dot_shapes']}"
    )


def test_train_step_grads_match_xla_with_sfc_attention():
    """Numerics: the dot_general-free step advances params identically to
    the XLA/blockwise step at f32."""
    from repro.models.registry import build_model
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.train.step import BackendConfig, make_train_step

    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
    }
    opt = AdamWConfig(lr=1e-3)
    step_s = make_train_step(
        model, opt, remat="none", backend=BackendConfig(gemm_backend="sfc_pallas"))
    step_x = make_train_step(
        model, opt, remat="none", backend=BackendConfig(gemm_backend="xla", attn_impl="blockwise"))
    p_s, _, m_s = step_s(params, adamw_init(params), batch)
    p_x, _, m_x = step_x(params, adamw_init(params), batch)
    np.testing.assert_allclose(
        float(m_s["loss"]), float(m_x["loss"]), rtol=1e-4
    )
    for ls, lx in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_x)):
        np.testing.assert_allclose(
            np.asarray(ls), np.asarray(lx), rtol=1e-4, atol=1e-5
        )


def test_attention_backend_context_overrides_config():
    """The contextvar pin (make_train_step's attn_impl=...) wins over the
    per-call config value at trace time."""
    q, k, v = _qkv(1, 16, 16, 2, 2, 8)
    from repro.models.attention import _attend

    with ab.attention_backend("sfc"):
        c = _count(
            lambda q, k, v: _attend(
                q, k, v, causal=True, q_chunk=16, k_chunk=16,
                attn_impl="blockwise",
            ).sum(),
            q, k, v,
        )
    assert c["pallas"] == 1 and c["dot"] == 0
    with pytest.raises(ValueError):
        ab.attention_backend("nope").__enter__()


def test_prefill_respects_attn_impl():
    """Regression (bugfix): attention_prefill previously hardwired
    blockwise_attention regardless of attn_impl."""
    from repro.models import attention as attn

    cfg = _tiny_cfg()
    p = attn.attention_init(
        jax.random.PRNGKey(0), d_model=cfg.d_model, n_heads=cfg.n_heads,
        kv_heads=cfg.kv_heads, head_dim=cfg.head_dim_,
    )
    x = _rand(2, 16, cfg.d_model)

    def prefill(x):
        o, _ = attn.attention_prefill(
            p, x, n_heads=cfg.n_heads, kv_heads=cfg.kv_heads, cache_len=32,
            q_chunk=16, k_chunk=16, attn_impl="sfc",
        )
        return o

    c = _count(prefill, x)
    assert c["pallas"] == 1, "prefill ignored attn_impl='sfc'"
    # and the two impls agree numerically
    o_sfc, cache_sfc = attn.attention_prefill(
        p, x, n_heads=cfg.n_heads, kv_heads=cfg.kv_heads, cache_len=32,
        q_chunk=16, k_chunk=16, attn_impl="sfc",
    )
    o_blk, cache_blk = attn.attention_prefill(
        p, x, n_heads=cfg.n_heads, kv_heads=cfg.kv_heads, cache_len=32,
        q_chunk=16, k_chunk=16, attn_impl="blockwise",
    )
    np.testing.assert_allclose(
        np.asarray(o_sfc), np.asarray(o_blk), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(cache_sfc["k"]), np.asarray(cache_blk["k"]), rtol=1e-6
    )


def test_serving_prefill_decode_agree_across_impls():
    """End-to-end model prefill+decode under attn_impl='sfc' matches the
    blockwise implementation (greedy tokens identical)."""
    from repro.models.registry import build_model

    outs = {}
    for impl in ("blockwise", "sfc"):
        cfg = dataclasses.replace(_tiny_cfg(), attn_impl=impl)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
        logits, cache = model.prefill(params, tokens, cache_len=24, remat="none")
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        seq = [np.asarray(tok)]
        for _ in range(3):
            logits, cache = model.decode_step(params, tok, cache)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            seq.append(np.asarray(tok))
        outs[impl] = np.concatenate(seq, axis=1)
    np.testing.assert_array_equal(outs["sfc"], outs["blockwise"])


# ---------------------------------------------------------------------------
# tune-namespace integration
# ---------------------------------------------------------------------------


def test_attn_tune_namespaces_consulted(tmp_path, monkeypatch):
    """flash_attention resolves op='attn_fwd' (and the backward
    op='attn_bwd') from the tune cache; a cached winner steers the chunk
    knobs without changing the numbers."""
    import repro.tune
    import repro.tune.tuner as tuner
    from repro.tune import Knobs

    monkeypatch.setenv("REPRO_SFC_TUNE_CACHE", str(tmp_path / "knobs.json"))
    tuner._DEFAULT_CACHE = None
    try:
        cache = tuner.default_cache()
        cache.put(
            64, 64, 16, np.float32, "cpu",
            Knobs(bm=32, bn=16, k_layers=1, k_block_factor=1), op="attn_fwd",
        )
        seen = []
        real = repro.tune.lookup_knobs

        def spy(m_, n_, k_, dtype, **kw):
            hit = real(m_, n_, k_, dtype, **kw)
            seen.append(((m_, n_, k_), kw.get("op"), hit))
            return hit

        monkeypatch.setattr(repro.tune, "lookup_knobs", spy)
        q, k, v = _qkv(1, 64, 64, 2, 2, 16)
        want = flash_attention_ref(q, k, v, causal=True)
        got = ab.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )
        fwd_hits = [hit for (_, op, hit) in seen if op == "attn_fwd"]
        assert fwd_hits and fwd_hits[0] is not None
        assert fwd_hits[0].bm == 32 and fwd_hits[0].bn == 16

        jax.grad(
            lambda q: ab.flash_attention(q, k, v, causal=True).sum()
        )(q)
        assert any(op == "attn_bwd" for (_, op, _) in seen)
    finally:
        tuner._DEFAULT_CACHE = None


def test_attn_cached_winner_overrides_config_hint(tmp_path, monkeypatch):
    """Model configs always pass q_chunk/k_chunk, so the measured winner
    must take precedence over the hint — a hint-wins rule would leave the
    whole attn tuning pipeline inert for every model path (regression)."""
    import repro.tune.tuner as tuner
    from repro.tune import Knobs

    monkeypatch.setenv("REPRO_SFC_TUNE_CACHE", str(tmp_path / "knobs.json"))
    tuner._DEFAULT_CACHE = None
    try:
        tuner.default_cache().put(
            64, 64, 16, np.float32, "cpu",
            Knobs(bm=32, bn=16, k_layers=1, k_block_factor=1), op="attn_fwd",
        )
        qc, kc = ab.resolve_attn_knobs(
            64, 64, 16, jnp.float32, op="attn_fwd", q_chunk=64, k_chunk=64
        )
        assert (qc, kc) == (32, 16), "cached winner lost to the config hint"
        # no winner -> the hint stands
        qc, kc = ab.resolve_attn_knobs(
            64, 64, 16, jnp.float32, op="attn_bwd", q_chunk=64, k_chunk=64
        )
        assert (qc, kc) == (64, 64)
        # and the full model path picks the winner up (flash_attention
        # receives the config chunks yet launches with the tuned ones)
        q, k, v = _qkv(1, 64, 64, 2, 2, 16)
        want = flash_attention_ref(q, k, v, causal=True)
        got = ab.flash_attention(q, k, v, causal=True, q_chunk=64, k_chunk=64)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )
    finally:
        tuner._DEFAULT_CACHE = None


def test_tune_gemm_measures_attn_namespaces(tmp_path, monkeypatch):
    """tune_gemm accepts the attn namespaces end-to-end (simulator-scored
    on CPU) and persists winners the resolver can read back."""
    from repro.tune import KnobCache, lookup_knobs, tune_gemm

    monkeypatch.setenv("REPRO_SFC_TUNE_CACHE", str(tmp_path / "k.json"))
    import repro.tune.tuner as tuner

    tuner._DEFAULT_CACHE = None
    try:
        cache = KnobCache(str(tmp_path / "k.json"))

        def fake_measure(m, n, k, dtype, knobs, *, op="gemm"):
            return float(knobs.bm + knobs.bn)  # deterministic argmin

        for op in ("attn_fwd", "attn_bwd", "attn_decode"):
            got = tune_gemm(
                64, 64, 16, np.float32, cache=cache,
                measure_fn=fake_measure, op=op,
            )
            assert got.source == "measured"
            hit = lookup_knobs(64, 64, 16, np.float32, cache=cache, op=op)
            assert hit is not None and hit.bm == got.bm
    finally:
        tuner._DEFAULT_CACHE = None


def test_serving_tune_table_includes_attn_rows():
    from repro.models.registry import build_model  # noqa: F401
    from repro.serving.engine import ServingEngine

    cfg = _tiny_cfg()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(
        cfg, params, max_batch=2, max_seq=32, gemm_backend="sfc_pallas"
    )
    table = eng.tune_table(16, backward=True)
    ops = [op for (op, *_ ) in table]
    assert "attn_fwd" in ops and "attn_bwd" in ops and "attn_decode" in ops
    decode_row = [r for r in table if r[0] == "attn_decode"][0]
    assert decode_row[1:] == (cfg.n_heads, 32, cfg.head_dim_)
    # the blockwise config emits no attention namespaces
    cfg_blk = dataclasses.replace(cfg, attn_impl="blockwise")
    eng2 = ServingEngine(cfg_blk, params, max_batch=2, max_seq=32)
    assert not any(op.startswith("attn") for (op, *_ ) in eng2.tune_table(16))


# ---------------------------------------------------------------------------
# perf-model attention terms
# ---------------------------------------------------------------------------


def test_flash_simulation_band_census():
    from repro.core.perf_model import (
        simulate_flash_attention,
        unfused_attention_bytes,
    )

    r = simulate_flash_attention(
        1, 8, 1024, 1024, 64, q_chunk=128, k_chunk=128, causal=True,
        phase="fwd", hkv=2,
    )
    # causal band: nq(nq+1)/2 tiles of an 8x8 grid
    assert r["n_tiles"] == 36
    assert r["bytes"] > 0 and r["time_s"] > 0
    full = simulate_flash_attention(
        1, 8, 1024, 1024, 64, q_chunk=128, k_chunk=128, causal=False,
        phase="fwd", hkv=2,
    )
    assert full["n_tiles"] == 64 and full["bytes"] > r["bytes"]
    # the flash schedule moves far fewer bytes than materialized scores
    assert unfused_attention_bytes(1, 8, 1024, 1024, 64) > 3 * r["bytes"]
    bwd = simulate_flash_attention(
        1, 8, 1024, 1024, 64, q_chunk=128, k_chunk=128, causal=True,
        phase="bwd", hkv=2,
    )
    assert bwd["flops"] > r["flops"]


def test_decode_simulation_valid_bound():
    from repro.core.perf_model import (
        simulate_decode_attention,
        unfused_decode_attention_bytes,
    )

    half = simulate_decode_attention(8, 32, 4, 8192, 128, valid_frac=0.5)
    full = simulate_decode_attention(8, 32, 4, 8192, 128, valid_frac=1.0)
    assert half["bytes"] < full["bytes"]
    # head expansion + dead-chunk reads make the unfused path strictly worse
    assert unfused_decode_attention_bytes(8, 32, 4, 8192, 128) > full["bytes"]
