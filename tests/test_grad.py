"""Differentiable SFC GEMM: the custom-VJP backward pass.

Differential tests of `jax.grad` through `sfc_matmul` / `sfc_glu_matmul` /
the grouped forms against the XLA formulation (fp32 tight, bf16 loose),
backend-level grad agreement for all three gemm backends, and structural
jaxpr checks: the sfc_pallas backward contains no `dot_general` outside the
Pallas kernels — dA/dW run on the NT/TN SFC kernels."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (
    sfc_glu_matmul,
    sfc_grouped_glu_matmul,
    sfc_grouped_matmul,
    sfc_matmul,
    sfc_matmul_nt,
    sfc_matmul_tn,
)
from repro.testing.jaxpr import op_census


def _rand(*shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng([seed, *[int(s) for s in shape]])
    return jnp.asarray(rng.normal(size=shape), dtype)


def _tol(dtype):
    return 2e-4 if dtype == jnp.float32 else 8e-2


def _grads_close(got, want, dtype, msg=""):
    for i, (g, w) in enumerate(zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=_tol(dtype), atol=_tol(dtype) * 5,
            err_msg=f"{msg} grad leaf {i}",
        )


# ---------------------------------------------------------------------------
# structural: the backward is SFC kernels, not dot_general
# ---------------------------------------------------------------------------


def _grad_census(fn, *args):
    jx = jax.make_jaxpr(jax.grad(fn, argnums=tuple(range(len(args)))))(*args)
    return op_census(jx.jaxpr)


def test_matmul_backward_is_sfc_kernels():
    """grad(sfc_matmul) = forward + NT + TN pallas launches, zero dots."""
    a, b = _rand(34, 21), _rand(21, 27, seed=1)
    c = _grad_census(lambda a, b: sfc_matmul(a, b, interpret=True).sum(), a, b)
    assert c["dot"] == 0, f"backward fell back to dot_general: {c['dot_shapes']}"
    assert c["pallas"] == 3, f"expected fwd+NT+TN launches, saw {c['pallas']}"


def test_glu_backward_is_dual_sfc_kernels():
    """The GLU backward is ONE dual NT + ONE dual TN launch (four backward
    GEMMs, two traversals), not four separate launches."""
    a, bg, bv = _rand(34, 21), _rand(21, 27, seed=1), _rand(21, 27, seed=2)
    c = _grad_census(
        lambda a, bg, bv: sfc_glu_matmul(a, bg, bv, interpret=True).sum(),
        a, bg, bv,
    )
    assert c["dot"] == 0
    assert c["pallas"] == 3, f"expected fwd+dualNT+dualTN, saw {c['pallas']}"


def test_grouped_backward_is_sfc_kernels():
    gs = (5, 0, 19, 8)
    a = _rand(sum(gs), 13)
    w = _rand(4, 13, 11, seed=1)
    c = _grad_census(
        lambda a, w: sfc_grouped_matmul(a, w, gs, interpret=True).sum(), a, w
    )
    assert c["dot"] == 0
    assert c["pallas"] == 3


# ---------------------------------------------------------------------------
# differential: grads vs the XLA formulation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("activation", [None, "silu", "gelu"])
def test_matmul_epilogue_grads_match_xla(dtype, activation):
    m, n, k = 34, 21, 45  # padded everywhere
    a, b = _rand(m, k, dtype=dtype), _rand(k, n, dtype=dtype, seed=1)
    bias = _rand(n, dtype=dtype, seed=2)
    res = _rand(m, n, dtype=dtype, seed=3)

    def f_sfc(a, b, bias, res):
        return sfc_matmul(
            a, b, bias=bias, activation=activation, out_scale=0.5,
            residual=res, interpret=True,
        ).astype(jnp.float32).sum()

    def f_xla(a, b, bias, res):
        y = (a.astype(jnp.float32) @ b.astype(jnp.float32)) + bias.astype(
            jnp.float32
        )
        if activation is not None:
            y = getattr(jax.nn, activation)(y)
        return (y * 0.5 + res.astype(jnp.float32)).astype(dtype).astype(
            jnp.float32
        ).sum()

    args = (a, b, bias, res)
    gs = jax.grad(f_sfc, argnums=(0, 1, 2, 3))(*args)
    gx = jax.grad(f_xla, argnums=(0, 1, 2, 3))(*args)
    _grads_close(gs, gx, dtype, f"act={activation}")


@pytest.mark.parametrize("lead", [(), (3,), (2, 2)])
def test_batched_matmul_grads_match_xla(lead):
    a = _rand(*lead, 18, 21)
    b = _rand(21, 17, seed=1)
    gs = jax.grad(
        lambda a, b: sfc_matmul(a, b, activation="relu", interpret=True).sum(),
        argnums=(0, 1),
    )(a, b)
    gx = jax.grad(
        lambda a, b: jax.nn.relu(a @ b).sum(), argnums=(0, 1)
    )(a, b)
    _grads_close(gs, gx, jnp.float32, f"lead={lead}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_glu_grads_match_xla(dtype):
    m, n, k = 19, 45, 53
    a = _rand(m, k, dtype=dtype)
    bg, bv = _rand(k, n, dtype=dtype, seed=1), _rand(k, n, dtype=dtype, seed=2)
    bias, gbias = _rand(n, dtype=dtype, seed=3), _rand(n, dtype=dtype, seed=4)

    def f_sfc(a, bg, bv, bias, gbias):
        return sfc_glu_matmul(
            a, bg, bv, activation="silu", bias=bias, gate_bias=gbias,
            interpret=True,
        ).astype(jnp.float32).sum()

    def f_xla(a, bg, bv, bias, gbias):
        af = a.astype(jnp.float32)
        g = af @ bg.astype(jnp.float32) + gbias.astype(jnp.float32)
        h = af @ bv.astype(jnp.float32) + bias.astype(jnp.float32)
        return (jax.nn.silu(g) * h).astype(dtype).astype(jnp.float32).sum()

    args = (a, bg, bv, bias, gbias)
    gs = jax.grad(f_sfc, argnums=(0, 1, 2, 3, 4))(*args)
    gx = jax.grad(f_xla, argnums=(0, 1, 2, 3, 4))(*args)
    _grads_close(gs, gx, dtype)


@pytest.mark.parametrize("group_sizes", [(5, 0, 19, 8), (1, 2, 3)])
def test_grouped_grads_match_xla(group_sizes):
    e = len(group_sizes)
    t, k, n = sum(group_sizes), 13, 11
    a = _rand(t, k)
    w = _rand(e, k, n, seed=1)
    bias = _rand(e, n, seed=2)

    def f_sfc(a, w, bias):
        return sfc_grouped_matmul(
            a, w, group_sizes, bias=bias, activation="gelu", interpret=True
        ).sum()

    def f_xla(a, w, bias):
        off, total = 0, 0.0
        for ei, g in enumerate(group_sizes):
            total += jax.nn.gelu(a[off:off + g] @ w[ei] + bias[ei]).sum()
            off += g
        return total

    gs = jax.grad(f_sfc, argnums=(0, 1, 2))(a, w, bias)
    gx = jax.grad(f_xla, argnums=(0, 1, 2))(a, w, bias)
    _grads_close(gs, gx, jnp.float32, f"groups={group_sizes}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_glu_grads_match_xla(dtype):
    group_sizes = (5, 0, 19, 8)
    e, t, k, n = 4, 32, 13, 11
    a = _rand(t, k, dtype=dtype)
    wg = _rand(e, k, n, dtype=dtype, seed=1)
    wv = _rand(e, k, n, dtype=dtype, seed=2)

    def f_sfc(a, wg, wv):
        return sfc_grouped_glu_matmul(
            a, wg, wv, group_sizes, interpret=True
        ).astype(jnp.float32).sum()

    def f_xla(a, wg, wv):
        off, total = 0, 0.0
        for ei, g in enumerate(group_sizes):
            af = a[off:off + g].astype(jnp.float32)
            y = jax.nn.silu(af @ wg[ei].astype(jnp.float32)) * (
                af @ wv[ei].astype(jnp.float32)
            )
            total += y.astype(dtype).astype(jnp.float32).sum()
            off += g
        return total

    gs = jax.grad(f_sfc, argnums=(0, 1, 2))(a, wg, wv)
    gx = jax.grad(f_xla, argnums=(0, 1, 2))(a, wg, wv)
    _grads_close(gs, gx, dtype)


def test_nt_tn_wrappers_match_transpose():
    """The backward entry points themselves: padded odd shapes, dual forms."""
    a, b = _rand(34, 45), _rand(21, 45, seed=1)
    np.testing.assert_allclose(
        np.asarray(sfc_matmul_nt(a, b, interpret=True)),
        np.asarray(a @ b.T), rtol=2e-5, atol=2e-5,
    )
    a2, b2 = _rand(34, 45, seed=2), _rand(21, 45, seed=3)
    np.testing.assert_allclose(
        np.asarray(sfc_matmul_nt(a, b, a2, b2, interpret=True)),
        np.asarray(a @ b.T + a2 @ b2.T), rtol=2e-5, atol=2e-5,
    )
    x, d1, d2 = _rand(37, 13), _rand(37, 29, seed=1), _rand(37, 29, seed=2)
    w1, w2 = sfc_matmul_tn(x, d1, d2, interpret=True)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(x.T @ d1),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(x.T @ d2),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# backend-level + model-level training
# ---------------------------------------------------------------------------

BACKENDS = ("xla", "sfc_pallas", "sfc_reference")


def test_backend_matmul_grads_agree():
    from repro.core.gemm_backend import gemm_backend, matmul

    x, w = _rand(24, 40), _rand(40, 16, seed=1)
    bias = _rand(16, seed=2)

    grads = {}
    for backend in BACKENDS:
        def f(x, w, bias, _b=backend):
            with gemm_backend(_b):
                return matmul(x, w, bias=bias, activation="silu").sum()

        grads[backend] = jax.grad(f, argnums=(0, 1, 2))(x, w, bias)
    _grads_close(grads["sfc_pallas"], grads["xla"], jnp.float32, "sfc_pallas")
    _grads_close(grads["sfc_reference"], grads["xla"], jnp.float32,
                 "sfc_reference")


def test_mlp_grads_agree_across_backends():
    from repro.core.gemm_backend import gemm_backend
    from repro.models.layers import mlp, mlp_init

    p = mlp_init(jax.random.PRNGKey(0), 24, 48, jnp.float32, gated=True)
    x = _rand(2, 10, 24)

    grads = {}
    for backend in BACKENDS:
        def loss(p, _b=backend):
            with gemm_backend(_b):
                return (mlp(p, x) ** 2).sum()

        grads[backend] = jax.grad(loss)(p)
    _grads_close(grads["sfc_pallas"], grads["xla"], jnp.float32, "sfc_pallas")
    _grads_close(grads["sfc_reference"], grads["xla"], jnp.float32,
                 "sfc_reference")


def test_moe_grads_match_xla():
    from repro.core.gemm_backend import gemm_backend
    from repro.models import moe as moe_lib

    p = moe_lib.moe_init(
        jax.random.PRNGKey(0), d_model=32, d_ff=64, n_experts=4,
        dtype=jnp.float32,
    )
    x = _rand(2, 8, 32)

    def loss(p, backend):
        with gemm_backend(backend):
            out, aux = moe_lib.moe_forward(p, x, top_k=2)
            return (out ** 2).sum() + aux["moe_aux_loss"] + aux["moe_z_loss"]

    gx = jax.grad(lambda p: loss(p, "xla"))(p)
    gs = jax.grad(lambda p: loss(p, "sfc_pallas"))(p)
    _grads_close(gs, gx, jnp.float32, "moe")


def _tiny_cfg():
    from repro.configs import get_config

    return dataclasses.replace(
        get_config("yi_6b").reduced(), n_layers=2, vocab=128
    )


def test_train_step_grads_match_xla_fp32():
    """Acceptance: value_and_grad of a transformer loss under sfc_pallas
    matches the XLA backend at fp32 rtol <= 1e-4."""
    from repro.core.gemm_backend import gemm_backend
    from repro.models.registry import build_model

    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
    }

    def loss(p, backend):
        with gemm_backend(backend):
            return model.loss(p, batch, remat="none")

    lx, gx = jax.value_and_grad(lambda p: loss(p, "xla"))(params)
    ls, gs = jax.value_and_grad(lambda p: loss(p, "sfc_pallas"))(params)
    np.testing.assert_allclose(float(ls), float(lx), rtol=1e-4)
    for leaf_s, leaf_x in zip(jax.tree.leaves(gs), jax.tree.leaves(gx)):
        np.testing.assert_allclose(
            np.asarray(leaf_s), np.asarray(leaf_x), rtol=1e-4, atol=1e-5
        )


def test_train_step_backward_no_projection_dot_general():
    """Acceptance: the backward jaxpr of a train step under sfc_pallas has
    no dot_general on projection shapes.  Projections (weights are rank-2)
    all route through the SFC kernels; the only dot_generals left are the
    rank-4 attention-score einsums."""
    from repro.core.gemm_backend import gemm_backend
    from repro.models.registry import build_model
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.train.step import BackendConfig, make_train_step

    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(lr=1e-3)
    opt_state = adamw_init(params)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
    }

    step = make_train_step(
        model, opt_cfg, remat="none", backend=BackendConfig(gemm_backend="sfc_pallas"))
    jx = jax.make_jaxpr(step)(params, opt_state, batch)
    c = op_census(jx.jaxpr)
    assert c["pallas"] > 0, "sfc backend did not launch any SFC kernels"
    rank2 = [
        shp for shp in c["dot_shapes"] if any(len(op) <= 2 for op in shp)
    ]
    assert not rank2, (
        f"projection-shaped dot_general in the train step: {rank2}"
    )
    for shp in c["dot_shapes"]:  # whatever remains is attention scores
        assert all(len(op) >= 3 for op in shp), shp


def test_train_step_runs_on_sfc_backend():
    """One optimizer step end-to-end under gemm_backend('sfc_pallas')
    matches the XLA step (same loss metric, params advance identically)."""
    from repro.models.registry import build_model
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.train.step import BackendConfig, make_train_step

    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(lr=1e-3)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32),
    }

    outs = {}
    for backend in ("xla", "sfc_pallas"):
        step = make_train_step(
            model, opt_cfg, remat="none", backend=BackendConfig(gemm_backend=backend))
        new_params, _, metrics = step(params, adamw_init(params), batch)
        outs[backend] = (new_params, metrics["loss"])
    np.testing.assert_allclose(
        float(outs["sfc_pallas"][1]), float(outs["xla"][1]), rtol=1e-4
    )
    for ls, lx in zip(
        jax.tree.leaves(outs["sfc_pallas"][0]), jax.tree.leaves(outs["xla"][0])
    ):
        np.testing.assert_allclose(
            np.asarray(ls, np.float32), np.asarray(lx, np.float32),
            rtol=5e-4, atol=1e-5,
        )


def test_backward_tune_namespaces_consulted(tmp_path, monkeypatch):
    """The backward kernels consult their own op='nt'/'tn' tune namespaces
    (buckets per `perf_model.backward_gemm_shapes`), and a cached winner
    there steers them without breaking the grads."""
    import repro.tune
    import repro.tune.tuner as tuner
    from repro.core.perf_model import backward_gemm_shapes
    from repro.tune import Knobs

    monkeypatch.setenv("REPRO_SFC_TUNE_CACHE", str(tmp_path / "knobs.json"))
    tuner._DEFAULT_CACHE = None
    m, n, k = 32, 48, 24  # forward: a (32, 24) @ b (24, 48)
    buckets = backward_gemm_shapes(m, n, k)
    assert buckets == {"nt": (32, 24, 48), "tn": (24, 48, 32)}
    try:
        cache = tuner.default_cache()
        cache.put(*buckets["nt"], np.float32, "cpu",
                  Knobs(bm=8, bn=8, k_layers=1, k_block_factor=2), op="nt")

        # spy on the cache consult the knob resolver performs
        seen = []
        real_lookup = repro.tune.lookup_knobs

        def spy(m_, n_, k_, dtype, **kw):
            hit = real_lookup(m_, n_, k_, dtype, **kw)
            seen.append(((m_, n_, k_), kw.get("op", "gemm"), hit))
            return hit

        monkeypatch.setattr(repro.tune, "lookup_knobs", spy)

        a, b = _rand(m, k), _rand(k, n, seed=1)
        gs = jax.grad(lambda a, b: sfc_matmul(a, b, interpret=True).sum(),
                      argnums=(0, 1))(a, b)

        nt_consults = [(s, hit) for s, op, hit in seen if op == "nt"]
        tn_consults = [(s, hit) for s, op, hit in seen if op == "tn"]
        assert nt_consults and tn_consults, f"backward did not consult nt/tn: {seen}"
        assert nt_consults[0][0] == buckets["nt"]
        assert tn_consults[0][0] == buckets["tn"]
        # the seeded NT winner was found and used; TN had no entry
        assert nt_consults[0][1] is not None and nt_consults[0][1].bm == 8
        assert tn_consults[0][1] is None

        # grads still correct with the cached (tiny) backward knobs active
        gx = jax.grad(lambda a, b: (a @ b).sum(), argnums=(0, 1))(a, b)
        _grads_close(gs, gx, jnp.float32)
    finally:
        tuner._DEFAULT_CACHE = None
