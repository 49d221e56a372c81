"""Fused-epilogue SFC GEMM: differential tests vs the jnp reference for
bias/activation/scale/residual/GLU epilogues (f32 accumulation), the
layer-inner single-launch structure (no (K_layers, M, N) HBM intermediate),
the replicated-form fallback, and the widened gemm_backend surface."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (
    sfc_glu_matmul,
    sfc_grouped_glu_matmul,
    sfc_grouped_matmul,
    sfc_matmul,
)
from repro.testing.jaxpr import walk_eqns


def _rand(*shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng([seed, *[int(s) for s in shape]])
    return jnp.asarray(rng.normal(size=shape), dtype)


def _act(name):
    from repro.kernels.sfc_gemm import activation_fn

    return activation_fn(name)


def _epilogue_ref(a, b, *, bias=None, activation=None, out_scale=None,
                  residual=None, out_dtype=None):
    """f32-accumulated oracle matching the kernel flush semantics."""
    acc = jnp.matmul(
        a.astype(jnp.float32), b.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if activation is not None:
        acc = _act(activation)(acc)
    if out_scale is not None:
        acc = acc * out_scale
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    return acc.astype(out_dtype or a.dtype)


def _glu_ref(a, bg, bv, *, activation="silu", bias=None, gate_bias=None,
             out_scale=None, residual=None, out_dtype=None):
    af = a.astype(jnp.float32)
    g = af @ bg.astype(jnp.float32)
    if gate_bias is not None:
        g = g + gate_bias.astype(jnp.float32)
    h = af @ bv.astype(jnp.float32)
    if bias is not None:
        h = h + bias.astype(jnp.float32)
    y = _act(activation)(g) * h
    if out_scale is not None:
        y = y * out_scale
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return y.astype(out_dtype or a.dtype)


def _tol(dtype):
    return 3e-5 if dtype == jnp.float32 else 6e-2


def _close(got, want, dtype, msg=""):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=_tol(dtype), atol=_tol(dtype), err_msg=msg,
    )


# ---------------------------------------------------------------------------
# structural: the fused path is one launch, no replicated HBM intermediate
# ---------------------------------------------------------------------------


def _trace(fn, *args):
    pallas_eqns, shapes = [], []
    for eqn in walk_eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "pallas_call":
            pallas_eqns.append(eqn)
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                shapes.append(tuple(aval.shape))
    return pallas_eqns, shapes


def test_fused_path_single_launch_no_replicated_intermediate():
    """With k_layers=4 the fused path is one pallas_call and never holds a
    (K_layers, M, N) value; the replicated fallback launches twice and does
    (the HBM round-trip the layer-inner grid deletes)."""
    kl, m, n, k = 4, 64, 64, 128
    a, b = _rand(m, k), _rand(k, n, seed=1)

    def fused(a, b):
        return sfc_matmul(a, b, bm=16, bn=16, k_layers=kl, k_block_factor=1,
                          interpret=True)

    def fallback(a, b):
        return sfc_matmul(a, b, bm=16, bn=16, k_layers=kl, k_block_factor=1,
                          interpret=True, fuse=False)

    calls, shapes = _trace(fused, a, b)
    assert len(calls) == 1, f"fused path must be a single launch, saw {len(calls)}"
    assert (kl, m, n) not in shapes, "fused path materialized replicated C copies"

    calls, shapes = _trace(fallback, a, b)
    assert len(calls) == 2, "replicated fallback is gemm + add_reduce"
    assert (kl, m, n) in shapes, "fallback should hold the replicated copies"

    _close(fused(a, b), fallback(a, b), jnp.float32)


def test_fused_glu_single_launch():
    a, bg, bv = _rand(32, 64), _rand(64, 32, seed=1), _rand(64, 32, seed=2)

    def fused(a, bg, bv):
        return sfc_glu_matmul(a, bg, bv, bm=16, bn=16, k_layers=2,
                              k_block_factor=1, interpret=True)

    calls, _ = _trace(fused, a, bg, bv)
    assert len(calls) == 1, "GLU must be one dual-B launch, not two GEMMs"


# ---------------------------------------------------------------------------
# differential: epilogues vs jnp reference
# ---------------------------------------------------------------------------

EPILOGUE_CASES = [
    # (m, n, k, kwargs, use_bias, use_residual, dtype)
    (32, 32, 64, dict(bm=16, bn=16, k_layers=2, k_block_factor=1), True, False,
     jnp.float32),
    (48, 80, 96, dict(bm=16, bn=16, k_layers=2, k_block_factor=3), True, True,
     jnp.float32),
    (34, 21, 95, dict(bm=16, bn=16, k_layers=2, k_block_factor=2), True, True,
     jnp.float32),  # padded M/N/K
    (34, 21, 95, dict(bm=16, bn=16, k_layers=2, k_block_factor=2), True, True,
     jnp.bfloat16),
    (64, 32, 128, dict(bm=16, bn=16, k_layers=4, k_block_factor=1), False, True,
     jnp.bfloat16),
]


@pytest.mark.parametrize("activation", [None, "silu", "gelu", "relu"])
@pytest.mark.parametrize("m,n,k,kw,use_bias,use_res,dtype", EPILOGUE_CASES)
def test_epilogue_matches_reference(m, n, k, kw, use_bias, use_res, dtype,
                                    activation):
    a, b = _rand(m, k, dtype=dtype), _rand(k, n, dtype=dtype, seed=1)
    bias = _rand(n, dtype=dtype, seed=2) if use_bias else None
    res = _rand(m, n, dtype=dtype, seed=3) if use_res else None
    got = sfc_matmul(a, b, bias=bias, activation=activation, out_scale=0.5,
                     residual=res, interpret=True, **kw)
    want = _epilogue_ref(a, b, bias=bias, activation=activation,
                         out_scale=0.5, residual=res)
    assert got.shape == (m, n) and got.dtype == dtype
    _close(got, want, dtype, f"act={activation}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("lead", [(), (3,), (2, 2)])
def test_glu_matches_reference(lead, dtype):
    m, n, k = 19, 45, 53  # padded everywhere
    a = _rand(*lead, m, k, dtype=dtype)
    bg = _rand(k, n, dtype=dtype, seed=1)
    bv = _rand(k, n, dtype=dtype, seed=2)
    bias = _rand(n, dtype=dtype, seed=3)
    gbias = _rand(n, dtype=dtype, seed=4)
    got = sfc_glu_matmul(a, bg, bv, activation="silu", bias=bias,
                         gate_bias=gbias, bm=16, bn=16, k_layers=2,
                         k_block_factor=2, interpret=True)
    want = _glu_ref(a, bg, bv, activation="silu", bias=bias, gate_bias=gbias)
    assert got.shape == (*lead, m, n)
    _close(got, want, dtype)


def test_glu_fallback_matches_fused():
    a, bg, bv = _rand(34, 95), _rand(95, 21, seed=1), _rand(95, 21, seed=2)
    kw = dict(bm=16, bn=16, k_layers=2, k_block_factor=2, interpret=True)
    fused = sfc_glu_matmul(a, bg, bv, **kw)
    unfused = sfc_glu_matmul(a, bg, bv, fuse=False, **kw)
    _close(fused, unfused, jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_fallback_reduce_per_element(dtype):
    """fuse=False with leading dims + k_layers>1 exercises the per-batch
    add_reduce (no transpose+reshape HBM fold of the copies)."""
    a = _rand(3, 37, 53, dtype=dtype)
    b = _rand(53, 21, dtype=dtype, seed=1)
    got = sfc_matmul(a, b, bm=16, bn=16, k_layers=2, k_block_factor=2,
                     interpret=True, fuse=False)
    _close(got, jnp.matmul(a, b), dtype)
    fused = sfc_matmul(a, b, bm=16, bn=16, k_layers=2, k_block_factor=2,
                       interpret=True)
    _close(got, fused, dtype)


GROUPED_CASES = [
    ((5, 0, 19, 32), 24, 18, jnp.float32),  # ragged incl. empty expert
    ((5, 0, 19, 32), 24, 18, jnp.bfloat16),
    ((1, 2, 3), 7, 9, jnp.float32),  # tiny odd dims
]


@pytest.mark.parametrize("group_sizes,k,n,dtype", GROUPED_CASES)
def test_grouped_epilogue_matches_reference(group_sizes, k, n, dtype):
    t = sum(group_sizes)
    e = len(group_sizes)
    a = _rand(t, k, dtype=dtype)
    w = _rand(e, k, n, dtype=dtype, seed=1)
    bias = _rand(e, n, dtype=dtype, seed=2)
    got = sfc_grouped_matmul(a, w, group_sizes, bias=bias, activation="gelu",
                             out_scale=2.0, bm=16, bn=16, interpret=True)
    off, parts = 0, []
    for ei, g in enumerate(group_sizes):
        parts.append(_epilogue_ref(a[off:off + g], w[ei], bias=bias[ei],
                                   activation="gelu", out_scale=2.0))
        off += g
    _close(got, jnp.concatenate(parts), dtype)


@pytest.mark.parametrize("group_sizes,k,n,dtype", GROUPED_CASES)
def test_grouped_glu_matches_reference(group_sizes, k, n, dtype):
    t = sum(group_sizes)
    e = len(group_sizes)
    a = _rand(t, k, dtype=dtype)
    wg = _rand(e, k, n, dtype=dtype, seed=1)
    wv = _rand(e, k, n, dtype=dtype, seed=2)
    got = sfc_grouped_glu_matmul(a, wg, wv, group_sizes, bm=16, bn=16,
                                 interpret=True)
    off, parts = 0, []
    for ei, g in enumerate(group_sizes):
        parts.append(_glu_ref(a[off:off + g], wg[ei], wv[ei]))
        off += g
    _close(got, jnp.concatenate(parts), dtype)


# ---------------------------------------------------------------------------
# widened gemm_backend surface
# ---------------------------------------------------------------------------

BACKENDS = ("xla", "sfc_pallas", "sfc_reference")


def test_backend_matmul_epilogue_agrees():
    from repro.core.gemm_backend import gemm_backend, matmul

    x, w = _rand(24, 40), _rand(40, 16, seed=1)
    bias = _rand(16, seed=2)
    res = _rand(24, 16, seed=3)
    want = _epilogue_ref(x, w, bias=bias, activation="silu", out_scale=0.5,
                         residual=res)
    for backend in BACKENDS:
        with gemm_backend(backend):
            got = matmul(x, w, bias=bias, activation="silu", out_scale=0.5,
                         residual=res)
        _close(got, want, jnp.float32, backend)


@pytest.mark.parametrize("shape", [(24, 40), (2, 12, 40), (4, 1, 40), (40,)])
def test_backend_glu_agrees(shape):
    from repro.core.gemm_backend import gemm_backend, glu_matmul

    x = _rand(*shape)
    wg, wv = _rand(40, 16, seed=1), _rand(40, 16, seed=2)
    want = _glu_ref(x if x.ndim > 1 else x[None], wg, wv)
    if x.ndim == 1:
        want = want[0]
    for backend in BACKENDS:
        with gemm_backend(backend):
            got = glu_matmul(x, wg, wv)
        assert got.shape == (*shape[:-1], 16)
        _close(got, want, jnp.float32, f"{backend}/{shape}")


def test_backend_grouped_glu_agrees():
    from repro.core.gemm_backend import gemm_backend, grouped_glu_matmul

    x = _rand(2, 4, 6, 16)  # (G, E, C, d)
    wg = _rand(4, 16, 12, seed=1)
    wv = _rand(4, 16, 12, seed=2)
    want = jax.nn.silu(jnp.einsum("gecd,edf->gecf", x, wg)) * jnp.einsum(
        "gecd,edf->gecf", x, wv
    )
    for backend in BACKENDS:
        with gemm_backend(backend):
            got = grouped_glu_matmul(x, wg, wv)
        assert got.shape == (2, 4, 6, 12)
        _close(got, want, jnp.float32, backend)


def test_mlp_fused_backend_matches_xla():
    """The whole gated MLP (dual-B fused under sfc_pallas) agrees with the
    unfused xla formulation."""
    from repro.core.gemm_backend import gemm_backend
    from repro.models.layers import mlp, mlp_init

    p = mlp_init(jax.random.PRNGKey(0), 24, 48, jnp.float32, gated=True)
    x = _rand(2, 10, 24)
    with gemm_backend("xla"):
        want = mlp(p, x)
    with gemm_backend("sfc_pallas"):
        got = mlp(p, x)
    _close(got, want, jnp.float32)


# ---------------------------------------------------------------------------
# tune-cache namespace + serving shapes for the fused variants
# ---------------------------------------------------------------------------


def test_tune_cache_glu_namespace(tmp_path):
    from repro.tune import KnobCache, Knobs

    cache = KnobCache(str(tmp_path / "knobs.json"))
    kg = Knobs(bm=64, bn=64, k_layers=1, k_block_factor=2, source="measured")
    kglu = Knobs(bm=32, bn=32, k_layers=1, k_block_factor=4, source="measured")
    cache.put(256, 256, 256, np.float32, "cpu", kg)
    cache.put(256, 256, 256, np.float32, "cpu", kglu, op="glu")
    assert cache.get(256, 256, 256, np.float32, "cpu").bm == 64
    assert cache.get(256, 256, 256, np.float32, "cpu", op="glu").bm == 32


def test_tune_gemm_glu_op_separate_winner(tmp_path):
    from repro.tune import KnobCache, tune_gemm

    cache = KnobCache(str(tmp_path / "knobs.json"))
    calls = []

    def fake_measure(m, n, k, dtype, knobs, *, op="gemm"):
        calls.append((op, knobs))
        return 1.0 / knobs.bm

    a = tune_gemm(96, 96, 96, np.float32, cache=cache, measure_fn=fake_measure)
    n_gemm = len(calls)
    b = tune_gemm(96, 96, 96, np.float32, cache=cache, measure_fn=fake_measure,
                  op="glu")
    assert len(calls) > n_gemm, "glu namespace must tune separately"
    assert all(op == "glu" for op, _ in calls[n_gemm:]), "op must reach measure_fn"
    b2 = tune_gemm(96, 96, 96, np.float32, cache=cache, measure_fn=fake_measure,
                   op="glu")
    assert b2.source == "cached" and (b2.bm, b2.bn) == (b.bm, b.bn)
    assert a.source == "measured"

    # a measurer that cannot take op must not silently mis-score a glu sweep
    def no_op_measure(m, n, k, dtype, knobs):
        return 1.0

    with pytest.raises(ValueError, match="op"):
        tune_gemm(64, 64, 64, np.float32, cache=cache,
                  measure_fn=no_op_measure, op="glu", force=True)


def test_engine_projection_shapes_tag_glu():
    from repro.configs import get_config
    from repro.serving.engine import ServingEngine

    cfg = get_config("yi_6b").reduced()  # llama-style: gated MLP
    params = None  # shapes only; no forward pass

    class _Shim(ServingEngine):
        def __init__(self, cfg):  # skip model build/jit
            self.cfg = cfg
            self.max_batch = 4

    ops = {s[0] for s in _Shim(cfg).projection_gemm_shapes(32)}
    assert "glu" in ops and "gemm" in ops
