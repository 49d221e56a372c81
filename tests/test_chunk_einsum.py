"""chunk_einsum: the schedule-compiler payoff — xLSTM/SSM chunked-recurrence
intra-chunk einsums routed through the SFC batched kernels with *no new
table code* (the task table, tune bucket and fallback ladder all derive
from the compiled `ScheduleSpec`).

Acceptance contract (ISSUE 8): the routed blocks match `jnp.einsum` at f32
rtol 1e-4, and under ``gemm_backend("sfc_pallas")`` their jaxpr contains
no `dot_general` — jaxpr-gated per signature and at the model level.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gemm_backend as gb
from repro.core import namespaces as ns
from repro.testing.jaxpr import op_census

SIGNATURES = {
    "blhp,bjhp->bljh": ((2, 24, 3, 16), (2, 24, 3, 16)),
    "bljh,bjhp->blhp": ((2, 24, 24, 3), (2, 24, 3, 16)),
    "bcin,bcjn->bcij": ((2, 4, 24, 16), (2, 4, 24, 16)),
    "bcijh,bcjhp->bcihp": ((1, 2, 24, 24, 3), (1, 2, 24, 3, 16)),
}


def _operands(subs, seed=0):
    sa, sb = SIGNATURES[subs]
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.standard_normal(sa), jnp.float32),
        jnp.asarray(rng.standard_normal(sb), jnp.float32),
    )


def _count(fn, *args):
    return op_census(jax.make_jaxpr(fn)(*args).jaxpr)


# ---------------------------------------------------------------------------
# per-signature: numerics, gradients, jaxpr gate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("subs", sorted(SIGNATURES))
def test_chunk_einsum_matches_jnp(subs):
    a, b = _operands(subs)
    ref = jnp.einsum(subs, a, b, preferred_element_type=jnp.float32)
    with gb.gemm_backend("sfc_pallas"):
        got = gb.chunk_einsum(subs, a, b, preferred_element_type=jnp.float32)
    assert got.dtype == ref.dtype and got.shape == ref.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("subs", sorted(SIGNATURES))
def test_chunk_einsum_is_dot_general_free(subs):
    a, b = _operands(subs)

    def routed(a, b):
        with gb.gemm_backend("sfc_pallas"):
            return gb.chunk_einsum(
                subs, a, b, preferred_element_type=jnp.float32
            )

    c = _count(routed, a, b)
    assert c["pallas"] > 0
    assert c["dot"] == 0, f"dot_general survived chunk_einsum({subs!r})"


def test_chunk_einsum_xla_backend_is_verbatim_einsum():
    subs = "blhp,bjhp->bljh"
    a, b = _operands(subs)
    with gb.gemm_backend("xla"):
        got = gb.chunk_einsum(subs, a, b, preferred_element_type=jnp.float32)
    ref = jnp.einsum(subs, a, b, preferred_element_type=jnp.float32)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_chunk_einsum_rejects_unknown_signature():
    a, b = _operands("bcin,bcjn->bcij")
    with pytest.raises(ValueError, match="registered signatures"):
        gb.chunk_einsum("bin,bjn->bij", a, b)


def test_chunk_einsum_grads_match_xla():
    subs = "blhp,bjhp->bljh"
    a, b = _operands(subs, seed=1)

    def loss(route):
        def f(a, b):
            if route:
                with gb.gemm_backend("sfc_pallas"):
                    y = gb.chunk_einsum(
                        subs, a, b, preferred_element_type=jnp.float32
                    )
            else:
                y = jnp.einsum(subs, a, b, preferred_element_type=jnp.float32)
            return jnp.sum(y**2)

        return f

    gs = jax.grad(loss(True), (0, 1))(a, b)
    gx = jax.grad(loss(False), (0, 1))(a, b)
    for s, x in zip(gs, gx):
        np.testing.assert_allclose(
            np.asarray(s), np.asarray(x), rtol=1e-4, atol=1e-5
        )


# ---------------------------------------------------------------------------
# schedule-derived identity: tune namespace + per-schedule ladder
# ---------------------------------------------------------------------------


def test_chunk_gemm_plan_namespace_is_schedule_qualified():
    from repro.kernels.ops import chunk_gemm_plan

    namespace, knobs = chunk_gemm_plan(24, 24, 16, jnp.float32)
    assert ns.is_schedule_namespace(namespace)
    assert ns.base_namespace(namespace) == ns.NS_GEMM
    base, key = namespace.split("@")
    assert len(key) == 12
    assert set(knobs) == {"bm", "bn", "k_layers", "k_block_factor"}
    # deterministic: the same tile space compiles to the same identity
    assert chunk_gemm_plan(24, 24, 16, jnp.float32)[0] == namespace
    # a different tile space is a different bucket (24x24 and 192x24 pad
    # to the *same* 3x3 grid, so they intentionally share one)
    assert chunk_gemm_plan(192, 24, 16, jnp.float32)[0] == namespace
    other, _ = chunk_gemm_plan(1024, 512, 64, jnp.float32)
    assert other != namespace and ns.base_namespace(other) == ns.NS_GEMM


def test_chunk_einsum_heals_per_schedule():
    from repro.robust import FaultSpec, fault_injection

    subs = "bcin,bcjn->bcij"
    a, b = _operands(subs)
    ref = jnp.einsum(subs, a, b, preferred_element_type=jnp.float32)
    with fault_injection(
        FaultSpec(f"{ns.NS_GEMM}@*", kind="compile")
    ) as state:
        with gb.gemm_backend("sfc_pallas"):
            got = gb.chunk_einsum(
                subs, a, b, preferred_element_type=jnp.float32
            )
    assert state.fired, "injected fault never matched the schedule namespace"
    assert all(ns.is_schedule_namespace(f[0]) for f in state.fired)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5
    )


# ---------------------------------------------------------------------------
# model level: the xLSTM / SSD intra-chunk blocks route end-to-end
# ---------------------------------------------------------------------------


def _mlstm_inputs(seed=0):
    rng = np.random.default_rng(seed)
    b, s, h, p = 1, 48, 2, 16
    mk = lambda *shape: jnp.asarray(
        rng.standard_normal(shape) * 0.3, jnp.float32
    )
    return mk(b, s, h, p), mk(b, s, h, p), mk(b, s, h, p), mk(b, s, h), mk(b, s, h)


def _ssd_inputs(seed=0):
    rng = np.random.default_rng(seed)
    b, s, h, p, n = 1, 48, 2, 16, 8
    x = jnp.asarray(rng.standard_normal((b, s, h, p)) * 0.3, jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, s, n)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, s, n)) * 0.3, jnp.float32)
    la = -jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    return x, bm, cm, la


def test_mlstm_chunked_matches_xla_backend():
    from repro.models.xlstm import mlstm_chunked

    args = _mlstm_inputs()
    with gb.gemm_backend("xla"):
        ref = mlstm_chunked(*args, chunk=24)
    with gb.gemm_backend("sfc_pallas"):
        got = mlstm_chunked(*args, chunk=24)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5
    )


def test_ssd_chunked_matches_xla_backend():
    from repro.models.ssm import ssd_chunked

    args = _ssd_inputs()
    with gb.gemm_backend("xla"):
        ref = ssd_chunked(*args, chunk=24)
    with gb.gemm_backend("sfc_pallas"):
        got = ssd_chunked(*args, chunk=24)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("which", ["mlstm", "ssd"])
def test_intra_chunk_blocks_are_jaxpr_gated(which):
    """The routed intra-chunk einsums (two per model) vanish from the
    dot_general census under sfc_pallas and reappear as pallas launches;
    the inter-chunk scan carries stay on XLA dots (not in scope)."""
    if which == "mlstm":
        from repro.models.xlstm import mlstm_chunked as fn
        args = _mlstm_inputs()
    else:
        from repro.models.ssm import ssd_chunked as fn
        args = _ssd_inputs()

    def run(backend):
        def wrapped(*a):
            with gb.gemm_backend(backend):
                return fn(*a, chunk=24)

        return _count(wrapped, *args)

    c_xla = run("xla")
    c_sfc = run("sfc_pallas")
    assert c_xla["pallas"] == 0
    assert c_sfc["pallas"] > 0, "no SFC kernel launched in the chunked scan"
    assert c_sfc["dot"] == c_xla["dot"] - 2, (
        "expected exactly the two intra-chunk einsums to leave the "
        f"dot_general census: xla={c_xla['dot']} sfc={c_sfc['dot']}"
    )
