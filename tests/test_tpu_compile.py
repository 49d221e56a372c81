"""Compiles for a described TPU v5e: the main path's kernels at yi-6b widths.

No chip is needed.  The TPU compiler is installed, and it compiles for a
v5e that is described (`jax.experimental.topologies`) and not attached, so
what Mosaic refuses on the chip (a block that is not tile-aligned, more VMEM
than the scoped limit) is refused here.  Code that asks
`jax.default_backend()` still sees the CPU and would take its interpret
branch, so interpret mode is forced off inside the tests.

The topology is described in a fixture, never at import: only one process
may load the TPU library, and under pytest-xdist every worker imports this
file.  Keep these tests in this one file, so that one worker loads it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import attention_backend as ab
from repro.core import gemm_backend as gb
from repro.kernels import ops
from repro.robust import degradation_report

YI = get_config("yi-6b")
D, FF, V = YI.d_model, YI.d_ff, YI.vocab
HD = YI.head_dim_
B, S = 4, 512  # the serving batch: 4 prompts of 512 tokens


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(monkeypatch):
    """Mosaic, not the interpreter, and no persistent compilation cache:
    an entry compiled for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes
    ]
    return jax.jit(fn).lower(*args).compile()


def _assert_on_sfc_kernels(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    rep = degradation_report()
    assert rep["fallback_calls"] == 0, rep
    assert not rep["quarantined"], rep["quarantined"]


def _on_sfc(fn):
    def run(*args):
        with gb.gemm_backend("sfc_pallas"):
            return fn(*args)

    return run


bf16 = jnp.bfloat16

# every projection of a yi-6b prefill (B x S tokens) and decode (B x 1)
# step, through the same `gemm_backend` entry points the model calls
PROJECTIONS = {
    "prefill_q_o": (gb.matmul, [((B, S, D), bf16), ((D, D), bf16)]),
    "prefill_down": (gb.matmul, [((B, S, FF), bf16), ((FF, D), bf16)]),
    "prefill_glu": (
        gb.glu_matmul, [((B, S, D), bf16), ((D, FF), bf16), ((D, FF), bf16)]
    ),
    "decode_down": (gb.matmul, [((B, 1, FF), bf16), ((FF, D), bf16)]),
    "lm_head": (gb.matmul, [((B, 1, D), bf16), ((D, V), bf16)]),
}


@pytest.mark.parametrize("name", sorted(PROJECTIONS))
def test_yi_projection_compiles_on_fused_rung(name, one_chip, chip_compile):
    fn, shapes = PROJECTIONS[name]
    _assert_on_sfc_kernels(_compile(_on_sfc(fn), shapes, one_chip))


BACKWARD = {
    # dA = dC . W^T for the up-projection, dW = A^T . dC for the down one
    "nt": (ops.sfc_matmul_nt, [((S, FF), bf16), ((D, FF), bf16)]),
    "tn": (ops.sfc_matmul_tn, [((S, FF), bf16), ((S, D), bf16)]),
    "grouped": (
        lambda r, w: ops.sfc_grouped_matmul(r, w, (512, 384, 640, 512)),
        [((2048, D), bf16), ((4, D, FF), bf16)],
    ),
}


@pytest.mark.parametrize("name", sorted(BACKWARD))
def test_yi_width_kernel_compiles(name, one_chip, chip_compile):
    fn, shapes = BACKWARD[name]
    _assert_on_sfc_kernels(_compile(fn, shapes, one_chip))


def test_yi_layer_prefill_compiles(one_chip, chip_compile):
    """One yi-6b layer at full width, embedding and LM head included, as
    the serving engine traces its prefill under ``sfc_pallas``."""
    import dataclasses

    from repro.models.registry import param_specs
    from repro.serving.engine import ServingEngine

    cfg = dataclasses.replace(YI, n_layers=1)
    engine = ServingEngine(cfg, None, max_batch=B, max_seq=S + 33,
                           gemm_backend="sfc_pallas")
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        param_specs(cfg),
    )
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)
    compiled = jax.jit(engine._prefill_impl).lower(params, tokens).compile()
    # q, k, v, o, the GLU up-projection, the down-projection, the LM head
    assert compiled.as_text().count("tpu_custom_call") >= 7
    _assert_on_sfc_kernels(compiled)


ATTN_SHAPES = [((B, S, YI.n_heads, HD), bf16)] + 2 * [
    ((B, S, YI.kv_heads, HD), bf16)
]


def test_sfc_flash_attention_compiles(one_chip, chip_compile):
    """Forward and backward: one head per lane block of the native
    (B, S, H, D) layout, GQA resolved in the index maps."""

    def loss(q, k, v):
        o = ab.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), ATTN_SHAPES,
                        one_chip)
    # forward, dQ and dK/dV kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3
    _assert_on_sfc_kernels(compiled)


def test_sfc_decode_attention_compiles(one_chip, chip_compile):
    def attn(q, k, v, valid):
        return ab.decode_attention(q, k, v, valid, interpret=False)

    t = S + 33
    shapes = [
        ((B, 1, YI.n_heads, HD), bf16),
        ((B, t, YI.kv_heads, HD), bf16),
        ((B, t, YI.kv_heads, HD), bf16),
        ((B,), jnp.int32),
    ]
    _assert_on_sfc_kernels(_compile(attn, shapes, one_chip))
