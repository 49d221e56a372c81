"""Compile every cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse_compile.py [cell ...]

For each cell of ``BENCHMARK.json`` (or those named): the programs its
window drives, at its real sizes, and its reference's, compiled for one
chip of a described ``v5e:2x2`` (all four for a four-chip cell).  Prints
each program's bytes from ``memory_analysis()`` and its count of
``tpu_custom_call`` kernels.  Refusals of the TPU compiler (tiling, VMEM,
memory) surface here at no chip time.  Nothing runs, so nothing is timed.
"""

from __future__ import annotations

import dataclasses
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

import run as bench  # noqa: E402

GB = 1e9


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _report(name, compiled):
    mem = compiled.memory_analysis()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    print(f"  {name}: arguments {mem.argument_size_in_bytes / GB:.3f} GB, "
          f"temp {mem.temp_size_in_bytes / GB:.3f} GB, output "
          f"{mem.output_size_in_bytes / GB:.3f} GB, tpu_custom_call "
          f"{n_kernels}", flush=True)


def serve_programs(spec, topo):
    from repro.configs.base import ArchConfig
    from repro.serving.engine import ServingEngine

    from chipbench import serve, weights

    model, t = spec["config"]["model"], spec["traffic"]
    one = SingleDeviceSharding(topo.devices[0])
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    cfg = ArchConfig(**{k: v for k, v in model.items() if k in fields})
    params = _shapes(jax.eval_shape(
        lambda: weights.decoder_params(model, 0)), one)
    engine = ServingEngine(cfg, None, max_batch=t["max_batch"],
                           max_seq=t["max_seq"], gemm_backend="sfc_pallas")
    tokens = jax.ShapeDtypeStruct((t["batch"], t["prompt_len"]), jnp.int32,
                                  sharding=one)
    prefill = jax.jit(engine._prefill_impl).lower(params, tokens).compile()
    _report("prefill", prefill)
    if t["max_new_tokens"] > 1:
        cache = _shapes(jax.eval_shape(engine._prefill_impl, params, tokens)[1],
                        one)
        token = jax.ShapeDtypeStruct((t["batch"], 1), jnp.int32, sharding=one)
        _report("decode", jax.jit(engine._decode_impl).lower(
            params, token, cache).compile())
    ref = serve.load_reference(spec["config"]["reference"])
    frozen = tuple(sorted((k, v) for k, v in model.items()
                          if isinstance(v, (int, float, str, bool))))
    seq = t["prompt_len"] + t["max_new_tokens"] - 1
    x = jax.ShapeDtypeStruct((t["check"]["block"], seq, model["d_model"]),
                             jnp.float32, sharding=one)
    index = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    for precision in ("f32", "fp8"):
        _report(f"reference layer ({precision})", ref._layer.lower(
            x, params["layers"], index, model=frozen,
            precision=precision).compile())


def gemm_programs(spec, topo):
    from repro.core.ca_matmul import ca_matmul

    from chipbench import serve

    g = spec["config"]["gemm"]
    mesh = Mesh(np.asarray(topo.devices).reshape(
        g["mesh"]["tm"], g["mesh"]["tn"], g["mesh"]["kl"]), ("tm", "tn", "kl"))
    dt = jnp.dtype(g["dtype"])
    a = jax.ShapeDtypeStruct((g["m"], g["k"]), dt,
                             sharding=NamedSharding(mesh, P("tm", "kl")))
    b = jax.ShapeDtypeStruct((g["k"], g["n"]), dt,
                             sharding=NamedSharding(mesh, P("kl", "tn")))
    program = jax.jit(lambda a, b: ca_matmul(
        a, b, mesh=mesh, tm_axis="tm", tn_axis="tn", kl_axis="kl",
        backend=g["backend"], reduce=g["reduce"]))
    _report("ca_matmul", program.lower(a, b).compile())
    ref = serve.load_reference(spec["config"]["reference"])
    out = NamedSharding(mesh, P("tm", ("tn", "kl")))
    for precision in ("f32", "fp8"):
        _report(f"reference ({precision})",
                ref._program(out, precision).lower(a, b).compile())


def main(names):
    import json

    from jax.experimental import topologies

    sys.path[:0] = [str(bench.HERE), str(bench.ROOT / "src")]
    bench.setup_env()
    # no persistent cache: an entry compiled for a described chip cannot be
    # read back here; and Mosaic, not the interpreter the CPU would pick
    jax.config.update("jax_enable_compilation_cache", False)
    from repro.kernels import ops

    ops.default_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench_json = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    for cell in names or [w["name"] for w in bench_json["workloads"]]:
        spec = bench.load_cell(cell)
        print(f"{cell}:", flush=True)
        if spec["config"]["kind"] == "decoder_lm":
            serve_programs(spec, topo)
        else:
            gemm_programs(spec, topo)


if __name__ == "__main__":
    main(sys.argv[1:])
