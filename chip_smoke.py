"""Chip smoke test: the quickest proof that the system runs on a TPU.

    python chip_smoke.py             # one chip: yi-6b on the SFC kernels
    python chip_smoke.py --chips 4   # four chips: distributed 2.5D GEMM only

One chip.  The SFC GEMM kernels (forward, NT, TN, GLU, grouped) are checked
at yi-6b widths against an f32 ``jnp.dot``; then the yi-6b `ServingEngine`
(all 32 layers, bf16 weights made from a seed, ``gemm_backend="sfc_pallas"``)
warms up and answers 8 requests of 512 prompt tokens and 32 new tokens, with
``max_batch`` 4.  Its prefill logits are compared with the ``xla`` backend's
on the same weights and prompts, at a tolerance set by XLA's own bf16
distance from an f32 reference.  ``REPRO_STRICT=1`` is set, knobs come from
the committed code only (an empty knob cache this run creates), and the run
fails if any fallback rung served or anything was quarantined.

Four chips (``--chips 4``).  `ca_matmul` (``psum`` and ``psum_scatter``) and
`summa_ca_matmul`, with the ``sfc_pallas`` local GEMM, at 8192^3 bf16 on a
4-device mesh, each against XLA's ``jnp.dot`` on the same sharded operands.

Everything runs in this one process: a chip belongs to one process, so no
child is started.  The script fails, and prints no result, where JAX finds no
TPU.  The last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out"

# yi-6b serving traffic of the one-chip phase
N_REQUESTS, PROMPT_LEN, MAX_NEW, MAX_BATCH = 8, 512, 32, 4
# bf16 GEMM outputs against an f32 reference: relative Frobenius error
KERNEL_TOL = 1e-2
# sfc_pallas vs xla prefill logits: the two bf16 programs round at different
# points of the same math.  Each is measured against an f32 reference of the
# same weights (activations in f32, matmuls at "highest" precision); the
# sfc-vs-xla difference may be at most this multiple of XLA's own bf16
# distance from that reference (two programs each e from it differ by <= 2e)
LOGITS_TOL_VS_XLA_BF16 = 2.0
# the distributed phase: one square bf16 GEMM
DIST_SIZE = 8192


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, want) -> float:
    """||got - want||_F / ||want||_F, in f32 on the device."""
    import jax.numpy as jnp

    g = jnp.asarray(got, jnp.float32)
    w = jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))


def require_tpu(n_chips: int):
    """The TPU devices, or exit non-zero naming what is missing."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU: JAX's default device is "
            f"{devices[0].platform!r} ({devices[0].device_kind})"
        )
    if len(devices) != n_chips:
        sys.exit(
            f"chip_smoke: --chips {n_chips} needs {n_chips} TPU devices, "
            f"JAX sees {len(devices)}"
        )
    return devices


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _timed(fn, *args):
    """Compile ``jax.jit(fn)`` for ``args``; return (compiled, seconds)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def kernel_checks(d: int, ff: int, m: int, groups) -> None:
    """SFC forward, NT, TN, GLU and grouped GEMMs against f32 ``jnp.dot``
    at widths (d_model ``d``, d_ff ``ff``, ``m`` token rows)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    hi = jax.lax.Precision.HIGHEST

    def f32dot(a, b):
        return jnp.dot(
            a.astype(jnp.float32), b.astype(jnp.float32), precision=hi
        )

    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def rand(*shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    x, h = rand(m, d), rand(m, ff)
    w_up, w_gate, w_down = rand(d, ff), rand(d, ff), rand(ff, d)
    rows = rand(sum(groups), d)
    w_grouped = rand(len(groups), d, ff)

    def grouped_ref(rows, w):
        parts, off = [], 0
        for e, g in enumerate(groups):
            parts.append(f32dot(rows[off:off + g], w[e]))
            off += g
        return jnp.concatenate(parts)

    cases = {
        # name: (SFC kernel, f32 reference, operands)
        f"forward {m}x{d}x{ff} (down-projection)": (
            ops.sfc_matmul, f32dot, (h, w_down)),
        f"nt {m}x{ff}x{d} (dA = dC.W^T)": (
            ops.sfc_matmul_nt, lambda a, b: f32dot(a, b.T), (x, w_up.T)),
        f"tn {d}x{ff}x{m} (dW = A^T.dC)": (
            ops.sfc_matmul_tn, lambda a, b: f32dot(a.T, b), (x, h)),
        f"glu {m}x{ff}x{d}": (
            lambda a, g, v: ops.sfc_glu_matmul(a, g, v),
            lambda a, g, v: jax.nn.silu(f32dot(a, g)) * f32dot(a, v),
            (x, w_gate, w_up)),
        f"grouped {len(groups)}x{d}x{ff} rows={tuple(groups)}": (
            lambda r, w: ops.sfc_grouped_matmul(r, w, groups),
            grouped_ref, (rows, w_grouped)),
    }
    for name, (kernel, ref, operands) in cases.items():
        compiled, secs = _timed(kernel, *operands)
        n_custom = compiled.as_text().count("tpu_custom_call")
        err = rel_err(compiled(*operands), jax.jit(ref)(*operands))
        log(f"[kernel] {name}: rel_err={err:.3e} (tol {KERNEL_TOL:g}) "
            f"tpu_custom_call={n_custom} compile_s={secs:.1f}")
        check(n_custom > 0, f"{name}: no tpu_custom_call in the program")
        check(err <= KERNEL_TOL, f"{name}: rel_err {err:.3e} > {KERNEL_TOL:g}")


def serve_yi(cfg, *, n_requests: int, prompt_len: int, max_new: int,
             max_batch: int) -> None:
    """Serve ``cfg`` on the SFC kernels and check the answers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.registry import build_model
    from repro.robust import degradation_report
    from repro.serving.engine import ServingEngine

    log(f"[config] {cfg.name}: d_model={cfg.d_model} n_layers={cfg.n_layers} "
        f"n_heads={cfg.n_heads} kv_heads={cfg.kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} param_dtype={cfg.param_dtype} "
        f"attn_impl={cfg.attn_impl}")
    t0 = time.perf_counter()
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    log(f"[init] {n_params} params made from seed 0 in "
        f"{time.perf_counter() - t0:.1f}s")

    max_seq = prompt_len + max_new + 1
    engine = ServingEngine(cfg, params, max_batch=max_batch, max_seq=max_seq,
                           gemm_backend="sfc_pallas")
    tokens = jnp.zeros((max_batch, prompt_len), jnp.int32)
    prefill_c, prefill_s = _timed(engine._prefill_impl, params, tokens)
    cache = jax.eval_shape(engine._prefill_impl, params, tokens)[1]
    token = jax.ShapeDtypeStruct((max_batch, 1), jnp.int32)
    decode_c, decode_s = _timed(engine._decode_impl, params, token, cache)
    n_prefill = prefill_c.as_text().count("tpu_custom_call")
    n_decode = decode_c.as_text().count("tpu_custom_call")
    log(f"[compile] prefill {prefill_s:.1f}s, decode {decode_s:.1f}s; "
        f"tpu_custom_call: prefill={n_prefill} decode={n_decode}")
    check(n_prefill > 0 and n_decode > 0,
          "the prefill or decode program holds no SFC kernel")

    t0 = time.perf_counter()
    engine.warmup(prompt_len)
    log(f"[warmup] {time.perf_counter() - t0:.1f}s")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)
               for _ in range(n_requests)]
    t0 = time.perf_counter()
    done = engine.run(engine.submit_many(prompts, max_new_tokens=max_new))
    wall = time.perf_counter() - t0
    n_tokens = sum(len(r.output or []) for r in done)
    answered = sum(r.status == "completed" and len(r.output) == max_new
                   for r in done)
    log(f"[serve] {answered}/{n_requests} requests answered, {n_tokens} "
        f"tokens served in {wall:.2f}s (smoke timing, not a benchmark)")
    check(answered == n_requests, "not every request was answered in full")

    # the same weights and prompts through plain XLA, in bf16 and in f32
    batch = jnp.asarray(np.stack(prompts[:max_batch]))
    sfc_logits = engine._prefill(params, batch)[0]
    xla_logits = ServingEngine(
        cfg, params, max_batch=max_batch, max_seq=max_seq,
        gemm_backend="xla",
    )._prefill(params, batch)[0]
    # f32 embeddings make every activation f32; the weights stay bf16
    ref_params = dict(params, embed=params["embed"].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        ref_logits = ServingEngine(
            cfg, ref_params, max_batch=max_batch, max_seq=max_seq,
            gemm_backend="xla",
        )._prefill(ref_params, batch)[0]
    del ref_params
    err_sfc = rel_err(sfc_logits, ref_logits)
    err_xla = rel_err(xla_logits, ref_logits)
    err = rel_err(sfc_logits, xla_logits)
    tol = LOGITS_TOL_VS_XLA_BF16 * err_xla
    agree = float(jnp.mean(
        jnp.argmax(sfc_logits, -1) == jnp.argmax(xla_logits, -1)))
    log(f"[logits] prefill {tuple(sfc_logits.shape)} vs f32 reference: "
        f"sfc_pallas rel_err={err_sfc:.3e}, xla (bf16) rel_err={err_xla:.3e}")
    log(f"[logits] sfc_pallas vs xla: rel_err={err:.3e} (tol "
        f"{LOGITS_TOL_VS_XLA_BF16:g} x xla's bf16 error = {tol:.3e}), "
        f"argmax agreement {agree:.2f}")
    check(bool(jnp.all(jnp.isfinite(sfc_logits))), "non-finite sfc logits")
    check(err <= tol, f"logits rel_err {err:.3e} > {tol:.3e}")

    rep = degradation_report()
    log(f"[degradation] {json.dumps(rep, sort_keys=True)}")
    check(rep["fallback_calls"] == 0, "a fallback rung served")
    check(not rep["quarantined"], "a rung was quarantined")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def distributed_checks(devices, size: int) -> None:
    """2.5D `ca_matmul` (psum, psum_scatter) and `summa_ca_matmul` with the
    SFC local GEMM on a 4-device mesh, each against XLA's ``jnp.dot`` on
    the same sharded operands."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.ca_matmul import ca_matmul, summa_ca_matmul

    ka, kb = jax.random.split(jax.random.PRNGKey(2))
    a = jax.random.normal(ka, (size, size), jnp.bfloat16)
    b = jax.random.normal(kb, (size, size), jnp.bfloat16)
    grid = np.asarray(devices).reshape(2, 1, 2)
    mesh_25d = Mesh(grid, ("tm", "tn", "kl"))
    mesh_2d = Mesh(grid.reshape(2, 2), ("tm", "tn"))

    variants = {
        # name: (mesh, A spec, B spec, program, distinct output blocks)
        "ca_matmul psum (tm=2, kl=2)": (
            mesh_25d, P("tm", "kl"), P("kl", "tn"),
            lambda a, b: ca_matmul(a, b, mesh=mesh_25d, tm_axis="tm",
                                   tn_axis="tn", kl_axis="kl",
                                   backend="sfc_pallas", reduce="psum"),
            2),
        "ca_matmul psum_scatter (tm=2, kl=2)": (
            mesh_25d, P("tm", "kl"), P("kl", "tn"),
            lambda a, b: ca_matmul(a, b, mesh=mesh_25d, tm_axis="tm",
                                   tn_axis="tn", kl_axis="kl",
                                   backend="sfc_pallas",
                                   reduce="psum_scatter"),
            4),
        "summa_ca_matmul (tm=2, tn=2)": (
            mesh_2d, P("tm", "tn"), P(None, "tn"),
            lambda a, b: summa_ca_matmul(a, b, mesh=mesh_2d, tm_axis="tm",
                                         tn_axis="tn", backend="sfc_pallas"),
            4),
    }

    def xla_dot(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    for name, (mesh, a_spec, b_spec, program, n_blocks) in variants.items():
        a_s = jax.device_put(a, NamedSharding(mesh, a_spec))
        b_s = jax.device_put(b, NamedSharding(mesh, b_spec))
        compiled, secs = _timed(program, a_s, b_s)
        out = compiled(a_s, b_s)
        ref = jax.jit(xla_dot)(a_s, b_s)
        err = rel_err(out, ref)
        shards = out.addressable_shards
        blocks = {}
        for sh in shards:
            key = tuple((s.start, s.stop) for s in sh.index)
            blocks.setdefault(key, []).append(np.asarray(sh.data))
        copies_equal = all(
            all(np.array_equal(c[0], ci) for ci in c[1:])
            for c in blocks.values()
        )
        n_devices = len({sh.device for sh in shards})
        log(f"[dist] {name}: {tuple(out.shape)} {out.dtype} rel_err={err:.3e} "
            f"(tol {KERNEL_TOL:g}) vs jnp.dot; {len(shards)} addressable "
            f"shards on {n_devices} devices, {len(blocks)} distinct blocks, "
            f"replicas identical={copies_equal}; compile_s={secs:.1f}")
        check(err <= KERNEL_TOL, f"{name}: rel_err {err:.3e} > {KERNEL_TOL:g}")
        check(n_devices == 4 and len(shards) == 4,
              f"{name}: output not spread over 4 devices")
        check(len(blocks) == n_blocks, f"{name}: {len(blocks)} distinct "
              f"output blocks, expected {n_blocks}")
        check(copies_equal, f"{name}: replicated blocks differ")


# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: yi-6b serving on one chip (default); 4: only "
                         "the distributed 2.5D/SUMMA GEMM on four chips")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    # knobs from the committed code only: a knob cache this run creates empty
    OUT_DIR.mkdir(exist_ok=True)
    knob_cache = OUT_DIR / "chip_smoke_knobs.json"
    knob_cache.write_text("{}")
    os.environ["REPRO_SFC_TUNE_CACHE"] = str(knob_cache)
    os.environ["REPRO_STRICT"] = "1"

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    kind = devices[0].device_kind
    log(f"[device] platform={devices[0].platform} kind={kind} "
        f"count={len(devices)}")
    log(f"[cache] compilation cache: {use_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        distributed_checks(devices, DIST_SIZE)
    else:
        cfg = get_config("yi-6b")
        kernel_checks(cfg.d_model, cfg.d_ff, PROMPT_LEN,
                      groups=(512, 384, 640, 512))
        serve_yi(cfg, n_requests=N_REQUESTS, prompt_len=PROMPT_LEN,
                 max_new=MAX_NEW, max_batch=MAX_BATCH)
    log(f"[done] {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
