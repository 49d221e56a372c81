import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against ShapeDtypeStruct stand-ins (no allocation), print
memory_analysis / cost_analysis, and derive SSRoofline terms.

The two lines above MUST stay the very first statements: jax locks the
device count at first init, and the production meshes need 512 placeholder
host devices.  (Do NOT set this flag globally — smoke tests and benches are
single-device.)

Cost source: XLA's `compiled.cost_analysis()` counts every `while` (scan)
body ONCE, undercounting deep layer stacks by their trip count, so the
roofline terms come from `repro.roofline.hlo_cost.module_cost` — a static
walker over the optimized HLO that multiplies loop bodies by their
`known_trip_count` (validated exact on known programs in tests).  The raw
cost_analysis numbers are recorded alongside for reference.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ArchConfig, SHAPES, ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build_model, input_specs, param_specs
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.parallel.act_sharding import activation_sharding
from repro.parallel.sharding import (
    batch_specs,
    cache_specs,
    data_axes,
    make_shardings,
    spec_for_tree,
)
from repro.roofline.analysis import model_flops, roofline_hw, roofline_terms
from repro.roofline.hlo_cost import module_cost
from repro.train.step import make_train_step

# the deployment the dry-run models: production meshes of TPU v5e chips
TARGET_DEVICE_KIND = "TPU v5 lite"

SKIPS: Dict[tuple, str] = {}
for _arch in ARCH_IDS:
    _cfg = get_config(_arch)
    if not _cfg.subquadratic:
        SKIPS[(_arch, "long_500k")] = (
            "pure full-attention arch: long_500k requires sub-quadratic "
            "attention (DESIGN.md SSArch-applicability)"
        )


def _abstract_opt_state(params_abs):
    return jax.eval_shape(adamw_init, params_abs)


def auto_microbatches(cfg: ArchConfig, shape: ShapeConfig, n_dp: int) -> int:
    """Pick grad-accumulation so saved layer-boundary activations fit ~6GB
    per chip under remat='full' (saved = L x B_chip x S x d x 2B / mb)."""
    if shape.mode != "train":
        return 1
    b_chip = max(shape.global_batch // n_dp, 1)
    layers = cfg.n_layers + cfg.encoder_layers
    saved = layers * b_chip * shape.seq_len * cfg.d_model * 2
    mb = 1
    while saved / mb > 6e9 and mb < b_chip:
        mb *= 2
    return mb


def _compile_step(
    cfg: ArchConfig,
    shape: ShapeConfig,
    mesh,
    *,
    profile: str,
    remat: str,
    microbatches: int,
):
    """Lower + compile the step implied by shape.mode; returns (lowered, compiled)."""
    model = build_model(cfg)
    params_abs = param_specs(cfg)
    p_spec = spec_for_tree(params_abs, cfg, mesh, profile)
    p_shard = make_shardings(mesh, p_spec)
    act_policy = activation_sharding(mesh, data_axes(mesh), "model")

    if shape.mode == "train":
        opt_abs = _abstract_opt_state(params_abs)
        o_shard = make_shardings(mesh, spec_for_tree(opt_abs, cfg, mesh, profile))
        batch_abs = input_specs(cfg, shape, "train")["batch"]
        b_spec = batch_specs(cfg, mesh, shape.global_batch)
        b_shard = {k: NamedSharding(mesh, b_spec[k]) for k in batch_abs}
        step = make_train_step(
            model, AdamWConfig(), remat=remat, microbatches=microbatches
        )
        with mesh, act_policy:
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
            return lowered, lowered.compile()

    if shape.mode == "prefill":
        spec = input_specs(cfg, shape, "prefill")
        b_specs = batch_specs(cfg, mesh, shape.global_batch)
        in_sh = {k: NamedSharding(mesh, b_specs.get(k, P())) for k in spec}

        def prefill_fn(params, inputs):
            if cfg.family == "audio":
                return model.prefill(
                    params, inputs["tokens"], inputs["src_embeds"],
                    cache_len=shape.seq_len, remat=remat,
                )
            kw = {}
            if cfg.family == "vlm":
                kw = dict(
                    mrope_positions=inputs["mrope_positions"],
                    vision_embeds=inputs["vision_embeds"],
                )
            return model.prefill(
                params, inputs["tokens"], cache_len=shape.seq_len, remat=remat, **kw
            )

        with mesh, act_policy:
            jitted = jax.jit(prefill_fn, in_shardings=(p_shard, in_sh))
            lowered = jitted.lower(params_abs, spec)
            return lowered, lowered.compile()

    if shape.mode == "decode":
        spec = input_specs(cfg, shape, "decode")
        c_shard = make_shardings(
            mesh, cache_specs(spec["cache"], cfg, mesh, shape.global_batch)
        )
        t_shard = NamedSharding(mesh, P())

        def decode_fn(params, token, cache):
            return model.decode_step(params, token, cache)

        with mesh, act_policy:
            jitted = jax.jit(
                decode_fn,
                in_shardings=(p_shard, t_shard, c_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(params_abs, spec["token"], spec["cache"])
            return lowered, lowered.compile()

    raise ValueError(shape.mode)


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool,
    profile: str = "baseline",
    remat: str = "full",
    microbatches: Optional[int] = None,
):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    n_dp = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    mb = microbatches or auto_microbatches(cfg, shape, n_dp)

    t0 = time.time()
    lowered, compiled = _compile_step(
        cfg, shape, mesh, profile=profile, remat=remat, microbatches=mb
    )
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    raw_cost = compiled.cost_analysis()
    # jax < 0.5 returned [dict] (one per partition program), newer return dict
    if isinstance(raw_cost, (list, tuple)):
        raw_cost = raw_cost[0] if raw_cost else {}

    hlo = compiled.as_text()
    c = module_cost(hlo)  # loop-aware static cost (per-partition program)
    coll = {
        "total_bytes": c.total_coll_bytes,
        "per_op_bytes": c.coll_bytes,
        "per_op_counts": c.coll_counts,
    }
    terms = roofline_terms(
        {"flops": c.flops, "bytes accessed": c.bytes}, coll, n_chips=n_chips,
        hw=roofline_hw(TARGET_DEVICE_KIND),
    )
    mf = model_flops(cfg, shape)

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "profile": profile,
        "remat": remat,
        "microbatches": mb,
        "n_chips": n_chips,
        "compile_s": round(compile_s, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "raw_cost_analysis": {
            k: raw_cost.get(k) for k in ("flops", "bytes accessed")
        },
        "collectives": coll,
        "roofline": terms,
        "model_flops": mf,
        "useful_flops_ratio": (mf / n_chips) / terms["hlo_flops"]
        if terms["hlo_flops"]
        else None,
        "status": "ok",
    }


def run_cell(arch, shape_name, multi_pod, out_dir, **kw):
    key = (arch, shape_name)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    if key in SKIPS:
        rec = {
            "arch": arch,
            "shape": shape_name,
            "mesh": mesh_tag,
            "status": "skip",
            "reason": SKIPS[key],
        }
    else:
        try:
            rec = lower_cell(arch, shape_name, multi_pod=multi_pod, **kw)
        except Exception as e:  # a failed cell is a bug — record loudly
            rec = {
                "arch": arch,
                "shape": shape_name,
                "mesh": mesh_tag,
                "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = kw.get("profile", "baseline")
        fname = f"{arch}__{shape_name}__{mesh_tag}__{tag}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    status = rec["status"]
    extra = ""
    if status == "ok":
        r = rec["roofline"]
        extra = (
            f" compile={rec['compile_s']}s mb={rec['microbatches']}"
            f" dominant={r['dominant']}"
            f" t=({r['t_compute_s']:.2e},{r['t_memory_s']:.2e},{r['t_collective_s']:.2e})s"
            f" useful={rec['useful_flops_ratio']:.2f}"
        )
    elif status == "error":
        extra = " " + rec["error"][:200]
    print(f"[dryrun] {arch:22s} {shape_name:12s} {mesh_tag:8s} {status}{extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--profile", default="baseline")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    kw = dict(profile=args.profile, remat=args.remat, microbatches=args.microbatches)
    if args.all:
        meshes = [False, True]
        if args.single_pod_only:
            meshes = [False]
        if args.multi_pod_only:
            meshes = [True]
        n_ok = n_skip = n_err = 0
        for mp in meshes:
            for arch in ARCH_IDS:
                for shape in SHAPES:
                    rec = run_cell(arch, shape, mp, args.out, **kw)
                    n_ok += rec["status"] == "ok"
                    n_skip += rec["status"] == "skip"
                    n_err += rec["status"] == "error"
        print(f"[dryrun] done: ok={n_ok} skip={n_skip} error={n_err}")
        raise SystemExit(1 if n_err else 0)

    assert args.arch and args.shape, "--arch/--shape or --all"
    arch = args.arch.replace("-", "_").replace(".", "_")
    rec = run_cell(arch, args.shape, args.multi_pod, args.out, **kw)
    raise SystemExit(0 if rec["status"] in ("ok", "skip") else 1)


if __name__ == "__main__":
    main()
