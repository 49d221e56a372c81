"""Training launcher: end-to-end driver usable at laptop scale (CPU) and,
unchanged, on a real mesh (the mesh/axis wiring is the dry-run's).

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
      --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Fault tolerance: auto-resumes from the newest committed checkpoint; the
synthetic data pipeline regenerates batch(step) deterministically, so a
killed-and-restarted run continues the exact loss trajectory
(tests/test_fault_tolerance.py asserts bitwise equality).
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.synthetic import SyntheticLM, SyntheticLMConfig
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh_for
from repro.models.registry import build_model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.parallel.act_sharding import activation_sharding
from repro.parallel.sharding import batch_specs, data_axes, make_shardings, spec_for_tree
from repro.train.checkpoint import CheckpointManager
from repro.train.fault_tolerance import StepWatchdog, TrainLoop
from repro.train.step import BackendConfig, make_train_step


def build_trainer(
    cfg,
    *,
    batch: int,
    seq: int,
    lr: float = 3e-4,
    total_steps: int = 1000,
    remat: str = "none",
    microbatches: int = 1,
    mesh=None,
    seed: int = 0,
    gemm_backend: Optional[str] = None,
    fused_optimizer: bool = False,
    stochastic_round: bool = True,
):
    """Returns (params, opt_state, jitted step, batch_fn).

    ``gemm_backend="sfc_pallas"`` trains end-to-end on the SFC kernels:
    forward projections AND the custom-VJP backward (NT/TN kernels).
    ``fused_optimizer=True`` additionally runs AdamW inside the TN kernel
    flush for routed 2-D weights (single-host; clip-by-global-norm stays
    exact via the two-phase flush — see `train.step.make_train_step`)."""
    if fused_optimizer and mesh is not None:
        raise ValueError("fused_optimizer is a single-host path (no mesh)")
    model = build_model(cfg)
    opt_cfg = AdamWConfig(lr=lr, total_steps=total_steps, warmup_steps=min(100, total_steps // 10 + 1))
    step_fn = make_train_step(
        model, opt_cfg, remat=remat, microbatches=microbatches,
        backend=BackendConfig(gemm_backend=gemm_backend, fused_optimizer=fused_optimizer, stochastic_round=stochastic_round),
    )

    params = model.init(jax.random.PRNGKey(seed))
    opt_state = adamw_init(params)

    data = SyntheticLM(SyntheticLMConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed))

    def batch_fn(step: int):
        b = data.batch(step)
        out = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.family == "audio":
            rng = np.random.default_rng(step)
            out["src_embeds"] = jnp.asarray(
                rng.normal(size=(batch, seq, cfg.d_model)).astype(np.float32) * 0.1
            )
        if cfg.family == "vlm":
            out["mrope_positions"] = jnp.broadcast_to(
                jnp.arange(seq)[None, None], (3, batch, seq)
            ).astype(jnp.int32)
            rng = np.random.default_rng(step)
            n_img = min(8, seq)
            out["vision_embeds"] = jnp.asarray(
                rng.normal(size=(batch, n_img, cfg.d_model)).astype(np.float32) * 0.1
            )
        return out

    if mesh is not None:
        p_sh = make_shardings(mesh, spec_for_tree(params, cfg, mesh))
        o_sh = make_shardings(mesh, spec_for_tree(opt_state, cfg, mesh))
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)
        with mesh, activation_sharding(mesh, data_axes(mesh), "model"):
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_sh, o_sh, None),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),
            )
    else:
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    return params, opt_state, jitted, batch_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--fail-at", type=int, default=None, help="simulate preemption")
    ap.add_argument(
        "--backend", default=None,
        choices=["xla", "sfc_pallas", "sfc_reference"],
        help="GEMM backend for the train step (fwd + custom-VJP bwd)",
    )
    ap.add_argument(
        "--fused-optimizer", action="store_true",
        help="AdamW inside the TN kernel flush for routed 2-D weights "
             "(dW never touches HBM; exact grad clipping via the "
             "two-phase flush)",
    )
    ap.add_argument(
        "--no-stochastic-round", action="store_true",
        help="round-to-nearest bf16 write-back in the fused flush",
    )
    ap.add_argument(
        "--obs-export", default=None, metavar="PATH",
        help="write the JSONL telemetry snapshot here on exit "
             "(train-step spans, [ft] event counters, tune/ladder series)",
    )
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = None
    if args.data_parallel * args.model_parallel > 1:
        mesh = make_mesh_for(args.data_parallel, args.model_parallel)

    params, opt_state, jitted, batch_fn = build_trainer(
        cfg,
        batch=args.batch,
        seq=args.seq,
        lr=args.lr,
        total_steps=args.steps,
        remat=args.remat,
        microbatches=args.microbatches,
        mesh=mesh,
        gemm_backend=args.backend,
        fused_optimizer=args.fused_optimizer,
        stochastic_round=not args.no_stochastic_round,
    )

    ckpt = CheckpointManager(args.ckpt_dir or "/tmp/repro_ckpt", interval=args.ckpt_every)
    loop = TrainLoop(
        train_step=jitted, batch_fn=batch_fn, ckpt=ckpt, watchdog=StepWatchdog()
    )
    try:
        params, opt_state, history = loop.run(
            params,
            opt_state,
            num_steps=args.steps,
            resume=args.ckpt_dir is not None,
            fail_at=args.fail_at,
        )
    finally:
        if args.obs_export:
            from repro import obs

            n = obs.to_jsonl(args.obs_export)
            print(f"[obs] wrote {n} series to {args.obs_export}")
    print(f"final loss: {history[-1][1]:.4f}  (from {history[0][1]:.4f})")


if __name__ == "__main__":
    main()
