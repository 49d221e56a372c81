"""Serving launcher: batched-request demo driver.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --requests 12 --prompt-len 32 --max-new 16 --backend sfc_pallas
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import build_model
from repro.serving.engine import ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--backend", default="xla", choices=["xla", "sfc_pallas", "sfc_reference"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family == "audio":
        raise SystemExit("enc-dec serving demo: use examples/serve_batched.py")

    model = build_model(cfg)
    # jitted: the random draws and the cast to param_dtype fuse, so no f32
    # copy of a full-width model is ever resident
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    engine = ServingEngine(
        cfg,
        params,
        max_batch=args.max_batch,
        max_seq=args.prompt_len + args.max_new + 1,
        gemm_backend=args.backend,
    )
    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32)
        for _ in range(args.requests)
    ]
    reqs = engine.submit_many(prompts, max_new_tokens=args.max_new)
    done = engine.run(reqs)
    rep = engine.latency_report(done)
    print(
        f"[serve] backend={args.backend} n={rep['n_requests']} "
        f"ttft={rep['ttft_mean_s']*1e3:.1f}ms latency={rep['latency_mean_s']*1e3:.1f}ms "
        f"throughput={rep['tokens_per_s']:.1f} tok/s "
        f"fallback_calls={engine.degradation_report()['fallback_calls']}"
    )


if __name__ == "__main__":
    main()
