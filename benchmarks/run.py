"""Benchmark runner — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; with ``--json PATH`` the same
rows are also written as a JSON document (the ``BENCH_*.json`` artifact CI
uploads so the perf trajectory is tracked across PRs).  ``--smoke`` runs a
reduced gemm_sweep + data-movement + llm_prefill subset that finishes in CI
minutes.

    python benchmarks/run.py                              # full CSV stream
    python benchmarks/run.py --smoke --json BENCH_gemm.json
"""

from __future__ import annotations

import argparse
import json
import platform
import sys


def _write_json(path: str) -> None:
    from benchmarks.common import records

    try:
        import jax

        backend = jax.default_backend()
        jax_version = jax.__version__
    except Exception:  # records are host-side; don't lose them over metadata
        backend = jax_version = "unknown"
    doc = {
        "schema": "repro-bench-v1",
        "backend": backend,
        "jax": jax_version,
        "python": platform.python_version(),
        "rows": records(),
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {len(doc['rows'])} rows to {path}", file=sys.stderr)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--json", metavar="PATH", default=None,
                   help="also write the emitted rows as JSON (BENCH_*.json)")
    p.add_argument("--smoke", action="store_true",
                   help="fast CI subset: gemm_sweep + data movement + one "
                        "llm_prefill cell")
    p.add_argument("--full", action="store_true",
                   help="full 125-shape gemm sweep")
    p.add_argument("--obs-jsonl", metavar="PATH", default=None,
                   help="write the repro.obs JSONL telemetry snapshot here "
                        "after the benchmarks run (the obs-smoke artifact)")
    args = p.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from benchmarks import (
        abft,
        data_movement,
        distributed_gemm,
        gemm_sweep,
        knob_prediction,
        llm_prefill,
        serving_smoke,
    )

    print("name,us_per_call,derived")
    if args.smoke:
        gemm_sweep.run(smoke=True)       # paper Figs. 1 / 6 / 9 (subset)
        gemm_sweep.run_backward(smoke=True)  # NT/TN + grouped/MoE buckets
        data_movement.run()              # paper Fig. 7
        data_movement.run_glu()          # fused gated-MLP HBM model
        data_movement.run_train()        # fwd + NT/TN backward traffic
        data_movement.run_train_update()  # fused-optimizer flush rows
        data_movement.run_attention()    # SFC flash prefill + decode rows
        abft.run()                       # checksum-lane overhead (gated)
        abft.run_measured()              # detect-vs-off liveness check
        llm_prefill.run(smoke=True)      # paper Fig. 10 (one cell)
        serving_smoke.run()              # obs series liveness (tune/serve)
    else:
        gemm_sweep.run(full=args.full)   # paper Figs. 1 / 6 / 9
        gemm_sweep.run_backward()        # NT/TN + grouped/MoE buckets
        data_movement.main()             # paper Fig. 7 + fused gated-MLP
        abft.main()                      # checksum-lane overhead rows
        knob_prediction.main()           # paper Fig. 8
        llm_prefill.main()               # paper Fig. 10
        distributed_gemm.main()          # paper Fig. 11

    if args.json:
        _write_json(args.json)
    if args.obs_jsonl:
        from repro import obs

        n = obs.to_jsonl(args.obs_jsonl)
        print(f"# wrote {n} obs series to {args.obs_jsonl}", file=sys.stderr)


if __name__ == "__main__":
    main()
