"""Chip benchmark: runs one cell of ``BENCHMARK.json`` and prints its result.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json`` names its configuration (``configs/<config>.json``, which
names its reference module, ``references/<reference>.py``: the weights, the
plain reference and the work counts of its architecture) and its traffic
(``traffic/<traffic>.json``); ``limits/<cell>.json`` holds the limits of
its correctness check and ``metrics/<metric>.py`` reads each per-layer
metric.  Adding a cell, or a decoder of a new architecture, adds files and
entries and edits none.

``--trace 0`` measures the cell's end-to-end metrics over ``--seconds``;
``--trace 1`` traces the traffic's ``trace_rounds`` rounds with the JAX
profiler and reports the per-layer metrics.  Both check the outputs of the
timed programs against the reference once the window has closed.  The last
line of standard output is one JSON object; the compared numbers and their
limits are the last lines of standard error.  Without the chips the cell
asks for, the run exits non-zero and prints no result.

``--study N`` (not a benchmark run) reads the program's numbers and the
fp8 control's on N seeds from ``--seed`` on, one short window each, in one
process: the readings the limits are set from.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import atexit  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "cell": cell,
        "config": json.loads((root / config["file"]).read_text()),
        "traffic": json.loads(
            (HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def require_devices(n: int):
    """The cell's accelerators, or exit non-zero: never the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit(f"no accelerator: JAX's default device is "
                         f"{devices[0].device_kind!r}")
    if len(devices) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def setup_env() -> None:
    """Strict mode, knobs from the committed code only (a fresh empty knob
    cache outside the checkout), and JAX's compilation cache at one fixed
    place in the checkout unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    os.environ["REPRO_STRICT"] = "1"
    fd, knobs = tempfile.mkstemp(prefix="sfc_knobs_", suffix=".json")
    os.write(fd, b"{}")
    os.close(fd)
    atexit.register(os.remove, knobs)
    os.environ["REPRO_SFC_TUNE_CACHE"] = knobs
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR)))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts lowerings (each a compile, or a read of the compile cache)."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


def load_reader(name: str):
    from chipbench import load_module

    return load_module(HERE / "metrics" / f"{name}.py",
                       f"metric_{name.replace('.', '_')}").read


def memory_peak_bytes(devices) -> int:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


def make_cell(spec: dict, seed: int, devices):
    kind = spec["config"]["kind"]
    if kind == "decoder_lm":
        from chipbench import serve

        return serve.Cell(spec["cell"]["name"], spec["config"],
                          spec["traffic"], seed)
    if kind == "distributed_gemm":
        from chipbench import gemm

        return gemm.Cell(spec["cell"]["name"], spec["config"],
                         spec["traffic"], seed, devices)
    raise SystemExit(f"unknown configuration kind {kind!r}")


def traced_window(cell, seed: int, rounds: int, out_dir: str,
                  save_events: str = ""):
    """The traced window: ``rounds`` rounds under the profiler."""
    import jax
    from jax.profiler import TraceAnnotation

    from chipbench import trace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host annotations only: no Python calls
    jax.profiler.start_trace(out_dir, profiler_options=options)
    try:
        win = cell.window(seed, 0.0, n_rounds=rounds, annotate=TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    t0 = time.perf_counter()
    events = trace.extract(trace.xplane_path(out_dir))
    reduced = trace.Trace(events)
    log(f"trace read in {time.perf_counter() - t0!r} s")
    if save_events:
        pathlib.Path(save_events).write_text(json.dumps(
            {**trace.trimmed(events), "summary": reduced.summary()}))
    return win, reduced


def run(spec: dict, seed: int, seconds: float, traced: bool, devices,
        t_process: float = T_PROCESS, save_events: str = "") -> dict:
    """One run of a cell; the result's dict."""
    from chipbench import compare, flops

    name = spec["cell"]["name"]
    peaks = flops.device_peaks(devices[0].device_kind)
    counter = CompileCounter()
    runtime_s = time.perf_counter() - t_process
    cell = make_cell(spec, seed, devices)
    compiles_before = counter.n
    setup_s = time.perf_counter() - t_process
    log(f"setup_s {setup_s!r}: runtime and imports {runtime_s!r}, "
        + ", ".join(f"{k} {v!r}" for k, v in cell.setup_parts.items()))
    trace_ = None
    if traced:
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as tmp:
            win, trace_ = traced_window(cell, seed,
                                        spec["traffic"]["trace_rounds"], tmp,
                                        save_events)
    else:
        win = cell.window(seed, seconds)
    compiles = counter.n - compiles_before
    measured = cell.metrics(win)
    work = cell.work(win, peaks)
    mem = memory_peak_bytes(devices)
    cell.drop_engine()
    checks = compare.judge(cell.check(win), name)

    metrics = {}
    if traced:
        ctx = {"trace": trace_, "work": work, "peaks": peaks,
               "config": spec["config"], "traffic": spec["traffic"]}
        for m in spec["per_layer"]:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": compare.is_correct(checks),
              "attempted": measured["_attempted"],
              "failed": measured["_failed"], "metrics": metrics,
              "device": device, "compiles_in_window": compiles,
              "setup_s": setup_s}
    if traced:
        device.update(busy_s=trace_.busy_s(), window_s=trace_.window_s)
        result["breakdown"] = {"device_ops": trace_.top_ops(10),
                               "idle_gaps": trace_.idle_gaps(10)}
    result["checks"] = checks
    return result


def study(spec: dict, seed: int, n_seeds: int, devices) -> None:
    """Program and control readings on ``n_seeds`` seeds, one process."""
    cell = make_cell(spec, seed, devices)
    n_rounds = spec["traffic"]["study_rounds"]
    for s in range(seed, seed + n_seeds):
        if s != seed:
            if hasattr(cell, "make_params"):
                cell.params = cell.engine.params = None
                cell.params = cell.engine.params = cell.make_params(s)
            else:
                cell.a = cell.b = None
                cell.operands(s)
        win = cell.window(s, 0.0, n_rounds=n_rounds)
        readings = cell.check(win, control=True)
        print(json.dumps({"study_seed": s, **readings}), flush=True)
        del win


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--study", type=int, default=0)
    ap.add_argument("--save-events", default="",
                    help="with --trace 1: write a trimmed copy of the trace's "
                         "events to this file")
    args = ap.parse_args(argv)

    spec = load_cell(args.workload)
    devices = require_devices(spec["cell"]["chips"])
    setup_env()
    sys.path[:0] = [str(HERE), str(ROOT / "src")]

    if args.study:
        study(spec, args.seed, args.study, devices)
        return
    result = run(spec, args.seed, args.seconds, bool(args.trace), devices,
                 save_events=args.save_events)
    from chipbench import compare

    log(f"compiles_in_window {result['compiles_in_window']}")
    compare.report(result["checks"])
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
