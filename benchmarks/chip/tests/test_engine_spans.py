"""The readers of the engine's host spans and counters
(``sync_idle_ms.decode``, ``launch_idle_ms.decode``,
``host_reads_per_step.decode``), on synthetic events and on the spans a tiny
engine emits under the profiler on the CPU."""

from __future__ import annotations

import copy
import tempfile

import pytest

import run as bench
from chipbench import trace
from chipbench.spans import host_spans

SYNC = bench.load_reader("sync_idle_ms.decode")
LAUNCH = bench.load_reader("launch_idle_ms.decode")
READS = bench.load_reader("host_reads_per_step.decode")


def synthetic():
    """One device, window [0, 1000) ns.

    A prefill [0, 100): launch [0, 10), token sync [10, 100), its program
    [5, 60), so 40 ns idle in its sync (not a decode step's).  Decode step 1
    [100, 300): launch [105, 115), sync [115, 280), program [112, 200).
    Decode step 2 [300, 500): launch [300, 320), sync [320, 490), program
    [310, 400).  A token sync outside any decode [600, 650), and a decode
    step [950, 1050) that the window cuts, with its children."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 5, 55], ["fusion.2", 112, 88],
                    ["fusion.3", 310, 90], ["fusion.4", 960, 30]],
            "modules": [],
        }},
        "host": [
            ["bench/window", 0, 1000],
            ["serving/prefill", 0, 100], ["serving/launch", 0, 10],
            ["serving/token_sync", 10, 90],
            ["serving/decode", 100, 200], ["serving/launch", 105, 10],
            ["serving/token_sync", 115, 165],
            ["serving/decode", 300, 200], ["serving/launch", 300, 20],
            ["serving/token_sync", 320, 170],
            ["serving/token_sync", 600, 50],
            ["serving/decode", 950, 100], ["serving/launch", 950, 5],
            ["serving/token_sync", 990, 40],
        ],
        "window": [0, 1000],
    }


def ctx(events):
    return {"trace": trace.Trace(events)}


def test_idle_inside_each_child_of_a_decode_step():
    c = ctx(synthetic())
    # sync: [200, 280) = 80 and [400, 490) = 90, over 2 decode steps
    assert SYNC(c) == pytest.approx(85e-6)
    # launch: [105, 112) = 7 and [300, 310) = 10
    assert LAUNCH(c) == pytest.approx(8.5e-6)


def test_a_program_without_the_child_spans_reads_nothing():
    ev = synthetic()
    ev["host"] = [h for h in ev["host"]
                  if h[0] in ("bench/window", "serving/decode",
                              "serving/prefill")]
    assert SYNC(ctx(ev)) is None and LAUNCH(ctx(ev)) is None
    ev["host"] = [["bench/window", 0, 1000]]
    assert SYNC(ctx(ev)) is None and LAUNCH(ctx(ev)) is None


def test_span_names_match_exactly():
    ev = synthetic()
    ev["host"] = [[n + "#step=1#", s, d] if n == "serving/token_sync" else
                  [n, s, d] for n, s, d in ev["host"]]
    assert SYNC(ctx(ev)) is None


@pytest.fixture
def registry():
    from repro import obs

    obs.reset()
    obs.set_enabled(True)
    yield obs
    obs.reset()
    obs.set_enabled(None)


def test_host_reads_per_step(registry):
    assert READS({}) is None  # no counters: a program without them
    registry.inc("serving.host_reads", 16, phase="prefill")
    for _ in range(4):
        registry.inc("serving.decode_steps")
        registry.inc("serving.host_reads", 16, phase="decode")
    assert READS({}) == 16.0


def test_the_engines_own_spans_under_the_profiler(registry):
    """A tiny engine traced on the CPU: its spans are host events of the
    window's line under these exact names (attributes are not part of
    them), each decode step holds one launch and one token sync, and the
    prefill's token sync is left out."""
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.serving.engine import ServingEngine

    cfg = get_config("qwen3_4b").reduced()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_batch=2, max_seq=32)
    prompts = list(np.random.default_rng(5).integers(
        0, cfg.vocab, (2, 8), dtype=np.int32))
    engine.run(engine.submit_many(prompts, max_new_tokens=2))  # compile
    registry.reset()
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            with TraceAnnotation(trace.WINDOW_SPAN):
                engine.run(engine.submit_many(prompts, max_new_tokens=4))
        finally:
            jax.profiler.stop_trace()
        events = trace.extract(trace.xplane_path(tmp))
    # the CPU has no device line: give the trace one with no op in the
    # window, so every span is idle throughout
    events = copy.deepcopy(events)
    lo = events["window"][0]
    events["devices"] = {"/device:TPU:0": {"ops": [["fusion", lo - 10, 5]],
                                           "modules": []}}
    t = trace.Trace(events)
    decodes = host_spans(t, "serving/decode")
    syncs = host_spans(t, "serving/token_sync")
    launches = host_spans(t, "serving/launch")
    assert len(decodes) == 3 and len(host_spans(t, "serving/prefill")) == 1
    assert len(syncs) == len(launches) == 4
    in_decode = [s for s in syncs if any(a <= s[0] and s[1] <= b
                                         for a, b in decodes)]
    assert len(in_decode) == 3
    want = trace.total(in_decode) / 3 * 1e-6
    assert SYNC({"trace": t}) == pytest.approx(want)
    assert LAUNCH({"trace": t}) > 0
    assert READS({}) == 2.0
