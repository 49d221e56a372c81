"""Tests for `repro.obs`: registry semantics, the REPRO_OBS gate, span
tracing, exporters, the drift monitor, and the unified telemetry surfaces
(health registry / knob cache / serving / train loop as obs views)."""

import json
import math

import numpy as np
import pytest

from repro import obs
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_labels_and_total():
    c = obs_metrics.Registry().counter("x")
    c.inc()
    c.inc(2.0, op="gemm")
    c.inc(op="gemm")
    assert c.value() == 1.0
    assert c.value(op="gemm") == 3.0
    assert c.total() == 4.0


def test_gauge_last_write_wins():
    g = obs_metrics.Registry().gauge("g")
    g.set(1.0, ns="a")
    g.set(7.5, ns="a")
    assert g.value(ns="a") == 7.5
    assert g.value(ns="missing") is None


def test_histogram_summary_percentiles():
    h = obs_metrics.Histogram("h")
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100
    assert s["sum"] == pytest.approx(5050.0)
    assert s["max"] == 100.0
    assert s["p50"] == pytest.approx(50.5)
    assert 95.0 <= s["p95"] <= 96.0
    assert 99.0 <= s["p99"] <= 100.0


def test_histogram_empty_summary_is_zeros():
    h = obs_metrics.Histogram("h")
    assert h.summary() == {
        "count": 0, "sum": 0.0, "mean": 0.0, "max": 0.0,
        "p50": 0.0, "p95": 0.0, "p99": 0.0,
    }


def test_registry_kind_clash_raises():
    reg = obs_metrics.Registry()
    reg.counter("m")
    with pytest.raises(TypeError):
        reg.gauge("m")


def test_snapshot_shape():
    obs.set_enabled(True)
    obs.inc("c", op="a")
    obs.set_gauge("g", 3.0)
    obs.observe("h", 1.0)
    snap = obs.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["c"] == [{"labels": {"op": "a"}, "value": 1.0}]
    assert snap["gauges"]["g"][0]["value"] == 3.0
    assert snap["histograms"]["h"][0]["count"] == 1


# ---------------------------------------------------------------------------
# the REPRO_OBS gate
# ---------------------------------------------------------------------------


def test_env_gate_parsing(monkeypatch):
    obs.set_enabled(None)
    for off in ("0", "false", "OFF", "no"):
        monkeypatch.setenv("REPRO_OBS", off)
        assert not obs_metrics.enabled()
    for on in ("1", "true", "yes", "anything"):
        monkeypatch.setenv("REPRO_OBS", on)
        assert obs_metrics.enabled()
    monkeypatch.delenv("REPRO_OBS")
    assert obs_metrics.enabled()  # default on


def test_disabled_gate_drops_everything():
    obs.set_enabled(False)
    obs.inc("c")
    obs.set_gauge("g", 1.0)
    obs.observe("h", 1.0)
    with obs.span("ladder/run"):
        pass
    assert obs.registry().names() == []


def test_disabled_mode_sfc_matmul_records_zero_events():
    """REPRO_OBS=0 contract: a full knob-resolved kernel call records
    nothing — the counter-spy sees an empty registry, so the per-call
    cost of the instrumentation is one short-circuited branch."""
    import jax.numpy as jnp

    from repro.kernels.ops import sfc_matmul

    obs.set_enabled(False)
    a = jnp.asarray(np.random.default_rng(0).normal(size=(32, 32)), jnp.float32)
    jnp_out = np.asarray(a) @ np.asarray(a)
    out = sfc_matmul(a, a)
    np.testing.assert_allclose(np.asarray(out), jnp_out, rtol=1e-4, atol=1e-4)
    assert obs.registry().names() == []
    assert obs.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_records_duration_histogram():
    obs.set_enabled(True)
    with obs.span("serving/prefill", request_id=7):
        pass
    h = obs.registry().histogram("span.serving/prefill_us")
    assert h.count() == 1
    assert h.summary()["max"] >= 0.0


def test_span_records_on_exception():
    obs.set_enabled(True)
    with pytest.raises(ValueError):
        with obs.span("train/step"):
            raise ValueError("boom")
    assert obs.registry().histogram("span.train/step_us").count() == 1


def test_span_taxonomy_is_documented():
    # every span name the instrumented call sites use must stay on the
    # documented taxonomy (README table + trace.SPAN_NAMES)
    assert len(obs.SPAN_NAMES) == 13
    assert len(set(obs.SPAN_NAMES)) == 13
    for name in obs.SPAN_NAMES:
        assert "/" in name


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_jsonl_roundtrip_and_require(tmp_path):
    obs.set_enabled(True)
    obs.inc("tune.cache.hit", op="gemm")
    obs.observe("serving.ttft_us", 1234.0)
    path = str(tmp_path / "t.jsonl")
    n = obs.to_jsonl(path)
    assert n == 2
    rows = obs.read_jsonl(path)
    by_name = {r["series"]: r for r in rows}
    assert by_name["tune.cache.hit"]["type"] == "counter"
    assert by_name["tune.cache.hit"]["value"] == 1.0
    assert by_name["tune.cache.hit"]["labels"] == {"op": "gemm"}
    hist = by_name["serving.ttft_us"]
    assert hist["type"] == "histogram"
    assert hist["count"] == 1 and hist["p95"] == pytest.approx(1234.0)
    assert obs.missing_series(path, ["serving.ttft_us"]) == []
    assert obs.missing_series(path, ["nope"]) == ["nope"]


def test_export_cli_gates_required_series(tmp_path, capsys):
    obs.set_enabled(True)
    obs.inc("ladder.served", namespace="gemm", rung="sfc_pallas")
    path = str(tmp_path / "t.jsonl")
    obs.to_jsonl(path)
    assert obs_export.main(["--check", path, "--require", "ladder.served"]) == 0
    assert obs_export.main(["--check", path, "--require", "absent.series"]) == 1
    assert "absent.series" in capsys.readouterr().err


def test_prometheus_text_format():
    obs.set_enabled(True)
    obs.inc("tune.cache.hit", op="gemm")
    obs.observe("span.ladder/run_us", 5.0)
    text = obs.to_prometheus()
    assert '# TYPE tune_cache_hit counter' in text
    assert 'tune_cache_hit{op="gemm"} 1.0' in text
    # histogram -> summary with quantile labels + _sum/_count
    assert 'span_ladder_run_us{quantile="0.95"} 5.0' in text
    assert "span_ladder_run_us_count 1" in text


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------


def test_drift_monitor_flags_and_recovers():
    mon = obs.DriftMonitor(threshold=0.5, window=16, min_samples=3)
    for _ in range(3):
        mon.observe("gemm", predicted_s=1.0, measured_s=1.05)
    assert mon.flagged() == ()
    with pytest.warns(RuntimeWarning, match="perf drift"):
        for _ in range(6):
            mon.observe("gemm", predicted_s=10.0, measured_s=1.0)
    assert mon.flagged() == ("gemm",)
    assert mon.median_error("gemm") > 0.5
    # enough healthy samples push the rolling median back under: flag lifts
    for _ in range(12):
        mon.observe("gemm", predicted_s=1.0, measured_s=1.0)
    assert mon.flagged() == ()


def test_drift_monitor_ignores_garbage_samples():
    mon = obs.DriftMonitor(min_samples=1)
    assert mon.observe("g", predicted_s=float("nan"), measured_s=1.0) is None
    assert mon.observe("g", predicted_s=1.0, measured_s=0.0) is None
    assert mon.observe("g", predicted_s=None, measured_s=1.0) is None
    assert mon.report() == {}


def test_miscalibrated_constant_flags_namespace_and_invalidates(tmp_path):
    """Acceptance: inject a deliberately mis-calibrated platform constant,
    tune through it, and the drift monitor flags the namespace as stale;
    invalidate_calibration() then purges the persisted constants."""
    import dataclasses as _dc

    from repro.tune import tune_gemm
    from repro.tune.cache import KnobCache
    from repro.tune.calibrate import PlatformConstants
    from repro.tune.tuner import _backend_name, _measure_simulated

    obs.set_enabled(True)
    backend = _backend_name()
    cache = KnobCache(path=str(tmp_path / "knobs.json"))
    # 300x throughput derate: predictions come out ~300x the simulator
    # measurement, an unmissable drift signal
    bad = PlatformConstants(
        device_kind=cache.device, backend=backend, time_scale=300.0,
        launch_overhead_s=0.0, flush_overhead_s=0.0, vmem_penalty=0.0,
        n_samples=8, median_abs_rel_err=0.01,
    )
    cache.put_platform(backend, bad.as_dict())

    mon = obs.get_monitor()
    with pytest.warns(RuntimeWarning, match="perf drift"):
        for shape in ((256, 256, 256), (512, 256, 128), (128, 512, 512)):
            tune_gemm(*shape, np.float32, cache=cache,
                      measure_fn=_measure_simulated)
    assert "gemm" in mon.flagged()
    assert (
        obs.registry().counter("drift.flagged").value(namespace="gemm") == 1.0
    )

    assert cache.get_platform(backend) is not None
    assert mon.invalidate_calibration(cache, backend=backend)
    assert cache.get_platform(backend) is None  # constants marked stale
    assert mon.flagged() == ()  # windows dropped: fresh verdict required


def test_well_calibrated_constant_does_not_flag(tmp_path):
    from repro.tune import tune_gemm
    from repro.tune.cache import KnobCache
    from repro.tune.tuner import _measure_simulated

    obs.set_enabled(True)
    cache = KnobCache(path=str(tmp_path / "knobs.json"))
    # no persisted constants: prediction and simulator measurement share
    # the datasheet model, so drift error is ~0
    for shape in ((256, 256, 256), (512, 256, 128), (128, 512, 512)):
        tune_gemm(*shape, np.float32, cache=cache,
                  measure_fn=_measure_simulated)
    mon = obs.get_monitor()
    assert mon.flagged() == ()
    med = mon.median_error("gemm")
    assert med is not None and med < 0.5


# ---------------------------------------------------------------------------
# unified surfaces: health registry / knob cache / serving / train loop
# ---------------------------------------------------------------------------


def test_degradation_report_is_view_over_obs_store():
    from repro.robust import get_registry

    obs.set_enabled(True)
    reg = get_registry()
    reg.record_served("gemm", "sfc_pallas", degraded=False)
    reg.record_served("gemm", "xla", degraded=True)
    reg.record_sdc("gemm", healed=True)
    rep = reg.degradation_report()
    assert rep["total_calls"] == 2
    assert rep["fallback_calls"] == 1
    assert rep["served"] == {"gemm": {"sfc_pallas": 1, "xla": 1}}
    assert rep["sdc"] == {"gemm": {"detected": 0, "healed": 1}}
    # the same events are mirrored into the gated process registry
    c = obs.registry().counter("ladder.served")
    assert c.value(namespace="gemm", rung="sfc_pallas") == 1.0
    assert c.value(namespace="gemm", rung="xla") == 1.0
    assert obs.registry().counter("ladder.fallback").total() == 1.0


def test_degradation_report_survives_disabled_obs():
    """The ledger is a private always-on store: turning telemetry export
    off must not blind degradation_report()."""
    from repro.robust import get_registry

    obs.set_enabled(False)
    reg = get_registry()
    reg.record_served("gemm", "xla", degraded=True)
    rep = reg.degradation_report()
    assert rep["total_calls"] == 1
    assert rep["served"] == {"gemm": {"xla": 1}}
    assert obs.registry().names() == []  # but nothing leaked to the export


def test_knob_cache_corrupt_counter_fires_every_occurrence(tmp_path):
    """Satellite bugfix: the log line is warn-once per path, but the
    counter must record EVERY corruption so fleets can alert on
    recurrence."""
    from repro.tune.cache import KnobCache, _WARNED_CORRUPT

    obs.set_enabled(True)
    path = str(tmp_path / "knobs.json")
    counter = obs.registry().counter("tune.cache.corrupt")

    with open(path, "w") as f:
        f.write("{not json")
    with pytest.warns(RuntimeWarning, match="corrupt"):
        assert KnobCache(path=path).get(64, 64, 64, np.float32, "cpu") is None
    assert counter.value(path=path) == 1.0
    assert path in _WARNED_CORRUPT

    # corrupt the rebuilt file again: warning stays deduplicated, the
    # counter keeps counting
    with open(path, "w") as f:
        f.write("{still not json")
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")  # a second warning would raise
        assert KnobCache(path=path).get(64, 64, 64, np.float32, "cpu") is None
    assert counter.value(path=path) == 2.0


def test_knob_cache_hit_miss_counters(tmp_path):
    from repro.tune.cache import KnobCache, Knobs

    obs.set_enabled(True)
    cache = KnobCache(path=str(tmp_path / "k.json"))
    assert cache.get(64, 64, 64, np.float32, "cpu") is None
    cache.put(64, 64, 64, np.float32, "cpu",
              Knobs(bm=32, bn=32, k_layers=1, k_block_factor=1))
    assert cache.get(64, 64, 64, np.float32, "cpu") is not None
    c = obs.registry()
    assert c.counter("tune.cache.miss").total() == 1.0
    assert c.counter("tune.cache.hit").total() == 1.0


def test_latency_report_percentiles_consistent_with_obs_store():
    from repro.serving.engine import Request, ServingEngine

    obs.set_enabled(True)
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(40):
        ttft = float(rng.uniform(0.010, 0.200))
        n_tok = 8
        r = Request(uid=i, prompt=np.zeros(4, np.int32), max_new_tokens=n_tok)
        r.status = "completed"
        r.submitted_at = 100.0
        r.first_token_at = 100.0 + ttft
        gaps = rng.uniform(0.002, 0.010, n_tok - 1)
        r.token_times = list(r.first_token_at + np.r_[0.0, np.cumsum(gaps)])
        r.done_at = r.token_times[-1]
        r.output = list(range(n_tok))
        reqs.append(r)
        ServingEngine._record_retired(r)

    rep = ServingEngine.latency_report(reqs)
    store = obs.registry().histogram("serving.ttft_us").summary()
    assert store["count"] == 40
    # report seconds vs store microseconds: same samples, same math
    assert rep["ttft_p50_s"] * 1e6 == pytest.approx(store["p50"], rel=1e-9)
    assert rep["ttft_p95_s"] * 1e6 == pytest.approx(store["p95"], rel=1e-9)
    assert rep["ttft_p99_s"] * 1e6 == pytest.approx(store["p99"], rel=1e-9)
    assert rep["ttft_mean_s"] * 1e6 == pytest.approx(store["mean"], rel=1e-9)
    tok = obs.registry().histogram("serving.itl_us").summary()
    assert tok["count"] == 40 * 7  # every gap between successive tokens
    assert rep["token_p95_s"] * 1e6 == pytest.approx(tok["p95"], rel=1e-9)
    assert obs.registry().counter("serving.completed").total() == 40.0
    assert obs.registry().counter("serving.tokens").total() == 40.0 * 8


def test_lowering_counter_counts_compiles_not_cache_hits():
    import jax
    import jax.numpy as jnp

    obs.set_enabled(True)
    obs.count_lowerings()
    obs.count_lowerings()  # idempotent: one listener, one count per lowering
    lowerings = obs.registry().counter("jax.lowerings")
    f = jax.jit(lambda x: x * 3 + 1)
    a, b = jnp.ones(5), jnp.zeros(5)
    before = lowerings.total()
    f(a)
    assert lowerings.total() - before == 1
    f(b)  # same shapes: the jit cache serves it, no lowering
    assert lowerings.total() - before == 1
    obs.set_enabled(False)  # the gate is read as each event arrives
    jax.jit(lambda x: x - 2)(a)
    assert lowerings.total() - before == 1


def test_structured_log_counts_and_forwards():
    obs.set_enabled(True)
    lines = []
    log = obs.as_structured(lines.append)
    log.event("ft.rollback", "[ft] oops: rolled back 5 -> 3", step=5)
    log("plain line")
    assert lines == ["[ft] oops: rolled back 5 -> 3", "plain line"]
    c = obs.registry().counter("log.events")
    assert c.value(kind="ft.rollback") == 1.0
    assert c.value(kind="info") == 1.0
    # idempotent coercion
    assert obs.as_structured(log) is log


def test_train_loop_on_metrics_and_structured_logger(tmp_path):
    from repro.train.checkpoint import CheckpointManager
    from repro.train.fault_tolerance import CorruptionPolicy, TrainLoop

    obs.set_enabled(True)

    def train_step(params, opt_state, batch, lr_scale=1.0):
        # batch_fn sees the loop's 0-based step; metrics report 1-based,
        # so batch step 1 == reported step 2
        loss = float("inf") if batch["step"] == 1 else 1.0 / (1 + batch["step"])
        return params, opt_state, {"loss": loss}

    def batch_fn(step):
        return {"step": step}

    seen = []
    logs = []
    loop = TrainLoop(
        train_step=train_step,
        batch_fn=batch_fn,
        ckpt=CheckpointManager(str(tmp_path / "ckpt"), interval=100),
        corruption_policy=CorruptionPolicy(skip_steps=2, rollback_on_sdc=False),
        on_metrics=seen.append,
    )
    loop.run({}, {}, num_steps=5, resume=False, log_every=2, logger=logs.append)

    assert len(seen) == 5
    assert set(seen[0]) == {
        "step", "loss", "dt_s", "nonfinite_streak", "sdc_delta", "lr_scale",
    }
    assert [m["step"] for m in seen] == [1, 2, 3, 4, 5]
    assert math.isinf(seen[1]["loss"]) and seen[1]["nonfinite_streak"] == 1
    assert seen[2]["nonfinite_streak"] == 0  # finite loss resets
    # the human lines still reach the injected sink
    assert any("nonfinite loss at step 2" in l for l in logs)
    assert any("recovered" in l for l in logs)
    assert any(l.startswith("[train] step=") for l in logs)
    # and the loop's telemetry landed in the registry
    reg = obs.registry()
    assert reg.counter("train.steps").total() == 5.0
    assert reg.counter("train.nonfinite").total() == 1.0
    assert reg.counter("log.events").value(kind="ft.nonfinite") == 1.0
    assert reg.histogram("span.train/step_us").count() == 5
    assert reg.histogram("train.step_us").count() == 5


def test_e2e_export_contains_every_series_family(tmp_path):
    """Acceptance: one (dummy-stepped) train-loop run plus one serving
    batch plus tune-cache and ABFT activity produce a JSONL export with
    the tune-cache, ladder, ABFT, serving-lifecycle, and train-step
    series families."""
    import jax.numpy as jnp

    from repro.robust import abft, get_registry
    from repro.serving.engine import Request, ServingEngine
    from repro.train.checkpoint import CheckpointManager
    from repro.train.fault_tolerance import TrainLoop
    from repro.tune.cache import KnobCache, Knobs

    obs.set_enabled(True)

    # tune-cache activity
    cache = KnobCache(path=str(tmp_path / "k.json"))
    cache.get(64, 64, 64, np.float32, "cpu")  # miss
    cache.put(64, 64, 64, np.float32, "cpu",
              Knobs(bm=32, bn=32, k_layers=1, k_block_factor=1))
    cache.get(64, 64, 64, np.float32, "cpu")  # hit

    # ladder activity
    get_registry().record_served("gemm", "sfc_pallas", degraded=False)

    # ABFT verify (eager, checksums agree)
    out = jnp.ones((4, 4), jnp.float32)
    chk = jnp.asarray(4.0)
    abft.verify("gemm", out, chk, jnp.asarray(4.0), jnp.asarray(1.0),
                contract_dim=4, mode="detect")

    # serving lifecycle
    r = Request(uid=1, prompt=np.zeros(4, np.int32), max_new_tokens=4)
    r.status = "completed"
    r.submitted_at, r.first_token_at, r.done_at = 1.0, 1.1, 1.4
    r.output = [1, 2, 3, 4]
    ServingEngine._record_retired(r)

    # train loop
    loop = TrainLoop(
        train_step=lambda p, o, b: (p, o, {"loss": 0.5}),
        batch_fn=lambda step: {},
        ckpt=CheckpointManager(str(tmp_path / "ckpt"), interval=100),
    )
    loop.run({}, {}, num_steps=3, resume=False, logger=lambda _line: None)

    path = str(tmp_path / "telemetry.jsonl")
    obs.to_jsonl(path)
    assert obs.missing_series(path, [
        "tune.cache.miss", "tune.cache.hit",
        "ladder.served",
        "abft.checks",
        "serving.ttft_us", "serving.completed", "serving.tokens",
        "train.steps", "train.step_us", "span.train/step_us",
    ]) == []
    # every row is valid standalone JSON with the schema fields
    for line in open(path):
        row = json.loads(line)
        assert {"series", "type", "labels"} <= set(row)
