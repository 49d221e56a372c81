"""Serving engine tests: continuous batching, backend equivalence, metrics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.gemm_backend import gemm_backend
from repro.models.registry import build_model
from repro.serving.engine import ServingEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("qwen3_4b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_engine_batched_requests(small_model):
    cfg, model, params = small_model
    engine = ServingEngine(cfg, params, max_batch=3, max_seq=32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=12).astype(np.int32) for _ in range(7)]
    reqs = engine.submit_many(prompts, max_new_tokens=6)
    done = engine.run(reqs)
    assert len(done) == 7
    for r in done:
        assert len(r.output) == 6
        assert r.done_at >= r.first_token_at >= r.submitted_at
    rep = engine.latency_report(done)
    assert rep["tokens_total"] == 42
    assert rep["tokens_per_s"] > 0


def test_engine_matches_manual_greedy(small_model):
    """Engine greedy output == manual prefill+decode loop."""
    cfg, model, params = small_model
    engine = ServingEngine(cfg, params, max_batch=1, max_seq=24)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, size=10).astype(np.int32)
    [req] = engine.submit_many([prompt], max_new_tokens=5)
    [done] = engine.run([req])

    logits, cache = model.prefill(params, jnp.asarray(prompt)[None], cache_len=24)
    want = [int(jnp.argmax(logits, -1)[0])]
    tok = jnp.argmax(logits, -1)[:, None]
    for _ in range(4):
        logits, cache = model.decode_step(params, tok, cache)
        tok = jnp.argmax(logits, -1)[:, None]
        want.append(int(tok[0, 0]))
    assert done.output == want


def test_backend_equivalence_through_serving(small_model):
    cfg, model, params = small_model
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    outs = {}
    for backend in ("xla", "sfc_pallas"):
        engine = ServingEngine(cfg, params, max_batch=1, max_seq=16, gemm_backend=backend)
        [req] = engine.submit_many([prompt], max_new_tokens=4)
        [done] = engine.run([req])
        outs[backend] = done.output
    assert outs["xla"] == outs["sfc_pallas"]


def test_deadline_sheds_waiting_and_retires_live(small_model):
    cfg, model, params = small_model
    engine = ServingEngine(cfg, params, max_batch=2, max_seq=32)
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab, size=8).astype(np.int32) for _ in range(3)
    ]
    reqs = engine.submit_many(prompts, max_new_tokens=4, deadline_s=60.0)
    # one request "arrived" long ago: already past its budget when run()
    # starts, so it must be shed before any compute is spent on it
    reqs[1].submitted_at -= 120.0
    done = engine.run(reqs)
    assert len(done) == 3
    by_uid = {r.uid: r for r in done}
    shed = by_uid[reqs[1].uid]
    assert shed.status == "timed_out"
    assert shed.output == []
    assert shed.first_token_at == 0.0  # never prefillled
    for r in (by_uid[reqs[0].uid], by_uid[reqs[2].uid]):
        assert r.status == "completed"
        assert len(r.output) == 4
    rep = engine.latency_report(done)
    assert rep["n_requests"] == 3
    assert rep["n_timed_out"] == 1
    assert rep["tokens_total"] == 8
    assert rep["ttft_mean_s"] >= 0.0


def test_deadline_retires_mid_decode(small_model):
    cfg, model, params = small_model
    engine = ServingEngine(cfg, params, max_batch=1, max_seq=32)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    # a generous deadline survives the whole decode
    [req] = engine.submit_many([prompt], max_new_tokens=16, deadline_s=1e9)
    done = engine.run([req])[0]
    assert done.status == "completed"
    # an expiring one: admitted fresh, then the budget burns away during
    # serving so a decode-boundary check retires it mid-generation
    [req2] = engine.submit_many([prompt], max_new_tokens=16)

    orig_decode = engine._decode

    def slow_decode(*args):
        req2.submitted_at -= 1.0  # burn the budget during serving
        return orig_decode(*args)

    engine._decode = slow_decode
    req2.deadline_s = 0.5
    done2 = engine.run([req2])[0]
    assert done2.status == "timed_out"
    assert 1 <= len(done2.output) < 16  # partial output kept
    rep = engine.latency_report([done2])
    assert rep["n_timed_out"] == 1


def test_latency_report_empty_is_zeros(small_model):
    cfg, model, params = small_model
    engine = ServingEngine(cfg, params, max_batch=1, max_seq=16)
    rep = engine.latency_report([])
    assert rep == {
        "n_requests": 0,
        "n_timed_out": 0,
        "ttft_mean_s": 0.0,
        "ttft_p50_s": 0.0,
        "ttft_p95_s": 0.0,
        "ttft_p99_s": 0.0,
        "latency_mean_s": 0.0,
        "token_p50_s": 0.0,
        "token_p95_s": 0.0,
        "token_p99_s": 0.0,
        "tokens_total": 0,
        "tokens_per_s": 0.0,
    }


def test_deadline_retires_at_prefill_boundary(small_model):
    """A budget that burns away *during* prefill retires the request at
    the prefill boundary — no first token, no decode compute — while its
    batchmates decode normally."""
    cfg, model, params = small_model
    engine = ServingEngine(cfg, params, max_batch=2, max_seq=32)
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(0, cfg.vocab, size=8).astype(np.int32) for _ in range(2)
    ]
    reqs = engine.submit_many(prompts, max_new_tokens=4)
    reqs[0].deadline_s = 0.5  # alive at admission...

    orig_prefill = engine._prefill

    def slow_prefill(*args):
        reqs[0].submitted_at -= 1.0  # ...but the budget burns inside prefill
        return orig_prefill(*args)

    engine._prefill = slow_prefill
    done = engine.run(reqs)
    by_uid = {r.uid: r for r in done}
    timed_out = by_uid[reqs[0].uid]
    assert timed_out.status == "timed_out"
    assert timed_out.output == []
    assert timed_out.first_token_at == 0.0
    ok = by_uid[reqs[1].uid]
    assert ok.status == "completed" and len(ok.output) == 4
    rep = engine.latency_report(done)
    assert rep["n_timed_out"] == 1
    assert rep["tokens_total"] == 4


def test_sampled_abft_verification_counts_and_matches(small_model):
    """verify_every=N runs every Nth decode step under abft="detect";
    a clean run verifies without perturbing outputs or counting SDC."""
    from repro.robust import reset_runtime_sdc

    cfg, model, params = small_model
    reset_runtime_sdc()
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab, size=8).astype(np.int32)

    base = ServingEngine(cfg, params, max_batch=1, max_seq=32,
                         gemm_backend="sfc_pallas")
    [r1] = base.submit_many([prompt], max_new_tokens=6)
    [d1] = base.run([r1])

    eng = ServingEngine(cfg, params, max_batch=1, max_seq=32,
                        gemm_backend="sfc_pallas", verify_every=2)
    [r2] = eng.submit_many([prompt], max_new_tokens=6)
    [d2] = eng.run([r2])

    assert d2.output == d1.output
    rep = eng.degradation_report()["verify"]
    assert rep == {
        "verify_every": 2,
        "decode_steps": 5,      # max_new_tokens - 1 loop iterations
        "verified_steps": 2,    # steps 2 and 4
        "sdc_detections": 0,
    }


def test_sampled_verification_detection_redoes_step(small_model):
    """A runtime SDC detection during a verified step quarantines the
    Pallas rungs, re-jits, and redoes the step — the request completes
    and the detection is ledgered."""
    from repro.robust import abft, get_registry

    cfg, model, params = small_model
    abft.reset_runtime_sdc()
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
    eng = ServingEngine(cfg, params, max_batch=1, max_seq=32,
                        verify_every=3)

    orig_verify = eng._decode_verify

    def corrupted_verify(params, token, cache):
        # model an in-kernel checksum mismatch surfacing via the runtime
        # counter mid-step (the jitted program cannot raise)
        abft._record_runtime_sdc("gemm", True, 1.0, 0.0)
        return orig_verify(params, token, cache)

    eng._decode_verify = corrupted_verify
    [req] = eng.submit_many([prompt], max_new_tokens=6)
    [done] = eng.run([req])

    assert done.status == "completed"
    assert len(done.output) == 6
    rep = eng.degradation_report()["verify"]
    # step 3 detected and was redone; the re-jit replaced the corrupted
    # wrapper, so step 6 (if verified) runs clean
    assert rep["sdc_detections"] == 1
    assert rep["verified_steps"] >= 1
    reg = get_registry()
    assert "gemm" in reg.quarantined_namespaces()
    assert {r["reason"] for r in reg.export_state().values()} == {"sdc"}
    abft.reset_runtime_sdc()


@pytest.mark.parametrize("strict", [False, True])
def test_engine_runtime_failure_heals_unless_strict(small_model, monkeypatch,
                                                     strict):
    """A classified failure of a whole compiled program (what the trace-time
    ladder cannot see) re-traces on fallback rungs, except under
    REPRO_STRICT=1, where it raises and quarantines nothing."""
    from repro.robust import StrictFallbackError, get_registry

    if strict:
        monkeypatch.setenv("REPRO_STRICT", "1")
    else:
        monkeypatch.delenv("REPRO_STRICT", raising=False)
    cfg, model, params = small_model
    engine = ServingEngine(cfg, params, max_batch=2, max_seq=16,
                           gemm_backend="sfc_pallas")

    def exhausted(*args):
        raise RuntimeError("RESOURCE_EXHAUSTED: scoped vmem limit exceeded")

    engine._prefill = exhausted
    tokens = jnp.zeros((2, 8), jnp.int32)
    if strict:
        with pytest.raises(StrictFallbackError):
            engine._run_healed("_prefill", tokens)
        assert not get_registry().degradation_report()["quarantined"]
    else:
        logits, _ = engine._run_healed("_prefill", tokens)
        assert logits.shape == (2, cfg.vocab)
        assert get_registry().degradation_report()["quarantined"]
