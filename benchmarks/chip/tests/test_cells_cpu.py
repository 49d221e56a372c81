"""Every cell kind driven end to end on the CPU at a tiny size: the run's
result, its control, and the faults its check has to catch.

These skip the look for a chip (`run.require_devices`) and call `run.run`
itself, with tiny configurations and limits of their own.
"""

from __future__ import annotations

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import run as bench
from chipbench import compare, flops

V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

TINY_MODEL = dict(
    name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    kv_heads=2, head_dim=16, d_ff=128, vocab=256, qk_norm=True,
    rope_theta=1e6, rms_norm_eps=1e-6, tie_embeddings=False,
    attn_impl="blockwise", q_chunk=16, k_chunk=16, param_dtype="bfloat16")
TINY_TRAFFIC = dict(
    kind="closed_loop", batch=2, prompt_len=16, max_new_tokens=4,
    max_batch=2, max_seq=21, trace_rounds=1, study_rounds=1,
    check=dict(rounds=1, of_first=2, rows=2, block=2))
TINY_GEMM = dict(m=256, n=256, k=256, dtype="bfloat16",
                 mesh=dict(tm=2, tn=1, kl=2), backend="sfc_pallas",
                 reduce="psum_scatter")
GEMM_TRAFFIC = dict(kind="back_to_back", calls_per_round=2, trace_rounds=1,
                    study_rounds=1, check=dict(calls=2, of_first=4))

# tiny-size limits, set from the tiny readings as the chip limits are set
# from the chip's: program gap <= 2e-3, rel_err <= 6e-3 (bf16 at 2 layers),
# fp8 control rel_err ~ 6e-2; GEMM bf16 output rounding ~ 2e-3
TINY_LIMITS = {
    "tiny-serve": {"missing_tokens": 0, "logit_gap": 0.05,
                   "logit_rel_err": 0.02},
    "tiny-gemm": {"c_rel_err": 0.01},
}

SEED = 2 ** 33 + 12345


@pytest.fixture(autouse=True)
def tiny_world(monkeypatch, tmp_path):
    for name, lim in TINY_LIMITS.items():
        (tmp_path / f"{name}.json").write_text(json.dumps({"limits": lim}))
    monkeypatch.setattr(compare, "LIMITS_DIR", tmp_path)
    monkeypatch.setattr(flops, "device_peaks", lambda kind: V5E)


def serve_spec():
    return {
        "cell": {"name": "tiny-serve", "chips": 1},
        "config": {"kind": "decoder_lm", "reference": "dense_decoder",
                   "model": TINY_MODEL},
        "traffic": TINY_TRAFFIC,
        "end_to_end": [{"name": n, "unit": u} for n, u in (
            ("decode_tok_s", "tokens/s"), ("itl_p95_ms", "ms"),
            ("setup_s", "s"))],
        "per_layer": [],
    }


def gemm_spec():
    return {
        "cell": {"name": "tiny-gemm", "chips": 4},
        "config": {"kind": "distributed_gemm", "reference": "gemm",
                   "gemm": TINY_GEMM},
        "traffic": GEMM_TRAFFIC,
        "end_to_end": [{"name": "gemm_tflops", "unit": "TFLOP/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }


def run_cell(spec):
    devices = jax.devices()[:spec["cell"]["chips"]]
    return bench.run(spec, SEED, 0.5, False, devices)


def test_serve_cell_runs_and_is_correct():
    res = run_cell(serve_spec())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"decode_tok_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"


def test_gemm_cell_runs_and_is_correct():
    res = run_cell(gemm_spec())
    assert res["correct"], res["checks"]
    assert res["metrics"]["gemm_tflops"]["value"] > 0
    assert res["compiles_in_window"] == 0


def test_same_seed_same_inputs():
    from chipbench import weights

    a = weights.decoder_params(TINY_MODEL, SEED)
    b = weights.decoder_params(TINY_MODEL, SEED)
    c = weights.decoder_params(TINY_MODEL, SEED + 1)
    assert all(bool(jnp.array_equal(x, y)) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["head"], c["head"]))
    r1 = weights.host_rng(SEED, "traffic").integers(0, 100, 8)
    r2 = weights.host_rng(SEED, "traffic").integers(0, 100, 8)
    assert list(r1) == list(r2)


# ---------------------------------------------------------------------------
# the control: the reference in fp8 has to fail the check
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make_spec", [serve_spec, gemm_spec],
                         ids=["serve", "gemm"])
def test_control_fails_the_check(make_spec):
    spec = make_spec()
    devices = jax.devices()[:spec["cell"]["chips"]]
    cell = bench.make_cell(spec, SEED, devices)
    win = cell.window(SEED, 0.0, n_rounds=spec["traffic"]["study_rounds"])
    cell.drop_engine()
    readings = cell.check(win, control=True)
    lim = TINY_LIMITS[spec["cell"]["name"]]
    program = {k: v for k, v in readings.items() if not k.startswith("control_")}
    control = {k[len("control_"):]: v for k, v in readings.items()
               if k.startswith("control_")}
    assert compare.is_correct(compare.judge(program, spec["cell"]["name"]))
    assert not compare.is_correct(compare.judge(
        {**program, **control}, spec["cell"]["name"])), (readings, lim)


# ---------------------------------------------------------------------------
# faults planted under the timed path: each must make `correct` false
# ---------------------------------------------------------------------------


def _wrap_engine_program(monkeypatch, which, corrupt):
    from repro.serving.engine import ServingEngine

    orig = ServingEngine._jit

    def _jit(self):
        orig(self)
        program = getattr(self, which)

        def broken(params, *args):
            logits, cache = program(params, *args)
            return corrupt(logits), cache

        setattr(self, which, broken)

    monkeypatch.setattr(ServingEngine, "_jit", _jit)


def test_fault_token_altered(monkeypatch):
    # every decode step's best token is moved to the next vocabulary id
    _wrap_engine_program(monkeypatch, "_decode",
                         lambda lg: jnp.roll(lg, 1, axis=-1))
    assert not run_cell(serve_spec())["correct"]


def test_fault_state_unchanged(monkeypatch):
    # a decode step hands back the KV cache it was given
    from repro.serving.engine import ServingEngine

    orig = ServingEngine._jit

    def _jit(self):
        orig(self)
        program = self._decode

        def stale(params, token, cache):
            return program(params, token, cache)[0], cache

        self._decode = stale

    monkeypatch.setattr(ServingEngine, "_jit", _jit)
    assert not run_cell(serve_spec())["correct"]


def test_fault_half_batch_left_out(monkeypatch):
    # the second half of the batch gets the first half's logits
    def half(lg):
        h = lg.shape[0] // 2
        return jnp.concatenate([lg[:h], lg[:lg.shape[0] - h]])

    _wrap_engine_program(monkeypatch, "_prefill", half)
    _wrap_engine_program(monkeypatch, "_decode", half)
    assert not run_cell(serve_spec())["correct"]


def test_fault_answer_never_comes(monkeypatch):
    # the engine stops one token short of what every request asked for
    from repro.serving.engine import ServingEngine

    orig = ServingEngine.submit_many

    def short(self, prompts, max_new_tokens=16, deadline_s=None):
        return orig(self, prompts, max_new_tokens - 1, deadline_s)

    monkeypatch.setattr(ServingEngine, "submit_many", short)
    assert not run_cell(serve_spec())["correct"]


def test_fault_exchange_left_out(monkeypatch):
    # the reduce-scatter over the K layers keeps this layer's partial C
    from repro.core import ca_matmul as ca

    def no_exchange(x, axis_name, *, scatter_dimension, tiled):
        n = x.shape[scatter_dimension] // jax.lax.axis_size(axis_name)
        i = jax.lax.axis_index(axis_name)
        return jax.lax.dynamic_slice_in_dim(x, i * n, n, scatter_dimension)

    monkeypatch.setattr(ca.lax, "psum_scatter", no_exchange)
    assert not run_cell(gemm_spec())["correct"]


def test_fault_answer_altered(monkeypatch):
    # one element of every output is changed where it is produced
    from repro.core import ca_matmul as ca

    orig = ca.ca_matmul

    def altered(*args, **kw):
        c = orig(*args, **kw)
        return c.at[0, 0].add(1000.0)

    monkeypatch.setattr(ca, "ca_matmul", altered)
    assert not run_cell(gemm_spec())["correct"]


# ---------------------------------------------------------------------------
# the look for a chip
# ---------------------------------------------------------------------------


def test_no_accelerator_exits_nonzero_without_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(bench.HERE / "run.py"), "--workload",
         "qwen3-4b-prefill-2k", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, cwd=bench.ROOT, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path), "TMPDIR": str(tmp_path)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
