"""A second decoder architecture enters the benchmark by files alone.

`data/tiny_moe_decoder.py` is the reference module of a tiny MoE decoder
(the program's ``family="moe"`` `DecoderLM`, 8 experts, top 2, dropless):
its own seeded weights, dropless logits and work counts.  It is found by
name, as a configuration's ``reference`` is, and no file of the harness
knows its layout.
"""

from __future__ import annotations

import json
import pathlib

import jax
import pytest

import run as bench
from chipbench import compare, flops, serve
from test_cells_cpu import SEED, TINY_MODEL, TINY_TRAFFIC, V5E

DATA = pathlib.Path(__file__).with_name("data")
N_EXPERTS, TOP_K = 8, 2
TINY_MOE = dict(TINY_MODEL, name="tiny-moe", family="moe",
                n_experts=N_EXPERTS, moe_top_k=TOP_K,
                capacity_factor=N_EXPERTS / TOP_K)
# tiny-size limits, as TINY_LIMITS in test_cells_cpu.py
LIMITS = {"missing_tokens": 0, "logit_gap": 0.05, "logit_rel_err": 0.02}


@pytest.fixture(autouse=True)
def moe_world(monkeypatch, tmp_path):
    (tmp_path / "tiny-moe-serve.json").write_text(json.dumps({"limits": LIMITS}))
    monkeypatch.setattr(compare, "LIMITS_DIR", tmp_path)
    monkeypatch.setattr(flops, "device_peaks", lambda kind: V5E)
    monkeypatch.setattr(serve, "REFERENCES", DATA)


def moe_spec(reference="tiny_moe_decoder"):
    return {
        "cell": {"name": "tiny-moe-serve", "chips": 1},
        "config": {"kind": "decoder_lm", "reference": reference,
                   "model": TINY_MOE},
        "traffic": TINY_TRAFFIC,
        "end_to_end": [{"name": n, "unit": u} for n, u in (
            ("decode_tok_s", "tokens/s"), ("itl_p95_ms", "ms"),
            ("setup_s", "s"))],
        "per_layer": [],
    }


@pytest.mark.parametrize("capacity_factor, correct", [
    (N_EXPERTS / TOP_K, True),
    (1.0, False),  # the program drops tokens past each expert's capacity
], ids=["dropless", "dropping"])
def test_moe_cell_runs_and_is_correct(capacity_factor, correct):
    spec = moe_spec()
    spec["config"]["model"] = dict(TINY_MOE, capacity_factor=capacity_factor)
    res = bench.run(spec, SEED, 0.5, False, jax.devices()[:1])
    assert res["correct"] is correct, res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"decode_tok_s", "itl_p95_ms", "setup_s"}
    assert res["compiles_in_window"] == 0


def test_moe_work_is_the_modules_times_the_rounds():
    cell = bench.make_cell(moe_spec(), SEED, jax.devices()[:1])
    win = cell.window(SEED, 0.0, n_rounds=2)
    t = TINY_TRAFFIC
    one = cell.reference.round_work(t["batch"], t["prompt_len"],
                                    t["max_new_tokens"], TINY_MOE, V5E)
    assert cell.work(win, V5E) == {"rounds": 2, **{k: 2 * v for k, v in one.items()}}
    # every token of the round takes TOP_K experts in each layer
    tokens = t["batch"] * (t["prompt_len"] + t["max_new_tokens"] - 1)
    assert one["expert_rows"] == TINY_MOE["n_layers"] * tokens * TOP_K


def test_dense_weights_for_a_moe_layout_are_refused(tmp_path, monkeypatch):
    (tmp_path / "dense_params.py").write_text(
        "from chipbench.weights import decoder_params as params\n")
    monkeypatch.setattr(serve, "REFERENCES", tmp_path)
    with pytest.raises(SystemExit, match="do not match the program's "
                                         "parameter layout"):
        bench.make_cell(moe_spec("dense_params"), SEED, jax.devices()[:1])
