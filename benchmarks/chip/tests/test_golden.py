"""What the existing cells read, pinned: the dense weights, the work counts
and every per-layer reader's value on the recorded chip traces, as the
harness computed them before each configuration's reference module became
the home of its weights and work counts."""

from __future__ import annotations

import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

import run as bench
from chipbench import serve, trace
from test_cells_cpu import SEED, TINY_MODEL, V5E

DATA = pathlib.Path(__file__).with_name("data")

# sha256 (first 16 hex digits) of each leaf's bytes
WEIGHTS = {
    "['embed']": "d6f258e9ef28aa6c",
    "['final_norm']['scale']": "39e9f2652be11eae",
    "['head']": "3d1b3b07e318b1fd",
    "['layers']['attn']['k_norm']['scale']": "cc6e543c6b831060",
    "['layers']['attn']['q_norm']['scale']": "59eb4680a8ca2da9",
    "['layers']['attn']['wk']": "d21caedeb4e00ef4",
    "['layers']['attn']['wo']": "041fa6f73c6531ea",
    "['layers']['attn']['wq']": "65db5a18f3f5fb79",
    "['layers']['attn']['wv']": "f2f73d36b164ce34",
    "['layers']['mlp']['w_gate']": "b3bc5eab94d88e84",
    "['layers']['mlp']['w_in']": "05e040db31665c90",
    "['layers']['mlp']['w_out']": "1bc7ea1a3a9294d8",
    "['layers']['norm1']['scale']": "262d1b502029c60b",
    "['layers']['norm2']['scale']": "76c7807e13729e3a",
}
# one round of each cell's traffic on v5e peaks: flops, gemm_least_s
WORK = {
    "qwen3-4b-prefill-2k": (64481576615936.0, 0.30312518224225804),
    "yi-6b-decode-512-256": (140505560121344.0, 4.0904353626967325),
}
# each per-layer reader on the cell's recorded (trimmed) trace, with the work
# of the traffic's trace_rounds: a trimmed window holds a part of a round, so
# the shares of the peak and of the roofline read far above 100 here; the
# program's counters are empty, and the spans postdate the recordings
READINGS = {
    "qwen3-4b-prefill-2k": {
        "idle_share.prefill": 55.57731591872093,
        "mfu.prefill": 1243.7676980379058,
        "gemm_roofline.prefill": 6339.611442105703,
    },
    "yi-6b-decode-512-256": {
        "idle_share.decode": 42.33278124647971,
        "decode_gap_ms.decode": None,
        "mfu.decode": 645.3364737976144,
        "gemm_roofline.decode": 8889.52112573418,
        "sync_idle_ms.decode": None,
        "launch_idle_ms.decode": None,
        "host_reads_per_step.decode": None,
        "host_transfers_per_step.decode": None,
    },
}


def reference(cell):
    return serve.load_reference(bench.load_cell(cell)["config"]["reference"])


def test_dense_weights_are_bit_identical():
    params = reference("yi-6b-decode-512-256").params(TINY_MODEL, SEED)
    got = {jax.tree_util.keystr(path): hashlib.sha256(
        np.asarray(leaf).tobytes()).hexdigest()[:16]
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)}
    assert got == WEIGHTS


@pytest.mark.parametrize("cell", sorted(WORK))
def test_round_work_is_unchanged(cell):
    t = bench.load_cell(cell)["traffic"]
    one = reference(cell).round_work(
        t["batch"], t["prompt_len"], t["max_new_tokens"],
        bench.load_cell(cell)["config"]["model"], V5E)
    assert (one["flops"], one["gemm_least_s"]) == WORK[cell]


@pytest.mark.parametrize("cell, metric", [
    (c, m) for c in sorted(READINGS) for m in READINGS[c]])
def test_reader_value_is_unchanged(cell, metric):
    from repro import obs

    obs.reset()
    spec = bench.load_cell(cell)
    assert metric in {m["name"] for m in spec["per_layer"]}
    t = spec["traffic"]
    one = reference(cell).round_work(t["batch"], t["prompt_len"],
                                     t["max_new_tokens"],
                                     spec["config"]["model"], V5E)
    n = t["trace_rounds"]
    ctx = {"trace": trace.Trace(json.loads(
               (DATA / f"{cell}.events.json").read_text())),
           "work": {"rounds": n, **{k: v * n for k, v in one.items()}},
           "peaks": V5E, "config": spec["config"], "traffic": t}
    assert bench.load_reader(metric)(ctx) == READINGS[cell][metric]
