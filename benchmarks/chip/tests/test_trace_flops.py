"""The trace reduction and the FLOP/byte functions, on the CPU."""

from __future__ import annotations

import json
import pathlib

import pytest

from chipbench import flops
from chipbench.trace import Trace, is_collective, op_matcher, subtract, union

DATA = pathlib.Path(__file__).with_name("data")
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def synthetic():
    """One device, window [0, 100) ns: a GEMM kernel [10, 40), a fusion
    [35, 50) overlapping it, a reduce-scatter [60, 80) with a fusion
    [70, 75) beside it; decode programs [5, 52) and [60, 90), a prefill
    program [91, 95)."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [["sfc_gemm_fused", 10, 30], ["fusion.1", 35, 15],
                    ["reduce-scatter.2", 60, 20], ["fusion.3", 70, 5]],
            "modules": [["jit__decode_impl", 5, 47],
                        ["jit__decode_impl", 60, 30],
                        ["jit__prefill_impl", 91, 4]],
        }},
        "host": [["bench/window", 0, 100], ["bench/round", 0, 100],
                 ["serving/decode", 50, 12]],
        "window": [0, 100],
    }


def test_interval_arithmetic():
    assert union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_busy_idle_and_kernel_time():
    t = Trace(synthetic())
    assert t.window_s == pytest.approx(100e-9)
    # busy: [10, 50) and [60, 80) = 60 ns
    assert t.busy_s() == pytest.approx(60e-9)
    assert t.idle_share() == pytest.approx(0.4)
    assert t.kernel_seconds(op_matcher(["sfc_gemm"])) == pytest.approx(30e-9)
    # the reduce-scatter [60, 80) less the fusion beside it [70, 75)
    assert t.exposed_seconds(is_collective) == pytest.approx(15e-9)
    # the TPU names it reduce_scatter: the same op
    assert is_collective("%reduce_scatter.4 = bf16[8,8] reduce-scatter(...)")
    assert is_collective("reduce_scatter") and not is_collective("fusion.3")


def test_module_gaps_and_breakdown():
    t = Trace(synthetic())
    # decode [5, 52) then [60, 90): idle between is [52, 60) = 8 ns
    gaps = t.module_gaps(op_matcher(["decode_impl"]),
                         apart=op_matcher(["prefill_impl"]))
    assert gaps == [8]
    top = t.top_ops(2)
    assert top[0][0] == "sfc_gemm_fused"
    assert top[0][1] == pytest.approx(30e-9)
    idle = t.idle_gaps(3)
    # longest idle gap [80, 100) has no host event but the round; the
    # innermost host event at the midpoint of [50, 60) is serving/decode
    assert idle[0] == ["bench/round", pytest.approx(20e-9)]
    assert ["serving/decode", pytest.approx(10e-9)] in idle


def test_a_pair_split_by_prefill_is_not_consecutive():
    ev = synthetic()
    ev["devices"]["/device:TPU:0"]["modules"].insert(
        1, ["jit__prefill_impl", 53, 2])
    t = Trace(ev)
    assert t.module_gaps(op_matcher(["decode_impl"]),
                         apart=op_matcher(["prefill_impl"])) == []


def test_nested_ops_count_self_time_and_staging_copies():
    """A ``while`` spanning its body; a kernel whose weight operand an
    earlier slice copy staged; ops named by their HLO text."""
    ev = {"devices": {"/device:TPU:0": {"ops": [
        ["%while.3 = (s32[]) while(...)", 0, 100],
        ["%dynamic-slice_bitcast_fusion.18 = bf16[64,128]{1,0:S(1)} "
         "fusion(bf16[4,64,128] %p)", 10, 20],
        ["%sfc_gemm_fused.36 = bf16[16,128] custom-call(s32[3,4] %t, "
         "bf16[16,64] %x, bf16[64,128]{1,0:S(1)} "
         "%dynamic-slice_bitcast_fusion.18)", 30, 10],
        ["%fusion.2 = bf16[16,128] fusion(bf16[16,128] %sfc_gemm_fused.36)",
         40, 5],
        ["%dynamic-slice_bitcast_fusion.19 = bf16[64,128] fusion(...)", 50, 7],
    ], "modules": []}}, "host": [], "window": [0, 100]}
    t = Trace(ev)
    assert t.busy_s() == pytest.approx(100e-9)
    top = dict(t.top_ops(10))
    assert top["while"] == pytest.approx(58e-9)  # 100 less 20+10+5+7
    assert top["sfc_gemm_fused"] == pytest.approx(10e-9)
    gemm = op_matcher(["sfc_gemm"])
    assert t.kernel_seconds(gemm) == pytest.approx(10e-9)
    # the slice that feeds the kernel counts, the one that does not, not
    assert t.kernel_seconds(gemm, staging=op_matcher(["dynamic-slice"])) == (
        pytest.approx(30e-9))
    # leaves only: the while does not hide a leaf's exposure
    assert t.exposed_seconds(op_matcher(["fusion.2", "fusion"])) > 0


def recorded():
    return sorted(DATA.glob("*.events.json"))


@pytest.mark.parametrize("path", recorded(), ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    """A trace recorded on the chip (trimmed): the reduction reads it, every
    share lies in [0, 1], and the kernel time is part of the busy time."""
    t = Trace(json.loads(path.read_text()))
    assert t.window_s > 0
    assert 0.0 < t.busy_s() <= t.window_s
    assert 0.0 <= t.idle_share() < 1.0
    k = t.kernel_seconds(op_matcher(["sfc_gemm"]))
    assert 0.0 < k <= t.busy_s() * 1.0001
    staged = t.kernel_seconds(op_matcher(["sfc_gemm"]),
                              staging=op_matcher(["dynamic-slice"]))
    assert k < staged <= t.busy_s() * 1.0001
    assert t.top_ops(10) and len(t.idle_gaps(10)) <= 10


def test_gemm_counts():
    g = flops.Gemm(8192, 4096, 2560)
    assert g.flops == 2 * 8192 * 4096 * 2560
    assert g.bytes == 2 * (8192 * 2560 + 2560 * 4096 + 8192 * 4096)
    assert g.least_s(V5E) == pytest.approx(g.flops / 197e12)  # compute-bound
    skinny = flops.Gemm(16, 11008, 4096, n_b=2)
    assert skinny.least_s(V5E) == pytest.approx(skinny.bytes / 819e9)


def test_model_flops_match_the_parameter_count():
    qwen = json.loads((DATA.parents[1] / "configs" / "qwen3-4b.json")
                      .read_text())["model"]
    one_token = flops.step_gemms(1, 0, qwen)
    params = sum(g.k * g.n * g.n_b for g in one_token)
    # Qwen3-4B's card: 4.0e9 parameters, 3.6e9 outside the embedding; the
    # projections here are those 3.6e9 and the 0.39e9 head
    assert params == pytest.approx(4.0e9, rel=0.02)
    work = flops.prefill_work(4, 2048, qwen)
    per_token = work["flops"] / (4 * 2048)
    assert 7.5e9 < per_token < 8.5e9


def test_round_work_counts_every_decode_step():
    yi = json.loads((DATA.parents[1] / "configs" / "yi-6b.json")
                    .read_text())["model"]
    one = flops.round_work(16, 512, 2, yi, V5E)
    two = flops.round_work(16, 512, 3, yi, V5E)
    step = flops.decode_work(16, 513, yi)
    assert two["flops"] - one["flops"] == pytest.approx(step["flops"])
    # a decode step streams ~11.6 GB of weights: >= 14 ms at 819 GB/s
    assert two["gemm_least_s"] - one["gemm_least_s"] == pytest.approx(
        sum(g.least_s(V5E) for g in step["gemms"]))
    assert 0.0135 < sum(g.least_s(V5E) for g in step["gemms"]) < 0.0150
