"""Performance-model tests: BRGEMM taxonomy, knob predictors, roofline."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hypothesis optional: property test skips, rest run
    given = settings = st = None

from repro.core.decomposition import sfc_decompose
from repro.core.perf_model import (
    TPU_V5E,
    NearestNeighborModel,
    analytical_time,
    choose_knobs_analytical,
    choose_knobs_autotune,
    gemm_flops,
    roofline_best_time,
    simulate_gemm,
    simulate_patch_traversal,
)


def test_brgemm_taxonomy_counts():
    """On a rectangular patch with infinite fast memory the SFC traversal
    fetches each A row-panel and B col-panel once: brgemm0+1+2 == rows+cols-1."""
    d = sfc_decompose(8, 8, 4, 1)
    p = d.patches[0]
    r = simulate_patch_traversal(
        p.cells, bm=128, bn=128, K=1024, k_layers=1, k_block_factor=1, hw=TPU_V5E
    )
    assert r.total == p.n_cells
    fetches = r.brgemm0 * 2 + r.brgemm1 + r.brgemm2
    assert fetches == p.n_rows + p.n_cols


def test_sfc_order_beats_row_major():
    """Paper Fig.-7 claim: in the realistic regime (fast memory holds a
    quadrant's panels but not a full row sweep's), SFC traversal moves
    several times fewer slow-memory bytes than row-major."""
    from repro.core.perf_model import HardwareModel

    hw = HardwareModel(
        name="cache32mb", gamma=1 / 197e12, beta=1 / 819e9, fast_bytes=32 * 2**20
    )
    d = sfc_decompose(32, 32, 1, 1)
    cells_sfc = d.patches[0].cells
    rows = np.repeat(np.arange(32), 32)
    cols = np.tile(np.arange(32), 32)
    cells_rm = np.stack([rows, cols], 1)
    kw = dict(bm=128, bn=128, K=8192, k_layers=1, k_block_factor=1, hw=hw)
    sfc = simulate_patch_traversal(cells_sfc, **kw)
    rm = simulate_patch_traversal(cells_rm, **kw)
    assert rm.slow_bytes / sfc.slow_bytes > 3.0  # measured ~5.9x
    assert sfc.time <= rm.time

    # and with cache >> working set both degenerate to compulsory misses
    big = simulate_patch_traversal(cells_sfc, **{**kw, "hw": TPU_V5E, "K": 1024})
    big_rm = simulate_patch_traversal(cells_rm, **{**kw, "hw": TPU_V5E, "K": 1024})
    assert big.slow_bytes == big_rm.slow_bytes


def test_replication_reduces_gemm_phase_bytes():
    """§II-C: larger c -> fewer words in the GEMM phase (before C reduce)."""
    r1 = simulate_gemm(4096, 4096, 4096, n_workers=64, k_layers=1)
    r4 = simulate_gemm(4096, 4096, 4096, n_workers=64, k_layers=4)
    assert r4["slow_bytes_total"] < r1["slow_bytes_total"]


def test_analytical_vs_simulator_agree_on_ranking():
    """The closed-form model must rank configurations like the simulator
    (paper: predictors land within a few % of autotuned)."""
    M = N = K = 4096
    best_sim, sweep = choose_knobs_autotune(M, N, K, 256)
    c_an, kbf_an = choose_knobs_analytical(M, N, K, 256)
    t_best = sweep[best_sim]
    t_an = sweep.get((c_an, kbf_an), np.inf)
    assert t_an <= t_best * 1.15  # within 15% of exhaustive


def test_nn_model_predicts_trained_point():
    shapes = [(1024, 1024, 1024), (4096, 4096, 4096), (8192, 1024, 2048)]
    nn = NearestNeighborModel().fit_autotuned(shapes, 64)
    best, _ = choose_knobs_autotune(4096, 4096, 4096, 64)
    assert nn.predict(4096, 4096, 4096) == best
    assert nn.predict(4000, 4100, 4096) == best  # nearest neighbour


def test_roofline_never_exceeds_peak():
    t, (tm, tn, c) = roofline_best_time(8192, 8192, 8192, 256)
    tflops = gemm_flops(8192, 8192, 8192) / t
    assert tflops <= 256 * TPU_V5E.peak_flops * 1.0001
    assert tm * tn * c == 256


def _check_throughput_bounded(m, n, k):
    best, sweep = choose_knobs_autotune(m, n, k, 64)
    t_roof, _ = roofline_best_time(m, n, k, 64)
    # simulator can't beat the infinite-memory roofline by more than noise
    assert min(sweep.values()) >= t_roof * 0.8


@pytest.mark.parametrize("m,n,k", [(512, 512, 512), (2048, 1024, 4096)])
def test_simulated_throughput_bounded_smoke(m, n, k):
    """Hypothesis-free sample of the roofline-bound property."""
    _check_throughput_bounded(m, n, k)


if st is None:

    def test_roofline_property_needs_hypothesis():
        pytest.importorskip("hypothesis")  # visible skip, not silent drop

else:

    @given(
        st.sampled_from([512, 1024, 2048, 4096]),
        st.sampled_from([512, 1024, 2048, 4096]),
        st.sampled_from([512, 1024, 2048, 4096]),
    )
    @settings(max_examples=10, deadline=None)
    def test_simulated_throughput_bounded_by_roofline(m, n, k):
        _check_throughput_bounded(m, n, k)


def test_device_peaks_keyed_by_device_kind():
    """Published peaks are read by `device_kind`; a kind without a row is
    an error, never another chip's numbers."""
    from repro.core.perf_model import device_peaks
    from repro.roofline.analysis import roofline_hw

    v5e = device_peaks("TPU v5 lite")
    assert v5e.peak_flops == 197e12 and v5e.mem_bw == 819e9
    assert roofline_hw("TPU v5 lite")["peak_flops"] == 197e12
    for lookup in (device_peaks, roofline_hw):
        with pytest.raises(KeyError, match="TPU v9"):
            lookup("TPU v9")
