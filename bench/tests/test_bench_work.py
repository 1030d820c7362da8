"""Work counts of the kernels and the roofline arithmetic."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace, drivers, harness  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HIST = {"kernel": "hist", "pixels": 1 << 22, "channels": 4,
        "num_bins": 256, "commit_group": 32}
SCATTER = {"kernel": "scatter", "ids": 65536, "width": 1, "segments": 128,
           "commit_group": 32}


def test_histogram_work_at_the_paper_size():
    w = harness.load_work("hist").work(HIST)
    values = (1 << 22) * 4
    assert w["ops"] == values
    assert w["bytes"] == values + 4 * 256 * 4 + values // 32 * 4
    # memory bounds it: ~18.9 MB at 819 GB/s against 16.8 M ops at peak
    assert w["bytes"] / PEAKS["hbm_bytes_per_s"] > \
        w["ops"] / PEAKS["bf16_flops_per_s"]
    assert w["bytes"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(23.04e-6,
                                                                  rel=1e-3)


def test_scatter_work_of_the_router_batch():
    w = harness.load_work("scatter").work(SCATTER)
    assert w["ops"] == 65536
    assert w["bytes"] == 65536 * 4 + 65536 * 4 + 128 * 4 + 2048 * 4
    assert w["bytes"] / PEAKS["hbm_bytes_per_s"] > \
        w["ops"] / PEAKS["bf16_flops_per_s"]


def _run(trace, launches):
    w = drivers.Window()
    w.launches = launches
    return harness.Run(cell="c", config={}, traffic={}, peaks=PEAKS,
                       setup_s=1.0, window=w, trace=trace,
                       trace_window_s=1.0)


def test_roofline_is_least_time_over_device_time():
    ms = 1_000_000
    trace = devtrace.DeviceTrace(ops={"/device:TPU:0": [
        ("_hist_kernel", 0, 20 * ms), ("copy", 20 * ms, 23 * ms),
        ("_hist_kernel", 50 * ms, 70 * ms)]}, host=[])
    run = _run(trace, [HIST, HIST, SCATTER])
    w = harness.load_work("hist").work(HIST)
    least = w["bytes"] / PEAKS["hbm_bytes_per_s"]
    got = harness.kernel_roofline(run, ("_hist_kernel",), "hist")
    assert got == pytest.approx(100 * 2 * least / 0.040)


def test_roofline_is_silent_without_launches():
    trace = devtrace.DeviceTrace(ops={"/device:TPU:0": [("x", 0, 5)]},
                                 host=[])
    assert harness.kernel_roofline(_run(trace, [HIST]), ("_hist_kernel",),
                                   "hist") is None
    assert harness.kernel_roofline(_run(None, [HIST]), ("_hist_kernel",),
                                   "hist") is None


def test_percentile_is_nearest_rank():
    assert harness.p95(list(range(1, 101))) == 95
    assert harness.p95([3.0]) == 3.0
    assert harness.p95(list(range(1, 21))) == 19
