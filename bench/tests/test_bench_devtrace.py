"""The reduction from a profiler trace: kernel device time, the busy union
and the idle share, and the host activity idle gaps are laid to."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace, drivers, harness  # noqa: E402

US = 1_000


DATA = Path(__file__).resolve().parent / "data"


def test_recorded_chip_trace():
    """A 0.3 s window of moe-skewed traced on one v5e: 76 verdicts."""
    t = devtrace.load(DATA / "moe-skewed.xplane.pb.gz")
    assert t.chips == 1
    names = harness.load_metric("scatter_kernel_roofline").KERNEL_NAMES
    n, secs = t.kernel(names)
    assert n == 76 and secs == pytest.approx(0.006891169, abs=1e-9)
    assert t.busy_s() == pytest.approx(0.008869842, abs=1e-9)
    assert [op for op, _ in t.top_ops(2)] == ["_unknown_.1", "copy.2"]
    assert sum(1 for name, _, _ in t.host if name == "bench.verdict") == 76
    w = drivers.Window()
    w.launches = [{"kernel": "scatter", "ids": 65536, "width": 1,
                   "segments": 128, "commit_group": 32}]
    run = harness.Run(cell="moe-skewed", config={}, traffic={},
                      peaks={"bf16_flops_per_s": 197e12,
                             "hbm_bytes_per_s": 819e9},
                      setup_s=1.0, window=w, trace=t,
                      trace_window_s=0.3988914499999794)
    # the run itself reported 0.7177240188359202 % and 97.77637700682719 %
    assert harness.load_metric("scatter_kernel_roofline").read(run) == \
        pytest.approx(0.7177240188359202, rel=1e-12)
    assert harness.load_metric("device.idle_share").read(run) == \
        pytest.approx(97.77637700682719, rel=1e-12)


def _trace():
    ops = {"/device:TPU:0": [("k", 0, 10 * US), ("copy", 5 * US, 12 * US),
                             ("k", 30 * US, 40 * US),
                             ("fusion", 100 * US, 101 * US)]}
    host = [("bench.verdict", 0, 200 * US), ("hash", 12 * US, 29 * US),
            ("bench.traffic_wait", 45 * US, 99 * US)]
    return devtrace.DeviceTrace(ops=ops, host=host)


def test_busy_union_counts_overlap_once():
    t = _trace()
    assert t.busy_s() == pytest.approx((12 + 10 + 1) * 1e-6)
    assert devtrace.union_ns([(0, 5), (3, 8), (10, 11)]) == 9


def test_kernel_time_sums_the_named_ops():
    n, secs = _trace().kernel(("k",))
    assert n == 2 and secs == pytest.approx(20e-6)


def test_top_ops_by_total_time():
    top = _trace().top_ops()
    assert top[0] == ["k", pytest.approx(20e-6)]
    assert [n for n, _ in top] == ["k", "copy", "fusion"]


def test_idle_gaps_are_named_by_the_innermost_host_event():
    gaps = _trace().idle_gaps()
    assert gaps[0] == ["bench.traffic_wait", pytest.approx(60e-6)]
    assert gaps[1] == ["hash", pytest.approx(18e-6)]


def test_gaps_inside_a_verdict_are_named_by_its_program_spans():
    ops = {"/device:TPU:0": [("k", 0, 10 * US), ("k", 50 * US, 60 * US)]}
    host = [("bench.verdict", 0, 70 * US)]
    spans = [[{"name": "session.profile", "start_ms": 0.0, "dur_ms": 0.07},
              {"name": "session.collect", "start_ms": 0.001, "dur_ms": 0.05}]]
    t = devtrace.DeviceTrace(ops=ops, host=host)
    assert t.idle_gaps(spans=spans) == [["session.collect",
                                         pytest.approx(40e-6)]]
    assert t.idle_gaps()[0][0] == "bench.verdict"


def test_op_names_are_the_hlo_instruction_names():
    assert devtrace.op_name("%_unknown_.1 = (f32[128,1]) custom-call(%a)") \
        == "_unknown_.1"
    assert devtrace.op_name("copy.2") == "copy.2"


def test_idle_share_reader():
    w = drivers.Window()
    run = harness.Run(cell="c", config={}, traffic={}, peaks={},
                      setup_s=1.0, window=w, trace=_trace(),
                      trace_window_s=200e-6)
    share = harness.load_metric("device.idle_share").read(run)
    assert share == pytest.approx(100 * (1 - 23 / 200))
    run.trace = None
    assert harness.load_metric("device.idle_share").read(run) is None
