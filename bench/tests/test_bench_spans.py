"""Readings of the program's spans: the mean per verdict of a named span,
a span's self time from ``id``/``parent``, and the readers built on them,
which read nothing where the program records no such span."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace, drivers, harness, spans  # noqa: E402

NEW_READERS = ["session.fingerprint_ms", "kernel.prepare_ms", "kernel.h2d_ms",
               "kernel.launch_ms", "kernel.wait_ms", "kernel.readback_ms",
               "kernel.counters_ms", "session.collect_self_ms",
               "session.provider_self_ms"]


def _span(name, sid, parent, start, dur, **attrs):
    out = {"name": name, "id": sid, "parent": parent, "start_ms": start,
           "dur_ms": dur}
    if attrs:
        out["attrs"] = attrs
    return out


def _verdict(shift=0.0):
    """session.profile > session.collect > fingerprint and session.provider
    > h2d, launch, wait and two counters spans; 1.25 ms of collection and
    0.75 ms of the provider call that no child covers."""
    return [
        _span("session.fingerprint", 3, 2, 1.0 + shift, 2.0),
        _span("kernel.h2d", 4, 10, 3.0 + shift, 1.0, bytes=4_000_000),
        _span("kernel.launch", 5, 10, 4.5 + shift, 0.5),
        _span("kernel.wait", 6, 10, 5.0 + shift, 2.0),
        _span("kernel.counters", 7, 10, 7.0 + shift, 0.25),
        _span("kernel.counters", 8, 10, 7.25 + shift, 0.25),
        _span("session.provider", 10, 2, 3.0 + shift, 4.75),
        _span("session.collect", 2, 1, 0.5 + shift, 8.0),
        _span("session.model", 9, 1, 8.5 + shift, 1.0),
        _span("session.profile", 1, None, 0.0 + shift, 10.0),
    ]


def _run(verdicts):
    w = drivers.Window()
    w.spans = verdicts
    return harness.Run(cell="c", config={}, traffic={}, peaks={},
                       setup_s=1.0, window=w)


def test_mean_per_verdict_sums_a_verdicts_spans_of_one_name():
    run = _run([_verdict(), _verdict(), []])
    assert spans.mean_ms(run, "kernel.counters") == pytest.approx(1.0 / 3)
    assert spans.mean_ms(run, "kernel.wait") == pytest.approx(4.0 / 3)
    assert spans.mean_ms(run, "kernel.prepare") is None


def test_self_time_is_what_no_child_covers():
    v = _verdict()
    # collect: 8 ms, children cover 2 + 4.75 ms; grandchildren don't count
    assert spans.self_ms(v, "session.collect") == pytest.approx(1.25)
    # provider: 4.75 ms, children cover 1 + 0.5 + 2 + 0.25 + 0.25 = 4 ms
    assert spans.self_ms(v, "session.provider") == pytest.approx(0.75)
    # profile: 10 ms less collect (8) and model (1)
    assert spans.self_ms(v, "session.profile") == pytest.approx(1.0)
    assert spans.self_ms(v, "kernel.wait") == pytest.approx(2.0)


def test_self_time_counts_overlapping_children_once_and_clips_them():
    v = [_span("p", 1, None, 0.0, 10.0),
         _span("a", 2, 1, 1.0, 4.0), _span("b", 3, 1, 3.0, 4.0),
         _span("late", 4, 1, 9.0, 5.0), _span("other", 5, None, 0.0, 3.0)]
    # covered: [1, 7] and [9, 10] of the parent's [0, 10]
    assert spans.self_ms(v, "p") == pytest.approx(3.0)


def test_mean_self_time_needs_span_ids():
    assert spans.mean_self_ms(_run([_verdict(), _verdict(5.0)]),
                              "session.collect") == pytest.approx(1.25)
    no_ids = [[{"name": "session.collect", "start_ms": 0.0, "dur_ms": 3.0}]]
    assert spans.mean_self_ms(_run(no_ids), "session.collect") is None
    assert spans.mean_self_ms(_run([[]]), "session.collect") is None


def test_readers_of_the_new_spans():
    run = _run([_verdict(), _verdict(1.0)])
    read = {n: harness.load_metric(n).read(run) for n in NEW_READERS}
    assert read["session.fingerprint_ms"] == pytest.approx(2.0)
    assert read["kernel.h2d_ms"] == pytest.approx(1.0)
    assert read["kernel.launch_ms"] == pytest.approx(0.5)
    assert read["kernel.wait_ms"] == pytest.approx(2.0)
    assert read["kernel.counters_ms"] == pytest.approx(0.5)
    assert read["session.collect_self_ms"] == pytest.approx(1.25)
    assert read["session.provider_self_ms"] == pytest.approx(0.75)
    assert read["kernel.prepare_ms"] is None
    assert read["kernel.readback_ms"] is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_reads_nothing_where_its_span_is_absent(name):
    """A program without the spans (the parent of this benchmark's span
    metrics) records only the session's, with no ids."""
    old = [[{"name": "session.profile", "start_ms": 0.0, "dur_ms": 4.0},
            {"name": "session.collect", "start_ms": 0.1, "dur_ms": 3.0},
            {"name": "session.model", "start_ms": 3.2, "dur_ms": 0.6}]]
    assert harness.load_metric(name).read(_run(old)) is None
    assert harness.load_metric(name).read(_run([])) is None


# -- a recorded chip trace ----------------------------------------------------

FIXTURE = Path(__file__).resolve().parent / "data" / "moe-skewed-spans.xplane.pb.gz"
PROGRAM = ("session.", "kernel.")
#: the largest constant shift between the device's clock and the host's
#: seen in a traced chip run of this program (1.32 ms, TPU v5e, this
#: fixture): the profiler aligns the two per run to about this precision
SHIFT_NS = 1.35e6


def test_device_launches_lie_inside_their_verdicts_launch_to_wait():
    """The program's spans and the device's ops share one clock: every
    scatter kernel runs between its verdict's ``kernel.launch`` start and
    ``kernel.wait`` end on the host, in the verdicts' order.  The profiler
    aligns the device's clock to the host's only to within about a
    millisecond, by a different amount in each run, so the check allows
    one shift for the whole trace, no larger than ``SHIFT_NS``."""
    t = devtrace.load(FIXTURE)
    names = harness.load_metric("scatter_kernel_roofline").KERNEL_NAMES
    kernels = sorted((s, e) for ev in t.ops.values() for n, s, e in ev
                     if n in names)
    windows = []
    for _, vs, ve in sorted(h for h in t.host if h[0] == "bench.verdict"):
        (launch,) = [s for n, s, _ in t.host
                     if n == "kernel.launch" and vs <= s <= ve]
        (wait,) = [e for n, _, e in t.host
                   if n == "kernel.wait" and vs <= e <= ve]
        windows.append((launch, wait))
    assert len(kernels) == len(windows) > 50
    # a shift c puts kernel k inside window w iff ls - ks <= c <= we - ke
    least = max(ls - ks for (ks, _), (ls, _) in zip(kernels, windows))
    most = min(we - ke for (_, ke), (_, we) in zip(kernels, windows))
    assert least <= most
    assert least <= SHIFT_NS and most >= -SHIFT_NS


def test_the_longest_idle_gaps_fall_in_program_spans_or_the_traffic_wait():
    t = devtrace.load(FIXTURE)
    gaps = []
    for ev in t.ops.values():
        merged = devtrace._merge([(s, e) for _, s, e in ev])
        gaps += [(b[0] - a[1], (a[1] + b[0]) / 2)
                 for a, b in zip(merged, merged[1:]) if b[0] > a[1]]
    gaps.sort(reverse=True)
    reported = t.idle_gaps()
    assert len(reported) == 10
    for (length, mid), (name, secs) in zip(gaps, reported):
        assert secs == pytest.approx(length / 1e9)
        covering = {n for n, s, e in t.host if s <= mid <= e}
        assert name in covering
        assert any(n.startswith(PROGRAM) for n in covering) or \
            "bench.traffic_wait" in covering, (name, covering)
