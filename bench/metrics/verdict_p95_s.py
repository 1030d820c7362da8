"""95th percentile, over every verdict issued in the window, of the time
from issuing the call to holding the verdict.  Nearest rank; failed
answers are left out (they count in ``failed``)."""

from bench.harness import p95


def read(run):
    lat = run.window.latencies_s
    return p95(lat) if lat else None
