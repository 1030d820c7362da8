"""Percent of the roofline of DeepSeek-V3's router as the routed program
runs it: logits, sigmoid, group and expert top-k, before the count.

The router is XLA's, not a kernel of its own, so it is found in the
device trace by position: in each run of the routed program, the ops from
its first, the logits' matmul fusion (``ROUTER_FIRST``, with the sigmoid
and the bias fused in), up to the count kernel (``COUNT``).  The chip
runs one program at a time, so nothing else lies between.  Their device
time is the union of those ops; the least time comes from
``bench/work/router.py``.  ``KERNEL_NAMES`` names the matmul alone, for
the harness's log line.
"""

import statistics

from bench.devtrace import union_ns
from bench.harness import load_work

ROUTER_FIRST = "fusion.3"
COUNT = "_routed_count.1"
KERNEL_NAMES = (ROUTER_FIRST,)


def router_time(trace) -> tuple:
    """(runs of the routed program, device seconds of their router ops)."""
    runs, ns = 0, 0.0
    for ops in trace.ops.values():
        ops = sorted(ops, key=lambda op: op[1])
        first = None
        for i, (name, _, _) in enumerate(ops):
            if name == ROUTER_FIRST:
                first = i
            elif name == COUNT and first is not None:
                ns += union_ns([(s, e) for _, s, e in ops[first:i]])
                runs += 1
                first = None
    return runs, ns / 1e9


def read(run):
    if run.trace is None or not run.peaks:
        return None
    n, secs = router_time(run.trace)
    shapes = [s for s in run.window.launches if "top_k" in s]
    if not n or not shapes or secs <= 0:
        return None
    work = load_work("router")
    least = statistics.mean(
        max(w["ops"] / run.peaks["bf16_flops_per_s"],
            w["bytes"] / run.peaks["hbm_bytes_per_s"])
        for w in (work.work(s) for s in shapes))
    return 100.0 * n * least / secs
