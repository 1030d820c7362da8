"""Percent of the instrumented scatter-add kernel's roofline.

The kernel is found in the device trace by the name its ``pallas_call``
shows there (the jitted launch wraps a ``functools.partial``, so the HLO
custom call is named ``_unknown_``); its least time comes from
``bench/work/scatter.py``.  Bound
by HBM bandwidth at every size the cells run.
"""

from bench.harness import kernel_roofline

KERNEL_NAMES = ("_unknown_.1",)


def read(run):
    return kernel_roofline(run, KERNEL_NAMES, "scatter")
