"""Percent of the instrumented histogram kernel's roofline.

The kernel is found in the device trace by the name its ``pallas_call``
shows there (the HLO custom call of the jitted launch
``_histogram_and_degrees``, for ``hist`` and ``hist2`` alike); its least
time comes from ``bench/work/hist.py``.  Bound by
HBM bandwidth at every size the cells run.
"""

from bench.harness import kernel_roofline

KERNEL_NAMES = ("_histogram_and_degrees.1",)


def read(run):
    return kernel_roofline(run, KERNEL_NAMES, "hist")
