"""Percent of the roofline of the instrumented scatter-add kernel as the
routed program launches it: the expert-load count of the router's ids
(unit values, width 1).

The kernel is found in the device trace by the name its ``pallas_call``
shows there: the custom call of the jitted program ``_routed_count``
(``repro.kernels.scatter_add.ops.count_program``).  Its least time comes
from ``bench/work/scatter.py``.
"""

from bench.harness import kernel_roofline

KERNEL_NAMES = ("_routed_count.1",)


def read(run):
    return kernel_roofline(run, KERNEL_NAMES, "scatter")
