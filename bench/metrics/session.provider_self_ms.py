"""Self time of the program's ``session.provider`` span, mean per verdict:
the provider call for the memo's misses less the kernel steps inside it
(the provider's dispatch, its backend check and the counter frame built
from its counters)."""

from bench.spans import mean_self_ms


def read(run):
    return mean_self_ms(run, "session.provider")
