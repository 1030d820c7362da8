"""The program's ``kernel.wait`` span, mean per verdict: waiting on the
launch's wave degrees: the device's time plus its queue, and the end of
the inputs' copy where ``kernel.h2d`` returned before it landed."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run, "kernel.wait")
