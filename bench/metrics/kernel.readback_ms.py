"""The program's ``kernel.readback`` span, mean per verdict: the
device-to-host copy of the wave degrees."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run, "kernel.readback")
