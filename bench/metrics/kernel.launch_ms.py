"""The program's ``kernel.launch`` span, mean per verdict: the jitted
instrumented launch, from the call to its return (dispatch, not the
device's time)."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run, "kernel.launch")
