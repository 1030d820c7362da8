"""The program's ``kernel.h2d`` span, mean per verdict: one ``jax.device_put``
of the launch's host inputs, to its return (the host's staging of the copy;
what is still in flight then lands in ``kernel.wait``)."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run, "kernel.h2d")
