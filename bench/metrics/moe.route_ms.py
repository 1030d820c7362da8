"""The program's ``moe.route`` span, mean per verdict: the host's part of
routing a batch on the device (looking up the compiled routed-count
program and placing the layer's router weight and bias on the device)."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run, "moe.route")
