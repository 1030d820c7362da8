"""The program's ``kernel.counters`` spans, total per verdict: the
``WaveTrace`` built from the read-back degrees and ``CounterSet.from_trace``."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run, "kernel.counters")
