"""The program's ``kernel.prepare`` span, mean per verdict: the scatter-add
launch's host-side input preparation in numpy (committed-stream padding,
the float32 cast and the (n,) -> (n, 1) reshape of the values)."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run, "kernel.prepare")
