"""Self time of the program's ``session.collect`` span, mean per verdict:
its duration less what its child spans (the fingerprint and the provider
call) cover: the memo, batch grouping and relabelling.  Time the
collection spends where no span looks."""

from bench.spans import mean_self_ms


def read(run):
    return mean_self_ms(run, "session.collect")
