"""The program's ``session.fingerprint`` span, mean per verdict: the content
hash of the spec that keys the session's memo (in ``Session.collect_cached_batch``)."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run, "session.fingerprint")
