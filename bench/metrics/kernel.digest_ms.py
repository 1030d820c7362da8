"""The program's ``kernel.digest`` span, mean per verdict: the content
digest of a spec's device-resident arrays, computed on the device inside
``session.fingerprint``, with the copy of the digests to the host."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run, "kernel.digest")
