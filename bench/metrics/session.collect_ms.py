"""Provider collection per verdict: the mean duration of the program's
``session.collect`` span (fingerprint, memo lookup, the kernel provider's
host-to-device copy, launch and read-back, and the counters built)."""


def read(run):
    durs = [s["dur_ms"] for spans in run.window.spans for s in spans
            if s["name"] == "session.collect"]
    return sum(durs) / len(run.window.spans) if durs else None
