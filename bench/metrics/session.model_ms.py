"""Queue-model evaluation per verdict: the mean duration of the program's
``session.model`` span (``profile_batch`` and the verdict's assembly)."""


def read(run):
    durs = [s["dur_ms"] for spans in run.window.spans for s in spans
            if s["name"] == "session.model"]
    return sum(durs) / len(run.window.spans) if durs else None
