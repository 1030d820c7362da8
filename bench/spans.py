"""Readings of the program's spans, as a traced window recorded them.

``run.window.spans[i]`` holds the i-th verdict's spans: the dicts of
``repro.obs.telemetry.span``, with ``name``, ``id``, ``parent`` (the id
of the enclosing span), ``start_ms``, ``dur_ms`` and ``attrs``.
"""

from __future__ import annotations

from bench.devtrace import union_ns


def named(run, name: str) -> list:
    """Every span named ``name``, over all the window's verdicts."""
    return [s for spans in run.window.spans for s in spans
            if s["name"] == name]


def mean_ms(run, name: str):
    """Mean per verdict of the total duration of the spans named
    ``name``; None where no verdict recorded one."""
    durs = [s["dur_ms"] for s in named(run, name)]
    return sum(durs) / len(run.window.spans) if durs else None


def self_ms(spans, name: str) -> float:
    """One verdict's self time of the spans named ``name``: each one's
    duration less the part of it that its child spans cover."""
    total = 0.0
    for s in spans:
        if s["name"] != name:
            continue
        lo, hi = s["start_ms"], s["start_ms"] + s["dur_ms"]
        kids = [(max(c["start_ms"], lo), min(c["start_ms"] + c["dur_ms"], hi))
                for c in spans if c.get("parent") == s["id"]]
        total += s["dur_ms"] - union_ns([k for k in kids if k[1] > k[0]])
    return total


def mean_self_ms(run, name: str):
    """Mean per verdict of ``self_ms``; None where no verdict recorded a
    span named ``name`` with an ``id``."""
    if not any(s["name"] == name and "id" in s
               for spans in run.window.spans for s in spans):
        return None
    return (sum(self_ms(spans, name) for spans in run.window.spans)
            / len(run.window.spans))

