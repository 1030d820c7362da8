"""Reduction of a profiler trace to device busy time, kernel time and gaps.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane, named by their HLO instruction (the event name
is the whole instruction; its name is what precedes `` = ``).  Host
activity is the events of the host plane's threads, on the same clock,
which the idle gaps are laid to.
"""

from __future__ import annotations

import dataclasses
import gzip
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_MARK = "bench."   # the harness's own annotations mark its threads
VERDICT = "bench.verdict"


@dataclasses.dataclass
class DeviceTrace:
    """Device op intervals per chip, and host events of the main thread."""

    ops: dict          # plane name -> [(name, start_ns, end_ns)]
    host: list         # [(name, start_ns, end_ns)] of the annotated thread

    @property
    def chips(self) -> int:
        return len(self.ops)

    def busy_s(self) -> float:
        """Union of the device's op intervals, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(union_ns([(s, e) for _, s, e in ev])
                   for ev in self.ops.values()) / len(self.ops) / 1e9

    def kernel(self, names) -> tuple[int, float]:
        """(launches, device seconds) of the ops named in ``names``."""
        names = tuple(names)
        hits = [(s, e) for ev in self.ops.values() for n, s, e in ev
                if n in names]
        return len(hits), sum(e - s for s, e in hits) / 1e9

    def top_ops(self, k: int = 10) -> list:
        total: dict = {}
        for ev in self.ops.values():
            for n, s, e in ev:
                total[n] = total.get(n, 0) + (e - s)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[n, ns / 1e9] for n, ns in top]

    def idle_gaps(self, k: int = 10, spans=None) -> list:
        """The ``k`` longest gaps between device ops, each named by what
        the host was doing over its midpoint: the innermost host event of
        the annotated threads, or, inside a ``bench.verdict`` event, the
        innermost of that verdict's program spans (``spans[i]`` holds the
        i-th verdict's, in ms from its start)."""
        gaps = []
        for ev in self.ops.values():
            merged = _merge([(s, e) for _, s, e in ev])
            gaps += [(b[0] - a[1], a[1], b[0])
                     for a, b in zip(merged, merged[1:]) if b[0] > a[1]]
        gaps.sort(reverse=True)
        verdicts = sorted(s for n, s, _ in self.host if n == VERDICT)
        out = []
        for length, start, end in gaps[:k]:
            mid = (start + end) / 2
            covering = sorted((e - s, s, n) for n, s, e in self.host
                              if s <= mid <= e)
            name = covering[0][2] if covering else "host idle"
            if name == VERDICT and spans:
                i = verdicts.index(covering[0][1])
                at_ms = (mid - covering[0][1]) / 1e6
                inner = sorted((sp["dur_ms"], sp["name"])
                               for sp in (spans[i] if i < len(spans) else [])
                               if sp["start_ms"] <= at_ms
                               <= sp["start_ms"] + sp["dur_ms"])
                if inner:
                    name = inner[0][1]
            out.append([name, length / 1e9])
        return out


def op_name(event_name: str) -> str:
    """``%name = shape op(...)`` -> ``name``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _merge(intervals):
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals) -> float:
    return float(sum(e - s for s, e in _merge(intervals)))


def load(path) -> DeviceTrace:
    """Read an ``.xplane.pb`` (or its ``.gz``) into a ``DeviceTrace``."""
    from jax.profiler import ProfileData  # lazy: jax

    path = Path(path)
    data = path.read_bytes()
    if path.suffix == ".gz":
        data = gzip.decompress(data)
    return from_profile(ProfileData.from_serialized_xspace(data))


def from_profile(pd) -> DeviceTrace:
    ops: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (op_name(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events]
                if any(n.startswith(HOST_MARK) for n, _, _ in events):
                    host.extend(events)
    return DeviceTrace(ops=ops, host=host)
