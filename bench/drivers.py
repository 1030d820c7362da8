"""The entry the window drives: ``Session.profile`` in process, in a
closed loop with one caller.

The driver warms up the shapes its traffic uses, runs the measured
window, and after the window hands back the program's verdicts for a
sample of the requests, drawn from the seed, for the comparison with the
reference.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time

from bench import compare
from bench.traffic import requests, seeded_rng


def _annotate(traced: bool, name: str):
    if not traced:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation  # lazy: jax
    return TraceAnnotation(name)


class Window:
    """What a measured window recorded."""

    def __init__(self) -> None:
        self.attempted = 0
        self.failed = 0
        self.completed_in_window = 0
        self.latencies_s: list = []
        self.seconds = 0.0
        self.traffic_wait_s = 0.0
        self.spans: list = []        # per verdict: the program's spans
        self.launches: list = []
        self.stats_delta: dict = {}


class SessionDriver:
    """``Session.profile`` in a closed loop with one caller."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, table_dir) -> None:
        from repro.analysis import Session  # lazy: the system under test

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.gen = requests(cfg, traffic, seed)
        self.sess = Session(cfg["device"], provider=cfg["provider"],
                            cache_dir=table_dir)
        self._keys = self.gen.keys()
        self._queue: queue.Queue = queue.Queue(maxsize=traffic["prefetch"])
        self._stop = threading.Event()
        self._thread = None
        self.records: list = []      # (key, variant, profile)

    def _produce(self) -> None:
        for key in self._keys:
            item = (key, self.gen.payload(key))
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if self._stop.is_set():
                return

    def warmup(self) -> None:
        """One call per variant on a request the window never repeats, then
        the prefetch queue filled."""
        key = next(self._keys)
        payload = self.gen.payload(key)
        for v in self.gen.variants:
            self.sess.profile(self.gen.spec(payload, v, f"warmup-{v}"))
        self._thread = threading.Thread(target=self._produce, daemon=True,
                                        name="bench-traffic")
        self._thread.start()
        while not self._queue.full():
            time.sleep(0.01)

    def window(self, seconds: float, traced: bool) -> Window:
        from repro.obs import telemetry

        w = Window()
        before = self.sess.stats_snapshot()
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            tw = time.perf_counter()
            with _annotate(traced, "bench.traffic_wait"):
                key, payload = self._queue.get()
            w.traffic_wait_s += time.perf_counter() - tw
            shape = self.gen.launch(payload)
            for v in self.gen.variants:
                if time.perf_counter() >= end:
                    break
                spec = self.gen.spec(payload, v, f"{key}-{v}")
                w.attempted += 1
                scope = (telemetry.trace_scope() if traced
                         else contextlib.nullcontext({"spans": []}))
                t_issue = time.perf_counter()
                try:
                    with _annotate(traced, "bench.verdict"), scope as rec:
                        prof = self.sess.profile(spec)
                except Exception as exc:  # noqa: BLE001 — counted, reported
                    w.failed += 1
                    print(f"verdict {key}-{v} failed: "
                          f"{type(exc).__name__}: {exc}", flush=True)
                    continue
                t_done = time.perf_counter()
                w.latencies_s.append(t_done - t_issue)
                if t_done <= end:
                    w.completed_in_window += 1
                w.spans.append(rec["spans"])
                w.launches.append(shape)
                self.records.append((key, v, prof))
        w.seconds = seconds
        self._stop.set()
        self._thread.join(10)
        after = self.sess.stats_snapshot()
        w.stats_delta = {k: after[k] - before[k] for k in after}
        return w

    def program_verdicts(self, sample: int) -> tuple[list, int]:
        """(key, variant, program verdict) for a sample drawn from the seed,
        and how many sampled verdicts the session no longer held.

        The counters come from the session's memo: what the window's call
        collected, looked up by the same content fingerprint.
        """
        rng = seeded_rng(self.seed, 9)
        picks = []
        for v in self.gen.variants:   # as many of each variant
            idx = [i for i, r in enumerate(self.records) if r[1] == v]
            k = min(-(-sample // len(self.gen.variants)), len(idx))
            picks += [idx[j] for j in rng.choice(len(idx), k, replace=False)]
        out, unverified = [], 0
        for i in sorted(picks):
            key, v, prof = self.records[i]
            payload = self.gen.payload(key)
            before = self.sess.stats_snapshot()["collected"]
            cset = self.sess.collect_cached(
                self.gen.spec(payload, v, f"{key}-{v}"))
            if self.sess.stats_snapshot()["collected"] != before:
                unverified += 1
                continue
            out.append((key, v, program_verdict(cset, prof)))
        return out, unverified

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(10)
        self.sess = None


def program_verdict(cset, prof) -> dict:
    """A ``Session`` verdict in the comparison's terms."""
    pc = prof.per_core
    return {
        "counters": {k: getattr(cset, k) for k in compare.COUNTER_KEYS},
        "model": {
            "e": [c.e for c in pc], "n_hat": [c.n_hat for c in pc],
            "c": [c.c for c in pc], "S": [c.S_cycles for c in pc],
            "B": [c.B_cycles for c in pc], "T": [c.T_cycles for c in pc],
            "U": [c.U for c in pc],
            "scatter_model_U": prof.scatter_utilization,
            **{f"U_{u.name}": u.utilization for u in prof.units},
        },
        "bottleneck": prof.bottleneck,
    }
