"""The observability layer: heat-map attribution + telemetry.

The load-bearing claims: (a) heat-map renderers round-trip — json and
csv parse back to exactly the per-bin attribution the ``Heatmap``
carries, (b) per-bin totals stay bit-consistent with the profile path —
the embedded ``CounterSet`` is bitwise-equal to the provider's
``collect`` and per-bin hits sum to the committed stream length, (c)
empty-stream and single-bin streams are well-defined, not crashes, (d)
the metrics registry enforces its label-cardinality bound even under
concurrent writers without losing counts, and (e) the service surfaces
it all: ``/metrics`` serves Prometheus-parseable text, ``/status``
carries ``SweepCache.stats()``, and every job answer carries a
propagated trace id plus span summaries.
"""

import csv
import io
import json
import os
import re
import subprocess
import sys
import threading
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import Session, WorkloadSpec
from repro.analysis.providers.trace import TraceProvider
from repro.core.counters import COMMIT_GROUP, LANES, bitwise_equal
from repro.data.images import make_image
from repro.obs import (Heatmap, heatmap_for_spec, heatmap_from_stream,
                       telemetry)
from repro.obs.telemetry import (OVERFLOW, MetricsRegistry, span,
                                 span_summaries, trace_scope)
from repro.service import ProfilingService, ServiceConfig
from repro.service.server import make_http_server


@pytest.fixture(autouse=True)
def _isolate_artifacts(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_RESULTS", str(tmp_path / "results"))
    yield


def _session():
    return Session("v5e")


def _hist_spec(variant="hist", pixels=1 << 13):
    img = make_image("solid", pixels)
    return WorkloadSpec.from_histogram(
        img, label=f"solid-{variant}", variant=variant)


# -- attribution --------------------------------------------------------------


def test_heatmap_bit_consistent_with_counterset():
    """The tentpole invariant: same stream, same kernels, same counters."""
    prov = TraceProvider()
    for variant in ("hist", "hist2"):
        spec = _hist_spec(variant)
        hm = heatmap_for_spec(spec)
        cset = prov.collect(spec, None)
        assert bitwise_equal(hm.counters, cset)
        # hits sum to the committed stream length (pixels x channels)
        stream, _, _ = spec.committed_stream()
        assert int(hm.hits.sum()) == stream.size
        assert hm.total_hits == stream.size
        # the wave series is the trace's degree array: one entry per
        # wave job, summing (per core) into the CounterSet's O
        assert hm.num_waves == cset.num_waves
        assert np.isclose(hm.wave_degree.sum(), cset.total_O)


def test_heatmap_localizes_hist_and_hist2_disperses():
    """Identical hit totals; strictly lower top-bin replay share for
    hist2 — the §5 story the heat map exists to show."""
    hist = heatmap_for_spec(_hist_spec("hist"))
    hist2 = heatmap_for_spec(_hist_spec("hist2"))
    assert np.array_equal(hist.bins, hist2.bins)
    assert np.array_equal(hist.hits, hist2.hits)
    assert hist.peak_degree == 32.0 and hist2.peak_degree == 8.0
    assert hist2.top_bin_share < hist.top_bin_share
    assert len(hist.hot_bins) >= 1
    assert list(hist.hot_bins) == list(hist2.hot_bins)


def test_heatmap_session_method_and_indices_source():
    idx = np.array([7] * LANES + [1, 2, 3], np.int64)
    spec = WorkloadSpec.from_indices(idx, 16, label="idx")
    hm = _session().heatmap(spec)
    assert isinstance(hm, Heatmap)
    assert hm.total_hits == idx.size
    assert hm.top_bin == 7
    # bin 7: one full wave of LANES hits, each commit group all-7s
    i7 = list(hm.bins).index(7)
    assert hm.hits[i7] == LANES
    assert hm.replays[i7] == LANES - LANES // COMMIT_GROUP
    assert hm.max_wave_degree[i7] == float(COMMIT_GROUP)


def test_heatmap_rejects_streamless_sources():
    tr = TraceProvider()._synthesize(_hist_spec())
    spec = WorkloadSpec(label="pre-recorded", trace=tr)
    with pytest.raises(ValueError, match="no committed index stream"):
        _session().heatmap(spec)


def test_heatmap_empty_stream():
    hm = heatmap_from_stream(np.empty(0, np.int64), label="empty")
    assert hm.total_hits == 0
    assert hm.bins.size == 0
    assert hm.top_bin is None
    assert hm.top_bin_share == 0.0
    assert hm.hot_bins.size == 0
    # all three renderers still produce output
    assert "empty" in hm.render("text")
    assert json.loads(hm.render("json"))["total_hits"] == 0
    assert hm.render("csv").startswith("bin,")


def test_heatmap_single_bin_stream():
    n = 4 * LANES
    hm = heatmap_from_stream(np.zeros(n, np.int64), label="one-bin")
    assert list(hm.bins) == [0]
    assert hm.hits[0] == n
    assert hm.replays[0] == n - n // COMMIT_GROUP
    assert hm.max_wave_degree[0] == float(COMMIT_GROUP)
    assert hm.top_bin == 0
    assert hm.top_bin_share == pytest.approx((COMMIT_GROUP - 1)
                                             / COMMIT_GROUP)
    assert list(hm.hot_bins) == [0]


def test_heatmap_negative_stream_rejected():
    with pytest.raises(ValueError, match="negative"):
        heatmap_from_stream(np.array([-1, 2]))


# -- renderers ----------------------------------------------------------------


def test_render_json_round_trip():
    hm = heatmap_for_spec(_hist_spec())
    body = json.loads(hm.render("json", top_k=64))
    assert body["label"] == hm.label
    assert body["total_hits"] == hm.total_hits
    assert body["hot_bins"] == [int(b) for b in hm.hot_bins]
    assert body["top_bin"] == hm.top_bin
    assert body["top_bin_share"] == pytest.approx(hm.top_bin_share)
    assert body["peak_wave"] == hm.peak_wave
    assert body["counters"]["total_O"] == hm.counters.total_O
    assert len(body["wave_degree"]) == hm.num_waves
    assert np.allclose(body["wave_degree"], hm.wave_degree)
    by_bin = {r["bin"]: r for r in body["bins"]}
    for i, b in enumerate(hm.bins):
        assert by_bin[int(b)]["hits"] == int(hm.hits[i])
        assert by_bin[int(b)]["replays"] == int(hm.replays[i])


def test_render_csv_round_trip():
    hm = heatmap_for_spec(_hist_spec())
    rows = list(csv.DictReader(io.StringIO(hm.render("csv"))))
    assert len(rows) == hm.bins.size
    for i, row in enumerate(sorted(rows, key=lambda r: int(r["bin"]))):
        assert int(row["bin"]) == int(hm.bins[i])
        assert int(row["hits"]) == int(hm.hits[i])
        assert int(row["replays"]) == int(hm.replays[i])
        assert float(row["max_wave_degree"]) == \
            pytest.approx(float(hm.max_wave_degree[i]))
        assert row["hot"] in ("0", "1")


def test_render_text_and_unknown_format():
    hm = heatmap_for_spec(_hist_spec())
    text = hm.render("text")
    assert "contention heat map" in text
    assert "top-bin share" in text
    assert "hot bins: 4" in text
    with pytest.raises(ValueError, match="unknown heat-map format"):
        hm.render("yaml")


# -- metrics registry ---------------------------------------------------------


def test_metrics_label_cardinality_bound_under_concurrency():
    reg = MetricsRegistry(max_series=8)
    ctr = reg.counter("test_total", "t", ("worker",))
    n_threads, per_thread = 16, 50

    def hammer(tid: int) -> None:
        for i in range(per_thread):
            ctr.inc(worker=f"w{tid}-{i}")   # every label value distinct

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    series = ctr.series()
    assert len(series) <= 8 + 1            # bound + the overflow series
    assert (OVERFLOW,) in series
    # nothing is dropped: every increment landed somewhere
    total = sum(v[0] for v in series.values())
    assert total == n_threads * per_thread


def test_metrics_registry_types_and_render():
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", "jobs", ("kind",))
    g = reg.gauge("depth", "queue depth")
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    c.inc(kind="profile")
    c.inc(2, kind="sweep")
    g.set(3)
    h.observe(0.05)
    h.observe(5.0)
    text = reg.render()
    assert '# TYPE jobs_total counter' in text
    assert 'jobs_total{kind="profile"} 1' in text
    assert 'jobs_total{kind="sweep"} 2' in text
    assert "# TYPE depth gauge\ndepth 3" in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert 'lat_seconds_count 2' in text
    # prometheus text format: every non-comment line is `name{...} value`
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert re.match(r'^[a-zA-Z_:][\w:]*(\{[^}]*\})? \S+$', line)
    # same name, different shape -> rejected
    with pytest.raises(ValueError, match="re-registered"):
        reg.counter("jobs_total", "jobs", ())
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1, kind="profile")
    with pytest.raises(ValueError, match="bad metric name"):
        reg.counter("bad name", "x")
    reg.reset()
    assert 'jobs_total{kind="profile"}' not in reg.render()


def test_spans_record_inside_scope_only():
    with span("orphan"):
        pass
    assert span_summaries() == []
    with trace_scope("tid123") as rec:
        with span("outer", label="x"):
            with span("inner"):
                pass
        assert rec["id"] == "tid123"
    names = [s["name"] for s in rec["spans"]]
    assert names == ["inner", "outer"]     # closed in completion order
    assert all(s["dur_ms"] >= 0 for s in rec["spans"])
    assert rec["spans"][1]["attrs"] == {"label": "x"}


def test_span_ids_record_their_nesting():
    with trace_scope() as outer:
        with span("a"):
            with span("b"):
                with span("c"):
                    pass
            with span("d"):
                with trace_scope() as inner:
                    with span("e"):
                        with span("f"):
                            pass
        with span("g"):
            pass
    by = {s["name"]: s for s in outer["spans"]}
    assert set(by) == set("abcdg")
    assert len({s["id"] for s in outer["spans"]}) == 5
    assert by["a"]["parent"] is None and by["g"]["parent"] is None
    assert by["b"]["parent"] == by["d"]["parent"] == by["a"]["id"]
    assert by["c"]["parent"] == by["b"]["id"]
    # the inner scope numbers its own spans and roots them at None
    ib = {s["name"]: s for s in inner["spans"]}
    assert set(ib) == {"e", "f"}
    assert ib["e"]["parent"] is None and ib["f"]["parent"] == ib["e"]["id"]
    assert ib["e"]["id"] == 1


def test_a_disabled_span_records_nothing(monkeypatch):
    opened = []
    monkeypatch.setattr(telemetry, "_ANNOTATION", opened.append)
    with telemetry.disabled(), trace_scope() as rec:
        with span("off"):
            pass
    assert rec["spans"] == [] and opened == []


def test_a_span_opens_a_trace_annotation_of_its_name(monkeypatch):
    import contextlib

    opened = []

    @contextlib.contextmanager
    def annotation(name):
        opened.append(name)
        yield
        opened.append("/" + name)
    monkeypatch.setattr(telemetry, "_ANNOTATION", annotation)
    with span("outside.scope"):           # annotated, scope or no scope
        pass
    with trace_scope() as rec:
        with span("a", k=1):
            with span("b"):
                pass
    assert opened == ["outside.scope", "/outside.scope",
                      "a", "b", "/b", "/a"]
    assert [s["name"] for s in rec["spans"]] == ["b", "a"]


def test_the_copy_is_never_waited_on(monkeypatch):
    """Traced or not, ``to_device`` runs one path: it stages the copy and
    returns; the launch's wait absorbs what is still in flight."""
    import jax

    from repro.kernels import instrumentation as instr

    waited = []
    wait = jax.block_until_ready
    monkeypatch.setattr(instr.jax, "block_until_ready",
                        lambda x: waited.append(1) or wait(x))
    host = np.arange(8, dtype=np.int32)
    (dev,) = instr.to_device(host)
    with trace_scope() as rec:
        (dev,) = instr.to_device(host)
        instr.to_device(dev)                # already on the device
    assert waited == []
    assert [s["attrs"]["bytes"] for s in rec["spans"]] == [32, 0]
    np.testing.assert_array_equal(np.asarray(dev), host)


def test_the_cap_keeps_the_spans_that_enclose_others():
    """Spans are recorded as they close, outer ones last: the cap keeps a
    place for every span still open, and drops inner ones instead."""
    with trace_scope() as rec:
        with span("job"):
            with span("sweep"):
                for _ in range(2 * telemetry.MAX_SPANS):
                    with span("step"):
                        with span("leaf"):
                            pass
        with span("after"):                 # the cap is reached
            pass
    names = [s["name"] for s in rec["spans"]]
    assert len(names) == telemetry.MAX_SPANS
    assert names[-2:] == ["sweep", "job"]
    by = {s["name"]: s for s in rec["spans"]}
    assert by["sweep"]["parent"] == by["job"]["id"]


def test_telemetry_imports_without_jax():
    src = str(Path(__file__).resolve().parents[1] / "src")
    code = ("import sys, repro.obs.telemetry as t\n"
            "with t.trace_scope() as rec:\n"
            "    with t.span('x'):\n"
            "        pass\n"
            "assert rec['spans'][0]['name'] == 'x'\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          env={**os.environ, "PYTHONPATH": src},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_profile_counts_one_entry_point():
    calls = telemetry.counter("repro_session_calls_total", "",
                              ("method",))
    sess = _session()
    sess.profile(_hist_spec())          # warm: the table is built once
    before = {m: calls.value(method=m) for m in ("profile", "analyze")}
    with trace_scope() as rec:
        sess.profile(_hist_spec("hist2"))
    assert calls.value(method="profile") == before["profile"] + 1
    assert calls.value(method="analyze") == before["analyze"]
    roots = [s["name"] for s in rec["spans"] if s["parent"] is None]
    assert roots == ["session.profile"]
    # the public analyze keeps its own count
    sess.analyze([_hist_spec()])
    assert calls.value(method="analyze") == before["analyze"] + 1


KERNEL_STEPS = ["session.fingerprint", "kernel.h2d", "kernel.launch",
                "kernel.wait", "kernel.readback", "kernel.counters"]


def test_kernel_profile_spans_on_the_profiler_trace(tmp_path):
    """The program's spans are profiler annotations: under
    ``jax.profiler.trace`` a kernel-provider profile shows its steps inside
    ``session.collect``, in order, on one host thread."""
    import jax.profiler

    sess = Session("v5e", provider="kernel")
    img = make_image("solid", 1 << 12)
    sess.profile(WorkloadSpec.from_histogram(img, label="warm"))
    with jax.profiler.trace(str(tmp_path)):
        sess.profile(WorkloadSpec.from_histogram(img + 1, label="traced"))
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events] for line in host.lines]
    (events,) = [ev for ev in lines
                 if any(n == "session.collect" for n, _, _ in ev)]
    (collect,) = [e for e in events if e[0] == "session.collect"]
    (profile,) = [e for e in events if e[0] == "session.profile"]
    (provider,) = [e for e in events if e[0] == "session.provider"]
    assert profile[1] <= collect[1] and collect[2] <= profile[2]
    assert collect[1] <= provider[1] and provider[2] <= collect[2]
    inside = sorted((s, n) for n, s, e in events
                    if n in KERNEL_STEPS and collect[1] <= s
                    and e <= collect[2])
    order = [n for _, n in inside]
    assert [n for i, n in enumerate(order) if n not in order[:i]] == \
        KERNEL_STEPS
    assert all(provider[1] <= s for s, n in inside
               if n.startswith("kernel."))


# -- service surface ----------------------------------------------------------


@pytest.fixture
def service():
    svc = ProfilingService(ServiceConfig(
        workers=2, queue_depth=16, persistent_cache=True)).start()
    yield svc
    svc.stop()


def test_service_heatmap_kind_and_trace_ids(service):
    status, body = service.handle(
        {"kind": "heatmap",
         "workload": {"workload": "histogram", "pixels": 1 << 13,
                      "dist": "solid"},
         "options": {"top_k": 4, "hot_degree": 2.0}},
        trace_id="deadbeef01")
    assert status == 200, body
    assert body["trace_id"] == "deadbeef01"
    names = [s["name"] for s in body["spans"]]
    assert "service.dispatch" in names and "session.heatmap" in names
    result = body["result"]
    assert len(result["hot_bins"]) >= 1
    assert result["top_bin_share"] > 0
    # a heatmap job over a multi-point grid is a 400, like profile
    status, body = service.handle(
        {"kind": "heatmap",
         "workload": {"workload": "indices", "size": [1024, 2048]}})
    assert status == 400


def test_a_large_sweep_job_keeps_its_outer_spans(service):
    """A sweep of more points than the span cap still returns the spans
    that attribute the job: dispatch, analyze, collect and model."""
    status, body = service.handle(
        {"kind": "sweep",
         "workload": {"workload": "indices", "dist": "solid",
                      "size": [1024 * k for k in range(1, 16)],
                      "waves_per_tile": list(range(1, 21))}})
    assert status == 200, body
    assert len(body["result"]["points"]) == 300
    names = {s["name"] for s in body["spans"]}
    assert {"service.dispatch", "session.analyze", "session.collect",
            "session.fingerprint", "session.model"} <= names
    assert len(body["spans"]) <= telemetry.MAX_SPANS


def test_service_status_includes_cache_stats(service):
    service.handle({"kind": "profile",
                    "workload": {"workload": "indices", "size": 1024}})
    status = service.status()
    assert "cache" in status
    for key in ("entries", "bytes", "quarantined"):
        assert key in status["cache"]
    assert status["cache"]["entries"] >= 1


def test_metrics_endpoint_and_trace_header(service):
    server = make_http_server(service, port=0)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        port = server.server_address[1]
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/jobs",
            data=json.dumps(
                {"kind": "profile",
                 "workload": {"workload": "indices",
                              "size": 1024}}).encode(),
            headers={"X-Repro-Trace-Id": "my-trace-42"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["X-Repro-Trace-Id"] == "my-trace-42"
            body = json.loads(resp.read())
        assert body["ok"] and body["trace_id"] == "my-trace-42"
        assert isinstance(body["spans"], list) and body["spans"]

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        assert re.search(
            r'repro_service_jobs_total\{kind="profile",outcome="ok"\} \d+',
            text)
        assert "repro_circuit_breaker_open" in text
        assert "repro_service_queue_depth" in text
        assert "repro_session_calls_total" in text
    finally:
        server.shutdown()
        server.server_close()


def test_schema_lists_heatmap_kind(service):
    from repro.service.jobs import JOB_KINDS
    assert "heatmap" in JOB_KINDS


# -- CLI ----------------------------------------------------------------------


def test_cli_version(capsys):
    import repro
    from repro.cli.main import main
    with pytest.raises(SystemExit) as ei:
        main(["--version"])
    assert ei.value.code == 0
    assert repro.__version__ in capsys.readouterr().out


def test_cli_heatmap(capsys):
    from repro.cli.main import main
    rc = main(["heatmap", "--workload", "histogram", "--pixels", "2^13",
               "--dist", "solid", "--format", "json", "--no-artifact"])
    out = capsys.readouterr().out
    assert rc == 0
    body = json.loads(out)
    assert len(body["hot_bins"]) >= 1
    assert body["top_bin_share"] > 0


def test_cli_heatmap_writes_artifact(tmp_path, monkeypatch, capsys):
    from repro.cli.main import main
    monkeypatch.setenv("REPRO_RESULTS", str(tmp_path))
    rc = main(["heatmap", "--size", "2^12", "--dist", "solid",
               "--format", "csv"])
    capsys.readouterr()
    assert rc == 0
    arts = list(tmp_path.rglob("heatmap-*.csv"))
    assert len(arts) == 1
    rows = list(csv.DictReader(arts[0].open()))
    assert sum(int(r["hits"]) for r in rows) == 1 << 12
