"""The repro.analysis session API: Device registry, WorkloadSpec, Session."""

import dataclasses
import hashlib
import json

import numpy as np
import pytest

from repro.analysis import (
    Device,
    Session,
    WorkloadSpec,
    get_device,
)
from repro.analysis import device as device_mod
from repro.analysis import workload as workload_mod
from repro.core import counters
from repro.core.profiler import CacheModel


@pytest.fixture
def sess(tmp_path):
    device_mod._TABLE_MEMO.clear()
    return Session("v5e", cache_dir=tmp_path)


def _solid(num_waves=64):
    return np.zeros(num_waves * 1024, np.int64)


def _uniform(num_waves=64, num_bins=256, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, num_bins, num_waves * 1024)


# -- Device registry ----------------------------------------------------------


def test_get_device_known_and_passthrough():
    dev = get_device("v5e")
    assert dev.name == "v5e"
    assert get_device(dev) is dev


def test_get_device_unknown_lists_registry():
    with pytest.raises(KeyError, match="v5e"):
        get_device("h100")


def test_device_variant_with_():
    dev = get_device("v5e").with_(cache=CacheModel(llc_bytes=1))
    assert dev.cache.llc_bytes == 1
    assert get_device("v5e").cache.llc_bytes != 1  # registry untouched


def test_device_is_frozen():
    with pytest.raises(dataclasses.FrozenInstanceError):
        get_device("v5e").num_cores = 4


# -- WorkloadSpec -------------------------------------------------------------


def test_spec_requires_exactly_one_source():
    with pytest.raises(ValueError, match="exactly one"):
        WorkloadSpec(label="none")
    tr = counters.trace_from_indices(_solid(2), 256)
    with pytest.raises(ValueError, match="exactly one"):
        WorkloadSpec(label="both", trace=tr, indices=_solid(2))


def test_spec_is_frozen_and_with_derives():
    spec = WorkloadSpec.from_indices(_solid(4), 256, label="a",
                                     waves_per_tile=8)
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.label = "b"
    spec2 = spec.with_(label="b", waves_per_tile=16)
    assert (spec.label, spec.waves_per_tile) == ("a", 8)
    assert (spec2.label, spec2.waves_per_tile) == ("b", 16)


def test_spec_resolve_trace_applies_geometry_without_mutation():
    tr = counters.trace_from_indices(_solid(8), 256, waves_per_tile=4)
    spec = WorkloadSpec.from_trace(tr, label="g", waves_per_tile=32,
                                   pipeline_depth=4)
    resolved = spec.resolve_trace()
    assert (resolved.waves_per_tile, resolved.pipeline_depth) == (32, 4)
    assert (tr.waves_per_tile, tr.pipeline_depth) == (4, 2)  # source intact
    np.testing.assert_array_equal(resolved.degree, tr.degree)


def test_spec_from_indices_defaults_bytes_read():
    spec = WorkloadSpec.from_indices(_solid(4), 256, label="b")
    assert spec.bytes_read == 4 * 1024 * 4


# -- Session ------------------------------------------------------------------


def test_session_profile_solid_vs_uniform(sess):
    solid = sess.profile(WorkloadSpec.from_indices(
        _solid(), 256, label="solid", waves_per_tile=32))
    uniform = sess.profile(WorkloadSpec.from_indices(
        _uniform(), 256, label="uniform", waves_per_tile=32))
    assert solid.per_core[0].e > uniform.per_core[0].e
    assert solid.scatter_utilization > uniform.scatter_utilization


def test_session_uses_device_bundle(tmp_path):
    device_mod._TABLE_MEMO.clear()
    dev = get_device("v5e").with_(num_cores=2)
    sess = Session(dev, cache_dir=tmp_path)
    prof = sess.profile(WorkloadSpec.from_indices(
        _solid(), 256, label="2core", waves_per_tile=32, num_cores=2))
    assert len(prof.per_core) == 2


def test_session_classify_and_speedup(sess):
    verdict = sess.classify(WorkloadSpec.from_indices(
        _solid(), 256, label="solid", waves_per_tile=32))
    assert verdict.bottleneck == "scatter"
    sp = sess.speedup(
        WorkloadSpec.from_indices(_solid(), 256, label="before",
                                  waves_per_tile=32),
        WorkloadSpec.from_indices(_uniform(), 256, label="after",
                                  waves_per_tile=32))
    assert sp > 1.0  # de-conflicted stream must be faster


def test_session_sweep_detects_shift(tmp_path):
    """Growing working set + tiny LLC + low concurrency: scatter -> hbm."""
    device_mod._TABLE_MEMO.clear()
    dev = get_device("v5e").with_(cache=CacheModel(
        llc_bytes=1 << 20, miss_latency_cycles=2000, hide_concurrency=64.0))
    sess = Session(dev, cache_dir=tmp_path)
    specs = [
        WorkloadSpec.from_indices(
            _uniform(num_waves=1 << p0, seed=p0), 256,
            label=f"2^{p0 + 10}", waves_per_tile=2,
            bytes_read=float((1 << p0) * 1024 * 4))
        for p0 in range(2, 11)]
    result = sess.sweep(specs)
    assert len(result) == 9
    assert len(result.verdicts) == 9
    assert result.bottlenecks[0] == "scatter"
    assert any(s.unit_after == "hbm" for s in result.shifts), \
        result.bottlenecks
    # sweep utilization arrays are aligned with the points
    assert result.utilization["hbm"].shape == (9,)


def test_sweep_requires_specs(sess):
    with pytest.raises(ValueError):
        sess.sweep([])


# -- reporting ----------------------------------------------------------------


def test_report_before_profile_raises(tmp_path):
    device_mod._TABLE_MEMO.clear()
    with pytest.raises(RuntimeError):
        Session("v5e", cache_dir=tmp_path).report()


def test_report_formats(sess):
    specs = [WorkloadSpec.from_indices(_solid(), 256, label="solid",
                                       waves_per_tile=32),
             WorkloadSpec.from_indices(_uniform(), 256, label="uniform",
                                       waves_per_tile=32)]
    sess.sweep(specs)

    text = sess.report()
    assert "solid" in text and "uniform" in text and "v5e" in text

    payload = json.loads(sess.report("json"))
    assert payload["device"] == "v5e"
    assert [p["label"] for p in payload["points"]] == ["solid", "uniform"]
    assert {"bottleneck", "U_scatter", "U_hbm",
            "speedup_vs_first"} <= set(payload["points"][0])

    lines = sess.report("csv").strip().splitlines()
    assert len(lines) == 3  # header + 2 points
    assert lines[0].startswith("label,")

    with pytest.raises(ValueError):
        sess.report("yaml")


def test_speedup_records_both_profiles(sess):
    """report() after speedup() must show the pair, not a stale result."""
    before = WorkloadSpec.from_indices(_solid(), 256, label="before",
                                       waves_per_tile=32)
    after = WorkloadSpec.from_indices(_uniform(), 256, label="after",
                                      waves_per_tile=32)
    sess.profile(WorkloadSpec.from_indices(_solid(4), 256, label="stale"))
    sp = sess.speedup(before, after)
    assert sp > 1.0
    assert len(sess.last) == 2
    text = sess.report()
    assert "before" in text and "after" in text and "stale" not in text
    assert float(sess.last.speedup_vs_first[1]) == sp


def test_single_point_report_has_no_sweep_lines(sess):
    sess.profile(WorkloadSpec.from_indices(_solid(), 256, label="one",
                                           waves_per_tile=32))
    text = sess.report()
    assert "one" in text
    assert "no bottleneck shifts" not in text
    assert "profile" in text and "sweep" not in text


def test_to_rows_aggregates_all_cores():
    """e/n_hat must reflect every core, not per_core[0] (satellite fix)."""
    import repro.core.profiler as prof_mod
    from repro.core import qmodel

    def core(i, e, n_hat, n_jobs=4):
        return qmodel.CoreUtilization(core_id=i, N=n_jobs, n_hat=n_hat, e=e,
                                      c=0.0, S_cycles=1.0, B_cycles=4.0,
                                      T_cycles=10.0, U=0.4)

    p = prof_mod.WorkloadProfile(
        label="multi",
        per_core=[core(0, 2.0, 8.0, n_jobs=12), core(1, 4.0, 16.0, n_jobs=4)],
        units=[prof_mod.UnitUtilization("scatter", 4.0, 10.0)],
        T_cycles=np.array([10.0, 10.0]))
    from repro.analysis.session import SweepResult
    from repro.core import bottleneck as bn
    result = SweepResult(
        device=get_device("v5e"), specs=[], profiles=[p],
        verdicts=[bn.classify(p)], shifts=[],
        utilization={"scatter": np.array([0.4])},
        speedup_vs_first=np.array([1.0]))
    row = result.to_rows()[0]
    # job-weighted mean (12*2 + 4*4)/16, matching e = O/N — neither
    # per_core[0] nor the unweighted core mean
    assert row["e"] == 2.5
    assert row["n_hat"] == 16.0  # max(8, 16), not per_core[0]


def test_render_csv_roundtrips_to_rows(sess):
    import csv as csv_mod
    import io

    sess.sweep([
        WorkloadSpec.from_indices(_solid(), 256, label="solid",
                                  waves_per_tile=32),
        WorkloadSpec.from_indices(_uniform(), 256, label="uniform",
                                  waves_per_tile=32)])
    rows = sess.last.to_rows()
    parsed = list(csv_mod.DictReader(io.StringIO(sess.report("csv"))))
    assert len(parsed) == len(rows)
    for got, want in zip(parsed, rows):
        assert set(got) == set(want)
        assert got["label"] == want["label"]
        assert got["bottleneck"] == want["bottleneck"]
        assert float(got["e"]) == pytest.approx(want["e"])
        assert float(got["n_hat"]) == pytest.approx(want["n_hat"])
        assert float(got["U_scatter"]) == pytest.approx(want["U_scatter"])


def test_render_json_schema_is_stable(sess):
    sess.sweep([WorkloadSpec.from_indices(_solid(), 256, label="s",
                                          waves_per_tile=32)])
    payload = json.loads(sess.report("json"))
    assert set(payload) == {"device", "points", "shifts"}
    assert set(payload["points"][0]) == {
        "label", "bottleneck", "saturated", "comment", "hint",
        "scatter_model_U", "speedup_vs_first", "e", "n_hat", "U_scatter",
        "U_hbm", "U_mxu", "U_ici"}


def test_render_unknown_fmt_raises(sess):
    sess.profile(WorkloadSpec.from_indices(_solid(4), 256, label="x"))
    with pytest.raises(ValueError, match="unknown report format"):
        sess.last.render("yaml")


# -- grid-sweep engine --------------------------------------------------------


def test_spec_grid_cartesian_labels():
    spec = WorkloadSpec.from_indices(_solid(4), 256, label="base")
    grid = spec.grid(waves_per_tile=[4, 8], pipeline_depth=[2, 4])
    assert len(grid) == 4
    assert grid[0].label == "base[waves_per_tile=4,pipeline_depth=2]"
    assert grid[-1].label == "base[waves_per_tile=8,pipeline_depth=4]"
    assert (grid[-1].waves_per_tile, grid[-1].pipeline_depth) == (8, 4)
    assert spec.waves_per_tile is None  # base untouched


def test_spec_grid_unknown_axis_raises():
    spec = WorkloadSpec.from_indices(_solid(4), 256, label="base")
    with pytest.raises(ValueError, match="not a WorkloadSpec field"):
        spec.grid(wpt=[4, 8])


def test_spec_fingerprint_content_keyed():
    a = WorkloadSpec.from_indices(_solid(4), 256, label="a",
                                  waves_per_tile=8)
    b = WorkloadSpec.from_indices(_solid(4), 256, label="b",
                                  waves_per_tile=8)
    c = WorkloadSpec.from_indices(_solid(4), 256, label="a",
                                  waves_per_tile=16)
    d = WorkloadSpec.from_indices(_uniform(4), 256, label="a",
                                  waves_per_tile=8)
    assert a.fingerprint() == b.fingerprint()      # label-independent
    assert a.fingerprint() != c.fingerprint()      # geometry matters
    assert a.fingerprint() != d.fingerprint()      # content matters
    assert WorkloadSpec(label="r", run=lambda: None).fingerprint() is None


# -- fingerprint: arrays hashed in place, large ones as a sha256 tree ---------

MiB = 1 << 20
CHUNK = workload_mod._TREE_CHUNK_BYTES


def _pin_histogram(img):
    return WorkloadSpec.from_histogram(img, label="pin", variant="hist2",
                                       bytes_read=256.0)


def _pin_img():
    return (np.arange(64 * 4, dtype=np.int32).reshape(64, 4) * 7) % 256


def _tobytes_fingerprint(spec):
    """The formula before arrays were hashed in place, for ``indices``
    specs: every array copied out with ``tobytes``."""
    h = hashlib.sha256()
    for part in ("indices", spec.indices, spec.num_bins, spec.job_class,
                 spec.waves_per_tile, spec.pipeline_depth, spec.num_cores,
                 spec.num_devices, spec.bytes_read, spec.flops,
                 spec.overhead_cycles):
        if isinstance(part, np.ndarray):
            arr = np.ascontiguousarray(part)
            h.update(str(arr.dtype).encode())
            h.update(str(arr.shape).encode())
            h.update(arr.tobytes())
        else:
            h.update(repr(part).encode())
        h.update(b"|")
    return h.hexdigest()


def _big(nbytes=3 * CHUNK):
    return np.arange(nbytes, dtype=np.uint32).astype(np.uint8)


@pytest.mark.parametrize("spec, digest", [
    (WorkloadSpec(label="pin", indices=np.arange(4096, dtype=np.int32) % 256),
     "36195f094902ca773548603ddbee2b151227de1b18c5f408abd835b6e55063d2"),
    (_pin_histogram(_pin_img()),
     "9e520fb0988d43511158fbceabe2c622d8f186260cb36cb56c340bae95f3d8d1"),
    (_pin_histogram(np.asfortranarray(_pin_img())),
     "9e520fb0988d43511158fbceabe2c622d8f186260cb36cb56c340bae95f3d8d1"),
], ids=["indices", "histogram", "histogram-fortran"])
def test_fingerprint_small_array_digest_pinned(spec, digest):
    assert spec.fingerprint() == digest


@pytest.mark.parametrize("arr", [
    np.arange(4096, dtype=np.int64),
    np.arange(12, dtype=np.float32).reshape(3, 4),
    np.asarray(7, np.int16),
    np.zeros(0, np.int8),
    np.arange(24, dtype=np.int32).reshape(2, 3, 4)[:, ::2, 1:],
    np.ones(MiB - 1, np.int64),        # 8 bytes under the tree's threshold
], ids=["int64", "2d", "0d", "empty", "strided", "under-8MiB"])
def test_fingerprint_flat_path_matches_tobytes_formula(arr):
    spec = WorkloadSpec(label="x", indices=arr)
    assert spec.fingerprint() == _tobytes_fingerprint(spec)


@pytest.mark.parametrize("workers", [1, 4])
def test_fingerprint_tree_independent_of_workers(monkeypatch, workers):
    pools = []

    class Pool(workload_mod.ThreadPoolExecutor):
        def __init__(self, max_workers):
            pools.append(max_workers)
            super().__init__(max_workers)

    arr = _big(3 * CHUNK + 1000)
    spec = WorkloadSpec(label="x", indices=arr)
    monkeypatch.setattr(workload_mod, "ThreadPoolExecutor", Pool)
    monkeypatch.setattr(workload_mod, "_usable_cores", lambda: workers)
    first, second = spec.fingerprint(), spec.fingerprint()
    assert pools == ([] if workers == 1 else [workers, workers])
    # the tree, worked out here: tag, chunk count, chunk digests in order
    h = hashlib.sha256()
    for part in ("indices", arr):
        if isinstance(part, np.ndarray):
            h.update(b"uint8" + str(arr.shape).encode())
            h.update(b"sha256-tree/4MiB" + b"4")
            h.update(b"".join(hashlib.sha256(arr[i:i + CHUNK]).digest()
                              for i in range(0, arr.nbytes, CHUNK)))
        else:
            h.update(repr(part).encode())
        h.update(b"|")
    for part in (spec.num_bins, spec.job_class, spec.waves_per_tile,
                 spec.pipeline_depth, spec.num_cores, spec.num_devices,
                 spec.bytes_read, spec.flops, spec.overhead_cycles):
        h.update(repr(part).encode() + b"|")
    assert first == second == h.hexdigest()
    assert first != _tobytes_fingerprint(spec)


@pytest.mark.parametrize("nbytes, at", [
    (3 * CHUNK, 0),                      # first chunk
    (3 * CHUNK, CHUNK + CHUNK // 2),     # a middle chunk
    (3 * CHUNK, 3 * CHUNK - 1),          # the last chunk
    (3 * CHUNK + 1000, 3 * CHUNK + 999),  # a short final chunk
], ids=["first", "middle", "last", "short-final"])
def test_fingerprint_tree_sees_every_byte(nbytes, at):
    arr = _big(nbytes)
    before = WorkloadSpec(label="x", indices=arr).fingerprint()
    arr[at] ^= 1
    assert WorkloadSpec(label="x", indices=arr).fingerprint() != before


@pytest.mark.parametrize("nbytes", [4096, 3 * CHUNK], ids=["flat", "tree"])
@pytest.mark.parametrize("other", [
    lambda a: a.view(np.int32),
    lambda a: a.reshape(2, -1),
], ids=["dtype", "shape"])
def test_fingerprint_frames_dtype_and_shape(nbytes, other):
    arr = _big(nbytes)
    fp = WorkloadSpec(label="x", indices=arr).fingerprint()
    assert WorkloadSpec(label="x", indices=other(arr)).fingerprint() != fp


@pytest.mark.parametrize("rows", [64, 3 * CHUNK // 16], ids=["flat", "tree"])
@pytest.mark.parametrize("layout", [
    np.asfortranarray,
    lambda a: np.concatenate([a, a], axis=1)[:, ::2],
], ids=["fortran", "strided"])
def test_fingerprint_layout_hashes_as_c_copy(rows, layout):
    img = _big(rows * 4 * 4).view(np.int32).reshape(rows, 4)
    view = layout(img)
    assert not view.flags.c_contiguous
    assert (_pin_histogram(view).fingerprint()
            == _pin_histogram(np.ascontiguousarray(view)).fingerprint())


def test_fingerprint_counts_bytes_by_path():
    hashed = workload_mod._telemetry.counter(
        "repro_fingerprint_bytes_total", "", ("path",))
    small, big = np.zeros(1000, np.int32), _big(3 * CHUNK)
    before = {p: hashed.value(path=p) for p in ("flat", "chunked")}
    WorkloadSpec(label="s", indices=small).fingerprint()
    assert hashed.value(path="flat") == before["flat"] + small.nbytes
    assert hashed.value(path="chunked") == before["chunked"]
    WorkloadSpec(label="b", indices=big).fingerprint()
    assert hashed.value(path="flat") == before["flat"] + small.nbytes
    assert hashed.value(path="chunked") == before["chunked"] + big.nbytes


def test_sweep_parallel_matches_serial(sess):
    specs = WorkloadSpec.from_indices(
        _uniform(), 256, label="u").grid(waves_per_tile=[2, 4, 8, 16, 32],
                                         pipeline_depth=[2, 4])
    serial = Session("v5e", table=sess.table).sweep(specs)
    parallel = Session("v5e", table=sess.table).sweep(specs, parallel=8)
    assert len(parallel) == 10
    assert [p.label for p in parallel.profiles] == \
        [p.label for p in serial.profiles]          # order preserved
    np.testing.assert_array_equal(parallel.speedup_vs_first,
                                  serial.speedup_vs_first)
    for a, b in zip(serial.profiles, parallel.profiles):
        assert a.scatter_utilization == b.scatter_utilization
        np.testing.assert_array_equal(a.T_cycles, b.T_cycles)


def test_sweep_memoizes_by_content(sess):
    """Repeated points are collected once and served relabeled."""
    calls = []
    inner = sess.provider

    class Counting:
        name = "counting"

        def collect(self, spec, device):
            calls.append(spec.label)
            return inner.collect(spec, device)

    sess.provider = Counting()
    spec = WorkloadSpec.from_indices(_uniform(), 256, label="a",
                                     waves_per_tile=8)
    sess.sweep([spec, spec.with_(label="b")])
    assert calls == ["a"]                       # second point: cache hit
    assert [p.label for p in sess.last.profiles] == ["a", "b"]
    sess.sweep([spec.with_(label="c")])
    assert calls == ["a"]                       # re-run: still cached
    sess.sweep([spec.with_(waves_per_tile=16, label="d")])
    assert calls == ["a", "d"]                  # new content: collected


def test_sweep_grid_per_device(tmp_path):
    from repro.analysis import sweep_grid
    device_mod._TABLE_MEMO.clear()
    base = WorkloadSpec.from_indices(_uniform(), 256, label="u")
    results = sweep_grid(base, {"waves_per_tile": [4, 32]},
                         devices=("v5e", "v5p"), parallel=2,
                         cache_dir=tmp_path)
    assert list(results) == ["v5e", "v5p"]
    for res in results.values():
        assert len(res) == 2
        assert res.profiles[0].label == "u[waves_per_tile=4]"


def test_render_csv_ragged_union_columns():
    """Rows with later-only U_* columns must render, empty-filled (fix)."""
    import csv as csv_mod
    import io

    import repro.core.profiler as prof_mod
    from repro.analysis.session import SweepResult
    from repro.core import bottleneck as bn

    def prof(label, units):
        return prof_mod.WorkloadProfile(
            label=label, per_core=[],
            units=[prof_mod.UnitUtilization(n, b, 1000.0)
                   for n, b in units.items()],
            T_cycles=np.array([1000.0]))

    profiles = [prof("a", {"scatter": 500.0}),
                prof("b", {"scatter": 100.0, "ici": 700.0})]
    result = SweepResult(
        device=get_device("v5e"), specs=[], profiles=profiles,
        verdicts=[bn.classify(p) for p in profiles], shifts=[],
        utilization={}, speedup_vs_first=np.array([1.0, 1.0]))
    text = result.render("csv")
    rows = list(csv_mod.DictReader(io.StringIO(text)))
    assert "U_ici" in rows[0]
    assert rows[0]["U_ici"] == ""           # missing cell: empty, not crash
    assert float(rows[1]["U_ici"]) == 0.7


# -- deprecation shims --------------------------------------------------------


def test_old_core_imports_still_resolve():
    from repro.core import (  # noqa: F401
        CacheModel,
        ServiceTimeTable,
        WaveTrace,
        build_table,
        classify,
        detect_shifts,
        profile_scatter_workload,
        trace_from_indices,
    )


def test_core_namespace_forwards_session_with_warning():
    import repro.core as core
    with pytest.warns(DeprecationWarning, match="repro.analysis"):
        assert core.Session is Session
    with pytest.warns(DeprecationWarning):
        assert core.Device is Device
    with pytest.raises(AttributeError):
        core.not_a_real_name


# -- HLO provider meta surfaces in reports (unresolved loops, collectives) ----

_META_HLO = """\
HloModule meta_demo

cond {
  p = (s32[], s32[]) parameter(0)
  i = s32[] get-tuple-element(p), index=0
  n = s32[] get-tuple-element(p), index=1
  ROOT lt = pred[] compare(i, n), direction=LT
}

body {
  p = (s32[], s32[]) parameter(0)
  i = s32[] get-tuple-element(p), index=0
  n = s32[] get-tuple-element(p), index=1
  one = s32[] constant(1)
  i2 = s32[] add(i, one)
  ROOT t = (s32[], s32[]) tuple(i2, n)
}

ENTRY main {
  a = s32[] parameter(0)
  n = s32[] parameter(1)
  x = f32[8,8]{1,0} parameter(2)
  ar = f32[8,8]{1,0} all-reduce(x), replica_groups=[2,4]<=[8], to_apply=body
  t0 = (s32[], s32[]) tuple(a, n)
  ROOT w = (s32[], s32[]) while(t0), condition=cond, body=body
}
"""


def test_report_surfaces_hlo_meta_footers(tmp_path):
    """A dynamically-bounded while + an all-reduce: the provider's meta
    (unresolved_loops, collectives) must reach the text footer and the
    json payload of Session.report."""
    sess = Session("v5e", provider="hlo", cache_dir=tmp_path)
    spec = WorkloadSpec.from_compiled(hlo_text=_META_HLO, label="meta-demo",
                                      num_devices=8)
    sess.profile(spec)
    text = sess.report("text")
    assert "hlo meta [meta-demo]:" in text
    assert "unresolved loop trip count" in text
    assert "lower bounds" in text
    assert "collective op(s)" in text

    payload = json.loads(sess.report("json"))
    meta = payload["meta"]["meta-demo"]
    assert meta["unresolved_loops"] >= 1
    assert "all-reduce" in meta["collectives"]


def test_report_no_meta_footer_for_trace_sources(sess):
    spec = WorkloadSpec.from_indices(_uniform(), 256, label="plain")
    sess.profile(spec)
    assert "hlo meta" not in sess.report("text")
    assert "meta" not in json.loads(sess.report("json"))
