"""Columnar batch profiler: CounterFrame, profile_batch, sweep cache.

The acceptance contract of PR 4: for a >= 64-point grid, the batch path
(``CounterFrame`` + ``profiler.profile_batch``) must agree with the
scalar per-point path (``profiler.profile_counters``) point for point —
U, n-hat, e within rtol 1e-9 (they are in fact bit-identical), and
``classify``/``detect_shifts`` outputs identical — and the persistent
sweep cache must let a fresh Session re-sweep without collecting a
single counter.
"""

import dataclasses

import numpy as np
import pytest

from repro.analysis import Session, WorkloadSpec
from repro.analysis import device as device_mod
from repro.analysis.sweep_cache import SweepCache, save_counter_set
from repro.core import bottleneck, profiler, timing
from repro.core.counters import CounterFrame, CounterSet
from repro.core.profiler import CacheModel


@pytest.fixture
def sess(tmp_path):
    device_mod._TABLE_MEMO.clear()
    return Session("v5e", cache_dir=tmp_path)


def _grid_specs(n_points=64, stream=1 << 14, seed=0):
    rng = np.random.default_rng(seed)
    base = WorkloadSpec.from_indices(
        rng.integers(0, 256, stream), 256, label="grid")
    specs = base.grid(waves_per_tile=[1, 2, 4, 8, 16, 32, 64, 128],
                      pipeline_depth=[1, 2, 4, 8],
                      overhead_cycles=[500.0, 2000.0])
    assert len(specs) >= n_points
    return specs[:n_points]


def _scalar_profiles(sess, csets, **kw):
    dev = sess.device
    return [profiler.profile_counters(
        c, sess.table, params=dev.scatter, chip=dev.chip, cache=dev.cache,
        **kw) for c in csets]


def _assert_equivalent(scalar, batch, rtol=1e-9):
    assert len(scalar) == len(batch)
    for a, b in zip(scalar, batch):
        assert a.label == b.label
        np.testing.assert_allclose(b.scatter_utilization,
                                   a.scatter_utilization, rtol=rtol)
        np.testing.assert_allclose(b.e, a.e, rtol=rtol)
        np.testing.assert_allclose(b.n_hat, a.n_hat, rtol=rtol)
        np.testing.assert_allclose(b.T_cycles, a.T_cycles, rtol=rtol)
        assert len(a.per_core) == len(b.per_core)
        for ca, cb in zip(a.per_core, b.per_core):
            for f in ("N", "n_hat", "e", "c", "S_cycles", "B_cycles",
                      "T_cycles", "U"):
                np.testing.assert_allclose(getattr(cb, f), getattr(ca, f),
                                           rtol=rtol, err_msg=f)
        assert [u.name for u in a.units] == [u.name for u in b.units]
        for ua, ub in zip(a.units, b.units):
            np.testing.assert_allclose(ub.utilization, ua.utilization,
                                       rtol=rtol)
        assert a.bottleneck == b.bottleneck
        assert bottleneck.classify(a) == bottleneck.classify(b)
        assert a.params == b.params


# -- the acceptance grid ------------------------------------------------------


def test_batch_equals_scalar_on_64_point_grid(sess):
    specs = _grid_specs()
    csets = [sess.collect(s) for s in specs]
    scalar = _scalar_profiles(sess, csets)
    batch = profiler.profile_batch(
        CounterFrame.from_sets(csets), sess.table,
        params=sess.device.scatter, chip=sess.device.chip,
        cache=sess.device.cache)
    _assert_equivalent(scalar, batch)
    # shift events from both paths are identical, tolerance included
    assert bottleneck.detect_shifts(scalar) == bottleneck.detect_shifts(batch)
    assert (bottleneck.detect_shifts(scalar, tol=0.0)
            == bottleneck.detect_shifts(batch, tol=0.0))


def test_batch_equals_scalar_use_true_n(sess):
    specs = _grid_specs(n_points=16)
    csets = [sess.collect(s) for s in specs]
    scalar = _scalar_profiles(sess, csets, use_true_n=True)
    batch = profiler.profile_batch(
        CounterFrame.from_sets(csets), sess.table,
        params=sess.device.scatter, chip=sess.device.chip,
        cache=sess.device.cache, use_true_n=True)
    _assert_equivalent(scalar, batch)


def test_batch_handles_mixed_job_classes_and_empty_points(sess):
    """POPC/CAS rows and counter-less (HLO-style) rows in one frame."""
    rng = np.random.default_rng(1)
    specs = [
        WorkloadSpec.from_indices(np.zeros(1 << 13, np.int64), 256,
                                  label="popc", job_class=timing.POPC,
                                  waves_per_tile=8),
        WorkloadSpec.from_indices(rng.integers(0, 8, 1 << 13), 256,
                                  label="cas", job_class=timing.CAS,
                                  waves_per_tile=8),
        WorkloadSpec.from_indices(rng.integers(0, 256, 1 << 13), 256,
                                  label="fao", waves_per_tile=32),
    ]
    csets = [sess.collect(s) for s in specs]
    csets.append(CounterSet(label="hlo-only", source="hlo", num_cores=8,
                            bytes_read=4e6, flops=2e10))
    scalar = _scalar_profiles(sess, csets)
    batch = profiler.profile_batch(
        CounterFrame.from_sets(csets), sess.table,
        params=sess.device.scatter, chip=sess.device.chip,
        cache=sess.device.cache)
    _assert_equivalent(scalar, batch)
    assert batch[-1].per_core == []             # counter-less point


def test_batch_empty_frame_list():
    assert profiler.profile_batch.__name__  # import sanity
    with pytest.raises(ValueError, match="at least one"):
        CounterFrame.from_sets([])


def test_session_sweep_equals_scalar_loop_with_shifts(tmp_path):
    """End-to-end Session.sweep (batch) vs scalar loop, across a real
    bottleneck shift (the PR-1 scatter->hbm sweep)."""
    device_mod._TABLE_MEMO.clear()
    dev = device_mod.get_device("v5e").with_(cache=CacheModel(
        llc_bytes=1 << 20, miss_latency_cycles=2000, hide_concurrency=64.0))
    sess = Session(dev, cache_dir=tmp_path)
    rng = np.random.default_rng(0)
    specs = [
        WorkloadSpec.from_indices(
            rng.integers(0, 256, (1 << p) * 1024), 256,
            label=f"2^{p + 10}", waves_per_tile=2,
            bytes_read=float((1 << p) * 1024 * 4))
        for p in range(2, 11)]
    result = sess.sweep(specs)
    csets = [sess.collect(s) for s in specs]
    scalar = _scalar_profiles(sess, csets)
    _assert_equivalent(scalar, result.profiles)
    assert bottleneck.detect_shifts(scalar, tol=sess.shift_tol) \
        == result.shifts
    assert any(s.unit_after == "hbm" for s in result.shifts)


def test_session_profile_single_point_matches_scalar(sess):
    spec = WorkloadSpec.from_indices(np.zeros(1 << 14, np.int64), 256,
                                     label="solid", waves_per_tile=32)
    prof = sess.profile(spec)
    [scalar] = _scalar_profiles(sess, [sess.collect(spec)])
    _assert_equivalent([scalar], [prof])


def test_session_groups_mixed_core_counts(sess):
    """A sweep mixing num_cores still profiles (grouped frames)."""
    rng = np.random.default_rng(2)
    idx = rng.integers(0, 256, 1 << 13)
    specs = [
        WorkloadSpec.from_indices(idx, 256, label="8core", num_cores=8,
                                  waves_per_tile=8),
        WorkloadSpec.from_indices(idx, 256, label="2core", num_cores=2,
                                  waves_per_tile=8),
        WorkloadSpec.from_indices(idx, 256, label="8core-b", num_cores=8,
                                  waves_per_tile=16),
    ]
    result = sess.sweep(specs)
    assert [p.label for p in result.profiles] == ["8core", "2core", "8core-b"]
    assert [len(p.per_core) for p in result.profiles] == [8, 2, 8]
    csets = [sess.collect(s) for s in specs]
    _assert_equivalent(_scalar_profiles(sess, csets), result.profiles)


# -- CounterFrame -------------------------------------------------------------


def test_counter_frame_row_round_trip(sess):
    specs = _grid_specs(n_points=4)
    csets = [sess.collect(s) for s in specs]
    frame = CounterFrame.from_sets(csets)
    assert len(frame) == 4 and frame.num_points == 4
    for i, cs in enumerate(csets):
        back = frame.row(i)
        assert back.label == cs.label and back.source == cs.source
        assert back.num_cores == cs.num_cores
        for f in ("O", "N_f", "N_c", "N_p"):
            np.testing.assert_array_equal(getattr(back, f), getattr(cs, f))
        for f in ("lanes_active", "num_waves", "waves_per_tile",
                  "pipeline_depth", "bytes_read", "flops", "ici_bytes",
                  "overhead_cycles", "wall_time_s", "meta"):
            assert getattr(back, f) == getattr(cs, f)


def test_counter_frame_rejects_ragged_cores():
    a = CounterSet(label="a", num_cores=8)
    b = CounterSet(label="b", num_cores=2)
    with pytest.raises(ValueError, match="share num_cores"):
        CounterFrame.from_sets([a, b])


def test_counter_frame_derived_columns_match_sets(sess):
    specs = _grid_specs(n_points=8)
    csets = [sess.collect(s) for s in specs]
    frame = CounterFrame.from_sets(csets)
    n_max = sess.device.scatter.n_max
    for i, cs in enumerate(csets):
        assert float(frame.total_jobs[i]) == cs.total_jobs
        assert float(frame.total_O[i]) == cs.total_O
        np.testing.assert_allclose(float(frame.e[i]), cs.e, rtol=1e-12)
        assert float(frame.occupancy(n_max)[i]) == cs.occupancy(n_max)
        assert float(frame.true_n(n_max)[i]) == cs.true_n(n_max)


# -- persistent sweep cache ---------------------------------------------------


def test_sweep_cache_round_trip(tmp_path, sess):
    cache = SweepCache(tmp_path / "cache")
    cset = sess.collect(WorkloadSpec.from_indices(
        np.zeros(1 << 13, np.int64), 256, label="solid", waves_per_tile=8))
    key = cache.key("trace", "fp", sess.device.table_key())
    assert cache.get(key) is None
    cache.put(key, cset)
    back = cache.get(key)
    assert back is not None
    assert back.label == cset.label and back.source == cset.source
    for f in ("O", "N_f", "N_c", "N_p"):
        np.testing.assert_array_equal(getattr(back, f), getattr(cset, f))
    assert back.wall_time_s is None             # None survives, not 0.0
    assert back.meta == cset.meta
    assert len(cache) == 1
    assert cache.clear() == 1 and len(cache) == 0


def test_sweep_cache_wall_time_round_trip(tmp_path):
    cache = SweepCache(tmp_path)
    cset = CounterSet(label="timed", num_cores=2, wall_time_s=1.25,
                      meta={"k": "v"})
    cache.put("k1", cset)
    back = cache.get("k1")
    assert back.wall_time_s == 1.25 and back.meta == {"k": "v"}


def test_sweep_cache_corrupt_entry_is_a_miss(tmp_path):
    cache = SweepCache(tmp_path)
    cache.put("bad", CounterSet(label="x", num_cores=1))
    cache.path("bad").write_bytes(b"not an npz")
    assert cache.get("bad") is None


def test_warm_session_skips_collection(tmp_path, sess):
    """A fresh Session over a populated cache collects nothing and
    reproduces the cold sweep bit for bit."""
    root = tmp_path / "cache"
    specs = _grid_specs(n_points=8)
    cold = Session("v5e", table=sess.table, persistent_cache=root)
    r_cold = cold.sweep(specs, parallel=2)
    assert cold.stats["collected"] == len(specs)
    warm = Session("v5e", table=sess.table, persistent_cache=root)
    r_warm = warm.sweep(specs, parallel=2)
    assert warm.stats["collected"] == 0
    assert warm.stats["disk_hits"] == len(specs)
    for a, b in zip(r_cold.profiles, r_warm.profiles):
        assert a.label == b.label
        assert a.scatter_utilization == b.scatter_utilization
        np.testing.assert_array_equal(a.T_cycles, b.T_cycles)
    assert r_cold.shifts == r_warm.shifts
    assert [v.bottleneck for v in r_cold.verdicts] \
        == [v.bottleneck for v in r_warm.verdicts]


def test_cache_key_tracks_provider_fingerprint_and_device(tmp_path):
    cache = SweepCache(tmp_path)
    base = cache.key("trace", "fp1", "v5e-key")
    assert cache.key("kernel", "fp1", "v5e-key") != base
    assert cache.key("trace", "fp2", "v5e-key") != base
    assert cache.key("trace", "fp1", "v5p-key") != base
    assert cache.key("trace", "fp1", "v5e-key") == base


def test_kernel_cache_entry_from_cpu_is_not_a_chip_hit(tmp_path, sess,
                                                     monkeypatch):
    """Kernel-provider keys carry platform, device kind and interpret
    mode: counters an interpreted CPU run cached are never served as a
    disk hit to a run on the chip (whose kernels must then launch)."""
    from repro import kernels
    spec = WorkloadSpec.from_indices(
        np.random.default_rng(0).integers(0, 64, 2048), 64, label="ids")
    cold = Session("v5e", provider="kernel", cache_dir=tmp_path,
                   persistent_cache=tmp_path / "cache")
    cset = cold.collect_cached(spec)
    assert (cset.meta["platform"], cset.meta["interpret"]) == ("cpu", True)
    warm = Session("v5e", provider="kernel", cache_dir=tmp_path,
                   persistent_cache=tmp_path / "cache")
    warm.collect_cached(spec)
    assert warm.stats["disk_hits"] == 1          # same machine: a hit
    monkeypatch.setattr(kernels, "backend_info", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite",
        "interpret": False})
    chip = Session("v5e", provider="kernel", cache_dir=tmp_path,
                   persistent_cache=tmp_path / "cache")
    got = chip.collect_cached(spec)
    assert chip.stats["disk_hits"] == 0 and chip.stats["collected"] == 1
    assert got.meta["device_kind"] == "TPU v5 lite"
    key = SweepCache(tmp_path).key
    assert key("kernel", "fp1", "v5e-key", "cpu|cpu|interpret=True") \
        != key("kernel", "fp1", "v5e-key", "tpu|TPU v5 lite|interpret=False")


def test_cache_key_tracks_collection_implementation(tmp_path, monkeypatch):
    """Changing the counter-producing code must invalidate old entries:
    the key folds in a digest of the collection source files."""
    from repro.analysis import sweep_cache as sc
    cache = SweepCache(tmp_path)
    digest = sc._collection_code_digest()
    assert digest and digest == sc._collection_code_digest()  # stable
    base = cache.key("trace", "fp1", "v5e-key")
    monkeypatch.setattr(sc, "_collection_code_digest", lambda: "deadbeef")
    assert cache.key("trace", "fp1", "v5e-key") != base


def test_unfingerprintable_specs_bypass_cache(tmp_path, sess):
    from repro.core import counters as counters_mod
    tr = counters_mod.trace_from_indices(np.zeros(2048, np.int64), 256)
    spec = WorkloadSpec(label="opaque", run=lambda: tr)
    s = Session("v5e", table=sess.table, persistent_cache=tmp_path / "c")
    s.profile(spec)
    s.profile(spec)
    assert s.stats["collected"] == 2            # collected twice, never cached
    assert len(s.sweep_cache) == 0


def test_save_counter_set_atomic_leaves_no_tmp(tmp_path):
    path = tmp_path / "entry.npz"
    save_counter_set(CounterSet(label="a", num_cores=4), path)
    assert path.exists()
    assert list(tmp_path.glob("*.tmp")) == []


def test_session_rejects_nothing_and_keeps_memo_priority(tmp_path, sess):
    """Memo hits never touch the disk cache (stats prove the order)."""
    spec = WorkloadSpec.from_indices(np.zeros(1 << 12, np.int64), 256,
                                     label="m", waves_per_tile=4)
    s = Session("v5e", table=sess.table, persistent_cache=tmp_path / "c")
    s.profile(spec)
    s.profile(spec.with_(label="m2"))
    assert s.stats == {"collected": 1, "memo_hits": 1, "disk_hits": 0,
                       "batch_calls": 1}


def test_single_pass_profile_counters_matches_dataclass_fields(sess):
    """Satellite: the de-duplicated profile_counters still reports a
    consistent U = B / T against the modeled window."""
    cset = sess.collect(WorkloadSpec.from_indices(
        np.zeros(1 << 14, np.int64), 256, label="solid", waves_per_tile=32))
    prof = profiler.profile_counters(cset, sess.table,
                                     params=sess.device.scatter,
                                     chip=sess.device.chip,
                                     cache=sess.device.cache)
    for i, row in enumerate(prof.per_core):
        assert row.T_cycles == float(prof.T_cycles[i])
        np.testing.assert_allclose(row.U, row.B_cycles / row.T_cycles,
                                   rtol=1e-12)
        assert dataclasses.asdict(row)  # rows stay plain dataclasses
