"""The provider layer: registry, the four backends, Session.validate.

The load-bearing claims: (a) TraceProvider and InstrumentedKernelProvider
agree *bit-for-bit* on the serialization counters (the instrumentation
docstring's promise, now enforced at the acquisition API), and (b)
``Session.validate`` reports zero relative error on the paper's histogram
case study — the §5 model-vs-measured validation.
"""

import numpy as np
import pytest

from repro.analysis import (
    CounterSet,
    Session,
    WorkloadSpec,
    get_device,
    get_provider,
    register_provider,
)
from repro.analysis import device as device_mod
from repro.analysis.providers import PROVIDERS
from repro.core import counters, profiler

jnp = pytest.importorskip("jax.numpy")


@pytest.fixture
def sess(tmp_path):
    device_mod._TABLE_MEMO.clear()
    return Session("v5e", cache_dir=tmp_path)


def _uniform_indices(num_waves=8, num_bins=256, seed=0):
    # length a multiple of the scatter kernel tile (2048) so the trace
    # and kernel providers see identical wave counts
    rng = np.random.default_rng(seed)
    return rng.integers(0, num_bins, num_waves * 1024)


def _image(kind="uniform", n=2048):
    from repro.data.images import make_image
    return jnp.asarray(make_image(kind, n))


# -- registry -----------------------------------------------------------------


def test_registry_contains_the_four_shipped_providers():
    assert {"trace", "kernel", "hlo", "microbench"} <= set(PROVIDERS)


def test_get_provider_by_name_and_passthrough():
    p = get_provider("trace")
    assert p.name == "trace"
    assert get_provider(p) is p


def test_get_provider_unknown_lists_registry():
    with pytest.raises(KeyError, match="trace"):
        get_provider("nvml")


def test_get_provider_rejects_non_provider():
    with pytest.raises(TypeError):
        get_provider(42)


def test_register_custom_provider(sess):
    class Fixed:
        name = "fixed"

        def collect(self, spec, device):
            return CounterSet(label=spec.label, source=self.name,
                              num_cores=1, O=np.array([8.0]),
                              N_f=np.array([4.0]), num_waves=4,
                              waves_per_tile=4)

    register_provider(Fixed())
    try:
        cset = sess.collect(WorkloadSpec.from_indices(
            _uniform_indices(), 256, label="x"), provider="fixed")
        assert cset.source == "fixed" and cset.e == 2.0
    finally:
        del PROVIDERS["fixed"]


# -- trace vs kernel equivalence (the instrumentation promise) ----------------


def test_indices_providers_agree_bit_for_bit(sess):
    spec = WorkloadSpec.from_indices(_uniform_indices(), 256, label="idx",
                                     waves_per_tile=4)
    ct = sess.collect(spec, provider="trace")
    ck = sess.collect(spec, provider="kernel")
    assert ct.source == "trace" and ck.source == "kernel"
    np.testing.assert_array_equal(ct.O, ck.O)
    np.testing.assert_array_equal(ct.N, ck.N)
    assert ct.e == ck.e
    assert ct.num_waves == ck.num_waves


@pytest.mark.parametrize("kind,variant,pixels", [
    ("uniform", "hist", 2048),
    ("solid", "hist", 2048),
    ("solid", "hist2", 3000),   # padding + channel-rotation path
])
def test_histogram_providers_agree_bit_for_bit(sess, kind, variant, pixels):
    spec = WorkloadSpec.from_histogram(
        _image(kind, pixels), label=f"{kind}/{variant}", variant=variant,
        force_fao=True)
    ct = sess.collect(spec, provider="trace")
    ck = sess.collect(spec, provider="kernel")
    np.testing.assert_array_equal(ct.O, ck.O)
    np.testing.assert_array_equal(ct.N, ck.N)
    assert ct.e == ck.e


def test_kernel_provider_rejects_non_tile_multiple_indices(sess):
    """Sentinel-padded waves would be counted: refuse, don't diverge."""
    spec = WorkloadSpec.from_indices(
        np.random.default_rng(0).integers(0, 256, 1000), 256, label="odd")
    assert sess.collect(spec, provider="trace").total_jobs == 1  # trace ok
    with pytest.raises(ValueError, match="multiple of the scatter tile"):
        sess.collect(spec, provider="kernel")


def test_kernel_provider_records_where_it_ran(sess):
    spec = WorkloadSpec.from_indices(_uniform_indices(2), 256, label="m")
    meta = sess.collect(spec, provider="kernel").meta
    assert meta["platform"] == "cpu" and meta["interpret"] is True
    assert meta["device_kind"]


@pytest.mark.parametrize("kind,match", [
    ("TPU v5p", "session models 'v5e'"),       # registered, not modeled
    ("TPU v99", "no registered Device"),       # unknown: no rates assumed
])
def test_kernel_provider_checks_the_attached_chip(sess, monkeypatch, kind,
                                                  match):
    from repro import kernels
    monkeypatch.setattr(kernels, "backend_info", lambda: {
        "platform": "tpu", "device_kind": kind, "interpret": False})
    spec = WorkloadSpec.from_indices(_uniform_indices(2), 256, label="c")
    with pytest.raises(RuntimeError, match=match):
        sess.collect(spec, provider="kernel")


def test_kernel_provider_rejects_bare_trace(sess):
    tr = counters.trace_from_indices(_uniform_indices(2), 256)
    spec = WorkloadSpec.from_trace(tr, label="pre-recorded")
    with pytest.raises(ValueError, match="runnable"):
        sess.collect(spec, provider="kernel")


def test_trace_provider_synthesizes_without_kernel_run(sess, monkeypatch):
    """The 'trace' path must not launch Pallas for a histogram spec."""
    from repro.kernels.histogram import ops as hist_ops

    def boom(*a, **k):
        raise AssertionError("trace provider launched the kernel")

    monkeypatch.setattr(hist_ops, "histogram_instrumented", boom)
    spec = WorkloadSpec.from_histogram(_image(), label="synth")
    cset = sess.collect(spec, provider="trace")
    assert cset.total_jobs > 0


# -- end-to-end sessions ------------------------------------------------------


def test_session_kernel_provider_classify_end_to_end(tmp_path):
    """ISSUE acceptance: kernel-provider classify on the histogram case."""
    device_mod._TABLE_MEMO.clear()
    sess = Session(device="v5e", provider="kernel", cache_dir=tmp_path)
    spec = WorkloadSpec.from_histogram(_image("solid", 1 << 15),
                                       label="solid 32Kpx",
                                       force_fao=True, waves_per_tile=32)
    verdict = sess.classify(spec)
    assert verdict.bottleneck == "scatter"
    assert sess.last.profiles[0].params["source"] == "kernel"


def test_validate_histogram_zero_rel_err(sess):
    """ISSUE acceptance: trace-vs-kernel e relative error == 0 (paper §5)."""
    spec = WorkloadSpec.from_histogram(_image("solid", 1 << 14),
                                       label="solid 16Kpx", force_fao=True,
                                       waves_per_tile=32)
    report = sess.validate(spec, providers=("trace", "kernel"))
    assert report.reference == "trace"
    assert report.rel_err("kernel", "e") == 0.0
    assert report.max_rel_err == 0.0
    text = report.render()
    assert "validation" in text and "kernel" in text
    payload = report.to_dict()
    assert payload["comparisons"][1]["provider"] == "kernel"
    with pytest.raises(ValueError):
        report.render("csv")


def test_validate_json_stays_valid_with_zero_reference(sess):
    """An HLO reference has N=O=0; inf rel-errs must not poison the JSON."""
    import jax
    import json

    f = jax.jit(lambda a: (a * a).sum())
    a = jnp.ones((64, 64), jnp.float32)
    hlo_spec = WorkloadSpec.from_compiled(f.lower(a).compile(), label="step")

    class HloThenTrace:
        """Adapter: one spec per provider, exercising a 0-counter reference."""
        def __init__(self, name, inner_spec):
            self.name, self._spec = name, inner_spec

        def collect(self, spec, device):
            return get_provider(self.name).collect(self._spec, device)

    trace_spec = WorkloadSpec.from_indices(_uniform_indices(2), 256,
                                           label="step")
    report = sess.validate(trace_spec, providers=(
        HloThenTrace("hlo", hlo_spec), HloThenTrace("trace", trace_spec)))
    assert report.rel_err("trace", "N") == float("inf")
    payload = json.loads(report.render("json"))   # must parse strictly
    assert payload["comparisons"][1]["rel_err"]["N"] is None


def test_validate_needs_two_providers(sess):
    spec = WorkloadSpec.from_indices(_uniform_indices(), 256, label="x")
    with pytest.raises(ValueError, match="two providers"):
        sess.validate(spec, providers=("trace",))


# -- microbench provider ------------------------------------------------------


def test_microbench_provider_fills_wall_time(sess):
    spec = WorkloadSpec.from_indices(_uniform_indices(), 256, label="mb",
                                     waves_per_tile=4)
    cset = sess.collect(spec, provider="microbench")
    assert cset.source == "microbench"
    assert cset.wall_time_s is not None and cset.wall_time_s > 0
    # counters themselves match the trace path (only the clock is added)
    ct = sess.collect(spec, provider="trace")
    assert cset.e == ct.e and cset.total_jobs == ct.total_jobs
    prof = Session("v5e", provider="microbench",
                   table=sess.table).profile(spec)
    assert prof.params["wall_time_s"] == cset.wall_time_s


# -- hlo provider -------------------------------------------------------------


def test_hlo_provider_from_compiled(sess):
    import jax

    f = jax.jit(lambda a, b: (a @ b).sum())
    a = jnp.ones((128, 128), jnp.float32)
    compiled = f.lower(a, a).compile()
    spec = WorkloadSpec.from_compiled(compiled, label="matmul")
    cset = sess.collect(spec, provider="hlo")
    assert cset.source == "hlo"
    assert cset.flops >= 2 * 128 ** 3   # one 128^3 matmul at least
    assert cset.bytes_read > 0
    assert cset.total_jobs == 0         # no scatter visibility from HLO
    prof = profiler.profile_counters(cset, sess.table)
    assert prof.per_core == []
    assert prof.bottleneck in ("hbm", "mxu")


def test_hlo_counter_set_gets_no_cache_exposure(sess):
    """The LLC-exposure heuristic reads launch geometry HLO doesn't have."""
    big = CounterSet(label="step", source="hlo", num_cores=1,
                     bytes_read=64 * 1024 ** 2)   # >> llc_bytes
    prof = profiler.profile_counters(big, sess.table)
    chip = get_device("v5e").chip
    ideal = big.bytes_read / (chip.hbm_bw / chip.clock_hz)
    assert prof.unit("hbm").busy_cycles == ideal   # no exposure term


def test_hlo_profiles_have_structural_unit_set(sess):
    """Mixed sweeps (some points with collectives, some without) must not
    crash: the unit list is a function of the source kind, not values."""
    with_ici = CounterSet(label="a", source="hlo", num_cores=1,
                          bytes_read=1024.0, flops=1024.0, ici_bytes=512.0)
    without = CounterSet(label="b", source="hlo", num_cores=1,
                         bytes_read=1024.0, flops=1024.0)
    profs = [profiler.profile_counters(c, sess.table)
             for c in (with_ici, without)]
    assert [u.name for u in profs[0].units] == \
        [u.name for u in profs[1].units]
    for order in (profs, profs[::-1]):
        sweep = profiler.utilization_sweep(order)
        assert sweep["ici"].shape == (2,)


def test_hlo_provider_from_text(sess):
    import jax

    f = jax.jit(lambda a, b: (a @ b).sum())
    a = jnp.ones((64, 64), jnp.float32)
    text = f.lower(a, a).compile().as_text()
    spec = WorkloadSpec.from_compiled(hlo_text=text, label="matmul-text")
    cset = sess.collect(spec, provider="hlo")
    assert cset.flops >= 2 * 64 ** 3
    assert cset.bytes_read > 0


def test_hlo_provider_honors_roofline_overrides(sess):
    import jax

    f = jax.jit(lambda a: (a * a).sum())
    a = jnp.ones((64, 64), jnp.float32)
    compiled = f.lower(a).compile()
    spec = WorkloadSpec.from_compiled(compiled, label="override",
                                      bytes_read=1e9, flops=5e12)
    cset = sess.collect(spec, provider="hlo")
    assert cset.bytes_read == 1e9 and cset.flops == 5e12


def test_hlo_provider_requires_compiled_source(sess):
    spec = WorkloadSpec.from_indices(_uniform_indices(2), 256, label="x")
    with pytest.raises(ValueError, match="compiled"):
        sess.collect(spec, provider="hlo")
    with pytest.raises(ValueError, match="hlo"):
        spec.with_(indices=None,
                   hlo_text="HloModule m").resolve_trace()


def test_scatter_add_providers_agree_bit_for_bit(sess):
    ids = _uniform_indices(num_waves=4, num_bins=128, seed=3)
    vals = np.ones((ids.size, 1), np.float32)
    spec = WorkloadSpec.from_scatter_add(ids, vals, 128, label="scat",
                                         waves_per_tile=2)
    ct = sess.collect(spec, provider="trace")
    ck = sess.collect(spec, provider="kernel")
    np.testing.assert_array_equal(ct.O, ck.O)
    np.testing.assert_array_equal(ct.N, ck.N)
    assert ct.e == ck.e


def test_weighted_histogram_maps_to_cas_class(sess):
    spec = WorkloadSpec.from_histogram(_image(), label="w", weighted=True)
    for provider in ("trace", "kernel"):
        cset = sess.collect(spec, provider=provider)
        assert np.sum(cset.N_c) == cset.total_jobs   # all CAS-class
        assert np.sum(cset.N_f) == np.sum(cset.N_p) == 0


def test_unweighted_unforced_histogram_maps_to_popc_class(sess):
    spec = WorkloadSpec.from_histogram(_image(), label="p", force_fao=False)
    cset = sess.collect(spec, provider="trace")
    assert np.sum(cset.N_p) == cset.total_jobs


@pytest.mark.parametrize("entry", [
    "trace_collect", "trace_collect_batch", "kernel_collect",
    "resolve_trace", "committed_stream", "heatmap_for_spec"])
def test_unknown_kernel_op_raises(sess, entry):
    """Every entry point reaches the spec's one op switch."""
    from repro.analysis import KernelSource
    from repro.obs import heatmap_for_spec
    spec = WorkloadSpec(label="bad", kernel=KernelSource(op="fft"))
    call = {
        "trace_collect": lambda: sess.collect(spec, provider="trace"),
        "trace_collect_batch": lambda: get_provider("trace").collect_batch(
            [spec], sess.device),
        "kernel_collect": lambda: sess.collect(spec, provider="kernel"),
        "resolve_trace": spec.resolve_trace,
        "committed_stream": spec.committed_stream,
        "heatmap_for_spec": lambda: heatmap_for_spec(spec),
    }[entry]
    with pytest.raises(ValueError, match="unknown kernel op"):
        call()


def test_spec_rejects_compiled_plus_trace_source():
    tr = counters.trace_from_indices(_uniform_indices(2), 256)
    with pytest.raises(ValueError, match="exactly one"):
        WorkloadSpec(label="both", trace=tr, hlo_text="HloModule m")


def test_validate_accepts_provider_instances(sess):
    from repro.analysis import InstrumentedKernelProvider, TraceProvider
    spec = WorkloadSpec.from_indices(_uniform_indices(), 256, label="inst",
                                     waves_per_tile=4)
    report = sess.validate(
        spec, providers=(TraceProvider(), InstrumentedKernelProvider()))
    assert report.max_rel_err == 0.0


def test_session_profile_params_record_source(sess):
    spec = WorkloadSpec.from_indices(_uniform_indices(2), 256, label="src")
    prof = sess.profile(spec)
    assert prof.params["source"] == "trace"
    assert prof.params["wall_time_s"] is None


def test_microbench_provider_on_histogram_spec(sess):
    spec = WorkloadSpec.from_histogram(_image(), label="mb-hist",
                                       force_fao=True)
    cset = sess.collect(spec, provider="microbench")
    assert cset.wall_time_s is not None and cset.wall_time_s > 0
    assert cset.e == sess.collect(spec, provider="trace").e


# -- CounterSet ---------------------------------------------------------------


def test_counter_set_empty_defaults():
    cset = CounterSet(label="empty", num_cores=2)
    assert cset.total_jobs == 0 and cset.total_O == 0
    assert cset.e == 1.0
    assert cset.O.shape == (2,)


def test_geometry_helpers_match_wave_trace_methods():
    tr = counters.trace_from_indices(_uniform_indices(6), 256,
                                     waves_per_tile=2, pipeline_depth=3)
    for n_max in (4, 64):
        assert tr.occupancy(n_max) == counters.geometry_occupancy(
            tr.num_waves, tr.waves_per_tile, tr.pipeline_depth, n_max)
        assert tr.true_n(n_max) == counters.geometry_true_n(
            tr.num_waves, tr.waves_per_tile, tr.pipeline_depth, n_max)


def test_counter_set_from_trace_matches_basic_counters():
    tr = counters.trace_from_indices(_uniform_indices(4), 256, num_cores=4,
                                     waves_per_tile=2)
    cset = CounterSet.from_trace(tr, label="t", num_cores=4)
    basic = counters.collect_basic_counters(
        tr, num_cores=4, T_cycles_per_core=np.ones(4))
    for core, bc in enumerate(basic):
        assert cset.O[core] == bc.O
        assert cset.N_f[core] == bc.N_f
        assert cset.N_c[core] == bc.N_c
        assert cset.N_p[core] == bc.N_p
    got = cset.to_basic_counters(np.ones(4), 64)
    assert [b.occupancy for b in got] == [b.occupancy for b in basic]
    assert [b.n_true for b in got] == [b.n_true for b in basic]


def test_profile_counters_matches_legacy_trace_path(sess):
    """The legacy entry must be a pure delegation (same numbers out)."""
    tr = counters.trace_from_indices(_uniform_indices(), 256, num_cores=8,
                                     waves_per_tile=4)
    legacy = profiler.profile_scatter_workload(
        tr, sess.table, label="x", bytes_read=1 << 20, num_cores=8,
        overhead_cycles=500.0)
    cset = CounterSet.from_trace(tr, label="x", num_cores=8,
                                 bytes_read=float(1 << 20),
                                 overhead_cycles=500.0)
    new = profiler.profile_counters(cset, sess.table)
    np.testing.assert_array_equal(legacy.T_cycles, new.T_cycles)
    assert legacy.scatter_utilization == new.scatter_utilization
    assert [u.utilization for u in legacy.units] == \
        [u.utilization for u in new.units]
