"""Fingerprints of device-resident specs are computed on the device.

``WorkloadSpec.fingerprint`` digests a ``jax.Array`` kernel parameter where
it lies (``repro.kernels.digest``) and copies only the digest back, by an
explicit ``jax.device_get``: an implicit copy of the array itself (what
``np.asarray`` would make) is refused under
``jax.transfer_guard_device_to_host("disallow")`` on an accelerator.  On
the CPU a jax array shares the host's memory and no guard fires, so the
test also hands the fingerprint a numpy that refuses any jax array.
Equal content gives
equal fingerprints, whichever array object holds it, so the session's memo
still finds a rebuilt request; one changed element, a reshape or a cast
gives another.  Host-array fingerprints are unchanged: the literals below
are the digests of a moe-skewed-sized scatter spec before device digests
existed.  A ``jax.Array``'s digest is kept while the array lives, so a
batch shared by four layers' specs goes to the device once; kept or not,
the fingerprints are the pinned ones.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import WorkloadSpec
from repro.kernels import digest
from repro.models import moe
from repro.obs import telemetry

CFG = moe.MoEConfig(d_model=64, d_expert=16, num_experts=32, top_k=4,
                    scoring="sigmoid", n_group=4, topk_group=2,
                    routed_scaling_factor=2.5)


def _router(seed=0):
    r = moe.init_router(jax.random.key(seed), CFG)
    r["bias"] = 0.02 * jax.random.normal(jax.random.key(seed + 1), (32,))
    return r


def _hidden(seed=3, t=256):
    return jax.random.normal(jax.random.key(seed), (t, 64)).astype(
        jnp.bfloat16)


def _spec(hidden, router=None, **kw):
    return WorkloadSpec.from_moe_router({"router": router or _router()},
                                        hidden, CFG, label="d", layer=3, **kw)


class _HostOnlyNumpy:
    """numpy, except that no function of it may be handed a jax array."""

    def __getattr__(self, name):
        f = getattr(np, name)
        if isinstance(f, type) or not callable(f):
            return f

        def guarded(*args, **kw):
            if any(isinstance(a, jax.Array)
                   for a in (*args, *kw.values())):
                raise AssertionError(f"np.{name} given a device array")
            return f(*args, **kw)
        return guarded


def test_the_host_only_numpy_refuses_a_device_array(monkeypatch):
    from repro.analysis import workload

    monkeypatch.setattr(workload, "np", _HostOnlyNumpy())
    with pytest.raises(AssertionError, match="device array"):
        workload.np.asarray(_hidden())
    assert workload.np.asarray([1]).shape == (1,)


def test_a_device_spec_is_fingerprinted_without_copying_it(monkeypatch):
    from repro.analysis import workload

    x, r = _hidden(), _router()
    spec = _spec(x, r)
    want = spec.fingerprint()
    flat = telemetry.counter("repro_fingerprint_bytes_total", "", ("path",))
    before = (flat.value(path="flat"), flat.value(path="chunked"))
    monkeypatch.setattr(workload, "np", _HostOnlyNumpy())
    with jax.transfer_guard_device_to_host("disallow"):
        fp = spec.fingerprint()
    assert fp == want
    assert isinstance(fp, str) and len(fp) == 64
    # nothing went through the host hash
    assert (flat.value(path="flat"), flat.value(path="chunked")) == before


def test_equal_content_equal_fingerprints():
    x = _hidden()
    copy = jnp.array(np.asarray(x))         # another array, same bits
    assert copy is not x
    assert _spec(x).fingerprint() == _spec(copy).fingerprint()
    assert _spec(x).fingerprint() == _spec(_hidden()).fingerprint()
    # the label is not part of the content
    assert _spec(x).fingerprint() == _spec(x).with_(label="e").fingerprint()


@pytest.mark.parametrize("where", [(0, 0), (128, 31), (255, 63)])
def test_one_changed_element_changes_the_fingerprint(where):
    x = _hidden()
    changed = x.at[where].set(x[where] + 1)
    assert _spec(x).fingerprint() != _spec(changed).fingerprint()
    d0, d1 = digest.digests([x, changed])
    lanes0 = np.frombuffer(d0, "<u4")
    lanes1 = np.frombuffer(d1, "<u4")
    assert np.all(lanes0 != lanes1)          # every lane moves


def test_router_layer_and_framing_change_the_fingerprint():
    x = _hidden()
    base = _spec(x).fingerprint()
    assert _spec(x, _router(5)).fingerprint() != base
    assert _spec(x).with_(waves_per_tile=4).fingerprint() != base
    spec = _spec(x)
    other_layer = WorkloadSpec.from_moe_router(
        {"router": _router()}, x, CFG, label="d", layer=4)
    assert other_layer.fingerprint() != spec.fingerprint()
    # same bits under another shape or dtype are other content
    assert _spec(x.reshape(128, 128)).fingerprint() != base
    d_bf16, d_u16 = digest.digests(
        [x, jax.lax.bitcast_convert_type(x, jnp.uint16)])
    assert d_bf16 == d_u16                   # the digest is of the bits...
    assert _spec(jax.lax.bitcast_convert_type(x, jnp.uint16)
                 ).fingerprint() != base     # ...the fingerprint frames dtype


def test_swapped_elements_change_the_digest():
    a = jnp.arange(1024, dtype=jnp.float32).reshape(8, 128)
    b = a.at[0, 0].set(a[0, 1]).at[0, 1].set(a[0, 0])
    assert digest.digests([a])[0] != digest.digests([b])[0]


@pytest.mark.parametrize("dtype", [jnp.bool_, jnp.int8, jnp.int32,
                                   jnp.float16])
def test_digests_cover_dtypes_and_empty_arrays(dtype):
    a = jnp.zeros((3, 5), dtype)
    b = jnp.ones((3, 5), dtype)
    da, db, de = digest.digests([a, b, jnp.zeros((0,), dtype)])
    assert len(da) == 16 and da != db
    assert de == bytes(16)


def test_host_array_fingerprints_are_unchanged():
    rng = np.random.default_rng([2**31 + 5, 3])
    ids = rng.integers(0, 128, 8192 * 8).astype(np.int32)
    ones = np.ones((ids.size, 1), np.float32)
    spec = WorkloadSpec.from_scatter_add(ids, ones, 128, label="x",
                                         waves_per_tile=32)
    assert spec.fingerprint() == (
        "dc725529bf7dbcba9eed29175da16bd74ef07425b2115fb053516943847ef5db")
    small = WorkloadSpec.from_scatter_add(ids[:4096], ones[:4096], 128,
                                          label="y")
    assert small.fingerprint() == (
        "25f112ea5f7b83db0e27164cde30d1ed9311bfa44fbb6fc81e332082071433cf")


# -- the digest kept per array ---------------------------------------------

_ARRAYS = telemetry.counter("repro_device_digest_arrays_total", "",
                            ("result",))


def _lookups() -> tuple:
    return _ARRAYS.value(result="hit"), _ARRAYS.value(result="miss")


def _ramp(n, m=65521):
    return ((np.arange(n, dtype=np.int64) * 40503) % m / m - 0.5).astype(
        np.float32)


def _pinned_spec():
    x = jnp.asarray(_ramp(256 * 64).reshape(256, 64)).astype(jnp.bfloat16)
    w = jnp.asarray(_ramp(64 * 32, 4093).reshape(64, 32))
    b = jnp.asarray(_ramp(32, 31) / 10)
    return WorkloadSpec.from_moe_router({"router": {"w": w, "bias": b}}, x,
                                        CFG, label="p", layer=3)


#: the spec's fingerprint and its arrays' digests as the one program over
#: the tuple of a spec's device arrays computed them, before digests were
#: kept per array
_PINNED_FP = "0b3fadc2d21576515dc006333357f1173fcd1a06cf4e48f963337da2e2bc038b"
_PINNED_DIGESTS = ("3e665b1002214f9551fe213cdc6de2d1",
                   "c4352a3bc4c31bdf03f0d5dfa222072b",
                   "2d70e28b8f986413f5ade8fb66645ae2")


def test_kept_digests_give_the_pinned_fingerprint():
    spec = _pinned_spec()
    p = spec.kernel.params
    arrays = [p["hidden"], p["router_w"], p["router_bias"]]
    assert tuple(d.hex() for d in digest.digests(arrays)) == _PINNED_DIGESTS
    assert tuple(np.asarray(jax.jit(digest._digest)(a), "<u4").tobytes().hex()
                 for a in arrays) == _PINNED_DIGESTS
    hits, misses = _lookups()
    assert spec.fingerprint() == _PINNED_FP          # digested, then kept
    assert _lookups() == (hits, misses + 3)
    assert spec.fingerprint() == _PINNED_FP          # served from the kept
    assert _lookups() == (hits + 3, misses + 3)
    assert _pinned_spec().fingerprint() == _PINNED_FP    # new arrays


def _spy_on_the_device(monkeypatch) -> list:
    """Every array handed to the device digest, in order."""
    sent = []
    real = digest.digests

    def spy(arrays):
        sent.extend(arrays)
        return real(arrays)
    monkeypatch.setattr(digest, "digests", spy)
    return sent


def _copy(a):
    return jnp.array(np.asarray(a))          # another array, same bits


def test_four_layers_of_one_batch_digest_it_once(monkeypatch):
    sent = _spy_on_the_device(monkeypatch)
    x = _hidden(seed=40)
    routers = [_router(seed=41 + 2 * k) for k in range(4)]
    specs = [WorkloadSpec.from_moe_router({"router": r}, x, CFG,
                                          label=f"l{k}", layer=3 + k)
             for k, r in enumerate(routers)]
    hits, misses = _lookups()
    fps = [s.fingerprint() for s in specs]
    assert len(set(fps)) == 4
    assert sum(a is x for a in sent) == 1
    assert len(sent) == 1 + 2 * 4             # the batch, each gate and bias
    assert _lookups() == (hits + 3, misses + 9)
    # each equals the fingerprint of a spec of fresh copies
    for k, (r, fp) in enumerate(zip(routers, fps)):
        fresh = {"w": _copy(r["w"]), "bias": _copy(r["bias"])}
        assert WorkloadSpec.from_moe_router(
            {"router": fresh}, _copy(x), CFG, label="c",
            layer=3 + k).fingerprint() == fp


def test_a_second_fingerprint_sends_nothing_to_the_device(monkeypatch):
    spec = _spec(_hidden(seed=50), _router(seed=51))
    first = spec.fingerprint()
    sent = _spy_on_the_device(monkeypatch)
    with telemetry.trace_scope() as rec:
        assert spec.fingerprint() == first
    assert sent == []
    assert not [s for s in rec["spans"] if s["name"] == "kernel.digest"]


def test_an_entry_is_retired_when_its_array_is_collected():
    import gc

    from repro.analysis import workload

    x = _hidden(seed=60)
    key = id(x)
    _spec(x).fingerprint()
    assert workload._DIGESTS._entries[key][0]() is x
    del x
    gc.collect()
    assert key not in workload._DIGESTS._entries


def test_a_stale_callback_leaves_a_newer_entry():
    from repro.analysis import workload

    cache = workload._DigestCache()
    x = _hidden(seed=61)
    cache.put(x, b"a" * 16)
    stale = cache._entries[id(x)][0]
    cache.put(x, b"b" * 16)                  # the id's entry is replaced
    stale.__callback__(stale)                # the old entry's retirement
    assert cache.get(x) == b"b" * 16


def test_a_deleted_array_is_not_served_from_the_cache():
    x = _hidden(seed=62)
    spec = _spec(x)
    spec.fingerprint()
    x.delete()
    with pytest.raises(RuntimeError, match="deleted"):
        spec.fingerprint()


def test_a_new_array_of_a_seen_shape_compiles_nothing():
    import jax.monitoring

    x, r = _hidden(seed=70, t=384), _router(seed=71)
    _spec(x, r).fingerprint()                # (h, w, b) digested
    programs = digest._digest_one._cache_size()
    lowered = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        _spec(_hidden(seed=72, t=384), r).fingerprint()   # misses h alone
        _spec(_hidden(seed=73, t=384), _router(seed=74)).fingerprint()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert digest._digest_one._cache_size() == programs
    assert lowered == []


def test_threads_sharing_arrays_get_the_serial_keys():
    import os
    import sys
    from concurrent.futures import ThreadPoolExecutor

    from repro.analysis import workload

    batches = [_hidden(seed=80 + i) for i in range(3)]
    routers = [_router(seed=90 + 2 * i) for i in range(4)]

    def specs(copy=lambda a: a):
        return [WorkloadSpec.from_moe_router(
                    {"router": {k: copy(v) for k, v in r.items()}}, copy(x),
                    CFG, label="t", layer=k)
                for x in batches for k, r in enumerate(routers)]
    serial = [s.fingerprint() for s in specs(_copy)]  # unshared arrays
    assert len(set(serial)) == 12
    workers = len(os.sched_getaffinity(0)) + 2      # more than the cores
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(workers) as pool:
            for _ in range(3):
                futures = [pool.submit(s.fingerprint) for s in specs()]
                assert [f.result(timeout=60) for f in futures] == serial
    finally:
        sys.setswitchinterval(interval)
    # every shared array kept once, under its own digest
    shared = [*batches, *(a for r in routers for a in r.values())]
    assert [workload._DIGESTS.get(a) for a in shared] == \
        digest.digests(shared)
