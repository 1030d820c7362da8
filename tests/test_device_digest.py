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
existed.
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
