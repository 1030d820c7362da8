"""A DeepSeek-V3-style router on the profiler's normal path, at a small size
on the CPU: ``Session.profile(WorkloadSpec.from_moe_router(...))`` with the
kernel provider routes the batch and counts its expert loads in one device
program, records its steps as spans (``moe.route`` under the provider,
``kernel.digest`` under the fingerprint) and counts the routed tokens per
layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import Session, WorkloadSpec
from repro.core.counters import bitwise_equal
from repro.models import moe
from repro.obs import telemetry
from repro.obs.telemetry import trace_scope

CFG = moe.MoEConfig(d_model=64, d_expert=16, num_experts=32, top_k=4,
                    scoring="sigmoid", n_group=4, topk_group=2,
                    routed_scaling_factor=2.5)
T = 256


@pytest.fixture(autouse=True)
def _isolate_artifacts(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_RESULTS", str(tmp_path / "results"))


def _spec(seed: int, layer=3, label="r"):
    r = moe.init_router(jax.random.key(seed), CFG)
    r["bias"] = 0.02 * jax.random.normal(jax.random.key(seed + 1), (32,))
    x = jax.random.normal(jax.random.key(seed + 2), (T, 64)).astype(
        jnp.bfloat16)
    return WorkloadSpec.from_moe_router({"router": r}, x, CFG, label=label,
                                        layer=layer), r, x


def _by_name(spans, name):
    return [s for s in spans if s["name"] == name]


def _inside(spans, child, parent) -> bool:
    ids = {s["id"]: s for s in spans}
    p = ids.get(child["parent"])
    while p is not None:
        if p["name"] == parent:
            return True
        p = ids.get(p["parent"])
    return False


def test_router_steps_nest_under_the_collection():
    sess = Session("v5e", provider="kernel")
    sess.profile(_spec(0)[0])                   # compile outside the scope
    spec, r, x = _spec(10)
    with trace_scope() as rec:
        sess.profile(spec)
    spans = rec["spans"]
    (collect,) = _by_name(spans, "session.collect")
    (digest,) = _by_name(spans, "kernel.digest")
    (route,) = _by_name(spans, "moe.route")
    assert _inside(spans, digest, "session.fingerprint")
    assert _inside(spans, digest, "session.collect")
    assert _inside(spans, route, "session.provider")
    assert _inside(spans, route, "session.collect")
    assert digest["attrs"]["arrays"] == 3
    assert digest["attrs"]["bytes"] == x.nbytes + r["w"].nbytes + \
        r["bias"].nbytes
    assert route["attrs"]["layer"] == 3
    for name in ("kernel.launch", "kernel.wait", "kernel.readback",
                 "kernel.counters"):
        assert all(_inside(spans, s, "session.provider")
                   for s in _by_name(spans, name))
    # nothing is copied to the device for a device-resident batch
    assert not _by_name(spans, "kernel.h2d")
    # the largest expert load, as a share of the batch's ids
    (counters,) = [s for s in _by_name(spans, "kernel.counters")
                   if "attrs" in s]
    ids = np.asarray(moe.route({"router": r}, x, CFG)[1]).reshape(-1)
    loads = np.bincount(ids, minlength=32)
    assert counters["attrs"]["max_load_share"] == pytest.approx(
        loads.max() / ids.size, rel=1e-6)


def test_routed_tokens_are_counted_per_layer():
    routed = telemetry.counter("repro_moe_routed_tokens_total", "",
                               ("layer",))
    sess = Session("v5e", provider="kernel")
    before = {lay: routed.value(layer=str(lay)) for lay in (3, 4)}
    for i in range(3):
        sess.profile(_spec(20 + 3 * i, layer=3)[0])
    sess.profile(_spec(40, layer=4)[0])
    sess.profile(_spec(40, layer=4)[0])         # a memo hit routes nothing
    assert routed.value(layer="3") == before[3] + 3 * T
    assert routed.value(layer="4") == before[4] + T


def test_counters_are_those_of_the_routed_ids():
    """The kernel provider's counters equal those of the router's ids,
    read back here and counted by the same kernel from the host."""
    spec, r, x = _spec(50)
    sess = Session("v5e", provider="kernel")
    routed = sess.collect(spec)
    ids = np.asarray(moe.route({"router": r}, x, CFG)[1])
    host = sess.collect(WorkloadSpec.from_scatter_add(
        ids, np.ones(ids.size, np.float32), 32, label="r"))
    assert bitwise_equal(routed, host, ignore=("meta",))
    # 256 tokens x top-4 = 1024 ids, padded to one 2048-id tile: 2 waves
    assert routed.num_waves == 2


@pytest.mark.parametrize("entry", ["committed_stream", "trace_provider",
                                   "heatmap_for_spec"])
def test_routed_ids_have_no_host_stream(entry):
    """The router's ids are made on the device: no host-stream entry
    point can synthesize or attribute them."""
    from repro.obs import heatmap_for_spec

    spec = _spec(70)[0]
    call = {
        "committed_stream": spec.committed_stream,
        "trace_provider": lambda: Session("v5e", provider="trace").collect(
            spec),
        "heatmap_for_spec": lambda: heatmap_for_spec(spec),
    }[entry]
    with pytest.raises(ValueError, match="no host index stream"):
        call()


def test_a_changed_batch_is_a_memo_miss():
    sess = Session("v5e", provider="kernel")
    spec, r, x = _spec(60)
    sess.profile(spec)
    again = WorkloadSpec.from_moe_router(
        {"router": r}, jnp.array(np.asarray(x)), CFG, label="same",
        layer=3)
    sess.profile(again)
    assert sess.stats["memo_hits"] == 1
    changed = WorkloadSpec.from_moe_router(
        {"router": r}, x.at[0, 0].set(x[0, 0] + 1), CFG, label="changed",
        layer=3)
    sess.profile(changed)
    assert sess.stats["collected"] == 2
