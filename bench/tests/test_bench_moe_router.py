"""The dsv3-routed cell at a size a CPU test can hold, and its readers.

The request kind makes the same batch and routers from the same seed and
key; a run through the harness comes out correct, with the kernel
provider's counters equal to the float64 reference's; a run whose count
or router is broken underneath, and the float32 control, come out not
correct.  The readers of the new per-layer metrics find the router's ops
and the count kernel in a device trace, the spans in a window, and
nothing where there is nothing.  The uniform mix's requests are noise in
every block, never repeat, and move the counters.
"""

import hashlib
import itertools
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare, devtrace, drivers, harness, traffic  # noqa: E402

SMALL = {"hidden_size": 64, "n_routed_experts": 32, "n_group": 4,
         "topk_group": 2, "num_experts_per_tok": 4, "tokens_per_batch": 256}
BIG = 2**31 + 12345
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config(**changes) -> dict:
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "deepseek-v3-moe-router.json").read_text())
    return {**cfg, **changes}


def _gen(seed=BIG):
    return traffic.requests(_config(**SMALL), traffic.load("dsv3-prefill"),
                            seed)


def _digest(arr) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(arr)).tobytes()
                          ).hexdigest()


def test_same_seed_and_key_same_batch_and_routers():
    a, b, c = _gen(), _gen(), _gen(BIG + 1)
    assert a.variants == [3, 4, 5, 6]
    for key in (0, 7):
        assert _digest(a.payload(key)["hidden"]) == \
            _digest(b.payload(key)["hidden"])
    assert _digest(a.payload(0)["hidden"]) != \
        _digest(a.payload(1)["hidden"])
    assert _digest(a.payload(0)["hidden"]) != \
        _digest(c.payload(0)["hidden"])
    for layer in a.variants:
        assert _digest(a.routers[layer]["w"]) == \
            _digest(b.routers[layer]["w"])
        assert _digest(a.routers[layer]["bias"]) == \
            _digest(b.routers[layer]["bias"])
    assert len({_digest(a.routers[v]["w"]) for v in a.variants}) == 4


def test_batches_are_rms_normalised_bf16_on_the_device():
    import jax

    gen = _gen()
    x = gen.payload(3)["hidden"]
    assert isinstance(x, jax.Array) and x.dtype == "bfloat16"
    assert x.shape == (256, 64)
    rms = np.sqrt(np.mean(np.asarray(x, np.float64) ** 2, axis=1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-2)   # bf16 rounding
    bias = np.asarray(gen.routers[3]["bias"])
    assert 0.005 < bias.std() < 0.05                  # N(0, 0.02)


def test_launch_shape_of_a_request():
    gen = _gen()
    shape = gen.launch(gen.payload(0))
    assert shape == {"kernel": "scatter", "ids": 1024, "width": 1,
                     "segments": 32, "commit_group": 32, "tokens": 256,
                     "hidden": 64, "top_k": 4}


def test_program_counters_equal_the_reference():
    from repro.analysis import Session

    gen, cfg = _gen(), _config(**SMALL)
    sess = Session("v5e", provider="kernel")
    for key, layer in ((0, 3), (1, 6)):
        payload = gen.payload(key)
        cset = sess.collect(gen.spec(payload, layer, "t"))
        ref = compare.reference_verdict(cfg, payload, layer, np.float64)
        for k in compare.COUNTER_KEYS:
            assert np.array_equal(np.asarray(getattr(cset, k), np.float64),
                                  np.asarray(ref["counters"][k],
                                             np.float64)), k


def run(**kw) -> dict:
    return harness.run_cell("dsv3-routed", seed=BIG, seconds=1.0,
                            traced=False, t_start=time.monotonic(),
                            require_chip=False, config_changes=SMALL,
                            log=lambda *a: None, **kw)


def failing(result: dict) -> set:
    return {k for k, c in result["checks"].items() if c["value"] > c["limit"]}


def test_a_small_run_is_correct():
    result = run()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert result["checks"]["unverified"]["value"] == 0
    assert set(result["metrics"]) >= {"setup_s", "verdicts_per_s",
                                      "verdict_p95_s"}


def test_an_altered_degree_is_not_correct(monkeypatch):
    from repro.kernels.scatter_add import ops as scat_ops

    make = scat_ops.count_program

    def altered(*a, **kw):
        program = make(*a, **kw)

        def run_altered(*args):
            counts, packed = program(*args)
            return counts, packed.at[0].add(1.0)
        return run_altered
    monkeypatch.setattr(scat_ops, "count_program", altered)
    result = run()
    assert not result["correct"]
    assert failing(result) & {"counter_gap", "model_rel_gap"}


def test_a_router_that_drops_the_bias_is_not_correct(monkeypatch):
    """Selecting on the unbiased score moves far more tokens than lie near
    a cut; the reference borrows none of them."""
    from repro.models import moe

    stream = moe.expert_stream

    def unbiased(cfg):
        fn = stream(cfg)

        def route(x, router):
            return fn(x, {**router, "bias": 0.0 * router["bias"]})
        return route
    monkeypatch.setattr(moe, "expert_stream", unbiased)
    result = run()
    assert not result["correct"]
    assert "counter_gap" in failing(result)


def test_the_float32_control_is_not_correct():
    result = run(control=True)
    assert not result["correct"]
    assert failing(result) == {"model_rel_gap"}


# -- readers -------------------------------------------------------------------


def _reader(name):
    return harness.load_metric(name)


def _run(trace, launches, spans=()):
    w = drivers.Window()
    w.launches = launches
    w.spans = list(spans)
    return harness.Run(cell="dsv3-routed", config={}, traffic={},
                       peaks=PEAKS, setup_s=1.0, window=w, trace=trace,
                       trace_window_s=1.0)


FULL = {"kernel": "scatter", "ids": 131072, "width": 1, "segments": 256,
        "commit_group": 32, "tokens": 16384, "hidden": 7168, "top_k": 8}


def test_router_work_at_the_published_size():
    w = harness.load_work("router").work(FULL)
    assert w["ops"] == 2 * 16384 * 7168 * 256
    assert w["bytes"] == 16384 * 7168 * 2 + 7168 * 256 * 4 + 256 * 4 + \
        16384 * 8 * 8
    # balanced: 0.305 ms of operations at peak, 0.297 ms of bytes
    assert w["ops"] / PEAKS["bf16_flops_per_s"] == pytest.approx(3.05e-4,
                                                                 rel=1e-2)
    assert w["bytes"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(2.97e-4,
                                                                  rel=1e-2)


def test_router_roofline_spans_the_matmul_to_the_count():
    r = _reader("moe_router_roofline")
    ms = 1_000_000
    first, count = r.ROUTER_FIRST, r.COUNT
    ops = [(first, 0, 2 * ms),                  # a digest's op, same name
           ("pad_add_fusion", 2 * ms, 3 * ms),
           (first, 10 * ms, 12 * ms),           # the routed program
           ("sort.2", 12 * ms, 13 * ms),
           ("slice.5", 13 * ms, 13 * ms + ms // 2),
           (count, 14 * ms, 15 * ms),
           ("pad_maximum_fusion", 15 * ms, 15 * ms + ms // 10),
           (first, 20 * ms, 21 * ms),
           ("sort.2", 21 * ms, 23 * ms),
           (count, 23 * ms, 24 * ms)]
    trace = devtrace.DeviceTrace(ops={"/device:TPU:0": ops}, host=[])
    n, secs = r.router_time(trace)
    assert n == 2 and secs == pytest.approx((3.5 + 3.0) * 1e-3)
    w = harness.load_work("router").work(FULL)
    least = max(w["ops"] / PEAKS["bf16_flops_per_s"],
                w["bytes"] / PEAKS["hbm_bytes_per_s"])
    got = r.read(_run(trace, [FULL, FULL]))
    assert got == pytest.approx(100 * 2 * least / 6.5e-3)
    assert r.KERNEL_NAMES == (first,)


def test_routed_count_roofline_reads_the_count_kernel():
    r = _reader("routed_count_roofline")
    ms = 1_000_000
    trace = devtrace.DeviceTrace(ops={"/device:TPU:0": [
        ("fusion.3", 0, 2 * ms), (r.KERNEL_NAMES[0], 2 * ms, 3 * ms)]},
        host=[])
    w = harness.load_work("scatter").work(FULL)
    least = w["bytes"] / PEAKS["hbm_bytes_per_s"]
    assert r.read(_run(trace, [FULL])) == pytest.approx(100 * least / 1e-3)


def test_readers_are_silent_without_their_ops_or_spans():
    trace = devtrace.DeviceTrace(ops={"/device:TPU:0": [("x", 0, 5)]},
                                 host=[])
    for name in ("moe_router_roofline", "routed_count_roofline"):
        assert _reader(name).read(_run(trace, [FULL])) is None
        assert _reader(name).read(_run(None, [FULL])) is None
    for name in ("moe.route_ms", "kernel.digest_ms"):
        assert _reader(name).read(_run(None, [], [[]])) is None


def test_span_readers_take_the_mean_per_verdict():
    verdict = [{"name": "moe.route", "id": 3, "parent": 2, "start_ms": 1.0,
                "dur_ms": 0.25},
               {"name": "kernel.digest", "id": 5, "parent": 4,
                "start_ms": 0.1, "dur_ms": 0.5}]
    run = _run(None, [], [verdict, verdict, []])
    assert _reader("moe.route_ms").read(run) == pytest.approx(0.5 / 3)
    assert _reader("kernel.digest_ms").read(run) == pytest.approx(1.0 / 3)


def test_uniform_pairs_covers_every_block():
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "sec5-hist-4mpx.json").read_text())
    mix = traffic.load("uniform-pairs")
    blocks = cfg["pixels"] // cfg["launch"]["commit_group"]
    assert mix["request"]["noise_blocks"] == [blocks, blocks]
    small = {**mix, "request": {**mix["request"], "noise_blocks": [128, 128]}}
    gen = traffic.requests({**cfg, "pixels": 4096}, small, BIG)
    keys = list(itertools.islice(gen.keys(), 50))
    imgs = [gen.payload(k)["img"] for k in keys]
    assert len({_digest(i) for i in imgs}) == 50
    again = traffic.requests({**cfg, "pixels": 4096}, small, BIG)
    assert [_digest(again.payload(k)["img"]) for k in keys[:3]] == \
        [_digest(i) for i in imgs[:3]]
    # every block noise: no channel keeps one value in most pixels
    for img in imgs[:3]:
        assert img.shape == (4096, 4) and img.dtype == np.int32
        for ch in range(4):
            assert np.mean(img[:, ch] == np.bincount(img[:, ch]).argmax()) \
                < 0.05
    with pytest.raises(ValueError, match="every block"):
        traffic.requests({**cfg, "pixels": 4096}, mix, BIG)


def test_rolled_noise_moves_the_counters():
    """Two requests from one pool image differ in their wave degrees, so a
    stale answer cannot pass the check."""
    from repro.analysis import Session

    cfg = json.loads((ROOT / "bench" / "configs"
                      / "sec5-hist-4mpx.json").read_text())
    mix = traffic.load("uniform-pairs")
    small = {**mix, "request": {**mix["request"], "noise_blocks": [128, 128],
                                "pool_images": 1}}
    gen = traffic.requests({**cfg, "pixels": 4096}, small, BIG)
    k1, k2 = itertools.islice(gen.keys(), 2)
    sess = Session("v5e", provider="trace")
    o1, o2 = (sess.collect(gen.spec(gen.payload(k), "hist", "u")).O
              for k in (k1, k2))
    assert not np.array_equal(o1, o2)
