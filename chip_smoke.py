"""Chip smoke test: the profiler's device path, end to end, on one TPU.

    python chip_smoke.py

Drives the tool's main path once through the entry points its users
call — ``Session`` with the ``kernel`` provider, the kernels' jitted ops,
and the ``repro serve`` daemon over localhost HTTP — with every Pallas
kernel compiled for the attached chip, and checks each result against a
plain numpy reference or the trace provider:

  (a) device     the backend is a TPU (platform, kind and count printed);
  (b) histogram  the paper's case study at its largest image (RGBA,
                 2^22 px, seed 0), solid and uniform, ``hist`` and
                 ``hist2``: counts equal a per-channel ``np.bincount``,
                 the kernel's ``e`` equals the trace provider's exactly;
                 U, bottleneck and the predicted hist -> hist2 speedup,
                 beside the measured ratio of the compiled kernels (shown
                 for information; it is not a claim);
  (c) MoE        dispatch of qwen3-moe-235b-a22b's router (128 experts,
                 top-8, 8,192 tokens = 65,536 ids), balanced / skewed /
                 collapsed: counts equal ``np.bincount``, per-wave
                 degrees equal the trace provider's;
  (d) scatter    65,536 ids over 16,384 segments at D=128 (four segment
                 blocks on the 2-D grid): the sum matches ``np.add.at``,
                 degrees equal the trace provider's;
  (e) served     ``ProfilingService`` with the ``kernel`` provider and no
                 fallback, answering profile / validate / heatmap jobs
                 for (b)'s points: every answer 200 and undegraded.

Counter and table caches go to fresh directories, so every kernel runs.
One process holds the chip throughout; the service runs in threads.  The
last line of standard output is the JSON result; any failure raises and
exits non-zero before it is printed, as does a run that finds no TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PLATFORM = "tpu"
PIXELS = 1 << 22              # data.images.PAPER_SIZES[-1]
EXPERTS, TOP_K, TOKENS = 128, 8, 8192   # configs/qwen3_moe_235b_a22b.py
SEG_IDS, SEGMENTS, SEG_D = 1 << 16, 16384, 128
TIMING_REPS = 20
COLD_S = 600.0                # a served call may include a cold compile


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def phase(name: str) -> None:
    print(f"== {name}", flush=True)


def device_check() -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    check(dev["platform"] == PLATFORM,
          f"backend is {dev['platform']!r}, not {PLATFORM!r}")
    return dev


def check_kernel_meta(cset, dev: dict) -> None:
    meta = {k: cset.meta.get(k)
            for k in ("platform", "device_kind", "interpret")}
    want = {"platform": dev["platform"], "device_kind": dev["kind"],
            "interpret": dev["platform"] != "tpu"}
    check(meta == want, f"{cset.label}: kernel counters from {meta}, "
                        f"expected {want}")


def check_validation(rep) -> None:
    e_err = rep.rel_err("kernel", "e")
    check(e_err == 0.0, f"{rep.label}: kernel e rel err {e_err}")
    check(rep.max_rel_err == 0.0,
          f"{rep.label}: kernel counters differ from trace "
          f"(max rel err {rep.max_rel_err})")


def trace_degrees(ids, num_segments: int):
    """Per-wave degrees the trace provider models for a scatter stream."""
    from repro.core import counters
    from repro.kernels.scatter_add import ops as scat_ops

    stream = scat_ops.committed_id_stream(ids, num_segments)
    return counters.trace_from_indices(stream, num_segments).degree


def time_histogram(img, variant: str) -> float:
    """Median seconds of the compiled histogram op, after a warm-up."""
    from repro.kernels.histogram import ops as hist_ops

    def run():
        hist_ops.histogram(img, variant=variant).block_until_ready()

    run()
    times = []
    for _ in range(TIMING_REPS):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def histograms(sess, dev: dict) -> list:
    import jax.numpy as jnp

    from repro.analysis import WorkloadSpec
    from repro.core import bottleneck
    from repro.data.images import make_image
    from repro.kernels.histogram import ops as hist_ops

    phase(f"(b) histograms: RGBA {PIXELS} px, hist vs hist2")
    points = []
    for kind in ("solid", "uniform"):
        img = make_image(kind, PIXELS, seed=0)
        expect = np.stack([np.bincount(img[:, c], minlength=256)
                           for c in range(img.shape[1])])
        img_dev = jnp.asarray(img)
        profs, secs = {}, {}
        for variant in ("hist", "hist2"):
            spec = WorkloadSpec.from_histogram(
                img, label=f"{kind}-{variant}", variant=variant)
            check_validation(sess.validate(spec,
                                           providers=("trace", "kernel")))
            prof = sess.profile(spec)
            check_kernel_meta(sess.collect_cached(spec), dev)
            got = np.asarray(hist_ops.histogram(img_dev, variant=variant))
            check(np.array_equal(got, expect),
                  f"{spec.label}: histogram differs from np.bincount")
            secs[variant] = time_histogram(img_dev, variant)
            profs[variant] = prof
            points.append((kind, variant))
            print(f"{spec.label}: U={prof.scatter_utilization!r} "
                  f"e={prof.e!r} bottleneck={prof.bottleneck} "
                  f"kernel_s={secs[variant]!r}", flush=True)
        predicted = bottleneck.speedup_estimate(profs["hist"],
                                                profs["hist2"])
        print(f"{kind}: predicted hist->hist2 speedup={predicted!r} "
              f"measured ratio={secs['hist'] / secs['hist2']!r} "
              f"(information, not a claim)", flush=True)
    return points


def moe_dispatch(sess, dev: dict) -> None:
    import jax.numpy as jnp

    from repro.analysis import WorkloadSpec
    from repro.kernels.scatter_add import ops as scat_ops

    n = TOKENS * TOP_K
    phase(f"(c) MoE dispatch: {EXPERTS} experts, top-{TOP_K}, "
          f"{TOKENS} tokens = {n} ids")
    rng = np.random.default_rng(0)
    for label, ids in (("balanced", rng.integers(0, EXPERTS, n)),
                       ("skewed", rng.zipf(1.3, n) % EXPERTS),
                       ("collapsed", np.zeros(n, np.int64))):
        ids = ids.astype(np.int32)
        ones = np.ones((n, 1), np.float32)
        spec = WorkloadSpec.from_scatter_add(
            ids, ones, EXPERTS, label=f"moe-{label}", waves_per_tile=32,
            bytes_read=float(n * 4))
        check_validation(sess.validate(spec, providers=("trace", "kernel")))
        prof = sess.profile(spec)
        check_kernel_meta(sess.collect_cached(spec), dev)
        expect = np.bincount(ids, minlength=EXPERTS)
        counts = np.asarray(scat_ops.bincount(jnp.asarray(ids),
                                              num_segments=EXPERTS))
        check(np.array_equal(counts, expect),
              f"{spec.label}: bincount differs from np.bincount")
        out, c = scat_ops.instrumented_scatter_add(ids, ones, EXPERTS)
        check(np.array_equal(np.asarray(out)[:, 0], expect),
              f"{spec.label}: dispatch counts differ from np.bincount")
        check(np.array_equal(c["degree"], trace_degrees(ids, EXPERTS)),
              f"{spec.label}: wave degrees differ from the trace's")
        print(f"{spec.label}: U={prof.scatter_utilization!r} "
              f"e={prof.e!r} bottleneck={prof.bottleneck}", flush=True)


def segment_sum(sess, dev: dict) -> None:
    import jax.numpy as jnp

    from repro.analysis import WorkloadSpec
    from repro.kernels.scatter_add import ops as scat_ops

    phase(f"(d) scatter-add: {SEG_IDS} ids over {SEGMENTS} segments, "
          f"D={SEG_D}")
    rng = np.random.default_rng(1)
    ids = rng.integers(0, SEGMENTS, SEG_IDS).astype(np.int32)
    vals = rng.standard_normal((SEG_IDS, SEG_D)).astype(np.float32)
    expect = np.zeros((SEGMENTS, SEG_D))
    np.add.at(expect, ids, vals.astype(np.float64))
    spec = WorkloadSpec.from_scatter_add(ids, vals, SEGMENTS,
                                         label="segment-sum")
    check_validation(sess.validate(spec, providers=("trace", "kernel")))
    prof = sess.profile(spec)
    check_kernel_meta(sess.collect_cached(spec), dev)
    out, c = scat_ops.instrumented_scatter_add(ids, vals, SEGMENTS)
    plain = scat_ops.scatter_add(jnp.asarray(vals), jnp.asarray(ids),
                                 num_segments=SEGMENTS)
    for name, got in (("instrumented", out), ("plain", plain)):
        err = float(np.max(np.abs(np.asarray(got) - expect)))
        check(np.allclose(np.asarray(got), expect, rtol=1e-5, atol=1e-5),
              f"{name} scatter-add differs from np.add.at (max abs {err})")
    check(np.array_equal(c["degree"], trace_degrees(ids, SEGMENTS)),
          "segment-sum wave degrees differ from the trace's")
    print(f"segment-sum: U={prof.scatter_utilization!r} e={prof.e!r} "
          f"bottleneck={prof.bottleneck}", flush=True)


def served(points: list, dev: dict) -> None:
    from repro.analysis.sweep_cache import SweepCache
    from repro.service.client import ServiceClient
    from repro.service.server import (ProfilingService, ServiceConfig,
                                      make_http_server)

    phase("(e) served path: repro serve with the kernel provider")
    service = ProfilingService(ServiceConfig(
        provider="kernel", fallbacks=(), timeout_s=COLD_S,
        max_timeout_s=COLD_S, call_timeout_s=COLD_S, workers=2)).start()
    server = make_http_server(service, port=0)
    thread = threading.Thread(target=server.serve_forever,
                              kwargs={"poll_interval": 0.2}, daemon=True)
    thread.start()
    try:
        client = ServiceClient("127.0.0.1", server.server_address[1],
                               timeout_s=COLD_S + 60)
        for kind, variant in points:
            workload = {"workload": "histogram", "pixels": PIXELS,
                        "dist": kind, "variant": variant, "seed": 0}
            for job in ("profile", "validate", "heatmap"):
                t0 = time.perf_counter()
                ans = client.submit({"kind": job, "workload": workload,
                                     "timeout_s": COLD_S})
                label = f"{job} {kind}-{variant}"
                check(ans["ok"] and not ans["degraded"]
                      and ans["fallback_providers"] == [],
                      f"{label}: degraded, {ans['fallback_providers']}")
                if job == "validate":
                    errs = {c["provider"]: c["rel_err"]["e"]
                            for c in ans["result"]["comparisons"]}
                    check(errs.get("kernel") == 0.0,
                          f"{label}: kernel e rel err {errs}")
                print(f"served {label}: 200 in "
                      f"{time.perf_counter() - t0!r} s", flush=True)
        stats = service.status()["sessions"]["v5e"]
        print(f"service session stats: {stats}", flush=True)
        check(stats["collected"] > 0, "the service collected nothing")
        entries = [c for _, c in SweepCache(service.cache.root)
                   .iter_entries()]
        check(len(entries) > 0, "the service cached no kernel counters")
        for cset in entries:
            check_kernel_meta(cset, dev)
    finally:
        server.shutdown()
        server.server_close()
        thread.join(10)
        service.stop()


def main() -> dict:
    phase("(a) device")
    dev = device_check()

    from repro import kernels
    from repro.analysis import Session

    print(f"compile cache: {kernels.enable_compile_cache()}", flush=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as fresh:
        os.environ["REPRO_RESULTS"] = str(Path(fresh) / "results")
        os.environ["REPRO_TABLE_CACHE"] = str(Path(fresh) / "tables")
        sess = Session("v5e", provider="kernel", persistent_cache=True)
        points = histograms(sess, dev)
        moe_dispatch(sess, dev)
        segment_sum(sess, dev)
        print(f"session stats: {sess.stats}", flush=True)
        check(sess.stats["collected"] > 0,
              "no kernel-provider collection ran")
        # a second fresh counter cache: the service's kernels run too
        os.environ["REPRO_RESULTS"] = str(Path(fresh) / "served")
        served(points, dev)
    return dev


if __name__ == "__main__":
    t_start = time.perf_counter()
    device = main()
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t_start!r} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))
