"""One run of one cell of ``BENCHMARK.json``: set-up, window, check, metrics.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``bench/configs/<config>.json`` (and its plain reference
``<config>.py``), ``bench/traffic/<traffic>.json``,
``bench/requests/<kind>.py`` (the request kind a mix names),
``bench/metrics/<metric>.py``, ``bench/work/<kernel>.py`` and
``bench/limits/<cell>.json``.  Adding a cell means adding such files and
entries; nothing here names a cell.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gzip
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import compare, devtrace, drivers, load_module
from bench import traffic as traffic_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX finds no accelerator the cell can run on."""


@dataclasses.dataclass
class Run:
    """What the metric readers in ``bench/metrics`` are handed."""

    cell: str
    config: dict
    traffic: dict
    peaks: dict
    setup_s: float
    window: drivers.Window
    trace: object = None            # devtrace.DeviceTrace, traced runs only
    trace_window_s: float = 0.0


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_parts(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(cell entry, configuration, traffic mix) of cell ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {', '.join(sorted(cells))})")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    return cell, cfg, traffic_mod.load(cell["traffic"])


def load_metric(name: str):
    """The reader of metric ``name`` (``bench/metrics/<name>.py``)."""
    return load_module(BENCH / "metrics" / f"{name}.py")


def load_work(kernel: str):
    """The work count of ``kernel`` (``bench/work/<kernel>.py``)."""
    return load_module(BENCH / "work" / f"{kernel}.py")


def metrics_for(bench: dict, cell: str, traced: bool) -> list:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def chip(chips: int, require: bool) -> dict:
    """The attached devices; ``NoChip`` unless they are TPUs of a kind in
    ``bench/peaks.json``, at least ``chips`` of them."""
    import jax

    peaks = json.loads((BENCH / "peaks.json").read_text())["kinds"]
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require:
        if info["platform"] != "tpu":
            raise NoChip(f"JAX runs on {info['platform']!r}, not a TPU")
        if info["count"] < chips:
            raise NoChip(f"{info['count']} chip(s) attached, the cell needs "
                         f"{chips}")
        if info["kind"] not in peaks:
            raise NoChip(f"device kind {info['kind']!r} is not in "
                         f"bench/peaks.json")
    return {**info, "peaks": peaks.get(info["kind"], {})}


def enable_compile_cache() -> dict:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or ``JAX_COMPILATION_CACHE_DIR``), every program cached, so
    that only a checkout's first run compiles.  Returns the settings it
    replaced."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / "results" / "jax_cache")
    settings = {"jax_compilation_cache_dir": path,
                "jax_persistent_cache_min_compile_time_secs": 0.0,
                "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {k: getattr(jax.config, k) for k in settings}
    for k, v in settings.items():
        jax.config.update(k, v)
    return before


@contextlib.contextmanager
def run_scope(tmp: Path):
    """Compile cache on, and the program's table and counter caches in
    ``tmp``, for one run; the process's settings are restored after it."""
    import jax

    saved_env = {k: os.environ.get(k)
                 for k in ("REPRO_TABLE_CACHE", "REPRO_RESULTS")}
    saved_cfg = enable_compile_cache()
    os.environ["REPRO_TABLE_CACHE"] = str(tmp / "tables")
    os.environ["REPRO_RESULTS"] = str(tmp / "results")
    try:
        yield
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for k, v in saved_cfg.items():
            jax.config.update(k, v)


def p95(values) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(values)
    return v[max(0, int(np.ceil(0.95 * len(v))) - 1)]


def run_cell(name: str, *, seed: int, seconds: float, traced: bool,
             t_start: float, require_chip: bool = True,
             config_changes: dict | None = None, trace_out=None,
             control: bool = False, log=print) -> dict:
    """One run of cell ``name``; the result object of the last line.

    ``config_changes`` shrinks a cell for the CPU tests.
    ``control`` puts the control (``compare.control_verdict``) in the
    program's place in the comparison: such a run must not be correct.
    """
    bench = load_benchmark()
    cell, cfg, tr = cell_parts(bench, name)
    cfg = {**cfg, **(config_changes or {})}
    limits = compare.load_limits(name)

    import jax
    import jax.monitoring
    import jax.profiler

    phases = {"imports": time.monotonic() - t_start}
    dev = chip(cell["chips"], require_chip)
    phases["device"] = time.monotonic() - t_start
    lowerings = [0]

    def count_lowering(event, duration, **kw):
        lowerings[0] += event == LOWERING_EVENT

    jax.monitoring.register_event_duration_secs_listener(count_lowering)
    # the table and every counter cache start empty in every run
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp, \
            run_scope(Path(tmp)):
        driver = drivers.SessionDriver(cfg, tr, seed, Path(tmp) / "tables")
        phases["system"] = time.monotonic() - t_start
        try:
            driver.warmup()
            setup_s = time.monotonic() - t_start
            trace_dir = Path(tmp) / "trace"
            if traced:
                jax.profiler.start_trace(
                    str(trace_dir), profiler_options=_profile_options())
            compiled_before = lowerings[0]
            tw0 = time.perf_counter()
            window = driver.window(seconds, traced)
            trace_window_s = time.perf_counter() - tw0
            compiles = lowerings[0] - compiled_before
            trace = None
            if traced:
                jax.profiler.stop_trace()
                (xplane,) = trace_dir.rglob("*.xplane.pb")
                trace = devtrace.load(xplane)
                if trace_out:
                    out = Path(trace_out) / f"{name}-{seed}.xplane.pb.gz"
                    out.parent.mkdir(parents=True, exist_ok=True)
                    out.write_bytes(gzip.compress(xplane.read_bytes()))
            mem = _memory_peak()
            checked, unverified = driver.program_verdicts(tr["check_sample"])
        finally:
            driver.close()
            jax.monitoring.unregister_event_duration_listener(count_lowering)

    if control:
        checked = [(k, v, compare.control_verdict(
                        cfg, driver.gen.payload(k), v, program))
                   for k, v, program in checked]
    rows = [compare.gaps(program, compare.reference_verdict(
                cfg, driver.gen.payload(key), variant, np.float64))
            for key, variant, program in checked]
    numbers = compare.worst(rows)
    numbers["unverified"] = unverified + (0 if rows else 1)
    numbers["window_hits"] = (window.stats_delta["memo_hits"]
                              + window.stats_delta["disk_hits"])
    numbers["collect_gap"] = abs(window.stats_delta["collected"]
                                 - (window.attempted - window.failed))
    numbers["window_compiles"] = compiles
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    run = Run(cell=name, config=cfg, traffic=tr, peaks=dev["peaks"],
              setup_s=setup_s, window=window, trace=trace,
              trace_window_s=trace_window_s)
    metrics = {}
    for m in metrics_for(bench, name, traced):
        value = load_metric(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    log("set-up: " + ", ".join(f"{k} done at {v!r} s" for k, v in
                               {**phases, "warm-up": setup_s}.items()))
    log(f"window: {window.attempted} attempted, {window.failed} failed, "
        f"{window.completed_in_window} completed in {seconds} s; "
        f"traffic wait {window.traffic_wait_s!r} s; compiles in window "
        f"{compiles}; session stats delta {window.stats_delta}")
    if window.latencies_s:
        log(f"latency: median {statistics.median(window.latencies_s)!r} s, "
            f"p95 {p95(window.latencies_s)!r} s over "
            f"{len(window.latencies_s)} verdicts")
    _log_kernel_times(run, driver, log)
    log(f"checked {len(rows)} verdicts against the plain reference")

    device = {k: dev[k] for k in ("platform", "kind", "count")}
    device["memory_peak_bytes"] = mem
    result = {"correct": correct, "attempted": window.attempted,
              "failed": window.failed, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace_window_s
        result["breakdown"] = {"device_ops": trace.top_ops(),
                               "idle_gaps": trace.idle_gaps(
                                   spans=window.spans)}
    result["checks"] = checks
    return result


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1   # the runtime's own host events cost much
    opts.enable_hlo_proto = False
    return opts


def _memory_peak() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return int(max((s.get("peak_bytes_in_use", 0) for s in stats), default=0))


def _log_kernel_times(run: Run, driver, log) -> None:
    """Predicted against measured kernel time, for information only: the
    queue model's window for the launch beside the device's time for it."""
    records = driver.records
    if not records:
        return
    clock = run.config["device_model"]["clock_hz"]
    predicted = statistics.mean(float(np.max(p.T_cycles)) / clock
                                for _, _, p in records)
    line = f"kernel time: predicted by the model {predicted!r} s per launch"
    if run.trace is not None:
        for name in sorted(f.stem for f in (BENCH / "metrics").glob(
                "*_roofline.py")):
            names = load_metric(name).KERNEL_NAMES
            n, secs = run.trace.kernel(names)
            if n:
                line += f"; measured {secs / n!r} s per {names[0]} launch"
    log(line + " (information, not a metric)")


def kernel_roofline(run: Run, names, kernel: str):
    """Percent of a kernel's roofline: the least time its launches need,
    the larger of operations over peak and bytes over HBM bandwidth
    (``bench/work/<kernel>.py``), over their device time in the trace.
    None where the trace holds no launch of it."""
    if run.trace is None or not run.peaks:
        return None
    n, secs = run.trace.kernel(names)
    shapes = [s for s in run.window.launches if s["kernel"] == kernel]
    if not n or not shapes or secs <= 0:
        return None
    work = load_work(kernel)
    least = statistics.mean(
        max(w["ops"] / run.peaks["bf16_flops_per_s"],
            w["bytes"] / run.peaks["hbm_bytes_per_s"])
        for w in (work.work(s) for s in shapes))
    return 100.0 * n * least / secs


def print_result(result: dict) -> None:
    """The check lines last on standard error, the result last on
    standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
