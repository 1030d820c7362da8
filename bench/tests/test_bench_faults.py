"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
at a size a CPU test can hold (the kernels run in the Pallas interpreter).
The faults a cell of this benchmark can have: an answer altered where it
is produced (one wave's degree), half of the batch left out, and a step
that hands back its previous state (the provider returns stale counters,
or the launch reads the previous image of its variant).  There is no
exchange between chips to leave out: every cell takes one.
"""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

SMALL = {
    "hist-solid": {"pixels": 4096},
    "moe-skewed": {"tokens_per_batch": 512},
}


def run(cell: str, **kw) -> dict:
    return harness.run_cell(cell, seed=2**31 + 99, seconds=1.0, traced=False,
                            t_start=time.monotonic(), require_chip=False,
                            config_changes=SMALL[cell],
                            log=lambda *a: None, **kw)


def failing(result: dict) -> set:
    return {k for k, c in result["checks"].items() if c["value"] > c["limit"]}


def _patch_degrees(monkeypatch, cell: str, change):
    """Wrap the jitted launch that returns the kernel's wave degrees."""
    from repro.kernels.histogram import ops as hist_ops
    from repro.kernels.scatter_add import ops as scat_ops

    if cell == "moe-skewed":
        launch = scat_ops._scatter_and_degrees

        def broken(values, ids, num_segments, **kw):
            values, ids = change(values, ids)
            return launch(values, ids, num_segments, **kw)
        monkeypatch.setattr(scat_ops, "_scatter_and_degrees", broken)
    else:
        launch = hist_ops._histogram_and_degrees

        def broken(img, **kw):
            (img,) = change(img)
            return launch(img, **kw)
        monkeypatch.setattr(hist_ops, "_histogram_and_degrees", broken)
    return launch


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_an_answer_altered_where_it_is_produced(monkeypatch, cell):
    from repro.kernels.histogram import ops as hist_ops
    from repro.kernels.scatter_add import ops as scat_ops

    mod, attr = ((scat_ops, "_scatter_and_degrees") if cell == "moe-skewed"
                 else (hist_ops, "_histogram_and_degrees"))
    launch = getattr(mod, attr)

    def altered(*a, **kw):
        out, deg = launch(*a, **kw)
        return out, deg.at[0].add(1.0)
    monkeypatch.setattr(mod, attr, altered)
    result = run(cell)
    assert not result["correct"]
    assert failing(result) & {"counter_gap", "model_rel_gap"}


@pytest.mark.parametrize("cell", ["hist-solid", "moe-skewed"])
def test_half_of_the_batch_left_out(monkeypatch, cell):
    def half(*arrays):
        return tuple(a[:a.shape[0] // 2] for a in arrays)
    _patch_degrees(monkeypatch, cell, half)
    result = run(cell)
    assert not result["correct"]
    assert "counter_gap" in failing(result)


@pytest.mark.parametrize("cell", ["hist-solid", "moe-skewed"])
def test_a_step_that_returns_its_previous_state(monkeypatch, cell):
    from repro.analysis.providers import kernel as kernel_provider

    collect = kernel_provider.InstrumentedKernelProvider.collect
    first = {}

    def stale(self, spec, device):
        if "cset" not in first:
            first["cset"] = collect(self, spec, device)
        return first["cset"]
    monkeypatch.setattr(kernel_provider.InstrumentedKernelProvider,
                        "collect", stale)
    result = run(cell)
    assert not result["correct"]


@pytest.mark.parametrize("cell", ["hist-solid", "moe-skewed"])
def test_a_step_that_returns_the_previous_state_of_its_variant(monkeypatch,
                                                              cell):
    """The provider hands back the counters it collected for the previous
    request of the same variant."""
    from repro.analysis.providers import kernel as kernel_provider

    collect = kernel_provider.InstrumentedKernelProvider.collect
    previous = {}

    def stale(self, spec, device):
        variant = spec.label.rsplit("-", 1)[1]
        fresh = collect(self, spec, device)
        out = previous.get(variant, fresh)
        previous[variant] = fresh
        return out
    monkeypatch.setattr(kernel_provider.InstrumentedKernelProvider,
                        "collect", stale)
    result = run(cell)
    assert not result["correct"]
    assert "counter_gap" in failing(result)


def test_a_launch_that_reads_the_previous_image_of_its_variant(monkeypatch):
    """The histogram launch is handed the device buffer of the previous
    image profiled as the same variant, not the new one."""
    from repro.kernels.histogram import ops as hist_ops

    launch = hist_ops._histogram_and_degrees
    previous = {}

    def stale(img, **kw):
        out = previous.get(kw["variant"], img)
        previous[kw["variant"]] = img
        return launch(out, **kw)
    monkeypatch.setattr(hist_ops, "_histogram_and_degrees", stale)
    result = run("hist-solid")
    assert not result["correct"]
    assert "counter_gap" in failing(result)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_a_sound_run_is_correct_and_the_control_is_not(cell):
    sound = run(cell)
    assert sound["correct"], sound["checks"]
    control = run(cell, control=True)
    assert not control["correct"]
    assert failing(control) == {"model_rel_gap"}
    assert np.isfinite(control["checks"]["model_rel_gap"]["value"])
