"""Jit'd public wrappers for the histogram kernels + profiler glue.

Instruction-class mapping (paper §2 / §4):

  * unweighted, result-unread  -> POPC class (Ampere's ``ATOMS.POPC.INC``:
    the compiler's cheap population-count increment; our one-hot popcount
    reduction is literally that operation),
  * unweighted, ``force_fao``  -> FAO class (the paper forces ``ATOMS.ADD``
    back with a dummy read of the atomic's result),
  * weighted (f32 accumulate)  -> CAS class (FP atomics lower to
    compare-and-swap loops on the GPU; the read-modify-verify analogue).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import counters as counters_mod
from repro.core import timing
from repro.kernels import instrumentation as instr
from repro.kernels.histogram import kernel as hk


def _pad(img: jnp.ndarray, tile: int) -> tuple[jnp.ndarray, int]:
    n = img.shape[0]
    pad = (-n) % tile
    if pad:
        img = jnp.concatenate(
            [img, jnp.zeros((pad, img.shape[1]), img.dtype)], axis=0)
    return img, pad


@functools.partial(jax.jit, static_argnames=("num_bins", "variant", "tile"))
def histogram(img: jnp.ndarray, *, num_bins: int = 256,
              variant: str = "hist",
              tile: int = hk.DEFAULT_TILE) -> jnp.ndarray:
    """(C, num_bins) int32 histogram; `variant` is 'hist' or 'hist2'."""
    reorder = {"hist": False, "hist2": True}[variant]
    padded, pad = _pad(img.astype(jnp.int32), tile)
    out = hk.histogram_pallas(padded, num_bins=num_bins, reorder=reorder,
                              tile=tile)
    if pad:  # padding pixels are zeros: remove their channel-0-value counts
        out = out.at[:, 0].add(-pad)
    return out


@functools.partial(jax.jit, static_argnames=("num_bins", "variant", "tile"))
def histogram_weighted(img: jnp.ndarray, weights: jnp.ndarray, *,
                       num_bins: int = 256, variant: str = "hist",
                       tile: int = hk.DEFAULT_TILE) -> jnp.ndarray:
    reorder = {"hist": False, "hist2": True}[variant]
    padded, pad = _pad(img.astype(jnp.int32), tile)
    w = jnp.concatenate([weights.astype(jnp.float32),
                         jnp.zeros((pad,), jnp.float32)]) if pad else weights
    return hk.histogram_pallas(padded, num_bins=num_bins, reorder=reorder,
                               tile=tile, weights=w.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("num_bins", "variant", "tile"))
def _histogram_and_degrees(img: jnp.ndarray, *, num_bins: int, variant: str,
                           tile: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One jitted instrumented launch: (C, num_bins) counts + wave degrees."""
    reorder = {"hist": False, "hist2": True}[variant]
    padded, pad = _pad(img.astype(jnp.int32), tile)
    hist, degrees = hk.histogram_pallas(
        padded, num_bins=num_bins, reorder=reorder, tile=tile,
        instrumented=True)
    if pad:
        hist = hist.at[:, 0].add(-pad)
    return hist, degrees


def histogram_instrumented(
    img: jnp.ndarray,
    *,
    num_bins: int = 256,
    variant: str = "hist",
    tile: int = hk.DEFAULT_TILE,
    force_fao: bool = False,
    weighted: bool = False,
    num_cores: int = 8,
    waves_per_tile: Optional[int] = None,
    pipeline_depth: int = 2,
) -> tuple[jnp.ndarray, counters_mod.WaveTrace]:
    """Histogram + the wave trace its instrumentation emits.

    The committed-index stream is identical for the weighted variant, so
    the integer instrumented kernel supplies the trace in both cases; only
    the job class differs (CAS for weighted f32 accumulation).

    ``waves_per_tile``/``pipeline_depth`` describe the launch geometry the
    occupancy model sees; ``waves_per_tile`` defaults to the kernel's own
    tiling (``tile * channels / LANES``) and, when overridden, also governs
    the round-robin core assignment — it *is* the scheduled tile size.
    """
    hist, degrees = _histogram_and_degrees(img, num_bins=num_bins,
                                           variant=variant, tile=tile)
    deg = np.asarray(degrees)
    num_waves = deg.shape[0]
    if waves_per_tile is None:
        waves_per_tile = default_waves_per_tile(img, tile)
    tiles = np.arange(num_waves) // max(waves_per_tile, 1)
    job_class = histogram_job_class(force_fao=force_fao, weighted=weighted)
    trace = counters_mod.WaveTrace(
        degree=deg,
        job_class=np.full(num_waves, job_class, np.int32),
        core=(tiles % num_cores).astype(np.int32),
        lanes_active=np.full(num_waves, float(instr.LANES)),
        waves_per_tile=waves_per_tile,
        pipeline_depth=pipeline_depth,
    )
    return hist, trace


def image_bytes(img: jnp.ndarray) -> float:
    """HBM read traffic of the launch: 1 byte/channel as in the paper."""
    return float(img.shape[0] * img.shape[1])


def histogram_job_class(*, force_fao: bool, weighted: bool) -> int:
    """Instruction-class mapping (module docstring): CAS > FAO > POPC."""
    if weighted:
        return timing.CAS
    if force_fao:
        return timing.FAO
    return timing.POPC


def default_waves_per_tile(img, tile: int = hk.DEFAULT_TILE) -> int:
    """The kernel's own tiling: waves issued per grid tile."""
    return (tile * np.shape(img)[1]) // instr.LANES


def committed_index_stream(img, *, num_bins: int = 256,
                           variant: str = "hist",
                           tile: int = hk.DEFAULT_TILE) -> np.ndarray:
    """The flat bin-index stream the kernel commits, synthesized in numpy.

    Mirrors ``kernel.commit_layout`` and ``kernel._commit_bins``
    (zero-padding to a tile multiple, channel-offset bins, per-lane
    channel rotation for hist2, step-major ordering within each commit
    group) without running Pallas —
    the modeled counter source the instrumented kernel cross-validates.
    The per-commit-group transform never mixes rows across tiles, so it is
    applied to the whole padded image at once.
    """
    reorder = {"hist": False, "hist2": True}[variant]
    a = np.asarray(img).astype(np.int32)
    pad = (-a.shape[0]) % tile
    if pad:
        a = np.concatenate([a, np.zeros((pad, a.shape[1]), a.dtype)])
    t, c = a.shape
    g = instr.COMMIT_GROUP
    step = np.broadcast_to(np.arange(c, dtype=np.int32)[None, :], (t, c))
    if reorder:
        lane = ((np.arange(t, dtype=np.int32) % tile)[:, None]
                + np.zeros((1, c), np.int32))
        ch = (step + lane) % c
        vals = np.take_along_axis(a, ch, axis=1)
    else:
        ch = step
        vals = a
    bins = ch * num_bins + vals                           # (t, c) pixel-major
    bins = bins.reshape(t // g, g, c).transpose(0, 2, 1)  # step-major
    return bins.reshape(t * c)


def collect_counters(
    img,
    *,
    label: str = "",
    num_bins: int = 256,
    variant: str = "hist",
    tile: int = hk.DEFAULT_TILE,
    force_fao: bool = False,
    weighted: bool = False,
    num_cores: int = 8,
    waves_per_tile: Optional[int] = None,
    pipeline_depth: int = 2,
    bytes_read: Optional[float] = None,
    flops: float = 0.0,
    overhead_cycles: float = 500.0,
) -> counters_mod.CounterSet:
    """Run the instrumented kernel and return its counters as a CounterSet.

    The provider hook: ``repro.analysis.providers.InstrumentedKernelProvider``
    calls this so every counter (``O``, ``N``, active lanes) is read back
    from the Pallas launch, not synthesized.
    """
    img = jnp.asarray(img)
    _, trace = histogram_instrumented(
        img, num_bins=num_bins, variant=variant, tile=tile,
        force_fao=force_fao, weighted=weighted, num_cores=num_cores,
        waves_per_tile=waves_per_tile, pipeline_depth=pipeline_depth)
    return counters_mod.CounterSet.from_trace(
        trace, label=label, num_cores=num_cores,
        bytes_read=image_bytes(img) if bytes_read is None else bytes_read,
        flops=flops, overhead_cycles=overhead_cycles,
        source="kernel", meta={"op": "histogram", "variant": variant})
