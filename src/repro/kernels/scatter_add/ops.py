"""Jit'd public wrappers for scatter-add / bincount + instrumentation glue."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import counters as counters_mod
from repro.core import timing
from repro.kernels import instrumentation as instr
from repro.kernels.scatter_add import kernel as sk


def _pad_n(ids: jnp.ndarray, values: jnp.ndarray, tile: int):
    n = ids.shape[0]
    pad = (-n) % tile
    if pad:
        ids = jnp.concatenate([ids, jnp.zeros((pad,), ids.dtype)])
        values = jnp.concatenate(
            [values, jnp.zeros((pad,) + values.shape[1:], values.dtype)])
    return ids, values, pad


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "tile", "seg_block"))
def scatter_add(values: jnp.ndarray, ids: jnp.ndarray, *, num_segments: int,
                tile: int = sk.DEFAULT_TILE,
                seg_block: int = sk.DEFAULT_SEG_BLOCK) -> jnp.ndarray:
    """Segment-sum: (N, D) values + (N,) ids -> (num_segments, D) f32.

    Padding rows carry zero values, so their (id 0) contribution is zero.
    """
    ids, values, _ = _pad_n(ids.astype(jnp.int32), values, tile)
    return sk.scatter_add_pallas(values, ids, num_segments, tile=tile,
                                 seg_block=seg_block)


@functools.partial(jax.jit, static_argnames=("num_segments", "tile"))
def bincount(ids: jnp.ndarray, *, num_segments: int,
             tile: int = sk.DEFAULT_TILE) -> jnp.ndarray:
    """(num_segments,) int32 counts (the MoE dispatch histogram)."""
    n = ids.shape[0]
    ids_p, _, pad = _pad_n(ids.astype(jnp.int32),
                           jnp.zeros((n, 1), jnp.float32), tile)
    out = sk.bincount_pallas(ids_p, num_segments, tile=tile)
    if pad:  # padding ids are 0: remove their counts
        out = out.at[0].add(-pad)
    return out


def committed_id_stream(ids, num_segments: int, *,
                        tile: int = sk.DEFAULT_TILE) -> np.ndarray:
    """The flat id stream the instrumented kernel commits (numpy).

    Pads to a tile multiple with *unique out-of-range* sentinel ids: they
    match no segment block (contributing nothing) and add no artificial
    conflicts to the degree counters.  ``instrumented_scatter_add`` feeds
    this exact stream to the kernel, so trace-side synthesis and in-kernel
    instrumentation see identical commit groups.
    """
    ids = np.asarray(ids).astype(np.int32).reshape(-1)
    pad = (-ids.shape[0]) % tile
    if pad:
        seg_blocks = -(-num_segments // min(sk.DEFAULT_SEG_BLOCK, num_segments))
        base = seg_blocks * min(sk.DEFAULT_SEG_BLOCK, num_segments)
        sentinel = base + np.arange(pad, dtype=np.int32)
        ids = np.concatenate([ids, sentinel]).astype(np.int32)
    return ids


def default_waves_per_tile(tile: int = sk.DEFAULT_TILE) -> int:
    """The kernel's own tiling: waves issued per grid tile."""
    return tile // instr.LANES


def collect_counters(
    ids,
    values,
    num_segments: int,
    *,
    label: str = "",
    tile: int = sk.DEFAULT_TILE,
    num_cores: int = 8,
    job_class: int = timing.FAO,
    waves_per_tile: int | None = None,
    pipeline_depth: int = 2,
    bytes_read: float | None = None,
    flops: float = 0.0,
    overhead_cycles: float = 500.0,
) -> counters_mod.CounterSet:
    """Run the instrumented kernel and return its counters as a CounterSet.

    The provider hook: ``repro.analysis.providers.InstrumentedKernelProvider``
    calls this so every counter is read back from the Pallas launch, not
    synthesized.
    """
    _, counters = instrumented_scatter_add(
        ids, values, num_segments, tile=tile, num_cores=num_cores,
        job_class=job_class, waves_per_tile=waves_per_tile,
        pipeline_depth=pipeline_depth)
    if bytes_read is None:
        bytes_read = float(np.asarray(ids).size * 4)
    return counters_mod.CounterSet.from_trace(
        counters["trace"], label=label, num_cores=num_cores,
        bytes_read=bytes_read, flops=flops, overhead_cycles=overhead_cycles,
        source="kernel", meta={"op": "scatter_add"})


_scatter_and_degrees = jax.jit(
    functools.partial(sk.scatter_add_pallas, instrumented=True),
    static_argnames=("num_segments", "tile"))


def instrumented_scatter_add(
    ids,
    values,
    num_segments: int,
    *,
    tile: int = sk.DEFAULT_TILE,
    num_cores: int = 8,
    job_class: int = timing.FAO,
    waves_per_tile: int | None = None,
    pipeline_depth: int = 2,
):
    """Scatter-add + the paper-Table-1 counters its instrumentation emits.

    Returns (out, counters) where counters has the basic quantities
    ``N`` (wave jobs), ``O`` (serialization transactions), per-wave
    ``degree``, and a ready-to-profile ``trace``.

    ``waves_per_tile`` (default: the kernel tiling ``tile / LANES``) and
    ``pipeline_depth`` set the trace's launch geometry directly — no
    post-construction mutation needed.
    """
    n = np.asarray(ids).reshape(-1).shape[0]
    ids = jnp.asarray(
        committed_id_stream(ids, num_segments, tile=tile))
    values = jnp.asarray(values, jnp.float32)
    if values.ndim == 1:
        values = values[:, None]
    pad = ids.shape[0] - n
    if pad:
        values = jnp.concatenate(
            [values, jnp.zeros((pad,) + values.shape[1:], values.dtype)])
    out, deg = _scatter_and_degrees(values, ids, num_segments, tile=tile)
    deg = np.asarray(deg)
    num_waves = deg.shape[0]
    if waves_per_tile is None:
        waves_per_tile = tile // instr.LANES
    tiles = np.arange(num_waves) // max(waves_per_tile, 1)
    trace = counters_mod.WaveTrace(
        degree=deg,
        job_class=np.full(num_waves, job_class, np.int32),
        core=(tiles % num_cores).astype(np.int32),
        lanes_active=np.full(num_waves, float(instr.LANES)),
        waves_per_tile=waves_per_tile,
        pipeline_depth=pipeline_depth,
    )
    counters = {
        "N": float(num_waves),
        "O": float(deg.sum()),
        "degree": deg,
        "trace": trace,
    }
    return out, counters
