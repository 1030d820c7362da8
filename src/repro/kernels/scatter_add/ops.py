"""Jit'd public wrappers for scatter-add / bincount + instrumentation glue."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import timing
from repro.kernels import instrumentation as instr
from repro.kernels.scatter_add import kernel as sk
from repro.obs import telemetry


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
        sentinel = _sentinel_base(num_segments) + np.arange(pad, dtype=np.int32)
        ids = np.concatenate([ids, sentinel]).astype(np.int32)
    return ids


def _sentinel_base(num_segments: int) -> int:
    """The first id past the kernel's last segment block."""
    block = min(sk.DEFAULT_SEG_BLOCK, num_segments)
    return -(-num_segments // block) * block


def committed_id_stream_device(ids: jnp.ndarray, num_segments: int, *,
                               tile: int = sk.DEFAULT_TILE) -> jnp.ndarray:
    """``committed_id_stream`` inside a jitted program, for ids made on the
    device: the same flat int32 stream with the same sentinels."""
    ids = ids.reshape(-1).astype(jnp.int32)
    pad = (-ids.shape[0]) % tile
    if pad:
        sentinel = _sentinel_base(num_segments) + jnp.arange(pad,
                                                            dtype=jnp.int32)
        ids = jnp.concatenate([ids, sentinel])
    return ids


def default_waves_per_tile(tile: int = sk.DEFAULT_TILE) -> int:
    """The kernel's own tiling: waves issued per grid tile."""
    return tile // instr.LANES


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
    with telemetry.span("kernel.prepare"):
        n = np.asarray(ids).reshape(-1).shape[0]
        ids = committed_id_stream(ids, num_segments, tile=tile)
        values = np.asarray(values, np.float32)
        if values.ndim == 1:
            values = values[:, None]
        pad = ids.shape[0] - n
        if pad:
            values = np.concatenate(
                [values, np.zeros((pad,) + values.shape[1:], values.dtype)])
    values, ids = instr.to_device(values, ids)
    out, deg = instr.launch(_scatter_and_degrees, values, ids, num_segments,
                            tile=tile)
    if waves_per_tile is None:
        waves_per_tile = default_waves_per_tile(tile)
    trace = instr.wave_trace(deg, job_class=job_class, num_cores=num_cores,
                             waves_per_tile=waves_per_tile,
                             pipeline_depth=pipeline_depth)
    counters = {
        "N": float(deg.shape[0]),
        "O": float(deg.sum()),
        "degree": deg,
        "trace": trace,
    }
    return out, counters


@functools.lru_cache(maxsize=None)
def count_program(stream_fn, num_segments: int, tile: int = sk.DEFAULT_TILE):
    """One jitted device program: ``stream_fn(*args)`` makes an id stream
    on the device, which is committed as ``committed_id_stream`` would and
    counted by the instrumented scatter-add with unit values.

    The program returns ``(counts (num_segments,), packed)``, where
    ``packed`` holds the per-wave degrees and, last, the largest count as
    a share of the stream's ids, so one read-back brings both.  Cached per
    ``stream_fn`` (hold one function per stream, e.g.
    ``repro.models.moe.expert_stream``), so each shape compiles once.
    """
    def _routed_count(*args):
        ids = stream_fn(*args)
        stream = committed_id_stream_device(ids, num_segments, tile=tile)
        ones = jnp.ones((stream.shape[0], 1), jnp.float32)
        counts, deg = sk.scatter_add_pallas(ones, stream, num_segments,
                                            tile=tile, instrumented=True)
        counts = counts[:, 0]
        share = jnp.max(counts) / ids.size
        return counts, jnp.concatenate([deg, share[None]])

    return jax.jit(_routed_count)


def instrumented_count(program, *args, num_cores: int = 8,
                       job_class: int = timing.FAO,
                       waves_per_tile: int | None = None,
                       pipeline_depth: int = 2,
                       tile: int = sk.DEFAULT_TILE):
    """Launch a ``count_program`` on device-resident ``args``; nothing is
    copied to the device and only the degrees come back.

    Returns ``(counts on the device, counters)`` as
    ``instrumented_scatter_add`` does, with ``max_load_share``: the
    largest count over the stream's ids.
    """
    counts, packed = instr.launch(program, *args)
    deg, share = packed[:-1], float(packed[-1])
    trace = instr.wave_trace(
        deg, job_class=job_class, num_cores=num_cores,
        waves_per_tile=waves_per_tile or default_waves_per_tile(tile),
        pipeline_depth=pipeline_depth, max_load_share=share)
    return counts, {"N": float(deg.shape[0]), "O": float(deg.sum()),
                    "degree": deg, "trace": trace, "max_load_share": share}
