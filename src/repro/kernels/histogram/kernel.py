"""Pallas TPU histogram kernel — the paper's case-study kernel, TPU-native.

GPU original (paper Listings 1-2): each thread reads a pixel's channels and
``atomicAdd``s into a shared-memory sub-histogram; Listing 2 rotates the
channel processing order by thread id so same-color neighbours hit
different sub-histogram banks.

TPU adaptation: there is no atomic unit; the idiomatic TPU histogram keeps
the (channels x bins) accumulator resident in VMEM across the grid (output
block with a constant index_map) and commits each wave with a one-hot
reduction.  The queuing model prices that commit as a unit that
serializes duplicate destinations; whether this kernel's chip time
follows it is an open question (``PERF.md``).  Two variants:

  * ``hist``   — channels processed in natural order (Listing 1): a
    solid-color tile drives every lane of a wave into one bin.
  * ``hist2``  — channel order rotated per lane (Listing 2): a solid-color
    tile spreads each commit group over ``channels`` distinct bins,
    cutting the serialization degree by ~channels.

Both produce identical histograms (tests assert vs ``ref.py``); they
differ in the *conflict structure* of the committed index stream, which
the instrumented variants measure in-kernel (``instrumentation.py``).

Block layout: the wrapper lays the image out in commit order before the
launch (``commit_layout``): a lane-dense ``(N*C/128, 128)`` array whose
row-major order is the committed stream, four 32-lane commit groups per
row.  Each grid step streams one wave (8 rows = 1024 commits) HBM->VMEM;
the kernel adds the channel offsets from lane iotas, commits the wave as
one one-hot popcount, and keeps a ``(C*num_bins, 128)`` per-lane
accumulator resident in VMEM (constant index_map) for the whole launch —
the scratchpad residency pattern the paper's kernels use shared memory
for.  The wrapper sums the 128 lane partials.  Nothing inside the kernel
changes the shape of the stream, so Mosaic compiles it for the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.kernels import instrumentation as instr

DEFAULT_TILE = 2048


def commit_layout(img: jnp.ndarray, *, reorder: bool,
                  tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """(N, C) channel values -> (N*C/ROW, ROW) values in commit order.

    The GPU kernel's warp issues channel step s for all 32 of its pixels
    together (Listing 1's inner loop), so the committed stream is
    step-major within each 32-pixel group — that ordering is what the
    conflict structure (and our wave_degrees instrumentation) sees.
    ``reorder`` rotates the channel read at step s by the pixel's lane in
    the tile (Listing 2).  ``histogram.ops.committed_index_stream`` is the
    numpy mirror of this layout plus the kernel's channel offsets.
    """
    n, c = img.shape
    g = instr.COMMIT_GROUP
    if reorder:
        # step s of pixel p reads channel (s + p % tile) % c: rotate the
        # channel axis by the pixel's lane (selects, not a gather)
        rot = (jnp.arange(n, dtype=jnp.int32) % tile % c)[:, None]
        img = functools.reduce(
            lambda acc, j: jnp.where(rot == j, jnp.roll(img, -j, axis=1),
                                     acc),
            range(1, c), img)
    img = img.reshape(n // g, g, c).transpose(0, 2, 1)  # step-major
    return img.reshape(-1, instr.ROW)


def _commit_bins(vals: jnp.ndarray, *, num_bins: int, channels: int,
                 reorder: bool) -> jnp.ndarray:
    """Channel-offset bin ids of a commit-ordered ``(rows, ROW)`` block.

    Lane ``j`` of a row belongs to commit group ``j // 32``, i.e. channel
    step ``(j // 32) % channels``; with ``reorder`` the channel read at
    that step is rotated by the pixel's lane ``j % 32`` (the tile offset
    of a 32-pixel group is a multiple of ``channels``).
    """
    g = instr.COMMIT_GROUP
    lane = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    step = jax.lax.rem(jax.lax.div(lane, g), channels)
    ch = jax.lax.rem(step + jax.lax.rem(lane, g), channels) if reorder \
        else step
    return ch * num_bins + vals


def _onehot(bins: jnp.ndarray, total_bins: int) -> jnp.ndarray:
    """(rows, total_bins, ROW) one-hot of a (rows, ROW) bin block."""
    r, w = bins.shape
    return bins[:, None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (r, total_bins, w), 1)


def _hist_kernel(vals_ref, out_ref, deg_ref=None, *, num_bins: int,
                 channels: int, reorder: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = _commit_bins(vals_ref[...], num_bins=num_bins,
                        channels=channels, reorder=reorder)
    onehot = _onehot(bins, out_ref.shape[0])
    out_ref[...] += onehot.astype(jnp.int32).sum(axis=0)
    if deg_ref is not None:
        # one wave per step: lane (i % waves_per_tile) of this tile's block
        (deg,) = instr.wave_degrees(bins)
        lane = jax.lax.broadcasted_iota(jnp.int32, deg_ref.shape, 2)
        wave = jax.lax.rem(i, deg_ref.shape[2])
        deg_ref[...] = jnp.where(lane == wave, deg, deg_ref[...])


def _hist_weighted_kernel(vals_ref, w_ref, out_ref, *, num_bins: int,
                          channels: int, reorder: bool):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = _commit_bins(vals_ref[...], num_bins=num_bins,
                        channels=channels, reorder=reorder)
    onehot = _onehot(bins, out_ref.shape[0])
    out_ref[...] += (onehot.astype(jnp.float32)
                     * w_ref[...][:, None, :]).sum(axis=0)


def histogram_pallas(
    img: jnp.ndarray,
    *,
    num_bins: int = 256,
    reorder: bool = False,
    tile: int = DEFAULT_TILE,
    weights: jnp.ndarray | None = None,
    instrumented: bool = False,
):
    """Launch the histogram kernel.  img: (N, C) ints, N % tile == 0.

    Returns (C, num_bins) counts — int32, or f32 when ``weights`` given.
    With ``instrumented=True`` additionally returns per-wave serialization
    degrees, shape (N*C/LANES,).
    """
    n, c = img.shape
    assert n % tile == 0, "pad in ops.py before calling"
    assert (tile * c) % instr.LANES == 0
    assert (instr.ROW // instr.COMMIT_GROUP) % c == 0, \
        "a stream row must hold whole channel rounds"
    waves_per_tile = (tile * c) // instr.LANES
    grid = (n * c // instr.LANES,)
    total_bins = c * num_bins
    vals = commit_layout(img, reorder=reorder, tile=tile)
    wave_spec = pl.BlockSpec((instr.WAVE_ROWS, instr.ROW), lambda i: (i, 0))
    out_spec = pl.BlockSpec((total_bins, instr.ROW), lambda i: (0, 0))
    params = dict(num_bins=num_bins, channels=c, reorder=reorder)

    def per_channel(lane_partials):
        return lane_partials.sum(axis=1).reshape(c, num_bins)

    if weights is not None:
        w = commit_layout(jnp.broadcast_to(weights[:, None], (n, c)),
                          reorder=False, tile=tile)
        out = pl.pallas_call(
            functools.partial(_hist_weighted_kernel, **params),
            grid=grid,
            in_specs=[wave_spec, wave_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((total_bins, instr.ROW),
                                           jnp.float32),
            interpret=kernels.interpret_mode(),
        )(vals, w)
        return per_channel(out)

    if instrumented:
        out, deg = pl.pallas_call(
            functools.partial(_hist_kernel, **params),
            grid=grid,
            in_specs=[wave_spec],
            out_specs=[out_spec,
                       pl.BlockSpec((1, 1, waves_per_tile),
                                    lambda i: (i // waves_per_tile, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((total_bins, instr.ROW),
                                            jnp.int32),
                       jax.ShapeDtypeStruct((n // tile, 1, waves_per_tile),
                                            jnp.float32)],
            interpret=kernels.interpret_mode(),
        )(vals)
        return per_channel(out), deg.reshape(-1)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, **params),
        grid=grid,
        in_specs=[wave_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((total_bins, instr.ROW), jnp.int32),
        interpret=kernels.interpret_mode(),
    )(vals)
    return per_channel(out)
