"""Pallas TPU histogram kernel — the paper's case-study kernel, TPU-native.

GPU original (paper Listings 1-2): each thread reads a pixel's channels and
``atomicAdd``s into a shared-memory sub-histogram; Listing 2 rotates the
channel processing order by thread id so same-color neighbours hit
different sub-histogram banks.

TPU adaptation: there is no atomic unit; the idiomatic TPU histogram keeps
the (channels x bins) accumulator resident in VMEM across the grid (output
block with a constant index_map) and commits each tile of the stream as
one contraction of one-hots.  The queuing model prices that commit as a
unit that serializes duplicate destinations; whether this kernel's chip
time follows it is an open question (``PERF.md``).  Two variants:

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
row.  Each grid step streams one tile (``tile*C/128`` rows, several
waves) HBM->VMEM and adds the channel offsets from lane iotas.  The
commit factors each channel-offset bin ``b`` as ``(b // FACTOR,
b % FACTOR)``: two narrow one-hots, ``(rows, hi_bins, 128)`` and
``(rows, FACTOR, 128)`` in bfloat16, contracted over the lanes on the MXU
(batched over rows, float32 results) give every row's joint counts, and
their sum over rows is the tile's ``(hi_bins, FACTOR)`` histogram, which
is added into an int32 accumulator resident in VMEM (constant index_map)
for the whole launch — the scratchpad residency pattern the paper's
kernels use shared memory for.  Counts stay exact: 0 and 1 are exact in
bfloat16, a step adds at most ``rows*128`` to a cell (far below 2^24),
and the sum across steps is int32.  The wrapper reads the accumulator
back as ``(C, num_bins)``.  Nothing inside the kernel changes the shape
of the stream, so Mosaic compiles it for the TPU.  The weighted kernel
keeps a per-wave one-hot reduction on the VPU: its float32 weights would
be rounded on the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.kernels import instrumentation as instr

DEFAULT_TILE = 2048
FACTOR = 32         # bins per lo one-hot of the factored commit


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


def _hi_bins(total_bins: int) -> int:
    """Rows of the factored accumulator: ``ceil(total_bins / FACTOR)``,
    padded to a multiple of 8 sublanes."""
    return -(-total_bins // (FACTOR * 8)) * 8


def _factored_counts(bins: jnp.ndarray, num_hi: int) -> jnp.ndarray:
    """(num_hi, FACTOR) int32 counts of a (rows, ROW) bin block.

    Row ``r``'s one-hots of ``bins // FACTOR`` and ``bins % FACTOR``,
    contracted over the lanes, count its lanes per (hi, lo) pair; the sum
    over rows is the block's histogram of ``hi * FACTOR + lo = bins``.
    """
    hi = _onehot(jax.lax.div(bins, FACTOR), num_hi).astype(jnp.bfloat16)
    lo = _onehot(jax.lax.rem(bins, FACTOR), FACTOR).astype(jnp.bfloat16)
    joint = jax.lax.dot_general(
        hi, lo, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return joint.sum(axis=0).astype(jnp.int32)


def _hist_kernel(vals_ref, out_ref, deg_ref=None, *, num_bins: int,
                 channels: int, reorder: bool):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = _commit_bins(vals_ref[...], num_bins=num_bins,
                        channels=channels, reorder=reorder)
    out_ref[...] += _factored_counts(bins, out_ref.shape[0])
    if deg_ref is not None:
        # one tile per step: its block holds a degree per wave, in order
        lane = jax.lax.broadcasted_iota(jnp.int32, deg_ref.shape, 2)
        deg = jnp.zeros(deg_ref.shape, jnp.float32)
        for w, d in enumerate(instr.wave_degrees(bins)):
            deg = jnp.where(lane == w, d, deg)
        deg_ref[...] = deg


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
    total_bins = c * num_bins
    vals = commit_layout(img, reorder=reorder, tile=tile)
    params = dict(num_bins=num_bins, channels=c, reorder=reorder)

    if weights is not None:
        w = commit_layout(jnp.broadcast_to(weights[:, None], (n, c)),
                          reorder=False, tile=tile)
        wave_spec = pl.BlockSpec((instr.WAVE_ROWS, instr.ROW),
                                 lambda i: (i, 0))
        out = pl.pallas_call(
            functools.partial(_hist_weighted_kernel, **params),
            grid=(n * c // instr.LANES,),
            in_specs=[wave_spec, wave_spec],
            out_specs=pl.BlockSpec((total_bins, instr.ROW),
                                   lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((total_bins, instr.ROW),
                                           jnp.float32),
            interpret=kernels.interpret_mode(),
        )(vals, w)
        return out.sum(axis=1).reshape(c, num_bins)

    grid = (n // tile,)
    tile_spec = pl.BlockSpec((tile * c // instr.ROW, instr.ROW),
                             lambda i: (i, 0))
    counts_shape = (_hi_bins(total_bins), FACTOR)
    counts_spec = pl.BlockSpec(counts_shape, lambda i: (0, 0))
    counts = jax.ShapeDtypeStruct(counts_shape, jnp.int32)

    def per_channel(joint):
        return joint.reshape(-1)[:total_bins].reshape(c, num_bins)

    if instrumented:
        out, deg = pl.pallas_call(
            functools.partial(_hist_kernel, **params),
            grid=grid,
            in_specs=[tile_spec],
            out_specs=[counts_spec,
                       pl.BlockSpec((1, 1, waves_per_tile),
                                    lambda i: (i, 0, 0))],
            out_shape=[counts,
                       jax.ShapeDtypeStruct((n // tile, 1, waves_per_tile),
                                            jnp.float32)],
            interpret=kernels.interpret_mode(),
        )(vals)
        return per_channel(out), deg.reshape(-1)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, **params),
        grid=grid,
        in_specs=[tile_spec],
        out_specs=counts_spec,
        out_shape=counts,
        interpret=kernels.interpret_mode(),
    )(vals)
    return per_channel(out)
