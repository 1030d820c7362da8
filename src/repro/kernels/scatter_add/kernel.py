"""Pallas TPU scatter-add (segment-sum) and bincount kernels.

These are the production faces of the paper's hot spot inside the
framework: MoE token->expert dispatch counting (bincount), expert-output
combine and embedding-gradient accumulation (scatter-add).  The GPU
implementations of all three are shared-memory-atomic loops — the programs
the paper's model exists to diagnose.

TPU adaptation: scatter-add becomes a one-hot matmul on the MXU
(``onehot(ids).T @ values``), with the destination accumulator resident in
VMEM across grid steps (constant output index_map).  Duplicate ids within
a commit wave serialize in the VPU/MXU commit path; the instrumented
variants measure that serialization degree in-kernel.

Blocking: a 2-D grid (segment-block j outer, token tile i inner) so the
segment axis can exceed VMEM (embedding-gradient case: vocab up to 256k):
each (j, i) step accumulates tile i's contribution to segment rows
[j*SB, (j+1)*SB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.kernels import instrumentation as instr

DEFAULT_TILE = 2048
DEFAULT_SEG_BLOCK = 4096

# The one-hot matmul's update rows are deliberately commit-group aligned
# (D a multiple of 32 keeps the MXU contraction dense); the bank-stride
# hazard the lint models is accepted here.
# repro: noqa KERN002


def _scatter_kernel(ids_ref, val_ref, out_ref, *, seg_block: int):
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]                      # (TILE,)
    vals = val_ref[...]                     # (TILE, D)
    local = ids - j * seg_block
    t = ids.shape[0]
    onehot = (local[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (t, seg_block), 1))
    out_ref[...] += jax.lax.dot_general(
        onehot.astype(vals.dtype), vals,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _bincount_kernel(ids_ref, out_ref, *, num_segments: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]
    t = ids.shape[0]
    onehot = (ids[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (t, num_segments), 1))
    out_ref[...] += onehot.astype(jnp.int32).sum(axis=0)[None, :]


def _scatter_instrumented_kernel(ids_ref, rows_ref, val_ref, out_ref,
                                 deg_ref, *, seg_block: int):
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]
    vals = val_ref[...]
    local = ids - j * seg_block
    t = ids.shape[0]
    onehot = (local[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (t, seg_block), 1))
    out_ref[...] += jax.lax.dot_general(
        onehot.astype(vals.dtype), vals,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    # the degrees depend only on the id stream, but tile i's block is
    # written back after every (j, i) step, so every step writes all of it
    degs = instr.wave_degrees(rows_ref[...])
    lane = jax.lax.broadcasted_iota(jnp.int32, deg_ref.shape, 2)
    out = jnp.zeros(deg_ref.shape, jnp.float32)
    for w, deg in enumerate(degs):
        out = jnp.where(lane == w, deg, out)
    deg_ref[...] = out


def scatter_add_pallas(
    values: jnp.ndarray,
    ids: jnp.ndarray,
    num_segments: int,
    *,
    tile: int = DEFAULT_TILE,
    seg_block: int = DEFAULT_SEG_BLOCK,
    instrumented: bool = False,
):
    """values (N, D) f32/bf16, ids (N,) int32 in [0, num_segments).

    With ``instrumented=True`` additionally returns per-wave serialization
    degrees of the id stream, shape (N/LANES,).
    """
    n, d = values.shape
    assert n % tile == 0, "pad in ops.py"
    assert num_segments % seg_block == 0 or num_segments < seg_block
    seg_block = min(seg_block, num_segments)
    num_seg_blocks = -(-num_segments // seg_block)
    grid = (num_seg_blocks, n // tile)

    ids_spec = pl.BlockSpec((tile,), lambda j, i: (i,))
    val_spec = pl.BlockSpec((tile, d), lambda j, i: (i, 0))
    out_spec = pl.BlockSpec((seg_block, d), lambda j, i: (j, 0))

    if instrumented:
        assert tile % instr.LANES == 0
        waves_per_tile = tile // instr.LANES
        kernel = functools.partial(_scatter_instrumented_kernel,
                                   seg_block=seg_block)
        # the same ids again as lane-dense commit rows for the degrees
        rows_spec = pl.BlockSpec((tile // instr.ROW, instr.ROW),
                                 lambda j, i: (i, 0))
        out, deg = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[ids_spec, rows_spec, val_spec],
            out_specs=[out_spec,
                       pl.BlockSpec((1, 1, waves_per_tile),
                                    lambda j, i: (i, 0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct((num_seg_blocks * seg_block, d),
                                     jnp.float32),
                jax.ShapeDtypeStruct((n // tile, 1, waves_per_tile),
                                     jnp.float32)],
            interpret=kernels.interpret_mode(),
        )(ids, ids.reshape(-1, instr.ROW), values)
        return out[:num_segments], deg.reshape(-1)

    kernel = functools.partial(_scatter_kernel, seg_block=seg_block)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[ids_spec, val_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((num_seg_blocks * seg_block, d),
                                       jnp.float32),
        interpret=kernels.interpret_mode(),
    )(ids, values)
    return out[:num_segments]


def bincount_pallas(
    ids: jnp.ndarray,
    num_segments: int,
    *,
    tile: int = DEFAULT_TILE,
) -> jnp.ndarray:
    """(num_segments,) int32 counts; the MoE dispatch/POPC-class kernel."""
    n = ids.shape[0]
    assert n % tile == 0, "pad in ops.py"
    assert num_segments <= 8192, "use scatter_add blocking for larger"
    kernel = functools.partial(_bincount_kernel, num_segments=num_segments)
    out = pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((tile,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1, num_segments), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, num_segments), jnp.int32),
        interpret=kernels.interpret_mode(),
    )(ids)
    return out[0]
