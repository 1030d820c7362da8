"""In-kernel conflict instrumentation shared by the Pallas scatter kernels.

This is the counter source the paper wishes hardware provided (§4: "No GPU
performance counter directly measures n and we recommend GPU manufacturers
add one").  The instrumented kernel variants compute, *inside the kernel
body* and from the same index stream the scatter path commits:

  * per-wave serialization degree (the replay-count analogue feeding the
    paper's ``O`` counter: ``e = O / N``),

matching ``repro.core.counters.wave_degree`` bit-for-bit (cross-validated
by tests).  Instrumentation mirrors NCU's replay counters: it adds
overhead when enabled and is compiled out of production kernels.

Layout: the kernels hand the instrumentation their committed stream as
``(rows, ROW)`` int32 blocks in row-major commit order, so one row holds
``ROW // COMMIT_GROUP`` commit groups side by side on the lanes and a
wave is ``WAVE_ROWS`` consecutive rows.  Every operation is a lane
rotation, compare, add, max or a reduction of a whole (8, 128)-tiled
block — nothing changes shape, which is what Mosaic lowers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 1024        # one wave = 8 x 128 VPU lane group
COMMIT_GROUP = 32   # lanes retiring together; conflicts serialize within
ROW = 128           # lanes per stream row: four commit groups
WAVE_ROWS = LANES // ROW


def _group_roll(x: jnp.ndarray, shift: int, pos: jnp.ndarray) -> jnp.ndarray:
    """``x`` rotated by ``shift`` lanes cyclically within each commit group.

    Lane ``g + p`` of the result holds lane ``g + (p - shift) % GROUP`` of
    ``x``: the whole-row rotation is right where it stays inside the
    group, and the rotation by ``shift - GROUP`` (mod ``ROW``) is right
    where it wraps.
    """
    inside = pltpu.roll(x, shift, 1)
    wrapped = pltpu.roll(x, ROW - COMMIT_GROUP + shift, 1)
    return jnp.where(pos >= shift, inside, wrapped)


def wave_degrees(rows: jnp.ndarray) -> list:
    """Per-wave serialization degrees of a ``(rows, ROW)`` stream block.

    ``rows.shape[0]`` must be a multiple of ``WAVE_ROWS``.  Returns one
    float32 scalar per wave: the mean over the wave's commit groups of
    the largest duplicate multiplicity within the group.
    """
    assert rows.shape[1] == ROW and rows.shape[0] % WAVE_ROWS == 0
    pos = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1),
                      COMMIT_GROUP)
    mult = jnp.ones(rows.shape, jnp.float32)
    for d in range(1, COMMIT_GROUP):
        mult += (rows == _group_roll(rows, d, pos)).astype(jnp.float32)
    shift = 1
    while shift < COMMIT_GROUP:     # butterfly: every lane holds its group max
        mult = jnp.maximum(mult, _group_roll(mult, shift, pos))
        shift *= 2
    # each group's max sits on all COMMIT_GROUP of its lanes, so a wave's
    # lane sum over LANES is the mean over its groups (exact in float32)
    return [jnp.sum(mult[w * WAVE_ROWS:(w + 1) * WAVE_ROWS]) / LANES
            for w in range(rows.shape[0] // WAVE_ROWS)]
