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

Host side, both kernel families launch through the same three steps,
each a ``repro.obs.telemetry`` span: ``to_device`` (``kernel.h2d``),
``launch`` (``kernel.launch``, ``kernel.wait``, ``kernel.readback``) and
``wave_trace`` (``kernel.counters``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.core import counters as counters_mod
from repro.obs import telemetry

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


def to_device(*arrays) -> tuple:
    """One ``device_put`` of a launch's host inputs, not waited on.

    The ``kernel.h2d`` span times the call: the host's staging of the
    copy, which for a large host array is the copy itself.  What is still
    in flight when it returns lands in ``kernel.wait``, since the device
    orders the launch after it.  The span's ``bytes`` counts the arrays
    not already on a device, which are the ones copied.
    """
    copied = sum(a.nbytes for a in arrays if not isinstance(a, jax.Array))
    with telemetry.span("kernel.h2d", bytes=copied):
        return jax.device_put(arrays)


def launch(fn, *args, **static):
    """Call an instrumented launch ``fn -> (out, degrees)``, wait for its
    degrees and read them back: ``(out, degrees as numpy)``.

    The degrees' copy to the host is queued with the launch, so that the
    read-back after the wait finds it under way.
    """
    with telemetry.span("kernel.launch"):
        out, degrees = fn(*args, **static)
        degrees.copy_to_host_async()
    with telemetry.span("kernel.wait"):
        degrees.block_until_ready()
    with telemetry.span("kernel.readback"):
        return out, np.asarray(degrees)


def wave_trace(degrees: np.ndarray, *, job_class: int, num_cores: int,
               waves_per_tile: int, pipeline_depth: int,
               **span_attrs) -> counters_mod.WaveTrace:
    """The ``WaveTrace`` of a launch's read-back wave degrees: every wave
    of one job class and full lanes, tiles dealt to cores round-robin.
    ``span_attrs`` go on the ``kernel.counters`` span."""
    with telemetry.span("kernel.counters", **span_attrs):
        num_waves = degrees.shape[0]
        tiles = np.arange(num_waves) // max(waves_per_tile, 1)
        return counters_mod.WaveTrace(
            degree=degrees,
            job_class=np.full(num_waves, job_class, np.int32),
            core=(tiles % num_cores).astype(np.int32),
            lanes_active=np.full(num_waves, float(LANES)),
            waves_per_tile=waves_per_tile,
            pipeline_depth=pipeline_depth,
        )
