"""Tool 1 (paper §3.4): build the once-per-chip ``S(n, e, c)`` table.

The paper's first tool runs a microbenchmark that issues ``A = n`` atomic
warp-instructions at once with controlled active-thread count ``e`` and CAS
count ``c``, measures total time ``T`` from first arrival to last
completion, and derives ``S = T / n`` by job flow balance.

Here the measurement has two modes:

* ``analytic`` (default): query the calibrated v5e timing model directly on
  the full (n, e, c) grid.  This is the CPU-container stand-in for running
  on hardware; on a real TPU this mode is replaced by wall-clock timing of
  the same generated access patterns.
* ``kernel``: additionally *executes* the instrumented Pallas scatter
  kernel (compiled on a TPU, interpreted elsewhere) on synthetic index
  patterns constructed to have a designed (n, e, c), recovers the
  counters from instrumentation, checks they match the design
  (validating the counter path end-to-end), and uses
  the counted values to index the timing model.  This mirrors the paper's
  point that ``T(n,e,c)`` "does not reveal any hardware implementation
  details" — the table is produced by running code, not by reading specs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core import qmodel, timing


def default_grids(params: timing.ScatterUnitParams = timing.V5E_SCATTER):
    n_grid = np.arange(0, params.n_max + 1, dtype=np.float64)  # all integral n
    e_grid = np.arange(1, params.e_max + 1, dtype=np.float64)  # all integral e
    cfrac_grid = np.linspace(0.0, 1.0, 17)
    return n_grid, e_grid, cfrac_grid


def build_table(
    params: timing.ScatterUnitParams = timing.V5E_SCATTER,
    mode: str = "analytic",
    kernel_validation_points: int = 8,
    seed: int = 0,
) -> qmodel.ServiceTimeTable:
    """Measure T(n, e, c) over the full grid; once per chip model."""
    n_grid, e_grid, cfrac_grid = default_grids(params)
    nn, ee, cf = np.meshgrid(n_grid, e_grid, cfrac_grid, indexing="ij")
    cc = cf * nn  # integral-c design points rectangularized by fraction
    T = timing.total_time_cycles(nn, ee, cc, 0.0, params)
    popc = timing.total_time_cycles(nn[..., 0], ee[..., 0],
                                    0.0, nn[..., 0], params)
    meta = {"mode": mode, "params": dataclasses.asdict(params)}

    if mode == "kernel":
        meta["kernel_validation"] = _validate_with_kernel(
            params, kernel_validation_points, seed)

    return qmodel.ServiceTimeTable(
        n_grid=n_grid, e_grid=e_grid, cfrac_grid=cfrac_grid, T=T,
        popc_T=popc, clock_hz=params.clock_hz, meta=meta,
    )


def make_pattern(n: int, e: int, num_bins: int, lanes: int = 1024,
                 seed: int = 0) -> np.ndarray:
    """Synthesize ``n`` waves of scatter indices with serialization degree e.

    Degree e means each wave's ``lanes`` updates hit ``lanes // e`` distinct
    bins (duplicate multiplicity e), the TPU analogue of ``e`` threads of a
    warp hitting one bank.  Used both by the microbenchmark and the kernel
    tests.
    """
    assert 1 <= e <= lanes
    rng = np.random.default_rng(seed)
    distinct = max(1, lanes // e)
    waves = []
    for _ in range(n):
        bins = rng.choice(num_bins, size=distinct, replace=False)
        idx = np.repeat(bins, e)[:lanes]
        if idx.size < lanes:  # pad with the first bin (raises degree slightly)
            idx = np.concatenate([idx, np.full(lanes - idx.size, bins[0])])
        waves.append(idx)
    return np.stack(waves).astype(np.int32)


def _validate_with_kernel(params, num_points: int, seed: int) -> list[dict]:
    """Run the instrumented kernel on designed patterns; compare counters."""
    from repro.kernels.scatter_add import ops as scatter_ops  # lazy import

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(num_points):
        n = int(rng.integers(1, params.n_max + 1))
        e = int(2 ** rng.integers(0, 6))  # 1..32
        num_bins = 4096
        idx = make_pattern(n, e, num_bins, seed=int(rng.integers(1 << 31)))
        values = np.ones(idx.shape, np.float32)
        _, counters = scatter_ops.instrumented_scatter_add(
            idx.reshape(-1), values.reshape(-1), num_bins)
        measured_e = counters["O"] / counters["N"]
        out.append({
            "designed": {"n": n, "e": e},
            "counted": {"N": float(counters["N"]), "e": float(measured_e)},
            "e_rel_err": abs(measured_e - e) / e,
        })
    return out
