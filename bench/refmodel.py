"""Plain reference of a verdict: wave degrees -> counters -> queue model.

Written from the paper's definitions (arXiv:2503.17893, section 3: the
service-time table T(n, e, c), S = T / n, B = N * S, U = B / T) and the
numbers in a configuration file.  It imports nothing of the program under
test.  ``dtype`` sets the precision of every floating-point step: float64 is
the reference, float32 the control one precision below it.
"""

from __future__ import annotations

import numpy as np

CLASSES = ("FAO", "CAS", "POPC")
UNITS = ("scatter", "hbm", "mxu", "ici")
_CHUNK = 4096  # commit groups per pairwise-equality block (4 MiB of bools)


def group_degrees(stream, *, group: int, lanes: int, dtype) -> np.ndarray:
    """Per-wave serialization degree of a committed destination stream.

    A wave is ``lanes`` consecutive commits, a commit group ``group`` of
    them; the degree of a group is the largest number of its commits that
    share one destination, and a wave's degree is the mean over its groups.
    """
    s = np.asarray(stream, np.int32).reshape(-1, group)
    mult = np.empty(s.shape[0], np.int64)
    for st in range(0, s.shape[0], _CHUNK):
        g = s[st:st + _CHUNK]
        mult[st:st + _CHUNK] = np.count_nonzero(
            g[:, :, None] == g[:, None, :], axis=2).max(axis=1)
    return mult.reshape(-1, lanes // group).astype(dtype).mean(
        axis=1, dtype=dtype)


def counters(degrees, *, num_cores: int, waves_per_tile: int,
             pipeline_depth: int, job_class: str, dtype) -> dict:
    """Per-core transactions O and wave jobs per class.

    Tiles of ``waves_per_tile`` waves go round-robin over the cores.
    """
    deg = np.asarray(degrees, dtype)
    core = (np.arange(deg.shape[0]) // waves_per_tile) % num_cores
    out = {"O": np.zeros(num_cores, dtype),
           **{f"N_{k[0].lower()}": np.zeros(num_cores, dtype)
              for k in CLASSES}}
    key = f"N_{job_class[0].lower()}"
    for c in range(num_cores):
        sel = core == c
        out["O"][c] = deg[sel].sum(dtype=dtype)
        out[key][c] = sel.sum()
    out.update(num_waves=deg.shape[0], waves_per_tile=waves_per_tile,
               pipeline_depth=pipeline_depth)
    return out


def _total_time(n, e, cfrac, dm: dict, f):
    """T(n, e, c) in cycles at a sampled point: one pipeline fill, then one
    issue interval per job, CAS jobs (c = cfrac * n) at their own rate."""
    e = np.clip(e, f(1), f(dm["e_max"]))
    c = cfrac * n
    fill = f(dm["fill_cycles"]) + f(dm["fill_per_conflict"]) * e
    t = (fill + (n - c) * (f(dm["fao_base"]) + f(dm["fao_slope"]) * e)
         + c * (f(dm["cas_base"]) + f(dm["cas_slope"]) * e))
    return np.where(n > 0, t, f(0))


def _popc_total_time(n, e, dm: dict, f):
    e = np.clip(e, f(1), f(dm["e_max"]))
    t = (f(dm["fill_cycles"]) + f(dm["fill_per_conflict"]) * e
         + n * (f(dm["popc_base"]) + f(dm["popc_slope"]) * e))
    return np.where(n > 0, t, f(0))


def _bracket(grid: np.ndarray, x):
    """Lower and upper grid points around ``x`` (clamped) and the weight of
    the upper one."""
    x = min(max(x, grid[0]), grid[-1])
    hi = int(np.clip(np.searchsorted(grid, x), 1, len(grid) - 1))
    lo = hi - 1
    return grid[lo], grid[hi], (x - grid[lo]) / (grid[hi] - grid[lo])


def _interpolate(fn, grids, point, f):
    """Multilinear interpolation of ``fn`` sampled on ``grids`` at ``point``."""
    brackets = [_bracket(g, x) for g, x in zip(grids, point)]
    out = f(0)
    for corner in range(1 << len(grids)):
        w = f(1)
        args = []
        for d, (lo, hi, wd) in enumerate(brackets):
            up = corner >> d & 1
            args.append(hi if up else lo)
            w = w * (wd if up else f(1) - wd)
        out = out + w * fn(*args)
    return out


def verdict(cnt: dict, *, launch: dict, bytes_read: float, dm: dict,
            dtype) -> dict:
    """The queue model's outputs for one launch's counters."""
    f = np.dtype(dtype).type
    C = len(cnt["O"])
    O, N_f, N_c, N_p = (np.asarray(cnt[k], dtype)
                        for k in ("O", "N_f", "N_c", "N_p"))
    N = N_f + N_c + N_p
    jobs = N.sum(dtype=dtype)
    e = O.sum(dtype=dtype) / jobs if jobs > 0 else f(1)
    W, wpt = cnt["num_waves"], cnt["waves_per_tile"]
    n_max = dm["n_max"]
    n_hat = f(min(wpt * cnt["pipeline_depth"], n_max, max(W, 1)))

    n_grid = np.arange(0, n_max + 1, dtype=dtype)
    e_grid = np.arange(1, dm["e_max"] + 1, dtype=dtype)
    cfrac_grid = np.linspace(0, 1, dm["cfrac_points"], dtype=dtype)
    faocas = N_f + N_c
    c = np.where(faocas > 0, n_hat * N_c / np.where(faocas > 0, faocas, 1),
                 f(0))
    S = np.zeros(C, dtype)
    for i in range(C):
        if faocas[i] > 0 and n_hat > 0:
            t = _interpolate(lambda n, ee, cf: _total_time(n, ee, cf, dm, f),
                             (n_grid, e_grid, cfrac_grid),
                             (n_hat, e, c[i] / n_hat), f)
            S[i] = t / n_hat
    busy = faocas * S
    if np.any(N_p > 0) and n_hat > 0:
        sp = _interpolate(lambda n, ee: _popc_total_time(n, ee, dm, f),
                          (n_grid, e_grid), (n_hat, e), f) / n_hat
        busy = busy + np.where(N_p > 0, N_p * sp, f(0))
    if not jobs > 0:
        busy = np.zeros(C, dtype)

    clock = f(dm["clock_hz"])
    mem = (f(bytes_read) / f(C)) / (f(dm["hbm_bytes_per_s"]) / clock)
    if jobs > 0 and bytes_read > dm["llc_bytes"]:
        hide = min(f(1), n_hat / f(dm["hide_concurrency"]))
        tiles = max(f(1), f(W) / f(max(wpt, 1)))
        mem = mem + (tiles / f(C)) * f(dm["miss_latency_cycles"]) * (f(1) - hide)
    mxu = (f(launch["flops"]) / f(C)) / (f(dm["peak_flops"]) / clock)
    ici = f(0)
    T = f(launch["overhead_cycles"]) + np.maximum(
        busy, max(mem, mxu, ici))
    U = np.where(T > 0, busy / np.where(T > 0, T, 1), f(0))
    window = T.max()
    units = {"scatter": busy.mean(dtype=dtype) / window, "hbm": mem / window,
             "mxu": mxu / window, "ici": ici / window}
    best, best_u = "none", f(0)
    for name in UNITS:
        if units[name] > best_u:
            best, best_u = name, units[name]
    return {
        "e": e, "n_hat": n_hat, "c": c, "S": S, "B": busy, "T": T, "U": U,
        "scatter_model_U": U.mean(dtype=dtype),
        **{f"U_{k}": v for k, v in units.items()},
        "bottleneck": best,
    }
