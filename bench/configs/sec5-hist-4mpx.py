"""Plain reference of sec5-hist-4mpx: the stream of bins the histogram commits.

The paper's Listing 1 (``hist``): a warp of 32 pixels reads channel ``s``
of every pixel at step ``s`` and adds one to bin ``s * bins + value``.
Listing 2 (``hist2``) rotates the channel that pixel ``p`` reads at step
``s`` to ``(s + p) % channels``.  The committed stream is step-major
within each 32-pixel group; 1,024 consecutive commits form a wave.
"""

from __future__ import annotations

import numpy as np


def committed_stream(img, variant: str, cfg: dict) -> np.ndarray:
    a = np.asarray(img, np.int64)
    n, c = a.shape
    g = cfg["launch"]["commit_group"]
    step = np.broadcast_to(np.arange(c)[None, :], (n, c))
    if variant == "hist2":
        ch = (step + np.arange(n)[:, None]) % c
    elif variant == "hist":
        ch = step
    else:
        raise ValueError(f"unknown variant {variant!r}")
    bins = ch * cfg["num_bins"] + np.take_along_axis(a, ch, axis=1)
    return bins.reshape(n // g, g, c).transpose(0, 2, 1).reshape(-1)


def launch(cfg: dict, payload: dict, variant: str) -> dict:
    """The launch the verdict describes, and the bytes it reads."""
    lc = cfg["launch"]
    n, c = payload["img"].shape
    return {**lc,
            "waves_per_tile": lc["tile_pixels"] * c // lc["wave_lanes"],
            "job_class": cfg["job_class"],
            "bytes_read": float(n * c * cfg["bytes_per_value"])}


def degrees(cfg: dict, payload: dict, variant: str, dtype, refmodel):
    lc = cfg["launch"]
    return refmodel.group_degrees(
        committed_stream(payload["img"], variant, cfg),
        group=lc["commit_group"], lanes=lc["wave_lanes"], dtype=dtype)
