"""Requests of kind ``histogram``: one image of the configuration's size,
profiled as each of its variants.

Each image is solid, one colour per channel, the four colours drawn
together from the seed and never repeated in a run, with a number of
32-pixel blocks of uniform 8-bit noise planted at places drawn from the
seed: between ``noise_blocks[0]`` and ``noise_blocks[1]`` of them, a count
drawn per request.  The blocks make the kernel's counters (every wave's
degree, and so each core's transactions) a function of the request,
while a handful of them leaves e at the solid image's.  A range that
covers every block makes uniform images.
"""

from __future__ import annotations

import numpy as np

from bench.traffic import seeded_rng


class Requests:
    def __init__(self, cfg: dict, request: dict, seed: int) -> None:
        self.cfg, self.request, self.seed = cfg, request, seed
        self.variants = list(cfg["variants"])
        n, g = cfg["pixels"], cfg["launch"]["commit_group"]
        self.blocks = n // g
        lo, hi = request["noise_blocks"]
        if not 0 <= lo <= hi <= self.blocks:
            raise ValueError(f"noise_blocks {lo}..{hi} outside 0.."
                             f"{self.blocks} blocks of {g} pixels")

    def keys(self):
        """Colour codes (channel ``c``'s colour is byte ``c``), never
        repeated in a run."""
        rng = seeded_rng(self.seed, 1)
        seen: set = set()
        while True:
            code = int(rng.integers(0, 1 << 32))
            if code not in seen:
                seen.add(code)
                yield code

    def payload(self, key: int) -> dict:
        cfg = self.cfg
        n, c, g = cfg["pixels"], cfg["channels"], cfg["launch"]["commit_group"]
        img = np.empty((n, c), np.int32)
        img[:] = np.asarray([(key >> (8 * ch)) & 0xFF for ch in range(c)],
                            np.int32)
        rng = seeded_rng(self.seed, 2, key)
        lo, hi = self.request["noise_blocks"]
        count = int(rng.integers(lo, hi + 1))
        where = rng.choice(self.blocks, count, replace=False)
        img.reshape(self.blocks, g, c)[where] = rng.integers(
            0, 1 << cfg["value_bits"], (count, g, c), dtype=np.int32)
        return {"img": img}

    def spec(self, payload: dict, variant: str, label: str):
        from repro.analysis import WorkloadSpec  # lazy: the system under test

        return WorkloadSpec.from_histogram(
            payload["img"], label=label, variant=variant,
            num_bins=self.cfg["num_bins"])

    def launch(self, payload: dict) -> dict:
        n, c = payload["img"].shape
        return {"kernel": "hist", "pixels": n, "channels": c,
                "num_bins": self.cfg["num_bins"],
                "commit_group": self.cfg["launch"]["commit_group"]}
