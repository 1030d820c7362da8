"""Requests of kind ``noise_pool``: images of uniform 8-bit noise in every
32-pixel block, profiled as each of the configuration's variants.

Making a 2^22 px image of noise takes numpy longer than its two verdicts
take the chip, so a closed loop that made one per request would wait on
its own traffic.  Instead ``pool_images`` such images are made from the
seed at set-up, and each request is one of them rolled along the pixel
axis by a number of pixels drawn from the seed: still uniform noise in
every block, never repeated in a run, and since the roll moves pixels
across 32-pixel commit groups, every wave's degree (and so each core's
transactions) is a function of the request.  ``noise_blocks`` must name
every block: this kind makes nothing else.
"""

from __future__ import annotations

import numpy as np

from bench.traffic import seeded_rng


class Requests:
    def __init__(self, cfg: dict, request: dict, seed: int) -> None:
        self.cfg, self.request, self.seed = cfg, request, seed
        self.variants = list(cfg["variants"])
        n, c = cfg["pixels"], cfg["channels"]
        blocks = n // cfg["launch"]["commit_group"]
        if request["noise_blocks"] != [blocks, blocks]:
            raise ValueError(f"noise_pool makes every block noise: "
                             f"noise_blocks must be [{blocks}, {blocks}]")
        rng = seeded_rng(seed, 8)
        self._pool = [rng.integers(0, 1 << cfg["value_bits"], (n, c),
                                   dtype=np.int32)
                      for _ in range(request["pool_images"])]

    def keys(self):
        """(pool image, roll) codes, never repeated in a run."""
        rng = seeded_rng(self.seed, 1)
        n = self.cfg["pixels"]
        seen: set = set()
        while True:
            code = int(rng.integers(0, len(self._pool) * n))
            if code not in seen:
                seen.add(code)
                yield code

    def payload(self, key: int) -> dict:
        image, shift = divmod(key, self.cfg["pixels"])
        return {"img": np.roll(self._pool[image], shift, axis=0)}

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
