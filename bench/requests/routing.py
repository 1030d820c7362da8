"""Requests of kind ``routing``: one router batch, dispatched as the
expert-load scatter (unit values, one row per expert id).

Every token's ``num_experts_per_tok`` experts are a Gumbel-top-k over a
Zipf popularity of the expert ranks, taken from a pool of
``pool_batches`` batches of such draws made at set-up; each request picks
its tokens from the pool and permutes the ranking of the experts, both
from the seed.
"""

from __future__ import annotations

import numpy as np

from bench.traffic import seeded_rng, zipf_probabilities


class Requests:
    variants = [None]

    def __init__(self, cfg: dict, request: dict, seed: int) -> None:
        self.cfg, self.request, self.seed = cfg, request, seed
        self._pool = self._routing_pool()
        self._ones = None

    def keys(self):
        i = 0
        while True:
            yield i
            i += 1

    def payload(self, key: int) -> dict:
        cfg = self.cfg
        rng = seeded_rng(self.seed, 3, key)
        perm = rng.permutation(cfg["num_experts"]).astype(np.int32)
        rows = rng.integers(0, self._pool.shape[0], cfg["tokens_per_batch"])
        return {"ids": perm[self._pool[rows]].reshape(-1)}

    def _routing_pool(self) -> np.ndarray:
        """Expert ranks of ``pool_batches`` batches of tokens: each row is
        one token's top-k of Zipf log-popularity plus Gumbel noise."""
        cfg, req = self.cfg, self.request
        experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
        tokens = req["pool_batches"] * cfg["tokens_per_batch"]
        logp = np.log(zipf_probabilities(experts, req["popularity_exponent"]))
        u = seeded_rng(self.seed, 4).random((tokens, experts))
        score = logp - float(req["gumbel_scale"]) * np.log(-np.log(u))
        top = np.argpartition(-score, k - 1, axis=1)[:, :k]
        order = np.argsort(-np.take_along_axis(score, top, 1), axis=1)
        return np.take_along_axis(top, order, 1).astype(np.int32)

    def spec(self, payload: dict, variant, label: str):
        from repro.analysis import WorkloadSpec  # lazy: the system under test

        cfg, ids = self.cfg, payload["ids"]
        if self._ones is None or self._ones.shape[0] != ids.size:
            self._ones = np.ones((ids.size, cfg["value_width"]), np.float32)
        return WorkloadSpec.from_scatter_add(
            ids, self._ones, cfg["num_experts"], label=label,
            waves_per_tile=cfg["launch"]["waves_per_tile"])

    def launch(self, payload: dict) -> dict:
        cfg = self.cfg
        return {"kernel": "scatter", "ids": int(np.asarray(payload["ids"]).size),
                "width": cfg["value_width"], "segments": cfg["num_experts"],
                "commit_group": cfg["launch"]["commit_group"]}
