"""Plain reference of qwen3-moe-router: the expert ids the dispatch commits.

The router emits each token's ``num_experts_per_tok`` experts in
token-major order; the scatter of unit values commits that id stream as
it is, 32 consecutive ids to a commit group and 1,024 to a wave.
"""

from __future__ import annotations

import numpy as np


def launch(cfg: dict, payload: dict, variant: str) -> dict:
    ids = np.asarray(payload["ids"])
    return {**cfg["launch"], "job_class": cfg["job_class"],
            "bytes_read": float(ids.size * cfg["bytes_per_id"])}


def degrees(cfg: dict, payload: dict, variant: str, dtype, refmodel):
    lc = cfg["launch"]
    return refmodel.group_degrees(
        np.asarray(payload["ids"]).reshape(-1), group=lc["commit_group"],
        lanes=lc["wave_lanes"], dtype=dtype)
