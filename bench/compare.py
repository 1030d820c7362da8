"""The comparison that decides ``correct``: the program's verdict against
the plain reference's, number by number.

Three numbers per verdict, each held to a limit from
``bench/limits/<cell>.json``:

* ``counter_gap``: the largest absolute difference of a counter (per-core
  transactions O and wave jobs, waves, launch geometry).  Exact: limit 0.
* ``model_rel_gap``: the largest relative difference of a queue-model
  output (per-core n, e, c, S, B, T, U; the unit utilizations).
* ``bottleneck_mismatches``: verdicts that name another bottleneck.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import load_module, refmodel

BENCH = Path(__file__).resolve().parent
MODEL_KEYS = ("e", "n_hat", "c", "S", "B", "T", "U", "scatter_model_U",
              "U_scatter", "U_hbm", "U_mxu", "U_ici")
COUNTER_KEYS = ("O", "N_f", "N_c", "N_p", "num_waves", "waves_per_tile",
                "pipeline_depth")


def load_limits(cell: str) -> dict:
    return json.loads((BENCH / "limits" / f"{cell}.json").read_text())


def config_reference(name: str):
    """The plain reference module that sits beside a configuration."""
    return load_module(BENCH / "configs" / f"{name}.py")


def reference_verdict(cfg: dict, payload: dict, variant, dtype) -> dict:
    """Counters and queue-model outputs of one request, from the plain
    reference in precision ``dtype``."""
    ref = config_reference(cfg["name"])
    launch = ref.launch(cfg, payload, variant)
    deg = ref.degrees(cfg, payload, variant, dtype, refmodel)
    cnt = refmodel.counters(
        deg, num_cores=launch["num_cores"],
        waves_per_tile=launch["waves_per_tile"],
        pipeline_depth=launch["pipeline_depth"],
        job_class=launch["job_class"], dtype=dtype)
    v = refmodel.verdict(cnt, launch=launch, bytes_read=launch["bytes_read"],
                         dm=cfg["device_model"], dtype=dtype)
    return {"counters": cnt,
            "model": {k: v[k] for k in MODEL_KEYS},
            "bottleneck": v["bottleneck"]}


def control_verdict(cfg: dict, payload: dict, variant, like: dict) -> dict:
    """The control: the reference one precision below the configuration's
    (float32 for its float64 model), put in the program's place, carrying
    the same numbers as the program's answer ``like``."""
    if cfg["model_precision"] != "float64":
        raise ValueError(f"no control below {cfg['model_precision']!r}")
    ref = reference_verdict(cfg, payload, variant, np.float32)
    return {"counters": {k: ref["counters"][k] for k in like["counters"]},
            "model": {k: ref["model"][k] for k in like["model"]},
            "bottleneck": ref["bottleneck"]}


def gaps(program: dict, reference: dict) -> dict:
    """The three compared numbers for one verdict."""
    counter_gap = 0.0
    for k in program["counters"]:
        p = np.asarray(program["counters"][k], np.float64)
        r = np.asarray(reference["counters"][k], np.float64)
        counter_gap = max(counter_gap, float(np.max(np.abs(p - r))))
    rel = 0.0
    for k in program["model"]:
        p = np.asarray(program["model"][k], np.float64)
        r = np.broadcast_to(np.asarray(reference["model"][k], np.float64),
                            p.shape)
        d = np.abs(p - r) / np.maximum(np.abs(r), 1e-300)
        d = np.where(p == r, 0.0, d)
        rel = max(rel, float(np.max(d)))
    return {"counter_gap": counter_gap, "model_rel_gap": rel,
            "bottleneck_mismatches":
                int(program["bottleneck"] != reference["bottleneck"])}


def worst(rows: list) -> dict:
    """Largest counter and model gaps, and the mismatch count, of a sample."""
    out = {"counter_gap": 0.0, "model_rel_gap": 0.0,
           "bottleneck_mismatches": 0}
    for g in rows:
        out["counter_gap"] = max(out["counter_gap"], g["counter_gap"])
        out["model_rel_gap"] = max(out["model_rel_gap"], g["model_rel_gap"])
        out["bottleneck_mismatches"] += g["bottleneck_mismatches"]
    return out
