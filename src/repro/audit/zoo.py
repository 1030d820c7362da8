"""Model-zoo lowering for the audit: config -> per-step pre-opt HLO text.

Audits read *pre-optimization* HLO (``compiler_ir(dialect="hlo")``),
which is pre-SPMD: shapes are global, so lowering runs on a tiny
``(1, 1)`` compat mesh with no device-count override and no ``.compile()``
call — a full-size config lowers in about a second.  Importing this
module pulls in jax; the CLI defers the import until an audit actually
runs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.launch.lowering import (build_lowered, pre_optimization_hlo,
                                   shape_tuned_config)
from repro.launch.mesh import make_mesh

# step name -> production shape audited for it
AUDIT_SHAPES = {
    "train": "train_4k",
    "prefill": "prefill_32k",
    "decode": "decode_32k",
}

# ``reduced=True`` smoke geometry: keeps lowering sub-second in tests
# while preserving every scatter/DUS idiom of the full shapes.
_REDUCED_GEOM = {"train": (4, 64), "prefill": (4, 256), "decode": (4, 256)}


def normalize_arch(name: str) -> str:
    """Accept underscore- or module-spelled config names (CLI/CI)."""
    if name in ARCHS:
        return name
    dashed = name.replace("_", "-")
    if dashed in ARCHS:
        return dashed
    for arch, module in ARCHS.items():   # e.g. zamba2_1p2b -> zamba2-1.2b
        if name == module:
            return arch
    raise KeyError(f"unknown config {name!r} (known: {', '.join(ARCHS)})")


def lower_config_steps(arch: str, *, steps: Optional[Sequence[str]] = None,
                       reduced: bool = False, variant: str = "base",
                       ) -> dict[str, str]:
    """Lower each requested step of a config; returns step -> HLO text.

    Inapplicable (config, shape) cells — per ``shape_applicable`` — are
    silently skipped, matching the dry-run grid.
    """
    arch = normalize_arch(arch)
    cfg0 = get_config(arch)
    if reduced:
        cfg0 = cfg0.reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    out: dict[str, str] = {}
    for step in (steps or AUDIT_SHAPES):
        shape = SHAPES[AUDIT_SHAPES[step]]
        if reduced:
            gb, sl = _REDUCED_GEOM[step]
            shape = dataclasses.replace(shape, global_batch=gb, seq_len=sl)
        ok, _why = shape_applicable(cfg0, shape)
        if not ok:
            continue
        cfg, loss_chunk, train_kw = shape_tuned_config(cfg0, shape, variant)
        lowered = build_lowered(cfg, shape, mesh, loss_chunk=loss_chunk,
                                train_kw=train_kw)
        out[step] = pre_optimization_hlo(lowered)
    return out
