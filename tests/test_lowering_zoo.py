"""Reduced ``build_lowered`` smoke over the full config zoo.

Every registered architecture must lower end to end on the audit's
reduced smoke geometry — the same path ``repro audit --reduced`` and the
lint/audit CI gates depend on.  One applicable step per config keeps the
sweep sub-minute while still exercising every architecture module,
``shape_tuned_config`` and the pre-SPMD compat mesh.
"""

from __future__ import annotations

import dataclasses

import pytest

from repro.audit.zoo import AUDIT_SHAPES, _REDUCED_GEOM
from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.launch.lowering import (build_lowered, pre_optimization_hlo,
                                   shape_tuned_config)
from repro.launch.mesh import make_mesh


def _reduced_shape(step: str):
    shape = SHAPES[AUDIT_SHAPES[step]]
    gb, sl = _REDUCED_GEOM[step]
    return dataclasses.replace(shape, global_batch=gb, seq_len=sl)


def _first_applicable(cfg):
    """(step, shape) for the first audit step this config supports."""
    for step in AUDIT_SHAPES:
        shape = _reduced_shape(step)
        ok, _why = shape_applicable(cfg, shape)
        if ok:
            return step, shape
    return None, None


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_reduced_lowering_smoke(arch):
    cfg = get_config(arch).reduced()
    step, shape = _first_applicable(cfg)
    assert step is not None, f"{arch}: no applicable audit step"
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg_t, loss_chunk, train_kw = shape_tuned_config(cfg, shape, "base")
    lowered = build_lowered(cfg_t, shape, mesh, loss_chunk=loss_chunk,
                            train_kw=train_kw)
    text = pre_optimization_hlo(lowered)
    assert "HloModule" in text
    # pre-SPMD lowering carries the *global* shapes: a real module body,
    # not a stub
    assert text.count("\n") > 20, f"{arch}/{step}: suspiciously small HLO"
