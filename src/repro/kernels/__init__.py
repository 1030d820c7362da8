"""Pallas kernels: histogram (paper case study), scatter_add (MoE
dispatch / embedding-grad), flash_attention (online-softmax, VMEM-tiled),
conflict instrumentation.

Where the kernels run is decided here, once, from the backend in use:
on a TPU every ``pallas_call`` compiles through Mosaic, anywhere else it
runs in the Pallas interpreter (the CPU test path).  A Mosaic error on a
TPU is raised to the caller; nothing retries it interpreted.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

_REPO_ROOT = Path(__file__).resolve().parents[3]


def interpret_mode() -> bool:
    """True unless the default backend is a TPU (read at trace time)."""
    return jax.default_backend() != "tpu"


def backend_info() -> dict:
    """Where kernel counters come from: platform, device kind, interpret."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "interpret": interpret_mode()}


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it
    and nothing is changed.  Otherwise the cache lives at the fixed path
    ``results/jax_cache/`` of this checkout (the path is part of the
    cache key, so it must not move between runs).  Called by the process
    entry points (``python -m repro``, ``chip_smoke.py``), never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    path = _REPO_ROOT / "results" / "jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
