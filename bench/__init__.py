"""The chip benchmark: one cell of BENCHMARK.json per run (see run_cell.py)."""

import importlib.util
from pathlib import Path


def load_module(path: Path):
    """A benchmark file found by name (a metric reader, a work count, a
    configuration's reference), loaded from its path."""
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:])
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
