"""Run one cell of BENCHMARK.json on the attached chip; print its result.

    python bench/run_cell.py --workload hist-solid --seed 7 --seconds 45 --trace 0

The last line of standard output is the result object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``checks``: each number compared with the plain reference beside its
limit, which also end standard error.  Exits non-zero, with no result,
when JAX finds no TPU of a kind in ``bench/peaks.json``.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="with --trace 1, also keep the profiler's trace "
                         "(.xplane.pb.gz) in this directory")
    args = ap.parse_args(argv)
    try:
        result = harness.run_cell(args.workload, seed=args.seed,
                                  seconds=args.seconds,
                                  traced=bool(args.trace), t_start=T_START,
                                  trace_out=args.trace_out)
    except harness.NoChip as exc:
        print(f"run_cell: {exc}", file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
