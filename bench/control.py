"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/control.py --workload hist-solid --seconds 5 \
        --seeds 11 12 13 ... --control-seeds 21 22 23

In one process: a short window at the cell's own load for every seed,
with the program's verdicts compared with the plain reference (the lower
readings), then with the control in the program's place (the upper
readings; every such run has to come out not correct).  One JSON line per
run.  Not part of a benchmark run.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    runs = [(s, False) for s in args.seeds] + \
        [(s, True) for s in args.control_seeds]
    for seed, control in runs:
        try:
            res = harness.run_cell(args.workload, seed=seed,
                                   seconds=args.seconds, traced=False,
                                   t_start=time.monotonic(), control=control,
                                   log=lambda *a: None)
        except harness.NoChip as exc:
            print(f"control: {exc}", file=sys.stderr)
            return 3
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": control, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    print(f"control: {len(runs)} runs in {time.monotonic() - T_START!r} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
