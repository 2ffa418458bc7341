"""Readings that set the correctness limit, on the chip.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 3 \\
        [--mode imprecise_int8]

Runs the cell's whole timed path once per seed in one process and prints
one JSON line per run with the numbers ``correct`` compares.  With the
configuration's own mode these are the sound readings (the lower end of
the limit); with ``--mode imprecise_int8``, the program's own int8 path,
the next precision below the configuration's bfloat16, they are the
control's (the upper end).  The benchmark's own runs never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--mode", default=None,
                    help="compute mode in place of the configuration's")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.mode:
        cell = dataclasses.replace(cell, config=dict(cell.config,
                                                     mode=args.mode))
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            line = harness.run_cell(cell, seed, args.seconds, False,
                                    t_process=time.perf_counter())
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "mode": cell.config["mode"],
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
