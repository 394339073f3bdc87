"""A cell with its guarantee broken, on the chip: the control that the
comparison has to fail.

    python3 benchmark/control.py --workload <name> --seed <n> --seconds <s> --fault <name>

Runs the cell exactly as `run.py` does, with one fault of `faults.py`
planted in the program, and prints the numbers compared, each beside its
limit, and whether `correct` came out false, as it has to.  Exit code 0
when the comparison caught the fault, 1 when it did not.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_with_fault(workload: str, seed: int, seconds: float, fault: str,
                   nth: int = 2, **run_kwargs) -> tuple[dict, int]:
    """(result, times the fault fired): the fault is planted when the
    window opens, so that set-up and warm-up run sound."""
    from benchmark import faults, run

    planted = []
    window_open = run.Context.window_open

    def open_and_plant(ctx):
        window_open(ctx)
        planted.append(faults.plant(fault, nth))

    run.Context.window_open = open_and_plant
    try:
        result = run.run_cell(workload, seed, seconds, 0, **run_kwargs)
    finally:
        run.Context.window_open = window_open
        fired = sum(remove() for remove in planted)
    return result, fired


def main(argv=None) -> int:
    from benchmark import faults

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--fault", required=True, choices=faults.NAMES)
    p.add_argument("--nth", type=int, default=2,
                   help="which data insert of the window takes the fault")
    args = p.parse_args(argv)
    result, fired = run_with_fault(args.workload, args.seed, args.seconds,
                                   args.fault, args.nth)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "fault": args.fault,
        "fired": fired, "correct": result["correct"],
        "attempted": result["attempted"], "failed": result["failed"],
        "compared": result["compared"]}), flush=True)
    return 0 if fired and not result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
