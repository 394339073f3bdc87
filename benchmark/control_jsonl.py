"""The cell `clickbench-jsonl-snapshot` with its source text altered, on
the chip: the control that the comparison has to fail.

    python3 benchmark/control_jsonl.py --workload <name> --seed <n> --seconds <s>

Runs the cell exactly as `run.py` does, but the world, once it has written
the truth (the parquet part files the reference reads) and the JSON-lines
objects, changes one digit of one number in one line of one object - the
`--column` (CounterID) of a row that the filter keeps and the comparison
samples.  The system decodes what the object holds, so that row lands with
another value than the truth's: every pass of the window has to read one
mismatched cell, and `correct` false.  Exit code 0 when the comparison
caught it, 1 when it did not.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_with_fault(workload: str, seed: int, seconds: float, column: str,
                   **run_kwargs) -> dict:
    from benchmark import run

    shrink = run_kwargs.pop("shrink", None)

    def plant(cell, config):
        if shrink is not None:
            shrink(cell, config)
        cell["params"]["text_fault"] = column

    return run.run_cell(workload, seed, seconds, 0, shrink=plant,
                        **run_kwargs)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="clickbench-jsonl-snapshot")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--column", default="CounterID")
    args = p.parse_args(argv)
    result = run_with_fault(args.workload, args.seed, args.seconds,
                            args.column)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "text_fault": result["info"]["text_fault"],
        "correct": result["correct"], "attempted": result["attempted"],
        "failed": result["failed"], "compared": result["compared"]}),
        flush=True)
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
