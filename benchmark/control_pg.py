"""The `tpch-lineitem-q6` cell with its answer broken, on the chip: the
control that its comparison has to fail.

    python3 benchmark/control_pg.py --workload <name> --seed <n> --seconds <s> --fault served_discount_low

  served_discount_low  the Postgres stand-in serves one row's `l_discount`
                    a hundredth under what the generator's table holds
                    (`0.05`, Q6's lower bound, as `0.04`; the cell's
                    `standin_fault` parameter, which this file alone sets):
                    the program, comparing exactly, drops the row, and the
                    reference, which reads the generator's integers, keeps
                    it - `rows_missing` 1 a pass.

`faults.py`'s faults of the ClickHouse sink run on this cell through
`control.py` as on any other.  Prints the numbers compared, each beside its
limit; exit code 0 when the comparison caught the fault.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NAMES = ("served_discount_low",)


def run_with_fault(workload: str, seed: int, seconds: float, fault: str,
                   shrink=None, **run_kwargs) -> tuple[dict, int]:
    """(result, whether the stand-in altered a row)."""
    from benchmark import run

    if fault not in NAMES:
        raise ValueError(f"unknown fault {fault!r}; one of {NAMES}")

    def with_fault(cell, config):
        if shrink is not None:
            shrink(cell, config)
        cell["params"]["standin_fault"] = fault

    result = run.run_cell(workload, seed, seconds, 0, shrink=with_fault,
                          **run_kwargs)
    return result, int(result["info"]["standin_fault_row"] is not None)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--fault", required=True, choices=NAMES)
    args = p.parse_args(argv)
    result, fired = run_with_fault(args.workload, args.seed, args.seconds,
                                   args.fault)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "fault": args.fault,
        "fired": fired, "correct": result["correct"],
        "attempted": result["attempted"], "failed": result["failed"],
        "compared": result["compared"]}), flush=True)
    return 0 if fired and not result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
