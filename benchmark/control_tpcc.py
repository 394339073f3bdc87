"""The `tpcc-snapshot-debezium` cell with its answer broken, on the chip:
the controls that its comparison has to fail.

    python3 benchmark/control_tpcc.py --workload <name> --seed <n> --seconds <s> --fault <name>

  served_balance_low    the MySQL stand-in serves one sampled customer's
                        `c_balance` a cent under what the generator's
                        arrays hold (`-10.00` as `-10.01`): the envelope
                        carries what was served, the reference reads the
                        arrays - `sample_cells_mismatched` 1 a pass.
  dropped_acked_record  the broker stand-in loses one record of the
                        window's first publish after acknowledging it:
                        `rows_missing` 1 (and, where the record was a
                        sampled one, `sample_keys_missing` 1).

Both are the cell's `standin_fault` parameter, which this file alone sets.
Prints the numbers compared, each beside its limit; exit code 0 when the
comparison caught the fault.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NAMES = ("served_balance_low", "dropped_acked_record")


def run_with_fault(workload: str, seed: int, seconds: float, fault: str,
                   shrink=None, **run_kwargs) -> tuple[dict, int]:
    """(result, whether a stand-in altered anything)."""
    from benchmark import run

    if fault not in NAMES:
        raise ValueError(f"unknown fault {fault!r}; one of {NAMES}")

    def with_fault(cell, config):
        if shrink is not None:
            shrink(cell, config)
        cell["params"]["standin_fault"] = fault

    result = run.run_cell(workload, seed, seconds, 0, shrink=with_fault,
                          **run_kwargs)
    info = result["info"]
    fired = info["standin_fault_row"] is not None \
        or bool(info["standin_dropped"])
    return result, int(fired)


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
