"""Reader `filter_roofline`: the least time the chip's memory could take
for what a filter's predicate needs, whatever implements it, over the time
the matching XLA modules ran, in %.

The bytes a row needs are constants of the metric's file: the predicate's
columns in at the narrowest width that holds them exactly
(`bytes_in_per_row`: for TPC-H Q6, 4 of `l_shipdate`'s int32 days and 4
each of `l_discount` and `l_quantity`, whose unscaled integers reach the
device as int32) and the answer out (`bits_out_per_row`: 1).  The rows are
those whose predicate the device took while the trace ran
(`telemetry_traced.filter_rows_device`); the seconds are summed over the
device planes as `reduce_trace.reduce` sums them.  params: {"modules":
regex, "bytes_in_per_row": n, "bits_out_per_row": n}.  No device time, or
no row on the device, returns nothing - never 0.
"""

import re


def read(params: dict, data: dict):
    tr = data["trace"]
    pat = re.compile(params["modules"])
    seconds = sum(s for name, s in tr["modules"].items() if pat.search(name))
    rows = data["telemetry_traced"].get("filter_rows_device", 0)
    if not tr["window_s"] or not seconds or not rows:
        return None
    needed = rows * (params["bytes_in_per_row"]
                     + params["bits_out_per_row"] / 8.0)
    return 100.0 * needed / data["peaks"]["hbm_bytes_per_s"] / seconds
