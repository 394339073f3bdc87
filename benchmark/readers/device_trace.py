"""Reader `device_trace`: numbers from the profiler's trace of the traced
part of the window, as `reduce_trace.py` reduced it.

params {"what": "idle_share"}: 100 x (1 - busy / traced window), in %;
100 where the trace is there and nothing ran on the device (`auto` kept
the chain on the host): the chip was idle, and that is the reading.

params {"what": "hbm_roofline", "modules": regex}: the least time the
chip's memory could take for the bytes the mask needs, over the time the
matching XLA modules ran, in %.  The bytes are counted here, from what the
world's generator knows and whatever implements the mask must move: for
every (row, masked column) the device took on the flat route, the value
padded to SHA-256 blocks in and a 32-byte digest out.  The route counters do
not say which column a row belonged to, so a row is charged the mean of the
masked columns' block bytes (PERF.md, Open questions).  No device time, or
no row on the device, returns nothing - never 0.
"""

import re


def needed_bytes(flat_row_columns: int, block_bytes_per_row: dict) -> float:
    if not block_bytes_per_row:
        return 0.0
    mean_blocks = sum(block_bytes_per_row.values()) / len(block_bytes_per_row)
    return flat_row_columns * (mean_blocks + 32.0)


def read(params: dict, data: dict):
    tr = data["trace"]
    if not tr["window_s"]:
        return None
    if params["what"] == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if params["what"] == "hbm_roofline":
        pat = re.compile(params["modules"])
        seconds = sum(s for name, s in tr["modules"].items()
                      if pat.search(name))
        # the trace covers part of the window: the rows are those the
        # route counter took while the trace ran
        flat = data["telemetry_traced"].get("mask_rows_device_flat", 0)
        blocks = data["compared"].get("sha_block_bytes_per_row")
        if not tr["busy_s"] or not seconds or not flat or not blocks:
            return None
        least = needed_bytes(flat, blocks) / data["peaks"]["hbm_bytes_per_s"]
        return 100.0 * least / seconds
    raise ValueError(f"device_trace: unknown reading {params['what']!r}")
