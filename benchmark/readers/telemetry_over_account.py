"""Reader `telemetry_over_account`: a sum of the program's counters over
the window, over a count from the world's account of the window.

params: {"numerator": [counter keys], "per": account key, "scale": 1}.
For a share whose whole the program does not count: the rows the mask
took on the device over the masked cells the window's passes held
(`masked_cells_in_window`), where only one table of nine is masked and
the window's rows are not the mask's.  A counter the program lacks, or a
zero count, returns nothing.
"""


def read(params: dict, data: dict):
    tel = data["telemetry"]
    if any(k not in tel for k in params["numerator"]):
        return None
    den = data["account"].get(params["per"])
    if not den:
        return None
    return params.get("scale", 1) * sum(
        tel[k] for k in params["numerator"]) / den
