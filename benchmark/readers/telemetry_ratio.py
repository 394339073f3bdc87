"""Reader `telemetry_ratio`: a ratio (or, without a denominator, a sum) of
the program's device counters over the window.

params: {"numerator": [keys], "scale": 1} and one of
  "denominator": [keys]               - a sum of counters;
  "per_row_and_masked_column": true   - the window's rows times the masked
                                        columns of the cell's chain: the
                                        mask-route counters count a row once
                                        per masked column.
A zero denominator returns nothing.
"""


def read(params: dict, data: dict):
    tel = data["telemetry"]
    if any(k not in tel for k in params["numerator"]):
        return None
    num = sum(tel[k] for k in params["numerator"])
    if params.get("per_row_and_masked_column"):
        den = data["rows"] * len(
            data["compared"].get("sha_block_bytes_per_row", {}))
    elif "denominator" in params:
        den = sum(tel.get(k, 0) for k in params["denominator"])
    else:
        return num
    if not den:
        return None
    return params.get("scale", 1) * num / den
