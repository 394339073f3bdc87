"""Reader `span_tail`: a percentile of the durations of one program span,
in milliseconds.  params: {"span": name, "percentile": 95}."""

import numpy as np


def read(params: dict, data: dict):
    durs = [s[4] for s in data["spans"]
            if s[0] == params["span"] and s[6] >= 0]
    if not durs:
        return None
    return float(np.percentile(durs, params["percentile"])) * 1e3
