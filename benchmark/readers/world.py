"""Reader `world`: a number from the world's own account of the window
(generator lateness, polls the broker served ...).

params: {"key": name} or {"numerator": name, "denominator": name}.
"""


def read(params: dict, data: dict):
    acc = data["account"]
    if "key" in params:
        return acc.get(params["key"])
    num, den = acc.get(params["numerator"]), acc.get(params["denominator"])
    if num is None or not den:
        return None
    return num / den
