"""Reader `span_longest_share`: the longest single span of a name within
the traced part of the window, as a share of that part, in %.

params: {"span": name}.  For `part` it says how much of a pass hangs on
one part thread: 100 means one part was the whole pass, 100 / threads is
an even split.  The traced part is the window's first pass; the spans'
clock starts with the window, so its spans are those that end within the
traced seconds.  No such span, or no traced part, returns nothing.
"""


def read(params: dict, data: dict):
    window = data["trace"]["window_s"]
    if not window:
        return None
    durs = [s[4] for s in data["spans"]
            if s[0] == params["span"] and s[6] >= 0
            and s[3] + s[4] <= window]
    if not durs:
        return None
    return 100.0 * max(durs) / window
