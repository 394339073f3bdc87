"""Reader `span_self_time`: self seconds of the named program spans, summed
over threads, per million of the rows those spans worked on (`s/Mrow`).

params: {"spans": [names], "per": key}.  `per` names the count in the
world's account of the window that the spans' work is divided by: a
replication source parses what it fetches (`consumed_in_window`), which in a
window is not what the sink lands; without it the count is the window's
rows.  An optional `where` ({arg: value}) keeps only the spans whose args
carry those values: `sink_push` names the ClickHouse POST
(`direction="clickhouse_http"`), the arrow_ipc write and the asynchronizer's
wrapper alike.  Nothing to read (no such span recorded, or no such row) returns
nothing.
"""


def read(params: dict, data: dict):
    names = set(params["spans"])
    where = params.get("where", {}).items()
    hits = [s for s in data["spans"] if s[0] in names and s[6] >= 0
            and all((s[7] or {}).get(k) == v for k, v in where)]
    rows = data["account"].get(params["per"]) if "per" in params \
        else data["rows"]
    if not hits or not rows:
        return None
    return sum(s[5] for s in hits) / (rows / 1e6)
