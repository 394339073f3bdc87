"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell, warms it up, measures one window, checks what the window
landed against the plain reference, and prints one JSON object as the last
line of standard output.  Everything that belongs to one cell, one
configuration, one traffic kind or one per-layer metric is a file that is
found by its name (workloads/, configs/, traffic/, metrics/, readers/):
a later PR adds a cell or a metric by adding files and an entry in
BENCHMARK.json.

Two processes.  This one holds the chip and is the system under test: it
calls the entry a user calls (`trtpu activate`, `run_replication`) on the
cell's transfer YAML with only host, port, salt and path filled in -
no placement, no environment knob, no sink option is set.  The other
(`world.py`) is everything outside the system, and owns every clock and
count that an end-to-end metric is made of.

Without a TPU (or with fewer chips than the cell asks for) the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_PARAM = re.compile(r"\$\{([A-Za-z_][A-Za-z0-9_]*)\}")


def load_json(*parts: str):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def load_cell(name: str) -> tuple[dict, dict, dict, str]:
    """(BENCHMARK.json, the cell's file, its configuration's file, the
    configuration's transfer YAML as text).  Which configuration, which
    traffic and how many chips a cell takes is BENCHMARK.json's to say and
    nobody else's; the cell's file holds the traffic's parameters."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json("workloads", f"{name}.json")
    cell.update({k: entry[k] for k in ("config", "traffic", "chips")})
    cfg_file = next(c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_file)) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, os.path.splitext(cfg_file)[0] + ".yaml")) \
            as fh:
        transfer_yaml = fh.read()
    return bench, cell, config, transfer_yaml


def render_transfer(text: str, values: dict, cell: dict, out: str) -> str:
    """The configuration's transfer YAML with its ${...} parameters (host,
    port, brokers, salt, path) filled in and the cell's transformer chain,
    where the cell has one, in place of the configuration's."""
    import yaml

    def fill(m):
        if m.group(1) not in values:
            raise SystemExit(f"transfer YAML asks for ${{{m.group(1)}}}, "
                             f"which the world does not give")
        return str(values[m.group(1)])

    doc = yaml.safe_load(_PARAM.sub(fill, text))
    if "transformation" in cell:
        doc["transformation"] = json.loads(
            _PARAM.sub(fill, json.dumps(cell["transformation"])))
    with open(out, "w") as fh:
        yaml.safe_dump(doc, fh)
    return out


class World:
    """The parent's end of the pipe to world.py."""

    def __init__(self, cell, config, seed, seconds, work_dir):
        from benchmark import world

        ctx = multiprocessing.get_context("spawn")
        self.conn, child = ctx.Pipe()
        self.proc = ctx.Process(
            target=world.main, name="world",
            args=(child, ROOT, cell, config, seed, seconds, work_dir))
        self.proc.start()
        child.close()
        self.endpoints = self._answer()

    def _answer(self):
        if not self.conn.poll(900):
            raise RuntimeError("the world did not answer in 900 s")
        status, payload = self.conn.recv()
        if status != "ok":
            raise RuntimeError("the world failed:\n" + str(payload))
        return payload

    def __call__(self, command: str, **kwargs):
        self.conn.send((command, kwargs))
        return self._answer()

    def stop(self) -> None:
        try:
            if self.proc.is_alive():
                self.conn.send(("exit", {}))
                self.conn.poll(20)
        except (OSError, EOFError, BrokenPipeError):
            pass
        self.proc.join(timeout=20)
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(timeout=20)
        self.conn.close()


def _delta(now: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in now.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


class Context:
    """What a traffic kind's system side is handed: the cell, the rendered
    transfer, the world, and the window's instruments.  With --trace 0 the
    instruments do nothing."""

    def __init__(self, cell, config, seconds, trace, world, work_dir):
        self.cell = cell
        self.config = config
        self.seconds = seconds
        self.trace = bool(trace)
        self.world = world
        self.work_dir = work_dir
        self.transfer_yaml = ""
        self.warm_yaml = ""
        self.setup_s = None
        self.spans: list = []
        self.telemetry: dict = {}
        self.trace_dir = os.path.join(work_dir, "profile")
        self.traced_window_s = 0.0
        self._trace_t0 = 0.0
        self._telemetry0: dict = {}
        self.telemetry_traced: dict = {}
        self.traced_unix_ns = None
        self.span_epoch_unix = 0.0
        self._telemetry_trace0: dict = {}

    def window_open(self) -> None:
        from transferia_tpu.stats import trace
        from transferia_tpu.stats.trace import TELEMETRY

        self.setup_s = time.monotonic() - T_START
        self._telemetry0 = TELEMETRY.snapshot()
        if self.trace:
            trace.enable(True, capacity=2_000_000)
            trace.reset()

    def window_close(self) -> None:
        from transferia_tpu.stats import trace
        from transferia_tpu.stats.trace import TELEMETRY

        self.telemetry = _delta(TELEMETRY.snapshot(), self._telemetry0)
        if self.trace:
            self.spans = trace.spans()
            self.span_epoch_unix = trace.epoch_unix()
            trace.enable(False)

    def trace_start(self) -> None:
        if self.trace:
            import jax
            from transferia_tpu.stats.trace import TELEMETRY

            jax.profiler.start_trace(self.trace_dir)
            self._telemetry_trace0 = TELEMETRY.snapshot()
            self._trace_unix0 = time.time_ns()
            self._trace_t0 = time.monotonic()

    def trace_stop(self) -> None:
        if self.trace and self._trace_t0:
            import jax
            from transferia_tpu.stats.trace import TELEMETRY

            self.traced_window_s = time.monotonic() - self._trace_t0
            self.traced_unix_ns = (self._trace_unix0, time.time_ns())
            self.telemetry_traced = _delta(TELEMETRY.snapshot(),
                                           self._telemetry_trace0)
            jax.profiler.stop_trace()
            self._trace_t0 = 0.0

    def trace_for(self, seconds: float) -> None:
        if self.trace:
            self.trace_start()
            time.sleep(seconds)
            self.trace_stop()


def span_totals(spans: list, top: int = 30) -> dict:
    """{span name: [calls, self seconds, total seconds]}, largest self time
    first: every span the program recorded in the window, read or not."""
    per: dict[str, list] = {}
    for s in spans:
        if s[6] < 0:
            continue
        d = per.setdefault(s[0], [0, 0.0, 0.0])
        d[0] += 1
        d[1] += s[5]
        d[2] += s[4]
    return dict(sorted(per.items(), key=lambda kv: -kv[1][1])[:top])


def compiles(spans: list) -> list:
    """Every compile or persistent-cache load the window saw, in order of
    time: [seconds into the spans' epoch, seconds it took, whether the cache
    answered, the span it fired in, that span's args] - `device_dispatch`
    carries the rows and bytes of the chunk whose shape was new."""
    by_id = {s[9]: s for s in spans if s[6] >= 0}
    out = []
    for s in spans:
        if s[0] == "xla_compile" and s[6] < 0:
            args = s[7] or {}
            inside = by_id.get(s[10])
            out.append([s[3], args.get("seconds"), args.get("cache_hit"),
                        inside[0] if inside else None,
                        (inside[7] if inside else None) or {}])
    return sorted(out, key=lambda c: c[0])


def read_per_layer(bench: dict, cell_name: str, data: dict) -> dict:
    """Every per-layer metric that lists this cell, by the reader its file
    names; a reader that finds nothing to read leaves its metric out."""
    out = {}
    for m in bench["per_layer"]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        spec = load_json("metrics", f"{m['name']}.json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec.get("params", {}), data)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: int,
             require_chip: bool = True, shrink=None) -> dict:
    """The whole run; returns the result object.  `require_chip=False` and
    `shrink(cell, config)` are for the tests' rehearsals on the CPU, which
    print no result."""
    bench, cell, config, transfer_text = load_cell(name)
    if importlib.util.find_spec("transferia_tpu") is None:
        raise SystemExit("the system under test (transferia_tpu) is not in "
                         "this checkout")
    if shrink is not None:
        shrink(cell, config)
    kind = importlib.import_module(f"benchmark.traffic.{cell['kind']}")
    work_dir = tempfile.mkdtemp(prefix="trtpu-benchmark-")
    world = None
    try:
        world = World(cell, config, seed, seconds, work_dir)
        files = []
        if "table" in config:
            # before JAX is imported: the pool's workers are spawned copies
            from benchmark import datagen

            t = config["table"]
            files = datagen.generate(
                os.path.join(work_dir, "hits"), seed, int(t["rows"]),
                int(t["file_rows"]), int(t["batch_rows"]),
                workers=min(len(os.sched_getaffinity(0)), 12),
                columns_file=os.path.join(HERE, "configs", t["columns"]))
            warm_dir = os.path.join(work_dir, "hits-warm")
            os.makedirs(warm_dir)
            os.link(files[0],
                    os.path.join(warm_dir, os.path.basename(files[0])))

        from transferia_tpu import native
        from transferia_tpu.runtime.backend import (
            describe_backend,
            setup_compile_cache,
        )

        setup_compile_cache()
        backend = describe_backend()
        if require_chip and (backend["platform"] != "tpu"
                             or backend["device_count"] < cell["chips"]):
            raise SystemExit(
                f"no chip for this cell: platform={backend['platform']!r} "
                f"devices={backend['device_count']}, the cell asks for "
                f"{cell['chips']} TPU chip(s)")
        import jax

        native.build()
        ctx = Context(cell, config, seconds, trace, world, work_dir)
        values = dict(world.endpoints)
        if files:
            values["DATA_PATH"] = os.path.dirname(files[0])
        ctx.transfer_yaml = render_transfer(
            transfer_text, values, cell,
            os.path.join(work_dir, "transfer.yaml"))
        if files:
            ctx.warm_yaml = render_transfer(
                transfer_text, {**values, "DATA_PATH": warm_dir}, cell,
                os.path.join(work_dir, "transfer-warm.yaml"))

        if trace:
            # spans on from warm-up on, as the traced window will run
            from transferia_tpu.stats import trace as program_trace

            program_trace.enable(True, capacity=2_000_000)
        account = kind.drive(ctx)

        stats = [d.memory_stats() or {} for d in jax.devices()]
        device = {"platform": backend["platform"],
                  "kind": backend["device_kind"],
                  "count": backend["device_count"],
                  "memory_peak_bytes": max(
                      (s.get("peak_bytes_in_use", 0) for s in stats),
                      default=0)}
        # the reference, once the window has closed and the peak is read
        verify_args = {"files": files} if files else {}
        compared = world("verify", **verify_args)
        numbers = dict(kind.account_numbers(account))
        numbers.update(compared["numbers"])
        # of what the kind measures, the metrics BENCHMARK.json gives this
        # cell end to end
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]
                 if name in m.get("workloads", [name])}
        end_to_end = {k: v for k, v in kind.end_to_end(account).items()
                      if k in units}
        end_to_end["setup_s"] = ctx.setup_s
        result = {
            "correct": all(v <= lim for v, lim in numbers.values()),
            "attempted": compared["attempted"],
            "failed": compared["failed"],
        }
        if trace:
            from benchmark import reduce_trace

            with open(os.path.join(HERE, "peaks.json")) as fh:
                peaks = json.load(fh)
            if device["kind"] not in peaks and require_chip:
                raise SystemExit(f"no peaks for device kind "
                                 f"{device['kind']!r} in peaks.json")
            reduced = reduce_trace.reduce_dir(
                ctx.trace_dir, ctx.traced_window_s, ctx.spans,
                ctx.span_epoch_unix, ctx.traced_unix_ns)
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            data = {"spans": ctx.spans, "telemetry": ctx.telemetry,
                    "telemetry_traced": ctx.telemetry_traced,
                    "account": account, "compared": compared["info"],
                    "trace": reduced, "rows": kind.window_rows(account),
                    "cell": cell, "peaks": peaks.get(device["kind"], {})}
            result["metrics"] = read_per_layer(bench, name, data)
            result["breakdown"] = reduced["breakdown"]
            result["span_totals"] = span_totals(ctx.spans)
            result["compiles"] = compiles(ctx.spans)
            result["trace_check"] = {
                k: reduced[k] for k in ("clock", "devices_busy",
                                        "busy_per_device_s",
                                        "longest_gap_s", "modules")}
        else:
            result["metrics"] = {
                k: {"value": v, "unit": units[k]}
                for k, v in end_to_end.items()}
        result["device"] = device
        result["account"] = {k: v for k, v in account.items()
                             if k != "passes"}
        if "passes" in account:
            result["account"]["pass_seconds"] = [
                p["seconds"] for p in account["passes"]]
            result["account"]["pass_compile_seconds"] = [
                p["compiled"]["compile_seconds"] for p in account["passes"]]
            result["account"]["standin_cost"] = \
                account["passes"][-1]["standin_cost"]
        result["info"] = compared["info"]
        result["telemetry"] = ctx.telemetry
        result["compared"] = numbers
        return result
    finally:
        if world is not None:
            world.stop()
        shutil.rmtree(work_dir, ignore_errors=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    import logging

    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(levelname).1s %(name)s: %(message)s")
    result = run_cell(args.workload, args.seed, args.seconds, args.trace)
    sys.stdout.flush()
    print(f"compared ({'correct' if result['correct'] else 'NOT CORRECT'}):",
          file=sys.stderr)
    for k, (v, lim) in result["compared"].items():
        print(f"  {k} = {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
