"""The world: everything outside the system under test, in a process of its
own - the Kafka and ClickHouse stand-ins, the load generator, the clocks
that stamp due times and arrivals, the ground truth and the comparison.

Started with `spawn` before the parent imports JAX, and never imports JAX
itself: a chip belongs to one process, and the clock must not belong to the
system that is being timed.  The parent sends (command, kwargs) over a pipe
and gets ("ok", result) or ("error", traceback) back; the traffic kind named
in the cell's file supplies the commands.
"""

from __future__ import annotations

import importlib
import sys
import traceback


def main(conn, root: str, cell: dict, config: dict, seed: int,
         seconds: float, work_dir: str) -> None:
    if root not in sys.path:
        sys.path.insert(0, root)
    side = None
    try:
        from benchmark import rowbinary

        rowbinary.build()
        kind = importlib.import_module(f"benchmark.traffic.{cell['kind']}")
        side = kind.World(cell, config, seed, seconds, work_dir)
        if "jax" in sys.modules:
            raise RuntimeError("the world imported JAX")
        conn.send(("ok", side.endpoints()))
        while True:
            command, kwargs = conn.recv()
            if command == "exit":
                conn.send(("ok", None))
                return
            try:
                conn.send(("ok", getattr(side, "cmd_" + command)(**kwargs)))
            except Exception:
                conn.send(("error", traceback.format_exc()))
    except (EOFError, KeyboardInterrupt):
        return
    except Exception:
        conn.send(("error", traceback.format_exc()))
    finally:
        if side is not None:
            side.close()
        conn.close()
