"""chip_smoke.py on a machine without a chip.

Two facts only a CPU host can pin: with no TPU the script refuses (exit
code, no result line, no rate), and its legs hold together end to end in
`--rehearsal` (CPU backend, tiny sizes) so chip time is never spent
debugging the script.  Each runs the script as a child process: it owns
its own JAX backend, compile-cache setting and loggers.
"""

from __future__ import annotations

import json
import os
import resource
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


# the rehearsal's 73-column table is 10.8 MB: one file of it would not fit
FILE_SIZE_LIMIT = 8 << 20


def _limit_file_size():
    resource.setrlimit(resource.RLIMIT_FSIZE,
                       (FILE_SIZE_LIMIT, FILE_SIZE_LIMIT))


def _run(args, tmp_path, **env):
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jaxcache"),
                 **env}
    return subprocess.run(
        [sys.executable, SCRIPT, "--out", str(tmp_path / "out"),
         "--data-dir", str(tmp_path / "data"), *args],
        capture_output=True, text=True, timeout=600, env=child_env,
        cwd=str(tmp_path), preexec_fn=_limit_file_size)


def _result_lines(stdout: str) -> list[str]:
    return [ln for ln in stdout.splitlines() if ln.startswith('{"ok"')]


def test_refuses_to_run_without_a_tpu(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert _result_lines(proc.stdout) == []
    assert "rows/s" not in proc.stdout
    # it stopped at the backend check: no data generated, no leg run
    assert "snapshot leg" not in proc.stdout
    assert not (tmp_path / "data").exists()


def test_rehearsal_drives_every_leg_and_prints_no_result(tmp_path):
    proc = _run(["--rehearsal"], tmp_path)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = proc.stdout
    assert "REHEARSAL" in out
    assert _result_lines(out) == []
    assert "rows/s" not in out and "ns/row" not in out
    for leg in ("snapshot leg: wide", "snapshot leg: ten",
                "replication leg"):
        assert leg in out
    assert "FAIL" not in out
    with open(tmp_path / "out" / "chip_smoke.json") as fh:
        summary = json.load(fh)
    assert summary["rehearsal"] is True and summary["ok"] is True
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["backend"]["platform"] == "cpu"
    dev = summary["snapshot"]["ten"]["passes"]["device"]
    assert dev["telemetry"]["device_launches"] > 0
    assert summary["replication"]["landed"] == \
        summary["replication"]["produced"] > 0
    # the machine that checks the script limits the size of one file:
    # both tables are directories of part files, read as one table
    assert summary["file_size_limit_bytes"] == FILE_SIZE_LIMIT
    for table in summary["data"]["tables"].values():
        assert table["files"] > 1
        assert table["largest_file_mb"] * 1e6 < FILE_SIZE_LIMIT
    # the cache went where the variable said, and nowhere else
    assert not (tmp_path / ".jax_cache").exists()
    assert summary["compile_cache"]["dir"] == str(tmp_path / "jaxcache")
    # scratch data is gone
    assert not (tmp_path / "data").exists()
