"""Without a TPU a run exits non-zero and prints no result."""

import os
import subprocess
import sys

from benchmark import run


def test_a_run_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    p = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "kafka2ch-catchup", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout and '"metrics"' not in p.stdout
    assert "no chip for this cell" in p.stderr
