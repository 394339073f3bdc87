"""runtime/limits.py::apply_allocator_policy: glibc's mallopt, set once a
process by the CLI's `_setup`.  The setting is a process's for life, so
every case runs in a fresh `python -c`."""

import ctypes
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

pytestmark = pytest.mark.skipif(
    not sys.platform.startswith("linux")
    or not hasattr(ctypes.CDLL(None), "mallopt"),
    reason="no mallopt in this libc")

# a spawned thread makes and keeps 2,000 `bytes` of 4,300 and looks, after
# each, at the end of the read-write mapping that holds it: an end that
# moved is one mprotect (nothing else changes part of a mapping)
HEAP_ENDS = """
import threading

def heap_ends():
    src, keep, ends = bytes(1 << 20), [], set()

    def work():
        for i in range(2000):
            b = src[i * 10:i * 10 + 4300]
            keep.append(b)
            with open("/proc/self/maps") as fh:
                for line in fh:
                    f = line.split()
                    lo, hi = (int(x, 16) for x in f[0].split("-"))
                    if lo <= id(b) < hi and f[1].startswith("rw"):
                        ends.add(hi)

    t = threading.Thread(target=work)
    t.start()
    t.join()
    return len(ends)
"""

# a libc whose mallopt counts its calls and refuses the parameters named
FAKE_LIBC = """
import logging
from transferia_tpu.runtime import limits

calls, warnings = [], []

class Handler(logging.Handler):
    def emit(self, record):
        warnings.append(record.getMessage())

limits.logger.addHandler(Handler(level=logging.WARNING))

class Libc:
    def __init__(self, refused=()):
        self.refused = refused
    def mallopt(self, param, value):
        calls.append(param)
        return 0 if param in self.refused else 1
"""


def run(code: str):
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], cwd=ROOT,
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("policy", [True, False], ids=["on", "off"])
def test_a_thread_heap_is_taken_whole_with_the_policy(policy):
    apply = ("from transferia_tpu.runtime.limits import "
             "apply_allocator_policy\napply_allocator_policy()\n"
             if policy else "")
    ends = run(apply + HEAP_ENDS + "import json\nprint(json.dumps("
                                   "heap_ends()))")
    if policy:
        assert ends <= 8
    else:
        assert ends >= 1000


def test_second_call_does_nothing():
    calls = run(FAKE_LIBC + """
limits.ctypes.CDLL = lambda name: Libc()
limits.apply_allocator_policy()
once = list(calls)
limits.apply_allocator_policy()
import json
print(json.dumps([once, calls, warnings]))
""")
    once, twice, warnings = calls
    assert sorted(once) == [-3, -2, -1]
    assert twice == once and warnings == []


@pytest.mark.parametrize("refused,named", [
    ([-3], ["M_MMAP_THRESHOLD"]),
    ([-1, -2, -3], ["M_TOP_PAD", "M_TRIM_THRESHOLD", "M_MMAP_THRESHOLD"]),
], ids=["one", "all"])
def test_a_refused_setting_is_a_warning_once(refused, named):
    warnings = run(FAKE_LIBC + f"""
limits.ctypes.CDLL = lambda name: Libc(refused={refused!r})
limits.apply_allocator_policy()
limits.apply_allocator_policy()
import json
print(json.dumps(warnings))
""")
    assert len(warnings) == len(named)
    for message, name in zip(warnings, named):
        assert name in message


def test_a_libc_without_mallopt_is_left_alone():
    warnings = run(FAKE_LIBC + """
limits.ctypes.CDLL = lambda name: object()
limits.apply_allocator_policy()
import json
print(json.dumps(warnings))
""")
    assert warnings == []


def test_the_cli_leaves_the_policy_applied(tmp_path):
    transfer = tmp_path / "transfer.yaml"
    transfer.write_text(textwrap.dedent("""
        id: allocator-policy
        type: SNAPSHOT_ONLY
        src: {type: sample, params: {preset: users, table: people, rows: 5}}
        dst: {type: memory, params: {sink_id: allocator_policy}}
    """))
    rc, ends = run(HEAP_ENDS + f"""
import contextlib, io, json
from transferia_tpu.cli.main import main
with contextlib.redirect_stdout(io.StringIO()):
    rc = main(["validate", "--transfer", {str(transfer)!r}])
print(json.dumps([rc, heap_ends()]))
""")
    assert rc == 0
    assert ends <= 8
