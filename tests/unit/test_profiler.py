"""Sampling CPU profiler (stats/profiler.py) + /debug/profile endpoint.

Reference parity: always-on pprof on the health port
(cmd/trcli/main.go:62-64); the perf methodology depends on it
(docs/benchmarks.md:44-60).
"""

import threading
import time
import urllib.request

from transferia_tpu.stats.profiler import Sampler, profile, sample_seconds


def _burn(deadline):
    x = 0
    while time.perf_counter() < deadline:
        for i in range(2000):
            x += i * i
    return x


def test_sampler_attributes_hot_function():
    # pin sampling to this thread: daemon threads leaked by earlier
    # tests in the shared pytest process otherwise absorb CPU-clock
    # deltas and break the single-threaded sum-to-wall invariant
    with profile(hz=250, threads={threading.get_ident()}) as p:
        _burn(time.perf_counter() + 0.4)
    rep = p.report
    assert rep.samples > 20
    top = rep.top(5)
    assert top, "no samples collected"
    assert any("_burn" in loc for loc, _, _ in top), top
    # self seconds sum to ~wall for single-threaded work
    assert 0.1 < sum(s for _, s, _ in rep.top(100)) <= rep.seconds + 0.1


def test_format_renders_table():
    with profile(hz=250) as p:
        _burn(time.perf_counter() + 0.2)
    text = p.report.format(5)
    assert "self" in text and "location" in text
    assert "Hz" in text


def test_sample_seconds_caps():
    rep = sample_seconds(0.1, hz=200)
    assert rep.seconds < 1.0


def test_debug_profile_endpoint():
    import threading

    from transferia_tpu.cli.main import _start_health_server

    port = _start_health_server(0)
    stop = time.perf_counter() + 1.5
    th = threading.Thread(target=_burn, args=(stop,), daemon=True)
    th.start()
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/debug/profile?seconds=0.4",
        timeout=10).read().decode()
    th.join()
    assert "location" in body
    assert "_burn" in body


# -- native-frame attribution ------------------------------------------------

def test_native_call_marker_scoped_and_reentrant():
    from transferia_tpu.stats.profiler import active_native, native_call

    ident = threading.get_ident()
    assert active_native(ident) is None
    with native_call("outer_sym"):
        assert active_native(ident) == "outer_sym"
        with native_call("inner_sym"):
            assert active_native(ident) == "inner_sym"
        assert active_native(ident) == "outer_sym"
    assert active_native(ident) is None


def test_sampler_tags_native_bound_frames():
    """A sample landing while the thread is inside a (marked) native
    call must blame the tagged native symbol, not the caller's Python
    line — the mis-attribution that inflated mask.py:104 with pure C++
    time in a CPU-host headline profile."""
    from transferia_tpu.stats.profiler import NATIVE_TAG, native_call

    stop = threading.Event()

    def burner():
        with native_call("hmac_sha256_hex"):
            x = 0
            while not stop.is_set():
                x += 1

    th = threading.Thread(target=burner, name="native-burner")
    th.start()
    try:
        s = Sampler(hz=250, threads={th.ident}).start()
        time.sleep(0.4)
        rep = s.stop()
    finally:
        stop.set()
        th.join()
    tagged = [loc for loc in rep.self_counts
              if NATIVE_TAG in loc and "hmac_sha256_hex" in loc]
    assert tagged, dict(rep.self_counts)
    # the caller context is preserved after the tag, not lost
    assert any("burner" in loc for loc in tagged)


def test_profiled_lib_proxy_marks_calls_and_forwards():
    from transferia_tpu.native import _ProfiledLib
    from transferia_tpu.stats.profiler import active_native

    class _FakeCdll:
        version = 7

    fake = _FakeCdll()
    seen = {}

    def myfn(x):
        seen["during"] = active_native(threading.get_ident())
        return x + 1

    fake.myfn = myfn
    lib = _ProfiledLib(fake)
    assert lib.version == 7           # non-callables pass through
    assert lib.myfn(41) == 42         # calls forward
    assert seen["during"] == "myfn"   # marker live DURING the call
    assert active_native(threading.get_ident()) is None  # and cleared
    assert hasattr(lib, "myfn")
    assert not hasattr(lib, "no_such_symbol")  # optional-symbol probes
    assert lib.myfn is lib.myfn       # wrapper cached


def test_real_native_lib_is_proxied_when_present():
    from transferia_tpu.native import _ProfiledLib, lib

    cdll = lib()
    if cdll is None:
        import pytest

        pytest.skip("native hostops unavailable in this environment")
    assert isinstance(cdll, _ProfiledLib)
    assert hasattr(cdll, "polyhash_varcol")
