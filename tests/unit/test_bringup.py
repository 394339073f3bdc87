"""Bring-up plumbing: where the compile cache goes, how the host-ops
library is built and what a failed build means, which backend a worker
says it got, and which fleet child is left the host's accelerator."""

from __future__ import annotations

import logging
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# -- compile cache ----------------------------------------------------------

# jax's config option is the variable's name in lower case (spelled this
# way so the tree keeps ONE site that names the option: the helper)
_PRINT_JAX_SETTING = (
    "print(getattr(jax.config, 'JAX_COMPILATION_CACHE_DIR'.lower()))\n")


def _cache_probe(code: str, **env) -> list[str]:
    child_env = {k: v for k, v in os.environ.items()
                 if k != "JAX_COMPILATION_CACHE_DIR"}
    child_env.update(env)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import os, sys\n"
         "from transferia_tpu.runtime.backend import setup_compile_cache\n"
         + code],
        capture_output=True, text=True, timeout=120, env=child_env,
        cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_compile_cache_defaults_to_the_checkout_and_skips_jax_import():
    got, exported, jax_loaded = _cache_probe(
        "print(setup_compile_cache())\n"
        "print(os.environ['JAX_COMPILATION_CACHE_DIR'])\n"
        "print('jax' in sys.modules)\n")
    assert got == exported == os.path.join(ROOT, ".jax_cache")
    assert jax_loaded == "False"


def test_compile_cache_set_from_outside_is_left_alone(tmp_path):
    outside = str(tmp_path / "elsewhere")
    got, seen_by_jax = _cache_probe(
        "import jax\n"
        "print(setup_compile_cache())\n"
        + _PRINT_JAX_SETTING,
        JAX_COMPILATION_CACHE_DIR=outside, JAX_PLATFORMS="cpu")
    assert got == seen_by_jax == outside


def test_compile_cache_reaches_an_already_imported_jax():
    (seen_by_jax,) = _cache_probe(
        "import jax\n"
        "setup_compile_cache()\n"
        + _PRINT_JAX_SETTING,
        JAX_PLATFORMS="cpu")
    assert seen_by_jax == os.path.join(ROOT, ".jax_cache")


# -- native library ---------------------------------------------------------

def test_native_library_is_named_by_a_hash_of_its_sources(tmp_path,
                                                          monkeypatch):
    from transferia_tpu import native

    assert native.lib() is not None
    so = native.so_path()
    assert re.fullmatch(r"libhostops-[0-9a-f]{16}\.so", so.name)
    assert so.exists()
    # same bytes, arbitrary mtimes (a copied tree): same library name;
    # one changed byte in an #included part: a different one
    for name in native._SOURCES + native._INCLUDES:
        shutil.copy(native._DIR / name, tmp_path / name)
    monkeypatch.setattr(native, "_DIR", tmp_path)
    assert native.so_path().name == so.name
    with open(tmp_path / native._INCLUDES[0], "a") as fh:
        fh.write("\n// edited\n")
    assert native.so_path().name != so.name


def test_native_build_without_a_compiler_raises(tmp_path, monkeypatch):
    from transferia_tpu import native

    for name in native._SOURCES + native._INCLUDES:
        shutil.copy(native._DIR / name, tmp_path / name)
    monkeypatch.setattr(native, "_DIR", tmp_path)
    monkeypatch.setattr(shutil, "which", lambda name: None)
    with pytest.raises(native.NativeBuildError, match="no C\\+\\+ compiler"):
        native.build()


def test_native_lib_failure_is_an_error_on_every_call(monkeypatch):
    from transferia_tpu import native

    def broken(force=False):
        raise native.NativeBuildError("compile failed")

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_failure", None)
    monkeypatch.setattr(native, "build", broken)
    monkeypatch.delenv("TRANSFERIA_TPU_NO_NATIVE", raising=False)
    for _ in range(2):  # the first error again, without recompiling
        with pytest.raises(native.NativeBuildError,
                           match="compile failed"):
            native.lib()
    # the numpy paths remain available, but only when asked for
    monkeypatch.setenv("TRANSFERIA_TPU_NO_NATIVE", "1")
    assert native.lib() is None


# -- which backend a worker got ---------------------------------------------

def test_first_fused_plan_logs_the_resolved_backend_once(monkeypatch,
                                                         caplog):
    from transferia_tpu.abstract.schema import TableID, new_table_schema
    from transferia_tpu.runtime import backend
    from transferia_tpu.transform import build_chain
    from transferia_tpu.transform.fused import set_device_fusion

    monkeypatch.setattr(backend, "_logged", False)
    schema = new_table_schema([("id", "int64", True), ("url", "utf8")])
    cfg = {"transformers": [{"mask_field": {"columns": ["url"],
                                            "salt": "s"}}]}
    set_device_fusion(True)
    try:
        with caplog.at_level(logging.INFO,
                             logger="transferia_tpu.runtime.backend"):
            for _ in range(2):
                build_chain(cfg).plan_for(TableID("t", "t"), schema)
    finally:
        set_device_fusion(None)
    lines = [r.getMessage() for r in caplog.records
             if r.name == "transferia_tpu.runtime.backend"]
    assert len(lines) == 1
    assert "platform=cpu" in lines[0] and "devices=8" in lines[0]


def test_require_tpu_refuses_the_cpu_backend():
    from transferia_tpu.runtime.backend import require_tpu

    with pytest.raises(SystemExit, match="no TPU"):
        require_tpu()


# -- one process per chip ---------------------------------------------------

def test_supervisor_leaves_the_accelerator_to_one_live_child(
        tmp_path, caplog, monkeypatch):
    """process mode: the first live child inherits the environment (and
    with it the host's accelerator); every later one is started on the
    CPU platform on purpose, until the owner is gone."""
    from transferia_tpu.fleet.worker import WorkerSupervisor

    def argv(index: int) -> list[str]:
        out = tmp_path / f"w{index}.txt"
        return [sys.executable, "-c",
                "import os, sys, time\n"
                f"open({str(out)!r}, 'w').write("
                "os.environ.get('JAX_PLATFORMS', '<unset>'))\n"
                "time.sleep(float(sys.argv[1]))", "30"]

    # what a machine with a chip exports (the children never import jax)
    inherited = "tpu,cpu"
    monkeypatch.setenv("JAX_PLATFORMS", inherited)
    sup = WorkerSupervisor(mode="process", spawn_argv=argv)

    def platform_of(index: int) -> str:
        path = tmp_path / f"w{index}.txt"
        for _ in range(200):
            if path.exists() and path.read_text():
                return path.read_text()
            import time

            time.sleep(0.05)
        raise AssertionError(f"worker {index} never reported")

    try:
        with caplog.at_level(logging.WARNING,
                             logger="transferia_tpu.fleet.worker"):
            first, second = sup.spawn(), sup.spawn()
            assert platform_of(first) == inherited
            assert platform_of(second) == "cpu"
            assert any("starts on the CPU platform" in r.getMessage()
                       for r in caplog.records)
            # the owner dies: the next child is left the accelerator
            owner = sup._chip_owner
            owner.proc.kill()
            owner.proc.wait(timeout=10)
            third = sup.spawn()
            assert platform_of(third) == inherited
            assert sup._chip_owner.index == third
    finally:
        for h in sup.handles():
            h.proc.kill()
            h.proc.wait(timeout=10)
