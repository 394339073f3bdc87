"""Which JAX backend this process got, and where its compile cache lives.

One process owns a chip: the first JAX call in a process claims every
device its host shows, and a second process that asks for them fails at
start-up ("The TPU is already in use by process …"; with JAX_PLATFORMS
empty it would pick the CPU platform instead, without a word).  Nothing
on the transfer path may therefore guess — `describe_backend()` resolves
the backend once and every entry point (CLI, chip_smoke.py)
logs or asserts on what it returned.
"""

from __future__ import annotations

import importlib.metadata
import logging
import os
import pathlib
import sys
import threading

logger = logging.getLogger(__name__)

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]

_lock = threading.Lock()
_described: dict | None = None
_logged = False


def setup_compile_cache() -> str:
    """Place JAX's persistent compile cache; returns its directory.

    Call before the first jit.  `JAX_COMPILATION_CACHE_DIR` set: JAX
    reads it itself and nothing is touched here.  Unset: the cache goes
    to `<checkout>/.jax_cache` — a fixed path, since a name with a pid
    or a timestamp in it would never be found by the next process.  The
    choice is exported through the same variable, so JAX picks it up at
    import (commands that never import JAX do not pay for it here) and
    child processes share the directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:  # already imported: the variable was read then
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


def describe_backend() -> dict:
    """The backend as JAX reports it, resolved once per process.

    Touching `jax.devices()` claims the chip, so this is also the point
    where a process that cannot have it fails — loudly, with JAX's own
    error, not with a quiet CPU run.
    """
    global _described
    with _lock:
        if _described is None:
            import jax

            devices = jax.devices()
            _described = {
                "platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "device_count": len(devices),
                "jax": jax.__version__,
                "jaxlib": _version("jaxlib"),
                "libtpu": _version("libtpu"),
            }
        return dict(_described)


def log_backend_once() -> dict:
    """Log the resolved backend the first time a device step is planned
    (transform/chain.py), so a worker that came up on the CPU platform
    says so in its own log."""
    global _logged
    info = describe_backend()
    with _lock:
        first, _logged = not _logged, True
    if first:
        logger.info(
            "jax backend: platform=%s device_kind=%s devices=%d "
            "(jax %s, jaxlib %s, libtpu %s)",
            info["platform"], info["device_kind"], info["device_count"],
            info["jax"], info["jaxlib"], info["libtpu"])
    return info


def require_tpu() -> dict:
    """The backend description, or SystemExit when it is not a TPU.
    For chip_smoke.py: a run that finds no chip prints nothing under a
    device metric's name."""
    info = describe_backend()
    if info["platform"] != "tpu":
        raise SystemExit(
            f"no TPU: jax resolved platform={info['platform']!r} "
            f"device_kind={info['device_kind']!r} — refusing to run a "
            f"device measurement on it")
    return info
