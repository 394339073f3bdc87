"""Test/dry-run bootstrap helpers shared by tests/conftest.py and
__graft_entry__.py.

On a machine with a chip JAX defaults to the TPU platform, and a test or
dry run that touched it would claim the chip (one process per chip).
Pointing JAX at a virtual CPU mesh takes three steps, all before any
backend touch: the env var, ``jax.config``, and ``XLA_FLAGS`` carrying
the virtual host device count before the CPU client spins up.  Keep
exactly one copy of the recipe, here.
"""

from __future__ import annotations

import os
import sys


def force_virtual_cpu_mesh(n_devices: int = 8) -> bool:
    """Point JAX at a virtual ``n_devices`` CPU mesh.

    Returns False when a jax backend is already live in this process (or
    liveness cannot be determined) — too late to flip platforms; the caller
    must re-exec a fresh interpreter with the env this call just set.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    if "jax" in sys.modules:
        import jax
        from jax._src import xla_bridge

        backends = getattr(xla_bridge, "_backends", None)
        if backends is None or backends:
            # live backend — or a jax refactor hid the attr, in which case
            # assume live: the optimistic path would flip the config
            # under a backend that already holds the chip.  A live
            # backend that already IS the virtual CPU mesh is fine as-is.
            return (jax.default_backend() == "cpu"
                    and len(jax.devices()) >= n_devices)

    import jax

    jax.config.update("jax_platforms", "cpu")
    return True
