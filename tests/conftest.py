"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Tests never need the chip and must not claim it (one process per chip):
the shared recipe in transferia_tpu.testing pins the CPU platform with
eight virtual devices before any backend is touched (also used by
__graft_entry__'s dry run — keep one copy).  chip_smoke.py and
benchmark/run.py do NOT import this and run on the real TPU.
"""

import os

os.environ.setdefault("TRANSFERIA_TPU_TESTING", "1")

try:
    from transferia_tpu.testing import force_virtual_cpu_mesh

    if not force_virtual_cpu_mesh(8):  # pragma: no cover
        raise RuntimeError(
            "jax backend initialized before conftest ran — tests cannot "
            "force the virtual CPU mesh; run pytest from a fresh interpreter"
        )
except ImportError:  # pragma: no cover - jax is an optional extra;
    pass  # non-jax test files still run without it


def pytest_collection_modifyitems(config, items):
    """Auto-skip `requires_pyarrow`-marked tests when pyarrow is absent
    (pyarrow is an optional extra: `pip install 'transferia-tpu[arrow]'`)."""
    from transferia_tpu.interchange._pyarrow import have_pyarrow

    if have_pyarrow():
        return
    import pytest

    skip = pytest.mark.skip(
        reason="pyarrow not installed; pip install 'transferia-tpu[arrow]'")
    for item in items:
        if "requires_pyarrow" in item.keywords:
            item.add_marker(skip)
