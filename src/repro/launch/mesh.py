"""Production mesh builders (functions, never module-level constants — the
dry-run must set XLA_FLAGS before any jax device initialization)."""
from __future__ import annotations

import jax


def _auto(n: int) -> tuple:
    """Auto axes: the steps place values with ``with_sharding_constraint``,
    which refers only to Auto axes (``jax.make_mesh`` defaults to Explicit)."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_dev_mesh():
    """Whatever is actually available (CPU tests / small runs)."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"), axis_types=_auto(2))


def mesh_axes(mesh) -> dict:
    """Logical -> physical axis mapping for a mesh (DESIGN.md §6)."""
    names = mesh.axis_names
    multi = "pod" in names
    return {
        "batch": ("pod", "data") if multi else ("data",),
        "fsdp": "data",
        "tp": "model",
        "rows": ("pod", "data", "model") if multi else ("data", "model"),
        "edges": ("pod", "data", "model") if multi else ("data", "model"),
        "cands": ("data", "model") if not multi else ("pod", "data", "model"),
        "seq": "model",
        "kv_all": ("pod", "data", "model") if multi else ("data", "model"),
    }
