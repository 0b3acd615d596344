"""Pallas kernels for the compute hot spots the paper optimizes with a
custom kernel. Each ``<name>/`` holds the kernel, a jnp oracle (``ref.py``)
and the dispatching op (``ops.py``)."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode. An explicit flag wins
    (the compile-for-a-described-chip tests pass ``False`` on a CPU host);
    ``None`` decides from the backend actually in use: interpreted on
    ``cpu``, compiled on ``tpu``. Any other platform has no Pallas TPU
    lowering, so it raises rather than silently interpreting."""
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels compile for 'tpu' and interpret on "
                       f"'cpu'; no mode for platform {platform!r}")
