"""Jit'd public FDE scan op: dispatches the Pallas kernel (TPU) or the jnp
oracle (XLA fallback used by the CPU brute-force candidate path)."""
from __future__ import annotations

import jax

from repro.kernels.fdescan.fdescan import fdescan_pallas
from repro.kernels.fdescan.ref import fdescan_ref

_ref_jit = jax.jit(fdescan_ref)


def fdescan(q, docs, *, use_pallas: bool = False,
            interpret: bool | None = None, block_docs: int = 256):
    """Batched FDE scoring: q (B, D) x docs (N, D) -> (B, N) fp32 inner
    products. use_pallas=True -> TPU kernel (interpret=None follows the
    backend: interpreted on CPU, compiled on TPU)."""
    if use_pallas:
        return fdescan_pallas(q, docs, block_docs=block_docs,
                              interpret=interpret)
    return _ref_jit(q, docs)
