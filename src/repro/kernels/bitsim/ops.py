"""Jit'd public packed-bit MaxSim op: dispatches the Pallas kernel (TPU) or
the jnp oracle (XLA fallback used by the CPU filtering path)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.bitsim.bitsim import bitsim_pallas
from repro.kernels.bitsim.ref import bitsim_ref


@functools.partial(jax.jit, static_argnames=("d",))
def _ref_jit(q, q_mask, docs_packed, doc_lens, d):
    return bitsim_ref(q, q_mask, docs_packed, doc_lens, d=d)


def bitsim(q, q_mask, docs_packed, doc_lens, *, d: int,
           use_pallas: bool = False, interpret: bool | None = None,
           block_docs: int = 16):
    """Asymmetric MaxSim scores (K,) fp32: full-precision query tokens vs
    sign-packed uint32 document lanes. use_pallas=True -> TPU kernel
    (interpret=None follows the backend: interpreted on CPU,
    compiled on TPU)."""
    if use_pallas:
        return bitsim_pallas(q, q_mask, docs_packed, doc_lens, d=d,
                             block_docs=block_docs, interpret=interpret)
    return _ref_jit(q, q_mask, docs_packed, doc_lens, d)
