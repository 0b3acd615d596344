"""Jit'd public MaxSim op: dispatches Pallas kernel (TPU) or the jnp oracle
(XLA fallback used by the dry-run and CPU paths)."""
from __future__ import annotations

import jax

from repro.kernels.maxsim.maxsim import maxsim_pallas
from repro.kernels.maxsim.ref import maxsim_ref


@jax.jit
def _ref_jit(q, q_mask, docs, doc_lens):
    return maxsim_ref(q, q_mask, docs, doc_lens)


def maxsim(q, q_mask, docs, doc_lens, *, use_pallas: bool = False,
           interpret: bool | None = None, block_docs: int = 16):
    """MaxSim scores (K,) fp32. use_pallas=True -> TPU kernel
    (interpret=None follows the backend: interpreted on CPU,
    compiled on TPU)."""
    if use_pallas:
        return maxsim_pallas(q, q_mask, docs, doc_lens,
                             block_docs=block_docs, interpret=interpret)
    return _ref_jit(q, q_mask, docs, doc_lens)
