"""Jit'd decode-attention op: Pallas kernel (TPU) or jnp oracle (XLA)."""
from __future__ import annotations

import jax

from repro.kernels.flash_decode.flash_decode import flash_decode_pallas
from repro.kernels.flash_decode.ref import flash_decode_ref


@jax.jit
def _ref_jit(q, k_cache, v_cache, lengths):
    return flash_decode_ref(q, k_cache, v_cache, lengths)


def flash_decode(q, k_cache, v_cache, lengths, *, use_pallas: bool = False,
                 interpret: bool | None = None, chunk: int = 512):
    if use_pallas:
        return flash_decode_pallas(q, k_cache, v_cache, lengths,
                                   chunk=chunk, interpret=interpret)
    return _ref_jit(q, k_cache, v_cache, lengths)
