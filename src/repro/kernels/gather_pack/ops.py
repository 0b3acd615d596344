"""Jit'd gather_pack op with Pallas/XLA dispatch."""
from __future__ import annotations

import jax

from repro.kernels.gather_pack.gather_pack import gather_pack_pallas
from repro.kernels.gather_pack.ref import gather_pack_ref


@jax.jit
def _ref_jit(pool, idx):
    return gather_pack_ref(pool, idx)


def gather_pack(pool, idx, *, use_pallas: bool = False,
                interpret: bool | None = None):
    if use_pallas:
        return gather_pack_pallas(pool, idx, interpret=interpret)
    return _ref_jit(pool, idx)
