"""Pallas gather_pack kernel — the TPU analogue of ESPN's CUDA
"restructuring kernel" (paper §5.1): parse ragged BOW records arriving from
storage into the padded (docs, T, D) layout the MaxSim kernel consumes.

Input is the flat token-row pool (R, D) that the storage engine DMA'd into
HBM plus a (K, T) row-index table (-1 = padding). The kernel walks one doc
tile per grid step and gathers rows with dynamic loads; on real TPU hardware
the pool stays in ANY/HBM memory space and each row move is an async DMA
(pltpu.make_async_copy) — the dynamic-load form below is semantically
identical and is what interpret mode validates.

This replaces "multiple calls to cudaMemcpyDeviceToDevice" (paper) with one
fused pass; the XLA fallback in ops.py is a take+where.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(idx_ref, pool_ref, out_ref, *, t: int):
    idx = idx_ref[...]                                # (1, T)

    def body(j, _):
        row = jnp.maximum(idx[0, j], 0)
        vec = pool_ref[pl.ds(row, 1), :]                             # (1, D)
        valid = (idx[0, j] >= 0).astype(vec.dtype)
        out_ref[pl.ds(j, 1), :] = vec * valid
        return 0

    jax.lax.fori_loop(0, t, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pack_pallas(pool, idx, *, interpret: bool | None = None):
    """pool: (R, D) token rows; idx: (K, T) int32 row ids (-1 pad).

    Returns (K, T, D) padded doc tiles (pad rows zeroed).
    """
    r, d = pool.shape
    k, t = idx.shape
    out = pl.pallas_call(
        functools.partial(_kernel, t=t),
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, t), lambda i: (i, 0)),
            pl.BlockSpec((r, d), lambda i: (0, 0)),   # whole pool resident
        ],
        out_specs=pl.BlockSpec((1 * t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k * t, d), pool.dtype),
        interpret=resolve_interpret(interpret),
    )(idx, pool)
    return out.reshape(k, t, d)
