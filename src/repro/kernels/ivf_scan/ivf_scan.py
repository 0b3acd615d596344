"""Pallas TPU kernel for IVF centroid scoring (candidate-generation hot loop).

scores = Q (B, D) @ C^T with padded-centroid masking fused in. Grid tiles the
centroid axis; the query block stays VMEM-resident. On MS-MARCO-v2-scale
indices (2^16 cells x 128d) this is the matmul the CPU FAISS loop spends its
time in; on TPU it is one MXU pass per tile.

Tiling: BN centroids/step (lane-aligned 128), D <= 512 resident, B padded to
8 sublanes. VMEM/step = BN*D*4 + B*D*4 + B*BN*4 ~= 0.4 MB at defaults.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG = -1e30


def _kernel(q_ref, c_ref, nvalid_ref, out_ref, *, bn: int):
    q = q_ref[...]                                    # (Bp, D)
    c = c_ref[...]                                    # (BN, D)
    nvalid = nvalid_ref[0]                            # scalar: # real centroids
    i = pl.program_id(0)
    s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Bp, BN)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + i * bn
    out_ref[...] = jnp.where(col < nvalid, s, NEG)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def ivf_scan_pallas(q, centroids, *, block_n: int = 128,
                    interpret: bool | None = None):
    """q: (B, D); centroids: (N, D). Returns (B, N) fp32 scores
    (padded tail columns = -1e30 so downstream top-k ignores them)."""
    b, d = q.shape
    n = centroids.shape[0]
    bp = -(-b // 8) * 8
    np_ = -(-n // block_n) * block_n
    qp = jnp.pad(q, ((0, bp - b), (0, 0)))
    cp = jnp.pad(centroids, ((0, np_ - n), (0, 0)))
    nvalid = jnp.asarray([n], jnp.int32)

    out = pl.pallas_call(
        functools.partial(_kernel, bn=block_n),
        grid=(np_ // block_n,),
        in_specs=[
            pl.BlockSpec((bp, d), lambda i: (0, 0)),
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bp, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(qp, cp, nvalid)
    return out[:b, :n]
