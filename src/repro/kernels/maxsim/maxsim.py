"""Pallas TPU MaxSim kernel (paper eq. 1; the CUDA MaxSim-kernel analogue).

Grid over document tiles; the query token matrix stays VMEM-resident across
the whole grid (BlockSpec index_map pins block 0). The wrapper pads every
document to Tp tokens (a sublane multiple of 8) and flattens the docs to
(K*Tp, D) on the XLA side, so each step loads one (BK*Tp, D) tile and runs
ONE MXU matmul (BK*Tp, D) x (D, Lqp). The (BK*Tp, Lqp) scores split at a
sublane-tile boundary into (BK, Tp, Lqp); the doc-length mask, the max over
tokens and the masked sum over query tokens all keep BK as the leading
(untiled) axis, which is why lengths and scores travel as (K, 1, 1).

VMEM budget per step (defaults BK=16, Tp=184, D=32 lane-padded to 128,
fp32): doc tile 16*184*128*4 = 1.5 MB (double-buffered 3 MB) + scores
1.5 MB, under the 16 MB scoped VMEM default of a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG = -1e30


def maxsim_tail(s, qmask_ref, len_ref, out_ref, *, bk: int, t: int):
    """Shared by the maxsim and bitsim kernels: (BK*Tp, Lqp) token scores ->
    masked max over each doc's tokens, masked sum over query tokens, written
    as (BK, 1, 1) scores."""
    s = s.reshape(bk, t, s.shape[-1])                # (BK, Tp, Lqp)
    tpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(tpos < len_ref[...], s, NEG)       # lens (BK, 1, 1)
    m = jnp.max(s, axis=1, keepdims=True)            # (BK, 1, Lqp)
    out_ref[...] = jnp.sum(m * qmask_ref[...], axis=2, keepdims=True)


def _kernel(q_ref, qmask_ref, d_ref, len_ref, out_ref, *, bk: int, t: int):
    s = jax.lax.dot_general(d_ref[...], q_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    maxsim_tail(s, qmask_ref, len_ref, out_ref, bk=bk, t=t)


def pad_operands(q, q_mask, docs, doc_lens, block_docs: int):
    """Kernel-side layout shared with bitsim: Lq padded to 8 (qmask as
    (1, 1, Lqp)), T to a multiple of 8, K to ``block_docs``; docs flattened
    to (Kp*Tp, last) and lengths shaped (Kp, 1, 1)."""
    lq = q.shape[0]
    k, t, last = docs.shape
    lqp = -(-lq // 8) * 8
    tp = -(-t // 8) * 8
    kp = -(-k // block_docs) * block_docs
    q = jnp.pad(q, ((0, lqp - lq), (0, 0)))
    q_mask = jnp.pad(q_mask.astype(jnp.float32), (0, lqp - lq))
    docs = jnp.pad(docs, ((0, kp - k), (0, tp - t), (0, 0)))
    doc_lens = jnp.pad(doc_lens.astype(jnp.int32), (0, kp - k))
    return (q, q_mask.reshape(1, 1, lqp), docs.reshape(kp * tp, last),
            doc_lens.reshape(kp, 1, 1), tp, kp)


def tiled_call(kernel, q, q_mask, docs, doc_lens, *, tp: int, kp: int,
               block_docs: int, interpret: bool):
    """The grid both MaxSim kernels share: query and mask pinned, one
    (BK*Tp, last) doc tile and (BK, 1, 1) lengths/scores per step."""
    lqp, d_q = q.shape
    last = docs.shape[1]
    return pl.pallas_call(
        functools.partial(kernel, bk=block_docs, t=tp),
        grid=(kp // block_docs,),
        in_specs=[
            pl.BlockSpec((lqp, d_q), lambda i: (0, 0)),            # q pinned
            pl.BlockSpec((1, 1, lqp), lambda i: (0, 0, 0)),        # mask
            pl.BlockSpec((block_docs * tp, last), lambda i: (i, 0)),
            pl.BlockSpec((block_docs, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_docs, 1, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, 1, 1), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, q_mask, docs, doc_lens)


@functools.partial(jax.jit, static_argnames=("block_docs", "interpret"))
def maxsim_pallas(q, q_mask, docs, doc_lens, *, block_docs: int = 16,
                  interpret: bool | None = None):
    """q: (Lq, D); q_mask: (Lq,) float; docs: (K, T, D); doc_lens: (K,).

    Returns (K,) fp32 MaxSim scores. ``block_docs`` must be a multiple of 8
    to compile for the TPU. ``interpret=None`` follows the backend in use
    (``repro.kernels.resolve_interpret``)."""
    k = docs.shape[0]
    q, q_mask, docs, doc_lens, tp, kp = pad_operands(q, q_mask, docs,
                                                     doc_lens, block_docs)
    out = tiled_call(_kernel, q, q_mask, docs, doc_lens, tp=tp, kp=kp,
                     block_docs=block_docs, interpret=interpret)
    return out.reshape(kp)[:k]
