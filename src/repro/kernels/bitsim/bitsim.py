"""Pallas TPU packed-bit asymmetric MaxSim kernel (Nardini et al. 2024).

Same grid, padding and reduction as the full-precision MaxSim kernel
(``repro.kernels.maxsim.maxsim``): query pinned in VMEM, one flattened
(BK*Tp, W) doc tile per step, scores written as (BK, 1, 1). The tile arrives
as sign-packed 32-bit lanes — 16-32x less VMEM/HBM traffic than bf16/fp32
tokens. Each step expands the W words to W*32 lanes (lane c takes bit c%32
of word c//32, via int32 shifts against a 2-D iota), maps bits to {-1, +1}
with a select, and runs ONE MXU matmul against the query zero-padded from D
to W*32 columns, so the pad bits of the last word contribute nothing.

VMEM budget per step (defaults BK=16, Tp=184, W=1 i.e. D=32): packed tile
16*184*128*4 = 1.5 MB lane-padded, the same again for the unpacked signs and
the scores — under the 16 MB scoped VMEM default of a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.maxsim.maxsim import maxsim_tail, pad_operands, tiled_call


def _kernel(q_ref, qmask_ref, d_ref, len_ref, out_ref, *, bk: int, t: int):
    words = d_ref[...]                               # (BK*Tp, W) int32
    rows, w = words.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, w * 32), 1)
    word = jnp.broadcast_to(words[:, :1], lane.shape)
    for i in range(1, w):
        word = jnp.where(lane // 32 == i, words[:, i:i + 1], word)
    bits = (word >> (lane % 32)) & 1
    sgn = jnp.where(bits == 1, 1.0, -1.0)            # (BK*Tp, W*32) fp32
    s = jax.lax.dot_general(sgn, q_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    maxsim_tail(s, qmask_ref, len_ref, out_ref, bk=bk, t=t)


@functools.partial(jax.jit,
                   static_argnames=("d", "block_docs", "interpret"))
def bitsim_pallas(q, q_mask, docs_packed, doc_lens, *, d: int,
                  block_docs: int = 16, interpret: bool | None = None):
    """q: (Lq, D) float; q_mask: (Lq,); docs_packed: (K, T, W) uint32 with
    W*32 >= d == D; doc_lens: (K,).

    Returns (K,) fp32 asymmetric MaxSim scores. ``interpret=None`` follows
    the backend in use (``repro.kernels.resolve_interpret``)."""
    k, _, w = docs_packed.shape
    q = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, w * 32 - d)))
    words = jax.lax.bitcast_convert_type(docs_packed.astype(jnp.uint32),
                                         jnp.int32)
    q, q_mask, words, doc_lens, tp, kp = pad_operands(q, q_mask, words,
                                                      doc_lens, block_docs)
    out = tiled_call(_kernel, q, q_mask, words, doc_lens, tp=tp, kp=kp,
                     block_docs=block_docs, interpret=interpret)
    return out.reshape(kp)[:k]
