"""Pallas TPU batched FDE dot-product scoring kernel.

Candidate generation for the fde backend is one dense (B, D) x (D, N)
matmul against the resident FDE table (brute force under the IVF
threshold). The kernel tiles the document axis: the query FDE block is
pinned in VMEM across the whole grid (block-0 index_map, same trick as
maxsim/bitsim) while (BN, D) document tiles stream through, each step
running ONE MXU matmul and writing a (B, BN) score tile. Mosaic has no
fp16 vector loads on a v5e, so the table's pad + upcast to fp32 is an XLA
op that ``allow_input_fusion`` asks XLA to fuse into the kernel's operand.
The program compiled for a v5e does fuse it: its entry computation takes
the fp16 table straight into the kernel and holds no fp32 copy
(``tests/test_tpu_compile.py`` checks the HLO). The bytes read per step on
the chip have not been measured.

VMEM budget per step (defaults BN=256, D=256): fp32 doc tile 256*256*4 =
256 KB + q block — far under the 16 MB ceiling. Alignment: D padded to a
lane multiple of 128, B to the fp32 sublane 8, BN a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(q_ref, d_ref, out_ref):
    out_ref[...] = jax.lax.dot_general(
        q_ref[...], d_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (Bp, BN)


@functools.partial(jax.jit, static_argnames=("block_docs", "interpret"))
def fdescan_pallas(q, docs, *, block_docs: int = 256,
                   interpret: bool | None = None):
    """q: (B, D) float; docs: (N, D) float (any float dtype, e.g. the fp16
    resident table). Returns (B, N) fp32 scores. Pads B to 8, D to 128, and
    N to block_docs; zero padding cannot perturb the inner products.
    ``interpret=None`` follows the backend in use
    (``repro.kernels.resolve_interpret``)."""
    b, d_dim = q.shape
    n = docs.shape[0]
    bp = -(-b // 8) * 8
    dp = -(-d_dim // 128) * 128
    np_ = -(-n // block_docs) * block_docs
    q = jnp.pad(q.astype(jnp.float32), ((0, bp - b), (0, dp - d_dim)))
    docs = jnp.pad(docs.astype(jnp.float32), ((0, np_ - n), (0, dp - d_dim)))

    grid = (np_ // block_docs,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bp, dp), lambda i: (0, 0)),            # q pinned
            pl.BlockSpec((block_docs, dp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bp, block_docs), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            allow_input_fusion=[False, True]),
        interpret=resolve_interpret(interpret),
    )(q, docs)
    return out[:b, :n]
