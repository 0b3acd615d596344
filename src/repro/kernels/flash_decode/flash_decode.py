"""Pallas TPU flash-decoding kernel: single-token GQA attention over a long
KV cache (the LM-serving hot spot for decode_32k / long_500k shapes).

Grid (B, KV, S/C): the cache streams through VMEM in (C, Dh) chunks along
the minor-most grid axis while running (m, l, acc) live in VMEM scratch —
the FlashDecoding split-K pattern. The query block (G, Dh) is tiny and
revisits the same output block every chunk step; masking comes from the
per-sequence cache length.

VMEM/step at defaults (C=512, Dh=128, G=8): k+v 0.25 MB, scratch ~12 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, len_ref, out_ref, m_ref, l_ref, acc_ref,
            *, chunk: int, n_chunks: int, scale: float):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                   # (G, Dh)
    k = k_ref[0, :, 0]                                # (C, Dh)
    v = v_ref[0, :, 0]                                # (C, Dh)
    length = len_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = ic * chunk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, NEG)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))       # (G,)
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ic == n_chunks - 1)
    def _done():
        out_ref[0, 0] = (acc_ref[...]
                         / jnp.maximum(l_ref[...], 1e-30)[:, None]
                         ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def flash_decode_pallas(q, k_cache, v_cache, lengths, *, chunk: int = 512,
                        interpret: bool | None = None):
    """q: (B, KV, G, Dh); k_cache/v_cache: (B, S, KV, Dh);
    lengths: (B,) int32 valid cache length per sequence.
    Returns (B, KV, G, Dh) attention output in q.dtype.
    """
    b, kv, g, dh = q.shape
    s = k_cache.shape[1]
    chunk = min(chunk, s)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = dh ** -0.5

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks,
                          scale=scale),
        grid=(b, kv, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda ib, ik, ic: (ib, ik, 0, 0)),
            pl.BlockSpec((1, chunk, 1, dh), lambda ib, ik, ic: (ib, ic, ik, 0)),
            pl.BlockSpec((1, chunk, 1, dh), lambda ib, ik, ic: (ib, ic, ik, 0)),
            pl.BlockSpec((1,), lambda ib, ik, ic: (ib,)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda ib, ik, ic: (ib, ik, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k_cache, v_cache, lengths.astype(jnp.int32))
    return out
