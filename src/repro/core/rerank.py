"""Early re-ranking, partial re-ranking, and score aggregation (paper §4.3-4.4).

Early re-ranking: MaxSim runs on prefetched embeddings during the remaining
ANN probes; the critical path only scores the misses and merges.

Partial re-ranking: only the top R candidates (by candidate-generation score)
get MaxSim; the rest keep their CLS ordering. R=64-128 retains 99.3-99.7% of
MRR@10 while cutting bandwidth 8-16x (Fig 6).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.core.maxsim import maxsim_scores
from repro.obs.trace import stage
from repro.storage.faults import DegradedQueryError


@dataclass
class RerankOutput:
    doc_ids: np.ndarray          # ranked doc ids (k,)
    scores: np.ndarray           # aggregate scores, descending
    n_reranked: int
    bow_bytes_read: int          # bandwidth bill for this query
    degraded: bool = False       # answered from resident/candidate scores
                                 # because the SSD rerank read failed


def _maxsim_np(q_bow: np.ndarray, q_len: int, d_bow: np.ndarray,
               d_lens: np.ndarray, use_pallas: bool = False) -> np.ndarray:
    """q_bow (Lq, D); d_bow (K, T, D); returns (K,) fp32 MaxSim scores.

    use_pallas=True routes through the Pallas MaxSim kernel (compiled on
    TPU, interpreted on CPU); default is the jnp/XLA path.
    """
    if d_bow.shape[0] == 0:
        return np.zeros((0,), np.float32)
    if use_pallas:
        from repro.kernels.maxsim.ops import maxsim as maxsim_kernel
        with stage("h2d"):
            q, d, lens = (jnp.asarray(q_bow[:q_len]), jnp.asarray(d_bow),
                          jnp.asarray(d_lens))
        with stage("maxsim"):
            return np.asarray(maxsim_kernel(
                q, jnp.ones((q_len,), jnp.float32), d, lens,
                use_pallas=True))
    with stage("h2d"):
        q, d, lens = (jnp.asarray(q_bow[None, :q_len]),
                      jnp.asarray(d_bow[None]), jnp.asarray(d_lens))
    with stage("maxsim"):
        qm = jnp.ones((1, q_len), bool)
        dm = (jnp.arange(d_bow.shape[1])[None, None, :]
              < lens[None, :, None])
        return np.asarray(maxsim_scores(q, qm, d, dm)[0])


def degraded_rerank(result, *, alpha: float = 1.0,
                    select: np.ndarray | None = None,
                    degrade: bool = True) -> RerankOutput:
    """Answer a query whose SSD rerank read failed, without touching its
    (zeroed) buffers: candidates keep their candidate-stage ordering
    (alpha*CLS / FDE score); bit-filter survivors (``select``) rank first in
    bit-score order — the best resident signal available. ``degrade=False``
    raises instead (the operator asked failed reads to fail hard)."""
    if not degrade:
        raise DegradedQueryError(
            "storage read failed and degraded-mode answering is disabled "
            "(FaultConfig.degrade=False)")
    ids = result.doc_ids
    k = len(ids)
    agg = alpha * np.asarray(result.cand_scores[:k], np.float32)
    if select is not None and len(select):
        sel = np.asarray(select, np.int64)
        rest = np.setdiff1d(np.arange(k), sel)   # candidate order preserved
        order = np.concatenate([sel, rest])
    else:
        order = np.argsort(-agg, kind="stable")
    return RerankOutput(doc_ids=ids[order], scores=agg[order], n_reranked=0,
                        bow_bytes_read=0, degraded=True)


def rerank_query(q_bow, q_len, result, *, alpha: float = 1.0,
                 rerank_count: int | None = None, doc_bytes=None,
                 use_pallas: bool = False,
                 select: np.ndarray | None = None,
                 degrade: bool = True) -> RerankOutput:
    """Score one QueryResult (from ANNPrefetcher.run_batch).

    rerank_count=None -> exact (re-rank every candidate, hits scored early,
    misses in the critical path). rerank_count=R -> partial re-ranking of the
    top-R candidates by CLS score; remaining docs keep alpha*CLS only.
    select=<positions> -> MaxSim exactly those candidate positions (e.g. the
    bit-filter survivors of the bitvec backend) instead of the CLS top-R.

    A query whose storage read failed (``result.io_failed``) never scores
    its zeroed buffers: it is answered from candidate-stage scores with
    ``degraded=True`` (or raises ``DegradedQueryError`` when
    ``degrade=False``).
    """
    if getattr(result, "io_failed", False):
        return degraded_rerank(result, alpha=alpha, select=select,
                               degrade=degrade)
    if result.wait_io is not None:
        # batch I/O engine: block until this query's arena runs have landed
        # (reads of later queries keep streaming while we score this one)
        with stage("io_wait"):
            result.wait_io()
    ids = result.doc_ids
    k = len(ids)
    if select is not None:
        sel = np.asarray(select, np.int64)
        rr = len(sel)
    else:
        rr = k if rerank_count is None else min(rerank_count, k)
        # candidates arrive CLS-sorted (IVF top-k): top-rr get MaxSim
        sel = np.arange(rr)

    bow_scores = np.zeros(k, np.float32)
    bytes_read = 0
    with stage("rerank_gather"):
        # hits: scored from the prefetch buffers (early re-rank)
        pref_rows, pref_pos = [], []
        miss_rows, miss_pos = [], []
        miss_row_of = {}
        if result.miss_rows is not None:
            # batch I/O engine: rows point into the shared miss arena
            miss_row_of = result.miss_rows
        elif result.miss_buffers is not None:
            miss_ids = ids[~result.hit_mask]
            miss_row_of = {int(i): j for j, i in enumerate(miss_ids)}
        for j in sel:
            i = int(ids[j])
            if i in result.prefetched and result.buffers is not None:
                pref_rows.append(result.prefetched[i])
                pref_pos.append(j)
            elif i in miss_row_of:
                miss_rows.append(miss_row_of[i])
                miss_pos.append(j)
        parts = []                     # (positions, token rows, lengths)
        if pref_rows:
            _, bow, lens = result.buffers
            parts.append((pref_pos, bow[pref_rows], lens[pref_rows]))
        if miss_rows:
            _, bow, lens = result.miss_buffers
            parts.append((miss_pos, bow[miss_rows], lens[miss_rows]))
    for pos, bow, lens in parts:
        bow_scores[pos] = _maxsim_np(q_bow, q_len, bow, lens, use_pallas)
    if doc_bytes is not None:
        bytes_read = int(sum(doc_bytes(int(ids[j])) for j in sel))

    agg = alpha * result.cand_scores[:k] + bow_scores
    order = np.argsort(-agg, kind="stable")
    return RerankOutput(doc_ids=ids[order], scores=agg[order], n_reranked=rr,
                        bow_bytes_read=bytes_read)
