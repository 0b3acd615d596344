"""Pluggable retrieval backends behind a string-keyed registry.

Each of the paper's five retrieval stacks (Tables 4/5) is a first-class
``RetrievalBackend``: ESPN's prefetched GDS path, plain GDS, the mmap/swap
O/S baselines, and the all-in-DRAM upper bound — joined by the bit-vector
rerank (Nardini et al. 2024) and MUVERA-style FDE candidate-gen (Dhulipala
et al. 2024) stacks from related work. New candidate-generation or re-rank
strategies plug in with ``@register_backend("name")`` and are immediately
reachable from ``Pipeline``, ``ESPNRetriever``, the serve launcher, and the
CLI.

A backend owns the full query path: candidate generation, storage reads,
re-ranking, and the per-stage latency accounting on the calibrated device
clock. All backends return the same ``RetrievalResponse``.
"""
from __future__ import annotations

import abc
import dataclasses
from typing import ClassVar

import numpy as np

from repro.core.espn import (ComputeModel, ESPNConfig, LatencyBreakdown,
                             RetrievalResponse)
from repro.core.ivf import (ANNCostModel, IVFIndex, build_ivf, ivf_add,
                            mask_dead, search, valid_candidates)
from repro.core.prefetcher import ANNPrefetcher, QueryResult
from repro.core.rerank import RerankOutput, rerank_query
from repro.obs.trace import stage
from repro.storage.batch_io import consumption_dedup_saved
from repro.storage.io_engine import StorageTier

_REGISTRY: dict[str, type["RetrievalBackend"]] = {}


def register_backend(name: str):
    """Class decorator: ``@register_backend("espn")``."""
    def deco(cls: type["RetrievalBackend"]) -> type["RetrievalBackend"]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def get_backend(name: str) -> type["RetrievalBackend"]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown retrieval backend {name!r}; registered backends: "
            f"{', '.join(available_backends())}") from None


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


class RetrievalBackend(abc.ABC):
    """One retrieval stack: ANN candidate gen -> storage reads -> re-rank.

    Class attributes describe how the stack maps onto the storage tier so
    callers (``Pipeline``, the serve launcher) can build the right
    ``StorageTier`` without per-mode conditionals:

      storage_stack       the ``StorageTier`` software stack to run on
      needs_mem_budget    True for the O/S paths that operate under a page
                          cache budget (mmap / swap)
      needs_bit_table     True for backends that filter against the resident
                          sign-bit tier (the tier must carry a BitTable)
      needs_fde_table     True for backends that candidate-generate against
                          the resident FDE tier (the tier must carry an
                          FDETable)
    """

    name: ClassVar[str] = ""
    storage_stack: ClassVar[str] = "espn"
    needs_mem_budget: ClassVar[bool] = False
    needs_bit_table: ClassVar[bool] = False
    needs_fde_table: ClassVar[bool] = False

    def __init__(self, index: IVFIndex, tier: StorageTier, cfg: ESPNConfig,
                 *, cost_model: ANNCostModel | None = None,
                 compute: ComputeModel | None = None, doc_bytes=None,
                 tracer=None):
        self.index = index
        self.tier = tier
        self.cfg = cfg
        self.cost = cost_model or ANNCostModel()
        self.compute = compute or ComputeModel()
        self.doc_bytes = doc_bytes or (lambda i: tier.layout.doc_bytes(i))
        self.tracer = tracer               # repro.obs.Tracer | None (off)

    # ------------------------------------------------------------------
    def query_batch(self, q_cls: np.ndarray, q_bow: np.ndarray,
                    q_lens: np.ndarray) -> RetrievalResponse:
        tr = self.tracer
        if tr is not None:
            tr.adopt_batch_qids()
        with stage("query_batch", tr, "batch") as root:
            if root is not None:
                root.args.update(mode=self.name,
                                 n_queries=int(q_cls.shape[0]))
            bd = LatencyBreakdown()
            bd.encode_s = self.compute.encode_time(q_cls.shape[0])
            if tr is not None:
                tr.add("encode", cat="compute", sim_s=bd.encode_s)
            # hedged re-issues and injected faults happen inside the tier
            # (storage cluster); surface this batch's share as stats deltas
            # without any per-backend plumbing
            _FKEYS = ("retries", "checksum_failures", "repair_bytes",
                      "faults_injected")
            hedge0 = self.tier.stats.get("hedge_bytes", 0)
            f0 = {k: self.tier.stats.get(k, 0) for k in _FKEYS}
            ranked = self._retrieve(q_cls, q_bow, q_lens, bd)
            bd.hedge_bytes_read = (self.tier.stats.get("hedge_bytes", 0)
                                   - hedge0)
            for k in _FKEYS:
                setattr(bd, k, self.tier.stats.get(k, 0) - f0[k])
            bd.degraded_queries = sum(int(r.degraded) for r in ranked)
            bd.total_s = (bd.encode_s + bd.ann_s + bd.critical_io_s
                          + bd.rerank_s + 0.2e-3)
            if root is not None:
                tr.end(root, sim_s=bd.total_s, breakdown=bd.as_dict())
        return RetrievalResponse(ranked=ranked, breakdown=bd)

    @abc.abstractmethod
    def _retrieve(self, q_cls, q_bow, q_lens,
                  bd: LatencyBreakdown) -> list[RerankOutput]:
        """Fill ``bd``'s ann/hidden/critical/rerank terms; return rankings."""

    # -- live-mutation hooks ------------------------------------------
    def _dead_masked(self, ids):
        """Tombstone deleted docs out of candidate rows (``-1`` padding;
        ``valid_candidates`` drops them with scores kept paired). Identity
        for tiers without a mutation layer."""
        return mask_dead(ids, getattr(self.tier, "alive", None))

    def on_mutation(self, ingested=None, deleted=None) -> None:
        """Called by ``Pipeline.ingest``/``delete`` after the tier and its
        side tables moved. Deletes need nothing here (the tombstone mask is
        consulted per query); backends holding device copies of a side tier
        override this to refresh them on ingest."""

    # -- shared helpers -----------------------------------------------
    def _maxsim_time(self, n_docs: int, q_len: int) -> float:
        layout = self.tier.layout
        return self.compute.maxsim_time(n_docs, q_len,
                                        float(layout.n_tokens.mean()),
                                        layout.d_bow)

    def _rerank_candidates(self, q_bow, q_lens, scores, ids,
                           bd: LatencyBreakdown) -> list[RerankOutput]:
        """Shared tail of every single-phase candidate generator (Direct*,
        FDE): per query, drop ``-1`` padding keeping ids/scores paired, then
        read the whole batch's top-``rerank_count`` candidates as ONE
        coalesced ``read_batch`` (dedup'd across queries, async runs) and
        re-rank each query as its arena rows land — I/O for later queries
        overlaps scoring of earlier ones. Billing: the batch pays one
        coalesced read in the critical path; duplicate candidate bytes are
        billed once, surfaced as ``bd.dedup_bytes_saved``."""
        cfg = self.cfg
        tr = self.tracer
        ids = self._dead_masked(ids)
        prep = []
        for b in range(len(ids)):
            fin, fin_scores = valid_candidates(ids[b], scores[b])
            rr = len(fin) if cfg.rerank_count is None else min(
                cfg.rerank_count, len(fin))
            prep.append((fin, fin_scores, rr))
        rspan = tr.begin("read", cat="io") if tr is not None else None
        batch = self.tier.read_batch([fin[:rr] for fin, _, rr in prep])
        if tr is not None:
            tr.end(rspan, sim_s=batch.sim_seconds)
        bd.critical_io_s += batch.sim_seconds
        ranked = []
        for b, (fin, fin_scores, rr) in enumerate(prep):
            res = QueryResult.from_batch_view(fin, fin_scores, batch, b,
                                              ann_s=bd.ann_s)
            out = rerank_query(q_bow[b], int(q_lens[b]), res,
                               alpha=cfg.alpha, rerank_count=rr,
                               doc_bytes=self.doc_bytes,
                               use_pallas=cfg.use_pallas,
                               degrade=getattr(self.tier, "degrade_reads",
                                               True))
            ranked.append(out)
            maxsim_t = 0.0
            if not out.degraded:       # a degraded query never ran MaxSim
                maxsim_t = self._maxsim_time(rr, int(q_lens[b]))
                bd.rerank_s += maxsim_t
            if tr is not None:
                qid = tr.query_key(b)
                tr.add("critical_io", cat="io", qid=qid,
                       sim_s=batch.io_s(b))
                if out.degraded:
                    tr.instant("degrade", cat="fault", qid=qid)
                else:
                    tr.add("rerank", cat="compute", qid=qid, sim_s=maxsim_t)
            bd.bytes_read += out.bow_bytes_read
        saved = batch.dedup_bytes_saved(self.doc_bytes)
        bd.bytes_read -= saved
        bd.dedup_bytes_saved += saved
        bd.hit_rate = 0.0
        return ranked

    def _bit_filter_rerank(self, q_bow, q_lens, scores, ids,
                           bd: LatencyBreakdown,
                           width: int) -> list[RerankOutput]:
        """Shared bit-filter + SSD-rerank tail (bitvec, cascade): score ALL
        candidates against the resident sign-bit tier (zero SSD traffic),
        keep the top ``width`` survivors per query, then ONE coalesced
        ``read_batch`` of the survivors and full-precision MaxSim as each
        query's arena rows land. Non-survivors keep their candidate-stage
        ordering (alpha*CLS for bitvec, FDE score for cascade)."""
        import jax.numpy as jnp

        from repro.kernels.bitsim.ops import bitsim

        cfg = self.cfg
        tr = self.tracer
        layout = self.tier.layout
        mean_t = float(layout.n_tokens.mean())
        ids = self._dead_masked(ids)
        # 1) resident bit filter: the top-``width`` survivors are chosen
        #    with a partial sort (argpartition + sort of ``width`` elements,
        #    like the FDE brute path), not a full argsort
        prep = []
        for b in range(len(ids)):
            fin, fin_scores = valid_candidates(ids[b], scores[b])
            qlen = int(q_lens[b])
            packed, lens = self.tier.read_bits(fin)
            bit_s = np.asarray(bitsim(
                jnp.asarray(q_bow[b][:qlen]),
                jnp.ones((qlen,), jnp.float32),
                jnp.asarray(packed), jnp.asarray(lens),
                d=layout.d_bow, use_pallas=cfg.use_pallas))
            bit_t = self.compute.bitsim_time(len(fin), qlen, mean_t,
                                             layout.d_bow)
            bd.rerank_s += bit_t
            if tr is not None:
                tr.add("bit_filter", cat="compute", qid=tr.query_key(b),
                       sim_s=bit_t, n_candidates=len(fin))
            r = min(width, len(fin))
            if r < len(fin):
                # O(n + r log r) instead of a full argsort; ties exactly at
                # the cutoff may pick a different (equal-score) survivor
                # subset than a stable full sort would, like the FDE brute
                # path's selection
                part = np.argpartition(-bit_s, r - 1)[:r]
            else:
                part = np.arange(len(fin))
            sel = part[np.argsort(-bit_s[part], kind="stable")]
            prep.append((fin, fin_scores, sel))
        # 2) ONE coalesced SSD read for every query's survivors, then
        #    full-precision MaxSim per query as its arena rows land
        rspan = tr.begin("read", cat="io") if tr is not None else None
        batch = self.tier.read_batch([fin[sel] for fin, _, sel in prep])
        if tr is not None:
            tr.end(rspan, sim_s=batch.sim_seconds)
        bd.critical_io_s += batch.sim_seconds
        ranked = []
        for b, (fin, fin_scores, sel) in enumerate(prep):
            qlen = int(q_lens[b])
            res = QueryResult.from_batch_view(fin, fin_scores, batch, b,
                                              ann_s=bd.ann_s)
            out = rerank_query(q_bow[b], qlen, res, alpha=cfg.alpha,
                               select=sel, doc_bytes=self.doc_bytes,
                               use_pallas=cfg.use_pallas,
                               degrade=getattr(self.tier, "degrade_reads",
                                               True))
            ranked.append(out)
            maxsim_t = 0.0
            if not out.degraded:
                maxsim_t = self._maxsim_time(len(sel), qlen)
                bd.rerank_s += maxsim_t
            if tr is not None:
                qid = tr.query_key(b)
                tr.add("critical_io", cat="io", qid=qid,
                       sim_s=batch.io_s(b))
                if out.degraded:
                    tr.instant("degrade", cat="fault", qid=qid)
                else:
                    tr.add("rerank", cat="compute", qid=qid, sim_s=maxsim_t)
            bd.bytes_read += out.bow_bytes_read
        saved = batch.dedup_bytes_saved(self.doc_bytes)
        bd.bytes_read -= saved
        bd.dedup_bytes_saved += saved
        bd.hit_rate = 0.0
        return ranked


@register_backend("espn")
class ESPNBackend(RetrievalBackend):
    """GDS-analogue batched reads + ANN-guided prefetcher + early re-rank
    (the paper's contribution, §4.2-4.3)."""

    storage_stack = "espn"

    def __init__(self, index, tier, cfg, **kw):
        super().__init__(index, tier, cfg, **kw)
        self.prefetcher = ANNPrefetcher(index, tier,
                                        prefetch_step=cfg.prefetch_step,
                                        cost_model=self.cost)

    def _retrieve(self, q_cls, q_bow, q_lens, bd):
        cfg = self.cfg
        tr = self.tracer
        if q_cls.shape[0] == 0:           # empty batch: nothing to rank,
            return []                     # hit_rate keeps its vacuous default
        with stage("candidate_gen", tr, "compute") as cspan:
            results = self.prefetcher.run_batch(q_cls, nprobe=cfg.nprobe,
                                                k=cfg.k_candidates)
            bd.ann_s = results[0].stats.ann_s
            if cspan is not None:
                tr.end(cspan, sim_s=bd.ann_s)
        ranked, hit_rates, hidden, critical = [], [], 0.0, 0.0
        for b, res in enumerate(results):
            out = rerank_query(q_bow[b], int(q_lens[b]), res,
                               alpha=cfg.alpha, rerank_count=cfg.rerank_count,
                               doc_bytes=self.doc_bytes,
                               use_pallas=cfg.use_pallas,
                               degrade=getattr(self.tier, "degrade_reads",
                                               True))
            ranked.append(out)
            early_t = self._maxsim_time(res.stats.n_hits, int(q_lens[b]))
            miss_t = self._maxsim_time(res.stats.n_misses, int(q_lens[b]))
            hidden_work = res.stats.prefetch_io_s + early_t
            leaked = max(0.0, hidden_work - res.stats.budget_s)
            hidden += min(hidden_work, res.stats.budget_s)
            critical += leaked + res.stats.miss_io_s
            if not out.degraded:       # a degraded query never ran MaxSim
                bd.rerank_s += miss_t
            if tr is not None:
                qid = tr.query_key(b)
                tr.add("hidden_io", cat="io", qid=qid,
                       sim_s=min(hidden_work, res.stats.budget_s))
                tr.add("critical_io", cat="io", qid=qid,
                       sim_s=leaked + res.stats.miss_io_s,
                       hit_rate=round(res.stats.hit_rate, 4))
                if out.degraded:
                    tr.instant("degrade", cat="fault", qid=qid)
                else:
                    tr.add("rerank", cat="compute", qid=qid, sim_s=miss_t)
            hit_rates.append(res.stats.hit_rate)
            bd.bytes_read += out.bow_bytes_read
        bd.hidden_s = hidden
        bd.critical_io_s = critical
        bd.hit_rate = float(np.mean(hit_rates))
        if self.tier.coalesce:
            # batch engine billed each doc once; surface the duplicate
            # consumptions the serial path would have re-billed
            saved = consumption_dedup_saved(
                [res.doc_ids[:out.n_reranked]
                 for res, out in zip(results, ranked)], self.doc_bytes)
            bd.bytes_read -= saved
            bd.dedup_bytes_saved += saved
        return ranked


class DirectBackend(RetrievalBackend):
    """Shared path for the non-prefetching stacks: single-phase ANN, then
    every candidate read sits in the critical path. Subclasses only choose
    the storage stack (which sets the calibrated clock in io_engine)."""

    def _retrieve(self, q_cls, q_bow, q_lens, bd):
        cfg = self.cfg
        tr = self.tracer
        if q_cls.shape[0] == 0:
            bd.hit_rate = 0.0
            return []
        cspan = tr.begin("candidate_gen", cat="compute") \
            if tr is not None else None
        scores, ids = search(self.index, q_cls, cfg.nprobe, cfg.k_candidates)
        scores, ids = np.asarray(scores), np.asarray(ids)
        bd.ann_s = self.cost.time(self.index, cfg.nprobe)
        if tr is not None:
            tr.end(cspan, sim_s=bd.ann_s)
        return self._rerank_candidates(q_bow, q_lens, scores, ids, bd)


@register_backend("gds")
class GDSBackend(DirectBackend):
    """GDS-analogue batched reads, no prefetch: the paper's ablation where
    all storage I/O lands in the critical path."""
    storage_stack = "espn"


@register_backend("mmap")
class MmapBackend(DirectBackend):
    """Conventional mmap'd index under a page-cache memory budget."""
    storage_stack = "mmap"
    needs_mem_budget = True


@register_backend("swap")
class SwapBackend(DirectBackend):
    """Anonymous memory + kernel swap under a memory budget."""
    storage_stack = "swap"
    needs_mem_budget = True


@register_backend("dram")
class DRAMBackend(DirectBackend):
    """Whole index resident in memory: the paper's upper-bound baseline."""
    storage_stack = "dram"


@register_backend("bitvec")
class BitvecBackend(RetrievalBackend):
    """Bit-vector compressed rerank (Nardini et al. 2024): every candidate is
    first scored against the *resident* sign-bit table with a packed-bit
    asymmetric MaxSim (no SSD traffic), then only the top ``bit_filter``
    survivors are read from storage for full-precision MaxSim. Non-survivors
    keep their alpha*CLS ordering, exactly like partial re-ranking — but the
    survivors are chosen by a token-level signal instead of the CLS score,
    so quality holds at much smaller R (and therefore far fewer BOW bytes
    per query)."""

    storage_stack = "espn"
    needs_bit_table = True

    def _retrieve(self, q_cls, q_bow, q_lens, bd):
        cfg = self.cfg
        tr = self.tracer
        if q_cls.shape[0] == 0:
            bd.hit_rate = 0.0
            return []
        cspan = tr.begin("candidate_gen", cat="compute") \
            if tr is not None else None
        scores, ids = search(self.index, q_cls, cfg.nprobe, cfg.k_candidates)
        scores, ids = np.asarray(scores), np.asarray(ids)
        bd.ann_s = self.cost.time(self.index, cfg.nprobe)
        if tr is not None:
            tr.end(cspan, sim_s=bd.ann_s)
        return self._bit_filter_rerank(q_bow, q_lens, scores, ids, bd,
                                       cfg.bit_filter)


@register_backend("fde")
class FDEBackend(RetrievalBackend):
    """MUVERA-style FDE candidate generation (Dhulipala et al. 2024):
    candidates come from single-vector ANN over the *resident* fixed
    dimensional encodings of the documents — one small vector per doc whose
    inner product with the query's FDE approximates Chamfer/MaxSim — instead
    of the CLS IVF index. Only the top candidates are then read from the SSD
    tier for full-precision MaxSim re-rank, so Chamfer-faithful recall costs
    a fraction of the CLS index's resident bytes.

    Below ``cfg.fde_brute_threshold`` documents the table is scanned brute
    force (one dense matmul, the ``kernels/fdescan`` Pallas kernel); above
    it an IVF index is built over the doc FDEs and probed like any other
    single-vector index."""

    storage_stack = "espn"
    needs_fde_table = True

    def __init__(self, index, tier, cfg, **kw):
        super().__init__(index, tier, cfg, **kw)
        from repro.core.fde import FDEEncoder
        if tier.fde is None:
            raise RuntimeError(
                "the fde backend needs a StorageTier built with a resident "
                "FDETable; construct it with fde=build_fde_table(...)")
        self.encoder = FDEEncoder(tier.fde.cfg)
        n = tier.fde.n_docs
        self.fde_index = None
        self._fde_vecs_dev = None
        if n > cfg.fde_brute_threshold:
            self.fde_index = build_ivf(
                np.asarray(tier.fde.vecs, np.float32),
                ncells=max(16, n // 270), iters=4)
        else:
            # the table is immutable for the backend's lifetime: upload it
            # to the device once, not per query batch
            import jax.numpy as jnp
            self._fde_vecs_dev = jnp.asarray(tier.fde.vecs)

    def on_mutation(self, ingested=None, deleted=None) -> None:
        """Ingest moved ``tier.fde`` under this backend: fold the new doc
        FDEs into the IVF wrapper when one exists, else refresh the device
        copy of the (no-longer-immutable) brute-scan table."""
        if ingested is None or len(ingested) == 0:
            return
        gids = np.asarray(ingested, np.int64)
        if self.fde_index is not None:
            ivf_add(self.fde_index,
                    np.asarray(self.tier.fde.vecs[gids], np.float32), gids)
        else:
            import jax.numpy as jnp
            self._fde_vecs_dev = jnp.asarray(self.tier.fde.vecs)

    def candidate_gen_bytes(self) -> int:
        """Resident bytes this backend's candidate generation needs (the
        quantity the paper's memory tables compare): the FDE table plus its
        IVF wrapper when one was built. The CLS index does not count — this
        backend never probes it."""
        return self.tier.fde.nbytes + (self.fde_index.memory_bytes()
                                       if self.fde_index is not None else 0)

    def _fde_candidates(self, q_bow, q_lens, bd):
        """Candidate generation against the resident FDE tier: returns
        (scores, ids) on MaxSim's scale, ready for any rerank tail."""
        import jax.numpy as jnp

        from repro.kernels.fdescan.ops import fdescan

        cfg = self.cfg
        q_fde = self.encoder.encode_queries(q_bow, q_lens)    # (B, d_fde)
        n = self.tier.fde.n_docs
        if self.fde_index is None:
            s = np.asarray(fdescan(jnp.asarray(q_fde), self._fde_vecs_dev,
                                   use_pallas=cfg.use_pallas))
            k = min(cfg.k_candidates, n)
            part = np.argpartition(-s, k - 1, axis=1)[:, :k]
            ps = np.take_along_axis(s, part, axis=1)
            order = np.argsort(-ps, axis=1, kind="stable")
            ids = np.take_along_axis(part, order, axis=1)
            scores = np.take_along_axis(ps, order, axis=1)
            # brute scan touches every doc FDE: one flat pass, no centroids
            bd.ann_s = self.cost.t0_s + self.cost.c_cand_s * n
        else:
            scores, ids = search(self.fde_index, jnp.asarray(q_fde),
                                 cfg.nprobe, cfg.k_candidates)
            scores, ids = np.asarray(scores), np.asarray(ids)
            bd.ann_s = self.cost.time(self.fde_index, cfg.nprobe)
        # the FDE inner product sums r_reps independent Chamfer estimates;
        # dividing brings candidate scores onto MaxSim's scale so the
        # full-precision re-rank, not the sketch, decides the final order
        scores = scores / float(self.tier.fde.cfg.r_reps)
        return scores, ids

    def _retrieve(self, q_cls, q_bow, q_lens, bd):
        tr = self.tracer
        if q_cls.shape[0] == 0:
            bd.hit_rate = 0.0
            return []
        cspan = tr.begin("candidate_gen", cat="compute") \
            if tr is not None else None
        scores, ids = self._fde_candidates(q_bow, q_lens, bd)
        if tr is not None:
            tr.end(cspan, sim_s=bd.ann_s)
        return self._rerank_candidates(q_bow, q_lens, scores, ids, bd)


@register_backend("cspn")
class CSPNBackend(DirectBackend):
    """Constant-space SSD rerank: the gds query path run over the
    ``fixed_stride`` pooled layout. Every document holds exactly ``pool_k``
    token vectors at a uniform block stride, so offsets are arithmetic
    (zero resident metadata), every read moves the same byte count, and the
    batch I/O plan degenerates to index math. The backend itself is layout-
    agnostic — it runs correctly (just without the constant-space wins) on
    a ragged layout too, which keeps the registry-wide invariant suites
    honest."""
    storage_stack = "espn"


@register_backend("cascade")
class CascadeBackend(FDEBackend):
    """Three-stage constant-space cascade: resident FDE candidate
    generation (MUVERA) -> resident sign-bit filter (Nardini) -> SSD
    full-precision MaxSim of the few survivors. Candidate width is
    ``cascade_candidates`` (0 = ``k_candidates``); only the top
    ``cascade_filter`` bit-score survivors pay SSD bytes, so the per-query
    storage bill is strictly below the single-filter stacks at equal
    recall. Designed for the ``fixed_stride`` pooled layout, where each
    survivor read is one constant-size strided gather."""

    storage_stack = "espn"
    needs_bit_table = True
    needs_fde_table = True

    def _retrieve(self, q_cls, q_bow, q_lens, bd):
        cfg = self.cfg
        tr = self.tracer
        if q_cls.shape[0] == 0:
            bd.hit_rate = 0.0
            return []
        width = cfg.cascade_candidates or cfg.k_candidates
        saved_cfg = self.cfg
        if width != cfg.k_candidates:
            self.cfg = dataclasses.replace(cfg, k_candidates=width)
        cspan = tr.begin("candidate_gen", cat="compute") \
            if tr is not None else None
        try:
            scores, ids = self._fde_candidates(q_bow, q_lens, bd)
        finally:
            self.cfg = saved_cfg
            if tr is not None:
                tr.end(cspan, sim_s=bd.ann_s)
        return self._bit_filter_rerank(q_bow, q_lens, scores, ids, bd,
                                       cfg.cascade_filter)
