#!/usr/bin/env python3
"""Bring-up smoke: ESPN's served path end to end on one TPU chip.

    python chip_smoke.py [--seed N]     # 1M passages, 64 queries

Builds a synthetic corpus at MS MARCO v1 passage shapes through
``Pipeline.build``, serves its queries through ``Pipeline.serve()`` /
``RetrievalServer.query_async`` in ``espn`` mode twice (XLA MaxSim, then the
Pallas kernel via ``Pipeline.with_mode``), and checks every answer against
a numpy host reference: the IVF candidate set (probe + scan + top-k), every
returned score (alpha*CLS + MaxSim from the packed layout), the ranking
order, and MRR@10 / Recall@100. It runs the bitsim and fdescan Pallas
kernels once at served widths against their ``ref.py`` oracles, and the
colberter query encoder at its full width. Each served mode gets a cold
window (compiles included, counted) and a warm window (the timed one).

It needs a TPU and has no CPU fallback: when JAX finds none it exits non-zero
without a result. Any failed request, an index array off the chip, or a
failed check also exits non-zero. The last line of a passing run is one JSON
object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MSMARCO_V1_PASSAGES = 8_841_823
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# TPU matmuls on float32 operands at default precision round each operand
# to bfloat16 (relative error <= 2^-9): one product term a_i*b_i is off by
# at most ~2^-8 |a_i b_i|, so a dot product by at most 2^-8 sum|a_i b_i|
BF16_DOT_REL = 2.0 ** -8


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one smoke run serves. ``SERVED`` is the run ``python
    chip_smoke.py`` makes (the paper's ``ESPNConfig`` settings on 1M
    passages); the CPU test hands ``run`` a tiny one."""
    docs: int = 1_000_000
    queries: int = 64
    k: int = 1000                 # k_candidates
    nprobe: int = 128
    encode_batch: int = 32
    fde_docs: int = 100_000       # FDE table rows: the brute-scan limit
    fde_batch: int = 16
    timeout_s: float = 600.0      # one served window
    seed: int = 0


SERVED = Sizes()


class CompileCounter:
    """Counts XLA/Mosaic program compilations (persistent-cache loads
    included) through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1
            self.secs += duration

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def score_tolerance(q_len: int, alpha: float) -> float:
    """Largest |served - reference| admitted for alpha*CLS + MaxSim.

    All token and CLS vectors are unit-norm, so by Cauchy-Schwarz one
    default-precision dot product is off by at most BF16_DOT_REL. MaxSim
    sums q_len maxima, each off by at most one dot's error; the CLS term
    adds alpha dots, plus 2^-11 because the IVF cells hold the float32 CLS
    vectors while the reference reads the float16 copy in the packed
    layout. 1e-4 covers float32 accumulation. A wrong document, a dropped
    token or a wrong length mask moves a score by far more than this bound
    (~0.1 at 24 tokens).
    """
    return (q_len * BF16_DOT_REL + alpha * (BF16_DOT_REL + 2.0 ** -11)
            + 1e-4)


def reference_scores(layout, q_cls, q_bow, doc_ids, alpha: float):
    """alpha*CLS + MaxSim for ``doc_ids``, in float32 numpy, decoding each
    record straight from the packed disk image (independent of the served
    gather path): record = [cls (d_cls), tokens (n_tokens, d_bow)]."""
    elt = layout.dtype.itemsize
    out = np.empty(len(doc_ids), np.float32)
    for j, i in enumerate(doc_ids):
        start = int(layout.offsets[i, 0]) * layout.block
        t = int(layout.n_tokens[i])
        n = layout.d_cls + t * layout.d_bow
        rec = layout.blob[start:start + n * elt].view(layout.dtype)
        rec = rec.astype(np.float32)
        cls, bow = rec[:layout.d_cls], rec[layout.d_cls:].reshape(t, -1)
        out[j] = alpha * (q_cls @ cls) + (q_bow @ bow.T).max(axis=1).sum()
    return out


def _top_n_bounds(lo, hi, n: int, sure):
    """Items score somewhere in [lo, hi]; ``sure`` marks those certainly in
    the pool. Returns (may, must): item i may be in a top-n if fewer than n
    sure items lie certainly above it, and must be (when sure itself) if
    fewer than n other items can reach its lower bound."""
    lo_sure = np.sort(lo[sure])
    may = len(lo_sure) - np.searchsorted(lo_sure, hi, "right") < n
    hi_all = np.sort(hi)
    reach = len(hi_all) - np.searchsorted(hi_all, lo, "left") - 1
    return may, sure & (reach < n)


class HostIVF:
    """Numpy oracle for IVF candidate generation, from the index's
    centroids and cell membership and the corpus's float32 CLS vectors: the
    top-``nprobe`` cells by centroid score, then the top-``k`` documents of
    those cells by CLS score.

    The device scores at default matmul precision, so a cell or document
    within the bf16 bound of a cut-off may fall either way. ``check`` admits
    exactly that freedom and nothing more: every served candidate must be
    one that some admissible scoring puts in the top-k, and every document
    that all admissible scorings put there must be served."""

    def __init__(self, index, cls: np.ndarray, nprobe: int, k: int):
        self.cent = np.asarray(index.centroids, np.float32)
        self.cell_ids = np.asarray(index.cell_ids)
        self.cls, self.nprobe, self.k = cls, nprobe, k

    def _scores(self, x, q):
        s = x @ q
        e = BF16_DOT_REL * (np.abs(x) @ np.abs(q)) + 1e-6
        return s, s - e, s + e

    def _docs(self, cells):
        ids = self.cell_ids[cells].ravel()
        return ids[ids >= 0]

    def expect(self, q) -> dict:
        """For one query: the plain float32 candidates (``ref``, best
        first), the documents that must be served, those that may be, and
        the least number of candidates."""
        sc, lo, hi = self._scores(self.cent, q)
        ref = self._docs(np.argsort(-sc, kind="stable")[:self.nprobe])
        ref = ref[np.argsort(-(self.cls[ref] @ q), kind="stable")[:self.k]]
        may_c, must_c = _top_n_bounds(lo, hi, self.nprobe,
                                      np.ones(len(sc), bool))
        pool = self._docs(may_c)
        _, lo, hi = self._scores(self.cls[pool], q)
        sure = np.isin(pool, self._docs(must_c))
        may, must = _top_n_bounds(lo, hi, self.k, sure)
        return {"ref": ref, "must": pool[must], "may": pool[may],
                "least": min(self.k, int(sure.sum()))}

    def check(self, exp: dict, served) -> list[str]:
        served = np.asarray(served)
        bad = []
        if len(np.unique(served)) != len(served):
            bad.append("duplicate candidates")
        if not exp["least"] <= len(served) <= self.k:
            bad.append(f"{len(served)} candidates, expected {self.k}")
        missing = np.setdiff1d(exp["must"], served)
        extra = np.setdiff1d(served, exp["may"])
        if len(missing):
            bad.append(f"{len(missing)} certain top-k documents missing "
                       f"(e.g. {missing[:3].tolist()})")
        if len(extra):
            bad.append(f"{len(extra)} candidates no admissible probe + scan "
                       f"selects (e.g. {extra[:3].tolist()})")
        return bad


def serve_window(pipe, qids, counter: CompileCounter, timeout_s: float):
    """Submit every query at once through the server; wait for all. Wall
    latency is arrival -> completion as the batcher records it; the handler
    copies every device result to the host (np.asarray), so completion
    follows the device work."""
    c = pipe.corpus
    server = pipe.serve()
    n0, s0 = counter.n, counter.secs
    t0 = time.monotonic()
    reqs = [server.query_async(c.queries_cls[i], c.queries_bow[i],
                               int(c.query_lens[i])) for i in qids]
    deadline = time.monotonic() + timeout_s
    timeouts = sum(not r.done.wait(max(0.0, deadline - time.monotonic()))
                   for r in reqs)
    server.shutdown()
    done = [r for r in reqs if r.done.is_set() and not r.shed]
    t1 = max((r.arrival_s + r.latency_s for r in done), default=t0)
    lat_ms = np.array([r.latency_s * 1e3 for r in done]) if done \
        else np.zeros(1)
    st = server.stats
    results = [r.result for r in reqs]
    return {
        "results": results,
        "errors": sum(r.error is not None for r in reqs),
        "shed": sum(r.shed for r in reqs),
        "timeouts": timeouts,
        "degraded": sum(r is not None and r.degraded for r in results),
        "wall_p50_ms": float(np.percentile(lat_ms, 50)),
        "wall_p99_ms": float(np.percentile(lat_ms, 99)),
        "qps": len(done) / max(t1 - t0, 1e-9),
        "compiles": counter.n - n0,
        "compile_s": counter.secs - s0,
        "mean_batch": st.batch_sizes.mean() if st.batch_sizes else 0.0,
        "modelled_p50_ms": st.percentile(50),
        "modelled_p99_ms": st.percentile(99),
    }


def check_window(pipe, qids, w, tol: float, ivf: HostIVF,
                 expected: list[dict]) -> list[str]:
    """Failures of one served window: request failures, degraded or partial
    reranks, candidates the host IVF oracle refuses, rankings not in
    descending score order, and any score off the host reference by more
    than ``tol``."""
    bad = [f"{k}={w[k]}" for k in ("errors", "shed", "timeouts") if w[k]]
    c = pipe.corpus
    alpha = pipe.cfg.retrieval.alpha
    worst, overlap = 0.0, 1.0
    w["cand_bad"] = 0
    for qi, res, exp in zip(qids, w["results"], expected):
        if res is None:
            continue
        if res.degraded or res.n_reranked != len(res.doc_ids):
            bad.append(f"query {qi}: reranked {res.n_reranked} of "
                       f"{len(res.doc_ids)}, degraded={res.degraded}")
            continue
        cand = ivf.check(exp, res.doc_ids)
        w["cand_bad"] += bool(cand)
        bad += [f"query {qi}: {b}" for b in cand]
        overlap = min(overlap, len(np.intersect1d(exp["ref"], res.doc_ids))
                      / max(len(exp["ref"]), 1))
        if np.any(np.diff(res.scores) > 0):
            bad.append(f"query {qi}: scores not in descending order")
        qlen = int(c.query_lens[qi])
        ref = reference_scores(pipe.layout, c.queries_cls[qi],
                               c.queries_bow[qi][:qlen], res.doc_ids, alpha)
        worst = max(worst, float(np.abs(res.scores - ref).max()))
    w["ref_max_abs_err"] = worst
    w["cand_overlap"] = overlap
    if worst > tol:
        bad.append(f"reference check: max |served - ref| {worst:.6f} > "
                   f"tol {tol:.6f}")
    return bad


def host_ranking(pipe, qids, expected: list[dict]) -> list[np.ndarray]:
    """The whole query path on the host: the oracle's float32 candidates
    ranked by alpha*CLS + MaxSim from the packed layout."""
    c = pipe.corpus
    out = []
    for qi, exp in zip(qids, expected):
        qlen = int(c.query_lens[qi])
        s = reference_scores(pipe.layout, c.queries_cls[qi],
                             c.queries_bow[qi][:qlen], exp["ref"],
                             pipe.cfg.retrieval.alpha)
        out.append(exp["ref"][np.argsort(-s, kind="stable")])
    return out


def compare_windows(a, b, tol: float) -> tuple[float, list[str]]:
    """The Pallas window against the XLA one: both run the same candidate
    stage, so each query's candidate set must be identical; their scores
    may differ by the two windows' reference tolerances."""
    worst, bad = 0.0, []
    for ra, rb in zip(a["results"], b["results"]):
        if ra is None or rb is None:
            continue
        sa = dict(zip(ra.doc_ids.tolist(), ra.scores.tolist()))
        sb = dict(zip(rb.doc_ids.tolist(), rb.scores.tolist()))
        if sa.keys() != sb.keys():
            bad.append(f"pallas vs xla: candidate sets differ "
                       f"({len(sa.keys() ^ sb.keys())} documents)")
            continue
        worst = max([worst] + [abs(sa[i] - sb[i]) for i in sa])
    if worst > tol:
        bad.append(f"pallas vs xla: max |delta| {worst:.6f} > {tol:.6f}")
    return worst, bad


def kernel_check(sizes: Sizes, lq: int, q_len: int, t_max: int, d_bow: int,
                 fde_dim: int) -> tuple[dict, list[str]]:
    """The bitsim and fdescan Pallas kernels once each at served widths
    (``interpret`` follows the backend: compiled on the TPU) against their
    ``ref.py`` oracles run at highest matmul precision on the same device.

    Bounds: the kernel's matmul may round its float32 operands to bfloat16
    (BF16_DOT_REL of sum|a_i b_i| per dot). bitsim's ±1 signs are exact, so
    each query token's maxima are off by at most BF16_DOT_REL * |q_t|_1 and
    the score by the sum over unmasked tokens. fdescan is bounded per
    element by BF16_DOT_REL * (|q| @ |docs|^T). 1e-4 / 1e-5 cover float32
    accumulation. A wrong bit order, word, length mask or tile moves a score
    by O(1)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.bitsim.bitsim import bitsim_pallas
    from repro.kernels.bitsim.ref import bitsim_ref
    from repro.kernels.fdescan.fdescan import fdescan_pallas
    from repro.kernels.fdescan.ref import fdescan_ref
    from repro.kernels.maxsim.maxsim import maxsim_pallas
    ks = jax.random.split(jax.random.PRNGKey(sizes.seed + 2), 5)
    q = jax.random.normal(ks[0], (lq, d_bow), jnp.float32)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    qmask = (jnp.arange(lq) < q_len).astype(jnp.float32)
    packed = jax.random.bits(ks[1], (sizes.k, t_max, -(-d_bow // 32)),
                             jnp.uint32)
    lens = jax.random.randint(ks[2], (sizes.k,), 1, t_max + 1, jnp.int32)
    qf = jax.random.normal(ks[3], (sizes.fde_batch, fde_dim), jnp.float32)
    table = jax.random.normal(ks[4], (sizes.fde_docs, fde_dim),
                              jnp.float32).astype(jnp.float16)

    bit_args = (q, qmask, packed, lens)
    got_b = np.asarray(bitsim_pallas(*bit_args, d=d_bow))
    got_f = np.asarray(fdescan_pallas(qf, table))
    with jax.default_matmul_precision("highest"):
        ref_b = np.asarray(jax.jit(functools.partial(bitsim_ref, d=d_bow))(
            *bit_args))
        ref_f = np.asarray(jax.jit(fdescan_ref)(qf, table))
        abs_f = np.asarray(jax.jit(fdescan_ref)(jnp.abs(qf),
                                                jnp.abs(table)))
    tol_b = float(BF16_DOT_REL * (np.abs(np.asarray(q)).sum(1)
                                  * np.asarray(qmask)).sum() + 1e-4)
    err_b = float(np.abs(got_b - ref_b).max())
    err_f = np.abs(got_f - ref_f)
    ratio_f = float((err_f / (BF16_DOT_REL * abs_f + 1e-5)).max())
    docs = jnp.zeros((sizes.k, t_max, d_bow), jnp.float32)
    lowered = {"maxsim": maxsim_pallas.lower(q, qmask, docs, lens),
               "bitsim": bitsim_pallas.lower(*bit_args, d=d_bow),
               "fdescan": fdescan_pallas.lower(qf, table)}
    out = {"bitsim": f"K {sizes.k} x T {t_max} x D {d_bow}, Lq {lq}",
           "fdescan": f"{sizes.fde_batch} x {sizes.fde_docs} x {fde_dim} "
                      f"float16 table",
           "bitsim_err": err_b, "bitsim_tol": tol_b,
           "fdescan_err": float(err_f.max()), "fdescan_ratio": ratio_f,
           "compiled": sorted(n for n, lo in lowered.items()
                              if "tpu_custom_call" in lo.as_text())}
    bad = []
    if got_b.shape != (sizes.k,) or not err_b <= tol_b:
        bad.append(f"bitsim_pallas vs ref: {err_b:.6f} > {tol_b:.6f}")
    if got_f.shape != (sizes.fde_batch, sizes.fde_docs) or not ratio_f <= 1:
        bad.append(f"fdescan_pallas vs ref: error {ratio_f:.3f} x its bound")
    return out, bad


def encoder_check(cfg, seed: int, batch: int, seq: int) -> tuple[dict, list]:
    """The colberter encoder at ``cfg`` on one batch of random queries,
    against the same function under highest matmul precision."""
    import jax

    from repro.models.colberter import encode, init_params
    params = init_params(cfg, jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq),
                                0, cfg.vocab_size)
    fn = jax.jit(functools.partial(encode, cfg))
    t0 = time.perf_counter()
    cls, bow, _ = jax.block_until_ready(fn(params, tokens))
    first_s = time.perf_counter() - t0
    runs = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(params, tokens))
        runs.append(time.perf_counter() - t0)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(functools.partial(encode, cfg))
        cls_h, bow_h, _ = jax.block_until_ready(ref(params, tokens))
    f32 = lambda x: np.asarray(x, np.float32)
    dev = max(float(np.abs(f32(cls) - f32(cls_h)).max()),
              float(np.abs(f32(bow) - f32(bow_h)).max()))
    out = {"layers": cfg.n_layers, "d_model": cfg.d_model,
           "batch": batch, "seq": seq, "first_call_s": first_s,
           "steady_ms": float(np.median(runs)) * 1e3, "max_dev": dev}
    bad = []
    if cls.shape != (batch, cfg.d_cls) or bow.shape != (batch, seq,
                                                         cfg.d_bow):
        bad.append(f"encoder shapes {cls.shape} {bow.shape}")
    if not (np.isfinite(f32(cls)).all() and np.isfinite(f32(bow)).all()):
        bad.append("encoder output not finite")
    # outputs are unit vectors computed in bfloat16: the two precisions
    # differ by bf16 rounding of float32 intermediates (~1e-3); 0.05 admits
    # that and nothing structural
    if not dev <= 0.05:
        bad.append(f"encoder default vs highest precision: {dev:.5f} > 0.05")
    return out, bad


def run(sizes: Sizes, platform: str, encoder_cfg=None) -> list[str]:
    """All phases at ``sizes``; prints one line per result and returns the
    failures. ``platform`` is where the index arrays must live."""
    counter = CompileCounter()
    try:
        return _phases(sizes, platform, encoder_cfg, counter)
    finally:
        counter.close()


def _phases(sizes: Sizes, platform, encoder_cfg, counter) -> list[str]:
    from repro.configs.base import get_config
    from repro.core.metrics import mrr_at_k, recall_at_k
    from repro.kernels import resolve_interpret
    from repro.pipeline import (CorpusConfig, Pipeline, PipelineConfig,
                                RetrievalConfig)

    cfg = PipelineConfig(
        corpus=CorpusConfig(n_docs=sizes.docs, n_queries=sizes.queries,
                            seed=sizes.seed),
        retrieval=RetrievalConfig(mode="espn", nprobe=sizes.nprobe,
                                  k_candidates=sizes.k))
    c = cfg.corpus
    t0 = time.perf_counter()
    pipe = Pipeline.build(cfg)
    build_s = time.perf_counter() - t0
    corpus, idx, lay = pipe.corpus, pipe.index, pipe.layout
    fp32_gb = (corpus.cls.nbytes + sum(b.nbytes for b in corpus.bow)) / 1e9
    print(f"corpus: {corpus.n_docs} passages (MS MARCO v1 has "
          f"{MSMARCO_V1_PASSAGES}; cut by host RAM: fp32 corpus "
          f"{fp32_gb:.2f} GB, packed layout {lay.nbytes / 1e9:.2f} GB), "
          f"d_cls={c.d_cls} d_bow={c.d_bow} tokens mean "
          f"{corpus.mean_tokens:.1f} max {int(corpus.doc_lens.max())}, "
          f"{len(corpus.qrels)} queries of {int(corpus.query_lens.max())} "
          f"tokens", flush=True)
    print(f"build: {build_s:.1f} s (corpus + IVF + pack); index "
          f"{idx.ncells} cells x {idx.max_cell}, nprobe={sizes.nprobe} "
          f"k_candidates={sizes.k}; compiles {counter.n} "
          f"({counter.secs:.1f} s)", flush=True)
    failures = []
    arrays = {"centroids": idx.centroids, "cell_ids": idx.cell_ids,
              "cell_vecs": idx.cell_vecs, "cell_scale": idx.cell_scale}
    for name, a in arrays.items():
        if a is not None and any(d.platform != platform
                                 for d in a.devices()):
            failures.append(f"index.{name} is on {a.devices()}, "
                            f"not {platform}")

    qids = list(range(len(corpus.qrels)))
    qrels = corpus.qrels
    n = len(qids)
    t0 = time.perf_counter()
    ivf = HostIVF(idx, corpus.cls, sizes.nprobe, sizes.k)
    expected = [ivf.expect(corpus.queries_cls[i]) for i in qids]
    host = host_ranking(pipe, qids, expected)
    host_mrr, host_rec = mrr_at_k(host, qrels, 10), recall_at_k(host, qrels,
                                                                 100)
    tol = score_tolerance(int(corpus.query_lens.max()),
                          cfg.retrieval.alpha)
    print(f"reference: numpy IVF (top-{sizes.nprobe} cells, top-{sizes.k} "
          f"by CLS) + alpha*CLS + MaxSim in float32 from the packed layout, "
          f"{time.perf_counter() - t0:.1f} s: MRR@10 {host_mrr:.4f} "
          f"Recall@100 {host_rec:.4f}; score tolerance {tol:.6f} (bf16 "
          f"operand rounding bound)", flush=True)
    windows = {}
    pallas = pipe.with_mode("espn", use_pallas=True)
    try:
        for name, p in (("xla", pipe), ("pallas", pallas)):
            for phase in ("cold", "warm"):
                w = serve_window(p, qids, counter, sizes.timeout_s)
                bad = check_window(p, qids, w, tol, ivf, expected)
                ranked = [r.doc_ids for r in w["results"] if r is not None]
                mrr = mrr_at_k(ranked, qrels, 10)
                rec = recall_at_k(ranked, qrels, 100)
                # the host and the device may order a near tie (within the
                # score tolerance) differently: one query's worth of slack
                if mrr < host_mrr - 1 / n or rec < host_rec - 1 / n:
                    bad.append(f"MRR@10 {mrr:.4f} Recall@100 {rec:.4f} "
                               f"below the host reference")
                failures += [f"{name}/{phase}: {b}" for b in bad]
                windows[name, phase] = w
                print(f"serve {name}/{phase}: {n} queries, errors "
                      f"{w['errors']} shed {w['shed']} timeouts "
                      f"{w['timeouts']} degraded {w['degraded']}; wall p50 "
                      f"{w['wall_p50_ms']:.1f} ms p99 {w['wall_p99_ms']:.1f} "
                      f"ms, {w['qps']:.2f} queries/s, mean batch "
                      f"{w['mean_batch']:.1f}; compiles {w['compiles']} "
                      f"({w['compile_s']:.1f} s); MRR@10 {mrr:.4f} "
                      f"Recall@100 {rec:.4f}; candidates failing the IVF "
                      f"oracle {w['cand_bad']}, min overlap with its float32 "
                      f"set "
                      f"{w['cand_overlap']:.4f}; max |served - ref| "
                      f"{w['ref_max_abs_err']:.6f}", flush=True)
                print(f"  modelled (ComputeModel + SSD clock, not measured): "
                      f"p50 {w['modelled_p50_ms']:.2f} ms p99 "
                      f"{w['modelled_p99_ms']:.2f} ms", flush=True)
    finally:
        pallas.close()
        pipe.close()
    delta, bad = compare_windows(windows["xla", "warm"],
                                 windows["pallas", "warm"], 2 * tol)
    failures += bad
    print(f"pallas vs xla: same candidate sets: {not bad}, max |delta| "
          f"{delta:.6f} (tol {2 * tol:.6f})", flush=True)

    enc_cfg = encoder_cfg or get_config("colberter")
    kern, bad = kernel_check(sizes, enc_cfg.max_query_len,
                             int(corpus.query_lens.max()), c.max_len,
                             c.d_bow, cfg.retrieval.fde_d_final)
    failures += bad
    interp = resolve_interpret()
    print(f"kernels: interpret={interp}, tpu_custom_call in "
          f"{kern['compiled']}; bitsim {kern['bitsim']}: max |kernel - ref| "
          f"{kern['bitsim_err']:.6f} (tol {kern['bitsim_tol']:.6f}); fdescan "
          f"{kern['fdescan']}: max |kernel - ref| {kern['fdescan_err']:.6f} "
          f"({kern['fdescan_ratio']:.3f} of its elementwise bound)",
          flush=True)
    if platform == "tpu" and (interp or len(kern["compiled"]) != 3):
        failures.append("the Pallas kernels are not compiled for the TPU")

    enc, bad = encoder_check(enc_cfg, sizes.seed, sizes.encode_batch,
                             enc_cfg.max_query_len)
    failures += bad
    print(f"encoder colberter {enc['layers']}x{enc['d_model']} bf16, random "
          f"weights: batch {enc['batch']} x {enc['seq']} tokens, first call "
          f"{enc['first_call_s']:.1f} s, steady {enc['steady_ms']:.2f} ms; "
          f"max |default - highest precision| {enc['max_dev']:.6f}",
          flush=True)
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=SERVED.seed,
                    help="seed of the corpus, kernel inputs and weights")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    held = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache: {cache} ({held} entries at start)", flush=True)
    failures = run(dataclasses.replace(SERVED, seed=args.seed), dev.platform)
    for f in failures:
        print(f"FAIL {f}", flush=True)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
