"""Serving launcher: builds the full ESPN stack through the
``repro.pipeline`` facade and replays a query stream through the continuous
batcher. The retrieval mode (and therefore the storage-tier software stack)
comes from the backend registry — any registered backend name works.

    PYTHONPATH=src python -m repro.launch.serve --docs 50000 --queries 128
"""
from __future__ import annotations

import argparse
import time


def main():
    # config import is jax-free: --help / flag errors return instantly
    from repro.pipeline.config import PipelineConfig

    ap = argparse.ArgumentParser()
    PipelineConfig.add_cli_args(ap)
    ap.set_defaults(clusters=0)        # 0 = derive from the cell count below
    args = ap.parse_args()
    cfg = PipelineConfig.from_cli(args)
    if not cfg.corpus.n_clusters:
        cfg.corpus.n_clusters = max(64, cfg.index.resolve_ncells(
            cfg.corpus.n_docs) // 2)

    from repro.compile_cache import enable_compile_cache
    from repro.core.metrics import mrr_at_k, recall_at_k
    from repro.pipeline import Pipeline

    enable_compile_cache()

    print(f"building corpus ({cfg.corpus.n_docs} docs) ...", flush=True)
    pipe = Pipeline.build(cfg)
    server = pipe.serve()
    c = pipe.corpus

    print(f"serving ({cfg.retrieval.mode} backend on "
          f"{pipe.backend.storage_stack} tier) ...", flush=True)
    t0 = time.time()
    reqs = [server.query_async(c.queries_cls[i], c.queries_bow[i],
                               int(c.query_lens[i]))
            for i in range(cfg.corpus.n_queries)]
    ranked, qrels = [], []
    for i, r in enumerate(reqs):
        r.done.wait(60)
        if r.shed:                     # admission control (--slo-ms): the
            continue                   # request has no result by design
        ranked.append(r.result.doc_ids)
        qrels.append(c.qrels[i])
    wall = time.time() - t0

    print(f"wall={wall:.2f}s  stats={server.stats.summary()}")
    if ranked:
        print(f"MRR@10={mrr_at_k(ranked, qrels, 10):.4f}  "
              f"R@100={recall_at_k(ranked, qrels, 100):.4f}")
    server.shutdown()
    pipe.close()


if __name__ == "__main__":
    main()
