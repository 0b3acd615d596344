"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV. ``REPRO_BENCH_FAST=1`` shrinks the
corpora (CI); the full run reproduces the paper's curve shapes.

    PYTHONPATH=src python -m benchmarks.run [--only fig7]
"""
from __future__ import annotations

import argparse
import os
import time

SUITES = [
    ("tables1-3:index-size", "benchmarks.bench_index_size"),
    ("fig5:ivf-recall", "benchmarks.bench_ivf_recall"),
    ("fig7:prefetcher-hit-rate", "benchmarks.bench_prefetcher"),
    ("fig6:partial-rerank", "benchmarks.bench_partial_rerank"),
    ("beyond:bitvec-filtered-rerank", "benchmarks.bench_bitvec_rerank"),
    ("beyond:fde-candidate-gen", "benchmarks.bench_fde_candidates"),
    ("tables4-5:latency-vs-memory", "benchmarks.bench_latency_memory"),
    ("figs8-10:batch-scaling", "benchmarks.bench_batch_scaling"),
    ("beyond:cluster-scaling", "benchmarks.bench_cluster_scaling"),
    ("beyond:mutation-churn", "benchmarks.bench_mutation_churn"),
    ("beyond:serve-slo", "benchmarks.bench_serve_slo"),
    ("beyond:constant-space", "benchmarks.bench_constant_space"),
    ("beyond:faults", "benchmarks.bench_faults"),
    ("beyond:observability", "benchmarks.bench_observability"),
    ("kernels", "benchmarks.bench_kernels"),
    ("beyond:espn-embedding-offload", "benchmarks.bench_espn_embedding"),
    ("beyond:disk-ivf-full-offload", "benchmarks.bench_disk_ivf"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on suite name")
    ap.add_argument("--json-dir", default=None,
                    help="directory for machine-readable BENCH_*.json "
                         "artifacts (default: working directory)")
    args = ap.parse_args()
    if args.json_dir:
        os.environ["REPRO_BENCH_OUT_DIR"] = args.json_dir

    import importlib

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("suite,name,us_per_call,derived")
    for name, mod_name in SUITES:
        if args.only and args.only not in name:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        mod = importlib.import_module(mod_name)
        mod.main()
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
