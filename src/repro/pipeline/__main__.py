"""CLI smoke entry for the pipeline facade:

    PYTHONPATH=src python -m repro.pipeline --docs 2000 --queries 8 --mode espn

Builds the full stack from flags, runs the bundled query set, and prints the
latency breakdown + quality metrics. Exercised by tests/test_pipeline_api.py
so this path cannot silently rot.
"""
from __future__ import annotations

import argparse

from repro.pipeline.config import PipelineConfig


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        prog="repro.pipeline",
        description="Build an ESPN retrieval stack and run its query set.")
    PipelineConfig.add_cli_args(ap)
    ap.add_argument("--save", default="",
                    help="directory to persist index+layout+corpus")
    args = ap.parse_args(argv)
    cfg = PipelineConfig.from_cli(args)

    from repro.compile_cache import enable_compile_cache
    from repro.pipeline import Pipeline

    enable_compile_cache()

    with Pipeline.build(cfg) as pipe:
        print(f"corpus: {pipe.corpus.n_docs} docs, "
              f"mean {pipe.corpus.mean_tokens:.0f} tokens/doc")
        print(f"index: {pipe.index.ncells} cells, "
              f"{pipe.index.memory_bytes()/2**20:.1f} MB; "
              f"blob {pipe.layout.nbytes/2**20:.1f} MB on "
              f"{pipe.backend.storage_stack}")
        ev = pipe.evaluate()
        print(f"mode={cfg.retrieval.mode} breakdown (ms): "
              f"{ev['breakdown_ms']}")
        print(f"MRR@10={ev['mrr@10']:.3f} Recall@100={ev['recall@100']:.3f}")
        if args.trace_json:
            n = pipe.export_trace(args.trace_json)
            print(f"trace: {n} events -> {args.trace_json}")
        if args.metrics_out:
            text = pipe.metrics_text()
            with open(args.metrics_out, "w") as f:
                f.write(text)
            print(f"metrics: {len(text.splitlines())} lines -> "
                  f"{args.metrics_out}")
        if args.save:
            print(f"saved -> {pipe.save(args.save)}")


if __name__ == "__main__":
    main()
