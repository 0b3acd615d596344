#!/usr/bin/env python3
"""The program's own spans on the served path, read from a traced run.

``repro.obs.trace.stage`` opens a profiler annotation ``espn.<name>`` where
the work happens; they share the device trace's clock:

- batcher thread: ``espn.query_batch`` (one per batch), ``espn.candidate_gen``
  and inside it ``espn.ivf_search`` (IVF probe + scan + top-k, results on
  the host) and ``espn.read_submit`` (read plan, arena, run submission);
  per query ``espn.io_wait`` (waiting for its reads), ``espn.rerank_gather``
  (hit/miss row selection and copies out of the arena), and per MaxSim
  call ``espn.h2d`` (query and candidates to the device) and
  ``espn.maxsim`` (the kernel and its scores back);
- the tier's I/O pool: ``espn.read_run``, one per gathered run.

``bench/tracefile.load`` keeps only the benchmark's own annotations;
``load`` here adds every ``espn.*`` span to its ``Trace``. Each reader gives
one number per traced run, None when the trace lacks its span.

As a script it makes one traced run of a cell through the harness, with the
chip's idle time charged to the innermost ``espn.*`` batcher span first
(``BATCHER_STATES`` ahead of ``tracefile.HOST_STATES``), and prints the
result line with the readers' numbers under ``spans``:

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import tracefile  # noqa: E402

PREFIX = "espn."
# batcher-thread spans, innermost first; espn.read_run runs on the I/O
# pool, so it never says what the batcher was doing
BATCHER_STATES = tuple(PREFIX + n for n in (
    "io_wait", "h2d", "maxsim", "rerank_gather", "read_submit",
    "ivf_search"))
_tracefile_load = tracefile.load


def load(path: str, chips: int) -> tracefile.Trace:
    """``tracefile.load``, plus every ``espn.*`` span of the host plane."""
    from jax.profiler import ProfileData
    tr = _tracefile_load(path, chips)
    extra = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX) and ev.name not in tr.spans:
                    extra.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    for name, v in extra.items():
        tr.spans[name] = tuple(np.asarray(x, np.float64)
                               for x in zip(*sorted(v)))
    return tr


def _per_batch_ms(tr, name: str) -> float | None:
    """Summed duration of ``name`` over the number of batches, in ms."""
    n = len(tr.span_durations_s(PREFIX + "query_batch"))
    d = tr.span_durations_s(PREFIX + name)
    return float(d.sum() * 1e3 / n) if n and len(d) else None


def ivf_ms(tr) -> float | None:
    """Mean ``espn.ivf_search`` time per batch."""
    return _per_batch_ms(tr, "ivf_search")


def read_wait_ms(tr) -> float | None:
    """``espn.io_wait`` summed per batch: read time the query path waits."""
    return _per_batch_ms(tr, "io_wait")


def h2d_ms(tr) -> float | None:
    """``espn.h2d`` summed per batch: MaxSim's inputs to the device."""
    return _per_batch_ms(tr, "h2d")


def read_ms(tr) -> float | None:
    """Per batch, the time in which at least one ``espn.read_run`` ran
    inside it (overlapping runs on the pool's threads count once); the
    mean over batches."""
    bs, be = tr.spans.get(PREFIX + "query_batch", (np.zeros(0),) * 2)
    rs, re = tr.spans.get(PREFIX + "read_run", (np.zeros(0),) * 2)
    if not len(bs) or not len(rs):
        return None
    per = []
    for lo, hi in zip(bs, be):
        sel = (rs < hi) & (re > lo)
        s, e = tracefile.merge(rs[sel], re[sel], lo, hi)
        per.append(float((e - s).sum()))
    return float(np.mean(per)) * 1e-6


READERS = {"ivf_ms": ivf_ms, "read_ms": read_ms,
           "read_wait_ms": read_wait_ms, "h2d_ms": h2d_ms}


def traced_run(cell: str, seed: int, seconds: float, *, t_start: float,
               root: str = ROOT, log=None) -> dict:
    """``harness.run_cell`` traced, idle charged to ``BATCHER_STATES``
    first; adds ``spans``: each reader's number, ``espn.*`` spans per batch
    and the trace file's size."""
    from bench import harness
    got = {}

    def load_kept(path: str, chips: int):
        got["bytes"] = os.path.getsize(path)
        got["trace"] = load(path, chips)
        return got["trace"]

    states = tracefile.HOST_STATES
    tracefile.HOST_STATES = BATCHER_STATES + states
    tracefile.load = load_kept
    try:
        out = harness.run_cell(cell, seed, seconds, True, t_start=t_start,
                               root=root, log=log)
    finally:
        tracefile.HOST_STATES, tracefile.load = states, _tracefile_load
    tr = got["trace"]
    n = len(tr.span_durations_s(PREFIX + "query_batch"))
    n_spans = sum(len(s) for k, (s, _) in tr.spans.items()
                  if k.startswith(PREFIX))
    out["spans"] = {name: f(tr) for name, f in READERS.items()}
    out["spans"].update(per_batch=n_spans / n if n else None,
                        trace_bytes=got["bytes"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    try:
        out = traced_run(args.workload, args.seed, args.seconds,
                         t_start=T_START)
    except harness.NoChip as e:
        print(f"bench/spans.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
