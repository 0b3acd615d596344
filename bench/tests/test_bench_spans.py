"""Reading the program's ``espn.*`` spans: the readers on hand-built traces,
the idle breakdown with the batcher spans ahead of the harness's, and a
traced run of the tiny cell on the CPU."""
import numpy as np
import pytest

from bench import spans, tracefile

MS = 1e6                                   # ns per ms


def _trace(window=(0.0, 100 * MS), modules=None, **named):
    """A ``Trace`` from ``name=[(start_ms, end_ms), ...]``; ``__`` in a name
    stands for ``.``."""
    sp = {}
    for name, iv in named.items():
        iv = sorted(iv)
        sp[name.replace("__", ".")] = (np.array([s for s, _ in iv]) * MS,
                                       np.array([e for _, e in iv]) * MS)
    mods = [] if modules is None else [
        (np.array([s for s, _ in modules]) * MS,
         np.array([e for _, e in modules]) * MS, ["jit_f"] * len(modules))]
    return tracefile.Trace(window=window, modules=mods, ops=[], spans=sp)


def test_readers_per_batch():
    tr = _trace(espn__query_batch=[(0, 40), (50, 90)],
                espn__ivf_search=[(1, 5), (51, 57)],
                espn__io_wait=[(10, 14), (20, 21), (60, 63)],
                espn__h2d=[(15, 16), (64, 66)])
    assert spans.ivf_ms(tr) == pytest.approx(5.0)
    assert spans.read_wait_ms(tr) == pytest.approx(4.0)
    assert spans.h2d_ms(tr) == pytest.approx(1.5)


def test_read_ms_counts_overlapping_runs_once():
    # batch 1: runs on two pool threads overlap over [8, 12]: 10 ms of
    # reading, not 14; batch 2: one run cut at the batch's end
    tr = _trace(espn__query_batch=[(0, 40), (50, 90)],
                espn__read_run=[(5, 12), (8, 15), (30, 33), (85, 95)])
    assert spans.read_ms(tr) == pytest.approx((10 + 3 + 5) / 2)


@pytest.mark.parametrize("reader", sorted(spans.READERS))
def test_reader_without_its_span_reads_nothing(reader):
    f = spans.READERS[reader]
    assert f(_trace(espn__query_batch=[(0, 40)])) is None
    # the parent's trace: only the harness's own annotations
    assert f(_trace(query_batch=[(0, 40)], rerank_query=[(10, 30)])) is None


def test_idle_goes_to_the_innermost_program_span(monkeypatch):
    # the chip runs [0, 10] and [90, 100]; the host is in rerank_query over
    # [20, 80], waiting for reads in espn.io_wait over [30, 50]
    tr = _trace(modules=[(0, 10), (90, 100)], query_batch=[(15, 85)],
                rerank_query=[(20, 80)], espn__io_wait=[(30, 50)])
    assert dict(tracefile.idle_by_state(tr))["rerank_query"] == \
        pytest.approx(60e-3)
    monkeypatch.setattr(tracefile, "HOST_STATES",
                        spans.BATCHER_STATES + tracefile.HOST_STATES)
    idle = dict(tracefile.idle_by_state(tr))
    assert idle["espn.io_wait"] == pytest.approx(20e-3)
    assert idle["rerank_query"] == pytest.approx(40e-3)
    assert idle["query_batch"] == pytest.approx(10e-3)
    assert idle[tracefile.IDLE_OUTSIDE] == pytest.approx(10e-3)


def test_traced_run_reads_program_spans(tiny_root, cpu_chip):
    import time
    states, load = tracefile.HOST_STATES, tracefile.load
    out = spans.traced_run("tiny-open", 7, 2.0, t_start=time.monotonic(),
                           root=str(tiny_root), log=lambda m: None)
    assert (tracefile.HOST_STATES, tracefile.load) == (states, load)
    assert out["correct"] is True
    got, m = out["spans"], out["metrics"]
    assert all(got[k] > 0 for k in spans.READERS), got
    assert got["read_wait_ms"] <= m["rerank_ms"]["value"]
    assert got["h2d_ms"] <= m["rerank_ms"]["value"]
    assert got["ivf_ms"] <= m["cand_ms"]["value"]
    assert got["read_ms"] <= m["batch_ms"]["value"]
    assert got["per_batch"] > 0 and got["trace_bytes"] > 0
