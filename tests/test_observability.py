"""Observability: streaming metrics, span tracing, tail diagnosis.

Covers the PR-10 invariants:

* ``StreamingHistogram`` percentiles track the exact (list-based) oracle
  within its log-bucket resolution, in constant memory,
* ``LatencyBreakdown.as_dict`` is COMPLETE (no dataclass field omitted),
* trace trees are well formed for every registered backend, faults on and
  off: every span closed exactly once, child wall intervals nested in the
  parent, per-query ``critical_io``/``rerank`` span sums reconciling with
  the batch ``LatencyBreakdown``, fault child spans present iff their
  counters fired,
* tracing is a pure observer: enabling it changes no ranking and no bill,
* ``analyze_trace`` attributes every SLO violation to a dominant stage,
* the Prometheus exposition and Perfetto JSON exports are well formed.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.obs import (Counter, Gauge, MetricsRegistry, StreamingHistogram,
                       Tracer, analyze_trace)
from repro.obs.analyze import STAGES, dominant_stage
from repro.pipeline import (Pipeline, PipelineConfig, RetrievalConfig,
                            StorageConfig)
from repro.pipeline.backends import available_backends

EPS = 1e-9


# -- streaming histograms -----------------------------------------------------

def test_histogram_percentiles_track_exact_oracle():
    rng = np.random.default_rng(7)
    xs = np.exp(rng.normal(2.0, 1.5, size=5000))    # lognormal latencies
    h = StreamingHistogram()
    h.extend(xs)
    for p in (50, 90, 99):
        exact = float(np.percentile(xs, p))
        approx = h.percentile(p)
        assert approx == pytest.approx(exact, rel=0.05), p
    assert h.min == pytest.approx(xs.min())
    assert h.max == pytest.approx(xs.max())
    assert h.mean() == pytest.approx(float(xs.mean()), rel=1e-9)
    assert len(h) == len(xs)


def test_histogram_constant_memory():
    h = StreamingHistogram()
    h.extend(np.linspace(0.5, 500.0, 100_000))
    # log(1000)/log(1.05) ~ 142 buckets cover three decades
    assert len(h.buckets) < 200
    assert len(h) == 100_000


def test_histogram_merge_and_edge_cases():
    a, b = StreamingHistogram(), StreamingHistogram()
    a.extend([1.0, 2.0, 3.0])
    b.extend([10.0, 20.0])
    b.observe(0.0)                     # nonpositive -> dedicated bucket
    a.merge(b)
    assert len(a) == 6
    assert a.min == 0.0 and a.max == 20.0
    assert a.percentile(0) == 0.0
    assert a.percentile(100) == pytest.approx(20.0)
    empty = StreamingHistogram()
    assert empty.percentile(99) == 0.0 and not empty
    with pytest.raises(ValueError):
        a.merge(StreamingHistogram(growth=1.1))


def test_histogram_keeps_list_recording_api():
    h = StreamingHistogram()
    h.append(4.2)                      # alias used by ServeStats recording
    h.extend([1.0, 2.0])
    assert len(h) == 3 and bool(h)


def test_serve_stats_percentiles_match_list_oracle():
    from repro.serve.engine import ServeStats
    rng = np.random.default_rng(3)
    xs = rng.gamma(2.0, 12.0, size=2000) + 0.5
    s = ServeStats()
    for x in xs:
        s.latencies_ms.append(float(x))
        s.sim_latencies_ms.append(float(x) * 0.5)
        s.slo_latencies_ms.append(float(x) * 1.5)
    for p in (50, 99):
        assert s.percentile(p, sim=False) == pytest.approx(
            float(np.percentile(xs, p)), rel=0.05)
        assert s.slo_percentile(p) == pytest.approx(
            float(np.percentile(xs * 1.5, p)), rel=0.05)
    out = s.summary()
    assert out["p50_ms"] == pytest.approx(
        float(np.percentile(xs * 0.5, 50)), rel=0.05)


# -- metrics registry ---------------------------------------------------------

def test_registry_exposition_format():
    reg = MetricsRegistry()
    reg.counter("reads_total", help="total reads").inc(3)
    reg.gauge("depth").set(7.5)
    reg.histogram("lat_ms").extend([1.0, 2.0, 400.0])
    reg.register_source("tier", lambda: {"blocks": 11, "ok": True,
                                         "skipme": "not-a-number"})
    text = reg.expose()
    assert "# TYPE reads_total counter" in text
    assert "reads_total 3" in text
    assert "gauge" in text and "depth 7.5" in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert "lat_ms_count 3" in text
    assert "tier_blocks 11" in text
    assert "tier_ok 1" in text                  # bools coerce to ints
    assert "skipme" not in text                 # non-numerics dropped
    assert text.endswith("\n")


def test_registry_kind_conflicts_and_dead_sources():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")

    def dying():
        raise RuntimeError("snapshot failed")

    reg.register_source("bad", dying)
    assert "x 0" in reg.expose()                # dead source never breaks it


# -- breakdown completeness ---------------------------------------------------

def test_as_dict_covers_every_breakdown_field():
    from repro.core.espn import LatencyBreakdown
    bd = LatencyBreakdown(encode_s=1e-3, ann_s=2e-3, critical_io_s=3e-3,
                          rerank_s=4e-3, total_s=10e-3, bytes_read=512,
                          retries=2)
    d = bd.as_dict()
    for f in dataclasses.fields(LatencyBreakdown):
        key = f.name[:-2] + "_ms" if f.name.endswith("_s") else f.name
        assert key in d, f"as_dict dropped {f.name}"
    assert d["encode_ms"] == pytest.approx(1.0)
    assert d["bytes_read"] == 512 and d["retries"] == 2
    # ms() is the lossy stage-only view; as_dict must strictly cover it
    for k in bd.ms():
        assert (k if k == "hit_rate" else k[:-2] + "_ms") in d
    assert len(d) >= len(dataclasses.fields(LatencyBreakdown))


# -- trace trees over every backend -------------------------------------------

def _build(corpus, *, faulted: bool) -> Pipeline:
    cfg = PipelineConfig(
        storage=StorageConfig(t_max=64),
        retrieval=RetrievalConfig(mode="espn", nprobe=16, k_candidates=50,
                                  prefetch_step=0.3))
    cfg.index.ncells = 32
    cfg.index.iters = 4
    if faulted:
        cfg.cluster.n_shards = 2
        cfg.cluster.replication = 2
        cfg.cluster.hedge_quantile = 0.9
        cfg.cluster.jitter_sigma = 0.4
        cfg.faults.read_error_rate = 0.05
        cfg.faults.stall_rate = 0.05
        cfg.faults.corruption_rate = 0.05
        cfg.faults.checksum = True
    return Pipeline.build(cfg, corpus=corpus)


@pytest.fixture(scope="module")
def plain(small_corpus):
    with _build(small_corpus, faulted=False) as p:
        yield p


@pytest.fixture(scope="module")
def faulted(small_corpus):
    with _build(small_corpus, faulted=True) as p:
        yield p


def _traced_run(base: Pipeline, mode: str, corpus):
    pipe = base.with_mode(mode)
    tr = Tracer()
    pipe.backend.tracer = tr
    pipe.tier.tracer = tr
    resp = pipe.backend.query_batch(corpus.queries_cls, corpus.queries_bow,
                                    corpus.query_lens)
    return pipe, tr, resp


@pytest.mark.parametrize("fixture", ["plain", "faulted"])
@pytest.mark.parametrize("mode", available_backends())
def test_trace_tree_invariants(fixture, mode, small_corpus, request):
    base = request.getfixturevalue(fixture)
    pipe, tr, resp = _traced_run(base, mode, small_corpus)
    spans = tr.spans()
    assert spans, "tracing produced no spans"
    # 1. every span closed exactly once
    assert tr.open_count() == 0
    by_sid = {}
    for sp in spans:
        assert sp.closed, f"span {sp.name} never closed"
        by_sid[sp.sid] = sp
    with pytest.raises(RuntimeError):
        tr.end(spans[0])               # double close must raise
    # 2. child wall intervals nest inside the parent
    for sp in spans:
        if sp.parent is None:
            continue
        par = by_sid[sp.parent]
        assert par.t0 - EPS <= sp.t0, (sp.name, par.name)
        assert sp.t1 <= par.t1 + EPS, (sp.name, par.name)
    # 3. per-query span sums reconcile with the batch breakdown
    bd = resp.breakdown
    cio = sum(s.sim_s for s in spans if s.name == "critical_io")
    rr = sum(s.sim_s for s in spans
             if s.name in ("rerank", "bit_filter"))
    assert cio == pytest.approx(bd.critical_io_s, abs=1e-9)
    assert rr == pytest.approx(bd.rerank_s, abs=1e-9)
    # 4. fault/hedge child spans appear iff their counters fired
    tier = pipe.tier
    names = {s.name for s in spans}
    stats = tier.stats
    for key, span_name in (("retries", "retry"), ("stalls", "stall"),
                           ("repairs", "repair"),
                           ("read_errors", "read_error")):
        if stats.get(key, 0):
            assert span_name in names, f"{key} fired but no {span_name} span"
    if fixture == "plain":
        assert not any(s.cat == "fault" for s in spans)
    if stats.get("hedged_reads", 0):
        assert "hedge" in names
    pipe.close()


@pytest.mark.parametrize("mode", available_backends())
def test_tracing_is_bitwise_invisible(faulted, mode, small_corpus):
    c = small_corpus
    off = faulted.with_mode(mode)
    r_off = off.backend.query_batch(c.queries_cls, c.queries_bow,
                                    c.query_lens)
    off.close()
    on, tr, r_on = _traced_run(faulted, mode, c)
    for a, b in zip(r_off.ranked, r_on.ranked):
        assert np.array_equal(a.doc_ids, b.doc_ids)
        assert np.array_equal(a.scores, b.scores)
    assert r_off.breakdown.total_s == r_on.breakdown.total_s
    assert r_off.breakdown.bytes_read == r_on.breakdown.bytes_read
    on.close()


def _profiled(fn, logdir):
    """Run ``fn`` under a profiler session; the host plane's lines (one per
    thread), each a list of (name, start_ns, end_ns)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    plane = next(p for p in ProfileData.from_file(path).planes
                 if p.name == "/host:CPU")
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in ln.events] for ln in plane.lines]
    return out, lines


SERVED_SPANS = {"espn.query_batch", "espn.candidate_gen", "espn.ivf_search",
                "espn.read_submit", "espn.read_run", "espn.io_wait",
                "espn.rerank_gather", "espn.h2d", "espn.maxsim"}


def test_profiler_spans_on_the_served_path(plain, small_corpus, tmp_path):
    c = small_corpus
    pipe = plain.with_mode("espn")

    def run():
        return pipe.backend.query_batch(c.queries_cls, c.queries_bow,
                                        c.query_lens)
    r_off = run()
    r_on, lines = _profiled(run, str(tmp_path))
    pipe.close()
    for a, b in zip(r_off.ranked, r_on.ranked):
        assert np.array_equal(a.doc_ids, b.doc_ids)
        assert np.array_equal(a.scores, b.scores)
    names = {n for ln in lines for n, _, _ in ln}
    assert SERVED_SPANS <= names, SERVED_SPANS - names
    batcher = [ln for ln in lines
               if any(n == "espn.query_batch" for n, _, _ in ln)]
    assert len(batcher) == 1
    (qb,) = [(s, e) for n, s, e in batcher[0] if n == "espn.query_batch"]
    inner = [(n, s, e) for n, s, e in batcher[0]
             if n in ("espn.io_wait", "espn.h2d", "espn.maxsim")]
    assert {n for n, _, _ in inner} == {"espn.io_wait", "espn.h2d",
                                        "espn.maxsim"}
    assert all(qb[0] <= s <= e <= qb[1] for _, s, e in inner)
    # the tier's pool gathers the runs, off the batcher's thread
    assert any(n == "espn.read_run" for ln in lines if ln is not batcher[0]
               for n, _, _ in ln)


def test_stage_feeds_the_tracer():
    from repro.obs.trace import stage
    with stage("x") as sp:
        assert sp is None                       # no tracer: profiler only
    tr = Tracer()
    with stage("outer", tr, "batch") as outer:
        with stage("inner", tr) as inner:
            tr.end(inner, sim_s=2.0)            # ended by the caller
    with pytest.raises(ValueError):
        with stage("failing", tr) as failing:
            raise ValueError
    assert tr.open_count() == 0
    assert (outer.name, outer.cat, outer.closed) == ("outer", "batch", True)
    assert inner.parent == outer.sid and inner.sim_s == 2.0
    assert failing.args == {"error": True} and failing.closed


def test_query_sims_index_matches_a_scan(faulted, small_corpus, tmp_path):
    c = small_corpus
    pipe = faulted.with_mode("espn")
    srv = pipe.serve(trace_path=str(tmp_path / "serve.json"))
    tr = srv.tracer
    for i in range(10):
        srv.query(c.queries_cls[i], c.queries_bow[i], int(c.query_lens[i]))
    srv.shutdown()
    pipe.close()
    spans = tr.spans()
    qids = {s.qid for s in spans}
    assert len(qids - {None}) >= 10
    for qid in qids:
        for names in (None, ("critical_io", "rerank")):
            scan = {}
            for s in spans:
                if s.qid == qid and (names is None or s.name in names):
                    scan[s.name] = scan.get(s.name, 0.0) + s.sim_s
            assert tr.query_sims(qid, names) == scan
    tr.clear()
    assert tr.query_sims(next(iter(qids - {None}))) == {}


def test_trace_export_is_perfetto_loadable(faulted, small_corpus, tmp_path):
    pipe, tr, _ = _traced_run(faulted, "espn", small_corpus)
    path = str(tmp_path / "trace.json")
    n = tr.export(path)
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc["traceEvents"], list)
    assert len(doc["traceEvents"]) == n > 0
    complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    for e in complete:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["pid"] in (1, 2)
    # dual clock: device-time events mirror spans with sim_s on pid 2
    assert any(e["pid"] == 2 for e in complete)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["pid"] for e in meta} == {1, 2}
    pipe.close()


# -- tail diagnosis -----------------------------------------------------------

def test_dominant_stage_refinements():
    stages = {"queue": 1.0, "critical_io": 9.0, "rerank": 2.0}
    assert dominant_stage(stages) == "critical_io"
    assert dominant_stage(stages, {"retries": 2}) == "retry_repair"
    assert dominant_stage(stages, {"repairs": 1}) == "retry_repair"
    assert dominant_stage(stages, {"hedged": 3,
                                   "hedge_wins": 0}) == "hedge_loss"
    assert dominant_stage(stages, {"hedged": 3,
                                   "hedge_wins": 1}) == "critical_io"
    assert dominant_stage({"queue": 5.0, "critical_io": 1.0}) == "queue"
    assert dominant_stage({}) in STAGES


def test_serve_violations_fully_attributed(faulted, small_corpus, tmp_path):
    c = small_corpus
    pipe = faulted.with_mode("espn")
    pipe.cfg.serve.slo_ms = 0.25       # far below the device bill: every
    pipe.cfg.serve.shed = False        # request violates, none shed
    pipe.cfg.serve.max_batch = 6
    path = str(tmp_path / "serve.json")
    srv = pipe.serve(trace_path=path)
    reqs = [srv.query_async(c.queries_cls[i % 24], c.queries_bow[i % 24],
                            int(c.query_lens[i % 24])) for i in range(18)]
    for r in reqs:
        assert r.done.wait(30)
    srv.shutdown()                      # exports the trace
    rep = analyze_trace(path)
    assert rep["requests"] == 18
    assert rep["violations"] == srv.stats.slo_violations > 0
    assert rep["attribution_rate"] == 1.0
    assert sum(rep["by_stage"].values()) == rep["violations"]
    for row in rep["rows"]:
        assert row["latency_ms"] > row["budget_ms"]
        assert set(row["stages_ms"]) == set(STAGES)
    # the same diagnosis feeds the autoscaler path via observe_stage
    from repro.serve.autoscaler import Autoscaler, AutoscalerConfig
    scaler = Autoscaler(pipe.tier, AutoscalerConfig(slo_ms=0.25, min_fill=1))
    for row in rep["rows"]:
        scaler.observe_stage(row["dominant"])
        scaler.observe(row["latency_ms"])
    act = scaler.step(now=0.0)
    assert act is not None and "evidence" in act
    assert act["evidence"]["violations_by_stage"]
    assert act["evidence"]["dominant"] in set(STAGES) | {"retry_repair",
                                                         "hedge_loss"}
    pipe.close()


def test_server_metrics_exposition(plain, small_corpus):
    c = small_corpus
    pipe = plain.with_mode("espn")
    srv = pipe.serve()
    for i in range(8):
        srv.query(c.queries_cls[i], c.queries_bow[i], int(c.query_lens[i]))
    text = srv.metrics_text()
    srv.shutdown()
    assert "# TYPE serve_latency_wall_ms histogram" in text
    assert "serve_latency_wall_ms_count 8" in text
    assert "serve_n_requests 8" in text
    assert "batcher_requests_dispatched 8" in text
    assert "storage_tier_" in text      # tier source registered underneath
    pipe.close()
