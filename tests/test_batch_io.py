"""Coalesced batch I/O engine: plan correctness, serial-path equivalence
(bitwise-identical rankings for every registered backend), dedup accounting
invariants, and the pipelined-arena contract."""
import numpy as np
import pytest

from repro.pipeline import (Pipeline, PipelineConfig, RetrievalConfig,
                            StorageConfig, available_backends)
from repro.storage.batch_io import BatchReadPlan, consumption_dedup_saved
from repro.storage.io_engine import StorageTier
from repro.storage.layout import pack


def _mini_layout(n=60, d_cls=16, d_bow=8, seed=3):
    rng = np.random.default_rng(seed)
    cls = rng.standard_normal((n, d_cls)).astype(np.float32)
    bow = [rng.standard_normal((int(t), d_bow)).astype(np.float32)
           for t in rng.integers(4, 40, n)]
    return pack(cls, bow, dtype=np.float16)


@pytest.fixture(scope="module")
def base(small_corpus):
    cfg = PipelineConfig(
        storage=StorageConfig(t_max=64, mem_budget_frac=1.0),
        retrieval=RetrievalConfig(mode="espn", nprobe=16, k_candidates=50,
                                  prefetch_step=0.3, bit_filter=16))
    cfg.index.ncells = 32
    pipe = Pipeline.build(cfg, corpus=small_corpus)
    yield pipe
    pipe.close()


def _with_io(base, mode, coalesce):
    cfg = PipelineConfig.from_dict(base.cfg.to_dict())
    cfg.retrieval.mode = mode
    cfg.storage.io_coalesce = coalesce
    return Pipeline.from_artifacts(cfg, index=base.index, layout=base.layout,
                                   corpus=base.corpus)


def _dup_heavy_queries(corpus, n_base=5, reps=3):
    """A skewed batch: each query appears ``reps`` times -> candidate sets
    overlap maximally across the batch."""
    return (np.tile(corpus.queries_cls[:n_base], (reps, 1)),
            np.tile(corpus.queries_bow[:n_base], (reps, 1, 1)),
            np.tile(corpus.query_lens[:n_base], reps))


# -- plan construction -------------------------------------------------------

def test_plan_dedup_and_arena_order():
    layout = _mini_layout()
    lists = [np.array([5, 9, 2]), np.array([9, 2, 40]), np.array([5])]
    plan = BatchReadPlan.build(layout, lists)
    assert plan.n_requested == 7
    assert plan.n_unique == 4
    assert sorted(plan.arena_ids.tolist()) == [2, 5, 9, 40]
    # arena is sorted by start block (coalesced ascending access)
    starts = layout.offsets[plan.arena_ids, 0]
    assert (np.diff(starts) >= 0).all()
    # every query's rows point at its own ids
    for q_ids, rows in zip(lists, plan.query_rows):
        np.testing.assert_array_equal(plan.arena_ids[rows], q_ids)
    # runs partition the arena
    assert plan.runs[0][0] == 0 and plan.runs[-1][1] == plan.n_unique
    for (_, e), (s, _) in zip(plan.runs[:-1], plan.runs[1:]):
        assert e == s
    # first-owner attribution conserves the block total
    assert plan.owned_blocks.sum() == plan.n_blocks
    # query 2 only requested doc 5, already owned by query 0
    assert plan.owned_blocks[2] == 0


def test_plan_membership_lookup():
    layout = _mini_layout()
    plan = BatchReadPlan.build(layout, [np.array([1, 2, 3])])
    np.testing.assert_array_equal(plan.contains([2, 7, 3]),
                                  [True, False, True])
    rows = plan.rows_of([3, 1])
    np.testing.assert_array_equal(plan.arena_ids[rows], [3, 1])


def test_pages_of_vectorized_matches_reference():
    layout = _mini_layout()
    tier = StorageTier(layout, stack="mmap", mem_budget_bytes=2**20)
    ids = [7, 3, 7, 12, 0]
    ref = []
    for i in np.asarray(ids, np.int64):
        s, nb = layout.offsets[i]
        ref.extend(range(int(s), int(s + nb)))
    np.testing.assert_array_equal(tier._pages_of(ids), ref)
    assert len(tier._pages_of([])) == 0
    tier.close()


# -- batch read execution ----------------------------------------------------

def test_read_batch_matches_serial_content():
    layout = _mini_layout()
    tier = StorageTier(layout, stack="espn", t_max=48)
    lists = [np.array([3, 8, 8, 1]), np.array([8, 3]), np.array([], np.int64)]
    batch = tier.read_batch(lists, coalesce=True)
    batch.wait_all()
    for b, ids in enumerate(lists):
        buffers, row_map, _ = batch.view(b)
        serial = tier.read(ids)
        for j, i in enumerate(ids):
            row = row_map[int(i)]
            np.testing.assert_array_equal(buffers[1][row], serial.bow[j])
            np.testing.assert_array_equal(buffers[0][row], serial.cls[j])
            assert buffers[2][row] == serial.lens[j]
    tier.close()


def test_views_are_zero_copy_into_shared_arena():
    layout = _mini_layout()
    tier = StorageTier(layout, stack="espn", t_max=48)
    batch = tier.read_batch([np.array([1, 2]), np.array([2, 3])])
    b0, _, _ = batch.view(0)
    b1, _, _ = batch.view(1)
    assert b0[1] is b1[1] is batch.arena[1]    # same ndarray, no copies
    tier.close()


def test_coalesced_clock_not_worse_than_serial():
    layout = _mini_layout()
    lists = [np.arange(20), np.arange(20), np.arange(10, 30)]
    t_c = StorageTier(layout, stack="espn", t_max=48)
    t_s = StorageTier(layout, stack="espn", t_max=48)
    coal = t_c.read_batch(lists, coalesce=True)
    ser = t_s.read_batch(lists, coalesce=False)
    assert coal.sim_seconds <= ser.sim_seconds
    assert coal.n_blocks <= ser.n_blocks
    assert coal.unique_docs == 30 and coal.requested_docs == 60
    # first-owner attribution sums exactly to the batch total
    shares = sum(coal.io_s(b) for b in range(3))
    assert shares == pytest.approx(coal.sim_seconds, rel=1e-12)
    t_c.close()
    t_s.close()


def test_dedup_bytes_saved_counts_duplicates():
    layout = _mini_layout()
    tier = StorageTier(layout, stack="espn", t_max=48)
    batch = tier.read_batch([np.array([4, 5]), np.array([5, 6]),
                             np.array([5])])
    saved = batch.dedup_bytes_saved(layout.doc_bytes)
    assert saved == 2 * layout.doc_bytes(5)
    assert consumption_dedup_saved([[4, 5], [5, 6], [5]],
                                   layout.doc_bytes) == saved
    serial = tier.read_batch([np.array([4, 5]), np.array([5])],
                             coalesce=False)
    assert serial.dedup_bytes_saved(layout.doc_bytes) == 0
    tier.close()


# -- end-to-end: every backend, coalesced == serial --------------------------

@pytest.mark.parametrize("mode", sorted(available_backends()))
def test_rankings_identical_to_serial_path(base, mode):
    """The engine must never change scores: a duplicate-heavy batch through
    the coalesced path returns bitwise-identical rankings to the seed's
    serial per-query reads, for every registered backend."""
    q = _dup_heavy_queries(base.corpus)
    coal = _with_io(base, mode, True)
    ser = _with_io(base, mode, False)
    a = coal.search(*q)
    b = ser.search(*q)
    assert len(a.ranked) == len(b.ranked) == len(q[0])
    for x, y in zip(a.ranked, b.ranked):
        np.testing.assert_array_equal(x.doc_ids, y.doc_ids)
        np.testing.assert_allclose(x.scores, y.scores, rtol=0, atol=0)
    # the clock and the bandwidth bill must only ever shrink
    assert a.breakdown.critical_io_s <= b.breakdown.critical_io_s
    assert a.breakdown.bytes_read <= b.breakdown.bytes_read
    assert a.breakdown.dedup_bytes_saved > 0
    assert b.breakdown.dedup_bytes_saved == 0
    coal.close()
    ser.close()


def test_dedup_savings_monotone_in_batch_size(base):
    """On a skewed workload (same queries repeated) the dedup savings grow
    with batch size."""
    pipe = _with_io(base, "gds", True)
    c = pipe.corpus
    saved = []
    for reps in (1, 2, 4):
        q = (np.tile(c.queries_cls[:4], (reps, 1)),
             np.tile(c.queries_bow[:4], (reps, 1, 1)),
             np.tile(c.query_lens[:4], reps))
        saved.append(pipe.search(*q).breakdown.dedup_bytes_saved)
    assert saved[0] < saved[1] < saved[2]
    pipe.close()


def test_espn_misses_served_from_batch_prefetch_arena(base):
    """A miss that ANY query in the batch prefetched is served from the
    shared arena (cross-query reuse), not re-read from storage — duplicate
    queries ride entirely on the first twin's I/O."""
    from repro.core.prefetcher import ANNPrefetcher

    c = base.corpus
    pf = ANNPrefetcher(base.index, base.tier, prefetch_step=0.3)
    q = np.tile(c.queries_cls[:3], (2, 1))     # queries 3..5 duplicate 0..2
    results = pf.run_batch(q, nprobe=16, k=50)
    for first, dup in zip(results[:3], results[3:]):
        np.testing.assert_array_equal(first.doc_ids, dup.doc_ids)
        # the duplicate first-owns nothing: its prefetch AND misses were
        # already in the batch arenas, so it pays zero I/O
        assert dup.stats.prefetch_io_s == 0.0
        assert dup.stats.miss_io_s == 0.0
        assert first.stats.prefetch_io_s >= 0.0
        # both twins can still score every candidate
        rows = set(dup.prefetched) | set(dup.miss_rows or {})
        assert set(dup.doc_ids.tolist()) <= rows


def test_served_miss_rows_covered_by_wait_barrier(base, monkeypatch):
    """Regression: a miss served from the prefetch arena lives in runs owned
    by OTHER queries; wait_io must block on those runs too, or rerank scores
    all-zero rows. Deterministic setup: query 1 prefetches nothing and all
    its misses are served from query 0's arena, so pre-fix its barrier had
    nothing to wait on while the serving gathers were still in flight."""
    import threading

    import repro.core.prefetcher as P
    from repro.core.prefetcher import ANNPrefetcher
    from repro.storage.layout import gather_docs_into, unpack_doc

    pref0 = np.arange(40)
    fin1 = np.array([10, 11])

    def fake_two_phase(index, q, nprobe, k, delta):
        a_ids = np.vstack([pref0, np.full(40, -1)])
        a_scores = np.zeros_like(a_ids, np.float32)
        f_ids = np.vstack([pref0[:2], fin1])
        f_scores = np.zeros_like(f_ids, np.float32)
        return (a_scores, a_ids), (f_scores, f_ids), None

    monkeypatch.setattr(P, "search_two_phase", fake_two_phase)
    tier = StorageTier(base.layout, stack="espn", t_max=64, io_chunk_docs=4)
    gate = threading.Event()
    orig_submit = tier._pool.submit

    def gated_submit(fn, *a, **kw):
        if fn is gather_docs_into:
            def gated(*aa, **kk):
                assert gate.wait(timeout=30)
                return fn(*aa, **kk)
            return orig_submit(gated, *a, **kw)
        return orig_submit(fn, *a, **kw)

    tier._pool.submit = gated_submit
    try:
        pf = ANNPrefetcher(base.index, tier, prefetch_step=0.3)
        results = pf.run_batch(base.corpus.queries_cls[:2], nprobe=16, k=40)
        res = results[1]
        assert not res.hit_mask.any()          # all of fin1 are misses…
        assert set(res.prefetched) == {10, 11}  # …served from q0's arena
        snapshots = {}

        def consume():
            res.wait_io()
            _, bow, lens = res.buffers
            for i in fin1:
                row = res.prefetched[int(i)]
                snapshots[int(i)] = bow[row, :int(lens[row])].copy()

        t = threading.Thread(target=consume)
        t.start()
        t.join(timeout=0.3)
        assert t.is_alive()   # barrier must block while gathers are gated
        gate.set()
        t.join(timeout=30)
        assert not t.is_alive()
        for i in fin1:        # and the consumed rows hold the real doc data
            ref = unpack_doc(base.layout, int(i))[1][:len(snapshots[int(i)])]
            np.testing.assert_array_equal(snapshots[int(i)], ref)
    finally:
        gate.set()
        tier.close()


def test_empty_and_degenerate_batches():
    layout = _mini_layout()
    tier = StorageTier(layout, stack="espn", t_max=48)
    empty = tier.read_batch([], coalesce=True)
    assert empty.sim_seconds == 0.0 and empty.unique_docs == 0
    allempty = tier.read_batch([np.array([], np.int64)] * 3, coalesce=True)
    assert allempty.sim_seconds == 0.0
    buffers, row_map, io_s = allempty.view(1)
    assert row_map == {} and io_s == 0.0
    tier.close()


def test_coalesced_read_submits_gather_docs_into_itself():
    """The fault-free coalesced path hands ``gather_docs_into`` itself to the
    pool — one task per block-contiguous run — and the arena it fills holds
    each passage exactly as ``unpack_doc`` reads it."""
    from repro.storage.layout import gather_docs_into, unpack_doc
    layout = _mini_layout()
    tier = StorageTier(layout, stack="espn", t_max=32, io_chunk_docs=5)
    submitted = []
    orig_submit = tier._pool.submit

    def recording_submit(fn, *a, **kw):
        submitted.append(fn)
        return orig_submit(fn, *a, **kw)

    tier._pool.submit = recording_submit
    try:
        res = tier.read_batch([[3, 9, 10, 41], [9, 22, 57, 58, 0]],
                              coalesce=True)
        res.wait_all()
        assert len(submitted) == len(res.plan.runs) > 1
        assert all(fn is gather_docs_into for fn in submitted)
        cls, bow, lens = res.arena
        for row, i in enumerate(res.plan.arena_ids):
            c, b = unpack_doc(layout, int(i))
            t = min(b.shape[0], 32)
            assert lens[row] == t
            np.testing.assert_array_equal(cls[row], c)
            np.testing.assert_array_equal(bow[row, :t], b[:t])
    finally:
        tier.close()
