"""Storage tier: layout roundtrip, timing-model properties, cache emergence."""
import numpy as np
import pytest

from repro.storage import ssd as S
from repro.storage.cache import PageCache
from repro.storage.io_engine import StorageTier
from repro.storage.layout import gather_docs, pack, unpack_doc


def _mini_layout(n=50, d_cls=16, d_bow=8, seed=0, dtype=np.float16):
    rng = np.random.default_rng(seed)
    cls = rng.standard_normal((n, d_cls)).astype(np.float32)
    bow = [rng.standard_normal((int(t), d_bow)).astype(np.float32)
           for t in rng.integers(4, 40, n)]
    return cls, bow, pack(cls, bow, dtype=dtype)


def test_pack_unpack_roundtrip():
    cls, bow, layout = _mini_layout()
    for i in (0, 7, 49):
        c, b = unpack_doc(layout, i)
        np.testing.assert_allclose(c, cls[i], atol=2e-3)
        np.testing.assert_allclose(b, bow[i], atol=2e-3)


def test_cls_bow_colocated_single_block():
    """Small docs cost exactly ONE block (paper §4.1)."""
    _, _, layout = _mini_layout()
    small = [i for i in range(50) if layout.doc_bytes(i) <= 4096]
    assert small
    for i in small:
        assert layout.offsets[i, 1] == 1


def test_gather_docs_padding():
    cls, bow, layout = _mini_layout()
    ids = [3, 1, 4]
    c, b, lens = gather_docs(layout, ids, t_max=16)
    assert b.shape == (3, 16, 8)
    for j, i in enumerate(ids):
        t = min(16, bow[i].shape[0])
        assert lens[j] == t
        np.testing.assert_allclose(b[j, :t], bow[i][:t], atol=2e-3)
        if t < 16:
            assert np.abs(b[j, t:]).max() == 0


def test_ssd_timing_monotone():
    for spec in (S.PM983_PCIE3, S.PM9A3_PCIE4, S.DRAM):
        ts = [spec.read_time(n) for n in (1, 10, 100, 1000, 10000)]
        assert all(b >= a for a, b in zip(ts, ts[1:]))
    # DRAM must beat SSD by a lot at every size
    assert S.DRAM.read_time(1000) < S.PM983_PCIE3.read_time(1000) / 3


def test_gds_vs_dram_ratio_calibration():
    """Paper Fig 8: GDS ~7.2x DRAM access latency for ~1000-doc reads."""
    n_blocks = 1000
    gds = S.PM983_PCIE3.read_time(n_blocks) + S.h2d_time(n_blocks * 4096)
    dram = S.DRAM.read_time(n_blocks)
    assert 4.0 < gds / dram < 12.0


def test_mmap_slower_than_batched_and_budget_sensitive():
    cls, bow, layout = _mini_layout(n=400)
    tight = StorageTier(layout, stack="mmap",
                        mem_budget_bytes=layout.nbytes // 10)
    roomy = StorageTier(layout, stack="mmap",
                        mem_budget_bytes=layout.nbytes * 2)
    ids = np.arange(300)
    t_tight = tight.read(ids).sim_seconds
    _ = roomy.read(ids)               # warm the cache
    t_roomy = roomy.read(ids).sim_seconds
    assert t_roomy < t_tight          # page cache emergence
    espn = StorageTier(layout, stack="espn")
    assert espn.read(ids).sim_seconds < t_tight


def test_swap_oom_when_exceeding_capacity():
    cls, bow, layout = _mini_layout(n=100)
    tier = StorageTier(layout, stack="swap", mem_budget_bytes=1024)
    tier.swap_capacity = layout.nbytes // 2
    with pytest.raises(MemoryError):
        tier.read(np.arange(10))


def test_espn_resident_memory_is_metadata_only():
    cls, bow, layout = _mini_layout(n=200)
    espn = StorageTier(layout, stack="espn")
    dram = StorageTier(layout, stack="dram", mem_budget_bytes=layout.nbytes)
    assert espn.memory_resident_bytes() < dram.memory_resident_bytes() / 10


def test_page_cache_lru():
    pc = PageCache(capacity_bytes=3 * 4096)
    for p in (1, 2, 3):
        assert not pc.access(p)
    assert pc.access(1)               # hit, moves to MRU
    assert not pc.access(4)           # evicts 2
    assert not pc.access(2)           # miss (was evicted)
    assert pc.access(4)


def test_async_read_matches_sync():
    cls, bow, layout = _mini_layout()
    tier = StorageTier(layout, stack="espn", t_max=32)
    ids = [1, 5, 9]
    sync = tier.read(ids)
    fut = tier.read_async(ids)
    async_r = fut.result(timeout=10)
    np.testing.assert_array_equal(sync.bow, async_r.bow)
    tier.close()


def test_close_is_idempotent():
    """with_mode docs say "close both" — stacked pipelines double-close
    shared-ancestry tiers, so close() must be safe to repeat."""
    _, _, layout = _mini_layout()
    tier = StorageTier(layout, stack="espn", t_max=32)
    tier.read([0, 1])
    tier.close()
    tier.close()                      # second close must not raise


def test_close_cancels_pending_async_reads():
    """A queued read_async future must resolve (cancelled), not hang forever
    after close()."""
    import threading
    from concurrent.futures import CancelledError

    _, _, layout = _mini_layout()
    tier = StorageTier(layout, stack="espn", t_max=32, n_io_threads=1)
    started = threading.Event()
    release = threading.Event()
    real_read = tier.read

    def slow_read(ids, t_max=None):
        started.set()
        release.wait(timeout=10)
        return real_read(ids, t_max)

    tier.read = slow_read
    running = tier.read_async([0])
    assert started.wait(timeout=10)   # worker busy -> next future queues
    pending = tier.read_async([1])
    tier.close()
    release.set()
    with pytest.raises(CancelledError):
        pending.result(timeout=10)
    assert running.result(timeout=10) is not None   # in-flight read finishes


# -- bulk ragged gather: bit-for-bit against a per-passage unpack_doc loop --

def _gather_layout(dtype=np.float16, d_cls=16, d_bow=8, n=40, seed=5,
                   zero_doc=None, scaled=False):
    rng = np.random.default_rng(seed)
    cls = rng.standard_normal((n, d_cls)).astype(np.float32)
    bow = [rng.standard_normal((int(t), d_bow)).astype(np.float32)
           for t in rng.integers(1, 60, n)]
    if zero_doc is not None:
        bow[zero_doc] = np.zeros((0, d_bow), np.float32)
    scales = None
    if scaled:
        scales = np.array([max(np.abs(c).max(), np.abs(b).max(initial=0.0))
                           / 127.0 for c, b in zip(cls, bow)], np.float32)
    return pack(cls, bow, dtype=dtype, scales=scales)


def _unpack_reference(layout, ids, rows, out_cls, out_bow, out_lens):
    for i, r in zip(ids, rows):
        c, b = unpack_doc(layout, int(i))
        t = min(b.shape[0], out_bow.shape[1])
        out_bow[r, :t] = b[:t]
        out_cls[r] = c
        out_lens[r] = t


def _arena(layout, n_rows, t_max, fill):
    return (np.full((n_rows, layout.d_cls), fill, np.float32),
            np.full((n_rows, t_max, layout.d_bow), fill, np.float32),
            np.full(n_rows, -1, np.int32))


def _bits(a):
    return a.view(np.uint32) if a.dtype == np.float32 else a


# (layout kwargs, t_max, ids, rows: "contiguous" | "shuffled", arena fill)
_GATHER_CASES = {
    "float16": (dict(), 64, [3, 1, 4, 15, 9], "contiguous", 0.0),
    "float32": (dict(dtype=np.float32), 64, [0, 7, 39, 2], "contiguous",
                0.0),
    "int8_scales": (dict(dtype=np.int8, scaled=True), 64,
                    list(range(0, 40, 3)), "contiguous", 0.0),
    "truncated_past_t_max": (dict(), 5, list(range(12)), "contiguous", 0.0),
    "zero_token_passage": (dict(zero_doc=6), 64, [5, 6, 7], "contiguous",
                           0.0),
    "empty_ids": (dict(), 64, [], "contiguous", 0.0),
    "shuffled_rows": (dict(), 64, [30, 2, 17, 8, 25, 11], "shuffled", 0.0),
    "element_view_d_cls_not_multiple": (dict(d_cls=15), 64,
                                        [1, 38, 20, 4], "contiguous", 0.0),
    "sentinel_kept_past_length": (dict(zero_doc=2), 48, list(range(10)),
                                  "shuffled", 7.25),
}


@pytest.mark.parametrize("case", list(_GATHER_CASES))
def test_bulk_gather_bit_identical_to_unpack_doc(case):
    """The ragged gather reads every passage in one bulk pass; the arena it
    fills must equal, bit for bit, a per-passage ``unpack_doc`` loop —
    across dtypes and scales, truncation, zero-token passages, empty input,
    interleaved cluster-style rows, widths that force an item-level view,
    and a pre-filled arena whose padding must survive."""
    from repro.storage.layout import gather_docs_at
    kw, t_max, ids, rows_kind, fill = _GATHER_CASES[case]
    layout = _gather_layout(**kw)
    ids = np.asarray(ids, np.int64)
    n_rows = 2 * len(ids) + 3
    rows = np.arange(len(ids))
    if rows_kind == "shuffled":   # interleaved, out of order, with gaps
        rows = np.random.default_rng(1).permutation(n_rows)[:len(ids)]
    want = _arena(layout, n_rows, t_max, fill)
    _unpack_reference(layout, ids, rows, *want)
    got = _arena(layout, n_rows, t_max, fill)
    gather_docs_at(layout, ids, rows, *got)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(_bits(g), _bits(w))
    for r, i in zip(rows, ids):   # padding past each length is untouched
        t = min(int(layout.n_tokens[i]), t_max)
        assert int(got[2][r]) == t
        assert (got[1][r, t:] == fill).all()
    untouched = np.setdiff1d(np.arange(n_rows), rows)
    assert (got[2][untouched] == -1).all()
    assert (got[1][untouched] == fill).all()


def test_bulk_gather_never_unpacks_per_passage(monkeypatch):
    """A 500-passage run is gathered with ``unpack_doc`` made to raise, so a
    per-passage loop cannot come back unnoticed."""
    import repro.storage.layout as L
    layout = _gather_layout(n=600, seed=11)
    ids = np.arange(50, 550)
    want = _arena(layout, len(ids), 48, 0.0)
    _unpack_reference(layout, ids, np.arange(len(ids)), *want)

    def no_unpack(*a, **k):
        raise AssertionError("gather fell back to a per-passage unpack_doc")

    monkeypatch.setattr(L, "unpack_doc", no_unpack)
    got = _arena(layout, len(ids), 48, 0.0)
    L.gather_docs_into(layout, ids, *got)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(_bits(g), _bits(w))
