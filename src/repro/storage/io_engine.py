"""StorageTier: serves document embeddings through a device model + software
stack. The GDS-analogue path ("espn") issues batched block reads at high
queue depth directly into accelerator-bound buffers; "mmap"/"swap" model the
conventional O/S paths the paper compares against; "dram" is the all-in-memory
upper bound.

Data movement is real (numpy gather from the disk-image blob, thread-pool
async); the *clock* is the calibrated model in storage/ssd.py. Every read
returns its simulated duration so the pipeline can account overlap exactly
like the paper's prefetch-budget math.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from repro.core.fde import FDETable
from repro.obs.trace import stage
from repro.storage import ssd as ssd_lib
from repro.storage.batch_io import (BatchReadPlan, BatchReadResult,
                                    _exclusive_cumsum, serial_batch)
from repro.storage.cache import PageCache
from repro.storage.faults import (FaultInjector, ReadFaultError,
                                  fault_span_counts, zero_fault_stats)
from repro.storage.layout import (BitTable, EmbeddingLayout, gather_docs,
                                  gather_docs_into)


@dataclass
class ReadResult:
    cls: np.ndarray           # (n, d_cls) fp32
    bow: np.ndarray           # (n, t_max, d_bow) fp32 padded
    lens: np.ndarray          # (n,) int32
    sim_seconds: float        # modeled device+software time
    n_blocks: int


class StorageTier:
    def __init__(self, layout: EmbeddingLayout, *,
                 spec: ssd_lib.StorageSpec = ssd_lib.PM983_PCIE3,
                 stack: str = "espn", mem_budget_bytes: int | None = None,
                 t_max: int = 180, qd: int = 64, include_h2d: bool = True,
                 n_io_threads: int = 4, bits: BitTable | None = None,
                 fde: FDETable | None = None, coalesce: bool = True,
                 io_chunk_docs: int | None = None,
                 faults: FaultInjector | None = None,
                 tracer=None):
        assert stack in ("espn", "mmap", "swap", "dram")
        self.layout = layout
        self.tracer = tracer          # repro.obs.Tracer | None (tracing off)
        if layout.mode == "fixed_stride":
            # every doc holds exactly pool_k tokens: arena rows sized to k,
            # not the ragged t_max padding ceiling
            t_max = min(t_max, layout.pool_k)
        self.bits = bits              # resident sign-bit tier (bitvec filter)
        self.fde = fde                # resident FDE tier (fde candidate gen)
        self._closed = False
        self.spec = spec
        self.stack = stack
        self.t_max = t_max
        self.qd = qd
        self.include_h2d = include_h2d
        self.coalesce = coalesce      # read_batch default: coalesced vs serial
        self.io_chunk_docs = io_chunk_docs   # pipelining granularity (docs/run)
        self.n_io_threads = n_io_threads
        self._pool = ThreadPoolExecutor(max_workers=n_io_threads,
                                        thread_name_prefix="espn-io")
        self._lock = threading.Lock()
        budget = mem_budget_bytes if mem_budget_bytes is not None else 0
        self.page_cache = PageCache(budget, layout.block)
        if stack == "swap":
            self.swap_capacity = (mem_budget_bytes or 0) + 32 * 2**30
        self.stats = {"reads": 0, "docs": 0, "doc_requests": 0, "blocks": 0,
                      "sim_seconds": 0.0, "batch_reads": 0, "io_runs": 0,
                      "dedup_docs": 0}
        self.faults = faults           # FaultInjector | None (None = inert)
        self.degrade_reads = faults.cfg.degrade if faults is not None \
            else True
        if faults is not None:
            self.stats |= zero_fault_stats()
            self._fault_seq = 0

    # -- timing ------------------------------------------------------------
    def _pages_of(self, ids) -> np.ndarray:
        """Pages (device blocks) touched by ``ids``, vectorized: per-doc
        ``range()`` loops replaced by a repeat/cumsum arange construction."""
        offs = self.layout.offsets[np.asarray(ids, np.int64).ravel()]
        starts, counts = offs[:, 0], offs[:, 1]
        total = int(counts.sum())
        if total == 0:
            return np.empty(0, np.int64)
        base = np.repeat(starts - _exclusive_cumsum(counts), counts)
        return base + np.arange(total, dtype=np.int64)

    def _sim_time(self, ids) -> tuple[float, int]:
        n_blocks = self.layout.blocks_for(ids)
        bytes_moved = n_blocks * self.layout.block
        if self.stack == "dram":
            t = ssd_lib.DRAM.read_time(n_blocks, qd=self.qd)
        elif self.stack == "espn":
            t = self.spec.read_time(n_blocks, qd=self.qd)
        else:
            pages = self._pages_of(ids)
            with self._lock:
                h, m = self.page_cache.access_many(pages)
            hr = h / max(1, h + m)
            if self.stack == "mmap":
                t = ssd_lib.mmap_read_time(self.spec, len(pages), hr)
            else:
                if self.layout.nbytes > self.swap_capacity:
                    raise MemoryError("OOM: index exceeds memory + swap space")
                t = ssd_lib.swap_read_time(self.spec, len(pages), hr)
        if self.include_h2d and self.stack != "dram":
            t += ssd_lib.h2d_time(bytes_moved)
        return t, n_blocks

    # -- fault injection -----------------------------------------------------
    def _repair_time(self, n_blocks: int) -> float:
        """One extra device read of a corrupted record (repair bill)."""
        if self.stack == "dram":
            return ssd_lib.DRAM.read_time(n_blocks, qd=self.qd)
        return self.spec.read_time(n_blocks, qd=self.qd)

    def _faulty_read_clock(self, base_s: float, ids) -> tuple[float, int,
                                                              bool, dict]:
        """Run one device read through the fault machine (single device: no
        failover target). Returns ``(sim_s, corrupt_pos, ok, events)`` — the
        clock including retries/stalls/repair, the position in ``ids`` whose
        gathered data must be corrupted (-1 = none: no corruption, or it
        was detected and repaired), whether the read succeeded at all, and
        the event-count dict for this read (empty when nothing fired; the
        tracer renders retries/repairs as child spans from it). Fault
        counters fold into ``self.stats``."""
        fi = self.faults
        with self._lock:
            seq = self._fault_seq
            self._fault_seq += 1
        if not fi.any_event(seq, 0, 0):
            return base_s, -1, True, {}
        ev = zero_fault_stats()
        # a single tier has one "replica"; a flap is an outage for this read
        flapped = fi.flap(seq, 0, 0)
        if flapped:
            ev["replica_flaps"] += 1
            ev["faults_injected"] += 1
            elapsed, ok = 0.0, False
        else:
            elapsed, ok = fi.attempt_loop(seq, 0, 0, base_s, ev)
        corrupt_pos = -1
        if ok and len(ids) and fi.corrupt(seq, 0):
            ev["corruptions_injected"] += 1
            ev["faults_injected"] += 1
            v = fi.victim(seq, 0, len(ids))
            gid = int(np.asarray(ids, np.int64)[v])
            if fi.cfg.checksum \
                    and fi.wire_corruption_detected(self.layout, gid):
                # detected: repair = re-read the record (the on-device image
                # is healthy; the corruption was on the wire). Billed to
                # repair_bytes, never to the query's unique-bytes bill.
                ev["checksum_failures"] += 1
                ev["repairs"] += 1
                nbv = self.layout.blocks_for([gid])
                ev["repair_bytes"] += nbv * self.layout.block
                elapsed += self._repair_time(nbv)
            else:
                corrupt_pos = v    # undetected: corrupt bytes reach scoring
        with self._lock:
            for k, n in ev.items():
                self.stats[k] += n
        return elapsed, corrupt_pos, ok, ev

    # -- reads ---------------------------------------------------------------
    def read(self, ids, t_max: int | None = None) -> ReadResult:
        ids = np.asarray(ids, np.int64)
        t_max = t_max or self.t_max
        sim, n_blocks = self._sim_time(ids)
        corrupt_pos = -1
        if self.faults is not None and self.faults.cfg.enabled():
            sim, corrupt_pos, ok, _ = self._faulty_read_clock(sim, ids)
            if not ok:
                with self._lock:
                    self.stats["sim_seconds"] += sim
                raise ReadFaultError(
                    "storage read failed after exhausting retries")
        cls, bow, lens = gather_docs(self.layout, ids, t_max)
        if corrupt_pos >= 0:
            bow[corrupt_pos] = -bow[corrupt_pos]
        with self._lock:
            self.stats["reads"] += 1
            self.stats["docs"] += len(ids)
            self.stats["doc_requests"] += len(ids)
            self.stats["blocks"] += n_blocks
            self.stats["sim_seconds"] += sim
        return ReadResult(cls, bow, lens, sim, n_blocks)

    def read_async(self, ids, t_max: int | None = None) -> Future:
        return self._pool.submit(self.read, ids, t_max)

    def read_batch(self, per_query_ids, t_max: int | None = None, *,
                   coalesce: bool | None = None,
                   skip_empty: bool = False) -> BatchReadResult:
        """One storage transaction for a whole query batch.

        Coalesced (the default, ``self.coalesce``): doc ids are dedup'd
        across queries, the union is split into block-contiguous runs, runs
        are gathered concurrently on the tier's thread pool into a shared
        arena (call ``ensure_query(b)`` before consuming query ``b``'s rows
        — rerank of earlier queries overlaps the remaining I/O), and the
        clock bills ONE read of the unique blocks at this tier's queue
        depth. Per-query shares (first-owner attribution) sum exactly to
        the batch total.

        ``coalesce=False``: the seed-faithful serial path — one blocking
        ``read`` per query, duplicates billed per requesting query
        (``skip_empty`` skips zero-id queries, matching the prefetcher's
        historical behaviour; the direct backends always billed the empty
        read's h2d floor).
        """
        t_max = t_max or self.t_max
        coalesce = self.coalesce if coalesce is None else coalesce
        tr = self.tracer
        lists = [np.asarray(x, np.int64).ravel() for x in per_query_ids]
        if not coalesce:
            if tr is None:
                return serial_batch(lambda ids: self.read(ids, t_max), lists,
                                    skip_empty)
            sp = tr.begin("read_batch", cat="io", serial=True)
            try:
                res = serial_batch(lambda ids: self.read(ids, t_max), lists,
                                   skip_empty)
            except BaseException:
                tr.end(sp, error=True)
                raise
            tr.end(sp, sim_s=res.sim_seconds)
            res.span = sp
            return res
        with stage("read_submit"):
            return self._submit_coalesced(lists, t_max)

    def _submit_coalesced(self, lists: list, t_max: int) -> BatchReadResult:
        """``read_batch``'s coalesced path: plan, arena, run submission."""
        tr = self.tracer
        t_plan0 = tr.clock() if tr is not None else 0.0
        plan = BatchReadPlan.build(self.layout, lists,
                                   chunk_docs=self.io_chunk_docs)
        if tr is not None:
            plan.span = tr.add("plan", cat="io", t0=t_plan0, t1=tr.clock(),
                               n_unique=plan.n_unique,
                               n_blocks=plan.n_blocks)
        if plan.n_unique == 0:
            return BatchReadResult(coalesced=True, plan=plan,
                                   sim_seconds=0.0, n_blocks=0,
                                   arena=(np.zeros((0, self.layout.d_cls),
                                                   np.float32),
                                          np.zeros((0, t_max,
                                                    self.layout.d_bow),
                                                   np.float32),
                                          np.zeros(0, np.int32)))
        t_rb0 = tr.clock() if tr is not None else 0.0
        sim, n_blocks = self._sim_time(plan.arena_ids)
        corrupt_row = -1
        fault_ev: dict = {}

        def _rb_span(sim_s: float, nb: int, failed: bool = False):
            """Retroactive read_batch span + fault-event child spans."""
            sp = tr.add("read_batch", cat="io", t0=t_rb0, t1=tr.clock(),
                        sim_s=sim_s, n_unique=plan.n_unique, n_blocks=nb,
                        failed=failed)
            for name, count in fault_span_counts(fault_ev):
                tr.add(name, cat="fault", t0=sp.t0, t1=sp.t1, parent=sp,
                       count=count)
            return sp

        if self.faults is not None and self.faults.cfg.enabled():
            sim, corrupt_row, ok, fault_ev = self._faulty_read_clock(
                sim, plan.arena_ids)
            if not ok:
                # the coalesced transaction is one device read: when it
                # exhausts the retry budget every query in the batch is
                # marked failed (a single tier has no failover target)
                with self._lock:
                    self.stats["reads"] += 1
                    self.stats["batch_reads"] += 1
                    self.stats["doc_requests"] += plan.n_requested
                    self.stats["sim_seconds"] += sim
                u = plan.n_unique
                res = BatchReadResult(
                    coalesced=True, plan=plan, sim_seconds=sim, n_blocks=0,
                    arena=(np.zeros((u, self.layout.d_cls), np.float32),
                           np.zeros((u, t_max, self.layout.d_bow),
                                    np.float32),
                           np.zeros(u, np.int32)),
                    failed_queries=np.ones(len(lists), bool))
                if tr is not None:
                    res.span = _rb_span(sim, 0, failed=True)
                return res
        u = plan.n_unique
        arena = (np.zeros((u, self.layout.d_cls), np.float32),
                 np.zeros((u, t_max, self.layout.d_bow), np.float32),
                 np.zeros(u, np.int32))

        def _gather_corrupted(r0: int, r1: int) -> None:
            gather_docs_into(self.layout, plan.arena_ids[r0:r1],
                             arena[0][r0:r1], arena[1][r0:r1],
                             arena[2][r0:r1])
            # undetected wire corruption: the victim's received BOW bytes
            # are garbage — modeled as a sign flip (worst case for MaxSim:
            # the doc's score is driven to the bottom)
            arena[1][corrupt_row] = -arena[1][corrupt_row]

        # the fault-free path submits gather_docs_into itself (callers and
        # tests key on the submitted function's identity)
        futures = [self._pool.submit(_gather_corrupted, r0, r1)
                   if r0 <= corrupt_row < r1 else
                   self._pool.submit(
                       gather_docs_into, self.layout, plan.arena_ids[r0:r1],
                       arena[0][r0:r1], arena[1][r0:r1], arena[2][r0:r1])
                   for r0, r1 in plan.runs]
        with self._lock:
            self.stats["reads"] += 1
            self.stats["batch_reads"] += 1
            self.stats["io_runs"] += len(plan.runs)
            self.stats["docs"] += u
            self.stats["doc_requests"] += plan.n_requested
            self.stats["dedup_docs"] += plan.n_requested - u
            self.stats["blocks"] += n_blocks
            self.stats["sim_seconds"] += sim
        res = BatchReadResult(coalesced=True, plan=plan, sim_seconds=sim,
                              n_blocks=n_blocks, arena=arena,
                              futures=futures)
        if tr is not None:
            res.span = _rb_span(sim, n_blocks)
        return res

    def read_bits(self, ids, t_max: int | None = None):
        """Gather packed sign bits for ``ids`` from the *resident* bit tier:
        no SSD blocks, no simulated device time — the whole point of the
        bitvec filter is that this read is a memory access."""
        if self.bits is None:
            raise RuntimeError(
                "this StorageTier was built without a resident BitTable; "
                "construct it with bits=pack_bits(...)")
        return self.bits.gather(ids, t_max or self.t_max)

    # -- reporting -----------------------------------------------------------
    def memory_resident_bytes(self) -> int:
        """Host/device memory this tier requires (ESPN: offsets only;
        fixed-stride layouts compute offsets, so their metadata is free)."""
        meta = self.layout.meta_nbytes
        if self.bits is not None:
            meta += self.bits.nbytes
        if self.fde is not None:
            meta += self.fde.nbytes
        if self.stack == "dram":
            return self.layout.nbytes + meta
        if self.stack in ("mmap", "swap"):
            return self.page_cache.capacity_pages * self.layout.block + meta
        return meta

    def metrics_sources(self) -> list:
        """``(prefix, snapshot_fn)`` pairs for a ``MetricsRegistry``:
        everything in ``self.stats`` (including the fault-layer counters
        when an injector is attached) plus the resident-bytes gauge.
        Snapshots run at expose() time only — zero hot-path cost."""
        def snap():
            with self._lock:
                s = dict(self.stats)
            s["memory_resident_bytes"] = self.memory_resident_bytes()
            return s
        return [("storage_tier", snap)]

    def close(self):
        """Idempotent shutdown: pending ``read_async`` futures are cancelled
        rather than abandoned (callers holding one see CancelledError instead
        of a hang); in-flight reads finish. Safe to call more than once —
        ``Pipeline.with_mode`` documents "close both", so stacked pipelines
        routinely double-close shared-ancestry tiers."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._pool.shutdown(wait=False, cancel_futures=True)
