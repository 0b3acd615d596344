"""Embedding binary layout: CLS + BOW co-located, block-aligned.

Reproduces ESPN §4.1: the CLS vector and the BOW token matrix of a document
are packed together and aligned so a typical compressed document costs ONE
I/O block instead of two. The "disk image" is a single uint8 numpy array;
an offsets table (kept in host memory, as in the paper) maps doc id ->
(start_block, n_blocks, n_tokens).

Two layout **modes** share the accessor API:

- ``ragged`` (the paper's layout): per-doc ``n_tokens``, variable
  ``n_blocks``, offsets stored in host memory.
- ``fixed_stride`` (constant-space, MacAvaney et al. 2025): every doc holds
  exactly ``pool_k`` pooled tokens (see ``repro.core.pool``), so every row
  spans the same ``stride_blocks`` blocks and ``offsets``/``n_tokens`` are
  *computable*, not stored — ``meta_nbytes`` is zero, and the gather paths
  take a bulk strided ``blob.reshape(...)`` fast path with no per-doc
  Python loop. The persistence layer skips the tables entirely
  (``repro.pipeline.persist``); in-process they are materialized once in
  ``__post_init__`` so every existing consumer of ``layout.offsets`` keeps
  working unchanged.

``BitTable`` is the second, *resident* tier (Nardini et al. 2024): every
document token sign-binarized and bit-packed, ~1/16th the fp16 BOW bytes, so
the bitvec backend can filter candidates in memory and hit the SSD only for
the survivors.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from repro.core.quantize import binary_pack, to_uint32_lanes
from repro.obs.trace import stage
from repro.storage.ssd import DEFAULT_BLOCK

LAYOUT_MODES = ("ragged", "fixed_stride")


@dataclass
class EmbeddingLayout:
    blob: np.ndarray              # uint8 disk image (block-aligned)
    offsets: np.ndarray | None    # (N, 2) int64: start_block, n_blocks
    n_tokens: np.ndarray | None   # (N,) int32
    d_cls: int
    d_bow: int
    dtype: np.dtype               # stored element dtype (e.g. float16/int8)
    scales: np.ndarray | None     # (N,) fp32 dequant scales (int8/int4 modes)
    block: int = DEFAULT_BLOCK
    mode: str = "ragged"          # "ragged" | "fixed_stride"
    stride_blocks: int = 0        # fixed mode: blocks per doc (uniform)
    pool_k: int = 0               # fixed mode: tokens per doc (uniform)
    checksums: np.ndarray | None = field(default=None, repr=False)
                                  # (N,) uint32 per-record crc32 (integrity
                                  # tier; None = packed without checksums)

    def __post_init__(self):
        if self.mode not in LAYOUT_MODES:
            raise ValueError(f"unknown layout mode {self.mode!r}; "
                             f"expected one of {LAYOUT_MODES}")
        if self.mode == "fixed_stride":
            if self.stride_blocks <= 0 or self.pool_k <= 0:
                raise ValueError("fixed_stride layout requires positive "
                                 "stride_blocks and pool_k")
            n = self.blob.nbytes // (self.stride_blocks * self.block)
            # offsets/n_tokens are pure arithmetic in fixed mode; they are
            # materialized here (not persisted — meta_nbytes stays 0) so the
            # ragged accessor API works on both modes unchanged
            if self.offsets is None:
                starts = np.arange(n, dtype=np.int64) * self.stride_blocks
                self.offsets = np.stack(
                    [starts, np.full(n, self.stride_blocks, np.int64)],
                    axis=1)
            if self.n_tokens is None:
                self.n_tokens = np.full(n, self.pool_k, np.int32)
        elif self.offsets is None or self.n_tokens is None:
            raise ValueError("ragged layout requires stored offsets "
                             "and n_tokens")

    @property
    def n_docs(self) -> int:
        return len(self.offsets)

    @property
    def nbytes(self) -> int:
        return self.blob.nbytes

    @property
    def meta_nbytes(self) -> int:
        """Host-resident metadata bytes. Zero in fixed-stride mode: offsets
        and token counts are computable, so nothing rides in memory."""
        if self.mode == "fixed_stride":
            return 0
        return self.offsets.nbytes + self.n_tokens.nbytes

    def doc_bytes(self, i: int) -> int:
        elt = np.dtype(self.dtype).itemsize
        return (self.d_cls + int(self.n_tokens[i]) * self.d_bow) * elt

    def blocks_for(self, ids) -> int:
        """Total blocks touched by a set of doc ids (the IO bill)."""
        ids = np.asarray(ids, np.int64)
        if self.mode == "fixed_stride":
            return len(ids) * self.stride_blocks
        return int(self.offsets[ids, 1].sum())


def pack(cls_embs: np.ndarray, bow_embs: list[np.ndarray], *,
         dtype=np.float16, scales: np.ndarray | None = None,
         block: int = DEFAULT_BLOCK, mode: str = "ragged",
         pool_k: int = 0, d_bow: int | None = None,
         checksum: bool = False) -> EmbeddingLayout:
    """Build the block-aligned disk image.

    cls_embs: (N, d_cls) fp32; bow_embs: list of (t_i, d_bow) fp32 arrays.
    Stored as ``dtype`` (fp16 default, int8 with per-doc scale supported).

    ``mode="fixed_stride"`` requires every doc to hold exactly ``pool_k``
    tokens (pool first — ``repro.core.pool``); the resulting layout stores
    no per-doc offset/token tables. An empty corpus packs to a valid empty
    layout (``d_bow`` may be passed explicitly when it cannot be inferred
    from a zero-doc ``bow_embs``).

    ``checksum=True`` attaches per-record crc32 checksums (the integrity
    tier — ``repro.storage.faults``); record bytes are unchanged, so a
    checksummed layout ranks and bills identically to a plain one.
    """
    n = len(bow_embs)
    cls_embs = np.asarray(cls_embs)
    d_cls = cls_embs.shape[1] if cls_embs.ndim == 2 else 0
    if n:
        d_bow = bow_embs[0].shape[1]
    elif d_bow is None:
        d_bow = 0
    elt = np.dtype(dtype).itemsize
    n_tokens = np.array([b.shape[0] for b in bow_embs], np.int32)
    if mode == "fixed_stride":
        if pool_k <= 0:
            raise ValueError("fixed_stride pack requires pool_k > 0")
        if n and not (n_tokens == pool_k).all():
            raise ValueError("fixed_stride pack requires every doc to hold "
                             f"exactly pool_k={pool_k} tokens; "
                             "pool the corpus first (repro.core.pool)")
        stride = (d_cls + pool_k * d_bow) * elt
        stride_blocks = max(1, -(-stride // block))
        n_blocks = np.full(n, stride_blocks, np.int64)
    else:
        sizes = (d_cls + n_tokens.astype(np.int64) * d_bow) * elt
        n_blocks = (sizes + block - 1) // block
    starts = np.zeros(n, np.int64)
    np.cumsum(n_blocks[:-1], out=starts[1:])
    blob = np.zeros(int(n_blocks.sum()) * block, np.uint8)
    if n and (n_tokens == n_tokens[0]).all():
        # uniform token count (always true in fixed mode): one bulk write —
        # bit-identical to the per-doc loop, which writes the same record
        # bytes at the same block starts
        recs = np.concatenate(
            [cls_embs, np.stack(bow_embs).reshape(n, -1)], axis=1)
        if scales is not None:
            recs = recs / scales[:, None]
        raw = np.ascontiguousarray(recs.astype(dtype)).view(np.uint8)
        rb = raw.shape[1]
        view = blob.reshape(n, int(n_blocks[0]) * block)
        view[:, :rb] = raw
    else:
        for i in range(n):
            rec = np.concatenate([cls_embs[i].ravel(), bow_embs[i].ravel()])
            if scales is not None:
                rec = rec / scales[i]
            rec = rec.astype(dtype)
            raw = rec.view(np.uint8)
            s = starts[i] * block
            blob[s:s + raw.nbytes] = raw
    if mode == "fixed_stride":
        out = EmbeddingLayout(blob=blob, offsets=None, n_tokens=None,
                              d_cls=d_cls, d_bow=d_bow,
                              dtype=np.dtype(dtype), scales=scales,
                              block=block, mode=mode,
                              stride_blocks=int(stride_blocks),
                              pool_k=pool_k)
    else:
        offsets = np.zeros((n, 2), np.int64)
        offsets[:, 0] = starts
        offsets[:, 1] = n_blocks
        out = EmbeddingLayout(blob=blob, offsets=offsets, n_tokens=n_tokens,
                              d_cls=d_cls, d_bow=d_bow,
                              dtype=np.dtype(dtype), scales=scales,
                              block=block)
    if checksum:
        from repro.storage.faults import add_checksums
        add_checksums(out)
    return out


def unpack_doc(layout: EmbeddingLayout, i: int):
    """Read one doc back: returns (cls (d_cls,), bow (t_i, d_bow)) fp32."""
    start, nb = layout.offsets[i]
    t = int(layout.n_tokens[i])
    elt = layout.dtype.itemsize
    raw = layout.blob[start * layout.block:
                      start * layout.block + (layout.d_cls + t * layout.d_bow) * elt]
    vals = raw.view(layout.dtype).astype(np.float32)
    if layout.scales is not None:
        vals = vals * layout.scales[i]
    return vals[:layout.d_cls], vals[layout.d_cls:].reshape(t, layout.d_bow)


@dataclass
class BitTable:
    """Resident sign-bit table over all document tokens.

    ``packed`` concatenates every doc's (t_i, W) bit-packed token matrix
    along axis 0; ``starts`` is the (N+1,) token-offset prefix sum. Lane
    dtype is a storage knob (``StorageConfig.bit_dtype``): uint8 wastes no
    pad bytes when d_bow % 32 != 0, uint32 is the bitsim kernel's native
    width. ``gather`` always hands back uint32 lanes (bit-exact re-view).
    """
    packed: np.ndarray            # (total_tokens, W) unsigned int lanes
    starts: np.ndarray            # (N + 1,) int64 token offsets
    d_bow: int
    _lanes32: np.ndarray | None = field(default=None, repr=False,
                                        compare=False)

    @property
    def n_docs(self) -> int:
        return len(self.starts) - 1

    @property
    def nbytes(self) -> int:
        return self.packed.nbytes + self.starts.nbytes

    def doc(self, i: int) -> np.ndarray:
        return self.packed[self.starts[i]:self.starts[i + 1]]

    @property
    def lanes32(self) -> np.ndarray:
        """Kernel-native uint32 view of the whole table, converted once (a
        no-copy re-view when the pack dtype is already uint32) — gather is
        the per-query hot path of the bitvec filter."""
        if self._lanes32 is None:
            self._lanes32 = to_uint32_lanes(self.packed)
        return self._lanes32

    def append(self, bow_embs: list[np.ndarray]) -> None:
        """Extend the table with newly ingested docs' tokens, in doc-id
        order. Bit-packing concatenates per doc, so this is bit-identical
        to re-packing the grown corpus from scratch; the cached uint32
        re-view is invalidated."""
        if not bow_embs:
            return
        add = pack_bits(list(bow_embs), dtype=str(self.packed.dtype))
        self.packed = np.concatenate([self.packed, add.packed], axis=0)
        self.starts = np.concatenate(
            [self.starts, add.starts[1:] + self.starts[-1]])
        self._lanes32 = None

    def gather(self, ids, t_max: int):
        """Padded uint32-lane gather: (len(ids), t_max, W32) + lengths.

        One bulk fancy-index over the lane table via the ``starts`` prefix
        sums — no per-doc Python loop (this is the bitvec filter's
        per-query hot path)."""
        ids = np.asarray(ids, np.int64)
        lanes = self.lanes32
        m = len(ids)
        out = np.zeros((m, t_max, lanes.shape[-1]), np.uint32)
        lens = np.zeros(m, np.int32)
        if m == 0:
            return out, lens
        s = self.starts[ids]
        t = np.minimum(self.starts[ids + 1] - s, t_max)
        off = np.zeros(m, np.int64)
        np.cumsum(t[:-1], out=off[1:])
        tot = int(t.sum())
        if tot:
            flat = np.arange(tot, dtype=np.int64)
            rows = np.repeat(np.arange(m, dtype=np.int64), t)
            pos = flat - np.repeat(off, t)
            src = np.repeat(s - off, t) + flat
            out[rows, pos] = lanes[src]
        lens[:] = t.astype(np.int32)
        return out, lens


def pack_bits(bow_embs: list[np.ndarray], *, dtype: str = "uint32",
              d_bow: int = 0) -> BitTable:
    """Sign-binarize and bit-pack a ragged BOW list into one resident table.

    An empty list packs to a valid empty table; pass ``d_bow`` so the lane
    width matches the layout it mirrors (keeps ``append`` concatenation and
    ``bits_from_layout`` on an empty layout consistent)."""
    n_tokens = np.array([b.shape[0] for b in bow_embs], np.int64)
    starts = np.zeros(len(bow_embs) + 1, np.int64)
    np.cumsum(n_tokens, out=starts[1:])
    flat = np.concatenate([b for b in bow_embs], axis=0) if bow_embs else \
        np.zeros((0, d_bow), np.float32)
    return BitTable(packed=binary_pack(flat, dtype=dtype), starts=starts,
                    d_bow=flat.shape[-1])


def bits_from_layout(layout: EmbeddingLayout, *,
                     dtype: str = "uint32") -> BitTable:
    """Build the resident bit table from an already-packed disk layout (the
    save/load and from_artifacts paths, where the fp32 BOW list is gone).
    Signs survive fp16/int8 storage quantization, so this is equivalent to
    packing the original embeddings.

    Vectorized: every doc's BOW bytes occupy one contiguous blob range, so
    the whole table is one bulk byte gather driven by the offset prefix
    sums (bit-identical to the per-doc unpack loop)."""
    n = layout.n_docs
    if n == 0:
        return pack_bits([], dtype=dtype, d_bow=layout.d_bow)
    elt = layout.dtype.itemsize
    nt = layout.n_tokens.astype(np.int64)
    byte_counts = nt * (layout.d_bow * elt)
    bow_starts = layout.offsets[:, 0] * layout.block + layout.d_cls * elt
    off = np.zeros(n, np.int64)
    np.cumsum(byte_counts[:-1], out=off[1:])
    tot = int(byte_counts.sum())
    src = np.repeat(bow_starts - off, byte_counts) + np.arange(tot)
    vals = layout.blob[src].view(layout.dtype).astype(np.float32)
    if layout.scales is not None:
        vals = vals * np.repeat(layout.scales, nt * layout.d_bow)
    flat = vals.reshape(-1, layout.d_bow)
    starts = np.zeros(n + 1, np.int64)
    np.cumsum(nt, out=starts[1:])
    return BitTable(packed=binary_pack(flat, dtype=dtype), starts=starts,
                    d_bow=layout.d_bow)


def _gather_fixed_at(layout: EmbeddingLayout, ids: np.ndarray,
                     rows: np.ndarray, out_cls: np.ndarray,
                     out_bow: np.ndarray, out_lens: np.ndarray) -> None:
    """Fixed-stride bulk gather: one strided fancy-index over the blob —
    no per-doc loop. Bit-identical to the ragged unpack path (same record
    bytes, same fp32 conversion, same scale multiply)."""
    k = layout.pool_k
    t = min(k, out_bow.shape[1])
    elt = layout.dtype.itemsize
    stride_bytes = layout.stride_blocks * layout.block
    rec_bytes = (layout.d_cls + k * layout.d_bow) * elt
    raw = layout.blob.reshape(-1, stride_bytes)[ids, :rec_bytes]
    vals = raw.view(layout.dtype).astype(np.float32)
    if layout.scales is not None:
        vals = vals * layout.scales[ids, None]
    out_cls[rows] = vals[:, :layout.d_cls]
    out_bow[rows, :t] = vals[:, layout.d_cls:layout.d_cls + t * layout.d_bow] \
        .reshape(len(ids), t, layout.d_bow)
    out_lens[rows] = t


def gather_docs_at(layout: EmbeddingLayout, ids, rows, out_cls: np.ndarray,
                   out_bow: np.ndarray, out_lens: np.ndarray) -> None:
    """Gather ``ids`` into arbitrary (non-contiguous) buffer rows.

    The storage cluster's per-shard runs land in interleaved slots of the
    batch's shared arena (the arena is global-block-sorted while a shard owns
    a strided subset of it), so the contiguous-slice contract of
    ``gather_docs_into`` does not apply.

    Ragged layouts take one bulk gather, a fixed number of numpy calls
    whatever ``len(ids)``: every CLS vector and every kept token vector is
    read by one fancy index each over the blob cut into units of ``ub``
    bytes, converted to fp32 once, scaled once and scattered once. The
    arena is bit-identical to ``unpack_doc`` per passage (same record
    bytes, same conversion, then the same scale multiply); rows past a
    passage's length are left untouched.
    """
    ids = np.asarray(ids, np.int64)
    rows = np.asarray(rows, np.int64)
    if not len(ids):
        return
    if layout.mode == "fixed_stride":
        _gather_fixed_at(layout, ids, rows, out_cls, out_bow, out_lens)
        return
    elt = layout.dtype.itemsize
    cls_b, tok_b = layout.d_cls * elt, layout.d_bow * elt
    # the widest unit of bytes every record, CLS vector and token vector
    # starts and ends on, taken from the layout's widths: one whole token
    # vector where the block and the CLS vector are multiples of it (4 KiB
    # blocks, 128-d CLS, 32-d fp16 tokens), else a few items, one item, or
    # one byte for a block that is no multiple of the item size
    ub = math.gcd(layout.block, cls_b, tok_b)
    units = layout.blob.reshape(-1, ub)
    cls_u, tok_u = cls_b // ub, tok_b // ub
    start = layout.offsets[ids, 0] * (layout.block // ub)
    t = np.minimum(layout.n_tokens[ids], out_bow.shape[1]).astype(np.int64)
    owner = np.repeat(np.arange(len(ids)), t)
    pos = np.arange(len(owner)) - np.repeat(np.cumsum(t) - t, t)
    tok0 = start[owner] + cls_u + pos * tok_u
    cls = units[start[:, None] + np.arange(cls_u)] \
        .reshape(len(ids), cls_b).view(layout.dtype).astype(np.float32)
    bow = units[tok0[:, None] + np.arange(tok_u)] \
        .reshape(len(owner), tok_b).view(layout.dtype).astype(np.float32)
    if layout.scales is not None:
        s = layout.scales[ids]
        cls = cls * s[:, None]
        bow = bow * s[owner][:, None]
    out_bow[rows[owner], pos] = bow
    out_cls[rows] = cls
    out_lens[rows] = t


def gather_docs_into(layout: EmbeddingLayout, ids, out_cls: np.ndarray,
                     out_bow: np.ndarray, out_lens: np.ndarray) -> None:
    """Gather ``ids`` into caller-owned buffer slices (rows ``0..len(ids)``).

    The batch I/O engine preallocates one shared arena for a whole query
    batch and hands each block-contiguous run a disjoint slice, so runs can
    gather concurrently on the tier's thread pool with no further copies.
    """
    with stage("read_run"):
        ids = np.asarray(ids, np.int64)
        gather_docs_at(layout, ids, np.arange(len(ids)), out_cls, out_bow,
                       out_lens)


def gather_docs(layout: EmbeddingLayout, ids, t_max: int):
    """Host-side ragged gather -> padded (len(ids), t_max, d_bow) + lengths.

    This is the numpy fallback for the ``gather_pack`` Pallas kernel (the
    paper's CUDA restructuring-kernel analogue).
    """
    ids = np.asarray(ids, np.int64)
    out = np.zeros((len(ids), t_max, layout.d_bow), np.float32)
    cls = np.zeros((len(ids), layout.d_cls), np.float32)
    lens = np.zeros(len(ids), np.int32)
    gather_docs_into(layout, ids, cls, out, lens)
    return cls, out, lens
