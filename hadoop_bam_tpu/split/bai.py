"""BAI genomic index: build, read, and query for interval split trimming.

The reference's interval support (hb/BAMInputFormat.java, upstream 7.7+)
trims InputSplits with the BAM's `.bai` sidecar so only file regions that
can contain overlapping records are read; records are then filtered
exactly in the reader.  This module is both halves without htsjdk: a BAI
builder (we have no external indexer in this environment) and a reader +
query that turns intervals into merged virtual-offset ranges.

Format [SPEC SAMv1 section 5.2]: magic "BAI\\1"; per reference a binning
index (R-tree bins over 16 KiB..512 Mbp regions, each bin holding chunks
of (begin, end) virtual offsets) plus a linear index of the smallest
virtual offset overlapping each 16 KiB window.  Bin numbering follows the
standard reg2bin/reg2bins arithmetic reproduced here.
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

BAI_MAGIC = b"BAI\x01"
BAI_SUFFIX = ".bai"
_LINEAR_SHIFT = 14          # 16 KiB windows
_METADATA_BIN = 37450       # pseudo-bin some writers emit; skipped on read


def reg2bin(beg: int, end: int) -> int:
    """Bin for a 0-based half-open region [SPEC section 5.3 C code]."""
    end -= 1
    if beg >> 14 == end >> 14:
        return ((1 << 15) - 1) // 7 + (beg >> 14)
    if beg >> 17 == end >> 17:
        return ((1 << 12) - 1) // 7 + (beg >> 17)
    if beg >> 20 == end >> 20:
        return ((1 << 9) - 1) // 7 + (beg >> 20)
    if beg >> 23 == end >> 23:
        return ((1 << 6) - 1) // 7 + (beg >> 23)
    if beg >> 26 == end >> 26:
        return ((1 << 3) - 1) // 7 + (beg >> 26)
    return 0


def reg2bins(beg: int, end: int) -> List[int]:
    """All bins that may hold records overlapping [beg, end) [SPEC]."""
    end -= 1
    out = [0]
    for shift, off in ((26, 1), (23, 9), (20, 73), (17, 585), (14, 4681)):
        out.extend(range(off + (beg >> shift), off + (end >> shift) + 1))
    return out


@dataclass
class RefIndex:
    bins: Dict[int, List[Tuple[int, int]]] = field(default_factory=dict)
    linear: List[int] = field(default_factory=list)  # voffsets, 0 = unset


@dataclass
class BaiIndex:
    refs: List[RefIndex]

    # -- serialization ------------------------------------------------------
    def to_bytes(self) -> bytes:
        out = [BAI_MAGIC, struct.pack("<i", len(self.refs))]
        for ref in self.refs:
            out.append(struct.pack("<i", len(ref.bins)))
            for bin_no in sorted(ref.bins):
                chunks = ref.bins[bin_no]
                out.append(struct.pack("<Ii", bin_no, len(chunks)))
                for beg, end in chunks:
                    out.append(struct.pack("<QQ", beg, end))
            out.append(struct.pack("<i", len(ref.linear)))
            for v in ref.linear:
                out.append(struct.pack("<Q", v))
        return b"".join(out)

    @classmethod
    def from_bytes(cls, raw: bytes) -> "BaiIndex":
        if raw[:4] != BAI_MAGIC:
            raise ValueError("not a BAI index (bad magic)")
        off = 4
        (n_ref,) = struct.unpack_from("<i", raw, off)
        off += 4
        refs: List[RefIndex] = []
        for _ in range(n_ref):
            (n_bin,) = struct.unpack_from("<i", raw, off)
            off += 4
            bins: Dict[int, List[Tuple[int, int]]] = {}
            for _ in range(n_bin):
                bin_no, n_chunk = struct.unpack_from("<Ii", raw, off)
                off += 8
                chunks = []
                for _ in range(n_chunk):
                    beg, end = struct.unpack_from("<QQ", raw, off)
                    off += 16
                    chunks.append((beg, end))
                if bin_no != _METADATA_BIN:
                    bins[bin_no] = chunks
            (n_intv,) = struct.unpack_from("<i", raw, off)
            off += 4
            linear = list(struct.unpack_from(f"<{n_intv}Q", raw, off))
            off += 8 * n_intv
            refs.append(RefIndex(bins=bins, linear=linear))
        return cls(refs=refs)

    # -- query --------------------------------------------------------------
    def query(self, rid: int, beg: int, end: int) -> List[Tuple[int, int]]:
        """Merged (start, end) virtual-offset ranges that can contain
        records overlapping the 0-based half-open region [beg, end)."""
        if rid < 0 or rid >= len(self.refs):
            return []
        ref = self.refs[rid]
        win = beg >> _LINEAR_SHIFT
        min_off = ref.linear[win] if win < len(ref.linear) else 0
        chunks: List[Tuple[int, int]] = []
        for bin_no in reg2bins(beg, end):
            for cbeg, cend in ref.bins.get(bin_no, ()):
                if cend > min_off:
                    chunks.append((max(cbeg, min_off), cend))
        chunks.sort()
        merged: List[Tuple[int, int]] = []
        for cbeg, cend in chunks:
            if merged and cbeg <= merged[-1][1]:
                if cend > merged[-1][1]:
                    merged[-1] = (merged[-1][0], cend)
            else:
                merged.append((cbeg, cend))
        return merged


CSI_MAGIC = b"CSI\x01"
CSI_SUFFIX = ".csi"


def csi_reg2bins(beg: int, end: int, min_shift: int, depth: int
                 ) -> List[int]:
    """Bins possibly overlapping [beg, end) for a CSI index with the given
    geometry [SPEC CSIv1] — the generalized reg2bins."""
    out: List[int] = []
    end -= 1
    s = min_shift + depth * 3
    t = 0
    for level in range(depth + 1):
        b = t + (beg >> s)
        e = t + (end >> s)
        out.extend(range(b, e + 1))
        s -= 3
        t += 1 << (level * 3)
    return out


@dataclass
class CsiIndex:
    """CSI (.csi) sidecar: BAI generalized to configurable bin geometry,
    stored BGZF-compressed.  Read/write + the same query contract as
    BaiIndex; per-bin ``loffset`` replaces the 16 KiB linear index."""
    min_shift: int
    depth: int
    refs: List[Dict[int, Tuple[int, List[Tuple[int, int]]]]]
    # refs[rid]: bin -> (loffset, chunks)

    def to_bytes(self) -> bytes:
        body = [CSI_MAGIC,
                struct.pack("<iii", self.min_shift, self.depth, 0),
                struct.pack("<i", len(self.refs))]
        for bins in self.refs:
            body.append(struct.pack("<i", len(bins)))
            for bin_no in sorted(bins):
                loffset, chunks = bins[bin_no]
                body.append(struct.pack("<IQi", bin_no, loffset,
                                        len(chunks)))
                for beg, end in chunks:
                    body.append(struct.pack("<QQ", beg, end))
        from hadoop_bam_tpu.formats import bgzf
        return bgzf.compress_bytes(b"".join(body))

    @classmethod
    def from_bytes(cls, raw: bytes) -> "CsiIndex":
        from hadoop_bam_tpu.formats import bgzf
        if raw[:2] == b"\x1f\x8b":
            raw = bgzf.decompress_bytes(raw)
        if raw[:4] != CSI_MAGIC:
            raise ValueError("not a CSI index (bad magic)")
        min_shift, depth, l_aux = struct.unpack_from("<iii", raw, 4)
        off = 16 + l_aux
        (n_ref,) = struct.unpack_from("<i", raw, off)
        off += 4
        refs = []
        for _ in range(n_ref):
            (n_bin,) = struct.unpack_from("<i", raw, off)
            off += 4
            bins: Dict[int, Tuple[int, List[Tuple[int, int]]]] = {}
            for _ in range(n_bin):
                bin_no, loffset, n_chunk = struct.unpack_from("<IQi", raw,
                                                              off)
                off += 16
                chunks = []
                for _ in range(n_chunk):
                    beg, end = struct.unpack_from("<QQ", raw, off)
                    off += 16
                    chunks.append((beg, end))
                if bin_no != _METADATA_BIN:
                    bins[bin_no] = (loffset, chunks)
            refs.append(bins)
        return cls(min_shift=min_shift, depth=depth, refs=refs)

    def _min_offset(self, bins, beg: int) -> int:
        """Smallest virtual offset that can hold records overlapping
        positions >= ``beg``: the loffset of the nearest present bin at or
        before beg, walking previous-sibling-then-parent from the leaf bin
        (the CSI analog of BAI's linear-index pruning)."""
        bin_no = ((1 << (3 * self.depth)) - 1) // 7 + \
            (beg >> self.min_shift)
        while bin_no:
            entry = bins.get(bin_no)
            if entry is not None:
                return entry[0]
            first_sibling = (((bin_no - 1) >> 3) << 3) + 1
            bin_no = bin_no - 1 if bin_no > first_sibling \
                else (bin_no - 1) >> 3
        entry = bins.get(0)
        return entry[0] if entry is not None else 0

    def query(self, rid: int, beg: int, end: int) -> List[Tuple[int, int]]:
        if rid < 0 or rid >= len(self.refs):
            return []
        bins = self.refs[rid]
        min_off = self._min_offset(bins, beg)
        chunks: List[Tuple[int, int]] = []
        for bin_no in csi_reg2bins(beg, end, self.min_shift, self.depth):
            entry = bins.get(bin_no)
            if entry is None:
                continue
            _loffset, bin_chunks = entry
            for cbeg, cend in bin_chunks:
                if cend > min_off:
                    chunks.append((max(cbeg, min_off), cend))
        chunks.sort()
        merged: List[Tuple[int, int]] = []
        for cbeg, cend in chunks:
            if merged and cbeg <= merged[-1][1]:
                if cend > merged[-1][1]:
                    merged[-1] = (merged[-1][0], cend)
            else:
                merged.append((cbeg, cend))
        return merged

    @classmethod
    def from_bai(cls, bai: "BaiIndex", min_shift: int = 14,
                 depth: int = 5) -> "CsiIndex":
        """Re-express a BAI as CSI (same 16 KiB / depth-5 geometry —
        BAI bin numbers are exactly CSI bins at these parameters)."""
        refs = []
        for ref in bai.refs:
            bins: Dict[int, Tuple[int, List[Tuple[int, int]]]] = {}
            for bin_no, chunks in ref.bins.items():
                # loffset must lower-bound the start of ANY record
                # overlapping the bin's region — records assigned to
                # ancestor bins included.  The BAI linear index has
                # exactly that for the bin's first 16 KiB window; the
                # bin's own min chunk start alone could overestimate.
                level = 0
                while level < depth and \
                        ((1 << (3 * (level + 1))) - 1) // 7 <= bin_no:
                    level += 1
                region_start = (bin_no - ((1 << (3 * level)) - 1) // 7) \
                    << (min_shift + 3 * (depth - level))
                win = region_start >> _LINEAR_SHIFT
                lin = ref.linear[win] if win < len(ref.linear) else 0
                # lin == 0 (window unset) stays 0: "no pruning" is the
                # only safe fallback — the bin's own min chunk start can
                # exceed the start of an ancestor-bin record overlapping
                # this bin's region
                bins[bin_no] = (lin, list(chunks))
            refs.append(bins)
        return cls(min_shift=min_shift, depth=depth, refs=refs)


class IncrementalBinningCore:
    """Shared chunk/linear machinery of ``BAIBuilder`` and
    ``split/tabix.TabixBuilder`` — BAI and tabix use the same 14/5 bin
    arithmetic, the same deferred chunk ends, and the same 16 KiB
    linear index, so the logic lives ONCE here (the PR-8 chunk-end bug
    lived in exactly this code; two hand-synced copies would let the
    index families silently diverge on the next fix).

    Subclasses own ``self.refs`` (a list of ``RefIndex``) and call
    ``_observe`` per mapped record after their own rid resolution.

    Chunk ENDS are deferred: record i's chunk closes at record i+1's
    start voffset (or at ``finalize``'s end voffset for the last
    record), so every stored end carries a real block-boundary coffset.
    The old fallback packed (coffset+1, 0), one BYTE past the block
    start: BGZFReader-based chunk reads tolerated that by accident, but
    block-table consumers (plan_interval_spans -> coverage's
    fetch_span_raw) need end coffsets on real block boundaries and
    died mid-block with "truncated BGZF header".
    """

    refs: List[RefIndex]

    def __init__(self):
        self._pending: Optional[Tuple[int, int, int]] = None

    def _close(self, v1: int) -> None:
        if self._pending is None:
            return
        rid, b, v0 = self._pending
        self._pending = None
        chunks = self.refs[rid].bins.setdefault(b, [])
        if chunks and chunks[-1][1] >= v0:          # adjacent: extend
            chunks[-1] = (chunks[-1][0], v1)
        else:
            chunks.append((v0, v1))

    def _observe(self, rid: int, beg: int, end: int, voffset: int) -> None:
        """Record one mapped observation: open its (deferred-end) chunk
        and fold it into the linear index."""
        ref = self.refs[rid]
        self._pending = (rid, reg2bin(beg, end), voffset)
        w0 = beg >> _LINEAR_SHIFT
        w1 = max(end - 1, beg) >> _LINEAR_SHIFT
        if len(ref.linear) <= w1:
            ref.linear.extend([0] * (w1 + 1 - len(ref.linear)))
        for w in range(w0, w1 + 1):
            if ref.linear[w] == 0 or voffset < ref.linear[w]:
                ref.linear[w] = voffset


class BAIBuilder(IncrementalBinningCore):
    """Incremental BAI construction: one ``add`` per coordinate-sorted
    record, ``finalize`` closes the trailing chunk — the reusable core
    behind both the whole-file ``build_bai`` rescan and the write path's
    index-during-write sink (``write/indexing.IndexingSink``), which
    cannot afford a second pass over the file it just produced.
    """

    def __init__(self, n_ref: int):
        super().__init__()
        self.refs = [RefIndex() for _ in range(n_ref)]

    def add(self, rid: int, beg: int, end: int, voffset: int) -> None:
        """Observe one record: 0-based half-open [beg, end) on reference
        ``rid`` (negative = unmapped, indexed only as a chunk closer),
        starting at packed virtual offset ``voffset``."""
        self._close(voffset)
        if rid < 0:
            return
        self._observe(rid, beg, end, voffset)

    def finalize(self, end_voffset: int) -> BaiIndex:
        """Close the trailing chunk at ``end_voffset`` (end-of-data
        position — block-aligned by construction) and return the index."""
        self._close(end_voffset)
        return BaiIndex(refs=self.refs)


def _reg2bin_vec(beg: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Vectorized ``reg2bin`` over int64 column arrays."""
    e = end - 1
    return np.select(
        [beg >> 14 == e >> 14, beg >> 17 == e >> 17,
         beg >> 20 == e >> 20, beg >> 23 == e >> 23,
         beg >> 26 == e >> 26],
        [4681 + (beg >> 14), 585 + (beg >> 17), 73 + (beg >> 20),
         9 + (beg >> 23), 1 + (beg >> 26)],
        default=0)


def bai_from_columns(n_ref: int, refid: np.ndarray, beg: np.ndarray,
                     end: np.ndarray, voffsets: np.ndarray,
                     end_voffset: int) -> BaiIndex:
    """Vectorized twin of feeding the same file-ordered columns through
    ``BAIBuilder.add`` row by row (bit-identical output; the fuzz test
    pins it).  The write path's indexing sink already holds these
    columns, and a per-record Python loop over 10^8 records would put
    minutes of interpreter time on the critical path between the pooled
    deflate and publication — here bins come from one ``np.select``,
    chunks from same-(rid,bin) run detection, and the linear index from
    ``np.minimum.at`` per window stride.
    """
    refid = np.asarray(refid, np.int64)
    beg = np.asarray(beg, np.int64)
    end = np.asarray(end, np.int64)
    voffs = np.asarray(voffsets, np.uint64)
    n = refid.size
    refs = [RefIndex() for _ in range(n_ref)]
    if not n:
        return BaiIndex(refs=refs)

    mapped = refid >= 0
    bins = _reg2bin_vec(beg, end)
    # record i's chunk closes at record i+1's start (see the core's
    # deferred-end note); the last closes at end_voffset
    cend = np.empty(n, np.uint64)
    cend[:-1] = voffs[1:]
    cend[-1] = np.uint64(end_voffset)

    # a chunk extends exactly over a run of CONSECUTIVE mapped records
    # sharing (rid, bin): any break (bin change, ref change, unmapped
    # record between) moves the next start voffset past the closed
    # chunk's end, so the serial builder never merges across it
    prev_mapped = np.empty(n, bool)
    prev_mapped[0] = False
    prev_mapped[1:] = mapped[:-1]
    same = np.zeros(n, bool)
    same[1:] = (refid[1:] == refid[:-1]) & (bins[1:] == bins[:-1])
    new_run = mapped & ~(same & prev_mapped)

    midx = np.flatnonzero(mapped)
    run_of = np.cumsum(new_run)[midx] - 1        # run id per mapped row
    n_runs = int(run_of[-1]) + 1 if midx.size else 0
    run_ids = np.arange(n_runs)
    first = midx[np.searchsorted(run_of, run_ids, side="left")]
    last = midx[np.searchsorted(run_of, run_ids, side="right") - 1]
    run_rid = refid[first]
    run_bin = bins[first]
    run_v0 = voffs[first]
    run_v1 = cend[last]
    for k in range(n_runs):
        refs[int(run_rid[k])].bins.setdefault(int(run_bin[k]), []).append(
            (int(run_v0[k]), int(run_v1[k])))

    unset = np.uint64(0xFFFFFFFFFFFFFFFF)
    for rid in np.unique(refid[mapped]):
        m = mapped & (refid == rid)
        w0 = beg[m] >> _LINEAR_SHIFT
        w1 = np.maximum(end[m] - 1, beg[m]) >> _LINEAR_SHIFT
        lin = np.full(int(w1.max()) + 1, unset, np.uint64)
        v = voffs[m]
        span = w1 - w0
        for k in range(int(span.max()) + 1):
            sel = span >= k
            np.minimum.at(lin, w0[sel] + k, v[sel])
        lin[lin == unset] = 0
        refs[int(rid)].linear = [int(x) for x in lin]
    return BaiIndex(refs=refs)


def build_bai(bam_path: str, header=None) -> BaiIndex:
    """Build a BAI from a coordinate-sorted BAM in one streaming pass
    (the htsjdk/samtools `index` equivalent) — a thin wrapper over the
    incremental ``BAIBuilder``; bins and reference spans come from
    vectorized batch columns.  Spans are record-aligned and contiguous,
    so the builder's next-record chunk ends coincide with the per-span
    end voffsets the pre-builder implementation used."""
    from hadoop_bam_tpu.api.dataset import open_bam

    ds = open_bam(bam_path)
    header = header or ds.header
    builder = BAIBuilder(len(header.ref_names))
    end_v = 0

    for span in ds.spans():
        from hadoop_bam_tpu.split.planners import read_bam_span
        batch = read_bam_span(bam_path, span, header=header)
        end_v = (int(span.end[0]) << 16) | int(span.end[1])
        n = len(batch)
        if not n:
            continue
        voffs = batch.voffsets
        if voffs is None:
            raise ValueError("BAI build needs record voffsets from the "
                             "span reader")
        refid = batch.refid
        pos = batch.pos.astype(np.int64)            # 0-based
        span_len = np.maximum(batch.reference_span(), 1).astype(np.int64)
        end = pos + span_len                        # half-open
        for i in range(n):
            builder.add(int(refid[i]), int(pos[i]), int(end[i]),
                        int(voffs[i]))
    return builder.finalize(end_v)


def write_bai(bam_path: str, out_path: Optional[str] = None) -> str:
    out_path = out_path or bam_path + BAI_SUFFIX
    idx = build_bai(bam_path)
    with open(out_path, "wb") as f:
        f.write(idx.to_bytes())
    return out_path


def load_bai_for(bam_path: str):
    """Load a genomic index sidecar: .bai preferred, .csi fallback (both
    answer the same query contract)."""
    import os
    p = bam_path + BAI_SUFFIX
    if os.path.exists(p):
        return BaiIndex.from_bytes(open(p, "rb").read())
    p = bam_path + CSI_SUFFIX
    if os.path.exists(p):
        return CsiIndex.from_bytes(open(p, "rb").read())
    return None


def plan_interval_spans(bam_path: str, intervals, header,
                        bai: Optional[BaiIndex] = None):
    """Interval list -> record-region FileVirtualSpans via the BAI (the
    reference's split-trimming).  Callers still row-filter for exactness;
    this only bounds what gets read and inflated."""
    from hadoop_bam_tpu.split.spans import FileVirtualSpan

    bai = bai or load_bai_for(bam_path)
    if bai is None:
        return None
    rid_of = {n: i for i, n in enumerate(header.ref_names)}
    ranges: List[Tuple[int, int]] = []
    for iv in intervals:
        rid = rid_of.get(iv.rname)
        if rid is None:
            continue
        beg0 = max(iv.start - 1, 0)
        end0 = iv.end
        ranges.extend(bai.query(rid, beg0, end0))
    ranges.sort()
    merged: List[Tuple[int, int]] = []
    for beg, end in ranges:
        if merged and beg <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((beg, end))
    return [FileVirtualSpan(bam_path, beg, end) for beg, end in merged]
