"""BCF split guesser: find the next BCF2 *record* boundary from an arbitrary
file offset, as a virtual offset.

Rebuild of hb/BCFSplitGuesser.java (SURVEY.md section 2.2): works on both
containers — BGZF-compressed BCF (candidate = BGZF block start × in-block
offset, like the BAM guesser) and raw/uncompressed BCF (candidate = plain byte
offset, virtual offset = ``offset << 16``).  A candidate record start is
accepted when a chain of consecutive records validates: sane ``l_shared`` /
``l_indiv`` block lengths, CHROM index within the header's contig dictionary,
0-based POS >= -1, non-negative rlen (formats/bcf.plausible_record_start),
for MIN_CHAIN records or until the inspection window/EOF ends.

What runs (since PR 39, where ``utils/native.load()`` gives the library;
no flag, no option): the candidate test of a window is one native scan
with an early exit, ``native/hbam_native.cpp::hbam_bcf_guess`` — a real
record start is found after a record's length of candidates, not after a
sweep of all 65,280.  A BGZF window's first block is asked alone first
(one inflate where the window takes four): the scan says whether its
answer leaned on where its bytes end, and only then is the whole window
inflated and asked.  What stays as the oracle, and as the path of a host
without the library: ``_plausible_offsets`` (the design shift vs the
reference's per-offset decode loop: five fields gathered at every
candidate offset in ~70 NumPy passes) + ``_chain_ok``.  Both give the same
virtual offset for every byte offset asked
(tests/test_bcf_native_walk.py), so the spans a file is cut into do not
depend on the host.  Counters: ``vcf.guess_native`` / ``vcf.guess_numpy``,
once a boundary.
"""
from __future__ import annotations

import struct
from typing import Optional, Tuple

import numpy as np

from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.formats.bcf import plausible_record_start
from hadoop_bam_tpu.formats.vcf import VCFHeader
from hadoop_bam_tpu.formats.virtual_offset import make_voffset
from hadoop_bam_tpu.split.bgzf_guesser import BGZFSplitGuesser
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.seekable import as_byte_source

MIN_CHAIN = 3
INSPECT_BLOCKS = 4
RAW_WINDOW = 1 << 20  # inspection window for uncompressed BCF


class BCFSplitGuesser:

    def __init__(self, source, header: VCFHeader, *, is_bgzf: bool = True):
        self._src = as_byte_source(source)
        self._header = header
        self._n_contigs = max(header.n_contigs, 1)
        self._is_bgzf = is_bgzf
        self._bgzf = BGZFSplitGuesser(self._src) if is_bgzf else None

    def guess_next_record_start(self, offset: int) -> Optional[int]:
        """Smallest confirmed record-start virtual offset at or after byte
        ``offset``; None if none found before EOF."""
        METRICS.count("vcf.guess_native" if native.available()
                      else "vcf.guess_numpy")
        if self._is_bgzf:
            return self._guess_bgzf(offset)
        return self._guess_raw(offset)

    # -- BGZF container ------------------------------------------------------
    def _guess_bgzf(self, offset: int) -> Optional[int]:
        coffset = offset
        while True:
            coffset = self._bgzf.guess_next_block_start(coffset)
            if coffset is None:
                return None
            raw = self._src.pread(coffset, INSPECT_BLOCKS * bgzf.MAX_BLOCK_SIZE)
            blocks = self._parse_chain(raw)
            u, decided = self._find_in_first_block(raw, blocks)
            if not decided:
                blocks, data = self._inflate_chain(raw, blocks)
                if blocks and blocks[0].isize > 0:
                    at_eof = (coffset + sum(b.block_size for b in blocks)
                              >= self._src.size)
                    u = self._find_record(data, blocks[0].isize,
                                          partial=at_eof)
            if u is not None:
                return make_voffset(coffset, u)
            if not blocks:
                return None
            coffset += blocks[0].block_size
            if coffset >= self._src.size:
                return None

    @staticmethod
    def _parse_chain(raw: bytes) -> list:
        """The headers of the first blocks of ``raw`` that parse, at most
        INSPECT_BLOCKS."""
        blocks = []
        off = 0
        while off < len(raw) and len(blocks) < INSPECT_BLOCKS:
            try:
                blocks.append(bgzf.parse_block_header(raw, off))
            except bgzf.BGZFError:
                break
            off = blocks[-1].next_coffset
        return blocks

    def _find_in_first_block(self, raw: bytes, blocks: list
                             ) -> Tuple[Optional[int], bool]:
        """(the record start in the window's first block, True) where the
        first block ALONE decides it: a record start a few KB in whose
        chain of MIN_CHAIN records ends inside the block is the answer of
        the whole window too (``hbam_bcf_guess`` says when its answer did
        not lean on where its bytes end), and three of the window's four
        inflates are saved.  (None, False) where it does not — a chain
        that runs into the next block, no library — and the whole window
        is asked."""
        if not blocks or blocks[0].isize <= 0 or not native.available():
            return None, False
        try:
            first = bgzf.inflate_block(raw, blocks[0], check_crc=False)
        except bgzf.BGZFError:
            return None, False
        u, edge = native.bcf_guess(first, len(first), self._n_contigs,
                                   MIN_CHAIN, False)
        return (None if u < 0 else u), not edge

    @staticmethod
    def _inflate_chain(raw: bytes, blocks: list):
        """(the blocks of the chain up to the first that does not inflate,
        their inflated bytes)."""
        chunks = []
        for info in blocks:
            try:
                chunks.append(bgzf.inflate_block(raw, info, check_crc=False))
            except bgzf.BGZFError:
                break
        return blocks[:len(chunks)], b"".join(chunks)

    # -- raw container -------------------------------------------------------
    def _guess_raw(self, offset: int) -> Optional[int]:
        size = self._src.size
        while offset < size:
            data = self._src.pread(offset, RAW_WINDOW)
            at_eof = offset + len(data) >= size
            u = self._find_record(data, len(data), partial=at_eof)
            if u is not None:
                return make_voffset(offset + u, 0)
            if at_eof:
                return None
            # overlap windows so a boundary record isn't missed
            offset += RAW_WINDOW - 64
        return None

    # -- shared chain validation ---------------------------------------------
    def _find_record(self, data: bytes, first_len: int,
                     partial: bool) -> Optional[int]:
        """The smallest offset in the window's first block (the raw
        container: in the window) that passes the plausibility sweep and
        starts a chain of valid records.  With the native library one
        scan with an early exit (``hbam_bcf_guess``); ``_plausible_offsets``
        + ``_chain_ok`` are its oracle and the path of a host without the
        library — the same offset either way, so the same spans."""
        if native.available():
            u, _edge = native.bcf_guess(data, first_len, self._n_contigs,
                                        MIN_CHAIN, partial)
            return None if u < 0 else u
        for u in self._plausible_offsets(data, first_len):
            if self._chain_ok(data, int(u), partial):
                return int(u)
        return None

    def _plausible_offsets(self, data: bytes, first_len: int) -> np.ndarray:
        """Vectorized plausibility over every candidate offset in the first
        block (the design shift vs the reference's per-offset decode loop)."""
        b = np.frombuffer(data, dtype=np.uint8)
        n = b.size
        hi = min(first_len, n - 32)
        if hi <= 0:
            return np.empty(0, dtype=np.int64)
        offs = np.arange(hi, dtype=np.int64)

        def u32(shift):
            return (b[offs + shift].astype(np.int64)
                    | (b[offs + shift + 1].astype(np.int64) << 8)
                    | (b[offs + shift + 2].astype(np.int64) << 16)
                    | (b[offs + shift + 3].astype(np.int64) << 24))

        def i32(shift):
            return u32(shift).astype(np.uint32).astype(np.int32).astype(np.int64)

        l_shared = u32(0)
        l_indiv = u32(4)
        chrom = i32(8)
        pos0 = i32(12)
        rlen = i32(16)
        mask = (
            (l_shared >= 24) & (l_shared < (1 << 24))
            & (l_indiv < (1 << 24))
            & (chrom >= 0) & (chrom < self._n_contigs)
            & (pos0 >= -1) & (rlen >= 0)
        )
        return offs[mask]

    def _chain_ok(self, data: bytes, u: int, partial: bool) -> bool:
        """``partial`` means the window reaches EOF: then the chain must end
        exactly at the window end (a valid file ends on a record boundary),
        which kills false positives whose fake record runs past the tail."""
        n = len(data)
        count = 0
        p = u
        while count < MIN_CHAIN:
            if p == n:
                return count >= 1 or partial
            if p + 32 > n:
                return False if partial else count >= 1
            if not plausible_record_start(data, p, self._n_contigs):
                return False
            l_shared, l_indiv = struct.unpack_from("<II", data, p)
            nxt = p + 8 + l_shared + l_indiv
            if nxt > n:
                return False if partial else count >= 1
            p = nxt
            count += 1
        return True
