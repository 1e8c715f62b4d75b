"""Read datasets: the FASTQ/QSEQ/FASTA InputFormat surface, iterator-shaped.

Rebuild of hb/FastqInputFormat.java, hb/QseqInputFormat.java,
hb/FastaInputFormat.java (SURVEY.md section 2.3) in dataset clothes, plus a
padded-array bridge that feeds device pipelines the same way BamBatch does.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.formats.fasta import ReferenceFragment, parse_fasta
from hadoop_bam_tpu.formats.fastq import SequencedFragment, parse_fastq
from hadoop_bam_tpu.formats.qseq import parse_qseq
from hadoop_bam_tpu.split.planners import plan_text_spans, read_text_span
from hadoop_bam_tpu.split.read_planners import (
    GZIP_MAGIC, iter_gzip_text_chunks, iter_on_thread, plan_fasta_spans,
    read_fasta_span, read_fastq_span,
)
from hadoop_bam_tpu.split.spans import FileByteSpan
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.seekable import scoped_byte_source


class TextChunk:
    """One record-aligned piece of a span's text, as a whole-file driver
    tokenises it: a plain-text span is one chunk whose ``text()`` reads
    the span (re-readable, so it may be retried); a compressed span is a
    stream of chunks that carry their inflated text (``streamed``: read
    once, in order, never retried).  ``done()`` says the chunk's text is
    no longer needed — what the stream's count of text alive goes by."""

    __slots__ = ("span", "streamed", "_text", "_read", "_alive")

    def __init__(self, span: FileByteSpan, *,
                 read: Optional[Callable[[], bytes]] = None,
                 text: Optional[bytes] = None,
                 alive: "Optional[_TextAlive]" = None):
        self.span = span
        self.streamed = read is None
        self._text, self._read, self._alive = text, read, alive

    def text(self) -> bytes:
        return self._text if self.streamed else self._read()

    def done(self) -> None:
        text, self._text = self._text, None
        if text is not None and self._alive is not None:
            self._alive.add(-len(text))


class _TextAlive:
    """Bytes of a stream's inflated text alive between the inflater —
    its workers' buffers too — and the end of their tokenise, and their
    high-water mark."""

    def __init__(self):
        self._lock = threading.Lock()
        self._now = 0
        self.peak = 0

    def add(self, n: int) -> None:
        with self._lock:
            self._now += n
            self.peak = max(self.peak, self._now)


class _SpannedDataset:
    """Shared span bookkeeping + checkpoint/resume."""

    fmt = "read"                # the dataset's prefix of spans and counters
    lines_per_record = 1        # how a stream of its text is cut

    def __init__(self, path: str, config: HBamConfig):
        self.path = path
        self.config = config
        self._plan: Optional[List[FileByteSpan]] = None
        self._plan_num_spans: Optional[int] = None
        self._next_span = 0

    def parse_text(self, text: bytes) -> List:
        """The records of a record-aligned piece of text."""
        raise NotImplementedError

    def read_span(self, span: FileByteSpan) -> List:
        return self.parse_text(self.read_span_text(span))

    def _iter_spans(self, num_spans: Optional[int]) -> Iterator:
        """Span-granular resumable iteration (state = spans delivered).
        A fresh call after exhaustion restarts from the beginning; a call
        after load_state_dict resumes mid-plan."""
        plan = self.spans(num_spans)
        if self._next_span >= len(plan):
            self._next_span = 0
        while self._next_span < len(plan):
            recs = self.read_span(plan[self._next_span])
            self._next_span += 1
            yield from recs

    def is_compressed(self) -> bool:
        """gzip/BGZF input?  Compressed text is ONE span in the plan —
        the reference's behavior for non-splittable Hadoop codecs, and
        the unit a journal records — and a stream of record-aligned
        chunks at read time (``iter_span_chunks``)."""
        cached = getattr(self, "_compressed", None)
        if cached is None:
            with scoped_byte_source(self.path) as src:
                cached = src.pread(0, 2) == GZIP_MAGIC
            self._compressed = cached
        return cached

    def _plan_spans(self, num_spans: Optional[int]) -> List[FileByteSpan]:
        if self.is_compressed():
            with scoped_byte_source(self.path) as src:
                return [FileByteSpan(self.path, 0, src.size)]
        return plan_text_spans(self.path, num_spans=num_spans,
                               span_bytes=None if num_spans
                               else self.config.split_size)

    def _read_plain_span(self, span: FileByteSpan) -> bytes:
        """Record-aligned text of a plain-text span."""
        raise NotImplementedError

    def _streamed(self, span: FileByteSpan) -> bool:
        return span.start == 0 and self.is_compressed()

    def _stream_text(self, grain: int,
                     alive: "Optional[_TextAlive]" = None) -> Iterator[bytes]:
        return iter_gzip_text_chunks(self.path, grain,
                                     self.lines_per_record, fmt=self.fmt,
                                     alive=alive)

    def read_span_text(self, span: FileByteSpan) -> bytes:
        """Raw record-aligned text of a span (the whole inflated file for
        a compressed input's one span) — what the object parse reads."""
        if self._streamed(span):
            return b"".join(self._stream_text(self.config.split_size))
        return self._read_plain_span(span)

    def iter_span_chunks(self, span: FileByteSpan, grain: int
                         ) -> Iterator[TextChunk]:
        """The span's text as the ``TextChunk``s a whole-file driver
        tokenises.  A plain-text span is one chunk, read when its
        ``text()`` is asked for (on the driver's pool, under its retry).
        A compressed span is a stream: one thread a file,
        ``hbam-inflate-stream``, hands on its record-aligned chunks of at
        most ``grain`` bytes, one chunk ahead of the consumer — inflating
        them itself or walking the pieces of the ``hbam-inflate_<i>``
        workers (``iter_gzip_text_chunks``); an error in it (a corrupt or
        truncated member) is raised here.  When the stream ends, its
        high-water mark of text alive is added to
        ``<fmt>.stream_peak_text_bytes``: a chunk's text until its
        ``done()``, and what the workers hold before it — 2 B a symbol
        decoded ahead, then the bytes until they are copied into a
        chunk."""
        if not self._streamed(span):
            yield TextChunk(span, read=lambda: self._read_plain_span(span))
            return
        alive = _TextAlive()

        def chunks() -> Iterator[TextChunk]:
            for text in self._stream_text(grain, alive):
                alive.add(len(text))
                yield TextChunk(span, text=text, alive=alive)

        try:
            yield from iter_on_thread(chunks, "hbam-inflate-stream")
        finally:
            METRICS.count(f"{self.fmt}.stream_peak_text_bytes", alive.peak)

    def spans(self, num_spans: Optional[int] = None) -> List[FileByteSpan]:
        if self._plan is not None and num_spans is not None \
                and num_spans != self._plan_num_spans:
            raise ValueError(
                f"span plan already built with num_spans="
                f"{self._plan_num_spans}; open a new dataset to re-plan")
        if self._plan is None:
            self._plan = self._plan_spans(num_spans)
            self._plan_num_spans = num_spans
        return self._plan

    def state_dict(self) -> Dict:
        return {"path": self.path,
                "plan": [s.to_dict() for s in (self._plan or [])],
                "next_span": self._next_span}

    def load_state_dict(self, state: Dict) -> None:
        assert state["path"] == self.path
        self._plan = [FileByteSpan.from_dict(d) for d in state["plan"]] or None
        self._next_span = int(state["next_span"])


class FastqDataset(_SpannedDataset):
    """Splittable FASTQ: record-quadruple alignment at every span
    boundary; a compressed input is one span in the plan and a stream of
    4-line-aligned chunks at read time (base class)."""

    fmt = "fastq"
    lines_per_record = 4

    def _read_plain_span(self, span: FileByteSpan) -> bytes:
        return read_fastq_span(self.path, span)

    def parse_text(self, text: bytes) -> List[SequencedFragment]:
        return parse_fastq(text,
                           encoding=self.config.fastq_base_quality_encoding,
                           filter_failed_qc=self.config.fastq_filter_failed_qc)

    def records(self, num_spans: Optional[int] = None
                ) -> Iterator[SequencedFragment]:
        return self._iter_spans(num_spans)

    def tensor_batches(self, mesh=None, geometry=None,
                       num_spans: Optional[int] = None) -> Iterator[Dict]:
        """Device-resident read batches sharded over the mesh's data axis:
        ``seq_packed`` uint8 [n_dev, cap, seq_stride] (BAM 4-bit nibble
        codes, same alphabet as BamDataset.tensor_batches), ``qual`` uint8,
        ``lengths`` int32 [n_dev, cap], ``n_records`` int32 [n_dev].
        The FINAL batch may arrive with fewer rows than
        geometry.tile_records (shrunk to the smallest dispatch bucket) —
        size consumer buffers from each batch's own shape."""
        from hadoop_bam_tpu.parallel.pipeline import (
            stream_read_tensor_batches,
        )
        yield from stream_read_tensor_batches(
            self.spans(num_spans), self.read_span, self.config, mesh,
            geometry, fmt="fastq")


class QseqDataset(_SpannedDataset):
    """Illumina qseq: one record per line."""

    fmt = "qseq"

    def _read_plain_span(self, span: FileByteSpan) -> bytes:
        return read_text_span(self.path, span)

    def parse_text(self, text: bytes) -> List[SequencedFragment]:
        return parse_qseq(text,
                          encoding=self.config.qseq_base_quality_encoding,
                          filter_failed_qc=self.config.qseq_filter_failed_qc)

    def records(self, num_spans: Optional[int] = None
                ) -> Iterator[SequencedFragment]:
        return self._iter_spans(num_spans)

    def tensor_batches(self, mesh=None, geometry=None,
                       num_spans: Optional[int] = None) -> Iterator[Dict]:
        """Same device batch layout as FastqDataset.tensor_batches."""
        from hadoop_bam_tpu.parallel.pipeline import (
            stream_read_tensor_batches,
        )
        yield from stream_read_tensor_batches(
            self.spans(num_spans), self.read_span, self.config, mesh,
            geometry, fmt="qseq")


class FastaDataset(_SpannedDataset):
    """Reference FASTA: spans hold whole contigs (snapped to '>')."""

    def _plan_spans(self, num_spans: Optional[int]) -> List[FileByteSpan]:
        return plan_fasta_spans(self.path, num_spans=num_spans,
                                config=self.config)

    def read_span(self, span: FileByteSpan) -> List[ReferenceFragment]:
        return parse_fasta(read_fasta_span(self.path, span))

    def fragments(self, num_spans: Optional[int] = None
                  ) -> Iterator[ReferenceFragment]:
        return self._iter_spans(num_spans)

    def window_tensor_batches(self, window: int = 1024, stride: int = 0,
                              mesh=None, geometry=None,
                              num_spans: Optional[int] = None
                              ) -> Iterator[Dict]:
        """Reference windows as device tensors: each contig is cut into
        ``window``-base pieces every ``stride`` bases (default stride =
        window, i.e. non-overlapping) and packed into the same 4-bit
        nibble tiles as the read feeds — the reference-context input for
        models that consume (read, reference) pairs.  Yields the
        FastqDataset.tensor_batches layout."""
        from hadoop_bam_tpu.parallel.pipeline import (
            PayloadGeometry, stream_read_tensor_batches,
        )

        stride = stride or window
        if geometry is None:
            geometry = PayloadGeometry(max_len=window)

        def read_windows(span) -> List[SequencedFragment]:
            out: List[SequencedFragment] = []
            # contig-order reassembly: fragments of one contig arrive in
            # position order within a span (spans snap to '>')
            per_contig: Dict[str, List[ReferenceFragment]] = {}
            for frag in self.read_span(span):
                per_contig.setdefault(frag.contig, []).append(frag)
            for contig, frags in per_contig.items():
                seq = "".join(f.sequence for f in frags)
                n = len(seq)
                if not n:
                    continue
                if n <= window:
                    out.append(SequencedFragment(sequence=seq, quality=""))
                    continue
                last = n - window
                starts = list(range(0, last + 1, stride))
                if starts[-1] != last:
                    starts.append(last)  # flush a final full window
                for off in starts:
                    out.append(SequencedFragment(
                        sequence=seq[off:off + window], quality=""))
            return out

        yield from stream_read_tensor_batches(
            self.spans(num_spans), read_windows, self.config, mesh,
            geometry, fmt="fasta")


def open_fastq(path: str, config: HBamConfig = DEFAULT_CONFIG) -> FastqDataset:
    return FastqDataset(path, config)


def open_qseq(path: str, config: HBamConfig = DEFAULT_CONFIG) -> QseqDataset:
    return QseqDataset(path, config)


def open_fasta(path: str, config: HBamConfig = DEFAULT_CONFIG) -> FastaDataset:
    return FastaDataset(path, config)


# ---------------------------------------------------------------------------
# device bridge: fragments -> fixed-shape arrays
# ---------------------------------------------------------------------------

# Unknown/ambiguity characters (IUPAC codes, gaps) map to N (4), never to a
# confident base; 5 is reserved for padding.
_BASE_CODE = np.full(256, 4, dtype=np.uint8)
for i, c in enumerate("ACGT"):
    _BASE_CODE[ord(c)] = i
    _BASE_CODE[ord(c.lower())] = i


# ASCII -> BAM 4-bit base codes [SPEC]: the same nibble alphabet the BAM
# payload tiles use, so one Pallas kernel (ops/seq_pallas.py) serves every
# read format.  Unknown characters map to N (15).
_NIBBLE_CODE = np.full(256, 15, dtype=np.uint8)
for _c, _code in (("=", 0), ("A", 1), ("C", 2), ("M", 3), ("G", 4),
                  ("R", 5), ("S", 6), ("V", 7), ("T", 8), ("W", 9),
                  ("Y", 10), ("H", 11), ("K", 12), ("D", 13), ("B", 14),
                  ("N", 15)):
    _NIBBLE_CODE[ord(_c)] = _code
    _NIBBLE_CODE[ord(_c.lower())] = _code


def _scan_lines(buf: np.ndarray) -> Tuple[np.ndarray, np.ndarray, bool]:
    """Newline scan -> CRLF-safe (starts, ends, synthesized_last) line
    table.  A final line without a terminating newline still counts as a
    line; ``synthesized_last`` marks it so callers can drop only THAT
    line when it is empty (a real empty line must be kept or rejected by
    format-specific rules)."""
    nl = np.flatnonzero(buf == 0x0A)
    synthesized_last = nl.size == 0 or nl[-1] != buf.size - 1
    if synthesized_last:
        nl = np.append(nl, buf.size)
    starts = np.empty(nl.size, dtype=np.int64)
    starts[0] = 0
    starts[1:] = nl[:-1] + 1
    ends = nl.copy()
    has_cr = (ends > starts) & (buf[np.minimum(ends - 1, buf.size - 1)]
                                == 0x0D)
    ends = ends - has_cr
    return starts, ends, synthesized_last


def _pack_seq_qual_tiles(buf: np.ndarray, seq_starts: np.ndarray,
                         qual_starts: np.ndarray, lengths: np.ndarray,
                         seq_stride: int, qual_stride: int,
                         qual_offset: int,
                         guard_lens: Optional[np.ndarray] = None
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Gather per-record SEQ/QUAL runs into payload tiles: nibble-code +
    pair-pack the bases, re-base the qualities with the wrong-encoding
    guard (shared by the FASTQ and QSEQ grid tokenizers — their behavior
    must stay byte-identical, so this is one function).

    ``guard_lens`` is the UNTRUNCATED quality-field length per record:
    the object parsers (convert_quality) validate the whole string, not
    just the max_len prefix the tiles keep, so the guard must too."""
    from hadoop_bam_tpu.formats.fastq import FastqError

    n = lengths.size
    seq = np.zeros((n, seq_stride), dtype=np.uint8)
    qual = np.zeros((n, qual_stride), dtype=np.uint8)
    if qual_offset != 33 and n and guard_lens is not None             and guard_lens.size:
        Lg = int(guard_lens.max())
        if Lg:
            colg = np.arange(Lg, dtype=np.int64)[None, :]
            maskg = colg < guard_lens[:, None]
            gg = np.minimum(qual_starts[:, None] + colg, buf.size - 1)
            vals = buf[gg].astype(np.int16) - qual_offset
            # mirror convert_quality: re-based ASCII must stay printable,
            # i.e. Phred in [0, 93], over the FULL field
            bad = maskg & ((vals < 0) | (vals > 93))
            if bad.any():
                raise FastqError(
                    "quality out of range after re-encoding — wrong "
                    "base-quality-encoding config?")
    L = int(lengths.max()) if n else 0
    if not L:
        return seq, qual
    L_even = L + (L & 1)
    col = np.arange(L_even, dtype=np.int64)[None, :]
    mask = col < lengths[:, None]
    g = np.minimum(seq_starts[:, None] + col, buf.size - 1)
    codes = np.where(mask, _NIBBLE_CODE[buf[g]], 0).astype(np.uint8)
    packed = (codes[:, 0::2] << 4) | codes[:, 1::2]
    ks = min(packed.shape[1], seq_stride)
    seq[:, :ks] = packed[:, :ks]

    gq = np.minimum(qual_starts[:, None] + col[:, :L], buf.size - 1)
    q = np.where(mask[:, :L], buf[gq].astype(np.int16) - qual_offset, 0)
    kq = min(L, qual_stride)
    qual[:, :kq] = np.clip(q, 0, 255).astype(np.uint8)[:, :kq]
    return seq, qual


def fastq_text_to_payload_tiles(text: bytes, seq_stride: int,
                                qual_stride: int, max_len: int,
                                qual_offset: int = 33
                                ) -> Tuple[np.ndarray, np.ndarray,
                                           np.ndarray]:
    """FASTQ span -> payload tiles, no per-read Python objects.

    The stats drivers only need (packed bases, qualities, lengths); going
    through parse_fastq costs a SequencedFragment (with run-metadata name
    parsing) per read and dominates the FASTQ pipeline wall clock.  With
    the native library the span is one pass over its bytes with the
    interpreter lock released (``utils/native.py::fastq_tokenize``: find a
    record's four lines, check them, code and pack, pad the row).  Without
    it, and for any text the pass refuses, the NumPy twin below runs: the
    same tiles byte for byte, and the one place the refusals are worded.
    ``fastq.tokenize_native_records`` / ``fastq.tokenize_numpy_records``
    count whose rows a span's were.

    Validation matches parse_fastq's strictness where cheap (4n lines,
    '@'/'+' leads, SEQ/QUAL length equality); it raises the same FastqError.
    """
    if native.load() is not None:
        tiles = native.fastq_tokenize(
            text, _NIBBLE_CODE, seq_stride, qual_stride, max_len,
            qual_offset)
        if tiles is not None:
            METRICS.count("fastq.tokenize_native_records", tiles[2].size)
            return tiles
    tiles = _fastq_text_to_payload_tiles_numpy(
        text, seq_stride, qual_stride, max_len, qual_offset)
    METRICS.count("fastq.tokenize_numpy_records", tiles[2].size)
    return tiles


def _fastq_text_to_payload_tiles_numpy(text: bytes, seq_stride: int,
                                       qual_stride: int, max_len: int,
                                       qual_offset: int
                                       ) -> Tuple[np.ndarray, np.ndarray,
                                                  np.ndarray]:
    """``fastq_text_to_payload_tiles`` in NumPy: newline scan -> line
    table -> 4-line record grid -> one clamped gather per payload
    matrix."""
    from hadoop_bam_tpu.formats.fastq import FastqError

    buf = np.frombuffer(text, dtype=np.uint8)
    if buf.size == 0:
        return (np.zeros((0, seq_stride), np.uint8),
                np.zeros((0, qual_stride), np.uint8),
                np.zeros((0,), np.int32))
    starts, ends, synthesized_last = _scan_lines(buf)
    # drop only the synthesized final line when empty — a real
    # zero-length final line (legal zero-length read) must be kept
    if synthesized_last and starts[-1] >= ends[-1]:
        starts, ends = starts[:-1], ends[:-1]
    if starts.size % 4:
        raise FastqError(f"FASTQ span has {starts.size} lines (not 4n)")
    n = starts.size // 4
    if n == 0:
        return (np.zeros((0, seq_stride), np.uint8),
                np.zeros((0, qual_stride), np.uint8),
                np.zeros((0,), np.int32))
    s4 = starts.reshape(n, 4)
    e4 = ends.reshape(n, 4)
    if not (buf[s4[:, 0]] == ord("@")).all() \
            or not (buf[s4[:, 2]] == ord("+")).all():
        bad = int(np.flatnonzero((buf[s4[:, 0]] != ord("@"))
                                 | (buf[s4[:, 2]] != ord("+")))[0])
        raise FastqError(f"malformed FASTQ record at line {bad * 4}")
    seq_len = e4[:, 1] - s4[:, 1]
    if not (seq_len == e4[:, 3] - s4[:, 3]).all():
        raise FastqError("SEQ/QUAL length mismatch")
    lengths = np.minimum(seq_len, max_len).astype(np.int32)
    seq, qual = _pack_seq_qual_tiles(buf, s4[:, 1], s4[:, 3], lengths,
                                     seq_stride, qual_stride, qual_offset,
                                     guard_lens=seq_len)
    return seq, qual, lengths


def qseq_text_to_payload_tiles(text: bytes, seq_stride: int,
                               qual_stride: int, max_len: int,
                               qual_offset: int = 64
                               ) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]:
    """Vectorized QSEQ span -> payload tiles (the 11-tab-field twin of
    fastq_text_to_payload_tiles): newline/tab grid -> one gather each for
    the SEQ (field 8; '.' reads as N via the nibble table) and QUAL
    (field 9, Illumina +64 by default) columns.  Validation matches
    parse_qseq: exactly 11 fields, SEQ/QUAL equal length, loud
    wrong-encoding guard."""
    from hadoop_bam_tpu.formats.fastq import FastqError

    buf = np.frombuffer(text, dtype=np.uint8)
    empty = (np.zeros((0, seq_stride), np.uint8),
             np.zeros((0, qual_stride), np.uint8),
             np.zeros((0,), np.int32))
    if buf.size == 0:
        return empty
    starts, ends, _synth = _scan_lines(buf)
    keep = ends > starts                    # parse_qseq skips empty lines
    starts, ends = starts[keep], ends[keep]
    n = starts.size
    if n == 0:
        return empty

    tabs = np.flatnonzero(buf == 0x09)
    t0 = np.searchsorted(tabs, starts)
    t1 = np.searchsorted(tabs, ends)
    ntab = t1 - t0
    if not (ntab == 10).all():
        bad = int(np.flatnonzero(ntab != 10)[0])
        raise FastqError(f"qseq line has {int(ntab[bad]) + 1} fields, "
                         f"need 11")
    k = np.arange(10, dtype=np.int64)[None, :]
    tabm = tabs[t0[:, None] + k]
    fs = np.concatenate([starts[:, None], tabm + 1], axis=1)
    fe = np.concatenate([tabm, ends[:, None]], axis=1)
    seq_len = fe[:, 8] - fs[:, 8]
    qual_len = fe[:, 9] - fs[:, 9]
    if not (seq_len == qual_len).all():
        raise FastqError("qseq SEQ/QUAL length mismatch")
    lengths = np.minimum(seq_len, max_len).astype(np.int32)
    seq, qual = _pack_seq_qual_tiles(buf, fs[:, 8], fs[:, 9], lengths,
                                     seq_stride, qual_stride, qual_offset,
                                     guard_lens=seq_len)
    return seq, qual, lengths


def ragged_to_payload_tiles(seq_cat: bytes, seq_lens: np.ndarray,
                            qual_cat: bytes, qual_lens: np.ndarray,
                            seq_stride: int, qual_stride: int,
                            max_len: int, qual_offset: int = 0,
                            out=None
                            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Concatenated ragged sequences/qualities -> payload tiles, fully
    vectorized (the packing half of fastq_text_to_payload_tiles, for
    producers that already hold decoded bytes — e.g. CRAM records).

    ``qual_cat`` holds per-record quality runs of ``qual_lens`` bytes
    (bytes or a uint8 array, as ``seq_cat``); ``qual_offset`` is
    subtracted (0 when the bytes are already raw Phred, 33 for printable
    ASCII).  Records with no quality simply have qual_lens 0 — their tile
    rows stay zero.  ``out`` = zeroed (seq, qual, lengths) rows to fill in
    place (a span's slices packed into one span-sized set of tiles)."""
    n = seq_lens.size
    if out is None:
        seq = np.zeros((n, seq_stride), dtype=np.uint8)
        qual = np.zeros((n, qual_stride), dtype=np.uint8)
        lengths = np.minimum(seq_lens, max_len).astype(np.int32)
    else:
        seq, qual, lengths = out
        np.minimum(seq_lens, max_len, out=lengths, casting="unsafe")
    if n == 0:
        return seq, qual, lengths
    sbuf = np.frombuffer(seq_cat, dtype=np.uint8)
    qbuf = np.frombuffer(qual_cat, dtype=np.uint8)
    rl, ql = int(seq_lens[0]), int(qual_lens[0])
    if (not qual_offset and seq.flags.c_contiguous
            and qual.flags.c_contiguous
            and int(seq_lens.min()) == int(seq_lens.max()) == rl
            and int(qual_lens.min()) == int(qual_lens.max())
            and ql in (0, rl) and sbuf.size == n * rl
            and qbuf.size == n * ql and native.available()):
        # one read length (the overwhelmingly common case): one native
        # pass, the interpreter lock released
        native.pack_reads(sbuf, qbuf, n, rl, ql, _NIBBLE_CODE, max_len,
                          seq, qual)
        return seq, qual, lengths
    s0 = np.cumsum(seq_lens, dtype=np.int64) - seq_lens
    q0 = np.cumsum(qual_lens, dtype=np.int64) - qual_lens

    L = int(lengths.max())
    if L:
        # uniform read length (the overwhelmingly common case): the
        # concatenated buffer IS the (n, len) matrix — reshape instead
        # of building per-row gather/mask matrices
        if int(seq_lens.min()) == int(seq_lens.max()):
            rl0 = int(seq_lens[0])
            mat = sbuf[:n * rl0].reshape(n, rl0)[:, :L]
            codes = _NIBBLE_CODE[mat]
            if L & 1:
                codes = np.concatenate(
                    [codes, np.zeros((n, 1), np.uint8)], axis=1)
        else:
            L_even = L + (L & 1)
            col = np.arange(L_even, dtype=np.int64)[None, :]
            mask = col < lengths[:, None]
            g = np.minimum(s0[:, None] + col, max(sbuf.size - 1, 0))
            codes = np.where(mask, _NIBBLE_CODE[sbuf[g]], 0
                             ).astype(np.uint8)
        packed = (codes[:, 0::2] << 4) | codes[:, 1::2]
        ks = min(packed.shape[1], seq_stride)
        seq[:, :ks] = packed[:, :ks]

    qlen = np.minimum(qual_lens, max_len).astype(np.int64)
    Lq = int(qlen.max(initial=0))
    if Lq and qbuf.size:
        kq = min(Lq, qual_stride)
        if int(qual_lens.min()) == int(qual_lens.max()):
            ql0 = int(qual_lens[0])
            mat = qbuf[:n * ql0].reshape(n, ql0)[:, :kq]
            if qual_offset:
                qual[:, :kq] = np.clip(
                    mat.astype(np.int16) - qual_offset, 0, 255
                ).astype(np.uint8)
            else:
                qual[:, :kq] = mat
        else:
            colq = np.arange(Lq, dtype=np.int64)[None, :]
            maskq = colq < qlen[:, None]
            gq = np.minimum(q0[:, None] + colq, qbuf.size - 1)
            vals = np.where(maskq, qbuf[gq].astype(np.int16)
                            - qual_offset, 0)
            qual[:, :kq] = np.clip(vals, 0, 255).astype(np.uint8)[:, :kq]
    return seq, qual, lengths


def fragments_to_payload_tiles(frags: List[SequencedFragment],
                               seq_stride: int, qual_stride: int,
                               max_len: int
                               ) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]:
    """Pack reads into the BAM-payload tile layout (4-bit bases, 2/byte,
    high nibble first; Phred quality bytes) — the FASTQ/QSEQ entry into
    the device payload path.  Returns (seq [n, seq_stride] uint8,
    qual [n, qual_stride] uint8, lengths [n] int32)."""
    n = len(frags)
    seq = np.zeros((n, seq_stride), dtype=np.uint8)
    qual = np.zeros((n, qual_stride), dtype=np.uint8)
    lengths = np.zeros(n, dtype=np.int32)
    for i, f in enumerate(frags):
        l = min(len(f.sequence), max_len)
        lengths[i] = l
        raw = np.frombuffer(f.sequence[:l].encode("latin-1"), np.uint8)
        codes = _NIBBLE_CODE[raw]
        if l % 2:
            codes = np.concatenate([codes, np.zeros(1, np.uint8)])
        packed = (codes[0::2] << 4) | codes[1::2]
        seq[i, :packed.size] = packed
        q = np.frombuffer(f.quality[:l].encode("latin-1"), np.uint8)
        qual[i, :q.size] = q - 33  # quality may be absent (FASTA windows)
    return seq, qual, lengths


def fragments_to_arrays(frags: List[SequencedFragment], max_len: int
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad/truncate reads into fixed shapes for the device:
    (bases [n, max_len] uint8 codes A0 C1 G2 T3 N4 pad5,
     quals [n, max_len] uint8 Phred values, lengths [n] int32)."""
    n = len(frags)
    bases = np.full((n, max_len), 5, dtype=np.uint8)
    quals = np.zeros((n, max_len), dtype=np.uint8)
    lengths = np.zeros(n, dtype=np.int32)
    for i, f in enumerate(frags):
        l = min(len(f.sequence), max_len)
        lengths[i] = l
        seq = np.frombuffer(f.sequence[:l].encode("latin-1"), dtype=np.uint8)
        bases[i, :l] = _BASE_CODE[seq]
        q = np.frombuffer(f.quality[:l].encode("latin-1"), dtype=np.uint8)
        quals[i, :l] = q - 33
    return bases, quals, lengths
