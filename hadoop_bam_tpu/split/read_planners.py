"""Span planning + readers for read formats: FASTQ, QSEQ, FASTA.

Rebuild of the getSplits/RecordReader behavior of hb/FastqInputFormat.java,
hb/QseqInputFormat.java, hb/FastaInputFormat.java (SURVEY.md section 2.3):

- FASTQ: plain byte splits; record alignment at read time via the @/+ record
  heuristic (formats/fastq.find_fastq_record_start) — each record belongs to
  the span its first byte starts in.
- QSEQ: one record per line; LineRecordReader semantics
  (split/planners.read_text_span).
- FASTA: splits snapped to ``>`` sequence starts at plan time, so every span
  holds whole contigs and per-fragment positions are well-defined.
- gzip'd FASTQ / QSEQ: one span a file (a gzip member has no index to
  enter it by), read as a STREAM of record-aligned text chunks
  (``iter_gzip_text_chunks``): bounded compressed reads, a cut after the
  last whole record, the tail carried.  The text comes from one of two
  producers, chosen by what the file is: ``_GzipMembers`` (one ``zlib``
  inflate, in order) or ``_SpeculativeMembers`` (the file's DEFLATE
  decoded on several threads, two stages: symbols from a block found by
  its header, then bytes once the text before it is known).
"""
from __future__ import annotations

import collections
import concurrent.futures as cf
import contextvars
import os
import queue
import struct
import threading
import time
import zlib
from typing import Callable, Iterator, List, NamedTuple, Optional, Tuple

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.formats.fasta import find_sequence_start
from hadoop_bam_tpu.formats.fastq import (
    FastqError, find_fastq_record_start, record_fully_visible,
)
from hadoop_bam_tpu.formats.bgzf import is_bgzf
from hadoop_bam_tpu.split.planners import plan_byte_ranges
from hadoop_bam_tpu.split.spans import FileByteSpan
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.pools import (
    NO_LEASE, SPAN_BUFFERS, SpanBuffer, text_inflate_workers,
)
from hadoop_bam_tpu.utils.seekable import as_byte_source, scoped_byte_source

_CHUNK = 1 << 20
GZIP_MAGIC = b"\x1f\x8b"


def read_fastq_span(source, span: FileByteSpan) -> bytes:
    """Bytes of all FASTQ records *starting* in [span.start, span.end)."""
    with scoped_byte_source(source) as src:
        start, end = span.start, span.end
        size = src.size

        # Window from start-1 (line-start context) extended until it contains
        # a record start past `end` (the stop boundary) or EOF.
        lo = max(0, start - 1)
        buf = bytearray()
        fetch_pos = lo
        first_rel: Optional[int] = None
        stop_rel: Optional[int] = None
        while True:
            got = src.pread(fetch_pos, _CHUNK)
            buf += got
            fetch_pos += len(got)
            at_eof = fetch_pos >= size or not got
            if first_rel is None:
                cand = find_fastq_record_start(buf, start - lo)
                # trust a candidate only once its record is fully in view
                # (a truncated tail can validate a false start) — unless EOF
                if cand is not None and (at_eof
                                         or record_fully_visible(buf, cand)):
                    first_rel = cand
                elif not at_eof:
                    continue
            if first_rel is not None and fetch_pos >= end:
                stop_rel = find_fastq_record_start(buf,
                                                   max(end - lo, first_rel))
                if stop_rel is not None and not at_eof \
                        and not record_fully_visible(buf, stop_rel):
                    stop_rel = None
                    continue  # fetch more before trusting the stop boundary
                if stop_rel is not None or at_eof:
                    break
            if at_eof:
                break
        if first_rel is None or first_rel >= end - lo:
            return b""
        if stop_rel is None:
            out = bytes(buf[first_rel:])
            if not out.endswith(b"\n"):
                out += b"\n"
            return out
        return bytes(buf[first_rel:stop_rel])


def plan_fasta_spans(path: str, *, num_spans: Optional[int] = None,
                     span_bytes: Optional[int] = None,
                     config: HBamConfig = DEFAULT_CONFIG) -> List[FileByteSpan]:
    """Byte ranges snapped forward to ``>`` header-line starts."""
    src = as_byte_source(path)
    try:
        size = src.size
        ranges = plan_byte_ranges(size, num_spans=num_spans,
                                  span_bytes=span_bytes if span_bytes
                                  else (None if num_spans else config.split_size))
        bounds: List[int] = []
        for (bstart, _bend) in ranges:
            if bstart == 0:
                bounds.append(0)
                continue
            # scan forward for "\n>" (whole-file read windows)
            snapped = size
            pos = bstart
            while pos < size:
                win = src.pread(max(0, pos - 1), _CHUNK + 1)
                rel = find_sequence_start(win, pos - max(0, pos - 1))
                if rel is not None:
                    snapped = max(0, pos - 1) + rel
                    break
                pos += _CHUNK
            bounds.append(snapped)
        bounds.append(size)
        spans = []
        for i in range(len(bounds) - 1):
            s, e = bounds[i], bounds[i + 1]
            if s < e:
                spans.append(FileByteSpan(path, s, e))
        return spans
    finally:
        src.close()


def read_fasta_span(source, span: FileByteSpan) -> bytes:
    """Raw bytes of a sequence-aligned FASTA span (whole contigs)."""
    with scoped_byte_source(source) as src:
        out = bytearray()
        pos = span.start
        while pos < span.end:
            got = src.pread(pos, min(_CHUNK, span.end - pos))
            if not got:
                break
            out += got
            pos += len(got)
        return bytes(out)


# ---------------------------------------------------------------------------
# gzip'd text as a stream of record-aligned chunks
# ---------------------------------------------------------------------------

class _GzipMembers:
    """Incremental inflate of a file of gzip members, one after another
    (a ``cat`` of lanes, a ``bgzip``ped file, an empty member): compressed
    bytes are fetched ``_CHUNK`` at a time, ``read(n)`` returns at most
    ``n`` inflated bytes and ``b""`` once the last member has ended.

    zlib (``wbits=31``) parses each member's header and checks its CRC32
    and ISIZE trailer.  A trailer that disagrees, a file that ends inside
    a member, and bytes after a member that start no member all raise
    ``FastqError`` naming the member and its offset."""

    def __init__(self, src, name: str):
        self._src, self._name = src, name
        self.fetched = 0                # compressed bytes read so far
        self._pending = b""             # fetched, not yet inflated
        self.members = 0
        self._member_off = 0
        self._z = zlib.decompressobj(wbits=31)

    def _fetch(self) -> bool:
        got = self._src.pread(self.fetched, _CHUNK)
        self.fetched += len(got)
        self._pending += got
        return bool(got)

    def _where(self) -> str:
        return (f"{self._name}: gzip member {self.members - 1} "
                f"(offset {self._member_off})")

    def _next_member(self) -> None:
        if len(self._pending) < 2:
            self._fetch()
        self._member_off = self.fetched - len(self._pending)
        if not self._pending.startswith(GZIP_MAGIC):
            raise FastqError(
                f"{self._name}: the bytes at offset {self._member_off}, "
                f"after gzip member {self.members - 1}, start no gzip "
                f"member")
        self.members += 1
        self._z = zlib.decompressobj(wbits=31)

    def read(self, n: int) -> bytes:
        while True:
            if self._z.eof or not self.members:
                if not (self._pending or self._fetch()):
                    return b""
                self._next_member()
            at_end = not (self._pending or self._fetch())
            try:
                out = self._z.decompress(self._pending, n)
            except zlib.error as e:
                raise FastqError(f"{self._where()} does not inflate: "
                                 f"{e}") from None
            self._pending = self._z.unused_data if self._z.eof \
                else self._z.unconsumed_tail
            if out:
                return out
            if at_end and not self._z.eof:
                raise FastqError(
                    f"{self._where()} is cut short at byte {self.fetched}: "
                    f"the file is truncated")


# A speculative chunk is this many compressed bytes: scaled to the file
# between the two, so that a small file still has several and a large one
# holds a bounded number of symbols per chunk in flight.
_SPEC_CHUNK_MIN = 1 << 16
_SPEC_CHUNK_MAX = 1 << 18
# What a worker reads past its chunk's nominal end: the block that
# straddles the edge (zlib's hold 16 Ki symbols, ~30 KB of FASTQ).
_SPEC_SLACK = 1 << 17
# Symbols a chunk may decode to before it stops at the next block boundary
# (what stops short is carried on from there, in order), as a multiple of
# its compressed bytes; and the room past that for the block in hand.
_SPEC_RATIO = 8
_SPEC_BLOCK_ROOM = 1 << 22


def gzip_speculation(src) -> Optional[Tuple[int, int]]:
    """(compressed bytes a speculative chunk, inflate workers) for the
    gzip'd source ``src``, or ``None`` where its one inflate stays
    serial: a host with one CPU, a build without the native library, a
    file shorter than two chunks, a BGZF file (its members are 64 KiB at
    most: nothing to speculate inside).  Decided by the input alone:
    ``utils/pools.py::text_inflate_workers`` says how many of the host's
    CPUs inflate, beside the tokenisers of ``text_stream_window``."""
    if (os.cpu_count() or 1) < 2 or not native.available():
        return None
    workers = text_inflate_workers()
    size = src.size
    chunk = min(_SPEC_CHUNK_MAX,
                max(_SPEC_CHUNK_MIN, size // (4 * (workers + 1))))
    if size < 2 * chunk or is_bgzf(src.pread(0, 1 << 16)):
        return None
    return chunk, workers


def _gzip_header_end(head: bytes) -> Optional[int]:
    """Length of the gzip member header ``head`` opens with (RFC 1952:
    ten bytes, then FEXTRA, FNAME, FCOMMENT, FHCRC as FLG says), or
    ``None`` where ``head`` ends inside it."""
    if len(head) < 10:
        return None
    flags, pos = head[3], 10
    if flags & 4:                                               # FEXTRA
        if pos + 2 > len(head):
            return None
        pos += 2 + struct.unpack_from("<H", head, pos)[0]
    for bit in (8, 16):                                 # FNAME, FCOMMENT
        if flags & bit:
            pos = head.find(b"\0", pos) + 1
            if not pos:
                return None
    if flags & 2:                                                # FHCRC
        pos += 2
    return pos if pos <= len(head) else None


class _Spec(NamedTuple):
    """What a worker made of its chunk: the block it found, where its
    decode stopped, whether that was the member's final block, the
    symbols between and the lease of the buffer they lie in."""
    found: int
    end: int
    final: bool
    symbols: "object"
    lease: SpanBuffer


class _SpeculativeMembers:
    """``_GzipMembers`` on several threads: the same ``fetched`` and
    ``members``, the same members accepted and refused (each trailer's
    CRC32 and ISIZE checked), the same chunks (``chunks`` is its
    ``_record_chunks``), the DEFLATE decoded by ``workers`` threads named
    ``hbam-inflate_<i>`` through the native library's two-stage decoder.

    The FILE's compressed bytes are cut into nominal chunks of ``chunk``
    bytes.  A worker takes chunk ``j > 0`` with the text before it
    unknown: it finds the first dynamic-Huffman block header in the chunk
    and decodes from there into 16-bit symbols — a byte, or a mark for a
    byte of the unknown 32 KiB window — up to the first block boundary at
    or past chunk ``j + 1``.  This thread walks the file in order from
    the one position where everything is known, a member's first block:
    where the position is the block a worker found, the worker's symbols
    are the text from there (``<fmt>.inflated_bytes_parallel``) — their
    last 32 KiB are resolved here against the window before them, which
    gives the next window, the whole chunk on a worker, which also takes
    its CRC32; the pieces' CRCs are combined in order.  Where it is not
    (a stored or fixed block at the edge, a false start, no header found,
    a chunk that stopped short) the stretch up to the next found block
    is decoded here from the true bit with the known window, and a chunk
    whose block the position has passed is thrown away
    (``<fmt>.inflate_respeculated_chunks``) — before any of its text was
    handed on.  A final block ends a member wherever a chunk edge lies:
    the trailer is checked once the member's last piece is resolved, the
    next header parsed, and the next member starts with an empty window.

    At most ``workers + 1`` chunks are decoded ahead of the position and
    two resolved behind it, in buffers leased from ``SPAN_BUFFERS`` (a
    fresh one is faulted in page by page, and page faults do not run in
    parallel).  ``alive.add(n)``, where given, is told of what the
    buffers hold: 2 B a symbol until its chunk is resolved, then the
    text until it is copied into the chunk that ``chunks`` yields."""

    def __init__(self, src, name: str, fmt: str, chunk: int, workers: int,
                 alive=None):
        self._src, self._name, self._fmt = src, name, fmt
        self._chunk, self._depth = int(chunk), int(workers) + 1
        self._room = _SPEC_RATIO * self._chunk     # symbols, then it stops
        self._alive = alive
        self.fetched = 0
        self.members = 0
        self._member_off = 0
        self._pool = cf.ThreadPoolExecutor(
            max_workers=int(workers), thread_name_prefix="hbam-inflate")

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)

    def chunks(self, grain: int, lines_per_record: int) -> Iterator[bytes]:
        """``iter_gzip_text_chunks``'s chunks, the same cuts as the one
        inflate's, from the workers' pieces.  The workers count each
        piece's line ends, so a chunk is its pieces copied once — the
        tail before them, the last of them up to the last whole record,
        found from its end — and only the head of the piece a grain
        divides is counted here."""
        fmt, pieces = self._fmt, self._text()
        tail, tail_eols, held, index, ended = b"", 0, None, 0, False
        try:
            while not ended:
                t_cpu, fetched, members = \
                    time.thread_time_ns(), self.fetched, self.members
                with METRICS.span(f"{fmt}.inflate_wall", chunk=index) as late:
                    parts, leases = [tail], []
                    have, eols, room = len(tail), tail_eols, grain
                    while True:
                        while have < room:
                            if held is None:
                                held = next(pieces, None)
                                if held is None:
                                    ended = True
                                    break
                            view, lease, n = held
                            held = None
                            if len(view) > room - have:
                                head = view[:room - have]
                                held = (view[len(head):], lease,
                                        n - bytes(head).count(b"\n"))
                                view, lease, n = head, NO_LEASE, n - held[2]
                            parts.append(view)
                            leases.append(lease)
                            have, eols = have + len(view), eols + n
                        if ended:
                            chunk, tail = b"".join(parts), b""
                            break
                        cut = _cut_parts(parts, eols, lines_per_record)
                        if cut is not None:
                            chunk, tail, tail_eols = cut
                            break
                        # a record longer than the grain: gather on
                        room += grain
                    self._held(len(tail) - have)
                    for lease in leases:
                        lease.release()
                    late["bytes"] = len(chunk)
                _count_chunk(fmt, self, t_cpu, fetched, members, chunk)
                if chunk:
                    index += 1
                    yield chunk
        finally:
            pieces.close()

    # -- the threads' work ------------------------------------------------

    def _held(self, n: int) -> None:
        if self._alive is not None and n:
            self._alive.add(n)

    def _submit(self, fn, *args) -> cf.Future:
        return self._pool.submit(contextvars.copy_context().run,
                                 self._timed, fn, *args)

    def _timed(self, fn, *args):
        t_cpu = time.thread_time_ns()
        try:
            with METRICS.span(f"{self._fmt}.inflate_wall",
                              stage=fn.__name__.lstrip("_")):
                return fn(*args)
        finally:
            METRICS.count(f"{self._fmt}.inflate_busy_ns",
                          time.thread_time_ns() - t_cpu)

    def _read(self, offset: int, size: int) -> bytes:
        """``size`` bytes at ``offset``, fewer only at the file's end (a
        source may answer a read short)."""
        got = self._src.pread(offset, size)
        if len(got) == size or not got:
            return got
        parts, have = [got], len(got)
        while have < size:
            got = self._src.pread(offset + have, size - have)
            if not got:
                break
            parts.append(got)
            have += len(got)
        return b"".join(parts)

    def _window(self, offset: int, size: int):
        """``_read`` into a leased buffer where the source can fill one
        (the workers' compressed windows: a fresh one a task is a mapping
        made and unmade a task): (bytes-like, lease)."""
        if self._src.pread_into is None:
            return self._read(offset, size), NO_LEASE
        lease = SPAN_BUFFERS.lease(size)
        got = self._src.pread_into(offset, lease.array[:size])
        return lease.array[:got], lease

    def _decode(self, data, start: int, stop: int,
                window: Optional[bytes], room: int):
        """``native.deflate_decode_symbols`` into a leased buffer with
        ``room`` symbols of room: (status, end bit, symbols, lease)."""
        lease = SPAN_BUFFERS.lease(
            2 * (native.DEFLATE_WINDOW + room + native.DEFLATE_SLACK))
        rc, end, symbols = native.deflate_decode_symbols(
            data, start, stop, self._room, window,
            lease.array.view("<u2"))
        if rc < 0:
            lease.release()
        else:
            self._held(2 * symbols.size)
        return rc, end, symbols, lease

    def _speculate(self, j: int) -> Optional[_Spec]:
        """Chunk ``j`` with the text before it unknown; ``None`` where
        it holds no block header or what follows one does not decode."""
        lo = j * self._chunk
        data, window = self._window(lo, self._chunk + _SPEC_SLACK)
        try:
            found = native.deflate_find_block(
                data, 0, 8 * min(self._chunk, len(data)))
            if found < 0:
                return None
            rc, end, symbols, lease = self._decode(
                data, found, 8 * self._chunk, None,
                self._room + _SPEC_BLOCK_ROOM)
        finally:
            window.release()
        if rc < 0:
            return None
        return _Spec(8 * lo + found, 8 * lo + end, rc == 1, symbols, lease)

    def _resolve(self, symbols, lease: SpanBuffer, window: Optional[bytes]):
        """A chunk's (bytes, CRC32, line ends, lease of the bytes'
        buffer); its symbols' buffer goes back."""
        out = SPAN_BUFFERS.lease(symbols.size)
        text, crc, eols = native.deflate_resolve(symbols, window, out.array)
        lease.release()
        self._held(-symbols.size)       # 2 B a symbol -> 1 B a byte
        return text, crc, eols, out

    # -- the walk, in order -------------------------------------------------

    def _where(self) -> str:
        return (f"{self._name}: gzip member {self.members - 1} "
                f"(offset {self._member_off})")

    def _cut_short(self) -> FastqError:
        return FastqError(
            f"{self._where()} is cut short at byte {self._src.size}: "
            f"the file is truncated")

    def _member_header(self, off: int) -> int:
        """The member header at byte ``off`` (RFC 1952), refused where
        zlib refuses it; the bit offset of the member's first block."""
        head = self._read(off, 1 << 10)
        if not head.startswith(GZIP_MAGIC):
            raise FastqError(
                f"{self._name}: the bytes at offset {off}, after gzip "
                f"member {self.members - 1}, start no gzip member")
        self.members += 1
        self._member_off = off
        while True:
            if len(head) >= 4 and (head[2] != 8 or head[3] & 0xe0):
                raise FastqError(
                    f"{self._where()} does not inflate: unknown "
                    f"compression method or header flags")
            end = _gzip_header_end(head)
            if end is not None:
                break
            if off + len(head) >= self._src.size:
                raise self._cut_short()
            head = self._read(off, 2 * len(head))
        if head[3] & 2 and struct.unpack_from("<H", head, end - 2)[0] \
                != zlib.crc32(head[:end - 2]) & 0xffff:
            raise FastqError(f"{self._where()} does not inflate: header "
                             f"crc mismatch")
        return 8 * (off + end)

    def _serial(self, cur: int, target: int, window: bytes):
        """Decode from bit ``cur``, the ``window`` before it known, to
        the first block boundary at or past ``target`` — or short of it,
        at a boundary, where that is much text.  (status, end bit,
        symbols, lease): status 1 at the member's final block."""
        lo, room = cur >> 3, self._room + _SPEC_BLOCK_ROOM
        want = min(max(0, (target >> 3) - lo), self._chunk) + _SPEC_SLACK
        while True:
            data, held = self._window(lo, want)
            rc, end, symbols, lease = self._decode(
                data, cur - 8 * lo, target - 8 * lo, window, room)
            held.release()
            if rc == -1:
                raise FastqError(f"{self._where()} does not inflate: "
                                 f"invalid deflate data")
            if rc == -2:
                room *= 2
            elif rc == -3:
                if lo + len(data) >= self._src.size:
                    raise self._cut_short()
                want *= 2
            else:
                return rc, 8 * lo + end, symbols, lease

    def _text(self) -> Iterator[Tuple[memoryview, SpanBuffer, int]]:
        """The file's text in order, a piece a decoded stretch: (view,
        lease of the buffer it lies in, its line ends)."""
        size, chunk, fmt = self._src.size, self._chunk, self._fmt
        n_chunks = -(-size // chunk)
        specs: dict = {}                # chunk -> its worker's future
        submitted = 1                   # chunk 0 opens with a member
        pending = collections.deque()   # resolved or being resolved
        m_crc = m_len = 0               # of the member being handed on

        def drain(keep: int) -> Iterator[Tuple[memoryview, SpanBuffer, int]]:
            """Hand on the oldest pieces until ``keep`` are pending; a
            member's trailer is checked behind its last piece."""
            nonlocal m_crc, m_len
            while len(pending) > keep:
                entry = pending.popleft()
                if len(entry) == 2:
                    (text, crc, eols, lease), self.fetched = \
                        entry[0].result(), entry[1]
                    m_crc = native.crc32_combine(m_crc, crc, text.size) \
                        if m_len else crc
                    m_len += text.size
                    yield memoryview(text), lease, eols
                    continue
                want_crc, want_len, where, self.fetched = entry
                for what, want, got in (
                        ("data check (CRC32)", want_crc, m_crc),
                        ("length check (ISIZE)", want_len,
                         m_len & 0xffffffff)):
                    if want != got:
                        pending.clear()     # nothing past a fault goes on
                        raise FastqError(f"{where} does not inflate: "
                                         f"incorrect {what}")
                m_crc = m_len = 0

        byte, k, fault = 0, 1, None
        try:
            while byte < size:
                cur, window, final = self._member_header(byte), b"", False
                while not final:
                    # the next chunk whose block the position has not
                    # passed; the ones before it are thrown away
                    spec = None
                    while k < n_chunks:
                        while submitted < min(n_chunks, k + self._depth):
                            specs[submitted] = self._submit(
                                self._speculate, submitted)
                            submitted += 1
                        spec = specs[k].result()
                        if spec is not None and spec.found >= cur:
                            break
                        if spec is not None:
                            self._held(-2 * spec.symbols.size)
                            spec.lease.release()
                        METRICS.count(f"{fmt}.inflate_respeculated_chunks")
                        del specs[k]
                        k, spec = k + 1, None
                    if spec is not None and spec.found == cur:
                        del specs[k]
                        k += 1
                        METRICS.count(f"{fmt}.inflated_bytes_parallel",
                                      spec.symbols.size)
                        # the next window from this one, here; the whole
                        # chunk, and its CRC, on a worker
                        tail = native.deflate_resolve(
                            spec.symbols[-native.DEFLATE_WINDOW:], window)[0]
                        piece = self._submit(self._resolve, spec.symbols,
                                             spec.lease, window)
                        cur, final = spec.end, spec.final
                    else:
                        # up to the block it found (or the member's end)
                        # from the true bit, the window known
                        rc, cur, symbols, lease = self._serial(
                            cur, 1 << 62 if spec is None else spec.found,
                            window)
                        done = self._resolve(symbols, lease, None)
                        piece = cf.Future()
                        piece.set_result(done)
                        tail, final = done[0], rc == 1
                    pending.append((piece, (cur + 7) >> 3))
                    window = (window + tail[-native.DEFLATE_WINDOW:]
                              .tobytes())[-native.DEFLATE_WINDOW:]
                    yield from drain(2)
                trailer = self._read((cur + 7) >> 3, 8)
                if len(trailer) < 8:
                    raise self._cut_short()
                byte = ((cur + 7) >> 3) + 8
                pending.append(struct.unpack("<II", trailer)
                               + (self._where(), byte))
        except FastqError as e:
            fault = e
        # what lies before a fault in the file is handed on, and checked,
        # before the fault is raised
        yield from drain(0)
        if fault is not None:
            raise fault


def _record_cut(buf: bytes, lines_per_record: int) -> int:
    """Length of the longest prefix of ``buf`` made of whole records,
    ``buf`` starting at a record's first byte: the lines are counted,
    never guessed from ``@`` / ``+`` leads (0: not one whole record)."""
    cut = len(buf)
    for _ in range(buf.count(b"\n") % lines_per_record + 1):
        cut = buf.rfind(b"\n", 0, cut)
        if cut < 0:
            return 0
    return cut + 1


# How far from a piece's end its last record boundary is looked for before
# the pieces are joined and cut as a whole.
_CUT_PROBE = 1 << 16


def _cut_parts(parts: list, eols: int, lines_per_record: int
               ) -> Optional[Tuple[bytes, bytes, int]]:
    """``_record_cut`` of ``b"".join(parts)``, which holds ``eols`` line
    ends, without the join where the boundary lies near the end of the
    last part: (the whole records, the tail, the tail's line ends), or
    ``None`` where there is not one whole record."""
    past = eols % lines_per_record      # line ends behind the boundary
    last = parts[-1]
    probe = bytes(last[-_CUT_PROBE:])
    pos = len(probe)
    for _ in range(past + 1):
        pos = probe.rfind(b"\n", 0, pos)
        if pos < 0:
            break
    else:
        keep = len(last) - len(probe) + pos + 1
        return (b"".join(parts[:-1] + [last[:keep]]), bytes(last[keep:]),
                past)
    buf = b"".join(parts)
    cut = _record_cut(buf, lines_per_record)
    return (buf[:cut], buf[cut:], past) if cut else None


def _count_chunk(fmt: str, gz, t_cpu: int, fetched: int, members: int,
                 chunk: bytes) -> None:
    """The stream thread's counters of one chunk: its CPU time since
    ``t_cpu``, what ``gz`` consumed since ``fetched`` / ``members``."""
    METRICS.count(f"{fmt}.inflate_busy_ns", time.thread_time_ns() - t_cpu)
    METRICS.count(f"{fmt}.compressed_bytes", gz.fetched - fetched)
    METRICS.count(f"{fmt}.stream_members", gz.members - members)
    if chunk:
        METRICS.count(f"{fmt}.inflated_bytes", len(chunk))
        METRICS.count(f"{fmt}.stream_chunks")


def iter_gzip_text_chunks(source, grain: int, lines_per_record: int,
                          fmt: str = "fastq", alive=None) -> Iterator[bytes]:
    """The inflated text of a gzip'd file as record-aligned chunks of at
    most ``grain`` bytes, in order: cut after the last whole record
    (``lines_per_record`` lines each, counted from the stream's start),
    the tail carried into the next chunk; records may straddle members.
    What is alive is a chunk and a record's tail, never the file.  A
    record longer than the grain comes whole (the stream inflates on, a
    grain at a time, until it has it).  The end of the file ends the last
    chunk wherever it is (a text that ends inside a record is the
    tokeniser's to refuse).

    Who inflates is decided by the file (``gzip_speculation``): one
    ``zlib`` inflate on the calling thread, or — a file of two
    speculative chunks or more, a host with CPUs to spare, the native
    library — ``_SpeculativeMembers``' workers, this thread walking their
    pieces in order.  Either way the chunks are the same bytes, and
    ``alive.add(n)`` is told of the buffers the workers hold.

    Each chunk is one ``<fmt>.inflate_wall`` span on the calling thread
    — its compressed reads, its inflate (or its wait for the workers'
    pieces), its cut and its copy — and each worker's task another;
    ``<fmt>.inflate_busy_ns`` is the CPU time of all of them inside
    their spans (waits for the interpreter lock left out)."""
    grain = max(1, int(grain))
    name = str(getattr(source, "path", source))
    with scoped_byte_source(source) as src:
        how = gzip_speculation(src)
        if how is None:
            yield from _record_chunks(_GzipMembers(src, name), grain,
                                      lines_per_record, fmt)
            return
        gz = _SpeculativeMembers(src, name, fmt, *how, alive=alive)
        try:
            yield from gz.chunks(grain, lines_per_record)
        finally:
            gz.close()


def _record_chunks(gz: _GzipMembers, grain: int, lines_per_record: int,
                   fmt: str) -> Iterator[bytes]:
    """``iter_gzip_text_chunks`` over the one inflate's ``read(n)``."""
    tail, index, ended = b"", 0, False
    while not ended:
        t_cpu, fetched, members = \
            time.thread_time_ns(), gz.fetched, gz.members
        with METRICS.span(f"{fmt}.inflate_wall", chunk=index) as late:
            parts, have, room = [tail], len(tail), grain
            while True:
                while have < room:
                    piece = gz.read(room - have)
                    if not piece:
                        ended = True
                        break
                    parts.append(piece)
                    have += len(piece)
                buf = b"".join(parts)
                cut = len(buf) if ended \
                    else _record_cut(buf, lines_per_record)
                if cut or ended:
                    break
                # a record longer than the grain: inflate on
                parts, room = [buf], room + grain
            chunk, tail = buf[:cut], buf[cut:]
            late["bytes"] = len(chunk)
        _count_chunk(fmt, gz, t_cpu, fetched, members, chunk)
        if chunk:
            index += 1
            yield chunk


_END = object()


def iter_on_thread(make_iter: Callable[[], Iterator], name: str
                   ) -> Iterator:
    """``make_iter()``'s items, made on a thread of their own named
    ``name``, one item ahead of the consumer: the thread starts the next
    item only when the consumer has taken the one before (it takes the
    one slot before it starts an item, the consumer gives the slot back
    when it takes the item).  The thread
    starts at the first ``next`` and runs in a copy of that caller's
    context, so its spans and counters land where the caller's do.  What
    the producer raises is raised here, in order; closing this generator
    stops the producer after the item in hand and joins it."""
    q: "queue.SimpleQueue" = queue.SimpleQueue()
    slots = threading.Semaphore(1)
    stop = threading.Event()

    def produce() -> None:
        it = make_iter()
        try:
            while True:
                while not slots.acquire(timeout=0.05):
                    if stop.is_set():
                        return
                if stop.is_set():
                    return
                try:
                    q.put((next(it), None))
                except StopIteration:
                    q.put((_END, None))
                    return
        except BaseException as e:  # noqa: BLE001 — crosses the thread
            q.put((_END, e))
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()

    ctx = contextvars.copy_context()
    thread = threading.Thread(target=lambda: ctx.run(produce), name=name,
                              daemon=True)
    thread.start()
    try:
        while True:
            item, err = q.get()
            if err is not None:
                raise err
            if item is _END:
                return
            slots.release()
            yield item
    finally:
        stop.set()
        thread.join()
