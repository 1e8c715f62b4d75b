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
- gzip'd FASTQ / QSEQ: one span a file (a gzip member cannot be entered
  anywhere but at its start), read as a STREAM of record-aligned text
  chunks (``iter_gzip_text_chunks``): bounded compressed reads, incremental
  inflate, a cut after the last whole record, the tail carried.
"""
from __future__ import annotations

import contextvars
import queue
import threading
import time
import zlib
from typing import Callable, Iterator, List, Optional

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.formats.fasta import find_sequence_start
from hadoop_bam_tpu.formats.fastq import (
    FastqError, find_fastq_record_start, record_fully_visible,
)
from hadoop_bam_tpu.split.planners import plan_byte_ranges
from hadoop_bam_tpu.split.spans import FileByteSpan
from hadoop_bam_tpu.utils.metrics import METRICS
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


def iter_gzip_text_chunks(source, grain: int, lines_per_record: int,
                          fmt: str = "fastq") -> Iterator[bytes]:
    """The inflated text of a gzip'd file as record-aligned chunks of at
    most ``grain`` bytes, in order: inflated incrementally (``zlib``
    releases the interpreter lock), cut after the last whole record
    (``lines_per_record`` lines each, counted from the stream's start),
    the tail carried into the next chunk; records may straddle members.
    What is alive is a chunk and a record's tail, never the file.  A
    record longer than the grain comes whole (the stream inflates on, a
    grain at a time, until it has it).  The end of the file ends the last
    chunk wherever it is (a text that ends inside a record is the
    tokeniser's to refuse).

    Each chunk is one ``<fmt>.inflate_wall`` span — its compressed reads,
    its inflate and its cut — on the calling thread;
    ``<fmt>.inflate_busy_ns`` is that thread's CPU time inside the span
    (waits for the interpreter lock left out)."""
    grain = max(1, int(grain))
    with scoped_byte_source(source) as src:
        gz = _GzipMembers(src, str(getattr(source, "path", source)))
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
            METRICS.count(f"{fmt}.inflate_busy_ns",
                          time.thread_time_ns() - t_cpu)
            METRICS.count(f"{fmt}.compressed_bytes", gz.fetched - fetched)
            METRICS.count(f"{fmt}.stream_members", gz.members - members)
            if chunk:
                METRICS.count(f"{fmt}.inflated_bytes", len(chunk))
                METRICS.count(f"{fmt}.stream_chunks")
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
