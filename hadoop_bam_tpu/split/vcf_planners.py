"""VCF/BCF span planning + span readers: the getSplits layer for variants.

Rebuild of hb/VCFInputFormat.java's split behavior (SURVEY.md section 3.4):

- text ``.vcf``: plain byte splits, line-aligned at read time (LineRecordReader
  semantics — split/planners.read_text_span).
- ``.vcf.gz`` (BGZF): splittable via BGZF block alignment — the
  hb/util/BGZFCodec.java [VER? 7.8] + LineRecordReader path.  Spans are
  *compressed* byte ranges snapped to confirmed BGZF block starts; ownership
  of a line that starts exactly on a block boundary is resolved by probing the
  previous block's final byte, so the union of all spans yields each line
  exactly once at every possible boundary.
- ``.bcf`` (BGZF or raw): record-aligned virtual-offset spans via
  hb/BCFSplitGuesser (split/bcf_guesser.py).
"""
from __future__ import annotations

import contextlib
import struct
from typing import Iterator, List, Optional, Tuple, Union

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.formats.bcf import BCFRecordCodec
from hadoop_bam_tpu.formats.bcfio import read_bcf_header
from hadoop_bam_tpu.formats.vcf import VCFHeader, VcfRecord
from hadoop_bam_tpu.ops import inflate as inflate_ops
from hadoop_bam_tpu.split.bcf_guesser import BCFSplitGuesser
from hadoop_bam_tpu.split.bgzf_guesser import BGZFSplitGuesser
from hadoop_bam_tpu.split.planners import plan_byte_ranges
from hadoop_bam_tpu.split.spans import FileByteSpan, FileVirtualSpan
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.pools import NO_LEASE, SPAN_BUFFERS, SpanBuffer
from hadoop_bam_tpu.utils.seekable import as_byte_source, scoped_byte_source


# ---------------------------------------------------------------------------
# BGZF-compressed text (.vcf.gz): block-aligned spans
# ---------------------------------------------------------------------------

def plan_bgzf_text_spans(path: str, *, num_spans: Optional[int] = None,
                         span_bytes: Optional[int] = None,
                         config: HBamConfig = DEFAULT_CONFIG
                         ) -> List[FileByteSpan]:
    """Compressed byte ranges snapped to confirmed BGZF block starts."""
    src = as_byte_source(path)
    try:
        size = src.size
        ranges = plan_byte_ranges(size, num_spans=num_spans,
                                  span_bytes=span_bytes if span_bytes
                                  else (None if num_spans else config.split_size))
        guesser = BGZFSplitGuesser(src)
        bounds: List[int] = []
        for (bstart, _bend) in ranges:
            if bstart == 0:
                bounds.append(0)
                continue
            b = guesser.guess_next_block_start(bstart)
            bounds.append(size if b is None else b)
        bounds.append(size)
        spans = []
        for i in range(len(bounds) - 1):
            s, e = bounds[i], bounds[i + 1]
            if s < e:
                spans.append(FileByteSpan(path, s, e))
        return spans
    finally:
        src.close()


def _prev_block_last_byte(src, coffset: int) -> Optional[int]:
    """Final inflated byte of the BGZF block that ends exactly at
    ``coffset`` (None when it cannot be located or is empty)."""
    lo = max(0, coffset - bgzf.MAX_BLOCK_SIZE)
    win = src.pread(lo, coffset - lo + bgzf.HEADER_SIZE)
    arr = np.frombuffer(win[:coffset - lo], dtype=np.uint8)
    for cand in bgzf.find_block_starts_numpy(arr):
        c = lo + int(cand)
        try:
            info = bgzf.parse_block_header(win, int(cand))
        except bgzf.BGZFError:
            continue
        if c + info.block_size == coffset:
            try:
                data = bgzf.inflate_block(win, info, check_crc=False)
            except bgzf.BGZFError:
                continue
            return data[-1] if data else None
    return None


def read_bgzf_text_span(source, span: FileByteSpan) -> bytes:
    """All text lines *starting* within the span's compressed block range,
    as memory of the caller's own — ``bgzf_text_span`` without the
    lease."""
    with bgzf_text_span(source, span) as text:
        return bytes(text)


@contextlib.contextmanager
def bgzf_text_span(source, span: FileByteSpan
                   ) -> Iterator[memoryview]:
    """``with bgzf_text_span(...) as text``: all text lines *starting*
    within the span's compressed block range — the input of the text
    tokeniser (parallel/variant_pipeline.pack_variant_tiles_from_text);
    ``bgzf_text_span_lines`` without the count of its records."""
    with bgzf_text_span_lines(source, span) as (text, _records):
        yield text


@contextlib.contextmanager
def bgzf_text_span_lines(source, span: FileByteSpan
                         ) -> Iterator[Tuple[memoryview, int]]:
    """``with bgzf_text_span_lines(...) as (text, records)``: the lines of
    ``bgzf_text_span`` and the count of record lines among them, or -1
    where the read did not count them.

    A line belongs to the span in which its first byte lies: the span
    whose blocks [span.start, span.end) hold that byte.  So the partial
    line at the span's head is the previous span's (told by the last byte
    of the block before ``span.start``), the span's last line is read to
    its end in the blocks that follow, the first span owns the header
    lines, and the union of a plan's spans is every line of the file
    exactly once, whatever the span count.

    The host decides the path.  With the native library the span is read
    by native calls with the interpreter lock released
    (``_lease_bgzf_text``): then ``text`` is a view of a buffer leased
    from the span-buffer pool, good until the ``with`` ends — whatever
    outlives it must be a copy.  Without it the blocks are inflated one by
    one in Python (``_inflate_text_python``, ``_prev_block_last_byte``,
    ``_owned_text``: the statement of the rules) and ``text`` is a view of
    their ``bytes``.  Counters: ``vcf.native_read_spans`` /
    ``vcf.python_read_spans``."""
    with scoped_byte_source(source) as src:
        leased = _lease_bgzf_text(src, span)
        if leased is None:
            METRICS.count("vcf.python_read_spans")
            buf, base_len = _inflate_text_python(src, span)
            skip_first = False
            if span.start > 0 and base_len:
                prev = _prev_block_last_byte(src, span.start)
                skip_first = prev is not None and prev != 0x0A
            lo, hi = _owned_text(buf, base_len, skip_first)
            yield memoryview(buf)[lo:hi], -1
            return
        METRICS.count("vcf.native_read_spans")
        lease, text, records = leased
        try:
            yield text, records
        finally:
            lease.release()


def _owned_text(buf, base_len: int, skip_first: bool) -> Tuple[int, int]:
    """[lo, hi) of ``buf`` that is the lines whose first byte lies in
    ``buf[:base_len]`` (the span's own blocks; what follows is the tail
    read to finish its last line).  ``skip_first``: the first line began
    in a block before the span."""
    view = memoryview(buf)
    n = len(view)
    lo = 0
    if skip_first:
        lo = _find_newline(view, 0, n) + 1
        if lo == 0 or lo >= base_len:       # one line covers the span
            return 0, 0
    if base_len == 0:
        return 0, 0
    if view[base_len - 1] == 0x0A:
        return lo, base_len
    nl = _find_newline(view, base_len, n)
    return lo, n if nl < 0 else nl + 1


def _find_newline(view: memoryview, start: int, stop: int) -> int:
    """Index of the first newline in ``view[start:stop]``, or -1."""
    step = 1 << 16                  # a line's end is near: look a bit at a time
    while start < stop:
        end = min(stop, start + step)
        at = bytes(view[start:end]).find(b"\n")
        if at >= 0:
            return start + at
        start, step = end, step * 4
    return -1


def _tail_blocks(src, coffset: int) -> Iterator[bytes]:
    """The inflated blocks from ``coffset`` on, one at a time (the tail of
    a span's last line: nearly always the one block after the span)."""
    while coffset < src.size:
        head = src.pread(coffset, bgzf.MAX_BLOCK_SIZE)
        info = bgzf.parse_block_header(head, 0)
        yield bgzf.inflate_block(head, info, check_crc=False)
        coffset += info.block_size


def _inflate_text_python(src, span: FileByteSpan) -> Tuple[bytes, int]:
    """The span's blocks inflated one by one with ``zlib`` (the host has
    no native library, or the native read declined): (their text + the
    blocks that finish the last line, the length of the span's own text).
    One ``pread`` of the span's compressed range (and a block's worth
    more: a span that does not end on a block start still gets its last
    block whole), one a tail block."""
    want = max(0, min(span.end, src.size) - span.start)
    raw = src.pread(span.start, want + bgzf.MAX_BLOCK_SIZE)
    chunks: List[bytes] = []
    off = 0
    while off < want:
        info = bgzf.parse_block_header(raw, off)
        chunks.append(bgzf.inflate_block(raw, info, check_crc=False))
        off += info.block_size
    base = b"".join(chunks)
    if not base or base.endswith(b"\n"):
        return base, len(base)
    chunks = [base]
    for ext in _tail_blocks(src, span.start + off):
        chunks.append(ext)
        if b"\n" in ext:
            break
    return b"".join(chunks), len(base)


# room leased behind a span's own text for the blocks that finish its last
# line: four blocks, where a line of a 3,202-sample GATK call set is ~83 KB
_TAIL_ROOM = 4 * bgzf.MAX_BLOCK_SIZE


def _lease_bgzf_text(src, span: FileByteSpan
                     ) -> Optional[Tuple[SpanBuffer, memoryview, int]]:
    """A BGZF text span read by native calls with the interpreter lock
    released (``utils/native.py::vcf_text_span_read``): ONE positioned
    read of the span's compressed range with a block's worth before it and
    after it, then ``hbam_vcf_text_span_read`` twice — the header walk
    that sizes the text, then the inflate of the span's blocks into a
    buffer leased from the span-buffer pool, the probe of the block
    before, the blocks after inflated until the last line ends, the trim
    to the lines the span owns and the count of its record lines.  ISIZE
    is verified and CRC32 is not, as ``bgzf.inflate_block(check_crc=
    False)`` does.  A last line that runs past what that read holds or
    the lease's room takes is finished block by block in Python and its
    records are not counted (``vcf.text_span_tail_spans``).

    Returns (lease, the span's own lines, their record lines or -1); the
    caller releases the lease.  Returns None — nothing leased, the Python
    path decides and raises its own ``BGZFError`` — without the native
    library, for an empty span and when the block chain does not parse or
    inflate."""
    end = min(span.end, src.size)
    if not native.available() or span.start >= end:
        return None
    r0 = max(0, span.start - bgzf.MAX_BLOCK_SIZE)
    lease = raw_lease = NO_LEASE
    try:
        with METRICS.span("bam.fetch_wall", nbytes=end - span.start):
            raw, raw_lease = _pread_leased(
                src, r0, min(src.size, end + bgzf.MAX_BLOCK_SIZE) - r0)
        args = (raw, span.start - r0, end - span.start, src.size - r0)
        rc, info = native.vcf_text_span_read(*args, None)
        if rc == 1:
            lease = SPAN_BUFFERS.lease(info[0] + _TAIL_ROOM)
            rc, info = native.vcf_text_span_read(*args, lease.array)
        if rc < 0:
            lease.release()
            return None
    except BaseException:
        lease.release()
        raise
    finally:
        raw_lease.release()     # nothing below reads the compressed bytes
    total, _base_len, lo, hi, records, resume = info
    if rc == 2:
        METRICS.count("vcf.text_span_tail_spans")
        lease, hi = _read_tail(src, lease, total, r0 + resume)
        records = -1
    return lease, memoryview(lease.array)[lo:hi], records


def _pread_leased(src, offset: int, n: int) -> Tuple[np.ndarray, SpanBuffer]:
    """``src``'s bytes [offset, offset + n) clipped to the file: read into
    a buffer leased from the span-buffer pool where the source can fill
    one in place, else ``pread``.  Returns (bytes, lease)."""
    pread_into = getattr(src, "pread_into", None)
    if pread_into is None:
        return np.frombuffer(src.pread(offset, n), np.uint8), NO_LEASE
    lease = SPAN_BUFFERS.lease(n)
    try:
        got = pread_into(offset, memoryview(lease.array)[:n])
    except BaseException:
        lease.release()
        raise
    return lease.array[:got], lease


def _read_tail(src, lease: SpanBuffer, total: int, coffset: int
               ) -> Tuple[SpanBuffer, int]:
    """A span's last line that runs past the block after it, finished
    block by block from ``coffset`` into ``lease`` after its ``total``
    bytes (a bigger lease where they do not fit).  Returns (the lease,
    the end of the line: after its newline, or the text's end); releases
    the lease where it raises."""
    start = total
    try:
        for ext in _tail_blocks(src, coffset):
            if total + len(ext) > lease.array.size:
                bigger = SPAN_BUFFERS.lease(2 * (total + len(ext)))
                bigger.array[:total] = lease.array[:total]
                lease.release()
                lease = bigger
            lease.array[total:total + len(ext)] = np.frombuffer(ext,
                                                                np.uint8)
            total += len(ext)
            if b"\n" in ext:
                break
    except BaseException:
        lease.release()
        raise
    nl = _find_newline(memoryview(lease.array), start, total)
    return lease, total if nl < 0 else nl + 1


# ---------------------------------------------------------------------------
# BCF: record-aligned virtual-offset spans
# ---------------------------------------------------------------------------

def plan_bcf_spans(path: str, *, num_spans: Optional[int] = None,
                   config: HBamConfig = DEFAULT_CONFIG,
                   header: Optional[VCFHeader] = None,
                   ) -> List[FileVirtualSpan]:
    """hb/VCFInputFormat BCF path: BCFSplitGuesser-aligned virtual spans."""
    src = as_byte_source(path)
    try:
        size = src.size
        hdr, first_voffset, is_bgzf = read_bcf_header(src)
        if header is None:
            header = hdr
        ranges = plan_byte_ranges(size, num_spans=num_spans,
                                  span_bytes=None if num_spans
                                  else config.split_size)
        guesser = BCFSplitGuesser(src, header, is_bgzf=is_bgzf)
        boundaries: List[int] = []
        for (bstart, _bend) in ranges:
            if bstart == 0:
                boundaries.append(first_voffset)
                continue
            v = guesser.guess_next_record_start(bstart)
            boundaries.append(size << 16 if v is None
                              else max(v, first_voffset))
        boundaries.append(size << 16)
        spans: List[FileVirtualSpan] = []
        for i in range(len(boundaries) - 1):
            s, e = boundaries[i], boundaries[i + 1]
            if s < e:
                spans.append(FileVirtualSpan(path, s, e))
        return spans
    finally:
        src.close()


def read_bcf_span(source, span: FileVirtualSpan,
                  header: Optional[VCFHeader] = None,
                  is_bgzf: Optional[bool] = None) -> List[VcfRecord]:
    """hb/BCFRecordReader semantics: every record whose start virtual offset
    is in [span.start_voffset, span.end_voffset)."""
    src = as_byte_source(source)
    if header is None or is_bgzf is None:
        header, _, is_bgzf = read_bcf_header(src)
    codec = BCFRecordCodec(header)
    out: List[VcfRecord] = []
    if is_bgzf:
        r = bgzf.BGZFReader(src)
        r.seek_voffset(span.start_voffset)
        while True:
            v = r.voffset()
            if v >= span.end_voffset:
                break
            head = r.read(8)
            if len(head) < 8:
                break
            l_shared, l_indiv = struct.unpack("<II", head)
            body = r.read(l_shared + l_indiv)
            rec, _ = codec.decode(head + body, 0)
            out.append(rec)
    else:
        pos = span.start[0]
        end_byte = span.end[0]
        while pos < min(end_byte, src.size):
            head = src.pread(pos, 8)
            if len(head) < 8:
                break
            l_shared, l_indiv = struct.unpack("<II", head)
            body = src.pread(pos + 8, l_shared + l_indiv)
            rec, _ = codec.decode(head + body, 0)
            out.append(rec)
            pos += 8 + l_shared + l_indiv
    return out


def read_bcf_span_bytes(source, span: FileVirtualSpan,
                        is_bgzf: Optional[bool] = None) -> bytes:
    """Raw concatenated record bytes of a BCF span (no decode) — the input
    of the fast column scanner (formats/bcf.py scan_variant_columns)."""
    return read_bcf_span_frames(source, span, is_bgzf)[0]


def read_bcf_span_frames(source, span: FileVirtualSpan,
                         is_bgzf: Optional[bool] = None
                         ) -> Tuple[bytes, np.ndarray]:
    """(concatenated record bytes, per-record start offsets) of a BCF
    span, as memory of the caller's own — ``bcf_span_frames`` without
    the lease."""
    with bcf_span_frames(source, span, is_bgzf) as (raw, starts):
        return bytes(raw), starts


@contextlib.contextmanager
def bcf_span_frames(source, span: FileVirtualSpan,
                    is_bgzf: Optional[bool] = None
                    ) -> Iterator[Tuple[Union[bytes, memoryview],
                                        np.ndarray]]:
    """``with bcf_span_frames(...) as (raw, starts)``: the concatenated
    record bytes of a BCF span and the per-record start offsets — the
    input of the columnar decoder (formats/bcf_columns.decode_bcf_columns).

    A record belongs to the span iff its first byte does; the tail record
    runs past the span end exactly as the per-record reader reads it.  A
    record cut off by EOF is kept (the decoder raises ``BCFError`` on it,
    matching the record path); a bare header stub at EOF is dropped (the
    record path never emitted it either).

    The input decides the path.  A BGZF source with the native library is
    read as the BAM feed reads a span (``_lease_bgzf_span_frames``): then
    ``raw`` is a view of a buffer leased from the span-buffer pool, good
    until the ``with`` ends — whatever outlives it must be a copy.  A raw
    BCF, no native library, or a span that read declines goes block by
    block through ``bgzf.BGZFReader`` (``_read_bcf_span_frames``: the
    byte-identity oracle), and ``raw`` is ``bytes``.  Counters:
    ``vcf.native_read_spans`` / ``vcf.python_read_spans``."""
    with scoped_byte_source(source) as src:
        if is_bgzf is None:
            _, _, is_bgzf = read_bcf_header(src)
        leased = _lease_bgzf_span_frames(src, span) if is_bgzf else None
        if leased is None:
            METRICS.count("vcf.python_read_spans")
            yield _read_bcf_span_frames(src, span, is_bgzf)
        else:
            METRICS.count("vcf.native_read_spans")
            lease, raw, starts = leased
            try:
                yield raw, starts
            finally:
                lease.release()


_FRAME_LENGTHS = struct.Struct("<II").unpack_from


def _chase_frames(buf, n0: int, grow) -> Tuple[object, int, np.ndarray]:
    """The cursor chase over the ``l_shared``/``l_indiv`` prefixes of the
    records that start in ``buf[:n0]``.  ``grow(need)`` returns the buffer
    made ``need`` bytes long, or as long as the file allows.  Returns
    (the buffer, the length of the span's records in it, their starts).

    With the native library the chase is ``hbam_bcf_chase`` (one call, the
    interpreter lock released; one more a ``grow``); the loop below is its
    oracle and the path of a host without the library."""
    if native.available():
        return _chase_frames_native(buf, n0, grow)
    starts: List[int] = []
    p = 0
    while p < n0:
        if p + 8 > len(buf):
            buf = grow(p + 8)
            if p + 8 > len(buf):                # EOF mid-header stub
                break
        l_shared, l_indiv = _FRAME_LENGTHS(buf, p)
        end = p + 8 + l_shared + l_indiv
        if end > len(buf):
            buf = grow(end)
            if end > len(buf):                  # EOF mid-body: keep the
                starts.append(p)                # partial; decode raises
                p = len(buf)
                break
        starts.append(p)
        p = end
    return buf, p, np.asarray(starts, np.int64)


def _chase_frames_native(buf, n0: int, grow
                         ) -> Tuple[object, int, np.ndarray]:
    """``_chase_frames`` through ``utils/native.py::bcf_chase``: the native
    chase stops at a record the buffer does not hold whole and says how
    long the buffer has to be; ``grow`` makes it so (no view of ``buf`` is
    held across it: a ``bytearray`` is resized) and the chase goes on from
    there."""
    parts: List[np.ndarray] = []
    p = 0
    while True:
        starts, p, need = native.bcf_chase(np.frombuffer(buf, np.uint8),
                                           p, n0)
        parts.append(starts)
        if not need:
            break
        buf = grow(need)
        if need > len(buf):                     # EOF inside the tail record
            if p + 8 <= len(buf):               # mid-body: keep the
                parts.append(np.array([p], np.int64))   # partial; decode
                p = len(buf)                    # raises.  A bare header
            break                               # stub is dropped
    return buf, p, parts[0] if len(parts) == 1 else np.concatenate(parts)


def _read_bcf_span_frames(src, span: FileVirtualSpan, is_bgzf: bool
                          ) -> Tuple[bytes, np.ndarray]:
    """The span's whole inflated range read in BULK (block-granular, not
    per-record — two tiny ``BGZFReader.read`` calls per record were 2.5x
    the columnar decode itself), then framed by one cursor chase."""
    if is_bgzf:
        r = bgzf.BGZFReader(src)
        r.seek_voffset(span.start_voffset)
        buf = bytearray(r.read_to_voffset(span.end_voffset))

        def read_more(k: int) -> bytes:
            return r.read(k)
    else:
        pos0 = span.start[0]
        n_raw = max(0, min(span.end[0], src.size) - pos0)
        buf = bytearray(src.pread(pos0, n_raw) if n_raw else b"")

        def read_more(k: int) -> bytes:
            return src.pread(pos0 + len(buf), k)

    def grow(need: int) -> bytearray:
        buf.extend(read_more(need - len(buf)))
        return buf

    buf, length, starts = _chase_frames(buf, len(buf), grow)
    del buf[length:]
    return bytes(buf), starts


def _lease_bgzf_span_frames(src, span: FileVirtualSpan
                            ) -> Optional[Tuple[SpanBuffer, memoryview,
                                                np.ndarray]]:
    """A BGZF BCF span read the way the BAM feed reads one: ONE positioned
    read of the compressed range (``ops.inflate.fetch_span_raw``), the
    native header walk, ONE native inflate of all its blocks into a
    buffer leased from the span-buffer pool — one release of the
    interpreter lock a span where the ``BGZFReader`` takes two a block —
    and the chase over a view of that buffer, no copy.  One native thread:
    the pool's threads are the parallelism.  ISIZE is verified and CRC32
    is not, as ``BGZFReader(src)`` does.

    Returns (lease, record bytes, starts); the caller releases the lease
    when nothing reads the bytes any more.  Returns None — nothing leased,
    the ``BGZFReader`` path decides — without the native library, for a
    span that is empty or does not start and end inside its blocks, and
    when the block chain does not parse or inflate (that path then raises
    its own ``BGZFError``)."""
    start_u, end_u = span.start[1], span.end[1]
    if not native.available() or span.start_voffset >= span.end_voffset:
        return None
    lease = raw_lease = NO_LEASE
    try:
        raw, end_block_size, next_c, raw_lease = \
            inflate_ops.fetch_span_raw(src, span)
        if not raw:
            return None
        table = inflate_ops.block_table(raw)
        isize = table["isize"]
        total = int(isize.sum())
        n0 = (total - int(isize[-1]) + end_u if end_block_size else total) \
            - start_u
        if start_u > int(isize[0]) or end_u > int(isize[-1]) or n0 <= 0:
            return None
        lease = SPAN_BUFFERS.lease(total)
        inflate_ops.inflate_span(raw, table, backend="native", n_threads=1,
                                 out=lease.array)
    except BaseException as e:
        lease.release()
        if isinstance(e, bgzf.BGZFError):
            return None
        raise
    finally:
        raw_lease.release()     # nothing below reads the compressed bytes

    tail = None

    def grow(need: int) -> memoryview:
        """The tail record runs past the last fetched block: the
        following block(s) through the block reader, never a speculative
        over-read."""
        nonlocal lease, total, tail
        if tail is None:
            tail = bgzf.BGZFReader(src)
            tail.seek_voffset(next_c << 16)
        more = tail.read(need - (total - start_u))
        if total + len(more) > lease.array.size:
            bigger = SPAN_BUFFERS.lease(total + len(more))
            bigger.array[:total] = lease.array[:total]
            lease.release()
            lease = bigger
        lease.array[total:total + len(more)] = np.frombuffer(more, np.uint8)
        total += len(more)
        return memoryview(lease.array)[start_u:total]

    try:
        buf, length, starts = _chase_frames(
            memoryview(lease.array)[start_u:total], n0, grow)
    except BaseException:
        lease.release()
        raise
    return lease, buf[:length], starts
