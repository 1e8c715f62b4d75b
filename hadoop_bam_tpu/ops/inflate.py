"""Batched BGZF inflate dispatch: the framework's replacement for the
per-block zlib-over-JNI inflate in the reference hot loop (SURVEY.md 3.2).

Paths, in preference order:

- ``native``: C++ multithreaded zlib over all blocks of a span at once
  (native/hbam_native.cpp) — the production host path feeding device batches.
- ``zlib``: Python zlib per block (portable fallback, still batched at the
  span level).

Both paths share one contract: given the raw compressed span bytes and the
parsed block table, produce a contiguous inflated buffer + per-block inflated
offsets.
"""
from __future__ import annotations

import zlib
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.pools import (
    NO_LEASE, SPAN_BUFFERS, SpanBuffer, SpanBufferPool,
)


def fetch_span_raw(src, span
                   ) -> Tuple[memoryview, int, int, SpanBuffer]:
    """Fetch one span's compressed bytes: the whole blocks in
    [start_c, end_c) plus the block AT end_c when the span ends inside it
    (end_u > 0), so one block table and one native job cover the span.

    A source that can fill a buffer in place (a local file, or the retry
    wrapper around one) is read ONCE, [start_c, end_c + MAX_BLOCK_SIZE)
    clipped to the file, into a buffer leased from the span-buffer pool;
    the end block's size comes from its header where it lies.  Any other
    source (in-memory bytes, the chaos wrapper, a remote source) keeps
    two ``pread``s and a concatenate.  Returns (raw, end_block_size,
    next_c, lease): ``next_c`` is the compressed offset of the first
    block past ``raw``; ``lease`` (``NO_LEASE`` on the ``pread`` path) is
    what ``raw`` lives in — whoever takes ``raw`` releases it once
    nothing reads ``raw`` any more."""
    start_c, start_u = span.start
    end_c, end_u = span.end
    want = max(end_c - start_c, 0)
    end_block = end_u > 0 and end_c < src.size
    pread_into = getattr(src, "pread_into", None)
    lease = NO_LEASE
    with METRICS.span("bam.fetch_wall", nbytes=want):
        if pread_into is None:
            raw = src.pread(start_c, want)
            end_block_size = 0
            if end_block:
                head = src.pread(end_c, bgzf.MAX_BLOCK_SIZE)
                end_block_size = bgzf.parse_block_header(head, 0).block_size
                raw = raw + head[:end_block_size]
            raw = memoryview(raw)
        else:
            ask = min(want + (bgzf.MAX_BLOCK_SIZE if end_block else 0),
                      src.size - start_c)
            raw, end_block_size = memoryview(b""), 0
            if ask > 0:
                lease = SPAN_BUFFERS.lease(ask)
                try:
                    buf = memoryview(lease.array)[:ask]
                    buf = buf[:pread_into(start_c, buf)]
                    if end_block:
                        end_block_size = bgzf.parse_block_header(
                            buf, want).block_size
                except BaseException:
                    lease.release()
                    raise
                raw = buf[:want + end_block_size]
    next_c = (end_c + end_block_size) if raw else start_c
    return raw, end_block_size, next_c, lease


def block_table(raw, offset: int = 0) -> dict:
    """Parse consecutive BGZF block headers into a columnar table.

    The native library walks the header chain in one call; the Python
    parser below is the whole walk without it, and otherwise starts at
    the first header the native walk did not accept — so a malformed
    chain raises ``parse_block_header``'s own ``BGZFError``."""
    cols = [[], [], [], []]     # coffset, cdata_off, cdata_len, isize
    p = offset
    n = len(raw)
    head = None
    if p < n and native.available():
        head, p = native.block_table(np.frombuffer(raw, dtype=np.uint8), p)
    while p < n:
        info = bgzf.parse_block_header(raw, p)
        cols[0].append(info.coffset)
        cols[1].append(info.cdata_offset)
        cols[2].append(info.cdata_size)
        cols[3].append(info.isize)
        p = info.next_coffset
    dtypes = (np.int64, np.int64, np.int32, np.int32)
    cols = [np.asarray(c, dtype=dt) for c, dt in zip(cols, dtypes)]
    if head is not None:
        cols = [np.concatenate([h, c]) if c.size else h
                for h, c in zip(head, cols)]
    return dict(zip(("coffset", "cdata_off", "cdata_len", "isize"), cols))


def inflate_span(raw: bytes, table: Optional[dict] = None,
                 backend: str = "auto", n_threads: int = 0,
                 out: Optional[np.ndarray] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Inflate all blocks of a compressed span.

    Returns (data, ubase): ``data`` is the contiguous inflated bytes of the
    span; ``ubase[i]`` is each block's starting offset within ``data`` (the
    map from (block, in-block offset) to buffer offset — i.e. from virtual
    offsets to positions).  ``data`` is fresh memory, or the head of
    ``out`` (uint8, at least the blocks' ISIZE sum) when the
    caller brings the buffer.
    """
    if table is None:
        table = block_table(raw)
    isize = table["isize"]
    ubase = np.zeros(isize.size + 1, dtype=np.int64)
    np.cumsum(isize, out=ubase[1:])
    total = int(ubase[-1])
    dst = np.empty(total, dtype=np.uint8) if out is None else out[:total]
    src = np.frombuffer(raw, dtype=np.uint8)

    if backend == "auto":
        backend = "native" if native.available() else "zlib"
    if backend == "native":
        try:
            native.inflate_batch(src, table["cdata_off"],
                                 table["cdata_len"], dst, ubase[:-1],
                                 isize, n_threads)
        except ValueError as e:
            # same class as the zlib backend and the fused path: bad
            # DEFLATE bytes are a BGZF-level corruption either way
            raise bgzf.BGZFError(str(e)) from e
    elif backend == "zlib":
        mv = memoryview(raw)
        for i in range(isize.size):
            o, l = int(table["cdata_off"][i]), int(table["cdata_len"][i])
            try:
                # decompress straight off the memoryview slice — the old
                # bytes(mv[...]) copy doubled this backend's allocation
                # traffic (one copy per block before zlib even ran)
                out = zlib.decompress(mv[o:o + l], wbits=-15)
            except zlib.error as e:
                # classified at the policy boundary: bad DEFLATE bytes are
                # deterministic corruption, not a retryable read fault
                raise bgzf.BGZFError(
                    f"corrupt DEFLATE payload in block {i}: {e}") from e
            if len(out) != int(isize[i]):
                raise bgzf.BGZFError(f"ISIZE mismatch in block {i}")
            dst[int(ubase[i]):int(ubase[i + 1])] = np.frombuffer(out, np.uint8)
    else:
        # PLAN class (still a ValueError): a bad backend name is run
        # configuration, not data — never retried, never quarantined
        from hadoop_bam_tpu.utils.errors import PlanError
        raise PlanError(f"unknown inflate backend {backend!r}")
    return dst, ubase[:-1]


def footer_crcs(src: np.ndarray, table: dict) -> np.ndarray:
    """Each block's expected CRC32, read from the BGZF footers (the CRC
    sits 8 bytes before each block end)."""
    foot = table["cdata_off"] + table["cdata_len"]
    return (src[foot].astype(np.uint32)
            | (src[foot + 1].astype(np.uint32) << 8)
            | (src[foot + 2].astype(np.uint32) << 16)
            | (src[foot + 3].astype(np.uint32) << 24))


def verify_crcs(raw: bytes, table: dict, data: np.ndarray,
                ubase: np.ndarray, n_threads: int = 0) -> None:
    """Validate every block's CRC32 footer against the inflated bytes
    (native batched CRC when available)."""
    n = table["isize"].size
    src = np.frombuffer(raw, dtype=np.uint8)
    expect = footer_crcs(src, table)
    if native.available():
        import ctypes
        lib = native.load()
        got = np.empty(n, dtype=np.uint32)
        lib.hbam_crc32_batch(
            data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ubase.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            table["isize"].ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n, got.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            n_threads if n_threads > 0 else 0 or 1)
    else:
        got = np.empty(n, dtype=np.uint32)
        for i in range(n):
            s, e = int(ubase[i]), int(ubase[i]) + int(table["isize"][i])
            got[i] = zlib.crc32(data[s:e].tobytes()) & 0xFFFFFFFF
    bad = np.nonzero(got != expect)[0]
    if bad.size:
        raise bgzf.BGZFError(f"CRC32 mismatch in block(s) {bad[:8].tolist()}")


def walk_records(data: np.ndarray, start: int = 0,
                 cap: Optional[int] = None) -> Tuple[np.ndarray, int]:
    """Record-boundary walk over inflated bytes: native when available,
    NumPy/Python otherwise.  Returns (offsets, tail_offset) where tail_offset
    is the first incomplete record's offset (== len when exact)."""
    if cap is None:
        # min on-wire record = 4-byte block_size + 32-byte fixed core (the
        # native walker accepts any bs >= 32), so count can never exceed //36
        cap = max(16, data.size // 36)
    if native.available():
        return native.walk_bam_records(np.ascontiguousarray(data), start, cap)
    from hadoop_bam_tpu.formats.bam import walk_record_offsets
    # walk straight over the array's buffer — the old data.tobytes() here
    # duplicated the whole inflated span per walk (DP701's founding case)
    offs = walk_record_offsets(np.ascontiguousarray(data), start=start)
    tail = int(offs[-1] + 4 + int.from_bytes(
        data[int(offs[-1]):int(offs[-1]) + 4].tobytes(), "little", signed=True)
        ) if offs.size else start
    return offs, tail


# ---------------------------------------------------------------------------
# Fused single-pass span decode (native/hbam_native.cpp hbam_fused_*)
# ---------------------------------------------------------------------------

def fused_available() -> bool:
    """True when the native fused decode entry points are loadable."""
    return native.available() and native.fused_available()


def _raise_fused_error(rc: int, index: int) -> None:
    """Map a fused-decode rc to the same exception CLASS the two-pass
    path raises for the identical corruption (the fuzz tests pin this):
    BGZF-level faults -> BGZFError, record-chain faults -> the CORRUPT
    taxonomy (the two-pass walkers' bare ValueError classifies the same
    way through classify_error)."""
    from hadoop_bam_tpu.utils.errors import CorruptDataError

    kind = -rc
    if kind == 1:
        raise bgzf.BGZFError(f"corrupt DEFLATE payload in block {index}")
    if kind == 2:
        raise bgzf.BGZFError(f"ISIZE mismatch in block {index}")
    if kind == 3:
        raise bgzf.BGZFError(f"CRC32 mismatch in block(s) [{index}]")
    if kind == 5:
        raise CorruptDataError(
            f"record count exceeds capacity at offset {index}")
    raise CorruptDataError("malformed BAM record chain")


class FusedSpanDecode:
    """One span's fused native inflate + walk + pack (+ CRC fold) job.

    Wraps ``utils/native.FusedJob`` with the span-level geometry: builds
    the inflated-offset table, sizes the packed outputs for the worst
    case, and exposes the decode as a stream of completed row chunks::

        dec = FusedSpanDecode(raw, table, start=s, stop=e, mode="rows",
                              sel=ranges, row_stride=w, check_crc=True)
        for lo, hi in dec.chunks():
            consume(dec.rows[lo:hi])          # packed while cache-hot
        n, tail = dec.finish()

    ``chunks()`` yields ``[row_lo, row_hi)`` ranges the moment the native
    walk publishes them — downstream tile packing starts before the
    span's tail blocks are even inflated.  After ``finish()``:
    ``data`` holds the fully inflated span, ``offsets[:n]`` the record
    starts, and the mode-specific outputs (``rows`` / ``prefix``+
    ``seq``+``qual``) their packed tiles.  Corruption raises the same
    ``BGZFError``/``ValueError`` the two-pass path raises; closing the
    stream early (generator abandoned) joins the native workers.

    Who owns the memory.  The packed outputs are always fresh arrays and
    the caller's to keep: the feed's packer holds views of ``rows``
    across spans.  ``data`` and ``offsets`` are fresh too unless the
    caller passes ``buffers`` (a ``SpanBufferPool``), which says neither
    escapes the span — the streamed drivers, whose consumers see packed
    rows only.  Then both live in one leased buffer, belong to this
    decode until ``finish()`` (every way out ends there: a normal end, an
    error, an early close, ``__del__``) and read ``None`` afterwards.
    ``raw_lease`` is the lease under ``raw`` when the compressed bytes sit
    in a pooled buffer; the native job reads them until it is joined, so
    it goes back at the same point.

    Modes: ``"offsets"`` (walk only — callers packing variable-length
    series themselves), ``"rows"`` (fixed-prefix ``sel`` ranges packed
    into ``row_stride``-byte rows), ``"payload"`` (prefix/seq/qual tiles,
    ``hbam_walk_bam_payload`` layout)."""

    def __init__(self, raw: bytes, table: Optional[dict] = None, *,
                 start: int = 0, stop: Optional[int] = None,
                 mode: str = "offsets",
                 sel: Optional[Sequence[Tuple[int, int]]] = None,
                 row_stride: int = 0, max_len: int = 0, seq_stride: int = 0,
                 qual_stride: int = 0, check_crc: bool = False,
                 chunk_blocks: int = 32, n_threads: int = 0,
                 buffers: Optional[SpanBufferPool] = None,
                 raw_lease: SpanBuffer = NO_LEASE):
        self._buffers = buffers
        self._leases = [raw_lease]
        self._job = None
        if table is None:
            table = block_table(raw)
        isize = table["isize"]
        ubase = np.zeros(isize.size + 1, dtype=np.int64)
        np.cumsum(isize, out=ubase[1:])
        total = int(ubase[-1])
        self.inflated_bytes = total
        self.ubase = ubase[:-1]
        self.stop = total if stop is None else min(int(stop), total)
        self.rows = self.prefix = self.seq = self.qual = None
        src = np.frombuffer(raw, dtype=np.uint8)
        expect = footer_crcs(src, table) if check_crc else None
        # min on-wire record = 4-byte block_size + 32-byte fixed core
        cap = max(16, (self.stop - start) // 36 + 1)
        if buffers is None:
            self.data = np.empty(total, dtype=np.uint8)
            self.offsets = np.empty(cap, dtype=np.int64)
        else:
            # ONE buffer for both: two leases a span out of one size
            # class would ask the class for twice what it keeps
            lease = buffers.lease(total + 8 + 8 * cap)
            self._leases.append(lease)
            self.data = lease.array[:total]
            off0 = (total + 7) & ~7
            self.offsets = lease.array[off0:off0 + 8 * cap].view(np.int64)
        mode_id = {"offsets": native.FUSED_OFFSETS,
                   "rows": native.FUSED_ROWS,
                   "payload": native.FUSED_PAYLOAD}[mode]
        sel_off = sel_len = out_rows = out_seq = out_qual = None
        if mode == "rows":
            sel_off = np.asarray([o for o, _ in sel], dtype=np.int32)
            sel_len = np.asarray([l for _, l in sel], dtype=np.int32)
            self.rows = out_rows = np.empty((cap, row_stride),
                                            dtype=np.uint8)
        elif mode == "payload":
            # zeroed like the two-pass wrappers: the C side writes only
            # each row's payload bytes, padding stays zero
            self.prefix = out_rows = np.zeros((cap, 36), dtype=np.uint8)
            self.seq = out_seq = np.zeros((cap, seq_stride), dtype=np.uint8)
            self.qual = out_qual = np.zeros((cap, qual_stride),
                                            dtype=np.uint8)
        self.n_blocks = int(isize.size)
        if self.n_blocks == 0:
            self.n_rows, self.tail = 0, int(start)
            self._release()
            return
        self._job = native.FusedJob(
            src, table["cdata_off"], table["cdata_len"], isize, expect,
            self.data, self.ubase, start, self.stop, mode_id, sel_off,
            sel_len, row_stride, out_rows, out_seq, out_qual, max_len,
            seq_stride, qual_stride, self.offsets, chunk_blocks, n_threads)
        self.n_rows: Optional[int] = None
        self.tail: Optional[int] = None

    def _release(self) -> None:
        """Hand every leased buffer back (idempotent).  Only once no
        native worker runs: they read ``raw`` and write ``data``."""
        if self._buffers is not None:
            self.data = self.offsets = None
        leases, self._leases = self._leases, []
        for lease in leases:
            lease.release()

    def chunks(self) -> "Iterator[Tuple[int, int]]":
        """Yield ``(row_lo, row_hi)`` as the native walk completes them;
        raises on corruption.  Always drives the job to completion unless
        the generator is closed early (which cancels + joins)."""
        if self._job is None:
            return
        try:
            while True:
                c = self._job.next_chunk()
                if c is None:
                    if self._job.rc < 0:
                        _raise_fused_error(self._job.rc,
                                           self._job.err_index)
                    return
                yield c
        finally:
            # abandoned mid-stream (early generator close): join workers
            # so no native thread outlives its span's buffers
            if self.n_rows is None:
                self.finish(check=False)

    def finish(self, check: bool = True) -> Tuple[int, int]:
        """Join the job and hand the leased buffers back; returns
        (n_rows, tail).  ``check=False`` skips raising (the cancellation
        path)."""
        job, self._job = self._job, None
        rc, idx = 0, -1
        if job is not None:
            rc = job.finish()
            self.n_rows, self.tail = job.n_rows, job.tail
            idx = job.err_index
            # the host feed's "time busy", measured where the work
            # happens: core-nanoseconds of inflate + walk + pack
            METRICS.count("decode.native_busy_ns", job.busy_ns)
            METRICS.count("decode.native_jobs")
        self._release()
        if check and rc < 0:
            _raise_fused_error(rc, idx)
        return self.n_rows, self.tail

    def __del__(self):
        # Dropped unfinished.  The collector may run this on a thread
        # that holds any lock, the metrics' among them: join natively,
        # hand the buffers back (the pool takes no lock), count nothing.
        try:
            job, self._job = self._job, None
            if job is not None:
                job.finish()
            self._release()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    @property
    def err_index(self) -> int:
        return -1 if self._job is None else self._job.err_index

    def run(self) -> Tuple[int, int]:
        """Non-streamed convenience: drain every chunk, then finish."""
        for _ in self.chunks():
            pass
        return self.finish()
