"""The sharded decode pipeline: spans -> host inflate -> device SoA batches.

This is the TPU rebuild of the reference's read hot path (SURVEY.md section
3.2): where a map task ran ``BAMRecordReader.nextKeyValue()`` per record, a
mesh step consumes one *span batch* — per-device inflated bytes + record
offsets, static shapes — and unpacks/reduces on all devices at once:

    plan (once, host 0)                 hb/BAMInputFormat.getSplits
    fetch + inflate span (host threads) BlockCompressedInputStream + zlib JNI
    walk record offsets (host/native)   implicit in per-record decode
    unpack fields + compute (device)    htsjdk BAMRecordCodec.decode + mapper
    psum stats over the data axis       MR shuffle/reduce

Host stages for batch k+1 overlap device compute for batch k via a prefetch
thread pool (the HBM-feed analog of MapReduce's record-ahead buffering).
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import itertools
import os
import time
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hadoop_bam_tpu.parallel.mesh import shard_map
# the scan feed; its window and failure policy stay importable from here
from hadoop_bam_tpu.parallel.scan import (  # noqa: F401 — re-exports
    ScanFeed, _iter_windowed, decode_with_retry,
)
from hadoop_bam_tpu.parallel.staging import bucket_cap

from hadoop_bam_tpu.config import (
    DEFAULT_CONFIG, HBamConfig, resolve_inflate_backend,
)
# plane gating lives in plan/executor.py (the ONE predicate table; the
# planroute lint rule PL101 keeps gate conditionals out of this module).
# _use_fused keeps its historical name here for the span-level decoders.
from hadoop_bam_tpu.plan.executor import _use_fused, select_plane
from hadoop_bam_tpu.formats.bam import SAMHeader
from hadoop_bam_tpu.ops import inflate as inflate_ops
from hadoop_bam_tpu.ops.flagstat import flagstat_from_columns
from hadoop_bam_tpu.ops.unpack_bam import (
    ALL_FIELDS, FLAGSTAT_PROJECTION, PREFIX, projection_ranges,
    projection_row_bytes, unpack_fixed_fields, unpack_projected_tile,
)
from hadoop_bam_tpu.resilience import chaos
from hadoop_bam_tpu.resilience.domains import decode_ladder
from hadoop_bam_tpu.split.spans import FileVirtualSpan
from hadoop_bam_tpu.utils import errors as hberrors
from hadoop_bam_tpu.utils.errors import PlanError, classify_error
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.pools import (
    SPAN_BUFFERS, decode_pool_size, stream_window_cap, text_stream_window,
)
from hadoop_bam_tpu.utils.resilient import (
    QuarantineManifest, RetryPolicy, RetryingByteSource,
)
from hadoop_bam_tpu.utils.seekable import as_byte_source, scoped_byte_source
from hadoop_bam_tpu.utils.stepcache import named_step


@dataclasses.dataclass(frozen=True)
class DecodeGeometry:
    """Static shapes of one device's slice of a span batch (jit contract)."""
    bytes_cap: int = 1 << 24       # inflated bytes per device per step (span mode)
    records_cap: int = 1 << 18     # record offsets per device per step
    tile_records: int = 1 << 18    # records per device per step (prefix-tile mode)

    def round_trip_bytes(self) -> int:
        return self.bytes_cap + 4 * self.records_cap


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class PayloadGeometry:
    """Static shapes of the tensor-batch feed (seq/qual payload tiles).

    Strides round up to 32 bytes — TRANSFER-compact, not lane-aligned:
    every byte of a row crosses the host->device link, while Mosaic
    pads the lane dimension in VMEM itself, so shipping 128-byte-aligned
    rows only inflates H2D traffic (388 B/read for 151 bp reads;
    compact strides make it 260).  The trade against HBM lane padding
    has not been re-measured on the current machine.  Reads longer than
    max_len are truncated on pack (full l_seq stays available in the
    prefix columns).
    """
    max_len: int = 160             # bases per read kept on device
    tile_records: int = 1 << 16    # records per device per step:
                                   # fewer, larger tiles amortize the
                                   # per-dispatch cost; 64k reads/tile
                                   # is ~17 MB staged.  Not re-measured
                                   # on the current machine
    block_n: int = 256             # Pallas record-tile height
    fixed_shape: bool = False      # True: the FINAL partial batch pads
                                   # to tile_records instead of
                                   # shrinking to a dispatch bucket —
                                   # for consumers that preallocate by
                                   # tile_records (costs padding
                                   # transfer on the last batch only)

    @property
    def seq_stride(self) -> int:
        return _round_up((self.max_len + 1) // 2, 32)

    @property
    def qual_stride(self) -> int:
        return _round_up(self.max_len, 32)


@dataclasses.dataclass
class HostSpanBatch:
    """Host-side decoded span group, ready to stack for n devices."""
    data: np.ndarray       # [n_dev, bytes_cap] uint8
    offsets: np.ndarray    # [n_dev, records_cap] int32
    n_records: np.ndarray  # [n_dev] int32
    voffsets: List[np.ndarray]  # per-device per-record virtual offsets


def _decode_span_core(source, span: FileVirtualSpan,
                      check_crc: bool = False,
                      inflate_backend: str = "auto",
                      packed_walker=None,
                      want_voffs: bool = True,
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 Optional[np.ndarray]]:
    """Fetch + inflate one span and walk its records (host stage).

    Returns (data, offsets, voffsets, rows) — unpadded; ``rows`` is the
    packed row tile when ``packed_walker`` is given (else None).  Only
    records *starting* inside the span are owned (reference reader
    contract); the final record may extend into the following blocks, which
    are fetched as needed.

    This is the TWO-PASS path (inflate the whole span to DRAM, then walk
    it again) — the byte-identity oracle the fused single-pass path
    (``_decode_span_fused``) is pinned against, and the fallback when the
    native library is unavailable or ``config.use_fused_decode`` is off.
    """
    from hadoop_bam_tpu.formats import bgzf

    src = as_byte_source(source)
    start_c, start_u = span.start
    end_c, end_u = span.end
    METRICS.count("pipeline.spans")

    raw, end_block_size, next_c, lease = inflate_ops.fetch_span_raw(src, span)
    if raw:
        try:
            table = inflate_ops.block_table(raw)
            with METRICS.timer("pipeline.inflate"), \
                    METRICS.span("bam.inflate_wall", nbytes=len(raw)):
                data, ubase = inflate_ops.inflate_span(
                    raw, table, backend=inflate_backend)
            METRICS.count("pipeline.blocks", int(table["isize"].size))
            METRICS.count("pipeline.inflated_bytes", int(data.size))
            if check_crc:
                # a separate third sweep over the inflated bytes — the
                # fused path folds this into its single visit for ~free
                with METRICS.timer("pipeline.crc"):
                    inflate_ops.verify_crcs(raw, table, data, ubase)
        finally:
            lease.release()     # nothing below reads the compressed bytes
        abs_coffs = table["coffset"] + start_c
    else:
        data = np.empty(0, dtype=np.uint8)
        ubase = np.empty(0, dtype=np.int64)
        abs_coffs = np.empty(0, dtype=np.int64)

    def extend_past(tail: int) -> None:
        """Fetch + inflate the following blocks until the record starting
        at ``tail`` (cut at the buffer end) is complete, accumulating in a
        chunk list with ONE final concatenate — per-block np.concatenate
        re-copied the whole span each iteration (quadratic on long
        multi-block record chains)."""
        nonlocal data, ubase, abs_coffs, next_c
        chunks: List[np.ndarray] = [data]
        new_bases: List[int] = []
        new_coffs: List[int] = []
        cur = data.size

        def fetch_block() -> None:
            nonlocal cur, next_c
            head = src.pread(next_c, bgzf.MAX_BLOCK_SIZE)
            info = bgzf.parse_block_header(head, 0)
            extra = bgzf.inflate_block(head, info, check_crc=check_crc)
            new_bases.append(cur)
            new_coffs.append(next_c)
            chunks.append(np.frombuffer(extra, np.uint8))
            cur += len(extra)
            next_c += info.block_size

        def read_bytes(pos: int, n: int) -> bytes:
            out = bytearray()
            base = 0
            for c in chunks:
                lo = pos - base
                if 0 <= lo < c.size and len(out) < n:
                    out += c[lo:lo + n - len(out)].tobytes()
                elif lo < 0 and len(out) < n:
                    out += c[:n - len(out)].tobytes()
                base += c.size
            return bytes(out)

        # the 4-byte block_size field itself may be cut
        while cur < tail + 4 and next_c < src.size:
            fetch_block()
        if cur >= tail + 4:
            bs = int.from_bytes(read_bytes(tail, 4), "little", signed=True)
            needed = tail + 4 + max(bs, 0)
            while cur < needed and next_c < src.size:
                fetch_block()
        if new_bases:
            ubase = np.concatenate([ubase, np.asarray(new_bases, np.int64)])
            abs_coffs = np.concatenate(
                [abs_coffs, np.asarray(new_coffs, np.int64)])
            data = np.concatenate(chunks)

    # 2. The span may end inside the block at end_c (already inflated as the
    #    final table entry): its first end_u inflated bytes still hold
    #    records owned by this span.
    if end_block_size:
        end_inflated = int(ubase[-1]) + end_u
    else:
        end_inflated = data.size

    # 3+4. Walk record boundaries; own records starting in
    #    [start_u, end_inflated).  If the walk's tail (first incomplete
    #    record) starts before end_inflated, an owned record is cut at the
    #    buffer end — append following blocks and re-walk until it completes
    #    (reference reader contract: the last record may extend past the
    #    split's end voffset).
    rows = None
    while True:
        with METRICS.timer("pipeline.walk"), \
                METRICS.span("bam.walk_wall"):
            if packed_walker is not None:
                rows, offs, tail = packed_walker(data, start_u, end_inflated)
            else:
                offs, tail = inflate_ops.walk_records(data, start=start_u)
        if tail < end_inflated and next_c < src.size:
            prev_size = data.size
            extend_past(tail)
            if data.size == prev_size:
                break  # no more bytes to fetch: truncated file
            continue
        break
    keep = int(np.searchsorted(offs, max(end_inflated, 1)))  # offs ascend
    offs = offs[:keep]
    if rows is not None:
        rows = rows[:keep]
    METRICS.count("pipeline.records", int(offs.size))

    # 5. Map record offsets back to packed virtual offsets.
    if offs.size and want_voffs:
        blk = np.searchsorted(ubase, offs, side="right") - 1
        voffs = (abs_coffs[blk].astype(np.uint64) << np.uint64(16)) | \
            (offs - ubase[blk]).astype(np.uint64)
    else:
        voffs = np.empty(0, dtype=np.uint64)
    return data, offs, voffs, rows


# ---------------------------------------------------------------------------
# Fused single-pass span decode (native hbam_fused_*: ops/inflate.py
# FusedSpanDecode).  One streamed native pass replaces the two-pass path's
# three DRAM sweeps (inflate -> walk re-read -> optional CRC sweep): each
# native worker inflates a run of decode_chunk_blocks BGZF blocks and the
# record walk + projection pack + CRC fold consume those bytes cache-hot.
# The two-pass _decode_span_core stays as the byte-identity oracle and the
# automatic fallback (no native library, non-native backends,
# config.use_fused_decode=False, and the rare cut-final-record span).
# ---------------------------------------------------------------------------

def _stream_window(window: int) -> int:
    """Cap the in-flight window for STREAMED fused decode: each windowed
    span is a live multi-threaded native job (the pool task only fetches
    and starts it), so the pool-sized window that bounds buffered decodes
    would oversubscribe the host several-fold here."""
    return min(window, stream_window_cap())


def _fused_off(config: Optional[HBamConfig]) -> HBamConfig:
    """A config copy with the fused path disabled — the streamed paths'
    tail-extension fallback must run the two-pass oracle, not re-run the
    fused decode it just finished."""
    cfg = config if config is not None else DEFAULT_CONFIG
    return dataclasses.replace(cfg, use_fused_decode=False)


def _start_fused_span(src, span: FileVirtualSpan, mode: str, *,
                      sel=None, row_bytes: int = 0,
                      geometry: "Optional[PayloadGeometry]" = None,
                      check_crc: bool = False,
                      config: Optional[HBamConfig] = None,
                      data_escapes: bool = True):
    """Fetch one span and start its fused native decode job.

    The fetch runs HERE, on the caller's thread — transient I/O faults
    surface inside the decode_with_retry boundary even when the chunk
    stream is consumed later.  ``data_escapes=False`` (the streamed
    callers: their consumers see packed rows only) lets the decode lease
    its inflated bytes and offsets from the span-buffer pool until its
    ``finish()``.  Returns (dec, end_inflated, next_c, table) or None for
    an empty span (the two-pass path disposes of those)."""
    raw, end_block_size, next_c, lease = inflate_ops.fetch_span_raw(src, span)
    if not raw:
        return None
    try:
        table = inflate_ops.block_table(raw)
        isize = table["isize"]
        total = int(isize.sum())
        end_inflated = (total - int(isize[-1]) + span.end[1]) \
            if end_block_size else total
        cfg = config if config is not None else DEFAULT_CONFIG
        kwargs = {}
        if mode == "rows":
            kwargs = dict(sel=sel, row_stride=row_bytes)
        elif mode == "payload":
            kwargs = dict(max_len=geometry.max_len,
                          seq_stride=geometry.seq_stride,
                          qual_stride=geometry.qual_stride)
        # the decode owns ``lease`` from here: its finish() releases it
        dec = inflate_ops.FusedSpanDecode(
            raw, table, start=span.start[1], stop=end_inflated, mode=mode,
            check_crc=check_crc,
            chunk_blocks=max(1, int(cfg.decode_chunk_blocks)),
            buffers=None if data_escapes else SPAN_BUFFERS,
            raw_lease=lease, **kwargs)
    except BaseException:
        lease.release()
        raise
    return dec, end_inflated, next_c, table


def _fused_span_counts(dec, table, n: int) -> None:
    """Span bookkeeping on fused-decode success (the two-pass core counts
    these itself; a fused span that falls back must not double-count)."""
    METRICS.count("pipeline.spans")
    METRICS.count("pipeline.blocks", int(table["isize"].size))
    METRICS.count("pipeline.inflated_bytes", dec.inflated_bytes)
    METRICS.count("pipeline.records", n)


def _decode_span_fused(source, span: FileVirtualSpan, mode: str, *,
                       check_crc: bool = False, sel=None, row_bytes: int = 0,
                       geometry: "Optional[PayloadGeometry]" = None,
                       want_voffs: bool = True,
                       config: Optional[HBamConfig] = None):
    """Buffered fused decode of one span — the drop-in replacement for
    ``_decode_span_core`` + packed walker.

    Returns (data, offs, voffs, outs) with ``outs`` mode-dependent
    (rows / (prefix, seq, qual) / None), or **None** when this span needs
    the two-pass path: an empty span, or a final owned record extending
    past the span's inflated blocks (the tail-extension case — a record
    crossing the end block's boundary, well under 1% of spans; the oracle
    path re-decodes those whole for simplicity)."""
    src = as_byte_source(source)
    started = _start_fused_span(src, span, mode, sel=sel,
                                row_bytes=row_bytes, geometry=geometry,
                                check_crc=check_crc, config=config)
    if started is None:
        return None
    dec, end_inflated, next_c, table = started
    try:
        with METRICS.timer("pipeline.fused_decode"), \
                METRICS.span("bam.fused_decode_wall",
                             nbytes=int(dec.data.size)):
            n, tail = dec.run()
    except Exception:
        # counter parity with the two-pass path (which counts spans at
        # entry): a span that FAILED decode still counts as attempted —
        # the success/fallback paths count elsewhere, exactly once
        METRICS.count("pipeline.spans")
        raise
    if tail < end_inflated and next_c < src.size:
        return None             # cut final record: two-pass oracle path
    _fused_span_counts(dec, table, n)
    offs = dec.offsets[:n]
    if n and want_voffs:
        abs_coffs = table["coffset"] + span.start[0]
        blk = np.searchsorted(dec.ubase, offs, side="right") - 1
        voffs = (abs_coffs[blk].astype(np.uint64) << np.uint64(16)) | \
            (offs - dec.ubase[blk]).astype(np.uint64)
    else:
        voffs = np.empty(0, dtype=np.uint64)
    if mode == "rows":
        outs = dec.rows[:n]
    elif mode == "payload":
        outs = (dec.prefix[:n], dec.seq[:n], dec.qual[:n])
    else:
        outs = None
    return dec.data, offs, voffs, outs


class _FusedChunkStream:
    """One span's streamed fused decode: iterate for row-array tuples,
    ``close()`` to join the native workers deterministically (works even
    when iteration never started — the GC ``__del__`` backstop is for
    interpreter teardown, not the normal abandon path)."""

    __slots__ = ("_dec", "_gen")

    def __init__(self, dec, gen):
        self._dec = dec
        self._gen = gen

    def __iter__(self):
        return self._gen

    def close(self) -> None:
        self._gen.close()
        self._dec.finish(check=False)


def _iter_fused_span_chunks(src, span: FileVirtualSpan, mode: str, *,
                            sel=None, row_bytes: int = 0,
                            geometry: "Optional[PayloadGeometry]" = None,
                            check_crc: bool = False,
                            config: Optional[HBamConfig] = None,
                            fallback_fn: Optional[Callable] = None):
    """Streamed fused decode: start the span's native job NOW (fetch on
    the caller's thread, inside the retry boundary) and return an iterable
    of packed row-array TUPLES — mode "rows" yields ``(rows,)``, mode
    "payload" ``(prefix, seq, qual)`` — in record order, each yielded the
    moment the native walk publishes it.  Feeding these straight into the
    FeedPipeline means staging-ring tiles for dispatch start packing
    before the span's tail blocks are even inflated.

    The rare cut-final-record span completes through ``fallback_fn`` (the
    two-pass oracle, returning the whole span's packed arrays as a tuple):
    rows ``[n:]`` of its result are appended, so the concatenated stream
    stays byte-identical to the buffered paths.  Corruption raises from
    the iterator (the consumer side) — callers gate streaming off when
    ``skip_bad_spans`` needs span-granular quarantine."""
    src = as_byte_source(src)
    started = _start_fused_span(src, span, mode, sel=sel,
                                row_bytes=row_bytes, geometry=geometry,
                                check_crc=check_crc, config=config,
                                data_escapes=False)

    def slices(lo: int, hi: int) -> Tuple[np.ndarray, ...]:
        if mode == "rows":
            return (dec.rows[lo:hi],)
        return (dec.prefix[lo:hi], dec.seq[lo:hi], dec.qual[lo:hi])

    if started is None:
        METRICS.count("pipeline.spans")     # empty span, still planned
        return iter(())
    dec, end_inflated, next_c, table = started
    src_size = src.size

    def gen():
        t_prev = time.perf_counter()
        try:
            # the consumption below IS the span's host decode (the
            # native waits are inflate+walk work): accrue it into the
            # same host_decode timer/walls the buffered paths use, with
            # fused_decode as the sub-stage, so the stage taxonomy keeps
            # meaning "all host decode work" under streaming
            with METRICS.timer("pipeline.host_decode"), \
                    METRICS.wall_timer("pipeline.host_decode_wall"), \
                    METRICS.timer("pipeline.fused_decode"), \
                    METRICS.span("bam.fused_decode_wall",
                                 nbytes=dec.inflated_bytes):
                for lo, hi in dec.chunks():
                    now = time.perf_counter()
                    # per-chunk handoff latency: the stall a staging
                    # tile pays waiting for its next batch of rows
                    METRICS.observe("pipeline.decode_chunk_s",
                                    now - t_prev)
                    t_prev = now
                    yield slices(lo, hi)
                n, tail = dec.finish()
        except GeneratorExit:
            raise
        except Exception as e:  # noqa: BLE001 — counter parity only
            # streamed corruption raises on the consumer side, outside
            # decode_with_retry — keep the spans/corrupt_spans counters
            # in step with the buffered/two-pass paths (the fallback
            # path below goes through decode_with_retry, which counts
            # its own failures; success counts via _fused_span_counts)
            METRICS.count("pipeline.spans")
            if classify_error(e) == hberrors.CORRUPT:
                METRICS.count("pipeline.corrupt_spans")
            raise
        if tail < end_inflated and next_c < src_size:
            full = fallback_fn()
            rest = tuple(a[n:] for a in full)
            if rest[0].shape[0]:
                yield rest
        else:
            _fused_span_counts(dec, table, n)

    return _FusedChunkStream(dec, gen())


def decode_span_host(source, span: FileVirtualSpan, geometry: DecodeGeometry,
                     check_crc: bool = False,
                     inflate_backend: str = "auto",
                     config: Optional[HBamConfig] = None,
                     ) -> Tuple[np.ndarray, np.ndarray, int, np.ndarray]:
    """Span mode: full inflated bytes + offsets padded to geometry caps.

    Returns (data[bytes_cap], offsets[records_cap], n_records, voffsets[n]).
    """
    got = _decode_span_fused(source, span, "offsets", check_crc=check_crc,
                             config=config) \
        if _use_fused(config, inflate_backend) else None
    if got is not None:
        data, offs, voffs, _ = got
    else:
        data, offs, voffs, _ = _decode_span_core(source, span, check_crc,
                                                 inflate_backend)
    n = int(offs.size)
    g = geometry
    if data.size > g.bytes_cap or n > g.records_cap:
        # PlanError: a mis-sized plan is a configuration fault — the retry
        # policy must neither re-decode it nor skip_bad_spans-eat it
        raise PlanError(
            f"span exceeds geometry: {data.size}B/{n} records vs caps "
            f"{g.bytes_cap}B/{g.records_cap} — plan smaller spans")
    out_data = np.zeros(g.bytes_cap, dtype=np.uint8)
    out_data[:data.size] = data
    out_offs = np.zeros(g.records_cap, dtype=np.int32)
    out_offs[:n] = offs
    return out_data, out_offs, n, voffs


def _interval_mask(data: np.ndarray, offs: np.ndarray, header, intervals
                   ) -> np.ndarray:
    """Row keep-mask for interval filtering on the mesh decode paths
    (hb/BAMInputFormat's hadoopbam.bam.intervals record filter): overlap
    test on pos + CIGAR reference span via the columnar batch."""
    from hadoop_bam_tpu.formats.bam import BamBatch
    from hadoop_bam_tpu.split.intervals import batch_overlap_mask

    batch = BamBatch(data, offs.astype(np.int64), header=header)
    return batch_overlap_mask(batch, intervals, header)


def decode_span_prefix_host(source, span: FileVirtualSpan,
                            check_crc: bool = False,
                            inflate_backend: str = "auto",
                            projection: Tuple[str, ...] = ALL_FIELDS,
                            want_voffs: bool = True,
                            intervals=None, header=None,
                            config: Optional[HBamConfig] = None,
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Prefix mode: pack each owned record's projected columns densely.

    Returns (rows[n, row_bytes] uint8, voffsets[n]).  This is the columnar
    transfer layout: for fixed-field consumers (flagstat, filters, sort
    keys) only the projected bytes cross the host->device link — 36 B/record
    for the full fixed prefix, 11 B for the flagstat projection — instead of
    the whole inflated span (~250 B/record on 150 bp WGS data), and field
    extraction on device needs no gather, the tile is already dense.  With
    the native library, walk + pack is a single C++ pass over the inflated
    bytes.
    """
    from hadoop_bam_tpu.utils import native

    row_bytes = projection_row_bytes(projection)
    ranges = projection_ranges(projection)
    if _use_fused(config, inflate_backend):
        got = _decode_span_fused(source, span, "rows", check_crc=check_crc,
                                 sel=ranges, row_bytes=row_bytes,
                                 want_voffs=want_voffs, config=config)
        if got is not None:
            data, offs, voffs, rows = got
            if intervals and offs.size:
                keep = _interval_mask(data, offs, header, intervals)
                rows = rows[keep]
                if voffs.size:
                    voffs = voffs[keep]
            return rows, voffs
    use_native = native.available()

    def walker(data, start, end_limit):
        if use_native:
            stop = min(int(end_limit), data.size)
            cap = max(16, (stop - start) // 36 + 1)
            rows, offs, tail = native.walk_bam_packed(
                np.ascontiguousarray(data), start, cap, ranges, row_bytes,
                stop=stop)
            return rows, offs, tail
        offs, tail = inflate_ops.walk_records(data, start=start)
        return None, offs, tail

    data, offs, voffs, rows = _decode_span_core(
        source, span, check_crc, inflate_backend, packed_walker=walker,
        want_voffs=want_voffs)
    if rows is None:
        # NumPy fallback: gather the full prefix tile, then slice columns.
        if offs.size == 0:
            rows = np.empty((0, row_bytes), dtype=np.uint8)
        else:
            idx = offs[:, None] + np.arange(PREFIX, dtype=offs.dtype)[None, :]
            tile = data[idx]
            cols = []
            for off, width in ranges:
                cols.append(tile[:, off:off + width])
            rows = np.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]
    if intervals and offs.size:
        keep = _interval_mask(data, offs, header, intervals)
        rows = rows[keep]
        if voffs.size:
            voffs = voffs[keep]
    return rows, voffs


def decode_span_payload_host(source, span: FileVirtualSpan,
                             geometry: PayloadGeometry,
                             check_crc: bool = False,
                             inflate_backend: str = "auto",
                             want_voffs: bool = False,
                             intervals=None, header=None,
                             config: Optional[HBamConfig] = None,
                             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                        np.ndarray]:
    """Payload mode: pack prefix + 4-bit seq + qual into dense row tiles.

    Returns (prefix[n, 36], seq[n, seq_stride], qual[n, qual_stride],
    voffsets[n]) — the host half of the tensor-batch feed.  Native path is
    one C++ pass (hbam_walk_bam_payload); the fallback walks offsets and
    packs per record in NumPy.
    """
    from hadoop_bam_tpu.utils import native

    g = geometry
    if _use_fused(config, inflate_backend):
        got = _decode_span_fused(source, span, "payload",
                                 check_crc=check_crc, geometry=g,
                                 want_voffs=want_voffs, config=config)
        if got is not None:
            data, offs, voffs, (prefix, seq, qual) = got
            if intervals and offs.size:
                keep = _interval_mask(data, offs, header, intervals)
                prefix, seq, qual = prefix[keep], seq[keep], qual[keep]
                if voffs.size:
                    voffs = voffs[keep]
            return prefix, seq, qual, voffs
    use_native = native.available()
    out: Dict[str, np.ndarray] = {}

    def walker(data, start, end_limit):
        if not use_native:
            offs, tail = inflate_ops.walk_records(data, start=start)
            return None, offs, tail
        stop = min(int(end_limit), data.size)
        cap = max(16, (stop - start) // 36 + 1)
        prefix, seq, qual, offs, tail = native.walk_bam_payload(
            np.ascontiguousarray(data), start, cap, g.max_len,
            g.seq_stride, g.qual_stride, stop=stop)
        out["prefix"], out["seq"], out["qual"] = prefix, seq, qual
        # rows (= prefix) flows through the core's keep-truncation; seq/qual
        # are truncated identically below from the kept count
        return prefix, offs, tail

    data, offs, voffs, rows = _decode_span_core(
        source, span, check_crc, inflate_backend, packed_walker=walker,
        want_voffs=want_voffs)
    n = int(offs.size)

    def apply_intervals(prefix, seq, qual, voffs):
        if intervals and offs.size:
            keep = _interval_mask(data, offs, header, intervals)
            prefix, seq, qual = prefix[keep], seq[keep], qual[keep]
            if voffs.size:
                voffs = voffs[keep]
        return prefix, seq, qual, voffs

    if rows is not None:
        return apply_intervals(rows, out["seq"][:n], out["qual"][:n],
                               voffs)

    # NumPy fallback: per-record pack from the inflated span.
    prefix = np.zeros((n, PREFIX), dtype=np.uint8)
    seq = np.zeros((n, g.seq_stride), dtype=np.uint8)
    qual = np.zeros((n, g.qual_stride), dtype=np.uint8)
    for i in range(n):
        p = int(offs[i])
        prefix[i] = data[p:p + PREFIX]
        l_read_name = int(data[p + 12])
        n_cigar = int(data[p + 16]) | (int(data[p + 17]) << 8)
        l_seq = int.from_bytes(data[p + 20:p + 24].tobytes(), "little",
                               signed=True)
        bs = int.from_bytes(data[p:p + 4].tobytes(), "little", signed=True)
        seq_off = p + PREFIX + l_read_name + 4 * n_cigar
        nb = (l_seq + 1) // 2
        # same payload-bounds validation as the native walker: a corrupt
        # l_seq must fail loudly, not pack neighboring records' bytes
        if l_seq < 0 or (seq_off - p) + nb + l_seq > 4 + bs:
            raise ValueError("malformed BAM record chain")
        use = min(l_seq, g.max_len)
        seq[i, :(use + 1) // 2] = data[seq_off:seq_off + (use + 1) // 2]
        qual[i, :use] = data[seq_off + nb:seq_off + nb + use]
    return apply_intervals(prefix, seq, qual, voffs)


def stack_span_group(source, spans: Sequence[FileVirtualSpan], n_dev: int,
                     geometry: DecodeGeometry, check_crc: bool = False,
                     executor: Optional[cf.ThreadPoolExecutor] = None,
                     config: Optional[HBamConfig] = None,
                     ) -> HostSpanBatch:
    """Decode up to n_dev spans (threaded) and stack into device-batch shape;
    missing spans become empty shards (zero records)."""
    spans = list(spans)[:n_dev]
    results = [None] * n_dev

    def work(i):
        return decode_span_host(source, spans[i], geometry, check_crc,
                                config=config)

    if executor is None:
        outs = [work(i) for i in range(len(spans))]
    else:
        outs = list(executor.map(work, range(len(spans))))
    data = np.zeros((n_dev, geometry.bytes_cap), dtype=np.uint8)
    offsets = np.zeros((n_dev, geometry.records_cap), dtype=np.int32)
    counts = np.zeros((n_dev,), dtype=np.int32)
    voffs: List[np.ndarray] = [np.empty(0, dtype=np.uint64)] * n_dev
    for i, (d, o, n, v) in enumerate(outs):
        data[i], offsets[i], counts[i], voffs[i] = d, o, n, v
    return HostSpanBatch(data, offsets, counts, voffs)


# ---------------------------------------------------------------------------
# Device steps
# ---------------------------------------------------------------------------

_STEP_CACHE: Dict[Tuple, Callable] = {}


def make_flagstat_step(mesh: Mesh, axis: str = "data") -> Callable:
    """Jitted sharded step: (data [n,D], offsets [n,N], counts [n]) ->
    flagstat dict (replicated scalars, psum over the data axis).

    Cached per (mesh, axis): jax.jit keys on function identity, so rebuilding
    the closure per call would recompile every step (a silent 20-40s per-call
    tax on real TPUs)."""
    key = ("flagstat", tuple(mesh.devices.flat), mesh.axis_names, axis)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]

    from hadoop_bam_tpu.ops.flagstat import FLAGSTAT_FIELDS

    def per_device(data, offsets, count):
        # shard_map gives [1, D] slices; drop the leading axis
        data, offsets, count = data[0], offsets[0], count[0]
        cols = unpack_fixed_fields(data, offsets)
        valid = jnp.arange(offsets.shape[0], dtype=jnp.int32) < count
        stats = flagstat_from_columns(cols, valid)
        # one stacked vector, not 16 scalars: one D2H readback instead
        # of 16 (per-readback cost not re-measured on the current machine)
        vec = jnp.stack([stats[k] for k in FLAGSTAT_FIELDS])
        return jax.lax.psum(vec, axis)

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(P(axis), P(axis), P(axis)),
                   out_specs=P())
    step = named_step("flagstat_step", fn)
    _STEP_CACHE[key] = step
    return step


def make_flagstat_tile_step(mesh: Mesh, axis: str = "data",
                            projection: Tuple[str, ...] = FLAGSTAT_PROJECTION
                            ) -> Callable:
    """Jitted sharded step over dense projected tiles: (tile [n, cap, row],
    counts [n]) -> psum'd flagstat vector.  No gather on device — the host
    packed the tile, so field extraction is strided slicing straight into
    the reductions."""
    key = ("flagstat_tile", tuple(mesh.devices.flat), mesh.axis_names, axis,
           projection)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]

    from hadoop_bam_tpu.ops.flagstat import FLAGSTAT_FIELDS

    def per_device(tile, count):
        tile, count = tile[0], count[0]
        cols = unpack_projected_tile(tile, projection)
        valid = jnp.arange(tile.shape[0], dtype=jnp.int32) < count
        stats = flagstat_from_columns(cols, valid)
        vec = jnp.stack([stats[k] for k in FLAGSTAT_FIELDS])
        return jax.lax.psum(vec, axis)

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(P(axis), P(axis)),
                   out_specs=P())
    step = named_step("flagstat_tile_step", fn)
    _STEP_CACHE[key] = step
    return step


def make_unpack_step(mesh: Mesh, axis: str = "data") -> Callable:
    """Jitted sharded step returning sharded SoA columns + valid mask —
    the feed for downstream mesh compute (the 'mapper' input)."""
    key = ("unpack", tuple(mesh.devices.flat), mesh.axis_names, axis)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]

    def per_device(data, offsets, count):
        data, offsets, count = data[0], offsets[0], count[0]
        cols = unpack_fixed_fields(data, offsets)
        valid = jnp.arange(offsets.shape[0], dtype=jnp.int32) < count
        cols = dict(cols)
        cols["valid"] = valid
        return jax.tree.map(lambda a: a[None], cols)

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(P(axis), P(axis), P(axis)),
                   out_specs=P(axis))
    step = named_step("unpack_step", fn)
    _STEP_CACHE[key] = step
    return step


# ---------------------------------------------------------------------------
# End-to-end driver
# ---------------------------------------------------------------------------

def iter_span_groups(spans: Sequence[FileVirtualSpan], n_dev: int
                     ) -> Iterator[List[FileVirtualSpan]]:
    spans = list(spans)
    for i in range(0, len(spans), n_dev):
        yield spans[i:i + n_dev]


def _totals_add(a, b):
    return jnp.add(a, b)


_ADD = named_step("totals_add", _totals_add)


def parse_config_intervals(config: HBamConfig, header):
    """config.bam_intervals -> parsed Interval list (None when unset)."""
    if not config.bam_intervals:
        return None
    from hadoop_bam_tpu.split.intervals import parse_intervals
    return parse_intervals(config.bam_intervals,
                           header.ref_names if header else None)


def _resilient_source(path, config: HBamConfig):
    """What a driver's decode stages read through, opened ONCE a scan and
    shared by its pool tasks (positioned reads keep no seek state): the
    file's byte source — through the chaos registry's wrapper when one is
    installed for the path — inside a RetryingByteSource when
    ``config.io_read_retries`` asks for read-level retries (backoff +
    per-read deadline under the span grain).  Nobody closes it: a pool
    task abandoned by an early close may still read, so the descriptor
    goes when the last task drops the source."""
    src = as_byte_source(path)
    r = int(config.io_read_retries or 0)
    if r <= 0 or not isinstance(path, (str, os.PathLike)):
        return src
    return RetryingByteSource(src, RetryPolicy(
        retries=r,
        backoff_base_s=float(config.retry_backoff_base_s),
        backoff_max_s=float(config.retry_backoff_max_s),
        deadline_s=config.io_read_deadline_s))


def _iter_tile_tuples(array_tuples, cap: int, specs: Sequence
                      ) -> Iterator[Tuple[Tuple[np.ndarray, ...], int]]:
    """Repack a stream of tuples of row arrays kept in lockstep
    (prefix/seq/qual/lengths share record order and counts) into
    ``cap``-row tiles across span boundaries; only the final tile is
    padded.

    ``specs``: per-array spec — an int width (uint8 [cap, w] tile) or a
    (width_or_None, dtype) pair; width None means a 1-D [cap] tile.

    The serial tiler: the FeedPipeline byte-identity tests use it as the
    oracle."""
    from collections import deque

    norm = [(s, np.uint8) if isinstance(s, int) else tuple(s)
            for s in specs]
    # deque: parts.pop(0) was O(n^2) on many-small-span plans
    parts: "deque[Tuple[np.ndarray, ...]]" = deque()
    have = 0

    def emit(take: int) -> Tuple[Tuple[np.ndarray, ...], int]:
        nonlocal have
        alloc = np.empty if take == cap else np.zeros
        tiles = tuple(
            alloc((cap,) if w is None else (cap, w), dtype=dt)
            for w, dt in norm)
        filled = 0
        while filled < take:
            head = parts[0]
            m = min(take - filled, head[0].shape[0])
            for t, h in zip(tiles, head):
                t[filled:filled + m] = h[:m]
            if m == head[0].shape[0]:
                parts.popleft()
            else:
                parts[0] = tuple(h[m:] for h in head)
            filled += m
        have -= take
        return tiles, take

    for arrays in array_tuples:
        assert len(arrays) == len(norm)
        if arrays[0].shape[0]:
            parts.append(tuple(arrays))
            have += arrays[0].shape[0]
        while have >= cap:
            yield emit(cap)
    if have:
        yield emit(have)


# canonical home is parallel/staging.py (the FeedPipeline shares it);
# the alias keeps this module's historical import surface
_bucket_cap = bucket_cap


def _bam_span_rows(scan: ScanFeed, path: str, spans, header, prefetch: int,
                   host: Callable, mode: str, **fused) -> Iterator:
    """A BAM scan's spans decoded on the plane ``select_plane`` chose —
    the unit the flagstat and payload families share.  Where the plan may
    stream, a span's fused native job hands its ``mode`` chunks
    (``_iter_fused_span_chunks``, ``fused`` its shape) to the packer as
    the walk lands them; otherwise ``host(src, span, check_crc, backend,
    intervals, config)`` — the family's ``decode_span_*_host`` — decodes
    it whole into its tuple of row arrays, two-pass on the zlib rung.
    Corrupt failures on the native rung re-decode on zlib (byte-identical)
    under the demotion ladder, and oracle-confirmed blame opens the native
    domain's breaker."""
    config = scan.config
    intervals = parse_config_intervals(config, header)
    decision = select_plane(config, intervals=intervals)
    ladder = decode_ladder(path, decision.plane, config) \
        if config.adaptive_planes else None
    src = _resilient_source(path, config)
    check_crc = bool(config.check_crc)
    window = max(1, prefetch) * decode_pool_size(config)
    if decision.stream_fused:
        window = _stream_window(window)

    def decode(span, plane=None):
        # ladder-aware: decode_with_retry drives ``plane`` down the
        # demotion ladder on corrupt failures (None = the selected plane)
        hb = decision.plane if plane is None else plane
        if hb in ("auto", "native"):
            # chaos point for plane-local native faults — fires INSIDE the
            # retry/ladder boundary, so injected faults retry/demote
            # exactly like real ones
            chaos.fire("decode.native", span=str(span))
            if decision.stream_fused:
                # the tail-cut fallback runs LATER, on the consumer thread:
                # it re-reads the span, so it gets its own pass through the
                # retry policy (transients there must heal exactly like the
                # eager fetch's do)
                return _iter_fused_span_chunks(
                    src, span, mode, check_crc=check_crc, config=config,
                    fallback_fn=lambda: decode_with_retry(
                        lambda s: host(src, s, check_crc, decision.plane,
                                       None, _fused_off(config)),
                        span, config), **fused)
        return host(src, span, check_crc, hb, intervals,
                    config if hb != "zlib" else _fused_off(config))

    return scan.decoded(spans, decode, window, ladder=ladder,
                        chunk_streams=True)


def _payload_scan(path: str, config: HBamConfig, mesh,
                  geometry: PayloadGeometry, *, balance: bool = False,
                  quarantine: Optional[QuarantineManifest] = None
                  ) -> ScanFeed:
    """The feed of a BAM payload scan: prefix + 4-bit seq + qual rows in
    tiles of ``geometry.tile_records``; the final partial group shrinks to
    a dispatch bucket unless ``geometry.fixed_shape``."""
    return ScanFeed("bam", config, mesh,
                    (PREFIX, geometry.seq_stride, geometry.qual_stride),
                    geometry.tile_records, block_n=geometry.block_n,
                    fixed_shape=geometry.fixed_shape, balance=balance,
                    quarantine=quarantine, gate=path)


def _payload_rows(scan: ScanFeed, path: str, spans,
                  geometry: PayloadGeometry, header, prefetch: int):
    """A BAM payload scan's decoded (prefix, seq, qual) rows."""
    def host(src, span, check_crc, backend, intervals, cfg):
        return decode_span_payload_host(
            src, span, geometry, check_crc, backend, intervals=intervals,
            header=header, config=cfg)[:3]

    return _bam_span_rows(scan, path, spans, header, prefetch, host,
                          "payload", geometry=geometry)


def bam_payload_batches(path: str, spans: Sequence[FileVirtualSpan], mesh,
                        geometry: PayloadGeometry, config: HBamConfig,
                        header) -> Iterator[Dict]:
    """``BamDataset.tensor_batches``: the payload scan's groups as device
    dicts ``prefix`` / ``seq_packed`` / ``qual`` / ``n_records``, rows in
    the serial placement (no ``balance``) so public batches stay
    byte-stable across releases."""
    scan = _payload_scan(path, config, mesh, geometry)
    yield from scan.batches(
        _payload_rows(scan, path, spans, geometry, header, 2),
        ("prefix", "seq_packed", "qual"))


class _StatTotals:
    """Deferred 64-bit host accumulation of per-group device stat sums.

    ``add`` just enqueues the (f32 sums, i32 counts) device arrays —
    dispatch stays async so host decode overlaps device compute; ``drain``
    fetches them all at the end and reduces in float64/int64 (per-group
    device sums are exact; the running totals must be 64-bit)."""

    def __init__(self):
        self._pairs: List[Tuple] = []

    def add(self, fvec, ivec) -> None:
        self._pairs.append((fvec, ivec))

    def __bool__(self) -> bool:
        return bool(self._pairs)

    def drain(self) -> Tuple[np.ndarray, np.ndarray]:
        f0, i0 = self._pairs[0]
        tf = np.zeros(np.shape(f0), np.float64)
        ti = np.zeros(np.shape(i0), np.int64)
        with METRICS.span("pipeline.combine_wall", groups=len(self._pairs)):
            # ONE bulk device_get for every queued group (a per-group
            # fetch in the loop is a sync per group)
            for f, i in jax.device_get(self._pairs):
                tf += f
                ti += i
        return tf, ti


def _payload_stats_tail(stats, valid, axis: str):
    """Shared psum tail of the payload-stats steps: (f32[2] mean sums,
    i32[1+16] n_reads + base_hist) — counts ride the int vector because
    f32 accumulation drifts past 2^24."""
    nonpad = valid.astype(jnp.float32)
    fvec = jnp.stack([(stats["gc"] * nonpad).sum(),
                      (stats["mean_qual"] * nonpad).sum()])
    ivec = jnp.concatenate([
        valid.astype(jnp.int32).sum()[None], stats["base_hist"]])
    return jax.lax.psum(fvec, axis), jax.lax.psum(ivec, axis)


def _attach_quarantine(result: Dict,
                       quarantine: Optional[QuarantineManifest]) -> Dict:
    """Attach the quarantine manifest to a driver's result dict.  Only when
    non-empty: clean runs keep their exact historical result shape, and
    dict-equality comparisons across runs/hosts stay valid."""
    if quarantine:
        result["quarantine"] = quarantine.to_dicts()
    return result


def _payload_stats_result(totals: _StatTotals) -> Dict[str, object]:
    from hadoop_bam_tpu.ops.seq_pallas import N_CODES
    if not totals:
        return {"n_reads": 0, "mean_gc": 0.0, "mean_qual": 0.0,
                "base_hist": np.zeros(N_CODES, np.int64)}
    tf, ti = totals.drain()
    n = max(float(ti[0]), 1.0)
    return {"n_reads": int(ti[0]), "mean_gc": float(tf[0] / n),
            "mean_qual": float(tf[1] / n), "base_hist": ti[1:]}


def make_seq_stats_step(mesh: Mesh, geometry: PayloadGeometry,
                        axis: str = "data") -> Callable:
    """Jitted sharded step over payload tiles: (prefix [n, cap, 36],
    seq [n, cap, SB], qual [n, cap, QB], counts [n]) -> psum'd
    (f32 [2] (sum_gc, sum_mean_qual), i32 [1 + 16] (n_reads, base_hist))
    pair — see _payload_stats_tail.

    Lengths come from the prefix tile's l_seq column on device, clipped to
    max_len (the pack truncates there); padding rows get length 0 via the
    count mask.  The per-tile compute is the Pallas fused kernel
    (ops/seq_pallas.py) — bases never materialise in HBM.
    """
    key = ("seq_stats", tuple(mesh.devices.flat), mesh.axis_names, axis,
           geometry)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]

    from hadoop_bam_tpu.ops.seq_pallas import seq_qual_stats

    # interpret mode keyed to the MESH's devices, not the default backend:
    # a virtual CPU mesh in a TPU-default process still needs the
    # interpreter
    interpret = mesh.devices.flat[0].platform != "tpu"

    def per_device(prefix, seq, qual, count):
        prefix, seq, qual, count = prefix[0], seq[0], qual[0], count[0]
        with jax.named_scope("unpack"):
            cols = unpack_projected_tile(prefix, ALL_FIELDS)
            valid = jnp.arange(prefix.shape[0], dtype=jnp.int32) < count
            lengths = jnp.where(
                valid, jnp.minimum(cols["l_seq"], geometry.max_len), 0)
        with jax.named_scope("kernel"):
            stats = seq_qual_stats(seq, qual, lengths,
                                   block_n=geometry.block_n,
                                   interpret=interpret)
        with jax.named_scope("psum"):
            return _payload_stats_tail(stats, valid, axis)

    # check_vma=False: pallas_call's out_shape has no varying-mesh-axes
    # annotation, which the default shard_map VMA check rejects
    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(P(axis), P(axis), P(axis), P(axis)),
                   out_specs=(P(), P()), check_vma=False)
    step = named_step("seq_stats_step", fn)
    _STEP_CACHE[key] = step
    return step


def _read_specs(geometry: PayloadGeometry) -> Tuple:
    """A read-payload tile: 4-bit seq, qual, and each read's length."""
    return (geometry.seq_stride, geometry.qual_stride, (None, np.int32))


def stream_read_tensor_batches(spans, read_span_fn, config: HBamConfig,
                               mesh: Optional[Mesh],
                               geometry: "Optional[PayloadGeometry]",
                               tiles_fn=None,
                               quarantine: Optional[QuarantineManifest] = None,
                               fmt: str = "read",
                               ) -> Iterator[Dict]:
    """Shared tensor-batch generator for text/record read formats
    (FASTQ/QSEQ/CRAM): ``read_span_fn(span)`` returns a list of objects
    with ``.sequence``/``.quality`` attributes; yields sharded device
    batches {seq_packed, qual, lengths, n_records}.

    ``tiles_fn(span, geometry)``, when given, replaces the whole
    span->objects->tiles stage with a direct (seq, qual, lengths) tile
    producer — the columnar fast path (CRAM uses it to skip SAM record
    materialization entirely)."""
    from hadoop_bam_tpu.api.read_datasets import fragments_to_payload_tiles

    if geometry is None:
        geometry = PayloadGeometry()
    scan = ScanFeed(fmt, config, mesh, _read_specs(geometry),
                    geometry.tile_records, block_n=geometry.block_n,
                    fixed_shape=geometry.fixed_shape, quarantine=quarantine)

    def decode(s):
        if tiles_fn is not None:
            return tiles_fn(s, geometry)
        return fragments_to_payload_tiles(
            read_span_fn(s), geometry.seq_stride,
            geometry.qual_stride, geometry.max_len)

    yield from scan.batches(
        scan.decoded(spans, decode, 2 * decode_pool_size(config)),
        ("seq_packed", "qual", "lengths"))


def make_read_stats_step(mesh: Mesh, geometry: PayloadGeometry,
                         axis: str = "data") -> Callable:
    """Like make_seq_stats_step (same (f32[2], i32[1+16]) return pair) but
    with explicit per-read lengths instead of a BAM prefix tile — the step
    for text read formats (FASTQ/QSEQ) whose payload tiles come from
    fragments_to_payload_tiles."""
    key = ("read_stats", tuple(mesh.devices.flat), mesh.axis_names, axis,
           geometry)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]

    from hadoop_bam_tpu.ops.seq_pallas import seq_qual_stats

    interpret = mesh.devices.flat[0].platform != "tpu"

    def per_device(seq, qual, lengths, count):
        seq, qual, lengths, count = seq[0], qual[0], lengths[0], count[0]
        valid = jnp.arange(seq.shape[0], dtype=jnp.int32) < count
        lengths = jnp.where(valid, lengths, 0)
        with jax.named_scope("kernel"):
            stats = seq_qual_stats(seq, qual, lengths,
                                   block_n=geometry.block_n,
                                   interpret=interpret)
        with jax.named_scope("psum"):
            return _payload_stats_tail(stats, valid, axis)

    fn = shard_map(per_device, mesh=mesh, in_specs=(P(axis),) * 4,
                   out_specs=(P(), P()), check_vma=False)
    step = named_step("read_stats_step", fn)
    _STEP_CACHE[key] = step
    return step


def pipeline_grain(config: HBamConfig = DEFAULT_CONFIG) -> int:
    """The pipeline grain in bytes (``pipeline_span_count``'s): what a
    whole-file driver cuts a file — or a stream's inflated text — by."""
    return max(1, min(int(config.split_size), 4 << 20))


def pipeline_span_count(path, n_dev: int,
                        config: HBamConfig = DEFAULT_CONFIG,
                        size_scale: float = 1.0) -> int:
    """Span count at the PIPELINE grain for a whole-file stats driver.

    config.split_size is the HDFS-style job grain (128 MiB default); a
    driver that used it directly would get one span for most files and
    serialize host tokenize against device dispatch end to end.  The
    pipeline grain is min(split_size, 4 MiB) — honoring a user split
    size configured SMALLER than the pipeline default (a memory bound)
    while still slicing big-grain configs fine enough to overlap.
    Sized via as_byte_source so non-local byte sources keep pipelining;
    unsizable sources fall back to one span per device.  ``size_scale``
    weighs the file's bytes (the variant driver's: a file that deflates
    far better than the grain assumes, variant_span_count).
    """
    grain = float(pipeline_grain(config))
    try:
        with scoped_byte_source(path) as src:
            size = src.size
    except Exception:  # noqa: BLE001 — planning must not fail the driver
        return n_dev
    return max(n_dev, int(np.ceil(size * size_scale / grain)))


# text read-format extensions recognized by the payload stats dispatch
# (single source of truth — the CLI imports these)
FASTQ_EXTS = (".fastq", ".fq", ".fastq.gz", ".fq.gz")
QSEQ_EXTS = (".qseq", ".qseq.gz")
TEXT_READ_EXTS = FASTQ_EXTS + QSEQ_EXTS
CRAM_EXTS = (".cram",)


def cram_seq_stats_file(path: str, mesh: Optional[Mesh] = None,
                        config: HBamConfig = DEFAULT_CONFIG,
                        geometry: Optional[PayloadGeometry] = None,
                        spans=None,
                        quarantine: Optional[QuarantineManifest] = None,
                        ) -> Dict[str, object]:
    """GC / quality / base stats over a CRAM — what ``hbam seq-stats
    x.cram --reference x.fa`` runs: a thin plan builder
    (``plan/builders.py::cram_stats_plan``) over the one executor, whose
    runner (``_read_stats_impl``) decodes container-aligned spans with
    the columnar slice decoder into the FASTQ plan's tiles and step.  The
    reference is ``config.cram_reference_source_path``."""
    from hadoop_bam_tpu.plan import builders
    from hadoop_bam_tpu.plan import executor as plan_executor

    plan = builders.cram_stats_plan(path, config, geometry=geometry)
    return plan_executor.execute(plan, config=config, mesh=mesh,
                                 geometry=geometry, spans=spans,
                                 quarantine=quarantine)


def fastq_seq_stats_file(path: str, mesh: Optional[Mesh] = None,
                         config: HBamConfig = DEFAULT_CONFIG,
                         geometry: Optional[PayloadGeometry] = None,
                         spans=None,
                         prefetch: int = 2,
                         quarantine: Optional[QuarantineManifest] = None,
                         ) -> Dict[str, object]:
    """Distributed GC / quality / base stats over a FASTQ (or QSEQ) file,
    plain or gzip'd — the text-format twin of seq_stats_file, through the
    same fused Pallas payload kernel, and like it a thin plan builder
    over the one executor."""
    from hadoop_bam_tpu.plan import builders
    from hadoop_bam_tpu.plan import executor as plan_executor

    plan = builders.read_stats_plan(path, config, geometry=geometry)
    return plan_executor.execute(plan, config=config, mesh=mesh,
                                 geometry=geometry, spans=spans,
                                 prefetch=prefetch, quarantine=quarantine)


def _read_unit(path: str, fmt: str, config: HBamConfig,
               geometry: PayloadGeometry, prefetch: int):
    """What a read-payload scan decodes, by the source's format:
    ``(dataset, decode, window, stream)``, ``stream(spans)`` the chunks a
    compressed text file is cut into as it inflates (None where the units
    are the spans).

    CRAM: a span is a run of whole containers read with one read and
    decoded by ``api/cram_dataset.py::cram_span_tiles`` (the blocks the
    columnar decoder asks for, native rANS Nx16, bases gathered from the
    memory-mapped reference).  FASTQ / QSEQ: a plain file's span is read
    and tokenised on the pool; a gzip'd file is one span whose chunks one
    thread inflates in order (``iter_span_chunks``) while the pool
    tokenises the ones before."""
    from hadoop_bam_tpu.api.read_datasets import (
        fastq_text_to_payload_tiles, fragments_to_payload_tiles,
        open_fastq, open_qseq, qseq_text_to_payload_tiles,
    )

    if fmt == "cram":
        from hadoop_bam_tpu.api.cram_dataset import cram_span_tiles, open_cram

        ds = open_cram(path, config)
        # a span is in memory after its one read and its decode is
        # compute, much of it NumPy under the interpreter lock: as for a
        # text stream's tokenise, more spans in flight than cores only
        # contend for the lock (and hold their columns), so the window is
        # the text stream's
        return (ds, lambda s: cram_span_tiles(ds, s, geometry),
                text_stream_window(), None)
    is_qseq = fmt == "qseq"
    ds = open_qseq(path, config) if is_qseq else open_fastq(path, config)
    # Vectorized tokenize (no per-read Python objects) whenever the config
    # doesn't force the object path: failed-QC filtering needs parsed
    # fields (qseq's filter column / fastq's name metadata).
    if is_qseq:
        fast_tiles = not config.qseq_filter_failed_qc
        qual_offset = config.qseq_base_quality_encoding.value
        text_to_tiles = qseq_text_to_payload_tiles
    else:
        fast_tiles = not config.fastq_filter_failed_qc
        qual_offset = config.fastq_base_quality_encoding.value
        text_to_tiles = fastq_text_to_payload_tiles
    # plain spans wait for their reads, so more of them than cores are in
    # flight; a stream's chunks are pure compute behind one inflater
    streamed = ds.is_compressed()
    grain = pipeline_grain(config)

    def decode(unit):
        try:
            with METRICS.span(f"{fmt}.fetch_wall"):
                text = unit.text() if streamed else ds.read_span_text(unit)
            t_cpu = time.thread_time_ns()
            try:
                with METRICS.span(f"{fmt}.tokenize_wall"):
                    if fast_tiles:
                        return text_to_tiles(
                            text, geometry.seq_stride, geometry.qual_stride,
                            geometry.max_len, qual_offset)
                    return fragments_to_payload_tiles(
                        ds.parse_text(text), geometry.seq_stride,
                        geometry.qual_stride, geometry.max_len)
            finally:
                METRICS.count(f"{fmt}.tokenize_busy_ns",
                              time.thread_time_ns() - t_cpu)
        finally:
            if streamed:
                unit.done()

    if not streamed:
        return ds, decode, max(1, prefetch) * decode_pool_size(config), None
    return (ds, decode, text_stream_window(),
            lambda spans: itertools.chain.from_iterable(
                ds.iter_span_chunks(s, grain) for s in spans))


def _read_stats_impl(path: str, fmt: str, mesh: Optional[Mesh] = None,
                     config: HBamConfig = DEFAULT_CONFIG,
                     geometry: Optional[PayloadGeometry] = None,
                     spans=None,
                     prefetch: int = 2,
                     quarantine: Optional[QuarantineManifest] = None,
                     ) -> Dict[str, object]:
    """The read-payload stats implementation (executor runner; ``fmt`` is
    the plan's source format, "fastq" | "qseq" | "cram").  The formats
    differ only in the unit (``_read_unit``); from its tiles on they run
    the same feed and step.  A streamed chunk is not re-readable — its
    tiles may be on the device before a later chunk fails — so nothing of
    a stream is retried or quarantined: an error ends the scan."""
    if geometry is None:
        geometry = PayloadGeometry()
    # balance spreads the final partial group over all shards (stats are
    # psum'd, placement-invariant)
    scan = ScanFeed(fmt, config, mesh, _read_specs(geometry),
                    geometry.tile_records, block_n=geometry.block_n,
                    fixed_shape=geometry.fixed_shape, balance=True,
                    quarantine=QuarantineManifest() if quarantine is None
                    else quarantine)
    ds, decode, window, stream = _read_unit(path, fmt, config, geometry,
                                            prefetch)
    if spans is None:
        with METRICS.span(f"{fmt}.plan_wall"):
            spans = ds.spans(
                num_spans=pipeline_span_count(path, scan.n_dev, config))
    spans = list(spans)
    step = make_read_stats_step(scan.mesh, geometry)
    totals = _StatTotals()
    # async; drained once at the end
    scan.run(scan.decoded(spans, decode, window,
                          stream=None if stream is None else stream(spans)),
             lambda args, _counts: totals.add(*step(*args)))
    return _attach_quarantine(_payload_stats_result(totals), scan.quarantine)


def seq_stats_file(path: str, mesh: Optional[Mesh] = None,
                   config: HBamConfig = DEFAULT_CONFIG,
                   geometry: Optional[PayloadGeometry] = None,
                   header: Optional[SAMHeader] = None,
                   spans: Optional[Sequence[FileVirtualSpan]] = None,
                   prefetch: int = 2,
                   quarantine: Optional[QuarantineManifest] = None,
                   ) -> Dict[str, object]:
    """Distributed sequence/quality stats over a whole BAM: mean GC
    fraction, mean per-read quality, and the 4-bit base-code histogram —
    computed by the fused Pallas payload kernel on every device of the
    mesh.  The payload analog of flagstat_file, and like it a thin plan
    builder over the one executor."""
    from hadoop_bam_tpu.plan import builders
    from hadoop_bam_tpu.plan import executor as plan_executor

    plan = builders.seq_stats_plan(path, config, geometry=geometry)
    return plan_executor.execute(plan, config=config, mesh=mesh,
                                 geometry=geometry, header=header,
                                 spans=spans, prefetch=prefetch,
                                 quarantine=quarantine)


def _seq_stats_impl(path: str, mesh: Optional[Mesh] = None,
                    config: HBamConfig = DEFAULT_CONFIG,
                    geometry: Optional[PayloadGeometry] = None,
                    header: Optional[SAMHeader] = None,
                    spans: Optional[Sequence[FileVirtualSpan]] = None,
                    prefetch: int = 2,
                    quarantine: Optional[QuarantineManifest] = None,
                    ) -> Dict[str, object]:
    """The payload-stats mesh-feed implementation (executor runner): the
    BAM span unit under the shared routing decision, fused Pallas kernel
    per tile group, 64-bit host drain."""
    from hadoop_bam_tpu.formats.bamio import read_bam_header

    if geometry is None:
        geometry = PayloadGeometry()
    assert geometry.tile_records % geometry.block_n == 0
    if header is None:
        header, _ = read_bam_header(path)
    scan = _payload_scan(path, config, mesh, geometry, balance=True,
                         quarantine=QuarantineManifest() if quarantine is None
                         else quarantine)
    if spans is None:
        spans = _plan_bam_scan(path, header, config, scan.n_dev, 8 << 20)
    step = make_seq_stats_step(scan.mesh, geometry)
    totals = _StatTotals()
    scan.run(_payload_rows(scan, path, spans, geometry, header, prefetch),
             lambda args, _counts: totals.add(*step(*args)))
    return _attach_quarantine(_payload_stats_result(totals), scan.quarantine)


def flagstat_file(path: str, mesh: Optional[Mesh] = None,
                  config: HBamConfig = DEFAULT_CONFIG,
                  geometry: Optional[DecodeGeometry] = None,
                  header: Optional[SAMHeader] = None,
                  spans: Optional[Sequence[FileVirtualSpan]] = None,
                  prefetch: int = 2,
                  quarantine: Optional[QuarantineManifest] = None,
                  ) -> Dict[str, int]:
    """Distributed flagstat over a whole BAM — the minimum end-to-end slice
    (SURVEY.md section 7): plan -> shard -> inflate -> pack prefixes ->
    device reduce.

    A thin plan builder since the plan/execute layer landed: compiles to
    ``plan.builders.flagstat_plan`` and runs through the one executor
    (byte-identical to the inline path ``_flagstat_impl``, which the
    ``plan_overhead_pct`` bench row pins against this wrapper)."""
    from hadoop_bam_tpu.plan import builders
    from hadoop_bam_tpu.plan import executor as plan_executor

    plan = builders.flagstat_plan(path, config)
    return plan_executor.execute(plan, config=config, mesh=mesh,
                                 geometry=geometry, header=header,
                                 spans=spans, prefetch=prefetch,
                                 quarantine=quarantine)


def _plan_bam_scan(path: str, header, config: HBamConfig, n_dev: int,
                   span_bytes: int) -> List[FileVirtualSpan]:
    """A whole-BAM scan's span plan: about ``span_bytes`` of the file a
    span, one a device at least."""
    from hadoop_bam_tpu.split.planners import plan_spans_cached

    with scoped_byte_source(path) as src:
        n_spans = max(n_dev, int(np.ceil(src.size / span_bytes)))
    with METRICS.span("bam.plan_wall", spans=n_spans):
        return plan_spans_cached(path, header, config, num_spans=n_spans)


def _flagstat_impl(path: str, mesh: Optional[Mesh] = None,
                   config: HBamConfig = DEFAULT_CONFIG,
                   geometry: Optional[DecodeGeometry] = None,
                   header: Optional[SAMHeader] = None,
                   spans: Optional[Sequence[FileVirtualSpan]] = None,
                   prefetch: int = 2,
                   quarantine: Optional[QuarantineManifest] = None,
                   ) -> Dict[str, int]:
    """The flagstat mesh-feed implementation (executor runner).

    Uses the columnar projected-tile path: host threads inflate spans and
    pack just the flagstat columns (11 B/record over the link instead of
    whole spans); the device sees dense tiles and reduces them with one
    psum'd step per tile group.  Transfers issue sequentially from one
    thread (whether concurrent device_put streams would help has not
    been measured on the current machine); the host decode pool runs
    ``prefetch * n_workers`` spans ahead of the transfer loop, which
    bounds peak host memory.
    """
    from hadoop_bam_tpu.formats.bamio import read_bam_header
    from hadoop_bam_tpu.ops.flagstat import FLAGSTAT_FIELDS

    if geometry is None:
        geometry = DecodeGeometry()
    if header is None:
        header, _ = read_bam_header(path)
    projection = FLAGSTAT_PROJECTION
    row_bytes = projection_row_bytes(projection)
    # the upgraded quarantine circuit: a file whose last run tripped the
    # bad-span-fraction breaker fast-fails here while OPEN (retry_after
    # hint attached) instead of re-planning a doomed run; HALF_OPEN lets
    # this run through as the probe and a clean finish heals it.
    # balance: the final partial group spreads across all shards and
    # shrinks to a dispatch bucket — a file smaller than one full group
    # otherwise lands entirely on device 0 and ships n_dev*cap rows of
    # padding (the 8-device inverse-scaling tax); the bucket ladder
    # bounds the extra jit shapes at two.
    scan = ScanFeed("bam", config, mesh, (row_bytes,),
                    geometry.tile_records, balance=True,
                    quarantine=QuarantineManifest() if quarantine is None
                    else quarantine, gate=path)
    if spans is None:
        # Span size trades host-decode parallelism (smaller = more spans
        # in flight) against the per-span start (one read, one header
        # walk, one native job: PERF.md section 5); tiles repack across
        # span boundaries, so this does NOT couple to the device
        # geometry.  4 MiB is the size every chip number in PERF.md was
        # taken at; it has not been swept on that host.
        spans = _plan_bam_scan(path, header, config, scan.n_dev, 4 << 20)
    step = make_flagstat_tile_step(scan.mesh, projection=projection)
    totals_vec = None

    def consume(args, _counts):
        nonlocal totals_vec
        vec = step(*args)
        totals_vec = vec if totals_vec is None else _ADD(totals_vec, vec)

    def host(src, span, check_crc, backend, intervals, cfg):
        return (decode_span_prefix_host(
            src, span, check_crc, backend, projection, want_voffs=False,
            intervals=intervals, header=header, config=cfg)[0],)

    # chunk-streamed where the plan may stream: the packer takes a span's
    # rows as its native walk publishes them
    scan.run(_bam_span_rows(scan, path, spans, header, prefetch, host,
                            "rows", sel=projection_ranges(projection),
                            row_bytes=row_bytes),
             consume)
    if totals_vec is None:
        host = np.zeros(len(FLAGSTAT_FIELDS), dtype=np.int64)
    else:
        with METRICS.timer("pipeline.device_drain"), \
                METRICS.span("bam.combine_wall"):
            host = np.asarray(jax.device_get(totals_vec), dtype=np.int64)
    return _attach_quarantine(
        {k: int(host[i]) for i, k in enumerate(FLAGSTAT_FIELDS)},
        scan.quarantine)


# Coverage row layout: the fixed-field projection (offsets sourced from
# ops/unpack_bam.py::FIXED_FIELDS — ONE place owns the BAM field map; the
# high-position regression in test_cigar.py is what hand-copied offsets
# cost), then the cigar words.
_COVERAGE_PROJECTION = ("refid", "pos", "n_cigar", "flag")
_CIGAR_ROW_HDR = projection_row_bytes(_COVERAGE_PROJECTION)   # 12


def _cigar_row_bytes(max_cigar: int) -> int:
    return _CIGAR_ROW_HDR + 4 * max_cigar


def decode_span_cigar_rows(source, span: FileVirtualSpan, max_cigar: int,
                           check_crc: bool = False,
                           config: Optional[HBamConfig] = None) -> np.ndarray:
    """Host stage of the coverage path: inflate a span and pack one dense
    row per record — the (refid, pos, n_cigar, flag) projection + the
    cigar words, zero-padded to ``max_cigar`` ops.  ~268 B/record over
    the link instead of whole padded spans (the flagstat projected-tile
    idea applied to the one variable-length series coverage needs).

    Ops past ``max_cigar`` are dropped from the row; the row's n_cigar
    field keeps the FULL count so the driver can raise outside the
    span-retry boundary (a user-parameter error must not be retried or
    skip_bad_spans-eaten as corruption).
    """
    host_backend = resolve_inflate_backend(config)
    got = _decode_span_fused(source, span, "offsets", check_crc=check_crc,
                             want_voffs=False, config=config) \
        if _use_fused(config, host_backend) else None
    if got is not None:
        d, o, _voffs, _ = got      # fused: inflate+walk+CRC in one sweep
    else:
        d, o, _voffs, _ = _decode_span_core(source, span, check_crc,
                                            host_backend, want_voffs=False)
    c = o.size
    w = _cigar_row_bytes(max_cigar)
    rows = np.zeros((c, w), dtype=np.uint8)
    if c == 0:
        return rows
    o64 = o.astype(np.int64)
    dst = 0
    for src_off, width in projection_ranges(_COVERAGE_PROJECTION):
        rows[:, dst:dst + width] = \
            d[o64[:, None] + np.arange(src_off, src_off + width)]
        dst += width
    nc_off = _CIGAR_ROW_HDR - 4          # n_cigar u16 within the row
    n_cigar = (rows[:, nc_off].astype(np.int64)
               | (rows[:, nc_off + 1].astype(np.int64) << 8))
    l_read_name = d[o64 + 12].astype(np.int64)
    cigar_off = o64 + PREFIX + l_read_name
    # rows keep the FULL n_cigar value; ops past max_cigar are dropped
    # here and the DRIVER raises (outside the span-retry boundary, so a
    # user-parameter error is neither retried nor skip_bad_spans-eaten)
    byte_counts = 4 * np.minimum(n_cigar, max_cigar)
    total_b = int(byte_counts.sum())
    if total_b:
        starts_b = np.cumsum(byte_counts) - byte_counts
        flat_b = (np.arange(total_b, dtype=np.int64)
                  - np.repeat(starts_b, byte_counts))
        row_i = np.repeat(np.arange(c, dtype=np.int64), byte_counts)
        rows[row_i, _CIGAR_ROW_HDR + flat_b] = \
            d[np.repeat(cigar_off, byte_counts) + flat_b]
    return rows


def make_coverage_step(mesh: Mesh, window: int, max_cigar: int,
                       axis: str = "data") -> Callable:
    """Jitted sharded step: dense cigar-row tiles -> per-base window depth.

    Returns PER-DEVICE depth [n_dev, window] (no collective): the driver
    accumulates shard-locally across tile groups and reduces across
    devices once at the end, instead of paying a window-sized psum per
    dispatch."""
    key = ("coverage", tuple(mesh.devices.flat), mesh.axis_names, axis,
           window, max_cigar)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]

    from hadoop_bam_tpu.ops.cigar import window_coverage_from_tiles

    def per_device(tile, count, target_refid, win_start):
        tile, count = tile[0], count[0]
        cols = unpack_projected_tile(tile[:, :_CIGAR_ROW_HDR],
                                     _COVERAGE_PROJECTION)
        ops4 = tile[:, _CIGAR_ROW_HDR:].reshape(
            tile.shape[0], max_cigar, 4).astype(jnp.uint32)
        ops = (ops4[..., 0] | (ops4[..., 1] << 8) | (ops4[..., 2] << 16)
               | (ops4[..., 3] << 24))
        valid = jnp.arange(tile.shape[0], dtype=jnp.int32) < count
        depth = window_coverage_from_tiles(
            ops, cols["pos"], cols["refid"], cols["flag"], valid,
            target_refid, win_start, window)
        return depth[None]

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(P(axis), P(axis), P(), P()),
                   out_specs=P(axis))
    step = named_step("coverage_step", fn)
    _STEP_CACHE[key] = step
    return step


def coverage_file(path: str, region, mesh: Optional[Mesh] = None,
                  config: HBamConfig = DEFAULT_CONFIG,
                  header: Optional[SAMHeader] = None,
                  spans: Optional[Sequence[FileVirtualSpan]] = None,
                  max_cigar: int = 64, tile_records: int = 1 << 15,
                  prefetch: int = 2,
                  quarantine: Optional[QuarantineManifest] = None,
                  ) -> np.ndarray:
    """Distributed per-base aligned-base depth over a genomic window —
    the first analysis op past flagstat (SURVEY.md section 7 kernel (b)):
    plan -> shard -> inflate -> pack cigar rows -> device diff-scatter
    pileup -> psum.

    ``region`` is a samtools-style string ("chr20:1,000-2,000", 1-based
    inclusive) or an Interval.  Returns int32 depth, one entry per base.
    When a ``.bai`` sidecar exists the span plan is trimmed to the
    region's chunks; otherwise the whole file streams through and rows
    outside the region mask to zero on device.
    """
    from hadoop_bam_tpu.formats.bamio import read_bam_header
    from hadoop_bam_tpu.split.intervals import Interval, resolve_interval

    if header is None:
        header, _ = read_bam_header(path)
    if not isinstance(region, Interval):
        region = resolve_interval(region, header.ref_names)
    if region.rname not in header.ref_names:
        raise ValueError(f"region reference {region.rname!r} not in header")
    target_refid = header.ref_names.index(region.rname)
    ref_len = header.ref_lengths[target_refid]
    end = min(region.end, ref_len)
    window = end - region.start + 1
    if window <= 0:
        raise ValueError(f"empty region {region}")
    if window > (1 << 26):
        raise ValueError(f"region spans {window} bases; cap is 2^26 — "
                         f"tile larger regions across calls")
    win_start = region.start - 1          # 0-based half-open window

    # full-width ring tiles, their HEIGHT fixed (the step is cached per
    # (window, op width)); dispatch cuts each group down to its real op
    # width before it crosses the link
    scan = ScanFeed("bam", config, mesh, (_cigar_row_bytes(max_cigar),),
                    tile_records, fixed_shape=True, quarantine=quarantine)
    if spans is None:
        # pass the Interval OBJECT to the planner — round-tripping it
        # through the config string form would misparse contig names
        # that themselves contain ':' (GRCh38 HLA alts)
        from hadoop_bam_tpu.split.bai import plan_interval_spans
        with METRICS.span("bam.plan_wall"):
            spans = plan_interval_spans(path, [region], header)
        if spans is None:                   # no .bai sidecar: whole file
            spans = _plan_bam_scan(path, header, config, scan.n_dev,
                                   4 << 20)

    rep = NamedSharding(scan.mesh, P())
    check_crc = bool(config.check_crc)
    window_depth = None                   # [n_dev, window], device-sharded
    tref = jax.device_put(np.int32(target_refid), rep)
    wstart = jax.device_put(np.int32(win_start), rep)
    src = _resilient_source(path, config)
    nc_off = _CIGAR_ROW_HDR - 4

    def cut(arrays, counts):
        # most records carry far fewer ops than max_cigar; slice the
        # tile to the group's real op width (pow2-bucketed so the jit
        # cache stays small) before it crosses the link
        tiles = arrays[0]
        mc = 1
        for dev in range(scan.n_dev):
            c = int(counts[dev])
            if c:
                t = tiles[dev]
                nc = (t[:c, nc_off].astype(np.int32)
                      | (t[:c, nc_off + 1].astype(np.int32) << 8))
                mc = max(mc, int(nc.max()))
        if mc > max_cigar:
            raise PlanError(
                f"record with {mc} cigar ops exceeds "
                f"max_cigar={max_cigar}; pass a larger max_cigar")
        mc = min(max_cigar, max(8, 1 << (mc - 1).bit_length()))
        return (tiles[:, :, :_cigar_row_bytes(mc)],)

    def consume(args, _counts):
        nonlocal window_depth
        mc = (args[0].shape[2] - _CIGAR_ROW_HDR) // 4
        out = make_coverage_step(scan.mesh, window, mc)(*args, tref, wstart)
        window_depth = out if window_depth is None else \
            window_depth + out    # shard-local add, no collective

    scan.run(scan.decoded(
        spans, lambda s: (decode_span_cigar_rows(src, s, max_cigar, check_crc,
                                                 config=config),),
        max(1, prefetch) * decode_pool_size(config)), consume, cut=cut)
    if window_depth is None:
        return np.zeros(window, np.int32)
    # one cross-device reduce at the end instead of one psum per dispatch
    with METRICS.span("bam.combine_wall"):
        total = jnp.sum(window_depth, axis=0)
        return np.asarray(jax.device_get(total), dtype=np.int32)
