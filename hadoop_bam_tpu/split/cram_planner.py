"""CRAM split planning: container-boundary-aligned spans.

Rebuild of hb/CRAMInputFormat.java's ``getSplits``: the reference scans CRAM
container headers (htsjdk ``CramContainerIterator``) and snaps Hadoop's byte
splits to container starts, because containers are CRAM's independently
decodable unit (SURVEY.md sections 2.3 and 5 — the long-context analog: the
container grid is the parallelism axis).  Same idea here: one cheap header
scan yields every container offset; spans are container runs balanced by
compressed size.
"""
from __future__ import annotations

import struct
from typing import List, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.formats.bam import SAMHeader
from hadoop_bam_tpu.formats.cram import (
    CRAMError, FileDefinition, count_skipped_blocks,
    decompress_nx16_blocks, read_container, scan_container_offsets,
)
from hadoop_bam_tpu.formats.cramio import decode_container, read_cram_header
from hadoop_bam_tpu.split.spans import FileByteSpan
from hadoop_bam_tpu.utils.metrics import METRICS


def scan_cram_containers(source) -> List[Tuple[int, int, int]]:
    """[(offset, byte length, n_records)] for every data container (header
    container included with n_records=0; EOF container excluded).

    Path sources walk container HEADERS with seeks — a few KB of reads
    per container, never the file body — so a whole-file count
    (`hbam view -c`) touches ~0.01% of the bytes."""
    if isinstance(source, (bytes, bytearray)):
        buf = bytes(source)
        FileDefinition.from_bytes(buf)
        out = []
        for off, hdr in scan_container_offsets(buf):
            if hdr.is_eof:
                break
            # container total size = header size + block section length
            end = _container_end(buf, off, hdr)
            out.append((off, end - off, hdr.n_records))
        return out

    import os

    from hadoop_bam_tpu.formats.cram import ContainerHeader

    out = []
    with open(source, "rb") as f:
        FileDefinition.from_bytes(f.read(FileDefinition.SIZE))
        fsize = os.fstat(f.fileno()).st_size
        pos = FileDefinition.SIZE
        while pos < fsize:
            f.seek(pos)
            chunk_size = 1 << 16      # per container: one oversized
            while True:               # header must not tax the rest
                chunk = f.read(chunk_size)
                try:
                    hdr, after = ContainerHeader.from_buffer(chunk, 0)
                    break
                except (IndexError, ValueError, struct.error) as e:
                    # header longer than the probe (huge landmark array):
                    # widen, bounded so garbage can't loop forever; a
                    # truncated tail surfaces as CRAMError so callers
                    # (and the CLI) see the normal error type
                    if chunk_size >= (1 << 24) or len(chunk) < chunk_size:
                        raise CRAMError(
                            f"truncated or corrupt container header at "
                            f"offset {pos}: {e}") from e
                    chunk_size <<= 2
                    f.seek(pos)
            if hdr.is_eof:
                break
            end = pos + after + hdr.length
            out.append((pos, end - pos, hdr.n_records))
            pos = end
    return out


def _container_end(buf: bytes, off: int, hdr) -> int:
    from hadoop_bam_tpu.formats.cram import ContainerHeader
    _, after = ContainerHeader.from_buffer(buf, off)
    return after + hdr.length


def plan_cram_spans(path: str, *, num_spans: Optional[int] = None,
                    config: HBamConfig = DEFAULT_CONFIG
                    ) -> List[FileByteSpan]:
    """Group data containers into spans; each span starts and ends exactly on
    container boundaries (the hb/CRAMInputFormat.java contract)."""
    containers = scan_cram_containers(path)
    data = [(off, size) for off, size, n_rec in containers[1:]]
    if not data:
        return []
    total = sum(s for _, s in data)
    if num_spans is None:
        span_bytes = config.split_size
        num_spans = max(1, -(-total // span_bytes))
    num_spans = min(num_spans, len(data))
    target = total / num_spans
    spans: List[FileByteSpan] = []
    cur_start = data[0][0]
    acc = 0
    for i, (off, size) in enumerate(data):
        acc += size
        last = i == len(data) - 1
        if acc >= target * (len(spans) + 1) - 1e-9 or last:
            end = off + size
            spans.append(FileByteSpan(path, cur_start, end))
            if not last:
                cur_start = data[i + 1][0]
    return spans


def _iter_span_containers(source, span: FileByteSpan, lazy: bool = False):
    """Containers whose start lies in [span.start, span.end) — the shared
    walk behind both the SAM and the pre-SAM span readers.

    Spans are container-aligned (plan_cram_spans ends every span exactly
    on a container boundary), so only the span's own byte range is read,
    with one read — a whole-file read per span would make total I/O
    quadratic in file size once a file is planned into many
    pipeline-grain spans.  ``lazy`` hands the containers over with their
    blocks still compressed (``formats/cram.py::LazyBlock``)."""
    if isinstance(source, (bytes, bytearray)):
        buf = bytes(source)[span.start:span.end]
    else:
        with open(source, "rb") as f:
            f.seek(span.start)
            buf = f.read(max(0, span.end - span.start))
    METRICS.count("cram.compressed_bytes", len(buf))
    pos = 0
    n = len(buf)
    while pos < n:
        cont, pos = read_container(buf, pos, lazy=lazy)
        if cont.header.is_eof:
            break
        yield cont


def read_cram_span(source, span: FileByteSpan, *, header: SAMHeader,
                   ref_source=None):
    """Decode every container whose start lies in [span.start, span.end) —
    the per-span idempotent unit of work (hb/CRAMRecordReader.java)."""
    out = []
    for cont in _iter_span_containers(source, span):
        out.extend(decode_container(cont, header, ref_source))
    return out


def read_cram_span_raw(source, span: FileByteSpan, *, header: SAMHeader,
                       ref_source=None):
    """Pre-SAM CramRecords of the span's containers (features resolved,
    mates unlinked) — the stats tensor path's input; seq/qual/length are
    final at this stage, so SamRecord materialization is skipped."""
    from hadoop_bam_tpu.formats.cramio import decode_container_slices
    out = []
    for cont in _iter_span_containers(source, span):
        for _base, records in decode_container_slices(cont, header,
                                                      ref_source):
            out.extend(records)
    return out


def read_cram_span_tiles(source, span: FileByteSpan, *, header: SAMHeader,
                         ref_source, geometry):
    """One span straight to payload tiles (seq [n, seq_stride], qual
    [n, qual_stride], lengths [n]): each slice's columns — the columnar
    decoder's arrays, only the blocks it asks for decompressed; the
    record decoder, converted, where a slice's layout needs it — packed
    into its own rows of the span's tiles, with no span-level copy of the
    bases or qualities."""
    from hadoop_bam_tpu.api.read_datasets import ragged_to_payload_tiles
    from hadoop_bam_tpu.formats.cram_columns import (
        columnar_cids, decode_slice_columns, records_to_columns,
    )
    from hadoop_bam_tpu.formats.cram_decode import decode_slice_records
    from hadoop_bam_tpu.formats.cramio import iter_container_slices

    conts = list(_iter_span_containers(source, span, lazy=True))
    n = sum(c.header.n_records for c in conts)
    seq = np.zeros((n, geometry.seq_stride), np.uint8)
    qual = np.zeros((n, geometry.qual_stride), np.uint8)
    lengths = np.zeros(n, np.int32)
    at = 0
    for cont in conts:
        for comp, slice_hdr, core, external, codec_lens \
                in iter_container_slices(cont):
            decompress_nx16_blocks(cont.blocks, columnar_cids(comp))
            cols = decode_slice_columns(comp, slice_hdr, core, external,
                                        header.ref_names, ref_source,
                                        codec_rec_lens=codec_lens,
                                        as_arrays=True)
            if cols is None:
                cols = records_to_columns(decode_slice_records(
                    comp, slice_hdr, core, external, header.ref_names,
                    ref_source, codec_rec_lens=codec_lens))
                METRICS.count("cram.record_path_records", cols["n"])
            else:
                METRICS.count("cram.columnar_records", cols["n"])
            end = at + cols["n"]
            if end > n:
                raise CRAMError("slices hold more records than their "
                                "container headers say")
            ragged_to_payload_tiles(
                cols["seq_cat"], cols["seq_lens"], cols["qual_cat"],
                cols["qual_lens"], geometry.seq_stride,
                geometry.qual_stride, geometry.max_len,
                out=(seq[at:end], qual[at:end], lengths[at:end]))
            at = end
        count_skipped_blocks(cont.blocks)
    if at != n:
        raise CRAMError(f"slices hold {at} records, their container "
                        f"headers {n}")
    return seq, qual, lengths


def read_cram_span_columns(source, span: FileByteSpan, *,
                           header: SAMHeader, ref_source=None,
                           want_names: bool = False) -> dict:
    """One span as columns (cram_columns.decode_slice_columns layout):
    the vectorized slice decoder where the layout allows, the record
    path (converted) where it doesn't — output identical either way."""
    from hadoop_bam_tpu.formats.cram_columns import (
        concat_columns, decode_slice_columns, records_to_columns,
    )
    from hadoop_bam_tpu.formats.cram_decode import decode_slice_records
    from hadoop_bam_tpu.formats.cramio import iter_container_slices

    parts = []
    for cont in _iter_span_containers(source, span, lazy=True):
        for comp, slice_hdr, core, external, codec_lens \
                in iter_container_slices(cont):
            cols = decode_slice_columns(comp, slice_hdr, core, external,
                                        header.ref_names, ref_source,
                                        want_names=want_names,
                                        codec_rec_lens=codec_lens)
            if cols is None:
                cols = records_to_columns(
                    decode_slice_records(comp, slice_hdr, core, external,
                                         header.ref_names, ref_source,
                                         codec_rec_lens=codec_lens),
                    want_names=want_names)
                METRICS.count("cram.record_path_records", cols["n"])
            else:
                METRICS.count("cram.columnar_records", cols["n"])
            parts.append(cols)
        count_skipped_blocks(cont.blocks)
    return concat_columns(parts)
