"""Datasets: the InputFormat/RecordReader surface, iterator-shaped.

Where the reference exposed ``InputFormat<K, V>`` + ``RecordReader`` pairs
(hb/AnySAMInputFormat.java, hb/BAMInputFormat.java, hb/SAMInputFormat.java,
SURVEY.md section 2.3), this framework exposes datasets: ``open_bam(path)``
resolves the container (dispatch.py), reads the header, plans record-aligned
spans, and iterates SoA batches — host batches (``BamBatch``) or device-fed
mesh steps (parallel/pipeline.py).

Checkpoint/resume (SURVEY.md section 5): the iterator's position is just
(plan, next span index) — ``state_dict()`` / ``load_state_dict()`` make any
consumer resumable, the moral equivalent of the splitting-bai cursor idea.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig, ValidationStringency
from hadoop_bam_tpu.api.dispatch import SAMContainer, sniff_sam_container
from hadoop_bam_tpu.formats.bam import BamBatch, SAMHeader
from hadoop_bam_tpu.formats.bamio import read_bam_header
from hadoop_bam_tpu.formats.sam import SamRecord, read_sam_text
from hadoop_bam_tpu.split.planners import (
    plan_bam_spans, plan_text_spans, read_bam_span, read_text_span,
)
from hadoop_bam_tpu.split.spans import FileByteSpan, FileVirtualSpan
from hadoop_bam_tpu.utils.seekable import as_byte_source


def _check_replan(ds, num_spans) -> None:
    """Guard against silently reusing a plan built with a different
    num_spans (same contract as read_datasets._SpannedDataset.spans)."""
    cached = getattr(ds, "_plan_num_spans", None)
    if getattr(ds, "_plan", None) is not None and num_spans is not None \
            and num_spans != cached:
        raise ValueError(
            f"span plan already built with num_spans={cached}; "
            "open a new dataset to re-plan")


class BamDataset:
    """Record-aligned access to one BAM file (hb/BAMInputFormat +
    hb/BAMRecordReader in dataset clothes)."""

    def __init__(self, path: str, config: HBamConfig = DEFAULT_CONFIG):
        self.path = path
        self.config = config
        self.header, self.first_voffset = read_bam_header(path)
        self._plan: Optional[List[FileVirtualSpan]] = None
        self._next_span = 0
        self._intervals = None

    def spans(self, num_spans: Optional[int] = None) -> List[FileVirtualSpan]:
        _check_replan(self, num_spans)
        if self._plan is None:
            from hadoop_bam_tpu.split.planners import (
                plan_spans_maybe_intervals,
            )
            self._plan = plan_spans_maybe_intervals(
                self.path, self.header, self.config, num_spans=num_spans)
            self._plan_num_spans = num_spans
        return self._plan

    def read_span(self, span: FileVirtualSpan) -> BamBatch:
        batch = read_bam_span(self.path, span, header=self.header)
        if self.config.bam_intervals:
            from hadoop_bam_tpu.split.intervals import (
                filter_batch, parse_intervals,
            )
            if self._intervals is None:
                self._intervals = parse_intervals(self.config.bam_intervals,
                                                  self.header.ref_names)
            batch = filter_batch(batch, self._intervals, self.header)
        return batch

    def batches(self, num_spans: Optional[int] = None) -> Iterator[BamBatch]:
        """Yield one SoA batch per span, resumable via state_dict();
        a fresh call after exhaustion restarts from the beginning."""
        plan = self.spans(num_spans)
        if self._next_span >= len(plan):
            self._next_span = 0
        while self._next_span < len(plan):
            span = plan[self._next_span]
            batch = self.read_span(span)
            self._next_span += 1  # before yield: state = batches delivered
            yield batch

    def records(self, num_spans: Optional[int] = None) -> Iterator[SamRecord]:
        """Per-record view (tests/CLI; the batch path is the fast path)."""
        for batch in self.batches(num_spans):
            for i in range(len(batch)):
                yield SamRecord.from_line(batch.to_sam_line(i))

    # -- checkpoint / resume --
    def state_dict(self) -> Dict:
        return {
            "path": self.path,
            "plan": [s.to_dict() for s in (self._plan or [])],
            "next_span": self._next_span,
        }

    def load_state_dict(self, state: Dict) -> None:
        assert state["path"] == self.path
        self._plan = [FileVirtualSpan.from_dict(d) for d in state["plan"]] \
            or None
        self._next_span = int(state["next_span"])

    def tensor_batches(self, mesh=None, geometry=None,
                       num_spans: Optional[int] = None) -> Iterator[Dict]:
        """Yield device-resident tensor batches for mesh consumers — the
        ML-feed surface this framework exists for.  Each batch is a dict of
        arrays sharded over the mesh's data axis:

        - ``seq_packed`` [n_dev, cap, seq_stride] uint8 — 4-bit bases,
          2/byte, high nibble first [SPEC]; unpack on device with
          ops.seq_pallas.unpack_bases (or feed packed straight into a
          Pallas kernel)
        - ``qual`` [n_dev, cap, qual_stride] uint8
        - ``prefix`` [n_dev, cap, 36] uint8 — fixed columns; decode with
          ops.unpack_bam.unpack_fixed_fields_tile
        - ``n_records`` [n_dev] int32 — valid rows per shard

        ``cap`` is geometry.tile_records for every full batch; the FINAL
        batch of a run may arrive with fewer rows (shrunk to the
        smallest dispatch bucket that holds its records) — size consumer
        buffers from the batch's own shape, not the geometry.
        Consumers that preallocate by ``tile_records`` can opt out with
        ``PayloadGeometry(fixed_shape=True)``: the final batch then pads
        to ``tile_records`` instead of shrinking (every batch shares one
        shape, at the cost of padding transfer on the last batch).
        """
        from hadoop_bam_tpu.parallel.pipeline import (
            PayloadGeometry, bam_payload_batches,
        )

        if geometry is None:
            geometry = PayloadGeometry()
        yield from bam_payload_batches(self.path, self.spans(num_spans),
                                       mesh, geometry, self.config,
                                       self.header)

    def query(self, region: str) -> Iterator[SamRecord]:
        """Random access via a ``.bai``/``.csi`` sidecar: yields records
        overlapping the samtools-style region, reading only the index's
        chunk ranges (build with ``hbam index --flavor bai``).  Falls back
        to a full scan + filter when no genomic index exists."""
        from hadoop_bam_tpu.split.bai import load_bai_for, plan_interval_spans
        from hadoop_bam_tpu.split.intervals import (
            batch_overlap_mask, parse_intervals,
        )

        intervals = parse_intervals(region, self.header.ref_names)
        spans = plan_interval_spans(self.path, intervals, self.header)
        if spans is None:
            spans = self.spans()
        for span in spans:
            batch = read_bam_span(self.path, span, header=self.header)
            mask = batch_overlap_mask(batch, intervals, self.header)
            idx = np.nonzero(mask)[0]
            for i in idx:
                yield SamRecord.from_line(batch.to_sam_line(int(i)))

    def seq_stats(self, mesh=None, geometry=None) -> Dict:
        """Distributed GC / quality / base-composition stats via the fused
        Pallas payload kernel (parallel/pipeline.seq_stats_file).  Honors
        bam_intervals (rows filter host-side before tiling)."""
        from hadoop_bam_tpu.parallel.pipeline import seq_stats_file
        return seq_stats_file(self.path, mesh=mesh, config=self.config,
                              geometry=geometry, header=self.header)

    def flagstat(self, mesh=None) -> Dict[str, int]:
        """Distributed flagstat; honors bam_intervals via the mesh path's
        host-side row filter."""
        from hadoop_bam_tpu.parallel.pipeline import flagstat_file
        return flagstat_file(self.path, mesh=mesh, config=self.config,
                             header=self.header)


class SamDataset:
    """Plain-text SAM (hb/SAMInputFormat + hb/SAMRecordReader): line-split
    text; header read separately since mid-file spans never see it."""

    def __init__(self, path: str, config: HBamConfig = DEFAULT_CONFIG):
        self.path = path
        self.config = config
        self.header = self._read_header()
        self._next_span = 0

    def _read_header(self) -> SAMHeader:
        src = as_byte_source(self.path)
        try:
            chunks = []
            off = 0
            while True:
                got = src.pread(off, 1 << 16)
                if not got:
                    break
                chunks.append(got)
                off += len(got)
                # stop once a non-@ line has started
                text = b"".join(chunks)
                lines = text.split(b"\n")
                if any(l and not l.startswith(b"@") for l in lines[:-1]):
                    break
            text = b"".join(chunks)
            header_lines = []
            for line in text.split(b"\n"):
                if line.startswith(b"@"):
                    header_lines.append(line.decode() + "\n")
                elif line:
                    break
            return SAMHeader.from_sam_text("".join(header_lines))
        finally:
            src.close()

    def spans(self, num_spans: Optional[int] = None) -> List[FileByteSpan]:
        return plan_text_spans(self.path, num_spans=num_spans,
                               span_bytes=None if num_spans
                               else self.config.split_size)

    def read_span(self, span: FileByteSpan) -> List[SamRecord]:
        text = read_text_span(self.path, span).decode()
        out = []
        for line in text.splitlines():
            if not line or line.startswith("@"):
                continue
            try:
                out.append(SamRecord.from_line(line))
            except Exception:
                if self.config.validation_stringency is ValidationStringency.STRICT:
                    raise
        return out

    def records(self, num_spans: Optional[int] = None) -> Iterator[SamRecord]:
        for span in self.spans(num_spans):
            yield from self.read_span(span)

    def flagstat(self, mesh=None) -> Dict[str, int]:
        """Host-side flagstat (text SAM has no columnar device path);
        same counter definitions as the BAM mesh path."""
        return _flagstat_records(self.records())


def _flagstat_records(records) -> Dict[str, int]:
    """samtools-flagstat counters over an iterator of SamRecords — the
    uniform fallback for datasets without a device decode path."""
    import numpy as np

    from hadoop_bam_tpu.formats.bam import BamBatch
    from hadoop_bam_tpu.ops.flagstat import FLAGSTAT_FIELDS, flagstat_from_batch

    stats = {k: 0 for k in FLAGSTAT_FIELDS}

    class _Cols:
        pass

    flags, refids, mrefids, mapqs = [], [], [], []
    names: Dict[str, int] = {}
    for r in records:
        flags.append(r.flag)
        refids.append(-1 if r.rname == "*"
                      else names.setdefault(r.rname, len(names)))
        if r.rnext == "*":
            mrefids.append(-1)
        elif r.rnext == "=":
            mrefids.append(refids[-1])
        else:
            mrefids.append(names.setdefault(r.rnext, len(names)))
        mapqs.append(r.mapq)
    batch = _Cols()
    batch.flag = np.asarray(flags, dtype=np.int64)
    batch.refid = np.asarray(refids, dtype=np.int64)
    batch.mate_refid = np.asarray(mrefids, dtype=np.int64)
    batch.mapq = np.asarray(mapqs, dtype=np.int64)
    return flagstat_from_batch(batch, stats)


def open_bam(path: str, config: HBamConfig = DEFAULT_CONFIG) -> BamDataset:
    return BamDataset(path, config)


def open_sam(path: str, config: HBamConfig = DEFAULT_CONFIG) -> SamDataset:
    return SamDataset(path, config)


def open_any_sam(path: str, config: HBamConfig = DEFAULT_CONFIG):
    """hb/AnySAMInputFormat: resolve the container, return the dataset."""
    fmt = sniff_sam_container(path, config)
    if fmt is SAMContainer.BAM:
        return BamDataset(path, config)
    if fmt is SAMContainer.SAM:
        return SamDataset(path, config)
    if fmt is SAMContainer.CRAM:
        from hadoop_bam_tpu.api.cram_dataset import CramDataset
        return CramDataset(path, config)
    raise ValueError(f"unsupported container {fmt}")
