"""Variant datasets: the VCF/BCF InputFormat surface, iterator-shaped.

Rebuild of hb/VCFInputFormat.java + hb/VCFRecordReader.java +
hb/BCFRecordReader.java (SURVEY.md section 2.3): ``open_vcf(path)`` resolves
the container (text VCF, BGZF VCF, BCF — api/dispatch.py), reads the header
once (hb/util/VCFHeaderReader.java did this per task; we cache it), plans
spans, and yields records or SoA ``VariantBatch``es per span.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Iterator, List, Optional, Union

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig, ValidationStringency
from hadoop_bam_tpu.api.dispatch import VCFContainer, sniff_vcf_container
from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.formats.bcfio import read_bcf_header
from hadoop_bam_tpu.formats.vcf import (
    VCFHeader, VariantBatch, VcfRecord, read_vcf_header_bgzf,
    read_vcf_header_text,
)
from hadoop_bam_tpu.split.planners import plan_text_spans, read_text_span
from hadoop_bam_tpu.split.spans import FileByteSpan, FileVirtualSpan
from hadoop_bam_tpu.split.vcf_planners import (
    bgzf_text_span, plan_bcf_spans, plan_bgzf_text_spans, read_bcf_span,
    read_bgzf_text_span,
)
from hadoop_bam_tpu.utils.seekable import as_byte_source

Span = Union[FileByteSpan, FileVirtualSpan]


class VcfDataset:
    """Record-aligned access to one VCF/BCF file in any container."""

    def __init__(self, path: str, config: HBamConfig = DEFAULT_CONFIG,
                 container: Optional[VCFContainer] = None):
        self.path = path
        self.config = config
        self.container = container or sniff_vcf_container(path, config)
        self._is_bgzf_bcf = False
        self.header = self._read_header()
        self._plan: Optional[List[Span]] = None
        self._next_span = 0

    # -- header (hb/util/VCFHeaderReader.java) -------------------------------
    def _read_header(self) -> VCFHeader:
        src = as_byte_source(self.path)
        try:
            if self.container is VCFContainer.VCF:
                header, _ = read_vcf_header_text(src.pread)
                return header
            if self.container is VCFContainer.VCF_BGZF:
                return read_vcf_header_bgzf(src)
            if self.container is VCFContainer.VCF_GZIP:
                import gzip
                text = gzip.decompress(src.pread(0, src.size))

                def read_chunk(off: int, size: int) -> bytes:
                    return text[off:off + size]
                header, _ = read_vcf_header_text(read_chunk)
                return header
            header, _, self._is_bgzf_bcf = read_bcf_header(src)
            return header
        finally:
            src.close()

    # -- planning (hb/VCFInputFormat.getSplits) ------------------------------
    def spans(self, num_spans: Optional[int] = None) -> List[Span]:
        from hadoop_bam_tpu.api.dataset import _check_replan
        _check_replan(self, num_spans)
        if self._plan is None:
            self._plan_num_spans = num_spans
            if self.container is VCFContainer.VCF:
                self._plan = plan_text_spans(
                    self.path, num_spans=num_spans,
                    span_bytes=None if num_spans else self.config.split_size)
            elif self.container is VCFContainer.VCF_BGZF:
                self._plan = plan_bgzf_text_spans(
                    self.path, num_spans=num_spans, config=self.config)
            elif self.container is VCFContainer.VCF_GZIP:
                # plain gzip is not splittable: one whole-file span
                # (hb/util/BGZFEnhancedGzipCodec fallback)
                src = as_byte_source(self.path)
                try:
                    self._plan = [FileByteSpan(self.path, 0, src.size)]
                finally:
                    src.close()
            else:
                self._plan = plan_bcf_spans(
                    self.path, num_spans=num_spans, config=self.config,
                    header=self.header)
        return self._plan

    def read_span_text(self, span: Span) -> Optional[bytes]:
        """Raw text bytes of a span (None for the binary BCF container) —
        the input of the fast column tokenizer
        (parallel/variant_pipeline.pack_variant_tiles_from_text)."""
        if self.container is VCFContainer.BCF:
            return None
        if self.container is VCFContainer.VCF_BGZF:
            return read_bgzf_text_span(self.path, span)
        if self.container is VCFContainer.VCF_GZIP:
            import gzip
            with open(self.path, "rb") as f:
                return gzip.decompress(f.read())
        return read_text_span(self.path, span)

    @contextlib.contextmanager
    def span_text(self, span: Span):
        """``with ds.span_text(span) as text``: the span's text as
        ``read_span_text`` gives it, without the copy where the reader
        can lend its buffer (a BGZF VCF: a view of a leased span buffer,
        good until the ``with`` ends)."""
        if self.container is VCFContainer.VCF_BGZF:
            with bgzf_text_span(self.path, span) as text:
                yield text
        else:
            yield self.read_span_text(span)

    # -- span read (hb/VCFRecordReader / hb/BCFRecordReader) -----------------
    def read_span(self, span: Span) -> List[VcfRecord]:
        if self.container is VCFContainer.BCF:
            return read_bcf_span(self.path, span, header=self.header,
                                 is_bgzf=self._is_bgzf_bcf)
        if self.container is VCFContainer.VCF_BGZF:
            text = read_bgzf_text_span(self.path, span)
        elif self.container is VCFContainer.VCF_GZIP:
            import gzip
            with open(self.path, "rb") as f:
                text = gzip.decompress(f.read())
        else:
            text = read_text_span(self.path, span)
        out: List[VcfRecord] = []
        for line in text.decode().splitlines():
            if not line or line.startswith("#"):
                continue
            try:
                out.append(VcfRecord.from_line(line))
            except Exception:
                if (self.config.validation_stringency
                        is ValidationStringency.STRICT):
                    raise
        return out

    def records(self, num_spans: Optional[int] = None) -> Iterator[VcfRecord]:
        plan = self.spans(num_spans)
        if self._next_span >= len(plan):
            self._next_span = 0
        while self._next_span < len(plan):
            span = plan[self._next_span]
            recs = self.read_span(span)
            self._next_span += 1
            yield from recs

    def batches(self, num_spans: Optional[int] = None
                ) -> Iterator[VariantBatch]:
        plan = self.spans(num_spans)
        if self._next_span >= len(plan):
            self._next_span = 0
        while self._next_span < len(plan):
            span = plan[self._next_span]
            recs = self.read_span(span)
            self._next_span += 1
            yield VariantBatch(recs, self.header)

    def tensor_batches(self, mesh=None, geometry=None,
                       num_spans: Optional[int] = None) -> Iterator[Dict]:
        """Yield device-resident variant tensor batches sharded over the
        mesh's data axis: ``chrom``/``pos`` int32 [n_dev, cap], ``flags``
        uint8 (bit0 PASS, bit1 SNP), ``dosage`` int8 [n_dev, cap, S_pad]
        (ALT-allele dosage, -1 missing), ``n_records`` int32 [n_dev].

        Padding rows (beyond each shard's ``n_records``) carry the
        missing-value sentinels UNIFORMLY: dosage -1, qual NaN, other
        columns 0.  (Before the staging-ring feed, shards of the final
        group that received no spans were zero-filled — dosage 0 read
        as a hom-ref call; mask by ``n_records`` either way.)"""
        from hadoop_bam_tpu.parallel.scan import ScanFeed, _iter_windowed
        from hadoop_bam_tpu.parallel.variant_pipeline import (
            VariantGeometry, pack_variant_tiles,
        )
        from hadoop_bam_tpu.utils.pools import decode_pool, decode_pool_size

        if geometry is None:
            geometry = VariantGeometry(n_samples=self.header.n_samples)
        # fixed_shape keeps the historical contract that every variant
        # tensor batch carries full tile_records rows
        scan = ScanFeed("vcf", self.config, mesh, None,
                        geometry.tile_records, fixed_shape=True)
        spans = self.spans(num_spans)
        pool = decode_pool(self.config)

        def decode(span):
            if self.container is VCFContainer.BCF:
                # columnar fast path: no VcfRecord objects
                # (formats/bcf_columns.py, record-scan fallback)
                from hadoop_bam_tpu.parallel.variant_pipeline import (
                    bcf_span_stat_columns,
                )
                return bcf_span_stat_columns(
                    self.path, span, self.header, geometry,
                    self._is_bgzf_bcf)
            return pack_variant_tiles(
                VariantBatch(self.read_span(span), self.header),
                geometry)

        # the first span's columns name the schema; the decode is the
        # dataset's own, outside the scan verbs' span retry policy
        yield from scan.batches(_iter_windowed(
            pool, spans, decode, 2 * decode_pool_size(self.config),
            config=self.config))

    def variant_stats(self, mesh=None, geometry=None) -> Dict:
        """Distributed variant/SNP/PASS counts, mean ALT allele frequency,
        and per-sample call rates (parallel/variant_pipeline.py)."""
        from hadoop_bam_tpu.parallel.variant_pipeline import (
            variant_stats_file,
        )
        return variant_stats_file(self.path, mesh=mesh, config=self.config,
                                  header=self.header)

    def query(self, region: str) -> Iterator[VcfRecord]:
        """Random access via a ``.tbi`` sidecar (BGZF VCF): yields records
        overlapping the samtools-style region (``chr``, ``chr:start-end``)
        reading only the index's chunk ranges — build the sidecar with
        split.tabix.write_tabix or ``hbam index --flavor tbi``."""
        from hadoop_bam_tpu.split.intervals import parse_interval
        from hadoop_bam_tpu.split.tabix import TBI_SUFFIX, load_tabix_for
        from hadoop_bam_tpu.utils.seekable import as_byte_source

        if self.container is not VCFContainer.VCF_BGZF:
            raise ValueError("query() needs a BGZF-compressed VCF "
                             "(.vcf.gz); plain text/gzip cannot be "
                             "random-accessed")
        idx = load_tabix_for(self.path)
        if idx is None:
            raise FileNotFoundError(
                f"{self.path}{TBI_SUFFIX} not found — build it with "
                "split.tabix.write_tabix")
        iv = parse_interval(region)
        ranges = idx.query(iv.rname, iv.start - 1, iv.end)
        src = as_byte_source(self.path)
        try:
            r = bgzf.BGZFReader(src)
            for v0, v1 in ranges:
                r.seek_voffset(v0)
                text = r.read_to_voffset(v1)
                for line in text.split(b"\n"):
                    if not line or line[:1] == b"#":
                        continue
                    try:
                        rec = VcfRecord.from_line(line.decode())
                    except Exception:
                        if (self.config.validation_stringency
                                is ValidationStringency.STRICT):
                            raise
                        continue
                    if rec.chrom != iv.rname:
                        continue
                    if rec.pos <= iv.end and rec.pos + rec.rlen - 1 >= iv.start:
                        yield rec
        finally:
            src.close()

    # -- checkpoint / resume (SURVEY.md section 5) ---------------------------
    def state_dict(self) -> Dict:
        return {
            "path": self.path,
            "container": self.container.value,
            "plan": [s.to_dict() for s in (self._plan or [])],
            "next_span": self._next_span,
        }

    def load_state_dict(self, state: Dict) -> None:
        assert state["path"] == self.path
        cls = (FileVirtualSpan if self.container is VCFContainer.BCF
               else FileByteSpan)
        self._plan = [cls.from_dict(d) for d in state["plan"]] or None
        self._next_span = int(state["next_span"])


def open_vcf(path: str, config: HBamConfig = DEFAULT_CONFIG) -> VcfDataset:
    """hb/VCFInputFormat: resolve VCF/BCF container, return the dataset."""
    return VcfDataset(path, config)
