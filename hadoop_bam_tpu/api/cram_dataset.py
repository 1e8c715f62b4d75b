"""CramDataset: record-aligned access to one CRAM file.

The dataset face of hb/CRAMInputFormat.java + hb/CRAMRecordReader.java
(SURVEY.md section 2.3, [VER? 7.1+]): spans align to container boundaries,
each span decodes independently, and the reference source is resolved from
config (``cram_reference_source_path`` — the analog of
``hadoopbam.cram.reference-source-path``): a ``.fai``-indexed,
memory-mapped FASTA, so opening the dataset costs the index (and the
header container), not the genome or the file.  ``cram_span_tiles`` is
the unit ``hbam seq-stats`` runs a span through on ``plan.execute``
(``parallel/pipeline.py::_read_stats_impl``).
"""
from __future__ import annotations

import time
from typing import Dict, Iterator, List, Optional

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.formats.bam import SAMHeader
from hadoop_bam_tpu.formats.cram_decode import (
    FastaReferenceSource, ReferenceSource,
)
from hadoop_bam_tpu.formats.cramio import read_cram_header
from hadoop_bam_tpu.formats.sam import SamRecord
from hadoop_bam_tpu.split.cram_planner import plan_cram_spans, read_cram_span
from hadoop_bam_tpu.split.spans import FileByteSpan
from hadoop_bam_tpu.utils.metrics import METRICS


class CramDataset:
    def __init__(self, path: str, config: HBamConfig = DEFAULT_CONFIG):
        self.path = path
        self.config = config
        self.header, self._first_container = read_cram_header(path)
        self._plan: Optional[List[FileByteSpan]] = None
        self._next_span = 0
        self._ref_source: Optional[ReferenceSource] = None
        if config.cram_reference_source_path:
            self._ref_source = FastaReferenceSource(
                config.cram_reference_source_path)

    def spans(self, num_spans: Optional[int] = None) -> List[FileByteSpan]:
        from hadoop_bam_tpu.api.dataset import _check_replan
        _check_replan(self, num_spans)
        if self._plan is None:
            self._plan = plan_cram_spans(self.path, num_spans=num_spans,
                                         config=self.config)
            self._plan_num_spans = num_spans
        return self._plan

    def read_span(self, span: FileByteSpan) -> List[SamRecord]:
        return read_cram_span(self.path, span, header=self.header,
                              ref_source=self._ref_source)

    def records(self, num_spans: Optional[int] = None) -> Iterator[SamRecord]:
        plan = self.spans(num_spans)
        if self._next_span >= len(plan):
            self._next_span = 0
        while self._next_span < len(plan):
            span = plan[self._next_span]
            recs = self.read_span(span)
            self._next_span += 1
            yield from recs

    def tensor_batches(self, mesh=None, geometry=None,
                       num_spans: Optional[int] = None,
                       spans: Optional[List[FileByteSpan]] = None,
                       quarantine=None,
                       ) -> Iterator[Dict]:
        """Device-resident read batches (same layout as
        FastqDataset.tensor_batches) decoded from CRAM containers.

        Columnar fast path: each span decodes straight into tiles
        (``cram_span_tiles`` — the vectorized slice decoder, no
        CramRecord objects); slices outside the vectorizable layout fall
        back to the record decoder with identical output."""
        from hadoop_bam_tpu.parallel.pipeline import (
            stream_read_tensor_batches,
        )

        yield from stream_read_tensor_batches(
            self.spans(num_spans) if spans is None else spans, None,
            self.config, mesh, geometry,
            tiles_fn=lambda span, geom: cram_span_tiles(self, span, geom),
            quarantine=quarantine, fmt="cram")

    def flagstat(self, mesh=None) -> Dict[str, int]:
        """Host-side flagstat over decoded CRAM records (same counters as
        the BAM mesh path)."""
        from hadoop_bam_tpu.api.dataset import _flagstat_records
        return _flagstat_records(self.records())

    # -- checkpoint / resume (same contract as BamDataset) --
    def state_dict(self) -> Dict:
        return {"path": self.path,
                "plan": [s.to_dict() for s in (self._plan or [])],
                "next_span": self._next_span}

    def load_state_dict(self, state: Dict) -> None:
        assert state["path"] == self.path
        self._plan = [FileByteSpan.from_dict(d) for d in state["plan"]] \
            or None
        self._next_span = int(state["next_span"])


def cram_span_tiles(ds: CramDataset, span: FileByteSpan, geometry):
    """One span's (seq, qual, lengths) payload tiles: its containers read
    with one read, each slice's columns (the columnar slice decoder, only
    the blocks it asks for decompressed; the record decoder, converted,
    where a slice's layout needs it) packed 4-bit into the span's rows
    (``split/cram_planner.py::read_cram_span_tiles``; the qual_lens gate
    is the CF_QUAL_STORED gate of ``_to_sam``).  ``cram.decode_busy_ns``
    is the thread's CPU time for the whole of it."""
    from hadoop_bam_tpu.split.cram_planner import read_cram_span_tiles

    t_cpu = time.thread_time_ns()
    try:
        return read_cram_span_tiles(ds.path, span, header=ds.header,
                                    ref_source=ds._ref_source,
                                    geometry=geometry)
    finally:
        METRICS.count("cram.decode_busy_ns", time.thread_time_ns() - t_cpu)


def open_cram(path: str, config: HBamConfig = DEFAULT_CONFIG) -> CramDataset:
    return CramDataset(path, config)
