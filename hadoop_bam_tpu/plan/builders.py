"""Plan builders: drivers compile to IR here.

Each public driver is now a thin wrapper: build the plan, hand it to
``plan.executor.execute``.  The builders are the one catalogue of what
each workload IS — source format, span grain, tensor-op DAG, sink — so
a new workload (markdup, pileup windows, query-then-analyze fusion)
starts as a new builder composing existing ops, not a sixth hand-wired
pipeline.

Builders never touch the filesystem beyond what identity requires (the
cohort builder reads the manifest's identity digest); expensive
planning — span cutting, header reads — stays execution-time, so
``hbam explain`` can print any plan cheaply.
"""
from __future__ import annotations

import os
from typing import Optional, Union

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.plan.ir import (
    PlanIR, SinkIR, SourceIR, SpansIR, op_node,
)

# the whole-file scan span grains the drivers plan at when the caller
# didn't pin spans (values lifted from the drivers they replaced; the
# flagstat 4 MiB sweep result is recorded in parallel/pipeline.py)
FLAGSTAT_SPAN_BYTES = 4 << 20
PAYLOAD_SPAN_BYTES = 8 << 20


def flagstat_plan(path: str,
                  config: Optional[HBamConfig] = None) -> PlanIR:
    """BAM flagstat: project the flagstat columns, reduce with one psum
    per tile group."""
    from hadoop_bam_tpu.ops.unpack_bam import FLAGSTAT_PROJECTION

    cfg = config if config is not None else DEFAULT_CONFIG
    return PlanIR(
        source=SourceIR(path, "bam"),
        spans=SpansIR.auto(span_bytes=FLAGSTAT_SPAN_BYTES),
        ops=(op_node("project", projection=FLAGSTAT_PROJECTION,
                     intervals=cfg.bam_intervals),
             op_node("flagstat_reduce")),
        sink=SinkIR.of("flagstat"))


def seq_stats_plan(path: str, config: Optional[HBamConfig] = None,
                   geometry=None) -> PlanIR:
    """BAM payload stats: pack prefix + 4-bit seq + qual row tiles,
    reduce through the fused Pallas payload kernel."""
    from hadoop_bam_tpu.parallel.pipeline import PayloadGeometry

    cfg = config if config is not None else DEFAULT_CONFIG
    g = geometry if geometry is not None else PayloadGeometry()
    return PlanIR(
        source=SourceIR(path, "bam"),
        spans=SpansIR.auto(span_bytes=PAYLOAD_SPAN_BYTES),
        ops=(op_node("payload_pack", max_len=g.max_len,
                     seq_stride=g.seq_stride, qual_stride=g.qual_stride,
                     tile_records=g.tile_records,
                     fixed_shape=g.fixed_shape,
                     intervals=cfg.bam_intervals),
             op_node("seq_stats_reduce")),
        sink=SinkIR.of("seq_stats"))


def read_stats_plan(path: str, config: Optional[HBamConfig] = None,
                    geometry=None) -> PlanIR:
    """FASTQ / QSEQ payload stats (``hbam seq-stats reads.fastq[.gz]``):
    the file's text — plain spans at the pipeline grain, or a gzip'd
    file's one span streamed in chunks of it — tokenised into the same
    4-bit seq + qual row tiles as the BAM plan, through the same
    reduction.  Same sink as ``seq_stats_plan``: the executor picks the
    runner by the source's format."""
    from hadoop_bam_tpu.parallel.pipeline import (
        QSEQ_EXTS, PayloadGeometry, pipeline_grain,
    )

    cfg = config if config is not None else DEFAULT_CONFIG
    g = geometry if geometry is not None else PayloadGeometry()
    fmt = "qseq" if path.lower().endswith(QSEQ_EXTS) else "fastq"
    filt = getattr(cfg, f"{fmt}_filter_failed_qc")
    enc = getattr(cfg, f"{fmt}_base_quality_encoding")
    return PlanIR(
        source=SourceIR(path, fmt),
        spans=SpansIR.auto(span_bytes=pipeline_grain(cfg)),
        ops=(op_node("text_tokenize", quality_offset=int(enc.value),
                     filter_failed_qc=bool(filt)),
             op_node("payload_pack", max_len=g.max_len,
                     seq_stride=g.seq_stride, qual_stride=g.qual_stride,
                     tile_records=g.tile_records,
                     fixed_shape=g.fixed_shape),
             op_node("seq_stats_reduce")),
        sink=SinkIR.of("seq_stats"))


def cram_stats_plan(path: str, config: Optional[HBamConfig] = None,
                    geometry=None) -> PlanIR:
    """CRAM payload stats (``hbam seq-stats x.cram --reference x.fa``):
    spans at container boundaries at the pipeline grain, each decoded by
    the columnar slice decoder — only the blocks it reads decompressed,
    bases rebuilt from the reference and the features — into the same
    4-bit seq + qual row tiles as the FASTQ plan, through the same
    reduction.  The reference (``config.cram_reference_source_path``) is
    part of the plan's identity."""
    from hadoop_bam_tpu.parallel.pipeline import (
        PayloadGeometry, pipeline_grain,
    )

    cfg = config if config is not None else DEFAULT_CONFIG
    g = geometry if geometry is not None else PayloadGeometry()
    ref = cfg.cram_reference_source_path
    return PlanIR(
        source=SourceIR(path, "cram"),
        spans=SpansIR.auto(span_bytes=pipeline_grain(cfg)),
        ops=(op_node("cram_decode",
                     reference=os.path.abspath(ref) if ref else ""),
             op_node("payload_pack", max_len=g.max_len,
                     seq_stride=g.seq_stride, qual_stride=g.qual_stride,
                     tile_records=g.tile_records,
                     fixed_shape=g.fixed_shape),
             op_node("seq_stats_reduce")),
        sink=SinkIR.of("seq_stats"))


def variant_stats_plan(path: str, config: Optional[HBamConfig] = None,
                       geometry=None) -> PlanIR:
    """VCF/BCF variant stats: pack (chrom, pos, flags, dosage) tiles,
    reduce counts + allele frequency + per-sample call rates."""
    fmt = "bcf" if path.lower().endswith(".bcf") else "vcf"
    params = {}
    if geometry is not None:
        params = dict(n_samples=geometry.n_samples,
                      tile_records=geometry.tile_records)
    return PlanIR(
        source=SourceIR(path, fmt),
        spans=SpansIR.auto(),
        ops=(op_node("variant_pack", **params),
             op_node("variant_stats_reduce")),
        sink=SinkIR.of("variant_stats"))


def variant_gwas_plan(path: str, traits: str,
                      config: Optional[HBamConfig] = None) -> PlanIR:
    """VCF/BCF structure-adjusted association (``hbam vcf-gwas``): the
    variant scan's tiles kept in a device-resident int8 matrix while the
    GRM accumulates, its leading eigenvectors as covariates, then the
    score test of every site against every trait of the TSV ``traits``
    from the matrix (cohort/gwas.py has the formulas).  The numbers are
    the verb's constants; the trait file is part of the job's identity."""
    from hadoop_bam_tpu.cohort.gwas import GWAS_AXES, GWAS_MAF_PERCENT

    fmt = "bcf" if path.lower().endswith(".bcf") else "vcf"
    return PlanIR(
        source=SourceIR(path, fmt),
        spans=SpansIR.auto(),
        ops=(op_node("variant_pack"),
             op_node("resident_load"),
             op_node("grm_accumulate", maf_percent=GWAS_MAF_PERCENT),
             op_node("covariates", axes=GWAS_AXES),
             op_node("assoc_scan", traits=os.path.abspath(traits))),
        sink=SinkIR.of("variant_gwas"))


def serve_tile_plan(path: str, kind: str = "bam",
                    start_voffset: int = 0,
                    end_voffset: int = 0) -> PlanIR:
    """One cold serve-tile build: decode a coalesced chunk's virtual-
    offset range and pack the (rid, pos1, end1) interval tile the
    region-serve filter consumes (serve/tiles.py).  A tile build is not
    an executor sink — the serving loop owns ring/cache placement; the
    builder exists for the ``hbam explain serve-tile`` surface and the
    digest contract."""
    return PlanIR(
        source=SourceIR(path, kind, role="chunk"),
        spans=SpansIR.pin([(path, start_voffset, end_voffset)]),
        ops=(op_node("chunk_decode"), op_node("tile_build")),
        sink=SinkIR.of("serve_tiles"))


def query_chunk_plan(path: str, kind: str, start_voffset: int,
                     end_voffset: int) -> PlanIR:
    """One index-resolved, coalesced query chunk: decode the pinned
    virtual-offset range into host predicate columns for the mesh
    overlap filter (query/engine.py)."""
    return PlanIR(
        source=SourceIR(path, kind, role="chunk"),
        spans=SpansIR.pin([(path, start_voffset, end_voffset)]),
        ops=(op_node("chunk_decode"),),
        sink=SinkIR.of("chunk_columns"))


def query_region_plan(path: str, kind: str, region: str,
                      chunks) -> PlanIR:
    """A whole region query (the ``hbam explain query`` surface): every
    coalesced chunk the index resolved for ``region``, pinned."""
    return PlanIR(
        source=SourceIR(path, kind, role="chunk"),
        spans=SpansIR.pin([(path, s, e) for s, e in chunks]),
        ops=(op_node("chunk_decode"),
             op_node("overlap_filter", region=region)),
        sink=SinkIR.of("chunk_columns"))


def mkdup_plan(input_path: str, output_path: str,
               config: Optional[HBamConfig] = None, *,
               remove_duplicates: bool = False,
               library_from: str = "none") -> PlanIR:
    """The fused preprocessing pipeline (prep/): decode -> mesh sort
    exchange -> duplicate marking -> flag-patched indexed write, as ONE
    plan — records never re-inflate between the ops.

    The output-affecting markdup options ride the op node (they are
    part of the plan digest the journal refuses to resume across);
    the output path is the sink's identity."""
    return PlanIR(
        source=SourceIR(input_path, "bam"),
        spans=SpansIR.auto(span_bytes=PAYLOAD_SPAN_BYTES),
        ops=(op_node("sort_exchange"),
             op_node("markdup",
                     remove_duplicates=bool(remove_duplicates),
                     library_from=library_from),
             op_node("flag_patch_write")),
        sink=SinkIR.of("bam_file", path=os.path.abspath(output_path)))


def cohort_plan(manifest, config: Optional[HBamConfig] = None,
                geometry=None) -> PlanIR:
    """Cohort tensor batches: k single-sample call sets k-way
    position-joined, allele-harmonized, packed into
    [variants, samples] dosage/qual mesh tiles.

    The plan digest covers the manifest IDENTITY (anchor + per-input
    file identity digest) plus the JOIN-affecting knobs — exactly what
    the journaled join's refuse-to-resume contract needs
    (``jobs.runner.plan_journal_params``).  Feed-only geometry
    (tile_records) is deliberately NOT part of the identity: the
    journaled chunk artifacts are cut by chunk_sites and shaped by
    samples_pad, and a changed mesh-feed tile height replays them
    byte-identically."""
    from hadoop_bam_tpu.cohort.manifest import as_manifest

    cfg = config if config is not None else DEFAULT_CONFIG
    m = as_manifest(manifest)
    anchor, k, digest = m.identity()
    if geometry is None:
        from hadoop_bam_tpu.parallel.variant_pipeline import (
            VariantGeometry,
        )
        geometry = VariantGeometry(n_samples=k)
    return PlanIR(
        source=SourceIR(anchor or "<inline-manifest>", "cohort",
                        role="join"),
        spans=SpansIR.auto(),
        ops=(op_node("kway_join", samples=k, manifest_digest=digest,
                     chunk_sites=cfg.cohort_chunk_sites),
             op_node("variant_pack",
                     samples_pad=geometry.samples_pad)),
        sink=SinkIR.of("tensor_batches"))
