"""``python -m hadoop_bam_tpu <verb>`` — the CLI frontend.

Verb parity with the reference CLI (SURVEY.md section 2.7):

- ``view``      print records as SAM/VCF text (optionally header-only/count)
- ``index``     build a .splitting-bai / .sbi sidecar (SplittingBAMIndexer)
- ``cat``       concatenate same-header BAMs into one
- ``summarize`` distributed flagstat over the mesh pipeline
- ``sort``      coordinate- (or name-) sort a BAM
- ``fixmate``   fill mate fields on name-grouped records
- ``vcf-sort``  sort a VCF/BCF by (contig, position)

Each verb works on local paths and prints to stdout; exit code != 0 on error.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Tuple


def _parse_region(region: str) -> Tuple[str, int, int]:
    """'chr20:1,000-2,000' -> (chr20, 1000, 2000); open ends allowed."""
    if ":" not in region:
        return region, 1, 1 << 60
    name, rng = region.rsplit(":", 1)
    rng = rng.replace(",", "")
    if "-" in rng:
        lo, hi = rng.split("-", 1)
        return name, int(lo or 1), int(hi or 1 << 60)
    return name, int(rng), 1 << 60


# ---------------------------------------------------------------------------
# view
# ---------------------------------------------------------------------------

def cmd_view(args) -> int:
    from hadoop_bam_tpu.api.dispatch import sniff_sam_container, SAMContainer
    path = args.path
    if path.endswith((".vcf", ".vcf.gz", ".bcf")):
        return _view_vcf(args)
    fmt = sniff_sam_container(path)
    return _view_sam(args, fmt)


def _overlaps_region(rec, region) -> bool:
    """True iff the alignment's reference span intersects [start, end]."""
    if rec.rname != region[0]:
        return False
    return rec.pos <= region[2] and rec.pos + max(1, _alen(rec)) - 1 >= region[1]


def _view_sam(args, fmt) -> int:
    from hadoop_bam_tpu.api.dataset import open_any_sam
    ds = open_any_sam(args.path)
    header = ds.header
    if args.header_only:
        sys.stdout.write(header.to_sam_text())
        return 0
    region = None
    if args.region:
        from hadoop_bam_tpu.split.intervals import resolve_interval
        iv = resolve_interval(args.region, header.ref_names)
        region = (iv.rname, iv.start, iv.end)
    rid = header.ref_id(region[0]) if region else -2
    if region and rid < 0:
        print(f"unknown reference {region[0]!r}", file=sys.stderr)
        return 1
    n = 0
    if not args.count and not args.no_header:
        sys.stdout.write(header.to_sam_text())
    from hadoop_bam_tpu.api.dataset import BamDataset
    from hadoop_bam_tpu.formats.sam import SamRecord
    if isinstance(ds, BamDataset) and region and args.region:
        from hadoop_bam_tpu.split.bai import load_bai_for
        if load_bai_for(args.path) is not None:
            # genomic index present: read only the indexed chunk ranges
            for rec in ds.query(args.region):
                if args.count:
                    n += 1
                else:
                    print(rec.to_line())
            if args.count:
                print(n)
            return 0
    from hadoop_bam_tpu.api.cram_dataset import CramDataset
    if isinstance(ds, CramDataset) and args.count and not region:
        # container headers carry record counts: whole-file -c needs a
        # header scan, zero block decompression (samtools-style fast
        # count)
        from hadoop_bam_tpu.split.cram_planner import scan_cram_containers
        print(sum(nr for _off, _size, nr in scan_cram_containers(args.path)))
        return 0
    if isinstance(ds, BamDataset):
        for batch in ds.batches():
            import numpy as np
            idx = np.arange(len(batch))
            if region:
                # conservative vectorized pre-filter (start bound only; the
                # exact CIGAR-span overlap check runs on the decoded line)
                keep = (batch.refid == rid) & (batch.pos + 1 <= region[2])
                idx = idx[keep]
            for i in idx:
                line = batch.to_sam_line(int(i))
                if region and not _overlaps_region(SamRecord.from_line(line),
                                                   region):
                    continue
                if args.count:
                    n += 1
                else:
                    sys.stdout.write(line + "\n")
    else:
        for rec in ds.records():
            if region and not _overlaps_region(rec, region):
                continue
            if args.count:
                n += 1
            else:
                sys.stdout.write(rec.to_line() + "\n")
    if args.count:
        print(n)
    return 0


def _view_vcf(args) -> int:
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    ds = open_vcf(args.path)
    if args.header_only:
        sys.stdout.write(ds.header.to_text())
        return 0
    region = None
    if args.region:
        from hadoop_bam_tpu.split.intervals import resolve_interval
        iv = resolve_interval(args.region, ds.header.contigs)
        region = (iv.rname, iv.start, iv.end)
    n = 0
    if not args.count and not args.no_header:
        sys.stdout.write(ds.header.to_text())
    for rec in ds.records():
        if region and (rec.chrom != region[0]
                       or not (region[1] <= rec.pos <= region[2])):
            continue
        if args.count:
            n += 1
        else:
            sys.stdout.write(rec.to_line() + "\n")
    if args.count:
        print(n)
    return 0


# ---------------------------------------------------------------------------
# index
# ---------------------------------------------------------------------------

def cmd_index(args) -> int:
    from hadoop_bam_tpu.split.splitting_index import write_splitting_index
    for path in args.paths:
        if args.flavor == "bai":
            from hadoop_bam_tpu.split.bai import write_bai
            out = write_bai(path)
        elif args.flavor == "tbi":
            from hadoop_bam_tpu.split.tabix import write_tabix
            out = write_tabix(path)
        else:
            out = write_splitting_index(path, granularity=args.granularity,
                                        flavor=args.flavor)
        print(f"wrote {out}")
    return 0


# ---------------------------------------------------------------------------
# cat
# ---------------------------------------------------------------------------

def cmd_cat(args) -> int:
    """Concatenate BAMs sharing a header (reference CLI `cat`): header from
    the first input, record bytes streamed through, one EOF terminator."""
    from hadoop_bam_tpu.formats.bamio import BamWriter, read_bam_header
    from hadoop_bam_tpu.api.dataset import open_bam

    header, _ = read_bam_header(args.inputs[0])
    for path in args.inputs[1:]:
        other, _ = read_bam_header(path)
        if (other.ref_names != header.ref_names
                or other.ref_lengths != header.ref_lengths):
            print(f"error: {path} has a different reference dictionary than "
                  f"{args.inputs[0]}; refusing to concatenate", file=sys.stderr)
            return 1
    with BamWriter(args.output, header) as w:
        for path in args.inputs:
            ds = open_bam(path)
            for batch in ds.batches():
                for i in range(len(batch)):
                    w.write_record_bytes(batch.record_bytes(i))
    print(f"wrote {args.output} ({w.records_written} records)")
    return 0


# ---------------------------------------------------------------------------
# observability plumbing shared by the device verbs
# ---------------------------------------------------------------------------

def _start_obs(args) -> None:
    """--trace FILE: turn on the span trace ring before the verb runs."""
    if getattr(args, "trace", None):
        from hadoop_bam_tpu.obs import enable_tracing
        enable_tracing()


def _finish_obs(args, metrics=None) -> None:
    """Write the --trace Chrome trace file and/or the --metrics-json
    snapshot after the verb's work is done."""
    if getattr(args, "trace", None):
        from hadoop_bam_tpu.obs import disable_tracing
        rec = disable_tracing()
        if rec is not None:
            try:
                pid = (sys.modules["jax"].process_index()
                       if "jax" in sys.modules else 0)
            except Exception:  # noqa: BLE001 — labeling only
                pid = 0
            rec.save(args.trace, process_index=pid)
            print(f"wrote trace {args.trace} ({len(rec.events())} spans, "
                  f"{rec.dropped} dropped) — load in chrome://tracing or "
                  f"https://ui.perfetto.dev", file=sys.stderr)
    if getattr(args, "metrics_json", None):
        from hadoop_bam_tpu.obs import save_metrics_json
        if metrics is None:
            from hadoop_bam_tpu.utils.metrics import current_metrics
            metrics = current_metrics()
        save_metrics_json(metrics, args.metrics_json)
        print(f"wrote metrics snapshot {args.metrics_json} "
              f"(render/export it with `hbam metrics`)", file=sys.stderr)


def _add_obs_flags(sub) -> None:
    sub.add_argument("--trace", metavar="FILE", default=None,
                     help="record stage spans (all pipeline stages, all "
                          "pool threads) and write a Chrome trace-event "
                          "JSON file loadable in chrome://tracing / "
                          "Perfetto")
    sub.add_argument("--metrics-json", metavar="FILE", default=None,
                     help="write the run's full metrics snapshot "
                          "(counters, timers, walls, histogram buckets) "
                          "as JSON for `hbam metrics`")


# ---------------------------------------------------------------------------
# summarize
# ---------------------------------------------------------------------------

def cmd_summarize(args) -> int:
    from hadoop_bam_tpu.ops.flagstat import format_flagstat
    from hadoop_bam_tpu.parallel.distributed import distributed_flagstat
    _start_obs(args)
    # plan-once + per-host shares + one allgather under jax.distributed;
    # identical to flagstat_file in a single-process run
    stats = distributed_flagstat(args.path)
    sys.stdout.write(format_flagstat(stats))
    merged = None
    from hadoop_bam_tpu.parallel.distributed import (
        merge_metrics, process_count,
    )
    if args.metrics or args.metrics_json or process_count() > 1:
        # mesh-wide merge: under jax.distributed every host reports the
        # same job-level counters/histograms; single-process this is a
        # plain copy of the local state.  Multi-host runs enter the
        # merge UNCONDITIONALLY: it is a collective, and gating it on
        # per-host CLI flags would deadlock the mesh if the flags ever
        # diverged across hosts (the CL2xx lockstep rule, applied here)
        merged = merge_metrics()
    if args.metrics:
        print("\n-- pipeline metrics (mesh-merged) --", file=sys.stderr)
        print(merged.render(), file=sys.stderr)
    _finish_obs(args, metrics=merged)
    return 0


# ---------------------------------------------------------------------------
# seq-stats / vcf-stats (device payload paths; no reference-CLI analog —
# the closest is `summarize`, which these extend to payload columns)
# ---------------------------------------------------------------------------

_COVERAGE_TILE = 1 << 24        # bases per coverage_file call


def cmd_coverage(args) -> int:
    import contextlib

    import numpy as np

    from hadoop_bam_tpu.formats.bamio import read_bam_header
    from hadoop_bam_tpu.split.intervals import Interval, resolve_interval

    header, _ = read_bam_header(args.input)
    region = resolve_interval(args.region, header.ref_names)
    if region.rname not in header.ref_names:
        raise ValueError(f"region reference {region.rname!r} not in header")
    ref_len = header.ref_lengths[header.ref_names.index(region.rname)]
    start, end = region.start, min(region.end, ref_len)
    if end < start:
        raise ValueError(f"empty region {region}")

    # a bare contig name means the whole reference — tile it through
    # fixed-size windows so device memory stays bounded and the jit
    # caches one window shape.  Without a .bai sidecar every tile must
    # stream the whole file, so say so.
    from hadoop_bam_tpu.parallel.distributed import distributed_coverage
    from hadoop_bam_tpu.split.bai import load_bai_for
    n_tiles = (end - start) // _COVERAGE_TILE + 1
    if n_tiles > 1 and load_bai_for(args.input) is None:
        print(f"note: {n_tiles} tiles with no genomic index sidecar — "
              f"every tile streams the whole file; run "
              f"'hbam index --flavor bai' first for region-pruned reads",
              file=sys.stderr)
    total = covered = max_depth = 0
    depth_sum = 0
    bg_tmp = args.bedgraph + ".tmp" if args.bedgraph else None
    try:
        with (open(bg_tmp, "w") if bg_tmp
              else contextlib.nullcontext()) as bg:
            pending = None               # (start0, end0, depth) run buffer
            for lo in range(start, end + 1, _COVERAGE_TILE):
                hi = min(lo + _COVERAGE_TILE - 1, end)
                # plan-once/per-host-shares/one-allgather under
                # jax.distributed; plain single-process coverage_file
                # otherwise
                depth = distributed_coverage(args.input,
                                             Interval(region.rname, lo, hi),
                                             header=header,
                                             max_cigar=args.max_cigar)
                total += depth.size
                covered += int((depth > 0).sum())
                depth_sum += int(depth.sum(dtype=np.int64))
                if depth.size:
                    max_depth = max(max_depth, int(depth.max()))
                if bg is not None:
                    # run-length encode, merging runs across tile
                    # boundaries (0-based half-open [bedGraph])
                    edges = np.flatnonzero(np.diff(depth)) + 1
                    starts = np.concatenate([[0], edges])
                    ends = np.concatenate([edges, [depth.size]])
                    base = lo - 1
                    for s, e in zip(starts, ends):
                        d = int(depth[s])
                        if not d:
                            continue
                        if pending and pending[1] == base + s \
                                and pending[2] == d:
                            pending = (pending[0], base + e, d)
                        else:
                            if pending:
                                bg.write(f"{region.rname}\t{pending[0]}"
                                         f"\t{pending[1]}\t{pending[2]}\n")
                            pending = (base + s, base + e, d)
            if bg is not None and pending:
                bg.write(f"{region.rname}\t{pending[0]}\t{pending[1]}"
                         f"\t{pending[2]}\n")
    except BaseException:
        # never leave a truncated-but-plausible bedGraph behind
        if bg_tmp and os.path.exists(bg_tmp):
            os.unlink(bg_tmp)
        raise
    if bg_tmp:
        os.replace(bg_tmp, args.bedgraph)

    print(f"region\t{region.rname}:{start}-{end}")
    print(f"bases\t{total}")
    print(f"covered\t{covered}")
    print(f"mean_depth\t{depth_sum / total if total else 0.0:.4f}")
    print(f"max_depth\t{max_depth}")
    if args.bedgraph:
        print(f"wrote {args.bedgraph}")
    return 0


def cmd_seq_stats(args) -> int:
    from hadoop_bam_tpu.parallel.distributed import (
        distributed_cram_seq_stats, distributed_fastq_seq_stats,
        distributed_seq_stats,
    )
    from hadoop_bam_tpu.parallel.pipeline import (
        CRAM_EXTS, TEXT_READ_EXTS, PayloadGeometry,
    )
    geometry = PayloadGeometry(max_len=args.max_len)
    if args.path.lower().endswith(TEXT_READ_EXTS):
        stats = distributed_fastq_seq_stats(args.path, geometry=geometry)
    elif args.path.lower().endswith(CRAM_EXTS):
        import dataclasses

        from hadoop_bam_tpu.config import DEFAULT_CONFIG
        cfg = DEFAULT_CONFIG
        if getattr(args, "reference", None):
            cfg = dataclasses.replace(
                cfg, cram_reference_source_path=args.reference)
        stats = distributed_cram_seq_stats(args.path, config=cfg,
                                           geometry=geometry)
    else:
        stats = distributed_seq_stats(args.path, geometry=geometry)
    print(f"reads\t{stats['n_reads']}")
    print(f"mean_gc\t{stats['mean_gc']:.6f}")
    print(f"mean_qual\t{stats['mean_qual']:.3f}")
    names = ["=", "A", "C", "M", "G", "R", "S", "V",
             "T", "W", "Y", "H", "K", "D", "B", "N"]
    hist = stats["base_hist"]
    total = max(float(hist.sum()), 1.0)
    for code, name in enumerate(names):
        if hist[code]:
            print(f"base_{name}\t{int(hist[code])}\t{hist[code]/total:.4f}")
    return 0


def cmd_explain(args) -> int:
    """Compile the plan for an op and print the IR + routing decision:
    source, spans summary, op DAG, sink, digest, the selected decode
    plane, and the reason each rejected plane/mode failed its gate
    (plan/executor.select_plane — the same single predicate the
    drivers consume)."""
    import dataclasses as _dc
    import json as _json

    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.plan import builders
    from hadoop_bam_tpu.plan.executor import select_plane

    # flag -> config-field forwarding, value-filtered (no gate
    # conditionals here: PL101 applies to this module too)
    overrides = {
        "inflate_backend": args.inflate_backend,
        "bam_intervals": args.intervals,
        "skip_bad_spans": True if args.skip_bad_spans else None,
        "use_fused_decode": False if args.no_fused else None,
    }
    overrides = {k: v for k, v in overrides.items() if v is not None}
    cfg = _dc.replace(DEFAULT_CONFIG, **overrides) if overrides \
        else DEFAULT_CONFIG

    if args.op == "flagstat":
        plan = builders.flagstat_plan(args.path, cfg)
    elif args.op == "seq-stats":
        from hadoop_bam_tpu.parallel.pipeline import (
            CRAM_EXTS, TEXT_READ_EXTS,
        )
        path = args.path.lower()
        build = builders.read_stats_plan if path.endswith(TEXT_READ_EXTS) \
            else builders.cram_stats_plan if path.endswith(CRAM_EXTS) \
            else builders.seq_stats_plan
        plan = build(args.path, cfg)
    elif args.op == "vcf-stats":
        plan = builders.variant_stats_plan(args.path, cfg)
    elif args.op == "vcf-gwas":
        plan = builders.variant_gwas_plan(
            args.path, args.path + ".traits.tsv", cfg)
    elif args.op == "cohort":
        plan = builders.cohort_plan(args.path, cfg)
    elif args.op == "mkdup":
        plan = builders.mkdup_plan(args.path, args.path + ".mkdup.bam",
                                   cfg)
    elif args.op == "serve-tile":
        if args.region:
            # the realistic shape: resolve the region through the index
            # and explain the FIRST coalesced chunk's tile build
            from hadoop_bam_tpu.query.engine import QueryEngine
            engine = QueryEngine(config=cfg)
            meta = engine._file_meta(args.path)
            _iv, ranges = engine._resolve(meta, args.region)
            chunks = engine._coalesce(ranges, meta.kind)
            s, e = chunks[0] if chunks else (0, 0)
            plan = builders.serve_tile_plan(args.path, meta.kind, s, e)
        else:
            plan = builders.serve_tile_plan(args.path)
    else:  # query
        if not args.region:
            raise SystemExit("explain query needs --region")
        from hadoop_bam_tpu.query.engine import QueryEngine
        engine = QueryEngine(config=cfg)
        meta = engine._file_meta(args.path)
        _iv, ranges = engine._resolve(meta, args.region)
        chunks = engine._coalesce(ranges, meta.kind)
        plan = builders.query_region_plan(args.path, meta.kind,
                                          args.region, chunks)
    # the gate sees parsed intervals at run time; a set-but-unparsed
    # config string is the same gate signal for explain purposes
    intervals = () if cfg.bam_intervals else None
    decision = select_plane(cfg, intervals=intervals)
    if args.json:
        print(_json.dumps({"plan": plan.to_doc(),
                           "digest": plan.digest(),
                           "decision": decision.to_doc()},
                          indent=1, sort_keys=True))
        return 0
    for line in plan.render():
        print(line)
    print(f"plane   {decision.plane} ("
          f"fused={'on' if decision.use_fused else 'off'}, "
          f"stream_fused={'on' if decision.stream_fused else 'off'})")
    if decision.rejected:
        print("rejected:")
        for p, reason in decision.rejected:
            print(f"  {p:13s} {reason}")
    return 0


def cmd_vcf_stats(args) -> int:
    from hadoop_bam_tpu.parallel.distributed import (
        distributed_variant_stats,
    )
    stats = distributed_variant_stats(args.path)
    print(f"variants\t{stats['n_variants']}")
    print(f"snps\t{stats['n_snp']}")
    print(f"pass\t{stats['n_pass']}")
    print(f"mean_af\t{stats['mean_af']:.6f}")
    for i, cr in enumerate(stats["sample_callrate"]):
        print(f"callrate_{i}\t{cr:.4f}")
    return 0


def cmd_vcf_gwas(args) -> int:
    from hadoop_bam_tpu.cohort.gwas import format_gwas, variant_gwas_file

    for line in format_gwas(variant_gwas_file(args.path, args.pheno)):
        print(line)
    return 0


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------

def _write_config(args):
    """Write-path knobs shared by the sort verbs -> an HBamConfig."""
    import dataclasses

    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    overrides = {}
    if getattr(args, "compress_level", None) is not None:
        # validate at the argv boundary: an out-of-range level would
        # otherwise surface as a raw zlib.error from a pool worker —
        # and inconsistently, since the native backend accepts levels
        # zlib rejects
        if not 0 <= args.compress_level <= 9:
            raise SystemExit(
                f"--compress-level must be in 0-9, "
                f"got {args.compress_level}")
        overrides["write_compress_level"] = args.compress_level
    if getattr(args, "no_write_index", False):
        overrides["write_index_kinds"] = "none"
    return dataclasses.replace(DEFAULT_CONFIG, **overrides) \
        if overrides else DEFAULT_CONFIG


def _journal_arg(args, default_path: str) -> Optional[str]:
    """Resolve a ``--journal [PATH]`` flag: absent -> None, bare flag ->
    the job's default sibling journal, explicit value -> that path."""
    j = getattr(args, "journal", None)
    if j is None:
        return None
    return default_path if j == "" else j


def cmd_sort(args) -> int:
    if args.run_records is not None and args.run_records <= 0:
        raise SystemExit("--run-records must be positive")
    cfg = _write_config(args)
    journal = None
    if getattr(args, "journal", None) is not None:
        if not args.mesh:
            raise SystemExit("--journal requires --mesh (the spill-merge "
                             "sort is not journaled; its runs are "
                             "process-local temps)")
        from hadoop_bam_tpu.jobs import journal_path_for
        journal = _journal_arg(args, journal_path_for(args.output))
    if args.mesh:
        if args.by_name:
            raise SystemExit(
                "--mesh supports coordinate sort only (queryname keys "
                "have no fixed-width device representation); drop -n")
        from hadoop_bam_tpu.parallel.mesh_sort import sort_bam_mesh
        # --run-records under --mesh selects the multi-round SPILL
        # exchange: device memory bounded by ~that many records per
        # device per round (the MR shuffle's spill).  Output rides the
        # write/ subsystem: pooled deflate + co-written index sidecars
        n = sort_bam_mesh(args.input, args.output, exchange=args.exchange,
                          round_records=args.run_records, config=cfg,
                          journal_path=journal)
        mode = "mesh spill" if args.run_records is not None else "mesh"
        extra = f", journal {journal}" if journal else ""
        print(f"wrote {args.output} ({n} records, coordinate, {mode}"
              f"{extra})")
        return 0
    if args.exchange is not None:
        raise SystemExit("--exchange only applies to --mesh")
    from hadoop_bam_tpu.utils.sort import sort_bam

    n = sort_bam(args.input, args.output, by_name=args.by_name,
                 config=cfg,
                 run_records=args.run_records
                 if args.run_records is not None else 1_000_000)
    so = "queryname" if args.by_name else "coordinate"
    print(f"wrote {args.output} ({n} records, {so})")
    return 0


# ---------------------------------------------------------------------------
# fixmate
# ---------------------------------------------------------------------------

def cmd_fixmate(args) -> int:
    from hadoop_bam_tpu.utils.fixmate import fixmate_bam

    n = fixmate_bam(args.input, args.output, config=_write_config(args))
    print(f"wrote {args.output} ({n} records)")
    return 0


# ---------------------------------------------------------------------------
# mkdup
# ---------------------------------------------------------------------------

def cmd_mkdup(args) -> int:
    """The fused preprocessing pipeline: read -> mesh sort exchange ->
    duplicate marking -> flag-patched indexed write, one pass, driven
    through the plan IR (`hbam explain mkdup` shows the compiled
    plan)."""
    if args.run_records is not None and args.run_records <= 0:
        raise SystemExit("--run-records must be positive")
    cfg = _write_config(args)
    journal = None
    if getattr(args, "journal", None) is not None:
        from hadoop_bam_tpu.jobs import journal_path_for
        journal = _journal_arg(args, journal_path_for(args.output))
    from hadoop_bam_tpu.plan import builders
    from hadoop_bam_tpu.plan.executor import execute

    plan = builders.mkdup_plan(args.input, args.output, cfg,
                               remove_duplicates=args.remove_duplicates,
                               library_from=args.library_from)
    n = execute(plan, config=cfg, round_records=args.run_records,
                journal_path=journal)
    what = "removed" if args.remove_duplicates else "marked"
    extra = f", journal {journal}" if journal else ""
    print(f"wrote {args.output} ({n} records, duplicates {what}, "
          f"coordinate, fused mesh{extra})")
    return 0


def _alen(r) -> int:
    """Alignment span on the reference from the CIGAR (M/D/N/=/X)."""
    import re
    if r.cigar in ("*", ""):
        return len(r.seq) if r.seq != "*" else 0
    return sum(int(n) for n, op in re.findall(r"(\d+)([MIDNSHP=X])", r.cigar)
               if op in "MDN=X")


# ---------------------------------------------------------------------------
# query
# ---------------------------------------------------------------------------

def cmd_query(args) -> int:
    """Batched random-access region serving (query/engine.py): resolve
    every region through the file's genomic index (.bai/.csi for BAM,
    .tbi for BGZF VCF and BCF, container coordinates for CRAM), decode
    the union of needed chunks once, and filter on the mesh."""
    import dataclasses

    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.query import QueryEngine, QueryRequest

    from hadoop_bam_tpu.utils.metrics import METRICS

    cfg = DEFAULT_CONFIG
    if args.deadline is not None:
        cfg = dataclasses.replace(cfg, query_deadline_s=args.deadline)
    _start_obs(args)
    engine = QueryEngine(config=cfg)
    reqs = [QueryRequest(args.path, region) for region in args.regions]
    results = engine.query_records(reqs)
    for res in results:
        if args.count:
            print(f"{res.request.region}\t{len(res.records)}")
        else:
            for rec in res.records:
                print(rec.to_line())
    if args.metrics:
        stats = engine.stats()
        print("-- query cache --", file=sys.stderr)
        for k in sorted(stats):
            print(f"{k}\t{stats[k]}", file=sys.stderr)
        lat = METRICS.hist_summary("query.latency_s")
        if lat:
            print(f"latency_s\tp50={lat['p50']:.4g} p95={lat['p95']:.4g} "
                  f"p99={lat['p99']:.4g} n={lat['count']}",
                  file=sys.stderr)
    _finish_obs(args)
    return 0


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def cmd_serve(args) -> int:
    """Long-running multi-tenant region serving (serve/loop.py): JSONL
    requests over stdin/stdout (default) or TCP (--port), served from a
    device-resident decoded-tile cache above the host chunk LRU, with
    per-tenant admission quotas, priority classes, and predictive
    prefetch."""
    import dataclasses
    import json as _json

    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.serve import ServeLoop, make_tcp_server, serve_stdio

    cfg = DEFAULT_CONFIG
    overrides = {}
    if args.deadline is not None:
        overrides["query_deadline_s"] = args.deadline
    if args.tile_cache_bytes is not None:
        overrides["serve_tile_cache_bytes"] = args.tile_cache_bytes
    if args.no_prefetch:
        overrides["serve_prefetch"] = False
    if getattr(args, "breaker_cooldown", None) is not None:
        overrides["breaker_cooldown_s"] = args.breaker_cooldown
    if getattr(args, "flight_dir", None):
        overrides["flight_dump_dir"] = args.flight_dir
    if getattr(args, "replica_id", None):
        overrides["serve_replica_id"] = args.replica_id
    if getattr(args, "peers", None):
        overrides["serve_peers"] = args.peers
    if getattr(args, "replication", None) is not None:
        overrides["fleet_replication"] = args.replication
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if cfg.serve_peers and not cfg.serve_replica_id:
        print("error: --peers requires --replica-id (this replica's own "
              "name in the peer set)", file=sys.stderr)
        return 2
    if cfg.serve_peers and args.port is None:
        print("error: --peers requires --port (peer fetch rides the TCP "
              "transport)", file=sys.stderr)
        return 2
    _start_obs(args)
    n = 0
    with ServeLoop(config=cfg) as loop:
        for path in args.warm or ():
            # warm metadata + index up front so the first client query
            # doesn't pay the header walk
            loop.engine._file_meta(path)
        if args.port is not None:
            server = make_tcp_server(loop, host=args.host, port=args.port)
            host, port = server.server_address[:2]
            print(f"serving on {host}:{port} (JSONL; ^C stops)",
                  file=sys.stderr)
            if loop.fleet is not None:
                print(f"fleet replica={loop.fleet.replica_id} "
                      f"replication={loop.fleet.replication} "
                      f"peers={','.join(sorted(loop.fleet.peers)) or '-'}",
                      file=sys.stderr)
            try:
                server.serve_forever()
            except KeyboardInterrupt:
                pass
            finally:
                server.shutdown()
                server.server_close()
        else:
            n = serve_stdio(loop)
        if args.metrics:
            print("-- serve stats --", file=sys.stderr)
            for section, stats in sorted(loop.stats().items()):
                print(f"{section}\t{stats}", file=sys.stderr)
        # the degrade-and-heal surface, always reported at shutdown:
        # breaker/ladder state is exactly what an operator needs when a
        # server that kept serving was quietly demoted or shedding
        # (clients get the same document live via {"op": "health"})
        print("-- serve health --", file=sys.stderr)
        print(_json.dumps(loop.health(), default=str), file=sys.stderr)
    _finish_obs(args)
    if args.port is None:
        print(f"served {n} request(s)", file=sys.stderr)
    return 0


# ---------------------------------------------------------------------------
# metrics (snapshot render / export)
# ---------------------------------------------------------------------------

def cmd_metrics(args) -> int:
    """Render or re-export a metrics snapshot written by
    ``--metrics-json``: human text, Prometheus text
    exposition, or passthrough JSON.  Multiple snapshots merge with the
    same semantics as the mesh-wide allgather (counter sums, histogram
    bucket merges, wall maxima)."""
    from hadoop_bam_tpu.obs import (
        load_metrics_json, prometheus_text, render_metrics,
    )
    from hadoop_bam_tpu.utils.metrics import Metrics

    merged = Metrics()
    for path in args.files:
        merged.merge_dict(load_metrics_json(path))
    d = merged.to_dict()
    if args.format == "prometheus":
        sys.stdout.write(prometheus_text(d))
    elif args.format == "json":
        import json
        json.dump(d, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
    else:
        print(render_metrics(d))
    return 0


# ---------------------------------------------------------------------------
# lint
# ---------------------------------------------------------------------------

def cmd_lint(args) -> int:
    """Repo-native static analysis (hbam-lint): trace safety, collective
    lockstep, error taxonomy, binary-layout contracts.  Non-zero exit on
    unsuppressed findings — the CI contract."""
    from hadoop_bam_tpu.analysis.core import lint_main
    fwd: List[str] = []
    if args.root:
        fwd += ["--root", args.root]
    for only in args.only or ():
        fwd += ["--only", only]
    if args.baseline:
        fwd += ["--baseline", args.baseline]
    if args.no_baseline:
        fwd.append("--no-baseline")
    if args.update_baseline:
        fwd.append("--update-baseline")
    if args.show_suppressed:
        fwd.append("--show-suppressed")
    if args.format != "text":
        fwd += ["--format", args.format]
    if args.no_cache:
        fwd.append("--no-cache")
    return lint_main(fwd)


# ---------------------------------------------------------------------------
# vcf-sort
# ---------------------------------------------------------------------------

def cmd_vcf_sort(args) -> int:
    from hadoop_bam_tpu.utils.sort import sort_vcf

    if args.run_records <= 0:
        raise SystemExit("--run-records must be positive")
    n = sort_vcf(args.input, args.output, config=_write_config(args),
                 run_records=args.run_records)
    print(f"wrote {args.output} ({n} records)")
    return 0


# ---------------------------------------------------------------------------
# cohort
# ---------------------------------------------------------------------------

def cmd_cohort(args) -> int:
    """Cohort variant plane (cohort/): join the manifest's single-sample
    VCF/BCF inputs on position into one [variants, samples] mesh tensor
    and run the GWAS drivers (allele frequency, call rate, HWE; the
    score test with --pheno).  --region restricts the report to one
    slice; --tsv writes the full per-variant table."""
    import numpy as np

    from hadoop_bam_tpu.cohort import GWAS_COLUMNS, CohortDataset

    _start_obs(args)
    journal = None
    if getattr(args, "journal", None) is not None:
        from hadoop_bam_tpu.jobs import JOURNAL_SUFFIX
        journal = _journal_arg(args, args.manifest + JOURNAL_SUFFIX)
    ds = CohortDataset(args.manifest, journal_path=journal)
    pheno = None
    if args.pheno:
        # one float per manifest sample, in manifest order; 'nan' (or
        # any non-float token) = missing phenotype
        vals = []
        with open(args.pheno) as f:
            for line in f:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                try:
                    vals.append(float(line.split()[-1]))
                except ValueError:
                    vals.append(float("nan"))
        pheno = np.asarray(vals, np.float32)
    res = ds.gwas(phenotype=pheno)
    mask = np.ones(res["n_variants"], bool)
    if args.region:
        from hadoop_bam_tpu.split.intervals import parse_interval
        iv = parse_interval(args.region)
        rid = ds.contig_index(iv.rname)
        if rid < 0:
            raise SystemExit(f"contig {iv.rname!r} is in no sample header")
        mask = ((res["chrom"] == rid) & (res["pos"] >= iv.start)
                & (res["pos"] <= iv.end))
    n = int(mask.sum())
    print(f"samples\t{ds.n_samples}")
    print(f"variants\t{n}")
    print(f"quarantined\t{len(res['quarantined'])}")
    for sid in sorted(res["quarantined"]):
        print(f"quarantined_sample\t{sid}", file=sys.stderr)
    with np.errstate(invalid="ignore"):
        for col in GWAS_COLUMNS:
            v = res[col][mask]
            if col == "score_chi2" and pheno is None:
                continue
            if v.size and not np.all(np.isnan(v)):
                print(f"mean_{col}\t{np.nanmean(v):.6f}")
            else:
                print(f"mean_{col}\tnan")
    if args.tsv:
        cols = [c for c in GWAS_COLUMNS
                if not (c == "score_chi2" and pheno is None)]
        with open(args.tsv, "w") as f:
            f.write("\t".join(["chrom", "pos", "n_allele"] + cols) + "\n")
            rows = np.flatnonzero(mask)
            for r in rows:
                name = (ds.contigs[int(res["chrom"][r])]
                        if 0 <= int(res["chrom"][r]) < len(ds.contigs)
                        else str(int(res["chrom"][r])))
                f.write("\t".join(
                    [name, str(int(res["pos"][r])),
                     str(int(res["n_allele"][r]))]
                    + [f"{float(res[c][r]):.6g}" for c in cols]) + "\n")
        print(f"wrote {args.tsv} ({n} variants)", file=sys.stderr)
    _finish_obs(args)
    return 0


# ---------------------------------------------------------------------------
# resume / jobs (crash-safe job layer, jobs/)
# ---------------------------------------------------------------------------

def cmd_resume(args) -> int:
    """Resume (or verify) the job a journal describes: re-invokes the
    journaled pipeline, which replays the journal, verifies every
    recorded artifact, skips the completed units, and re-runs only the
    remainder.  Identity/fingerprint/plan mismatches refuse loudly
    (PlanError) rather than publish a silently-wrong output."""
    from hadoop_bam_tpu.jobs import resume_job
    from hadoop_bam_tpu.utils.metrics import METRICS

    _start_obs(args)
    out = resume_job(args.journal)
    for k in sorted(out):
        v = out[k]
        if v is not None:
            print(f"{k}\t{v}")
    for c in ("jobs.rounds_skipped", "jobs.spans_skipped",
              "jobs.shards_skipped", "jobs.chunks_replayed",
              "jobs.jobs_skipped", "jobs.stale_runs_swept",
              "jobs.stale_chunks_swept", "write.stale_temps_swept"):
        n = METRICS.counters.get(c, 0)
        if n:
            print(f"{c}\t{n}")
    _finish_obs(args)
    return 0


def cmd_jobs(args) -> int:
    """List job journals in a directory: kind, status (done / resumable
    / fresh / corrupt), committed units, output.  ``--json`` emits one
    machine-readable object per journal (trace_id, resume grain, units
    skipped/total) — the SAME document ``hbam top`` renders, so
    external schedulers and the live view share one parser
    (``jobs.runner.job_info_doc``)."""
    import json as _json

    from hadoop_bam_tpu.jobs import job_info_doc, job_status, list_jobs

    infos = [job_status(p) for p in args.journals] if args.journals \
        else list_jobs(args.dir)
    if getattr(args, "json", False):
        for i in infos:
            print(_json.dumps(job_info_doc(i), sort_keys=True))
        return 0
    if not infos:
        print(f"no *.hbam-journal files in {args.dir}")
        return 0
    for i in infos:
        detail = f"\t[{i.detail}]" if i.detail else ""
        print(f"{i.path}\t{i.kind}\t{i.status}\tunits={i.units}"
              f"\t{i.output or '-'}{detail}")
    return 0


# ---------------------------------------------------------------------------
# top (live ops view over a running `hbam serve`)
# ---------------------------------------------------------------------------

def _top_fetch(host: str, port: int, timeout: float = 10.0):
    """One poll of a live serve process: the health document and the
    metrics/SLO snapshot, over the JSONL TCP transport."""
    import json as _json
    import socket

    with socket.create_connection((host, port), timeout=timeout) as s:
        f = s.makefile("rw", encoding="utf-8", newline="\n")
        f.write(_json.dumps({"op": "health", "id": 1}) + "\n")
        f.write(_json.dumps({"op": "metrics", "id": 2}) + "\n")
        f.flush()
        docs = {}
        for _ in range(2):
            line = f.readline()
            if not line:
                break
            d = _json.loads(line)
            docs[d.get("id")] = d
    return (docs.get(1, {}).get("health", {}), docs.get(2, {}))


def _hist_summary(hists: dict, key: str) -> Optional[dict]:
    from hadoop_bam_tpu.obs import Histogram
    h = hists.get(key)
    if not isinstance(h, dict) or "buckets" not in h:
        return None
    return Histogram.from_dict(h).summary()


def _render_top(health: dict, mdoc: dict, prev_counters: Optional[dict],
                interval: float, jobs_dir: Optional[str]) -> str:
    """One `hbam top` frame as text: per-tenant q/s + latency
    percentiles, cache hit rates, pool occupancy, breaker/SLO state,
    and active-job resume progress."""
    metrics = mdoc.get("metrics", {}) or {}
    counters = {k: int(v)
                for k, v in dict(metrics.get("counters", {})).items()}
    hists = dict(metrics.get("histograms", {}))
    lines: List[str] = []
    tiles = health.get("tiles", {}) or {}
    pool = health.get("pool", {}) or {}
    lines.append(
        f"status={health.get('status', '?')} "
        f"queued={health.get('queued', '?')} "
        f"fault_pressure={health.get('fault_pressure', 0)} "
        f"open_breakers={health.get('open_breakers', 0)}")
    lines.append(
        f"pool: workers={pool.get('workers', '?')} "
        f"live={pool.get('threads_live', '?')} "
        f"queued={pool.get('queued_tasks', 0)} "
        f"bg={pool.get('bg_running', 0)}/{pool.get('bg_queued', 0)}")
    th = int(tiles.get("hits", 0))
    tm = int(tiles.get("misses", 0))
    ch = counters.get("query.cache_hits", 0)
    cm = counters.get("query.cache_misses", 0)
    lines.append(
        f"caches: tile_hit_rate="
        f"{th / (th + tm):.2f}" if (th + tm) else
        "caches: tile_hit_rate=-")
    lines[-1] += (f" chunk_hit_rate={ch / (ch + cm):.2f}"
                  if (ch + cm) else " chunk_hit_rate=-")
    for name, s in sorted((mdoc.get("slo") or {}).items()):
        burn = " ".join(f"{w}={v}" for w, v in sorted(s.items()))
        lines.append(f"slo {name}: {burn}")
    fl = health.get("flight", {}) or {}
    if fl:
        lines.append(f"flight: dumps={fl.get('dumps_written', 0)} "
                     f"last={fl.get('last_dump') or '-'}")
    # per-tenant table from the serve.requests.<tenant> counters and
    # serve.latency_s.<tenant> histograms the serve loop mirrors into
    # its process-global metrics
    _prefix = "serve.requests."
    tenants = sorted(k[len(_prefix):] for k in counters
                     if k.startswith(_prefix))
    tbreak = health.get("tenant_breakers", {}) or {}
    if tenants:
        lines.append(f"{'tenant':<16}{'q/s':>8}{'p50ms':>9}{'p99ms':>9}"
                     f"{'reqs':>8}  breaker")
        for t in tenants:
            reqs = counters.get(f"serve.requests.{t}", 0)
            if prev_counters is not None and interval > 0:
                d = reqs - prev_counters.get(f"serve.requests.{t}", 0)
                qps = f"{d / interval:.1f}"
            else:
                qps = "-"
            s = _hist_summary(hists, f"serve.latency_s.{t}")
            p50 = f"{s['p50'] * 1e3:.1f}" if s else "-"
            p99 = f"{s['p99'] * 1e3:.1f}" if s else "-"
            br = (tbreak.get(t) or {}).get("state", "closed")
            lines.append(f"{t:<16}{qps:>8}{p50:>9}{p99:>9}"
                         f"{reqs:>8}  {br}")
    else:
        lines.append("tenants: (no requests served yet)")
    if jobs_dir:
        from hadoop_bam_tpu.jobs import job_info_doc, list_jobs
        rows = [job_info_doc(i) for i in list_jobs(jobs_dir)]
        active = [r for r in rows if r["status"] != "done"]
        lines.append(f"jobs in {jobs_dir}: {len(rows)} journal(s), "
                     f"{len(active)} not done")
        for r in rows:
            lines.append(
                f"  {r['path']} {r['kind']} {r['status']} "
                f"grain={r['resume_grain']} "
                f"units={r['units_skipped']}/{r['units_total']} "
                f"trace={r['trace_id'] or '-'}")
    return "\n".join(lines)


def _parse_endpoints(spec: str) -> List[Tuple[str, int]]:
    out: List[Tuple[str, int]] = []
    for entry in (spec or "").split(","):
        entry = entry.strip()
        if not entry:
            continue
        host, _, port = entry.rpartition(":")
        if not host or not port.isdigit():
            raise ValueError(f"bad endpoint {entry!r} — want HOST:PORT")
        out.append((host, int(port)))
    if not out:
        raise ValueError("--endpoints needs at least one HOST:PORT")
    return out


def _fleet_snapshot(endpoints, timeout: float) -> List[dict]:
    """Poll every fleet endpoint once; an unreachable replica becomes a
    DOWN row, never a failed frame (the whole point of a fleet view is
    seeing who is missing)."""
    snaps = []
    for host, port in endpoints:
        ep = f"{host}:{port}"
        try:
            health, mdoc = _top_fetch(host, port, timeout=timeout)
        except (OSError, ValueError) as e:
            snaps.append({"endpoint": ep, "ok": False, "err": str(e)})
            continue
        snaps.append({"endpoint": ep, "ok": True,
                      "health": health, "mdoc": mdoc})
    return snaps


def _render_fleet_top(snaps: List[dict], prev: dict,
                      interval: float) -> str:
    """The ``hbam top --endpoints`` frame: one row per replica
    (q/s, p50/p99 across tenants, tile hit rate, peer-breaker states,
    degraded flag) plus fleet-wide aggregates."""
    from hadoop_bam_tpu.obs import Histogram

    lines: List[str] = []
    lines.append(f"{'replica':<12}{'endpoint':<22}{'q/s':>7}{'p50ms':>8}"
                 f"{'p99ms':>8}{'tile%':>7}{'peers':>12}  flags")
    up = 0
    tot_qps = 0.0
    tot_th = tot_tm = 0
    tot_fetch_ok = tot_served = tot_local = 0
    for snap in snaps:
        ep = snap["endpoint"]
        if not snap["ok"]:
            lines.append(f"{'-':<12}{ep:<22}{'-':>7}{'-':>8}{'-':>8}"
                         f"{'-':>7}{'-':>12}  DOWN ({snap['err']})")
            continue
        up += 1
        health, mdoc = snap["health"], snap["mdoc"]
        fleet = health.get("fleet") or {}
        rid = str(fleet.get("replica_id") or "-")
        metrics = mdoc.get("metrics", {}) or {}
        counters = {k: int(v)
                    for k, v in dict(metrics.get("counters", {})).items()}
        hists = dict(metrics.get("histograms", {}))
        reqs = sum(v for k, v in counters.items()
                   if k.startswith("serve.requests."))
        pc = prev.get(ep)
        if pc is not None and interval > 0:
            preqs = sum(v for k, v in pc.items()
                        if k.startswith("serve.requests."))
            qv = max(0, reqs - preqs) / interval
            tot_qps += qv
            qps = f"{qv:.1f}"
        else:
            qps = "-"
        merged = Histogram.merged(
            Histogram.from_dict(h) for k, h in hists.items()
            if k.startswith("serve.latency_s.")
            and isinstance(h, dict) and "buckets" in h)
        if merged.count:
            p50 = f"{merged.percentile(50) * 1e3:.1f}"
            p99 = f"{merged.percentile(99) * 1e3:.1f}"
        else:
            p50 = p99 = "-"
        tiles = health.get("tiles", {}) or {}
        th, tm = int(tiles.get("hits", 0)), int(tiles.get("misses", 0))
        tot_th += th
        tot_tm += tm
        tile = f"{100.0 * th / (th + tm):.0f}" if (th + tm) else "-"
        brk = {}
        for st in (d.get("state", "closed") for d in
                   dict(fleet.get("peer_breakers") or {}).values()):
            brk[st] = brk.get(st, 0) + 1
        peers = ",".join(f"{n}{s[:1].upper()}"
                         for s, n in sorted(brk.items())) or "-"
        flags = []
        if fleet.get("degraded"):
            flags.append("DEGRADED")
        if health.get("status") not in (None, "ok"):
            flags.append(str(health.get("status")))
        tot_fetch_ok += int(fleet.get("peer_fetch_ok", 0))
        tot_served += int(fleet.get("chunks_served", 0))
        tot_local += int(fleet.get("local_decodes", 0))
        lines.append(f"{rid:<12}{ep:<22}{qps:>7}{p50:>8}{p99:>8}"
                     f"{tile:>7}{peers:>12}  {' '.join(flags) or '-'}")
        snap["counters"] = counters
    agg_tile = (f"{100.0 * tot_th / (tot_th + tot_tm):.0f}%"
                if (tot_th + tot_tm) else "-")
    denom = tot_fetch_ok + tot_local
    xr = f"{tot_fetch_ok / denom:.2f}" if denom else "-"
    lines.append(
        f"fleet: up={up}/{len(snaps)} q/s={tot_qps:.1f} "
        f"tile_hit={agg_tile} peer_fetches={tot_fetch_ok} "
        f"chunks_served_for_peers={tot_served} "
        f"cross_replica_tile_rate={xr}")
    return "\n".join(lines)


def cmd_top(args) -> int:
    """Live introspection of a running ``hbam serve --port`` process:
    polls the ``{"op": "health"}`` / ``{"op": "metrics"}`` transport
    surfaces and renders per-tenant q/s, latency percentiles, cache hit
    rates, pool occupancy, breaker + SLO burn state, and (with
    ``--jobs-dir``) journaled-job resume progress.  With
    ``--endpoints HOST:PORT,...`` it becomes the FLEET view: one row
    per replica plus fleet-wide aggregates, DOWN rows for unreachable
    replicas."""
    import time as _time

    if getattr(args, "endpoints", None):
        try:
            endpoints = _parse_endpoints(args.endpoints)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        iterations = 1 if args.once else int(args.iterations)
        prev: dict = {}
        i = 0
        try:
            while True:
                i += 1
                snaps = _fleet_snapshot(endpoints, timeout=args.timeout)
                frame = _render_fleet_top(snaps, prev,
                                          float(args.interval))
                print(f"-- hbam top (fleet, poll {i}"
                      f"{'' if not iterations else f'/{iterations}'}"
                      f") --")
                print(frame, flush=True)
                prev = {s["endpoint"]: s.get("counters", {})
                        for s in snaps if s["ok"]}
                if iterations and i >= iterations:
                    return 0
                _time.sleep(max(0.1, float(args.interval)))
        except KeyboardInterrupt:
            return 0
    if args.port is None:
        print("error: --port (single server) or --endpoints (fleet) "
              "is required", file=sys.stderr)
        return 2
    iterations = 1 if args.once else int(args.iterations)
    prev_counters = None
    i = 0
    try:
        while True:
            i += 1
            try:
                health, mdoc = _top_fetch(args.host, args.port,
                                          timeout=args.timeout)
            except (OSError, ValueError) as e:
                print(f"error: cannot poll {args.host}:{args.port}: "
                      f"{e}", file=sys.stderr)
                return 1
            frame = _render_top(health, mdoc, prev_counters,
                                float(args.interval), args.jobs_dir)
            hdr = (f"-- hbam top {args.host}:{args.port} "
                   f"(poll {i}"
                   f"{'' if not iterations else f'/{iterations}'}) --")
            print(hdr)
            print(frame, flush=True)
            prev_counters = {
                k: int(v) for k, v in dict(
                    (mdoc.get("metrics", {}) or {})
                    .get("counters", {})).items()}
            if iterations and i >= iterations:
                return 0
            _time.sleep(max(0.1, float(args.interval)))
    except KeyboardInterrupt:
        # ^C is the documented way out of the default forever loop
        return 0


def cmd_fleet(args) -> int:
    """One replica's view of the serving fleet: the ``{"op": "fleet"}``
    transport surface — membership states (alive/suspect/evicted),
    per-peer breaker states, hedge soft deadline, peer-fetch/serve
    counters, degraded flag."""
    import json as _json
    import socket

    try:
        with socket.create_connection((args.host, args.port),
                                      timeout=args.timeout) as s:
            f = s.makefile("rw", encoding="utf-8", newline="\n")
            f.write(_json.dumps({"op": "fleet", "id": 1}) + "\n")
            f.flush()
            doc = _json.loads(f.readline() or "{}")
    except (OSError, ValueError) as e:
        print(f"error: cannot poll {args.host}:{args.port}: {e}",
              file=sys.stderr)
        return 1
    fleet = doc.get("fleet")
    if fleet is None:
        print(f"{args.host}:{args.port}: not a fleet replica "
              f"(started without --peers/--replica-id)")
        return 1
    if args.json:
        print(_json.dumps(fleet, sort_keys=True, default=str))
        return 0
    print(f"replica={fleet.get('replica_id')} "
          f"replication={fleet.get('replication')} "
          f"degraded={fleet.get('degraded')}")
    peers = dict((fleet.get("membership") or {}).get("peers") or {})
    for pid in sorted(peers):
        st = peers[pid] if isinstance(peers[pid], str) else \
            peers[pid].get("state", "?")
        brk = (dict(fleet.get("peer_breakers") or {}).get(pid)
               or {}).get("state", "-")
        print(f"  {pid:<16}{st:<10}breaker={brk}")
    soft = fleet.get("hedge_soft_deadline_s")
    print(f"hedge_soft_deadline_s={soft if soft is not None else '-'} "
          f"peer_fetch_ok={fleet.get('peer_fetch_ok', 0)} "
          f"peer_fetch_failed={fleet.get('peer_fetch_failed', 0)} "
          f"chunks_served={fleet.get('chunks_served', 0)} "
          f"hedges={fleet.get('hedges', 0)}/"
          f"{fleet.get('hedge_wins', 0)} wins "
          f"degraded_serves={fleet.get('degraded_serves', 0)}")
    return 0


# ---------------------------------------------------------------------------
# frontend
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hadoop_bam_tpu",
        description="TPU-native splittable genomics I/O — CLI verbs "
                    "(reference parity: cat, index, sort, summarize, view, "
                    "fixmate, vcf-sort)")
    sub = p.add_subparsers(dest="verb", required=True)

    v = sub.add_parser("view", help="print records as SAM/VCF text")
    v.add_argument("path")
    v.add_argument("region", nargs="?", default=None,
                   help="chr[:start-end] filter")
    v.add_argument("-H", "--header-only", action="store_true")
    v.add_argument("-c", "--count", action="store_true")
    v.add_argument("--no-header", action="store_true")
    v.set_defaults(fn=cmd_view, uses_device=False)

    i = sub.add_parser("index", help="build splitting index sidecar(s)")
    i.add_argument("paths", nargs="+")
    i.add_argument("-g", "--granularity", type=int, default=4096)
    i.add_argument("--flavor",
                   choices=["splitting-bai", "sbi", "bai", "tbi"],
                   default="splitting-bai",
                   help="bai = genomic BAI for BAM; tbi = tabix for BGZF "
                        "VCF (both need coordinate-sorted input and "
                        "enable interval queries/trimming)")
    i.set_defaults(fn=cmd_index, uses_device=False)

    c = sub.add_parser("cat", help="concatenate same-header BAMs")
    c.add_argument("output")
    c.add_argument("inputs", nargs="+")
    c.set_defaults(fn=cmd_cat, uses_device=False)

    s = sub.add_parser("summarize", help="distributed flagstat")
    s.add_argument("path")
    s.add_argument("--metrics", action="store_true",
                   help="dump mesh-merged pipeline counters/timers/"
                        "histograms to stderr")
    _add_obs_flags(s)
    s.set_defaults(fn=cmd_summarize, uses_device=True)

    sq = sub.add_parser("seq-stats",
                        help="GC/quality/base stats via the Pallas "
                             "payload kernel: a BAM, a CRAM, or a FASTQ "
                             "/ QSEQ read file, plain or gzip'd "
                             "(.fastq .fq .qseq, each also .gz)")
    sq.add_argument("path")
    sq.add_argument("--max-len", type=int, default=160)
    sq.add_argument("--reference",
                    help="FASTA reference for reference-compressed CRAM "
                         "(the hadoopbam.cram.reference-source-path "
                         "analog): indexed by its .fai (built in one pass "
                         "and written beside it when absent) and "
                         "memory-mapped, so a scan reads the ranges its "
                         "slices cover, not the genome")
    sq.set_defaults(fn=cmd_seq_stats, uses_device=True)

    vst = sub.add_parser("vcf-stats",
                         help="variant counts, allele freq, call rates "
                              "on the mesh")
    vst.add_argument("path")
    vst.set_defaults(fn=cmd_vcf_stats, uses_device=True)

    vg = sub.add_parser("vcf-gwas",
                        help="structure-adjusted association of every "
                             "site against every trait: a device-resident "
                             "dosage matrix, GRM eigenvectors as "
                             "covariates, a score test from the matrix")
    vg.add_argument("path")
    vg.add_argument("--pheno", required=True, metavar="TRAITS.TSV",
                    help="header 'sample' + trait names, one row a "
                         "sample, no missing values")
    vg.set_defaults(fn=cmd_vcf_gwas, uses_device=True)

    so = sub.add_parser("sort", help="sort a BAM (external spill-merge)")
    so.add_argument("input")
    so.add_argument("output")
    so.add_argument("-n", "--by-name", action="store_true")
    so.add_argument("--run-records", type=int, default=None,
                    help="memory bound in records: per in-memory sort run "
                         "(spill-merge mode, default 1000000), or per "
                         "device per exchange round (--mesh: engages the "
                         "multi-round spill shuffle)")
    so.add_argument("--mesh", action="store_true",
                    help="bucketed sort over the device mesh (device key "
                         "extraction + all_to_all exchange; coordinate "
                         "order only; without --run-records the input "
                         "must fit host/device memory)")
    so.add_argument("--exchange", choices=("index", "bytes"), default=None,
                    help="mesh shuffle flavor: 'index' (keys only ride the "
                         "all_to_all; single-host) or 'bytes' (record bytes "
                         "ride it; required and default under "
                         "jax.distributed multi-host runs)")
    so.add_argument("--compress-level", type=int, default=None,
                    metavar="0-9",
                    help="BGZF deflate level for the output (default "
                         "config write_compress_level = 6; the "
                         "hbam.write-compress-level key)")
    so.add_argument("--no-write-index", action="store_true",
                    help="skip the BAI + splitting-index sidecars the "
                         "write path co-writes with coordinate-sorted "
                         "output (-n output is never indexed)")
    so.add_argument("--journal", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="crash-safe run (--mesh only): record job "
                         "identity + per-round spill commits to an "
                         "fsync'd journal (default PATH: "
                         "<output>.hbam-journal) so a killed run "
                         "resumes via `hbam resume` — spill mode "
                         "(--run-records) resumes at round grain, "
                         "resident modes at job grain")
    so.set_defaults(fn=cmd_sort, uses_device=False)

    cov = sub.add_parser("coverage",
                         help="per-base aligned depth over a region "
                              "(device cigar pileup)")
    cov.add_argument("input")
    cov.add_argument("region", help='samtools-style region, e.g. '
                                    '"chr20:1,000-2,000"')
    cov.add_argument("--max-cigar", type=int, default=64,
                     help="cigar ops per record tile (loud error if "
                          "exceeded)")
    cov.add_argument("--bedgraph", metavar="PATH",
                     help="write non-zero depth runs as bedGraph")
    cov.set_defaults(fn=cmd_coverage, uses_device=True)

    f = sub.add_parser("fixmate", help="fill mate fields on name-grouped BAM")
    f.add_argument("input")
    f.add_argument("output")
    f.add_argument("--compress-level", type=int, default=None,
                   metavar="0-9",
                   help="BGZF deflate level for the output (default "
                        "config write_compress_level = 6)")
    f.add_argument("--no-write-index", action="store_true",
                   help="skip the index sidecars the write path "
                        "co-writes (name-grouped output is rarely "
                        "coordinate-compatible; the sidecars are only "
                        "meaningful when it is)")
    f.set_defaults(fn=cmd_fixmate, uses_device=False)

    md = sub.add_parser(
        "mkdup",
        help="mark (or remove) duplicates, fused: read -> mesh sort "
             "exchange -> on-device signature markdup -> flag-patched "
             "indexed write, one pass over the records")
    md.add_argument("input")
    md.add_argument("output")
    md.add_argument("--remove-duplicates", action="store_true",
                    help="drop duplicate records instead of setting "
                         "their 0x400 flag")
    md.add_argument("--library-from", choices=("none", "rg"),
                    default="none",
                    help="library component of the duplicate signature: "
                         "'none' (one anonymous library) or 'rg' (join "
                         "each record's RG:Z tag to its @RG LB header "
                         "library)")
    md.add_argument("--run-records", type=int, default=None,
                    help="records per device per exchange round (the "
                         "spill shuffle's memory bound; default "
                         "1000000)")
    md.add_argument("--compress-level", type=int, default=None,
                    metavar="0-9",
                    help="BGZF deflate level for the output (default "
                         "config write_compress_level = 6)")
    md.add_argument("--no-write-index", action="store_true",
                    help="skip the BAI + splitting-index sidecars the "
                         "write path co-writes with the coordinate-"
                         "sorted output")
    md.add_argument("--journal", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="crash-safe run: record per-round spills, the "
                         "duplicate bitmap, and per-shard writes to an "
                         "fsync'd journal (default PATH: "
                         "<output>.hbam-journal) so a killed run "
                         "resumes via `hbam resume` at stage grain")
    md.set_defaults(fn=cmd_mkdup, uses_device=True)

    q = sub.add_parser("query",
                       help="batched random-access region queries via the "
                            "genomic index (.bai/.csi, .tbi, CRAM "
                            "containers); device interval predicate + "
                            "chunk cache")
    q.add_argument("path")
    q.add_argument("regions", nargs="+",
                   help='samtools-style regions, e.g. "chr20:1,000-2,000"')
    q.add_argument("-c", "--count", action="store_true",
                   help="print per-region match counts instead of records")
    q.add_argument("--deadline", type=float, default=None,
                   help="per-batch deadline in seconds (blown deadlines "
                        "raise the retryable TransientIOError)")
    q.add_argument("--metrics", action="store_true",
                   help="dump chunk-cache hit/miss stats and latency "
                        "percentiles to stderr")
    _add_obs_flags(q)
    q.set_defaults(fn=cmd_query, uses_device=True)

    sv = sub.add_parser("serve",
                        help="long-running multi-tenant region server: "
                             "JSONL requests on stdin (or --port TCP), "
                             "device-resident tile cache, per-tenant "
                             "quotas + priority classes, predictive "
                             "prefetch")
    sv.add_argument("--port", type=int, default=None,
                    help="listen on TCP PORT (0 = ephemeral) instead of "
                         "stdin/stdout JSONL")
    sv.add_argument("--host", default="127.0.0.1",
                    help="bind address for --port (default 127.0.0.1)")
    sv.add_argument("--deadline", type=float, default=None,
                    help="default per-request deadline in seconds, "
                         "anchored at enqueue (admission wait counts)")
    sv.add_argument("--tile-cache-bytes", type=int, default=None,
                    help="device-resident decoded-tile LRU budget "
                         "(default config.serve_tile_cache_bytes)")
    sv.add_argument("--no-prefetch", action="store_true",
                    help="disable predictive adjacent-window prefetch")
    sv.add_argument("--warm", metavar="PATH", action="append",
                    help="pre-resolve header+index of PATH at startup; "
                         "repeatable")
    sv.add_argument("--metrics", action="store_true",
                    help="dump tile/chunk/prefetch/tenant stats to "
                         "stderr at shutdown")
    sv.add_argument("--breaker-cooldown", type=float, default=None,
                    help="seconds an OPEN breaker (tenant / decode "
                         "plane / quarantine) waits before its "
                         "half-open re-probe (default "
                         "config.breaker_cooldown_s)")
    sv.add_argument("--flight-dir", metavar="DIR", default=None,
                    help="write flight-recorder incident dumps "
                         "(breaker trips, plane demotions, deadline "
                         "misses, serve errors) as redacted JSON here, "
                         "rotation-capped (config.flight_dump_cap); "
                         "without it the always-on ring is memory-only "
                         "and still served via {\"op\": \"health\"}")
    sv.add_argument("--replica-id", default=None, metavar="ID",
                    help="this replica's name in the fleet peer set "
                         "(enables fleet mode with --peers)")
    sv.add_argument("--peers", default=None,
                    metavar="ID=HOST:PORT,...",
                    help="static fleet roster (every replica, including "
                         "this one): rendezvous-hashed tile ownership, "
                         "heartbeat membership, hedged peer-fetch of "
                         "decoded tiles over the same TCP transport")
    sv.add_argument("--replication", type=int, default=None,
                    help="tile ownership replication factor R "
                         "(default config.fleet_replication)")
    _add_obs_flags(sv)
    sv.set_defaults(fn=cmd_serve, uses_device=True)

    mt = sub.add_parser("metrics",
                        help="render/merge metrics snapshots written by "
                             "--metrics-json (text, Prometheus "
                             "exposition, or JSON)")
    mt.add_argument("files", nargs="+",
                    help="snapshot JSON file(s); several merge like the "
                         "mesh-wide allgather")
    mt.add_argument("--format", choices=("text", "prometheus", "json"),
                    default="text")
    mt.set_defaults(fn=cmd_metrics, uses_device=False)

    ex = sub.add_parser(
        "explain",
        help="compile an op's plan IR and print it with the decode-"
             "plane decision (which plane, and why each rejected "
             "plane failed its gate)")
    ex.add_argument("op", choices=["flagstat", "seq-stats", "vcf-stats",
                                   "vcf-gwas", "query", "cohort",
                                   "serve-tile", "mkdup"])
    ex.add_argument("path", help="input file (BAM/VCF/BCF) or cohort "
                                 "manifest JSON")
    ex.add_argument("--region", default=None,
                    help="region for `explain query`/`explain "
                         "serve-tile` (resolved through the file's "
                         "genomic index into pinned chunks)")
    ex.add_argument("--intervals", default=None,
                    help="explain with hadoopbam.bam.intervals set "
                         "(gates fused streaming)")
    ex.add_argument("--inflate-backend", default=None,
                    choices=["auto", "native", "zlib"],
                    help="explain under this backend instead of the "
                         "config default")
    ex.add_argument("--skip-bad-spans", action="store_true",
                    help="explain with quarantine-and-skip on")
    ex.add_argument("--no-fused", action="store_true",
                    help="explain with the fused decode knob off")
    ex.add_argument("--json", action="store_true",
                    help="emit {plan, digest, decision} as JSON")
    ex.set_defaults(fn=cmd_explain, uses_device=True)

    ln = sub.add_parser("lint",
                        help="static analysis: trace safety (TS1xx), "
                             "collective lockstep (CL2xx), error taxonomy "
                             "(ET3xx), layout contracts (LC4xx), "
                             "observability discipline (OB6xx), serving "
                             "cache bounds (SV8xx), write-path atomicity "
                             "(WR10x), thread-safety/lock order "
                             "(TH1xx/LK2xx); exits non-zero on "
                             "unsuppressed findings")
    ln.add_argument("--root", default=None,
                    help="package directory to analyze")
    ln.add_argument("--only", action="append", metavar="ANALYZER",
                    help="run one analyzer (trace_safety, lockstep, "
                         "taxonomy, layout, feedpath, querycache, obs, "
                         "decodepath, servebounds, threadsafety, "
                         "writepath); repeatable")
    ln.add_argument("--baseline", default=None,
                    help="baseline file (default analysis/baseline.json)")
    ln.add_argument("--no-baseline", action="store_true")
    ln.add_argument("--update-baseline", action="store_true",
                    help="accept all current findings into the baseline")
    ln.add_argument("--show-suppressed", action="store_true")
    ln.add_argument("--format", choices=("text", "json", "sarif"),
                    default="text",
                    help="findings output format (json/sarif for CI "
                         "annotation; text stays byte-stable)")
    ln.add_argument("--no-cache", action="store_true",
                    help="ignore the lint findings cache")
    ln.set_defaults(fn=cmd_lint, uses_device=False)

    ch = sub.add_parser(
        "cohort",
        help="join a cohort manifest of single-sample VCF/BCF files on "
             "position and run the GWAS mesh drivers")
    ch.add_argument("manifest",
                    help='manifest JSON ({"samples": [{"id", "path"}, ...]}'
                         " or a bare path list)")
    ch.add_argument("--region", default=None,
                    help="report one chr[:start-end] slice of the joined "
                         "tensor instead of the whole cohort")
    ch.add_argument("--pheno", default=None, metavar="FILE",
                    help="phenotype file (one float per manifest sample, "
                         "manifest order; nan = missing) — enables the "
                         "score-test association column")
    ch.add_argument("--tsv", default=None, metavar="FILE",
                    help="write the per-variant stats table")
    ch.add_argument("--journal", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="crash-safe join: persist every joined chunk + "
                         "an fsync'd journal (default PATH: "
                         "<manifest>.hbam-journal); a killed join "
                         "resumes via `hbam resume`, replaying the "
                         "committed chunks instead of re-joining them")
    _add_obs_flags(ch)
    ch.set_defaults(fn=cmd_cohort, uses_device=True)

    rs = sub.add_parser(
        "resume",
        help="resume (or verify) a journaled job after a crash")
    rs.add_argument("journal", help="the job's .hbam-journal file")
    _add_obs_flags(rs)
    rs.set_defaults(fn=cmd_resume, uses_device=True)

    jb = sub.add_parser(
        "jobs", help="list job journals (kind, status, committed units)")
    jb.add_argument("dir", nargs="?", default=".",
                    help="directory to scan for *.hbam-journal files")
    jb.add_argument("--journal", dest="journals", action="append",
                    default=None, metavar="PATH",
                    help="inspect specific journal file(s) instead of "
                         "scanning a directory")
    jb.add_argument("--json", action="store_true",
                    help="one machine-readable JSON object per journal "
                         "(trace_id, resume_grain, units skipped/total) "
                         "— the parser `hbam top` and external "
                         "schedulers share")
    jb.set_defaults(fn=cmd_jobs, uses_device=False)

    tp = sub.add_parser(
        "top",
        help="live ops view of a running `hbam serve --port` process: "
             "per-tenant q/s + p50/p99, cache hit rates, pool "
             "occupancy, breaker + SLO burn state, job resume progress")
    tp.add_argument("--host", default="127.0.0.1")
    tp.add_argument("--port", type=int, default=None,
                    help="the serve process's TCP port")
    tp.add_argument("--endpoints", default=None,
                    metavar="HOST:PORT,...",
                    help="fleet view: poll N replicas and render one "
                         "row each (q/s, p50/p99, tile hit rate, peer "
                         "breaker states, degraded flag) plus "
                         "fleet-wide aggregates; DOWN rows for "
                         "unreachable replicas")
    tp.add_argument("--interval", type=float, default=2.0,
                    help="seconds between polls (default 2)")
    tp.add_argument("--iterations", type=int, default=0,
                    help="stop after N polls (0 = until ^C)")
    tp.add_argument("--once", action="store_true",
                    help="poll exactly once and exit (scripting shape)")
    tp.add_argument("--timeout", type=float, default=10.0,
                    help="per-poll socket timeout")
    tp.add_argument("--jobs-dir", default=None, metavar="DIR",
                    help="also render *.hbam-journal resume progress "
                         "from DIR (the `hbam jobs --json` document)")
    tp.set_defaults(fn=cmd_top, uses_device=False)

    fl = sub.add_parser(
        "fleet",
        help="one replica's fleet view: membership states, per-peer "
             "breaker states, hedge soft deadline, peer-fetch counters")
    fl.add_argument("--host", default="127.0.0.1")
    fl.add_argument("--port", type=int, required=True,
                    help="any fleet replica's TCP port")
    fl.add_argument("--timeout", type=float, default=10.0)
    fl.add_argument("--json", action="store_true",
                    help="emit the raw fleet states document")
    fl.set_defaults(fn=cmd_fleet, uses_device=False)

    vs = sub.add_parser("vcf-sort", help="sort a VCF/BCF by (contig, pos) "
                                         "(external spill-merge)")
    vs.add_argument("input")
    vs.add_argument("output")
    vs.add_argument("--run-records", type=int, default=1_000_000)
    vs.add_argument("--compress-level", type=int, default=None,
                    metavar="0-9",
                    help="BGZF deflate level for compressed output")
    vs.add_argument("--no-write-index", action="store_true",
                    help="skip the .tbi sidecar co-written with sorted "
                         "BCF output")
    vs.set_defaults(fn=cmd_vcf_sort, uses_device=False)
    return p


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    # the verb is the first positional (the top-level parser takes no
    # option but -h); named before parsing so the trace and the span
    # below cover the parser build too
    verb = next((a for a in argv if not a.startswith("-")), "?")
    # one TraceContext per CLI invocation: the verb is an entry point,
    # and every span / journal line / flight-ring entry the verb
    # produces carries this trace id (obs/context.py)
    from hadoop_bam_tpu.obs.context import trace_context
    from hadoop_bam_tpu.utils.metrics import METRICS
    # cli.main_wall: the whole invocation; less plan.execute_wall it is
    # the verb's time outside the plan (parser, header, plan, print)
    with trace_context(op=f"cli.{verb}"), \
            METRICS.span("cli.main_wall", verb=verb):
        args = build_parser().parse_args(argv)
        try:
            # device verbs only: pure-IO verbs must not pay jax
            # import/backend init (or grab the accelerator) at startup.
            # A device verb runs on the platform JAX gives it, or fails:
            # a silent CPU fallback is refused (utils/backend.py).
            if getattr(args, "uses_device", False) \
                    or getattr(args, "mesh", False):
                from hadoop_bam_tpu.utils import backend
                backend.enable_compile_cache()
                backend.require_backend()
            return args.fn(args)
        except (ValueError, OSError) as e:
            # covers the classified taxonomy too: PlanError is a
            # ValueError, TransientIOError (shed load / blown deadline)
            # an OSError
            from hadoop_bam_tpu.obs import flight
            flight.recorder().dump(f"cli_error:{verb}", error=str(e))
            print(f"error: {e}", file=sys.stderr)
            return 1


if __name__ == "__main__":
    sys.exit(main())
