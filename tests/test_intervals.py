"""Interval filtering + keep-paired-reads-together split option.

Reference parity: hadoopbam.bam.intervals (hb/BAMInputFormat.java 7.7+) and
hadoopbam.bam.keep-paired-reads-together (7.9+)."""
import numpy as np
import pytest

from hadoop_bam_tpu.config import HBamConfig
from hadoop_bam_tpu.formats.bam import SAMHeader
from hadoop_bam_tpu.formats.bamio import BamWriter
from hadoop_bam_tpu.formats.sam import SamRecord
from hadoop_bam_tpu.split.intervals import (
    Interval, IntervalError, parse_interval, parse_intervals,
)

from fixtures import make_header, make_records


@pytest.mark.parametrize("text,expect", [
    ("chr1", Interval("chr1", 1, (1 << 31) - 1)),
    ("chr1:500", Interval("chr1", 500, 500)),
    ("chr1:500-", Interval("chr1", 500, (1 << 31) - 1)),
    ("chr1:500-900", Interval("chr1", 500, 900)),
    ("chr1:1,000-2,000", Interval("chr1", 1000, 2000)),
])
def test_parse_interval(text, expect):
    assert parse_interval(text) == expect


def test_parse_interval_errors():
    with pytest.raises(IntervalError):
        parse_interval("chr1:9-3")
    assert len(parse_intervals("chr1:1-10, chr2 ,chr3:5")) == 3


def test_parse_intervals_resolves_colon_contigs():
    # GRCh38-style contig containing ':' resolves verbatim when known
    ivs = parse_intervals("HLA-A*01:01", ref_names=["chr1", "HLA-A*01:01"])
    assert ivs == [Interval("HLA-A*01:01")]


def test_unknown_contig_raises(tmp_path):
    import hadoop_bam_tpu as hb
    header = make_header()
    path = _write(tmp_path, header, make_records(header, 5, seed=1),
                  "unk.bam")
    ds = hb.open_bam(path, HBamConfig(bam_intervals="chrX:1-100"))
    with pytest.raises(IntervalError):
        list(ds.batches())


def test_flagstat_respects_intervals(tmp_path):
    import hadoop_bam_tpu as hb
    header = make_header()
    recs = make_records(header, 200, seed=33)
    path = _write(tmp_path, header, recs, "fs.bam")
    ds = hb.open_bam(path, HBamConfig(bam_intervals="chr2"))
    stats = ds.flagstat()
    expect = sum(1 for r in recs if r.rname == "chr2")
    assert stats["total"] == expect


def _write(tmp_path, header, recs, name="t.bam"):
    path = str(tmp_path / name)
    with BamWriter(path, header) as w:
        for r in recs:
            w.write_sam_record(r)
    return path


def test_interval_filtering_exact_overlap(tmp_path):
    import hadoop_bam_tpu as hb
    header = make_header()
    # reads with known spans: pos 100 len 50 (ends 149), a deletion-extended
    # one, a soft-clipped one whose span is shorter than its seq
    recs = [
        SamRecord("a", 0, "chr1", 100, 60, "50M", "*", 0, 0, "A" * 50, "I" * 50),
        SamRecord("b", 0, "chr1", 200, 60, "10M30D10M", "*", 0, 0,
                  "A" * 20, "I" * 20),                     # span 200..249
        SamRecord("c", 0, "chr1", 300, 60, "40S10M", "*", 0, 0,
                  "A" * 50, "I" * 50),                     # span 300..309
        SamRecord("d", 0, "chr2", 100, 60, "50M", "*", 0, 0, "A" * 50, "I" * 50),
    ]
    path = _write(tmp_path, header, recs)

    def names(intervals):
        cfg = HBamConfig(bam_intervals=intervals)
        ds = hb.open_bam(path, cfg)
        return [b.read_name(i) for b in ds.batches() for i in range(len(b))]

    assert names("chr1:140-199") == ["a"]          # overlaps a's tail only
    assert names("chr1:150-199") == []             # gap between a and b
    assert names("chr1:249-249") == ["b"]          # deletion extends b's span
    assert names("chr1:310-400") == []             # soft clip does not
    assert names("chr1:309-400") == ["c"]
    assert names("chr2") == ["d"]
    assert sorted(names("chr1:100-300,chr2")) == ["a", "b", "c", "d"]


def test_interval_filtering_bulk_matches_bruteforce(tmp_path):
    import hadoop_bam_tpu as hb
    from hadoop_bam_tpu.tools.cli import _alen
    header = make_header()
    recs = make_records(header, 400, seed=21)
    path = _write(tmp_path, header, recs)
    iv = "chr1:200000-600000,chr3:1-50000"
    cfg = HBamConfig(bam_intervals=iv)
    got = {b.read_name(i) for b in hb.open_bam(path, cfg).batches()
           for i in range(len(b))}
    expect = set()
    for r in recs:
        end = r.pos + max(1, _alen(r)) - 1
        if r.rname == "chr1" and r.pos <= 600000 and end >= 200000:
            expect.add(r.qname)
        if r.rname == "chr3" and r.pos <= 50000:
            expect.add(r.qname)
    assert got == expect


def test_keep_paired_reads_together(tmp_path):
    import hadoop_bam_tpu as hb
    header = make_header()
    # queryname-grouped BAM: every name appears exactly twice, adjacent
    recs = []
    for i in range(600):
        for j, flag in enumerate((99, 147)):
            l = 100
            recs.append(SamRecord(
                f"pair{i:05d}", flag, "chr1", 1000 + i, 60, f"{l}M",
                "=", 1000 + i, l, "A" * l, "I" * l))
    path = _write(tmp_path, header, recs)
    cfg = HBamConfig(keep_paired_reads_together=True, split_size=1 << 16)
    ds = hb.open_bam(path, cfg)
    spans = ds.spans(num_spans=7)
    assert len(spans) >= 2
    all_names = []
    for span in spans:
        b = ds.read_span(span)
        names = [b.read_name(i) for i in range(len(b))]
        all_names.extend(names)
        # no span starts in the middle of a name group
        counts = {}
        for n in names:
            counts[n] = counts.get(n, 0) + 1
        # every name in this span appears exactly twice (whole pairs only)
        assert all(c == 2 for c in counts.values()), (span, counts)
    assert all_names == [r.qname for r in recs]


def test_reference_span_column(tmp_path):
    header = make_header()
    recs = [
        SamRecord("a", 0, "chr1", 10, 60, "10M5I10M", "*", 0, 0,
                  "A" * 25, "I" * 25),
        SamRecord("b", 0, "chr1", 10, 60, "5S10M100N10M", "*", 0, 0,
                  "A" * 25, "I" * 25),
        SamRecord("c", 4, "*", 0, 0, "*", "*", 0, 0, "A" * 30, "I" * 30),
    ]
    import hadoop_bam_tpu as hb
    path = _write(tmp_path, header, recs)
    ds = hb.open_bam(path)
    b = next(iter(ds.batches()))
    assert list(b.reference_span()) == [20, 120, 30]
    sub = b.select(np.array([2, 0]))
    assert [sub.read_name(i) for i in range(len(sub))] == ["c", "a"]


def test_mesh_flagstat_honors_intervals(tmp_path):
    """flagstat/seq_stats through the mesh path count only interval-
    overlapping records, matching the host-filtered oracle."""
    import dataclasses

    from fixtures import make_header, make_records
    from hadoop_bam_tpu.api.dataset import open_bam
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.formats.bamio import BamWriter
    from hadoop_bam_tpu.ops.flagstat import flagstat_from_batch

    header = make_header()
    records = make_records(header, 3000, seed=31)
    path = str(tmp_path / "iv.bam")
    with BamWriter(path, header) as w:
        for r in records:
            w.write_sam_record(r)
    iv = f"{header.ref_names[0]}:1000-40000"
    cfg = dataclasses.replace(DEFAULT_CONFIG, bam_intervals=iv)
    ds = open_bam(path, cfg)
    stats = ds.flagstat()

    # oracle: host batch filter over the same spans
    plain = open_bam(path)
    expect = {}
    for span in plain.spans():
        batch = ds.read_span(span)  # read_span applies the interval filter
        flagstat_from_batch(batch, expect)
    assert 0 < stats["total"] < len(records)
    assert stats["total"] == expect["total"]
    assert stats["mapped"] == expect["mapped"]

    sstats = ds.seq_stats()
    assert sstats["n_reads"] == stats["total"]


def _sorted_bam(tmp_path, n=4000, seed=17):
    from fixtures import make_header, make_records
    from hadoop_bam_tpu.formats.bamio import BamWriter

    header = make_header()
    records = make_records(header, n, seed=seed)
    rid = {name: i for i, name in enumerate(header.ref_names)}
    records.sort(key=lambda r: (rid.get(r.rname, 1 << 30), r.pos))
    path = str(tmp_path / "sorted.bam")
    with BamWriter(path, header) as w:
        for r in records:
            w.write_sam_record(r)
    return path, header, records


def test_bai_round_trip_and_query(tmp_path):
    from hadoop_bam_tpu.split.bai import (
        BaiIndex, build_bai, reg2bin, reg2bins,
    )

    # spec arithmetic sanity
    assert reg2bin(0, 1) == 4681
    assert reg2bin(0, 1 << 29) == 0
    assert 4681 in reg2bins(0, 100)
    assert 0 in reg2bins(0, 100)

    path, header, records = _sorted_bam(tmp_path)
    idx = build_bai(path)
    back = BaiIndex.from_bytes(idx.to_bytes())
    assert len(back.refs) == len(header.ref_names)
    ranges = back.query(0, 0, 1 << 29)
    assert ranges and ranges[0][0] < ranges[-1][1]
    # a region beyond all data yields nothing
    assert back.query(0, (1 << 28), (1 << 28) + 100) == []


def test_bai_chunk_ends_are_block_aligned(tmp_path):
    """Chunk END voffsets must carry real block-boundary coffsets: the
    old final-record fallback packed (coffset+1, 0) — one BYTE past the
    block start — which BGZFReader-based chunk reads tolerated by
    accident but block-table consumers (plan_interval_spans ->
    coverage's raw span fetch) died on mid-block with 'truncated BGZF
    header'."""
    from hadoop_bam_tpu.formats import bgzf
    from hadoop_bam_tpu.split.bai import build_bai, plan_interval_spans
    from hadoop_bam_tpu.split.intervals import resolve_interval
    from hadoop_bam_tpu.utils.seekable import as_byte_source

    path, header, _records = _sorted_bam(tmp_path)
    idx = build_bai(path)
    src = as_byte_source(path)

    def at_block_boundary(coffset):
        if coffset >= src.size:
            return True
        bgzf.parse_block_header(src.pread(coffset, 1 << 16), 0)
        return True

    n_chunks = 0
    for ref in idx.refs:
        for chunks in ref.bins.values():
            for beg, end in chunks:
                n_chunks += 1
                assert at_block_boundary(beg >> 16)
                assert at_block_boundary(end >> 16)
    assert n_chunks > 0

    # the exact failing composition: interval spans from the BAI feed
    # the raw-fetch + block-table path (what coverage_file does)
    from hadoop_bam_tpu.ops import inflate as inflate_ops

    iv = resolve_interval(f"{header.ref_names[0]}:1-100000000",
                          header.ref_names)
    spans = plan_interval_spans(path, [iv], header, bai=idx)
    assert spans
    for span in spans:
        raw, _end_block, _next_c, lease = inflate_ops.fetch_span_raw(
            src, span)
        table = inflate_ops.block_table(raw)   # raises on mid-block ends
        lease.release()
        assert int(table["isize"].sum()) > 0
    src.close()


def test_bai_split_trimming_matches_full_scan(tmp_path):
    import dataclasses

    from hadoop_bam_tpu.api.dataset import open_bam
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.split.bai import write_bai

    path, header, records = _sorted_bam(tmp_path)
    iv = f"{header.ref_names[0]}:5000-20000"
    cfg = dataclasses.replace(DEFAULT_CONFIG, bam_intervals=iv)

    # full-scan (no .bai yet): plans over the whole file + row filter
    full = open_bam(path, cfg).flagstat()

    write_bai(path)
    ds = open_bam(path, cfg)
    spans = ds.spans()
    import os
    assert sum(s.compressed_size for s in spans) < os.path.getsize(path), \
        "BAI trimming should read less than the whole file"
    trimmed = ds.flagstat()
    assert trimmed == full
    assert 0 < trimmed["total"] < len(records)

    # seq stats agree too
    assert ds.seq_stats()["n_reads"] == trimmed["total"]


def test_csi_round_trip_and_query_matches_bai(tmp_path):
    """CSI round-trips and answers interval queries like the BAI it was
    derived from; split trimming works through a .csi sidecar alone."""
    import dataclasses
    import os

    from hadoop_bam_tpu.api.dataset import open_bam
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.split.bai import (
        CsiIndex, build_bai, csi_reg2bins, reg2bins,
    )

    # at 14/5 geometry CSI bins == BAI bins
    assert csi_reg2bins(5000, 20000, 14, 5) == sorted(reg2bins(5000, 20000))

    path, header, records = _sorted_bam(tmp_path, n=3000, seed=19)
    bai = build_bai(path)
    csi = CsiIndex.from_bai(bai)
    back = CsiIndex.from_bytes(csi.to_bytes())
    assert back.min_shift == 14 and back.depth == 5
    for beg, end in ((0, 30000), (5000, 20000), (100000, 200000)):
        assert back.query(0, beg, end) == bai.query(0, beg, end) or \
            back.query(0, beg, end)  # CSI lacks the linear-index clip, so
        # its ranges may start earlier; they must still COVER the BAI's
        b_r, c_r = bai.query(0, beg, end), back.query(0, beg, end)
        if b_r:
            assert c_r and c_r[0][0] <= b_r[0][0] and \
                c_r[-1][1] >= b_r[-1][1]

    # adversarial loffset case: a long record in an ancestor bin overlaps
    # a leaf bin whose own chunks start later; with an unset linear window
    # the leaf's loffset must NOT prune the ancestor's chunk
    from hadoop_bam_tpu.split.bai import BaiIndex, RefIndex
    adv = BaiIndex(refs=[RefIndex(
        bins={73: [(100, 200)],          # record A: pos 20000-140000
              585: [(200, 300)]},        # record B: pos 35000-50000
        # linear windows 0..4 unset (no record STARTS there after A),
        # window 1 holds A's start
        linear=[0, 100, 100, 100, 100, 100])])
    adv_csi = CsiIndex.from_bai(adv)
    got = adv_csi.query(0, 81920, 81921)     # window 5, only A overlaps
    assert got and got[0][0] <= 100, got     # A's chunk must survive

    # full-scan oracle BEFORE any sidecar exists
    iv = f"{header.ref_names[0]}:5000-20000"
    cfg = dataclasses.replace(DEFAULT_CONFIG, bam_intervals=iv)
    full = open_bam(path, cfg).flagstat()
    assert 0 < full["total"] < len(records)

    # interval trimming via .csi only (no .bai written)
    open(path + ".csi", "wb").write(csi.to_bytes())
    ds = open_bam(path, cfg)
    spans = ds.spans()
    assert sum(s.compressed_size for s in spans) < os.path.getsize(path)
    assert ds.flagstat() == full


def test_resolve_interval_colon_contigs():
    """samtools-style resolution: verbatim contig wins; else longest
    known contig prefix + range; else plain grammar."""
    from hadoop_bam_tpu.split.intervals import Interval, resolve_interval
    refs = ["chr1", "HLA-A*01:01", "HLA-A*01:01:02"]
    assert resolve_interval("HLA-A*01:01", refs) == Interval("HLA-A*01:01")
    got = resolve_interval("HLA-A*01:01:5-10", refs)
    assert got == Interval("HLA-A*01:01", 5, 10)
    # longest known prefix wins over a shorter one
    got = resolve_interval("HLA-A*01:01:02:7", refs)
    assert got.rname == "HLA-A*01:01:02" and got.start == got.end == 7
    assert resolve_interval("chr1:1,000-2,000", refs) == \
        Interval("chr1", 1000, 2000)
    # unknown names fall back to the plain grammar
    assert resolve_interval("chr9:5-6", refs) == Interval("chr9", 5, 6)


def test_resolve_interval_error_names_user_region():
    from hadoop_bam_tpu.split.intervals import IntervalError, resolve_interval

    with pytest.raises(IntervalError) as ei:
        resolve_interval("chr1:bogus-range", ref_names=["chr1"])
    msg = str(ei.value)
    assert "chr1:bogus-range" in msg and "'x:" not in msg
