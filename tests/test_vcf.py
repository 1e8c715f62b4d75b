"""VCF/BCF family tests: codecs, guesser, spans, writers, mergers.

Mirrors the reference's test strategy for test/TestVCFInputFormat.java,
test/TestVCFOutputFormat.java, test/TestVCFRoundTrip.java (SURVEY.md
section 4): round-trips through our own codecs plus every-byte-offset split
robustness — the union of all spans must yield each record exactly once no
matter where boundaries land.
"""
from __future__ import annotations

import io
import os
import random

import pytest

from hadoop_bam_tpu.config import HBamConfig
from hadoop_bam_tpu.api.dispatch import (
    VCFContainer, clear_sniff_caches, sniff_vcf_container,
)
from hadoop_bam_tpu.api.vcf_dataset import open_vcf
from hadoop_bam_tpu.api.writers import (
    BcfShardWriter, VcfShardWriter, open_vcf_writer,
)
from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.formats.bcf import BCFRecordCodec, encode_header
from hadoop_bam_tpu.formats.bcfio import BcfWriter, read_bcf, read_bcf_header, write_bcf
from hadoop_bam_tpu.formats.vcf import VCFHeader, VariantBatch, VcfRecord
from hadoop_bam_tpu.split.bcf_guesser import BCFSplitGuesser
from hadoop_bam_tpu.split.spans import FileByteSpan, FileVirtualSpan
from hadoop_bam_tpu.split.vcf_planners import (
    plan_bcf_spans, plan_bgzf_text_spans, read_bcf_span, read_bgzf_text_span,
)
from hadoop_bam_tpu.utils.mergers import merge_bcf_shards, merge_vcf_shards

HEADER_TEXT = """##fileformat=VCFv4.2
##contig=<ID=chr20,length=64444167>
##contig=<ID=chr21,length=46709983>
##FILTER=<ID=q10,Description="Quality below 10">
##INFO=<ID=DP,Number=1,Type=Integer,Description="Depth">
##INFO=<ID=AF,Number=A,Type=Float,Description="Allele freq">
##INFO=<ID=DB,Number=0,Type=Flag,Description="dbSNP membership">
##INFO=<ID=END,Number=1,Type=Integer,Description="End position">
##FORMAT=<ID=GT,Number=1,Type=String,Description="Genotype">
##FORMAT=<ID=DP,Number=1,Type=Integer,Description="Read depth">
##FORMAT=<ID=PL,Number=G,Type=Integer,Description="Phred likelihoods">
#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tS1\tS2
"""


def make_vcf_header() -> VCFHeader:
    return VCFHeader.from_text(HEADER_TEXT)


def make_variants(n: int, seed: int = 0):
    rng = random.Random(seed)
    recs = []
    pos = 0
    for i in range(n):
        pos += rng.randint(1, 500)
        chrom = "chr20" if pos % 3 else "chr21"
        ref = rng.choice(["A", "C", "G", "T", "AT", "GCC"])
        alts = tuple(rng.sample(["A", "C", "G", "T", "TT"],
                                rng.randint(1, 2)))
        alts = tuple(a for a in alts if a != ref) or ("T" if ref != "T" else "A",)
        gts = []
        for _ in range(2):
            a = rng.randint(0, len(alts))
            b = rng.randint(0, len(alts))
            dp = rng.randint(0, 90)
            gts.append(f"{a}/{b}:{dp}")
        recs.append(VcfRecord(
            chrom=chrom, pos=pos,
            id=f"rs{i}" if rng.random() < 0.3 else None,
            ref=ref, alts=alts,
            qual=round(rng.uniform(1, 100), 1) if rng.random() < 0.8 else None,
            filters=("PASS",) if rng.random() < 0.7 else ("q10",),
            info={"DP": str(rng.randint(1, 99)),
                  **({"DB": True} if rng.random() < 0.2 else {})},
            fmt=("GT", "DP"), genotypes=gts))
    return recs


@pytest.fixture(scope="module")
def vcf_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("vcf")
    header = make_vcf_header()
    recs = make_variants(400, seed=7)
    text_path = str(d / "t.vcf")
    with VcfShardWriter(text_path, header, write_header=True) as w:
        for r in recs:
            w.write_record(r)
    gz_path = str(d / "t.vcf.gz")
    # small blocks so splits land mid-stream often
    with open(gz_path, "wb") as f:
        bw = bgzf.BGZFWriter(f, level=5)
        bw.write(header.to_text().encode())
        for r in recs:
            bw.write((r.to_line() + "\n").encode())
            if bw.tell_voffset() & 0xFFFF > 1200:
                bw.flush()
        bw.close()
    bcf_path = str(d / "t.bcf")
    with BcfWriter(bcf_path, header, level=5) as w:
        for r in recs:
            w.write_record(r)
            if w._w.tell_voffset() & 0xFFFF > 1200:
                w._w.flush()  # small blocks so splits land mid-stream
    raw_bcf_path = str(d / "t_raw.bcf")
    with BcfWriter(raw_bcf_path, header, compress=False) as w:
        for r in recs:
            w.write_record(r)
    return {"dir": d, "header": header, "recs": recs,
            "vcf": text_path, "vcf_gz": gz_path, "bcf": bcf_path,
            "raw_bcf": raw_bcf_path}


# ---------------------------------------------------------------------------
# codec round-trips
# ---------------------------------------------------------------------------

def test_vcf_line_roundtrip(vcf_files):
    for r in vcf_files["recs"]:
        assert VcfRecord.from_line(r.to_line()).to_line() == r.to_line()


def test_bcf_record_roundtrip(vcf_files):
    codec = BCFRecordCodec(vcf_files["header"])
    for r in vcf_files["recs"][:100]:
        buf = codec.encode(r)
        r2, end = codec.decode(buf)
        assert end == len(buf)
        assert r2.to_line() == r.to_line()


def test_bcf_file_roundtrip(vcf_files):
    header, recs = read_bcf(vcf_files["bcf"])
    assert header.to_text() == vcf_files["header"].to_text()
    assert [r.to_line() for r in recs] == \
        [r.to_line() for r in vcf_files["recs"]]


def test_raw_bcf_file_roundtrip(vcf_files):
    _, recs = read_bcf(vcf_files["raw_bcf"])
    assert [r.to_line() for r in recs] == \
        [r.to_line() for r in vcf_files["recs"]]


def test_header_dictionaries():
    h = make_vcf_header()
    d = h.string_dictionary()
    assert d[0] == "PASS"
    assert set(["q10", "DP", "AF", "GT", "PL"]) <= set(d)
    assert h.contigs == ["chr20", "chr21"]
    assert h.samples == ["S1", "S2"]


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def test_sniff_containers(vcf_files):
    clear_sniff_caches()
    cfg = HBamConfig(vcf_trust_exts=False)  # force content sniffing
    assert sniff_vcf_container(vcf_files["vcf"], cfg) is VCFContainer.VCF
    assert sniff_vcf_container(vcf_files["vcf_gz"], cfg) is VCFContainer.VCF_BGZF
    assert sniff_vcf_container(vcf_files["bcf"], cfg) is VCFContainer.BCF
    assert sniff_vcf_container(vcf_files["raw_bcf"], cfg) is VCFContainer.BCF
    clear_sniff_caches()


# ---------------------------------------------------------------------------
# datasets: union-of-spans == whole file
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key", ["vcf", "vcf_gz", "bcf", "raw_bcf"])
@pytest.mark.parametrize("num_spans", [1, 3, 8])
def test_dataset_span_union(vcf_files, key, num_spans):
    clear_sniff_caches()
    ds = open_vcf(vcf_files[key], HBamConfig(vcf_trust_exts=False))
    got = [r.to_line() for r in ds.records(num_spans=num_spans)]
    want = [r.to_line() for r in vcf_files["recs"]]
    assert got == want


def test_dataset_checkpoint_resume(vcf_files):
    clear_sniff_caches()
    ds = open_vcf(vcf_files["bcf"])
    it = ds.records(num_spans=4)
    first = [next(it).to_line() for _ in range(3)]
    state = ds.state_dict()
    ds2 = open_vcf(vcf_files["bcf"])
    ds2.load_state_dict(state)
    got = first[:0]  # records already consumed inside span 0 are re-read:
    # resume is span-granular, like re-running a map task from its split start
    rest = [r.to_line() for r in ds2.records()]
    all_lines = [r.to_line() for r in vcf_files["recs"]]
    assert rest[-1] == all_lines[-1]
    assert set(rest) <= set(all_lines)


# ---------------------------------------------------------------------------
# split robustness: every-byte-offset guessing (THE critical property)
# ---------------------------------------------------------------------------

def test_bcf_guesser_every_offset(vcf_files):
    """From every byte offset, the guesser must find a true record boundary
    (or EOF) — and never a false positive that decodes garbage."""
    path = vcf_files["bcf"]
    header = vcf_files["header"]
    size = os.path.getsize(path)
    g = BCFSplitGuesser(path, header, is_bgzf=True)
    want = [r.to_line() for r in vcf_files["recs"]]
    # a sample of offsets incl. adversarial ones near block boundaries
    rng = random.Random(3)
    offsets = sorted({0, 1, size - 1, size // 2} |
                     {rng.randrange(size) for _ in range(40)})
    for off in offsets:
        v = g.guess_next_record_start(off)
        if v is None:
            continue
        span = FileVirtualSpan(path, v, size << 16)
        recs = read_bcf_span(path, span, header=header, is_bgzf=True)
        got = [r.to_line() for r in recs]
        # suffix property: records from the guessed point = tail of the file
        assert got == want[len(want) - len(got):]


def test_bcf_spans_every_boundary(vcf_files):
    """Union of spans yields every record exactly once for many span counts."""
    path = vcf_files["bcf"]
    want = [r.to_line() for r in vcf_files["recs"]]
    for num_spans in (2, 5, 13):
        spans = plan_bcf_spans(path, num_spans=num_spans)
        got = []
        for s in spans:
            got += [r.to_line() for r in
                    read_bcf_span(path, s, header=vcf_files["header"],
                                  is_bgzf=True)]
        assert got == want, f"num_spans={num_spans}"


def test_bgzf_text_spans_every_boundary(vcf_files):
    path = vcf_files["vcf_gz"]
    raw = open(path, "rb").read()
    want = [r.to_line() for r in vcf_files["recs"]]
    # adversarial: span boundaries at every block start +/- 1
    blocks = [b.coffset for b in bgzf.scan_blocks(raw)]
    size = len(raw)
    for num_spans in (2, 7):
        spans = plan_bgzf_text_spans(path, num_spans=num_spans)
        assert spans[0].start == 0 and spans[-1].end == size
        got = []
        for s in spans:
            text = read_bgzf_text_span(path, s).decode()
            got += [l for l in text.splitlines() if l and not l.startswith("#")]
        assert got == want, f"num_spans={num_spans}"
    # hand-crafted spans exactly on block boundaries
    mid = blocks[len(blocks) // 2]
    for cut in (mid, mid - 1, mid + 1):
        s1 = FileByteSpan(path, 0, cut)
        s2 = FileByteSpan(path, cut, size)
        # snap: s2 must begin at a block start; emulate planner snapping
        g_start = cut if cut in blocks else next(b for b in blocks if b > cut)
        s1 = FileByteSpan(path, 0, g_start)
        s2 = FileByteSpan(path, g_start, size)
        text = (read_bgzf_text_span(path, s1) +
                read_bgzf_text_span(path, s2)).decode()
        got = [l for l in text.splitlines() if l and not l.startswith("#")]
        assert got == want, f"cut={cut}"


def _blocked_vcf_gz(path, payload: int, n_lines: int = 60,
                    n_meta: int = 120):
    """A ``.vcf.gz`` of fixed ``payload``-byte BGZF blocks (as bgzip cuts a
    stream, regardless of lines) whose header is longer than a block and
    whose lines are longer than a third of one.  Returns (text, lines,
    block count)."""
    rng = random.Random(payload)
    meta = ["##fileformat=VCFv4.2", "##contig=<ID=chr20,length=64444167>"]
    meta += [f"##INFO=<ID=K{i},Number=1,Type=Integer,Description=\"key {i} "
             f"of a long header\">" for i in range(n_meta)]
    meta.append("#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO")
    lines = [f"chr20\t{100 + 7 * i}\t.\tA\tG\t40\tPASS\t"
             + ";".join(f"K{k}={rng.randrange(10 ** 6)}"
                        for k in range(rng.randrange(payload // 30,
                                                     payload // 10)))
             for i in range(n_lines)]
    text = ("\n".join(meta + lines) + "\n").encode()
    header_len = len(("\n".join(meta) + "\n").encode())
    assert header_len > payload
    assert min(len(ln) for ln in lines) > payload // 3
    blocks = [bgzf.deflate_block(text[lo:lo + payload])
              for lo in range(0, len(text), payload)]
    with open(path, "wb") as f:
        f.write(b"".join(blocks) + bgzf.EOF_BLOCK)
    return text, lines, len(blocks)


@pytest.mark.parametrize("payload", [1500, 1511, 4096])
def test_bgzf_text_span_ownership_at_every_span_count(payload, tmp_path,
                                                      monkeypatch):
    """A line belongs to the span in which its first byte lies: for every
    span count from 1 to 3 x the block count, the spans' texts are each
    line of the file exactly once and in order (the first span owns the
    header lines), and the native read is the Python read, byte for
    byte."""
    from hadoop_bam_tpu.split import vcf_planners
    from hadoop_bam_tpu.utils import native
    from hadoop_bam_tpu.utils.metrics import base_metrics

    path = str(tmp_path / "blocked.vcf.gz")
    text, lines, n_blocks = _blocked_vcf_gz(path, payload)
    if not native.available():
        pytest.skip("no native library on this host")
    for num_spans in range(1, 3 * n_blocks + 1):
        spans = plan_bgzf_text_spans(path, num_spans=num_spans)
        assert spans[0].start == 0
        assert spans[-1].end == os.path.getsize(path)
        base_metrics().reset()
        fast = [read_bgzf_text_span(path, s) for s in spans]
        c = base_metrics().snapshot()["counters"]
        assert c["vcf.native_read_spans"] == len(spans)
        assert "vcf.python_read_spans" not in c
        assert b"".join(fast) == text, num_spans
        assert all(t == b"" or t.endswith(b"\n") for t in fast)
        with monkeypatch.context() as m:
            m.setattr(vcf_planners.native, "available", lambda: False)
            slow = [read_bgzf_text_span(path, s) for s in spans]
        assert slow == fast, num_spans
    got = [ln for ln in b"".join(fast).decode().splitlines()
           if not ln.startswith("#")]
    assert got == lines


def test_bgzf_text_span_last_line_without_newline_and_long_lines(tmp_path):
    """A file whose last line has no newline, and a line longer than
    several blocks (its tail is finished block by block)."""
    from hadoop_bam_tpu.split import vcf_planners

    head = "##fileformat=VCFv4.2\n#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n"
    body = ["chr20\t%d\t.\tA\tG\t40\tPASS\t%s" % (10 + i, "X" * w)
            for i, w in enumerate([50, 9000, 40, 70, 12000, 30])]
    text = (head + "\n".join(body)).encode()           # no final newline
    path = str(tmp_path / "long.vcf.gz")
    with open(path, "wb") as f:
        f.write(b"".join(bgzf.deflate_block(text[lo:lo + 2000])
                         for lo in range(0, len(text), 2000))
                + bgzf.EOF_BLOCK)
    for num_spans in (1, 2, 3, 5, 8, 13, 21):
        spans = plan_bgzf_text_spans(path, num_spans=num_spans)
        fast = [read_bgzf_text_span(path, s) for s in spans]
        assert b"".join(fast) == text, num_spans
        real = vcf_planners.native.available
        vcf_planners.native.available = lambda: False
        try:
            assert [read_bgzf_text_span(path, s) for s in spans] == fast
        finally:
            vcf_planners.native.available = real


def test_bgzf_vcf_header_of_several_blocks_is_read_once(tmp_path,
                                                        monkeypatch):
    """``VcfDataset._read_header`` on a BGZF VCF: each of the header's
    blocks is loaded once (it used to seek to 0 and re-read a chunk)."""
    clear_sniff_caches()
    path = str(tmp_path / "h.vcf.gz")
    # a header of ~75 KB: the header reader asks for 64 KiB chunks
    text, lines, n_blocks = _blocked_vcf_gz(path, 1500, n_lines=20,
                                            n_meta=1200)
    header_len = text.index(b"chr20\t100\t")
    assert header_len > (1 << 16)
    header_blocks = -(-header_len // 1500)
    loads = []
    real = bgzf.BGZFReader._load_block

    def counting(self, coffset):
        loads.append(coffset)
        return real(self, coffset)

    monkeypatch.setattr(bgzf.BGZFReader, "_load_block", counting)
    ds = open_vcf(path)
    assert len(ds.header.to_text().encode()) == header_len
    # every block once, and no further than a chunk (64 KiB = 44 blocks)
    # past the header's end
    assert len(loads) == len(set(loads)), "a block was re-read"
    assert header_blocks <= len(loads) <= header_blocks + 46
    assert [r.pos for r in ds.records()][:3] == [100, 107, 114]


# ---------------------------------------------------------------------------
# writers + mergers
# ---------------------------------------------------------------------------

def test_vcf_output_format_dispatch(vcf_files, tmp_path):
    header = vcf_files["header"]
    w = open_vcf_writer(str(tmp_path / "o.bcf"), header)
    assert isinstance(w, BcfShardWriter)
    w.close()
    w = open_vcf_writer(str(tmp_path / "o.vcf"), header)
    assert isinstance(w, VcfShardWriter)
    w.close()
    cfg = HBamConfig(vcf_output_format="BCF")
    w = open_vcf_writer(str(tmp_path / "part-00000"), header, cfg)
    assert isinstance(w, BcfShardWriter)
    w.close()


def test_merge_vcf_shards(vcf_files, tmp_path):
    header = vcf_files["header"]
    recs = vcf_files["recs"]
    cfg = HBamConfig(write_header=False, write_terminator=False)
    paths = []
    for i, lo in enumerate(range(0, len(recs), 150)):
        p = str(tmp_path / f"part-{i:05d}")
        with VcfShardWriter(p, header, cfg) as w:
            for r in recs[lo:lo + 150]:
                w.write_record(r)
        paths.append(p)
    out = str(tmp_path / "merged.vcf")
    merge_vcf_shards(paths, out, header)
    ds = open_vcf(out, HBamConfig(vcf_trust_exts=True))
    assert [r.to_line() for r in ds.records(num_spans=2)] == \
        [r.to_line() for r in recs]


def test_merge_bcf_shards(vcf_files, tmp_path):
    header = vcf_files["header"]
    recs = vcf_files["recs"]
    cfg = HBamConfig(write_header=False, write_terminator=False)
    paths = []
    for i, lo in enumerate(range(0, len(recs), 170)):
        p = str(tmp_path / f"part-{i:05d}.bcfshard")
        with BcfShardWriter(p, header, cfg) as w:
            for r in recs[lo:lo + 170]:
                w.write_record(r)
        paths.append(p)
    out = str(tmp_path / "merged.bcf")
    merge_bcf_shards(paths, out, header)
    hdr, got = read_bcf(out)
    assert [r.to_line() for r in got] == [r.to_line() for r in recs]
    # merged file ends with the EOF terminator [SPEC]
    assert open(out, "rb").read().endswith(bgzf.EOF_BLOCK)


# ---------------------------------------------------------------------------
# SoA batch
# ---------------------------------------------------------------------------

def test_variant_batch_columns(vcf_files):
    header = vcf_files["header"]
    recs = vcf_files["recs"][:50]
    b = VariantBatch(recs, header)
    assert len(b) == 50
    for i, r in enumerate(recs):
        assert b.pos[i] == r.pos
        assert b.chrom[i] == header.contig_index(r.chrom)
        assert b.n_allele[i] == r.n_allele


def test_plain_gzip_vcf_fallback(tmp_path):
    """A .vcf.gz that is plain gzip (NOT BGZF) reads as one whole-file
    span — the BGZFEnhancedGzipCodec fallback behavior — and stats work."""
    import gzip

    from hadoop_bam_tpu.api.dispatch import (
        VCFContainer, sniff_vcf_container, _vcf_cache,
    )
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf

    header_text = ("##fileformat=VCFv4.2\n"
                   "##contig=<ID=c1,length=1000>\n"
                   "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
    body = "".join(f"c1\t{10+i}\t.\tA\tG\t40\tPASS\t.\n" for i in range(300))
    path = str(tmp_path / "p.vcf.gz")
    with open(path, "wb") as f:
        f.write(gzip.compress((header_text + body).encode()))
    _vcf_cache.clear()
    assert sniff_vcf_container(path) is VCFContainer.VCF_GZIP
    ds = open_vcf(path)
    recs = list(ds.records())
    assert len(recs) == 300 and recs[0].pos == 10 and recs[-1].pos == 309
    stats = ds.variant_stats()
    assert stats["n_variants"] == 300 and stats["n_snp"] == 300


def test_tabix_query(tmp_path):
    """Build .tbi over a sorted BGZF VCF; region queries return exactly the
    overlapping records, reading only indexed chunk ranges."""
    import random

    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.formats import bgzf
    from hadoop_bam_tpu.split.tabix import TabixIndex, write_tabix

    header_text = ("##fileformat=VCFv4.2\n"
                   "##contig=<ID=c1,length=2000000>\n"
                   "##contig=<ID=c2,length=2000000>\n"
                   "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
    rng = random.Random(29)
    recs = []
    for chrom in ("c1", "c2"):
        poss = sorted(rng.sample(range(1, 1999000), 4000))
        for p in poss:
            recs.append((chrom, p))
    lines = [f"{c}\t{p}\t.\tA\tG\t30\tPASS\t." for c, p in recs]
    path = str(tmp_path / "t.vcf.gz")
    open(path, "wb").write(
        bgzf.compress_bytes((header_text + "\n".join(lines) + "\n")
                            .encode()))
    out = write_tabix(path)
    idx = TabixIndex.from_bytes(open(out, "rb").read())
    assert idx.names == ["c1", "c2"] and idx.fmt == 2

    ds = open_vcf(path)
    for region, want in (
        ("c1:500000-700000",
         [(c, p) for c, p in recs if c == "c1" and 500000 <= p <= 700000]),
        ("c2:1-1000",
         [(c, p) for c, p in recs if c == "c2" and p <= 1000]),
        ("c1", [(c, p) for c, p in recs if c == "c1"]),
    ):
        got = [(r.chrom, r.pos) for r in ds.query(region)]
        assert got == want, (region, len(got), len(want))
    assert list(ds.query("c9:1-100")) == []
