"""Driver level: the native inflate plane against the zlib plane.

Every scan driver runs under ``inflate_backend="native"`` (what "auto"
resolves to, and what every benchmark cell runs) and under ``"zlib"``
(the portable plane the demotion ladder falls to); both must give the
plain reference's answer, the same error class on bad bytes, and the
same outcome under CRC damage.  The variant path's portable plane is the
Python block reader a span falls to without the native library.

``test_scan_matches_reference_across_bgzf_levels`` runs the verbs
through ``tools.cli.main`` on inputs whose BGZF blocks were written at
level 0 (stored: what ``samtools view -u`` pipes), 1 and 9."""
import contextlib
import dataclasses
import io
import random

import numpy as np
import pytest

from hadoop_bam_tpu.config import DEFAULT_CONFIG
from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.formats.bamio import read_bam_header, write_bam
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.errors import CORRUPT, classify_error

from fixtures import make_header, make_records

PLANES = ("native", "zlib")


def _cfg(**kw):
    base = dict(retry_backoff_base_s=0.001, retry_backoff_max_s=0.002)
    base.update(kw)
    return dataclasses.replace(DEFAULT_CONFIG, **base)


# ---------------------------------------------------------------------------
# plain references (per record, independent of the code under test)
# ---------------------------------------------------------------------------

_GC = frozenset("GCS")


def _ref_flagstat(records):
    keys = ("total", "primary", "secondary", "supplementary", "duplicates",
            "primary_duplicates", "mapped", "primary_mapped", "paired",
            "read1", "read2", "properly_paired",
            "with_itself_and_mate_mapped", "singletons",
            "mate_on_different_chr", "mate_on_different_chr_mapq5")
    out = dict.fromkeys(keys, 0)
    for r in records:
        f = r.flag
        primary = not f & 0x900
        mapped, paired = not f & 0x4, bool(f & 0x1)
        both = paired and mapped and not f & 0x8
        diff = both and r.rnext not in ("=", "*", r.rname)
        for k, hit in (
                ("total", True), ("primary", primary),
                ("secondary", f & 0x100), ("supplementary", f & 0x800),
                ("duplicates", f & 0x400),
                ("primary_duplicates", primary and f & 0x400),
                ("mapped", mapped), ("primary_mapped", primary and mapped),
                ("paired", paired), ("read1", paired and f & 0x40),
                ("read2", paired and f & 0x80),
                ("properly_paired", paired and f & 0x2 and mapped),
                ("with_itself_and_mate_mapped", both),
                ("singletons", paired and mapped and f & 0x8),
                ("mate_on_different_chr", diff),
                ("mate_on_different_chr_mapq5", diff and r.mapq >= 5)):
            out[k] += bool(hit)
    return out


def _ref_seq_stats(records):
    hist = {}
    gc = qual = 0.0
    for r in records:
        gc += sum(b in _GC for b in r.seq) / len(r.seq)
        qual += sum(ord(c) - 33 for c in r.qual) / len(r.qual)
        for b in r.seq:
            hist[b] = hist.get(b, 0) + 1
    n = len(records)
    return {"n_reads": n, "mean_gc": gc / n, "mean_qual": qual / n,
            "hist": hist}


def _ref_depth(records, rname, lo1, hi1):
    depth = np.zeros(hi1 - lo1 + 1, np.int64)
    for r in records:
        if r.flag & 0x4 or r.rname != rname:
            continue
        assert r.cigar == f"{len(r.seq)}M"     # what make_records writes
        s, e = max(r.pos, lo1), min(r.pos + len(r.seq) - 1, hi1)
        if s <= e:
            depth[s - lo1:e - lo1 + 1] += 1
    return depth


def _sorted_records(header, n, seed):
    def key(r):
        return (header.ref_names.index(r.rname) if r.rname != "*"
                else 1 << 30, r.pos)
    return sorted(make_records(header, n, seed=seed), key=key)


_VCF_HDR = (
    "##fileformat=VCFv4.2\n"
    "##contig=<ID=chr20,length=64444167>\n"
    '##FILTER=<ID=q10,Description="Quality below 10">\n'
    '##INFO=<ID=DP,Number=1,Type=Integer,Description="Depth">\n'
    '##FORMAT=<ID=GT,Number=1,Type=String,Description="GT">\n'
    "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\ts0\ts1\ts2\n")
_GTS = ("0/0", "0/1", "1/1", "./.")


def _write_bcf(path, n, seed, level=None):
    """A seeded three-sample BCF through the repo's writer, and its plain
    stats: SNPs and deletions, PASS and q10, GT in 0/0 0/1 1/1 ./."""
    from hadoop_bam_tpu.api.writers import open_vcf_writer
    from hadoop_bam_tpu.formats.vcf import VCFHeader, VcfRecord

    rng = random.Random(seed)
    cfg = DEFAULT_CONFIG if level is None else dataclasses.replace(
        DEFAULT_CONFIG, write_compress_level=level)
    snp = n_pass = 0
    afs, called = [], np.zeros(3)
    with open_vcf_writer(path, VCFHeader.from_text(_VCF_HDR), cfg) as w:
        for i in range(n):
            is_snp, passes = rng.random() < 0.8, rng.random() < 0.9
            gt = [rng.randrange(4) for _ in range(3)]
            ref, alt = ("A", "G") if is_snp else ("AT", "A")
            w.write_record(VcfRecord.from_line(
                f"chr20\t{1000 + 7 * i}\t.\t{ref}\t{alt}\t{rng.randint(1, 99)}"
                f"\t{'PASS' if passes else 'q10'}\tDP={rng.randint(1, 60)}"
                f"\tGT\t" + "\t".join(_GTS[g] for g in gt)))
            snp += is_snp
            n_pass += passes
            dose = [g for g in gt if g != 3]
            called += [g != 3 for g in gt]
            if dose:
                afs.append(sum(dose) / (2.0 * len(dose)))
    return {"n_variants": n, "n_snp": snp, "n_pass": n_pass,
            "n_af": len(afs), "mean_af": float(np.mean(afs)),
            "sample_callrate": called / n}


def _assert_variant_stats(got, want):
    for k in ("n_variants", "n_snp", "n_pass", "n_af"):
        assert got[k] == want[k], k
    assert abs(got["mean_af"] - want["mean_af"]) < 1e-6
    np.testing.assert_allclose(got["sample_callrate"][:3],
                               want["sample_callrate"], atol=1e-9)


# ---------------------------------------------------------------------------
# fixtures and drivers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bam(tmp_path_factory):
    header = make_header(2)
    records = _sorted_records(header, 4000, seed=23)
    path = str(tmp_path_factory.mktemp("planes") / "p.bam")
    write_bam(path, header, records)
    return path, header, records


@pytest.fixture(scope="module")
def bcf(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("planes") / "p.bcf")
    return path, _write_bcf(path, 900, seed=7)


def _flagstat(path, **kw):
    from hadoop_bam_tpu.parallel.pipeline import flagstat_file
    return flagstat_file(path, **kw)


def _seq_stats(path, config):
    from hadoop_bam_tpu.parallel.pipeline import seq_stats_file
    return seq_stats_file(path, config=config)


def _variant_stats(path, config):
    from hadoop_bam_tpu.parallel.variant_pipeline import variant_stats_file
    return variant_stats_file(path, config=config)


def _outcome(fn):
    try:
        return ("ok", fn())
    except Exception as e:  # noqa: BLE001 — taxonomy-class comparison
        return ("err", classify_error(e))


def _flip_largest_footer(path, out):
    """Flip a CRC footer byte of the largest DATA block (block 0 holds
    the format header); no inflated byte changes."""
    from hadoop_bam_tpu.ops.inflate import block_table

    raw = open(path, "rb").read()
    table = block_table(raw)
    idx = int(np.argmax(table["cdata_len"]))
    bad = bytearray(raw)
    bad[int(table["cdata_off"][idx] + table["cdata_len"][idx])] ^= 0xFF
    with open(out, "wb") as f:
        f.write(bytes(bad))
    return out


# ---------------------------------------------------------------------------
# native against zlib, driver by driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("check_crc", [False, True])
@pytest.mark.parametrize("plane", PLANES)
def test_flagstat_explicit_spans_and_crc(bam, plane, check_crc):
    """A pinned multi-span plan cuts a record at every span boundary;
    each plane gives the reference's counts, with and without the CRC
    check, fused sweep on and off."""
    from hadoop_bam_tpu.split.planners import plan_spans_cached

    path, header, records = bam
    want = _ref_flagstat(records)
    spans = plan_spans_cached(path, header, DEFAULT_CONFIG, num_spans=6)
    assert len(spans) > 1
    for fused in (True, False):
        cfg = _cfg(inflate_backend=plane, check_crc=check_crc,
                   use_fused_decode=fused)
        got = _flagstat(path, config=cfg, spans=spans, header=header)
        assert got == want, fused


def test_flagstat_corrupt_chain_same_class(tmp_path):
    """A corrupted record chain (absurd block_size mid-span) raises the
    CORRUPT taxonomy class on BOTH planes, and the ladder charges
    neither: the bytes are bad, not a plane."""
    from hadoop_bam_tpu import resilience
    from hadoop_bam_tpu.ops.inflate import inflate_span, walk_records

    header = make_header()
    path = str(tmp_path / "chain.bam")
    write_bam(path, header, make_records(header, 800, seed=3))
    data, _ub = inflate_span(open(path, "rb").read())
    _hdr, voff = read_bam_header(path)
    offs, _tail = walk_records(data, start=voff & 0xFFFF)
    victim = int(offs[len(offs) // 2])
    bad = bytearray(data.tobytes())
    bad[victim:victim + 4] = (5).to_bytes(4, "little")   # block_size 5
    sink = io.BytesIO()
    w = bgzf.BGZFWriter(sink)
    w.write(bytes(bad))
    w.close()
    corrupt_path = str(tmp_path / "corrupt.bam")
    with open(corrupt_path, "wb") as f:
        f.write(sink.getvalue())
    for plane in PLANES:
        got = _outcome(lambda: _flagstat(
            corrupt_path, config=_cfg(inflate_backend=plane)))
        assert got == ("err", CORRUPT), plane
    assert resilience.registry().states() == {}


def test_seq_stats_equal(bam):
    path, _header, records = bam
    want = _ref_seq_stats(records)
    got = {p: _seq_stats(path, _cfg(inflate_backend=p)) for p in PLANES}
    for plane, g in got.items():
        assert g["n_reads"] == want["n_reads"], plane
        assert abs(g["mean_gc"] - want["mean_gc"]) < 1e-5, plane
        assert abs(g["mean_qual"] - want["mean_qual"]) < 1e-3, plane
        codes = "=ACMGRSVTWYHKDBN"
        assert {codes[i]: int(c) for i, c in enumerate(g["base_hist"])
                if c} == want["hist"], plane
    # the same rows reach the same kernel: not merely close, identical
    assert got["native"]["mean_gc"] == got["zlib"]["mean_gc"]
    assert got["native"]["mean_qual"] == got["zlib"]["mean_qual"]


def test_variant_stats_equal(bcf, monkeypatch):
    """The leased native span read (PR 29), the configured zlib backend
    and the Python block reader a span falls to without the native
    library all give the reference's stats."""
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    path, want = bcf
    with MetricsContext() as m:
        for plane in PLANES:
            _assert_variant_stats(
                _variant_stats(path, _cfg(inflate_backend=plane)), want)
    if native.available():
        assert m.get("vcf.native_read_spans") > 0
        assert m.get("vcf.python_read_spans") == 0
    monkeypatch.setattr(native, "available", lambda: False)
    with MetricsContext() as m:
        _assert_variant_stats(_variant_stats(path, _cfg()), want)
    assert m.get("vcf.python_read_spans") > 0
    assert m.get("vcf.native_read_spans") == 0


def _plain(outcome):
    """An ("ok", stats) outcome with its arrays as lists, comparable."""
    if outcome[0] == "err":
        return outcome
    return ("ok", {k: np.asarray(v).tolist() for k, v in outcome[1].items()})


@pytest.mark.parametrize("family", ["payload", "variant"])
def test_crc_flip_same_outcome(family, bam, bcf, tmp_path):
    """CRC-footer damage (data bytes intact) keeps the planes in
    lockstep: the BAM payload route honours ``check_crc`` on both
    (invisible off, CORRUPT on); the variant route's split guesser
    always verifies, so the native span read and the Python block
    reader both refuse the file either way."""
    # an arm: (config overrides, run without the native library)
    if family == "payload":
        path, run = bam[0], _seq_stats
        arms = [(dict(inflate_backend=p), False) for p in PLANES]
    else:
        path, run = bcf[0], _variant_stats
        arms = [({}, False), ({}, True)]
    bad = _flip_largest_footer(path, str(tmp_path / f"crc_{family}"))

    def outcomes(**extra):
        out = []
        for overrides, no_native in arms:
            with pytest.MonkeyPatch.context() as mp:
                if no_native:
                    mp.setattr(native, "available", lambda: False)
                out.append(_plain(_outcome(
                    lambda: run(bad, _cfg(**overrides, **extra)))))
        return out

    a, b = outcomes()
    assert a == b
    if family == "payload":
        assert a == _plain(("ok", run(path, _cfg())))
    else:
        assert a == ("err", CORRUPT)
    a, b = outcomes(check_crc=True)
    assert a == b == ("err", CORRUPT)


# ---------------------------------------------------------------------------
# the verbs, on inputs written at each BGZF level
# ---------------------------------------------------------------------------

def _run_cli(argv) -> str:
    from hadoop_bam_tpu.tools.cli import main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert main(list(argv)) == 0, argv
    return out.getvalue()


def _fields(out: str):
    return {ln.split("\t")[0]: ln.split("\t")[1:]
            for ln in out.strip().splitlines()}


@pytest.mark.parametrize("level", [0, 1, 9])
@pytest.mark.parametrize("verb", ["summarize", "seq-stats", "coverage",
                                  "vcf-stats"])
def test_scan_matches_reference_across_bgzf_levels(verb, level, tmp_path):
    from hadoop_bam_tpu.ops.inflate import block_table

    if verb == "vcf-stats":
        path = str(tmp_path / f"l{level}.bcf")
        want = _write_bcf(path, 600, seed=40 + level, level=level)
    else:
        header = make_header(2)
        records = _sorted_records(header, 1500, seed=50 + level)
        path = str(tmp_path / f"l{level}.bam")
        write_bam(path, header, records, level=level)
    raw = open(path, "rb").read()
    table = block_table(raw)
    # the level took: stored blocks are larger than their payload
    stored = bool((table["cdata_len"][:-1] > table["isize"][:-1]).all())
    assert stored == (level == 0)

    if verb == "summarize":
        got = [int(ln.split(" ", 1)[0])
               for ln in _run_cli(["summarize", path]).strip().splitlines()]
        assert got == list(_ref_flagstat(records).values())
    elif verb == "seq-stats":
        f = _fields(_run_cli(["seq-stats", path]))
        want = _ref_seq_stats(records)
        assert int(f["reads"][0]) == want["n_reads"]
        assert abs(float(f["mean_gc"][0]) - want["mean_gc"]) < 5e-6
        assert abs(float(f["mean_qual"][0]) - want["mean_qual"]) < 2e-3
        assert {k[5:]: int(v[0]) for k, v in f.items()
                if k.startswith("base_")} == want["hist"]
    elif verb == "coverage":
        _run_cli(["index", "--flavor", "bai", path])
        rname, lo, hi = header.ref_names[0], 1, 400_000
        f = _fields(_run_cli(["coverage", path, f"{rname}:{lo}-{hi}"]))
        depth = _ref_depth(records, rname, lo, hi)
        assert int(f["bases"][0]) == hi - lo + 1
        assert int(f["covered"][0]) == int((depth > 0).sum())
        assert int(f["max_depth"][0]) == int(depth.max())
        assert abs(float(f["mean_depth"][0]) - depth.mean()) < 1e-4
    else:
        f = _fields(_run_cli(["vcf-stats", path]))
        assert int(f["variants"][0]) == want["n_variants"]
        assert int(f["snps"][0]) == want["n_snp"]
        assert int(f["pass"][0]) == want["n_pass"]
        assert abs(float(f["mean_af"][0]) - want["mean_af"]) < 2e-6
        for i in range(3):
            assert abs(float(f[f"callrate_{i}"][0])
                       - want["sample_callrate"][i]) < 1e-4
