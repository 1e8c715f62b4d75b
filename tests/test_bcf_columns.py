"""Columnar BCF decode parity: formats/bcf_columns.py vs the record
codec and the record-serial scanner, plus corruption fuzz (the columnar
path must raise on malformed input, never mis-decode silently).

Quick selection: ``pytest -m bcf``; the suite is part of tier-1.
"""
import random
import struct

import numpy as np
import pytest

from hadoop_bam_tpu.formats.bcf import (
    BCFError, BCFRecordCodec, T_INT8, T_INT16, T_INT32, scan_variant_columns,
)
from hadoop_bam_tpu.formats.bcf_columns import (
    STAT_KEYS, decode_bcf_columns, frame_record_starts, stat_columns,
)
from hadoop_bam_tpu.formats.vcf import VariantBatch, VCFHeader, VcfRecord

pytestmark = pytest.mark.bcf

N_SAMPLES = 4
HDR = (
    "##fileformat=VCFv4.2\n"
    "##contig=<ID=c1,length=1000000>\n"
    "##contig=<ID=c2,length=500000>\n"
    '##FILTER=<ID=q10,Description="x">\n'
    '##FILTER=<ID=s50,Description="x">\n'
    '##INFO=<ID=DP,Number=1,Type=Integer,Description="x">\n'
    '##INFO=<ID=AF,Number=A,Type=Float,Description="x">\n'
    '##INFO=<ID=NM,Number=1,Type=String,Description="x">\n'
    '##INFO=<ID=DB,Number=0,Type=Flag,Description="x">\n'
    '##INFO=<ID=END,Number=1,Type=Integer,Description="x">\n'
    '##FORMAT=<ID=GT,Number=1,Type=String,Description="x">\n'
    '##FORMAT=<ID=DP,Number=1,Type=Integer,Description="x">\n'
    '##FORMAT=<ID=AD,Number=R,Type=Integer,Description="x">\n'
    '##FORMAT=<ID=GL,Number=G,Type=Float,Description="x">\n'
    '##FORMAT=<ID=FT,Number=1,Type=String,Description="x">\n'
    "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\t"
    + "\t".join(f"s{i}" for i in range(N_SAMPLES)) + "\n")

# every typed-value type (int8/int16/int32/float/char/flag), missing
# values at every position, multi-allelic, END/rlen, extended (>14)
# counts via long strings, mixed ploidy, phased-missing, wide GT, a
# record with no genotype block, and GT in a non-leading FORMAT slot
LINES = [
    # plain SNP; int8-range INFO; full genotypes
    "c1\t100\trs1\tA\tC\t30.5\tPASS\tDP=8;AF=0.25\tGT:DP\t"
    "0/1:3\t1|1:7\t0/0:0\t./.:.",
    # multi-allelic SNP, non-PASS filter, flag INFO, >14-char string
    # (extended char count), int16 INFO
    "c1\t200\t.\tA\tC,G,T\t.\tq10\tDB;NM=averylongstringvalue0123456789;"
    "DP=4000\tGT\t1/2\t0|3\t2\t.",
    # long REF (not a SNP), END-driven rlen, float FORMAT with missing,
    # int32 INFO
    "c2\t300\t.\tACGTACGTACGTACGTACGT\tA\t0\t.\tEND=500;DP=3000000\t"
    "GT:GL\t0/0:-1.5,0,-2\t0/1:.\t1/1:0,0,0\t0/0:.",
    # symbolic-ish ALT (indel), mixed ploidy, negative int16 INFO
    "c2\t400\t.\tG\tGTT\t12\tPASS\tDP=-40000\tGT\t0|0\t0/1/1\t.\t0",
    # phased-missing alleles, multi-filter (not PASS), AD vector
    "c2\t500\t.\tT\tA\t1e6\tq10;s50\tAF=0.5,0.25\tGT:AD\t"
    "0|.\t./0\t1/.\t.|1",
    # no genotype block at all
    "c1\t600\t.\tC\tG\t9\tPASS\tDP=1\t",
    # GT not in the leading FORMAT slot + char FORMAT field
    "c1\t700\t.\tG\tT\t5\tPASS\tDP=2\tDP:GT:FT\t1:0/1:ok\t"
    "2:1/1:no\t.:./.:x\t3:0|1:y",
]


def _header():
    return VCFHeader.from_text(HDR)


def _wide_lines():
    """>63 ALTs force int16 GT vectors (value (70+1)<<1 > int8 max)."""
    alts = ",".join("ACGT"[i % 4] * (i // 4 + 2) for i in range(70))
    return [
        f"c1\t100\t.\tA\t{alts}\t30\tPASS\t.\tGT\t0/70\t70/70\t0/0\t./.",
        f"c1\t200\t.\tA\t{alts}\t30\tPASS\t.\tGT\t0/.\t./0\t1/.\t0|70",
    ]


def _encode(lines, header=None):
    header = header or _header()
    codec = BCFRecordCodec(header)
    recs = [VcfRecord.from_line(ln.rstrip("\t")) for ln in lines]
    buf = b"".join(codec.encode(r) for r in recs)
    return header, codec, recs, buf


@pytest.mark.parametrize("lines", [LINES, _wide_lines(),
                                   LINES + _wide_lines()])
def test_columns_match_record_scanner(lines):
    """STAT_KEYS columns == scan_variant_columns, column for column."""
    header, _, _, buf = _encode(lines)
    cols = decode_bcf_columns(buf, header, 8)
    assert cols is not None
    scan = scan_variant_columns(buf, header, 8)
    for k in STAT_KEYS:
        np.testing.assert_array_equal(cols[k], scan[k], err_msg=k)
        assert cols[k].dtype == scan[k].dtype, k


def test_extended_columns_match_record_codec():
    """rlen/qual/n_allele/n_fmt == the VariantBatch view of the decoded
    records (incl. the INFO/END-driven rlen)."""
    header, codec, recs, buf = _encode(LINES)
    cols = decode_bcf_columns(buf, header, 8)
    decoded = []
    off = 0
    while off < len(buf):
        r, off = codec.decode(buf, off)
        decoded.append(r)
    vb = VariantBatch(decoded, header)
    np.testing.assert_array_equal(cols["chrom"], vb.chrom)
    np.testing.assert_array_equal(cols["pos"], vb.pos)
    np.testing.assert_array_equal(cols["rlen"], vb.rlen)
    np.testing.assert_array_equal(cols["n_allele"], vb.n_allele)
    np.testing.assert_array_equal(np.isnan(cols["qual"]),
                                  np.isnan(vb.qual))
    m = ~np.isnan(vb.qual)
    np.testing.assert_allclose(cols["qual"][m], vb.qual[m])
    np.testing.assert_array_equal(
        cols["n_fmt"], [len(r.fmt) for r in decoded])
    assert cols["rlen"][2] == 500 - 300 + 1            # END semantics


def test_dosage_matches_variant_batch_oracle():
    """GT-leading records: dosage == VariantBatch.dosage_matrix (the
    pre-columnar oracle), padding columns stay -1."""
    gt_first = [ln for ln in LINES if "\tGT" in ln and "DP:GT" not in ln]
    header, codec, recs, buf = _encode(gt_first)
    cols = decode_bcf_columns(buf, header, 8)
    vb = VariantBatch(recs, header)
    np.testing.assert_array_equal(cols["dosage"][:, :N_SAMPLES],
                                  vb.dosage_matrix())
    assert (cols["dosage"][:, N_SAMPLES:] == -1).all()


def test_frame_starts_and_span_reader_agree(tmp_path):
    """read_bcf_span_frames' free framing == frame_record_starts."""
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.api.writers import open_vcf_writer
    from hadoop_bam_tpu.split.vcf_planners import read_bcf_span_frames

    header, _, recs, buf = _encode(LINES)
    np.testing.assert_array_equal(
        frame_record_starts(buf),
        np.cumsum([0] + [len(BCFRecordCodec(header).encode(r))
                         for r in recs])[:-1])
    path = str(tmp_path / "frames.bcf")
    with open_vcf_writer(path, header) as w:
        for r in recs:
            w.write_record(r)
    ds = open_vcf(path)
    total = 0
    for span in ds.spans(2):
        raw, starts = read_bcf_span_frames(path, span, ds._is_bgzf_bcf)
        np.testing.assert_array_equal(starts, frame_record_starts(raw))
        total += starts.size
    assert total == len(recs)


def test_empty_buffer():
    cols = decode_bcf_columns(b"", _header(), 8)
    assert cols["chrom"].size == 0
    assert cols["dosage"].shape == (0, 8)


# ---------------------------------------------------------------------------
# the native GT -> dosage kernel against its two oracles
# ---------------------------------------------------------------------------

_GT_MISS = {T_INT8: -128, T_INT16: -32768, T_INT32: -2147483648}
_GT_FMT = {T_INT8: "<b", T_INT16: "<h", T_INT32: "<i"}
# the widest allele index a width holds: ((a + 1) << 1) | 1 under its
# sentinels (int8 stops at 62, so a site with 64 alleles widens to int16)
_GT_AMAX = {T_INT8: 62, T_INT16: 16000, T_INT32: 1 << 20}
GT_PAD = 8                      # samples_pad of these spans; n_sample 5


def _raw_record(pos, typ=None, ploidy=0, gt=None, n_sample=5):
    """One BCF record built byte by byte so that the GT width is the
    test's choice (the codec always takes the narrowest): biallelic SNP,
    PASS, no INFO, FORMAT GT of ``typ`` x ``ploidy`` holding the
    [n_sample, ploidy] values ``gt`` — or no FORMAT at all."""
    from hadoop_bam_tpu.formats.bcf import (
        _descriptor, encode_typed_ints, encode_typed_string,
    )
    n_fmt = 0 if gt is None else 1
    shared = struct.pack("<iiifHHI", 0, pos, 1, 30.0, 0, 2,
                         (n_fmt << 24) | (n_sample if n_fmt else 0))
    shared += _descriptor(0, 7)                     # ID: empty string
    shared += encode_typed_string("A") + encode_typed_string("C")
    shared += encode_typed_ints([0])                # FILTER PASS
    indiv = b""
    if gt is not None:
        gt_key = _header().string_dictionary().index("GT")
        indiv = encode_typed_ints([gt_key]) + _descriptor(ploidy, typ)
        indiv += b"".join(struct.pack(_GT_FMT[typ], int(v))
                          for v in np.asarray(gt).reshape(-1))
    return struct.pack("<II", len(shared), len(indiv)) + shared + indiv


def _called(rng, typ, ploidy, ns, amax=3):
    """[ns, ploidy] genotype values, every allele called (0..amax),
    phased or not at random."""
    a = rng.integers(0, amax + 1, (ns, ploidy))
    return ((a + 1) << 1) | rng.integers(0, 2, (ns, ploidy))


def _gt_case(case, typ, ploidy, rng, ns=5):
    """The records of one case: a list of ``_raw_record`` keywords."""
    miss, eov = _GT_MISS[typ], _GT_MISS[typ] + 1
    recs = []
    for _ in range(6):
        g = _called(rng, typ, ploidy, ns)
        if case == "nocall":
            g[0, :] = 0                             # './.'
            g[1, :] = miss                          # typed MISSING
            g[2, rng.integers(ploidy)] = 0          # '0/.'
        elif case == "half_missing":
            g[0, -1] = 1                            # '0|.'
            g[1, 0] = 1                             # '.|0' (phase bit set)
            g[3, :] = 1
        elif case == "eov_padded":
            for s in range(ns):                     # s present, rest padded
                g[s, min(s, ploidy):] = eov         # sample 0: none present
        elif case == "big_allele":
            g = _called(rng, typ, ploidy, ns, _GT_AMAX[typ])
            g[0, :] = ((_GT_AMAX[typ] + 1) << 1) | 1
        recs.append(dict(typ=typ, ploidy=ploidy, gt=g, n_sample=ns))
        if case == "no_gt_rows":
            recs.append(dict())
        elif case == "two_groups":
            typ2 = T_INT16 if typ != T_INT16 else T_INT32
            recs.append(dict(typ=typ2, ploidy=ploidy + 1, n_sample=3,
                             gt=_called(rng, typ2, ploidy + 1, 3)))
    return recs


def _three_ways(recs, monkeypatch):
    """A span through the native kernel, the NumPy twin and the
    record-serial scanner; returns the native columns after pinning all
    three to each other, and checks which path counted the records."""
    from hadoop_bam_tpu.utils import native
    from hadoop_bam_tpu.utils.metrics import base_metrics

    assert native.available(), native.build_info()["error"]
    header = _header()
    buf = b"".join(_raw_record(100 + i, **kw) for i, kw in enumerate(recs))
    n_gt = sum(1 for kw in recs if kw)
    base_metrics().reset()
    fast = decode_bcf_columns(buf, header, GT_PAD)
    c = base_metrics().snapshot()["counters"]
    assert c.get("vcf.gt_native_records", 0) == n_gt
    assert "vcf.gt_numpy_records" not in c
    scan = scan_variant_columns(buf, header, GT_PAD)
    with monkeypatch.context() as m:
        m.setattr(native, "load", lambda: None)
        base_metrics().reset()
        slow = decode_bcf_columns(buf, header, GT_PAD)
        c = base_metrics().snapshot()["counters"]
    assert c.get("vcf.gt_numpy_records", 0) == n_gt
    assert "vcf.gt_native_records" not in c
    assert fast is not None and slow is not None
    for k in fast:
        np.testing.assert_array_equal(fast[k], slow[k], err_msg=k)
        assert fast[k].dtype == slow[k].dtype, k
    for k in STAT_KEYS:
        np.testing.assert_array_equal(fast[k], scan[k], err_msg=k)
    assert fast["dosage"].tobytes() == scan["dosage"].tobytes()
    return fast


GT_WIDTHS = pytest.mark.parametrize("typ", [T_INT8, T_INT16, T_INT32],
                                    ids=["int8", "int16", "int32"])
GT_CASES = ("called", "nocall", "half_missing", "eov_padded", "big_allele",
            "no_gt_rows", "two_groups")


@pytest.mark.parametrize("case", GT_CASES)
@pytest.mark.parametrize("ploidy", [1, 2, 3, 8])
@GT_WIDTHS
def test_native_gt_dosage_equals_both_oracles(typ, ploidy, case,
                                              monkeypatch):
    """Width x ploidy x genotype shape: the native kernel's dosage is
    the NumPy twin's and the record scanner's, byte for byte."""
    rng = np.random.default_rng(1000 * typ + 10 * ploidy
                                + GT_CASES.index(case))
    recs = _gt_case(case, typ, ploidy, rng)
    fast = _three_ways(recs, monkeypatch)
    d = fast["dosage"]
    assert (d[:, 5:] == -1).all()                   # n_sample < samples_pad
    if case == "called":
        assert (d[:, :5] >= 0).all()
    elif case == "nocall":
        assert (d[:, :3] == -1).all() and (d[:, 3:5] >= 0).all()
    elif case == "half_missing":
        assert (d[:, [0, 1, 3]] == -1).all()
    elif case == "eov_padded":
        assert (d[:, 0] == -1).all() and (d[:, 1:5] >= 0).all()
    elif case == "big_allele":
        assert (d[:, 0] == ploidy).all()
    elif case == "no_gt_rows":
        assert (d[1::2] == -1).all() and (d[0::2, :5] >= 0).all()
    else:
        assert (d[1::2, 3:] == -1).all() and (d[1::2, :3] >= 0).all()


@GT_WIDTHS
def test_native_gt_dosage_clamps_a_256_ploid_at_127(typ, monkeypatch):
    """Ploidy 256 (the guard's edge, an extended-count descriptor), every
    allele ALT: the count is held wider than int8 and clamped to 127; a
    sample with 127 ALTs and one with a missing entry sit beside it."""
    g = np.full((5, 256), (2 << 1) | 1)
    g[1, 127:] = 2                                  # 127 ALT, 129 REF
    g[2, 255] = _GT_MISS[typ]
    g[3, 100:] = _GT_MISS[typ] + 1                  # 100 ALT then EOV
    fast = _three_ways([dict(typ=typ, ploidy=256, gt=g)] * 3, monkeypatch)
    np.testing.assert_array_equal(
        fast["dosage"][0], [127, 127, -1, 100, 127, -1, -1, -1])


@GT_WIDTHS
@pytest.mark.parametrize("ploidy", [1, 2, 5])
def test_native_gt_kernel_over_every_value_near_the_sentinels(typ, ploidy):
    """The kernel alone against the NumPy twin on vectors longer than a
    SIMD register, at odd offsets: every int8 value (int16 / int32: every
    value beside the sentinels, zero and the extremes) in every ploidy
    slot."""
    from hadoop_bam_tpu.formats.bcf_columns import _GT_DTYPES, _gt_group_dosage
    from hadoop_bam_tpu.utils import native

    dt = _GT_DTYPES[typ]
    info = np.iinfo(dt)
    vals = np.unique(np.concatenate([
        np.arange(info.min, info.min + 130), np.arange(-3, 130),
        np.arange(info.max - 3, info.max + 1)]).clip(info.min, info.max))
    rng = np.random.default_rng(typ * 7 + ploidy)
    ns = 2504
    g = rng.choice(vals, (3, ns, ploidy))
    g[0, :vals.size, 0] = vals                      # each value, slot 0
    g[1, :vals.size, -1] = vals                     # and the last slot
    rows = np.array([2, 0, 4], np.int64)
    parts, offs, at = [], [], 0
    for r in range(3):                              # odd, unaligned offsets
        pad = b"\x5a" * (2 * r + 1)
        offs.append(at + len(pad))
        parts.append(pad + g[r].astype(dt).tobytes())
        at += len(parts[-1])
    b = np.frombuffer(b"".join(parts), np.uint8)
    offs = np.asarray(offs, np.int64)
    want = np.full((5, ns + 7), -1, np.int8)
    got = want.copy()
    _gt_group_dosage(b, rows, offs, typ, ploidy, ns, want)
    native.bcf_gt_dosage(b, rows, offs, typ, ploidy, ns, got)
    assert got.tobytes() == want.tobytes()
    assert (got[[1, 3]] == -1).all() and (got[:, ns:] == -1).all()


def test_native_gt_wrapper_refuses_what_lies_outside_its_buffers():
    """An offset, an extent or a row outside the span / the matrix is a
    BCFError from the kernel's own check, and nothing is written."""
    from hadoop_bam_tpu.utils import native

    b = np.zeros(100, np.uint8)
    rows = np.arange(2, dtype=np.int64)
    for offs, ns, typ, ploidy, rws in (
            ([0, 96], 5, T_INT8, 1, rows),          # 96 + 5 > 100
            ([0, -1], 5, T_INT8, 1, rows),
            ([0, 101], 0, T_INT8, 1, rows),
            ([0, 1 << 62], 5, T_INT32, 2, rows),
            ([0, 0], 5, T_INT8, 1, [0, 2]),         # row past the matrix
            ([0, 0], 5, T_INT8, 1, [-1, 0]),
            ([0, 0], 9, T_INT8, 1, rows),           # wider than the matrix
            ([0, 0], 5, 5, 1, rows),                # a float is no GT type
            ([0, 0], 5, T_INT8, -1, rows)):
        out = np.full((2, 8), 7, np.int8)
        with pytest.raises(BCFError):
            native.bcf_gt_dosage(b, np.asarray(rws, np.int64),
                                 np.asarray(offs, np.int64), typ, ploidy,
                                 ns, out)
        assert (out == 7).all()
    out = np.full((2, 8), 7, np.int8)               # the edge itself fits
    native.bcf_gt_dosage(b, rows, np.array([0, 95], np.int64), T_INT8, 1, 5,
                         out)
    assert (out[:, :5] == -1).all() and (out[:, 5:] == 7).all()
    native.bcf_gt_dosage(b, rows, np.array([0, 100], np.int64), T_INT8, 0, 5,
                         out)                       # ploidy 0: none present
    assert (out[:, :5] == -1).all()


# ---------------------------------------------------------------------------
# corruption fuzz: raise, never mis-decode
# ---------------------------------------------------------------------------

@pytest.fixture(params=["native", "numpy"])
def gt_path(request, monkeypatch):
    """Both GT -> dosage paths: the native kernel, and the NumPy twin a
    host without the library runs."""
    from hadoop_bam_tpu.utils import native
    if request.param == "numpy":
        monkeypatch.setattr(native, "load", lambda: None)
    else:
        assert native.available(), native.build_info()["error"]
    return request.param


def test_truncation_always_raises(gt_path):
    """Every cut that is not a record boundary must raise BCFError."""
    header, _, _, buf = _encode(LINES)
    bounds = set(frame_record_starts(buf).tolist()) | {len(buf)}
    step = max(1, len(buf) // 400)      # dense but bounded fuzz
    for cut in range(1, len(buf), step):
        if cut in bounds:
            continue
        with pytest.raises(BCFError):
            decode_bcf_columns(buf[:cut], header, 8)


def test_corrupt_lengths_and_type_codes_raise(gt_path):
    header, codec, recs, buf = _encode(LINES)
    starts = frame_record_starts(buf)

    # l_shared below the fixed-field floor
    bad = bytearray(buf)
    struct.pack_into("<I", bad, int(starts[1]), 10)
    with pytest.raises(BCFError):
        decode_bcf_columns(bytes(bad), header, 8,
                           starts=starts)          # framing bypassed
    # l_shared ballooned past the buffer
    bad = bytearray(buf)
    struct.pack_into("<I", bad, int(starts[1]), 1 << 30)
    with pytest.raises(BCFError):
        decode_bcf_columns(bytes(bad), header, 8, starts=starts)
    # reserved typed-value code in the ID slot (descriptor at the fixed
    # 24-byte prefix's end): type nibble 4 is undefined by the spec
    bad = bytearray(buf)
    off = int(starts[0]) + 32
    bad[off] = (bad[off] & 0xF0) | 0x04
    with pytest.raises(BCFError):
        decode_bcf_columns(bytes(bad), header, 8, starts=starts)


def test_random_byte_flips_never_decode_loosely(gt_path):
    """Flipping one byte either still yields records framed exactly as
    claimed (decode succeeds or falls back) or raises BCFError — no
    crash, no out-of-range read."""
    header, _, _, buf = _encode(LINES + _wide_lines())
    rng = random.Random(11)
    for _ in range(300):
        bad = bytearray(buf)
        i = rng.randrange(len(bad))
        bad[i] ^= 1 << rng.randrange(8)
        try:
            starts = frame_record_starts(bytes(bad))
            decode_bcf_columns(bytes(bad), header, 8, starts=starts)
        except BCFError:
            pass


# ---------------------------------------------------------------------------
# pipeline integration: the stats driver takes the columnar path
# ---------------------------------------------------------------------------

# the cross-container comparisons must drop the GT-not-first record:
# the text paths (tokenizer + VariantBatch) only read a LEADING GT,
# while both binary scanners key on the GT dictionary id anywhere —
# a pre-existing, documented divergence (see PARITY.md)
CROSS_LINES = [ln for ln in LINES
               if not ln.endswith("\t") and "DP:GT" not in ln]


def _write_pair(tmp_path, lines):
    """The same records as text VCF and BGZF BCF."""
    from hadoop_bam_tpu.api.writers import open_vcf_writer

    header, _, recs, _ = _encode(lines)
    vcf = str(tmp_path / "t.vcf")
    with open(vcf, "w") as f:
        f.write(HDR)
        for r in recs:
            f.write(r.to_line() + "\n")
    bcf = str(tmp_path / "t.bcf")
    with open_vcf_writer(bcf, header) as w:
        for r in recs:
            w.write_record(r)
    return vcf, bcf, header, recs


def test_variant_stats_bcf_uses_columnar_path(tmp_path, monkeypatch):
    """variant_stats_file on BCF == on the text twin, via the columnar
    decoder (the record-serial scanner is poisoned to prove no
    fallback)."""
    from hadoop_bam_tpu import formats
    from hadoop_bam_tpu.parallel.variant_pipeline import variant_stats_file

    vcf, bcf, header, recs = _write_pair(tmp_path, CROSS_LINES)
    expect = variant_stats_file(vcf)

    def boom(*a, **k):
        raise AssertionError("record-serial scan used on an eligible span")
    monkeypatch.setattr(formats.bcf, "scan_variant_columns", boom)
    got = variant_stats_file(bcf)
    for k in ("n_variants", "n_snp", "n_pass", "n_af"):
        assert got[k] == expect[k], k
    assert abs(got["mean_af"] - expect["mean_af"]) < 1e-6
    np.testing.assert_allclose(got["sample_callrate"],
                               expect["sample_callrate"], atol=1e-9)


def test_variant_stats_bcf_fallback_matches(tmp_path, monkeypatch):
    """With the columnar decoder declining every span, the scanner
    fallback must produce identical stats."""
    import hadoop_bam_tpu.formats.bcf_columns as bc
    from hadoop_bam_tpu.parallel.variant_pipeline import variant_stats_file

    _, bcf, header, recs = _write_pair(tmp_path, CROSS_LINES)
    expect = variant_stats_file(bcf)
    monkeypatch.setattr(bc, "decode_bcf_columns", lambda *a, **k: None)
    got = variant_stats_file(bcf)
    assert {k: v for k, v in got.items() if k != "sample_callrate"} \
        == {k: v for k, v in expect.items() if k != "sample_callrate"}
    np.testing.assert_array_equal(got["sample_callrate"],
                                  expect["sample_callrate"])


def test_tensor_batches_bcf_matches_text(tmp_path):
    """VcfDataset.tensor_batches over BCF (columnar feed) == over the
    text twin (record feed), tile for tile."""
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.parallel.variant_pipeline import VariantGeometry

    vcf, bcf, header, recs = _write_pair(tmp_path, CROSS_LINES * 30)
    g = VariantGeometry(tile_records=64, n_samples=header.n_samples)

    def collect(path):
        out = []
        for batch in open_vcf(path).tensor_batches(geometry=g,
                                                   num_spans=2):
            out.append({k: np.asarray(v) for k, v in batch.items()})
        return out

    a, b = collect(bcf), collect(vcf)
    assert len(a) == len(b)
    for ta, tb in zip(a, b):
        assert set(ta) == set(tb)
        for k in ta:
            np.testing.assert_array_equal(ta[k], tb[k], err_msg=k)
