"""The native BCF record walker against its twins
(native/hbam_native.cpp: ``hbam_bcf_chase``, ``hbam_bcf_span_columns``,
``hbam_bcf_guess``): wherever a scan walks the length-prefixed structure of
BCF records — the span read's frame chase, the columnar decode of a framed
span, the split guesser's candidate test — the native pass and the NumPy /
Python code it replaces give one answer, byte for byte: the same columns,
the same refusals, the same ``BCFError`` on the same corrupt bytes, the
same record boundary from every offset, the same spans.

``native.load`` patched to ``None`` is a host without the library: the
twins run and the ``*_numpy`` counters count.
"""
import concurrent.futures as cf
import json
import os
import random
import struct

import numpy as np
import pytest

import kgp3_reference as K
from test_bcf_columns import HDR, LINES, _encode, _header, _wide_lines

from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.formats.bcf import (
    BCFError, T_CHAR, T_FLOAT, T_INT8, T_INT16, T_INT32, _descriptor,
    decode_header, encode_typed_ints, encode_typed_string,
    scan_variant_columns,
)
from hadoop_bam_tpu.formats.bcf_columns import (
    STAT_KEYS, decode_bcf_columns, frame_record_starts,
)
from hadoop_bam_tpu.formats.vcf import VCFHeader
from hadoop_bam_tpu.split import vcf_planners as vp
from hadoop_bam_tpu.split.bcf_guesser import (
    INSPECT_BLOCKS, MIN_CHAIN, BCFSplitGuesser,
)
from hadoop_bam_tpu.split.vcf_planners import plan_bcf_spans
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS

pytestmark = [pytest.mark.bcf,
              pytest.mark.skipif(not native.available(),
                                 reason="native library unavailable")]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAD = 8
SMALL = K.Shape((3, 2, 2, 2, 3), missing=0.1, haploid=0.1, unphased=0.3,
                type_shares=(0.5, 0.3, 0.2), multi_share=0.3)


def _counters():
    return METRICS.snapshot()["counters"]


def _outcome(fn):
    """What a decode did: its columns, ``None`` (declined) or the class of
    what it raised."""
    try:
        return fn()
    except BCFError:
        return BCFError


def _both(buf, header, pad=PAD, starts=None):
    """A span through the native walk and through the NumPy twin: one
    outcome — equal columns (every key, dtype and byte), both declined or
    both ``BCFError`` — and each path counted under its own name.  Returns
    the outcome."""
    n = len(frame_record_starts(buf)) if starts is None else len(starts)
    METRICS.reset()
    fast = _outcome(lambda: decode_bcf_columns(buf, header, pad, starts))
    c = _counters()
    assert "vcf.walk_numpy_records" not in c
    assert "vcf.gt_numpy_records" not in c
    if isinstance(fast, dict) and n:
        assert c["vcf.walk_native_records"] == n
    with pytest.MonkeyPatch.context() as m:
        m.setattr(native, "load", lambda: None)
        METRICS.reset()
        slow = _outcome(lambda: decode_bcf_columns(buf, header, pad, starts))
        c = _counters()
    assert "vcf.walk_native_records" not in c
    assert "vcf.gt_native_records" not in c
    if n and slow is not BCFError:
        assert c["vcf.walk_numpy_records"] == n
    if not isinstance(fast, dict) or not isinstance(slow, dict):
        assert fast is slow, (fast, slow)
        return fast
    assert list(fast) == list(slow)
    for k in fast:
        assert fast[k].dtype == slow[k].dtype, k
        assert fast[k].shape == slow[k].shape, k
        assert fast[k].tobytes() == slow[k].tobytes(), k
    return fast


# ---------------------------------------------------------------------------
# columns: the native span pass == _cursor_walk + _gt_group_dosage
# ---------------------------------------------------------------------------

def _kgp3(n, shape=K.KGP3, seed=29):
    f = K.gen_fields(seed, 0, 1, n, shape)
    data, starts = K.assemble(f, shape)
    header, _ = decode_header(K.header_bytes(shape))
    return header, data.tobytes(), np.asarray(starts[:-1], np.int64)


@pytest.mark.parametrize("shape,n,pad", [
    (K.KGP3, 96, 2504), (K.KGP3, 40, 2560), (SMALL, 600, 16)],
    ids=["kgp3-2504", "kgp3-pad-2560", "small-every-genotype-form"])
@pytest.mark.parametrize("hand_starts", [True, False],
                         ids=["starts-handed-in", "chased"])
def test_kgp3_columns_equal_the_twin(shape, n, pad, hand_starts):
    header, buf, starts = _kgp3(n, shape)
    cols = _both(buf, header, pad, starts if hand_starts else None)
    scan = scan_variant_columns(buf, header, pad)
    for k in STAT_KEYS:
        assert cols[k].tobytes() == scan[k].tobytes(), k
    if shape is K.KGP3:
        assert (cols["dosage"][:, :2504] >= 0).all()
        assert (cols["dosage"][:, 2504:] == -1).all()


@pytest.mark.parametrize("lines", [LINES, _wide_lines(),
                                   LINES + _wide_lines(), LINES[5:6]],
                         ids=["lines", "wide", "both", "no-genotype-block"])
def test_codec_records_equal_the_twin(lines):
    """Every typed-value type, extended counts, multi-allelic and symbolic
    ALTs, mixed ploidy, phased-missing calls, GT second of three FORMAT
    keys, a record with no genotype block, int16 GT."""
    header, _, _, buf = _encode(lines)
    cols = _both(buf, header)
    scan = scan_variant_columns(buf, header, PAD)
    for k in STAT_KEYS:
        np.testing.assert_array_equal(cols[k], scan[k], err_msg=k)


def _record(pos=7, *, chrom=0, rlen=1, qual=30.0, n_info=0, ident=None,
            alleles=("A", "C"), filt=(0,), info=b"", n_sample=5, fmt=(),
            n_fmt=None, l_shared=None, l_indiv=None):
    """One BCF record byte by byte.  ``fmt`` is a list of (key bytes,
    descriptor bytes, data bytes); ``alleles`` strings or raw typed
    bytes; every length and count can be forced to lie."""
    n_fmt = len(fmt) if n_fmt is None else n_fmt
    shared = struct.pack("<iiifHHI", chrom, pos, rlen, qual, n_info,
                         len(alleles), (n_fmt << 24) | n_sample)
    shared += _descriptor(0, T_CHAR) if ident is None else ident
    for a in alleles:
        shared += a if isinstance(a, bytes) else encode_typed_string(a)
    shared += filt if isinstance(filt, bytes) \
        else encode_typed_ints(list(filt))
    shared += info
    indiv = b"".join(k + d + v for k, d, v in fmt)
    return struct.pack(
        "<II", len(shared) if l_shared is None else l_shared,
        len(indiv) if l_indiv is None else l_indiv) + shared + indiv


def _gt(values, typ=T_INT8, ploidy=2, key=None):
    """A GT FORMAT field of ``typ`` x ``ploidy`` over ``values``."""
    key = _header().string_dictionary().index("GT") if key is None else key
    fmt = {T_INT8: "<b", T_INT16: "<h", T_INT32: "<i"}[typ]
    return (encode_typed_ints([key]), _descriptor(ploidy, typ),
            b"".join(struct.pack(fmt, int(v)) for v in values))


def _ints(name, values, per_sample, typ=T_INT8):
    key = _header().string_dictionary().index(name)
    fmt = {T_INT8: "<b", T_INT16: "<h", T_INT32: "<i"}[typ]
    return (encode_typed_ints([key]), _descriptor(per_sample, typ),
            b"".join(struct.pack(fmt, int(v)) for v in values))


_CALLED = [2, 4, 4, 5, 2, 3, 6, 2, 4, 4]           # 0/1 1|1 0|0 2/0 1/1
_MISS8, _EOV8 = -128, -127

BUILT = {
    "biallelic-snp": [_record(fmt=[_gt(_CALLED)])],
    "multi-allelic": [_record(alleles=("A", "C", "G", "T"),
                              fmt=[_gt([2, 8, 6, 4, 4, 4, 2, 2, 8, 8])])],
    "symbolic-alt": [_record(alleles=("A", "<DEL>", "<INS:ME:ALU>"),
                             fmt=[_gt(_CALLED)])],
    "ref-not-a-base": [_record(alleles=("ACGT", "A"), fmt=[_gt(_CALLED)])],
    "lowercase-alt": [_record(alleles=("A", "c"), fmt=[_gt(_CALLED)])],
    "no-alt": [_record(alleles=("A",), fmt=[_gt(_CALLED)])],
    "no-alleles": [_record(alleles=(), fmt=[_gt(_CALLED)])],
    "extended-counts": [_record(
        ident=encode_typed_string("rs" + "7" * 40),
        alleles=("A" * 300, "C" * 20), fmt=[_gt(_CALLED)])],
    "extended-count-int16": [_record(
        alleles=(bytes([(15 << 4) | T_CHAR, (1 << 4) | T_INT16])
                 + struct.pack("<h", 300) + b"A" * 300, "C"),
        fmt=[_gt(_CALLED)])],
    "gt-second-of-three": [_record(fmt=[
        _ints("DP", [3, 7, 0, 2, 9], 1), _gt(_CALLED),
        _ints("AD", range(10), 2, T_INT16)])],
    "gt-twice-the-last-wins": [_record(fmt=[
        _gt(_CALLED), _gt([4] * 5, ploidy=1)])],
    "gt-int16": [_record(fmt=[_gt(_CALLED, T_INT16)])],
    "gt-int32": [_record(fmt=[_gt(_CALLED, T_INT32)])],
    "gt-float-is-no-gt": [_record(fmt=[(
        encode_typed_ints([_header().string_dictionary().index("GT")]),
        _descriptor(1, T_FLOAT), struct.pack("<5f", 1, 2, 3, 4, 5))])],
    "haploid": [_record(fmt=[_gt([2, 4, 6, 0, _MISS8], ploidy=1)])],
    "missing": [_record(fmt=[_gt([0, 0, _MISS8, _MISS8, 2, 4, 0, 2, 4, 4])])],
    "half-missing": [_record(fmt=[_gt([2, 1, 1, 2, 4, 0, 1, 1, 3, 5])])],
    "eov-padded": [_record(fmt=[_gt(
        [2, 4, 4, 4, _EOV8, _EOV8, _EOV8, _EOV8, _EOV8,
         2, 4, _EOV8, 0, _EOV8, _EOV8], ploidy=3)])],
    "ploidy-0": [_record(fmt=[_gt([], ploidy=0)])],
    "ploidy-256": [_record(fmt=[_gt([4] * 256 * 5, ploidy=256)])],
    "triploid-int16": [_record(fmt=[_gt([4, 2, 4] * 5, T_INT16, 3)])],
    "no-gt": [_record(fmt=[_ints("DP", [1, 2, 3, 4, 5], 1)])],
    "no-format": [_record(n_sample=0)],
    "n-sample-0-with-gt-key": [_record(n_sample=0,
                                       fmt=[_gt([], ploidy=2)])],
    "n-sample-fills-the-row": [_record(n_sample=PAD,
                                       fmt=[_gt([2, 4] * PAD)])],
    "n-fmt-overruns-its-block": [_record(fmt=[_gt(_CALLED)], n_fmt=3)],
    "filter-not-pass": [_record(filt=(3,), fmt=[_gt(_CALLED)])],
    "filter-two": [_record(filt=(0, 3), fmt=[_gt(_CALLED)])],
    "filter-empty": [_record(filt=(), fmt=[_gt(_CALLED)])],
    "filter-int16-zero": [_record(
        filt=bytes([(1 << 4) | T_INT16]) + struct.pack("<h", 0),
        fmt=[_gt(_CALLED)])],
    "filter-a-float": [_record(
        filt=bytes([(1 << 4) | T_FLOAT]) + struct.pack("<f", 0.0),
        fmt=[_gt(_CALLED)])],
    "info-jumped": [_record(n_info=2, info=os.urandom(64),
                            fmt=[_gt(_CALLED)])],
    "qual-missing-pos-extremes": [
        _record(pos=-1, qual=float("nan"), fmt=[_gt(_CALLED)]),
        struct.pack("<II", 24 + 7, 0) + struct.pack(
            "<iiiIHHI", 1, 0x7FFFFFFF, 0, 0x7F800001, 0, 2, 0)
        + _descriptor(0, T_CHAR) + encode_typed_string("A")
        + encode_typed_string("C") + encode_typed_ints([0]),
        struct.pack("<II", 24 + 7, 0) + struct.pack(
            "<iiiIHHI", 1, 5, 9, 0x7F800002, 0, 2, 0)
        + _descriptor(0, T_CHAR) + encode_typed_string("A")
        + encode_typed_string("C") + encode_typed_ints([0])],
    "a-span-of-every-layout": [
        _record(1, fmt=[_gt(_CALLED)]), _record(2, n_sample=0),
        _record(3, fmt=[_gt(_CALLED, T_INT16)]),
        _record(4, n_sample=3, fmt=[_gt([2, 4, 6], ploidy=1)]),
        _record(5, fmt=[_ints("DP", [1, 2, 3, 4, 5], 1), _gt(_CALLED)]),
        _record(6, fmt=[_gt([4, 2, 4] * 5, T_INT32, 3)]),
        _record(7, alleles=("A", "<DEL>"), filt=(1,), fmt=[_gt(_CALLED)])],
}


# where the record scanner is no oracle: it reads a float FILTER of 0.0 as
# PASS (the columnar walks want an int), and a POS of INT32_MAX overflows it
_SCANNER_DIFFERS = {"filter-a-float", "qual-missing-pos-extremes"}


@pytest.mark.parametrize("case", list(BUILT))
def test_built_records_equal_the_twin(case):
    buf = b"".join(BUILT[case])
    cols = _both(buf, _header())
    assert isinstance(cols, dict), cols
    if case not in _SCANNER_DIFFERS:
        scan = scan_variant_columns(buf, _header(), PAD)
        for k in STAT_KEYS:
            np.testing.assert_array_equal(cols[k], scan[k], err_msg=k)
    if case == "qual-missing-pos-extremes":
        assert np.isnan(cols["qual"][:2]).all()
        assert cols["qual"][1:].view(np.uint32).tolist() \
            == [0x7FC00000, 0x7F800002]             # MISSING -> NaN; EOV kept
        assert cols["pos"].tolist() == [0, -(1 << 31), 6]


def test_an_empty_span_and_a_dictionary_without_gt():
    header = _header()
    cols = _both(b"", header)
    assert cols["chrom"].size == 0 and cols["dosage"].shape == (0, PAD)
    no_gt = VCFHeader.from_text(
        "\n".join(ln for ln in HDR.split("\n") if "ID=GT" not in ln))
    assert "GT" not in no_gt.string_dictionary()
    buf = b"".join(BUILT["a-span-of-every-layout"])
    cols = _both(buf, no_gt)
    assert (cols["dosage"] == -1).all()


# ---------------------------------------------------------------------------
# corruption: the same BCFError from both walks, never a loose decode
# ---------------------------------------------------------------------------

_GOOD = _record(fmt=[_gt(_CALLED)])
_NEG_EXT = bytes([(15 << 4) | T_CHAR, (1 << 4) | T_INT8]) \
    + struct.pack("<b", -3)

CORRUPT = {
    "truncated-record": (_GOOD + _GOOD[:-9], None),
    "l-shared-under-24": (_record(l_shared=10), [0]),
    "l-shared-past-the-buffer": (_record(l_shared=1 << 30), [0]),
    "l-indiv-past-the-buffer": (_record(fmt=[_gt(_CALLED)],
                                        l_indiv=1 << 20), [0]),
    "start-out-of-range": (_GOOD, [0, len(_GOOD) - 8]),
    "start-negative": (_GOOD, [-1]),
    "reserved-type-code-in-id": (_record(ident=bytes([0x14, 0x41])), None),
    "reserved-type-code-in-filter": (_record(filt=bytes([0x16, 0])), None),
    "reserved-type-code-in-format": (_record(fmt=[(
        encode_typed_ints([3]), bytes([0x14]), b"\0" * 5)]), None),
    "id-overruns": (_record(ident=_descriptor(14, T_CHAR) + b"x" * 4,
                            alleles=(), filt=b""), None),
    "descriptor-past-the-record": (_record(alleles=("A", "C", "G"),
                                           filt=b"")[:-2]
                                   + b"", None),
    "allele-vector-overruns": (_record(
        alleles=("A", _descriptor(200, T_CHAR) + b"C")), None),
    "allele-not-a-char-vector": (_record(
        alleles=("A", encode_typed_ints([67]))), None),
    "negative-extended-count": (_record(alleles=("A", _NEG_EXT)), None),
    "extended-count-not-a-scalar": (_record(alleles=(
        "A", bytes([(15 << 4) | T_CHAR, (2 << 4) | T_INT8, 1, 1]))), None),
    "extended-count-a-float": (_record(alleles=(
        "A", bytes([(15 << 4) | T_CHAR, (1 << 4) | T_FLOAT])
        + struct.pack("<f", 2.0))), None),
    "extended-count-cut-off": (_record(
        alleles=("A",), filt=bytes([(15 << 4) | T_INT8, (1 << 4) | T_INT32,
                                    1])), None),
    "filter-vector-overruns": (_record(
        filt=_descriptor(9, T_INT32) + b"\0" * 4), None),
    "format-key-a-string": (_record(fmt=[(
        encode_typed_string("G"), _descriptor(2, T_INT8), b"\2" * 10)]),
        None),
    "format-key-a-vector": (_record(fmt=[(
        encode_typed_ints([1, 2]), _descriptor(2, T_INT8), b"\2" * 10)]),
        None),
    "format-key-overruns": (_record(fmt=[(
        bytes([(1 << 4) | T_INT32, 1]), b"", b"")]), None),
    "format-data-overruns": (_record(fmt=[(
        encode_typed_ints([9]), _descriptor(2, T_INT8), b"\2" * 9)]), None),
    "format-descriptor-missing": (_record(fmt=[(
        encode_typed_ints([9]), b"", b"")]), None),
    "gt-vector-overruns": (_record(fmt=[_gt(_CALLED[:7])]), None),
}


@pytest.mark.parametrize("case", list(CORRUPT))
def test_corruption_raises_the_same_error_from_both_walks(case):
    buf, starts = CORRUPT[case]
    # framed by hand where the chase itself would refuse the bytes
    if starts is None:
        starts = [0] if case != "truncated-record" else [0, len(_GOOD)]
    assert _both(buf, _header(),
                 starts=np.asarray(starts, np.int64)) is BCFError
    if case == "truncated-record":      # more bytes would cure this one
        return
    # a good record before and after changes nothing
    shifted = np.asarray([0] + [len(_GOOD) + s for s in starts]
                         + [len(_GOOD) + len(buf)], np.int64)
    assert _both(_GOOD + buf + _GOOD, _header(),
                 starts=shifted if min(starts) >= 0 else shifted[1:]) \
        is BCFError


def test_the_native_error_names_its_check_and_its_record():
    buf = _GOOD + _record(ident=bytes([0x14, 0x41])) + _GOOD
    starts = np.asarray([0, len(_GOOD), len(buf) - len(_GOOD)], np.int64)
    with pytest.raises(BCFError, match=r"unknown typed-value type.*record 1"):
        decode_bcf_columns(buf, _header(), PAD, starts)
    with pytest.raises(BCFError, match=r"shorter than its fixed.*record 2"):
        decode_bcf_columns(_GOOD * 2 + _record(l_shared=3), _header(), PAD,
                           np.asarray([0, len(_GOOD), 2 * len(_GOOD)]))


@pytest.mark.parametrize("seed", range(6))
def test_byte_flips_give_one_outcome_on_both_walks(seed):
    """One flipped byte anywhere: the two walks agree on the outcome —
    equal columns, both declined, or both ``BCFError`` — with the framing
    of the clean bytes (so lengths that lie reach the walk) and with the
    bytes chased afresh."""
    header, _, _, buf = _encode(LINES + _wide_lines())
    buf += b"".join(BUILT["a-span-of-every-layout"])
    clean = frame_record_starts(buf)
    rng = random.Random(seed)
    for _ in range(120):
        bad = bytearray(buf)
        i = rng.randrange(len(bad))
        bad[i] ^= 1 << rng.randrange(8)
        bad = bytes(bad)
        _both(bad, header, starts=clean)
        try:
            chased = frame_record_starts(bad)
        except BCFError:
            continue
        _both(bad, header, starts=chased)


def test_truncation_at_every_cut_gives_one_outcome():
    header, _, _, buf = _encode(LINES)
    for cut in range(1, len(buf), max(1, len(buf) // 150)):
        a = _outcome(lambda: decode_bcf_columns(buf[:cut], header, PAD))
        with pytest.MonkeyPatch.context() as m:
            m.setattr(native, "load", lambda: None)
            b = _outcome(lambda: decode_bcf_columns(buf[:cut], header, PAD))
        assert (a is BCFError) == (b is BCFError), cut


# ---------------------------------------------------------------------------
# the declined geometries fall through to the record scanner
# ---------------------------------------------------------------------------

def _wide_format_header(n_keys):
    lines = HDR.split("\n")
    at = next(i for i, ln in enumerate(lines) if ln.startswith("#CHROM"))
    extra = [f'##FORMAT=<ID=X{i},Number=1,Type=Integer,Description="x">'
             for i in range(n_keys)]
    return VCFHeader.from_text("\n".join(lines[:at] + extra + lines[at:]))


def _declined_case(case):
    """(header, lines) of a file with one record of a geometry the
    columnar walks leave to the record scanner, between plain ones."""
    header = _header()
    if case == "alleles":               # past _MAX_ALLELE_ROUNDS
        alts = ",".join("ACGT"[i % 4] * (i // 4 + 2) for i in range(600))
        odd = f"c1\t150\t.\tA\t{alts}\t30\tPASS\t.\tGT\t0/1\t5/9\t0/0\t./."
    elif case == "format-keys":         # past _MAX_FMT_ROUNDS
        header = _wide_format_header(70)
        keys = ":".join(["GT"] + [f"X{i}" for i in range(70)])
        cell = ":".join(["0/1"] + [str(i) for i in range(70)])
        odd = f"c1\t150\t.\tA\tC\t30\tPASS\t.\t{keys}" + f"\t{cell}" * 4
    else:                               # past _MAX_GT_PLOIDY
        gt = "/".join(["1"] * 300)
        odd = f"c1\t150\t.\tA\tC\t30\tPASS\t.\tGT" + f"\t{gt}" * 4
    return header, [LINES[0], odd, LINES[3]]


@pytest.mark.parametrize("case", ["alleles", "format-keys", "gt-ploidy"])
def test_declined_geometry_falls_through_to_the_record_scanner(
        case, tmp_path):
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.api.writers import open_vcf_writer
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        VariantGeometry, bcf_span_stat_columns,
    )

    header, lines = _declined_case(case)
    _, _, recs, buf = _encode(lines, header)
    assert _both(buf, header) is None
    want = scan_variant_columns(buf, header, PAD)
    path = str(tmp_path / "odd.bcf")
    with open_vcf_writer(path, header) as w:
        for r in recs:
            w.write_record(r)
    ds = open_vcf(path)
    geometry = VariantGeometry(n_samples=header.n_samples)
    got = {}
    for name, load in (("native", native.load), ("numpy", lambda: None)):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(native, "load", load)
            METRICS.reset()
            (span,) = ds.spans(1)
            got[name] = bcf_span_stat_columns(path, span, header, geometry,
                                              ds._is_bgzf_bcf)
            assert _counters()["vcf.columnar_declined_spans"] == 1
    for k in STAT_KEYS:
        assert got["native"][k].tobytes() == got["numpy"][k].tobytes(), k
        np.testing.assert_array_equal(
            got["native"][k][:, :PAD] if k == "dosage" else got["native"][k],
            want[k], err_msg=k)


def test_more_samples_than_the_tile_is_declined_by_both_walks():
    buf = b"".join(BUILT["a-span-of-every-layout"])
    assert isinstance(_both(buf, _header(), pad=5), dict)
    assert _both(buf, _header(), pad=4) is None
    # ... but only where a GT vector is that wide, and corruption in a
    # later record still outranks the refusal
    assert isinstance(_both(_record(fmt=[_ints("DP", range(5), 1)]),
                            _header(), pad=2), dict)
    bad = _record(ident=bytes([0x14, 0x41]))
    assert _both(buf + bad, _header(), pad=4) is BCFError


# ---------------------------------------------------------------------------
# the chase
# ---------------------------------------------------------------------------

def _chase_both(whole: bytes, have: int, n0: int):
    """``_chase_frames`` over ``whole[:have]`` growing into ``whole``
    (which ends where the file does): the native chase and the Python
    loop, which must agree on (bytes, length, starts)."""
    out = []
    for load in (native.load, lambda: None):
        buf = bytearray(whole[:have])
        grows = []

        def grow(need):
            grows.append(need)
            buf.extend(whole[len(buf):need])
            return buf

        with pytest.MonkeyPatch.context() as m:
            m.setattr(native, "load", load)
            got, length, starts = vp._chase_frames(buf, n0, grow)
        out.append((bytes(got), length, starts.tolist(), grows))
        assert starts.dtype == np.int64
    assert out[0] == out[1]
    return out[0]


def test_chase_grows_for_a_tail_record_that_crosses_the_spans_end():
    header, buf, starts = _kgp3(12, SMALL)
    ends = np.append(starts[1:], len(buf))
    for k in (1, 5, 11):
        lo, hi = int(starts[k]), int(ends[k])
        for n0 in (lo + 1, lo + 7, lo + 8, lo + 9, hi - 1):
            for have in (n0, min(hi - 1, n0 + 3)):
                got, length, found, grows = _chase_both(buf, have, n0)
                assert found == starts[:k + 1].tolist()
                assert length == hi and got[:length] == buf[:hi]
                assert grows and grows[-1] == hi
        # a span that ends on a record start needs no growth
        got, length, found, grows = _chase_both(buf, lo, lo)
        assert (found, length, grows) == (starts[:k].tolist(), lo, [])


@pytest.mark.parametrize("cut", ["mid-header", "mid-body"])
def test_chase_at_eof_inside_the_tail_record(cut):
    header, buf, starts = _kgp3(6, SMALL)
    last = int(starts[-1])
    eof = last + (5 if cut == "mid-header" else 40)
    whole = buf[:eof]
    got, length, found, grows = _chase_both(whole, last + 2, last + 1)
    if cut == "mid-header":             # a bare header stub is dropped
        assert found == starts[:-1].tolist() and length == last
    else:                               # a cut record is kept: decode raises
        assert found == starts.tolist() and length == eof
        with pytest.raises(BCFError):
            decode_bcf_columns(got[:length], header, 16,
                               np.asarray(found, np.int64))


def test_frame_record_starts_is_the_same_chase():
    header, buf, starts = _kgp3(20, SMALL)
    for load in (native.load, lambda: None):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(native, "load", load)
            np.testing.assert_array_equal(frame_record_starts(buf), starts)
            assert frame_record_starts(b"").size == 0
            for cut in (len(buf) - 1, int(starts[3]) + 5, 3):
                with pytest.raises(BCFError):
                    frame_record_starts(buf[:cut])
            with pytest.raises(BCFError):
                frame_record_starts(buf + b"\0" * 3)


def test_span_reads_agree_on_truncated_files(tmp_path):
    """EOF mid-header and mid-body through the two span readers, raw and
    BGZF: the native chase and the Python loop return the same frames."""
    shape_head = K.header_bytes(SMALL)
    header, buf, starts = _kgp3(40, SMALL)
    for tail in (5, 40):
        body = buf[:int(starts[-1]) + tail]
        raw_path = str(tmp_path / f"raw{tail}.bcf")
        with open(raw_path, "wb") as fh:
            fh.write(shape_head + body)
        gz_path = str(tmp_path / f"gz{tail}.bcf")
        with open(gz_path, "wb") as fh:
            blob = shape_head + body
            for lo in range(0, len(blob), 700):
                fh.write(bgzf.deflate_block(blob[lo:lo + 700]))
            fh.write(bgzf.EOF_BLOCK)
        for path, is_bgzf in ((raw_path, False), (gz_path, True)):
            spans = plan_bcf_spans(path, num_spans=3)
            got = {}
            for name, load in (("native", native.load),
                               ("python", lambda: None)):
                with pytest.MonkeyPatch.context() as m:
                    m.setattr(native, "load", load)
                    got[name] = [vp.read_bcf_span_frames(path, s, is_bgzf)
                                 for s in spans]
            for (ra, sa), (rb, sb) in zip(got["native"], got["python"]):
                assert ra == rb and sa.tolist() == sb.tolist()
            assert b"".join(r for r, _ in got["native"]) == \
                (body if tail == 40 else buf[:int(starts[-1])])


# ---------------------------------------------------------------------------
# threads
# ---------------------------------------------------------------------------

def test_eight_threads_on_one_buffer_give_one_answer():
    header, buf, starts = _kgp3(64)
    b = np.frombuffer(buf, np.uint8)
    want = decode_bcf_columns(buf, header, 2560, starts)

    def work(_):
        got = decode_bcf_columns(b, header, 2560, starts)
        chased, end, need = native.bcf_chase(b, 0, len(buf))
        return got, chased, end, need

    with cf.ThreadPoolExecutor(8) as pool:
        for got, chased, end, need in pool.map(work, range(32)):
            assert (chased == starts).all() and (end, need) == (len(buf), 0)
            for k in want:
                assert got[k].tobytes() == want[k].tobytes(), k


def test_without_the_library_the_twin_runs_and_counts(monkeypatch):
    header, buf, starts = _kgp3(24, SMALL)
    monkeypatch.setattr(native, "load", lambda: None)

    def boom(*a, **k):
        raise AssertionError("a native entry point ran without a library")
    for name in ("bcf_span_columns", "bcf_chase", "bcf_guess"):
        monkeypatch.setattr(native, name, boom)
    METRICS.reset()
    cols = decode_bcf_columns(buf, header, 16)
    c = _counters()
    assert c["vcf.walk_numpy_records"] == 24 == cols["chrom"].size
    assert c["vcf.gt_numpy_records"] > 0
    assert "vcf.walk_native_records" not in c


# ---------------------------------------------------------------------------
# the guesser
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def guess_files(tmp_path_factory):
    """A small cohort as BGZF BCF in 1,500-byte blocks (a record start
    lies in most blocks, some blocks hold none) and as raw BCF."""
    d = tmp_path_factory.mktemp("guess")
    head = K.header_bytes(SMALL)
    header, buf, starts = _kgp3(260, SMALL)
    blob = head + buf
    gz = str(d / "g.bcf")
    with open(gz, "wb") as fh:
        for lo in range(0, len(blob), 1500):
            fh.write(bgzf.deflate_block(blob[lo:lo + 1500]))
        fh.write(bgzf.EOF_BLOCK)
    raw = str(d / "g_raw.bcf")     # the twin's sweep is a window a guess:
    with open(raw, "wb") as fh:     # a quarter of the records keeps it short
        fh.write(blob[:len(head) + int(starts[70])])
    return {"header": header, "bgzf": gz, "raw": raw,
            "records": buf, "starts": starts, "head_len": len(head)}


def _guess_with(path, header, is_bgzf, load, offsets):
    with pytest.MonkeyPatch.context() as m:
        m.setattr(native, "load", load)
        METRICS.reset()
        g = BCFSplitGuesser(path, header, is_bgzf=is_bgzf)
        got = [g.guess_next_record_start(o) for o in offsets]
        return got, _counters()


@pytest.mark.parametrize("container", ["bgzf", "raw"])
def test_guess_from_every_byte_offset_is_the_numpy_guess(guess_files,
                                                         container):
    """THE property: from every byte offset of the file the native
    candidate test and the NumPy sweep + chain find the same record
    boundary (or none), ``partial`` windows at EOF included."""
    path = guess_files[container]
    size = os.path.getsize(path)
    offsets = range(size + 2)
    fast, c = _guess_with(path, guess_files["header"], container == "bgzf",
                          native.load, offsets)
    assert c["vcf.guess_native"] == size + 2 and "vcf.guess_numpy" not in c
    slow, c = _guess_with(path, guess_files["header"], container == "bgzf",
                          lambda: None, offsets)
    assert c["vcf.guess_numpy"] == size + 2 and "vcf.guess_native" not in c
    assert fast == slow
    found = {v for v in fast if v is not None}
    assert len(found) > 20 and fast[-1] is None
    assert fast[0] is not None
    if container == "raw":              # every answer is a true boundary
        true = set((guess_files["head_len"]
                    + guess_files["starts"]).tolist())
        assert {v >> 16 for v in found} <= true


def test_guess_window_by_window_is_the_sweep_and_the_chain(guess_files):
    """``hbam_bcf_guess`` against ``_plausible_offsets`` + ``_chain_ok`` on
    windows cut anywhere — chains that reach the window's end, ``partial``
    or not, a first block of any length — and its ``edge`` flag: where it
    is unset, a longer window of the same bytes answers the same."""
    header = guess_files["header"]
    data = guess_files["records"]
    g = BCFSplitGuesser(guess_files["raw"], header, is_bgzf=False)

    def twin(win, first_len, partial):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(native, "load", lambda: None)
            return g._find_record(win, first_len, partial)

    rng = random.Random(5)
    steady = 0
    for _ in range(400):
        a = rng.randrange(len(data) - 40)
        b = min(len(data), a + rng.choice([10, 33, 90, 300, 1500, 6000]))
        win = data[a:b]
        first_len = rng.choice([len(win), len(win) // 2, 1, 0, len(win) + 9])
        for partial in (False, True):
            u, edge = native.bcf_guess(win, first_len, g._n_contigs,
                                       MIN_CHAIN, partial)
            want = twin(win, first_len, partial)
            assert (None if u < 0 else u) == want, (a, b, first_len, partial)
            if not edge:
                steady += 1
                longer = data[a:min(len(data), b + rng.randrange(1, 9000))]
                for p2 in (False, True):
                    assert twin(longer, first_len, p2) == want
    assert steady > 50
    for tiny in (b"", b"\0" * 31, b"\0" * 32):
        assert native.bcf_guess(tiny, 40, 3, MIN_CHAIN, False)[0] == -1


def test_guess_on_adversarial_windows():
    """Fake record heads inside a window: plausible lengths whose chain
    breaks, a chain that ends exactly at EOF, block lengths at the 2^24
    edge (the sweep refuses 2^24, the chain's test allows it)."""
    g = BCFSplitGuesser.__new__(BCFSplitGuesser)
    g._n_contigs = 3

    def head(l_shared, l_indiv, chrom=1, pos=5, rlen=1, n_allele=2):
        return struct.pack("<IIiiifHH", l_shared, l_indiv, chrom, pos, rlen,
                           1.0, 0, n_allele) + b"\0" * 4
    rec = head(24, 0)
    cases = [
        rec * 4, rec * 2, rec, rec + rec[:20], b"\x07" * 9 + rec * 3,
        head(24, 8) + b"\0" * 8 + rec * 2,
        head(1 << 24, 0) + rec * 3,                 # the sweep's edge
        head(24, 0, chrom=3) + rec * 3, head(24, 0, pos=-2) + rec * 3,
        head(24, 0, pos=-1) + rec * 3, head(24, 0, rlen=-1) + rec * 3,
        head(24, 0, n_allele=1025) + rec * 3,
        head(24, 0, n_allele=1024) + rec * 3,
        rec + head(24, 0, n_allele=2000) + rec * 3,
        rec + head(1 << 24, 0) + rec,               # the chain's edge
        rec + head(24, 1 << 25) + rec * 3,
        head(23, 0) + rec * 3, rec * 2 + b"\1" * 31, rec * 2 + b"\1" * 33,
    ]
    for data in cases:
        for first_len in (len(data), 1, 33):
            for partial in (False, True):
                u, _ = native.bcf_guess(data, first_len, 3, MIN_CHAIN,
                                        partial)
                with pytest.MonkeyPatch.context() as m:
                    m.setattr(native, "load", lambda: None)
                    want = g._find_record(data, first_len, partial)
                assert (None if u < 0 else u) == want, (data, first_len)


@pytest.mark.parametrize("num_spans", [1, 7, 83])
def test_plan_bcf_spans_is_the_same_with_and_without_the_library(
        num_spans, tmp_path_factory):
    """The spans a file is cut into do not change by one byte: the tiny
    kgp3 file at the published width, BGZF in 0xff00-byte blocks."""
    d = tmp_path_factory.mktemp("plan")
    path = str(d / "tiny.bcf")
    K.write_bcf(path, 3_000_000_019, 2, 192, K.Reference())
    plans = {}
    for name, load in (("native", native.load), ("numpy", lambda: None)):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(native, "load", load)
            METRICS.reset()
            plans[name] = plan_bcf_spans(path, num_spans=num_spans)
            c = _counters()
        assert c.get(f"vcf.guess_{name}", 0) == num_spans - 1
    assert plans["native"] == plans["numpy"]
    assert 1 <= len(plans["native"]) <= num_spans
    assert plans["native"][-1].end_voffset == os.path.getsize(path) << 16


@pytest.mark.parametrize("block,alone", [(0xFF00, True), (2000, False)],
                         ids=["blocks-of-0xff00", "blocks-of-2000"])
def test_the_first_block_alone_decides_only_where_it_can(block, alone,
                                                         tmp_path):
    """The BGZF guess asks the window's first block first and the whole
    window only where that answer leaned on the block's end: a 5 KB record
    and its chain of three lie inside a 0xff00-byte block, never inside a
    2,000-byte one — and the boundary is the NumPy guess's either way."""
    head = K.header_bytes()
    header, buf, starts = _kgp3(48)
    path = str(tmp_path / "k.bcf")
    with open(path, "wb") as fh:
        blob = head + buf
        for lo in range(0, len(blob), block):
            fh.write(bgzf.deflate_block(blob[lo:lo + block]))
        fh.write(bgzf.EOF_BLOCK)
    size = os.path.getsize(path)
    asked = list(range(1, size, max(1, size // 60)))
    want, _ = _guess_with(path, header, True, lambda: None, asked)
    g = BCFSplitGuesser(path, header, is_bgzf=True)
    whole = []
    real = g._inflate_chain
    g._inflate_chain = lambda raw, blocks: whole.append(1) or real(raw,
                                                                   blocks)
    assert [g.guess_next_record_start(o) for o in asked] == want
    assert sum(v is not None for v in want) > 20
    if alone:       # the file's last blocks reach EOF: those ask the window
        assert len(whole) < len(asked) // 3
    else:
        assert len(whole) >= sum(v is not None for v in want)
    assert INSPECT_BLOCKS == 4


# ---------------------------------------------------------------------------
# the benchmark's four data files
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bench_run():
    import importlib
    import sys
    sys.path.insert(0, ROOT)
    try:
        return importlib.import_module("benchmark.run")
    finally:
        sys.path.remove(ROOT)


@pytest.mark.parametrize("name,cell,obs,want", [
    ("vcf.walk_native_share", "kgp3-chr20-vcfstats",
     {"counters": {"vcf.walk_native_records": 786432}}, 100.0),
    ("vcf.walk_native_share", "kgp3-chr20-vcfstats",
     {"counters": {"vcf.walk_native_records": 3, "vcf.walk_numpy_records": 1}},
     75.0),
    ("vcf.walk_native_share", "kgp3-chr20-vcfstats",
     {"counters": {"vcf.walk_numpy_records": 5}}, 0.0),
    ("vcf.walk_native_share", "kgp3-chr20-vcfstats",
     {"counters": {"vcf.gt_native_records": 5}}, None),     # the parent
    ("gwas.walk_native_share", "kgp3-chr20-gwas",
     {"counters": {"vcf.walk_native_records": 262144}}, 100.0),
    ("gwas.walk_native_share", "kgp3-chr20-gwas",
     {"counters": {"pipeline.records": 262144}}, None),
    ("vcf.plan_share", "kgp3-chr20-vcfstats",
     {"wall_timers": {"vcf.plan_wall": 0.15}}, 2.5),
    ("vcf.plan_share", "kgp3-chr20-vcfstats", {"wall_timers": {}}, None),
    ("gwas.plan_share", "kgp3-chr20-gwas",
     {"wall_timers": {"vcf.plan_wall": 1.2}}, 20.0),
    ("gwas.plan_share", "kgp3-chr20-gwas",
     {"wall_timers": {"gwas.load_wall": 4.0}}, None),
])
def test_walk_and_plan_share_metrics(bench_run, name, cell, obs, want):
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    assert entry[0]["workloads"] == [cell]
    assert entry[0]["moves"] == "scan_records_per_s"
    assert entry[0]["layer"] == "host feed" and entry[0]["unit"] == "%"
    snap = {"counters": {}, "wall_timers": {}, **obs}
    said = []
    got = bench_run.layer_metrics(
        {"per_layer": entry}, {"name": cell},
        {"snapshot": snap, "window_s": 6.0}, said.append)
    if want is None:
        assert got == {} and "left out" in said[0]
    else:
        assert got[name]["unit"] == "%"
        assert got[name]["value"] == pytest.approx(want)
    other = "chr20-flagstat"
    assert bench_run.layer_metrics(
        {"per_layer": entry}, {"name": other},
        {"snapshot": snap, "window_s": 6.0}, said.append) == {}
