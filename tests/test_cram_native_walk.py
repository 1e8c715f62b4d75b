"""The native CRAM slice rebuild against its twins
(native/hbam_native.cpp: ``hbam_cram_slice_rebuild``): a slice's bases and
qualities — gap fill and tails from the reference, ``X`` substitutions,
``b`` / ``I`` / ``S`` / ``B`` / ``i`` overlays, stored qualities and their
``B`` / ``Q`` / ``q`` overlays — from one native call give what the NumPy
rebuild (``formats/cram_columns.py::_rebuild_numpy``) and the record
decoder give, byte for byte: the same columns, the same refusals (``None``:
the record path's slice), the same ``CRAMError`` on the same bytes, the
same reference window fetched.

``native.cram_slice_rebuild`` patched to refuse its arguments runs the
twin; ``native.load`` patched to ``None`` is a host without the library,
where the columnar decoder declines every slice (its predecode needs the
native ITF8 batch)."""
import concurrent.futures as cf
import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cram31_reference as C  # noqa: E402
from test_cram_columns import (  # noqa: E402
    HDR, REF, _roundtrip_columns, _SliceBuilder as Slice,
)

from hadoop_bam_tpu.formats.cram import write_itf8
from hadoop_bam_tpu.formats.cram_columns import (
    decode_slice_columns, records_to_columns,
)
from hadoop_bam_tpu.formats.cram_decode import (
    ByteArrayStopEncoding, CF_QUAL_STORED, CF_UNKNOWN_BASES, CRAMError,
    ExternalEncoding, FastaReferenceSource, HuffmanEncoding,
    decode_slice_records,
)
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")

class _Recorder:
    """A reference source that notes every window it is asked for."""

    def __init__(self, inner):
        self.inner, self.asked = inner, []

    def get_bytes(self, name, start, length):
        self.asked.append((name, start, length))
        return self.inner.get_bytes(name, start, length)

    def get(self, name, start, length):
        return self.inner.get(name, start, length)


def _counters():
    return METRICS.snapshot()["counters"]


def _outcome(fn):
    try:
        return fn()
    except CRAMError:
        return CRAMError


def _decode(built, ref, want_names=True, as_arrays=False):
    comp, hdr, core, external = built
    return decode_slice_columns(comp, hdr, core, dict(external),
                                list(HDR.ref_names), ref,
                                want_names=want_names, as_arrays=as_arrays)


def _plain(cols):
    """Columns with the byte runs as bytes (``as_arrays`` hands views)."""
    if not isinstance(cols, dict):
        return cols
    return {k: (v.tobytes() if k.endswith("_cat")
                and isinstance(v, np.ndarray) else v)
            for k, v in cols.items()}


def _same(a, b):
    if not isinstance(a, dict) or not isinstance(b, dict):
        assert a is b, (a, b)
        return
    assert list(a) == list(b)
    for k in a:
        if isinstance(a[k], np.ndarray):
            assert a[k].dtype == b[k].dtype, k
            assert a[k].tobytes() == b[k].tobytes(), k
        else:
            assert a[k] == b[k], k


def _both(built, ref=None, as_arrays=False):
    """A slice through the native rebuild and through the NumPy twin: one
    outcome — equal columns (every key, dtype and byte), both ``None`` or
    both ``CRAMError`` — the same reference windows asked for, and each
    path counted under its own name.  Returns the outcome."""
    n = built[1].n_records
    rec_fast = _Recorder(ref) if ref is not None else None
    METRICS.reset()
    fast = _outcome(lambda: _plain(_decode(built, rec_fast,
                                           as_arrays=as_arrays)))
    c = _counters()
    assert "cram.walk_numpy_records" not in c
    assert c.get("cram.walk_native_records", 0) == (
        n if isinstance(fast, dict) else 0)
    rec_slow = _Recorder(ref) if ref is not None else None
    with pytest.MonkeyPatch.context() as m:
        m.setattr(native, "cram_slice_rebuild", lambda *a, **k: None)
        METRICS.reset()
        slow = _outcome(lambda: _plain(_decode(built, rec_slow,
                                               as_arrays=as_arrays)))
        c = _counters()
    assert "cram.walk_native_records" not in c
    assert c.get("cram.walk_numpy_records", 0) == (
        n if isinstance(slow, dict) else 0)
    _same(fast, slow)
    if ref is not None:
        assert rec_fast.asked == rec_slow.asked
    return fast


def _three(b, ref=None):
    """``_both``, and where the columnar decoder takes the slice, the
    record decoder's columns too."""
    built = b.build()
    cols = _both(built, ref)
    _both(built, ref, as_arrays=True)
    if isinstance(cols, dict):
        comp, hdr, core, external = built
        recs = decode_slice_records(comp, hdr, core, dict(external),
                                    list(HDR.ref_names), ref)
        _same(cols, records_to_columns(recs, want_names=True))
    return cols


# ---------------------------------------------------------------------------
# the fixtures of tests/test_cram_columns.py, and what they leave out
# ---------------------------------------------------------------------------

def _every_feature(b, ap=5, cf=CF_QUAL_STORED, name=b"e"):
    """One read of each feature code, in a read whose gaps and tail come
    from the reference."""
    b.add(rl=40, ap=ap, cf=cf, name=name, features=[
        (2, "X", 1), (4, "b", b"GG"), (7, "B", (ord("T"), 31)),
        (9, "i", ord("C")), (11, "I", b"AA"), (14, "D", 3), (14, "N", 2),
        (15, "P", 1), (15, "H", 4), (16, "q", bytes([5, 6])), (17, "Q", 40),
        (20, "X", 3), (38, "S", b"TTT")])


def _b_verbatim():
    b = Slice()
    b.add(rl=8, ap=10, features=[(1, "b", b"ACGTACGT")])
    b.add(rl=6, ap=20, features=[(1, "b", b"GGGTTT")], name=b"second")
    return b


def _b_unmapped():
    b = Slice(ref_seq_id=-1)
    b.add(bf=0x4, rl=7, ap=0, ba=b"ACGTNNN")
    b.add(bf=0x4, cf=0, rl=5, ap=0, ba=b"AAAAA")
    b.add(bf=0x4, cf=CF_UNKNOWN_BASES | CF_QUAL_STORED, rl=4, ap=0,
          ba=b"NNNN")
    b.add(bf=0x4, rl=0, ap=0, ba=b"", qual=b"")
    return b


def _b_unknown_mapped():
    b = Slice()
    b.add(rl=6, ap=5, cf=CF_UNKNOWN_BASES | CF_QUAL_STORED,
          features=[(1, "b", b"ACGTAC")])
    b.add(rl=9, ap=7, cf=CF_UNKNOWN_BASES | CF_QUAL_STORED,
          features=[(3, "X", 2), (5, "B", (ord("G"), 3))])
    b.add(rl=0, ap=9, features=[])
    b.add(rl=5, ap=11, features=[(1, "b", b"CCCCC")])
    return b


def _b_reference_fill():
    b = Slice()
    b.add(rl=10, ap=5, features=[])
    b.add(rl=10, ap=17, features=[(4, "X", 2)])
    b.add(rl=12, ap=31, features=[(3, "D", 4), (5, "I", b"TT"),
                                  (11, "S", b"GG")])
    b.add(rl=9, ap=55, features=[(4, "N", 6), (6, "P", 2), (6, "H", 3)])
    return b


def _b_single_bases():
    b = Slice()
    b.add(rl=10, ap=5, features=[(2, "B", (ord("T"), 7)), (5, "i", ord("C")),
                                 (8, "Q", 9)])
    b.add(rl=10, ap=30, features=[(3, "q", bytes([1, 2, 3]))])
    b.add(rl=6, ap=60, cf=0, features=[(2, "Q", 11)])
    return b


def _b_colliding():
    b = Slice()
    b.add(rl=8, ap=5, features=[(3, "Q", 41), (3, "q", bytes([7, 8, 9]))])
    b.add(rl=8, ap=40, features=[(2, "q", bytes([5, 6, 7])), (3, "Q", 42)])
    b.add(rl=8, ap=60, features=[(3, "q", bytes([1, 2])),
                                 (3, "B", (ord("A"), 9)), (4, "Q", 3)])
    return b


def _b_every_feature():
    b = Slice()
    _every_feature(b)
    _every_feature(b, ap=90, cf=0, name=b"noqual")
    _every_feature(b, ap=95, cf=CF_UNKNOWN_BASES | CF_QUAL_STORED,
                   name=b"unknown")
    b.add(bf=0x4, rl=5, ap=0, ba=b"ACGTN")
    _every_feature(b, ap=3000, name=b"far")
    return b


def _b_lowercase():
    b = Slice()
    b.add(rl=12, ap=3, features=[(2, "X", 0), (6, "X", 3), (9, "b", b"a")])
    return b


SLICES = {
    "verbatim-bases": _b_verbatim, "unmapped-and-unknown": _b_unmapped,
    "mapped-unknown-bases": _b_unknown_mapped,
    "reference-fill-and-substitution": _b_reference_fill,
    "single-base-features-and-qual-overlays": _b_single_bases,
    "colliding-overlays-in-feature-order": _b_colliding,
    "every-feature-code": _b_every_feature, "lowercase-reference":
    _b_lowercase,
}

LOWER = FastaReferenceSource(b">c1\n" + b"acgtNacgtR" * 100 + b"\n")


@pytest.mark.parametrize("with_ref", [True, False], ids=["ref", "no-ref"])
@pytest.mark.parametrize("case", sorted(SLICES))
def test_fixtures_equal_the_twin_and_the_record_path(case, with_ref):
    ref = (LOWER if case == "lowercase-reference" else REF) if with_ref \
        else None
    cols = _three(SLICES[case](), ref)
    if with_ref or case in ("verbatim-bases", "unmapped-and-unknown"):
        assert isinstance(cols, dict), cols


def test_colliding_overlays_resolve_as_the_record_path():
    cols = _three(_b_colliding(), REF)
    assert cols["qual_cat"][2] == 7            # rec 0, pos 3: 'q' won
    assert cols["qual_cat"][8 + 2] == 42       # rec 1, pos 3: 'Q' won
    assert list(cols["qual_cat"][16 + 2:16 + 4]) == [9, 3]


def test_multi_reference_slice_takes_the_twin():
    b = Slice(ref_seq_id=-2)
    b.ints["RI"] = bytearray()
    for ri, kw in ((0, dict(rl=8, ap=11, features=[])),
                   (1, dict(rl=8, ap=21, features=[(3, "X", 1)]))):
        b.ints["RI"] += write_itf8(ri)
        b.add(**kw)
    comp, hdr, core, external = b.build()
    comp.data_series["RI"] = ExternalEncoding(99)
    external[99] = bytes(b.ints["RI"])
    METRICS.reset()
    cols = _decode((comp, hdr, core, external), REF)
    c = _counters()
    assert c["cram.walk_numpy_records"] == 2
    assert "cram.walk_native_records" not in c
    recs = decode_slice_records(comp, hdr, core, dict(external),
                                list(HDR.ref_names), REF)
    _same(cols, records_to_columns(recs, want_names=True))


def test_byte_array_stop_streams_and_a_constant_bs():
    """IN / SC as stop-byte arrays (the CRAM 3.1 cell's layout) and BS as a
    0-bit Huffman constant."""
    from hadoop_bam_tpu.formats.cram import read_itf8

    b = Slice()
    for i in range(6):
        b.add(rl=20, ap=5 + 9 * i, features=[
            (1, "S", b"TT"), (6, "X", 1), (9, "I", b"GAG"), (15, "X", 1)])
    comp, hdr, core, external = b.build()
    cid = max(external) + 1
    for series in ("IN", "SC"):
        lens, vals = bytes(b.arr_len[series]), bytes(b.arr_val[series])
        chunks, at, p = [], 0, 0
        while p < len(lens):
            ln, p = read_itf8(lens, p)
            chunks.append(vals[at:at + ln] + b"\0")
            at += ln
        comp.data_series[series] = ByteArrayStopEncoding(0, cid)
        external[cid] = b"".join(chunks)
        cid += 1
    comp.data_series["BS"] = HuffmanEncoding([1], [0])
    built = (comp, hdr, core, external)
    cols = _both(built, REF)
    recs = decode_slice_records(comp, hdr, core, dict(external),
                                list(HDR.ref_names), REF)
    _same(cols, records_to_columns(recs, want_names=True))


def test_embedded_reference():
    b = Slice()
    b.add(rl=10, ap=3, features=[(4, "X", 2)])
    b.add(rl=10, ap=8, features=[(2, "D", 2)])
    comp, hdr, core, external = b.build()
    hdr.start = 1
    hdr.embedded_ref_id = 77
    external[77] = b"ACGTTGCAAC" * 4
    cols = _both((comp, hdr, core, external), None)
    recs = decode_slice_records(comp, hdr, core, dict(external),
                                list(HDR.ref_names), None)
    _same(cols, records_to_columns(recs, want_names=True))


def test_reads_past_the_contig_end_take_the_record_path():
    b = Slice()
    b.add(rl=10, ap=5, features=[])
    b.add(rl=10, ap=99_995, features=[(3, "X", 1)])
    assert _both(b.build(), REF) is None


GEOMETRY = {
    "overlapping-features": [(3, "b", b"GGG"), (4, "X", 1)],
    "feature-past-the-read": [(11, "X", 1)],
    "zero-length-feature-past-the-read": [(11, "D", 2)],
    "array-overruns-the-read": [(8, "S", b"TTTT")],
    "q-overruns-the-read": [(8, "q", bytes([1, 2, 3, 4]))],
    "feature-before-the-read": [(0, "X", 1)],
}


@pytest.mark.parametrize("case", sorted(GEOMETRY))
def test_geometry_the_record_path_must_judge(case):
    """Each check that sends a slice to the record path, alone in an
    otherwise clean slice: both walks decline it."""
    b = Slice()
    b.add(rl=10, ap=5, features=[(2, "X", 1)])
    b.add(rl=10, ap=9, features=GEOMETRY[case])
    assert _both(b.build(), REF) is None


def test_a_bad_substitution_code_raises_on_both_walks():
    b = Slice()
    b.add(rl=10, ap=5, features=[(3, "X", 1)])
    b.add(rl=10, ap=8, features=[(4, "X", 7)])
    assert _both(b.build(), REF) is CRAMError
    # ... but a run outside the window anywhere sends the slice on first
    b.add(rl=10, ap=99_996, features=[])
    assert _both(b.build(), REF) is None


def test_unknown_bases_codes_validated_on_both_walks():
    for ref in (None, REF):
        b = Slice()
        b.add(rl=6, ap=5, cf=CF_UNKNOWN_BASES | CF_QUAL_STORED,
              features=[(3, "X", 0xFF)])
        b.add(rl=4, ap=20, features=[(1, "b", b"ACGT")], name=b"ok")
        assert _both(b.build(), ref) is CRAMError


def test_missing_reference_and_unknown_code():
    b = Slice()
    b.add(rl=10, ap=5, features=[])
    assert _both(b.build(), None) is None
    b = Slice()
    b.add(rl=4, ap=5, features=[(1, "b", b"ACGT")])
    comp, hdr, core, external = b.build()
    external[comp.data_series["FC"].content_id] = b"z"
    assert _both((comp, hdr, core, external), REF) is CRAMError


@pytest.mark.parametrize("maker", ["mixed-cigars", "bench-fixture-layout"])
def test_file_parity(maker, monkeypatch):
    import test_cram_columns as T
    from hadoop_bam_tpu.formats.sam import SamRecord

    recs = []
    for i in range(240):
        if maker == "mixed-cigars":
            cig, seq = [("20M", "ACGTACGTACGTACGTACGT"),
                        ("8M4I8M", "ACGTACGTTTTTACGTACGT"),
                        ("5S10M5S", "GGGGGACGTACGTACGGGGG"),
                        ("10M6D10M", "ACGTACGTACACGTACGTAC")][i % 4]
            flag, pnext, rnext = 0, 0, "*"
        else:
            cig, seq = "12M", "ACGTACGTACGT"
            flag, pnext, rnext = (99 if i % 2 == 0 else 147), 60 + i, "="
        recs.append(SamRecord(
            qname=f"q{i // 2}", flag=flag, rname="c1", pos=1 + 7 * i,
            mapq=50 + i % 10, cigar=cig, rnext=rnext, pnext=pnext, tlen=0,
            seq=seq, qual="".join(chr(33 + (i + j) % 40)
                                  for j in range(len(seq)))))
    METRICS.reset()
    fast, raw = _roundtrip_columns(recs)
    assert _counters()["cram.walk_native_records"] == len(recs)
    monkeypatch.setattr(native, "cram_slice_rebuild", lambda *a, **k: None)
    METRICS.reset()
    slow, _ = _roundtrip_columns(recs)
    assert _counters()["cram.walk_numpy_records"] == len(recs)
    _same(fast, slow)
    T._assert_columns_match(fast, raw)


def _random_slice(rng, n_recs):
    b = Slice()
    ap = 5
    for _ in range(n_recs):
        if rng.random() < 0.2:
            rl = rng.randint(0, 30)
            cf = CF_QUAL_STORED if rng.random() < 0.7 else 0
            if rng.random() < 0.1:
                cf |= CF_UNKNOWN_BASES
            b.add(bf=0x4, cf=cf, rl=rl, ap=0,
                  ba=bytes(rng.choice(b"ACGTN") for _ in range(rl)),
                  qual=bytes(rng.randrange(40) for _ in range(rl))
                  if cf & CF_QUAL_STORED else None)
            continue
        rl = rng.randint(1, 40)
        feats = []
        rp = 1
        while rp <= rl and rng.random() < 0.6:
            fpos = rng.randint(rp, rl)
            room = rl - fpos + 1
            code = rng.choice("bXBIiSqQDNPH")
            if code in "bIS":
                ln = rng.randint(1, room)
                feats.append((fpos, code, bytes(
                    rng.choice(b"ACGT") for _ in range(ln))))
                rp = fpos + ln
            elif code == "q":
                feats.append((fpos, code, bytes(
                    rng.randrange(40) for _ in range(rng.randint(0, room)))))
                rp = fpos
            elif code in "DN":
                feats.append((fpos, code, rng.randint(0, 9)))
                rp = fpos
            elif code in "PH":
                feats.append((fpos, code, rng.randint(1, 5)))
                rp = fpos
            elif code == "X":
                feats.append((fpos, code, rng.randrange(4)))
                rp = fpos + 1
            elif code == "B":
                feats.append((fpos, code,
                              (rng.choice(b"ACGT"), rng.randrange(40))))
                rp = fpos + 1
            elif code == "i":
                feats.append((fpos, code, rng.choice(b"ACGT")))
                rp = fpos + 1
            else:
                feats.append((fpos, code, rng.randrange(40)))
                rp = fpos
        cf = CF_QUAL_STORED if rng.random() < 0.8 else 0
        if rng.random() < 0.1:
            cf |= CF_UNKNOWN_BASES
        b.add(rl=rl, ap=ap, cf=cf, features=feats, mq=rng.randrange(60),
              qual=bytes(rng.randrange(40) for _ in range(rl))
              if cf & CF_QUAL_STORED else None, name=b"r")
        ap += rng.randint(0, 20)
    return b


@pytest.mark.parametrize("seed", range(6))
def test_randomized_slice_fuzz(seed):
    """Random slices mixing every feature code at random positions,
    mapped, unmapped and unknown-bases reads, stored and missing
    qualities, empty reads and zero-length arrays."""
    rng = random.Random(4100 + seed)
    for _ in range(8):
        b = _random_slice(rng, rng.randint(1, 40))
        assert isinstance(_three(b, REF), dict)


# ---------------------------------------------------------------------------
# corrupt streams: one outcome from both walks
# ---------------------------------------------------------------------------

def _stream_cids(built):
    """series name -> content id of every stream a slice built by
    ``Slice`` holds (each byte array's lengths and values apart)."""
    comp = built[0]
    out = {}
    for name, enc in comp.data_series.items():
        if isinstance(enc, ExternalEncoding):
            out[name] = enc.content_id
        elif hasattr(enc, "len_encoding"):
            out[name + "_len"] = enc.len_encoding.content_id
            out[name] = enc.val_encoding.content_id
    return out


TRUNCATED = ("QS", "BA", "BS", "BB", "BB_len", "QQ", "QQ_len", "IN",
             "IN_len", "SC", "SC_len", "DL", "RS", "FC", "FP", "FN", "RL",
             "AP")


@pytest.mark.parametrize("series", TRUNCATED)
def test_every_cut_of_each_stream(series):
    b = Slice()
    _every_feature(b)
    b.add(bf=0x4, rl=6, ap=0, ba=b"ACGTNN")
    _every_feature(b, ap=70, name=b"two")
    comp, hdr, core, external = b.build()
    cid = _stream_cids((comp, hdr, core, external))[series]
    whole = external[cid]
    assert whole
    outcomes = set()
    for cut in range(len(whole)):
        ext = dict(external)
        ext[cid] = whole[:cut]
        got = _both((comp, hdr, core, ext), REF)
        outcomes.add(got if not isinstance(got, dict) else dict)
    assert None in outcomes or CRAMError in outcomes


def test_byte_flips():
    rng = random.Random(41)
    b = Slice()
    _every_feature(b)
    b.add(bf=0x4, rl=6, ap=0, ba=b"ACGTNN")
    _every_feature(b, ap=70, name=b"two")
    _every_feature(b, ap=99, cf=CF_UNKNOWN_BASES | CF_QUAL_STORED)
    built = b.build()
    comp, hdr, core, external = built
    cids = sorted(set(_stream_cids(built).values()) - {
        comp.data_series["RN"].content_id})
    seen = set()
    for _ in range(300):
        cid = rng.choice(cids)
        data = bytearray(external[cid])
        if not data:
            continue
        data[rng.randrange(len(data))] ^= 1 << rng.randrange(8)
        ext = dict(external)
        ext[cid] = bytes(data)
        got = _both((comp, hdr, core, ext), REF)
        seen.add(got if not isinstance(got, dict) else dict)
    assert dict in seen and None in seen


# ---------------------------------------------------------------------------
# threads, a host without the library, the benchmark's file
# ---------------------------------------------------------------------------

def test_eight_threads_on_one_slice_give_one_answer():
    rng = random.Random(8)
    built = _random_slice(rng, 300).build()
    want = _plain(_decode(built, REF))
    assert isinstance(want, dict)
    with cf.ThreadPoolExecutor(8) as ex:
        got = list(ex.map(lambda _: _plain(_decode(built, REF)), range(64)))
    for g in got:
        _same(g, want)


def test_without_the_library_the_columnar_decoder_declines(monkeypatch):
    built = _b_reference_fill().build()
    monkeypatch.setattr(native, "load", lambda: None)
    METRICS.reset()
    assert _decode(built, REF) is None
    c = _counters()
    assert "cram.walk_native_records" not in c
    assert "cram.walk_numpy_records" not in c


def test_refused_arguments_take_the_twin(monkeypatch):
    """The wrapper refuses what the kernel cannot take (feature positions
    that do not pair with their codes) and the twin decodes the slice."""
    built = _b_every_feature().build()
    want = _plain(_decode(built, REF))
    real = native.cram_slice_rebuild
    monkeypatch.setattr(native, "cram_slice_rebuild",
                        lambda bf, cf_, rl, pos, fn, mq, fc, fp, *a:
                        real(bf, cf_, rl, pos, fn, mq, fc, fp[:-1], *a))
    METRICS.reset()
    _same(_plain(_decode(built, REF)), want)
    assert _counters()["cram.walk_numpy_records"] == built[1].n_records


@pytest.fixture(scope="module")
def tiny_cram(tmp_path_factory):
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = json.load(open(os.path.join(
        root, "benchmark", "configs", "na12878-chr20-cram31-x1.json")))
    d = str(tmp_path_factory.mktemp("cram31_walk"))
    return C.write_cram(d, 3000000041, cfg["tiny"]["chunks"],
                        cfg["tiny"]["chunk_records"])


def test_a_scan_of_the_benchmarks_file_walks_every_read_natively(tiny_cram):
    import dataclasses

    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.parallel.pipeline import cram_seq_stats_file

    written, sums = tiny_cram
    METRICS.reset()
    res = cram_seq_stats_file(written.cram, config=dataclasses.replace(
        DEFAULT_CONFIG, cram_reference_source_path=written.fasta))
    c = _counters()
    assert res["n_reads"] == sums.n
    assert c["cram.walk_native_records"] == sums.n == c["pipeline.records"]
    assert "cram.walk_numpy_records" not in c
    assert c["cram.columnar_records"] == sums.n


def test_the_benchmarks_file_equals_the_twin(tiny_cram):
    from hadoop_bam_tpu.api.cram_dataset import open_cram
    from hadoop_bam_tpu.split.cram_planner import read_cram_span_columns
    import dataclasses

    from hadoop_bam_tpu.config import DEFAULT_CONFIG

    written, _ = tiny_cram
    ds = open_cram(written.cram, dataclasses.replace(
        DEFAULT_CONFIG, cram_reference_source_path=written.fasta))
    ref = FastaReferenceSource(written.fasta)
    span = ds.spans(num_spans=1)[0]
    fast = read_cram_span_columns(written.cram, span, header=ds.header,
                                  ref_source=ref)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(native, "cram_slice_rebuild", lambda *a, **k: None)
        slow = read_cram_span_columns(written.cram, span, header=ds.header,
                                      ref_source=ref)
    _same(fast, slow)
