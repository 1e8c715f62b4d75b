"""The FASTQ tokenise + 4-bit pack as one native pass
(native/hbam_native.cpp::hbam_fastq_tokenize, utils/native.py::fastq_tokenize)
against its NumPy twin and oracle
(api/read_datasets.py::_fastq_text_to_payload_tiles_numpy):

- the same tiles byte for byte — ``seq``, ``qual``, ``lengths`` — on every
  text both accept;
- the same refusals, each the twin's own ``FastqError``;
- ``fastq.tokenize_native_records`` / ``fastq.tokenize_numpy_records`` say
  whose rows a scan's were, and ``native.load()`` alone chooses;
- the benchmark's ``fastq.tokenize_native_share`` reads them.
"""
import dataclasses
import gzip
import json
import os
import random
import sys

import numpy as np
import pytest

from hadoop_bam_tpu.api import read_datasets
from hadoop_bam_tpu.api.read_datasets import (
    _NIBBLE_CODE, _fastq_text_to_payload_tiles_numpy,
    fastq_text_to_payload_tiles,
)
from hadoop_bam_tpu.config import DEFAULT_CONFIG
from hadoop_bam_tpu.formats.fastq import FastqError
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import MetricsContext

import hiseq_fastq_reference as H

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native library not built")

IUPAC = b"ACGTNacgtnMRSVWYHKDBmrsvwyhkdb=.-*"


def record(name: bytes, seq: bytes, qual: bytes, eol: bytes = b"\n"
           ) -> bytes:
    return b"@" + name + eol + seq + eol + b"+" + eol + qual + eol


def random_chunk(rng: random.Random, n: int, max_read: int, offset: int = 33,
                 eol: bytes = b"\n", alphabet: bytes = b"ACGTN") -> bytes:
    """``n`` well-formed records: uneven lengths (zero too), qualities that
    open with '@' and '+', a repeated name on some third lines."""
    out = []
    for i in range(n):
        ln = rng.choice((0, 1, 2, max_read)) if rng.random() < 0.1 \
            else rng.randint(0, max_read)
        seq = bytes(rng.choice(alphabet) for _ in range(ln))
        qual = bytes(rng.randint(offset, offset + 41) for _ in range(ln))
        if ln and rng.random() < 0.2:
            qual = rng.choice((b"@", b"+")) + qual[1:] if offset == 33 \
                else qual
        name = b"r%d:%d %d:N:0" % (i, rng.randint(1, 99999), 1 + i % 2)
        third = b"+" + name if rng.random() < 0.1 else b"+"
        out.append(b"@" + name + eol + seq + eol + third + eol + qual + eol)
    return b"".join(out)


def hiseq_text(read: int) -> bytes:
    return next(H.iter_chunks(5, read, 192, 192))[0]


def both(text, seq_stride, qual_stride, max_len, qual_offset=33):
    got = native.fastq_tokenize(text, _NIBBLE_CODE, seq_stride, qual_stride,
                                max_len, qual_offset)
    want = _fastq_text_to_payload_tiles_numpy(text, seq_stride, qual_stride,
                                              max_len, qual_offset)
    return got, want


def assert_same_tiles(got, want):
    assert got is not None, "the native pass refused a text NumPy takes"
    for g, w, dtype in zip(got, want, (np.uint8, np.uint8, np.int32)):
        assert g.dtype == w.dtype == dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


_RNG = random.Random(37)
HIGH = bytes(range(128, 256))
PARITY = {
    # name: (text, seq_stride, qual_stride, max_len, qual_offset)
    "hiseq_r1": (hiseq_text(1), 80, 160, 160, 33),
    "hiseq_r2": (hiseq_text(2), 64, 128, 101, 33),
    "crlf": (random_chunk(_RNG, 40, 30, eol=b"\r\n"), 16, 32, 32, 33),
    "crlf_no_final_newline":
        (random_chunk(_RNG, 9, 30, eol=b"\r\n")[:-2], 16, 32, 32, 33),
    "crlf_final_cr_only":
        (random_chunk(_RNG, 9, 30, eol=b"\r\n")[:-1], 16, 32, 32, 33),
    "no_final_newline": (random_chunk(_RNG, 12, 30)[:-1], 16, 32, 32, 33),
    "empty_final_line": (record(b"a", b"ACGT", b"IIII")
                         + record(b"b", b"", b""), 8, 8, 8, 33),
    "lone_cr_behind_the_last_record":
        (record(b"a", b"ACGT", b"IIII") + b"\r", 8, 8, 8, 33),
    "zero_length_reads": (b"".join(record(b"z%d" % i, b"", b"")
                                   for i in range(7)), 8, 8, 8, 33),
    "cr_inside_a_read": (record(b"a", b"AC\rGT\r", b"II\rII\r", b"\r\n"),
                         8, 8, 8, 33),
    "over_max_len_odd": (random_chunk(_RNG, 60, 40), 16, 32, 17, 33),
    "over_max_len_even": (random_chunk(_RNG, 60, 40), 16, 32, 18, 33),
    "max_len_zero": (random_chunk(_RNG, 10, 12), 8, 8, 0, 33),
    "rows_cut_at_their_strides": (random_chunk(_RNG, 60, 40), 5, 7, 40, 33),
    "odd_seq_stride_cut": (random_chunk(_RNG, 30, 21), 10, 19, 21, 33),
    "zero_strides": (random_chunk(_RNG, 5, 9), 0, 0, 9, 33),
    "iupac_and_lower_case":
        (random_chunk(_RNG, 50, 33, alphabet=IUPAC), 17, 33, 33, 33),
    "bytes_over_127": (record(b"h\xff", HIGH, HIGH) * 3, 64, 128, 128, 33),
    "quality_bytes_under_the_offset":
        (record(b"q", b"ACGTACGT", b" !\"#\t\x00\x1f~"), 8, 8, 8, 33),
    "qual_offset_64": (random_chunk(_RNG, 50, 30, offset=64), 16, 32, 32,
                       64),
    "qual_offset_64_edges": (record(b"e", b"ACGTA", b"@@\x9d\x9d~"), 8, 8, 8,
                             64),
    "qual_offset_0": (record(b"o", b"ACG", b"\x00\x01]"), 8, 8, 8, 0),
    "empty_chunk": (b"", 8, 8, 8, 33),
    "only_a_lone_cr": (b"\r", 8, 8, 8, 33),
    "one_record": (record(b"one", b"ACGTN", b"IIII#"), 8, 8, 8, 33),
    "one_record_no_final_newline":
        (record(b"one", b"ACGTN", b"IIII#")[:-1], 8, 8, 8, 33),
    "at_and_plus_inside_fields":
        (record(b"a@+", b"ACGT", b"@+@+") + record(b"b", b"AC", b"+@"),
         8, 8, 8, 33),
    "a_memoryview": (memoryview(random_chunk(_RNG, 8, 20)), 10, 20, 20, 33),
}


@needs_native
@pytest.mark.parametrize("case", sorted(PARITY))
def test_native_tiles_equal_numpy_tiles(case):
    text, seq_stride, qual_stride, max_len, qual_offset = PARITY[case]
    got, want = both(text, seq_stride, qual_stride, max_len, qual_offset)
    assert_same_tiles(got, want)
    # the public function took the native rows, and says so
    with MetricsContext() as m:
        pub = fastq_text_to_payload_tiles(text, seq_stride, qual_stride,
                                          max_len, qual_offset)
    assert_same_tiles(pub, want)
    assert m.get("fastq.tokenize_native_records") == want[2].size
    assert m.get("fastq.tokenize_numpy_records") == 0


@needs_native
@pytest.mark.parametrize("seed", range(8))
def test_native_tiles_equal_numpy_tiles_fuzz(seed):
    """40 random well-formed chunks a seed: line ends, lengths, strides,
    the cut at ``max_len``, the offset and the alphabet all drawn."""
    rng = random.Random(1000 + seed)
    for _ in range(40):
        max_read = rng.choice((0, 1, 7, 36, 101, 151))
        offset = rng.choice((33, 33, 64))
        eol = rng.choice((b"\n", b"\n", b"\r\n"))
        text = random_chunk(
            rng, rng.randint(0, 60), max_read, offset, eol=eol,
            alphabet=rng.choice((b"ACGT", b"ACGTN", IUPAC, HIGH + b"ACGT")))
        # no final newline, where the last read is not a zero-length one
        # (its empty quality line would then be no line at all)
        if text and not text.endswith(eol + eol) and rng.random() < 0.3:
            text = text[:-1]
        max_len = rng.choice((max_read, max(0, max_read - 3), max_read + 5))
        seq_stride = rng.choice(((max_len + 1) // 2, max_len // 3,
                                 (max_len + 1) // 2 + 3))
        qual_stride = rng.choice((max_len, max_len // 2, max_len + 4))
        got, want = both(text, seq_stride, qual_stride, max_len, offset)
        assert_same_tiles(got, want)


REFUSALS = {
    # name: (text, qual_offset, what the FastqError says)
    "three_lines": (b"@a\nACGT\n+\n", 33, "3 lines (not 4n)"),
    "five_lines": (record(b"a", b"AC", b"II") + b"@b\n", 33,
                   "5 lines (not 4n)"),
    "an_empty_line_behind_the_last_record":
        (record(b"a", b"AC", b"II") + b"\n", 33, "5 lines (not 4n)"),
    "a_last_record_with_no_quality_line":
        (record(b"a", b"AC", b"II") + b"@b\n\n+\n", 33, "7 lines (not 4n)"),
    "only_newlines": (b"\n\n\n\n", 33, "malformed FASTQ record at line 0"),
    "no_newline_at_all": (b"@aACGT+IIII", 33, "1 lines (not 4n)"),
    "no_at": (record(b"a", b"AC", b"II") + b"b\nACGT\n+\nIIII\n", 33,
              "malformed FASTQ record at line 4"),
    "no_plus": (b"@a\nACGT\n-\nIIII\n", 33,
                "malformed FASTQ record at line 0"),
    "an_empty_name_line": (b"\nACGT\n+\nIIII\n", 33,
                           "malformed FASTQ record at line 0"),
    "lengths_differ": (b"@a\nACGT\n+\nII\n", 33,
                       "SEQ/QUAL length mismatch"),
    "lengths_differ_by_a_cr": (b"@a\nACGT\n+\nIIII\r\r\n", 33,
                               "SEQ/QUAL length mismatch"),
    "quality_under_the_offset": (record(b"a", b"ACGT", b"hh5h"), 64,
                                 "quality out of range"),
    "quality_over_phred_93": (record(b"a", b"ACGT", b"hh\x9eh"), 64,
                              "quality out of range"),
}


@needs_native
@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_native_refusals_are_the_numpy_errors(case):
    text, qual_offset, says = REFUSALS[case]
    assert native.fastq_tokenize(text, _NIBBLE_CODE, 8, 8, 8,
                                 qual_offset) is None
    with pytest.raises(FastqError) as want:
        _fastq_text_to_payload_tiles_numpy(text, 8, 8, 8, qual_offset)
    with MetricsContext() as m, pytest.raises(FastqError) as got:
        fastq_text_to_payload_tiles(text, 8, 8, 8, qual_offset)
    assert str(got.value) == str(want.value) and says in str(got.value)
    assert m.get("fastq.tokenize_native_records") == 0
    assert m.get("fastq.tokenize_numpy_records") == 0


@needs_native
def test_the_quality_guard_reads_the_full_field():
    """A bad quality past ``max_len`` refuses the chunk under offset 64 (as
    ``convert_quality`` would) and is clipped away under offset 33."""
    text = record(b"a", b"ACGTACGT", b"hhhhhh5h")
    assert native.fastq_tokenize(text, _NIBBLE_CODE, 8, 8, 4, 64) is None
    with pytest.raises(FastqError, match="quality out of range"):
        fastq_text_to_payload_tiles(text, 8, 8, 4, qual_offset=64)
    assert_same_tiles(*both(text, 8, 8, 4, 33))


@needs_native
def test_arguments_the_pass_cannot_take_go_to_numpy():
    text = record(b"a", b"ACGT", b"IIII")
    for max_len, qual_offset in ((1 << 40, 33), (8, 300)):
        assert native.fastq_tokenize(text, _NIBBLE_CODE, 8, 8, max_len,
                                     qual_offset) is None
    with MetricsContext() as m:
        got = fastq_text_to_payload_tiles(text, 8, 8, 1 << 40)
    assert got[2].tolist() == [4]
    assert m.get("fastq.tokenize_numpy_records") == 1
    with pytest.raises(ValueError):
        native.fastq_tokenize(text, _NIBBLE_CODE[:16], 8, 8, 8, 33)


@needs_native
def test_the_code_table_is_the_one_passed_in():
    text = record(b"a", b"ACGTN", b"IIIII")
    table = np.arange(256, dtype=np.uint8) & 15
    seq, _qual, _len = native.fastq_tokenize(text, table, 4, 8, 8, 33)
    want = [(ord("A") & 15) << 4 | ord("C") & 15,
            (ord("G") & 15) << 4 | ord("T") & 15, (ord("N") & 15) << 4, 0]
    assert seq.tolist() == [want]


# ---------------------------------------------------------------------------
# the scan: native.load() alone chooses, and the counters say who ran
# ---------------------------------------------------------------------------

N_SCAN = 3000


@pytest.fixture(scope="module")
def lane(tmp_path_factory):
    rng = random.Random(3)
    text = random_chunk(rng, N_SCAN, 120)
    d = tmp_path_factory.mktemp("lane")
    gz, plain = str(d / "lane.fastq.gz"), str(d / "lane.fastq")
    with open(gz, "wb") as fh:
        fh.write(gzip.compress(text, 4))
    with open(plain, "wb") as fh:
        fh.write(text)
    return gz, plain


def scan(path):
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file

    cfg = dataclasses.replace(DEFAULT_CONFIG, split_size=65536)
    with MetricsContext() as m:
        out = fastq_seq_stats_file(path, config=cfg)
    return out, m


@pytest.mark.parametrize("which", ["gz", "plain"])
def test_scan_runs_numpy_when_the_library_does_not_load(lane, which,
                                                         monkeypatch):
    path = lane[which == "plain"]
    want, _m = scan(path)
    monkeypatch.setattr(native, "load", lambda: None)
    got, m = scan(path)
    assert m.get("fastq.tokenize_numpy_records") == N_SCAN
    assert m.get("fastq.tokenize_native_records") == 0
    assert got["n_reads"] == want["n_reads"] == N_SCAN
    assert got["base_hist"].tolist() == want["base_hist"].tolist()
    assert got["mean_gc"] == want["mean_gc"]
    assert got["mean_qual"] == want["mean_qual"]


@needs_native
@pytest.mark.parametrize("which", ["gz", "plain"])
def test_scan_runs_the_native_pass_when_the_library_loads(lane, which):
    out, m = scan(lane[which == "plain"])
    assert out["n_reads"] == N_SCAN
    assert m.get("fastq.tokenize_native_records") == N_SCAN
    assert m.get("fastq.tokenize_numpy_records") == 0
    assert m.get("pipeline.records") == N_SCAN
    assert m.get("fastq.tokenize_busy_ns") > 0


@needs_native
def test_the_pass_allocates_nothing_but_its_tiles(monkeypatch):
    """Apart from the three outputs no array that grows with the chunk is
    made on the Python side: the twin's helpers are never entered."""
    def boom(*_a, **_k):
        raise AssertionError("the NumPy twin ran")

    monkeypatch.setattr(read_datasets, "_scan_lines", boom)
    monkeypatch.setattr(read_datasets, "_pack_seq_qual_tiles", boom)
    text = hiseq_text(1)
    seq, qual, lengths = fastq_text_to_payload_tiles(text, 80, 160, 160)
    assert seq.shape == (192, 80) and qual.shape == (192, 160)
    assert lengths.tolist() == [101] * 192
    for a in (seq, qual, lengths):
        assert a.flags.owndata and a.flags.c_contiguous


# ---------------------------------------------------------------------------
# the benchmark's reader of the two counters
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bench_run():
    sys.path.insert(0, ROOT)
    try:
        from benchmark import run
    finally:
        sys.path.remove(ROOT)
    return run


@pytest.mark.parametrize("counters,want", [
    ({"fastq.tokenize_native_records": 4096}, 100.0),
    ({"fastq.tokenize_numpy_records": 4096}, 0.0),
    ({"fastq.tokenize_native_records": 3072,
      "fastq.tokenize_numpy_records": 1024}, 75.0),
    ({"pipeline.records": 4096}, None),       # a program without the pass
])
def test_tokenize_native_share_metric(bench_run, counters, want):
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    entry = [m for m in bench["per_layer"]
             if m["name"] == "fastq.tokenize_native_share"]
    assert len(entry) == 1      # later PRs append their own after it
    assert entry[0]["workloads"] == ["hiseq-fastqgz-seqstats"]
    assert entry[0]["moves"] == "scan_records_per_s"
    said = []
    got = bench_run.layer_metrics(
        {"per_layer": entry}, {"name": "hiseq-fastqgz-seqstats"},
        {"snapshot": {"counters": counters}, "window_s": 6.0}, said.append)
    if want is None:
        assert got == {} and "left out" in said[0]
    else:
        assert got == {"fastq.tokenize_native_share":
                       {"value": want, "unit": "%"}}
    # another cell never reads it
    assert bench_run.layer_metrics(
        {"per_layer": entry}, {"name": "chr20-flagstat"},
        {"snapshot": {"counters": counters}, "window_s": 6.0},
        said.append) == {}
