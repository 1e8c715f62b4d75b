"""A gzip'd FASTQ / QSEQ file as a stream of record-aligned chunks
(split/read_planners.py::iter_gzip_text_chunks,
api/read_datasets.py::iter_span_chunks) and the driver that tokenises them
while the file inflates (parallel/pipeline.py::_read_stats_impl).

- the chunks concatenate to ``gzip.decompress`` of the file byte for byte, at
  every chunk grain and wherever a compressed read ends;
- members that follow one another — a ``cat`` of files, a ``bgzip``ped file,
  an empty member — stream as one;
- a truncated member, a flipped CRC32, a wrong ISIZE and bytes that start no
  member each fail ``hbam seq-stats`` non-zero with no totals printed;
- the text alive at once is bounded by the chunks in flight, not the file;
- the verb runs ``plan.execute`` and builds its device step once.
"""
import contextlib
import dataclasses
import gzip
import io
import random
import struct
import zlib

import pytest

from hadoop_bam_tpu.config import DEFAULT_CONFIG
from hadoop_bam_tpu.formats.fastq import FastqError
from hadoop_bam_tpu.obs import disable_tracing, enable_tracing
from hadoop_bam_tpu.split.read_planners import (
    iter_gzip_text_chunks, iter_on_thread,
)
from hadoop_bam_tpu.utils.metrics import MetricsContext
from hadoop_bam_tpu.utils.pools import text_stream_window
from hadoop_bam_tpu.utils.seekable import ByteSource

import kgp3_reference as K


def fastq_text(n: int, seed: int = 11, max_len: int = 120) -> bytes:
    """Reads of uneven lengths, qualities that open with '@' and '+' (the
    leads a cut may not trust), CASAVA 1.8 names with both filter flags."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        ln = rng.randint(1, max_len)
        seq = "".join(rng.choice("ACGTN") for _ in range(ln))
        qual = rng.choice("@+I") + "".join(
            chr(rng.randint(35, 74)) for _ in range(ln - 1))
        flag = "Y" if i % 7 == 0 else "N"
        out.append(f"@M1:7:FC1:2:{1101 + i // 40}:{rng.randint(1, 20000)}:"
                   f"{1000 + 3 * i} 1:{flag}:0:ACGT\n{seq}\n+\n{qual}\n")
    return "".join(out).encode()


def run_cli(argv):
    from hadoop_bam_tpu.tools.cli import main

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = main(list(argv))
    return rc, out.getvalue(), err.getvalue()


class CutSource(ByteSource):
    """A source whose reads never cross ``cut``: the first compressed read
    ends there, wherever the caller meant it to end."""

    def __init__(self, data: bytes, cut: int):
        self._data, self._cut, self.size = data, cut, len(data)

    def pread(self, offset: int, size: int) -> bytes:
        end = offset + size
        if offset < self._cut:
            end = min(end, self._cut)
        return self._data[offset:end]


# ---------------------------------------------------------------------------
# the stream is the file
# ---------------------------------------------------------------------------

TEXT = fastq_text(600)
_LINES = TEXT.split(b"\n")[:-1]
RECORD_MAX = max(sum(len(ln) + 1 for ln in _LINES[i:i + 4])
                 for i in range(0, len(_LINES), 4))


@pytest.mark.parametrize("grain", [1, RECORD_MAX, 1000, 4096, 65536,
                                   len(TEXT), 1 << 22],
                         ids=lambda g: f"grain{g}")
def test_chunks_concatenate_to_the_inflated_file(tmp_path, grain):
    path = str(tmp_path / "r.fastq.gz")
    with open(path, "wb") as fh:
        fh.write(gzip.compress(TEXT, 4))
    chunks = list(iter_gzip_text_chunks(path, grain, 4))
    assert b"".join(chunks) == gzip.decompress(open(path, "rb").read())
    # every chunk is whole records, no larger than the grain unless one
    # record is (the stream then inflates a grain at a time until it has
    # the record whole)
    for c in chunks:
        assert c.startswith(b"@") and c.endswith(b"\n")
        assert c.count(b"\n") % 4 == 0
        assert len(c) <= max(grain, RECORD_MAX + grain)
        assert len(c) <= grain or grain < RECORD_MAX
    if grain >= len(TEXT):
        assert len(chunks) == 1


SMALL = fastq_text(6, seed=3, max_len=30)
SMALL_GZ = gzip.compress(SMALL[:150], 4) + gzip.compress(SMALL[150:], 4)


@pytest.mark.parametrize("cut", range(1, len(SMALL_GZ)))
def test_a_compressed_read_may_end_at_any_offset(cut):
    """Two members, the first read of the file ending at ``cut``: inside a
    header, a deflate block, a trailer, or between the members (where a
    record straddles them)."""
    chunks = list(iter_gzip_text_chunks(CutSource(SMALL_GZ, cut), 64, 4))
    assert b"".join(chunks) == SMALL
    assert all(c.count(b"\n") % 4 == 0 for c in chunks)


def _members(kind: str, text: bytes) -> bytes:
    if kind == "single":
        return gzip.compress(text, 4)
    if kind == "two_members":       # cut inside a record
        return gzip.compress(text[:70001], 4) + gzip.compress(text[70001:],
                                                              4)
    if kind == "empty_member":
        return (gzip.compress(text[:5000], 4) + gzip.compress(b"")
                + gzip.compress(text[5000:], 4) + gzip.compress(b""))
    assert kind == "bgzip"
    return b"".join(K.bgzf(text[i:i + 0xff00], 6)
                    for i in range(0, len(text), 0xff00)) + K.BGZF_EOF


@pytest.fixture(scope="module")
def single_answer(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("fqs") / "single.fastq.gz")
    with open(path, "wb") as fh:
        fh.write(_members("single", TEXT))
    rc, out, _ = run_cli(["seq-stats", path])
    assert rc == 0 and out.startswith("reads\t600\n")
    return out


@pytest.mark.parametrize("kind", ["two_members", "empty_member", "bgzip"])
def test_members_that_follow_each_other_stream_as_one(tmp_path, kind,
                                                      single_answer):
    path = str(tmp_path / f"{kind}.fastq.gz")
    with open(path, "wb") as fh:
        fh.write(_members(kind, TEXT))
    with MetricsContext() as m:
        chunks = list(iter_gzip_text_chunks(path, 4096, 4))
    assert b"".join(chunks) == TEXT
    want = {"two_members": 2, "empty_member": 4,
            "bgzip": -(-len(TEXT) // 0xff00) + 1}[kind]
    assert m.get("fastq.stream_members") == want
    assert m.get("fastq.inflated_bytes") == len(TEXT)
    assert m.get("fastq.compressed_bytes") == len(_members(kind, TEXT))
    assert m.get("fastq.stream_chunks") == len(chunks)
    rc, out, _ = run_cli(["seq-stats", path])
    assert rc == 0 and out == single_answer


# ---------------------------------------------------------------------------
# never a shorter answer
# ---------------------------------------------------------------------------

def _damage(kind: str) -> bytes:
    blob = gzip.compress(TEXT, 4)
    crc, isize = struct.unpack("<II", blob[-8:])
    assert crc == zlib.crc32(TEXT) and isize == len(TEXT)
    if kind == "truncated":
        return blob[:len(blob) * 2 // 3]
    if kind == "truncated_trailer":
        return blob[:-3]
    if kind == "flipped_crc":
        return blob[:-8] + struct.pack("<II", crc ^ 1, isize)
    if kind == "wrong_isize":
        return blob[:-8] + struct.pack("<II", crc, isize + 1)
    if kind == "flipped_bit":       # in the deflate stream
        i = len(blob) // 2
        return blob[:i] + bytes([blob[i] ^ 0x10]) + blob[i + 1:]
    if kind == "second_member_truncated":
        return blob + blob[:len(blob) // 2]
    assert kind == "trailing_garbage"
    return blob + b"\x00" * 16


@pytest.mark.parametrize("kind", [
    "truncated", "truncated_trailer", "flipped_crc", "wrong_isize",
    "flipped_bit", "second_member_truncated", "trailing_garbage"])
def test_a_damaged_member_fails_the_scan_with_no_totals(tmp_path, kind):
    path = str(tmp_path / "bad.fastq.gz")
    with open(path, "wb") as fh:
        fh.write(_damage(kind))
    with pytest.raises(FastqError, match="offset"):
        for _ in iter_gzip_text_chunks(path, 4096, 4):
            pass
    rc, out, err = run_cli(["seq-stats", path])
    assert rc != 0
    assert out == ""                    # no totals, not even `reads`
    assert "error:" in err and "gzip member" in err or "start no" in err


def test_an_error_in_the_stream_reaches_the_consumer_in_order():
    def items():
        yield 1
        yield 2
        raise FastqError("boom at offset 7")

    got = []
    with pytest.raises(FastqError, match="boom"):
        for x in iter_on_thread(items, "hbam-inflate-stream"):
            got.append(x)
    assert got == [1, 2]


def test_closing_the_stream_early_stops_its_thread():
    import threading

    started = threading.Event()

    def items():
        for i in range(10_000):
            started.set()
            yield i

    it = iter_on_thread(items, "hbam-inflate-stream-test")
    assert next(it) == 0 and started.is_set()
    it.close()
    assert not any(t.name == "hbam-inflate-stream-test"
                   for t in threading.enumerate())


# ---------------------------------------------------------------------------
# the driver: bounded memory, the plan, one step
# ---------------------------------------------------------------------------

N_LONG = 4400


@pytest.fixture(scope="module")
def long_file(tmp_path_factory):
    """50 grains of 16 KiB of text (and the same reads as a plain file)."""
    text = fastq_text(N_LONG, seed=5, max_len=150)
    assert len(text) > 50 * 16384
    d = tmp_path_factory.mktemp("fql")
    gz, plain = str(d / "long.fastq.gz"), str(d / "long.fastq")
    with open(gz, "wb") as fh:
        fh.write(gzip.compress(text, 4))
    with open(plain, "wb") as fh:
        fh.write(text)
    return gz, plain, text


def test_text_alive_is_bounded_by_the_chunks_in_flight(long_file):
    from hadoop_bam_tpu.parallel.pipeline import (
        fastq_seq_stats_file, pipeline_grain,
    )

    gz, plain, text = long_file
    cfg = dataclasses.replace(DEFAULT_CONFIG, split_size=16384)
    grain = pipeline_grain(cfg)
    assert grain == 16384
    with MetricsContext() as m:
        got = fastq_seq_stats_file(gz, config=cfg)
    assert got["n_reads"] == N_LONG
    assert m.get("fastq.stream_chunks") >= 50
    assert m.get("fastq.inflated_bytes") == len(text)
    peak = m.get("fastq.stream_peak_text_bytes")
    assert 0 < peak <= (text_stream_window() + 2) * grain < len(text) / 4
    assert m.get("pipeline.records") == N_LONG
    assert m.get("fastq.inflate_busy_ns") > 0
    assert m.get("fastq.tokenize_busy_ns") > 0
    # the plain file of the same reads: many spans, one chunk each, the
    # same lines from the chunk onward — and no stream
    with MetricsContext() as m2:
        want = fastq_seq_stats_file(plain, config=cfg)
    assert m2.get("fastq.stream_chunks") == 0
    assert m2.get("pipeline.records") == N_LONG
    assert got["n_reads"] == want["n_reads"]
    assert got["base_hist"].tolist() == want["base_hist"].tolist()
    assert got["mean_gc"] == pytest.approx(want["mean_gc"], abs=1e-6)
    assert got["mean_qual"] == pytest.approx(want["mean_qual"], abs=1e-4)


def test_the_inflate_runs_on_its_own_thread_a_span_a_chunk(long_file):
    gz, _plain, text = long_file
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file

    cfg = dataclasses.replace(DEFAULT_CONFIG, split_size=65536)
    disable_tracing()
    rec = enable_tracing()
    try:
        with MetricsContext() as m:
            fastq_seq_stats_file(gz, config=cfg)
    finally:
        disable_tracing()
    spans = [(thread, args) for name, _ts, _dur, _tid, thread, args
             in rec.events() if name == "fastq.inflate_wall"]
    assert {t for t, _a in spans} == {"hbam-inflate-stream"}
    assert sum(a["bytes"] for _t, a in spans) == len(text)
    assert [a["chunk"] for _t, a in spans if a["bytes"]] \
        == list(range(m.get("fastq.stream_chunks")))
    tok = {thread for name, _ts, _dur, _tid, thread, _a in rec.events()
           if name == "fastq.tokenize_wall"}
    assert tok and all(t.startswith("hbam-decode") for t in tok)


def test_the_verb_runs_plan_execute_and_builds_its_step_once(long_file,
                                                             tmp_path):
    gz, plain, _text = long_file
    disable_tracing()
    rec = enable_tracing()
    try:
        with MetricsContext() as m:
            # a max_len no other test uses: the step cache is the process's
            for path in (gz, plain, gz):
                rc, out, _ = run_cli(["seq-stats", path, "--max-len",
                                      "136"])
                assert rc == 0 and out.startswith(f"reads\t{N_LONG}\n")
    finally:
        disable_tracing()
    assert m.get("steps.built.hbam_read_stats_step") == 1
    assert m.get("plan.executions") == 3
    walls = [args for name, *_rest, args in rec.events()
             if name == "plan.execute_wall"]
    assert len(walls) == 3
    assert all(a["fmt"] == "fastq" and a["sink"] == "seq_stats"
               for a in walls)
    w = m.snapshot()["wall_timers"]
    assert w["cli.main_wall"] >= w["plan.execute_wall"] > 0


def test_read_stats_plan_names_the_source_and_the_tokeniser():
    from hadoop_bam_tpu.plan import builders

    plan = builders.read_stats_plan("lane_R1.fastq.gz")
    assert (plan.source.fmt, plan.sink.kind) == ("fastq", "seq_stats")
    assert [o.op for o in plan.ops] == ["text_tokenize", "payload_pack",
                                        "seq_stats_reduce"]
    assert dict(plan.ops[0].params) == {"quality_offset": 33,
                                        "filter_failed_qc": False}
    qseq = builders.read_stats_plan("s_1_1_0001_qseq.qseq.gz")
    assert qseq.source.fmt == "qseq"
    assert dict(qseq.ops[0].params)["quality_offset"] == 64
    assert plan.digest() != qseq.digest() \
        != builders.seq_stats_plan("x.bam").digest()
    filt = builders.read_stats_plan(
        "lane_R1.fastq.gz",
        dataclasses.replace(DEFAULT_CONFIG, fastq_filter_failed_qc=True))
    assert filt.digest() != plan.digest()


# ---------------------------------------------------------------------------
# the filter, QSEQ, the object API
# ---------------------------------------------------------------------------

def test_filter_failed_qc_streams_through_the_object_parse(long_file):
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file

    gz, plain, _text = long_file
    cfg = dataclasses.replace(DEFAULT_CONFIG, split_size=65536,
                              fastq_filter_failed_qc=True)
    with MetricsContext() as m:
        got = fastq_seq_stats_file(gz, config=cfg)
    want = fastq_seq_stats_file(plain, config=cfg)
    assert got["n_reads"] == want["n_reads"] \
        == N_LONG - len(range(0, N_LONG, 7))        # every seventh is Y
    assert got["base_hist"].tolist() == want["base_hist"].tolist()
    assert m.get("fastq.stream_chunks") > 1


def _qseq_text(n: int) -> bytes:
    rng = random.Random(9)
    lines = []
    for i in range(n):
        ln = rng.randint(20, 80)
        seq = "".join(rng.choice("ACGT.") for _ in range(ln))
        qual = "".join(chr(rng.randint(66, 104)) for _ in range(ln))
        lines.append(f"M1\t7\t2\t{1101 + i // 50}\t{rng.randint(1, 9999)}\t"
                     f"{i}\tACGT\t1\t{seq}\t{qual}\t{i % 5 != 0:d}\n")
    return "".join(lines).encode()


def test_a_qseq_gz_streams_a_line_a_record(tmp_path):
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file

    text = _qseq_text(3000)
    gz, plain = str(tmp_path / "s.qseq.gz"), str(tmp_path / "s.qseq")
    with open(gz, "wb") as fh:
        fh.write(gzip.compress(text[:100000], 4)
                 + gzip.compress(text[100000:], 4))
    with open(plain, "wb") as fh:
        fh.write(text)
    cfg = dataclasses.replace(DEFAULT_CONFIG, split_size=32768)
    with MetricsContext() as m:
        got = fastq_seq_stats_file(gz, config=cfg)
    want = fastq_seq_stats_file(plain, config=cfg)
    assert got["n_reads"] == want["n_reads"] == 3000
    assert got["base_hist"].tolist() == want["base_hist"].tolist()
    assert got["mean_qual"] == pytest.approx(want["mean_qual"], abs=1e-4)
    # the same names under the format's own prefix
    assert m.get("qseq.stream_chunks") >= len(text) // 32768
    assert m.get("qseq.stream_members") == 2
    assert m.get("qseq.inflated_bytes") == len(text)
    assert 0 < m.get("qseq.stream_peak_text_bytes") \
        <= (text_stream_window() + 2) * 32768
    assert m.get("fastq.stream_chunks") == 0
    chunks = list(iter_gzip_text_chunks(gz, 1000, 1, fmt="qseq"))
    assert b"".join(chunks) == text
    assert all(c.endswith(b"\n") for c in chunks)
    rc, out, _ = run_cli(["seq-stats", gz])
    assert rc == 0 and out.startswith("reads\t3000\n")


def test_the_object_api_keeps_its_results(long_file):
    from hadoop_bam_tpu.api.read_datasets import open_fastq
    from hadoop_bam_tpu.formats.fastq import parse_fastq

    gz, plain, text = long_file
    ds = open_fastq(gz)
    (span,) = ds.spans()                # one span in the plan
    assert (span.start, span.end) == (0, len(open(gz, "rb").read()))
    assert ds.read_span_text(span) == text
    want = parse_fastq(text)
    assert ds.read_span(span) == want
    assert list(ds.records()) == want
    assert list(open_fastq(plain).records()) == want
    # a chunk's text is given up once it is done with
    chunks = list(ds.iter_span_chunks(span, 65536))
    assert all(c.streamed for c in chunks)
    assert b"".join(c.text() for c in chunks) == text
    for c in chunks:
        c.done()
        assert c.text() is None
    (one,) = list(open_fastq(plain).iter_span_chunks(
        open_fastq(plain).spans(num_spans=1)[0], 65536))
    assert not one.streamed and one.text() == text
