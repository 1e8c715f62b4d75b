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
- the verb runs ``plan.execute`` and builds its device step once;
- a file of two speculative chunks or more is inflated by several threads
  (``_SpeculativeMembers``): the same bytes, the same refusals, whatever
  blocks, members and headers the file is made of.
"""
import contextlib
import dataclasses
import gzip
import io
import random
import re
import struct
import zlib

import pytest

from hadoop_bam_tpu.config import DEFAULT_CONFIG
from hadoop_bam_tpu.formats.fastq import FastqError
from hadoop_bam_tpu.obs import disable_tracing, enable_tracing
from hadoop_bam_tpu.split import read_planners
from hadoop_bam_tpu.split.read_planners import (
    _SpeculativeMembers, gzip_speculation, iter_gzip_text_chunks,
    iter_on_thread,
)
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import MetricsContext
from hadoop_bam_tpu.utils.pools import text_stream_window
from hadoop_bam_tpu.utils.seekable import ByteSource, BytesByteSource

import hiseq_fastq_reference as H
from test_deflate_native import reads_text

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


def speculation_budget(path: str, text_bytes: int) -> int:
    """What the inflate workers of ``path``'s stream may hold besides the
    chunks being tokenised (0 where its inflate stays serial): a chunk's
    text is ``chunk`` compressed bytes at the file's ratio and up to a
    block more, ``workers + 1`` of them decoded ahead at 2 B a symbol,
    three resolved behind at 1 B."""
    with open(path, "rb") as fh:
        blob = fh.read()
    how = gzip_speculation(BytesByteSource(blob))
    if how is None:
        return 0
    chunk, workers = how
    piece = 2 * chunk * text_bytes // len(blob) + (1 << 17)
    return (2 * (workers + 1) + 3) * piece


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
    assert (text_stream_window() + 2) * grain < len(text) / 4
    assert 0 < peak <= (text_stream_window() + 2) * grain \
        + speculation_budget(gz, len(text))
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
    # a span a chunk on the stream's thread; where the file is inflated on
    # several threads, a span a task on each of those too
    workers = [(t, a) for t, a in spans if t != "hbam-inflate-stream"]
    spans = [(t, a) for t, a in spans if t == "hbam-inflate-stream"]
    assert sum(a["bytes"] for _t, a in spans) == len(text)
    assert [a["chunk"] for _t, a in spans if a["bytes"]] \
        == list(range(m.get("fastq.stream_chunks")))
    if gzip_speculation(BytesByteSource(open(gz, "rb").read())) is None:
        assert not workers
    else:
        assert {t.rsplit("_", 1)[0] for t, _a in workers} == {"hbam-inflate"}
        assert {a["stage"] for _t, a in workers} == {"speculate", "resolve"}
        assert m.get("fastq.inflated_bytes_parallel") > 0
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
        <= (text_stream_window() + 2) * 32768 \
        + speculation_budget(gz, len(text))
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


# ---------------------------------------------------------------------------
# several threads inside one member: the same bytes, the same refusals
# ---------------------------------------------------------------------------

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="no native library")


def spec_chunks(blob: bytes, grain: int, lines: int, chunk: int = 4096,
                workers: int = 3, fmt: str = "fastq", alive=None):
    """The speculative producer's chunks at a chunk size of the test's
    choosing (``iter_gzip_text_chunks`` picks it from the file's size)."""
    gz = _SpeculativeMembers(BytesByteSource(blob), "mem.gz", fmt, chunk,
                             workers, alive=alive)
    try:
        yield from gz.chunks(grain, lines)
    finally:
        gz.close()


def hiseq_text(pairs: int, seed: int = 7, read: int = 0) -> bytes:
    return b"".join(t for t, _a, _p in H.iter_chunks(seed, read, pairs))


def far_text(n: int) -> bytes:
    """Every record is the one 97 records (~26 KB) earlier with three bases
    changed: matches reach across every chunk edge, nearly a window back."""
    return reads_text(n, seed=17, back=97)


def gzip_member(text: bytes, level: int = 4, strategy: int = 0,
                flags: int = 0) -> bytes:
    """One gzip member built by hand: FEXTRA (4), FNAME (8), FCOMMENT (16)
    and FHCRC (2) as ``flags`` says."""
    head = bytes([0x1f, 0x8b, 8, flags, 0, 0, 0, 0, 0, 3])
    if flags & 4:
        extra = b"AP\x05\x00hello" + b"ZZ\x00\x00"
        head += struct.pack("<H", len(extra)) + extra
    if flags & 8:
        head += b"lane_R1_001.fastq\x00"
    if flags & 16:
        head += b"a comment\x00"
    if flags & 2:
        head += struct.pack("<H", zlib.crc32(head) & 0xffff)
    c = zlib.compressobj(level, zlib.DEFLATED, -15, 8, strategy)
    return head + c.compress(text) + c.flush() \
        + struct.pack("<II", zlib.crc32(text), len(text) & 0xffffffff)


HISEQ = hiseq_text(1200)


def _planted(n: int) -> bytes:
    """Incompressible bytes (level 0: stored blocks, the bytes verbatim)
    laced with a real dynamic block's first bytes: a header parses there,
    and what follows it is noise."""
    c = zlib.compressobj(6, zlib.DEFLATED, -15)
    head = (c.compress(HISEQ[:40_000]) + c.flush(zlib.Z_SYNC_FLUSH))[:200]
    rng = random.Random(23)
    out = bytearray(rng.getrandbits(8) for _ in range(n))
    for at in range(3000, n - 300, 7000):
        out[at:at + len(head)] = head
    return bytes(out)


def _parallel_case(kind: str):
    """(gzip bytes, lines a record) of one parallel case."""
    if kind.startswith("level"):
        return gzip.compress(HISEQ, int(kind[5:])), 4
    if kind == "stored":                # no dynamic block anywhere
        return gzip_member(HISEQ, 0), 4
    if kind == "fixed":
        return gzip_member(HISEQ, 6, zlib.Z_FIXED), 4
    if kind == "far_matches":
        return gzip.compress(far_text(2500), 9), 4
    if kind == "false_starts":
        return gzip_member(_planted(150_000), 0), 1
    if kind == "two_members":           # cut inside a record
        return (gzip.compress(HISEQ[:100_001], 4)
                + gzip.compress(HISEQ[100_001:], 6)), 4
    if kind == "three_members":         # dynamic, stored, fixed blocks
        return (gzip.compress(HISEQ[:180_001], 1)
                + gzip_member(HISEQ[180_001:240_000], 0)
                + gzip_member(HISEQ[240_000:], 9, zlib.Z_FIXED)), 4
    if kind == "empty_members":
        return (gzip.compress(b"") + gzip.compress(HISEQ[:5000], 4)
                + gzip.compress(b"") + gzip.compress(HISEQ[5000:], 4)
                + gzip.compress(b"")), 4
    if kind == "header_fields":         # FEXTRA + FNAME + FCOMMENT + FHCRC
        return (gzip_member(HISEQ[:90_000], 4, flags=4 | 8 | 16 | 2)
                + gzip_member(HISEQ[90_000:], 4, flags=8)), 4
    if kind == "bgzip":
        return _members("bgzip", HISEQ), 4
    assert kind == "qseq"
    return gzip.compress(_qseq_text(3000), 4), 1


PARALLEL_CASES = ["level1", "level4", "level6", "level9", "stored", "fixed",
                  "far_matches", "false_starts", "two_members",
                  "three_members", "empty_members", "header_fields", "bgzip",
                  "qseq"]


@needs_native
@pytest.mark.parametrize("chunk", [1024, 4096, 30_000])
@pytest.mark.parametrize("kind", PARALLEL_CASES)
def test_several_threads_give_zlibs_bytes(kind, chunk):
    blob, lines = _parallel_case(kind)
    want = gzip.decompress(blob)
    grain = 20_000
    with MetricsContext() as m:
        chunks = list(spec_chunks(blob, grain, lines, chunk,
                                  fmt="qseq" if kind == "qseq" else "fastq"))
        serial = list(read_planners._record_chunks(
            read_planners._GzipMembers(BytesByteSource(blob), "mem.gz"),
            grain, lines, "serial"))
    assert b"".join(chunks) == want == b"".join(serial)
    longest = max(len(ln) + 1 for ln in want.split(b"\n"))
    # (the end of the file ends the last chunk wherever it is)
    for c in chunks[:None if want.endswith(b"\n") else -1]:
        assert c.endswith(b"\n") and c.count(b"\n") % lines == 0
        assert len(c) <= max(grain, lines * longest)
    fmt = "qseq" if kind == "qseq" else "fastq"
    for name in ("stream_members", "inflated_bytes", "compressed_bytes"):
        assert m.get(f"{fmt}.{name}") == m.get(f"serial.{name}"), name
    assert m.get(f"{fmt}.compressed_bytes") == len(blob)
    assert m.get(f"{fmt}.stream_chunks") == len(chunks)
    assert m.get(f"{fmt}.inflate_busy_ns") > 0
    par = m.get(f"{fmt}.inflated_bytes_parallel")
    if kind in ("stored", "fixed", "false_starts"):
        # no dynamic block exists: every stretch is decoded in order
        assert par == 0
        assert m.get(f"{fmt}.inflate_respeculated_chunks") \
            == -(-len(blob) // chunk) - 1
    elif kind == "bgzip":
        assert 0 <= par < len(want)
    elif kind == "three_members":
        assert chunk > 4096 or 0 < par <= 180_001
    elif kind != "empty_members" and chunk <= 4096:
        assert par > len(want) // 2, par
    if kind == "false_starts":
        # the planted headers were found and decoded from, for nothing
        found = [native.deflate_find_block(blob, 8 * at, 8 * (at + chunk))
                 for at in range(chunk, len(blob), chunk)]
        assert sum(f >= 0 for f in found) >= 3


@needs_native
@pytest.mark.parametrize("grain", [1, 64, RECORD_MAX, 1000, 65536, 1 << 22],
                         ids=lambda g: f"grain{g}")
def test_several_threads_cut_at_records_at_any_grain(grain):
    """A record longer than the grain comes whole; otherwise no chunk
    passes the grain, and the chunks are the one inflate's."""
    blob = gzip.compress(TEXT, 4)
    chunks = list(spec_chunks(blob, grain, 4, chunk=2048))
    assert b"".join(chunks) == TEXT
    assert chunks == list(read_planners._record_chunks(
        read_planners._GzipMembers(BytesByteSource(blob), "mem.gz"),
        grain, 4, "serial"))
    for c in chunks:
        assert c.startswith(b"@") and c.count(b"\n") % 4 == 0
        assert len(c) <= grain or grain < RECORD_MAX
        assert len(c) <= max(grain, RECORD_MAX + grain)
    if grain == 1:
        assert len(chunks) == 600       # a record a chunk
    if grain >= len(TEXT):
        assert len(chunks) == 1
    # one line a record: the same text cut at any line end
    lines = list(spec_chunks(blob, 64, 1, chunk=2048))
    assert b"".join(lines) == TEXT and all(c.endswith(b"\n") for c in lines)


def _damage_blob(blob: bytes, kind: str) -> bytes:
    crc, isize = struct.unpack("<II", blob[-8:])
    if kind == "truncated":
        return blob[:len(blob) * 2 // 3]
    if kind == "truncated_trailer":
        return blob[:-3]
    if kind == "flipped_crc":
        return blob[:-8] + struct.pack("<II", crc ^ 1, isize)
    if kind == "wrong_isize":
        return blob[:-8] + struct.pack("<II", crc, isize + 1)
    if kind == "flipped_byte":          # in the deflate stream
        i = len(blob) // 2
        return blob[:i] + bytes([blob[i] ^ 0x55]) + blob[i + 1:]
    if kind == "second_member_truncated":
        return blob + blob[:len(blob) // 2]
    if kind == "second_member_flipped_crc":
        return blob + blob[:-8] + struct.pack("<II", crc ^ 1, isize)
    if kind == "bad_method":
        return blob + blob[:2] + b"\x07" + blob[3:]
    if kind == "header_crc":
        good = gzip_member(HISEQ[:3000], 4, flags=2 | 8)
        return blob + good[:12] + b"X" + good[13:]
    assert kind == "trailing_garbage"
    return blob + b"\x00" * 16


DAMAGE = ["truncated", "truncated_trailer", "flipped_crc", "wrong_isize",
          "flipped_byte", "second_member_truncated",
          "second_member_flipped_crc", "bad_method", "header_crc",
          "trailing_garbage"]


@needs_native
@pytest.mark.parametrize("chunk", [4096, 65536])
@pytest.mark.parametrize("kind", DAMAGE)
def test_several_threads_refuse_what_zlib_refuses(kind, chunk):
    blob = _damage_blob(gzip.compress(HISEQ, 4), kind)
    second = kind.startswith("second") or kind in (
        "bad_method", "header_crc", "trailing_garbage")
    got = []
    with pytest.raises(FastqError, match="offset") as bad:
        for c in spec_chunks(blob, 20_000, 4, chunk):
            got.append(c)
    # it names the member and its offset, as the one inflate does
    with pytest.raises(FastqError) as serial:
        for _ in read_planners._record_chunks(
                read_planners._GzipMembers(BytesByteSource(blob), "mem.gz"),
                20_000, 4, "serial"):
            pass
    member = re.search(r"gzip member -?\d+(?: \(offset \d+\))?|offset \d+, "
                       r"after gzip member \d+", str(bad.value))
    assert member and member.group(0) in str(serial.value)
    # what was handed on before the fault is the text before it, in order
    # (past a flipped byte it is what the bytes decode to, as zlib's is,
    # until the decoder or the trailer refuses it)
    text = b"".join(got)
    if kind != "flipped_byte":
        assert HISEQ.startswith(text[:len(HISEQ)])
    if second:
        assert len(text) > len(HISEQ) - 2 * 20_000
    if kind in ("flipped_crc", "wrong_isize"):
        assert "incorrect" in str(bad.value)
    if kind.endswith("truncated") or kind == "truncated_trailer":
        assert "truncated" in str(bad.value)


@pytest.fixture(scope="module")
def lane_file(tmp_path_factory):
    """A file of several speculative chunks as ``iter_gzip_text_chunks``
    sizes them (4,096 reads, ~340 KB of gzip: the benchmark's tiny file)."""
    text = hiseq_text(4096, seed=11)
    path = str(tmp_path_factory.mktemp("lane") / "lane_R1_001.fastq.gz")
    with open(path, "wb") as fh:
        fh.write(gzip.compress(text, 4))
    return path, text


@pytest.fixture
def eight_cpus(monkeypatch):
    """The path is chosen by the host's CPUs: make the test's host one
    with CPUs to spare, whatever it runs on."""
    monkeypatch.setattr(read_planners.os, "cpu_count", lambda: 8)


@needs_native
def test_the_file_decides_who_inflates(lane_file, eight_cpus, monkeypatch):
    path, text = lane_file
    size = len(open(path, "rb").read())
    chunk, workers = gzip_speculation(BytesByteSource(open(path, "rb").read()))
    assert (chunk, workers) == (65536, 2) and size >= 2 * chunk
    with MetricsContext() as m:
        chunks = list(iter_gzip_text_chunks(path, 1 << 22, 4))
    assert b"".join(chunks) == text
    assert m.get("fastq.inflated_bytes_parallel") > len(text) // 2
    assert m.get("fastq.inflated_bytes") == len(text)
    # a large file: chunks of the largest size, a third of the CPUs up to
    # the four workers the stream's one thread can take pieces from

    big = BytesByteSource(open(path, "rb").read())
    big.size = 1 << 30
    assert gzip_speculation(big) == (read_planners._SPEC_CHUNK_MAX, 2)
    monkeypatch.setattr(read_planners.os, "cpu_count", lambda: 13)
    assert gzip_speculation(big)[1] == 4 and text_stream_window() == 8
    monkeypatch.setattr(read_planners.os, "cpu_count", lambda: 96)
    assert gzip_speculation(big)[1] == 4 and text_stream_window() == 70
    monkeypatch.setattr(read_planners.os, "cpu_count", lambda: 8)
    # a short file, a BGZF file, one CPU, no native library: one inflate
    assert gzip_speculation(BytesByteSource(gzip.compress(TEXT, 4))) is None
    bg = _members("bgzip", text)
    assert len(bg) > 4 * chunk
    assert gzip_speculation(BytesByteSource(bg)) is None
    monkeypatch.setattr(read_planners.os, "cpu_count", lambda: 1)
    assert gzip_speculation(BytesByteSource(open(path, "rb").read())) is None
    monkeypatch.setattr(read_planners.os, "cpu_count", lambda: 8)
    monkeypatch.setattr(native, "load", lambda: None)
    assert gzip_speculation(BytesByteSource(open(path, "rb").read())) is None
    with MetricsContext() as m:
        serial = list(iter_gzip_text_chunks(path, 1 << 22, 4))
    assert b"".join(serial) == text
    assert m.get("fastq.inflated_bytes_parallel") == 0


@needs_native
@pytest.mark.parametrize("kind", ["flipped_byte", "flipped_crc",
                                  "wrong_isize", "truncated",
                                  "trailing_garbage"])
def test_a_damaged_member_fails_the_scan_on_several_threads(
        lane_file, eight_cpus, tmp_path, kind):
    path, text = lane_file
    bad = str(tmp_path / "bad_R1_001.fastq.gz")
    with open(bad, "wb") as fh:
        fh.write(_damage_blob(open(path, "rb").read(), kind))
    assert gzip_speculation(BytesByteSource(open(bad, "rb").read()))
    with MetricsContext() as m:
        with pytest.raises(FastqError, match="offset"):
            for _ in iter_gzip_text_chunks(bad, 65536, 4):
                pass
        assert m.get("fastq.inflated_bytes_parallel") > 0
    rc, out, err = run_cli(["seq-stats", bad])
    assert rc != 0
    assert out == ""                    # no totals, not even `reads`
    assert "error:" in err and "gzip member" in err or "start no" in err
    # and the sound file's answer is the plain file's
    rc, out, _ = run_cli(["seq-stats", path])
    plain = str(tmp_path / "lane.fastq")
    with open(plain, "wb") as fh:
        fh.write(text)
    assert rc == 0 and out == run_cli(["seq-stats", plain])[1]
    assert out.startswith("reads\t4096\n")


@needs_native
def test_closing_the_stream_early_stops_its_workers():
    import threading

    before = {t.name for t in threading.enumerate()}
    it = spec_chunks(gzip.compress(HISEQ, 4), 4096, 4, chunk=2048)
    assert next(it).startswith(b"@")
    assert any(t.name.startswith("hbam-inflate_")
               for t in threading.enumerate())
    it.close()
    assert {t.name for t in threading.enumerate()
            if t.name.startswith("hbam-inflate")} <= before


class _Alive:
    def __init__(self):
        self.now = self.peak = 0
        self._lock = __import__("threading").Lock()

    def add(self, n):
        with self._lock:
            self.now += n
            self.peak = max(self.peak, self.now)


@needs_native
def test_the_workers_buffers_are_counted_and_given_back():
    blob = gzip.compress(HISEQ, 4)
    alive = _Alive()
    sizes = [len(c) for c in spec_chunks(blob, 20_000, 4, chunk=8192,
                                         workers=3, alive=alive)]
    assert sum(sizes) == len(HISEQ)
    assert alive.now == 0               # everything handed on or freed
    # at most workers + 1 chunks of symbols (2 B each) ahead, two pieces
    # and a chunk's worth of text behind
    piece = 8192 * len(HISEQ) // len(blob) + 40_000
    assert 2 * piece < alive.peak <= (2 * 4 + 3) * piece + 20_000


@needs_native
def test_text_alive_does_not_grow_with_the_file(tmp_path, eight_cpus):
    """Two files at the largest chunk size, one twice the other: the same
    high-water mark of text and symbols alive."""
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file

    peaks = []
    for pairs in (49_152, 98_304):
        path = str(tmp_path / f"lane{pairs}_R1_001.fastq.gz")
        c = zlib.compressobj(4, zlib.DEFLATED, 31)
        n_text = 0
        with open(path, "wb") as fh:
            for t, _a, _p in H.iter_chunks(29, 0, pairs):
                fh.write(c.compress(t))
                n_text += len(t)
            fh.write(c.flush())
        how = gzip_speculation(BytesByteSource(open(path, "rb").read()))
        assert how == (read_planners._SPEC_CHUNK_MAX, 2)
        cfg = dataclasses.replace(DEFAULT_CONFIG, split_size=1 << 20)
        marks = []
        for _scan in range(2):
            with MetricsContext() as m:
                got = fastq_seq_stats_file(path, config=cfg)
            assert got["n_reads"] == pairs
            assert m.get("fastq.inflated_bytes") == n_text
            assert m.get("fastq.inflated_bytes_parallel") > 0.9 * n_text
            peak = m.get("fastq.stream_peak_text_bytes")
            assert 0 < peak <= (text_stream_window() + 2) * (1 << 20) \
                + speculation_budget(path, n_text)
            marks.append(peak)
        # the native tokenise never fills its window, so a scan's mark is
        # what the workers hold ahead, and more in a scan that waited (the
        # step's compile, a thread put off a core): the lower of two is
        # the scan left alone
        peaks.append(min(marks))
    assert peaks[1] <= 1.25 * peaks[0]
    assert peaks[1] < 0.6 * n_text


@needs_native
def test_many_workers_and_streams_at_once_lose_nothing():
    """More workers than cores, three streams at once, the interpreter
    switching threads every 10 us: every stream is the text, every buffer
    counted is counted back (a lost update would leave a remainder)."""
    import sys
    import threading

    blob = gzip.compress(HISEQ, 4) + gzip.compress(HISEQ[:50_000], 9)
    want = HISEQ + HISEQ[:50_000]
    results, alives = {}, [_Alive() for _ in range(3)]

    def stream(i):
        results[i] = b"".join(spec_chunks(blob, 30_000, 4, chunk=1024,
                                          workers=16, alive=alives[i]))

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=stream, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(before)
    assert [results.get(i) == want for i in range(3)] == [True] * 3
    assert [a.now for a in alives] == [0, 0, 0]
    assert all(a.peak > 0 for a in alives)
