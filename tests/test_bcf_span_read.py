"""A BGZF BCF span read the way the BAM feed reads one
(``split/vcf_planners.bcf_span_frames``): one positioned read, the native
header walk, one native inflate into a leased buffer, the record chase
over a view of it.

What is pinned: the read is the ``BGZFReader`` path's, byte for byte and
start for start, on every kind of span; the input decides the path (a raw
BCF, no native library -> the block reader); corrupt blocks raise
``BGZFError`` either way; every way out hands the leased buffers back; no
column a decode returns lives in a leased buffer.
"""
import concurrent.futures as cf
import struct

import numpy as np
import pytest

import kgp3_reference as K
from test_bcf_columns import LINES, _encode

from hadoop_bam_tpu.formats import bcf_columns, bgzf
from hadoop_bam_tpu.formats.bcf import (
    BCFError, decode_header, encode_header,
)
from hadoop_bam_tpu.formats.bcf_columns import (
    STAT_KEYS, decode_bcf_columns, frame_record_starts,
)
from hadoop_bam_tpu.ops import inflate as inflate_ops
from hadoop_bam_tpu.parallel.variant_pipeline import (
    VariantGeometry, bcf_span_stat_columns,
)
from hadoop_bam_tpu.split import vcf_planners as vp
from hadoop_bam_tpu.split.spans import FileVirtualSpan
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.pools import SpanBufferPool
from hadoop_bam_tpu.utils.seekable import BytesByteSource, FileByteSource

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")

SMALL = K.Shape((3, 2, 2, 2, 3), missing=0.1, haploid=0.1, unphased=0.3,
                type_shares=(0.5, 0.3, 0.2), multi_share=0.3)


class BcfFile:
    """A BGZF BCF written block by block, with the map from a record to
    its virtual offset."""

    def __init__(self, path, header_blob, records, starts, block,
                 eof_marker=True):
        blocks = [bgzf.deflate_block(bytes(header_blob[lo:lo + block]))
                  for lo in range(0, len(header_blob), block)]
        blocks += [bgzf.deflate_block(bytes(records[lo:lo + block]))
                   for lo in range(0, len(records), block)]
        if eof_marker:
            blocks.append(bgzf.EOF_BLOCK)
        self.whole = b"".join(blocks)
        self.path = str(path)
        with open(self.path, "wb") as fh:
            fh.write(self.whole)
        self.records = bytes(records)
        self.header, _ = decode_header(bytes(header_blob))
        self.geometry = VariantGeometry(n_samples=self.header.n_samples)
        table = inflate_ops.block_table(self.whole)
        self.coffset = table["coffset"]
        self.ubase = np.concatenate([[0], np.cumsum(table["isize"])])
        # inflated offset of every record start, and of the records' end
        self.rec = len(header_blob) + np.asarray(starts, np.int64)
        self.end_voffset = len(self.whole) << 16

    def voffset(self, x: int) -> int:
        """The virtual offset of inflated offset ``x``."""
        blk = int(np.searchsorted(self.ubase, x, side="right")) - 1
        if blk >= self.coffset.size:
            return self.end_voffset
        return (int(self.coffset[blk]) << 16) | (x - int(self.ubase[blk]))

    def span(self, start: int, end: int) -> FileVirtualSpan:
        return FileVirtualSpan(self.path, start, end)

    def block_start_inside_a_record(self, after_rec: int) -> int:
        """The first block boundary past record ``after_rec`` that falls
        inside a record: a span ending there has ``end_u == 0`` and a tail
        record that crosses it."""
        for b in range(self.coffset.size):
            x = int(self.ubase[b])
            if self.rec[after_rec] < x < self.rec[-1] \
                    and x not in self.rec:
                return int(self.coffset[b]) << 16
        raise AssertionError("no such block")


def _kgp3_records(n, shape=K.KGP3, seed=29):
    f = K.gen_fields(seed, 0, 1, n, shape)
    data, starts = K.assemble(f, shape)
    return K.header_bytes(shape), data.tobytes(), starts


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """The tiny kgp3 file at the published width in blocks of 0xff00 and
    of 2,000 bytes (a 5 KB record crosses two and three boundaries), a
    small cohort with every genotype form in 300-byte blocks, and the
    columnar decoder's own fixture."""
    d = tmp_path_factory.mktemp("bcfspan")
    head, recs, starts = _kgp3_records(320)
    out = {"kgp3": BcfFile(d / "kgp3.bcf", head, recs, starts, 0xFF00),
           "kgp3-2k": BcfFile(d / "kgp3-2k.bcf", head, recs[:starts[96]],
                              starts[:97], 2000)}
    head, recs, starts = _kgp3_records(600, SMALL)
    out["small-300"] = BcfFile(d / "small.bcf", head, recs, starts, 300)
    header, _, _, buf = _encode(LINES * 40)
    out["lines"] = BcfFile(
        d / "lines.bcf", encode_header(header), buf,
        np.append(frame_record_starts(buf), len(buf)), 700)
    return out


@pytest.fixture()
def pool(monkeypatch):
    """A private span-buffer pool in place of the process-wide one."""
    p = SpanBufferPool()
    monkeypatch.setattr(inflate_ops, "SPAN_BUFFERS", p)
    monkeypatch.setattr(vp, "SPAN_BUFFERS", p)
    METRICS.reset()
    return p


def _count(name: str) -> int:
    return int(METRICS.snapshot()["counters"].get(name, 0))


def _all_back(pool) -> bool:
    """Every buffer the pool minted lies free in it again."""
    return sum(pool.free_counts().values()) \
        == _count("feed.span_buffers_minted")


def _oracle(f: BcfFile, span):
    return vp._read_bcf_span_frames(BytesByteSource(f.whole), span, True)


def _span_kinds(f: BcfFile):
    """name -> span: every way a span can lie over the blocks."""
    n = f.rec.size - 1
    v = f.voffset
    a, b = n // 5, (3 * n) // 5
    cross = f.block_start_inside_a_record(b)
    kinds = {
        "record-to-record": f.span(v(int(f.rec[a])), v(int(f.rec[b]))),
        "ends-inside-a-record": f.span(v(int(f.rec[a])),
                                       v(int(f.rec[b]) + 11)),
        "ends-on-a-block-inside-a-record": f.span(v(int(f.rec[a])), cross),
        "one-record": f.span(v(int(f.rec[b])), v(int(f.rec[b]) + 1)),
        "first-record-to-eof": f.span(v(int(f.rec[0])), f.end_voffset),
        "last-span-at-eof": f.span(v(int(f.rec[b])), f.end_voffset),
        "empty": f.span(v(int(f.rec[a])), v(int(f.rec[a]))),
        "inverted": f.span(v(int(f.rec[b])), v(int(f.rec[a]))),
        "past-the-file": f.span(f.end_voffset, f.end_voffset + (1 << 16)),
    }
    assert kinds["record-to-record"].start[1] > 0
    assert kinds["record-to-record"].end[1] > 0
    assert kinds["ends-on-a-block-inside-a-record"].end[1] == 0
    return kinds


FILES = ("kgp3", "kgp3-2k", "small-300", "lines")
KINDS = ("record-to-record", "ends-inside-a-record",
         "ends-on-a-block-inside-a-record", "one-record",
         "first-record-to-eof", "last-span-at-eof", "empty", "inverted",
         "past-the-file")
NATIVE_KINDS = KINDS[:6]        # the others are the block reader's


# ---------------------------------------------------------------------------
# the read IS the block reader's read
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name", FILES)
def test_span_read_is_the_block_readers(files, pool, name, kind):
    f = files[name]
    span = _span_kinds(f)[kind]
    want_raw, want_starts = _oracle(f, span)
    raw, starts = vp.read_bcf_span_frames(f.path, span, True)
    assert isinstance(raw, bytes) and starts.dtype == np.int64
    assert raw == want_raw
    np.testing.assert_array_equal(starts, want_starts)
    assert vp.read_bcf_span_bytes(f.path, span) == want_raw
    took_native = _count("vcf.native_read_spans")
    assert took_native == (2 if kind in NATIVE_KINDS else 0)
    assert took_native + _count("vcf.python_read_spans") == 2
    if kind in NATIVE_KINDS:
        assert starts.size > 0
        np.testing.assert_array_equal(starts, frame_record_starts(raw))
    else:
        assert raw == b"" and starts.size == 0
    assert _all_back(pool)


@pytest.mark.parametrize("name,lo,hi", [("kgp3", 1, 1), ("kgp3-2k", 2, 3)])
def test_the_tail_record_crosses_one_and_more_blocks(files, name, lo, hi):
    """The fixtures hold what their names promise: the span's last record
    starts before the span's end and runs over ``lo`` to ``hi`` block
    boundaries, the span's end among them."""
    f = files[name]
    span = _span_kinds(f)["ends-on-a-block-inside-a-record"]
    raw, starts = _oracle(f, span)
    first = int(f.rec[(f.rec.size - 1) // 5])    # the span's first record
    tail_start, tail_end = first + int(starts[-1]), first + len(raw)
    crossed = int(np.count_nonzero((f.ubase > tail_start)
                                   & (f.ubase < tail_end)))
    assert lo <= crossed <= hi, crossed


@pytest.mark.parametrize("n_spans", [1, 3, 7])
@pytest.mark.parametrize("name", FILES)
def test_every_span_of_a_plan(files, pool, name, n_spans):
    """The planner's spans: each read equals the block reader's, and
    together they hold every record once."""
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf

    f = files[name]
    ds = open_vcf(f.path)
    assert ds._is_bgzf_bcf
    spans = ds.spans(n_spans)
    got = []
    for span in spans:
        want_raw, want_starts = _oracle(f, span)
        raw, starts = vp.read_bcf_span_frames(f.path, span)
        assert raw == want_raw
        np.testing.assert_array_equal(starts, want_starts)
        got.append(raw)
    assert b"".join(got) == f.records
    assert _count("vcf.native_read_spans") == len(spans)
    assert _count("vcf.python_read_spans") == 0
    assert _all_back(pool)


# ---------------------------------------------------------------------------
# the end of the file inside a record
# ---------------------------------------------------------------------------

def _cut_file(tmp_path, cut: int, eof_marker: bool) -> BcfFile:
    """The small cohort's file, its last record cut to ``cut`` bytes."""
    head, recs, starts = _kgp3_records(40, SMALL)
    return BcfFile(tmp_path / f"cut{cut}-{eof_marker}.bcf", head,
                   recs[:int(starts[-2]) + cut], starts[:-1], 300,
                   eof_marker=eof_marker)


@pytest.mark.parametrize("eof_marker", [True, False])
@pytest.mark.parametrize("cut", [5, 8, 40])
def test_eof_inside_the_last_record(tmp_path, pool, cut, eof_marker):
    """A bare header stub at EOF is dropped; a record cut in its body is
    kept, and the decoder raises ``BCFError`` on it."""
    f = _cut_file(tmp_path, cut, eof_marker)
    n = f.rec.size - 1              # the cut record
    span = f.span(f.voffset(int(f.rec[n - 3])), f.end_voffset)
    want_raw, want_starts = _oracle(f, span)
    raw, starts = vp.read_bcf_span_frames(f.path, span, True)
    assert raw == want_raw
    np.testing.assert_array_equal(starts, want_starts)
    assert _count("vcf.native_read_spans") == 1
    whole = int(f.rec[n]) - int(f.rec[n - 3])
    if cut < 8:
        assert starts.size == 3 and len(raw) == whole
        cols = bcf_span_stat_columns(f.path, span, f.header, f.geometry,
                                     True)
        assert cols["chrom"].size == 3
    else:
        assert starts.size == 4 and len(raw) == whole + cut
        with pytest.raises(BCFError):
            bcf_span_stat_columns(f.path, span, f.header, f.geometry, True)
    assert _all_back(pool)


@pytest.mark.parametrize("cut", [5, 40])
def test_eof_inside_a_record_the_span_ends_before(tmp_path, pool, cut):
    """The tail record's header or body is cut by EOF while the chase
    reaches for the blocks past the span's own."""
    f = _cut_file(tmp_path, cut, True)
    n = f.rec.size - 1              # the cut record: the span's tail
    span = f.span(f.voffset(int(f.rec[n - 3])),
                  f.voffset(int(f.rec[n]) + 1))
    want_raw, want_starts = _oracle(f, span)
    raw, starts = vp.read_bcf_span_frames(f.path, span, True)
    assert raw == want_raw
    np.testing.assert_array_equal(starts, want_starts)
    assert starts.size == (3 if cut < 8 else 4)
    assert _count("vcf.native_read_spans") == 1
    assert _all_back(pool)


# ---------------------------------------------------------------------------
# the input decides the path
# ---------------------------------------------------------------------------

def test_a_raw_bcf_takes_the_block_reader(files, tmp_path, pool):
    f = files["small-300"]
    head, recs, starts = _kgp3_records(600, SMALL)
    path = str(tmp_path / "raw.bcf")
    with open(path, "wb") as fh:
        fh.write(head + recs)
    lo, hi = len(head) + int(starts[100]), len(head) + int(starts[400])
    raw, got = vp.read_bcf_span_frames(
        path, FileVirtualSpan(path, lo << 16, (hi + 3) << 16))
    assert raw == recs[int(starts[100]):int(starts[401])] \
        and raw == f.records[int(starts[100]):int(starts[401])]
    np.testing.assert_array_equal(got, starts[100:401] - starts[100])
    assert _count("vcf.python_read_spans") == 1
    assert _count("vcf.native_read_spans") == 0
    assert _count("feed.span_buffers_minted") == 0


@pytest.mark.parametrize("kind", NATIVE_KINDS)
def test_a_source_that_cannot_fill_a_buffer_keeps_its_two_reads(
        files, pool, kind):
    """``BytesByteSource`` has no ``pread_into``: the fetch leases
    nothing (two ``pread``s), the inflated bytes are leased all the
    same, the result is the same."""
    f = files["kgp3"]
    span = _span_kinds(f)[kind]
    src = BytesByteSource(f.whole)
    assert src.pread_into is None
    raw, starts = vp.read_bcf_span_frames(src, span, True)
    want_raw, want_starts = _oracle(f, span)
    assert raw == want_raw
    np.testing.assert_array_equal(starts, want_starts)
    assert _count("vcf.native_read_spans") == 1
    assert _count("feed.span_buffers_minted") == 1      # inflated only
    file_src = FileByteSource(f.path)
    assert vp.read_bcf_span_frames(file_src, span, True)[0] == want_raw
    file_src.pread(0, 1)            # a source handed in stays open
    file_src.close()
    assert _all_back(pool)


@pytest.mark.parametrize("kind", NATIVE_KINDS)
@pytest.mark.parametrize("name", ["kgp3", "small-300"])
def test_without_the_native_library_the_block_reader_reads(
        files, pool, monkeypatch, name, kind):
    f = files[name]
    span = _span_kinds(f)[kind]
    with_native = vp.read_bcf_span_frames(f.path, span, True)
    assert _count("vcf.native_read_spans") == 1
    monkeypatch.setattr(native, "available", lambda: False)
    raw, starts = vp.read_bcf_span_frames(f.path, span, True)
    assert raw == with_native[0]
    np.testing.assert_array_equal(starts, with_native[1])
    assert _count("vcf.python_read_spans") == 1
    assert _count("feed.span_buffers_minted") \
        + _count("feed.span_buffers_reused") == 2      # the first read's


# ---------------------------------------------------------------------------
# corrupt blocks raise BGZFError on both paths
# ---------------------------------------------------------------------------

def _corrupted(f: BcfFile, tmp_path, what: str, blk: int) -> str:
    bad = bytearray(f.whole)
    c = int(f.coffset[blk])
    info = bgzf.parse_block_header(f.whole, c)
    if what == "deflate":
        # BFINAL=1, BTYPE=3: a reserved block type, refused by every
        # inflater
        bad[info.cdata_offset] = 0xFF
    else:
        struct.pack_into("<I", bad, c + info.block_size - 4,
                         info.isize - 1)
    path = str(tmp_path / f"{what}.bcf")
    with open(path, "wb") as fh:
        fh.write(bad)
    return path


@pytest.mark.parametrize("where", ["first-block", "middle", "end-block",
                                   "tail-extension"])
@pytest.mark.parametrize("what", ["deflate", "isize"])
def test_corrupt_blocks_raise_bgzf_error_on_both_paths(
        files, tmp_path, pool, monkeypatch, what, where):
    f = files["kgp3-2k"]
    span = _span_kinds(f)["ends-on-a-block-inside-a-record"]
    first = int(np.searchsorted(f.coffset, span.start[0]))
    end = int(np.searchsorted(f.coffset, span.end[0]))
    blk = {"first-block": first, "middle": (first + end) // 2,
           "end-block": end - 1, "tail-extension": end}[where]
    path = _corrupted(f, tmp_path, what, blk)
    bad_span = FileVirtualSpan(path, span.start_voffset, span.end_voffset)
    with pytest.raises(bgzf.BGZFError):
        vp.read_bcf_span_frames(path, bad_span, True)
    with pytest.raises(bgzf.BGZFError):
        bcf_span_stat_columns(path, bad_span, f.header, f.geometry, True)
    assert _all_back(pool)
    monkeypatch.setattr(native, "available", lambda: False)
    with pytest.raises(bgzf.BGZFError):
        vp.read_bcf_span_frames(path, bad_span, True)


def test_a_read_error_hands_the_buffers_back(files, pool):
    """A fault that is not the file's (the source fails mid-span) keeps
    its class and leaves no lease out."""
    from hadoop_bam_tpu.utils.errors import TransientIOError

    f = files["kgp3"]
    span = _span_kinds(f)["ends-on-a-block-inside-a-record"]

    class Flaky(FileByteSource):
        def pread(self, offset, size):      # the tail-extension read
            raise TransientIOError("injected")

    vp.read_bcf_span_frames(f.path, span, True)         # warm the pool
    src = Flaky(f.path)
    with pytest.raises(TransientIOError):
        vp.read_bcf_span_frames(src, span, True)
    src.close()
    assert _all_back(pool)


# ---------------------------------------------------------------------------
# what a decode returns is memory of its own
# ---------------------------------------------------------------------------

def _dirty(pool) -> list:
    """Overwrite every buffer the pool holds; returns the buffers."""
    leases = [pool.lease(size) for size, n in pool.free_counts().items()
              for _ in range(n)]
    assert leases
    bufs = [lease.array for lease in leases]
    for lease in leases:
        lease.array[:] = 0xAA
        lease.release()
    return bufs


def _oracle_columns(f: BcfFile, span):
    raw, starts = _oracle(f, span)
    cols = decode_bcf_columns(raw, f.header, f.geometry.samples_pad,
                              starts=starts)
    return {k: cols[k] for k in STAT_KEYS}


@pytest.mark.parametrize("declined", [False, True])
@pytest.mark.parametrize("name", ["kgp3", "small-300", "lines"])
def test_no_column_lives_in_a_leased_buffer(files, pool, monkeypatch, name,
                                            declined):
    f = files[name]
    spans = [_span_kinds(f)[k] for k in NATIVE_KINDS]
    want = [_oracle_columns(f, s) for s in spans]
    if declined:        # the record-serial scanner reads the view too
        monkeypatch.setattr(bcf_columns, "_MAX_FMT_ROUNDS", 0)
    got = [bcf_span_stat_columns(f.path, s, f.header, f.geometry, True)
           for s in spans]
    assert _count("vcf.native_read_spans") == len(spans)
    assert (_count("vcf.columnar_declined_spans") == len(spans)) == declined
    assert _count("vcf.inflated_bytes") \
        == sum(len(_oracle(f, s)[0]) for s in spans)
    assert _all_back(pool)
    bufs = _dirty(pool)
    for cols, ref in zip(got, want):
        assert set(cols) == set(STAT_KEYS)
        for k in STAT_KEYS:
            np.testing.assert_array_equal(cols[k], ref[k], err_msg=k)
            assert not any(np.shares_memory(cols[k], b) for b in bufs), k


def test_thirty_two_threads_over_the_same_spans(files, pool):
    """The pool's threads read and decode at once out of one pool of
    buffers: every span's columns are the oracle's, every time."""
    f = files["kgp3"]
    spans = [_span_kinds(f)[k] for k in NATIVE_KINDS]
    want = [_oracle_columns(f, s) for s in spans]
    jobs = list(range(len(spans))) * 16

    def one(i):
        return i, bcf_span_stat_columns(f.path, spans[i], f.header,
                                        f.geometry, True)

    with cf.ThreadPoolExecutor(32) as ex:
        done = list(ex.map(one, jobs))
    assert len(done) == len(jobs)
    for i, cols in done:
        for k in STAT_KEYS:
            np.testing.assert_array_equal(cols[k], want[i][k], err_msg=k)
    totals = sum(int(cols["chrom"].size) for _, cols in done)
    assert totals == 16 * sum(int(w["chrom"].size) for w in want)
    assert _count("vcf.native_read_spans") == len(jobs)
    assert _count("vcf.python_read_spans") == 0
    assert _count("feed.span_buffers_reused") > 0


def test_the_scan_takes_the_leased_read(files, pool):
    """``hbam vcf-stats``'s driver over the tiny kgp3 file: every span by
    the native read, the answer the plain reference's."""
    from hadoop_bam_tpu.parallel.variant_pipeline import variant_stats_file

    f = files["kgp3"]
    ref = K.Reference()
    ref.add(K.gen_fields(29, 0, 1, 320), len(f.records))
    out = variant_stats_file(f.path)
    assert out["n_variants"] == ref.n == 320
    assert _count("vcf.native_read_spans") >= 1
    assert _count("vcf.python_read_spans") == 0
    assert _count("vcf.inflated_bytes") == len(f.records)
    assert _count("pipeline.records") == 320
