"""The span start without fresh memory: the span-buffer pool
(``utils/pools.SpanBufferPool``), the single read into a leased buffer
(``ops/inflate.fetch_span_raw``), the leased inflated bytes of the
streamed fused decode (``ops/inflate.FusedSpanDecode``) and the native
BGZF header walk (``hbam_block_table``).

What is pinned: a recycled buffer never changes a row (cold, warm and
deliberately dirtied pools decode byte-identically to the two-pass
oracle), rows a consumer holds outlive the span's buffers, every way out
of a decode hands its buffers back, and the native header walk is the
Python walk — tables and error messages alike.
"""
import struct
import sys
import threading

import numpy as np
import pytest

from hadoop_bam_tpu.config import HBamConfig
from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.formats.bamio import BamWriter
from hadoop_bam_tpu.ops import inflate as inflate_ops
from hadoop_bam_tpu.ops.unpack_bam import (
    FLAGSTAT_PROJECTION, projection_ranges, projection_row_bytes,
)
from hadoop_bam_tpu.parallel import pipeline as pl
from hadoop_bam_tpu.split.planners import plan_bam_spans
from hadoop_bam_tpu.split.spans import FileVirtualSpan
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.pools import SpanBufferPool
from hadoop_bam_tpu.utils.resilient import RetryingByteSource, RetryPolicy
from hadoop_bam_tpu.utils.seekable import BytesByteSource, FileByteSource

from fixtures import make_header, make_records

needs_fused = pytest.mark.skipif(not inflate_ops.fused_available(),
                                 reason="native fused decode unavailable")
needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native library unavailable")

SEL = projection_ranges(FLAGSTAT_PROJECTION)
ROW_W = projection_row_bytes(FLAGSTAT_PROJECTION)
CFG_ON = HBamConfig(backend="cpu")
CFG_OFF = HBamConfig(backend="cpu", use_fused_decode=False)
MIB = 1 << 20


def _oracle_rows(path, span):
    return pl.decode_span_prefix_host(
        path, span, projection=FLAGSTAT_PROJECTION, want_voffs=False,
        config=CFG_OFF)[0]


@pytest.fixture(scope="module")
def bam3(tmp_path_factory):
    """A BAM planned as three spans, and the two-pass oracle's rows."""
    path = str(tmp_path_factory.mktemp("spanbuf") / "f.bam")
    header = make_header()
    with BamWriter(path, header) as w:
        for r in make_records(header, 6000, seed=5):
            w.write_sam_record(r)
    spans = list(plan_bam_spans(path, num_spans=3, header=header))
    assert len(spans) == 3
    oracle = [_oracle_rows(path, s) for s in spans]
    assert sum(o.shape[0] for o in oracle) == 6000
    return path, header, spans, oracle


@pytest.fixture()
def pool(monkeypatch):
    """A private pool in place of the process-wide one."""
    p = SpanBufferPool()
    monkeypatch.setattr(pl, "SPAN_BUFFERS", p)
    monkeypatch.setattr(inflate_ops, "SPAN_BUFFERS", p)
    METRICS.reset()
    return p


def _counters():
    c = METRICS.snapshot()["counters"]
    return (int(c.get("feed.span_buffers_minted", 0)),
            int(c.get("feed.span_buffers_reused", 0)),
            int(c.get("feed.span_fresh_bytes", 0)))


def _stream(path, span, fallback_fn=None):
    return pl._iter_fused_span_chunks(
        FileByteSource(path), span, "rows", sel=SEL, row_bytes=ROW_W,
        config=CFG_ON, fallback_fn=fallback_fn)


def _stream_rows(path, span):
    return np.concatenate([rows for (rows,) in _stream(path, span)]
                          or [np.empty((0, ROW_W), np.uint8)])


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

def test_pool_reuses_a_returned_buffer_and_mints_when_none_is_free():
    METRICS.reset()
    p = SpanBufferPool()
    a = p.lease(3 * MIB)
    assert a.array.size == 4 * MIB and a.array.dtype == np.uint8
    assert _counters() == (1, 0, 4 * MIB)
    b = p.lease(3 * MIB + 5)                # none free: a second buffer
    assert b.array is not a.array
    first = a.array
    a.release()
    assert a.array is None
    a.release()                             # a second release is a no-op
    assert p.free_counts() == {4 * MIB: 1}
    c = p.lease(2 * MIB + 1)                # same class: the same memory
    assert c.array is first
    assert p.lease(100).array.size == MIB   # the smallest class
    assert p.lease(4 * MIB + 1).array.size == 8 * MIB
    assert _counters() == (4, 1, 4 * MIB + 4 * MIB + MIB + 8 * MIB)


def test_pool_never_holds_more_than_its_bound():
    p = SpanBufferPool(max_free=3)
    leases = [p.lease(MIB) for _ in range(7)]
    for lease in leases:
        lease.release()
    assert p.free_counts() == {MIB: 3}
    big = p.lease(SpanBufferPool.MAX_KEPT_CLASS + 1)    # never kept
    big.release()
    assert p.free_counts() == {MIB: 3}
    # the default bound is what the streamed feed holds in flight, + 2
    assert SpanBufferPool().max_free == pl._stream_window(10 ** 6) + 2


def test_pool_eight_threads_lose_and_double_lease_nothing():
    METRICS.reset()
    p = SpanBufferPool(max_free=4)
    errors = []

    def work(tag: int) -> None:
        try:
            for i in range(2000):
                lease = p.lease(MIB if i % 3 else 2 * MIB)
                arr = lease.array
                arr[:64] = tag                  # ours alone until release
                arr[-1] = tag
                if not (arr[:64] == tag).all() or arr[-1] != tag:
                    errors.append((tag, i))
                lease.release()
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t + 1,))
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    minted, reused, _ = _counters()
    assert minted + reused == 8 * 2000
    held = [b for free in p._free.values() for b in free]
    assert len({id(b) for b in held}) == len(held)      # none held twice
    assert all(n <= 4 for n in p.free_counts().values())
    assert len(held) <= minted                          # none invented


# ---------------------------------------------------------------------------
# recycled memory never reaches a row
# ---------------------------------------------------------------------------

@needs_fused
@pytest.mark.parametrize("state", ["cold", "warm", "dirty"])
def test_streamed_rows_identical_whatever_the_pool_holds(bam3, pool, state):
    path, _, spans, oracle = bam3
    if state != "cold":
        for s in spans:
            _stream_rows(path, s)
    if state == "dirty":
        leases = [pool.lease(size) for size, n in pool.free_counts().items()
                  for _ in range(n)]
        assert leases
        for lease in leases:
            lease.array[:] = 0xFF
            lease.release()
    minted_before = _counters()[0]
    for s, want in zip(spans, oracle):
        assert np.array_equal(_stream_rows(path, s), want)
    if state != "cold":                     # a warm pool mints nothing
        assert _counters()[0] == minted_before
        assert _counters()[1] >= 2 * len(spans)


@needs_fused
def test_rows_held_across_finish_keep_their_bytes(bam3, pool):
    path, _, spans, oracle = bam3
    held = [rows for (rows,) in _stream(path, spans[0])]    # views, no copy
    # the span's buffers are back: take them all and overwrite them
    leases = [pool.lease(size) for size, n in pool.free_counts().items()
              for _ in range(n)]
    assert len(leases) == 2                 # compressed, inflated + offsets
    for lease in leases:
        lease.array[:] = 0xAA
    assert np.array_equal(np.concatenate(held), oracle[0])
    assert np.array_equal(_stream_rows(path, spans[1]), oracle[1])


@needs_fused
def test_buffered_decode_keeps_its_inflated_bytes_out_of_the_pool(bam3, pool):
    path, _, spans, _ = bam3
    data, offs, _, _ = pl._decode_span_fused(path, spans[0], "offsets",
                                             config=CFG_ON)
    want = pl._decode_span_core(path, spans[0])[0]
    # only the compressed buffer was leased, and it is back
    assert _counters()[0] == 1 and sum(pool.free_counts().values()) == 1
    pool.lease(1).array[:] = 0x55           # whatever the pool holds
    assert np.array_equal(data, want) and offs.size


# ---------------------------------------------------------------------------
# every way out hands the buffers back
# ---------------------------------------------------------------------------

def _all_back(pool) -> bool:
    return sum(pool.free_counts().values()) == _counters()[0] > 0


@needs_fused
def test_buffers_come_back_after_a_corrupt_block(bam3, pool, tmp_path):
    path, _, spans, _ = bam3
    raw = bytearray(open(path, "rb").read())
    table = inflate_ops.block_table(bytes(raw))
    raw[int(table["cdata_off"][2]) + 9] ^= 0xFF
    bad = str(tmp_path / "bad.bam")
    open(bad, "wb").write(bytes(raw))
    span = FileVirtualSpan(bad, spans[0].start_voffset, spans[0].end_voffset)
    with pytest.raises(bgzf.BGZFError):
        _stream_rows(bad, span)
    assert _all_back(pool)
    # a header the walk refuses, before any job starts: same
    raw[int(table["coffset"][1])] ^= 0xFF
    open(bad, "wb").write(bytes(raw))
    with pytest.raises(bgzf.BGZFError, match="bad gzip magic"):
        _stream(bad, span)
    assert _all_back(pool)


@needs_fused
def test_buffers_come_back_after_an_early_close(bam3, pool):
    path, _, spans, _ = bam3
    stream = _stream(path, spans[0])
    next(iter(stream))
    stream.close()
    assert _all_back(pool)
    unstarted = _stream(path, spans[1])     # closed before any iteration
    unstarted.close()
    assert _all_back(pool)
    dropped = _stream(path, spans[2])       # never closed: __del__
    del dropped
    assert _all_back(pool)


@needs_fused
def test_buffers_come_back_after_the_cut_record_fallback(pool, tmp_path):
    """A final owned record that runs past the span's last block: the
    stream ends through the two-pass fallback, whose rows complete it."""
    header = make_header()
    from hadoop_bam_tpu.formats.bamio import read_bam
    tmp = str(tmp_path / "tmp.bam")
    with BamWriter(tmp, header) as w:
        for r in make_records(header, 40, seed=9):
            w.write_sam_record(r)
    _, batch = read_bam(tmp)
    recs = [batch.record_bytes(i) for i in range(40)]
    rec_offs = np.cumsum([0] + [len(r) for r in recs])[:-1]
    empty = str(tmp_path / "hdr.bam")
    with BamWriter(empty, header):
        pass
    hdr_bytes = open(empty, "rb").read()[:-len(bgzf.EOF_BLOCK)]
    payload, chunk = b"".join(recs), 100    # every record crosses blocks
    path = str(tmp_path / "tiny.bam")
    with open(path, "wb") as f:
        f.write(hdr_bytes + b"".join(
            bgzf.deflate_block(payload[i:i + chunk])
            for i in range(0, len(payload), chunk)) + bgzf.EOF_BLOCK)
    coffs = [b.coffset for b in bgzf.scan_blocks(open(path, "rb").read())
             if b.coffset >= len(hdr_bytes)]
    u = int(rec_offs[20])
    span = FileVirtualSpan(path, len(hdr_bytes) << 16,
                           (coffs[u // chunk] << 16) | (u % chunk + 1))
    want = _oracle_rows(path, span)
    fell_back = []

    def fallback():
        fell_back.append(True)
        return (want,)

    got = np.concatenate([rows for (rows,) in
                          _stream(path, span, fallback_fn=fallback)])
    assert fell_back and got.shape[0] == 21
    assert np.array_equal(got, want)
    assert _all_back(pool)


# ---------------------------------------------------------------------------
# the compressed side: one read into a leased buffer
# ---------------------------------------------------------------------------

def test_one_read_fetches_what_two_reads_and_a_copy_fetched(bam3, pool):
    path, _, spans, _ = bam3
    whole = open(path, "rb").read()
    file_src = FileByteSource(path)
    retrying = RetryingByteSource(FileByteSource(path), RetryPolicy())
    assert retrying.pread_into is not None
    assert BytesByteSource(whole).pread_into is None
    inside = FileVirtualSpan(path, spans[1].start_voffset,
                             spans[1].start_voffset + 7)   # one block
    for span in list(spans) + [inside]:
        want = inflate_ops.fetch_span_raw(BytesByteSource(whole), span)
        assert want[3].array is None        # the pread path leases nothing
        for src in (file_src, retrying):
            raw, end_size, next_c, lease = inflate_ops.fetch_span_raw(
                src, span)
            assert bytes(raw) == bytes(want[0])
            assert (end_size, next_c) == want[1:3]
            assert raw.obj is lease.array
            lease.release()
    assert _all_back(pool)
    past_end = FileVirtualSpan(path, len(whole) << 16, len(whole) << 16)
    raw, end_size, next_c, lease = inflate_ops.fetch_span_raw(
        file_src, past_end)
    assert (len(raw), end_size, next_c) == (0, 0, len(whole))
    assert lease.array is None


# ---------------------------------------------------------------------------
# the header chain: the native walk IS the Python walk
# ---------------------------------------------------------------------------

def _python_block_table(raw, offset=0):
    cols = ([], [], [], [])
    p = offset
    while p < len(raw):
        info = bgzf.parse_block_header(raw, p)
        for col, v in zip(cols, (info.coffset, info.cdata_offset,
                                 info.cdata_size, info.isize)):
            col.append(v)
        p = info.next_coffset
    return cols


def _same_table(raw, offset=0):
    got = inflate_ops.block_table(raw, offset)
    want = _python_block_table(raw, offset)
    assert [got[k].dtype for k in ("coffset", "cdata_off", "cdata_len",
                                   "isize")] \
        == [np.int64, np.int64, np.int32, np.int32]
    for k, w in zip(("coffset", "cdata_off", "cdata_len", "isize"), want):
        assert got[k].tolist() == w, k
    return got


def _with_extra_subfield(block: bytes, before_bc: bool) -> bytes:
    """The same block with a second FEXTRA subfield ('X','Y', 3 bytes)."""
    extra = b"XY" + struct.pack("<H", 3) + b"abc"
    bc = block[12:18]
    bsize = struct.unpack_from("<H", block, 16)[0] + len(extra)
    bc = bc[:4] + struct.pack("<H", bsize)
    xtra = extra + bc if before_bc else bc + extra
    return block[:10] + struct.pack("<H", len(xtra)) + xtra + block[18:]


@needs_native
def test_native_block_table_equals_the_python_walk(bam3):
    path, _, _, _ = bam3
    raw = open(path, "rb").read()
    table = _same_table(raw)
    assert table["isize"].size >= 20
    _same_table(raw, int(table["coffset"][3]))      # from a later block
    _same_table(memoryview(raw))
    assert _same_table(b"")["isize"].size == 0
    # more blocks than the first table holds: the walk goes on
    many = bgzf.EOF_BLOCK * 300
    assert _same_table(many)["isize"].size == 300
    # FEXTRA subfields other than BC, on either side of it
    blocks = [raw[int(c):int(c) + int(n)] for c, n in zip(
        table["coffset"][:4], np.diff(table["coffset"][:5]))]
    mixed = (_with_extra_subfield(blocks[0], True) + blocks[1]
             + _with_extra_subfield(blocks[2], False) + blocks[3])
    got = _same_table(mixed)
    assert got["cdata_off"][0] == 12 + 13 and got["isize"].size == 4
    data, _ = inflate_ops.inflate_span(mixed, got)
    want, _ = inflate_ops.inflate_span(b"".join(blocks))
    assert np.array_equal(data, want)


def _no_bc(block: bytes) -> bytes:
    return block[:12] + b"XY" + block[14:]


@needs_native
@pytest.mark.parametrize("damage,message", [
    (lambda raw, c: raw[:c + 11], "truncated BGZF header"),
    (lambda raw, c: raw[:c] + b"\x1f\x8b\x08\x00" + raw[c + 4:],
     "not a BGZF block: bad gzip magic/flags"),
    (lambda raw, c: raw[:c] + _no_bc(raw[c:]),
     "gzip member without BGZF BC subfield"),
    (lambda raw, c: raw[:c + 200], "truncated BGZF block body"),
    (lambda raw, c: raw[:c + 10] + b"\xff\xff" + raw[c + 12:c + 40],
     "truncated FEXTRA"),
    (lambda raw, c: raw[:c + 16] + b"\x05\x00" + raw[c + 18:],
     "BSIZE smaller than header+footer"),
], ids=["truncated-header", "bad-magic", "missing-BC", "truncated-body",
        "truncated-FEXTRA", "small-BSIZE"])
def test_native_block_table_raises_what_the_python_walk_raises(
        bam3, damage, message):
    path, _, _, _ = bam3
    raw = open(path, "rb").read()
    c = int(inflate_ops.block_table(raw)["coffset"][5])   # five good blocks
    bad = damage(raw, c)
    with pytest.raises(bgzf.BGZFError) as want:
        _python_block_table(bad)
    with pytest.raises(bgzf.BGZFError) as got:
        inflate_ops.block_table(bad)
    assert str(got.value) == str(want.value) == message
    assert type(got.value) is type(want.value)
