"""Native inflate tests: the host plane every scan depends on.

``native.inflate_batch`` (libdeflate when built in, zlib otherwise) is
what the fused BAM read (PR 27) and the leased BCF span read (PR 29)
inflate with.  The parity oracle is Python zlib: every payload below is
deflated by zlib — not by this repo's writer — across all DEFLATE block
types (stored / fixed / dynamic), deep copy chains and multi-block
streams, inflated natively and compared with the original bytes.  Level
0 (stored blocks) is what ``samtools view -u`` pipes.

The span-level tests hold the native plane to the zlib plane through
``ops.inflate.inflate_span`` and the leased span read: randomized block
boundaries and split offsets, BCF- and tabix-shaped containers,
byte-flip fuzz raising the same error class on both planes, and a CRC
flip that only ``check_crc`` sees."""
import random
import struct
import zlib

import numpy as np
import pytest

from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.ops.inflate import (
    block_table, fetch_span_raw, inflate_span, verify_crcs,
)
from hadoop_bam_tpu.split.spans import FileVirtualSpan
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.errors import CORRUPT, classify_error
from hadoop_bam_tpu.utils.pools import SPAN_BUFFERS
from hadoop_bam_tpu.utils.seekable import as_byte_source

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")


def _deflate(data: bytes, level: int = 6, strategy: int = 0) -> bytes:
    co = zlib.compressobj(level, zlib.DEFLATED, -15, 9, strategy)
    return co.compress(data) + co.flush()


def _inflate_batch(comps, sizes, n_threads: int = 1):
    """Each raw DEFLATE stream of ``comps`` through ONE native batch."""
    src = np.frombuffer(b"".join(comps), np.uint8)
    lens = np.array([len(c) for c in comps], np.int32)
    offs = np.concatenate([[0], np.cumsum(lens[:-1])]).astype(np.int64)
    isize = np.array(sizes, np.int32)
    dst_off = np.concatenate([[0], np.cumsum(isize[:-1])]).astype(np.int64)
    dst = np.empty(int(isize.sum()), np.uint8)
    native.inflate_batch(src, offs, lens, dst, dst_off, isize, n_threads)
    return [dst[int(o):int(o) + int(n)].tobytes()
            for o, n in zip(dst_off, isize)]


def _payloads():
    rng = random.Random(3)
    return {
        "empty": b"",
        "one": b"A",
        "text": b"hello deflate world " * 200,
        "random": bytes(rng.randrange(256) for _ in range(50000)),
        "dna": bytes(rng.choice(b"ACGT") for _ in range(60000)),
        "rle_deep": b"A" * 65000,             # dist-1 overlapping copies
        "alternating": b"AB" * 30000,
        "qual": bytes(rng.choice(b"FFFFFF:,#IIII") for _ in range(64000)),
    }


@pytest.mark.parametrize("level", [0, 1, 6, 9])   # 0 = stored blocks
@pytest.mark.parametrize("name", sorted(_payloads()))
def test_inflate_batch_parity_vs_zlib(name, level):
    data = _payloads()[name]
    comp = _deflate(data, level)
    assert zlib.decompress(comp, -15) == data
    assert _inflate_batch([comp], [len(data)]) == [data]


@pytest.mark.parametrize("name", ["dna", "rle_deep", "random"])
def test_fixed_huffman_blocks(name):
    data = _payloads()[name]
    assert _inflate_batch([_deflate(data, 6, zlib.Z_FIXED)],
                          [len(data)]) == [data]


def test_multi_deflate_block_stream():
    """One stream of several DEFLATE blocks (full flushes between them:
    each ends on an empty stored block), as one BGZF payload."""
    rng = random.Random(11)
    co = zlib.compressobj(6, zlib.DEFLATED, -15)
    parts, data = [], b""
    for _ in range(5):
        d = bytes(rng.choice(b"ACGTN") for _ in range(8000))
        data += d
        parts.append(co.compress(d))
        parts.append(co.flush(zlib.Z_FULL_FLUSH))
    parts.append(co.flush())
    assert _inflate_batch([b"".join(parts)], [len(data)]) == [data]


@pytest.mark.parametrize("n_threads", [1, 4])
def test_batch_many_blocks(n_threads):
    """Forty heterogeneous streams at mixed levels in one batch, each
    landing at its own offset of the one output buffer."""
    rng = random.Random(13)
    datas = [bytes(rng.choice(b"ACGT") for _ in range(rng.randrange(1, 3000)))
             for _ in range(40)]
    comps = [_deflate(d, rng.choice([0, 1, 6, 9])) for d in datas]
    assert _inflate_batch(comps, [len(d) for d in datas],
                          n_threads) == datas


def test_corrupt_stream_rejected():
    """A flipped byte fails the batch and the error names the block; a
    stream that inflates to another length than its ISIZE fails too."""
    datas = [b"ACGTN" * 5000, b"TTGCA" * 4000, b"GATTACA" * 3000]
    comps = [_deflate(d) for d in datas]
    bad = bytearray(comps[1])
    bad[10] ^= 0xFF
    with pytest.raises(ValueError, match="block 1"):
        _inflate_batch([comps[0], bytes(bad), comps[2]],
                       [len(d) for d in datas])
    for wrong in (len(datas[0]) - 1, len(datas[0]) + 1):
        with pytest.raises(ValueError, match="block 0"):
            _inflate_batch([comps[0]], [wrong])


@pytest.mark.parametrize("cut", ["header", "mid-block", "final-block"])
def test_truncated_stream_rejected(cut):
    data = b"ACGTN" * 5000 + bytes(range(256)) * 40
    comp = _deflate(data)
    keep = {"header": 2, "mid-block": len(comp) // 2,
            "final-block": len(comp) - 1}[cut]
    with pytest.raises(ValueError, match="block 0"):
        _inflate_batch([comp[:keep]], [len(data)])


# ---------------------------------------------------------------------------
# span level: the native plane against the zlib plane
# ---------------------------------------------------------------------------

def _bgzf_block(payload: bytes, level: int) -> bytes:
    """One BGZF block framed by hand around a zlib-made DEFLATE stream
    (independent of the repo's writer, which deflates natively)."""
    cdata = _deflate(payload, level)
    size = 18 + len(cdata) + 8
    assert size <= bgzf.MAX_BLOCK_SIZE
    return (struct.pack("<BBBBIBBH", 31, 139, 8, 4, 0, 0, 255, 6)
            + struct.pack("<BBHH", 66, 67, 2, size - 1) + cdata
            + struct.pack("<II", zlib.crc32(payload) & 0xFFFFFFFF,
                          len(payload)))


def _bgzf_stream(payload: bytes, rng, sizes=(60000,), levels=(6,)) -> bytes:
    blocks, pos = [], 0
    while pos < len(payload):
        take = rng.choice(sizes)
        blocks.append(_bgzf_block(payload[pos:pos + take],
                                  rng.choice(levels)))
        pos += take
    return b"".join(blocks) + bgzf.EOF_BLOCK


def _leased_native(path: str, start_voffset: int, end_voffset: int):
    """The span read of PR 27 / PR 29: one positioned read of the
    compressed range, the header walk, ONE native inflate of its blocks
    into a buffer leased from the span-buffer pool.  Returns the bytes
    and the table."""
    src = as_byte_source(path)
    raw, _end_size, _next_c, raw_lease = fetch_span_raw(
        src, FileVirtualSpan(path, start_voffset, end_voffset))
    try:
        table = block_table(raw)
        lease = SPAN_BUFFERS.lease(int(table["isize"].sum()))
        try:
            data, _ubase = inflate_span(raw, table, backend="native",
                                        n_threads=1, out=lease.array)
            return data.tobytes(), table
        finally:
            lease.release()
    finally:
        raw_lease.release()
        src.close()


def test_span_randomized_split_offsets(tmp_path):
    """Byte identity of the native plane and the leased span read with
    the zlib plane over a BGZF stream whose block boundaries, block
    sizes and levels are drawn at random, whole and from split offsets
    that start and end inside it (the shapes real split plans make)."""
    rng = random.Random(41)
    payload = bytes(rng.choice(b"ACGTNacgtn#!Fqual\t|") for _ in range(150000))
    raw = _bgzf_stream(payload, rng, sizes=(37, 511, 2048, 30000, 60000),
                       levels=(0, 1, 6, 9))
    want, want_ubase = inflate_span(raw, backend="zlib")
    assert want.tobytes() == payload
    got, ubase = inflate_span(raw, backend="native")
    assert np.array_equal(got, want) and np.array_equal(ubase, want_ubase)

    path = str(tmp_path / "split.bgzf")
    with open(path, "wb") as f:
        f.write(raw)
    table = block_table(raw)
    coff, isize = table["coffset"], table["isize"]
    n = int(coff.size) - 1                   # the EOF block stays out
    for _ in range(12):
        i = rng.randrange(n)
        j = rng.randrange(i, n)
        # end_u == 0 ends the span before block j; > 0 ends inside it
        end_u = rng.choice([0, 1, int(isize[j])])
        if end_u == 0 and j == i:
            continue
        data, sub = _leased_native(path, int(coff[i]) << 16,
                                   (int(coff[j]) << 16) | end_u)
        hi = int(want_ubase[j]) + (int(isize[j]) if end_u else 0)
        assert data == payload[int(want_ubase[i]):hi], (i, j, end_u)
        assert np.array_equal(sub["isize"], isize[i:j + (1 if end_u else 0)])


def test_bcf_and_tabix_shaped_spans_identity(tmp_path):
    """The plane is container-agnostic: BCF bytes (binary BGZF) and a
    bgzipped VCF (the tabix container shape) inflate byte-identically on
    the native plane, the leased read and the zlib plane, and every CRC
    footer verifies against all three."""
    from hadoop_bam_tpu.api.writers import open_vcf_writer
    from hadoop_bam_tpu.formats.vcf import VCFHeader, VcfRecord

    hdr_text = (
        "##fileformat=VCFv4.2\n"
        "##contig=<ID=chr20,length=64444167>\n"
        '##INFO=<ID=DP,Number=1,Type=Integer,Description="Depth">\n'
        '##FORMAT=<ID=GT,Number=1,Type=String,Description="GT">\n'
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\ts0\ts1\n")
    header = VCFHeader.from_text(hdr_text)
    rng = random.Random(5)
    lines = []
    bcf = str(tmp_path / "t.bcf")
    with open_vcf_writer(bcf, header) as w:
        for i in range(500):
            rec = VcfRecord.from_line(
                f"chr20\t{1000 + 7 * i}\t.\tA\tG\t{rng.randint(1, 99)}"
                f"\tPASS\tDP={rng.randint(1, 60)}\tGT"
                f"\t{rng.choice(['0/0', '0/1', '1/1'])}"
                f"\t{rng.choice(['0/0', './.'])}")
            w.write_record(rec)
            lines.append(rec.to_line())
    tabix = str(tmp_path / "t.vcf.gz")
    with open(tabix, "wb") as f:
        f.write(_bgzf_stream((hdr_text + "\n".join(lines) + "\n").encode(),
                             rng, sizes=(4096, 20000)))
    for path in (bcf, tabix):
        raw = open(path, "rb").read()
        table = block_table(raw)
        want, want_ubase = inflate_span(raw, table, backend="zlib")
        got, ubase = inflate_span(raw, table, backend="native")
        assert np.array_equal(got, want)
        assert np.array_equal(ubase, want_ubase)
        leased, _ = _leased_native(path, 0, len(raw) << 16)
        assert leased == want.tobytes()
        for data in (want, got):
            verify_crcs(raw, table, data, ubase)


def test_byte_flip_fuzz_same_error_class():
    """Flipping a byte anywhere in the compressed span gives the SAME
    outcome on the native plane as on the zlib plane: the same bytes, or
    a BGZFError of the CORRUPT class on both."""
    rng = random.Random(9)
    payload = bytes(rng.choice(b"ACGT#F!") for _ in range(40000))
    raw = _bgzf_stream(payload, rng, sizes=(9000,), levels=(1, 6))
    mismatches = []
    for pos in rng.sample(range(len(raw)), 60):
        bad = bytearray(raw)
        bad[pos] ^= 0xFF
        bad = bytes(bad)
        outcomes = []
        for backend in ("zlib", "native"):
            try:
                data, _ = inflate_span(bad, backend=backend)
                outcomes.append(("ok", data.tobytes()))
            except Exception as e:  # noqa: BLE001 — class comparison
                outcomes.append(("err", isinstance(e, bgzf.BGZFError),
                                 classify_error(e)))
        if outcomes[0] != outcomes[1]:
            mismatches.append((pos, outcomes))
        if outcomes[0][0] == "err":
            assert outcomes[0][1:] == (True, CORRUPT)
    assert not mismatches, mismatches


def test_crc_flip_only_fails_with_check_crc():
    """A flipped CRC footer byte changes no inflated byte on either
    plane; only the CRC sweep sees it, and it raises the same class for
    both planes' bytes as the Python block reader does."""
    rng = random.Random(3)
    payload = bytes(rng.choice(b"ACGT") for _ in range(30000))
    raw = _bgzf_stream(payload, rng, sizes=(12000,))
    table = block_table(raw)
    # the CRC footer sits right after a block's DEFLATE payload
    foot = int(table["cdata_off"][1] + table["cdata_len"][1])
    bad = bytearray(raw)
    bad[foot] ^= 0xFF
    bad = bytes(bad)
    for backend in ("zlib", "native"):
        data, ubase = inflate_span(bad, backend=backend)
        assert data.tobytes() == payload
        with pytest.raises(bgzf.BGZFError, match="CRC32 mismatch"):
            verify_crcs(bad, block_table(bad), data, ubase)
        verify_crcs(raw, table, data, ubase)      # the clean footers pass
    r = bgzf.BGZFReader(bad, check_crc=True)
    with pytest.raises(bgzf.BGZFError, match="CRC32 mismatch"):
        r.read(len(payload))
