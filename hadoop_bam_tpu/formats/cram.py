"""CRAM 3.0 container layer: varints, blocks, containers, file definition.

[SPEC] CRAM 3.0 specification (hts-specs CRAMv3.pdf).  A CRAM file is::

    file definition (26 bytes: "CRAM", major, minor, 20-byte file id)
    container*                       # first container holds the SAM header
    EOF container (38 bytes, fixed)

Each container = container header (lengths, alignment metadata, landmarks,
CRC32) + a series of blocks.  Each block = method, content type, content id,
sizes, payload, CRC32.  Blocks are independently compressed (raw / gzip /
bzip2 / lzma / rANS-4x8) — CRAM's analog of BGZF's position-invariant random
access: containers are the split grain, exactly how hb/CRAMInputFormat.java
aligns Hadoop splits to container boundaries via htsjdk's
``CramContainerIterator``.

This module is the structural layer only; entropy codecs live in
cram_codecs.py, record semantics in cram_decode.py / cram_encode.py, file
orchestration in cramio.py.
"""
from __future__ import annotations

import os
import struct
import time
import zlib
from dataclasses import dataclass, field
from typing import BinaryIO, Iterator, List, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.utils.metrics import METRICS

CRAM_MAGIC = b"CRAM"
CRAM_MAJOR = 3
CRAM_MINOR = 0

# Block compression methods [SPEC section 8; CRAM 3.1 adds 5-8]
RAW, GZIP, BZIP2, LZMA, RANS4x8 = 0, 1, 2, 3, 4
RANSNx16, ARITH, FQZCOMP, NAME_TOK = 5, 6, 7, 8

# every 3.1 block method decodes: 5 rANS Nx16 (cram_codecs_nx16),
# 6 adaptive arithmetic (cram_arith), 7 fqzcomp (cram_fqzcomp),
# 8 name tokenizer (cram_name_tok3)

# Block content types [SPEC section 8.1]
FILE_HEADER = 0
COMPRESSION_HEADER = 1
MAPPED_SLICE_HEADER = 2
EXTERNAL_DATA = 4
CORE_DATA = 5

# Sentinel used as the alignment start of the EOF container: "EOF" read as a
# 24-bit big-endian integer.  [SPEC section 9]
EOF_ALIGNMENT_START = 0x454F46


class CRAMError(ValueError):
    pass


# ---------------------------------------------------------------------------
# ITF8 / LTF8 variable-length integers [SPEC section 2.3]
# ---------------------------------------------------------------------------

def read_itf8(buf: bytes, pos: int) -> Tuple[int, int]:
    """Decode one ITF8 (32-bit) value; returns (signed value, new pos)."""
    b0 = buf[pos]
    if b0 < 0x80:
        v, pos = b0, pos + 1
    elif b0 < 0xC0:
        v = ((b0 & 0x3F) << 8) | buf[pos + 1]
        pos += 2
    elif b0 < 0xE0:
        v = ((b0 & 0x1F) << 16) | (buf[pos + 1] << 8) | buf[pos + 2]
        pos += 3
    elif b0 < 0xF0:
        v = ((b0 & 0x0F) << 24) | (buf[pos + 1] << 16) | (buf[pos + 2] << 8) \
            | buf[pos + 3]
        pos += 4
    else:
        # 5-byte form: only the LOW 4 bits of the final byte are used [SPEC]
        v = ((b0 & 0x0F) << 28) | (buf[pos + 1] << 20) | (buf[pos + 2] << 12) \
            | (buf[pos + 3] << 4) | (buf[pos + 4] & 0x0F)
        pos += 5
    if v & 0x80000000:
        v -= 1 << 32
    return v, pos


def write_itf8(v: int) -> bytes:
    v &= 0xFFFFFFFF
    if v < 0x80:
        return bytes([v])
    if v < 0x4000:
        return bytes([0x80 | (v >> 8), v & 0xFF])
    if v < 0x200000:
        return bytes([0xC0 | (v >> 16), (v >> 8) & 0xFF, v & 0xFF])
    if v < 0x10000000:
        return bytes([0xE0 | (v >> 24), (v >> 16) & 0xFF, (v >> 8) & 0xFF,
                      v & 0xFF])
    return bytes([0xF0 | ((v >> 28) & 0x0F), (v >> 20) & 0xFF,
                  (v >> 12) & 0xFF, (v >> 4) & 0xFF, v & 0x0F])


def read_ltf8(buf: bytes, pos: int) -> Tuple[int, int]:
    """Decode one LTF8 (64-bit) value; returns (signed value, new pos)."""
    b0 = buf[pos]
    if b0 < 0x80:
        n = 0
    elif b0 < 0xC0:
        n = 1
    elif b0 < 0xE0:
        n = 2
    elif b0 < 0xF0:
        n = 3
    elif b0 < 0xF8:
        n = 4
    elif b0 < 0xFC:
        n = 5
    elif b0 < 0xFE:
        n = 6
    elif b0 < 0xFF:
        n = 7
    else:
        n = 8
    mask = (1 << (7 - n)) - 1 if n < 8 else 0
    v = b0 & mask
    for i in range(n):
        v = (v << 8) | buf[pos + 1 + i]
    pos += 1 + n
    if v & (1 << 63):
        v -= 1 << 64
    return v, pos


def write_ltf8(v: int) -> bytes:
    v &= 0xFFFFFFFFFFFFFFFF
    if v < (1 << 7):
        return bytes([v])
    for n in range(1, 8):
        if v < (1 << (7 * (n + 1))):
            prefix = (0xFF << (8 - n)) & 0xFF
            out = [prefix | (v >> (8 * n))]
            for i in range(n - 1, -1, -1):
                out.append((v >> (8 * i)) & 0xFF)
            return bytes(out)
    out = [0xFF]
    for i in range(7, -1, -1):
        out.append((v >> (8 * i)) & 0xFF)
    return bytes(out)


def read_itf8_array(buf: bytes, pos: int) -> Tuple[List[int], int]:
    n, pos = read_itf8(buf, pos)
    out = []
    for _ in range(n):
        v, pos = read_itf8(buf, pos)
        out.append(v)
    return out, pos


def write_itf8_array(vals) -> bytes:
    out = [write_itf8(len(vals))]
    out += [write_itf8(v) for v in vals]
    return b"".join(out)


# ---------------------------------------------------------------------------
# File definition [SPEC section 6]
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FileDefinition:
    major: int = CRAM_MAJOR
    minor: int = CRAM_MINOR
    file_id: bytes = b"\x00" * 20

    SIZE = 26

    def to_bytes(self) -> bytes:
        fid = (self.file_id + b"\x00" * 20)[:20]
        return CRAM_MAGIC + bytes([self.major, self.minor]) + fid

    @classmethod
    def from_bytes(cls, buf: bytes) -> "FileDefinition":
        if buf[:4] != CRAM_MAGIC:
            raise CRAMError("not a CRAM file (bad magic)")
        major, minor = buf[4], buf[5]
        if major != 3:
            raise CRAMError(f"unsupported CRAM version {major}.{minor} "
                            "(this reader implements CRAM 3.0)")
        return cls(major, minor, bytes(buf[6:26]))


# ---------------------------------------------------------------------------
# Blocks [SPEC section 8]
# ---------------------------------------------------------------------------

@dataclass
class Block:
    """One CRAM block; ``data`` is always the UNCOMPRESSED payload."""
    content_type: int
    content_id: int = 0
    data: bytes = b""
    method: int = RAW          # method to use when serializing
    # method-specific serialization context: for FQZCOMP, the per-record
    # lengths of the concatenated quality payload (the codec models
    # record boundaries; a plain byte blob has none)
    aux: Optional[list] = None

    def to_bytes(self) -> bytes:
        raw = self.data
        method = self.method
        if method == GZIP:
            co = zlib.compressobj(6, zlib.DEFLATED, 31)
            comp = co.compress(raw) + co.flush()
        elif method == RANS4x8:
            from hadoop_bam_tpu.formats.cram_codecs import rans4x8_encode
            comp = rans4x8_encode(raw, order=0)
        elif method == RANSNx16:
            from hadoop_bam_tpu.formats.cram_codecs_nx16 import (
                NX16_PACK, NX16_RLE, rans_nx16_encode,
            )
            comp = rans_nx16_encode(raw, NX16_PACK | NX16_RLE)
        elif method == NAME_TOK:
            from hadoop_bam_tpu.formats.cram_name_tok3 import (
                Tok3Error, tok3_encode,
            )
            try:
                comp = tok3_encode(raw)
            except Tok3Error:
                # payload isn't a clean name block; general codec instead
                from hadoop_bam_tpu.formats.cram_codecs_nx16 import (
                    NX16_PACK, NX16_RLE, rans_nx16_encode,
                )
                method = RANSNx16
                comp = rans_nx16_encode(raw, NX16_PACK | NX16_RLE)
        elif method == ARITH:
            from hadoop_bam_tpu.formats.cram_arith import (
                ARITH_ORDER1, arith_encode,
            )
            comp = arith_encode(raw, ARITH_ORDER1)
        elif method == FQZCOMP:
            from hadoop_bam_tpu.formats.cram_fqzcomp import fqz_encode
            # no rANS fallback here: fqz_encode only raises when the
            # per-record lengths disagree with the payload — a writer
            # bug that must surface at write time, not ship as a
            # silently-downgraded block
            comp = fqz_encode(raw, self.aux if self.aux else [len(raw)])
        elif method == RAW:
            comp = raw
        else:
            raise CRAMError(f"unsupported write method {method}")
        # don't let a poorly-compressing payload grow the file
        if method != RAW and len(comp) >= len(raw):
            method, comp = RAW, raw
        body = bytes([method, self.content_type]) + write_itf8(self.content_id) \
            + write_itf8(len(comp)) + write_itf8(len(raw)) + comp
        return body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)

    @classmethod
    def from_buffer(cls, buf: bytes, pos: int) -> Tuple["Block", int]:
        raw, pos = parse_raw_block(buf, pos)
        return cls.from_raw(raw), pos

    @classmethod
    def from_raw(cls, raw: "RawBlock",
                 data: Optional[bytes] = None) -> "Block":
        """Materialize from a parsed-but-compressed block; ``data``
        overrides decompression (the batched rANS path).  A block parsed
        lazily has its CRC32 checked here, before it is decompressed."""
        aux = None
        if data is None:
            raw.verify()
            payload = bytes(raw.payload)
            t_cpu = time.thread_time_ns()
            with METRICS.span("cram.entropy_wall"):
                if raw.method == FQZCOMP:
                    # capture the codec's own per-record lengths: the
                    # slice decoder cross-checks them against the RL
                    # series (the fqzcomp desync tripwire)
                    from hadoop_bam_tpu.formats.cram_fqzcomp import (
                        fqz_decode,
                    )
                    aux = []
                    data = fqz_decode(payload, raw.rsize, lens_out=aux)
                else:
                    data = decompress_block_payload(raw.method, payload,
                                                    raw.rsize)
            METRICS.count("cram.entropy_busy_ns",
                          time.thread_time_ns() - t_cpu)
        if len(data) != raw.rsize:
            raise CRAMError(
                f"block inflated to {len(data)} bytes, expected "
                f"{raw.rsize}")
        return cls(raw.content_type, raw.content_id, data, raw.method,
                   aux)


@dataclass
class RawBlock:
    """A block header + still-compressed payload — the unit the batched
    entropy decoders consume.  Parsed eagerly its CRC is already checked;
    parsed lazily (``parse_raw_block(..., lazy=True)``) the payload is a
    view of the container's bytes and ``crc`` is checked by ``verify``,
    when the block is first decompressed."""
    method: int
    content_type: int
    content_id: int
    payload: bytes
    rsize: int
    crc: Optional[int] = None          # still to check over ``covered``
    covered: Optional[memoryview] = None

    def verify(self) -> None:
        if self.crc is None:
            return
        if zlib.crc32(self.covered) & 0xFFFFFFFF != self.crc:
            raise CRAMError("block CRC32 mismatch")
        self.crc = self.covered = None


def parse_raw_block(buf: bytes, pos: int,
                    lazy: bool = False) -> Tuple[RawBlock, int]:
    start = pos
    method = buf[pos]
    ctype = buf[pos + 1]
    pos += 2
    cid, pos = read_itf8(buf, pos)
    csize, pos = read_itf8(buf, pos)
    rsize, pos = read_itf8(buf, pos)
    if lazy:
        if csize < 0 or pos + csize + 4 > len(buf):
            raise CRAMError("truncated block payload")
        view = memoryview(buf)
        (crc,) = struct.unpack_from("<I", buf, pos + csize)
        raw = RawBlock(method, ctype, cid, view[pos:pos + csize], rsize,
                       crc, view[start:pos + csize])
        return raw, pos + csize + 4
    payload = bytes(buf[pos:pos + csize])
    if len(payload) != csize:
        raise CRAMError("truncated block payload")
    pos += csize
    (crc,) = struct.unpack_from("<I", buf, pos)
    pos += 4
    if zlib.crc32(buf[start:pos - 4]) & 0xFFFFFFFF != crc:
        raise CRAMError("block CRC32 mismatch")
    return RawBlock(method, ctype, cid, payload, rsize), pos


class LazyBlock:
    """A block of a lazily read container: header fields at once, the
    payload checked and decompressed the first time ``data`` is read — so
    a consumer that never asks for a content id never pays its entropy
    decode (``cram.blocks_read_bytes`` / ``cram.blocks_skipped_bytes``
    count the compressed bytes of each kind)."""

    __slots__ = ("raw", "_block")

    def __init__(self, raw: RawBlock):
        self.raw = raw
        self._block: Optional[Block] = None

    content_type = property(lambda self: self.raw.content_type)
    content_id = property(lambda self: self.raw.content_id)
    method = property(lambda self: self.raw.method)

    @property
    def touched(self) -> bool:
        return self._block is not None

    def _get(self) -> Block:
        if self._block is None:
            self._block = Block.from_raw(self.raw)
            METRICS.count("cram.blocks_read_bytes", len(self.raw.payload))
        return self._block

    @property
    def data(self) -> bytes:
        return self._get().data

    @property
    def aux(self) -> Optional[list]:
        return self._get().aux


def decompress_nx16_blocks(blocks, cids) -> None:
    """Decompress, in ONE native call with the interpreter lock released,
    every rANS Nx16 block of a lazily read container whose content id is
    in ``cids`` and that nobody has decompressed yet — what a decoder is
    about to ask for, a slice's worth of streams at once instead of a
    Python round trip a block.  CRC32s are checked first.  A stream the
    batch cannot decode is left to its own first read, which words the
    error or takes the Python decoder."""
    from hadoop_bam_tpu.utils import native

    todo = [b for b in blocks
            if isinstance(b, LazyBlock) and not b.touched
            and b.method == RANSNx16 and b.content_id in cids]
    if not todo or not native.available():
        return
    for b in todo:
        b.raw.verify()
    # untouched arrays: their pages are first written by the decoder,
    # with the lock released (a bytearray is zeroed under it)
    outs = [np.empty(b.raw.rsize, np.uint8) for b in todo]
    t_cpu = time.thread_time_ns()
    with METRICS.span("cram.entropy_wall"):
        rc = native.rans_nx16_decode_batch([b.raw.payload for b in todo],
                                           outs)
    METRICS.count("cram.entropy_busy_ns", time.thread_time_ns() - t_cpu)
    read = decoded = 0
    for b, out, r in zip(todo, outs, rc):
        if r == 0:
            b._block = Block(b.raw.content_type, b.raw.content_id, out,
                             b.raw.method)
            read += len(b.raw.payload)
            decoded += len(out)
    if decoded:
        METRICS.count("cram.blocks_read_bytes", read)
        METRICS.count("cram.nx16_native_bytes", decoded)


def count_skipped_blocks(blocks) -> int:
    """Count (``cram.blocks_skipped_bytes``) and return the compressed
    bytes of a lazily read container's blocks nobody decompressed."""
    skipped = sum(len(b.raw.payload) for b in blocks
                  if isinstance(b, LazyBlock) and not b.touched)
    if skipped:
        METRICS.count("cram.blocks_skipped_bytes", skipped)
    return skipped


def decompress_block_payload(method: int, payload: bytes, rsize: int) -> bytes:
    if method == RAW:
        return payload
    if method == GZIP:
        return zlib.decompress(payload, wbits=31)
    if method == BZIP2:
        import bz2
        return bz2.decompress(payload)
    if method == LZMA:
        import lzma
        return lzma.decompress(payload)
    if method == RANS4x8:
        from hadoop_bam_tpu.formats.cram_codecs import rans4x8_decode
        return rans4x8_decode(payload)
    if method == RANSNx16:
        from hadoop_bam_tpu.formats.cram_codecs_nx16 import (
            rans_nx16_decode_block,
        )
        return rans_nx16_decode_block(payload, rsize)
    if method == NAME_TOK:
        from hadoop_bam_tpu.formats.cram_name_tok3 import tok3_decode
        return tok3_decode(payload, rsize)
    if method == FQZCOMP:
        from hadoop_bam_tpu.formats.cram_fqzcomp import fqz_decode
        return fqz_decode(payload, rsize)
    if method == ARITH:
        from hadoop_bam_tpu.formats.cram_arith import arith_decode
        return arith_decode(payload, rsize)
    raise CRAMError(f"unknown block compression method {method}")


# ---------------------------------------------------------------------------
# Container header [SPEC section 7]
# ---------------------------------------------------------------------------

@dataclass
class ContainerHeader:
    length: int                 # byte length of the blocks section
    ref_seq_id: int = -1        # -1 unmapped, -2 multi-ref
    start: int = 0
    span: int = 0
    n_records: int = 0
    record_counter: int = 0
    bases: int = 0
    n_blocks: int = 0
    landmarks: List[int] = field(default_factory=list)

    def to_bytes(self) -> bytes:
        body = struct.pack("<i", self.length)
        body += write_itf8(self.ref_seq_id) + write_itf8(self.start)
        body += write_itf8(self.span) + write_itf8(self.n_records)
        body += write_ltf8(self.record_counter) + write_ltf8(self.bases)
        body += write_itf8(self.n_blocks) + write_itf8_array(self.landmarks)
        return body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)

    @classmethod
    def from_buffer(cls, buf: bytes, pos: int) -> Tuple["ContainerHeader", int]:
        start0 = pos
        (length,) = struct.unpack_from("<i", buf, pos)
        pos += 4
        ref_seq_id, pos = read_itf8(buf, pos)
        start, pos = read_itf8(buf, pos)
        span, pos = read_itf8(buf, pos)
        n_records, pos = read_itf8(buf, pos)
        record_counter, pos = read_ltf8(buf, pos)
        bases, pos = read_ltf8(buf, pos)
        n_blocks, pos = read_itf8(buf, pos)
        landmarks, pos = read_itf8_array(buf, pos)
        (crc,) = struct.unpack_from("<I", buf, pos)
        pos += 4
        if zlib.crc32(buf[start0:pos - 4]) & 0xFFFFFFFF != crc:
            raise CRAMError("container header CRC32 mismatch")
        return cls(length, ref_seq_id, start, span, n_records, record_counter,
                   bases, n_blocks, landmarks), pos

    @property
    def is_eof(self) -> bool:
        return (self.n_records == 0 and self.ref_seq_id == -1
                and self.start == EOF_ALIGNMENT_START)


@dataclass
class Container:
    header: ContainerHeader
    blocks: List[Block]         # LazyBlock each when read lazily
    offset: int = 0             # absolute file offset of the container start


def build_container(blocks: List[Block], *, ref_seq_id: int, start: int,
                    span: int, n_records: int, record_counter: int,
                    bases: int, landmarks: List[int]) -> bytes:
    payload = b"".join(b.to_bytes() for b in blocks)
    hdr = ContainerHeader(
        length=len(payload), ref_seq_id=ref_seq_id, start=start, span=span,
        n_records=n_records, record_counter=record_counter, bases=bases,
        n_blocks=len(blocks), landmarks=landmarks)
    return hdr.to_bytes() + payload


def eof_container() -> bytes:
    """The CRAM 3.0 EOF container: an empty container whose alignment start
    spells "EOF".  Constructed (not pasted) — the result must be exactly the
    38-byte marker the spec fixes; cramio asserts that at import time."""
    empty_maps = b"\x01\x00" * 3   # three empty maps: size=1, count=0
    blk = Block(COMPRESSION_HEADER, 0, empty_maps, RAW)
    return build_container(
        [blk], ref_seq_id=-1, start=EOF_ALIGNMENT_START, span=0, n_records=0,
        record_counter=0, bases=0, landmarks=[])


EOF_CONTAINER = eof_container()
assert len(EOF_CONTAINER) == 38, len(EOF_CONTAINER)


# ---------------------------------------------------------------------------
# Scanning (the split grain — hb/CRAMInputFormat.java's container iterator)
# ---------------------------------------------------------------------------

def read_container(buf: bytes, pos: int,
                   rans_backend: Optional[str] = None,
                   lazy: bool = False) -> Tuple[Container, int]:
    """Parse one container.  All rANS blocks decode in ONE batch — the
    intra-container block parallelism the device decoder (ops/rans.py)
    exploits; ``rans_backend`` (default env HBAM_RANS_BACKEND or "host")
    picks where.  ``lazy`` leaves every block compressed (``LazyBlock``):
    a block is checked and decompressed when its ``data`` is first read."""
    offset = pos
    hdr, pos = ContainerHeader.from_buffer(buf, pos)
    end = pos + hdr.length
    raws: List[RawBlock] = []
    while pos < end:
        raw, pos = parse_raw_block(buf, pos, lazy=lazy)
        raws.append(raw)
    if pos != end:
        raise CRAMError("container blocks overran the declared length")
    if lazy:
        return Container(hdr, [LazyBlock(r) for r in raws], offset), pos

    backend = rans_backend or os.environ.get("HBAM_RANS_BACKEND", "host")
    if backend not in ("host", "device", "auto"):
        raise CRAMError(f"unknown rANS backend {backend!r} "
                        "(expected host/device/auto)")
    decoded: dict = {}
    rans_idx = [i for i, r in enumerate(raws) if r.method == RANS4x8]
    if backend == "device" and rans_idx:
        from hadoop_bam_tpu.ops.rans import rans_decode_batch
        outs = rans_decode_batch([raws[i].payload for i in rans_idx],
                                 backend=backend)
        decoded = dict(zip(rans_idx, outs))
    blocks = [Block.from_raw(r, decoded.get(i))
              for i, r in enumerate(raws)]
    return Container(hdr, blocks, offset), pos


def scan_container_offsets(buf: bytes, pos: int = FileDefinition.SIZE
                           ) -> Iterator[Tuple[int, ContainerHeader]]:
    """Yield (absolute offset, header) of every container without inflating
    any block — the cheap pass split planning needs."""
    n = len(buf)
    while pos < n:
        offset = pos
        hdr, after = ContainerHeader.from_buffer(buf, pos)
        yield offset, hdr
        pos = after + hdr.length
