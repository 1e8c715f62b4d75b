"""BAM binary format: header, record layout, structure-of-arrays batches.

[SPEC] SAMv1 section 4.2.  A BAM file is a BGZF stream whose inflated contents
are::

    magic "BAM\\1" | l_text (i32) | text | n_ref (i32) |
    per ref: l_name (i32) | name\\0 | l_ref (i32) |
    records...

Each alignment record::

    block_size i32            # byte length of the rest of the record
    refID      i32            # -1 = unmapped
    pos        i32            # 0-based leftmost, -1 = unmapped
    l_read_name u8            # includes trailing NUL
    mapq       u8
    bin        u16
    n_cigar_op u16
    flag       u16
    l_seq      i32
    next_refID i32
    next_pos   i32
    tlen       i32
    read_name  char[l_read_name]          # NUL-terminated
    cigar      u32[n_cigar_op]            # op_len<<4 | op  (op in "MIDNSHP=X")
    seq        u8[(l_seq+1)/2]            # 4-bit "=ACMGRSVTWYHKDBN"
    qual       u8[l_seq]                  # 0xFF = absent
    tags       ...                        # two-char tag, type char, value

Reference equivalents: htsjdk ``BAMRecordCodec`` (decode/encode) and
hb/SAMRecordWritable.java (which serializes via the same layout);
hb/LazyBAMRecordFactory.java's deferred field parse is rebuilt here as the
columnar ``BamBatch``: fields are *gathered on first access* with vectorized
NumPy (and on device in hadoop_bam_tpu/ops/unpack_bam.py), so map-side filters
never pay full parse cost — same goal, SoA shape instead of per-object laziness.
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

BAM_MAGIC = b"BAM\x01"
FIXED_RECORD_PREFIX = 36  # bytes from block_size through tlen inclusive
CORE_AFTER_BLOCKSIZE = 32

SEQ_NIBBLE = "=ACMGRSVTWYHKDBN"          # [SPEC] 4-bit base codes
CIGAR_OPS = "MIDNSHP=X"                  # [SPEC] op codes 0..8
_SEQ_NIBBLE_B = SEQ_NIBBLE.encode()
_CIGAR_OPS_B = CIGAR_OPS.encode()

# Flag bits [SPEC] section 1.4
FPAIRED, FPROPER_PAIR, FUNMAP, FMUNMAP = 0x1, 0x2, 0x4, 0x8
FREVERSE, FMREVERSE, FREAD1, FREAD2 = 0x10, 0x20, 0x40, 0x80
FSECONDARY, FQCFAIL, FDUP, FSUPPLEMENTARY = 0x100, 0x200, 0x400, 0x800


class BAMError(ValueError):
    pass


# ---------------------------------------------------------------------------
# Header
# ---------------------------------------------------------------------------

@dataclass
class SAMHeader:
    """SAM/BAM header: raw @-line text plus the binary reference dictionary.

    The reference reads headers through htsjdk ``SAMFileHeader`` via
    hb/util/SAMHeaderReader.java; here the text is kept verbatim (round-trip
    safe) and the reference dictionary is exposed as parallel arrays because
    the split guesser (hb/BAMSplitGuesser.java) only needs ``n_ref`` and
    per-reference lengths for plausibility checks.
    """

    text: str = ""
    ref_names: List[str] = field(default_factory=list)
    ref_lengths: List[int] = field(default_factory=list)

    @property
    def n_ref(self) -> int:
        return len(self.ref_names)

    def ref_id(self, name: str) -> int:
        try:
            return self.ref_names.index(name)
        except ValueError:
            return -1

    def ref_name(self, rid: int) -> str:
        return "*" if rid < 0 or rid >= self.n_ref else self.ref_names[rid]

    # -- binary (BAM) encoding [SPEC] --
    def to_bam_bytes(self) -> bytes:
        out = bytearray()
        text = self.text.encode()
        out += BAM_MAGIC
        out += struct.pack("<i", len(text))
        out += text
        out += struct.pack("<i", self.n_ref)
        for name, length in zip(self.ref_names, self.ref_lengths):
            nb = name.encode() + b"\x00"
            out += struct.pack("<i", len(nb)) + nb + struct.pack("<i", length)
        return bytes(out)

    @classmethod
    def from_bam_bytes(cls, buf: bytes, offset: int = 0) -> Tuple["SAMHeader", int]:
        """Parse from inflated BAM bytes; returns (header, offset_after)."""
        if buf[offset:offset + 4] != BAM_MAGIC:
            raise BAMError("bad BAM magic")
        p = offset + 4
        (l_text,) = struct.unpack_from("<i", buf, p); p += 4
        text = bytes(buf[p:p + l_text]).rstrip(b"\x00").decode(); p += l_text
        (n_ref,) = struct.unpack_from("<i", buf, p); p += 4
        names, lengths = [], []
        for _ in range(n_ref):
            (l_name,) = struct.unpack_from("<i", buf, p); p += 4
            names.append(bytes(buf[p:p + l_name - 1]).decode()); p += l_name
            (l_ref,) = struct.unpack_from("<i", buf, p); p += 4
            lengths.append(l_ref)
        return cls(text=text, ref_names=names, ref_lengths=lengths), p

    # -- text (SAM) encoding --
    def to_sam_text(self) -> str:
        """Header text, synthesizing @SQ lines from the binary dictionary when
        the text lacks them (htsjdk does the same merge)."""
        if "@SQ" in self.text or not self.ref_names:
            return self.text
        sq = "".join(f"@SQ\tSN:{n}\tLN:{l}\n"
                     for n, l in zip(self.ref_names, self.ref_lengths))
        # @HD first if present, then @SQ, then the rest.
        lines = self.text.splitlines(keepends=True)
        hd = [l for l in lines if l.startswith("@HD")]
        rest = [l for l in lines if not l.startswith("@HD")]
        return "".join(hd) + sq + "".join(rest)

    @classmethod
    def from_sam_text(cls, text: str) -> "SAMHeader":
        names, lengths = [], []
        for line in text.splitlines():
            if line.startswith("@SQ"):
                fields = dict(f.split(":", 1) for f in line.split("\t")[1:]
                              if ":" in f)
                if "SN" in fields and "LN" in fields:
                    names.append(fields["SN"])
                    lengths.append(int(fields["LN"]))
        return cls(text=text if text.endswith("\n") or not text else text + "\n",
                   ref_names=names, ref_lengths=lengths)


def reg2bin(beg: int, end: int) -> int:
    """[SPEC] SAMv1 section 5.3: compute the UCSC binning-scheme bin."""
    end -= 1
    if beg >> 14 == end >> 14:
        return ((1 << 15) - 1) // 7 + (beg >> 14)
    if beg >> 17 == end >> 17:
        return ((1 << 12) - 1) // 7 + (beg >> 17)
    if beg >> 20 == end >> 20:
        return ((1 << 9) - 1) // 7 + (beg >> 20)
    if beg >> 23 == end >> 23:
        return ((1 << 6) - 1) // 7 + (beg >> 23)
    if beg >> 26 == end >> 26:
        return ((1 << 3) - 1) // 7 + (beg >> 26)
    return 0


# ---------------------------------------------------------------------------
# Record walking (boundary discovery) and SoA batch
# ---------------------------------------------------------------------------

def walk_record_offsets(buf, start: int = 0, end: Optional[int] = None,
                        max_records: Optional[int] = None) -> np.ndarray:
    """Serial record-boundary walk: offsets of each record's block_size field.

    The chain offsets[i+1] = offsets[i] + 4 + block_size[i] is inherently
    sequential (this is exactly why BAM is "unsplittable" and the reference
    needs split guessers).  The native C++ path (native/) does this walk at
    memory speed; this NumPy/Python version is the portable reference.
    """
    mv = memoryview(buf)
    n = len(mv) if end is None else end
    offs: List[int] = []
    p = start
    while p + 4 <= n:
        bs = int.from_bytes(mv[p:p + 4], "little", signed=True)
        if bs < CORE_AFTER_BLOCKSIZE:
            raise BAMError(f"bad block_size {bs} at offset {p}")
        if p + 4 + bs > n:
            break  # record truncated at span end (caller handles tail)
        offs.append(p)
        p += 4 + bs
        if max_records is not None and len(offs) >= max_records:
            break
    return np.asarray(offs, dtype=np.int64)


def _gather_u8(data: np.ndarray, idx: np.ndarray) -> np.ndarray:
    return data[idx]


def _gather_le(data: np.ndarray, offs: np.ndarray, nbytes: int, signed: bool
               ) -> np.ndarray:
    """Vectorized little-endian integer gather at arbitrary byte offsets."""
    acc = np.zeros(offs.shape, dtype=np.uint64)
    for i in range(nbytes):
        acc |= data[offs + i].astype(np.uint64) << np.uint64(8 * i)
    if signed:
        bits = 8 * nbytes
        acc = acc.astype(np.int64)
        sign = np.int64(1) << np.int64(bits - 1)
        acc = (acc ^ sign) - sign if nbytes < 8 else acc
        return acc
    return acc.astype(np.int64) if nbytes < 8 else acc


class BamBatch:
    """Structure-of-arrays view over the BAM records inside one inflated span.

    This is the framework's record currency — the analog of a stream of
    htsjdk SAMRecords, but columnar: the inflated bytes are kept as one
    uint8 array and every fixed field is a lazily-gathered NumPy column.
    Variable-length payloads (name/cigar/seq/qual/tags) stay in place in the
    byte buffer and are addressed by per-record offset columns — the SoA
    rebuild of hb/LazyBAMRecordFactory.java's lazy field decode.
    """

    def __init__(self, data: np.ndarray, offsets: np.ndarray,
                 header: Optional[SAMHeader] = None,
                 voffsets: Optional[np.ndarray] = None):
        self.data = np.asarray(data, dtype=np.uint8)
        self.offsets = np.asarray(offsets, dtype=np.int64)
        self.header = header
        # Per-record virtual offsets (the reference's LongWritable record key,
        # hb/BAMRecordReader.java); filled by the reader when known.
        self.voffsets = voffsets
        self._cache: Dict[str, np.ndarray] = {}

    def __len__(self) -> int:
        return int(self.offsets.size)

    def _col(self, name: str, off: int, nbytes: int, signed: bool) -> np.ndarray:
        if name not in self._cache:
            self._cache[name] = _gather_le(self.data, self.offsets + off,
                                           nbytes, signed)
        return self._cache[name]

    # Fixed fields [SPEC layout offsets]
    @property
    def block_size(self): return self._col("block_size", 0, 4, True)
    @property
    def refid(self): return self._col("refid", 4, 4, True)
    @property
    def pos(self): return self._col("pos", 8, 4, True)
    @property
    def l_read_name(self): return self._col("l_read_name", 12, 1, False)
    @property
    def mapq(self): return self._col("mapq", 13, 1, False)
    @property
    def bin(self): return self._col("bin", 14, 2, False)
    @property
    def n_cigar(self): return self._col("n_cigar", 16, 2, False)
    @property
    def flag(self): return self._col("flag", 18, 2, False)
    @property
    def l_seq(self): return self._col("l_seq", 20, 4, True)
    @property
    def mate_refid(self): return self._col("mate_refid", 24, 4, True)
    @property
    def mate_pos(self): return self._col("mate_pos", 28, 4, True)
    @property
    def tlen(self): return self._col("tlen", 32, 4, True)

    # Derived payload offset columns — cached like the fixed fields: the
    # per-record accessors below index them once per record, and
    # rebuilding a whole-batch vector per access made every per-record
    # loop (the host sort / markdup oracles) quadratic in the batch size
    def _derived(self, name: str, build) -> np.ndarray:
        if name not in self._cache:
            self._cache[name] = build()
        return self._cache[name]

    @property
    def name_offset(self):
        return self._derived(
            "name_offset", lambda: self.offsets + FIXED_RECORD_PREFIX)
    @property
    def cigar_offset(self):
        return self._derived(
            "cigar_offset", lambda: self.name_offset + self.l_read_name)
    @property
    def seq_offset(self):
        return self._derived(
            "seq_offset", lambda: self.cigar_offset + 4 * self.n_cigar)
    @property
    def qual_offset(self):
        return self._derived(
            "qual_offset", lambda: self.seq_offset + (self.l_seq + 1) // 2)
    @property
    def tags_offset(self):
        return self._derived(
            "tags_offset", lambda: self.qual_offset + self.l_seq)
    @property
    def record_end(self):
        return self._derived(
            "record_end", lambda: self.offsets + 4 + self.block_size)

    def reference_span(self) -> np.ndarray:
        """Per-record alignment span on the reference (bases consumed by
        M/D/N/=/X CIGAR ops), vectorized over the ragged cigar arrays.
        Records with '*' CIGAR fall back to l_seq (htsjdk's convention for
        computing an end when no cigar is present)."""
        if "ref_span" in self._cache:
            return self._cache["ref_span"]
        counts = self.n_cigar.astype(np.int64)
        total = int(counts.sum())
        span = np.where(self.l_seq > 0, self.l_seq, 0).astype(np.int64)
        if total:
            firsts = np.cumsum(counts) - counts
            flat = np.arange(total, dtype=np.int64) - np.repeat(firsts, counts)
            offs = np.repeat(self.cigar_offset, counts) + 4 * flat
            vals = _gather_le(self.data, offs, 4, False)
            oplen = vals >> 4
            op = vals & 0xF
            consumes = (op == 0) | (op == 2) | (op == 3) | (op == 7) | (op == 8)
            seg = np.repeat(np.arange(counts.size), counts)
            cig_span = np.zeros(counts.size, dtype=np.int64)
            np.add.at(cig_span, seg, (oplen * consumes).astype(np.int64))
            span = np.where(counts > 0, cig_span, span)
        self._cache["ref_span"] = span
        return span

    def select(self, indices: np.ndarray) -> "BamBatch":
        """Row subset sharing the same byte buffer (zero-copy on data)."""
        idx = np.asarray(indices)
        return BamBatch(
            self.data, self.offsets[idx], header=self.header,
            voffsets=None if self.voffsets is None else self.voffsets[idx])

    # Per-record accessors (scalar paths for tests/CLI; batch paths in ops/)
    def read_name(self, i: int) -> str:
        o = int(self.name_offset[i]); l = int(self.l_read_name[i])
        return self.data[o:o + l - 1].tobytes().decode()

    def cigar_string(self, i: int) -> str:
        n = int(self.n_cigar[i])
        if n == 0:
            return "*"
        o = int(self.cigar_offset[i])
        raw = self.data[o:o + 4 * n].view("<u4")
        return "".join(f"{int(v) >> 4}{CIGAR_OPS[int(v) & 0xF]}" for v in raw)

    def seq_string(self, i: int) -> str:
        l = int(self.l_seq[i])
        if l == 0:
            return "*"
        o = int(self.seq_offset[i])
        packed = self.data[o:o + (l + 1) // 2]
        hi = packed >> 4
        lo = packed & 0xF
        nibbles = np.empty(packed.size * 2, dtype=np.uint8)
        nibbles[0::2] = hi
        nibbles[1::2] = lo
        lut = np.frombuffer(_SEQ_NIBBLE_B, dtype=np.uint8)
        return lut[nibbles[:l]].tobytes().decode()

    def qual_string(self, i: int) -> str:
        l = int(self.l_seq[i])
        o = int(self.qual_offset[i])
        q = self.data[o:o + l]
        if l == 0 or (q.size and q[0] == 0xFF):
            return "*"
        return (q + 33).tobytes().decode()

    def tags_raw(self, i: int) -> bytes:
        return self.data[int(self.tags_offset[i]):int(self.record_end[i])].tobytes()

    def tags(self, i: int) -> List[Tuple[str, str, object]]:
        return parse_tags(self.tags_raw(i))

    def to_sam_line(self, i: int) -> str:
        h = self.header or SAMHeader()
        flag = int(self.flag[i])
        rid = int(self.refid[i])
        pos = int(self.pos[i])
        mrid = int(self.mate_refid[i])
        mpos = int(self.mate_pos[i])
        if mrid == rid and mrid >= 0:
            rnext = "="
        else:
            rnext = h.ref_name(mrid)
        fields = [
            self.read_name(i), str(flag), h.ref_name(rid), str(pos + 1),
            str(int(self.mapq[i])), self.cigar_string(i), rnext,
            str(mpos + 1), str(int(self.tlen[i])),
            self.seq_string(i), self.qual_string(i),
        ]
        fields += [format_tag(t) for t in self.tags(i)]
        return "\t".join(fields)

    def record_bytes(self, i: int) -> bytes:
        return self.data[int(self.offsets[i]):int(self.record_end[i])].tobytes()


# ---------------------------------------------------------------------------
# Tags [SPEC] section 4.2.4
# ---------------------------------------------------------------------------

_TAG_SCALAR = {"c": ("<b", 1), "C": ("<B", 1), "s": ("<h", 2), "S": ("<H", 2),
               "i": ("<i", 4), "I": ("<I", 4), "f": ("<f", 4), "A": None}
_ARRAY_ELEM = {"c": ("<b", 1), "C": ("<B", 1), "s": ("<h", 2), "S": ("<H", 2),
               "i": ("<i", 4), "I": ("<I", 4), "f": ("<f", 4)}


def parse_tags(raw: bytes) -> List[Tuple[str, str, object]]:
    out: List[Tuple[str, str, object]] = []
    p, n = 0, len(raw)
    while p + 3 <= n:
        tag = raw[p:p + 2].decode()
        typ = chr(raw[p + 2])
        p += 3
        if typ == "A":
            out.append((tag, "A", chr(raw[p]))); p += 1
        elif typ in _TAG_SCALAR and _TAG_SCALAR[typ]:
            fmt, sz = _TAG_SCALAR[typ]
            out.append((tag, typ, struct.unpack_from(fmt, raw, p)[0])); p += sz
        elif typ in ("Z", "H"):
            z = raw.index(b"\x00", p)
            out.append((tag, typ, raw[p:z].decode())); p = z + 1
        elif typ == "B":
            etyp = chr(raw[p]); p += 1
            (cnt,) = struct.unpack_from("<I", raw, p); p += 4
            fmt, sz = _ARRAY_ELEM[etyp]
            vals = list(struct.unpack_from(f"<{cnt}{fmt[1]}", raw, p)); p += cnt * sz
            out.append((tag, "B", (etyp, vals)))
        else:
            raise BAMError(f"unknown tag type {typ!r}")
    return out


def format_tag(t: Tuple[str, str, object]) -> str:
    tag, typ, val = t
    if typ in "cCsSiI":
        return f"{tag}:i:{val}"
    if typ == "f":
        return f"{tag}:f:{val:g}"
    if typ == "A":
        return f"{tag}:A:{val}"
    if typ in ("Z", "H"):
        return f"{tag}:{typ}:{val}"
    if typ == "B":
        etyp, vals = val
        body = ",".join(f"{v:g}" if etyp == "f" else str(v) for v in vals)
        return f"{tag}:B:{etyp},{body}"
    raise BAMError(f"unknown tag type {typ!r}")


def encode_tag(tag: str, typ: str, val) -> bytes:
    head = tag.encode() + typ.encode()
    if typ == "A":
        return head + val.encode()
    if typ in _TAG_SCALAR and _TAG_SCALAR[typ]:
        fmt, _ = _TAG_SCALAR[typ]
        return head + struct.pack(fmt, val)
    if typ in ("Z", "H"):
        return head + val.encode() + b"\x00"
    if typ == "B":
        etyp, vals = val
        fmt, _ = _ARRAY_ELEM[etyp]
        return head + etyp.encode() + struct.pack("<I", len(vals)) + \
            struct.pack(f"<{len(vals)}{fmt[1]}", *vals)
    raise BAMError(f"unknown tag type {typ!r}")


def tag_from_sam(text: str) -> Tuple[str, str, object]:
    tag, typ, val = text.split(":", 2)
    if typ == "i":
        v = int(val)
        return (tag, "i", v)  # write as i32; htsjdk narrows similarly on write
    if typ == "f":
        return (tag, "f", float(val))
    if typ == "A":
        return (tag, "A", val)
    if typ in ("Z", "H"):
        return (tag, typ, val)
    if typ == "B":
        parts = val.split(",")
        etyp = parts[0]
        conv = float if etyp == "f" else int
        return (tag, "B", (etyp, [conv(x) for x in parts[1:]]))
    raise BAMError(f"bad SAM tag {text!r}")


# ---------------------------------------------------------------------------
# Record encoding (writer path)
# ---------------------------------------------------------------------------

_SEQ_CODE: Dict[int, int] = {c: i for i, c in enumerate(_SEQ_NIBBLE_B)}
_CIGAR_CODE: Dict[int, int] = {c: i for i, c in enumerate(_CIGAR_OPS_B)}


def encode_record(*, name: str, flag: int, refid: int, pos: int, mapq: int,
                  cigar: Sequence[Tuple[int, str]] = (), mate_refid: int = -1,
                  mate_pos: int = -1, tlen: int = 0, seq: str = "*",
                  qual: str = "*", tags: Sequence[Tuple[str, str, object]] = (),
                  bin_: Optional[int] = None) -> bytes:
    """Encode one alignment record to BAM bytes (htsjdk BAMRecordCodec.encode
    analog).  ``pos``/``mate_pos`` are 0-based (BAM convention); ``cigar`` is
    a sequence of (length, op_char)."""
    nameb = name.encode() + b"\x00"
    if not 1 <= len(nameb) <= 255:
        raise BAMError("read name length out of range")
    cigar_raw = b"".join(struct.pack("<I", (l << 4) | _CIGAR_CODE[ord(op)])
                         for l, op in cigar)
    if seq == "*" or seq == "":
        l_seq, seq_raw = 0, b""
    else:
        sb = seq.upper().encode()
        l_seq = len(sb)
        codes = [_SEQ_CODE.get(c, 15) for c in sb]
        if l_seq % 2:
            codes.append(0)
        seq_raw = bytes((codes[i] << 4) | codes[i + 1]
                        for i in range(0, len(codes), 2))
    if l_seq == 0:
        qual_raw = b""
    elif qual == "*" or qual == "":
        qual_raw = b"\xff" * l_seq
    else:
        if len(qual) != l_seq:
            raise BAMError("qual length != seq length")
        qual_raw = bytes(ord(c) - 33 for c in qual)
    tags_raw = b"".join(encode_tag(*t) for t in tags)
    if bin_ is None:
        end = pos + _cigar_reference_span(cigar)
        bin_ = reg2bin(max(pos, 0), max(end, pos + 1)) if pos >= 0 else 4680
    body = struct.pack("<iiBBHHHiiii", refid, pos, len(nameb), mapq, bin_,
                       len(cigar), flag, l_seq, mate_refid, mate_pos, tlen)
    body += nameb + cigar_raw + seq_raw + qual_raw + tags_raw
    return struct.pack("<i", len(body)) + body


def _cigar_reference_span(cigar: Sequence[Tuple[int, str]]) -> int:
    span = sum(l for l, op in cigar if op in "MDN=X")
    return span if span > 0 else 1


def parse_cigar_string(s: str) -> List[Tuple[int, str]]:
    if s == "*" or not s:
        return []
    out: List[Tuple[int, str]] = []
    num = 0
    for ch in s:
        if ch.isdigit():
            num = num * 10 + ord(ch) - 48
        else:
            if ch not in CIGAR_OPS:
                raise BAMError(f"bad CIGAR op {ch!r}")
            out.append((num, ch))
            num = 0
    return out
