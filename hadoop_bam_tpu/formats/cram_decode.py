"""CRAM 3.0 record semantics: encodings, compression header, slice decode.

[SPEC] CRAM 3.0 spec sections 8.4 (compression header), 8.5 (slice header),
10 (record structure), 13 (encodings).  The compression header declares, per
data series (two-letter keys: BF bam flags, CF cram flags, RI ref id, RL read
length, AP alignment position, RG read group, RN read name, MF mate flags,
NS/NP/TS mate ref/pos/template size, NF next-fragment distance, TL tag-line,
FN/FC/FP feature count/code/position, DL/BB/QQ/BS/IN/RS/PD/HC/SC/MQ/BA/QS
feature payloads), which *encoding* produces its values, drawing bits from the
CORE block or bytes from EXTERNAL blocks.

Reference-side equivalent: htsjdk's cram.structure/cram.encoding packages,
reached from Hadoop-BAM via hb/CRAMInputFormat.java → htsjdk CRAM iterator
(SURVEY.md section 2.3).  This module is a fresh implementation from the
public spec — decode here, encode in cram_encode.py.
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.formats.cram import (
    CRAMError, read_itf8, read_itf8_array, write_itf8, write_itf8_array,
    read_ltf8, write_ltf8,
)

# Encoding codec ids [SPEC section 13]
E_NULL, E_EXTERNAL, E_GOLOMB, E_HUFFMAN = 0, 1, 2, 3
E_BYTE_ARRAY_LEN, E_BYTE_ARRAY_STOP, E_BETA = 4, 5, 6
E_SUBEXP, E_GOLOMB_RICE, E_GAMMA = 7, 8, 9

# SAM flag bits carried by the MF (mate flags) series instead of BF
MATE_REVERSE = 0x20
MATE_UNMAPPED = 0x08

# CF (CRAM bit flags) [SPEC section 10.2]
CF_QUAL_STORED = 0x1
CF_DETACHED = 0x2
CF_HAS_MATE_DOWNSTREAM = 0x4
CF_UNKNOWN_BASES = 0x8

DEFAULT_SUBS_MATRIX = bytes([0x1B] * 5)  # identity-ish ordering per ref base


# ---------------------------------------------------------------------------
# Bit/byte cursors
# ---------------------------------------------------------------------------

class BitReader:
    """MSB-first bit reader over the CORE block."""

    def __init__(self, data: bytes):
        self.data = data
        self.pos = 0          # byte position
        self.bit = 0          # bits consumed of data[pos]

    def read(self, nbits: int) -> int:
        v = 0
        for _ in range(nbits):
            byte = self.data[self.pos]
            v = (v << 1) | ((byte >> (7 - self.bit)) & 1)
            self.bit += 1
            if self.bit == 8:
                self.bit = 0
                self.pos += 1
        return v

    def read_unary(self, stop_bit: int = 0) -> int:
        n = 0
        while self.read(1) != stop_bit:
            n += 1
        return n


class ByteCursor:
    """Sequential reader over one EXTERNAL block."""

    def __init__(self, data: bytes):
        self.data = data
        self.pos = 0

    def read_byte(self) -> int:
        b = self.data[self.pos]
        self.pos += 1
        return b

    def read_bytes(self, n: int) -> bytes:
        b = self.data[self.pos:self.pos + n]
        if len(b) != n:
            raise CRAMError("external block exhausted")
        self.pos += n
        return b

    def read_itf8(self) -> int:
        v, self.pos = read_itf8(self.data, self.pos)
        return v

    def read_until(self, stop: int) -> bytes:
        end = self.data.find(bytes([stop]), self.pos)
        if end < 0:
            raise CRAMError("BYTE_ARRAY_STOP: stop byte not found")
        b = self.data[self.pos:end]
        self.pos = end + 1
        return b


@dataclass
class DecodeState:
    core: BitReader
    ext: Dict[int, ByteCursor]
    qs_feat_bytes: int = 0     # QS bytes consumed by B/Q features (the
                               # fqzcomp tripwire skips when nonzero)

    def cursor(self, cid: int) -> ByteCursor:
        try:
            return self.ext[cid]
        except KeyError:
            raise CRAMError(f"record references missing external block {cid}")


# ---------------------------------------------------------------------------
# Encodings
# ---------------------------------------------------------------------------

class Encoding:
    codec_id: int = E_NULL

    def decode_int(self, st: DecodeState) -> int:
        raise CRAMError(f"{type(self).__name__} cannot decode ints")

    def decode_byte(self, st: DecodeState) -> int:
        raise CRAMError(f"{type(self).__name__} cannot decode bytes")

    def decode_array(self, st: DecodeState) -> bytes:
        raise CRAMError(f"{type(self).__name__} cannot decode byte arrays")

    def decode_bytes(self, st: DecodeState, n: int) -> bytes:
        """n bytes of this series — the bulk fast path (EXTERNAL series
        read a slice in one call; others fall back to per-byte decode)."""
        return bytes(self.decode_byte(st) for _ in range(n))

    def params(self) -> bytes:
        raise NotImplementedError

    def serialize(self) -> bytes:
        p = self.params()
        return write_itf8(self.codec_id) + write_itf8(len(p)) + p


@dataclass
class NullEncoding(Encoding):
    codec_id = E_NULL

    def params(self) -> bytes:
        return b""


@dataclass
class ExternalEncoding(Encoding):
    """ints as ITF8 / bytes raw, from external block ``content_id``."""
    content_id: int
    codec_id = E_EXTERNAL

    def decode_int(self, st: DecodeState) -> int:
        return st.cursor(self.content_id).read_itf8()

    def decode_byte(self, st: DecodeState) -> int:
        return st.cursor(self.content_id).read_byte()

    def decode_bytes(self, st: DecodeState, n: int) -> bytes:
        return st.cursor(self.content_id).read_bytes(n)

    def params(self) -> bytes:
        return write_itf8(self.content_id)


@dataclass
class HuffmanEncoding(Encoding):
    """Canonical Huffman over the CORE block; the 0-bit single-symbol case is
    the spec's idiom for constant series."""
    symbols: List[int]
    lengths: List[int]
    codec_id = E_HUFFMAN

    def __post_init__(self):
        order = sorted(range(len(self.symbols)),
                       key=lambda i: (self.lengths[i], self.symbols[i]))
        self._table: Dict[Tuple[int, int], int] = {}
        code, prev_len = 0, 0
        for i in order:
            ln = self.lengths[i]
            if ln == 0:
                continue
            code <<= (ln - prev_len)
            self._table[(ln, code)] = self.symbols[i]
            code += 1
            prev_len = ln
        self._const = self.symbols[0] if (
            len(self.symbols) == 1 and self.lengths[0] == 0) else None

    def decode_int(self, st: DecodeState) -> int:
        if self._const is not None:
            return self._const
        code, ln = 0, 0
        for _ in range(32):
            code = (code << 1) | st.core.read(1)
            ln += 1
            sym = self._table.get((ln, code))
            if sym is not None:
                return sym
        raise CRAMError("bad Huffman code (no symbol within 32 bits)")

    decode_byte = decode_int

    def params(self) -> bytes:
        return write_itf8_array(self.symbols) + write_itf8_array(self.lengths)


@dataclass
class BetaEncoding(Encoding):
    offset: int
    nbits: int
    codec_id = E_BETA

    def decode_int(self, st: DecodeState) -> int:
        return st.core.read(self.nbits) - self.offset

    decode_byte = decode_int

    def params(self) -> bytes:
        return write_itf8(self.offset) + write_itf8(self.nbits)


@dataclass
class GammaEncoding(Encoding):
    offset: int
    codec_id = E_GAMMA

    def decode_int(self, st: DecodeState) -> int:
        n = st.core.read_unary(stop_bit=1)     # count zeros until the 1
        rest = st.core.read(n)
        return ((1 << n) | rest) - self.offset

    def params(self) -> bytes:
        return write_itf8(self.offset)


@dataclass
class SubexpEncoding(Encoding):
    offset: int
    k: int
    codec_id = E_SUBEXP

    def decode_int(self, st: DecodeState) -> int:
        u = st.core.read_unary(stop_bit=0)     # count ones until the 0
        if u == 0:
            v = st.core.read(self.k)
        else:
            n = self.k + u - 1
            v = (1 << n) | st.core.read(n)
        return v - self.offset

    def params(self) -> bytes:
        return write_itf8(self.offset) + write_itf8(self.k)


@dataclass
class ByteArrayLenEncoding(Encoding):
    len_encoding: Encoding
    val_encoding: Encoding
    codec_id = E_BYTE_ARRAY_LEN

    def decode_array(self, st: DecodeState) -> bytes:
        n = self.len_encoding.decode_int(st)
        return self.val_encoding.decode_bytes(st, n)

    def params(self) -> bytes:
        return self.len_encoding.serialize() + self.val_encoding.serialize()


@dataclass
class ByteArrayStopEncoding(Encoding):
    stop: int
    content_id: int
    codec_id = E_BYTE_ARRAY_STOP

    def decode_array(self, st: DecodeState) -> bytes:
        return st.cursor(self.content_id).read_until(self.stop)

    def params(self) -> bytes:
        return bytes([self.stop]) + write_itf8(self.content_id)


def parse_encoding(buf: bytes, pos: int) -> Tuple[Encoding, int]:
    codec, pos = read_itf8(buf, pos)
    plen, pos = read_itf8(buf, pos)
    p, end = pos, pos + plen
    if codec == E_NULL:
        enc = NullEncoding()
    elif codec == E_EXTERNAL:
        cid, p = read_itf8(buf, p)
        enc = ExternalEncoding(cid)
    elif codec == E_HUFFMAN:
        syms, p = read_itf8_array(buf, p)
        lens, p = read_itf8_array(buf, p)
        enc = HuffmanEncoding(syms, lens)
    elif codec == E_BYTE_ARRAY_LEN:
        len_enc, p = parse_encoding(buf, p)
        val_enc, p = parse_encoding(buf, p)
        enc = ByteArrayLenEncoding(len_enc, val_enc)
    elif codec == E_BYTE_ARRAY_STOP:
        stop = buf[p]
        cid, p = read_itf8(buf, p + 1)
        enc = ByteArrayStopEncoding(stop, cid)
    elif codec == E_BETA:
        off, p = read_itf8(buf, p)
        nbits, p = read_itf8(buf, p)
        enc = BetaEncoding(off, nbits)
    elif codec == E_GAMMA:
        off, p = read_itf8(buf, p)
        enc = GammaEncoding(off)
    elif codec == E_SUBEXP:
        off, p = read_itf8(buf, p)
        k, p = read_itf8(buf, p)
        enc = SubexpEncoding(off, k)
    else:
        raise CRAMError(f"unsupported encoding codec id {codec} "
                        "(GOLOMB/GOLOMB_RICE are not implemented)")
    return enc, end


# ---------------------------------------------------------------------------
# Compression header [SPEC section 8.4]
# ---------------------------------------------------------------------------

@dataclass
class CompressionHeader:
    read_names_included: bool = True
    ap_delta: bool = False
    reference_required: bool = True
    substitution_matrix: bytes = DEFAULT_SUBS_MATRIX
    tag_dict: List[List[Tuple[str, str]]] = field(default_factory=lambda: [[]])
    data_series: Dict[str, Encoding] = field(default_factory=dict)
    tag_encodings: Dict[int, Encoding] = field(default_factory=dict)

    @classmethod
    def from_bytes(cls, buf: bytes) -> "CompressionHeader":
        pos = 0
        hdr = cls()
        # preservation map
        _size, pos = read_itf8(buf, pos)
        n, pos = read_itf8(buf, pos)
        for _ in range(n):
            key = buf[pos:pos + 2].decode("ascii")
            pos += 2
            if key in ("RN", "AP", "RR"):
                val = bool(buf[pos])
                pos += 1
                if key == "RN":
                    hdr.read_names_included = val
                elif key == "AP":
                    hdr.ap_delta = val
                else:
                    hdr.reference_required = val
            elif key == "SM":
                hdr.substitution_matrix = bytes(buf[pos:pos + 5])
                pos += 5
            elif key == "TD":
                tdlen, pos = read_itf8(buf, pos)
                hdr.tag_dict = _parse_tag_dict(buf[pos:pos + tdlen])
                pos += tdlen
            else:
                raise CRAMError(f"unknown preservation map key {key!r}")
        # data series encodings
        _size, pos = read_itf8(buf, pos)
        n, pos = read_itf8(buf, pos)
        for _ in range(n):
            key = buf[pos:pos + 2].decode("ascii")
            pos += 2
            enc, pos = parse_encoding(buf, pos)
            hdr.data_series[key] = enc
        # tag encodings
        _size, pos = read_itf8(buf, pos)
        n, pos = read_itf8(buf, pos)
        for _ in range(n):
            key, pos = read_itf8(buf, pos)
            enc, pos = parse_encoding(buf, pos)
            hdr.tag_encodings[key] = enc
        return hdr

    def to_bytes(self) -> bytes:
        pres = bytearray()
        entries = [(b"RN", bytes([self.read_names_included])),
                   (b"AP", bytes([self.ap_delta])),
                   (b"RR", bytes([self.reference_required])),
                   (b"SM", self.substitution_matrix),
                   (b"TD", write_itf8(len(self._td_bytes())) +
                    self._td_bytes())]
        pres += write_itf8(len(entries))
        for k, v in entries:
            pres += k + v
        out = write_itf8(len(pres)) + bytes(pres)

        ds = bytearray()
        ds += write_itf8(len(self.data_series))
        for k, enc in self.data_series.items():
            ds += k.encode("ascii") + enc.serialize()
        out += write_itf8(len(ds)) + bytes(ds)

        te = bytearray()
        te += write_itf8(len(self.tag_encodings))
        for key, enc in self.tag_encodings.items():
            te += write_itf8(key) + enc.serialize()
        out += write_itf8(len(te)) + bytes(te)
        return out

    def _td_bytes(self) -> bytes:
        out = bytearray()
        for line in self.tag_dict:
            for tag, typ in line:
                out += tag.encode("ascii") + typ.encode("ascii")
            out.append(0)
        return bytes(out)

    def series(self, key: str) -> Encoding:
        enc = self.data_series.get(key)
        if enc is None:
            raise CRAMError(f"compression header lacks data series {key}")
        return enc


def _parse_tag_dict(buf: bytes) -> List[List[Tuple[str, str]]]:
    lines: List[List[Tuple[str, str]]] = []
    for raw in buf.split(b"\x00")[:-1]:
        line = []
        if len(raw) % 3:
            raise CRAMError("tag dictionary line not a multiple of 3 bytes")
        for i in range(0, len(raw), 3):
            line.append((raw[i:i + 2].decode("ascii"), chr(raw[i + 2])))
        lines.append(line)
    return lines or [[]]


def tag_key(tag: str, typ: str) -> int:
    return (ord(tag[0]) << 16) | (ord(tag[1]) << 8) | ord(typ)


# ---------------------------------------------------------------------------
# Slice header [SPEC section 8.5]
# ---------------------------------------------------------------------------

@dataclass
class SliceHeader:
    ref_seq_id: int = -1
    start: int = 0
    span: int = 0
    n_records: int = 0
    record_counter: int = 0
    n_blocks: int = 0
    content_ids: List[int] = field(default_factory=list)
    embedded_ref_id: int = -1
    ref_md5: bytes = b"\x00" * 16
    tags: bytes = b""

    @classmethod
    def from_bytes(cls, buf: bytes) -> "SliceHeader":
        pos = 0
        ref_seq_id, pos = read_itf8(buf, pos)
        start, pos = read_itf8(buf, pos)
        span, pos = read_itf8(buf, pos)
        n_records, pos = read_itf8(buf, pos)
        record_counter, pos = read_ltf8(buf, pos)
        n_blocks, pos = read_itf8(buf, pos)
        content_ids, pos = read_itf8_array(buf, pos)
        embedded_ref_id, pos = read_itf8(buf, pos)
        ref_md5 = bytes(buf[pos:pos + 16])
        pos += 16
        return cls(ref_seq_id, start, span, n_records, record_counter,
                   n_blocks, content_ids, embedded_ref_id, ref_md5,
                   bytes(buf[pos:]))

    def to_bytes(self) -> bytes:
        return (write_itf8(self.ref_seq_id) + write_itf8(self.start)
                + write_itf8(self.span) + write_itf8(self.n_records)
                + write_ltf8(self.record_counter) + write_itf8(self.n_blocks)
                + write_itf8_array(self.content_ids)
                + write_itf8(self.embedded_ref_id) + self.ref_md5 + self.tags)


# ---------------------------------------------------------------------------
# Substitution matrix [SPEC section 10.6]
# ---------------------------------------------------------------------------

_BASES = "ACGTN"


def substitute_base(matrix: bytes, ref_base: str, code: int) -> str:
    ri = _BASES.find(ref_base.upper())
    if ri < 0:
        ri = 4
    byte = matrix[ri]
    candidates = [b for b in _BASES if b != _BASES[ri]]
    for j in range(4):
        if (byte >> (6 - 2 * j)) & 3 == code:
            return candidates[j]
    raise CRAMError("invalid substitution code")


def substitution_code(matrix: bytes, ref_base: str, read_base: str) -> int:
    ri = _BASES.find(ref_base.upper())
    if ri < 0:
        ri = 4
    byte = matrix[ri]
    candidates = [b for b in _BASES if b != _BASES[ri]]
    j = candidates.index(read_base.upper())
    return (byte >> (6 - 2 * j)) & 3


# ---------------------------------------------------------------------------
# Record decode [SPEC section 10]
# ---------------------------------------------------------------------------

@dataclass
class CramRecord:
    """Decoded CRAM record, pre-SAM: feature-resolved but mate links raw."""
    bf: int = 0
    cf: int = 0
    ref_id: int = -1
    read_length: int = 0
    pos: int = 0
    read_group: int = -1
    name: bytes = b""
    mate_flags: int = 0
    mate_ref_id: int = -1
    mate_pos: int = 0
    template_size: int = 0
    next_fragment: int = -1
    tags: List[Tuple[str, str, object]] = field(default_factory=list)
    mapq: int = 0
    seq: str = "*"
    qual: bytes = b""
    cigar: str = "*"


class ReferenceSource:
    """Resolves reference bases for slices — the analog of the reference's
    ``hadoopbam.cram.reference-source-path`` config (hb/CRAMInputFormat.java)."""

    def get(self, ref_name: str, start: int, length: int) -> str:
        raise NotImplementedError

    def get_bytes(self, ref_name: str, start: int, length: int
                  ) -> np.ndarray:
        """``get`` as uint8 bases (what the columnar decoder gathers
        from); sources that hold bytes override it."""
        return np.frombuffer(self.get(ref_name, start, length).encode(
            "latin-1"), np.uint8)


class FastaReferenceSource(ReferenceSource):
    """A FASTA as samtools holds one (``formats/fasta.py::IndexedFasta``):
    its ``.fai`` — built in one pass and written beside it when absent —
    and the file memory-mapped, so opening costs the index and a fetch
    the bytes of its range, not the genome."""

    def __init__(self, path_or_text):
        from hadoop_bam_tpu.formats.fasta import IndexedFasta
        from hadoop_bam_tpu.utils.metrics import METRICS

        with METRICS.span("cram.reference_wall"):
            self._fasta = IndexedFasta(path_or_text)

    def get_bytes(self, ref_name: str, start: int, length: int
                  ) -> np.ndarray:
        """1-based ``start``; with ``get``'s Python-slice semantics."""
        from hadoop_bam_tpu.utils.metrics import METRICS

        if ref_name not in self._fasta:
            raise CRAMError(f"reference contig {ref_name!r} not in source")
        lo, hi, _ = slice(start - 1, start - 1 + length).indices(
            self._fasta.length(ref_name))
        with METRICS.span("cram.reference_wall"):
            return self._fasta.fetch(ref_name, lo, hi)

    def get(self, ref_name: str, start: int, length: int) -> str:
        return self.get_bytes(ref_name, start, length).tobytes().decode(
            "latin-1")


class _EmbeddedReference(ReferenceSource):
    def __init__(self, bases: bytes, offset: int):
        self.bases = bytes(bases).decode("ascii")
        self.offset = offset   # 1-based position of bases[0]

    def get(self, ref_name: str, start: int, length: int) -> str:
        i = start - self.offset
        return self.bases[i:i + length]


def _encoding_cids(enc: Encoding) -> List[int]:
    if isinstance(enc, ExternalEncoding):
        return [enc.content_id]
    if isinstance(enc, ByteArrayStopEncoding):
        return [enc.content_id]
    if isinstance(enc, ByteArrayLenEncoding):
        return _encoding_cids(enc.len_encoding) + _encoding_cids(
            enc.val_encoding)
    return []


def _predecode_fixed(comp: CompressionHeader, slice_hdr: SliceHeader,
                     external: Dict[int, bytes],
                     record_fields: bool = True) -> Optional[Dict]:
    """Batch-decode the fixed int series of one slice, or None.

    ``record_fields=False`` (the columnar stats path) leaves out what only
    a SAM record needs — the detached-mate series MF / NS / NP / TS and
    the tag-line index TL — so their blocks are never asked for.

    Eligible when the native ITF8 batch decoder is loadable and every
    fixed series is either a constant (0-bit Huffman, the spec idiom) or
    an EXTERNAL ITF8 stream whose content id no other series shares —
    the common htslib layout.  Ineligible slices fall back to the
    per-record path; output is identical either way (parity tests pin
    this)."""
    from hadoop_bam_tpu.utils import native

    if not native.available():
        return None
    n = slice_hdr.n_records
    if n == 0:
        return None
    multiref = slice_hdr.ref_seq_id == -2

    # content-id exclusivity across EVERY encoding in the header
    cid_users: Dict[int, int] = {}
    for enc in list(comp.data_series.values()) \
            + list(comp.tag_encodings.values()):
        for cid in _encoding_cids(enc):
            cid_users[cid] = cid_users.get(cid, 0) + 1

    def batch(name: str, count: int,
              raw_bytes: bool = False) -> Optional[np.ndarray]:
        """count values of one fixed series; None = not eligible.
        ``raw_bytes`` reads one raw byte per value (the decode_byte
        contract, e.g. FC) instead of one ITF8 varint."""
        if count == 0:
            return np.zeros(0, np.int32)
        enc = comp.data_series.get(name)
        if enc is None:
            return None
        if isinstance(enc, HuffmanEncoding) and enc._const is not None:
            return np.full(count, enc._const, np.int32)
        if isinstance(enc, ExternalEncoding):
            cid = enc.content_id
            if cid_users.get(cid, 0) != 1 or cid not in external:
                return None
            if raw_bytes:
                raw = external[cid]
                if len(raw) < count:
                    return None        # truncated: per-record path raises
                return np.frombuffer(raw[:count], np.uint8
                                     ).astype(np.int32)
            try:
                vals, _used = native.itf8_decode_batch(
                    np.frombuffer(external[cid], np.uint8), count)
            except ValueError:
                return None            # truncated: per-record path raises
            return vals
        return None                    # core-bit codec: record-serial

    out: Dict[str, np.ndarray] = {}
    for name in ("BF", "CF"):
        v = batch(name, n)
        if v is None:
            return None
        out[name] = v
    detached = (out["CF"] & CF_DETACHED) != 0
    downstream = ~detached & ((out["CF"] & CF_HAS_MATE_DOWNSTREAM) != 0)
    mapped = (out["BF"] & 0x4) == 0
    counts = {"RL": n, "AP": n, "RG": n,
              "NF": int(downstream.sum()),
              "MQ": int(mapped.sum()), "FN": int(mapped.sum())}
    if record_fields:
        n_det = int(detached.sum())
        counts.update(TL=n, MF=n_det, NS=n_det, NP=n_det, TS=n_det)
    if multiref:
        counts["RI"] = n
    for name, k in counts.items():
        v = batch(name, k)
        if v is None:
            return None
        out[name] = v
    tl = out.get("TL")
    if tl is not None and tl.size and (
            int(tl.min()) < 0 or int(tl.max()) >= len(comp.tag_dict)):
        raise CRAMError(f"TL index {int(tl.max())} outside tag dictionary")
    if comp.ap_delta:
        out["POS"] = slice_hdr.start + np.cumsum(
            out["AP"], dtype=np.int64)
    else:
        out["POS"] = out["AP"].astype(np.int64)

    # feature streams: FC is one byte per feature and FP one ITF8 per
    # feature, totalling sum(FN) values each — batchable exactly like
    # the fixed series.  Optional: absence just keeps features on the
    # record-serial path.
    total_fn = int(out["FN"].sum())
    if total_fn:
        fc = batch("FC", total_fn, raw_bytes=True)
        fp = batch("FP", total_fn) if fc is not None else None
        if fc is not None and fp is not None:
            out["FC"] = fc
            out["FP"] = fp
    return out


def check_fqz_rec_lens(comp: CompressionHeader, codec_rec_lens,
                       expected: List[int],
                       qs_feat_bytes: int = 0) -> None:
    """fqzcomp desync tripwire, shared by both decode paths: the codec's
    own per-record lengths must match ``expected`` (each record's QS
    consumption per the RL series, stored-qual records only, >0).  A
    [SPEC-recalled] model constant mismatch desyncs the range coder into
    silently wrong values — this cheap invariant catches most desyncs
    loudly (ADVICE r4).  Skipped when B/Q feature bytes interleave into
    QS, or when QS shares its external block with another series (both
    make the per-record mapping ambiguous on a spec-valid file)."""
    if not codec_rec_lens or qs_feat_bytes:
        return
    enc = comp.data_series.get("QS")
    if not isinstance(enc, ExternalEncoding):
        return
    lens = codec_rec_lens.get(enc.content_id)
    if lens is None:
        return
    users = 0
    for e in list(comp.data_series.values()) \
            + list(comp.tag_encodings.values()):
        users += _encoding_cids(e).count(enc.content_id)
    if users != 1:
        return
    codec = [l for l in lens if l > 0]
    if codec != expected:
        raise CRAMError(
            "fqzcomp per-record quality lengths disagree with the "
            f"slice's RL series ({len(codec)} codec records vs "
            f"{len(expected)} stored-qual records) — desynced or "
            "miscalibrated quality stream")


def _check_codec_rec_lens(comp: CompressionHeader, codec_rec_lens,
                          records: List["CramRecord"],
                          st: DecodeState) -> None:
    if not codec_rec_lens:
        return
    expected = [r.read_length for r in records
                if r.cf & CF_QUAL_STORED and r.read_length > 0]
    check_fqz_rec_lens(comp, codec_rec_lens, expected, st.qs_feat_bytes)


def decode_slice_records(comp: CompressionHeader, slice_hdr: SliceHeader,
                         core: bytes, external: Dict[int, bytes],
                         ref_names: List[str],
                         ref_source: Optional[ReferenceSource] = None,
                         codec_rec_lens=None) -> List[CramRecord]:
    # a block the columnar batch decoded is a uint8 array: the cursors
    # read bytes
    external = {cid: d if isinstance(d, (bytes, bytearray)) else bytes(d)
                for cid, d in external.items()}
    st = DecodeState(BitReader(core),
                     {cid: ByteCursor(d) for cid, d in external.items()})
    if slice_hdr.embedded_ref_id >= 0 and ref_source is None:
        ref_source = _EmbeddedReference(external[slice_hdr.embedded_ref_id],
                                        slice_hdr.start)

    pre = _predecode_fixed(comp, slice_hdr, external)
    if pre is not None:
        records = _decode_slice_records_fast(comp, slice_hdr, st, pre,
                                             ref_names, ref_source)
        _check_codec_rec_lens(comp, codec_rec_lens, records, st)
        return records

    records: List[CramRecord] = []
    prev_pos = slice_hdr.start
    for _ in range(slice_hdr.n_records):
        r = CramRecord()
        r.bf = comp.series("BF").decode_int(st)
        r.cf = comp.series("CF").decode_int(st)
        if slice_hdr.ref_seq_id == -2:
            r.ref_id = comp.series("RI").decode_int(st)
        else:
            r.ref_id = slice_hdr.ref_seq_id
        r.read_length = comp.series("RL").decode_int(st)
        ap = comp.series("AP").decode_int(st)
        if comp.ap_delta:
            r.pos = prev_pos + ap
            prev_pos = r.pos
        else:
            r.pos = ap
        r.read_group = comp.series("RG").decode_int(st)
        if comp.read_names_included:
            r.name = comp.series("RN").decode_array(st)
        if r.cf & CF_DETACHED:
            r.mate_flags = comp.series("MF").decode_int(st)
            if not comp.read_names_included:
                r.name = comp.series("RN").decode_array(st)
            r.mate_ref_id = comp.series("NS").decode_int(st)
            r.mate_pos = comp.series("NP").decode_int(st)
            r.template_size = comp.series("TS").decode_int(st)
        elif r.cf & CF_HAS_MATE_DOWNSTREAM:
            r.next_fragment = comp.series("NF").decode_int(st)
        tl = comp.series("TL").decode_int(st)
        if not 0 <= tl < len(comp.tag_dict):
            raise CRAMError(f"TL index {tl} outside tag dictionary")
        for tag, typ in comp.tag_dict[tl]:
            enc = comp.tag_encodings[tag_key(tag, typ)]
            raw = enc.decode_array(st)
            r.tags.append(_tag_from_raw(tag, typ, raw))
        if not r.bf & 0x4:
            _decode_mapped(comp, st, r, ref_names, ref_source)
        else:
            ba = comp.series("BA")
            r.seq = ba.decode_bytes(st, r.read_length).decode("latin-1")
            r.cigar = "*"
            if r.cf & CF_QUAL_STORED:
                qs = comp.series("QS")
                r.qual = qs.decode_bytes(st, r.read_length)
        records.append(r)
    _check_codec_rec_lens(comp, codec_rec_lens, records, st)
    return records


def _decode_slice_records_fast(comp: CompressionHeader,
                               slice_hdr: SliceHeader, st: "DecodeState",
                               pre: Dict, ref_names: List[str],
                               ref_source: Optional[ReferenceSource]
                               ) -> List[CramRecord]:
    """Record assembly over predecoded fixed arrays: the loop still walks
    names/tags/features through the cursors (their streams interleave
    record-serially), but every fixed int is an array index — the per
    record codec dispatch that dominated the profile is gone."""
    bf, cf = pre["BF"], pre["CF"]
    rl, pos, rg, tl = pre["RL"], pre["POS"], pre["RG"], pre["TL"]
    ri = pre.get("RI")
    mf, ns, np_, ts = (pre["MF"], pre["NS"], pre["NP"], pre["TS"])
    nf, mq, fn = pre["NF"], pre["MQ"], pre["FN"]
    names_inc = comp.read_names_included
    # series("RN") (not .get) so a header lacking RN fails with the same
    # CRAMError as the record-serial path, not an AttributeError on None
    # (ADVICE r4); resolved lazily — a slice may legitimately never need
    # names (names excluded, no detached records)
    rn = comp.data_series.get("RN")

    def read_name() -> bytes:
        return (rn if rn is not None else comp.series("RN")
                ).decode_array(st)
    tag_dict, tag_encodings = comp.tag_dict, comp.tag_encodings
    fc_all, fp_all = pre.get("FC"), pre.get("FP")
    records: List[CramRecord] = []
    di = wi = mi = fi = 0
    for i in range(slice_hdr.n_records):
        r = CramRecord()
        r.bf = int(bf[i])
        r.cf = int(cf[i])
        r.ref_id = int(ri[i]) if ri is not None else slice_hdr.ref_seq_id
        r.read_length = int(rl[i])
        r.pos = int(pos[i])
        r.read_group = int(rg[i])
        if names_inc:
            r.name = read_name()
        if r.cf & CF_DETACHED:
            r.mate_flags = int(mf[di])
            if not names_inc:
                r.name = read_name()
            r.mate_ref_id = int(ns[di])
            r.mate_pos = int(np_[di])
            r.template_size = int(ts[di])
            di += 1
        elif r.cf & CF_HAS_MATE_DOWNSTREAM:
            r.next_fragment = int(nf[wi])
            wi += 1
        for tag, typ in tag_dict[int(tl[i])]:
            enc = tag_encodings[tag_key(tag, typ)]
            r.tags.append(_tag_from_raw(tag, typ, enc.decode_array(st)))
        if not r.bf & 0x4:
            k = int(fn[mi])
            if fc_all is not None:
                _decode_mapped(comp, st, r, ref_names, ref_source,
                               fn=k, mq=int(mq[mi]),
                               fc=fc_all[fi:fi + k], fp=fp_all[fi:fi + k])
                fi += k
            else:
                _decode_mapped(comp, st, r, ref_names, ref_source,
                               fn=k, mq=int(mq[mi]))
            mi += 1
        else:
            ba = comp.series("BA")
            r.seq = ba.decode_bytes(st, r.read_length).decode("latin-1")
            r.cigar = "*"
            if r.cf & CF_QUAL_STORED:
                r.qual = comp.series("QS").decode_bytes(st, r.read_length)
        records.append(r)
    return records


def _tag_from_raw(tag: str, typ: str, raw: bytes) -> Tuple[str, str, object]:
    from hadoop_bam_tpu.formats.bam import parse_tags
    parsed = parse_tags(tag.encode("ascii") + typ.encode("ascii") + raw)
    if len(parsed) != 1:
        raise CRAMError(f"tag {tag}:{typ} value bytes did not parse cleanly")
    return parsed[0]


_FEATURE_HAS_ARRAY = {"b": "BB", "q": "QQ", "I": "IN", "S": "SC"}
_FEATURE_HAS_INT = {"D": "DL", "N": "RS", "P": "PD", "H": "HC"}


def _decode_mapped(comp: CompressionHeader, st: DecodeState, r: CramRecord,
                   ref_names: List[str],
                   ref_source: Optional[ReferenceSource],
                   fn: Optional[int] = None,
                   mq: Optional[int] = None,
                   fc=None, fp=None) -> None:
    # fn/mq (ints) and fc/fp (this record's feature-code/position
    # slices) arrive predecoded from the vectorized fast path; None
    # means decode them from the record-serial streams here
    if fn is None:
        fn = comp.series("FN").decode_int(st)
    if fc is None:
        fc_enc = comp.series("FC")
        fp_enc = comp.series("FP")
    features = []
    fpos = 0
    for j in range(fn):
        if fc is not None:             # predecoded feature streams
            code = chr(int(fc[j]))
            fpos += int(fp[j])
        else:
            code = chr(fc_enc.decode_byte(st))
            fpos += fp_enc.decode_int(st)
        if code in _FEATURE_HAS_ARRAY:
            val = comp.series(_FEATURE_HAS_ARRAY[code]).decode_array(st)
        elif code in _FEATURE_HAS_INT:
            val = comp.series(_FEATURE_HAS_INT[code]).decode_int(st)
        elif code == "X":
            val = comp.series("BS").decode_byte(st)
        elif code == "B":
            val = (comp.series("BA").decode_byte(st),
                   comp.series("QS").decode_byte(st))
            st.qs_feat_bytes += 1
        elif code == "i":
            val = comp.series("BA").decode_byte(st)
        elif code == "Q":
            val = comp.series("QS").decode_byte(st)
            st.qs_feat_bytes += 1
        else:
            raise CRAMError(f"unknown feature code {code!r}")
        features.append((fpos, code, val))
    r.mapq = comp.series("MQ").decode_int(st) if mq is None else mq
    quals = bytearray(b"\xff" * r.read_length)
    if r.cf & CF_QUAL_STORED:
        quals = bytearray(
            comp.series("QS").decode_bytes(st, r.read_length))

    # reconstruct seq + cigar from the feature list
    ref_base_at = _make_ref_lookup(r, ref_names, ref_source)
    seq = bytearray(b"?" * r.read_length)
    cigar: List[Tuple[int, str]] = []
    rp = 1           # 1-based read position
    ref_off = 0      # bases of reference consumed so far

    def emit(op: str, n: int):
        if n <= 0:
            return
        if cigar and cigar[-1][1] == op:
            cigar[-1] = (cigar[-1][0] + n, op)
        else:
            cigar.append((n, op))

    def fill_from_ref(read_at: int, n: int):
        nonlocal ref_off
        for i in range(n):
            seq[read_at - 1 + i] = ord(ref_base_at(ref_off + i))
        ref_off += n

    for fpos, code, val in features:
        gap = fpos - rp
        if gap > 0:
            emit("M", gap)
            fill_from_ref(rp, gap)
            rp += gap
        if code == "b":
            emit("M", len(val))
            seq[rp - 1:rp - 1 + len(val)] = val
            ref_off += len(val)
            rp += len(val)
        elif code == "X":
            emit("M", 1)
            seq[rp - 1] = ord(substitute_base(
                comp.substitution_matrix, ref_base_at(ref_off), val))
            ref_off += 1
            rp += 1
        elif code == "B":
            emit("M", 1)
            seq[rp - 1] = val[0]
            quals[rp - 1] = val[1]
            ref_off += 1
            rp += 1
        elif code == "I":
            emit("I", len(val))
            seq[rp - 1:rp - 1 + len(val)] = val
            rp += len(val)
        elif code == "i":
            emit("I", 1)
            seq[rp - 1] = val
            rp += 1
        elif code == "S":
            emit("S", len(val))
            seq[rp - 1:rp - 1 + len(val)] = val
            rp += len(val)
        elif code == "D":
            emit("D", val)
            ref_off += val
        elif code == "N":
            emit("N", val)
            ref_off += val
        elif code == "P":
            emit("P", val)
        elif code == "H":
            emit("H", val)
        elif code == "q":
            quals[rp - 1:rp - 1 + len(val)] = val
        elif code == "Q":
            quals[rp - 1] = val
    tail = r.read_length - (rp - 1)
    if tail > 0:
        emit("M", tail)
        fill_from_ref(rp, tail)

    r.seq = seq.decode("ascii") if r.read_length else "*"
    if r.cf & CF_UNKNOWN_BASES:
        r.seq = "*"
    r.cigar = "".join(f"{n}{op}" for n, op in cigar) if cigar else "*"
    r.qual = bytes(quals)


def _make_ref_lookup(r: CramRecord, ref_names: List[str],
                     ref_source: Optional[ReferenceSource]):
    cache = {}

    def ref_base_at(off: int) -> str:
        if r.cf & CF_UNKNOWN_BASES:
            # bases are declared unknown and the decoded seq is discarded
            # as '*' — the placeholder is output-equivalent WITH a
            # reference too, skips the pointless fetch, and keeps BS-code
            # validation deterministic (identical to the columnar path's
            # 'N'-row check) instead of depending on which reference base
            # happens to sit under the feature
            return "N"
        if ref_source is None:
            raise CRAMError(
                "slice requires reference bases but no reference source was "
                "provided (set cram_reference_source_path — the analog of "
                "hadoopbam.cram.reference-source-path)")
        if off not in cache:
            name = ref_names[r.ref_id] if 0 <= r.ref_id < len(ref_names) \
                else "*"
            chunk = ref_source.get(name, r.pos + off, 64)
            for i, b in enumerate(chunk):
                cache[off + i] = b
        return cache[off]

    return ref_base_at
