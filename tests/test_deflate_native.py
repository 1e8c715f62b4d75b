"""The native library's three DEFLATE entry points (native/hbam_native.cpp,
bound in utils/native.py) against hand-built streams and ``zlib``:

- ``deflate_find_block``: the next bit at which a dynamic-Huffman block header
  parses whole — at any bit of a byte, never a stored or a fixed block, never a
  header the end of the input cuts;
- ``deflate_decode_symbols``: the decoder that starts mid-stream with the
  window unknown and writes 16-bit symbols — every byte it knows equal to
  ``zlib``'s, every mark the byte of the true window it names;
- ``deflate_resolve``: symbols -> bytes through the true window, their CRC32
  and their line ends; ``crc32_combine``.
"""
import random
import zlib

import numpy as np
import pytest

from hadoop_bam_tpu.utils import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="no native library")

W = native.DEFLATE_WINDOW


def reads_text(n: int, seed: int = 5, back: int = 0) -> bytes:
    """FASTQ-like records; with ``back``, every record is the one ``back``
    records earlier with a few bases changed, so matches reach far."""
    rng = random.Random(seed)
    recs = []
    for i in range(n):
        if back and i >= back:
            seq = list(recs[i - back][1])
            for _ in range(3):
                seq[rng.randrange(len(seq))] = rng.choice("ACGT")
            seq = "".join(seq)
        else:
            seq = "".join(rng.choice("ACGT") for _ in range(100))
        qual = "".join(chr(rng.randint(35, 74)) for _ in range(100))
        recs.append((f"@M1:7:FC1:2:{1101 + i // 40}:{rng.randint(1, 20000)}:"
                     f"{1000 + 3 * i} 1:N:0:ACGT", seq, qual))
    return "".join(f"{n}\n{s}\n+\n{q}\n" for n, s, q in recs).encode()


def raw_deflate(text: bytes, level: int = 6, strategy: int = 0,
                pieces: int = 1, flush: int = zlib.Z_BLOCK) -> bytes:
    """A raw DEFLATE stream of ``text``, ``flush``ed between ``pieces``
    (``Z_BLOCK`` ends a block at whatever bit it ends)."""
    c = zlib.compressobj(level, zlib.DEFLATED, -15, 9, strategy)
    out, step = [], -(-len(text) // pieces)
    for at in range(0, len(text), step):
        out.append(c.compress(text[at:at + step]))
        if at + step < len(text):
            out.append(c.flush(flush))
    out.append(c.flush())
    return b"".join(out)


def block_table(raw: bytes, text: bytes):
    """[(start bit, text offset)] of every block of ``raw`` and the end,
    from one-block decodes with the true window — each checked against
    ``text``, so the table is ``zlib``'s own."""
    bits, offs = [0], [0]
    buf = native.deflate_symbol_buffer(len(text) + 64)
    while True:
        rc, end, syms = native.deflate_decode_symbols(
            raw, bits[-1], 0, 0, text[max(0, offs[-1] - W):offs[-1]], buf)
        assert rc in (0, 1)
        assert syms.max(initial=0) < 256
        assert syms.astype(np.uint8).tobytes() \
            == text[offs[-1]:offs[-1] + syms.size]
        bits.append(end)
        offs.append(offs[-1] + syms.size)
        if rc == 1:
            assert offs[-1] == len(text) and (end + 7) // 8 == len(raw)
            return list(zip(bits, offs))


def block_type(raw: bytes, bit: int) -> int:
    return (int.from_bytes(raw[bit >> 3:(bit >> 3) + 2], "little")
            >> (bit & 7) >> 1) & 3


TEXT = reads_text(1500)


# ---------------------------------------------------------------------------
# the finder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("level", [1, 4, 6, 9])
def test_the_finder_finds_every_dynamic_block_at_any_bit(level):
    raw = raw_deflate(TEXT, level, pieces=12)
    table = block_table(raw, TEXT)
    starts = [b for b, _o in table[:-1]]
    dynamic = [b for b in starts[:-1] if block_type(raw, b) == 2]
    # the blocks of a Z_BLOCK-flushed stream start at every bit of a byte,
    # so some headers straddle a byte edge whichever way one counts
    assert len({b & 7 for b in dynamic}) >= 4
    for b in dynamic:
        assert native.deflate_find_block(raw, b, len(raw) * 8) == b
        # from just behind the block before, it is the next one found
        prev = max([0] + [s + 1 for s in starts if s < b])
        got = native.deflate_find_block(raw, max(prev, b - 4000), b + 1)
        assert got == b
    # past the last non-final dynamic block there is none: a final block
    # is none, and a header the input's end cuts is none
    assert native.deflate_find_block(raw, dynamic[-1] + 1, len(raw) * 8) == -1
    cut = raw[:(dynamic[1] >> 3) + 9]
    assert native.deflate_find_block(cut, dynamic[1], len(cut) * 8) == -1


@pytest.mark.parametrize("kind", ["stored", "fixed"])
def test_stored_and_fixed_blocks_are_not_found(kind):
    raw = raw_deflate(TEXT, 0) if kind == "stored" \
        else raw_deflate(TEXT, 6, zlib.Z_FIXED, pieces=6)
    assert {block_type(raw, b) for b, _o in block_table(raw, TEXT)[:-1]} \
        == {0 if kind == "stored" else 1}
    if kind == "fixed":
        assert native.deflate_find_block(raw, 0, len(raw) * 8) == -1
    else:
        # a stored block's bytes are the text's own: a header among them
        # would be a false start, and this text holds none
        assert native.deflate_find_block(raw, 0, len(raw) * 8) == -1


def test_a_planted_header_is_a_false_start_the_finder_reports():
    head = raw_deflate(TEXT, 6, pieces=4)[:160]     # a non-final block's
    assert block_type(head, 0) == 2
    rng = random.Random(2)
    noise = bytes(rng.getrandbits(8) for _ in range(5000))
    data = noise[:3001] + head + noise[3001:]
    got = native.deflate_find_block(data, 0, len(data) * 8)
    assert 0 <= got <= 3001 * 8     # the planted one, or noise before it
    assert native.deflate_find_block(data, 3001 * 8, len(data) * 8) \
        == 3001 * 8


# ---------------------------------------------------------------------------
# the decoder that does not know its window
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("level,back", [(1, 0), (4, 0), (6, 0), (9, 0),
                                        (6, 110), (9, 97)])
def test_marked_symbols_are_zlibs_bytes_given_the_true_window(level, back):
    text = reads_text(2500, seed=level, back=back)
    raw = raw_deflate(text, level, pieces=9 if back else 1)
    table = block_table(raw, text)
    assert zlib.decompress(raw, -15) == text
    buf = native.deflate_symbol_buffer(len(text))
    marked = 0
    for (bit, off), (stop_bit, _o) in zip(table[1:-1:2], table[3::2]):
        rc, end, syms = native.deflate_decode_symbols(
            raw, bit, stop_bit, 1 << 40, None, buf)
        assert rc in (0, 1) and end >= stop_bit
        stop = dict(table)[end]             # it stops at a block boundary
        assert syms.size == stop - off
        want = np.frombuffer(text, np.uint8)[off:stop]
        known = syms < 256
        assert (syms[known] == want[known]).all()
        k = syms[~known].astype(np.int64) - 256
        assert ((0 <= k) & (k < W)).all()
        # mark k is byte k of the W bytes before the block; a match never
        # reaches before the text's start
        assert (off - W + k >= 0).all()
        window = np.frombuffer(text[max(0, off - W):off].rjust(W, b"\0"),
                               np.uint8)
        assert (window[k] == want[~known]).all()
        marked += int((~known).sum())
        out, crc, eols = native.deflate_resolve(
            syms, text[max(0, off - W):off])
        assert out.tobytes() == text[off:stop]
        assert crc == zlib.crc32(text[off:stop])
        assert eols == text[off:stop].count(b"\n")
    assert marked > 0
    if back:        # far matches: marks survive to the end of a stretch
        rc, end, syms = native.deflate_decode_symbols(
            raw, table[2][0], 1 << 40, 1 << 40, None, buf)
        assert rc == 1 and (syms[-W:] >= 256).any()


def test_the_decoder_stops_where_it_is_told_and_says_why():
    raw = raw_deflate(TEXT, 6, pieces=10)
    table = block_table(raw, TEXT)
    buf = native.deflate_symbol_buffer(len(TEXT))
    bit, off = table[2]
    # the first boundary at or past the stop bit
    rc, end, syms = native.deflate_decode_symbols(
        raw, bit, table[4][0] - 1, 1 << 40, None, buf)
    assert (rc, end, syms.size) == (0, table[4][0], table[4][1] - off)
    # the first boundary with the soft cap's symbols written
    rc, end, syms = native.deflate_decode_symbols(
        raw, bit, 1 << 40, table[3][1] - off + 1, None, buf)
    assert (rc, end) == (0, table[4][0])
    # the final block's end
    rc, end, syms = native.deflate_decode_symbols(
        raw, bit, 1 << 40, 1 << 40, None, buf)
    assert (rc, end, syms.size) == (1, table[-1][0], len(TEXT) - off)
    # the input ends inside the third block: two whole blocks are the result
    cut = raw[:(table[5][0] >> 3) - 40]
    rc, end, syms = native.deflate_decode_symbols(
        cut, bit, 1 << 40, 1 << 40, None, buf)
    assert (rc, end) == (0, table[4][0])
    # ... inside the first: nothing, and out of input
    rc, end, syms = native.deflate_decode_symbols(
        raw[:(table[3][0] >> 3) - 40], bit, 1 << 40, 1 << 40, None, buf)
    assert (rc, syms.size) == (-3, 0)
    # the room ends inside the first block: nothing, and out of room
    small = native.deflate_symbol_buffer(100)
    rc, end, syms = native.deflate_decode_symbols(
        raw, bit, 1 << 40, 1 << 40, None, small)
    assert (rc, syms.size) == (-2, 0)
    # a match that reaches before the known text is no DEFLATE
    rc, _end, _s = native.deflate_decode_symbols(
        raw, bit, 1 << 40, 1 << 40, b"", buf)
    assert rc == -1
    # nor is the reserved block type, nor a stored block whose lengths
    # disagree
    assert native.deflate_decode_symbols(
        b"\x07" + bytes(16), 0, 1 << 40, 1 << 40, b"", buf)[0] == -1
    assert native.deflate_decode_symbols(
        b"\x01\x05\x00\xfa\xfe" + bytes(8), 0, 1 << 40, 1 << 40, b"",
        buf)[0] == -1


@pytest.mark.parametrize("kind", ["stored", "fixed", "mixed"])
def test_every_block_type_decodes_to_zlibs_bytes(kind):
    if kind == "mixed":     # stored, fixed and dynamic blocks in one stream
        rng = random.Random(8)
        parts = [(bytes(rng.getrandbits(8) for _ in range(40_000)), 6, 0),
                 (TEXT[:30_000], 6, zlib.Z_FIXED), (TEXT, 9, 0),
                 (b"ACGT" * 9, 1, 0)]
        text, raw = b"".join(p for p, _l, _s in parts), b""
        c = zlib.compressobj(6, zlib.DEFLATED, -15)
        for i, (p, level, strategy) in enumerate(parts):
            # compressobj.copy keeps no strategy switch: one object a part,
            # glued at byte edges by a full flush (window kept out of it)
            c = zlib.compressobj(level, zlib.DEFLATED, -15, 9, strategy)
            raw += c.compress(p) + (c.flush() if i == len(parts) - 1
                                    else c.flush(zlib.Z_FULL_FLUSH))
        assert zlib.decompress(raw, -15) == text
    else:
        text = TEXT
        raw = raw_deflate(text, 0) if kind == "stored" \
            else raw_deflate(text, 6, zlib.Z_FIXED, pieces=5)
    table = block_table(raw, text)
    if kind == "mixed":
        assert {block_type(raw, b) for b, _o in table[:-1]} == {0, 1, 2}
    buf = native.deflate_symbol_buffer(len(text))
    rc, end, syms = native.deflate_decode_symbols(
        raw, 0, 1 << 40, 1 << 40, b"", buf)
    assert rc == 1 and syms.astype(np.uint8).tobytes() == text


# ---------------------------------------------------------------------------
# the resolve and the CRCs
# ---------------------------------------------------------------------------

def test_resolve_reads_marks_through_the_window_and_takes_the_crc():
    rng = np.random.default_rng(4)
    window = bytes(rng.integers(0, 256, W, dtype=np.uint8))
    syms = rng.integers(0, 256 + W, 100_000).astype(np.uint16)
    lut = np.concatenate([np.arange(256, dtype=np.uint8),
                          np.frombuffer(window, np.uint8)])
    want = lut[syms].tobytes()
    out, crc, eols = native.deflate_resolve(syms, window)
    assert out.tobytes() == want and crc == zlib.crc32(want)
    assert eols == want.count(b"\n")
    # a shorter window is the END of the W bytes; what lies before reads 0
    short = window[-1000:]
    out, crc, _e = native.deflate_resolve(syms, short)
    lut[256:256 + W - 1000] = 0
    assert out.tobytes() == lut[syms].tobytes() == bytes(out)
    assert crc == zlib.crc32(lut[syms].tobytes())
    # into a caller's buffer; no symbols at all
    room = np.empty(200_000, np.uint8)
    out, crc2, _e = native.deflate_resolve(syms, short, room)
    assert out.base is room and crc2 == crc
    out, crc, eols = native.deflate_resolve(syms[:0], None)
    assert (out.size, crc, eols) == (0, 0, 0)
    with pytest.raises(ValueError):
        native.deflate_resolve(syms, None, np.empty(10, np.uint8))
    with pytest.raises(ValueError):
        native.deflate_resolve(syms.astype(np.int32), None)


@pytest.mark.parametrize("cut", [0, 1, 4097, len(TEXT) - 1, len(TEXT)])
def test_crc32_combine_is_zlibs(cut):
    a, b = TEXT[:cut], TEXT[cut:]
    assert native.crc32_combine(zlib.crc32(a), zlib.crc32(b), len(b)) \
        == zlib.crc32(TEXT)
