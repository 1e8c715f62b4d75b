"""rANS Nx16 entropy codec (CRAM 3.1 block method 5).

[SPEC] CRAMcodecs "rANS Nx16": the CRAM 3.1 evolution of rANS 4x8 —
N interleaved 32-bit states (N = 4, or 32 with the X32 flag), **16-bit**
renormalization (lower bound 2^15, one little-endian u16 read per step at
most), 12-bit normalized frequencies, plus optional byte-stream
transforms applied before entropy coding:

    PACK (0x80)   bit-pack when <= 16 distinct symbols (0/1/2/4 bits each)
    RLE  (0x40)   run-length split into literal + run-length streams
    CAT  (0x20)   stored uncompressed
    NOSZ (0x10)   uncompressed size omitted (caller knows it)
    STRIPE (0x08) bytes striped over X independent sub-streams
    X32  (0x04)   32-way state interleave (SIMD-friendly)
    ORDER (0x01)  order-1 (context = previous byte) vs order-0

Encode pipeline: PACK -> RLE -> rANS; decode runs the inverse order.
Frequency tables: same ascending-symbol RLE alphabet as 4x8
(cram_codecs.py); frequencies are uint7 varints; order-1 tables carry a
leading byte (high nibble = frequency shift, bit 0 = "tables themselves
are order-0-compressed") and each context total normalizes to
``1 << shift``.

Provenance note: the container-level flag values and the core N-state /
16-bit-renorm entropy coder follow the public htscodecs layout; the
PACK/RLE/STRIPE *metadata* byte layouts are reconstructed from knowledge
of that library ([SPEC-recalled]) and are pinned by round-trip tests
against this module's own encoder — the in-image environment has no
htslib to cross-validate against (SURVEY.md section 0 fallback).

Reference-side equivalent: htsjdk/htslib rANSNx16 reached through CRAM
3.1 decode (SURVEY.md section 2.8).
"""
from __future__ import annotations

import struct
from typing import List, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.formats.cram_codecs import (
    RansError, _check_final_states, _normalize_freqs, _read_symbol_table,
    _write_symbol_table, normalize_truncation,
)

# flag bits [SPEC]
NX16_ORDER1 = 0x01
NX16_X32 = 0x04
NX16_STRIPE = 0x08
NX16_NOSZ = 0x10
NX16_CAT = 0x20
NX16_RLE = 0x40
NX16_PACK = 0x80

RANS_LOW_16 = 1 << 15           # 16-bit renormalization lower bound


# ---------------------------------------------------------------------------
# uint7 varints (big-endian 7-bit groups, high bit = continuation) [SPEC]
# ---------------------------------------------------------------------------

def var_put_u32(v: int) -> bytes:
    out = bytearray()
    if v >= (1 << 28):
        out.append(0x80 | ((v >> 28) & 0x7F))
    if v >= (1 << 21):
        out.append(0x80 | ((v >> 21) & 0x7F))
    if v >= (1 << 14):
        out.append(0x80 | ((v >> 14) & 0x7F))
    if v >= (1 << 7):
        out.append(0x80 | ((v >> 7) & 0x7F))
    out.append(v & 0x7F)
    return bytes(out)


def var_get_u32(buf: bytes, pos: int) -> Tuple[int, int]:
    v = 0
    while True:
        b = buf[pos]
        pos += 1
        v = (v << 7) | (b & 0x7F)
        if not (b & 0x80):
            return v, pos


# ---------------------------------------------------------------------------
# Frequency tables
# ---------------------------------------------------------------------------

def _write_freqs_nx16(freqs: np.ndarray) -> bytes:
    """Alphabet (shared RLE grammar) followed by uint7 frequencies."""
    out = bytearray(_write_symbol_table(freqs, emit_freq=False))
    for j in range(256):
        if freqs[j] > 0:
            out += var_put_u32(int(freqs[j]))
    return bytes(out)


def _read_alphabet(buf: bytes, pos: int) -> Tuple[List[int], int]:
    syms: List[int] = []

    def read_value(sym, p):
        syms.append(sym)
        return p

    _, pos = _read_symbol_table(buf, pos, read_value)
    return syms, pos


def _read_freqs_nx16(buf: bytes, pos: int, shift: int
                     ) -> Tuple[np.ndarray, int]:
    syms, pos = _read_alphabet(buf, pos)
    freqs = np.zeros(256, dtype=np.int64)
    for s in syms:
        f, pos = var_get_u32(buf, pos)
        freqs[s] = f
    total = int(freqs.sum())
    want = 1 << shift
    if total != want and total > 0:
        # [SPEC] stored frequencies may be un-normalized; renormalize
        freqs = _normalize_freqs(freqs, want)
    return freqs, pos


def _tables(freqs: np.ndarray, shift: int
            ) -> Tuple[np.ndarray, np.ndarray]:
    cum = np.zeros(257, dtype=np.int64)
    np.cumsum(freqs, out=cum[1:])
    slot2sym = np.zeros(1 << shift, dtype=np.uint8)
    for s in range(256):
        if freqs[s]:
            slot2sym[cum[s]:cum[s + 1]] = s
    return cum, slot2sym


# ---------------------------------------------------------------------------
# Core N-state entropy coder (16-bit renormalization)
# ---------------------------------------------------------------------------

def _enc_put16(x: int, freq: int, cum: int, shift: int,
               out: bytearray) -> int:
    x_max = ((RANS_LOW_16 >> shift) << 16) * freq
    if x >= x_max:
        out += struct.pack("<H", x & 0xFFFF)
        x >>= 16
    return ((x // freq) << shift) + (x % freq) + cum


def _encode_order0_core(data: bytes, N: int, shift: int = 12) -> bytes:
    counts = np.bincount(np.frombuffer(data, dtype=np.uint8),
                         minlength=256)
    freqs = _normalize_freqs(counts, 1 << shift)
    cum = np.zeros(257, dtype=np.int64)
    np.cumsum(freqs, out=cum[1:])
    table = _write_freqs_nx16(freqs)

    n = len(data)
    states = [RANS_LOW_16] * N
    rev = bytearray()
    for i in range(n - 1, -1, -1):
        s = data[i]
        states[i % N] = _enc_put16(states[i % N], int(freqs[s]),
                                   int(cum[s]), shift, rev)
    body = b"".join(struct.pack("<I", st) for st in states)
    # rev holds little-endian u16 words emitted in reverse order
    words = bytes(rev)
    out = bytearray(table + body)
    for w in range(len(words) - 2, -1, -2):
        out += words[w:w + 2]
    return bytes(out)


def _decode_order0_core(buf: bytes, pos: int, out_size: int, N: int,
                        shift: int = 12) -> bytes:
    freqs, pos = _read_freqs_nx16(buf, pos, shift)
    cum, slot2sym = _tables(freqs, shift)
    states = list(struct.unpack_from(f"<{N}I", buf, pos))
    pos += 4 * N
    out = np.zeros(out_size, dtype=np.uint8)
    mask = (1 << shift) - 1
    for i in range(out_size):
        j = i % N
        x = states[j]
        m = x & mask
        s = int(slot2sym[m])
        out[i] = s
        x = int(freqs[s]) * (x >> shift) + m - int(cum[s])
        if x < RANS_LOW_16:
            x = (x << 16) | (buf[pos] | (buf[pos + 1] << 8))
            pos += 2
        states[j] = x
    _check_final_states(states, RANS_LOW_16, "rANS Nx16")
    return out.tobytes()


def _slices(n: int, N: int) -> Tuple[List[int], List[int]]:
    """Order-1 fragment boundaries: N slices of n//N, last takes the
    remainder (the 4x8 quarters generalized)."""
    q = n // N
    starts = [j * q for j in range(N)]
    ends = [*(starts[1:]), n]
    return starts, ends


def _encode_order1_core(data: bytes, N: int, shift: int = 12) -> bytes:
    n = len(data)
    arr = np.frombuffer(data, dtype=np.uint8)
    starts, ends = _slices(n, N)
    prev = np.concatenate([[0], arr[:-1]])
    for st in starts:
        prev[st] = 0
    counts = np.zeros((256, 256), dtype=np.int64)
    np.add.at(counts, (prev, arr), 1)

    freqs = np.zeros((256, 256), dtype=np.int64)
    cums = np.zeros((256, 257), dtype=np.int64)
    for c in range(256):
        if counts[c].sum():
            freqs[c] = _normalize_freqs(counts[c], 1 << shift)
            np.cumsum(freqs[c], out=cums[c][1:])

    ctx_present = counts.sum(axis=1) > 0
    tbl = bytearray()
    # leading byte: high nibble = shift, bit 0 = tables-compressed (we
    # always write them plain)
    tbl.append((shift << 4) | 0)
    # outer context alphabet, same RLE grammar
    ctx_freqs = np.zeros(256, dtype=np.int64)
    ctx_freqs[ctx_present] = 1
    tbl += _write_symbol_table(ctx_freqs, emit_freq=False)
    for c in range(256):
        if ctx_present[c]:
            tbl += _write_freqs_nx16(freqs[c])

    states = [RANS_LOW_16] * N
    rev = bytearray()
    lens = [ends[j] - starts[j] for j in range(N)]
    maxlen = max(lens) if n else 0
    for step in range(maxlen - 1, -1, -1):
        for j in range(N - 1, -1, -1):
            if step < lens[j]:
                i = starts[j] + step
                ctx = int(prev[i])
                s = int(arr[i])
                states[j] = _enc_put16(states[j], int(freqs[ctx][s]),
                                       int(cums[ctx][s]), shift, rev)
    body = b"".join(struct.pack("<I", st) for st in states)
    words = bytes(rev)
    out = bytearray(bytes(tbl) + body)
    for w in range(len(words) - 2, -1, -2):
        out += words[w:w + 2]
    return bytes(out)


def _read_order1_tables_nx16(buf: bytes, pos: int
                             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                        int, int]:
    lead = buf[pos]
    pos += 1
    shift = lead >> 4
    if lead & 1:
        # tables themselves are order-0 Nx16 compressed [SPEC]
        ulen, pos = var_get_u32(buf, pos)
        clen, pos = var_get_u32(buf, pos)
        tbl = _decode_order0_core(buf[pos:pos + clen], 0, ulen, 4, shift=12)
        pos += clen
        f, c, s, _ = _read_order1_ctx_tables(tbl, 0, shift)
        return f, c, s, shift, pos
    f, c, s, pos = _read_order1_ctx_tables(buf, pos, shift)
    return f, c, s, shift, pos


def _read_order1_ctx_tables(buf: bytes, pos: int, shift: int
                            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                       int]:
    ctxs, pos = _read_alphabet(buf, pos)
    freqs = np.zeros((256, 256), dtype=np.int64)
    cums = np.zeros((256, 257), dtype=np.int64)
    slot2sym = np.zeros((256, 1 << shift), dtype=np.uint8)
    for c in ctxs:
        f, pos = _read_freqs_nx16(buf, pos, shift)
        freqs[c] = f
        np.cumsum(f, out=cums[c][1:])
        for s in range(256):
            if f[s]:
                slot2sym[c, cums[c][s]:cums[c][s + 1]] = s
    return freqs, cums, slot2sym, pos


def _decode_order1_core(buf: bytes, pos: int, out_size: int, N: int
                        ) -> bytes:
    freqs, cums, slot2sym, shift, pos = _read_order1_tables_nx16(buf, pos)
    states = list(struct.unpack_from(f"<{N}I", buf, pos))
    pos += 4 * N
    starts, ends = _slices(out_size, N)
    out = np.zeros(out_size, dtype=np.uint8)
    mask = (1 << shift) - 1
    ctxs = [0] * N
    idx = list(starts)
    done = [idx[j] >= ends[j] for j in range(N)]
    while not all(done):
        for j in range(N):
            if done[j]:
                continue
            x = states[j]
            m = x & mask
            ctx = ctxs[j]
            s = int(slot2sym[ctx, m])
            out[idx[j]] = s
            x = int(freqs[ctx][s]) * (x >> shift) + m - int(cums[ctx][s])
            if x < RANS_LOW_16:
                x = (x << 16) | (buf[pos] | (buf[pos + 1] << 8))
                pos += 2
            states[j] = x
            ctxs[j] = s
            idx[j] += 1
            if idx[j] >= ends[j]:
                done[j] = True
    _check_final_states(states, RANS_LOW_16, "rANS Nx16")
    return out.tobytes()


# ---------------------------------------------------------------------------
# Byte-stream transforms
# ---------------------------------------------------------------------------

def _pack_encode(data: bytes) -> Optional[Tuple[bytes, bytes]]:
    """Bit-pack when <= 16 distinct symbols; returns (meta, packed) or
    None when not packable.  meta = nsym, symbol map."""
    syms = sorted(set(data))
    nsym = len(syms)
    if nsym > 16 or len(data) == 0:
        return None
    inv = np.zeros(256, dtype=np.uint8)
    inv[list(syms)] = np.arange(nsym, dtype=np.uint8)
    arr = np.frombuffer(data, dtype=np.uint8)
    mapped = inv[arr]
    if nsym <= 1:
        packed = b""
    elif nsym <= 2:
        pad = (-len(mapped)) % 8
        m = np.concatenate([mapped, np.zeros(pad, np.uint8)]).reshape(-1, 8)
        packed = (m << np.arange(8, dtype=np.uint8)).sum(
            axis=1, dtype=np.uint16).astype(np.uint8).tobytes()
    elif nsym <= 4:
        pad = (-len(mapped)) % 4
        m = np.concatenate([mapped, np.zeros(pad, np.uint8)]).reshape(-1, 4)
        packed = (m << (2 * np.arange(4, dtype=np.uint8))).sum(
            axis=1, dtype=np.uint16).astype(np.uint8).tobytes()
    else:
        pad = (-len(mapped)) % 2
        m = np.concatenate([mapped, np.zeros(pad, np.uint8)]).reshape(-1, 2)
        packed = (m[:, 0] | (m[:, 1] << 4)).astype(np.uint8).tobytes()
    meta = bytes([nsym]) + bytes(syms)
    return meta, packed


def _pack_decode(packed: bytes, meta_syms: bytes, out_size: int) -> bytes:
    nsym = len(meta_syms)
    table = np.zeros(256, dtype=np.uint8)
    table[:nsym] = np.frombuffer(meta_syms, dtype=np.uint8)
    if nsym <= 1:
        return bytes(meta_syms[:1]) * out_size if nsym else b""
    arr = np.frombuffer(packed, dtype=np.uint8)
    if nsym <= 2:
        bits = (arr[:, None] >> np.arange(8, dtype=np.uint8)) & 1
        vals = bits.reshape(-1)[:out_size]
    elif nsym <= 4:
        bits = (arr[:, None] >> (2 * np.arange(4, dtype=np.uint8))) & 3
        vals = bits.reshape(-1)[:out_size]
    else:
        bits = np.stack([arr & 0xF, arr >> 4], axis=1)
        vals = bits.reshape(-1)[:out_size]
    return table[vals].tobytes()


def _rle_encode(data: bytes) -> Optional[Tuple[bytes, bytes]]:
    """Split into (meta = rle symbol set + run lengths, literals).

    Symbols chosen: any byte whose total run savings are positive."""
    if not data:
        return None
    arr = np.frombuffer(data, dtype=np.uint8)
    # run starts
    starts = np.concatenate([[0], np.nonzero(np.diff(arr))[0] + 1])
    lens = np.diff(np.concatenate([starts, [arr.size]]))
    run_syms = arr[starts]
    savings = np.zeros(256, dtype=np.int64)
    np.add.at(savings, run_syms, lens - 2)  # ~1 literal + ~1 run byte kept
    use = savings > 0
    if not use.any():
        return None
    lits = bytearray()
    runs = bytearray()
    for s, ln in zip(run_syms.tolist(), lens.tolist()):
        if use[s]:
            lits.append(s)
            runs += var_put_u32(ln - 1)
        else:
            lits += bytes([s]) * ln
    n_use = int(use.sum())
    meta = bytes([n_use & 0xFF]) + bytes(np.nonzero(use)[0].astype(
        np.uint8).tolist()) + bytes(runs)
    return meta, bytes(lits)


def _rle_decode(lits: bytes, meta: bytes, out_size: int) -> bytes:
    pos = 0
    n_use = meta[pos]
    pos += 1
    if n_use == 0:
        n_use = 256
    use = np.zeros(256, dtype=bool)
    for _ in range(n_use):
        use[meta[pos]] = True
        pos += 1
    out = bytearray()
    for s in lits:
        if use[s]:
            run, pos = var_get_u32(meta, pos)
            out += bytes([s]) * (run + 1)
        else:
            out.append(s)
    if len(out) != out_size:
        raise RansError(f"RLE expanded to {len(out)}, expected {out_size}")
    return bytes(out)


# ---------------------------------------------------------------------------
# Public stream API
# ---------------------------------------------------------------------------

def rans_nx16_encode(data: bytes, flags: int = 0) -> bytes:
    """Encode with the requested flag set; PACK/RLE are dropped
    automatically when they don't apply, tiny payloads fall back to CAT.
    STRIPE recurses into X=4 NOSZ sub-streams."""
    n = len(data)

    if flags & NX16_STRIPE:
        X = 4
        out = bytearray([NX16_STRIPE])
        out += var_put_u32(n)
        subs = [rans_nx16_encode(bytes(data[j::X]),
                                 (flags & ~NX16_STRIPE) | NX16_NOSZ)
                for j in range(X)]
        out.append(X)
        for s in subs:
            out += var_put_u32(len(s))
        for s in subs:
            out += s
        return bytes(out)

    payload = data
    pack_meta = rle_meta = None
    if flags & NX16_PACK:
        packed = _pack_encode(payload)
        if packed is None:
            flags &= ~NX16_PACK
        else:
            pack_meta, payload = packed
    if flags & NX16_RLE:
        rled = _rle_encode(payload)
        if rled is None:
            flags &= ~NX16_RLE
        else:
            rle_meta, payload = rled

    N = 32 if flags & NX16_X32 else 4
    if len(payload) < 32:
        flags |= NX16_CAT            # entropy tables cost more than CAT
    if flags & NX16_CAT or len(payload) < N:
        flags &= ~NX16_ORDER1
        if not (flags & NX16_CAT):
            flags |= NX16_CAT

    out = bytearray([flags])
    if not (flags & NX16_NOSZ):
        out += var_put_u32(n)
    if flags & NX16_PACK:
        out += pack_meta                     # nsym byte + symbol map
    if flags & NX16_RLE:
        # meta stored raw: (len << 1) | 1, meta bytes, literal length
        out += var_put_u32((len(rle_meta) << 1) | 1)
        out += rle_meta
        out += var_put_u32(len(payload))
    if flags & NX16_CAT:
        out += payload
    elif flags & NX16_ORDER1:
        out += _encode_order1_core(payload, N)
    else:
        out += _encode_order0_core(payload, N)
    return bytes(out)


def rans_nx16_decode(payload: bytes, out_size: Optional[int] = None
                     ) -> bytes:
    """Decode one rANS Nx16 stream.  ``out_size`` is required when the
    stream carries the NOSZ flag (the CRAM block header supplies it).

    One native call a stream with the interpreter lock released
    (``utils/native.py::rans_nx16_decode``, every flag combination)
    wherever the library loads; the Python decoder below runs on hosts
    without it and for any stream the pass refuses, and is the tests'
    oracle.  Decoded bytes are counted by path
    (``cram.nx16_native_bytes`` / ``cram.nx16_python_bytes``)."""
    return bytes(rans_nx16_decode_block(payload, out_size))


def rans_nx16_decode_block(payload: bytes, out_size: Optional[int] = None
                           ) -> "bytes | bytearray":
    """``rans_nx16_decode`` for a CRAM block's payload: the native pass
    writes straight into the ``bytearray`` it returns, with no copy of
    the (often megabytes of) output into ``bytes``."""
    from hadoop_bam_tpu.utils import native
    from hadoop_bam_tpu.utils.metrics import METRICS

    with normalize_truncation("rANS Nx16"):
        if payload and native.available():
            size = out_size
            if not payload[0] & NX16_NOSZ:
                size, _ = var_get_u32(payload, 1)
            if size is None:
                raise RansError("NOSZ stream needs an external size")
            buf = bytearray(size)
            if native.rans_nx16_decode(payload, size, into=buf) is not None:
                METRICS.count("cram.nx16_native_bytes", size)
                return buf
        out = _rans_nx16_decode(payload, out_size)
        METRICS.count("cram.nx16_python_bytes", len(out))
        return out


def rans_nx16_decode_python(payload: bytes,
                            out_size: Optional[int] = None) -> bytes:
    """The Python decoder alone (the oracle the native pass is held to)."""
    with normalize_truncation("rANS Nx16"):
        return _rans_nx16_decode(payload, out_size)


def _rans_nx16_decode(payload: bytes, out_size: Optional[int] = None
                      ) -> bytes:
    if not payload:
        raise RansError("empty rANS Nx16 stream")
    pos = 0
    flags = payload[pos]
    pos += 1
    if not (flags & NX16_NOSZ):
        out_size, pos = var_get_u32(payload, pos)
    if out_size is None:
        raise RansError("NOSZ stream needs an external size")
    if out_size == 0:
        return b""

    if flags & NX16_STRIPE:
        X = payload[pos]
        pos += 1
        clens = []
        for _ in range(X):
            c, pos = var_get_u32(payload, pos)
            clens.append(c)
        outs = []
        for j in range(X):
            sub_len = (out_size - j + X - 1) // X
            sub = payload[pos:pos + clens[j]]
            if not sub:
                raise RansError("empty rANS Nx16 stream")
            outs.append(_rans_nx16_decode(sub, sub_len))
            pos += clens[j]
        out = np.zeros(out_size, dtype=np.uint8)
        for j in range(X):
            out[j::X] = np.frombuffer(outs[j], dtype=np.uint8)
        return out.tobytes()

    pack_syms = None
    if flags & NX16_PACK:
        nsym = payload[pos]
        pos += 1
        pack_syms = payload[pos:pos + nsym]
        pos += nsym

    rle_meta = None
    lit_len = None
    if flags & NX16_RLE:
        mlen, pos = var_get_u32(payload, pos)
        if mlen & 1:
            mlen >>= 1
            rle_meta = payload[pos:pos + mlen]
            pos += mlen
        else:
            mlen >>= 1
            clen, pos = var_get_u32(payload, pos)
            rle_meta = _decode_order0_core(payload, pos, mlen, 4)
            pos += clen
        lit_len, pos = var_get_u32(payload, pos)

    # size entering the entropy stage
    if flags & NX16_RLE:
        stage_size = lit_len
    elif flags & NX16_PACK:
        stage_size = _packed_size(out_size, len(pack_syms))
    else:
        stage_size = out_size

    if flags & NX16_CAT:
        stage = payload[pos:pos + stage_size]
        if len(stage) != stage_size:
            raise RansError("truncated CAT payload")
    else:
        N = 32 if flags & NX16_X32 else 4
        if flags & NX16_ORDER1:
            stage = _decode_order1_core(payload, pos, stage_size, N)
        else:
            stage = _decode_order0_core(payload, pos, stage_size, N)

    if flags & NX16_RLE:
        target = (_packed_size(out_size, len(pack_syms))
                  if flags & NX16_PACK else out_size)
        stage = _rle_decode(stage, rle_meta, target)
    if flags & NX16_PACK:
        stage = _pack_decode(stage, pack_syms, out_size)
    if len(stage) != out_size:
        raise RansError(
            f"rANS Nx16 decoded {len(stage)} bytes, expected {out_size}")
    return stage


def _packed_size(n: int, nsym: int) -> int:
    if nsym <= 1:
        return 0
    if nsym <= 2:
        return (n + 7) // 8
    if nsym <= 4:
        return (n + 3) // 4
    return (n + 1) // 2
