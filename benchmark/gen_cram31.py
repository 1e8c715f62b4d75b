"""The plain reference of the ``na12878-chr20-cram31-x1`` deployment: the
seeded NA12878-chr20 reads of ``na12878-chr20-x1`` (``benchmark/gen.py``'s
``gen_fields``, unedited: the same seed and scale give the same records)
written as ``samtools view -O cram,version=3.1`` writes them against a chr20
FASTA, and the answers ``hbam seq-stats`` must give on it.

NumPy, zlib and the standard library only; nothing here imports the program
under test.  ``benchmark/gen_cram31.py`` is a verbatim copy
(``tests/test_cram31_seqstats.py`` holds the two together).

What is made:

- ``chr20.fa`` (+ ``.fai``): one seeded contig of 64,444,167 uniform ACGT
  bases in 60-base lines.
- ``chr20.cram``: CRAM 3.1, reference-compressed (``RR=1``, no embedded
  reference), coordinate-sorted, one slice a container, 10,000 reads a
  slice and no slice across a generator chunk; ``AP`` delta; read names
  kept (``RN=1``) and tokenised (tok3); mates attached (CF mate-downstream
  + ``NF``) when both primaries fall in one slice, detached (``MF`` /
  ``NS`` / ``NP`` / ``TS``) otherwise; ``RG`` as its data series; ``NM:C``
  kept as a tag; every data series in its own EXTERNAL block.
- Bases: ``gen_fields`` cuts a read's bases as ``ref[pos:pos+151]`` from a
  chunk-local reference whatever its CIGAR, so they are re-derived here:
  each aligned base is cut from the one FASTA ALONG its CIGAR (a
  ``70M2D81M`` read skips two reference bases), soft clips and insertions
  get random bases, unmapped reads random bases stored verbatim (``BA``);
  then the source's miscall (0.4 %) and N (0.1 %) rates.  Every other field
  is ``gen_fields``'.  A base that differs from the reference is an ``X``
  feature (substitution code in ``BS``), clips ``S`` (``SC``), the
  insertion ``I`` (``IN``), the deletion ``D`` (``DL``).
- Block methods (htslib's "normal" profile as recalled, ``assumed``): a
  series is tried, on the first slice of each chunk, as rANS Nx16 order 0
  and 1, each with and without PACK (at most 16 symbols) and RLE (where
  runs save bytes), and as gzip level 5; the smallest — rANS sizes
  reckoned from the stream's entropy under the normalised table, gzip
  compressed — is kept for that series for the chunk's slices.  Streams
  under 32 bytes are stored (CAT).  The 4-way Nx16 only (X32 is decoded
  by the program and tested, not written).
- The rANS Nx16 encoder is vectorised: the four states of every stream of
  a chunk — every slice's, every series' — advance in lockstep, one NumPy
  step a symbol position, longest streams first.

The answers (``Reference``) come from the generator's arrays, never from
the file: read count, the 16 base counts, and the float64 sums of every
read's (G + C) / length and mean Phred, beside the same sums with each
per-read mean rounded to bfloat16.
"""
from __future__ import annotations

import hashlib
import os
import struct
import zlib
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from benchmark import gen as G

CONTIG, CONTIG_LEN, READ_LEN = G.CONTIG, G.CONTIG_LEN, G.READ_LEN
SEQS_PER_SLICE = 10_000
FASTA_LINE = 60
MISCALL_RATE, N_RATE = 0.004, 0.001
GZIP_LEVEL = 5
CAT_BELOW = 32                     # stored, not entropy coded
BASE_NAMES = G.BASE_NAMES
CODE_OF = np.zeros(256, np.uint8)  # ASCII base -> BAM 4-bit code
for _b, _c in zip(b"ACGTN", (1, 2, 4, 8, 15)):
    CODE_OF[_b] = _c
ACGT = np.frombuffer(b"ACGT", np.uint8)

# [SPEC] CRAM 3.0/3.1 constants (block methods, content types, encodings)
RAW, GZIP, RANS_NX16, NAME_TOK = 0, 1, 5, 8
FILE_HEADER, COMPRESSION_HEADER, MAPPED_SLICE_HEADER = 0, 1, 2
EXTERNAL_DATA, CORE_DATA = 4, 5
E_EXTERNAL, E_HUFFMAN, E_BYTE_ARRAY_LEN, E_BYTE_ARRAY_STOP = 1, 3, 4, 5
CF_QUAL_STORED, CF_DETACHED, CF_MATE_DOWNSTREAM = 0x1, 0x2, 0x4
NX_ORDER1, NX_CAT, NX_RLE, NX_PACK = 0x01, 0x20, 0x40, 0x80
RANS_LOW, TF_SHIFT = 1 << 15, 12
SUBS_MATRIX = bytes([0x1B] * 5)    # codes 0..3 = the other bases in order

# one content id a series (htslib's layout: exclusive EXTERNAL blocks)
CID = {k: i + 1 for i, k in enumerate(
    ("BF", "CF", "RL", "AP", "RG", "MF", "NS", "NP", "TS", "NF", "TL",
     "FN", "FC", "FP", "DL", "BA", "QS", "BS", "MQ", "RN", "IN", "SC"))}
NM_KEY = (ord("N") << 16) | (ord("M") << 8) | ord("C")
CID["NM"] = NM_KEY
INT_SERIES = ("BF", "CF", "RL", "AP", "RG", "MF", "NS", "NP", "TS", "NF",
              "TL", "FN", "FP", "DL", "MQ")
BYTE_SERIES = ("FC", "BA", "QS", "BS")
STOP_SERIES = ("RN", "IN", "SC")           # BYTE_ARRAY_STOP(0x00)

# read positions of each CIGAR class of gen.CIGARS: the reference offset
# of every read base, -1 where the base is clipped or inserted
REF_OFF = np.full((6, READ_LEN), -1, np.int64)
REF_OFF[0] = np.arange(151)                                   # 151M
REF_OFF[1, 12:] = np.arange(139)                              # 12S139M
REF_OFF[2, :141] = np.arange(141)                             # 141M10S
REF_OFF[3, :70] = np.arange(70)                               # 70M2D81M
REF_OFF[3, 70:] = 72 + np.arange(81)
REF_OFF[4, 5:65] = np.arange(60)                              # 5S60M3I83M
REF_OFF[4, 68:] = 60 + np.arange(83)
# (class, 1-based read position, code, length) of each fixed feature
FIXED_FEATURES = ((1, 1, ord("S"), 12), (2, 142, ord("S"), 10),
                  (3, 71, ord("D"), 2), (4, 1, ord("S"), 5),
                  (4, 66, ord("I"), 3))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def round_bf16(x: np.ndarray) -> np.ndarray:
    """float64 -> the nearest bfloat16 (ties to even), as float64."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


class Sums(NamedTuple):
    """What ``hbam seq-stats`` reduces a set of reads to, as exact sums."""
    n: int
    hist: np.ndarray            # [16] int64, BAM's 4-bit codes
    gc: float                   # sum of per-read (G + C) / length
    mq: float                   # sum of per-read mean Phred
    gc_bf16: float              # the same with every per-read mean ...
    mq_bf16: float              # ... rounded to bfloat16 first

    @classmethod
    def zero(cls) -> "Sums":
        return cls(0, np.zeros(16, np.int64), 0.0, 0.0, 0.0, 0.0)

    @classmethod
    def of(cls, bases: np.ndarray, qual: np.ndarray) -> "Sums":
        """``bases`` [n, 151] ASCII, ``qual`` [n, 151] Phred."""
        codes = CODE_OF[bases]
        gc = ((codes == 2) | (codes == 4)).sum(axis=1) / float(READ_LEN)
        mq = qual.sum(axis=1, dtype=np.int64) / float(READ_LEN)
        return cls(int(bases.shape[0]),
                   np.bincount(codes.ravel(), minlength=16).astype(np.int64),
                   float(gc.sum()), float(mq.sum()),
                   float(round_bf16(gc).sum()), float(round_bf16(mq).sum()))

    def plus(self, o: "Sums") -> "Sums":
        return Sums(self.n + o.n, self.hist + o.hist, self.gc + o.gc,
                    self.mq + o.mq, self.gc_bf16 + o.gc_bf16,
                    self.mq_bf16 + o.mq_bf16)

    def means(self, reading: str = "f64") -> Tuple[float, float]:
        """(mean_gc, mean_qual); ``reading`` "bf16" for the rounded one."""
        n = max(self.n, 1)
        if reading == "bf16":
            return self.gc_bf16 / n, self.mq_bf16 / n
        return self.gc / n, self.mq / n

    def wrong(self, printed: str, tol: Dict[str, float]) -> Optional[str]:
        """``None`` if ``hbam seq-stats``' printed answer agrees: reads and
        the base counts exactly, the means within the printed
        tolerances."""
        kv = {ln.split("\t")[0]: ln.split("\t")[1:]
              for ln in printed.strip().splitlines()}
        if int(kv["reads"][0]) != self.n:
            return f"reads {kv['reads'][0]} != {self.n}"
        hist = [int(kv.get(f"base_{c}", [0])[0]) for c in BASE_NAMES]
        if hist != self.hist.tolist():
            return f"base histogram {hist} != {self.hist.tolist()}"
        gc, mq = self.means()
        got_gc, got_mq = float(kv["mean_gc"][0]), float(kv["mean_qual"][0])
        if abs(got_gc - gc) > tol["mean_gc"]:
            return f"mean_gc {got_gc} vs {gc}"
        if abs(got_mq - mq) > tol["mean_qual"]:
            return f"mean_qual {got_mq} vs {mq}"
        return None

    def outside(self, got: Tuple[float, float], tol: Dict[str, float]
                ) -> List[str]:
        """Which of the unrounded limits the reading (mean_gc, mean_qual)
        breaks against the float64 sums."""
        gc, mq = self.means()
        return [k for k, off in (("mean_gc", abs(got[0] - gc)),
                                 ("mean_qual", abs(got[1] - mq)))
                if off > tol[k]]


# ---------------------------------------------------------------------------
# the reference genome
# ---------------------------------------------------------------------------

def genome(seed: int, length: int = CONTIG_LEN) -> np.ndarray:
    """The seeded chr20: uniform ACGT bases, ASCII."""
    return ACGT[np.random.default_rng([seed, 1 << 21]).integers(
        0, 4, length, np.uint8)]


def fasta_offset(base: int, name: str = CONTIG) -> int:
    """File offset of 0-based ``base`` in the FASTA ``write_fasta`` made."""
    return len(name) + 2 + (base // FASTA_LINE) * (FASTA_LINE + 1) \
        + base % FASTA_LINE


def write_fasta(path: str, ref: np.ndarray, name: str = CONTIG) -> str:
    """``path`` and ``path.fai`` (samtools faidx layout); returns the
    contig's MD5 (the header's ``M5``)."""
    n = ref.size
    full, last = divmod(n, FASTA_LINE)
    with open(path, "wb") as fh:
        fh.write(f">{name}\n".encode())
        rows = np.empty((full, FASTA_LINE + 1), np.uint8)
        rows[:, :FASTA_LINE] = ref[:full * FASTA_LINE].reshape(
            full, FASTA_LINE)
        rows[:, FASTA_LINE] = 10
        fh.write(rows.tobytes())
        if last:
            fh.write(ref[full * FASTA_LINE:].tobytes() + b"\n")
    with open(path + ".fai", "w", encoding="ascii") as fh:
        fh.write(f"{name}\t{n}\t{len(name) + 2}\t{FASTA_LINE}\t"
                 f"{FASTA_LINE + 1}\n")
    return hashlib.md5(ref.tobytes()).hexdigest()


def read_window(path: str, lo: int, hi: int) -> np.ndarray:
    """Bases [lo, hi) of the one-contig FASTA ``write_fasta`` made."""
    l0, l1 = lo // FASTA_LINE, (hi - 1) // FASTA_LINE
    want = (l1 - l0 + 1) * (FASTA_LINE + 1)
    with open(path, "rb") as fh:
        fh.seek(fasta_offset(l0 * FASTA_LINE))
        raw = np.frombuffer(fh.read(want), np.uint8)
    raw = np.concatenate([raw, np.zeros(want - raw.size, np.uint8)])
    rows = raw.reshape(-1, FASTA_LINE + 1)[:, :FASTA_LINE].ravel()
    return rows[lo - l0 * FASTA_LINE:hi - l0 * FASTA_LINE]


# ---------------------------------------------------------------------------
# the reads: gen_fields' records, bases re-derived along the CIGAR
# ---------------------------------------------------------------------------

def chunk_reads(seed: int, c: int, n_chunks: int, chunk_records: int,
                fasta: str) -> dict:
    """``gen_fields``' arrays of chunk ``c`` with ``bases`` [n, 151] ASCII
    re-derived from the FASTA along each read's CIGAR and ``ref_bases``
    (the reference base under every aligned read base, 0 elsewhere)."""
    f = G.gen_fields(seed, c, n_chunks, chunk_records, c == n_chunks - 1)
    n = f["pos"].size
    lo = CONTIG_LEN * c // n_chunks
    hi = min(CONTIG_LEN, CONTIG_LEN * (c + 1) // n_chunks + 256)
    win = read_window(fasta, lo, hi)
    rng = np.random.default_rng([seed, c, 1 << 22])
    bases = ACGT[rng.integers(0, 4, (n, READ_LEN), np.uint8)]
    ref_bases = np.zeros((n, READ_LEN), np.uint8)
    mapped = (f["flag"] & 4) == 0
    for k in range(5):
        idx = np.flatnonzero(mapped & (f["cig"] == k))
        if not idx.size:
            continue
        cols = np.flatnonzero(REF_OFF[k] >= 0)
        at = (f["pos"][idx] - lo)[:, None] + REF_OFF[k][cols][None, :]
        ref_bases[idx[:, None], cols[None, :]] = win[at]
    aligned = ref_bases != 0
    bases[aligned] = ref_bases[aligned]
    u = rng.random((n, READ_LEN), dtype=np.float32)
    miscall = u < MISCALL_RATE
    bases[miscall] = ACGT[rng.integers(0, 4, int(miscall.sum()), np.uint8)]
    bases[u > 1 - N_RATE] = ord("N")
    f["bases"], f["ref_bases"] = bases, ref_bases
    return f


def names_of(pair: np.ndarray) -> np.ndarray:
    """[n, 12] 'q' + the pair id in 11 digits (gen.assemble's names)."""
    out = np.empty((pair.size, 12), np.uint8)
    out[:, 0] = ord("q")
    for k in range(11):
        out[:, 11 - k] = 48 + (pair // 10 ** k) % 10
    return out


# ---------------------------------------------------------------------------
# [SPEC] CRAM integers, blocks and containers (after formats/cram.py's)
# ---------------------------------------------------------------------------

def itf8(v: int) -> bytes:
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


def ltf8(v: int) -> bytes:
    v &= 0xFFFFFFFFFFFFFFFF
    if v < (1 << 7):
        return bytes([v])
    for n in range(1, 8):
        if v < (1 << (7 * (n + 1))):
            out = [((0xFF << (8 - n)) & 0xFF) | (v >> (8 * n))]
            out += [(v >> (8 * i)) & 0xFF for i in range(n - 1, -1, -1)]
            return bytes(out)
    return bytes([0xFF] + [(v >> (8 * i)) & 0xFF for i in range(7, -1, -1)])


def itf8_array(vals) -> bytes:
    return itf8(len(vals)) + b"".join(itf8(v) for v in vals)


def itf8_stream(values: np.ndarray) -> np.ndarray:
    """Every value as ITF8, concatenated: uint8 [total]."""
    v = np.asarray(values, np.int64) & 0xFFFFFFFF
    nb = (1 + (v >= 0x80) + (v >= 0x4000) + (v >= 0x200000)
          + (v >= 0x10000000)).astype(np.int64)
    out = np.empty(int(nb.sum()), np.uint8)
    at = np.cumsum(nb) - nb
    for k, lead, shifts in (
            (1, 0x00, (0,)), (2, 0x80, (8, 0)), (3, 0xC0, (16, 8, 0)),
            (4, 0xE0, (24, 16, 8, 0))):
        m = nb == k
        if m.any():
            vv, a = v[m], at[m]
            for j, s in enumerate(shifts):
                out[a + j] = ((vv >> s) & 0xFF) | (lead if j == 0 else 0)
    m = nb == 5
    if m.any():
        vv, a = v[m], at[m]
        out[a] = 0xF0 | ((vv >> 28) & 0x0F)
        out[a + 1] = (vv >> 20) & 0xFF
        out[a + 2] = (vv >> 12) & 0xFF
        out[a + 3] = (vv >> 4) & 0xFF
        out[a + 4] = vv & 0x0F
    return out


def uint7(v: int) -> bytes:
    """[SPEC] CRAMcodecs uint7: big-endian 7-bit groups."""
    out = bytearray()
    for s in (28, 21, 14, 7):
        if v >= (1 << s):
            out.append(0x80 | ((v >> s) & 0x7F))
    out.append(v & 0x7F)
    return bytes(out)


def uint7_stream(values: np.ndarray) -> np.ndarray:
    v = np.asarray(values, np.int64)
    nb = 1 + sum((v >= (1 << s)).astype(np.int64) for s in (7, 14, 21, 28))
    out = np.empty(int(nb.sum()), np.uint8)
    end = np.cumsum(nb)
    for g in range(5):                    # group g counted from the end
        m = nb > g
        out[end[m] - 1 - g] = ((v[m] >> (7 * g)) & 0x7F) | (
            0x80 if g else 0)
    return out


def block(method: int, ctype: int, cid: int, raw_len: int,
          payload: bytes) -> bytes:
    body = (bytes([method, ctype]) + itf8(cid) + itf8(len(payload))
            + itf8(raw_len) + payload)
    return body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)


def container(blocks: List[bytes], ref_id: int, start: int, span: int,
              n_records: int, counter: int, bases: int,
              landmarks: List[int]) -> bytes:
    payload = b"".join(blocks)
    hdr = (struct.pack("<i", len(payload)) + itf8(ref_id) + itf8(start)
           + itf8(span) + itf8(n_records) + ltf8(counter) + ltf8(bases)
           + itf8(len(blocks)) + itf8_array(landmarks))
    return (hdr + struct.pack("<I", zlib.crc32(hdr) & 0xFFFFFFFF)
            + payload)


def file_definition(file_id: bytes = b"chr20.cram") -> bytes:
    return b"CRAM" + bytes([3, 1]) + (file_id + b"\0" * 20)[:20]


def header_container(text: str) -> bytes:
    raw = text.encode("ascii")
    payload = struct.pack("<i", len(raw)) + raw
    z = zlib.compressobj(GZIP_LEVEL, zlib.DEFLATED, 31)
    comp = z.compress(payload) + z.flush()
    return container([block(GZIP, FILE_HEADER, 0, len(payload), comp)],
                     -1, 0, 0, 0, 0, 0, [0])


def eof_container() -> bytes:
    """[SPEC] the 38-byte CRAM 3 EOF container."""
    b = block(RAW, COMPRESSION_HEADER, 0, 6, b"\x01\x00" * 3)
    return container([b], -1, 0x454F46, 0, 0, 0, 0, [])


def enc_external(cid: int) -> bytes:
    p = itf8(cid)
    return itf8(E_EXTERNAL) + itf8(len(p)) + p


def enc_const(v: int) -> bytes:
    p = itf8_array([v]) + itf8_array([0])
    return itf8(E_HUFFMAN) + itf8(len(p)) + p


def enc_stop(cid: int, stop: int = 0) -> bytes:
    p = bytes([stop]) + itf8(cid)
    return itf8(E_BYTE_ARRAY_STOP) + itf8(len(p)) + p


def enc_len(len_enc: bytes, val_enc: bytes) -> bytes:
    p = len_enc + val_enc
    return itf8(E_BYTE_ARRAY_LEN) + itf8(len(p)) + p


def compression_header() -> bytes:
    """Preservation map (RN, AP delta, RR, SM, TD), one EXTERNAL block a
    series, NM:C as BYTE_ARRAY_LEN(constant 1, EXTERNAL)."""
    td = b"NMC\0"
    pres = [(b"RN", b"\x01"), (b"AP", b"\x01"), (b"RR", b"\x01"),
            (b"SM", SUBS_MATRIX), (b"TD", itf8(len(td)) + td)]
    p = itf8(len(pres)) + b"".join(k + v for k, v in pres)
    ds = [(k.encode(), enc_external(CID[k]))
          for k in INT_SERIES + BYTE_SERIES]
    ds += [(k.encode(), enc_stop(CID[k])) for k in STOP_SERIES]
    d = itf8(len(ds)) + b"".join(k + v for k, v in ds)
    t = itf8(1) + itf8(NM_KEY) + enc_len(enc_const(1),
                                         enc_external(CID["NM"]))
    return (itf8(len(p)) + p) + (itf8(len(d)) + d) + (itf8(len(t)) + t)


# ---------------------------------------------------------------------------
# rANS Nx16 (4 states), vectorised over every stream of a chunk
# ---------------------------------------------------------------------------

def normalize(counts: np.ndarray, total: int = 1 << TF_SHIFT) -> np.ndarray:
    """Rows of counts -> frequencies summing to ``total`` (present symbols
    at least 1; the rounding drift on each row's largest)."""
    counts = np.atleast_2d(np.asarray(counts, np.int64))
    n = counts.sum(axis=1, keepdims=True)
    f = counts * total // np.maximum(n, 1)
    f[(counts > 0) & (f == 0)] = 1
    rows = np.flatnonzero(n[:, 0] > 0)
    j = np.argmax(f[rows], axis=1)
    f[rows, j] += total - f[rows].sum(axis=1)
    if (f[rows, j] < 1).any():
        raise ValueError("cannot normalise a frequency table")
    return f


def alphabet(present: np.ndarray) -> bytes:
    """[SPEC] the ascending symbol list with its run bytes."""
    out = bytearray()
    rle = 0
    for j in np.flatnonzero(present).tolist():
        if rle > 0:
            rle -= 1
            continue
        out.append(j)
        if j > 0 and present[j - 1]:
            k = j + 1
            while k < 256 and present[k]:
                rle += 1
                k += 1
            out.append(rle)
    out.append(0)
    return bytes(out)


def freq_table(freqs: np.ndarray) -> bytes:
    return alphabet(freqs > 0) + b"".join(
        uint7(int(f)) for f in freqs[freqs > 0])


def order1_context(data: np.ndarray, n_states: int = 4
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """(fragment starts, the context of every symbol: the one before it
    in its fragment, 0 at a fragment's start)."""
    q = data.size // n_states
    starts = np.arange(n_states, dtype=np.int64) * q
    ctx = np.zeros(data.size, np.int64)
    ctx[1:] = data[:-1]
    ctx[starts[starts < data.size]] = 0
    return starts, ctx


def entropy_bytes(data: np.ndarray, order: int) -> int:
    """What a 4-way rANS Nx16 stream of ``data`` costs: the tables, the
    states, and the information under the normalised frequencies."""
    if data.size == 0:
        return 0
    if order == 0:
        counts = np.bincount(data, minlength=256)
        f = normalize(counts)[0]
        tbl = len(freq_table(f))
        bits = float((counts[f > 0] * (TF_SHIFT - np.log2(f[f > 0]))).sum())
    else:
        _, ctx = order1_context(data)
        counts = np.bincount(ctx * 256 + data, minlength=65536).reshape(
            256, 256)
        f = normalize(counts)
        tbl = 1 + len(alphabet(counts.sum(1) > 0)) + sum(
            len(freq_table(f[c])) for c in np.flatnonzero(counts.sum(1)))
        m = f > 0
        bits = float((counts[m] * (TF_SHIFT - np.log2(f[m]))).sum())
    return tbl + 16 + int(np.ceil(bits / 8))


class _Job(NamedTuple):
    data: np.ndarray        # the stage's symbols, uint8
    order: int


def _lane_sizes(n: int, order: int) -> np.ndarray:
    """Symbols of each of the 4 states: order 0 deals symbol i to state
    i % 4; order 1 gives state j the j-th quarter, the last the rest."""
    if order == 0:
        return np.array([(n - j + 3) // 4 for j in range(4)], np.int64)
    q = n // 4
    return np.array([q, q, q, n - 3 * q], np.int64)


def _symbol_coding(jb: _Job):
    """(frequency table bytes, f and cum of every symbol, each symbol's
    state and step) of one job's stream."""
    d = jb.data.astype(np.int64)
    n = d.size
    if jb.order == 0:
        counts = np.bincount(d, minlength=256)
        fr = normalize(counts)[0]
        cum = np.concatenate([[0], np.cumsum(fr)])
        return freq_table(fr), fr[d], cum[d], np.arange(n) % 4, \
            np.arange(n) // 4
    starts, ctx = order1_context(d)
    counts = np.bincount(ctx * 256 + d, minlength=65536).reshape(256, 256)
    fr = normalize(counts)
    cum = np.zeros((256, 257), np.int64)
    np.cumsum(fr, axis=1, out=cum[:, 1:])
    present = counts.sum(1) > 0
    head = (bytes([TF_SHIFT << 4]) + alphabet(present)
            + b"".join(freq_table(fr[c]) for c in np.flatnonzero(present)))
    lane = (np.minimum(np.arange(n) // max(n // 4, 1), 3) if n >= 4
            else np.full(n, 3))
    step = np.arange(n) - starts[lane] if n >= 4 else np.arange(n)
    return head, fr[ctx, d], cum[ctx, d], lane, step


def rans_encode_batch(jobs: List[_Job]) -> List[bytes]:
    """The 4-way rANS Nx16 entropy stage (tables, states, words) of every
    job, all encoded at once: one lane a state, lanes sorted longest
    first, the active lanes (a prefix) stepped together from each lane's
    last symbol to its first.  17 bytes are held a symbol, step-major
    (f, its renormalisation bound, 4096 - f, cum, the word, whether it
    was emitted, where the symbol sits)."""
    # global lanes: job k lane j -> g = 4k + j; sorted longest first
    glen = (np.concatenate([_lane_sizes(jb.data.size, jb.order)
                            for jb in jobs])
            if jobs else np.zeros(0, np.int64))
    order = np.argsort(-glen, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(order.size)
    T = int(glen.max(initial=0))
    active = np.searchsorted(-glen[order], -np.arange(T), side="left") \
        if T else np.zeros(0, np.int64)                # lanes with len > t
    off = np.concatenate([[0], np.cumsum(active)])
    total = int(off[-1])
    fs = np.zeros(total, np.uint16)
    bound = np.zeros(total, np.uint32)   # ((RANS_LOW >> 12) << 16) * f
    gs = np.zeros(total, np.uint16)      # 4096 - f
    cs = np.zeros(total, np.uint16)
    heads, dsts = [], []
    for k, jb in enumerate(jobs):
        head, f_i, c_i, lane, step = _symbol_coding(jb)
        dst = off[step] + rank[4 * k + lane]
        fs[dst] = f_i
        bound[dst] = f_i << 19
        gs[dst] = (1 << TF_SHIFT) - f_i
        cs[dst] = c_i
        # where each symbol sits, in the order the decoder reads the
        # words: by step, then state
        dsts.append(dst[np.argsort(step * 4 + lane, kind="stable")]
                    .astype(np.int32))
        heads.append(head)
    words = np.zeros(total, np.uint16)
    emitted = np.zeros(total, bool)
    x = np.full(order.size, RANS_LOW, np.int64)
    for t in range(T - 1, -1, -1):
        a, k = int(off[t]), int(active[t])
        b, xk = a + k, x[:k]
        e = np.greater_equal(xk, bound[a:b], out=emitted[a:b])
        np.copyto(words[a:b], xk, casting="unsafe")       # the low 16 bits
        np.right_shift(xk, 16, out=xk, where=e)
        # x' = (x // f) << 12 + x % f + c = x + (x // f) * (4096 - f) + c
        q = xk // fs[a:b]
        q *= gs[a:b]
        xk += q
        xk += cs[a:b]
    out = []
    for k, d in enumerate(dsts):
        final = x[rank[4 * k:4 * k + 4]]
        w = words[d][emitted[d]]
        out.append(heads[k] + final.astype("<u4").tobytes()
                   + w.astype("<u2").tobytes())
    return out


def pack(data: np.ndarray) -> Optional[Tuple[bytes, np.ndarray]]:
    """PACK: (meta = nsym + the symbols, packed bytes), <= 16 symbols."""
    syms = np.flatnonzero(np.bincount(data, minlength=256))
    if syms.size > 16 or data.size == 0:
        return None
    inv = np.zeros(256, np.uint8)
    inv[syms] = np.arange(syms.size)
    m = inv[data]
    per = 0 if syms.size <= 1 else 8 if syms.size <= 2 else 4 \
        if syms.size <= 4 else 2
    if per == 0:
        packed = np.zeros(0, np.uint8)
    else:
        bits = 8 // per
        m = np.concatenate([m, np.zeros(-m.size % per, np.uint8)])
        packed = (m.reshape(-1, per).astype(np.uint16)
                  << (bits * np.arange(per, dtype=np.uint16))).sum(
            axis=1).astype(np.uint8)
    return bytes([syms.size]) + syms.astype(np.uint8).tobytes(), packed


def rle(data: np.ndarray) -> Optional[Tuple[bytes, np.ndarray]]:
    """RLE: (meta = the run symbols + run lengths, literals) where runs
    save bytes, else None."""
    if data.size == 0:
        return None
    starts = np.concatenate([[0], np.flatnonzero(np.diff(data)) + 1])
    lens = np.diff(np.concatenate([starts, [data.size]]))
    syms = data[starts]
    savings = np.bincount(syms, weights=lens - 2, minlength=256)
    use = savings > 0
    if not use.any():
        return None
    lits = np.repeat(syms, np.where(use[syms], 1, lens))
    runs = uint7_stream(lens[use[syms]] - 1)
    meta = (bytes([int(use.sum()) & 0xFF])
            + np.flatnonzero(use).astype(np.uint8).tobytes()
            + runs.tobytes())
    return meta, lits


class Plan(NamedTuple):
    """How a series is written: block method, and for rANS the order and
    the transforms."""
    method: int
    order: int = 0
    pack: bool = False
    rle: bool = False


def stage(data: np.ndarray, plan: Plan):
    """(frame head before the entropy body, the entropy stage's symbols,
    flags) of one rANS Nx16 frame — the transforms a stream admits."""
    flags = NX_ORDER1 if plan.order else 0
    meta, cur = b"", data
    if plan.pack:
        p = pack(cur)
        if p is not None:
            flags |= NX_PACK
            meta += p[0]
            cur = p[1]
    if plan.rle:
        r = rle(cur)
        if r is not None:
            flags |= NX_RLE
            meta += uint7((len(r[0]) << 1) | 1) + r[0] + uint7(r[1].size)
            cur = r[1]
    if cur.size < CAT_BELOW:
        flags = (flags | NX_CAT) & ~NX_ORDER1
    return bytes([flags]) + uint7(data.size) + meta, cur, flags


def trial(data: np.ndarray) -> Plan:
    """The smallest of the trial set (module docstring) for ``data``."""
    best, size = Plan(RAW), data.size
    z = zlib.compressobj(GZIP_LEVEL, zlib.DEFLATED, 31)
    g = len(z.compress(data.tobytes()) + z.flush())
    if g < size:
        best, size = Plan(GZIP), g
    for order in (0, 1):
        for p in (False, True):
            for r in (False, True):
                plan = Plan(RANS_NX16, order, p, r)
                head, cur, flags = stage(data, plan)
                if (p and not flags & NX_PACK) or (r and not flags & NX_RLE):
                    continue
                est = len(head) + (cur.size if flags & NX_CAT
                                   else entropy_bytes(cur, order))
                if est < size:
                    best, size = plan, est
    return best


# ---------------------------------------------------------------------------
# tok3 read names ([SPEC-recalled] layout, formats/cram_name_tok3.py's)
# ---------------------------------------------------------------------------

T_TYPE, T_ALPHA, T_CHAR, T_DUP, T_DIFF, T_MATCH, T_END = 0, 1, 2, 5, 6, 13, 15


def tok3_streams(names: np.ndarray, mate_back: np.ndarray):
    """The token streams of one slice's names: a name whose mate came
    earlier in the slice is a DUP of it; any other is a DIFF against the
    name before it (the first against none): the CHAR 'q' (MATCH after
    the first), the 11 digits as ALPHA (a run over 9 digits), END.
    Returns [(descriptor, data)] in frame order."""
    n = names.shape[0]
    dup = mate_back > 0
    diff = np.flatnonzero(~dup)
    u32 = lambda v: np.asarray(v, "<u4").view(np.uint8)  # noqa: E731
    sel = np.where(dup, T_DUP, T_DIFF).astype(np.uint8)
    diff_dist = np.ones(diff.size, np.int64)
    if diff.size:
        diff_dist[0] = 0
    t1 = np.full(diff.size, T_MATCH, np.uint8)
    if diff.size:
        t1[0] = T_CHAR
    alpha = np.zeros((diff.size, 12), np.uint8)
    alpha[:, :11] = names[diff, 1:]
    out = [(T_TYPE, sel)]
    if dup.any():
        out.append((T_DUP, u32(mate_back[dup])))
    out += [(T_DIFF, u32(diff_dist)),
            (0x80 | T_TYPE, t1), (T_CHAR, np.frombuffer(b"q", np.uint8)),
            (0x80 | T_TYPE, np.full(diff.size, T_ALPHA, np.uint8)),
            (T_ALPHA, alpha.ravel()),
            (0x80 | T_TYPE, np.full(diff.size, T_END, np.uint8))]
    return out, n * 13


# ---------------------------------------------------------------------------
# a chunk's containers
# ---------------------------------------------------------------------------

def slice_streams(f: dict, a: int, b: int) -> Tuple[dict, dict]:
    """(series -> uint8 stream, slice facts) of records [a, b)."""
    flag, pos, cig = f["flag"][a:b], f["pos"][a:b], f["cig"][a:b]
    n = b - a
    placed = f["refid"][a:b] >= 0
    mapped = (flag & 4) == 0
    pos1 = np.where(placed, pos + 1, 0)
    start = int(pos1[0]) if placed[0] else 0
    # mates: the pair's other record in this slice, both primary
    pair = f["pair"][a:b]
    o = np.argsort(pair, kind="stable")
    same = pair[o][1:] == pair[o][:-1]
    first, second = o[:-1][same], o[1:][same]
    primary = (flag & 0x900) == 0
    ok = primary[first] & primary[second]
    first, second = first[ok], second[ok]
    mate_back = np.zeros(n, np.int64)
    mate_back[second] = second - first
    cf = np.full(n, CF_QUAL_STORED | CF_DETACHED, np.int64)
    cf[first] = CF_QUAL_STORED | CF_MATE_DOWNSTREAM
    cf[second] = CF_QUAL_STORED
    det = (cf & CF_DETACHED) != 0
    s = {"BF": flag & ~(0x20 | 0x8), "CF": cf,
         "RL": np.full(n, READ_LEN), "AP": np.diff(pos1, prepend=start),
         "RG": f["rg"][a:b], "TL": np.zeros(n, np.int64),
         "NF": (second - first - 1),
         "MF": (((flag & 0x20) != 0) | (((flag & 0x8) != 0) << 1))[det],
         "NS": f["mref"][a:b][det],
         "NP": np.where(f["mpos"][a:b] >= 0, f["mpos"][a:b] + 1, 0)[det],
         "TS": f["tlen"][a:b][det],
         "MQ": f["mapq"][a:b][mapped]}
    s["NF"] = s["NF"][np.argsort(first, kind="stable")]
    streams = {k: itf8_stream(v) for k, v in s.items()}

    # features, in record order then read position (D before X)
    bases, refb = f["bases"][a:b], f["ref_bases"][a:b]
    xr, xc = np.nonzero((refb != 0) & (bases != refb) & mapped[:, None])
    rec, rpos, kind, code = [xr], [xc + 1], [np.ones(xr.size, np.int64)], \
        [np.full(xr.size, ord("X"))]
    for k, p1, c, ln in FIXED_FEATURES:
        r = np.flatnonzero(mapped & (cig == k))
        rec.append(r)
        rpos.append(np.full(r.size, p1))
        kind.append(np.zeros(r.size, np.int64))
        code.append(np.full(r.size, c))
    rec, rpos, kind, code = (np.concatenate(v) for v in (rec, rpos, kind,
                                                         code))
    o = np.lexsort((kind, rpos, rec))
    rec, rpos, code = rec[o], rpos[o], code[o]
    fp = np.diff(rpos, prepend=0)
    newrec = np.ones(rec.size, bool)
    newrec[1:] = rec[1:] != rec[:-1]
    fp[newrec] = rpos[newrec]
    fn = np.bincount(rec, minlength=n)[mapped]
    xm = code == ord("X")
    ref_base = refb[rec[xm], rpos[xm] - 1]
    read_base = bases[rec[xm], rpos[xm] - 1]
    streams.update(FN=itf8_stream(fn), FC=code.astype(np.uint8),
                   FP=itf8_stream(fp),
                   DL=itf8_stream(np.full(int((code == ord("D")).sum()), 2)),
                   BS=SUBST_CODE[ref_base, read_base])
    for series, c in (("SC", ord("S")), ("IN", ord("I"))):
        m = np.flatnonzero(code == c)
        ln = FEATURE_LEN[cig[rec[m]], rpos[m]]
        tgt = np.repeat(rec[m], ln + 1)
        col = np.repeat(rpos[m] - 1, ln + 1) + (
            np.arange(int((ln + 1).sum())) - np.repeat(np.cumsum(ln + 1)
                                                       - ln - 1, ln + 1))
        v = bases[tgt, np.minimum(col, READ_LEN - 1)]
        v[np.cumsum(ln + 1) - 1] = 0
        streams[series] = v
    streams["QS"] = np.ascontiguousarray(f["qual"][a:b]).ravel()
    streams["BA"] = np.ascontiguousarray(bases[~mapped]).ravel()
    streams["NM"] = f["nm"][a:b].astype(np.uint8)
    ends = pos1 + np.where(mapped, G.REF_LEN[cig], 1) - 1
    facts = {"n": n, "ref_id": 0 if placed[0] else -1, "start": start,
             "span": int(ends.max() - start + 1) if placed[0] else 0,
             "names": names_of(pair), "mate_back": mate_back}
    return streams, facts


# substitution code of (reference base, read base) under SUBS_MATRIX
SUBST_CODE = np.zeros((256, 256), np.uint8)
for _ri, _r in enumerate(b"ACGTN"):
    _cand = [c for c in b"ACGTN" if c != _r]
    for _j in range(4):
        SUBST_CODE[_r, _cand[_j]] = (SUBS_MATRIX[_ri] >> (6 - 2 * _j)) & 3
# bases a fixed S / I feature carries, by (CIGAR class, read position)
FEATURE_LEN = np.zeros((6, READ_LEN + 2), np.int64)
for _k, _p, _c, _ln in FIXED_FEATURES:
    if _c != ord("D"):
        FEATURE_LEN[_k, _p] = _ln


def _slice_blocks(streams: dict, facts: dict, plans: dict,
                  jobs: List[_Job]) -> list:
    """[(content id, raw length, method, parts)] of one slice: a part is
    bytes, or the index in ``jobs`` of a rANS body still to encode."""
    blocks = []
    for key in sorted(streams, key=lambda k: CID[k]):
        data = streams[key]
        if data.size == 0:
            continue
        plan = plans.get(key, Plan(RAW))
        if plan.method == RANS_NX16:
            head, cur, flags = stage(data, plan)
            if flags & NX_CAT:
                parts = [head + cur.tobytes()]
            else:
                jobs.append(_Job(cur, plan.order))
                parts = [head, len(jobs) - 1]
        elif plan.method == GZIP:
            z = zlib.compressobj(GZIP_LEVEL, zlib.DEFLATED, 31)
            parts = [z.compress(data.tobytes()) + z.flush()]
        else:
            parts = [data.tobytes()]
        blocks.append((CID[key], data.size, plan.method, parts))
    toks, ulen = tok3_streams(facts["names"], facts["mate_back"])
    frame = [struct.pack("<II", ulen, facts["n"]) + b"\0"]
    for desc, data in toks:
        head, cur, flags = stage(data, Plan(RANS_NX16))
        if flags & NX_CAT:
            frame.append((desc, [head + cur.tobytes()]))
        else:
            jobs.append(_Job(cur, 0))
            frame.append((desc, [head, len(jobs) - 1]))
    blocks.append((CID["RN"], ulen, NAME_TOK, frame))
    return blocks


def chunk_job(job):
    """Worker side of ``write_cram``: the data containers of a run of
    chunks, each chunk's share of the reference and its byte counts
    (NumPy + zlib only; never imports JAX).  Every rANS stream of the run
    is encoded in one lockstep batch.  ``job`` = (seed, first chunk, chunk
    count, n_chunks, chunk_records, fasta)."""
    seed, first, count, n_chunks, chunk_records, fasta = job
    jobs: List[_Job] = []
    chunks = []
    for c in range(first, first + count):
        f = chunk_reads(seed, c, n_chunks, chunk_records, fasta)
        placed = int((f["refid"] >= 0).sum())
        cuts = [(a, min(a + SEQS_PER_SLICE, hi))      # placed reads, then
                for lo, hi in ((0, placed), (placed, f["refid"].size))
                for a in range(lo, hi, SEQS_PER_SLICE)]   # the unplaced
        slices = [slice_streams(f, a, b) for a, b in cuts]
        plans = {k: trial(v) for k, v in slices[0][0].items()}
        pending = [_slice_blocks(st, facts, plans, jobs)
                   for st, facts in slices]
        chunks.append((c, Sums.of(f["bases"], f["qual"]), cuts, slices,
                       pending, plans))
        del f
    bodies = rans_encode_batch(jobs)

    def done(parts) -> bytes:
        return b"".join(p if isinstance(p, bytes) else bodies[p]
                        for p in parts)

    comp = compression_header()
    comp_block = block(RAW, COMPRESSION_HEADER, 0, len(comp), comp)
    results = []
    for c, sums, cuts, slices, pending, plans in chunks:
        out, series_bytes = [], {}
        counter = c * chunk_records
        for (a, b), (streams, facts), blocks in zip(cuts, slices, pending):
            ext, cids = [], []
            for cid, raw_len, method, parts in blocks:
                if method == NAME_TOK:
                    payload = parts[0] + b"".join(
                        bytes([desc]) + uint7(len(done(p))) + done(p)
                        for desc, p in parts[1:])
                else:
                    payload = done(parts)
                if method == RANS_NX16 and len(payload) >= raw_len:
                    method, payload = RAW, streams[
                        next(k for k in streams if CID[k] == cid)].tobytes()
                ext.append(block(method, EXTERNAL_DATA, cid, raw_len,
                                 payload))
                cids.append(cid)
                series_bytes[cid] = series_bytes.get(cid, 0) + len(ext[-1])
            core = block(RAW, CORE_DATA, 0, 0, b"")
            ref_id, start, span = (facts["ref_id"], facts["start"],
                                   facts["span"])
            md5 = (hashlib.md5(read_window(fasta, start - 1,
                                           start - 1 + span).tobytes()
                               ).digest() if ref_id >= 0 else b"\0" * 16)
            sh = (itf8(ref_id) + itf8(start) + itf8(span) + itf8(facts["n"])
                  + ltf8(counter + a) + itf8(1 + len(ext))
                  + itf8_array(cids) + itf8(-1) + md5)
            blocks_out = [comp_block,
                          block(RAW, MAPPED_SLICE_HEADER, 0, len(sh), sh),
                          core] + ext
            out.append(container(blocks_out, ref_id, start, span,
                                 facts["n"], counter + a,
                                 facts["n"] * READ_LEN, [len(comp_block)]))
        results.append((out, sums, series_bytes,
                        {k: tuple(p) for k, p in plans.items()}))
    return results


class Written(NamedTuple):
    cram: str
    fasta: str
    cram_bytes: int
    series_bytes: Dict[int, int]   # content id -> bytes of its blocks
    methods: Dict[str, tuple]      # series -> Plan of chunk 0


def write_cram(directory: str, seed: int, n_chunks: int, chunk_records: int,
               workers: int = 1) -> Tuple[Written, Sums]:
    """``chr20.fa`` (+ ``.fai``) and ``chr20.cram`` under ``directory``;
    ``workers`` > 1 makes the chunks in spawned NumPy-only processes, in
    order, while this process writes."""
    fasta = os.path.join(directory, "chr20.fa")
    cram = os.path.join(directory, "chr20.cram")
    m5 = write_fasta(fasta, genome(seed))
    text = G.HEADER_TEXT.replace(
        f"LN:{CONTIG_LEN}\n", f"LN:{CONTIG_LEN}\tM5:{m5}\tUR:chr20.fa\n")
    # one chunk a worker (a chunk's lockstep batch holds ~1.3 GB, and the
    # chip host's 40 GiB are shared with the TPU runtime); one process
    # encodes every chunk in one batch, whose steps cost about the same
    # for more lanes
    run = 1 if workers > 1 else n_chunks
    jobs = [(seed, c, min(run, n_chunks - c), n_chunks, chunk_records,
             fasta) for c in range(0, n_chunks, run)]
    pool = None
    if workers > 1:
        import multiprocessing

        pool = multiprocessing.get_context("spawn").Pool(len(jobs))
    sums, series, methods = Sums.zero(), {}, {}
    try:
        with open(cram, "wb") as fh:
            fh.write(file_definition())
            fh.write(header_container(text))
            for part in (pool.imap(chunk_job, jobs) if pool
                         else map(chunk_job, jobs)):
                for conts, s, sb, m in part:
                    for cont in conts:
                        fh.write(cont)
                    sums = sums.plus(s)
                    for cid, v in sb.items():
                        series[cid] = series.get(cid, 0) + v
                    methods = methods or m
            fh.write(eof_container())
            size = fh.tell()
    finally:
        if pool is not None:
            pool.terminate()
            pool.join()     # every worker has ended before set-up goes on
    return Written(cram, fasta, size, series, methods), sums
