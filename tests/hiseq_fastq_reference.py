"""A HiSeq 2000/2500 paired-end lane as ``bcl2fastq`` delivers it, made from a
seed, and the plain reference of ``hbam seq-stats`` over it.

NumPy + zlib only, independent of the code under test.  This file is copied
verbatim to ``benchmark/gen_hiseq_fastq.py`` (a benchmark may add files only
under its own directory); ``tests/test_hiseq_fastqgz.py`` holds the two
together.  Edit both.

The shape (``benchmark/configs/hiseq-fastqgz-x1.json``; nothing here is read
from the source — no network — so every share below is ``assumed`` there):

- two files a lane, ``_R1_001.fastq.gz`` and ``_R2_001.fastq.gz``, equal read
  counts, matching names, each ONE gzip member: a plain
  ``zlib.compressobj(4, wbits=31)`` stream (``bcl2fastq``'s default level),
  no FEXTRA, no index, nothing a decoder could split by;
- 2 x 101 bases; CASAVA 1.8 names ``@instrument:run:flowcell:lane:tile:x:y
  read:filter:0:index``, tiles 1101..2316 in order, y rising within a tile,
  ~1.5 % of pairs flagged ``Y`` (failed the chastity filter, kept in the file
  as ``bcl2fastq --with-failed-reads`` keeps them);
- Phred+33, qualities unbinned Q2..Q41: a level a read, a decline a cycle,
  R2 lower than R1, ``#`` (Q2) tails on ~3 % of reads;
- bases from a seeded 41 %-GC genome at random positions and strands, inserts
  normal(400, 60), 0.4 % miscalls; N at Q2 on ~0.1 % of bases, all among the
  first reads of a tile (its edge);
- a bare ``+`` third line, ``\\n`` line ends.

The reference's numbers are computed in float64 from the base and quality
rows the text is written from: reads, the 16-code base histogram (BAM's 4-bit
alphabet), the sum of per-read GC fractions and of per-read mean qualities —
over all reads and over those that passed the filter — and a second reading
with each per-read mean rounded to bfloat16, which a comparison at float32's
accuracy has to refuse.
"""
from __future__ import annotations

import os
import queue
import threading
import zlib
from typing import Dict, Iterator, List, NamedTuple, Tuple

import numpy as np

READ_LEN = 101
INSTRUMENT, RUN, FLOWCELL, LANE = "HSQ1004", 134, "C0D8DACXX", 3
INDEX = "CGATGT"
# HiSeq 2000: 2 surfaces x 3 swaths x 16 tiles
TILES = tuple(s * 1000 + w * 100 + t for s in (1, 2) for w in (1, 2, 3)
              for t in range(1, 17))
GZIP_LEVEL = 4                  # bcl2fastq's --fastq-compression-level
GENOME_BASES = 1 << 22          # the seeded genome the inserts come from
GC = 0.41
INSERT_MEAN, INSERT_SD = 400.0, 60.0
MISCALL = 0.004
FILTER_FAIL = 0.015
HASH_TAIL = 0.03                # reads whose calls end in a run of '#'
EDGE_READS, EDGE_N = 0.04, 0.025    # 0.04 x 0.025 = 0.1 % of bases are N
Q_MIN, Q_MAX = 2, 41
# (level mean, level sd, decline over the read) of R1 and R2; every base
# falls under its level by Q_NOISE x |N(0, 1)|, and Q_DIP of them by 4..33
Q_MODEL = ((40.5, 1.6, 7.0), (39.5, 2.2, 11.0))
Q_NOISE, Q_DIP = 1.0, 0.012
X_RANGE = (1_100, 20_900)
Y_RANGE = (2_000, 200_000)
CHUNK_PAIRS = 1 << 16

BASE_NAMES = ("=", "A", "C", "M", "G", "R", "S", "V",
              "T", "W", "Y", "H", "K", "D", "B", "N")
_ACGT = np.frombuffer(b"ACGT", np.uint8)
_COMP = np.array([3, 2, 1, 0], np.uint8)           # of an index into ACGT
_CODE = np.array([1, 2, 4, 8, 15], np.uint8)       # A C G T N as nibbles
_N = 4


def file_names(directory: str) -> Tuple[str, str]:
    stem = f"NA12878_S1_L{LANE:03d}"
    return (os.path.join(directory, f"{stem}_R1_001.fastq.gz"),
            os.path.join(directory, f"{stem}_R2_001.fastq.gz"))


# ---------------------------------------------------------------------------
# the records
# ---------------------------------------------------------------------------

def genome(seed: int) -> np.ndarray:
    """Indices into ACGT, 41 % G + C, independent draws."""
    rng = np.random.default_rng([seed, 0x6E0])
    p = np.array([(1 - GC) / 2, GC / 2, GC / 2, (1 - GC) / 2])
    return rng.choice(4, size=GENOME_BASES, p=p).astype(np.uint8)


def _tile_layout(pairs: int) -> np.ndarray:
    """First record of every tile (and the end): the lane's pairs dealt
    evenly over the 96 tiles, in tile order."""
    return (np.arange(len(TILES) + 1, dtype=np.int64) * pairs) // len(TILES)


def gen_pairs(seed: int, chunk: int, pairs: int, chunk_pairs: int,
              g: np.ndarray) -> Dict[str, np.ndarray]:
    """What both reads of pairs [chunk * chunk_pairs, ...) share: tile, x,
    y, the filter flag, and the fragment each came from."""
    lo = chunk * chunk_pairs
    n = min(chunk_pairs, pairs - lo)
    rng = np.random.default_rng([seed, 0xF0, chunk])
    i = lo + np.arange(n, dtype=np.int64)
    starts = _tile_layout(pairs)
    t = np.searchsorted(starts, i, side="right") - 1
    in_tile = i - starts[t]
    per_tile = (starts[t + 1] - starts[t]).astype(np.float64)
    step = (Y_RANGE[1] - Y_RANGE[0]) / per_tile
    y = (Y_RANGE[0] + np.floor((in_tile + rng.random(n)) * step)
         ).astype(np.int64)
    ins = np.clip(np.rint(rng.normal(INSERT_MEAN, INSERT_SD, n)),
                  READ_LEN, 1000).astype(np.int64)
    return {
        "n": n,
        "tile": np.asarray(TILES, np.int64)[t],
        "x": rng.integers(X_RANGE[0], X_RANGE[1], n),
        "y": y,
        "failed": rng.random(n) < FILTER_FAIL,
        "edge": in_tile < EDGE_READS * per_tile,
        "start": rng.integers(0, g.size - 1000, n),
        "insert": ins,
        "minus": rng.random(n) < 0.5,
    }


def gen_read(seed: int, chunk: int, read: int, p: Dict[str, np.ndarray],
             g: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Read ``read`` (1 | 2) of the chunk's pairs: bases as indices into
    ACGTN and Phred qualities, both [n, 101] uint8."""
    n = p["n"]
    rng = np.random.default_rng([seed, 0xF0 + read, chunk])
    col = np.arange(READ_LEN, dtype=np.int64)[None, :]
    # R1 reads the fragment's own strand from its start, R2 the other
    # strand from its end; a fragment from the minus strand swaps them
    from_end = p["minus"] != (read == 2)
    first = np.where(from_end, p["start"] + p["insert"] - 1, p["start"])
    b = g[first[:, None] + np.where(from_end, -1, 1)[:, None] * col]
    b[from_end] = _COMP[b[from_end]]
    # the sparse events by their count and places, not a draw a base
    flat = b.reshape(-1)
    at = rng.integers(0, flat.size, rng.binomial(flat.size, MISCALL))
    flat[at] = (flat[at] + rng.integers(1, 4, at.size)) % 4

    mean, sd, decline = Q_MODEL[read - 1]
    f32 = np.float32
    level = rng.normal(mean, sd, n).astype(f32)[:, None]
    cycle = ((col / (READ_LEN - 1.0)) ** 2 * decline).astype(f32)
    noise = np.abs(rng.standard_normal((n, READ_LEN), dtype=f32))
    q = np.rint(level - cycle - f32(Q_NOISE) * noise)
    qf = q.reshape(-1)
    at = rng.integers(0, qf.size, rng.binomial(qf.size, Q_DIP))
    qf[at] -= rng.integers(4, 34, at.size).astype(f32)
    q = np.clip(q, Q_MIN, Q_MAX).astype(np.uint8)
    tail = np.flatnonzero(rng.random(n) < HASH_TAIL)
    tail_at = rng.integers(30, READ_LEN, tail.size)
    q[tail] = np.where(col >= tail_at[:, None], Q_MIN, q[tail])
    edge = np.flatnonzero(p["edge"])
    no_call = rng.random((edge.size, READ_LEN)) < EDGE_N
    b[edge] = np.where(no_call, _N, b[edge])
    q[edge] = np.where(no_call, Q_MIN, q[edge])
    return b, q


def _digits(v: np.ndarray, width: int) -> np.ndarray:
    """Decimal digits of ``v`` as ASCII, [n, width], 0 where a leading
    digit is absent (``assemble`` drops zero bytes)."""
    pw = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    d = (v[:, None] // pw[None, :]) % 10
    lead = v[:, None] >= pw[None, :]
    lead[:, -1] = True
    return np.where(lead, d + 48, 0).astype(np.uint8)


def assemble(read: int, p: Dict[str, np.ndarray], b: np.ndarray,
             q: np.ndarray) -> bytes:
    """The chunk's FASTQ text: every record laid out in a row as wide as
    the longest, absent digits as zero bytes, then the zero bytes dropped."""
    n = p["n"]
    flag = np.where(p["failed"], ord("Y"), ord("N")).astype(np.uint8)

    def const(text: str) -> np.ndarray:
        a = np.frombuffer(text.encode(), np.uint8)
        return np.broadcast_to(a, (n, a.size))

    row = np.concatenate([
        const(f"@{INSTRUMENT}:{RUN}:{FLOWCELL}:{LANE}:"),
        _digits(p["tile"], 4), const(":"), _digits(p["x"], 5), const(":"),
        _digits(p["y"], 6), const(f" {read}:"), flag[:, None],
        const(f":0:{INDEX}\n"), np.frombuffer(b"ACGTN", np.uint8)[b],
        const("\n+\n"), q + 33, const("\n")], axis=1)
    return row[row != 0].tobytes()


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
    def of(cls, b: np.ndarray, q: np.ndarray) -> "Sums":
        hist = np.zeros(16, np.int64)
        hist[_CODE] = np.bincount(b.ravel(), minlength=5)
        gc = ((b == 1) | (b == 2)).sum(axis=1) / float(READ_LEN)
        mq = q.sum(axis=1, dtype=np.int64) / float(READ_LEN)
        return cls(int(b.shape[0]), hist, float(gc.sum()), float(mq.sum()),
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


class Reference:
    """The reference's sums a file: ``all[r]`` over every read of file
    ``r`` (0 = R1, 1 = R2), ``passed[r]`` over those whose filter flag is
    ``N`` (what ``fastq_filter_failed_qc`` leaves)."""

    def __init__(self):
        self.all = [Sums.zero(), Sums.zero()]
        self.passed = [Sums.zero(), Sums.zero()]
        self.text_bytes = [0, 0]
        self.gz_bytes = [0, 0]

    def pair(self, passed: bool = False) -> Sums:
        s = self.passed if passed else self.all
        return s[0].plus(s[1])

    def wrong(self, printed: str, r: int, tol: Dict[str, float],
              passed: bool = False):
        """``None`` if ``hbam seq-stats``' printed answer for file ``r``
        agrees: reads and the base counts exactly, the means within the
        printed tolerances."""
        want = (self.passed if passed else self.all)[r]
        kv = {ln.split("\t")[0]: ln.split("\t")[1:]
              for ln in printed.strip().splitlines()}
        if int(kv["reads"][0]) != want.n:
            return f"reads {kv['reads'][0]} != {want.n}"
        hist = [int(kv.get(f"base_{c}", [0])[0]) for c in BASE_NAMES]
        if hist != want.hist.tolist():
            return f"base histogram {hist} != {want.hist.tolist()}"
        gc, mq = want.means()
        got_gc, got_mq = float(kv["mean_gc"][0]), float(kv["mean_qual"][0])
        if abs(got_gc - gc) > tol["mean_gc"]:
            return f"mean_gc {got_gc} vs {gc}"
        if abs(got_mq - mq) > tol["mean_qual"]:
            return f"mean_qual {got_mq} vs {mq}"
        return None


def outside(got: Tuple[float, float], want: Sums, tol: Dict[str, float]
            ) -> List[str]:
    """Which of the unrounded limits the reading (mean_gc, mean_qual)
    breaks against the float64 reference."""
    gc, mq = want.means()
    return [k for k, off in (("mean_gc", abs(got[0] - gc)),
                             ("mean_qual", abs(got[1] - mq)))
            if off > tol[k]]


# ---------------------------------------------------------------------------
# the files
# ---------------------------------------------------------------------------

def iter_chunks(seed: int, read: int, pairs: int,
                chunk_pairs: int = CHUNK_PAIRS
                ) -> Iterator[Tuple[bytes, Sums, Sums]]:
    """(text, sums of all reads, sums of the passed reads) a chunk."""
    g = genome(seed)
    for chunk in range(-(-pairs // chunk_pairs)):
        p = gen_pairs(seed, chunk, pairs, chunk_pairs, g)
        b, q = gen_read(seed, chunk, read, p, g)
        ok = ~p["failed"]
        yield assemble(read, p, b, q), Sums.of(b, q), Sums.of(b[ok], q[ok])


def write_file(job) -> Tuple[int, Sums, Sums, int, int]:
    """One file of the pair, whole: its chunks made on this thread while
    another deflates the one before into the file's ONE gzip member (zlib
    releases the interpreter lock).  ``job`` = (path, seed, read, pairs,
    chunk_pairs, level).  Module-level so a spawned process can run it."""
    path, seed, read, pairs, chunk_pairs, level = job
    q: "queue.Queue" = queue.Queue(maxsize=2)
    sizes = [0, 0]

    def deflate() -> None:
        z = zlib.compressobj(level, zlib.DEFLATED, 31)
        with open(path, "wb") as fh:
            while True:
                text = q.get()
                if text is None:
                    break
                fh.write(z.compress(text))
                sizes[0] += len(text)
            fh.write(z.flush())
            sizes[1] = fh.tell()

    writer = threading.Thread(target=deflate, name="gen-deflate")
    writer.start()
    every, passed = Sums.zero(), Sums.zero()
    try:
        for text, s_all, s_ok in iter_chunks(seed, read, pairs, chunk_pairs):
            every, passed = every.plus(s_all), passed.plus(s_ok)
            q.put(text)
    finally:
        q.put(None)
        writer.join()
    return read, every, passed, sizes[0], sizes[1]


def write_pair(directory: str, seed: int, pairs: int, ref: Reference,
               workers: int = 1, chunk_pairs: int = CHUNK_PAIRS,
               level: int = GZIP_LEVEL) -> Tuple[str, str]:
    """The lane's two files under ``directory``; ``workers`` > 1 makes R1
    and R2 (and their sums) in two spawned NumPy-only processes at once.
    Fills ``ref`` and returns the paths."""
    paths = file_names(directory)
    jobs = [(paths[r], seed, r + 1, pairs, chunk_pairs, level)
            for r in (0, 1)]
    pool = None
    if workers > 1:
        import multiprocessing

        pool = multiprocessing.get_context("spawn").Pool(2)
    try:
        for read, every, passed, text, gz in (
                pool.imap(write_file, jobs) if pool
                else map(write_file, jobs)):
            r = read - 1
            ref.all[r], ref.passed[r] = every, passed
            ref.text_bytes[r], ref.gz_bytes[r] = text, gz
    finally:
        if pool is not None:
            pool.terminate()
            pool.join()     # both workers have ended before set-up goes on
    return paths
