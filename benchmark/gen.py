"""The benchmark's data: a seeded NA12878-chr20-shaped BAM and its answers.

``gen_fields`` and ``assemble`` are a verbatim COPY of ``chip_smoke.py``'s
generator (same seed -> same bytes; ``tests/test_benchmark.py`` holds the two
together), kept here so that later PRs may change the program and the smoke
but not the yardstick.  ``Reference`` is plain NumPy over the generator's own
field arrays and never touches the code under test.

Shapes (never cut): 2x151 bp pairs on chr20 (LN 64,444,167), five CIGAR
forms, seeded unmapped / secondary / supplementary / duplicate shares, 4-bin
qualities, two read groups, bases from a seeded reference.  Scale (records,
chunks) comes from the configuration file.
"""
from __future__ import annotations

import hashlib
import zlib

import numpy as np

# ---------------------------------------------------------------------------
# the deployment's shape (BASELINE.json configs[0]; ROADMAP R1)
# ---------------------------------------------------------------------------

CONTIG, CONTIG_LEN = "chr20", 64_444_167
READ_LEN = 151
SAMPLE_READS = 12_800_000          # 30 x LN / 151 (ROADMAP R1)
CHUNK_RECORDS = 1 << 18            # generator grain == DecodeGeometry tile
HEADER_TEXT = (
    "@HD\tVN:1.6\tSO:coordinate\n"
    f"@SQ\tSN:{CONTIG}\tLN:{CONTIG_LEN}\n"
    "@RG\tID:rg0\tSM:NA12878\tLB:libA\tPL:ILLUMINA\n"
    "@RG\tID:rg1\tSM:NA12878\tLB:libB\tPL:ILLUMINA\n")

# CIGAR forms: (ops, aligned (ref_offset, length) segments, ref_len,
# leading clip, trailing clip).  Class 5 is the '*' CIGAR of an unmapped
# read.  Op codes [SPEC]: M=0 I=1 D=2 S=4.
CIGARS = (
    (((151, 0),), ((0, 151),), 151, 0, 0),                      # 151M
    (((12, 4), (139, 0)), ((0, 139),), 139, 12, 0),             # 12S139M
    (((141, 0), (10, 4)), ((0, 141),), 141, 0, 10),             # 141M10S
    (((70, 0), (2, 2), (81, 0)), ((0, 70), (72, 81)), 153, 0, 0),  # 70M2D81M
    (((5, 4), (60, 0), (3, 1), (83, 0)), ((0, 60), (60, 83)), 143, 5, 0),
    ((), (), 0, 0, 0),                                          # '*'
)
CIGAR_P = (0.70, 0.08, 0.08, 0.07, 0.07)
REF_LEN = np.array([c[2] for c in CIGARS], np.int64)
N_CIGAR = np.array([len(c[0]) for c in CIGARS], np.int64)
NAME_LEN = 13                       # 'q' + 11 digits + NUL
AUX = 7 + 4                         # RG:Z:rgN\0 + NM:C:n
REC_WIDTH = 36 + NAME_LEN + 4 * N_CIGAR + (READ_LEN + 1) // 2 + READ_LEN + AUX
QUAL_BINS = np.array([2, 12, 23, 37], np.uint8)     # binned qualities
QUAL_P = (0.03, 0.07, 0.20, 0.70)
BASE_CODES = np.array([1, 2, 4, 8], np.uint8)       # A C G T [SPEC 4-bit]


def _le(values, dtype) -> np.ndarray:
    """[n] ints -> [n, itemsize] little-endian bytes."""
    a = np.ascontiguousarray(np.asarray(values).astype(dtype))
    return a.view(np.uint8).reshape(a.shape[0], -1)


def _reg2bin(beg: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Vectorized [SPEC] SAMv1 5.3 reg2bin (end exclusive)."""
    end = end - 1
    out = np.zeros(beg.shape, np.int64)
    done = np.zeros(beg.shape, bool)
    for shift, base in ((14, 4681), (17, 585), (20, 73), (23, 9), (26, 1)):
        hit = ~done & ((beg >> shift) == (end >> shift))
        out[hit] = base + (beg[hit] >> shift)
        done |= hit
    return out


def gen_fields(seed: int, chunk: int, n_chunks: int, n: int,
               with_unmapped_tail: bool) -> dict:
    """Field arrays of one coordinate-sorted chunk of ``n`` records (n/2
    pairs), confined to the chunk's own slice of the contig so chunks
    concatenate into one sorted file."""
    rng = np.random.default_rng([seed, chunk])
    n_pairs = n // 2
    lo = CONTIG_LEN * chunk // n_chunks
    hi = CONTIG_LEN * (chunk + 1) // n_chunks
    start = rng.integers(lo, hi - 1200, n_pairs)
    insert = np.clip(rng.normal(400, 60, n_pairs), 200, 900).astype(np.int64)
    cf = rng.choice(5, n_pairs, p=CIGAR_P)          # forward read's CIGAR
    cr = rng.choice(5, n_pairs, p=CIGAR_P)          # reverse read's CIGAR
    f1r2 = rng.random(n_pairs) < 0.5                # which read is forward
    # duplicates of another molecule: same ends, same layout
    dup = np.flatnonzero(rng.random(n_pairs) < 0.05)
    src = rng.integers(0, n_pairs, dup.size)
    for a in (start, insert, cf, cr, f1r2):
        a[dup] = a[src]
    pair_id = np.int64(chunk) * (CHUNK_RECORDS // 2) \
        + np.arange(n_pairs, dtype=np.int64)

    f_pos = start
    r_pos = start + insert - REF_LEN[cr]
    f_flag = np.where(f1r2, 99, 163)
    r_flag = np.where(f1r2, 147, 83)
    f_tlen, r_tlen = insert.copy(), -insert
    f_mpos, r_mpos = r_pos.copy(), f_pos.copy()
    f_mref = np.zeros(n_pairs, np.int64)
    r_ref = np.zeros(n_pairs, np.int64)

    # reverse read unmapped: placed at its mate's coordinate, '*' CIGAR
    um = rng.random(n_pairs) < 0.01
    f_flag = np.where(um, np.where(f1r2, 73, 137), f_flag)
    r_flag = np.where(um, np.where(f1r2, 133, 69), r_flag)
    cr = np.where(um, 5, cr)
    r_pos = np.where(um, f_pos, r_pos)
    f_mpos = np.where(um, f_pos, f_mpos)
    f_tlen = np.where(um, 0, f_tlen)
    r_tlen = np.where(um, 0, r_tlen)
    f_ref = np.zeros(n_pairs, np.int64)
    if with_unmapped_tail:
        # both reads unmapped: no coordinate, sorts last in the file
        uu = ~um & (rng.random(n_pairs) < 0.005)
        f_flag = np.where(uu, 77, f_flag)
        r_flag = np.where(uu, 141, r_flag)
        cf = np.where(uu, 5, cf)
        cr = np.where(uu, 5, cr)
        for a in (f_pos, r_pos, f_mpos, r_mpos, f_ref, r_ref, f_mref):
            a[uu] = -1
        f_tlen = np.where(uu, 0, f_tlen)
        r_tlen = np.where(uu, 0, r_tlen)
    r_mref = f_ref.copy()

    refid = np.concatenate([f_ref, r_ref])
    pos = np.concatenate([f_pos, r_pos])
    flag = np.concatenate([f_flag, r_flag])
    cig = np.concatenate([cf, cr])
    mapped = (flag & 4) == 0
    # seeded shares of secondary / supplementary / duplicate flags
    u = rng.random(n)
    flag = flag | np.where(mapped & (u < 0.01), 0x100, 0)
    flag = flag | np.where(mapped & (u >= 0.01) & (u < 0.015), 0x800, 0)
    flag = flag | np.where(mapped & (rng.random(n) < 0.03), 0x400, 0)
    mapq = np.where(rng.random(n) < 0.7, 60, rng.integers(0, 60, n))
    mapq = np.where(mapped, mapq, 0)
    order = np.argsort(np.where(refid < 0, np.int64(1) << 40, pos),
                       kind="stable")
    f = {
        "refid": refid, "pos": pos, "flag": flag, "cig": cig, "mapq": mapq,
        "mref": np.concatenate([f_mref, r_mref]),
        "mpos": np.concatenate([f_mpos, r_mpos]),
        "tlen": np.concatenate([f_tlen, r_tlen]),
        "pair": np.concatenate([pair_id, pair_id]),
        "rg": np.concatenate([pair_id, pair_id]) & 1,
        "nm": rng.integers(0, 5, n),
    }
    f = {k: v[order] for k, v in f.items()}

    # bases from a seeded reference for this slice, so overlapping reads
    # repeat each other the way real coverage does (LZ77 sees matches)
    ref = BASE_CODES[rng.integers(0, 4, hi - lo + 2048, np.uint8)]
    at = np.clip(f["pos"] - lo, 0, hi - lo + 1024)
    codes = np.lib.stride_tricks.sliding_window_view(ref, READ_LEN)[at]
    unplaced = np.flatnonzero(f["refid"] < 0)
    codes[unplaced] = BASE_CODES[rng.integers(0, 4, (unplaced.size,
                                                     READ_LEN))]
    # work buffers are reused across chunks: fresh 100 MB temporaries
    # cost more in page faults than the arithmetic on them
    u, m = _work(n)
    rng.random(dtype=np.float32, out=u)
    sub = np.nonzero(np.less(u, 0.004, out=m))                  # miscalls
    codes[sub] = BASE_CODES[rng.integers(0, 4, sub[0].size)]
    codes[np.greater(u, 0.999, out=m)] = 15                     # N
    rng.random(dtype=np.float32, out=u)
    qi = np.zeros((n, READ_LEN), np.uint8)
    for t in np.cumsum(QUAL_P, dtype=np.float32)[:3]:
        qi += np.greater_equal(u, t, out=m).view(np.uint8)
    f["qual"] = QUAL_BINS[qi]
    f["codes"] = codes
    return f


_WORK: dict = {}


def _work(n: int):
    if n not in _WORK:
        _WORK.clear()
        _WORK[n] = (np.empty((n, READ_LEN), np.float32),
                    np.empty((n, READ_LEN), bool))
    return _WORK[n]


def assemble(f: dict, lo: int, hi: int):
    """Rows [lo, hi) of a field dict -> (flat record bytes, offsets)."""
    sl = slice(lo, hi)
    cig = f["cig"][sl]
    n = cig.size
    width = REC_WIDTH[cig]
    offs = np.cumsum(width) - width
    flat = np.empty(int(width.sum()), np.uint8)
    pos, refid = f["pos"][sl], f["refid"][sl]
    end = pos + np.maximum(REF_LEN[cig], 1)
    binv = np.where(refid < 0, 4680, _reg2bin(np.maximum(pos, 0),
                                              np.maximum(end, 1)))
    pid = f["pair"][sl]
    name = np.empty((n, NAME_LEN), np.uint8)
    name[:, 0] = ord("q")
    for k in range(11):
        name[:, 11 - k] = 48 + (pid // 10 ** k) % 10
    name[:, 12] = 0
    codes = np.concatenate([f["codes"][sl], np.zeros((n, 1), np.uint8)], 1)
    seq = (codes[:, 0::2] << 4) | codes[:, 1::2]
    aux = np.empty((n, AUX), np.uint8)
    aux[:, :5] = np.frombuffer(b"RGZrg", np.uint8)
    aux[:, 5] = 48 + f["rg"][sl]
    aux[:, 6] = 0
    aux[:, 7:10] = np.frombuffer(b"NMC", np.uint8)
    aux[:, 10] = f["nm"][sl]
    for k, (ops, _segs, _rl, _lead, _trail) in enumerate(CIGARS):
        idx = np.flatnonzero(cig == k)
        if not idx.size:
            continue
        w = int(REC_WIDTH[k])
        rows = np.empty((idx.size, w), np.uint8)
        rows[:, 0:4] = _le(np.full(idx.size, w - 4), "<i4")
        rows[:, 4:8] = _le(refid[idx], "<i4")
        rows[:, 8:12] = _le(pos[idx], "<i4")
        rows[:, 12] = NAME_LEN
        rows[:, 13] = f["mapq"][sl][idx]
        rows[:, 14:16] = _le(binv[idx], "<u2")
        rows[:, 16:18] = _le(np.full(idx.size, len(ops)), "<u2")
        rows[:, 18:20] = _le(f["flag"][sl][idx], "<u2")
        rows[:, 20:24] = _le(np.full(idx.size, READ_LEN), "<i4")
        rows[:, 24:28] = _le(f["mref"][sl][idx], "<i4")
        rows[:, 28:32] = _le(f["mpos"][sl][idx], "<i4")
        rows[:, 32:36] = _le(f["tlen"][sl][idx], "<i4")
        p = 36
        rows[:, p:p + NAME_LEN] = name[idx]
        p += NAME_LEN
        for ln, op in ops:
            rows[:, p:p + 4] = _le(np.full(idx.size, (ln << 4) | op), "<u4")
            p += 4
        rows[:, p:p + seq.shape[1]] = seq[idx]
        p += seq.shape[1]
        rows[:, p:p + READ_LEN] = f["qual"][sl][idx]
        p += READ_LEN
        rows[:, p:p + AUX] = aux[idx]
        flat[(offs[idx][:, None] + np.arange(w)[None, :]).ravel()] = \
            rows.ravel()
    return flat, offs




# ---------------------------------------------------------------------------
# plain NumPy references (independent of the code under test)
# ---------------------------------------------------------------------------

FLAGSTAT_KEYS = (
    "total", "primary", "secondary", "supplementary", "duplicates",
    "primary_duplicates", "mapped", "primary_mapped", "paired", "read1",
    "read2", "properly_paired", "with_itself_and_mate_mapped", "singletons",
    "mate_on_different_chr", "mate_on_different_chr_mapq5")
BASE_NAMES = "=ACMGRSVTWYHKDBN"


class Reference:
    """Expected answers, accumulated chunk by chunk from the generator's
    field arrays.  ``needs`` names what the cell compares (``flagstat``,
    ``seqstats``, ``regions``): a scan cell does not pay set-up for the
    interval table, a serving cell does not pay for base histograms."""

    def __init__(self, needs=("flagstat", "seqstats", "regions")):
        self.needs = frozenset(needs)
        self.n = 0
        self.flagstat = dict.fromkeys(FLAGSTAT_KEYS, 0)
        self.sum_gc = 0.0
        self.sum_mq = 0.0
        self.base_hist = np.zeros(16, np.int64)
        self._pos1: list = []
        self._end1: list = []
        self._sorted = None

    def add(self, f: dict) -> None:
        flag, refid, mref = f["flag"], f["refid"], f["mref"]
        self.n += flag.size
        if "flagstat" in self.needs:
            self._add_flagstat(flag, refid, mref, f["mapq"])
        if "seqstats" in self.needs:
            codes = f["codes"]
            gc = ((codes == 2) | (codes == 4) | (codes == 6)).sum(1)
            self.sum_gc += float((gc / READ_LEN).sum())
            self.sum_mq += float(f["qual"].mean(1, dtype=np.float64).sum())
            self.base_hist += np.bincount(codes.ravel(), minlength=16)
        if "regions" in self.needs:
            # 1-based inclusive [pos1, end1] of every placed record; an
            # unmapped read placed at its mate's coordinate spans READ_LEN
            rl = np.where(f["cig"] == 5, READ_LEN, REF_LEN[f["cig"]])
            placed = refid == 0
            self._pos1.append((f["pos"] + 1)[placed])
            self._end1.append((f["pos"] + np.maximum(rl, 1))[placed])
            self._sorted = None

    def merge(self, other: "Reference") -> None:
        """Fold in another instance's chunks (added after this one's)."""
        self.n += other.n
        for k, v in other.flagstat.items():
            self.flagstat[k] += v
        self.sum_gc += other.sum_gc
        self.sum_mq += other.sum_mq
        self.base_hist += other.base_hist
        self._pos1 += other._pos1
        self._end1 += other._end1
        self._sorted = None

    def _add_flagstat(self, flag, refid, mref, mapq) -> None:
        def has(bit):
            return (flag & bit) != 0
        primary = ~has(0x100) & ~has(0x800)
        mapped, paired, mmapped = ~has(0x4), has(0x1), ~has(0x8)
        both = paired & mapped & mmapped
        diff = both & (mref != refid) & (refid >= 0) & (mref >= 0)
        for k, m in (
                ("total", np.ones(flag.size, bool)), ("primary", primary),
                ("secondary", has(0x100)), ("supplementary", has(0x800)),
                ("duplicates", has(0x400)),
                ("primary_duplicates", primary & has(0x400)),
                ("mapped", mapped), ("primary_mapped", primary & mapped),
                ("paired", paired), ("read1", paired & has(0x40)),
                ("read2", paired & has(0x80)),
                ("properly_paired", paired & has(0x2) & mapped),
                ("with_itself_and_mate_mapped", both),
                ("singletons", paired & mapped & ~mmapped),
                ("mate_on_different_chr", diff),
                ("mate_on_different_chr_mapq5", diff & (mapq >= 5))):
            self.flagstat[k] += int(m.sum())

    def region_count(self, lo1: int, hi1: int) -> int:
        """Records on the contig overlapping 1-based inclusive
        [lo1, hi1].  No record spans more than max(REF_LEN) bases, so
        the candidates are a slice of the position-sorted table."""
        if self._sorted is None:
            pos1 = np.concatenate(self._pos1)
            end1 = np.concatenate(self._end1)
            order = np.argsort(pos1, kind="stable")
            self._sorted = (pos1[order], end1[order])
        pos1, end1 = self._sorted
        a = np.searchsorted(pos1, lo1 - int(REF_LEN.max()) - 1, "left")
        b = np.searchsorted(pos1, hi1, "right")
        return int((end1[a:b] >= lo1).sum())


# ---------------------------------------------------------------------------
# fixtures: the generator's records as files
# ---------------------------------------------------------------------------

_SLAB = 1 << 16      # records handed to the writer at a time


def _slabs(f: dict, n: int):
    for lo in range(0, n, _SLAB):
        yield assemble(f, lo, min(lo + _SLAB, n))


def chunk_fields(seed: int, n_chunks: int, chunk_records: int,
                 first: int = 0, count=None):
    """Field dicts of chunks [first, first+count) of an ``n_chunks``-chunk
    sample; only the sample's last chunk carries the unmapped tail."""
    count = n_chunks - first if count is None else count
    for c in range(first, first + count):
        yield gen_fields(seed, c, n_chunks, chunk_records,
                         c == n_chunks - 1)


def _chunk_job(job):
    """Worker side of ``write_sorted_bam``: one chunk's record bytes and
    its share of the reference (NumPy only; never imports JAX)."""
    seed, c, n_chunks, chunk_records, needs = job
    f = gen_fields(seed, c, n_chunks, chunk_records, c == n_chunks - 1)
    part = Reference(needs)
    part.add(f)
    return list(_slabs(f, chunk_records)), part


def write_sorted_bam(path: str, seed: int, n_chunks: int,
                     chunk_records: int, ref: Reference, writer, header_of,
                     workers: int = 1):
    """The coordinate-sorted sample through the program's own writer
    (``write_bam_records`` co-writes .bai + .sbi); every chunk is also
    folded into ``ref``.  Chunks are independent draws of (seed, chunk),
    so ``workers`` > 1 makes them in spawned NumPy-only processes, in
    order, while this process deflates and indexes.  Returns the
    writer's result."""
    jobs = [(seed, c, n_chunks, chunk_records, tuple(ref.needs))
            for c in range(n_chunks)]

    def chunks(results):
        for slabs, part in results:
            ref.merge(part)
            yield from slabs

    header = header_of(HEADER_TEXT)
    if workers <= 1:
        return writer(path, header, chunks(map(_chunk_job, jobs)))
    import multiprocessing

    pool = multiprocessing.get_context("spawn").Pool(min(workers, n_chunks))
    try:
        return writer(path, header, chunks(pool.imap(_chunk_job, jobs)))
    finally:
        pool.terminate()
        pool.join()         # every worker has ended before set-up goes on


def shuffled_fields(seed: int, n_chunks: int, chunk_records: int,
                    subset_chunks: int) -> dict:
    """The first ``subset_chunks`` chunks as an aligner emits them: one
    field dict in a seeded random order (the smoke's permutation)."""
    parts = list(chunk_fields(seed, n_chunks, chunk_records, 0,
                              subset_chunks))
    cat = {k: np.concatenate([f[k] for f in parts]) for k in parts[0]}
    n = subset_chunks * chunk_records
    perm = np.random.default_rng([seed, 1 << 20]).permutation(n)
    return {k: v[perm] for k, v in cat.items()}


def write_unsorted_bam(path: str, fields: dict, writer, header_of):
    n = fields["pos"].size
    header = header_of(HEADER_TEXT.replace("SO:coordinate", "SO:unsorted"))
    return writer(path, header, _slabs(fields, n), index_kinds=())


def sorted_stream_digest(fields: dict) -> str:
    """sha256 of the record stream a coordinate sort of ``fields`` must
    emit: a stable argsort by (refID, pos) with unplaced records
    (refID -1) last — ties keep input order."""
    refid, pos = fields["refid"], fields["pos"]
    key = np.where(refid < 0, np.int64(1) << 62,
                   (refid.astype(np.int64) << 32) | (pos + 1))
    order = np.argsort(key, kind="stable")
    f = {k: v[order] for k, v in fields.items()}
    h = hashlib.sha256()
    for data, _offs in _slabs(f, order.size):
        h.update(data.tobytes())
    return h.hexdigest()


def bam_record_stream_digest(path: str) -> str:
    """sha256 of a BAM's record stream (header skipped), inflated with
    plain zlib — no code of the program reads the output back."""
    with open(path, "rb") as fh:
        raw = fh.read()
    out = bytearray()
    p = 0
    while p < len(raw):
        # BGZF member: 18-byte header with BSIZE at 16, raw deflate,
        # CRC32 + ISIZE [SPEC 4.1]
        size = int.from_bytes(raw[p + 16:p + 18], "little") + 1
        out += zlib.decompress(raw[p + 18:p + size - 8], -15)
        p += size
    buf = memoryview(out)
    l_text = int.from_bytes(buf[4:8], "little")
    p = 8 + l_text
    n_ref = int.from_bytes(buf[p:p + 4], "little")
    p += 4
    for _ in range(n_ref):
        l_name = int.from_bytes(buf[p:p + 4], "little")
        p += 4 + l_name + 4
    return hashlib.sha256(buf[p:]).hexdigest()
