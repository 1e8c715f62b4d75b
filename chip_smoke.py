#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once — file -> host feed -> mesh -> serve — through the
entry points a user would call (`hbam` verbs via ``tools.cli.main``, the plan
executor, an in-process ``ServeLoop`` behind ``make_tcp_server``), in THIS
process (one process holds the chip), on data made from ``--seed`` at the
shape of BASELINE.json configs[0] (NA12878 chr20 30x: 2x151 bp pairs on chr20).
Every answer is compared with a plain NumPy reference computed from the
generator's own arrays, or with the repo's serial host oracles
(``utils/sort.sort_bam``, ``prep/oracle.py``) where the contract is byte
identity.  Any failed phase fails the run.

    python3 chip_smoke.py                 # on a TPU: 2^22 records, ~1.2 GB
    JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny   # sandbox / tier-1

Without ``--tiny`` the script refuses any platform but a TPU: it exits
non-zero and prints no result line.  ``--tiny`` additionally accepts an
explicitly requested CPU (``JAX_PLATFORMS=cpu``) and runs the same phases at
a size that proves the control flow only — every report line then says
``platform: cpu``.

Last stdout line on success: one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import hashlib
import io
import json
import os
import shutil
import socket
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

# ---------------------------------------------------------------------------
# the deployment's shape (BASELINE.json configs[0]; ROADMAP R1)
# ---------------------------------------------------------------------------

CONTIG, CONTIG_LEN = "chr20", 64_444_167
READ_LEN = 151
SAMPLE_READS = 12_800_000          # 30 x LN / 151 (ROADMAP R1)
FULL_RECORDS = 1 << 22             # the cut this smoke runs at
CHUNK_RECORDS = 1 << 18            # generator grain == DecodeGeometry tile
# Sort / mkdup / serve subset.  ISSUE 21 asks for >= 2^20; cut to 2^19
# because the exchange and markdup steps alone take ~440 s to compile on
# a cold one-chip machine (size-independent).
# `--sort-records 1048576` restores it (PERF.md: passed on 1 and 4 chips).
SORT_RECORDS = 1 << 19
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

class Reference:
    """Expected answers, accumulated chunk by chunk from the generator's
    field arrays."""

    def __init__(self, cov_lo: int, cov_hi: int):
        self.n = 0
        self.flagstat = dict.fromkeys(FLAGSTAT_KEYS, 0)
        self.sum_gc = 0.0
        self.sum_mq = 0.0
        self.base_hist = np.zeros(16, np.int64)
        self.cov_lo, self.cov_hi = cov_lo, cov_hi      # 1-based inclusive
        self.diff = np.zeros(cov_hi - cov_lo + 2, np.int64)
        self.intervals: list = []          # (refid, pos1, end1) per chunk

    def add(self, f: dict, keep_intervals: bool) -> None:
        flag, refid, mref = f["flag"], f["refid"], f["mref"]
        self.n += flag.size

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
                ("mate_on_different_chr_mapq5", diff & (f["mapq"] >= 5))):
            self.flagstat[k] += int(m.sum())
        codes = f["codes"]
        gc = ((codes == 2) | (codes == 4) | (codes == 6)).sum(1)
        self.sum_gc += float((gc / READ_LEN).sum())
        self.sum_mq += float(f["qual"].mean(1, dtype=np.float64).sum())
        self.base_hist += np.bincount(codes.ravel(), minlength=16)
        # depth: M/=/X bases of mapped records (ops/cigar.py contract)
        w0 = self.cov_lo - 1                     # 0-based window start
        wn = self.cov_hi - w0
        for k, (_ops, segs, _rl, _lead, _trail) in enumerate(CIGARS):
            idx = np.flatnonzero((f["cig"] == k) & mapped & (refid == 0))
            for off, ln in segs:
                s = np.clip(f["pos"][idx] + off - w0, 0, wn)
                e = np.clip(f["pos"][idx] + off + ln - w0, 0, wn)
                self.diff += np.bincount(s, minlength=wn + 1)
                self.diff -= np.bincount(e, minlength=wn + 1)
        if keep_intervals:
            rl = np.where(f["cig"] == 5, READ_LEN, REF_LEN[f["cig"]])
            self.intervals.append((refid, f["pos"] + 1,
                                   f["pos"] + np.maximum(rl, 1)))

    def depth(self) -> np.ndarray:
        return np.cumsum(self.diff[:-1])[:self.cov_hi - self.cov_lo + 1]

    def region_count(self, lo1: int, hi1: int) -> int:
        return sum(int(((refid == 0) & (pos1 <= hi1) & (end1 >= lo1)).sum())
                   for refid, pos1, end1 in self.intervals)


FLAGSTAT_KEYS = (
    "total", "primary", "secondary", "supplementary", "duplicates",
    "primary_duplicates", "mapped", "primary_mapped", "paired", "read1",
    "read2", "properly_paired", "with_itself_and_mate_mapped", "singletons",
    "mate_on_different_chr", "mate_on_different_chr_mapq5")


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for blk in iter(lambda: fh.read(1 << 22), b""):
            h.update(blk)
    return h.hexdigest()


def run_cli(argv) -> str:
    """One `hbam` verb through its normal entry point, in this process;
    returns its stdout."""
    from hadoop_bam_tpu.tools.cli import main as hbam_main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = hbam_main(list(argv))
    check(rc == 0, f"hbam {' '.join(argv)} exited {rc}")
    return out.getvalue()


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Smoke:
    def __init__(self, args, jax, devices, cache_dir: str):
        self.args, self.jax, self.devices = args, jax, devices
        d0 = devices[0]
        self.device = {"platform": d0.platform, "kind": d0.device_kind,
                       "count": len(devices)}
        self.tag = (f"platform: {d0.platform} device_kind: {d0.device_kind} "
                    f"devices: {len(devices)}")
        self.n_dev = len(devices)
        self.cache_dir = cache_dir
        tiny = args.tiny
        self.records = args.records or ((1 << 13) if tiny else FULL_RECORDS)
        self.chunk = min(CHUNK_RECORDS, self.records // 4) if tiny \
            else CHUNK_RECORDS
        self.n_chunks = self.records // self.chunk
        # sort / mkdup / serve subset: a whole number of generator chunks
        want = args.sort_records or (self.records // 4 if tiny
                                     else SORT_RECORDS)
        self.subset_chunks = min(self.n_chunks, max(1, want // self.chunk))
        self.subset = self.subset_chunks * self.chunk
        self.n_regions = 12 if tiny else 48
        win = 200_000 if tiny else 4_000_000
        self.cov_lo = 1_000_001
        self.cov_hi = self.cov_lo + win - 1
        self.phases: list = []
        self.report = {"device": self.device, "seed": args.seed,
                       "records": self.records, "tiny": bool(tiny),
                       "phases": self.phases}
        self.scratch = tempfile.mkdtemp(prefix="hbam_smoke_",
                                        dir=args.scratch)
        self._lock = threading.Lock()
        self._compile_s = 0.0
        self._hits = 0
        self._misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    # -- instrumentation -----------------------------------------------------

    def _on_secs(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self._compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self._hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self._misses += 1

    def _compile_state(self):
        with self._lock:
            return self._compile_s, self._hits, self._misses

    def peak_device_bytes(self):
        peaks = []
        for d in self.devices:
            st = d.memory_stats() or {}
            peaks.append(st.get("peak_bytes_in_use"))
        return peaks

    def say(self, phase: str, msg: str) -> None:
        print(f"[{phase}] {self.tag} | {msg}", flush=True)

    @contextlib.contextmanager
    def phase(self, key: str):
        """One strict phase: any exception marks it failed (and so the
        run), but later phases still run — a chip call should report
        every breakage it can find, not only the first."""
        rec = {"phase": key, "ok": False}
        self.phases.append(rec)
        c0, h0, m0 = self._compile_state()
        t0 = time.perf_counter()
        try:
            yield rec
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001 — the phase boundary
            rec["error"] = f"{type(e).__name__}: {e}"
            traceback.print_exc(file=sys.stdout)
        finally:
            c1, h1, m1 = self._compile_state()
            rec["seconds"] = round(time.perf_counter() - t0, 3)
            rec["compile_seconds"] = round(c1 - c0, 3)
            rec["cache_hits"] = h1 - h0
            rec["cache_entries_written"] = m1 - m0
            # a running maximum since process start (JAX cannot reset it)
            rec["peak_device_bytes_so_far"] = self.peak_device_bytes()
            self.say(key, ("ok" if rec["ok"] else
                           f"FAILED {rec.get('error')}")
                     + f" | {rec['seconds']}s wall, compile "
                       f"{rec['compile_seconds']}s, cache hits "
                       f"{rec['cache_hits']} / written "
                       f"{rec['cache_entries_written']}, peak device bytes "
                       f"so far {rec['peak_device_bytes_so_far']}")

    def need(self, *keys: str) -> None:
        by = {p["phase"]: p for p in self.phases}
        for k in keys:
            check(by.get(k, {}).get("ok"), f"needs phase {k!r}, which "
                                           f"did not pass")

    def device_rows(self, counters: dict, prefix: str):
        """Per-device record counts a mesh phase dispatched, from the
        program's own counters."""
        return [int(counters.get(f"{prefix}.{d}", 0))
                for d in range(self.n_dev)]

    def check_all_devices_fed(self, key: str, rows) -> None:
        self.say(key, f"per-device records {rows}")
        # at --tiny size a file can hold fewer spans than the mesh has
        # positions; at the real size an unfed device is a failure
        check(self.args.tiny or all(r > 0 for r in rows),
              f"{key}: a device received no records: {rows}")

    # -- phases --------------------------------------------------------------

    def run(self) -> bool:
        self.env()
        self.host_feed()
        self.fixture()
        if self.phases[-1]["ok"]:
            self.start_host_oracles()
        self.scan()
        self.sort_mkdup()
        self.serve()
        self.compile_cache()
        return all(p["ok"] for p in self.phases)

    def env(self) -> None:
        with self.phase("1-environment") as rec:
            import importlib.metadata as md

            import jaxlib
            vers = {"jax": self.jax.__version__,
                    "jaxlib": jaxlib.__version__}
            try:
                vers["libtpu"] = md.version("libtpu")
            except md.PackageNotFoundError:
                vers["libtpu"] = None
            rec["versions"] = vers
            rec["compile_cache_dir"] = self.cache_dir
            rec["compile_cache_from_env"] = bool(
                os.environ.get("JAX_COMPILATION_CACHE_DIR"))
            cut = SAMPLE_READS / self.records
            rec["cut"] = (f"{self.records} records = 1/{cut:.2f} of the "
                          f"sample's ~{SAMPLE_READS} reads (chr20 itself "
                          f"is ~2% of the genome); sort/mkdup/serve on the "
                          f"first {self.subset}"
                          + ("" if self.subset >= 1 << 20 or self.args.tiny
                             else " (cut below 2^20: cold compile of the "
                                  "exchange + markdup steps leaves the "
                                  "1200 s limit no room for more)"))
            self.say("1-environment", f"{vers} cache {self.cache_dir} "
                                      f"| {rec['cut']}")

    def host_feed(self) -> None:
        with self.phase("2-host-feed") as rec:
            from hadoop_bam_tpu.config import DEFAULT_CONFIG
            from hadoop_bam_tpu.utils import native
            from hadoop_bam_tpu.utils.pools import decode_pool_size

            info = native.build_info()
            check(native.load() is not None,
                  f"native library unavailable: {info['error']}")
            check(native.fused_available(), "fused decode entry points "
                                            "missing from the native build")
            rec["native"] = {"flavour": info["flavour"],
                             "artifact": os.path.basename(info["path"])}
            rec["decode_pool_workers"] = decode_pool_size(DEFAULT_CONFIG)
            rec["host_cpus"] = os.cpu_count()
            self.say("2-host-feed",
                     f"native inflate via {info['flavour']}, fused "
                     f"available, decode pool {rec['decode_pool_workers']} "
                     f"workers on {rec['host_cpus']} cpus")

    def fixture(self) -> None:
        with self.phase("0-fixture") as rec:
            from hadoop_bam_tpu.formats.bam import SAMHeader
            from hadoop_bam_tpu.write import write_bam_records

            self.ref = Reference(self.cov_lo, self.cov_hi)
            self.bam = os.path.join(self.scratch, "smoke.bam")
            nbytes = 0
            subset_fields: list = []

            def chunks():
                nonlocal nbytes
                for c in range(self.n_chunks):
                    f = gen_fields(self.args.seed, c, self.n_chunks,
                                   self.chunk, c == self.n_chunks - 1)
                    self.ref.add(f, keep_intervals=c < self.subset_chunks)
                    if c < self.subset_chunks:
                        subset_fields.append(f)
                    for lo in range(0, self.chunk, 1 << 16):
                        data, offs = assemble(
                            f, lo, min(lo + (1 << 16), self.chunk))
                        nbytes += data.size
                        yield data, offs

            t0 = time.perf_counter()
            res = write_bam_records(
                self.bam, SAMHeader.from_sam_text(HEADER_TEXT), chunks())
            dt = time.perf_counter() - t0
            check(res.records == self.records, "writer lost records")
            check({".bai", ".sbi"} <= set(res.sidecars),
                  f"index sidecars not co-written: {sorted(res.sidecars)}")

            # the shuffled subset sort/mkdup take in: the first
            # subset_chunks chunks in a seeded random order, no index
            cat = {k: np.concatenate([f[k] for f in subset_fields])
                   for k in subset_fields[0]}
            del subset_fields
            perm = np.random.default_rng(
                [self.args.seed, 1 << 20]).permutation(self.subset)
            cat = {k: v[perm] for k, v in cat.items()}
            self.shuffled = os.path.join(self.scratch, "shuffled.bam")
            unsorted = SAMHeader.from_sam_text(
                HEADER_TEXT.replace("SO:coordinate", "SO:unsorted"))
            write_bam_records(
                self.shuffled, unsorted,
                (assemble(cat, lo, min(lo + (1 << 16), self.subset))
                 for lo in range(0, self.subset, 1 << 16)),
                index_kinds=())
            rec.update(records=self.records, inflated_bytes=nbytes,
                       file_bytes=os.path.getsize(self.bam),
                       write_seconds=round(dt, 2),
                       subset_records=self.subset)
            self.say("0-fixture",
                     f"{self.records} records, {nbytes / 1e9:.3f} GB "
                     f"inflated -> {rec['file_bytes'] / 1e6:.1f} MB BGZF "
                     f"+ .bai/.sbi through write_bam_records in {dt:.1f}s "
                     f"(generate+deflate+index; smoke observation); "
                     f"shuffled subset {self.subset} records")

    def scan(self) -> None:
        from hadoop_bam_tpu.utils.metrics import MetricsContext

        with self.phase("3-scan") as rec:
            self.need("0-fixture")
            with MetricsContext() as m:
                # summarize (flagstat over the projected prefix tiles)
                t0 = time.perf_counter()
                out = run_cli(["summarize", self.bam])
                rec["summarize_seconds"] = round(time.perf_counter() - t0, 2)
                got = [int(ln.split(" ", 1)[0])
                       for ln in out.strip().splitlines()]
                want = [self.ref.flagstat[k] for k in FLAGSTAT_KEYS]
                check(got == want, f"summarize {got} != reference {want}")
                self.say("3-scan", f"summarize == NumPy reference "
                                   f"(total {got[0]}, mapped {got[6]}, "
                                   f"duplicates {got[4]}) in "
                                   f"{rec['summarize_seconds']}s")

                # seq-stats (payload tiles through the Pallas kernel)
                t0 = time.perf_counter()
                out = run_cli(["seq-stats", self.bam])
                rec["seq_stats_seconds"] = round(time.perf_counter() - t0, 2)
                kv = {ln.split("\t")[0]: ln.split("\t")[1:]
                      for ln in out.strip().splitlines()}
                check(int(kv["reads"][0]) == self.ref.n, "seq-stats reads")
                gc, mq = float(kv["mean_gc"][0]), float(kv["mean_qual"][0])
                check(abs(gc - self.ref.sum_gc / self.ref.n) < 2e-5,
                      f"mean_gc {gc} vs {self.ref.sum_gc / self.ref.n}")
                check(abs(mq - self.ref.sum_mq / self.ref.n) < 2e-3,
                      f"mean_qual {mq} vs {self.ref.sum_mq / self.ref.n}")
                names = "=ACMGRSVTWYHKDBN"
                hist = [int(kv.get(f"base_{c}", [0])[0]) for c in names]
                check(hist == self.ref.base_hist.tolist(),
                      f"base histogram {hist} != "
                      f"{self.ref.base_hist.tolist()}")
                rec["seq_stats_kernel"] = self.seq_stats_kernel_kind()
                self.say("3-scan", f"seq-stats == NumPy reference (mean_gc "
                                   f"{gc}, mean_qual {mq}) in "
                                   f"{rec['seq_stats_seconds']}s; step "
                                   f"kernel: {rec['seq_stats_kernel']}")

                # coverage (CIGAR pileup over a region, .bai-pruned)
                region = f"{CONTIG}:{self.cov_lo}-{self.cov_hi}"
                bg = os.path.join(self.scratch, "cov.bedgraph")
                t0 = time.perf_counter()
                out = run_cli(["coverage", self.bam, region,
                               "--bedgraph", bg])
                rec["coverage_seconds"] = round(time.perf_counter() - t0, 2)
                depth = np.zeros(self.cov_hi - self.cov_lo + 1, np.int64)
                with open(bg) as fh:
                    for ln in fh:
                        _c, s, e, d = ln.split("\t")
                        depth[int(s) - self.cov_lo + 1:
                              int(e) - self.cov_lo + 1] = int(d)
                want_depth = self.ref.depth()
                check(np.array_equal(depth, want_depth),
                      f"coverage differs from the reference at "
                      f"{int((depth != want_depth).sum())} bases")
                check(f"max_depth\t{int(want_depth.max())}" in out,
                      "coverage summary max_depth")
                self.say("3-scan", f"coverage {region} == NumPy reference "
                                   f"(mean depth {want_depth.mean():.3f}, "
                                   f"max {int(want_depth.max())}) in "
                                   f"{rec['coverage_seconds']}s")
            snap = m.snapshot()
            dem = int(snap["counters"].get("resilience.demotions", 0)) \
                + int(snap["counters"].get("pipeline.span_demotions", 0))
            rec["demotions"] = dem
            check(dem == 0, f"{dem} decode-plane demotions during the scan")
            rows = self.device_rows(snap["counters"], "pipeline.device_rows")
            rec["device_rows"] = rows
            self.check_all_devices_fed("3-scan", rows)

    def seq_stats_kernel_kind(self) -> str:
        """What the seq-stats step the run just used is compiled from:
        the Mosaic kernel ("tpu_custom_call" in the compiled module) or
        the plain-XLA twin.  On a TPU anything but Mosaic fails."""
        jax = self.jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from hadoop_bam_tpu.parallel.mesh import make_mesh
        from hadoop_bam_tpu.parallel.pipeline import (
            PayloadGeometry, make_seq_stats_step,
        )
        mesh = make_mesh()
        geo = PayloadGeometry(max_len=160)      # the verb's default
        step = make_seq_stats_step(mesh, geo)   # the run's cached step
        sh = NamedSharding(mesh, P("data"))
        cap = geo.tile_records

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct((self.n_dev,) + shape, dtype,
                                        sharding=sh)
        compiled = step.lower(
            spec((cap, 36), np.uint8), spec((cap, geo.seq_stride), np.uint8),
            spec((cap, geo.qual_stride), np.uint8),
            spec((), np.int32)).compile()
        mosaic = "tpu_custom_call" in compiled.as_text()
        if self.device["platform"] == "tpu":
            check(mosaic, "seq-stats step on the TPU does not contain the "
                          "Mosaic kernel (tpu_custom_call)")
        return "mosaic" if mosaic else "xla-twin"

    def regions(self, n: int, seed_tag: int,
                widths=(300, 2_000, 20_000)):
        """Seeded region windows inside the subset's slice of the contig
        (1-based inclusive)."""
        rng = np.random.default_rng([self.args.seed, 100 + seed_tag])
        span = CONTIG_LEN * self.subset_chunks // self.n_chunks
        out = []
        for _ in range(n):
            w = int(rng.choice(widths))
            lo = int(rng.integers(1, span - w))
            out.append((f"{CONTIG}:{lo}-{lo + w - 1}", lo, lo + w - 1))
        return out

    def start_host_oracles(self) -> None:
        """The serial host references phase 4 compares against
        (utils/sort.sort_bam, prep/oracle.py) are pure host Python; they
        run on a thread of their own from here on, beside the device
        phases, instead of adding their wall to the run's."""
        from hadoop_bam_tpu.prep.oracle import markdup_bam_oracle
        from hadoop_bam_tpu.utils.sort import sort_bam

        self.oracle = {"sorted": os.path.join(self.scratch,
                                              "sorted.oracle.bam"),
                       "mkdup": os.path.join(self.scratch,
                                             "mkdup.oracle.bam")}

        def work():
            try:
                t0 = time.perf_counter()
                sort_bam(self.shuffled, self.oracle["sorted"])
                t1 = time.perf_counter()
                markdup_bam_oracle(self.shuffled, self.oracle["mkdup"])
                self.oracle["seconds"] = (round(t1 - t0, 2), round(
                    time.perf_counter() - t1, 2))
            except BaseException as e:  # noqa: BLE001 — joined in phase 4
                self.oracle["error"] = e

        self.oracle_thread = threading.Thread(target=work, daemon=True)
        self.oracle_thread.start()

    def mesh_job(self, key: str, argv, want_sha: str, min_rounds: int):
        """One mesh verb: run it, compare its output bytes with the host
        oracle's, and read what the program counted."""
        from hadoop_bam_tpu.utils.metrics import MetricsContext

        out = argv[2]
        c0 = self._compile_state()[0]
        with MetricsContext() as m:
            t0 = time.perf_counter()
            run_cli(argv)
            dt = time.perf_counter() - t0
        snap = m.snapshot()["counters"]
        got = sha256_file(out)
        check(got == want_sha, f"hbam {' '.join(argv[:1] + argv[3:])}: "
                               f"output differs from the host oracle")
        rows = self.device_rows(snap, "mesh_sort.device_rows")
        rounds = int(snap.get("mesh_sort.rounds", 0))
        doc = {"seconds": round(dt, 2),
               "compile_seconds": round(self._compile_state()[0] - c0, 2),
               "rounds": rounds, "device_rows": rows, "sha256": got}
        self.say("4-sort-mkdup",
                 f"hbam {' '.join(argv[:1] + argv[3:])}: {self.subset} "
                 f"records, {rounds} exchange round(s), byte-identical to "
                 f"the host oracle (sha256 {got[:16]}) in {dt:.1f}s "
                 f"(compile {doc['compile_seconds']}s)")
        check(rounds >= min_rounds, f"{key}: {rounds} exchange rounds, "
                                    f"wanted >= {min_rounds}")
        self.check_all_devices_fed(f"4-sort-mkdup/{key}", rows)
        return doc, snap

    def sort_mkdup(self) -> None:
        with self.phase("4-sort-mkdup") as rec:
            self.need("0-fixture")
            self.oracle_thread.join()
            if "error" in self.oracle:
                raise self.oracle["error"]
            rec["host_oracle_seconds"] = dict(zip(("sort", "mkdup"),
                                                  self.oracle["seconds"]))
            want = sha256_file(self.oracle["sorted"])
            want_md = sha256_file(self.oracle["mkdup"])
            sc = self.scratch
            # >= 4 rounds whatever the mesh width, and rounds of equal
            # size: a round tile's shape follows its record count, and
            # every new shape is a fresh compile of the exchange step
            rr = str(max(1, self.subset // (4 * self.n_dev)))
            self.sorted_bam = os.path.join(sc, "sorted.index.bam")
            rec["sort_index"], _ = self.mesh_job(
                "index", ["sort", self.shuffled, self.sorted_bam, "--mesh",
                          "--exchange", "index"], want, 1)
            check(os.path.exists(self.sorted_bam + ".bai"),
                  "mesh sort did not co-write the .bai sidecar")
            tmp = os.path.join(sc, "job.bam")
            rec["sort_bytes"], _ = self.mesh_job(
                "bytes", ["sort", self.shuffled, tmp, "--mesh",
                          "--exchange", "bytes"], want, 1)
            rec["sort_bytes_spill"], _ = self.mesh_job(
                "bytes-spill", ["sort", self.shuffled, tmp, "--mesh",
                                "--run-records", rr], want, 3)
            rec["mkdup"], snap = self.mesh_job(
                "mkdup", ["mkdup", self.shuffled, tmp, "--run-records", rr],
                want_md, 3)
            dups = int(snap.get("prep.duplicates_marked", 0))
            rec["mkdup"]["duplicates_marked"] = dups
            check(dups > 0, "mkdup marked no duplicates on a fixture "
                            "seeded with them")
            self.say("4-sort-mkdup", f"mkdup marked {dups} duplicates")

    def serve(self) -> None:
        with self.phase("5-serve") as rec:
            self.need("4-sort-mkdup")
            from hadoop_bam_tpu.query.engine import QueryEngine, QueryRequest
            from hadoop_bam_tpu.serve import ServeLoop
            from hadoop_bam_tpu.serve.transport import make_tcp_server

            path = self.sorted_bam
            regions = self.regions(self.n_regions, seed_tag=6)
            names = [r for r, _lo, _hi in regions]
            want = [self.ref.region_count(lo, hi) for _r, lo, hi in regions]
            engine = QueryEngine()
            got = [len(r.records) for r in engine.query_records(
                [QueryRequest(path, r) for r in names])]
            check(got == want, f"QueryEngine.query_records {got} != NumPy "
                               f"reference {want}")

            with ServeLoop() as loop:
                server = make_tcp_server(loop)
                t = threading.Thread(target=server.serve_forever,
                                     daemon=True)
                t.start()
                try:
                    host, port = server.server_address[:2]
                    with socket.create_connection((host, port),
                                                  timeout=120) as sock:
                        rf = sock.makefile("r")

                        def ask(doc):
                            sock.sendall((json.dumps(doc) + "\n").encode())
                            return json.loads(rf.readline())

                        lat = {}
                        for label in ("cold", "warm"):
                            ms = []
                            for i, r in enumerate(names):
                                ans = ask({"id": i, "path": path,
                                           "regions": [r]})
                                check("results" in ans,
                                      f"serve error: {ans}")
                                check(ans["results"][0]["count"] == want[i],
                                      f"serve {label} {r}: "
                                      f"{ans['results'][0]['count']} != "
                                      f"{want[i]}")
                                ms.append(ans["latency_ms"])
                            lat[label] = float(np.median(ms))
                        health = ask({"op": "health"})["health"]
                        check(health["status"] == "serving",
                              f"health says {health['status']}")
                        tiles = loop.stats()["tiles"]
                finally:
                    server.shutdown()
                    server.server_close()
                    t.join(timeout=30)
            rec.update(regions=len(names), cold_median_ms=lat["cold"],
                       warm_median_ms=lat["warm"], tile_cache=tiles,
                       open_breakers=health["open_breakers"])
            check(not health["open_breakers"],
                  f"open breakers after serving: {health['open_breakers']}")
            self.say("5-serve",
                     f"{len(names)} regions over a socket, cold then warm, "
                     f"== QueryEngine.query_records == NumPy reference; "
                     f"median latency cold {lat['cold']:.1f} ms / warm "
                     f"{lat['warm']:.1f} ms (smoke observation); health "
                     f"ok; tile cache {tiles}")

    def compile_cache(self) -> None:
        with self.phase("6-compile-cache") as rec:
            total_c = sum(p.get("compile_seconds", 0) for p in self.phases)
            hits = sum(p.get("cache_hits", 0) for p in self.phases)
            wrote = sum(p.get("cache_entries_written", 0)
                        for p in self.phases)
            rec.update(total_compile_seconds=round(total_c, 2),
                       total_cache_hits=hits, total_entries_written=wrote,
                       dir=self.cache_dir)
            self.say("6-compile-cache",
                     f"compile {total_c:.1f}s over the run; persistent "
                     f"cache at {self.cache_dir}: {hits} hits, {wrote} "
                     f"entries written (a second run in this checkout "
                     f"reports hits instead)")
            check(hits + wrote > 0, "the persistent compile cache saw no "
                                    "traffic — it is not enabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20)
    ap.add_argument("--tiny", action="store_true",
                    help="control-flow size; also accepts an explicitly "
                         "requested CPU (JAX_PLATFORMS=cpu)")
    ap.add_argument("--records", type=int, default=None,
                    help="override the record count (a multiple of 2^18 "
                         "unless --tiny)")
    ap.add_argument("--sort-records", type=int, default=None,
                    help="records in the sort / mkdup / serve subset "
                         "(default 2^19; whole 2^18 chunks)")
    ap.add_argument("--out", default="chiprun_out",
                    help="directory for chip_smoke_report.json")
    ap.add_argument("--scratch", default=None,
                    help="parent of the scratch directory (default: the "
                         "system temp dir); removed on exit")
    args = ap.parse_args(argv)

    try:
        import jax

        from hadoop_bam_tpu.utils import backend
    except ImportError as e:
        print(f"chip_smoke: the program is not importable here: {e}",
              file=sys.stderr)
        return 2
    cache_dir = backend.enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not (args.tiny and platform == "cpu"
                                  and backend.cpu_requested()):
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              f"refusing to run.  The CPU is accepted only with --tiny "
              f"under an explicit JAX_PLATFORMS=cpu.", file=sys.stderr)
        return 2

    smoke = Smoke(args, jax, devices, cache_dir)
    try:
        ok = smoke.run()
    finally:
        shutil.rmtree(smoke.scratch, ignore_errors=True)
    smoke.report["ok"] = ok
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "chip_smoke_report.json"), "w") as fh:
        json.dump(smoke.report, fh, indent=1, default=str)
    if not ok:
        failed = [p["phase"] for p in smoke.phases if not p["ok"]]
        print(f"chip_smoke: FAILED phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": smoke.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
