"""Columnar BCF record decode: typed columns out, no per-record Python
objects and no per-typed-value ``struct`` calls.

The variant stats/tensor path needs columns — CHROM/POS/rlen/QUAL/
n_allele/n_fmt, the PASS/SNP flag byte, and the GT dosage matrix — not
``VcfRecord`` objects.  This module decodes a whole span of concatenated
BCF record bytes into exactly those columns, the BCF twin of
``formats/cram_columns.py``.

**What runs** (since PR 39, where ``utils/native.load()`` gives the
library — no flag, no option): a framed span is ONE native call,
``native/hbam_native.cpp::hbam_bcf_span_columns``, with the interpreter
lock released.  It walks a record at a time — the 24 fixed bytes, ID
skipped, alleles -> the SNP test, FILTER -> PASS, INFO jumped by
``l_shared``, the FORMAT keys walked to ``GT`` — and reduces the record's
GT vector to its int8 dosage row with the loop ``hbam_bcf_gt_dosage`` runs
for the record's own (width, ploidy): a loop the compiler vectorises over
samples for ploidy 1 and 2, a generic one for every other layout, chosen a
record from what the record states.  Every byte of a row is written once,
pad columns included, so ``dosage`` is minted uninitialised.  The framing,
where no starts are handed in, is ``hbam_bcf_chase`` — the chase the span
readers make (``split/vcf_planners.py::_chase_frames``).

**What stays as the oracle, and as the path of a host without the
library**: the NumPy decode below, the statement of the semantics the
native pass is pinned to byte for byte (tests/test_bcf_native_walk.py):

* record framing is one cheap cursor walk over the ``l_shared``/
  ``l_indiv`` length prefixes (or arrives precomputed from the span
  reader, which walks the same prefixes anyway to find the span end);
* the 24-byte fixed shared prefix of every record is one [n, 24]
  gather, so CHROM/POS/rlen/QUAL/n_info/n_allele/n_sample/n_fmt fall
  out as NumPy views;
* the variable typed-value region (ID, alleles, FILTER, FORMAT keys
  and descriptors) is decoded by a *lockstep cursor* (``_cursor_walk``):
  one int64 cursor per record advances through the same structural
  position of every record simultaneously, exploiting the
  length-prefixed typed-value encoding [SPEC BCF2.2] — each structural
  step is O(1) NumPy ops over all records instead of O(records) Python
  iterations.  The number of steps is max(n_allele) + max(n_fmt) + 3,
  which real call sets keep tiny (biallelic + GT:AD:DP-ish) — but each
  step is a handful of small NumPy calls that give the interpreter lock
  up and take it back, ~100 a span: with 32 pool threads decoding at
  once that, not the arithmetic, was the first half of every BCF scan
  (PERF.md section 6, PR 39);
* INFO is never touched: the shared-block length prefix lets the
  cursor jump straight to the per-sample block;
* GT payloads are reduced to the ALT-dosage matrix per (width, ploidy,
  n_sample) layout group by ``_gt_group_dosage`` (a 2-D byte gather +
  view a slab, then a walk of the ploidy axis).  The semantics are
  exactly those of ``formats/bcf.scan_variant_columns`` /
  ``VariantBatch.dosage_matrix``.

A count of work on a CPU (one thread, one 3,268-record span at 2,504
samples; not a speed): the NumPy walk + gather 35–45 ms a span (the
gather 30-45 us a record before PR 31), the native call 3.4–4.7 ms,
1.0–1.4 us a record.

Eligibility: pathological geometry that would make the lockstep rounds
degenerate (thousands of alleles or FORMAT fields per record, absurd
GT ploidy, more samples than the tile) returns None via
``decode_bcf_columns`` — the native pass declines exactly what the NumPy
walk declines — and the caller falls back to the record-serial scanner,
which handles anything.  Corruption — truncated records, undefined
typed-value codes, overrunning vectors — raises ``BCFError`` loudly on
EVERY path (the native pass's return code names the check and the
record); no path mis-decodes silently (tests/test_bcf_columns.py and
tests/test_bcf_native_walk.py fuzz this).

Reference-side equivalent: htsjdk ``BCF2Codec`` as driven by
hb/BCFRecordReader.java (SURVEY.md section 2.3); the columnar design is
the TPU-shaped replacement for its per-record object assembly, the same
move ``cram_columns.py`` made for the CRAM slice decode.
"""
from __future__ import annotations

import struct
from typing import Dict, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.formats.bcf import (
    BCFError, FLOAT_MISSING_BITS, T_CHAR, T_FLOAT, T_INT8, T_INT16,
    T_INT32, T_MISSING, _INT_EOV, _INT_MISSING,
)
from hadoop_bam_tpu.formats.vcf import VCFHeader
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS

# FLAG bits shared with parallel/variant_pipeline.py
FLAG_PASS = 1
FLAG_SNP = 2

# the stats/tensor tile schema (what the device feed ships)
STAT_KEYS = ("chrom", "pos", "flags", "dosage")

# element byte width per typed-value type code [SPEC BCF2.2 6.3.3];
# -1 marks the reserved codes — hitting one is corruption, not data
_ELEM_SIZE = np.full(16, -1, np.int64)
for _t, _w in ((T_MISSING, 0), (T_INT8, 1), (T_INT16, 2), (T_INT32, 4),
               (T_FLOAT, 4), (T_CHAR, 1)):
    _ELEM_SIZE[_t] = _w

_INT_TYPES = (T_INT8, T_INT16, T_INT32)
_GT_DTYPES = {T_INT8: np.dtype("i1"), T_INT16: np.dtype("<i2"),
              T_INT32: np.dtype("<i4")}
_SNP_BASE_VALS = np.frombuffer(b"ACGTN", np.uint8)

# lockstep-round guards: past these the vectorized passes degenerate
# into as many rounds as a scalar loop — fall back to the record scan
_MAX_ALLELE_ROUNDS = 512
_MAX_FMT_ROUNDS = 64
_MAX_GT_PLOIDY = 256


class _Ineligible(Exception):
    """Span cannot take the columnar path; caller falls back."""


def frame_record_starts(buf: bytes) -> np.ndarray:
    """Start offset of every record in concatenated BCF record bytes.

    One add-chase over the ``l_shared``/``l_indiv`` prefixes — the only
    sequentially dependent step of the columnar decode (span readers
    that walk records anyway hand their starts in instead).  Raises
    ``BCFError`` if the final record overruns or trailing bytes remain.
    """
    n = len(buf)
    if native.available():
        # the same chase the span readers make, in native code
        starts, p, _need = native.bcf_chase(np.frombuffer(buf, np.uint8),
                                            0, n)
    else:
        starts = []
        unpack = struct.Struct("<II").unpack_from
        p = 0
        while p + 8 <= n:
            starts.append(p)
            l_shared, l_indiv = unpack(buf, p)
            p += 8 + l_shared + l_indiv
    if p != n:
        raise BCFError("truncated BCF record in columnar frame")
    return np.asarray(starts, np.int64)


def stat_columns(cols: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Subset a full column dict to the device-tile schema (STAT_KEYS)."""
    return {k: cols[k] for k in STAT_KEYS}


def decode_bcf_columns(buf: bytes, header: VCFHeader, samples_pad: int,
                       starts: Optional[np.ndarray] = None
                       ) -> Optional[Dict[str, np.ndarray]]:
    """All records in ``buf`` -> typed columns, or None when only the
    record-serial path should decode them (pathological geometry).

    Returns {chrom i32, pos i32 (1-based), rlen i32, qual f32 (NaN =
    missing), n_allele i16, n_fmt i16, flags u8 (bit0 PASS, bit1 SNP),
    dosage i8 [n, samples_pad]}.  ``STAT_KEYS`` columns are equal to
    ``formats/bcf.scan_variant_columns`` output and the extended columns
    to the ``VariantBatch`` view of ``BCFRecordCodec.decode`` —
    tests/test_bcf_columns.py pins both.  Corrupt input raises
    ``BCFError``; it is never decoded loosely.
    """
    try:
        return _decode_columns(buf, header, samples_pad, starts)
    except _Ineligible:
        return None


# ---------------------------------------------------------------------------
# lockstep typed-value primitives
# ---------------------------------------------------------------------------

def _gather_u32(b: np.ndarray, off: np.ndarray) -> np.ndarray:
    """Little-endian u32 at each offset (offsets must be in bounds)."""
    r = b[off[:, None] + np.arange(4)].astype(np.uint32)
    return r[:, 0] | r[:, 1] << 8 | r[:, 2] << 16 | r[:, 3] << 24


def _gather_ints(b: np.ndarray, off: np.ndarray, typ: np.ndarray,
                 mask: np.ndarray) -> np.ndarray:
    """Sign-extended typed int (width per-row from ``typ``) at ``off``
    for rows where ``mask``; other rows read clamped junk and return 0.
    Callers bounds-check masked rows beforehand."""
    idx = np.minimum(off[:, None] + np.arange(4), b.size - 1)
    r = b[idx].astype(np.int64)
    u = r[:, 0] | r[:, 1] << 8 | r[:, 2] << 16 | r[:, 3] << 24
    sx8 = ((u & 0xFF) ^ 0x80) - 0x80
    sx16 = ((u & 0xFFFF) ^ 0x8000) - 0x8000
    sx32 = ((u & 0xFFFFFFFF) ^ 0x80000000) - 0x80000000
    out = np.where(typ == T_INT8, sx8,
                   np.where(typ == T_INT16, sx16, sx32))
    return np.where(mask, out, 0)


def _elem_size(typ: np.ndarray, active: np.ndarray) -> np.ndarray:
    es = _ELEM_SIZE[typ]
    if bool((active & (es < 0)).any()):
        bad = int(typ[active & (es < 0)][0])
        raise BCFError(f"unknown typed-value type {bad}")
    return np.where(active, es, 0)


def _read_descriptor(b: np.ndarray, q: np.ndarray, active: np.ndarray,
                     rec_end: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Lockstep read of one typed-value descriptor: (count, typ,
    cursor-after-header) for rows where ``active`` (inactive rows pass
    through with count 0 / type MISSING / unchanged cursor)."""
    if bool((active & (q >= rec_end)).any()):
        raise BCFError("typed-value descriptor overruns record")
    safe = np.where(active, q, 0)
    desc = b[safe].astype(np.int64)
    count = desc >> 4
    typ = desc & 0x0F
    hdr = np.ones_like(q)
    ext = active & (count == 15)
    if bool(ext.any()):
        # real count follows as a typed scalar int [SPEC]
        q2 = safe + 1
        if bool((ext & (q2 >= rec_end)).any()):
            raise BCFError("extended count overruns record")
        d2 = b[np.where(ext, q2, 0)].astype(np.int64)
        etyp = d2 & 0x0F
        ecnt = d2 >> 4
        if bool((ext & ((ecnt != 1) | ~np.isin(etyp, _INT_TYPES))).any()):
            raise BCFError("malformed extended-count scalar")
        esize = np.where(ext, _ELEM_SIZE[etyp], 0)
        if bool((ext & (q2 + 1 + esize > rec_end)).any()):
            raise BCFError("extended count overruns record")
        val = _gather_ints(b, q2 + 1, etyp, ext)
        if bool((ext & (val < 0)).any()):
            raise BCFError("negative typed-value count")
        count = np.where(ext, val, count)
        hdr = np.where(ext, 2 + esize, hdr)
    count = np.where(active, count, 0)
    typ = np.where(active, typ, T_MISSING)
    return count, typ, q + np.where(active, hdr, 0)


def _skip_typed(b: np.ndarray, q: np.ndarray, active: np.ndarray,
                rec_end: np.ndarray) -> np.ndarray:
    count, typ, q2 = _read_descriptor(b, q, active, rec_end)
    q3 = q2 + _elem_size(typ, active) * count
    if bool((active & (q3 > rec_end)).any()):
        raise BCFError("typed value overruns record")
    return q3


# ---------------------------------------------------------------------------
# the decode
# ---------------------------------------------------------------------------

def _empty_columns(samples_pad: int) -> Dict[str, np.ndarray]:
    return {
        "chrom": np.zeros(0, np.int32), "pos": np.zeros(0, np.int32),
        "rlen": np.zeros(0, np.int32), "qual": np.zeros(0, np.float32),
        "n_allele": np.zeros(0, np.int16), "n_fmt": np.zeros(0, np.int16),
        "flags": np.zeros(0, np.uint8),
        "dosage": np.empty((0, samples_pad), np.int8),
    }


def _cursor_walk(b: np.ndarray, header: VCFHeader,
                 starts: np.ndarray) -> Dict[str, np.ndarray]:
    """The sequentially dependent half of the columnar decode: bounds
    checks, the fixed 24-byte prefix views, and the lockstep typed-value
    walk (alleles -> SNP test, FILTER -> PASS, FORMAT -> GT layout).

    ``_decode_columns`` adds the GT->dosage gather.  Raises ``BCFError``
    on corruption and ``_Ineligible`` on pathological geometry."""
    n = starts.size
    if bool((starts < 0).any()) or int(starts.max()) + 32 > b.size:
        raise BCFError("BCF record start out of range")
    l_shared = _gather_u32(b, starts).astype(np.int64)
    l_indiv = _gather_u32(b, starts + 4).astype(np.int64)
    if bool((l_shared < 24).any()):
        raise BCFError("BCF shared block shorter than its fixed fields")
    end_shared = starts + 8 + l_shared
    rec_end = end_shared + l_indiv
    if int(rec_end.max()) > b.size:
        raise BCFError("truncated BCF record in columnar scan")

    # ---- fixed 24-byte shared prefix: one gather, then views ------------
    fixed = b[starts[:, None] + np.arange(8, 32)]
    chrom = fixed[:, 0:4].copy().view("<i4").ravel()
    pos0 = fixed[:, 4:8].copy().view("<i4").ravel()
    rlen = fixed[:, 8:12].copy().view("<i4").ravel()
    qual_bits = fixed[:, 12:16].copy().view("<u4").ravel()
    qual = fixed[:, 12:16].copy().view("<f4").ravel().copy()
    qual[qual_bits == FLOAT_MISSING_BITS] = np.nan
    n_allele = fixed[:, 18:20].copy().view("<u2").ravel().astype(np.int64)
    ns_nf = fixed[:, 20:24].copy().view("<u4").ravel()
    n_sample = (ns_nf & 0xFFFFFF).astype(np.int64)
    n_fmt = (ns_nf >> 24).astype(np.int64)

    max_allele = int(n_allele.max(initial=0))
    max_fmt = int(n_fmt.max(initial=0))
    if max_allele > _MAX_ALLELE_ROUNDS or max_fmt > _MAX_FMT_ROUNDS:
        raise _Ineligible("lockstep round count too large")

    all_rows = np.ones(n, bool)
    q = _skip_typed(b, starts + 32, all_rows, rec_end)      # ID

    # ---- alleles: SNP test in max(n_allele) lockstep rounds -------------
    snp = n_allele >= 2
    for k in range(max_allele):
        active = n_allele > k
        count, typ, q2 = _read_descriptor(b, q, active, rec_end)
        if bool((active & (typ != T_CHAR)).any()):
            raise BCFError("allele is not a char vector")
        q3 = q2 + count
        if bool((active & (q3 > rec_end)).any()):
            raise BCFError("allele overruns record")
        # REF (k == 0) only needs length 1; ALTs must also be bases
        # (matches VariantBatch.is_snp / scan_variant_columns)
        ok = active & (count == 1)
        if k > 0:
            base = b[np.where(ok, q2, 0)]
            ok &= np.isin(base, _SNP_BASE_VALS)
        snp &= ~active | ok
        q = q3

    # ---- FILTER: PASS == exactly the one int value 0 --------------------
    count, typ, q2 = _read_descriptor(b, q, all_rows, rec_end)
    es = _elem_size(typ, all_rows)
    if bool((q2 + es * count > rec_end).any()):
        raise BCFError("FILTER vector overruns record")
    int_filter = np.isin(typ, _INT_TYPES)
    one = int_filter & (count == 1)
    fval = _gather_ints(b, q2, typ, one)
    is_pass = one & (fval == 0)

    # ---- per-sample block (INFO is jumped over wholesale) ---------------
    gt_key = _gt_key(header)
    q = end_shared
    gt_typ = np.zeros(n, np.int64)          # 0 = no GT seen
    gt_count = np.zeros(n, np.int64)
    gt_off = np.zeros(n, np.int64)
    for _j in range(max_fmt):
        # n_fmt overruns are tolerated exactly like the record path:
        # the walk stops at the block end, it does not raise
        active = (n_fmt > _j) & (q < rec_end)
        if not bool(active.any()):
            break
        kcnt, ktyp, q2 = _read_descriptor(b, q, active, rec_end)
        if bool((active & (~np.isin(ktyp, _INT_TYPES) | (kcnt != 1))).any()):
            raise BCFError("malformed FORMAT key")
        if bool((active & (q2 + _elem_size(ktyp, active) > rec_end)).any()):
            raise BCFError("FORMAT key overruns record")
        key = _gather_ints(b, q2, ktyp, active)
        q3 = q2 + _elem_size(ktyp, active) * kcnt
        fcnt, ftyp, q4 = _read_descriptor(b, q3, active, rec_end)
        data_len = _elem_size(ftyp, active) * fcnt * n_sample
        if bool((active & (q4 + data_len > rec_end)).any()):
            raise BCFError("FORMAT data overruns record")
        is_gt = (active & (key == gt_key) & np.isin(ftyp, _INT_TYPES)
                 & (n_sample > 0)) if gt_key >= 0 else np.zeros(n, bool)
        if bool(is_gt.any()):
            gt_typ[is_gt] = ftyp[is_gt]
            gt_count[is_gt] = fcnt[is_gt]
            gt_off[is_gt] = q4[is_gt]
        q = q4 + data_len

    return {
        "chrom": chrom, "pos0": pos0, "rlen": rlen, "qual": qual,
        "n_allele": n_allele, "n_fmt": n_fmt, "n_sample": n_sample,
        "snp": snp, "is_pass": is_pass,
        "gt_typ": gt_typ, "gt_count": gt_count, "gt_off": gt_off,
    }


# GT values gathered at a time: the gather's index matrix and the widened
# genotypes are int64, so a whole span of a cohort-wide file at once was
# gigabytes of temporaries a pool thread (2,504 samples x 26,000 records:
# 1 GB each); a slab keeps them near the cache whatever the span holds
_GT_SLAB_VALUES = 1 << 20


def _gt_group_dosage(b: np.ndarray, rows: np.ndarray, offs: np.ndarray,
                     typ_g: int, cnt: int, ns: int,
                     dosage: np.ndarray) -> None:
    """ALT dosage of the records ``rows`` (one GT layout: type ``typ_g``,
    ploidy ``cnt``, ``ns`` samples; payloads at ``offs``) written into
    their ``dosage`` rows.  The NumPy twin of ``native.bcf_gt_dosage``:
    the oracle it is tested against, and the path of a host without the
    native library."""
    dt = _GT_DTYPES[typ_g]
    span = np.arange(dt.itemsize * cnt * ns)
    slab = max(1, _GT_SLAB_VALUES // max(1, cnt * ns))
    for lo in range(0, rows.size, slab):
        part = rows[lo:lo + slab]
        raw = b[offs[lo:lo + slab, None] + span]
        # the genotypes stay in their own width (int8 for every cohort
        # with under 64 alleles a site; nothing below leaves its range),
        # and the ploidy axis is walked, not reduced: NumPy's reductions
        # over an axis of 2 were four fifths of a slab's time
        g = raw.view(dt).reshape(part.size, ns, cnt)
        any_present = np.zeros((part.size, ns), bool)
        any_missing = np.zeros((part.size, ns), bool)
        n_alt = np.zeros((part.size, ns), np.int16)
        for k in range(cnt):
            gk = g[:, :, k]
            present = gk != _INT_EOV[typ_g]         # pre-EOV entries
            # allele index = (g >> 1) - 1; masking the phase bit is
            # required: a phased missing allele ('0|.') encodes as 1
            allele = gk >> 1
            any_present |= present
            any_missing |= present & ((allele == 0)
                                      | (gk == _INT_MISSING[typ_g]))
            n_alt += present & (allele > 1)
        d = np.where(any_present & ~any_missing, n_alt, -1)
        dosage[part, :ns] = np.minimum(d, 127).astype(np.int8)


def _gt_key(header: VCFHeader) -> int:
    """"GT" in the header's string dictionary, or -1."""
    try:
        return header.string_dictionary().index("GT")
    except ValueError:
        return -1


def _decode_columns_native(b: np.ndarray, header: VCFHeader,
                           samples_pad: int, starts: np.ndarray
                           ) -> Dict[str, np.ndarray]:
    """The walk and the GT -> dosage of a framed span as ONE native call
    (``native/hbam_native.cpp::hbam_bcf_span_columns``), the interpreter
    lock released: what ``_cursor_walk`` + ``_gt_group_dosage`` compute,
    byte for byte, with their checks and their two refusals."""
    with METRICS.span("vcf.gt_dosage_wall"):
        got = native.bcf_span_columns(
            b, starts, _gt_key(header), samples_pad, _MAX_ALLELE_ROUNDS,
            _MAX_FMT_ROUNDS, _MAX_GT_PLOIDY)
    if got is None:
        raise _Ineligible("geometry the native walk declines")
    cols, n_gt = got
    METRICS.count("vcf.walk_native_records", int(starts.size))
    if n_gt:
        METRICS.count("vcf.gt_native_records", n_gt)
    return cols


def _decode_columns(buf: bytes, header: VCFHeader, samples_pad: int,
                    starts: Optional[np.ndarray]) -> Dict[str, np.ndarray]:
    b = np.frombuffer(buf, np.uint8)
    if starts is None:
        starts = frame_record_starts(buf)
    starts = np.asarray(starts, np.int64)
    n = starts.size
    if n == 0:
        return _empty_columns(samples_pad)
    if native.available():
        return _decode_columns_native(b, header, samples_pad, starts)
    METRICS.count("vcf.walk_numpy_records", n)
    wk = _cursor_walk(b, header, starts)
    chrom, pos0, rlen, qual = (wk["chrom"], wk["pos0"], wk["rlen"],
                               wk["qual"])
    n_allele, n_fmt, n_sample = (wk["n_allele"], wk["n_fmt"],
                                 wk["n_sample"])
    snp, is_pass = wk["snp"], wk["is_pass"]
    gt_typ, gt_count, gt_off = (wk["gt_typ"], wk["gt_count"],
                                wk["gt_off"])

    # ---- GT -> dosage, gathered per (width, ploidy, n_sample) group -----
    dosage = np.full((n, samples_pad), -1, np.int8)
    have = gt_typ > 0
    if bool((have & (gt_count > _MAX_GT_PLOIDY)).any()):
        raise _Ineligible("GT ploidy too large")
    if bool((have & (n_sample > samples_pad)).any()):
        raise _Ineligible("record carries more samples than the tile")
    if bool(have.any()):
        with METRICS.span("vcf.gt_dosage_wall"):
            combo = (gt_typ << 48) | (gt_count << 24) | n_sample
            for c in np.unique(combo[have]):
                rows = np.flatnonzero(have & (combo == c))
                r0 = rows[0]
                _gt_group_dosage(b, rows, gt_off[rows], int(gt_typ[r0]),
                                 int(gt_count[r0]), int(n_sample[r0]),
                                 dosage)
        METRICS.count("vcf.gt_numpy_records", int(have.sum()))

    return {
        "chrom": chrom.astype(np.int32),
        "pos": (pos0 + 1).astype(np.int32),
        "rlen": rlen.astype(np.int32),
        "qual": qual.astype(np.float32),
        "n_allele": n_allele.astype(np.int16),
        "n_fmt": n_fmt.astype(np.int16),
        "flags": (is_pass.astype(np.uint8) * FLAG_PASS
                  | snp.astype(np.uint8) * FLAG_SNP),
        "dosage": dosage,
    }
