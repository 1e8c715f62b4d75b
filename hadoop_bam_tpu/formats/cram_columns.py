"""Vectorized (columnar) CRAM slice decode: arrays out, no record objects.

The stats/tensor path needs columns — flags, positions, lengths, one
concatenated seq/qual byte run — not ``CramRecord`` objects.  This module
decodes a whole slice into exactly those columns with NumPy batch ops:

* every fixed int series arrives predecoded by
  ``cram_decode._predecode_fixed`` (native batch ITF8);
* the payload series (QS/BA/BS, the BB/QQ/IN/SC arrays, DL/RS/PD/HC)
  are consumed by *computed offsets* instead of sequential cursors: the
  byte order of each EXTERNAL stream is a pure function of the predecoded
  BF/CF/RL/FN/FC columns, so one pass of cumsums yields every record's
  slice of every stream;
* seq/qual reconstruction (gap fill from the reference, substitutions,
  feature and quality overlays) is ONE native call a slice with the
  interpreter lock released (``native/hbam_native.cpp::
  hbam_cram_slice_rebuild``: ``cram_decode._decode_mapped``'s loop over the
  whole slice), with NumPy scatter/gather over flat base arrays
  (``_rebuild_numpy``) as its oracle and the path of a multi-reference
  slice; ``cram.walk_native_records`` / ``cram.walk_numpy_records`` count
  the slices' reads by path.

Eligibility mirrors the htslib-default layout the predecode already
requires (external or constant series, exclusive content ids, core block
unused).  Anything else — shared blocks, core-bit codecs, malformed
geometry (overlapping features, out-of-range positions) — returns None
and the caller falls back to the record-serial path, which reproduces
the exact reference error behavior.  Parity between both paths is pinned
by tests/test_cram_columns.py.

Reference-side equivalent: the htsjdk CRAM slice decode reached from
hb/CRAMInputFormat.java (SURVEY.md section 2.3); the columnar design is
the TPU-shaped replacement for its per-record object assembly.
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, List, Optional

import numpy as np

from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.formats.cram_decode import (
    ByteArrayLenEncoding, ByteArrayStopEncoding, CF_DETACHED,
    CF_QUAL_STORED, CF_UNKNOWN_BASES, CompressionHeader, CRAMError,
    ExternalEncoding, HuffmanEncoding, NullEncoding, ReferenceSource,
    SliceHeader, _EmbeddedReference, _predecode_fixed, _BASES,
)

_ARRAY_FEATURE_SERIES = {0x62: "BB", 0x71: "QQ", 0x49: "IN", 0x53: "SC"}
_INT_FEATURE_SERIES = {0x44: "DL", 0x4E: "RS", 0x50: "PD", 0x48: "HC"}
_KNOWN_CODES = (frozenset(_ARRAY_FEATURE_SERIES)
                | frozenset(_INT_FEATURE_SERIES)
                | frozenset(b"XBiQ"))
# 1 at every byte that is no feature code
_UNKNOWN_CODE = np.ones(256, np.int64)
_UNKNOWN_CODE[sorted(_KNOWN_CODES)] = 0

# read-consuming codes and their length source: arrays consume len(val),
# X/B/i consume 1, everything else consumes 0 read bases
_ONE_BASE_CODES = frozenset(b"XBi")


# the data series the columnar decoder reads (never RN, the tags, TL or
# the detached-mate MF / NS / NP / TS)
COLUMNAR_SERIES = ("BF", "CF", "RI", "RL", "AP", "RG", "NF", "MQ", "FN",
                   "FC", "FP", "DL", "RS", "PD", "HC", "BA", "QS", "BS",
                   "BB", "QQ", "IN", "SC")


def columnar_cids(comp: CompressionHeader) -> set:
    """The content ids of the blocks the columnar decoder reads."""
    from hadoop_bam_tpu.formats.cram_decode import _encoding_cids
    return {cid for name in COLUMNAR_SERIES
            if name in comp.data_series
            for cid in _encoding_cids(comp.data_series[name])}


_SCRATCH = threading.local()


def _scratch_bases(n: int) -> np.ndarray:
    """n bytes of this thread's reused base buffer (its pages touched
    once, not every slice): the ``as_arrays`` caller packs a slice's
    bases before it decodes the next."""
    buf = getattr(_SCRATCH, "bases", None)
    if buf is None or buf.size < n:
        buf = _SCRATCH.bases = np.empty(max(n, 1 << 21), np.uint8)
    return buf[:n]


class _Ineligible(Exception):
    """Slice cannot take the columnar path; caller falls back."""


def _core_free(enc) -> bool:
    if isinstance(enc, (ExternalEncoding, ByteArrayStopEncoding,
                        NullEncoding)):
        return True
    if isinstance(enc, HuffmanEncoding):
        return enc._const is not None        # 0-bit constant reads no core
    if isinstance(enc, ByteArrayLenEncoding):
        return (_core_free(enc.len_encoding)
                and _core_free(enc.val_encoding))
    return False


def _ragged_targets(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenated [start_i, start_i+len_i) index runs (the scatter and
    gather workhorse for every ragged copy below).  Built with one
    cumsum over the output instead of repeat+arange temporaries: the
    output is +1 steps everywhere except at run boundaries, where it
    jumps to the next start."""
    lens = lens.astype(np.int64)
    nz = lens > 0
    if not bool(nz.any()):
        return np.empty(0, np.int64)
    starts = starts.astype(np.int64)[nz]
    lens = lens[nz]
    total = int(lens.sum())
    out = np.ones(total, np.int64)
    firsts = np.cumsum(lens) - lens
    out[0] = starts[0]
    if starts.size > 1:
        out[firsts[1:]] = starts[1:] - (starts[:-1] + lens[:-1]) + 1
    return np.cumsum(out)


def _ragged_copy(dst: np.ndarray, starts: np.ndarray, lens: np.ndarray,
                 vals: np.ndarray) -> None:
    """dst[start_i:start_i+len_i] = next len_i vals, in run order — with a
    straight memcpy when the runs tile dst contiguously in order (the
    overwhelmingly common slice layout)."""
    lens = lens.astype(np.int64)
    ecs = np.cumsum(lens) - lens
    if vals.size == dst.size and np.array_equal(starts, ecs):
        dst[:] = vals
        return
    dst[_ragged_targets(starts, lens)] = vals


def _ragged_gather(src: np.ndarray, starts: np.ndarray, lens: np.ndarray
                   ) -> np.ndarray:
    """Concatenation of src[start_i:start_i+len_i] runs, with a zero-copy
    slice when the runs are contiguous in order from offset 0."""
    lens = lens.astype(np.int64)
    total = int(lens.sum())
    ecs = np.cumsum(lens) - lens
    if np.array_equal(starts, ecs):
        return src[:total]
    return src[_ragged_targets(starts, lens)]


def _seg_exclusive_cumsum(vals: np.ndarray, seg_firsts: np.ndarray,
                          seg_lens: np.ndarray) -> np.ndarray:
    """Per-segment exclusive cumsum of ``vals`` (segments given by their
    first flat index and length, covering vals exactly, in order)."""
    ecs = np.cumsum(vals, dtype=np.int64) - vals
    if ecs.size == 0:
        return ecs
    base = ecs[seg_firsts]
    return ecs - np.repeat(base, seg_lens)


class _Bulk:
    """Computed-offset access to one slice's EXTERNAL payload streams."""

    def __init__(self, comp: CompressionHeader, external: Dict[int, bytes],
                 cid_users: Dict[int, int]):
        self.comp = comp
        self.external = external
        self.cid_users = cid_users

    def _exclusive_block(self, enc: ExternalEncoding) -> bytes:
        cid = enc.content_id
        if self.cid_users.get(cid, 0) != 1 or cid not in self.external:
            raise _Ineligible(f"content id {cid} shared or missing")
        return self.external[cid]

    def _series(self, name: str):
        enc = self.comp.data_series.get(name)
        if enc is None:
            raise _Ineligible(f"series {name} absent")
        return enc

    def ints(self, name: str, count: int) -> np.ndarray:
        """count ITF8 ints of one series, in stream order."""
        if count == 0:
            return np.zeros(0, np.int64)
        enc = self._series(name)
        if isinstance(enc, HuffmanEncoding) and enc._const is not None:
            return np.full(count, enc._const, np.int64)
        if isinstance(enc, ExternalEncoding):
            from hadoop_bam_tpu.utils import native
            if not native.available():
                raise _Ineligible("native ITF8 batch decoder unavailable")
            block = self._exclusive_block(enc)
            try:
                vals, _ = native.itf8_decode_batch(
                    np.frombuffer(block, np.uint8), count)
            except ValueError:
                raise _Ineligible("ITF8 stream truncated")
            return vals.astype(np.int64)
        raise _Ineligible(f"series {name}: unsupported encoding")

    def raw(self, name: str, count: int) -> np.ndarray:
        """count single raw bytes of one series (the decode_byte contract)."""
        if count == 0:
            return np.zeros(0, np.uint8)
        enc = self._series(name)
        if isinstance(enc, HuffmanEncoding) and enc._const is not None:
            return np.full(count, enc._const & 0xFF, np.uint8)
        if isinstance(enc, ExternalEncoding):
            block = self._exclusive_block(enc)
            if len(block) < count:
                raise _Ineligible("byte stream truncated")
            return np.frombuffer(block, np.uint8, count)
        raise _Ineligible(f"series {name}: unsupported encoding")

    def stream(self, name: str, total: int) -> np.ndarray:
        """The series' whole byte stream, of which ``total`` bytes will be
        consumed at computed offsets."""
        enc = self._series(name)
        if not isinstance(enc, ExternalEncoding):
            raise _Ineligible(f"series {name}: not a plain external stream")
        block = self._exclusive_block(enc)
        if len(block) < total:
            raise _Ineligible("byte stream truncated")
        return np.frombuffer(block, np.uint8)

    def whole(self, name: str, count: int = 0) -> Optional[np.ndarray]:
        """The series' whole EXTERNAL block — or, given ``count``, a
        constant's ``count`` bytes, as ``raw`` reads them — for a consumer
        that checks what it reads against the length itself; None where
        the series cannot be read at computed offsets (absent, another
        codec, a content id it shares)."""
        enc = self.comp.data_series.get(name)
        if count and isinstance(enc, HuffmanEncoding) \
                and enc._const is not None:
            return np.full(count, enc._const & 0xFF, np.uint8)
        if not isinstance(enc, ExternalEncoding):
            return None
        cid = enc.content_id
        if self.cid_users.get(cid, 0) != 1 or cid not in self.external:
            return None
        return np.frombuffer(self.external[cid], np.uint8)

    def arrays(self, name: str, count: int):
        """(lens int64[count], vals uint8[sum lens]) of one byte-array
        series, in stream order."""
        if count == 0:
            return np.zeros(0, np.int64), np.zeros(0, np.uint8)
        enc = self._series(name)
        if isinstance(enc, ByteArrayLenEncoding):
            le, ve = enc.len_encoding, enc.val_encoding
            if isinstance(le, HuffmanEncoding) and le._const is not None:
                lens = np.full(count, le._const, np.int64)
            elif isinstance(le, ExternalEncoding):
                from hadoop_bam_tpu.utils import native
                if not native.available():
                    raise _Ineligible("native ITF8 decoder unavailable")
                try:
                    vals32, _ = native.itf8_decode_batch(
                        np.frombuffer(self._exclusive_block(le), np.uint8),
                        count)
                except ValueError:
                    raise _Ineligible("array len stream truncated")
                lens = vals32.astype(np.int64)
            else:
                raise _Ineligible(f"{name}: unsupported len encoding")
            if lens.size and int(lens.min()) < 0:
                raise _Ineligible(f"{name}: negative array length")
            total = int(lens.sum())
            if not isinstance(ve, ExternalEncoding):
                raise _Ineligible(f"{name}: unsupported val encoding")
            block = self._exclusive_block(ve)
            if len(block) < total:
                raise _Ineligible(f"{name}: val stream truncated")
            return lens, np.frombuffer(block, np.uint8, total)
        if isinstance(enc, ByteArrayStopEncoding):
            block = self._exclusive_block(enc)
            stops = np.flatnonzero(
                np.frombuffer(block, np.uint8) == enc.stop)
            if stops.size < count:
                raise _Ineligible(f"{name}: stop byte not found")
            ends = stops[:count]
            starts = np.concatenate(([0], ends[:-1] + 1))
            lens = (ends - starts).astype(np.int64)
            arr = np.frombuffer(block, np.uint8)
            vals = arr[_ragged_targets(starts, lens)]
            return lens, vals
        raise _Ineligible(f"{name}: unsupported array encoding")


def decode_slice_columns(comp: CompressionHeader, slice_hdr: SliceHeader,
                         core: bytes, external: Dict[int, bytes],
                         ref_names: List[str],
                         ref_source: Optional[ReferenceSource] = None,
                         want_names: bool = False,
                         codec_rec_lens=None,
                         as_arrays: bool = False) -> Optional[dict]:
    """One slice as columns, or None when only the record path can decode it.

    Returns {n, bf, cf, ref_id, rl, pos, mapq, read_group, seq_cat,
    seq_lens, qual_cat, qual_lens[, name_cat, name_lens]}: int arrays are
    per-record; seq/qual are concatenated per-record byte runs whose
    lengths are ``seq_lens``/``qual_lens`` (0 encodes "*").  Output is
    byte-identical to assembling the same columns from
    ``decode_slice_records`` — tests/test_cram_columns.py pins this.
    ``as_arrays`` hands ``seq_cat`` / ``qual_cat`` over as the uint8
    arrays they were built in, not as copies in ``bytes``: views of this
    thread's scratch buffer and of the QS block, to be consumed before
    the thread decodes its next slice.
    """
    try:
        return _decode_columns(comp, slice_hdr, core, external, ref_names,
                               ref_source, want_names, codec_rec_lens,
                               as_arrays)
    except _Ineligible:
        return None


def _decode_columns(comp, slice_hdr, core, external, ref_names, ref_source,
                    want_names, codec_rec_lens=None, as_arrays=False):
    pre = _predecode_fixed(comp, slice_hdr, external, record_fields=False)
    if pre is None:
        raise _Ineligible("fixed series not batch-decodable")
    n = slice_hdr.n_records

    if slice_hdr.embedded_ref_id >= 0 and ref_source is None:
        ref_source = _EmbeddedReference(
            external[slice_hdr.embedded_ref_id], slice_hdr.start)

    # skipped series (names unless wanted, all tags) and decoded payload
    # series must never touch the CORE bit stream: only then is skipping
    # or offset-computed consumption equivalent to cursor consumption
    for key, enc in comp.tag_encodings.items():
        if not _core_free(enc):
            raise _Ineligible("tag encoding reads core bits")
    rn = comp.data_series.get("RN")
    if rn is not None and not _core_free(rn):
        raise _Ineligible("RN reads core bits")
    for name in ("QS", "BA", "BS", "BB", "QQ", "IN", "SC"):
        enc = comp.data_series.get(name)
        if enc is not None and not _core_free(enc):
            raise _Ineligible(f"{name} reads core bits")

    bf, cf = pre["BF"].astype(np.int64), pre["CF"].astype(np.int64)
    rl = pre["RL"].astype(np.int64)
    if rl.size and int(rl.min()) < 0:
        raise _Ineligible("negative read length")
    pos = pre["POS"].astype(np.int64)
    rg = pre["RG"].astype(np.int64)
    ri = pre.get("RI")
    ref_id = (ri.astype(np.int64) if ri is not None
              else np.full(n, slice_hdr.ref_seq_id, np.int64))

    total_fn = int(pre["FN"].sum())
    if total_fn and "FC" not in pre:
        raise _Ineligible("feature streams not batch-decodable")
    fc = (pre["FC"].astype(np.uint8) if total_fn
          else np.zeros(0, np.uint8))
    fp = pre["FP"] if total_fn else np.zeros(0, np.int32)
    counts = np.bincount(fc, minlength=256)
    unknown = np.flatnonzero(counts * _UNKNOWN_CODE)
    if unknown.size:
        raise CRAMError(f"unknown feature code {chr(int(unknown[0]))!r}")

    bulk = _Bulk(comp, external, _cid_user_counts(comp))
    arrays = {code: bulk.arrays(series, int(counts[code]))
              for code, series in _ARRAY_FEATURE_SERIES.items()}
    int_vals = {}
    for code, series in _INT_FEATURE_SERIES.items():
        int_vals[code] = bulk.ints(series, int(counts[code]))
        if code in (0x44, 0x4E) and int_vals[code].size \
                and int(int_vals[code].min()) < 0:
            raise _Ineligible("negative deletion/skip length")

    # one native call rebuilds a slice of one reference; the NumPy twin a
    # multi-reference slice, and any slice whose arguments the call refuses
    rebuilt = None
    if slice_hdr.ref_seq_id != -2:
        rebuilt = _rebuild_native(
            comp, slice_hdr, pre, bf, cf, rl, fc, fp, counts, arrays,
            int_vals, bulk, ref_names, ref_source, codec_rec_lens, as_arrays)
    native_walk = rebuilt is not None
    if not native_walk:
        rebuilt = _rebuild_numpy(
            comp, slice_hdr, pre, bf, cf, rl, pos, ref_id, fc, fp, arrays,
            int_vals, bulk, ref_names, ref_source, codec_rec_lens, as_arrays)
    seq_flat, seq_lens, qual_flat, qual_lens, mapq = rebuilt

    out = {
        "n": n, "bf": bf, "cf": cf, "ref_id": ref_id, "rl": rl,
        "pos": pos, "mapq": mapq, "read_group": rg,
        "seq_cat": seq_flat if as_arrays else seq_flat.tobytes(),
        "seq_lens": seq_lens,
        "qual_cat": qual_flat if as_arrays else qual_flat.tobytes(),
        "qual_lens": qual_lens,
    }
    if want_names:
        out.update(_decode_names(comp, bulk, n, cf))
    METRICS.count("cram.walk_native_records" if native_walk
                  else "cram.walk_numpy_records", n)
    return out


def _rebuild_native(comp, slice_hdr, pre, bf, cf, rl, fc, fp, counts, arrays,
                    int_vals, bulk, ref_names, ref_source, codec_rec_lens,
                    as_arrays):
    """(seq_flat, seq_lens, qual_flat, qual_lens, mapq) of a slice of one
    reference in one native call with the interpreter lock released
    (``utils/native.py::cram_slice_rebuild``; a second where the slice
    reads the reference: the window is fetched between them), or None
    where the call refuses its arguments.  Sends the slice to the record
    path, or raises, where ``_rebuild_numpy`` does."""
    from hadoop_bam_tpu.utils import native

    stored = (cf & CF_QUAL_STORED) != 0
    n_bq = int(counts[0x42] + counts[0x51])           # 'B', 'Q'
    # a stream is asked for only where the slice reads it, as the twin
    # asks: a block nobody reads stays compressed and counted as skipped
    qs_total = int(rl[stored].sum()) + n_bq
    ba_total = int(rl[(bf & 0x4) != 0].sum()) + int(counts[0x42]
                                                   + counts[0x69])
    n_x = int(counts[0x58])
    streams = [bulk.whole("QS") if qs_total else None,
               bulk.whole("BA") if ba_total else None,
               bulk.whole("BS", n_x) if n_x else None]
    for code in _ARRAY_FEATURE_SERIES:
        streams.extend(arrays[code])
    streams += [int_vals[0x44], int_vals[0x4E]]
    total = int(rl.sum())
    seq_out = (_scratch_bases(total) if as_arrays
               else np.empty(total, np.uint8))
    # with no overlay and no 'B' / 'Q' byte ahead of them, the stored
    # qualities are the QS stream's first bytes as they lie
    overlays = n_bq or counts[0x71]
    qual_out = np.empty(total, np.uint8) if overlays else None
    table = (_substitution_table(bytes(comp.substitution_matrix)) if n_x
             else _NO_SUBSTITUTIONS)

    def rebuild(ref=None, ref_lo=0):
        return native.cram_slice_rebuild(
            pre["BF"], pre["CF"], pre["RL"], pre["POS"], pre["FN"],
            pre["MQ"], fc, fp, streams, table, ref_source is not None, ref,
            ref_lo, seq_out, qual_out)

    with METRICS.span("cram.ref_fill_wall"):
        got = rebuild()
        if got is None:
            return None
        if got[0] == native.CRAM_GEOMETRY:
            raise _Ineligible("features outside their reads or QS short")
        if codec_rec_lens:
            # fqzcomp desync tripwire — shared with the record path
            from hadoop_bam_tpu.formats.cram_decode import check_fqz_rec_lens
            lens = rl[stored]
            check_fqz_rec_lens(comp, codec_rec_lens,
                               [int(v) for v in lens[lens > 0]],
                               qs_feat_bytes=n_bq)
        if got[0] == native.CRAM_NEED_REF:
            lo, hi = int(got[1][0]), int(got[1][1])
            if hi - lo > (1 << 31):
                raise _Ineligible("reference window too large")
            rid = slice_hdr.ref_seq_id
            name = ref_names[rid] if 0 <= rid < len(ref_names) else "*"
            got = rebuild(ref_source.get_bytes(name, lo, hi - lo), lo)
            if got is None:
                return None
    rc, info, seq_lens, qual_lens, mapq = got
    if rc == native.CRAM_BAD_SUBST:
        raise CRAMError("invalid substitution code")
    if rc != native.CRAM_OK:
        raise _Ineligible("BA / BS short, or a read outside the reference")
    seq_flat = seq_out[:int(info[2])]
    if qual_out is not None:
        qual_flat = qual_out[:int(info[3])]
    else:
        qs = streams[0] if streams[0] is not None else np.zeros(0, np.uint8)
        qual_flat = qs[:int(info[3])]
    return seq_flat, seq_lens, qual_flat, qual_lens, mapq


def _rebuild_numpy(comp, slice_hdr, pre, bf, cf, rl, pos, ref_id, fc, fp,
                   arrays, int_vals, bulk, ref_names, ref_source,
                   codec_rec_lens, as_arrays):
    """``_rebuild_native`` with NumPy batch ops: the oracle the native call
    is tested against, and the path of a multi-reference slice."""
    n = slice_hdr.n_records
    mapped = (bf & 0x4) == 0
    mapped_idx = np.flatnonzero(mapped)
    unmapped_idx = np.flatnonzero(~mapped)
    fn = pre["FN"].astype(np.int64)          # per mapped record
    total_fn = int(fn.sum())
    fp = fp.astype(np.int64)

    mapq = np.zeros(n, np.int64)
    if mapped_idx.size:
        mapq[mapped_idx] = pre["MQ"].astype(np.int64)

    # ---- per-feature geometry -------------------------------------------
    rec_of_feat = np.repeat(mapped_idx, fn)          # sorted ascending
    seg_firsts = (np.cumsum(fn) - fn)[fn > 0]
    seg_lens = fn[fn > 0]
    fpos = _seg_exclusive_cumsum(fp, seg_firsts, seg_lens) + fp  # inclusive

    masks = {c: fc == c for c in
             (0x62, 0x71, 0x49, 0x53, 0x58, 0x42, 0x69, 0x51,
              0x44, 0x4E, 0x50, 0x48)}
    arr_lens = {code: arrays[code][0] for code in _ARRAY_FEATURE_SERIES}
    arr_vals = {code: arrays[code][1] for code in _ARRAY_FEATURE_SERIES}

    # read-consumed length of every feature
    read_len = np.zeros(total_fn, np.int64)
    for code in _ARRAY_FEATURE_SERIES:
        if code != 0x71:                    # 'q' consumes no read bases
            read_len[masks[code]] = arr_lens[code]
    for code in _ONE_BASE_CODES:
        read_len[masks[code]] = 1
    # ref-consumed length of every feature
    ref_len = np.zeros(total_fn, np.int64)
    ref_len[masks[0x62]] = arr_lens[0x62]            # 'b'
    ref_len[masks[0x58]] = 1                         # 'X'
    ref_len[masks[0x42]] = 1                         # 'B'
    ref_len[masks[0x44]] = int_vals[0x44]            # 'D'
    ref_len[masks[0x4E]] = int_vals[0x4E]            # 'N'

    # gaps between features (match runs filled from the reference)
    prev_end = np.empty(total_fn, np.int64)
    if total_fn:
        prev_end[0] = 1
        prev_end[1:] = fpos[:-1] + read_len[:-1]
        prev_end[seg_firsts] = 1
    gap = fpos - prev_end
    if total_fn and int(gap.min()) < 0:
        raise _Ineligible("overlapping features")
    rl_mapped = rl[mapped_idx]
    # coverage is contiguous from read position 1 (gaps close the holes),
    # so covered = end of the last feature
    covered = np.zeros(mapped_idx.size, np.int64)
    if total_fn:
        seg_last = seg_firsts + seg_lens - 1
        covered[fn > 0] = fpos[seg_last] + read_len[seg_last] - 1
    tail = rl_mapped - covered
    if tail.size and int(tail.min()) < 0:
        raise _Ineligible("features overrun read length")
    # per-base write positions must stay inside the record
    if total_fn:
        ends = fpos - 1 + np.maximum(read_len, 1)
        if int((ends - np.repeat(rl_mapped, fn)).max(initial=0)) > 0 \
                or int(fpos.min()) < 1:
            raise _Ineligible("feature position outside read")
        qmask = masks[0x71]
        if qmask.any():
            # 'q' writes arr_lens qual bytes from fpos-1
            qends = fpos[qmask] - 1 + arr_lens[0x71]
            if int((qends - np.repeat(rl_mapped, fn)[qmask]).max(
                    initial=0)) > 0:
                raise _Ineligible("qual feature outside read")

    # ---- QS / BA stream layout ------------------------------------------
    qual_stored = (cf & CF_QUAL_STORED) != 0
    qs_feat = masks[0x42] | masks[0x51]              # 'B', 'Q'
    qs_feat_per_rec = np.bincount(rec_of_feat[qs_feat], minlength=n)
    qs_per_rec = qs_feat_per_rec + rl * qual_stored
    qs_rec_start = np.cumsum(qs_per_rec) - qs_per_rec
    qs_total = int(qs_per_rec.sum())
    qs_stream = (bulk.stream("QS", qs_total) if qs_total
                 else np.zeros(0, np.uint8))

    # fqzcomp desync tripwire — shared with the record path
    if codec_rec_lens:
        from hadoop_bam_tpu.formats.cram_decode import check_fqz_rec_lens
        check_fqz_rec_lens(
            comp, codec_rec_lens,
            [int(v) for v in qs_per_rec[qs_per_rec > 0]],
            qs_feat_bytes=int(qs_feat.sum()) if total_fn else 0)

    ba_feat = masks[0x42] | masks[0x69]              # 'B', 'i'
    ba_feat_per_rec = np.bincount(rec_of_feat[ba_feat], minlength=n)
    ba_per_rec = ba_feat_per_rec + rl * ~mapped
    ba_rec_start = np.cumsum(ba_per_rec) - ba_per_rec
    ba_total = int(ba_per_rec.sum())
    ba_stream = (bulk.stream("BA", ba_total) if ba_total
                 else np.zeros(0, np.uint8))

    def _stream_offsets(mask: np.ndarray, rec_start: np.ndarray
                        ) -> np.ndarray:
        """Stream offset of each masked feature: record base + rank among
        this record's masked features (features are already in stream
        order, so rank = index - first index of the record's run)."""
        sub = rec_of_feat[mask]
        if sub.size == 0:
            return np.zeros(0, np.int64)
        rank = np.arange(sub.size, dtype=np.int64) \
            - np.searchsorted(sub, sub, side="left")
        return rec_start[sub] + rank

    qs_feat_off = _stream_offsets(qs_feat, qs_rec_start)
    ba_feat_off = _stream_offsets(ba_feat, ba_rec_start)

    # ---- seq assembly ----------------------------------------------------
    seq_starts = np.cumsum(rl) - rl
    total_bases = int(rl.sum())
    seq_flat = (_scratch_bases(total_bases) if as_arrays
                else np.empty(total_bases, np.uint8))
    seq_flat.fill(ord("?"))

    # unmapped records: BA block verbatim
    if unmapped_idx.size:
        vals = _ragged_gather(ba_stream,
                              ba_rec_start[unmapped_idx]
                              + ba_feat_per_rec[unmapped_idx],
                              rl[unmapped_idx])
        _ragged_copy(seq_flat, seq_starts[unmapped_idx],
                     rl[unmapped_idx], vals)

    # reference fill for gaps/tails + 'X' substitution bases
    unknown_bases = (cf & CF_UNKNOWN_BASES) != 0
    with METRICS.span("cram.ref_fill_wall"):
        _fill_reference(
            seq_flat, seq_starts, comp, slice_hdr, ref_names, ref_source,
            mapped_idx, rl_mapped, pos, ref_id, unknown_bases,
            fn, seg_firsts, seg_lens, rec_of_feat, fpos, gap, read_len,
            ref_len, tail, masks, bulk)

    # feature payload overlay (after ref fill, matching loop order)
    for code in (0x62, 0x49, 0x53):                  # 'b', 'I', 'S'
        m = masks[code]
        if not m.any():
            continue
        _ragged_copy(seq_flat, seq_starts[rec_of_feat[m]] + fpos[m] - 1,
                     arr_lens[code], arr_vals[code])
    for code in (0x42, 0x69):                         # 'B'/'i': base ← BA
        m = masks[code]
        if m.any():
            seq_flat[seq_starts[rec_of_feat[m]] + fpos[m] - 1] = \
                ba_stream[_mask_pick(ba_feat, m, ba_feat_off)]

    # ---- qual assembly ---------------------------------------------------
    qual_lens = rl * qual_stored
    qual_starts = np.cumsum(qual_lens) - qual_lens
    total_quals = int(qual_lens.sum())
    qual_flat = np.empty(0, np.uint8)
    stored_idx = np.flatnonzero(qual_stored)
    overlays = total_fn and bool((masks[0x71] | masks[0x51]
                                  | masks[0x42]).any())
    if stored_idx.size:
        vals = _ragged_gather(qs_stream,
                              qs_rec_start[stored_idx]
                              + qs_feat_per_rec[stored_idx],
                              rl[stored_idx])
        if as_arrays and not overlays and vals.size == total_quals:
            qual_flat = vals             # the QS stream itself: no copy
        else:
            qual_flat = np.empty(total_quals, np.uint8)
            _ragged_copy(qual_flat, qual_starts[stored_idx],
                         rl[stored_idx], vals)
    # overlays: only records with stored quals surface a qual column, so
    # scatter only into those segments.  Overlay writes CAN collide (a
    # 'Q' then an overlapping zero-advance 'q'), and the record path
    # resolves collisions by feature order — so all overlay writes are
    # merged and applied in one feature-order-stable scatter (NumPy
    # fancy assignment is last-write-wins in index order).
    feat_stored = (qual_stored[rec_of_feat] if total_fn
                   else np.zeros(0, bool))
    ov_fidx, ov_dst, ov_val = [], [], []
    m = masks[0x71] & feat_stored                     # 'q' from QQ
    if m.any():
        qq_sel = m[masks[0x71]]          # aligned with the QQ arrays
        qq_lens = arr_lens[0x71]
        qq_starts = np.cumsum(qq_lens) - qq_lens
        ov_fidx.append(np.repeat(np.flatnonzero(m), qq_lens[qq_sel]))
        ov_dst.append(_ragged_targets(
            qual_starts[rec_of_feat[m]] + fpos[m] - 1, qq_lens[qq_sel]))
        ov_val.append(arr_vals[0x71][
            _ragged_targets(qq_starts[qq_sel], qq_lens[qq_sel])])
    for code in (0x51, 0x42):                         # 'Q'/'B' from QS
        m = masks[code] & feat_stored
        if m.any():
            ov_fidx.append(np.flatnonzero(m))
            ov_dst.append(qual_starts[rec_of_feat[m]] + fpos[m] - 1)
            ov_val.append(qs_stream[_mask_pick(qs_feat, m, qs_feat_off)])
    if ov_fidx:
        fidx = np.concatenate(ov_fidx)
        dst = np.concatenate(ov_dst)
        val = np.concatenate(ov_val)
        o = np.argsort(fidx, kind="stable")
        qual_flat[dst[o]] = val[o]

    # ---- output compaction ----------------------------------------------
    seq_lens = rl.copy()
    # CF_UNKNOWN_BASES yields seq='*' for MAPPED records only (the record
    # path's unmapped branch keeps the BA bases regardless of the flag)
    drop = (unknown_bases & mapped) | (rl == 0)
    seq_lens[drop] = 0
    if drop.any():
        # seq starts must be recomputed by the consumer from seq_lens
        seq_flat = seq_flat[np.repeat(~drop, rl)]
    return seq_flat, seq_lens, qual_flat, qual_lens, mapq


def records_to_columns(records, want_names: bool = False) -> dict:
    """The same column dict built from decoded CramRecords — the fallback
    for slices the vectorized path declines, so span-level output is
    identical either way."""
    n = len(records)
    bf = np.fromiter((r.bf for r in records), np.int64, n)
    cf = np.fromiter((r.cf for r in records), np.int64, n)
    seqs = [r.seq if r.seq != "*" else "" for r in records]
    quals = [bytes(r.qual) if r.cf & CF_QUAL_STORED else b""
             for r in records]
    out = {
        "n": n, "bf": bf, "cf": cf,
        "ref_id": np.fromiter((r.ref_id for r in records), np.int64, n),
        "rl": np.fromiter((r.read_length for r in records), np.int64, n),
        "pos": np.fromiter((r.pos for r in records), np.int64, n),
        "mapq": np.fromiter(
            (r.mapq if not r.bf & 0x4 else 0 for r in records),
            np.int64, n),
        "read_group": np.fromiter((r.read_group for r in records),
                                  np.int64, n),
        "seq_cat": "".join(seqs).encode("latin-1"),
        "seq_lens": np.fromiter(map(len, seqs), np.int64, n),
        "qual_cat": b"".join(quals),
        "qual_lens": np.fromiter(map(len, quals), np.int64, n),
    }
    if want_names:
        out["name_cat"] = b"".join(r.name for r in records)
        out["name_lens"] = np.fromiter(
            (len(r.name) for r in records), np.int64, n)
    return out


def concat_columns(parts: List[dict]) -> dict:
    """Concatenate per-slice column dicts into one span-level dict."""
    if not parts:
        return {"n": 0,
                **{k: np.zeros(0, np.int64) for k in
                   ("bf", "cf", "ref_id", "rl", "pos", "mapq",
                    "read_group", "seq_lens", "qual_lens", "name_lens")},
                "seq_cat": b"", "qual_cat": b"", "name_cat": b""}
    if len(parts) == 1:
        return parts[0]
    out = {"n": sum(p["n"] for p in parts)}
    for k in parts[0]:
        if k == "n":
            continue
        v = parts[0][k]
        if isinstance(v, bytes):
            out[k] = b"".join(p[k] for p in parts)
        else:
            out[k] = np.concatenate([p[k] for p in parts])
    return out


def _mask_pick(superset_mask: np.ndarray, sub_mask: np.ndarray,
               offsets: np.ndarray) -> np.ndarray:
    """offsets is aligned with superset_mask's True positions; select the
    entries where sub_mask (a subset of superset_mask) is also True."""
    return offsets[sub_mask[superset_mask]]


def _cid_user_counts(comp: CompressionHeader) -> Dict[int, int]:
    from hadoop_bam_tpu.formats.cram_decode import _encoding_cids
    users: Dict[int, int] = {}
    for enc in list(comp.data_series.values()) \
            + list(comp.tag_encodings.values()):
        for cid in _encoding_cids(enc):
            users[cid] = users.get(cid, 0) + 1
    return users


def _decode_names(comp, bulk: _Bulk, n: int, cf: np.ndarray) -> dict:
    """RN column.  With read_names_included every record carries a name;
    otherwise only detached records do (the rest get generated names at
    the SAM layer, which the caller owns)."""
    if comp.read_names_included:
        cnt = n
        carriers = np.arange(n)
    else:
        carriers = np.flatnonzero((cf & CF_DETACHED) != 0)
        cnt = carriers.size
    lens, vals = bulk.arrays("RN", int(cnt))
    name_lens = np.zeros(n, np.int64)
    name_lens[carriers] = lens
    return {"name_cat": vals.tobytes(), "name_lens": name_lens}


def _fill_reference(seq_flat, seq_starts, comp, slice_hdr, ref_names,
                    ref_source, mapped_idx, rl_mapped, pos, ref_id,
                    unknown_bases, fn, seg_firsts, seg_lens, rec_of_feat,
                    fpos, gap, read_len, ref_len, tail, masks, bulk):
    """Fill match-run gaps/tails from the reference and apply 'X'
    substitutions — vectorized over all mapped records of the slice."""
    total_fn = rec_of_feat.size
    # cumulative ref offset consumed before each feature's gap starts
    adv = gap + ref_len
    ref_before_gap = _seg_exclusive_cumsum(adv, seg_firsts, seg_lens)
    # ref offset at the feature itself (its gap consumed)
    ref_at_feat = ref_before_gap + gap
    # per-record total ref consumed: fn==0 records are one whole-read match
    ref_consumed = np.zeros(mapped_idx.size, np.int64)
    if total_fn:
        seg_last = seg_firsts + seg_lens - 1
        ref_consumed[fn > 0] = (ref_before_gap + adv)[seg_last]
    ref_consumed += tail
    ref_consumed[fn == 0] = rl_mapped[fn == 0]

    x_mask = masks[0x58]
    need_gap = total_fn and bool((gap > 0).any())
    need_tail = bool((tail > 0).any())
    need_x = bool(x_mask.any())
    if not (need_gap or need_tail or need_x):
        return

    unk_mapped = unknown_bases[mapped_idx]
    # map each feature to its position on the mapped-record axis
    feat_mpos = (np.searchsorted(mapped_idx, rec_of_feat) if total_fn
                 else np.zeros(0, np.int64))

    if ref_source is None:
        # CF_UNKNOWN_BASES records surface seq='*' anyway; any other
        # record needing reference bases must go down the record path,
        # which raises the canonical CRAMError
        per_rec_need = tail > 0
        if total_fn:
            per_rec_need = per_rec_need.copy()
            per_rec_need[feat_mpos[gap > 0]] = True
            per_rec_need[feat_mpos[x_mask]] = True
        if bool((per_rec_need & ~unk_mapped).any()):
            raise _Ineligible("reference required but not provided")
        if need_x:
            # every remaining X feature sits on a CF_UNKNOWN_BASES
            # record; the record path still decodes its BS code and
            # substitutes against the 'N' placeholder row — a malformed
            # code must raise CRAMError identically here, not vanish
            # with the dropped seq
            codes = bulk.raw("BS", int(x_mask.sum()))
            _substitute_vec(comp.substitution_matrix,
                            np.full(codes.size, ord("N"), np.uint8),
                            codes)
        return

    from hadoop_bam_tpu.utils import native

    pos_mapped = pos[mapped_idx]
    rid_mapped = ref_id[mapped_idx]
    take = ~unk_mapped & (ref_consumed > 0)
    bs_codes = (bulk.raw("BS", int(x_mask.sum())) if need_x
                else np.zeros(0, np.uint8))
    if need_x:
        # X features on CF_UNKNOWN_BASES-skipped records never reach the
        # per-reference substitution below, but the record path decodes
        # and validates their BS codes against the 'N' placeholder row
        # (their seq is discarded as '*', so it never fetches reference
        # bases for them either); malformed codes must raise CRAMError
        # identically here
        unk_codes = bs_codes[(unk_mapped[feat_mpos] & x_mask)[x_mask]]
        if unk_codes.size:
            _substitute_vec(comp.substitution_matrix,
                            np.full(unk_codes.size, ord("N"), np.uint8),
                            unk_codes)
    for rid in np.unique(rid_mapped[take]):
        sel = take & (rid_mapped == rid)
        name = ref_names[rid] if 0 <= rid < len(ref_names) else "*"
        lo = int(pos_mapped[sel].min())
        hi = int((pos_mapped[sel] + ref_consumed[sel]).max())
        if hi - lo > (1 << 31):
            raise _Ineligible("reference window too large")
        ref_arr = ref_source.get_bytes(name, lo, hi - lo)
        base_of_rec = pos_mapped - lo        # junk outside sel, never used
        sel_feat = sel[feat_mpos] if total_fn else np.zeros(0, bool)

        def copy_runs(dst_at, src_at, lens):
            """seq_flat runs from the reference window, one native call
            (this path runs only where the library loads: it needs the
            native ITF8 batch decoder too)."""
            if not native.copy_runs(seq_flat, ref_arr, dst_at, src_at, lens):
                raise _Ineligible("reference run out of range")

        if need_gap:
            gm = (gap > 0) & sel_feat
            if bool(gm.any()):
                # the gap spans read positions [fpos-gap, fpos)
                copy_runs(seq_starts[rec_of_feat[gm]] + (fpos - gap)[gm] - 1,
                          base_of_rec[feat_mpos[gm]] + ref_before_gap[gm],
                          gap[gm])
        if need_tail:
            tm = sel & (tail > 0)
            if bool(tm.any()):
                copy_runs(seq_starts[mapped_idx[tm]] + rl_mapped[tm]
                          - tail[tm],
                          base_of_rec[tm] + ref_consumed[tm] - tail[tm],
                          tail[tm])
        if need_x:
            xm = x_mask & sel_feat
            if bool(xm.any()):
                roff = base_of_rec[feat_mpos[xm]] + ref_at_feat[xm]
                if bool(((roff < 0) | (roff >= ref_arr.size)).any()):
                    raise _Ineligible("reference run out of range")
                seq_flat[seq_starts[rec_of_feat[xm]] + fpos[xm] - 1] = \
                    _substitute_vec(comp.substitution_matrix,
                                    ref_arr[roff], bs_codes[xm[x_mask]])


_NO_SUBSTITUTIONS = np.zeros((5, 4), np.uint8)


@functools.lru_cache(maxsize=64)
def _substitution_table(matrix: bytes) -> np.ndarray:
    """table[row, code] -> the substituted base byte, rows A/C/G/T/N; 0
    marks a code the matrix byte never produces (malformed), matching
    substitute_base's raise.  Reversed j so the FIRST matching j wins on
    duplicate codes, exactly like the scalar loop."""
    table = np.zeros((5, 4), np.uint8)
    for ri in range(5):
        byte = matrix[ri]
        candidates = [b for b in _BASES if b != _BASES[ri]]
        for j in range(3, -1, -1):
            code = (byte >> (6 - 2 * j)) & 3
            table[ri, code] = ord(candidates[j])
    table.flags.writeable = False
    return table


def _substitute_vec(matrix: bytes, ref_bases: np.ndarray,
                    codes: np.ndarray) -> np.ndarray:
    """Vectorized substitution-matrix application [SPEC section 10.6]."""
    # base byte -> row index (A/C/G/T/N, everything else N)
    row_of = np.full(256, 4, np.uint8)
    for i, b in enumerate(_BASES):
        row_of[ord(b)] = i
        row_of[ord(b.lower())] = i
    table = _substitution_table(bytes(matrix))
    if codes.size and int(codes.max(initial=0)) > 3:
        raise CRAMError("invalid substitution code")
    out = table[row_of[ref_bases], codes]
    if bool((out == 0).any()):
        raise CRAMError("invalid substitution code")
    return out
