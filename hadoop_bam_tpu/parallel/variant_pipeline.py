"""The variant device feed: VCF/BCF spans -> typed column + dosage tiles ->
sharded mesh steps.

The variant-side mirror of parallel/pipeline.py's BAM columnar path
(reference scope: hb/VCFInputFormat.java + hb/VCFRecordReader.java +
hb/BCFRecordReader.java fed records to MapReduce one at a time; here span
readers feed a mesh batches of typed arrays).  Host threads parse spans into
``VariantBatch`` columns plus the ALT-dosage genotype matrix; devices see

    chrom [cap] i32, pos [cap] i32, flags [cap] u8 (bit0 PASS, bit1 SNP),
    dosage [cap, S_pad] i8, counts [] i32

and reduce with one psum'd step per tile group — variant counts, mean ALT
allele frequency, and per-sample call rates in a single pass.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from hadoop_bam_tpu.parallel.mesh import shard_map
from hadoop_bam_tpu.parallel.scan import ScanFeed

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.formats.vcf import VariantBatch, VCFHeader
from hadoop_bam_tpu.parallel.pipeline import (
    _STEP_CACHE, _StatTotals, pipeline_span_count,
)
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.pools import decode_pool_size
from hadoop_bam_tpu.utils.stepcache import named_step

# dispatch-bucket granularity for variant tiles (no Pallas block
# constraint on this path; 64 keeps the jit shape ladder tiny)
_VARIANT_BLOCK_N = 64


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class VariantGeometry:
    """Static shapes of one device's variant tile (jit contract).

    ``tile_records=None`` (the default) sizes the tile from the sample
    count: as many variants per step as keep the dosage tile within
    ~8 MB, clamped to [64, 65536].  Fewer, larger dispatches amortize
    the per-step issue cost (at 2,504 samples a tile is 3,352 records:
    on the v5e a 262,144-record scan was 79 groups, 8.35 MB over the
    link and 24 us of device time a group — PERF.md, PR 28),
    but a fixed 64k tile would be gigabytes for cohort-scale VCFs —
    the device step materializes int32 casts of the whole dosage tile.
    The floor is records-small on purpose: a 100k-sample cohort at the
    old 4096-record floor was a ~1.6 GB int32 tile, the very blow-up
    the byte budget exists to prevent (ADVICE r4).
    """
    tile_records: "Optional[int]" = None
    n_samples: int = 0             # from the header; padded to samples_pad

    def __post_init__(self):
        if self.tile_records is None:
            budget = (8 << 20) // max(1, self.samples_pad)
            object.__setattr__(
                self, "tile_records",
                max(64, min(1 << 16, _round_up(budget, 8))))

    @property
    def samples_pad(self) -> int:
        # transfer-compact (8-byte steps), not lane-aligned: a 3-sample
        # VCF padded to 128 lanes shipped 40x the dosage bytes over the
        # H2D link, which is the scarce resource on every measured
        # config; Mosaic/XLA pad the lane dim in VMEM for free
        return max(8, _round_up(self.n_samples, 8))


FLAG_PASS = 1
FLAG_SNP = 2


def pack_variant_tiles(batch: VariantBatch, geometry: VariantGeometry
                       ) -> Dict[str, np.ndarray]:
    """VariantBatch -> dense typed rows (unpadded; the group packer pads)."""
    n = len(batch)
    flags = (batch.is_pass.astype(np.uint8) * FLAG_PASS
             | batch.is_snp.astype(np.uint8) * FLAG_SNP)
    dosage = np.full((n, geometry.samples_pad), -1, dtype=np.int8)
    if geometry.n_samples:
        dosage[:, :geometry.n_samples] = batch.dosage_matrix()
    return {
        "chrom": batch.chrom.astype(np.int32),
        "pos": np.minimum(batch.pos, np.iinfo(np.int32).max
                          ).astype(np.int32),
        "flags": flags,
        "dosage": dosage,
    }


# Common diploid GT strings resolved by dict lookup — the fast path that
# skips per-field parsing for the overwhelming majority of genotypes.
_GT_DOSE = {b"0/0": 0, b"0|0": 0, b"0/1": 1, b"1/0": 1, b"0|1": 1,
            b"1|0": 1, b"1/1": 2, b"1|1": 2, b"./.": -1, b".|.": -1,
            b".": -1, b"0": 0, b"1": 1}

_SNP_ALTS = frozenset(b"ACGTN")


def pack_variant_tiles_from_text(text, header: VCFHeader,
                                 geometry: VariantGeometry
                                 ) -> Dict[str, np.ndarray]:
    """Text-VCF tokenizer for the stats/tensor path — the host-side 'VCF
    line tokenizer' kernel of SURVEY.md section 7.3(e).  ``text`` is any
    contiguous bytes-like (a view of a leased span buffer included);
    every column returned is memory of its own.

    One tokeniser for narrow and wide files, its work in proportion to
    the bytes and never to lines x samples.  One pass finds each record
    line, its first nine tabs and its dosage row where the sample block
    is a shape it reads: FORMAT exactly ``GT`` with the regular ``digit
    sep digit`` cells of a phased call set, or a keyed line — FORMAT
    ``GT:`` and more keys, every line a caller such as GATK writes
    (``GT:AD:DP:GQ:PL``) — whose cells each lead with ``digit sep
    digit``, a half-missing ``./1`` or a no-call ``./.`` / ``.`` before
    their first ':'.  That pass is ``utils/native.py::vcf_tokenize`` with
    the interpreter lock released, or ``_vcf_tokenize_numpy`` on a host
    without the library, which reads no keyed line (counted:
    ``vcf.text_native_records`` / ``vcf.text_numpy_records``).  CHROM,
    POS and the flags come from the nine tabs by a few NumPy gathers
    (``_fixed_field_columns``).  A line the pass does not read (a
    multi-digit allele, a haploid call, a missing call in a ``GT``-only
    line, a FORMAT not led by ``GT``, a short or long line, an ALT wider
    than its gather, a non-digit POS) is read by the scalar parse below,
    which stays the statement of the semantics (``vcf.text_bulk_records``
    / ``vcf.text_scalar_records``; asserted equal by tests).  Of the bulk
    records, the keyed ones are counted once a span as
    ``vcf.text_keyed_records`` and their no-call cells as
    ``vcf.text_nocall_cells``.

    A scan on a host with the library takes ``span_columns_native``
    instead; this composition is what a host without it runs, and that
    path's oracle."""
    buf = np.frombuffer(text, dtype=np.uint8)
    S, pad = geometry.n_samples, geometry.samples_pad
    keyed = nocall = 0
    with METRICS.span("vcf.gt_dosage_wall"):
        if native.load() is not None:
            bounds, ntab, bulk, dosage, keyed, nocall = native.vcf_tokenize(
                buf, S, pad)
            METRICS.count("vcf.text_native_records", len(ntab))
        else:
            bounds, ntab, bulk, dosage = _vcf_tokenize_numpy(buf, S, pad)
            METRICS.count("vcf.text_numpy_records", len(ntab))
    cols, odd = _fixed_field_columns(buf, bounds, header)
    cols["dosage"] = dosage
    rows = np.flatnonzero(odd | ~bulk)
    if rows.size:
        if keyed:
            # a keyed line the pass read whose fixed fields send it to the
            # scalar parse after all is no bulk record: out of the counts
            lost = rows[bulk[rows] & _keyed_lines(buf, bounds[rows],
                                                  ntab[rows])]
            keyed -= lost.size
            nocall -= int(np.count_nonzero(dosage[lost, :S] < 0))
        _patch_scalar_rows(cols, buf, rows, bounds[rows][:, (0, 10)],
                           header, geometry)
    _count_text_rows(len(ntab), int(rows.size), keyed, nocall)
    return cols


def _patch_scalar_rows(cols: Dict[str, np.ndarray], buf: np.ndarray,
                       rows: np.ndarray, lines: np.ndarray,
                       header: VCFHeader, geometry: VariantGeometry) -> None:
    """Rows ``rows`` of ``cols`` from the scalar parse of their lines
    (``lines``: each one's start and end in ``buf``)."""
    mv = memoryview(buf)
    patch = _pack_variant_tiles_from_text_scalar(
        b"\n".join(mv[s:e] for s, e in lines.tolist()) + b"\n",
        header, geometry)
    for k in cols:
        cols[k][rows] = patch[k]


def _count_text_rows(n: int, scalar: int, keyed: int, nocall: int) -> None:
    METRICS.count("vcf.text_bulk_records", n - scalar)
    METRICS.count("vcf.text_scalar_records", scalar)
    METRICS.count("vcf.text_keyed_records", keyed)
    METRICS.count("vcf.text_nocall_cells", nocall)


def span_columns_native(text, records: int, header: VCFHeader,
                        geometry: VariantGeometry,
                        contigs: "native.ContigTable"
                        ) -> Dict[str, np.ndarray]:
    """``pack_variant_tiles_from_text``'s columns, equal to them byte for
    byte, from ONE native pass over the span's lines with the interpreter
    lock released (``utils/native.py::vcf_span_columns``): the walk and
    dosage rows of ``hbam_vcf_tokenize`` with CHROM looked up in the
    scan's contig table, POS parsed and the PASS / SNP flags set beside
    them, in place of ``_fixed_field_columns``' gathers.  ``records`` is
    the count of record lines the read found (-1: the pass counts them
    first).  The scalar parse reads the lines the pass refuses, as there;
    but for an ALT wider than ``_ALT_W``, whose SNP flag the pass sets at
    any width.  Counted as that path counts, and once a span as
    ``vcf.text_span_native_spans``."""
    with METRICS.span("vcf.gt_dosage_wall"):
        cols, refused, keyed, nocall = native.vcf_span_columns(
            text, records, geometry.n_samples, geometry.samples_pad,
            contigs)
    n = len(cols["flags"])
    METRICS.count("vcf.text_native_records", n)
    if refused.shape[0]:
        _patch_scalar_rows(cols, np.frombuffer(text, np.uint8),
                           refused[:, 0], refused[:, 1:], header, geometry)
    _count_text_rows(n, int(refused.shape[0]), keyed, nocall)
    METRICS.count("vcf.text_span_native_spans")
    return cols


def _keyed_lines(buf: np.ndarray, bounds: np.ndarray, ntab: np.ndarray
                 ) -> np.ndarray:
    """Per line: its FORMAT starts ``GT:`` and sample fields follow (the
    native pass's keyed branch)."""
    at = np.minimum(bounds[:, 8] + 1, max(buf.size - 3, 0))
    return (ntab == 9) & (bounds[:, 9] - bounds[:, 8] > 3) \
        & (buf[at] == ord("G")) & (buf[at + 1] == ord("T")) \
        & (buf[at + 2] == ord(":"))


def _pack_variant_tiles_from_text_scalar(text: bytes, header: VCFHeader,
                                         geometry: VariantGeometry
                                         ) -> Dict[str, np.ndarray]:
    """Per-line reference tokenizer (the vectorized path's oracle and its
    irregular-row fallback)."""
    S = geometry.n_samples
    cap = text.count(b"\n") + 1
    chrom = np.empty(cap, np.int32)
    pos = np.empty(cap, np.int32)
    flags = np.empty(cap, np.uint8)
    dosage = np.full((cap, geometry.samples_pad), -1, np.int8)
    cmap: Dict[bytes, int] = {c.encode(): i
                              for i, c in enumerate(header.contigs)}
    n = 0
    for line in text.split(b"\n"):
        if not line or line[:1] == b"#":
            continue
        parts = line.split(b"\t")
        if len(parts) < 8:
            continue
        chrom[n] = cmap.get(parts[0], -1)
        pos[n] = int(parts[1])
        ref, alt, filt = parts[3], parts[4], parts[6]
        f = 0
        if filt == b"PASS":
            f |= FLAG_PASS
        if len(ref) == 1 and alt != b"." and all(
                len(a) == 1 and a[0] in _SNP_ALTS
                for a in alt.split(b",")):
            f |= FLAG_SNP
        flags[n] = f
        if S and len(parts) > 9 and parts[8][:2] == b"GT":
            row = dosage[n]
            for s, field in enumerate(parts[9:9 + S]):
                colon = field.find(b":")
                gt = field if colon < 0 else field[:colon]
                d = _GT_DOSE.get(gt)
                if d is None:  # polyploid / multi-allelic / malformed
                    d = 0
                    for a in gt.replace(b"|", b"/").split(b"/"):
                        if not a.isdigit():
                            d = -1
                            break
                        d += 1 if int(a) > 0 else 0
                row[s] = min(d, 127) if d >= 0 else -1
        n += 1
    return {"chrom": chrom[:n], "pos": pos[:n], "flags": flags[:n],
            "dosage": dosage[:n]}


def bcf_span_stat_columns(path: str, span, header: VCFHeader,
                          geometry: VariantGeometry,
                          is_bgzf: Optional[bool] = None
                          ) -> Dict[str, np.ndarray]:
    """One BCF span -> stats tile columns via the columnar decoder
    (formats/bcf_columns.py): the span walk frames records for free,
    one vectorized pass decodes them.  Spans the columnar path declines
    (pathological geometry) fall back to the record-serial scanner with
    identical output — the binary twin of the text tokenizer's
    vectorized/scalar split above."""
    from hadoop_bam_tpu.formats.bcf_columns import (
        decode_bcf_columns, stat_columns,
    )
    from hadoop_bam_tpu.split.vcf_planners import bcf_span_frames

    # vcf.decode_busy_ns: this thread's CPU time in the span's read +
    # decode (waits for the interpreter lock left out) — the host variant
    # plane's twin of decode.native_busy_ns
    t_cpu = time.thread_time_ns()
    try:
        # ``raw`` may be a view of a leased span buffer, handed back when
        # the stack unwinds: every column below is memory of its own (the
        # packer holds columns across spans)
        with contextlib.ExitStack() as leased:
            with METRICS.span("vcf.inflate_wall"):
                raw, starts = leased.enter_context(
                    bcf_span_frames(path, span, is_bgzf))
            METRICS.count("vcf.inflated_bytes", len(raw))
            with METRICS.span("vcf.tokenize_wall"):
                cols = decode_bcf_columns(raw, header, geometry.samples_pad,
                                          starts=starts)
                if cols is not None:
                    return stat_columns(cols)
                METRICS.count("vcf.columnar_declined_spans")
                from hadoop_bam_tpu.formats.bcf import scan_variant_columns
                return scan_variant_columns(raw, header,
                                            geometry.samples_pad)
    finally:
        METRICS.count("vcf.decode_busy_ns",
                      time.thread_time_ns() - t_cpu)


class _TextAlive:
    """A scan's inflated text alive at once: from a span's read until the
    end of its tokenise.  Its high-water mark is ``vcf.text_peak_bytes``,
    added once a scan: the window's spans x a span's text bound it,
    whatever the file's size."""

    def __init__(self):
        self._lock = threading.Lock()
        self._now = self.peak = 0

    def add(self, n: int) -> None:
        with self._lock:
            self._now += n
            self.peak = max(self.peak, self._now)


def text_span_stat_columns(ds, span, header: VCFHeader,
                           geometry: VariantGeometry, alive: _TextAlive,
                           contigs: "Optional[native.ContigTable]" = None
                           ) -> Dict[str, np.ndarray]:
    """One text-VCF span -> stats tile columns — the text twin of
    ``bcf_span_stat_columns``, with its spans and counters; ``alive`` is
    the scan's account of text in flight.

    ``contigs`` (the scan's contig table, built where the native library
    is) decides the path.  With it the span goes from its compressed bytes
    to its columns in native calls with the interpreter lock released: a
    BGZF file's lines read and counted (``split/vcf_planners.py::
    bgzf_text_span_lines``: two calls), any other container's by
    ``VcfDataset.span_text``, then ``span_columns_native`` (one call; one
    more to count lines the read did not).  Without it the Python
    composition runs — ``VcfDataset.span_text`` and
    ``pack_variant_tiles_from_text``, the native pass's oracle — counted
    ``vcf.text_span_python_spans``."""
    from hadoop_bam_tpu.api.dispatch import VCFContainer
    from hadoop_bam_tpu.split.vcf_planners import bgzf_text_span_lines

    t_cpu = time.thread_time_ns()
    held = 0
    try:
        # ``text`` may be a view of a leased span buffer, handed back when
        # the stack unwinds: every column below is memory of its own
        with contextlib.ExitStack() as leased:
            with METRICS.span("vcf.inflate_wall"):
                if contigs is not None \
                        and ds.container is VCFContainer.VCF_BGZF:
                    text, records = leased.enter_context(
                        bgzf_text_span_lines(ds.path, span))
                else:
                    text = leased.enter_context(ds.span_text(span))
                    records = -1
            held = len(text)
            METRICS.count("vcf.inflated_bytes", held)
            alive.add(held)
            with METRICS.span("vcf.tokenize_wall"):
                if contigs is not None:
                    return span_columns_native(text, records, header,
                                               geometry, contigs)
                METRICS.count("vcf.text_span_python_spans")
                return pack_variant_tiles_from_text(text, header, geometry)
    finally:
        alive.add(-held)
        METRICS.count("vcf.decode_busy_ns",
                      time.thread_time_ns() - t_cpu)


_ALT_W = 16            # widest ALT the vectorized SNP test gathers
_POS_W = 10            # max decimal digits in a 31-bit position
# the NumPy twin works a slab of lines at a time: the bytes it gathers at
# once (a window of each line's head; the lines' sample blocks)
_TWIN_SLAB_BYTES = 2 << 20
_TWIN_HEAD_W = 256     # a line's first nine tabs lie within this, mostly


def _vcf_tokenize_numpy(buf: np.ndarray, S: int, pad: int):
    """NumPy twin of ``native/hbam_native.cpp::hbam_vcf_tokenize`` (what a
    host without the library runs; array for array what
    ``utils/native.py::vcf_tokenize`` returns, a row with ``bulk`` unset
    aside, which neither promises): one newline scan, the first nine tabs
    of a line from a window of its head (widened for the lines whose ALT
    or INFO is long), and the regular sample blocks copied a line at a
    time into one ``[lines, S, 4]`` byte matrix and checked there — a slab
    of lines at once, no index of lines x samples and no loop over
    samples."""
    nl = np.concatenate([np.flatnonzero(buf[lo:lo + _TWIN_SLAB_BYTES] == 0x0A)
                         + lo for lo in range(0, buf.size, _TWIN_SLAB_BYTES)]
                        or [np.empty(0, np.int64)])
    if buf.size and (nl.size == 0 or nl[-1] != buf.size - 1):
        nl = np.append(nl, buf.size)
    starts = np.zeros(nl.size, dtype=np.int64)
    starts[1:] = nl[:-1] + 1
    ends = nl
    keep = ends > starts
    keep[keep] = buf[starts[keep]] != ord("#")
    starts, ends = starts[keep], ends[keep]

    # the first nine tabs, the line's end where it has fewer
    tabm = np.repeat(ends[:, None], 9, axis=1)
    ntab = np.zeros(starts.size, np.int32)
    todo, w = np.arange(starts.size), _TWIN_HEAD_W
    while todo.size:
        step = max(1, _TWIN_SLAB_BYTES // w)
        later = []
        for lo in range(0, todo.size, step):
            rows = todo[lo:lo + step]
            ln = ends[rows] - starts[rows]
            j = np.arange(min(w, int(ln.max())), dtype=np.int64)[None, :]
            win = buf[np.minimum(starts[rows, None] + j, buf.size - 1)]
            r, c = np.nonzero((win == 0x09) & (j < ln[:, None]))
            k = np.arange(r.size) - np.searchsorted(r, np.arange(rows.size))[r]
            cnt = np.bincount(r, minlength=rows.size)
            done = (cnt >= 9) | (ln <= j.size)
            on = done[r] & (k < 9)
            tabm[rows[r[on]], k[on]] = starts[rows[r[on]]] + c[on]
            ntab[rows[done]] = np.minimum(cnt[done], 9)
            later.append(rows[~done])
        todo, w = np.concatenate(later), w * 8
    keep = ntab >= 7                        # >= 8 fields, scalar parity
    starts, ends, tabm, ntab = (a[keep] for a in (starts, ends, tabm, ntab))
    n = starts.size
    bounds = np.concatenate([starts[:, None], tabm, ends[:, None]], axis=1)
    dosage = np.full((n, pad), -1, np.int8)
    bulk = np.ones(n, bool)
    if not S or not n:
        return bounds, ntab, bulk, dosage

    # FORMAT is field 8; the sample fields need the ninth tab
    f0, f1 = tabm[:, 7] + 1, tabm[:, 8]
    at = np.minimum(f0, buf.size - 2)
    has_gt = (ntab == 9) & (f1 - f0 >= 2) & (buf[at] == ord("G")) \
        & (buf[at + 1] == ord("T"))
    cand = has_gt & (f1 - f0 == 2) & (ends - f1 - 1 == 4 * S - 1)
    bulk[has_gt & ~cand] = False
    rows = np.flatnonzero(cand)
    step = max(1, _TWIN_SLAB_BYTES // (4 * S))
    for lo in range(0, rows.size, step):
        slab = rows[lo:lo + step]
        cells = np.empty((slab.size, 4 * S), np.uint8)
        cells[:, -1] = 0x09
        for i, o in enumerate((f1[slab] + 1).tolist()):
            cells[i, :4 * S - 1] = buf[o:o + 4 * S - 1]
        cells = cells.reshape(slab.size, S, 4)
        c0, c1, c2 = cells[:, :, 0], cells[:, :, 1], cells[:, :, 2]
        ok = ((c0 - 0x30 <= 9) & (c2 - 0x30 <= 9)       # uint8 wraps
              & ((c1 == ord("/")) | (c1 == ord("|")))
              & (cells[:, :, 3] == 0x09)).all(axis=1)
        bulk[slab] = ok
        dosage[slab[ok], :S] = ((c0 > 0x30).view(np.int8)
                                + (c2 > 0x30).view(np.int8))[ok]
    return bounds, ntab, bulk, dosage


def _fixed_field_columns(buf: np.ndarray, bounds: np.ndarray,
                         header: VCFHeader):
    """``chrom`` / ``pos`` / ``flags`` of the record lines whose ``bounds``
    (start, first nine tabs, end) the tokenise pass found: one clamped
    gather a field.  Returns (cols, odd): ``odd`` marks the rows the scalar
    parse has to read (an ALT wider than its gather, a POS that is not a
    31-bit decimal)."""
    n = bounds.shape[0]
    cols = {"chrom": np.full(n, -1, np.int32),
            "pos": np.zeros(n, np.int32),
            "flags": np.zeros(n, np.uint8)}
    odd = np.zeros(n, bool)
    if n == 0:
        return cols, odd

    def gather(f, width):
        """[n, width] bytes of field f, zero past its length, + lengths."""
        fs = bounds[:, f] + (1 if f else 0)
        ln = np.maximum(bounds[:, f + 1] - fs, 0)   # past the last: empty
        j = np.arange(width, dtype=np.int64)[None, :]
        g = buf[np.minimum(fs[:, None] + j, buf.size - 1)]
        return np.where(j < ln[:, None], g, 0), ln

    # CHROM: a span holds 1-2 distinct names, but a real header can carry
    # thousands of contigs — dedupe the gathered rows and dict-look-up
    # only the unique values (O(lines) + O(unique * lookup), not
    # O(lines * contigs))
    cmap = {c.encode(): i for i, c in enumerate(header.contigs)}
    cw = max((len(c) for c in header.contigs), default=1)
    cbytes, clen = gather(0, cw)
    # clen joins the key so a truncated long name can't alias a contig
    keyed = np.concatenate(
        [cbytes, np.minimum(clen, cw + 1)[:, None].astype(np.uint8)],
        axis=1)
    # hash-group the rows (neither a per-contig scan nor a lexicographic
    # row-unique is acceptable): u64 scalar unique + one vectorized
    # verify against each group's representative
    weights = ((2 * np.arange(cw + 1, dtype=np.uint64) + 1)
               * np.uint64(0x9E3779B97F4A7C15))
    with np.errstate(over="ignore"):
        h = (keyed.astype(np.uint64) * weights[None, :]).sum(
            axis=1, dtype=np.uint64)
    _, first_idx, inv = np.unique(h, return_index=True,
                                  return_inverse=True)
    lut = np.full(first_idx.size, -1, np.int32)
    for ui, ri in enumerate(first_idx):
        ul = int(clen[ri])
        if ul <= cw:
            lut[ui] = cmap.get(cbytes[ri, :ul].tobytes(), -1)
    cols["chrom"] = lut[inv]
    # hash-collision rows (different bytes, same hash): re-look-up exactly
    mismatch = np.flatnonzero(
        ~(keyed == keyed[first_idx[inv]]).all(axis=1))
    for ri in mismatch:
        ul = int(clen[ri])
        cols["chrom"][ri] = cmap.get(cbytes[ri, :ul].tobytes(), -1) \
            if ul <= cw else -1

    # POS: fixed-width decimal parse (int64 accumulate; values past
    # int32 fall back so the scalar path raises the same OverflowError
    # the pre-vectorized tokenizer did on out-of-spec input)
    pb, plen = gather(1, _POS_W)
    digit = (pb >= 0x30) & (pb <= 0x39)
    j = np.arange(_POS_W, dtype=np.int64)[None, :]
    in_field = j < plen[:, None]
    odd |= (plen > _POS_W) | (plen == 0) | (digit != in_field).any(axis=1)
    scale = np.where(in_field, 10 ** np.maximum(
        plen[:, None] - 1 - j, 0), 0)
    pos64 = ((pb.astype(np.int64) - 0x30) * in_field * scale).sum(axis=1)
    odd |= pos64 > np.iinfo(np.int32).max
    cols["pos"] = np.minimum(pos64, np.iinfo(np.int32).max) \
        .astype(np.int32)

    # FILTER == PASS
    fb, flen = gather(6, 4)
    is_pass = (flen == 4) & (fb == np.frombuffer(b"PASS", np.uint8)) \
        .all(axis=1)

    # SNP: REF is 1 base; ALT is single bases joined by commas
    _rb, rlen = gather(3, 1)
    ab, alen = gather(4, _ALT_W)
    odd |= alen > _ALT_W
    ja = np.arange(_ALT_W, dtype=np.int64)[None, :]
    in_alt = ja < alen[:, None]
    snp_char = np.isin(ab, np.frombuffer(b"ACGTN", np.uint8))
    ok_even = (~in_alt | (ja % 2 == 1) | snp_char).all(axis=1)
    ok_odd = (~in_alt | (ja % 2 == 0) | (ab == ord(","))).all(axis=1)
    is_snp = (rlen == 1) & (alen % 2 == 1) & ok_even & ok_odd
    cols["flags"] = (is_pass.astype(np.uint8) * FLAG_PASS
                     | is_snp.astype(np.uint8) * FLAG_SNP)
    return cols, odd


def make_variant_stats_step(mesh: Mesh, geometry: VariantGeometry,
                            axis: str = "data"):
    """Jitted sharded step: variant tiles -> psum'd stats vector
    [n_variants, n_snp, n_pass, sum_af, n_af] ++ per-sample called counts.

    AF per variant = sum(max(dosage,0)) / (2 * n_called) (diploid ALT
    frequency); variants with zero called samples are excluded from the AF
    mean (n_af counts the included ones).
    """
    key = ("variant_stats", tuple(mesh.devices.flat), mesh.axis_names, axis,
           geometry)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]

    def per_device(chrom, pos, flags, dosage, count):
        chrom, flags = chrom[0], flags[0]
        dosage, count = dosage[0], count[0]
        cap = flags.shape[0]
        valid = jnp.arange(cap, dtype=jnp.int32) < count
        # count-like quantities stay integer end to end (f32 accumulation
        # drifts past 2^24 — realistic for WGS-scale call sets)
        vi = valid.astype(jnp.int32)
        n_variants = vi.sum()
        n_snp = (valid & ((flags & FLAG_SNP) != 0)).sum().astype(jnp.int32)
        n_pass = (valid & ((flags & FLAG_PASS) != 0)).sum().astype(jnp.int32)
        with jax.named_scope("unpack"):
            d = dosage.astype(jnp.int32)
            called = (d >= 0) & valid[:, None]
        with jax.named_scope("reduce"):
            n_called = called.sum(axis=1)                       # [cap] i32
            alt_sum = jnp.where(called, d, 0).sum(axis=1
                                                  ).astype(jnp.float32)
            has_calls = n_called > 0
            af = jnp.where(has_calls,
                           alt_sum / (2.0 * jnp.maximum(n_called, 1)
                                      .astype(jnp.float32)),
                           0.0)
            sum_af = (af * valid.astype(jnp.float32)).sum()
            n_af = (has_calls & valid).sum().astype(jnp.int32)
            per_sample_called = called.astype(jnp.int32).sum(axis=0)  # [S]
            ivec = jnp.concatenate([
                jnp.stack([n_variants, n_snp, n_pass, n_af]),
                per_sample_called,
            ])
        with jax.named_scope("psum"):
            return (jax.lax.psum(sum_af[None], axis),
                    jax.lax.psum(ivec, axis))

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(P(axis),) * 5, out_specs=(P(), P()))
    step = named_step("variant_step", fn)
    _STEP_CACHE[key] = step
    return step


def _variant_stats_result(totals: _StatTotals,
                          header: VCFHeader) -> Dict[str, object]:
    """Result assembly of a variant-stats scan from its drained totals."""
    if not totals:
        return {"n_variants": 0, "n_snp": 0, "n_pass": 0, "mean_af": 0.0,
                "n_af": 0, "sample_callrate": np.zeros(header.n_samples)}
    tf, ints = totals.drain()
    sum_af = float(tf[0])
    n_variants = int(ints[0])
    callrate = (ints[4:4 + header.n_samples].astype(np.float64)
                / max(n_variants, 1)
                if header.n_samples else np.zeros(0))
    return {
        "n_variants": n_variants,
        "n_snp": int(ints[1]),
        "n_pass": int(ints[2]),
        "mean_af": float(sum_af / max(int(ints[3]), 1)),
        # the mean_af denominator (variants with computable AF): exposed
        # so multi-host combiners can weight means exactly
        "n_af": int(ints[3]),
        "sample_callrate": callrate,
    }


# the ratio's sample: whole blocks in this many windows of this size,
# spread evenly over the file (a BGZF block is 64 KiB at most, so every
# window holds one).  Single blocks of a call set deflate 30 % apart:
# 8 windows read its ratio 5 % off, 32 within 1 % (4 MiB read, ~12 ms)
_RATIO_WINDOWS = 32
_RATIO_WINDOW_BYTES = 128 << 10


def _bgzf_inflate_ratio(path: str) -> float:
    """Inflated / compressed bytes over the whole BGZF blocks that lie in
    32 windows of 128 KiB spread evenly over the file (read off their
    headers and footers; nothing is inflated).  1.0 when it cannot be
    told.  Spread over the file because its head is no guide: the first
    256 KiB of a 48 MB call set's text read 51.5x, 58.0x and 61.0x on
    three seeds whose files all deflate 55.8-56.1x (the header's block,
    then whichever sites a file happens to start with), and the span
    count followed it, 147 to 174 spans of one amount of text."""
    from hadoop_bam_tpu.formats import bgzf
    from hadoop_bam_tpu.parallel.pipeline import scoped_byte_source

    packed = inflated = 0
    try:
        with scoped_byte_source(path) as src:
            last = max(0, src.size - _RATIO_WINDOW_BYTES)
            starts = sorted({last * i // (_RATIO_WINDOWS - 1)
                             for i in range(_RATIO_WINDOWS)})
            for at in starts:
                head = src.pread(at, _RATIO_WINDOW_BYTES)
                p, n = _whole_blocks(head, from_start=at == 0)
                packed += p
                inflated += n
    except Exception:  # noqa: BLE001 — planning must not fail the driver
        return 1.0
    return max(1.0, inflated / packed) if packed else 1.0


def _whole_blocks(head: bytes, from_start: bool) -> Tuple[int, int]:
    """(compressed, inflated) bytes of the chain of whole BGZF blocks in
    ``head``: from its first byte, or from the first block start found in
    it that a second block follows."""
    from hadoop_bam_tpu.formats import bgzf

    def chain(off: int) -> Tuple[int, int, int]:
        packed = inflated = blocks = 0
        while True:
            try:
                info = bgzf.parse_block_header(head, off)
            except bgzf.BGZFError:
                return packed, inflated, blocks
            packed += info.block_size
            inflated += info.isize
            blocks += 1
            off += info.block_size

    if from_start:
        return chain(0)[:2]
    for cand in bgzf.find_block_starts_numpy(
            np.frombuffer(head, dtype=np.uint8)):
        packed, inflated, blocks = chain(int(cand))
        if blocks >= 2:
            return packed, inflated
    return 0, 0


def variant_span_count(ds, n_dev: int,
                       config: HBamConfig = DEFAULT_CONFIG) -> int:
    """Span count of a whole-file variant scan.  ``pipeline_span_count``
    bounds a span's COMPRESSED bytes (4 MiB), which fits files that
    deflate about 4x.  A cohort-wide call set deflates 30x and more — BCF
    (5 KB records of mostly 0|0) and bgzip'd VCF text (10 KB lines of
    mostly ``0|0``, ~50x) alike: 4 MiB of the BCF are 26,000 records of
    2,504 samples, of the text ~200 MB and 20,000 lines, ten spans a
    262,144-record scan, all decoded before the first tile reaches the
    device.  So any BGZF variant file's bytes are weighed by the ratio a
    sample of its blocks shows (``_bgzf_inflate_ratio``), which keeps a
    span's INFLATED bytes near four pipeline grains."""
    from hadoop_bam_tpu.api.dispatch import VCFContainer

    if not (ds._is_bgzf_bcf or ds.container is VCFContainer.VCF_BGZF):
        return pipeline_span_count(ds.path, n_dev, config)
    return pipeline_span_count(
        ds.path, n_dev, config,
        size_scale=max(1.0, _bgzf_inflate_ratio(ds.path) / 4.0))


def variant_stats_file(path: str, mesh: Optional[Mesh] = None,
                       config: HBamConfig = DEFAULT_CONFIG,
                       geometry: Optional[VariantGeometry] = None,
                       header: Optional[VCFHeader] = None,
                       spans=None,
                       prefetch: int = 2) -> Dict[str, object]:
    """Distributed variant stats over a whole VCF/BCF (any container the
    dispatcher recognises): variant/SNP/PASS counts, mean ALT allele
    frequency, and per-sample call rates, reduced over the mesh's data
    axis.  A thin plan builder over the one executor
    (plan/builders.py + plan/executor.py)."""
    from hadoop_bam_tpu.plan import builders
    from hadoop_bam_tpu.plan import executor as plan_executor

    plan = builders.variant_stats_plan(path, config, geometry=geometry)
    return plan_executor.execute(plan, config=config, mesh=mesh,
                                 geometry=geometry, header=header,
                                 spans=spans, prefetch=prefetch)


# a variant step's arguments, by the tile schema's names
_TILE_ARGS = ("chrom", "pos", "flags", "dosage", "n_records")


def _scan_variant_file(path: str, mesh: Optional[Mesh], config: HBamConfig,
                       geometry: Optional[VariantGeometry],
                       header: Optional[VCFHeader], spans, prefetch: int,
                       make_consume) -> VCFHeader:
    """What every whole-file variant driver runs: open the file, plan its
    spans, and run them through the scan feed (``parallel/scan.py``):
    decoded in the pool (retried), the rows repacked into tile groups
    (the balanced final group spread over all shards and shrunk to a
    dispatch bucket), each group to the driver's own step.

    ``make_consume(ds, header, mesh, geometry)`` is called once the spans
    are planned and returns ``consume(args, counts)`` (``ScanFeed.run``):
    ``args`` maps the tile schema's names and ``n_records`` to the group's
    device arrays.  Returns the header."""
    from hadoop_bam_tpu.api.dispatch import VCFContainer
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf

    ds = open_vcf(path, config)
    if header is None:
        header = ds.header
    if geometry is None:
        geometry = VariantGeometry(n_samples=header.n_samples)
    scan = ScanFeed("vcf", config, mesh, None, geometry.tile_records,
                    block_n=_VARIANT_BLOCK_N, balance=True)
    if spans is None:
        with METRICS.span("vcf.plan_wall"):
            spans = ds.spans(
                num_spans=variant_span_count(ds, scan.n_dev, config))
    consume = make_consume(ds, header, scan.mesh, geometry)
    is_text = ds.container is not VCFContainer.BCF
    alive = _TextAlive()
    # CHROM's table for the native text pass: once a scan, never a span
    contigs = native.contig_table(header.contigs) \
        if is_text and native.load() is not None else None

    def decode(s):
        if is_text:     # fast tokenizer, no record objects
            return text_span_stat_columns(ds, s, header, geometry, alive,
                                          contigs)
        return bcf_span_stat_columns(ds.path, s, header, geometry,
                                     ds._is_bgzf_bcf)

    scan.run(scan.decoded(
        spans, decode, max(1, prefetch) * decode_pool_size(config),
        empty=pack_variant_tiles(VariantBatch([], header), geometry)),
        consume)
    if is_text:
        METRICS.count("vcf.text_peak_bytes", alive.peak)
    return header


def _variant_stats_impl(path: str, mesh: Optional[Mesh] = None,
                        config: HBamConfig = DEFAULT_CONFIG,
                        geometry: Optional[VariantGeometry] = None,
                        header: Optional[VCFHeader] = None,
                        spans=None,
                        prefetch: int = 2) -> Dict[str, object]:
    """The variant-stats mesh-feed implementation (executor runner)."""
    totals = _StatTotals()

    def make_consume(ds, header, mesh, geometry):
        step = make_variant_stats_step(mesh, geometry)
        # async; drained at the end
        return lambda args, _counts: totals.add(
            *step(*(args[k] for k in _TILE_ARGS)))

    header = _scan_variant_file(path, mesh, config, geometry, header, spans,
                                prefetch, make_consume)
    return _variant_stats_result(totals, header)


# ---------------------------------------------------------------------------
# hbam vcf-gwas: the same feed, kept — a device-resident dosage matrix
# ---------------------------------------------------------------------------

# room over the estimated site count: the estimate is the file's inflated
# size over its first records' size, and records differ in width
_GWAS_HEADROOM = 1.0 / 64
_PROBE_BYTES = 256 << 10


def _estimate_variant_sites(ds) -> int:
    """Sites of a VCF/BCF from what a plan can observe without decoding
    it: the inflated size of the file after its header over the mean size
    of the first records there.  A BGZF file's inflated size is the sum
    of its blocks' ISIZE, read off the block chain (the compressed bytes
    are read once more for it, nothing is inflated: a call set's chunks
    deflate 3 % apart and single blocks 30 %, so no sample of blocks is a
    guide)."""
    from hadoop_bam_tpu.api.dispatch import VCFContainer
    from hadoop_bam_tpu.formats import bgzf
    from hadoop_bam_tpu.formats.bcfio import read_bcf_header
    from hadoop_bam_tpu.ops import inflate as inflate_ops
    from hadoop_bam_tpu.parallel.pipeline import scoped_byte_source
    from hadoop_bam_tpu.utils.errors import PlanError

    if ds.container is VCFContainer.VCF_GZIP:
        raise PlanError(f"{ds.path}: a plain-gzip VCF cannot be sized "
                        f"without inflating it; recompress it with bgzip")
    is_bcf = ds.container is VCFContainer.BCF
    blocked = ds._is_bgzf_bcf or ds.container is VCFContainer.VCF_BGZF
    with scoped_byte_source(ds.path) as src:
        first = read_bcf_header(src)[1] if is_bcf else 0
        inflated = float(src.size - (first >> 16))
        if blocked:
            chain = inflate_ops.block_table(
                src.pread(first >> 16, src.size - (first >> 16)))
            inflated = float(chain["isize"].sum() - (first & 0xFFFF))
            reader = bgzf.BGZFReader(src)
            reader.seek_voffset(first)
            probe = reader.read(_PROBE_BYTES)
        else:
            probe = src.pread(first >> 16, _PROBE_BYTES)
    if is_bcf:
        sizes, p = [], 0
        while p + 8 <= len(probe):
            l_shared, l_indiv = np.frombuffer(probe, "<u4", 2, p)
            sizes.append(8 + int(l_shared) + int(l_indiv))
            p += sizes[-1]
        mean = float(np.mean(sizes)) if sizes else 0.0
    else:
        lines = [ln for ln in probe.split(b"\n")[:-1]
                 if ln and not ln.startswith(b"#")]
        mean = float(np.mean([len(ln) + 1 for ln in lines])) if lines \
            else 0.0
    if mean <= 0:
        raise PlanError(f"{ds.path}: no record found after the header")
    return max(1, int(np.ceil(inflated / mean)))


@dataclasses.dataclass
class GwasResident:
    """What pass 1 of ``hbam vcf-gwas`` leaves on the device: the int8
    dosage matrix ``[capacity, Sp]`` (file order, pad rows and columns
    -1), the sites' ``(chrom, pos)`` ``[2, capacity]``, and the GRM
    accumulators (ops/gwas_pallas.py says what they hold)."""
    resident: object = None
    sites: object = None
    acc: object = None
    r: object = None
    c: object = None
    n_grm: object = None
    rows: int = 0


def _variant_gwas_load(path: str, mesh: Optional[Mesh], config: HBamConfig,
                       geometry: Optional[VariantGeometry],
                       header: Optional[VCFHeader], spans,
                       prefetch: int) -> Tuple[VCFHeader, GwasResident]:
    """Pass 1: the whole file through the variant feed, every tile group
    written into the resident matrix and added to the GRM on its way."""
    from hadoop_bam_tpu.cohort.gwas import make_gwas_load_step
    from hadoop_bam_tpu.ops.gwas_pallas import (
        ASSOC_ROWS, GRM_BLOCK, round_up,
    )
    from hadoop_bam_tpu.parallel.mesh import make_mesh
    from hadoop_bam_tpu.utils.errors import PlanError

    if mesh is None:
        mesh = make_mesh(devices=jax.devices()[:1])
    if mesh.devices.size != 1:
        raise PlanError("vcf-gwas keeps its matrix on one device; it was "
                        f"given a mesh of {mesh.devices.size}")
    dev = mesh.devices.flat[0]
    st = GwasResident()

    def make_consume(ds, header, mesh, geometry):
        tile = geometry.tile_records
        want = int(np.ceil(_estimate_variant_sites(ds)
                           * (1.0 + _GWAS_HEADROOM)))
        capacity = round_up(round_up(want, tile), ASSOC_ROWS)
        sp = round_up(geometry.samples_pad, GRM_BLOCK)
        need = capacity * (sp + 8) + 4 * sp * (sp + 1)
        stats = dev.memory_stats() or {}
        free = stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)
        if stats and need > free:
            raise PlanError(
                f"{ds.path}: the resident matrix of ~{want} sites x "
                f"{header.n_samples} samples needs {need / 1e9:.2f} GB on "
                f"the device and {free / 1e9:.2f} GB are free")
        with jax.default_device(dev):
            st.resident = jnp.full((capacity, sp), -1, jnp.int8)
            st.sites = jnp.zeros((2, capacity), jnp.int32)
            st.acc = jnp.zeros((sp, sp), jnp.float32)
            st.r = jnp.zeros((sp,), jnp.float32)
            st.c = jnp.zeros((), jnp.float32)
            st.n_grm = jnp.zeros((), jnp.int32)
        METRICS.count("gwas.resident_bytes", int(st.resident.nbytes))
        step = make_gwas_load_step(header.n_samples)

        def consume(args, counts):
            bucket = args["dosage"].shape[1]
            if st.rows + bucket > capacity:
                raise PlanError(
                    f"{ds.path}: more sites than its first records' size "
                    f"foretold ({capacity} rows were reserved)")
            (st.resident, st.sites, st.acc, st.r, st.c,
             st.n_grm) = step(st.resident, st.sites, st.acc, st.r, st.c,
                              st.n_grm, *(args[k] for k in _TILE_ARGS),
                              np.int32(st.rows))
            st.rows += int(counts[0])

        return consume

    header = _scan_variant_file(path, mesh, config, geometry, header, spans,
                                prefetch, make_consume)
    return header, st


def _variant_gwas_impl(path: str, traits: str, mesh: Optional[Mesh] = None,
                       config: HBamConfig = DEFAULT_CONFIG,
                       geometry: Optional[VariantGeometry] = None,
                       header: Optional[VCFHeader] = None, spans=None,
                       prefetch: int = 2,
                       return_table: bool = False) -> Dict[str, object]:
    """The ``hbam vcf-gwas`` job (executor runner; cohort/gwas.py has the
    formulas): traits read, pass 1 over the file, the covariates, pass 2
    over the resident matrix."""
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.cohort import gwas
    from hadoop_bam_tpu.ops.gwas_pallas import LANE, round_up, split_bf16
    from hadoop_bam_tpu.utils.errors import PlanError

    if header is None:
        header = open_vcf(path, config).header
    n_s = header.n_samples
    if n_s <= 1 + gwas.GWAS_AXES:
        raise PlanError(f"{path}: {n_s} samples cannot carry "
                        f"{gwas.GWAS_AXES} covariate axes")
    with METRICS.span("gwas.pheno_wall"):
        names, y = gwas.read_traits_tsv(traits, header.samples)
    with METRICS.span("gwas.load_wall"):
        header, st = _variant_gwas_load(path, mesh, config, geometry,
                                        header, spans, prefetch)
    n_sites = st.rows
    # A lives in the kept buffer until the covariates are out of it; then
    # the next job's A is written there, so no name of this job keeps it
    with gwas.grm_buffer(n_s) as a_out:
        with METRICS.span("gwas.grm_wall"):
            with METRICS.span("gwas.grm_readback_wall"):
                acc, r, c, n_grm = jax.device_get(
                    (st.acc, st.r, st.c, st.n_grm))
            st.acc = st.r = None
            n_grm = int(n_grm)
            with METRICS.span("gwas.grm_finish_wall"):
                a = gwas.grm_from_accumulators(acc, r, float(c), n_grm, n_s,
                                               out=a_out)
        if n_grm == 0:
            raise PlanError(f"{path}: no site passes the GRM's filter (SNP, "
                            f"no missing call, MAF >= "
                            f"{gwas.GWAS_MAF_PERCENT} %)")
        with METRICS.span("gwas.eigh_wall"):
            eigenvalues, q = gwas.covariates(a)
        del a
    with METRICS.span("gwas.pheno_wall"):
        yt = y - q @ (q.T @ y)
        sigma2 = (yt * yt).sum(axis=0) / n_s
        if not (sigma2 > 0).all():
            raise PlanError(f"{traits}: trait "
                            f"{names[int(np.argmin(sigma2))]!r} has no "
                            f"variance left beside the covariates")
    n_t = len(names)
    with METRICS.span("gwas.assoc_wall"):
        sp = st.resident.shape[1]
        np_ = round_up(n_t + q.shape[1], LANE)
        w = np.zeros((sp, np_), np.float32)
        w[:n_s, :n_t] = yt
        w[:n_s, n_t:n_t + q.shape[1]] = q
        isig = np.zeros(np_, np.float32)
        isig[:n_t] = 1.0 / sigma2
        # devices() may hand back the sharding's own set: read, never pop
        dev = next(iter(st.resident.devices()))
        step = gwas.make_gwas_assoc_step(n_s, n_t, return_table)
        # split on the host: under jit a TPU may drop the round trip
        out = step(st.resident,
                   jax.device_put(np.stack(split_bf16(w)), dev),
                   jax.device_put(isig, dev),
                   jax.device_put(np.array([n_sites], np.int32), dev))
        sites = np.asarray(st.sites)[:, :n_sites]
        max_site = sites[:, np.asarray(out["max_row"])]
        tested = int(out["tested"])
        res = {
            "n_sites": n_sites, "n_grm_sites": n_grm,
            "eigenvalues": eigenvalues, "q": q, "traits": names,
            "tested": tested,
            "mean_chi2": np.asarray(out["sum"], np.float64)
            / max(tested, 1),
            "max_chi2": np.asarray(out["max"]),
            "max_chrom": max_site[0], "max_pos": max_site[1],
            "genome_wide": np.asarray(out["hits"]),
        }
        if return_table:
            res.update(chrom=sites[0], pos=sites[1],
                       chi2=np.asarray(out["chi2"])[:n_sites])
    METRICS.count("gwas.sites", n_sites)
    METRICS.count("gwas.grm_sites", n_grm)
    METRICS.count("gwas.assoc_sites", n_sites)
    METRICS.count("gwas.assoc_sites_resident", st.rows)
    METRICS.count("gwas.traits", n_t)
    METRICS.count("gwas.jobs")
    return res
