"""QueryEngine: batched random-access region serving over indexed files.

Request shape: a BATCH of ``(path, region)`` pairs (the serving analog of
Hadoop-BAM's BAMInputFormat interval support, which only ever trimmed
scan plans).  The engine:

1. resolves every region through the file's genomic index — BAI/CSI for
   BAM (``split/bai.py``), tabix for BGZF VCF *and* BCF
   (``split/tabix.py``), the container coordinate table for CRAM
   (``split/cram_planner.py``) — into virtual-offset chunk ranges;
2. COALESCES and deduplicates the ranges across all requests touching
   the same file (overlapping hot regions share chunks; small compressed
   gaps merge so one pread+inflate serves neighbours) and decodes each
   chunk exactly once, through the ``ChunkCache`` so repeated queries
   reuse decoded chunks across batches;
3. routes the candidate record columns through the shared
   ``parallel/staging.FeedPipeline`` and filters them with a jitted
   interval-overlap predicate ON THE MESH (``make_overlap_step``) — the
   exactness filter runs as one sharded vector compare per tile group,
   not per-record host Python;
4. materializes per-request results (or yields the device tensor
   batches directly — ``api.query_regions``).

Failure policy rides the PR-1 taxonomy unchanged: chunk decode goes
through ``decode_with_retry`` (transient retries, corrupt fails fast),
admission/deadline pressure raises ``TransientIOError``, and bad
requests (missing index, unknown contig, unsupported container) raise
``PlanError``.
"""
from __future__ import annotations

import dataclasses
import struct
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.query.cache import ChunkCache, file_identity
from hadoop_bam_tpu.query.scheduler import Deadline, QueryScheduler
from hadoop_bam_tpu.split.intervals import Interval, resolve_interval
from hadoop_bam_tpu.split.spans import FileVirtualSpan
from hadoop_bam_tpu.utils.errors import PlanError
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.stepcache import BoundedStepCache, named_step

_I32_MAX = np.int32(np.iinfo(np.int32).max)
# compressed gap below which neighbouring index ranges coalesce into one
# chunk: one pread+inflate then serves both (htslib merges chunks the
# same way); the decoded-but-unrequested rows in the gap are filtered by
# the exact device predicate like any other non-overlapping candidate
_COALESCE_GAP_C = 1 << 14


@dataclasses.dataclass(frozen=True)
class QueryRequest:
    path: str
    region: str
    # per-request deadline override (seconds); None = the batch deadline
    deadline_s: Optional[float] = None


@dataclasses.dataclass
class QueryResult:
    request: QueryRequest
    records: List[object]          # SamRecord (BAM/CRAM) or VcfRecord
    n_candidates: int = 0          # rows the index surfaced pre-predicate


# ---------------------------------------------------------------------------
# device predicate
# ---------------------------------------------------------------------------

# bounded (SV801): one entry per (mesh, axis) actually used — a process
# cycling through many meshes must not grow this forever
_STEP_CACHE = BoundedStepCache(cap=8)

# tile column order fed through the FeedPipeline (all [] int32 series)
TILE_COLUMNS = ("rid", "pos1", "end1", "iv_rid", "iv_beg", "iv_end", "req")


def make_overlap_step(mesh, axis: str = "data"):
    """Jitted sharded predicate: per-row 1-based inclusive interval
    overlap — ``rid == iv_rid and pos1 <= iv_end and end1 >= iv_beg`` —
    over ``[n_dev, cap]`` int32 column tiles, returning the sharded
    boolean keep mask.  The interval bounds ride the tile as per-row
    columns, so one step serves rows belonging to DIFFERENT requests in
    the same dispatch (the whole point of batching the queries)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from hadoop_bam_tpu.parallel.mesh import shard_map

    key = ("query_overlap", tuple(mesh.devices.flat), mesh.axis_names, axis)

    def build():
        def per_device(rid, pos1, end1, iv_rid, iv_beg, iv_end, req,
                       count):
            rid, pos1, end1 = rid[0], pos1[0], end1[0]
            iv_rid, iv_beg, iv_end = iv_rid[0], iv_beg[0], iv_end[0]
            count = count[0]
            valid = jnp.arange(rid.shape[0], dtype=jnp.int32) < count
            keep = valid & (rid == iv_rid) & (pos1 <= iv_end) \
                & (end1 >= iv_beg)
            del req
            return keep[None]

        fn = shard_map(per_device, mesh=mesh, in_specs=(P(axis),) * 8,
                       out_specs=P(axis))
        return named_step("query_filter_step", fn)

    return _STEP_CACHE.get_or_build(key, build)


# ---------------------------------------------------------------------------
# per-format metadata + chunk decode
# ---------------------------------------------------------------------------

def _sniff_kind(path: str) -> str:
    lower = path.lower()
    if lower.endswith(".bam"):
        return "bam"
    if lower.endswith(".cram"):
        return "cram"
    if lower.endswith(".bcf"):
        return "bcf"
    if lower.endswith((".vcf.gz", ".vcf.bgz")):
        return "vcf"
    raise PlanError(
        f"cannot region-query {path!r}: supported containers are .bam "
        f"(.bai/.csi sidecar), .vcf.gz (.tbi), .bcf (.tbi), .cram")


def _ref_span_of_cigar(cigar: str, seq: str) -> int:
    """Reference span of a SAM CIGAR string (M/D/N/=/X) — host fallback
    for record formats without columnar CIGAR access (CRAM)."""
    import re
    if cigar in ("*", ""):
        return len(seq) if seq != "*" else 0
    return sum(int(n) for n, op in re.findall(r"(\d+)([MIDNSHP=X])", cigar)
               if op in "MDN=X")


class _FileMeta:
    """Header + index of one file identity, resolved once per engine."""

    __slots__ = ("path", "ident", "kind", "header", "ref_names", "index")

    def __init__(self, path: str, ident, kind: str, header, ref_names,
                 index):
        self.path = path
        self.ident = ident
        self.kind = kind
        self.header = header
        self.ref_names = list(ref_names)
        self.index = index


class QueryEngine:
    """Batched random-access query serving (module docstring)."""

    def __init__(self, config: HBamConfig = DEFAULT_CONFIG,
                 cache: Optional[ChunkCache] = None,
                 scheduler: Optional[QueryScheduler] = None,
                 mesh=None):
        self.config = config
        self.cache = cache if cache is not None else ChunkCache(
            int(getattr(config, "query_cache_bytes", 256 << 20)))
        self.scheduler = scheduler if scheduler is not None else \
            QueryScheduler(
                int(getattr(config, "query_max_in_flight", 8)),
                int(getattr(config, "query_queue_depth", 32)),
                getattr(config, "query_deadline_s", None))
        self._mesh = mesh
        # bounded metadata LRU + its lock: `hbam serve` drives one engine
        # from many client/dispatcher threads, so lookup/insert/evict of
        # the header+index table must be atomic
        import collections
        self._meta: "collections.OrderedDict[Tuple, _FileMeta]" = \
            collections.OrderedDict()
        self._meta_lock = threading.Lock()

    # -- metadata ------------------------------------------------------------

    def _mesh_or_make(self):
        with self._meta_lock:
            if self._mesh is None:
                from hadoop_bam_tpu.parallel.mesh import make_mesh
                self._mesh = make_mesh()
            return self._mesh

    def _file_meta(self, path: str) -> _FileMeta:
        ident = file_identity(path)
        with self._meta_lock:
            meta = self._meta.get(ident)
            if meta is not None:
                # true LRU: a hot file's header+index must never be the
                # one evicted at the 65th distinct file
                self._meta.move_to_end(ident)
                return meta
        kind = _sniff_kind(path)
        if kind == "bam":
            from hadoop_bam_tpu.formats.bamio import read_bam_header
            from hadoop_bam_tpu.split.bai import load_bai_for
            header, _ = read_bam_header(path)
            index = load_bai_for(path)
            if index is None:
                raise PlanError(
                    f"{path} has no .bai/.csi sidecar — region queries "
                    f"need a genomic index; build one with "
                    f"`hbam index --flavor bai {path}`")
            meta = _FileMeta(path, ident, kind, header, header.ref_names,
                             index)
        elif kind in ("vcf", "bcf"):
            from hadoop_bam_tpu.split.tabix import load_tabix_for
            header = self._variant_header(path, kind)
            index = load_tabix_for(path)
            if index is None:
                raise PlanError(
                    f"{path} has no .tbi sidecar — region queries need a "
                    f"tabix index; build one with "
                    f"`hbam index --flavor tbi {path}`")
            meta = _FileMeta(path, ident, kind, header, header.contigs,
                             index)
        else:  # cram
            from hadoop_bam_tpu.formats.cramio import read_cram_header
            header, _ = read_cram_header(path)
            index = self._cram_container_table(path, ident)
            meta = _FileMeta(path, ident, kind, header, header.ref_names,
                             index)
        with self._meta_lock:
            # two threads may have built the same meta concurrently; the
            # first insert wins so every caller shares one instance
            existing = self._meta.get(ident)
            if existing is not None:
                return existing
            if len(self._meta) >= 64:
                self._meta.pop(next(iter(self._meta)))
            self._meta[ident] = meta
        return meta

    def _variant_header(self, path: str, kind: str):
        from hadoop_bam_tpu.utils.seekable import scoped_byte_source
        with scoped_byte_source(path) as src:
            if kind == "bcf":
                from hadoop_bam_tpu.formats.bcfio import read_bcf_header
                header, _first, is_bgzf = read_bcf_header(src)
                if not is_bgzf:
                    raise PlanError(
                        f"{path} is a raw (non-BGZF) BCF — virtual-offset "
                        f"random access needs the BGZF container")
                return header
            from hadoop_bam_tpu.formats.vcf import read_vcf_header_bgzf
            return read_vcf_header_bgzf(src)

    def _cram_container_table(self, path: str, ident):
        """[(offset, end, ref_seq_id, start, span)] for every data
        container — the CRAM 'index': container headers carry their
        alignment coordinates, so one header walk (cached by file
        identity) answers region -> containers."""
        key = (ident, "cram-toc")
        hit = self.cache.get(key)
        if hit is not None:
            return hit
        from hadoop_bam_tpu.formats.cram import (
            ContainerHeader, FileDefinition,
        )
        from hadoop_bam_tpu.utils.seekable import scoped_byte_source
        table: List[Tuple[int, int, int, int, int]] = []
        # through as_byte_source, not a bare open(): the TOC walk reads
        # like any other engine read, so io_read_retries wraps it and
        # the install_chaos registry observes it (audited seam)
        with scoped_byte_source(path) as src:
            FileDefinition.from_bytes(src.pread(0, FileDefinition.SIZE))
            fsize = src.size
            pos = FileDefinition.SIZE
            while pos < fsize:
                chunk = src.pread(pos, 1 << 16)
                hdr, after = ContainerHeader.from_buffer(chunk, 0)
                if hdr.is_eof:
                    break
                end = pos + after + hdr.length
                table.append((pos, end, hdr.ref_seq_id, hdr.start,
                              hdr.span))
                pos = end
        table = table[1:]     # the first container is the SAM header
        self.cache.put(key, table, nbytes=48 * len(table))
        return table

    # -- resolution ----------------------------------------------------------

    def _resolve(self, meta: _FileMeta, region: str
                 ) -> Tuple[Interval, List[Tuple[int, int]]]:
        iv = resolve_interval(region, meta.ref_names)
        if iv.rname not in meta.ref_names:
            raise PlanError(
                f"region contig {iv.rname!r} is not in {meta.path}'s "
                f"reference dictionary")
        rid = meta.ref_names.index(iv.rname)
        beg0, end0 = iv.start - 1, iv.end
        if meta.kind == "bam":
            ranges = meta.index.query(rid, beg0, end0)
        elif meta.kind in ("vcf", "bcf"):
            ranges = meta.index.query(iv.rname, beg0, end0)
        else:  # cram: container coordinate overlap (multi-ref containers
            #    are always candidates; the predicate is exact)
            ranges = []
            for off, end, ref, start, span in meta.index:
                if ref == -2 or (ref == rid and start <= iv.end
                                 and start + max(span, 1) - 1 >= iv.start):
                    ranges.append((off, end))
        return iv, ranges

    def _coalesce(self, ranges: Sequence[Tuple[int, int]], kind: str
                  ) -> List[Tuple[int, int]]:
        """Merge overlapping/near-adjacent (start, end) ranges, bounded by
        ``query_chunk_bytes`` compressed per chunk (a single oversized
        range stays one chunk — splitting it would need record-aligned
        interior offsets the index does not provide).

        Gap/size arithmetic is in COMPRESSED bytes: BAM/VCF/BCF ranges
        are packed virtual offsets (compressed offset = value >> 16)
        while CRAM container ranges are already raw byte offsets — the
        shift must differ or CRAM gaps would read 65536x too small and
        whole-file stretches of unrelated containers would coalesce."""
        shift = 0 if kind == "cram" else 16
        cap_c = max(1 << 16,
                    int(getattr(self.config, "query_chunk_bytes", 1 << 20)))
        out: List[Tuple[int, int]] = []
        for s, e in sorted(set(ranges)):
            if out:
                ps, pe = out[-1]
                gap_c = (s >> shift) - (pe >> shift)
                size_c = (e >> shift) - (ps >> shift)
                if s <= pe or (gap_c <= _COALESCE_GAP_C
                               and size_c <= cap_c):
                    if e > pe:
                        out[-1] = (ps, e)
                    continue
            out.append((s, e))
        return out

    # -- chunk decode (cache + retry) ---------------------------------------

    def chunk_key(self, meta: _FileMeta, s: int, e: int) -> Tuple:
        return (meta.ident, meta.kind, s, e)

    def _chunk(self, meta: _FileMeta, s: int, e: int) -> Dict[str, object]:
        """Decoded chunk columns: {'rid','pos1','end1' int32 arrays,
        'records' materializer state} — cached by (identity, range)
        through the SINGLE-FLIGHT cache path, so many serve clients
        landing on the same cold chunk share one decode."""
        return self.cache.get_or_compute(
            self.chunk_key(meta, s, e),
            lambda: self._compute_chunk(meta, s, e))

    def _compute_chunk(self, meta: _FileMeta, s: int, e: int):
        """One cold chunk decode, compiled to a plan: the executor owns
        ``decode_with_retry`` and the query decode metrics taxonomy;
        this engine owns only the per-format column decoders and the
        cache tiering above."""
        from hadoop_bam_tpu.plan import builders
        from hadoop_bam_tpu.plan import executor as plan_executor

        plan = builders.query_chunk_plan(meta.path, meta.kind, s, e)
        return plan_executor.execute(
            plan, config=self.config,
            decode_fn=lambda sp: self._decode_chunk(meta, sp))

    def _decode_chunk(self, meta: _FileMeta,
                      span: FileVirtualSpan) -> Dict[str, object]:
        if meta.kind == "bam":
            return self._decode_bam_chunk(meta, span)
        if meta.kind == "vcf":
            return self._decode_vcf_chunk(meta, span)
        if meta.kind == "bcf":
            return self._decode_bcf_chunk(meta, span)
        return self._decode_cram_chunk(meta, span)

    def _decode_bam_chunk(self, meta, span) -> Dict[str, object]:
        from hadoop_bam_tpu.split.planners import read_bam_span
        batch = read_bam_span(meta.path, span, header=meta.header)
        n = len(batch)
        pos1 = batch.pos.astype(np.int64) + 1
        end1 = pos1 + np.maximum(batch.reference_span(), 1) - 1
        return {
            "rid": batch.refid.astype(np.int32),
            "pos1": np.minimum(pos1, _I32_MAX).astype(np.int32),
            "end1": np.minimum(end1, _I32_MAX).astype(np.int32),
            "batch": batch,
            "n": n,
            "nbytes": int(batch.data.nbytes) + 16 * n + 64,
        }

    def _variant_columns(self, meta, records) -> Dict[str, object]:
        rid_of = {c: i for i, c in enumerate(meta.ref_names)}
        n = len(records)
        rid = np.fromiter((rid_of.get(r.chrom, -1) for r in records),
                          np.int32, n)
        pos1 = np.fromiter((r.pos for r in records), np.int64, n)
        end1 = pos1 + np.fromiter((max(r.rlen, 1) for r in records),
                                  np.int64, n) - 1
        return {
            "rid": rid,
            "pos1": np.minimum(pos1, _I32_MAX).astype(np.int32),
            "end1": np.minimum(end1, _I32_MAX).astype(np.int32),
            "records": records,
            "n": n,
        }

    def _decode_vcf_chunk(self, meta, span) -> Dict[str, object]:
        from hadoop_bam_tpu.config import ValidationStringency
        from hadoop_bam_tpu.formats import bgzf
        from hadoop_bam_tpu.formats.vcf import VcfRecord
        from hadoop_bam_tpu.utils.seekable import scoped_byte_source
        records: List[VcfRecord] = []
        nbytes = 0
        with scoped_byte_source(meta.path) as src:
            r = bgzf.BGZFReader(src)
            r.seek_voffset(span.start_voffset)
            text = r.read_to_voffset(span.end_voffset)
            nbytes = len(text)
            for line in text.split(b"\n"):
                if not line or line[:1] == b"#":
                    continue
                try:
                    records.append(VcfRecord.from_line(line.decode()))
                except Exception:
                    if (self.config.validation_stringency
                            is ValidationStringency.STRICT):
                        raise
        out = self._variant_columns(meta, records)
        out["nbytes"] = 2 * nbytes + 64
        return out

    def _decode_bcf_chunk(self, meta, span) -> Dict[str, object]:
        from hadoop_bam_tpu.formats import bgzf
        from hadoop_bam_tpu.formats.bcf import BCFRecordCodec
        from hadoop_bam_tpu.utils.seekable import scoped_byte_source
        codec = BCFRecordCodec(meta.header)
        records = []
        nbytes = 0
        with scoped_byte_source(meta.path) as src:
            r = bgzf.BGZFReader(src)
            r.seek_voffset(span.start_voffset)
            while r.voffset() < span.end_voffset:
                head = r.read(8)
                if len(head) < 8:
                    break
                l_shared, l_indiv = struct.unpack("<II", head)
                body = r.read(l_shared + l_indiv)
                rec, _ = codec.decode(head + body, 0)
                records.append(rec)
                nbytes += 8 + l_shared + l_indiv
        out = self._variant_columns(meta, records)
        out["nbytes"] = 3 * nbytes + 64
        return out

    def _decode_cram_chunk(self, meta, span) -> Dict[str, object]:
        from hadoop_bam_tpu.split.cram_planner import read_cram_span
        from hadoop_bam_tpu.split.spans import FileByteSpan
        ref_source = None
        if self.config.cram_reference_source_path:
            from hadoop_bam_tpu.formats.cram_decode import (
                FastaReferenceSource,
            )
            ref_source = FastaReferenceSource(
                self.config.cram_reference_source_path)
        bspan = FileByteSpan(meta.path, span.start_voffset,
                             span.end_voffset)
        records = read_cram_span(meta.path, bspan, header=meta.header,
                                 ref_source=ref_source)
        rid_of = {c: i for i, c in enumerate(meta.ref_names)}
        n = len(records)
        rid = np.fromiter((rid_of.get(r.rname, -1) for r in records),
                          np.int32, n)
        pos1 = np.fromiter((r.pos for r in records), np.int64, n)
        spans = np.fromiter(
            (max(_ref_span_of_cigar(r.cigar, r.seq), 1) for r in records),
            np.int64, n)
        return {
            "rid": rid,
            "pos1": np.minimum(pos1, _I32_MAX).astype(np.int32),
            "end1": np.minimum(pos1 + spans - 1, _I32_MAX).astype(np.int32),
            "records": records,
            "n": n,
            "nbytes": sum(len(r.seq) + len(r.qual) + 64 for r in records)
            + 64,
        }

    @staticmethod
    def _materialize(meta: _FileMeta, value: Dict[str, object], row: int):
        if meta.kind == "bam":
            from hadoop_bam_tpu.formats.sam import SamRecord
            return SamRecord.from_line(value["batch"].to_sam_line(row))
        return value["records"][row]

    # -- serving -------------------------------------------------------------

    def _prepare(self, requests: Sequence[QueryRequest], deadline: Deadline):
        """Resolve + decode: returns (stream tuples, host refs,
        per-request candidate counts, interval list)."""
        tuples: List[Tuple[np.ndarray, ...]] = []
        refs: List[Tuple[int, _FileMeta, Dict[str, object]]] = []
        cand_counts = [0] * len(requests)
        ivs: List[Interval] = [None] * len(requests)
        # per-request deadline overrides ride alongside the batch one,
        # ANCHORED at the batch's enqueue instant (rebudget): admission
        # wait counts against them, matching query.latency_s
        req_deadlines = [
            None if r.deadline_s is None
            else deadline.rebudget(r.deadline_s)
            for r in requests]

        def check(i: int, what: str) -> None:
            deadline.check(what)
            if req_deadlines[i] is not None:
                req_deadlines[i].check(f"{what} (request {i})")

        # group by path, preserving first-appearance order
        by_path: Dict[str, List[int]] = {}
        for i, req in enumerate(requests):
            by_path.setdefault(req.path, []).append(i)

        with METRICS.span("query.resolve_wall", requests=len(requests)):
            plans = []           # (req_idx, meta, iv, ranges)
            # ranges accumulate BY FILE IDENTITY, not by path string —
            # two spellings of the same file (relative vs absolute)
            # resolve to one identity, and a per-path assignment here
            # would overwrite the earlier spelling's ranges
            ranges_by_ident: Dict[Tuple, List[Tuple[int, int]]] = {}
            kind_of_ident: Dict[Tuple, str] = {}
            for path, req_idxs in by_path.items():
                deadline.check("query resolve")
                meta = self._file_meta(path)
                acc = ranges_by_ident.setdefault(meta.ident, [])
                kind_of_ident[meta.ident] = meta.kind
                for i in req_idxs:
                    METRICS.count("query.requests")
                    check(i, "query resolve")
                    iv, ranges = self._resolve(meta, requests[i].region)
                    ivs[i] = iv
                    plans.append((i, meta, iv, ranges))
                    acc.extend(ranges)
            chunk_sets = {
                ident: self._coalesce(rs, kind_of_ident[ident])
                for ident, rs in ranges_by_ident.items()}

        for i, meta, iv, ranges in plans:
            check(i, "query decode")
            if not ranges:
                continue
            rid = np.int32(meta.ref_names.index(iv.rname))
            iv_beg = np.int32(min(iv.start, int(_I32_MAX)))
            iv_end = np.int32(min(iv.end, int(_I32_MAX)))
            lo = min(s for s, _ in ranges)
            hi = max(e for _, e in ranges)
            for s, e in chunk_sets[meta.ident]:
                if e <= lo or s >= hi:
                    continue             # chunk serves other requests only
                check(i, "query decode")
                value = self._chunk(meta, s, e)
                n = int(value["n"])
                if not n:
                    continue
                cand_counts[i] += n
                METRICS.count("query.rows_scanned", n)
                tuples.append((
                    value["rid"], value["pos1"], value["end1"],
                    np.full(n, rid, np.int32),
                    np.full(n, iv_beg, np.int32),
                    np.full(n, iv_end, np.int32),
                    np.full(n, i, np.int32),
                ))
                refs.append((i, meta, value))
        return tuples, refs, cand_counts, ivs

    def _stream_groups(self, tuples, deadline: Deadline) -> Iterator[Dict]:
        """Feed the candidate tuples through the shared FeedPipeline and
        yield device batches {rid,pos,end,req,keep,n_records}."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from hadoop_bam_tpu.parallel.staging import FeedPipeline, TileSpec

        if not tuples:
            return
        mesh = self._mesh_or_make()
        n_dev = int(np.prod(mesh.devices.shape))
        cap = int(getattr(self.config, "query_tile_records", 8192))
        sharding = NamedSharding(mesh, P("data"))
        step = make_overlap_step(mesh)
        fp = FeedPipeline(n_dev, cap,
                          [TileSpec((), np.int32)] * len(TILE_COLUMNS),
                          block_n=64, config=self.config,
                          name="query")

        def emit(arrays, counts) -> Dict:
            deadline.check("query filter")
            # ONE batched device_put for all eight leaves: per-leaf puts
            # were ~60% of the measured warm-path wall (8 python
            # dispatches per group), and the serving path lives on
            # per-query latency
            dev = jax.device_put((*arrays, counts), sharding)
            keep = step(*dev)
            # the dict doubles as the ring slot's in-flight handle
            return {"rid": dev[0], "pos": dev[1], "end": dev[2],
                    "req": dev[6], "keep": keep, "n_records": dev[7]}

        with METRICS.span("query.filter_wall"):
            yield from fp.stream(iter(tuples), emit)

    def tensor_batches(self, requests: Sequence[QueryRequest],
                       deadline_s: Optional[float] = None) -> Iterator[Dict]:
        """Device-batch surface (api.query_regions): yields sharded
        ``{rid,pos,end,req,keep,n_records}`` groups where ``keep`` is the
        mesh-computed interval-overlap mask and ``req`` maps each row back
        to its request index."""
        requests = [r if isinstance(r, QueryRequest) else QueryRequest(*r)
                    for r in requests]
        import time

        from hadoop_bam_tpu.obs.context import ensure_trace
        t0 = time.perf_counter()
        deadline = None
        try:
            # one trace per query batch (joined when the CLI / serve
            # tier already minted one): every span below — resolve,
            # pool-side chunk decode, staging dispatch — shares its id
            with ensure_trace(op="query.batch", deadline_s=deadline_s), \
                    self.scheduler.admit(deadline_s) as deadline:
                tuples, _refs, _counts, _ivs = self._prepare(requests,
                                                             deadline)
                yield from self._stream_groups(tuples, deadline)
        finally:
            # end-to-end batch latency (admission wait included): on a
            # single-request batch this IS the per-query latency the
            # bench's p50/p99 columns report
            METRICS.observe("query.latency_s", time.perf_counter() - t0)
            # one tick per batch whose deadline was missed — whether it
            # aborted mid-serve (check() already booked it) or merely
            # finished late (booked here)
            if deadline is not None and deadline.expired:
                deadline.book_miss()

    def query_records(self, requests: Sequence[QueryRequest],
                      deadline_s: Optional[float] = None
                      ) -> List[QueryResult]:
        """Exact per-request record lists, index-pruned + mesh-filtered.
        Results keep file order within each request and request order
        across the batch."""
        requests = [r if isinstance(r, QueryRequest) else QueryRequest(*r)
                    for r in requests]
        import time

        from hadoop_bam_tpu.obs.context import ensure_trace
        t_start = time.perf_counter()
        batch_deadline = None
        try:
            with ensure_trace(op="query.batch", deadline_s=deadline_s), \
                    self.scheduler.admit(deadline_s) as deadline:
                batch_deadline = deadline
                tuples, refs, cand_counts, _ivs = self._prepare(requests,
                                                                deadline)
                mesh = self._mesh_or_make()
                n_dev = int(np.prod(mesh.devices.shape))
                flat_keep: List[np.ndarray] = []
                for out in self._stream_groups(tuples, deadline):
                    counts = np.asarray(out["n_records"])
                    keep = np.asarray(out["keep"])
                    for dev in range(n_dev):
                        flat_keep.append(keep[dev, :int(counts[dev])])
        finally:
            if batch_deadline is not None and batch_deadline.expired:
                batch_deadline.book_miss()
        mask = (np.concatenate(flat_keep) if flat_keep
                else np.zeros(0, bool))
        results = [QueryResult(req, [], cand_counts[i])
                   for i, req in enumerate(requests)]
        base = 0
        for req_idx, meta, value in refs:
            n = int(value["n"])
            rows = np.flatnonzero(mask[base:base + n])
            base += n
            recs = results[req_idx].records
            for row in rows:
                recs.append(self._materialize(meta, value, int(row)))
        METRICS.count("query.rows_matched",
                      sum(len(r.records) for r in results))
        METRICS.observe("query.latency_s", time.perf_counter() - t_start)
        return results

    def stats(self) -> Dict[str, float]:
        return self.cache.stats()
