"""Typed configuration — the rebuild of Hadoop-BAM's string-keyed Configuration.

The reference's entire "flag system" is Hadoop ``Configuration`` string keys
scattered over the classes that consume them (SURVEY.md section 5):

- ``hadoopbam.anysam.trust-exts``              (hb/AnySAMInputFormat.java)
- ``hadoopbam.vcf.trust-exts``                 (hb/VCFInputFormat.java)
- ``hadoopbam.samheaderreader.validation-stringency`` (hb/util/SAMHeaderReader.java)
- ``hadoopbam.cram.reference-source-path``     (hb/CRAMInputFormat.java)
- ``hadoopbam.vcf.output-format``              (hb/VCFOutputFormat.java)
- ``hbam.fastq-input.base-quality-encoding``, ``...filter-failed-qc``
                                               (hb/FormatConstants.java)
- ``hadoopbam.bam.intervals``                  (hb/BAMInputFormat.java, 7.7+)

Here they become one typed dataclass with the same semantic knobs, plus the
TPU-specific knobs (backend selection, mesh shape, batch geometry).  A
``from_dict`` constructor accepts the reference's string keys verbatim so
Hadoop-BAM users can carry configs over unchanged.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Mapping, Optional, Sequence, Tuple


class ValidationStringency(enum.Enum):
    """Mirror of htsjdk ValidationStringency as consumed by
    hb/util/SAMHeaderReader.java: governs malformed-record handling."""

    STRICT = "STRICT"     # raise on malformed records
    LENIENT = "LENIENT"   # warn and skip
    SILENT = "SILENT"     # skip silently

    @classmethod
    def parse(cls, s: "str | ValidationStringency | None") -> "ValidationStringency":
        if s is None:
            return cls.SILENT
        if isinstance(s, cls):
            return s
        return cls[str(s).upper()]


class BaseQualityEncoding(enum.Enum):
    """FASTQ/QSEQ base-quality encodings (hb/FormatConstants.java).
    Offsets are [SPEC]: Sanger = Phred+33, Illumina(1.3-1.7) = Phred+64."""

    SANGER = 33
    ILLUMINA = 64

    @classmethod
    def parse(cls, s: "str | BaseQualityEncoding | None", default: "BaseQualityEncoding"):
        if s is None:
            return default
        if isinstance(s, cls):
            return s
        return cls[str(s).upper()]


# Mapping from the reference's Hadoop Configuration keys to dataclass fields.
_HADOOP_KEY_MAP = {
    "hadoopbam.anysam.trust-exts": "trust_exts",
    "hadoopbam.vcf.trust-exts": "vcf_trust_exts",
    "hadoopbam.samheaderreader.validation-stringency": "validation_stringency",
    "hadoopbam.cram.reference-source-path": "cram_reference_source_path",
    "hadoopbam.vcf.output-format": "vcf_output_format",
    "hadoopbam.bam.intervals": "bam_intervals",
    "hadoopbam.bam.keep-paired-reads-together": "keep_paired_reads_together",
    "hbam.fastq-input.base-quality-encoding": "fastq_base_quality_encoding",
    "hbam.fastq-input.filter-failed-qc": "fastq_filter_failed_qc",
    "hbam.qseq-input.base-quality-encoding": "qseq_base_quality_encoding",
    "hbam.qseq-input.filter-failed-qc": "qseq_filter_failed_qc",
    "hadoop-bam.backend": "backend",
    # failure-policy knobs (no reference analog: Hadoop relied on
    # mapreduce.map.maxattempts; these are the span-grain equivalents)
    "hbam.span-retries": "span_retries",
    "hbam.skip-bad-spans": "skip_bad_spans",
    "hbam.max-bad-span-fraction": "max_bad_span_fraction",
    "hbam.debug-keep-spill": "debug_keep_spill",
    # host->device feed knobs (parallel/staging.py; no reference analog —
    # Hadoop's record-ahead buffering was not configurable)
    "hbam.feed-ring-slots": "feed_ring_slots",
    "hbam.feed-dispatch-depth": "feed_dispatch_depth",
    "hbam.decode-pool-workers": "decode_pool_workers",
    # fused host decode knobs (ops/inflate.py FusedSpanDecode; the
    # reference's analog was per-block zlib-over-JNI with no fusion)
    "hbam.use-fused-decode": "use_fused_decode",
    "hbam.decode-chunk-blocks": "decode_chunk_blocks",
    # decode-plane selection (no reference analog — the JNI inflate had
    # exactly one implementation)
    "hbam.inflate-backend": "inflate_backend",
    # region-query serving knobs (query/; no reference analog — Hadoop-BAM
    # only ever trimmed scan plans with intervals, it never served them)
    "hbam.query-cache-bytes": "query_cache_bytes",
    "hbam.query-chunk-bytes": "query_chunk_bytes",
    "hbam.query-tile-records": "query_tile_records",
    "hbam.query-max-in-flight": "query_max_in_flight",
    "hbam.query-queue-depth": "query_queue_depth",
    "hbam.query-deadline-s": "query_deadline_s",
    # write-path knobs (write/; the reference's OutputFormats had only
    # the Hadoop codec's mapreduce.output.* compression settings)
    "hbam.write-compress-level": "write_compress_level",
    "hbam.write-parallel-workers": "write_parallel_workers",
    "hbam.write-index-kinds": "write_index_kinds",
    # serving knobs (serve/; no reference analog — Hadoop-BAM never ran
    # as a resident service)
    "hbam.serve-tile-cache-bytes": "serve_tile_cache_bytes",
    "hbam.serve-tile-records": "serve_tile_records",
    "hbam.serve-prefetch": "serve_prefetch",
    "hbam.serve-prefetch-depth": "serve_prefetch_depth",
    "hbam.serve-recent-regions": "serve_recent_regions",
    "hbam.serve-tenant-max-in-flight": "serve_tenant_max_in_flight",
    "hbam.serve-tenant-queue-depth": "serve_tenant_queue_depth",
    "hbam.serve-max-tenants": "serve_max_tenants",
    "hbam.serve-ring-slots": "serve_ring_slots",
    # fleet knobs (serve/fleet.py + serve/membership.py; no reference
    # analog — Hadoop-BAM had no serving tier to replicate)
    "hbam.serve-replica-id": "serve_replica_id",
    "hbam.serve-peers": "serve_peers",
    "hbam.fleet-replication": "fleet_replication",
    "hbam.fleet-heartbeat-s": "fleet_heartbeat_s",
    "hbam.fleet-suspicion-s": "fleet_suspicion_s",
    "hbam.fleet-eviction-s": "fleet_eviction_s",
    "hbam.fleet-peer-timeout-s": "fleet_peer_timeout_s",
    "hbam.fleet-hedge-min-s": "fleet_hedge_min_s",
    # resilience knobs (resilience/; no reference analog — Hadoop's only
    # adaptive behavior was task re-execution)
    "hbam.adaptive-planes": "adaptive_planes",
    "hbam.breaker-failure-threshold": "breaker_failure_threshold",
    "hbam.breaker-window-s": "breaker_window_s",
    "hbam.breaker-cooldown-s": "breaker_cooldown_s",
    "hbam.breaker-half-open-probes": "breaker_half_open_probes",
    "hbam.serve-shed-retry-after-s": "serve_shed_retry_after_s",
    "hbam.serve-prefetch-pause-pressure": "serve_prefetch_pause_pressure",
    "hbam.chaos-seed": "chaos_seed",
    # crash-safe job knobs (jobs/; the reference's analog was MapReduce
    # task re-execution + speculative execution, configured via
    # mapreduce.map.maxattempts / mapreduce.map.speculative)
    "hbam.pool-task-timeout-s": "pool_task_timeout_s",
    "hbam.speculative-decode": "speculative_decode",
    "hbam.straggler-multiplier": "straggler_multiplier",
    "hbam.straggler-min-s": "straggler_min_s",
    "hbam.collective-timeout-s": "collective_timeout_s",
    "hbam.journal-fsync": "journal_fsync",
    # cohort variant plane knobs (cohort/; no reference analog — Hadoop-BAM
    # never joined inputs, it only split them)
    "hbam.cohort-chunk-sites": "cohort_chunk_sites",
    "hbam.cohort-quarantine-inputs": "cohort_quarantine_inputs",
    "hbam.cohort-max-quarantine-fraction": "cohort_max_quarantine_fraction",
    "hbam.serve-cohort-manifests": "serve_cohort_manifests",
    # live-ops plane knobs (obs/flight.py, obs/slo.py; no reference
    # analog — Hadoop counters died with the job and nothing watched
    # them while it ran)
    "hbam.flight-dump-dir": "flight_dump_dir",
    "hbam.flight-dump-cap": "flight_dump_cap",
    "hbam.slo-latency-s": "slo_latency_s",
    "hbam.slo-target": "slo_target",
    "hbam.slo-tick-s": "slo_tick_s",
    "hbam.slo-min-events": "slo_min_events",
    "hbam.slo-shed-batch": "slo_shed_batch",
}


@dataclasses.dataclass
class HBamConfig:
    # --- format dispatch (hb/AnySAMInputFormat.java, hb/VCFInputFormat.java) ---
    trust_exts: bool = True          # skip magic sniffing when extension is known
    vcf_trust_exts: bool = True

    # --- decode behavior ---
    validation_stringency: ValidationStringency = ValidationStringency.SILENT
    cram_reference_source_path: Optional[str] = None

    # --- output ---
    vcf_output_format: str = "VCF"   # "VCF" | "BCF" (hb/VCFOutputFormat.java)
    write_header: bool = True        # per-shard header (KeyIgnoring*RecordWriter)
    write_terminator: bool = True    # BGZF EOF block on close
    # write path (write/): BGZF deflate level for EVERY producing path
    # (parallel writer, serial writers, shard parts, sort outputs);
    # htsjdk's BlockCompressedOutputStream default is 5, zlib's is 6 —
    # 6 kept for byte-compatibility with this repo's existing fixtures
    write_compress_level: int = 6
    write_parallel_workers: Optional[int] = None  # in-flight deflate
    #                                  bound for ParallelBGZFWriter;
    #                                  None = shared decode pool size,
    #                                  0 = serial in-line deflate
    write_index_kinds: str = "auto"  # sidecars co-written with outputs:
    #                                  "auto" (BAM: bai+sbi, BCF: tbi),
    #                                  "none", or a comma list
    # (3, 1) writes rANS Nx16 blocks.  EXPERIMENTAL: the Nx16 transform
    # metadata layouts are pinned by golden-byte tests against this repo's
    # own encoder only — no htslib cross-validation was possible in-image
    # (SURVEY.md section 0), so 3.1 output may not interop with samtools.
    cram_version: Tuple[int, int] = (3, 0)

    # --- FASTQ / QSEQ (hb/FormatConstants.java) ---
    fastq_base_quality_encoding: BaseQualityEncoding = BaseQualityEncoding.SANGER
    fastq_filter_failed_qc: bool = False
    qseq_base_quality_encoding: BaseQualityEncoding = BaseQualityEncoding.ILLUMINA
    qseq_filter_failed_qc: bool = False

    # --- interval filtering (hb/BAMInputFormat.java upstream 7.7+) ---
    # "chr20:1-100000,chr21" style; None = no filtering.
    bam_intervals: Optional[str] = None
    # keep both reads of a pair in the same span when the BAM is
    # queryname-grouped (hb/BAMInputFormat.java upstream 7.9+):
    keep_paired_reads_together: bool = False

    # --- failure policy (SURVEY.md section 5: spans are idempotent retry
    # units, the MapReduce task-retry analog — but retries are CLASSIFIED:
    # only transient I/O faults are re-attempted; corruption fails fast;
    # plan errors are never retried or skipped.  utils/errors.py owns the
    # taxonomy, utils/resilient.py the backoff/quarantine machinery.) ---
    span_retries: int = 2            # TRANSIENT re-decode attempts per span
    skip_bad_spans: bool = False     # after the policy: True = quarantine +
    #                                  skip (ticks pipeline.bad_spans and the
    #                                  manifest), False = raise
    max_bad_span_fraction: float = 1.0  # circuit breaker: abort once the
    #                                  quarantined fraction of planned spans
    #                                  exceeds this (1.0 = never trips)
    retry_backoff_base_s: float = 0.05  # first transient-retry delay
    retry_backoff_max_s: float = 2.0    # backoff ceiling
    io_read_retries: int = 0         # >0: wrap file sources in
    #                                  RetryingByteSource with this budget
    io_read_deadline_s: Optional[float] = None  # per-pread deadline
    check_crc: bool = False          # verify BGZF CRC32 footers on inflate

    # --- resilience (resilience/: adaptive degrade-and-heal; rides on
    # top of the failure policy above) ---
    adaptive_planes: bool = True     # decode-backend demotion ladder:
    #                                  oracle-confirmed plane-local
    #                                  faults demote device -> native ->
    #                                  zlib mid-run (byte-identical) and
    #                                  heal back via half-open probes;
    #                                  False = static plane selection
    breaker_failure_threshold: float = 3.0  # decayed failures within
    #                                  breaker_window_s that OPEN a
    #                                  fault domain's circuit
    breaker_window_s: float = 30.0   # failure-rate decay window
    breaker_cooldown_s: float = 5.0  # OPEN -> HALF_OPEN delay; also the
    #                                  retry_after hint open circuits
    #                                  report
    breaker_half_open_probes: int = 1  # concurrent probes HALF_OPEN
    #                                  admits before re-deciding
    serve_shed_retry_after_s: float = 0.1  # retry_after hint on
    #                                  admission-queue sheds (breaker
    #                                  sheds report their cooldown
    #                                  remainder instead)
    serve_prefetch_pause_pressure: float = 3.0  # registry-wide decayed
    #                                  failure count above which serve
    #                                  prefetch auto-pauses (speculative
    #                                  decode is the wrong spend under
    #                                  fault pressure)
    chaos_seed: Optional[int] = None  # seed for deterministic chaos
    #                                  schedules (tests/bench/soak);
    #                                  None = chaos only via explicit
    #                                  install_chaos / fault_points_on

    # --- crash-safe jobs (jobs/: durable journals, straggler defense;
    # the MapReduce analogs were task re-execution + speculative
    # execution) ---
    pool_task_timeout_s: Optional[float] = None  # hard per-future decode
    #                                  deadline on ACTIVE wait: queue
    #                                  time on a backlogged-but-healthy
    #                                  pool is excused up to an 8x grace
    #                                  (so a deep queue never false-
    #                                  fires, but a FULLY-wedged pool
    #                                  where nothing dequeues still
    #                                  surfaces); an overrunning task is
    #                                  abandoned and re-submitted once
    #                                  per span_retries budget, then
    #                                  raises TransientIOError — a
    #                                  wedged worker can no longer hang
    #                                  the consumer forever.  None = off
    speculative_decode: bool = True  # race a second copy of a span
    #                                  decode that outlives the job's
    #                                  soft deadline (first result wins,
    #                                  loser discarded); needs >= 16
    #                                  completed units before any
    #                                  deadline exists, so tiny runs
    #                                  never speculate
    straggler_multiplier: float = 4.0  # soft deadline = p95 of the
    #                                  decaying per-job unit-latency
    #                                  histogram x this
    straggler_min_s: float = 0.5     # soft-deadline floor: decode storms
    #                                  of sub-ms units must not
    #                                  speculate on scheduler jitter
    collective_timeout_s: Optional[float] = None  # multi-host loss
    #                                  detection: broadcast/allgather
    #                                  barriers outliving this surface
    #                                  TransientIOError (one dead host
    #                                  fails the collective fast) instead
    #                                  of blocking forever.  None = wait
    journal_fsync: bool = True       # fsync the job journal after every
    #                                  record (the durability the resume
    #                                  contract is written against);
    #                                  False trades crash-safety of the
    #                                  LAST unit for test speed

    # --- cohort variant plane (cohort/: k-way position join of
    # single-sample VCF/BCF inputs into [variants, samples] mesh tiles) ---
    cohort_chunk_sites: int = 1024   # joined sites per host column chunk
    #                                  handed to the feed pipeline (bounds
    #                                  host memory: k streams buffer one
    #                                  record each + one chunk of columns)
    cohort_quarantine_inputs: bool = True  # a sample file that faults
    #                                  mid-join (corrupt bytes, exhausted
    #                                  transient retries) is QUARANTINED:
    #                                  its column goes sentinel (-1/NaN)
    #                                  from the fault onward and the join
    #                                  completes; False = raise.  PLAN
    #                                  errors (bad paths/params) always
    #                                  raise either way
    cohort_max_quarantine_fraction: float = 0.5  # abort the build once
    #                                  more than this fraction of samples
    #                                  quarantined — a cohort that lost
    #                                  half its columns is not a result
    serve_cohort_manifests: int = 8  # cohort manifests kept resident in
    #                                  the serve tier before LRU eviction

    # --- live ops plane (obs/flight.py flight recorder + obs/slo.py
    # SLO burn accounting; `hbam top` reads both off the serve
    # transport) ---
    flight_dump_dir: Optional[str] = None  # where breaker-trip /
    #                                  demotion / deadline-miss /
    #                                  serve-error flight snapshots land
    #                                  (redacted JSON); None = the
    #                                  always-on ring stays memory-only
    #                                  (still served via {"op":"health"})
    flight_dump_cap: int = 16        # rotation cap on dump files kept
    slo_latency_s: float = 1.0       # per-tenant latency objective: a
    #                                  request slower than this spends
    #                                  error budget
    slo_target: float = 0.99         # promised good fraction
    slo_tick_s: float = 10.0         # burn-window snapshot cadence
    slo_min_events: int = 64         # window events below which burn
    #                                  reads 0 (a cold tenant's first
    #                                  slow request must not page)
    slo_shed_batch: bool = True      # shed batch-priority admissions
    #                                  for a tenant whose FAST burn
    #                                  window is alight (interactive
    #                                  traffic keeps flowing)

    # --- debug ---
    debug_keep_spill: bool = False   # keep mesh-sort .mesh-spill run dirs
    #                                  for post-mortem instead of removing
    #                                  them in the sort's finally

    # --- split planning ---
    split_size: int = 128 * 1024 * 1024   # analog of HDFS block size splits
    splitting_index_granularity: int = 4096  # records per splitting-bai sample
    use_splitting_index: bool = True      # snap splits via sidecar when present

    # --- host->device feed (parallel/staging.py) ---
    feed_ring_slots: int = 2         # preallocated group buffers in the
    #                                  staging ring (2 = one being packed
    #                                  while one is in dispatch; more buys
    #                                  slack at n_dev*cap*row_bytes each)
    feed_dispatch_depth: int = 2     # groups in flight past the packer
    #                                  (2 = double buffering: device_put k
    #                                  overlaps host repack of k+1)
    decode_pool_workers: Optional[int] = None  # shared decode pool size;
    #                                  None = min(32, max(4, 4*cpus)).
    #                                  First driver call in the process
    #                                  sizes the pool (utils/pools.py)
    use_fused_decode: bool = True    # single-pass native inflate+walk+pack
    #                                  (+CRC fold) per span, chunk-streamed
    #                                  into the staging ring; falls back to
    #                                  the two-pass oracle path when the
    #                                  native library is unavailable
    decode_chunk_blocks: int = 32    # BGZF blocks per fused decode chunk
    #                                  (~2 MiB inflated: big enough to
    #                                  amortize the walk handoff, small
    #                                  enough to stay cache-resident and
    #                                  stream tiles before the span tail
    #                                  inflates)
    inflate_backend: str = "auto"    # decode-plane selection:
    #                                  "auto"   = "native"
    #                                             (resolve_inflate_backend)
    #                                  "native" = host C++ inflate
    #                                             (+ fused single-pass)
    #                                  "zlib"   = Python zlib (portable;
    #                                             disables the fused path)

    # --- region-query serving (query/) ---
    query_cache_bytes: int = 256 << 20  # decoded-chunk LRU byte budget
    query_chunk_bytes: int = 1 << 20    # max compressed bytes coalesced
    #                                     into one cacheable chunk
    query_tile_records: int = 8192      # rows per device per predicate
    #                                     dispatch (FeedPipeline cap)
    query_max_in_flight: int = 8        # admission: concurrent queries
    query_queue_depth: int = 32         # admission: bounded wait queue;
    #                                     overflow sheds load with
    #                                     TransientIOError
    query_deadline_s: Optional[float] = None  # per-request wall budget;
    #                                     blown deadlines raise
    #                                     TransientIOError (retryable);
    #                                     anchored at ENQUEUE, so
    #                                     admission wait counts

    # --- serving (serve/: hbam serve / ServeLoop) ---
    serve_tile_cache_bytes: int = 512 << 20  # device-resident decoded-
    #                                     tile LRU budget (tier above the
    #                                     host chunk LRU; a hit skips
    #                                     fetch+inflate+host_decode)
    serve_tile_records: int = 4096      # rows per device per cached tile
    serve_prefetch: bool = True         # predictive adjacent-chunk
    #                                     prefetch at background pool
    #                                     priority
    serve_prefetch_depth: int = 2       # adjacent region windows
    #                                     prefetched per served query
    serve_recent_regions: int = 16      # per-file recency window driving
    #                                     prefetch dedup
    serve_tenant_max_in_flight: int = 4  # per-tenant admission quota
    serve_tenant_queue_depth: int = 16  # per-tenant bounded wait queue;
    #                                     overflow sheds with
    #                                     TransientIOError
    serve_max_tenants: int = 64         # idle tenant schedulers kept
    #                                     before LRU eviction
    serve_ring_slots: int = 3           # staging-ring slots for the tile
    #                                     builder (>= 3: one filling plus
    #                                     pinned-in-transfer slack)

    # --- serving fleet (serve/fleet.py, serve/membership.py) ---
    serve_replica_id: Optional[str] = None  # this process's fleet member
    #                                     id; None = not fleet-joined
    serve_peers: str = ""               # "id=host:port,..." peer list;
    #                                     empty = single-replica serving
    fleet_replication: int = 2          # R: rendezvous owners per tile
    #                                     key (self counts when ranked)
    fleet_heartbeat_s: float = 0.25     # peer heartbeat cadence
    fleet_suspicion_s: float = 1.5      # no heartbeat for this long ->
    #                                     SUSPECT (ownership unchanged)
    fleet_eviction_s: float = 5.0       # suspect for this long ->
    #                                     EVICTED from the member set
    #                                     (ownership re-ranks)
    fleet_peer_timeout_s: float = 2.0   # per-peer-call socket cap; the
    #                                     request's enqueue-anchored
    #                                     deadline still binds below it
    fleet_hedge_min_s: float = 0.05     # hedged peer-fetch soft-deadline
    #                                     floor (p95 * straggler_multiplier,
    #                                     never below this)

    # --- TPU backend ---
    backend: str = "tpu"                  # "tpu" | "cpu" (host NumPy decode)
    blocks_per_batch: int = 512           # BGZF blocks per device batch
    records_capacity_per_block: int = 2048  # SoA capacity per 64KiB block
    mesh_shape: Optional[Tuple[int, ...]] = None  # None = all local devices, 1D
    mesh_axis_names: Sequence[str] = ("data",)
    use_native: bool = True               # C++ batched inflate when available

    @classmethod
    def from_dict(cls, conf: Mapping[str, object]) -> "HBamConfig":
        """Build from a Hadoop-style string-keyed dict (reference key names)."""
        kwargs = {}
        for key, value in conf.items():
            field = _HADOOP_KEY_MAP.get(key, key)
            kwargs[field] = value
        return cls(**_coerce(kwargs))


def _coerce(kwargs: dict) -> dict:
    out = dict(kwargs)
    if "validation_stringency" in out:
        out["validation_stringency"] = ValidationStringency.parse(
            out["validation_stringency"])
    for k, default in (
        ("fastq_base_quality_encoding", BaseQualityEncoding.SANGER),
        ("qseq_base_quality_encoding", BaseQualityEncoding.ILLUMINA),
    ):
        if k in out:
            out[k] = BaseQualityEncoding.parse(out[k], default)
    for k in ("trust_exts", "vcf_trust_exts", "fastq_filter_failed_qc",
              "qseq_filter_failed_qc", "write_header", "write_terminator",
              "use_splitting_index", "use_native", "use_fused_decode",
              "keep_paired_reads_together", "skip_bad_spans",
              "debug_keep_spill", "serve_prefetch", "adaptive_planes",
              "cohort_quarantine_inputs", "speculative_decode",
              "journal_fsync", "slo_shed_batch"):
        if k in out and isinstance(out[k], str):
            out[k] = out[k].lower() in ("1", "true", "yes")
    for k in ("max_bad_span_fraction", "retry_backoff_base_s",
              "retry_backoff_max_s", "io_read_deadline_s",
              "query_deadline_s", "breaker_failure_threshold",
              "breaker_window_s", "breaker_cooldown_s",
              "serve_shed_retry_after_s",
              "serve_prefetch_pause_pressure",
              "cohort_max_quarantine_fraction", "pool_task_timeout_s",
              "straggler_multiplier", "straggler_min_s",
              "collective_timeout_s", "slo_latency_s", "slo_target",
              "slo_tick_s", "fleet_heartbeat_s", "fleet_suspicion_s",
              "fleet_eviction_s", "fleet_peer_timeout_s",
              "fleet_hedge_min_s"):
        if k in out and isinstance(out[k], str):
            out[k] = float(out[k])
    for k in ("span_retries", "io_read_retries", "feed_ring_slots",
              "feed_dispatch_depth", "decode_pool_workers",
              "decode_chunk_blocks",
              "write_compress_level", "write_parallel_workers",
              "query_cache_bytes", "query_chunk_bytes",
              "query_tile_records", "query_max_in_flight",
              "query_queue_depth",
              "serve_tile_cache_bytes", "serve_tile_records",
              "serve_prefetch_depth", "serve_recent_regions",
              "serve_tenant_max_in_flight", "serve_tenant_queue_depth",
              "serve_max_tenants", "serve_ring_slots",
              "breaker_half_open_probes", "chaos_seed",
              "cohort_chunk_sites", "serve_cohort_manifests",
              "flight_dump_cap", "slo_min_events",
              "fleet_replication"):
        if k in out and isinstance(out[k], str):
            out[k] = int(out[k])
    return out


DEFAULT_CONFIG = HBamConfig()


# ---------------------------------------------------------------------------
# Decode-plane selection.  Inflate is host work by measurement: the one
# chip run of an on-mesh DEFLATE plane (PR 21) scanned three orders of
# magnitude under the native host feed and PR 30 deleted it (PERF.md
# section 6).  What is left to choose is which host code inflates.
# ---------------------------------------------------------------------------

INFLATE_BACKENDS = ("auto", "native", "zlib")

# Decode planes, fastest first — the vocabulary plan/executor.select_plane
# (the ONE plane-gating predicate; planroute lint PL101 keeps gates out of
# every other package) decides over, and the rung order the resilience
# DemotionLadder demotes along.  "fused" is a MODE of the native plane
# (the single-pass sweep), not a plane of its own.
DECODE_PLANES = ("native", "zlib")


def resolve_inflate_backend(config: "HBamConfig | None") -> str:
    """Resolve a config's ``inflate_backend`` to a concrete plane name
    ("native" | "zlib"): a pure function of the config.  "auto" is
    "native" — the span decoders fall to zlib themselves when the native
    library is absent.

    This is only the STARTING rung, and only one input of the decision:
    per-plan routing (fused / fused-stream eligibility given intervals /
    skip_bad_spans) is decided in ``plan.executor.select_plane``, the
    single predicate table every driver consults.  With
    ``config.adaptive_planes`` the drivers run the resolved plane
    through a ``resilience.DemotionLadder`` — oracle-confirmed
    plane-local faults demote it mid-run and a half-open probe revisits
    the faster plane after the breaker cooldown."""
    backend = getattr(config, "inflate_backend", "auto") \
        if config is not None else "auto"
    if backend not in INFLATE_BACKENDS:
        # PLAN class: a bad plane name is run configuration, not data —
        # never retried, never quarantined (utils/errors classifies
        # PlanError by type; imported lazily to keep this module light)
        from hadoop_bam_tpu.utils.errors import PlanError
        raise PlanError(
            f"unknown inflate backend {backend!r}; "
            f"expected one of {INFLATE_BACKENDS}")
    return "native" if backend == "auto" else backend
