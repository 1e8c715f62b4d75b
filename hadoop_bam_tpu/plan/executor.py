"""The one executor: plane selection, dispatch, and the driver seam.

Two jobs live here, and ONLY here:

1. **Plane selection** (``select_plane``): the single predicate table
   that decides which decode plane a plan runs on and why every other
   plane was rejected.  The gates — ``intervals``, ``skip_bad_spans``,
   ``inflate_backend``, fused availability — used to be re-implemented
   per driver (three independent copies in ``parallel/pipeline.py``
   alone); the ``planroute`` lint analyzer (PL101) now keeps
   plane-gating conditionals out of every package but this one.

2. **Execution** (``execute``): the uniform entry the rewired drivers
   funnel through.  A driver is a thin plan *builder*
   (``plan/builders.py``); ``execute`` dispatches the compiled plan to
   its family runner, counting executions and stamping the
   ``plan.execute_wall`` span, and owns the generic wiring — the cohort
   tensor feed is wired HERE (the scan feed's lazy form), and the
   query-chunk runner owns ``decode_with_retry`` + the
   ``query.decode_wall``/chunk metrics taxonomy.  Family runners that
   need the mesh-feed machinery of ``parallel/pipeline.py`` delegate to
   its ``_*_impl`` functions, which consume the decision this module
   computed instead of re-deriving gates.

Decode planes (``config.DECODE_PLANES``): "native" (host C++ inflate,
with the fused single-pass sweep as a MODE when eligible) and "zlib"
(portable Python).  Inflate, record walk and row pack are host work by
measurement (PERF.md section 6, PR 30); the mesh runs unpack + reduce /
sort / filter.  ``resolve_inflate_backend`` (config.py) turns "auto"
into the starting rung; the ``DemotionLadder`` (resilience/domains.py)
may still demote mid-run.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, Optional, Tuple

from hadoop_bam_tpu.config import (
    DECODE_PLANES, DEFAULT_CONFIG, HBamConfig, resolve_inflate_backend,
)
from hadoop_bam_tpu.plan.ir import PlanIR
from hadoop_bam_tpu.utils.errors import PlanError
from hadoop_bam_tpu.obs import context as trace_ctx
from hadoop_bam_tpu.obs.trace import active_recorder
from hadoop_bam_tpu.utils.metrics import METRICS, process_usage


# ---------------------------------------------------------------------------
# plane selection — THE predicate table
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlaneDecision:
    """One plan's resolved routing: the selected plane (the backend
    string the span-level decoders consume), fused-mode eligibility,
    and — for ``hbam explain`` — why each rejected plane/mode failed
    its gate."""
    plane: str            # resolve_inflate_backend(config) result
    use_fused: bool       # fused single-pass native sweep eligible
    stream_fused: bool    # chunk-streamed fused decode eligible
    rejected: Tuple[Tuple[str, str], ...]   # (plane_or_mode, reason)

    def to_doc(self) -> Dict:
        return {"plane": self.plane, "use_fused": self.use_fused,
                "stream_fused": self.stream_fused,
                "rejected": {p: r for p, r in self.rejected}}


def _use_fused(config: Optional[HBamConfig],
               inflate_backend: str = "auto") -> bool:
    """Fused-path eligibility: the config knob (default on), a native
    backend choice, and the fused entry points actually loadable.  The
    span-level decoders (``decode_span_*``) consult this directly —
    they run under per-span ladder demotion, below the plan grain."""
    from hadoop_bam_tpu.ops import inflate as inflate_ops

    cfg = config if config is not None else DEFAULT_CONFIG
    return (bool(cfg.use_fused_decode)
            and inflate_backend in ("auto", "native")
            and inflate_ops.fused_available())


def select_plane(config: Optional[HBamConfig], *,
                 intervals=None) -> PlaneDecision:
    """THE plane-selection predicate table (module docstring).

    ``intervals`` is the parsed interval filter (None = no filtering —
    the gates test identity, matching the drivers' historical
    ``intervals is None``).  Native-library absence gates the fused
    mode (``fused_available`` implies native); the span decoders fall
    to zlib themselves when the library is absent.  Every family decides
    through these same gates."""
    from hadoop_bam_tpu.ops import inflate as inflate_ops

    cfg = config if config is not None else DEFAULT_CONFIG
    plane = resolve_inflate_backend(cfg)
    rejected = []

    fused = True
    if not cfg.use_fused_decode:
        fused = False
        rejected.append(("fused", "config.use_fused_decode is off"))
    elif plane != "native":
        fused = False
        rejected.append(
            ("fused", f"backend {plane!r} disables the native "
                      f"fused sweep"))
    elif not inflate_ops.fused_available():
        fused = False
        rejected.append(
            ("fused", "native fused entry points unavailable"))

    if plane == "zlib":
        rejected.append(
            ("native", "inflate_backend='zlib' pins the portable "
                       "plane"))

    stream = fused and intervals is None and not cfg.skip_bad_spans
    if fused and not stream:
        rejected.append(
            ("fused-stream",
             "interval filtering needs the whole span's offsets"
             if intervals is not None
             else "skip_bad_spans needs span-granular quarantine"))
    assert plane in DECODE_PLANES
    return PlaneDecision(plane=plane, use_fused=fused,
                         stream_fused=stream, rejected=tuple(rejected))


def select_chunk_source(*, tile_cached: bool, fleet_owned: bool,
                        degraded: bool, want_records: bool,
                        peer_ready: bool) -> Tuple[str, str]:
    """THE chunk-source predicate for the serving fleet: which plane
    answers one chunk of a region query — ``"tile"`` (device-resident
    tile, no work), ``"local"`` (host fetch+inflate+decode on this
    replica), or ``"peer"`` (fetch the decoded columns from the chunk's
    rendezvous owner, so a warm peer beats local host decode).

    Lives HERE for the same reason ``select_plane`` does: the serving
    loop consumes a decision instead of re-deriving routing gates, and
    ``hbam explain``/health surfaces can show why a chunk went where.
    Returns ``(source, reason)``."""
    if tile_cached:
        return "tile", "device-resident tile hit"
    if degraded:
        # quorum lost: serve what we own locally rather than erroring —
        # peers we cannot see cannot be owners we can reach
        return "local", "degraded partition mode (no quorum)"
    if want_records:
        # record materialization reads the host chunk anyway; a peer
        # round trip would be pure overhead on top of the local decode
        return "local", "records mode needs the local host chunk"
    if fleet_owned:
        return "local", "this replica is a rendezvous owner"
    if not peer_ready:
        return "local", "no reachable peer owner (breakers/eviction)"
    return "peer", "peer-owned chunk: fetch decoded columns"


# the driver families ``plane_report`` shows a decision for
PLANE_FAMILIES = ("flagstat", "payload", "variant", "serve")


def plane_report(config: Optional[HBamConfig] = None) -> Dict[str, Dict]:
    """Display-only decision table per driver family for this process +
    config — the ``hbam serve`` health surface.  Never touches files;
    the interval gate is approximated by whether ``config.bam_intervals``
    is set."""
    cfg = config if config is not None else DEFAULT_CONFIG
    intervals = () if getattr(cfg, "bam_intervals", None) else None
    return {name: select_plane(cfg, intervals=intervals).to_doc()
            for name in PLANE_FAMILIES}


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def execute(plan: PlanIR, *, config: Optional[HBamConfig] = None,
            **kw):
    """Run a compiled plan.  ``kw`` carries the family runner's
    execution-time context (mesh, header, pinned spans, geometry,
    quarantine manifest, prefetch depth, and family extras like the
    query runner's ``decode_fn`` or the cohort runner's ``dataset``).

    Returns whatever the sink promises: a stats dict, a lazy tensor
    batch iterator, or the query tier's (columns, cache-cost) pair."""
    cfg = config if config is not None else DEFAULT_CONFIG
    # a backend name the config cannot resolve is a PlanError before any
    # runner starts (not every family consults select_plane)
    resolve_inflate_backend(cfg)
    runner = _runner_for(plan)
    METRICS.count("plan.executions")
    if getattr(runner, "lazy_sink", False):
        # generator sinks: a span here would close at dispatch,
        # microseconds in — a mixed-semantics series next to the eager
        # sinks' full-run walls.  The iteration's own stage spans
        # (cohort.*) already cover the work.
        return runner(plan, cfg, kw)
    # the execute clock (what feed.first_dispatch_wait is measured from)
    # and, while a recorder is active, what the whole process — every
    # thread, the native workers and the text inflaters included — spent
    # while the runner ran (not always: under the chip host's sandboxed
    # kernel the two getrusage calls cost a 0.27 s scan ~1 %, and a serve
    # chunk would pay them too)
    t0 = time.perf_counter()
    with METRICS.span("plan.execute_wall", sink=plan.sink.kind,
                      fmt=plan.source.fmt), trace_ctx.execute_clock(t0):
        u0 = process_usage() if active_recorder() is not None else None
        try:
            return runner(plan, cfg, kw)
        finally:
            if u0 is not None:
                METRICS.count("exec.wall_ns",
                              int((time.perf_counter() - t0) * 1e9))
                for name, a, b in zip(("cpu_user_ns", "cpu_sys_ns"),
                                      u0, process_usage()):
                    METRICS.count(f"exec.{name}", b - a)


def _runner_for(plan: PlanIR):
    kind = plan.sink.kind
    if kind == "flagstat":
        return _run_flagstat
    if kind == "seq_stats":
        return _run_seq_stats
    if kind == "variant_stats":
        return _run_variant_stats
    if kind == "variant_gwas":
        return _run_variant_gwas
    if kind == "chunk_columns":
        return _run_chunk_columns
    if kind == "tensor_batches" and plan.source.role == "join":
        return _run_cohort_batches
    if kind == "bam_file":
        return _run_mkdup
    raise PlanError(
        f"no executor runner for sink {kind!r} "
        f"(source role {plan.source.role!r}) — known sinks: flagstat, "
        f"seq_stats, variant_stats, variant_gwas, chunk_columns, "
        f"join/tensor_batches, "
        f"bam_file")


def _run_flagstat(plan: PlanIR, cfg: HBamConfig, kw: Dict):
    from hadoop_bam_tpu.parallel import pipeline

    return pipeline._flagstat_impl(
        plan.source.path, mesh=kw.get("mesh"), config=cfg,
        geometry=kw.get("geometry"), header=kw.get("header"),
        spans=kw.get("spans"), prefetch=kw.get("prefetch", 2),
        quarantine=kw.get("quarantine"))


def _run_seq_stats(plan: PlanIR, cfg: HBamConfig, kw: Dict):
    """Payload stats, the runner by the source's format: BAM through the
    fused decode feed; FASTQ / QSEQ (text through the chunk tokeniser)
    and CRAM (the columnar slice decoder) through the read-payload
    runner, which picks the unit by the format."""
    from hadoop_bam_tpu.parallel import pipeline

    if plan.source.fmt in ("fastq", "qseq", "cram"):
        return pipeline._read_stats_impl(
            plan.source.path, plan.source.fmt, mesh=kw.get("mesh"),
            config=cfg, geometry=kw.get("geometry"), spans=kw.get("spans"),
            prefetch=kw.get("prefetch", 2),
            quarantine=kw.get("quarantine"))
    return pipeline._seq_stats_impl(
        plan.source.path, mesh=kw.get("mesh"), config=cfg,
        geometry=kw.get("geometry"), header=kw.get("header"),
        spans=kw.get("spans"), prefetch=kw.get("prefetch", 2),
        quarantine=kw.get("quarantine"))


def _run_variant_stats(plan: PlanIR, cfg: HBamConfig, kw: Dict):
    from hadoop_bam_tpu.parallel import variant_pipeline

    return variant_pipeline._variant_stats_impl(
        plan.source.path, mesh=kw.get("mesh"), config=cfg,
        geometry=kw.get("geometry"), header=kw.get("header"),
        spans=kw.get("spans"), prefetch=kw.get("prefetch", 2))


def _run_variant_gwas(plan: PlanIR, cfg: HBamConfig, kw: Dict):
    """``hbam vcf-gwas``: the trait file rides the ``assoc_scan`` op node
    (part of the plan's identity)."""
    from hadoop_bam_tpu.parallel import variant_pipeline

    traits = dict(next(op for op in plan.ops
                       if op.op == "assoc_scan").params)["traits"]
    return variant_pipeline._variant_gwas_impl(
        plan.source.path, traits, mesh=kw.get("mesh"), config=cfg,
        geometry=kw.get("geometry"), header=kw.get("header"),
        spans=kw.get("spans"), prefetch=kw.get("prefetch", 2),
        return_table=bool(kw.get("return_table", False)))


def _run_mkdup(plan: PlanIR, cfg: HBamConfig, kw: Dict):
    """The fused preprocessing pipeline: the ``bam_file`` sink names the
    output, the ``markdup`` op node carries the output-affecting
    options (both under the plan digest the journal pins)."""
    from hadoop_bam_tpu.prep.pipeline import markdup_bam_mesh

    md = dict(next(op for op in plan.ops if op.op == "markdup").params)
    sink = dict(plan.sink.params)
    return markdup_bam_mesh(
        plan.source.path, sink["path"], mesh=kw.get("mesh"),
        config=cfg, header=kw.get("header"),
        remove_duplicates=bool(md.get("remove_duplicates", False)),
        library_from=md.get("library_from", "none"),
        round_records=kw.get("round_records"),
        journal_path=kw.get("journal_path"))


def _run_chunk_columns(plan: PlanIR, cfg: HBamConfig, kw: Dict):
    """Query-engine chunk decode: ONE pinned span through
    ``decode_with_retry`` under the query metrics taxonomy.  Returns
    the ``(columns, cache_cost)`` pair ``ChunkCache.get_or_compute``
    stores — cost None on a quarantined chunk, so a healed transient
    fault re-decodes on the next query instead of caching emptiness."""
    import numpy as np

    from hadoop_bam_tpu.parallel.pipeline import decode_with_retry
    from hadoop_bam_tpu.split.spans import FileVirtualSpan

    decode_fn = kw["decode_fn"]
    (path, s, e), = plan.spans.pinned
    span = FileVirtualSpan(path, s, e)
    t0 = time.perf_counter()
    with METRICS.span("query.decode_wall", kind=plan.source.fmt):
        value = decode_with_retry(decode_fn, span, cfg)
    # per-chunk fetch+decode latency/size distributions: cache misses
    # only — the p99 here is what a cold region costs
    METRICS.observe("query.chunk_fetch_s", time.perf_counter() - t0)
    if value is None:
        # config.skip_bad_spans quarantined the chunk: serve it as
        # empty (the scan drivers' skip semantics), and do NOT cache
        METRICS.count("query.chunks_skipped")
        return ({"rid": np.empty(0, np.int32),
                 "pos1": np.empty(0, np.int32),
                 "end1": np.empty(0, np.int32),
                 "records": [], "n": 0, "nbytes": 0}, None)
    METRICS.observe("query.chunk_bytes", int(value["nbytes"]))
    METRICS.count("query.chunks_decoded")
    return (value, int(value["nbytes"]))


def _run_cohort_batches(plan: PlanIR, cfg: HBamConfig,
                        kw: Dict) -> Iterator[Dict]:
    """The cohort tensor feed, wired by the executor: joined site
    chunks through the scan feed's lazy form (``parallel/scan.py``; the
    first chunk's columns name the schema), every batch padded to the
    full tile height.  A generator, so a dataset whose ``tensor_batches``
    is built but never iterated starts no join (and opens no journal)."""
    dataset = kw["dataset"]

    def gen():
        from hadoop_bam_tpu.parallel.scan import ScanFeed

        geometry = kw.get("geometry")
        if geometry is None:
            geometry = dataset.geometry
        scan = ScanFeed("cohort", cfg, kw.get("mesh"), None,
                        geometry.tile_records, fixed_shape=True)
        yield from scan.batches(dataset.site_chunks())

    return gen()


_run_cohort_batches.lazy_sink = True   # see execute(): no dispatch span
