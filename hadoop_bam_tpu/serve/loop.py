"""ServeLoop: the long-running multi-tenant region-query server.

The PR-5 ``QueryEngine`` answers one batch and exits; production shape
is a RESIDENT server.  ``ServeLoop`` owns:

- one long-lived ``QueryEngine`` (host chunk LRU + metadata stay warm
  across requests, many client threads feed it safely);
- the device-resident ``DeviceTileCache`` tier above it — a warm query
  whose tiles are resident never touches fetch/inflate/host_decode and
  goes straight to the jitted interval-filter step;
- the ``Prefetcher`` (adjacent-window decode at background pool
  priority) and ``TenantQuotas`` (per-tenant admission + priority
  classes).

Threading model: clients call ``submit()`` from any thread and get a
``concurrent.futures.Future``; tenant admission blocks (bounded) on the
CLIENT's thread, then the job enters one priority heap.  A single
DISPATCHER thread drains the heap and does every jax call — device
dispatch stays single-threaded, exactly the FeedPipeline discipline —
while decode parallelism lives in the shared pool.  Each job runs under
the SUBMITTER's contextvars snapshot, so a client inside a
``MetricsContext`` gets its own isolated numbers even though the
serving and pool threads are shared (pinned by tests).

Span/metric taxonomy (PR-6 obs layer; all Prometheus-exportable):
``serve.request_wall`` / ``serve.tile_build_wall`` /
``serve.filter_wall`` spans, ``serve.latency_s`` end-to-end histogram
(enqueue -> result, admission wait included), ``serve.queue_wait_s``,
``serve.filter_launches`` (filter-step launches, one per tile group),
``serve.tile_hits/misses/evictions``, ``serve.prefetch_issued/useful``,
and ``query.deadline_misses`` for jobs that finish past their budget.
"""
from __future__ import annotations

import concurrent.futures as cf
import contextvars
import dataclasses
import heapq
import itertools
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.obs import flight
from hadoop_bam_tpu.obs.context import ensure_trace
from hadoop_bam_tpu.obs.slo import SloEngine
from hadoop_bam_tpu.query.engine import QueryEngine, _I32_MAX
from hadoop_bam_tpu.serve.prefetch import Prefetcher
from hadoop_bam_tpu.serve.tenancy import TenantQuotas, priority_rank
from hadoop_bam_tpu.plan.executor import select_chunk_source
from hadoop_bam_tpu.serve.tiles import (
    INTERVAL_PROJECTION, DeviceTileCache, TileBuilder,
    make_tile_filter_step, tile_key,
)
from hadoop_bam_tpu.utils.errors import (
    PLAN, CorruptDataError, PlanError, TransientIOError, classify_error,
)
from hadoop_bam_tpu.utils.metrics import (
    METRICS, base_metrics, current_metrics,
)


@dataclasses.dataclass
class ServeResult:
    """One served region: the match count is always computed (tile
    path); ``records`` materialize only when asked for.  ``extra``
    carries projection-specific aggregates (the cohort plane reports
    ``n_samples`` / ``mean_af`` / ``quarantined`` through it) and
    rides the wire doc verbatim."""
    region: str
    count: int
    n_candidates: int
    tile_hits: int               # chunks served from resident tiles
    tile_misses: int             # chunks that needed a tile build
    records: Optional[List[object]] = None
    extra: Optional[Dict[str, object]] = None


@dataclasses.dataclass(order=True)
class _Job:
    rank: int                    # priority class (lower first)
    seq: int                     # FIFO within a class
    tenant: str = dataclasses.field(compare=False)
    path: str = dataclasses.field(compare=False)
    regions: Sequence[str] = dataclasses.field(compare=False)
    want_records: bool = dataclasses.field(compare=False)
    deadline: object = dataclasses.field(compare=False)
    admission: object = dataclasses.field(compare=False)   # entered CM
    future: cf.Future = dataclasses.field(compare=False)
    ctx: contextvars.Context = dataclasses.field(compare=False)
    t_enqueue: float = dataclasses.field(compare=False)
    # cohort-slice request: ``path`` is a cohort manifest JSON and the
    # regions slice the joined [variants, samples] tensor
    cohort: bool = dataclasses.field(compare=False, default=False)


class ServeLoop:
    """The resident server (module docstring).  Use as a context
    manager, or ``start()``/``stop()`` explicitly; ``submit()``
    auto-starts."""

    def __init__(self, config: HBamConfig = DEFAULT_CONFIG,
                 engine: Optional[QueryEngine] = None, mesh=None,
                 fleet=None):
        self.config = config
        self.engine = engine if engine is not None else QueryEngine(
            config=config, mesh=mesh)
        # the serving fleet (serve/fleet.py): explicit injection wins
        # (tests drive injectable clocks); otherwise auto-built when the
        # config names a replica id AND a peer roster.  None = the
        # single-replica serving every prior PR shipped, untouched.
        if fleet is None and getattr(config, "serve_replica_id", None) \
                and getattr(config, "serve_peers", ""):
            from hadoop_bam_tpu.serve.fleet import Fleet
            fleet = Fleet(config)
        self.fleet = fleet
        self.tiles = DeviceTileCache(
            int(getattr(config, "serve_tile_cache_bytes", 512 << 20)))
        self.tenants = TenantQuotas(config)
        self.prefetcher = Prefetcher(self.engine, config)
        # SLO burn accounting (obs/slo.py): per-tenant latency
        # objectives over the server's PROCESS-GLOBAL metrics — client
        # MetricsContexts isolate per-request numbers, so the serving
        # path mirrors its latency observations into base_metrics()
        # where the engine (and the metrics transport op) read them
        self.slo = SloEngine(
            tick_s=float(getattr(config, "slo_tick_s", 10.0)),
            min_events=int(getattr(config, "slo_min_events", 64)))
        self.slo_metrics = base_metrics()
        self.slo_latency_s = float(getattr(config, "slo_latency_s", 1.0))
        self.slo_target = float(getattr(config, "slo_target", 0.99))
        self.slo.ensure_latency("latency/_all", "serve.latency_s",
                                self.slo_latency_s, self.slo_target)
        self.tenants.slo_engine = self.slo
        # tenants with mirrored per-tenant series, LRU-bounded: tenant
        # strings are CLIENT input, and without eviction every distinct
        # string would grow the process-global metrics forever (the
        # SV801 discipline; the quota LRU bounds gates, not metric keys)
        self._slo_tenants: "OrderedDict[str, bool]" = OrderedDict()
        # flight-recorder disk dumps: configured from this loop's config
        # when set (unset leaves the process-wide recorder as-is, so a
        # directory installed by the CLI or a test is not clobbered)
        fdir = getattr(config, "flight_dump_dir", None)
        if fdir:
            flight.recorder().configure(
                dump_dir=fdir,
                dump_cap=int(getattr(config, "flight_dump_cap", 16)))
        self.tile_cap = int(getattr(config, "serve_tile_records", 4096))
        self._builder: Optional[TileBuilder] = None
        self._cohort = None          # lazy cohort/serving.CohortServer
        self._cond = threading.Condition()
        self._heap: List[_Job] = []
        self._seq = itertools.count()
        self._thread: Optional[threading.Thread] = None
        self._stopping = False

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "ServeLoop":
        with self._cond:
            if self._thread is None or not self._thread.is_alive():
                self._stopping = False
                self._thread = threading.Thread(
                    target=self._dispatch_loop, name="hbam-serve",
                    daemon=True)
                self._thread.start()
        if self.fleet is not None:
            self.fleet.start()
        return self

    def stop(self) -> None:
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
        if self.fleet is not None:
            self.fleet.stop()
        self.prefetcher.stop()
        # anything still queued will never run: fail it loudly as
        # retryable (a restarting server is a transient condition)
        with self._cond:
            leftovers, self._heap = self._heap, []
        for job in leftovers:
            self._finish_admission(job)
            job.future.set_exception(
                TransientIOError("serve loop stopped before this "
                                 "request was dispatched — retry",
                                 retry_after_s=1.0))
        if self._builder is not None:
            self._builder.close()

    def __enter__(self) -> "ServeLoop":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client surface ------------------------------------------------------

    def submit(self, path: str, regions: Sequence[str], *,
               tenant: str = "default", priority: str = "interactive",
               deadline_s: Optional[float] = None,
               want_records: bool = False,
               cohort: bool = False) -> cf.Future:
        """Enqueue one request (a path + its regions) for serving.

        Blocks (bounded) on THIS thread for tenant admission — the
        backpressure lands on the flooding client — then returns a
        Future of ``[ServeResult, ...]``.  Over-quota tenants shed with
        ``TransientIOError``; bad parameters raise ``PlanError``.

        With ``cohort=True``, ``path`` names a cohort manifest JSON and
        each region is answered from the device-resident joined dosage
        tiles (cohort/serving.py) instead of the per-file index path."""
        if not regions:
            raise PlanError("submit() needs at least one region")
        rank = priority_rank(priority)
        with self._cond:
            if self._stopping:
                # a stopped loop sheds instead of silently resurrecting:
                # restart is an explicit start() by whoever owns the loop
                raise TransientIOError("serve loop is stopped — retry "
                                       "after it restarts",
                                       retry_after_s=1.0)
        if self._thread is None:
            self.start()
        # request identity: join the transport/CLI trace when one is
        # active, mint one for direct library callers — the contextvars
        # snapshot below carries it to the dispatcher, the decode pool
        # and the staging packer, so every span of this request shares
        # one trace_id end to end
        with ensure_trace(op="serve.submit", tenant=tenant,
                          deadline_s=deadline_s):
            # entered HERE (client thread: admission wait + shed happen
            # to the submitter); exited by the dispatcher when the job
            # finishes
            admission = self.tenants.admit(tenant, deadline_s,
                                           priority=priority)
            deadline = admission.__enter__()
            job = _Job(rank=rank, seq=next(self._seq), tenant=tenant,
                       path=path, regions=list(regions),
                       want_records=bool(want_records), deadline=deadline,
                       admission=admission, future=cf.Future(),
                       ctx=contextvars.copy_context(),
                       t_enqueue=time.perf_counter(), cohort=bool(cohort))
        with self._cond:
            if self._stopping:
                self._finish_admission(job)
                raise TransientIOError("serve loop is stopping — retry",
                                       retry_after_s=1.0)
            heapq.heappush(self._heap, job)
            self._cond.notify()
        return job.future

    def query(self, path: str, regions: Sequence[str],
              **kwargs) -> List[ServeResult]:
        """Blocking convenience: ``submit(...).result()``."""
        return self.submit(path, regions, **kwargs).result()

    def stats(self) -> Dict[str, object]:
        out = {"tiles": self.tiles.stats(),
               "chunks": self.engine.cache.stats(),
               "prefetch": self.prefetcher.stats(),
               "tenants": self.tenants.stats()}
        if self._cohort is not None:
            out["cohort"] = self._cohort.stats()
        return out

    def health(self) -> Dict[str, object]:
        """The degraded-mode diagnosis surface (``{"op": "health"}`` on
        the wire, and the CLI's shutdown report): loop liveness plus
        every adaptive-policy state — tenant breakers, the resilience
        registry's fault domains (decode-ladder + quarantine circuits),
        registry fault pressure, and whether prefetch auto-paused."""
        from hadoop_bam_tpu import resilience
        from hadoop_bam_tpu.plan.executor import plane_report

        reg = resilience.registry()
        with self._cond:
            stopping = self._stopping
            queued = len(self._heap)
        from hadoop_bam_tpu.utils import pools

        return {
            "status": "stopping" if stopping else "serving",
            "queued": queued,
            # the routing this process would decide right now, per
            # driver family (plan/executor.select_plane — display only,
            # consumes no breaker probes): what `hbam top` shows when
            # an operator asks "which plane is this server actually on"
            "planes": plane_report(self.config),
            "fault_pressure": round(reg.fault_pressure(), 4),
            "open_breakers": reg.open_breakers(),
            "domains": reg.states(),
            "tenant_breakers": self.tenants.breaker_states(),
            "prefetch": self.prefetcher.stats(),
            "tiles": self.tiles.stats(),
            # the live-ops additions: recent flight-recorder state (the
            # ring a breaker trip would dump), SLO burn rates, and pool
            # occupancy — the surfaces `hbam top` renders
            "flight": flight.recorder().stats(),
            "slo": self.slo.summary(self.slo_metrics),
            "pool": pools.pool_stats(),
            # fleet view: membership/ownership, per-peer breakers,
            # degraded flag, peer-fetch + hedge counters (None when
            # this process serves single-replica)
            "fleet": (self.fleet.states()
                      if self.fleet is not None else None),
        }

    # -- dispatcher ----------------------------------------------------------

    @staticmethod
    def _finish_admission(job: _Job) -> None:
        try:
            job.admission.__exit__(None, None, None)
        except Exception:  # noqa: BLE001 — release must never mask results
            pass

    def _dispatch_loop(self) -> None:
        while True:
            with self._cond:
                while not self._heap and not self._stopping:
                    self._cond.wait(0.1)
                if self._stopping:
                    return
                job = heapq.heappop(self._heap)
            try:
                # run under the SUBMITTER's contextvars snapshot: the
                # client's MetricsContext (and anything the decode pool
                # inherits from here) stays isolated per client
                job.ctx.run(self._run_job, job)
            except BaseException as e:  # noqa: BLE001 — keep serving
                if not job.future.done():
                    job.future.set_exception(e)

    def _run_job(self, job: _Job) -> None:
        t_run = time.perf_counter()
        METRICS.observe("serve.queue_wait_s", t_run - job.t_enqueue)
        try:
            with METRICS.span("serve.request_wall", tenant=job.tenant,
                              regions=len(job.regions)):
                results = [self._serve_region(job, region)
                           for region in job.regions]
            # outcome is recorded BEFORE the future resolves: a client
            # that saw its request fail and immediately retries must
            # find the breaker already fed (recording after set_result
            # races the next submit)
            self.tenants.record_outcome(job.tenant, None)
            job.future.set_result(results)
        except BaseException as e:  # noqa: BLE001 — crosses to the client
            # feed the tenant's half-open breaker: repeated serving
            # failures open it and the tenant sheds at admission until
            # a cooled-down probe succeeds (PLAN-class rejections are
            # the client's problem and never count)
            self.tenants.record_outcome(job.tenant, e)
            # an unhandled (non-PLAN) serving error is incident-grade:
            # snapshot the flight ring while the request's trace is
            # still the active context
            if classify_error(e) != PLAN:
                flight.recorder().dump("serve_error", error=str(e))
            job.future.set_exception(e)
        finally:
            lat = time.perf_counter() - job.t_enqueue
            METRICS.observe("serve.latency_s", lat)
            # mirror into the process-global metrics the SLO engine and
            # the metrics transport op read (a client MetricsContext
            # isolates the per-request view; the server still needs its
            # own aggregate), plus the per-tenant series hbam top and
            # the per-tenant SLO objectives consume.  Tenant cardinality
            # is bounded by the TenantQuotas LRU upstream of here.
            m = self.slo_metrics
            if current_metrics() is not m:
                # not already recorded there by the METRICS proxy above
                m.observe("serve.latency_s", lat)
            self._note_slo_tenant(job.tenant)
            m.observe(f"serve.latency_s.{job.tenant}", lat)
            m.count(f"serve.requests.{job.tenant}")
            self.slo.ensure_latency(
                f"latency/{job.tenant}",
                f"serve.latency_s.{job.tenant}",
                self.slo_latency_s, self.slo_target)
            self.slo.tick(m)
            if job.deadline is not None and job.deadline.expired:
                job.deadline.book_miss()
            self._finish_admission(job)

    def _note_slo_tenant(self, tenant: str) -> None:
        """Track (and LRU-bound) the tenants with mirrored per-tenant
        series; evicting one discards its metric keys so arbitrary
        client tenant strings cannot grow the process-global Metrics
        without bound.  Dispatcher-thread only."""
        lru = self._slo_tenants
        if tenant in lru:
            lru.move_to_end(tenant)
            return
        lru[tenant] = True
        cap = max(1, int(getattr(self.config, "serve_max_tenants", 64)))
        while len(lru) > cap:
            old, _ = lru.popitem(last=False)
            self.slo_metrics.discard_series(
                f"serve.latency_s.{old}", f"serve.requests.{old}")

    def _builder_or_make(self) -> TileBuilder:
        if self._builder is None:
            mesh = self.engine._mesh_or_make()
            self._builder = TileBuilder(
                mesh, self.tile_cap,
                int(getattr(self.config, "serve_ring_slots", 3)))
        return self._builder

    def _cohort_or_make(self):
        if self._cohort is None:
            from hadoop_bam_tpu.cohort.serving import CohortServer
            self._cohort = CohortServer(self.engine._mesh_or_make(),
                                        self.config)
        return self._cohort

    def _serve_region(self, job: _Job, region: str) -> ServeResult:
        if job.cohort:
            # the cohort plane: joined [variants, samples] tiles in the
            # SAME device cache, keyed by the manifest identity
            return self._cohort_or_make().serve(
                job.path, region, self.tiles,
                want_records=job.want_records, deadline=job.deadline)
        engine = self.engine
        job.deadline.check("serve resolve")
        meta = engine._file_meta(job.path)
        iv, ranges = engine._resolve(meta, region)
        chunks = engine._coalesce(ranges, meta.kind)
        builder = self._builder_or_make()
        step = make_tile_filter_step(builder.mesh)
        rid = meta.ref_names.index(iv.rname)
        iv_dev = builder.put_interval([
            rid, min(iv.start, int(_I32_MAX)), min(iv.end, int(_I32_MAX))])

        fleet = self.fleet
        degraded = fleet.degraded() if fleet is not None else False
        if degraded:
            fleet.note_degraded()
        count = 0
        n_candidates = 0
        tile_hits = 0
        tile_misses = 0
        peer_chunks = 0
        rows_per_chunk: List[Tuple[Tuple, np.ndarray, int]] = []
        for s, e in chunks:
            job.deadline.check("serve chunk")
            key = tile_key(meta.ident, meta.kind, s, e,
                           builder.n_dev, builder.cap)
            tiles = self.tiles.get(key)
            if tiles is None:
                tile_misses += 1
                value = None
                if fleet is not None:
                    # chunk-source routing is the executor's decision
                    # (plan/executor.select_chunk_source — the
                    # select_plane discipline applied to the fleet), the
                    # loop only consumes it
                    okey = (meta.ident, (s, e), INTERVAL_PROJECTION)
                    owner_ids = fleet.membership.owners_for(
                        okey, fleet.replication)
                    source, _why = select_chunk_source(
                        tile_cached=False,
                        fleet_owned=fleet.replica_id in owner_ids,
                        degraded=degraded,
                        want_records=job.want_records,
                        peer_ready=any(pid in fleet.peers
                                       for pid in owner_ids))
                    if source == "peer":
                        try:
                            value = fleet.fetch_chunk(
                                job.path, okey, s, e,
                                deadline=job.deadline)
                            peer_chunks += 1
                        except (TransientIOError, CorruptDataError,
                                RuntimeError, OSError, ValueError):
                            # every owner failed/hedged out: decode
                            # locally — sick peers never fail a request
                            # this replica can answer itself (the
                            # deadline still binds the fallback)
                            METRICS.count("fleet.peer_fallback_local")
                            value = None
                if value is None:
                    value = engine._chunk(meta, s, e)
                    # ticks serve.prefetch_useful when the host
                    # chunk was decoded ahead of need
                    self.prefetcher.was_prefetched(
                        engine.chunk_key(meta, s, e))
                    if fleet is not None:
                        fleet.note_local_decode()
                tiles = builder.build(meta.ident, value)
                quarantined = (int(value["n"]) == 0
                               and int(value["nbytes"]) == 0)
                if not quarantined:
                    self.tiles.put(key, tiles)
                else:
                    # a QUARANTINED chunk (skip_bad_spans healing path:
                    # n=0 AND nbytes=0 — a genuinely empty chunk always
                    # accounts >= 64 bytes) serves as empty but is NOT
                    # cached at either tier, so a healed transient fault
                    # re-decodes instead of returning empty forever
                    METRICS.count("serve.tiles_uncached_quarantine")
            else:
                tile_hits += 1
            n_candidates += tiles.n
            masks: List[np.ndarray] = []
            with METRICS.span("serve.filter_wall"):
                for g in tiles.groups:
                    keep, hits = step(*g.cols, g.counts, iv_dev)
                    # count-only serving reads just the [n_dev] match
                    # counts — a few bytes off the mesh; the full mask
                    # materializes only for records mode
                    count += int(np.asarray(hits).sum())
                    if job.want_records:
                        masks.append(np.asarray(keep))
            METRICS.count("serve.filter_launches", len(tiles.groups))
            if job.want_records and masks:
                rows_per_chunk.append((
                    (s, e), self._flat_rows(masks, builder), tiles.n))
        records = None
        if job.want_records:
            records = self._materialize(meta, rows_per_chunk)
        METRICS.count("serve.requests")
        self.prefetcher.note(meta, iv)
        extra = None
        if fleet is not None:
            # fleet provenance rides the wire doc verbatim: which
            # replica answered, whether it was partitioned (degraded
            # mode serves owned data instead of erroring), and how many
            # chunks arrived pre-decoded from peers
            extra = {"replica": fleet.replica_id}
            if degraded:
                extra["degraded"] = True
            if peer_chunks:
                extra["peer_chunks"] = peer_chunks
        return ServeResult(region=region, count=count,
                           n_candidates=n_candidates,
                           tile_hits=tile_hits, tile_misses=tile_misses,
                           records=records, extra=extra)

    @staticmethod
    def _flat_rows(masks: List[np.ndarray], builder: TileBuilder
                   ) -> np.ndarray:
        """Chunk-local row indices of kept rows, undoing the serial
        group/device packing of ``TileBuilder.build``."""
        rows: List[int] = []
        per_group = builder.n_dev * builder.cap
        for g_idx, k in enumerate(masks):
            for dev in range(builder.n_dev):
                hit = np.flatnonzero(k[dev])
                rows.extend(g_idx * per_group + dev * builder.cap + hit)
        return np.asarray(sorted(rows), dtype=np.int64)

    def _materialize(self, meta, rows_per_chunk) -> List[object]:
        """Host record objects for kept rows: the host chunk tier has
        (or re-decodes, byte-identically) the materializer state."""
        out: List[object] = []
        for (s, e), rows, _n in rows_per_chunk:
            value = self.engine._chunk(meta, s, e)
            for row in rows:
                out.append(QueryEngine._materialize(meta, value, int(row)))
        return out
