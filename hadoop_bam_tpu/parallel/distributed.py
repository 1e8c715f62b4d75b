"""Multi-host coordination: init, single-planner broadcast, span assignment.

The reference's "distributed backend" was Hadoop's (SURVEY.md section 2.9):
HDFS for placement, YARN for scheduling, one client-side getSplits() whose
result rode the job config to every task.  The TPU rebuild keeps that shape:

- ``initialize()`` — jax.distributed bootstrap (no-op single-host);
- ``broadcast_plan()`` — host 0 plans spans (guessers/index probing do real
  I/O and inflation, so they must run once, not per host — the analog of
  client-side split planning at job submission), every host receives the
  JSON-serialized plan over the ICI/DCN collective fabric;
- ``assign_spans()`` — contiguous per-host slices (locality: each host
  fetches only its slice's byte ranges), then per-device groups inside
  parallel/pipeline.py.

Failure recovery mirrors the reference (SURVEY.md section 5): spans are
self-describing and decode is idempotent/side-effect-free, so any span can be
re-decoded anywhere; ``retry_span`` is a plain re-invoke.
"""
from __future__ import annotations

import json
import logging
from typing import Callable, List, Optional, Sequence

import jax
import numpy as np

from hadoop_bam_tpu.split.spans import FileVirtualSpan
from hadoop_bam_tpu.utils.errors import (
    PlanError, TRANSIENT, TransientIOError, classify_error,
)
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.resilient import QuarantineManifest, RetryPolicy

logger = logging.getLogger(__name__)


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Bring up jax.distributed when configured; safe no-op otherwise."""
    if coordinator_address is None and num_processes is None:
        return  # single-host / env-driven auto-init
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def serialize_plan(spans: Sequence, max_bytes: int = 1 << 24) -> bytes:
    """JSON payload of a span plan, class-tagged; raises if it exceeds
    the fixed broadcast buffer.  Exposed separately so callers under a
    failure-flag protocol can validate the size INSIDE their flagged
    phase (a raise mid-broadcast strands the receiving hosts)."""
    payload = json.dumps(
        [{"k": type(s).__name__, **s.to_dict()} for s in spans]).encode()
    if len(payload) + 8 > max_bytes:
        # PLAN class (still a ValueError): a mis-sized broadcast buffer is
        # a configuration fault, not retryable and not skippable
        raise PlanError(f"plan of {len(spans)} spans serializes to "
                        f"{len(payload)} bytes — exceeds the "
                        f"{max_bytes}-byte broadcast buffer; raise "
                        f"max_bytes or plan coarser spans")
    return payload


class _CollectiveTimeout(Exception):
    """Internal sentinel: the collective outlived timeout_s.  Distinct from
    TransientIOError so the retry clause below cannot confuse a hang (never
    safe to re-enter solo) with a failed-and-returned transient error
    (safe to retry in lockstep)."""


def _run_collective(fn: Callable[[], object], what: str,
                    retries: int = 0,
                    timeout_s: Optional[float] = None):
    """Classified retry/timeout wrapper for multihost collectives.

    Retries fire only on TRANSIENT-classified failures raised by the
    collective itself (transport resets, interrupted syscalls) — failures
    every participating host observes — and the schedule is deterministic
    (``jitter=0``), so all hosts re-enter the collective in lockstep.  A
    TIMEOUT is different: the operation may still be in flight on peer
    hosts, and a solo re-entry would deadlock the group, so it surfaces
    immediately as ``TransientIOError`` for the caller to abort on.  The
    timed body runs on a DAEMON thread: a hung collective cannot be
    cancelled from Python, but a daemon never blocks interpreter exit, so
    the abort actually terminates the job.

    Retries REQUIRE a timeout: a transport error is not guaranteed to be
    observed by every peer, and an unbounded solo re-entry into a
    collective the peers already left would hang forever — so with
    ``timeout_s=None`` transient failures fail fast (the pre-resilience
    behavior) and the retry budget is ignored."""
    import threading
    import time as _time

    if timeout_s is None:
        retries = 0

    def run_once():
        if timeout_s is None:
            return fn()
        box: dict = {}

        def runner():
            try:
                box["value"] = fn()
            except BaseException as e:  # noqa: BLE001 — relayed below
                box["error"] = e

        t = threading.Thread(target=runner, daemon=True,
                             name=f"collective:{what}")
        t0 = _time.perf_counter()
        t.start()
        # heartbeat-stamped wait: join in bounded slices, stamping a
        # liveness counter each wake, so an operator watching the
        # metrics stream can tell "still waiting on a peer" (heartbeats
        # advancing) from "this process is itself wedged" (no stamps) —
        # and the wait distribution lands in a mergeable histogram
        deadline = t0 + timeout_s
        while t.is_alive():
            remaining = deadline - _time.perf_counter()
            if remaining <= 0:
                break
            t.join(min(1.0, remaining))
            METRICS.count("distributed.heartbeats")
        METRICS.observe("distributed.collective_wait_s",
                        _time.perf_counter() - t0)
        if t.is_alive():
            raise _CollectiveTimeout
        if "error" in box:
            raise box["error"]
        return box["value"]

    policy = RetryPolicy(retries=retries, jitter=0.0)
    for attempt in range(retries + 1):
        try:
            return run_once()
        except _CollectiveTimeout:
            raise TransientIOError(
                f"{what} timed out after {timeout_s:g}s — peers may still "
                "be in the collective; aborting rather than re-entering "
                "solo") from None
        except Exception as e:  # noqa: BLE001 — policy boundary
            if classify_error(e) != TRANSIENT or attempt >= retries:
                raise
            METRICS.count("distributed.collective_retries")
            d = policy.delay(attempt)
            logger.warning("%s failed transiently (attempt %d/%d), "
                           "retrying in %.3fs: %s", what, attempt + 1,
                           retries + 1, d, e)
            policy.sleep(d)
    raise AssertionError("unreachable")  # loop always returns or raises


def collective_timeout(config) -> Optional[float]:
    """The config's multi-host loss-detection budget
    (``collective_timeout_s``): how long any barrier/allgather may block
    before a dead peer surfaces as classified ``TransientIOError``
    instead of hanging the survivors forever.  None (the default) keeps
    the pre-jobs unbounded-wait behavior."""
    t = getattr(config, "collective_timeout_s", None) \
        if config is not None else None
    return float(t) if t else None


def guarded_allgather(arr: np.ndarray, what: str,
                      timeout_s: Optional[float] = None) -> np.ndarray:
    """``process_allgather`` under the classified timeout/heartbeat
    wrapper — the one helper every barrier-shaped collective in the
    mesh pipelines routes through (mesh_sort's round/merge flags, the
    spill-round geometry agreement), so one dead host fails the
    collective fast everywhere instead of wherever someone remembered
    to wrap it."""
    from jax.experimental import multihost_utils

    return _run_collective(
        lambda: np.asarray(multihost_utils.process_allgather(arr)),
        what, timeout_s=timeout_s)


def broadcast_plan(spans: Optional[Sequence],
                   max_bytes: int = 1 << 24,
                   retries: int = 2,
                   timeout_s: Optional[float] = None) -> List:
    """Host 0 passes its plan; other hosts pass None and receive it.

    Uses a fixed-size uint8 buffer through broadcast_one_to_all (the payload
    must have identical shape on all hosts).  Both span flavors travel
    (virtual-offset BAM spans and plain byte spans for text formats),
    tagged with their class.

    Transient collective failures are retried ``retries`` times on a
    deterministic (jitter-free) backoff schedule so every host re-enters in
    lockstep; ``timeout_s`` bounds the wall-clock wait and surfaces a hang
    as ``TransientIOError`` instead of blocking the job forever.  Retries
    only engage when ``timeout_s`` is set — an unbounded solo re-entry
    could hang on peers that already left the collective (see
    ``_run_collective``); without a timeout, transient failures fail
    fast."""
    from hadoop_bam_tpu.split.spans import FileByteSpan

    span_classes = {"FileVirtualSpan": FileVirtualSpan,
                    "FileByteSpan": FileByteSpan}
    if jax.process_count() == 1:
        assert spans is not None
        return list(spans)
    from jax.experimental import multihost_utils

    if jax.process_index() == 0:
        payload = serialize_plan(spans, max_bytes)
        buf = np.zeros(max_bytes, dtype=np.uint8)
        buf[:8] = np.frombuffer(np.int64(len(payload)).tobytes(), np.uint8)
        buf[8:8 + len(payload)] = np.frombuffer(payload, np.uint8)
    else:
        buf = np.zeros(max_bytes, dtype=np.uint8)
    out = _run_collective(
        lambda: np.asarray(multihost_utils.broadcast_one_to_all(buf)),
        "broadcast_plan", retries=retries, timeout_s=timeout_s)
    # some jax/gloo versions widen uint8 payloads element-wise through the
    # collective; each element still holds one byte value, so cast back
    out = out.astype(np.uint8, copy=False)
    n = int(np.frombuffer(out[:8].tobytes(), np.int64)[0])
    plan = json.loads(out[8:8 + n].tobytes().decode())
    return [span_classes[d.pop("k", "FileVirtualSpan")].from_dict(d)
            for d in plan]


def merge_quarantine_manifests(manifest: QuarantineManifest,
                               max_bytes: int = 1 << 20,
                               timeout_s: Optional[float] = None
                               ) -> QuarantineManifest:
    """Reduce-side manifest merge: every host contributes its local
    quarantine entries over one fixed-size allgather, and all hosts return
    the identical deduplicated, canonically-ordered union — so "what was
    skipped" is a property of the JOB, not of whichever host happened to
    decode the bad span.  Single-process: returns the manifest unchanged."""
    if jax.process_count() == 1:
        return manifest
    from jax.experimental import multihost_utils

    # cheap pre-check (8 bytes/host): clean runs — the common case — skip
    # the max_bytes-sized payload allgather entirely
    counts = _run_collective(
        lambda: np.asarray(multihost_utils.process_allgather(
            np.asarray([len(manifest)], np.int64))),
        "merge_quarantine_manifests:counts", timeout_s=timeout_s)
    if int(np.sum(counts)) == 0:
        return manifest

    payload = manifest.to_json().encode()
    if len(payload) + 8 > max_bytes:
        raise PlanError(f"quarantine manifest of {len(manifest)} entries "
                        f"serializes to {len(payload)} bytes — exceeds the "
                        f"{max_bytes}-byte allgather buffer")
    buf = np.zeros(max_bytes, dtype=np.uint8)
    buf[:8] = np.frombuffer(np.int64(len(payload)).tobytes(), np.uint8)
    buf[8:8 + len(payload)] = np.frombuffer(payload, np.uint8)
    rows = _run_collective(
        lambda: np.asarray(multihost_utils.process_allgather(buf)),
        "merge_quarantine_manifests", timeout_s=timeout_s)
    rows = rows.astype(np.uint8, copy=False)  # see broadcast_plan: some
    #                                           collectives widen uint8
    per_host = []
    for host in range(rows.shape[0]):
        n = int(np.frombuffer(rows[host, :8].tobytes(), np.int64)[0])
        per_host.append(QuarantineManifest.from_json(
            rows[host, 8:8 + n].tobytes().decode()))
    # merge the allgathered ROWS only (this host's own row is among them):
    # merged_with sums total_spans, and each host must count exactly once
    return per_host[0].merged_with(per_host[1:])


def merge_metrics(metrics=None, max_bytes: int = 1 << 20,
                  timeout_s: Optional[float] = None):
    """Mesh-wide metric merge: every host contributes its local Metrics
    state over one fixed-size allgather, and all hosts return the SAME
    merged ``Metrics`` — the job-level view the reference's Hadoop
    counters gave for free and per-host stderr dumps cannot.

    Merge semantics (``Metrics.merge_dict``): counters and timers SUM
    (work adds across hosts); histograms merge by bucket addition —
    associative, so the fold order across hosts cannot change the
    result (pinned in tests/test_obs.py); wall spans take the MAX
    across hosts (each host's value is already its local union, and
    hosts run concurrently — the mesh-wide wall is the slowest host's,
    not the sum).  Single-process: returns a detached copy of the
    current state, so callers can render/export it uniformly."""
    from hadoop_bam_tpu.utils.metrics import Metrics, current_metrics

    if metrics is None:
        metrics = current_metrics()
    if jax.process_count() == 1:
        return Metrics.from_dict(metrics.to_dict())
    from jax.experimental import multihost_utils

    payload = json.dumps(metrics.to_dict()).encode()
    if len(payload) + 8 > max_bytes:
        raise PlanError(f"metrics snapshot serializes to {len(payload)} "
                        f"bytes — exceeds the {max_bytes}-byte allgather "
                        f"buffer; raise max_bytes")
    buf = np.zeros(max_bytes, dtype=np.uint8)
    buf[:8] = np.frombuffer(np.int64(len(payload)).tobytes(), np.uint8)
    buf[8:8 + len(payload)] = np.frombuffer(payload, np.uint8)
    rows = _run_collective(
        lambda: np.asarray(multihost_utils.process_allgather(buf)),
        "merge_metrics", timeout_s=timeout_s)
    rows = rows.astype(np.uint8, copy=False)  # see broadcast_plan: some
    #                                           collectives widen uint8
    merged = Metrics()
    for host in range(rows.shape[0]):
        n = int(np.frombuffer(rows[host, :8].tobytes(), np.int64)[0])
        merged.merge_dict(json.loads(rows[host, 8:8 + n].tobytes()
                                     .decode()))
    merged.count("obs.hosts_merged", int(rows.shape[0]))
    return merged


def assign_spans(spans: Sequence[FileVirtualSpan],
                 index: Optional[int] = None,
                 count: Optional[int] = None) -> List[FileVirtualSpan]:
    """Contiguous per-host slice, balanced by compressed size."""
    index = jax.process_index() if index is None else index
    count = jax.process_count() if count is None else count
    if not spans:
        # a legitimately empty plan (e.g. a .bai-pruned region with no
        # aligned reads) assigns nothing everywhere — cum[-1] below
        # would IndexError on the empty array
        return []
    if count == 1:
        return list(spans)

    def size_of(s):
        sz = getattr(s, "compressed_size", None)   # virtual-offset spans
        if sz is None:
            sz = s.end - s.start                   # plain byte spans
        return max(int(sz), 1)

    sizes = np.asarray([size_of(s) for s in spans], dtype=np.float64)
    cum = np.cumsum(sizes)
    total = cum[-1]
    lo, hi = total * index / count, total * (index + 1) / count
    out = [s for s, c, z in zip(spans, cum, sizes)
           if lo < c - z / 2 <= hi]  # midpoint rule: every span exactly once
    return out


def _multihost_reduce(plan_builder, local_reducer, payload_len: int,
                      timeout_s: Optional[float] = None) -> np.ndarray:
    """Shared scaffold of the multi-host stat drivers.

    The reference shape (SURVEY.md sections 2.9/3.2): client-side
    ``getSplits()`` once, map tasks reduce their own splits, one final
    combine.  Host 0 runs ``plan_builder`` and broadcasts; each process
    runs ``local_reducer(assigned_spans)`` -> float64[payload_len] over
    ONLY its share; one allgather stacks the rows.

    Failure-flag convention (as in mesh_sort): a raise on one host
    before a collective would strand the others in it, so every phase
    reaches its collective and ships an ok/failed flag instead.
    Counters travel as float64 — exact up to 2^53, far beyond any
    record count here.  Returns the (n_hosts, payload_len) matrix.

    ``timeout_s`` (``config.collective_timeout_s`` at the drivers):
    every flag/row allgather runs under the heartbeat-stamped timeout,
    so one dead host fails the whole reduce with classified
    ``TransientIOError`` instead of hanging the survivors.
    """
    plan = None
    err = None
    if jax.process_index() == 0:
        try:
            plan = plan_builder()
            serialize_plan(plan)   # size-check INSIDE the flagged phase
        except Exception as e:  # noqa: BLE001 — must reach the collective
            err = e
    ok = np.asarray([0 if err is not None else 1], np.int32)
    g_ok = guarded_allgather(ok, "distributed reduce: plan flag",
                             timeout_s=timeout_s)
    if err is not None:
        raise err
    if int(g_ok.min()) == 0:
        raise RuntimeError("distributed reduce: span planning failed on "
                           "host 0")
    mine = assign_spans(broadcast_plan(plan, timeout_s=timeout_s))
    row = np.zeros(1 + payload_len, np.float64)
    try:
        row[1:] = local_reducer(mine)
        row[0] = 1.0
    except Exception as e:  # noqa: BLE001 — must reach the collective
        err = e
        row[:] = 0.0
    g = guarded_allgather(row, "distributed reduce: result rows",
                          timeout_s=timeout_s)
    if err is not None:
        raise err
    if (g[:, 0] < 1).any():
        raise RuntimeError("distributed reduce failed on another host")
    return g[:, 1:]


def _local_mesh():
    from hadoop_bam_tpu.parallel.mesh import make_mesh

    return make_mesh(devices=jax.local_devices())


def distributed_flagstat(path: str, config=None, header=None):
    """Whole-file flagstat across a multi-host ``jax.distributed`` job;
    single-process calls degrade to plain flagstat_file.  Flagstat
    counters are sum-combinable, so the combine is one addition."""
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.formats.bamio import read_bam_header
    from hadoop_bam_tpu.ops.flagstat import FLAGSTAT_FIELDS
    from hadoop_bam_tpu.parallel.pipeline import (
        flagstat_file, pipeline_span_count,
    )
    from hadoop_bam_tpu.split.planners import plan_spans_cached

    config = DEFAULT_CONFIG if config is None else config
    if header is None:
        header, _ = read_bam_header(path)
    if jax.process_count() == 1:
        return flagstat_file(path, config=config, header=header)

    def plan():
        n = pipeline_span_count(path, jax.device_count(), config)
        return plan_spans_cached(path, header, config, num_spans=n)

    # the circuit breaker trips HOST-LOCALLY (fraction over this host's
    # assigned spans) — safe against stranding peers because local() runs
    # inside _multihost_reduce's failure-flag phase: a CircuitBreakerError
    # rides the ok/failed allgather and every host raises
    quarantine = QuarantineManifest()

    def local(mine):
        stats = flagstat_file(path, mesh=_local_mesh(), config=config,
                              header=header, spans=mine,
                              quarantine=quarantine)
        return np.asarray([stats[k] for k in FLAGSTAT_FIELDS], np.float64)

    tot = _multihost_reduce(plan, local, len(FLAGSTAT_FIELDS),
                            timeout_s=collective_timeout(config)
                            ).sum(axis=0)
    out = {k: int(v) for k, v in zip(FLAGSTAT_FIELDS, tot)}
    # reduce-side manifest merge: every host reports the same union of
    # skipped spans (runs as its own collective AFTER the stat reduce, in
    # the same order on all hosts)
    from hadoop_bam_tpu.parallel.pipeline import _attach_quarantine
    return _attach_quarantine(out, merge_quarantine_manifests(quarantine))


def distributed_seq_stats(path: str, config=None, header=None,
                          geometry=None):
    """Multi-host seq_stats_file: counts and histograms sum; the means
    combine weighted by each host's read count."""
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.formats.bamio import read_bam_header
    from hadoop_bam_tpu.ops.seq_pallas import N_CODES
    from hadoop_bam_tpu.parallel.pipeline import (
        pipeline_span_count, seq_stats_file,
    )
    from hadoop_bam_tpu.split.planners import plan_spans_cached

    config = DEFAULT_CONFIG if config is None else config
    if header is None:
        header, _ = read_bam_header(path)
    if jax.process_count() == 1:
        return seq_stats_file(path, config=config, header=header,
                              geometry=geometry)

    def plan():
        n = pipeline_span_count(path, jax.device_count(), config)
        return plan_spans_cached(path, header, config, num_spans=n)

    quarantine = QuarantineManifest()

    def local(mine):
        return _pack_seq_stats(seq_stats_file(
            path, mesh=_local_mesh(), config=config, header=header,
            spans=mine, geometry=geometry, quarantine=quarantine))

    out = _combine_seq_stats(_multihost_reduce(
        plan, local, 3 + N_CODES,
        timeout_s=collective_timeout(config)))
    from hadoop_bam_tpu.parallel.pipeline import _attach_quarantine
    return _attach_quarantine(out, merge_quarantine_manifests(quarantine))


def _pack_seq_stats(s) -> np.ndarray:
    """One host's seq stats as a sum-combinable row: counts plus
    n-weighted means (the exact inverse of _combine_seq_stats)."""
    n = float(s["n_reads"])
    return np.concatenate([
        [n, s["mean_gc"] * n, s["mean_qual"] * n],
        np.asarray(s["base_hist"], np.float64)])


def _combine_seq_stats(rows: np.ndarray) -> dict:
    g = rows.sum(axis=0)
    n = max(g[0], 1.0)
    return {"n_reads": int(g[0]), "mean_gc": float(g[1] / n),
            "mean_qual": float(g[2] / n),
            "base_hist": g[3:].astype(np.int64)}


def distributed_fastq_seq_stats(path: str, config=None, geometry=None):
    """Multi-host fastq_seq_stats_file (FASTQ/QSEQ): same weighted
    combine as distributed_seq_stats, over byte-span plans."""
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.ops.seq_pallas import N_CODES
    from hadoop_bam_tpu.parallel.pipeline import (
        QSEQ_EXTS, fastq_seq_stats_file, pipeline_span_count,
    )

    config = DEFAULT_CONFIG if config is None else config
    if jax.process_count() == 1:
        return fastq_seq_stats_file(path, config=config, geometry=geometry)

    def plan():   # runs on host 0 only
        from hadoop_bam_tpu.api.read_datasets import open_fastq, open_qseq
        opener = open_qseq if path.lower().endswith(QSEQ_EXTS) \
            else open_fastq
        n = pipeline_span_count(path, jax.device_count(), config)
        return opener(path, config).spans(num_spans=n)

    def local(mine):
        return _pack_seq_stats(fastq_seq_stats_file(
            path, mesh=_local_mesh(), config=config, geometry=geometry,
            spans=mine))

    return _combine_seq_stats(_multihost_reduce(
        plan, local, 3 + N_CODES,
        timeout_s=collective_timeout(config)))


def distributed_cram_seq_stats(path: str, config=None, geometry=None):
    """Multi-host cram_seq_stats_file: same weighted combine as the
    other seq-stats drivers, over container-aligned byte-span plans."""
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.ops.seq_pallas import N_CODES
    from hadoop_bam_tpu.parallel.pipeline import (
        cram_seq_stats_file, pipeline_span_count,
    )

    config = DEFAULT_CONFIG if config is None else config
    if jax.process_count() == 1:
        return cram_seq_stats_file(path, config=config, geometry=geometry)

    def plan():   # runs on host 0 only
        from hadoop_bam_tpu.api.cram_dataset import open_cram
        n = pipeline_span_count(path, jax.device_count(), config)
        return open_cram(path, config).spans(num_spans=n)

    def local(mine):
        return _pack_seq_stats(cram_seq_stats_file(
            path, mesh=_local_mesh(), config=config, geometry=geometry,
            spans=mine))

    return _combine_seq_stats(_multihost_reduce(
        plan, local, 3 + N_CODES,
        timeout_s=collective_timeout(config)))


def distributed_variant_stats(path: str, config=None, header=None):
    """Multi-host variant_stats_file: counts sum; mean_af combines
    weighted by n_af; per-sample call rates by n_variants."""
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        variant_span_count, variant_stats_file,
    )

    config = DEFAULT_CONFIG if config is None else config
    if jax.process_count() == 1:
        return variant_stats_file(path, config=config, header=header)
    ds = open_vcf(path, config)        # one open: header + span planner
    if header is None:
        header = ds.header
    n_samples = header.n_samples

    def plan():
        n = variant_span_count(ds, jax.device_count(), config)
        return ds.spans(num_spans=n)

    def local(mine):
        s = variant_stats_file(path, mesh=_local_mesh(), config=config,
                               header=header, spans=mine)
        nv = float(s["n_variants"])
        return np.concatenate([
            [nv, s["n_snp"], s["n_pass"], s["n_af"],
             s["mean_af"] * s["n_af"]],
            np.asarray(s["sample_callrate"], np.float64) * nv])

    g = _multihost_reduce(plan, local, 5 + n_samples,
                          timeout_s=collective_timeout(config)).sum(axis=0)
    nv = int(g[0])
    return {"n_variants": nv, "n_snp": int(g[1]), "n_pass": int(g[2]),
            "mean_af": float(g[4] / max(g[3], 1.0)), "n_af": int(g[3]),
            "sample_callrate": g[5:] / max(nv, 1)}


def distributed_coverage(path: str, region, config=None, header=None,
                         max_cigar: int = 64) -> np.ndarray:
    """Multi-host coverage_file: each host piles up only its assigned
    spans over the SAME window, and per-base depths sum exactly across
    hosts (each record is decoded on exactly one host).

    The combine allgathers one float64 row per host of ``window``
    entries, so the per-call window is capped at 2^24 bases (128 MB/row)
    — tile larger regions across calls exactly like the CLI does.
    Single-process calls degrade to plain coverage_file."""
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.formats.bamio import read_bam_header
    from hadoop_bam_tpu.parallel.pipeline import coverage_file
    from hadoop_bam_tpu.split.intervals import Interval, resolve_interval

    config = DEFAULT_CONFIG if config is None else config
    if header is None:
        header, _ = read_bam_header(path)
    if jax.process_count() == 1:
        return coverage_file(path, region, config=config, header=header,
                             max_cigar=max_cigar)
    if not isinstance(region, Interval):
        region = resolve_interval(region, header.ref_names)
    if region.rname not in header.ref_names:
        raise ValueError(f"region reference {region.rname!r} not in header")
    ref_len = header.ref_lengths[header.ref_names.index(region.rname)]
    end = min(region.end, ref_len)
    window = end - region.start + 1
    if window <= 0:
        raise ValueError(f"empty region {region}")
    if window > (1 << 24):
        raise ValueError(f"distributed region spans {window} bases; the "
                         "per-call cap is 2^24 — tile larger regions "
                         "across calls")
    region = Interval(region.rname, region.start, end)

    def plan():
        # the same plan coverage_file builds itself: .bai-trimmed chunks
        # when a sidecar exists, whole-file pipeline-grain spans otherwise
        from hadoop_bam_tpu.parallel.pipeline import pipeline_span_count
        from hadoop_bam_tpu.split.bai import plan_interval_spans
        from hadoop_bam_tpu.split.planners import plan_spans_cached

        spans = plan_interval_spans(path, [region], header)
        if spans is None:
            n = pipeline_span_count(path, jax.device_count(), config)
            spans = plan_spans_cached(path, header, config, num_spans=n)
        return spans

    def local(mine):
        depth = coverage_file(path, region, mesh=_local_mesh(),
                              config=config, header=header, spans=mine,
                              max_cigar=max_cigar)
        return np.asarray(depth, np.float64)

    g = _multihost_reduce(plan, local, window,
                          timeout_s=collective_timeout(config)).sum(axis=0)
    return g.astype(np.int32)


def retry_span(decode_fn, span: FileVirtualSpan, attempts: int = 3,
               policy: Optional[RetryPolicy] = None):
    """Span-level retry — the framework's failure-recovery unit, now
    fault-classified via the shared ``call_with_retry`` core: only
    TRANSIENT failures are re-attempted (with the policy's backoff);
    corruption and plan errors raise on the first attempt (re-decoding
    the same corrupt bytes can never heal them)."""
    from hadoop_bam_tpu.utils.resilient import call_with_retry

    if policy is None:
        policy = RetryPolicy(retries=max(0, attempts - 1))
    return call_with_retry(lambda: decode_fn(span), policy,
                           what=f"decode of span {span}",
                           counter="pipeline.transient_retries")
