"""The process-wide shared decode pool.

Every driver call used to spin up (and tear down) its own
``ThreadPoolExecutor`` — a per-call tax of worker-thread creation plus a
join on exit, multiplied by the number of driver invocations in a run
(the bench alone makes dozens).  Decode work is uniform across drivers
(fetch + inflate + pack a span), so one pool sized once from the host's
CPU count serves them all; ``set_decode_pool`` injects a replacement for
tests (a recording pool, a single-thread pool for determinism).

The pool is created lazily on first use.  ``config.decode_pool_workers``
overrides the size at creation time only — the first caller wins, later
configs get the existing pool (one process, one pool, by design).
"""
from __future__ import annotations

import collections
import concurrent.futures as cf
import contextvars
import os
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.obs.trace import active_recorder
from hadoop_bam_tpu.resilience import chaos
from hadoop_bam_tpu.utils.metrics import (
    METRICS, current_metrics, thread_usage,
)

_LOCK = threading.Lock()
_POOL: Optional[cf.ThreadPoolExecutor] = None
_POOL_SIZE = 0


def default_pool_size(config=None) -> int:
    """Worker count for a fresh pool: config.decode_pool_workers when
    set, else the measured sweet spot of 4x CPUs in [4, 32] (decode
    threads block on I/O about as often as they inflate)."""
    n = getattr(config, "decode_pool_workers", None) if config else None
    if n:
        return max(1, int(n))
    return min(32, max(4, (os.cpu_count() or 4) * 4))


def decode_pool(config=None) -> cf.ThreadPoolExecutor:
    """The shared decode executor (created on first call, never torn
    down — idle workers cost nothing, re-creation per driver call cost
    thread spawns + a join on every invocation)."""
    global _POOL, _POOL_SIZE
    with _LOCK:
        if _POOL is None:
            _POOL_SIZE = default_pool_size(config)
            _POOL = cf.ThreadPoolExecutor(
                max_workers=_POOL_SIZE, thread_name_prefix="hbam-decode")
        return _POOL


def decode_pool_size(config=None) -> int:
    """Worker count of the shared pool (materializing it if needed) —
    what the drivers size their prefetch windows from."""
    decode_pool(config)
    return _POOL_SIZE


class TaskStamps:
    """One pool task's own clock, written by the worker and read by
    whoever holds the future (``fut.stamps``): ``submitted``, ``started``
    and ``finished`` are ``time.perf_counter`` instants (None until
    reached); ``usage`` is the run's ``thread_usage`` difference (user
    ns, system ns), taken only while a trace recorder is active."""
    __slots__ = ("submitted", "started", "finished", "usage")

    def __init__(self, submitted: float):
        self.submitted = submitted
        self.started: Optional[float] = None
        self.finished: Optional[float] = None
        self.usage: Optional[Tuple[int, ...]] = None


def _timed_task(fn, stamps: TaskStamps, args, kwargs):
    m = current_metrics()
    t0 = stamps.started = time.perf_counter()
    # queue wait + run durations as log-bucketed histograms: the pool is
    # SHARED across drivers, so p95 task_wait is the direct saturation
    # signal (a deep wait distribution means the pool, not the device,
    # is the bottleneck) — a flat timer cannot show that.  The consumer's
    # side of it is feed.head_queued (parallel/pipeline._iter_windowed):
    # how long the unit it needed NEXT sat in that queue.  The same two
    # clock reads are the task's stamps
    m.observe("pool.task_wait_s", t0 - stamps.submitted)
    # chaos point ON THE WORKER thread (pool.submit fires on the
    # submitter's): a "delay" fault here wedges a worker mid-task —
    # the exact hang shape the per-future timeout exists to surface
    chaos.fire("pool.task")
    # the thread's rusage only while a recorder is active: under the chip
    # host's sandboxed kernel the two calls a task — 6 us each on an idle
    # process — cost a 0.27 s flagstat scan of 77 units 3-5 % (they are
    # made with the interpreter lock held; PERF.md section 6, PR 36)
    u0 = thread_usage() if active_recorder() is not None else None
    try:
        return fn(*args, **kwargs)
    finally:
        if u0 is not None:
            stamps.usage = tuple(b - a for a, b in zip(u0, thread_usage()))
        t1 = stamps.finished = time.perf_counter()
        m.observe("pool.task_run_s", t1 - t0)


def result_with_timeout(fut: cf.Future, timeout_s: Optional[float],
                        what: str = "pool task"):
    """``fut.result()`` with a hard deadline, classified.

    A worker that never returns — an injected ``pool.task`` wedge, a
    kernel pread stuck on a dead NFS server — used to hang the consumer
    forever; the timeout converts it into ``TransientIOError`` so the
    caller's retry/breaker machinery (re-submit, quarantine, abort) gets
    to decide instead of the job just freezing.  The wedged THREAD is
    not recoverable (Python cannot kill it) — the caller abandons the
    future and the thread rejoins the pool if/when it unwedges.

    This is the standalone single-future primitive; the windowed span
    consumer (``parallel/pipeline._iter_windowed``) implements the same
    policy inline because it races speculative twins and re-submits —
    the ``pool.task_timeouts`` counter and TRANSIENT classification
    must stay in sync between the two."""
    try:
        return fut.result(timeout=timeout_s)
    except cf.TimeoutError:
        from hadoop_bam_tpu.utils.errors import TransientIOError
        METRICS.count("pool.task_timeouts")
        fut.cancel()
        raise TransientIOError(
            f"{what} exceeded the {timeout_s:g}s pool_task_timeout_s "
            f"deadline — worker presumed wedged, abandoning the "
            f"future") from None


def submit(pool: cf.ThreadPoolExecutor, fn, *args,
           priority: str = "fg", **kwargs) -> cf.Future:
    """Context-carrying, histogram-instrumented submit — what every
    decode-path call site uses instead of bare ``pool.submit``:

    - the submitter's ``contextvars`` context rides along, so work done
      on a pool thread records into the submitter's ``MetricsContext``
      (a bare submit silently falls back to the process-global Metrics
      and two concurrent engine batches smear into each other);
    - per-task queue-wait and run durations land in the
      ``pool.task_wait_s`` / ``pool.task_run_s`` histograms;
    - ``priority="bg"`` routes the task through the background gate:
      at most ``background_limit(pool)`` (a quarter of the workers,
      min 1) background tasks occupy the pool concurrently, so serve
      prefetch can soak idle decode capacity without ever starving
      foreground admission — excess background work queues in FIFO
      order and drains as permits free.
    """
    if priority not in ("fg", "bg"):
        from hadoop_bam_tpu.utils.errors import PlanError
        raise PlanError(f"pool priority must be 'fg' or 'bg', "
                        f"got {priority!r}")
    # chaos point: an injected submission failure surfaces HERE — on the
    # submitter's thread, classified TRANSIENT — exactly where a real
    # saturated/failing executor would (no-op unless armed)
    chaos.fire("pool.submit", priority=priority)
    ctx = contextvars.copy_context()
    stamps = TaskStamps(time.perf_counter())
    if priority == "fg":
        fut = pool.submit(ctx.run, _timed_task, fn, stamps, args, kwargs)
    else:
        fut = cf.Future()
        METRICS.count("pool.bg_submitted")
        with _BG_LOCK:
            _BG_QUEUE.append((pool, fut, ctx, fn, stamps, args, kwargs))
        _pump_background()
    fut.stamps = stamps
    return fut


# ---------------------------------------------------------------------------
# background priority gate (serve prefetch rides this)
# ---------------------------------------------------------------------------

_BG_LOCK = threading.Lock()
_BG_QUEUE: "collections.deque" = collections.deque()
_BG_RUNNING = [0]


def background_limit(pool: cf.ThreadPoolExecutor) -> int:
    """Concurrent background tasks allowed in ``pool``: a quarter of the
    workers (min 1), so >= 3/4 of the pool is always free the instant
    foreground decode work arrives."""
    size = int(getattr(pool, "_max_workers", 1) or 1)
    return max(1, size // 4)


def _run_background(fut: cf.Future, ctx, fn, stamps, args, kwargs) -> None:
    if not fut.set_running_or_notify_cancel():
        return
    try:
        fut.set_result(ctx.run(_timed_task, fn, stamps, args, kwargs))
    except BaseException as e:  # noqa: BLE001 — crosses the thread
        fut.set_exception(e)


def _pump_background() -> None:
    while True:
        with _BG_LOCK:
            if not _BG_QUEUE:
                return
            pool = _BG_QUEUE[0][0]
            if _BG_RUNNING[0] >= background_limit(pool):
                return
            item = _BG_QUEUE.popleft()
            _BG_RUNNING[0] += 1
        _pool, fut, ctx, fn, stamps, args, kwargs = item

        def task(fut=fut, ctx=ctx, fn=fn, stamps=stamps, args=args,
                 kwargs=kwargs):
            try:
                _run_background(fut, ctx, fn, stamps, args, kwargs)
            finally:
                with _BG_LOCK:
                    _BG_RUNNING[0] -= 1
                _pump_background()

        try:
            _pool.submit(task)
        except BaseException as e:  # noqa: BLE001 — pool shut down etc.
            # the permit was taken above and `task` will never run its
            # finally: give the permit back, fail the future (so waiters
            # like Prefetcher.drain never hang), and keep pumping — a
            # speculative submit must never wedge the gate or raise into
            # a foreground serve path
            with _BG_LOCK:
                _BG_RUNNING[0] -= 1
            if not fut.cancel():
                try:
                    fut.set_exception(e)
                except Exception:  # noqa: BLE001 — already resolved
                    pass


def cancel_background() -> int:
    """Cancel every QUEUED (not yet running) background task; returns the
    number cancelled.  ``ServeLoop.stop`` / ``Prefetcher`` teardown use
    this so a shutting-down server never keeps decoding regions nobody
    will ask for."""
    cancelled = 0
    with _BG_LOCK:
        while _BG_QUEUE:
            _p, fut, *_rest = _BG_QUEUE.popleft()
            if fut.cancel():
                cancelled += 1
    if cancelled:
        METRICS.count("pool.bg_cancelled", cancelled)
    return cancelled


def pool_stats() -> dict:
    """Occupancy snapshot of the shared decode pool for the health/
    `hbam top` surfaces: worker count, how many pool threads exist (a
    lazy executor only spawns them under load), and the background
    gate's running/queued depths.  Never materializes the pool."""
    with _LOCK:
        pool, size = _POOL, _POOL_SIZE
    with _BG_LOCK:
        bg_running, bg_queued = _BG_RUNNING[0], len(_BG_QUEUE)
    out = {"workers": size, "threads_live": 0,
           "bg_running": bg_running, "bg_queued": bg_queued}
    if pool is not None:
        out["threads_live"] = len(getattr(pool, "_threads", ()) or ())
        out["queued_tasks"] = getattr(pool, "_work_queue").qsize() \
            if hasattr(pool, "_work_queue") else 0
    return out


def set_decode_pool(pool: Optional[cf.ThreadPoolExecutor],
                    size: Optional[int] = None
                    ) -> Tuple[Optional[cf.ThreadPoolExecutor], int]:
    """Injection hook for tests: install ``pool`` (with its advertised
    ``size``) and return the previous (pool, size) for restoration.
    ``set_decode_pool(None)`` drops the override so the next
    ``decode_pool`` call creates a fresh default pool.  The caller owns
    shutdown of any pool it injects (and of a returned previous pool it
    chooses not to restore)."""
    global _POOL, _POOL_SIZE
    with _LOCK:
        prev, prev_size = _POOL, _POOL_SIZE
        _POOL = pool
        _POOL_SIZE = 0 if pool is None else int(
            size if size is not None else getattr(pool, "_max_workers", 1))
        return prev, prev_size


# ---------------------------------------------------------------------------
# span buffers (the host feed's recycled span-start memory)
# ---------------------------------------------------------------------------

def stream_window_cap() -> int:
    """Most spans a STREAMED fused decode keeps in flight (each is a live
    multi-threaded native job): twice the host's CPUs, at least 2."""
    return max(2, 2 * (os.cpu_count() or 1))


def text_inflate_workers() -> int:
    """Threads that inflate a compressed text STREAM from inside its
    members (``split/read_planners.py::_SpeculativeMembers``): a third of
    the host's CPUs, at least 2 — one worker's two stages are no faster
    than the one ``zlib`` inflate they replace — and at most 4.  Since
    FASTQ's tokenise is a native pass (PR 37) the inflate is that
    stream's whole cost a record (~1,400 ns of a core against the
    tokenise's ~130), so the workers set the pace up to what the stream's
    ONE in-order thread hands on: ~2.0 M FASTQ records/s, reached with
    four workers.  That plateau is the stream thread's, not a share of
    the CPUs, so the cap is a count.  The probes this rests on (PERF.md
    section 6, PR 37; 13 CPUs, records/s of whole FASTQ scans): 3 workers
    1.75 M, 4 1.98 M, 6 2.00 M, 8 1.99 M, 10 1.96 M; the stream alone,
    nothing downstream: 1.64 / 1.91 / 2.06 / 2.03 M with 3 / 4 / 6 / 8 —
    flat from four on, each worker past them only 0.3 points of
    ``fastq.resident_text_share``."""
    return min(4, max(2, (os.cpu_count() or 1) // 3))


def text_stream_window() -> int:
    """Most chunks of a compressed text STREAM tokenised at once: the
    host's CPUs less a quarter of them (at least 2) and two more, at
    least 2 — on every host what it was before FASTQ's tokenise became a
    native pass (PR 37).  A chunk's text is in memory already, so its
    tokenise never waits for a read and more of them in flight than cores
    only queue.  The native pass never fills the window (a chunk is
    tokenised in ~2 ms and the next arrives ~8 ms later: 2, 3, 4, 6, 7 or
    8 slots read the same rate and the same text alive), so for it the
    slots cost nothing.  The window is for the streams that still
    tokenise in NumPy — QSEQ, FASTQ on a host without the native library
    or on the object path — where a tokenise costs four to five times the
    CPU of the inflate that feeds it, the tokenisers set the pace and the
    inflate workers mostly wait.  Their probe (PERF.md section 6, PR 37;
    13 CPUs, window x workers, M records/s): FASTQ's NumPy twin 8 x 3
    0.81, 7 x 4 0.83, 8 x 4 0.93, 2 x 4 0.54; ``.qseq.gz`` 0.63, 0.60,
    0.71, 0.42; the twin behind one serial inflate 8: 0.67, 7: 0.68, 2:
    0.53 (PR 35's: 12 in flight no more than 8).  The window is also what
    bounds a streamed scan's memory: window + 2 chunks, and what the
    inflate workers hold ahead of them."""
    cpus = os.cpu_count() or 1
    return max(2, cpus - max(2, cpus // 4) - 2)


class SpanBuffer:
    """One leased buffer: ``array`` (uint8, its class's full size) is the
    holder's alone until ``release()``, which clears it.  A second
    release is a no-op, so every exit path may call it."""

    __slots__ = ("array", "_pool")

    def __init__(self, array: Optional[np.ndarray],
                 pool: Optional["SpanBufferPool"]):
        self.array = array
        self._pool = pool

    def release(self) -> None:
        buf, self.array = self.array, None
        if buf is not None:
            self._pool._give_back(buf)


class SpanBufferPool:
    """Recycled ``uint8`` buffers for what a span start used to allocate
    fresh: the compressed bytes, the inflated bytes and the record-offset
    scratch of a span (ops/inflate.py ``fetch_span_raw``,
    ops/inflate.py ``FusedSpanDecode``).  Each of those sits above the
    allocator's mmap threshold, so a fresh one is faulted in page by page
    on first touch and unmapped on free — ~2 GB a 4.19 M-record scan.

    One rule: a request rounds up to a power of two, 1 MiB at least, and
    that size is its class.  A class keeps at most ``max_free`` returned
    buffers — what the streamed feed holds in flight, plus two — and
    drops the oldest beyond that; a class above 64 MiB is never kept.  A
    buffer comes back dirty: holders write before they read.

    No lock: a class's free list is a bounded deque, whose append and pop
    are atomic, because a decode dropped unfinished returns its buffers
    from ``__del__``, which the collector may run on a thread that is
    inside this pool.  Counters (on the leasing thread):
    ``feed.span_buffers_reused``, ``feed.span_buffers_minted``,
    ``feed.span_fresh_bytes`` (bytes of every buffer minted)."""

    MIN_CLASS = 1 << 20
    MAX_KEPT_CLASS = 1 << 26

    def __init__(self, max_free: Optional[int] = None):
        self.max_free = stream_window_cap() + 2 if max_free is None \
            else int(max_free)
        self._free: Dict[int, "collections.deque[np.ndarray]"] = {}

    @classmethod
    def size_class(cls, nbytes: int) -> int:
        return max(cls.MIN_CLASS, 1 << max(0, int(nbytes) - 1).bit_length())

    def lease(self, nbytes: int) -> SpanBuffer:
        size = self.size_class(nbytes)
        try:
            buf = self._free[size].pop()
            METRICS.count("feed.span_buffers_reused")
        except (KeyError, IndexError):
            buf = np.empty(size, dtype=np.uint8)
            METRICS.count("feed.span_buffers_minted")
            METRICS.count("feed.span_fresh_bytes", size)
        return SpanBuffer(buf, self)

    def _give_back(self, buf: np.ndarray) -> None:
        if buf.size <= self.MAX_KEPT_CLASS:
            free = self._free.get(buf.size)
            if free is None:
                free = self._free.setdefault(
                    buf.size, collections.deque(maxlen=self.max_free))
            free.append(buf)

    def free_counts(self) -> Dict[int, int]:
        """{class size: buffers held free}."""
        return {k: len(v) for k, v in list(self._free.items()) if v}


# the process-wide pool, like the decode pool above: one feed, one pool
SPAN_BUFFERS = SpanBufferPool()
# the lease of bytes that live in no pooled buffer: releasing it does nothing
NO_LEASE = SpanBuffer(None, None)
