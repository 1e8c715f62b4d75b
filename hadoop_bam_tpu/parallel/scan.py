"""The one scan feed: what every whole-file verb runs between its span plan
and its device step.

A whole-file scan is one loop whatever the format: units of work — spans,
or the chunks a compressed text file is cut into as it inflates — decode
on the shared pool inside a bounded window (``_iter_windowed``) under the
span failure policy (``decode_with_retry``), their row arrays repack into
ring-slot tile groups (``staging.FeedPipeline``), and every group crosses
to the mesh sharded over its data axis, where the family's step runs.
``ScanFeed`` is that loop.  A family — ``parallel/pipeline.py``'s BAM
flagstat, payload and coverage scans and its read-payload runner,
``parallel/variant_pipeline.py``'s variant scan, the executor's cohort
feed — supplies only what is its own:

- its plan of units, under its ``<fmt>.plan_wall``;
- how one unit decodes: into a tuple of row arrays, a column dict, or a
  BAM span's fused chunk stream (``chunk_streams``);
- its tile specs, tile height and row placement (``balance`` /
  ``fixed_shape``);
- its step and how the step's results combine (``run``), or the names a
  ``tensor_batches`` API gives its batches (``batches``).

What the loop emits, the same for every family: the walls
``pipeline.host_decode_wall`` and ``<fmt>.host_decode_wall`` around a
unit's decode (and the thread-summed ``pipeline.host_decode`` timer where
a decode may hand back a chunk stream, whose consumption accrues into it
too), ``<fmt>.kernel_wall`` around a group's step, and the count
``pipeline.records`` as a group is dispatched — except where ``fmt`` is
``"bam"``, whose span decode counts its own records.
"""
from __future__ import annotations

import concurrent.futures as cf
import contextlib
import itertools
import logging
import time
from typing import (
    Callable, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple,
)

import numpy as np

from hadoop_bam_tpu.config import HBamConfig
from hadoop_bam_tpu.obs.trace import active_recorder
from hadoop_bam_tpu.parallel.staging import FeedPipeline, TileSpec
from hadoop_bam_tpu.resilience.domains import (
    DemotionLadder, check_quarantine_gate, quarantine_run_ok,
)
from hadoop_bam_tpu.split.spans import FileVirtualSpan
from hadoop_bam_tpu.utils import errors as hberrors
from hadoop_bam_tpu.utils.errors import classify_error
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.pools import decode_pool, submit as pool_submit
from hadoop_bam_tpu.utils.resilient import (
    QuarantineManifest, RetryPolicy, span_retry_policy,
)

logger = logging.getLogger(__name__)

# the padding a column-dict feed's rows past a device's count carry: the
# missing-value sentinel of the column, 0 elsewhere
_COLUMN_PADS = {"dosage": -1, "qual": np.nan}


def decode_with_retry(fn: Callable, span: FileVirtualSpan,
                      config: HBamConfig,
                      quarantine: Optional[QuarantineManifest] = None,
                      policy: Optional[RetryPolicy] = None,
                      ladder: Optional[DemotionLadder] = None):
    """Span-level failure policy (SURVEY.md section 5), fault-classified.

    A span is a self-describing, idempotent unit of work — the retry
    mechanism is re-decoding it, as MapReduce re-runs a map task — but
    unlike the reference, failures are classified (utils/errors.py) and
    each class gets its own policy:

    - TRANSIENT: re-attempted up to ``config.span_retries`` times with
      jittered exponential backoff (``policy`` injectable, so tests assert
      the exact schedule without real sleeps);
    - CORRUPT: fails fast with ZERO re-decodes — a CRC mismatch or
      malformed record chain never heals, re-reading it only wastes the
      budget;
    - PLAN: always raised — a misconfigured run must not be retried or
      quietly skipped as if the data were bad.

    Once the policy is exhausted, ``skip_bad_spans`` decides between
    raising and quarantine+skip: the span is recorded in ``quarantine``
    (file, virtual-offset range, error class, attempts) and None returned.
    Counters: ``pipeline.bad_spans`` ticks ONLY on an actual skip;
    ``pipeline.transient_retries`` counts re-attempts;
    ``pipeline.corrupt_spans`` counts corrupt failures.  The manifest's
    circuit breaker (``config.max_bad_span_fraction``) raises
    CircuitBreakerError when the run has quarantined too much of its plan
    to stay meaningful.

    With a ``ladder`` (resilience/domains.py) the CORRUPT branch grows a
    demotion step and ``fn`` takes ``(span, plane)``: a span failing
    corrupt on plane P re-decodes at the next plane down — byte-identical
    but more battle-tested — instead of failing outright.  Blame is
    oracle-confirmed: only when the LOWER plane succeeds on the same span
    is the failure charged to P's fault domain (repeated charges open
    P's breaker, demoting the whole run until a half-open probe heals
    it); when every plane fails, the bytes — not the plane — are bad,
    no domain is charged, and the classic raise/quarantine applies."""
    if policy is None:
        policy = span_retry_policy(config)
    last: Optional[BaseException] = None
    kind = hberrors.CORRUPT
    attempts = 0
    transient_tries = 0
    plane = ladder.plane() if ladder is not None else None
    blamed: List[Tuple[str, BaseException]] = []
    while attempts <= policy.retries + len(blamed):
        attempts += 1
        try:
            out = fn(span) if ladder is None else fn(span, plane)
            if ladder is not None:
                for bad_plane, exc in blamed:
                    # a lower plane just decoded these bytes: the upper
                    # plane's failure was plane-local — charge it
                    ladder.confirm_failure(bad_plane, exc)
                    METRICS.count("pipeline.span_demotions")
                ladder.record_success(plane)
            return out
        except Exception as e:  # noqa: BLE001 — policy boundary
            last = e
            kind = classify_error(e)
            if kind == hberrors.PLAN:
                raise
            if kind != hberrors.TRANSIENT:
                if ladder is not None:
                    nxt = ladder.next_lower(plane)
                    if nxt is not None and ladder.demotable(plane, e):
                        logger.warning(
                            "span %s failed on the %s plane (%s); "
                            "re-decoding on %s", span, plane, e, nxt)
                        blamed.append((plane, e))
                        plane = nxt
                        continue
                METRICS.count("pipeline.corrupt_spans")
                break
            if transient_tries < policy.retries:
                METRICS.count("pipeline.transient_retries")
                d = policy.delay(transient_tries)
                transient_tries += 1
                logger.debug("transient fault on span %s (attempt %d/%d), "
                             "retrying in %.3fs: %s", span, attempts,
                             policy.retries + 1, d, e)
                policy.sleep(d)
                continue
            break
    if config.skip_bad_spans:
        METRICS.count("pipeline.bad_spans")
        logger.warning("skipping bad span %s after %d attempt(s) [%s]: %s",
                       span, attempts, kind, last)
        if quarantine is not None:
            quarantine.add(span, last, kind, attempts)
            quarantine.check_circuit(config)  # may raise CircuitBreakerError
        return None
    raise last


# how long a QUEUED candidate's hard-timeout anchor is held, as a
# multiple of pool_task_timeout_s: long enough that a backlogged-but-
# healthy pool (queue waits of a few task durations) never false-fires,
# short enough that a fully-wedged pool — where re-submissions can
# never dequeue — still exhausts the budget and surfaces as
# TransientIOError instead of hanging forever
_QUEUED_GRACE = 8.0


def _iter_windowed(pool: cf.ThreadPoolExecutor, items: Sequence,
                   fn: Callable, window: int,
                   cleanup: Optional[Callable] = None,
                   config: Optional[HBamConfig] = None,
                   what: str = "span decode") -> Iterator:
    """Submit ``fn(item)`` to the pool with bounded in-flight futures and
    yield results in order.  Bounds host memory: at most ``window`` decoded
    spans exist at once (a plain list of futures would retain every span's
    rows for the whole run — concurrent.futures keeps results referenced).

    On early close (a consumer abandoning the stream), queued-but-unstarted
    futures are cancelled — the SHARED decode pool (utils/pools.py) never
    shuts down, so without the cancel an abandoned window of decodes would
    keep running to completion for nothing.  ``cleanup`` is called on
    results that already materialized but will never be yielded (the fused
    chunk streams hold live native jobs — closing them joins the workers
    instead of leaving that to GC).

    With a ``config``, the consumer grows the straggler + hang defense
    (jobs/speculate.py):

    - **speculation** (``config.speculative_decode``): a unit outliving
      the job's soft deadline — p95 of a decaying per-job latency
      histogram x ``straggler_multiplier`` — gets a second copy raced on
      the pool; the FIRST result wins and the loser is cancelled or
      reaped through ``cleanup`` (``jobs.speculative_launched`` /
      ``jobs.speculative_won``).  Safe because ``fn`` is an idempotent,
      side-effect-free span decode — the MapReduce speculative-execution
      contract.
    - **hard timeout** (``config.pool_task_timeout_s``): a future
      outliving it is abandoned (a wedged worker thread cannot be
      killed, only orphaned) and the item re-submitted, once per
      ``span_retries``; exhaustion surfaces ``TransientIOError`` into
      the caller's existing retry/breaker machinery instead of blocking
      forever (``pool.task_timeouts`` / ``jobs.timeout_resubmits``).
      The deadline covers ACTIVE wait on a runnable task — time spent
      queued behind a backlogged-but-healthy pool, or running
      overlapped before the consumer reached this entry, does not
      count (see ``_await``'s two-clock note).

    Without a config (or with both knobs off before any soft deadline
    exists) the await path is the plain blocking ``Future.result()``.

    Every unit carries its own clock (``utils/pools.TaskStamps``, written
    by the worker) and the consumer says what it waited for:

    - a head that is NOT done when the consumer arrives is
      ``feed.head_wait`` — a span on the pulling thread while a recorder
      is active (the packer's, or the dispatch thread's under a
      column-dict feed's schema peek, ``ScanFeed``), two clock reads
      otherwise — and at its end, from the stamps alone, three walls:
      ``feed.head_queued`` (the part before the head's ``started``: no
      pool thread was free), ``feed.head_running`` (the rest: it was on
      a thread and not done)
      and ``feed.ready_behind_head`` (the part during which a LATER unit
      of the window had already finished: what a hand-off out of order
      would not have waited).  A head that is done costs one ``done()``;
    - every unit taken adds ``feed.units`` and, from its stamps, the
      sums ``feed.unit_queued_ns`` / ``unit_run_ns`` / ``unit_held_ns``
      (finished -> taken) and — of units run while a recorder was active,
      whose thread usage the worker took — ``unit_cpu_ns`` /
      ``unit_sys_ns``.  Of a speculated or re-submitted unit the
      winner's stamps count.
    """
    from collections import deque

    from hadoop_bam_tpu.utils.resilient import call_with_retry

    it = iter(items)
    # entries: [item, future, speculated?, index]; the future's stamps
    # (submitted / started / finished, the run's rusage) are the unit's
    dq: "deque[list]" = deque()
    # transient SUBMISSION failures (a saturated executor, an injected
    # pool.submit chaos fault) retry briefly instead of killing the
    # whole driver run — the task itself has its own failure policy
    submit_policy = RetryPolicy(retries=3, backoff_base_s=0.01,
                                backoff_max_s=0.1)

    timeout_s = config.pool_task_timeout_s if config is not None else None
    timeout_s = float(timeout_s) if timeout_s else None
    max_resubmits = int(config.span_retries or 0) \
        if timeout_s is not None else 0
    latency = None
    if config is not None and bool(config.speculative_decode):
        from hadoop_bam_tpu.jobs.speculate import UnitLatency
        latency = UnitLatency.from_config(config)

    def _submit(item) -> cf.Future:
        # pools.submit, not pool.submit: the task carries the caller's
        # MetricsContext onto the worker thread and records its queue
        # wait + run into the pool.task_* histograms
        return call_with_retry(lambda: pool_submit(pool, fn, item),
                               submit_policy, what="decode pool submit",
                               counter="pool.submit_retries")

    def _reap(f: cf.Future) -> None:
        # done-callback: covers futures already finished AND ones
        # still running at teardown (fires on the worker thread when
        # they complete) without blocking this thread on .result()
        if f.cancelled():
            return
        try:
            cleanup(f.result())
        except Exception:  # noqa: BLE001 — best-effort teardown
            pass

    def _abandon(f: cf.Future) -> None:
        if not f.cancel() and cleanup is not None:
            f.add_done_callback(_reap)

    def _await(entry) -> object:
        """Resolve one entry under the defense policy (docstring); the
        future that won is left in ``entry[1]``."""
        if timeout_s is None and latency is None:
            return entry[1].result()           # undefended fast path
        # candidates: the primary plus at most one speculative twin plus
        # timeout re-submissions.  Two clocks on purpose:
        # - the DEADLINE anchor starts when this await begins (a decode
        #   that ran overlapped while earlier entries were consumed is
        #   not "stuck") and is refreshed while the future is still
        #   queued — otherwise a healthy-but-backlogged pool would burn
        #   the hard-timeout budget on queue wait (re-submissions land
        #   at the back of the same queue) and the soft deadline would
        #   speculate on tasks that never started (a twin queued behind
        #   the original can only lose);
        # - the future's SUBMIT stamp feeds the latency histogram:
        #   turnaround, which can only over-estimate, keeps the
        #   p95-derived soft deadline conservative.
        now = time.perf_counter()
        # fields: [future, deadline anchor, is_spec,
        # first-observed-queued stamp (None until seen pending)]
        cands = [[entry[1], now, False, None]]
        resubmits = 0
        while True:
            for c in list(cands):
                if not c[0].done():
                    continue
                try:
                    out = c[0].result()
                except Exception:  # noqa: BLE001 — policy boundary
                    # one copy failing while another runs must not kill
                    # the race — keep waiting on the survivor; but when
                    # the last candidate FAILS (vs times out), raise:
                    # the decode genuinely ran and failed, its own
                    # retry policy is spent, and burning the timeout
                    # re-submission budget on a known-failing span
                    # would just duplicate the failure
                    cands.remove(c)
                    if not cands:
                        raise
                    continue
                if latency is not None:
                    latency.observe(time.perf_counter()
                                    - c[0].stamps.submitted)
                if c[2]:
                    METRICS.count("jobs.speculative_won")
                for o in cands:
                    if o is not c:
                        _abandon(o[0])
                entry[1] = c[0]
                return out
            now = time.perf_counter()
            for c in cands:
                if not c[0].running() and not c[0].done():
                    if c[3] is None:
                        c[3] = now
                    # still queued: hold the deadline anchor — but only
                    # within a bounded grace.  Unbounded holding would
                    # make a FULLY-wedged pool (every worker stuck, so
                    # re-submissions never dequeue) immortal — the
                    # exact forever-hang this knob exists to end; a
                    # merely-backlogged pool drains within the grace
                    if timeout_s is None or \
                            now - c[3] <= timeout_s * _QUEUED_GRACE:
                        c[1] = now
            if timeout_s is not None:
                for c in list(cands):
                    if now - c[1] > timeout_s:
                        METRICS.count("pool.task_timeouts")
                        _abandon(c[0])
                        cands.remove(c)
            if not cands:
                if resubmits >= max_resubmits:
                    from hadoop_bam_tpu.utils.errors import (
                        TransientIOError,
                    )
                    raise TransientIOError(
                        f"{what} exceeded the {timeout_s:g}s "
                        f"pool_task_timeout_s deadline "
                        f"{resubmits + 1} time(s) — worker(s) presumed "
                        f"wedged") from None
                resubmits += 1
                METRICS.count("jobs.timeout_resubmits")
                cands.append([_submit(entry[0]), time.perf_counter(),
                              False, None])
                now = time.perf_counter()
            soft = latency.soft_deadline_s() if latency is not None \
                else None
            if soft is not None and not entry[2] and len(cands) == 1 \
                    and now - cands[0][1] > soft:
                entry[2] = True
                METRICS.count("jobs.speculative_launched")
                cands.append([_submit(entry[0]), time.perf_counter(),
                              True, None])
            # sleep until the nearest deadline (or a coarse slice that
            # keeps the undeadlined wait cheap), woken early by any
            # candidate completing
            waits = [0.25]
            if timeout_s is not None:
                waits += [c[1] + timeout_s - now for c in cands]
            if soft is not None and not entry[2]:
                waits += [cands[0][1] + soft - now]
            elif latency is not None and soft is None:
                waits += [float(latency.min_s)]
            cf.wait([c[0] for c in cands],
                    timeout=max(0.005, min(waits)),
                    return_when=cf.FIRST_COMPLETED)

    def _head_walls(entry, t_arrive: float, traced: bool) -> dict:
        """The wait that just ended, split by the stamps alone (nothing
        polled while it ran): before the head's ``started`` it was queued,
        after it running; from the earliest ``finished`` among the units
        still in the window, a finished unit sat behind it."""
        t_end = time.perf_counter()
        waited = t_end - t_arrive
        queued = min(max(entry[1].stamps.started - t_arrive, 0.0), waited)
        done = [f for f in (e[1].stamps.finished for e in dq)
                if f is not None and f < t_end]
        behind = t_end - max(t_arrive, min(done)) if done else 0.0
        if not traced:
            METRICS.add_wall("feed.head_wait", waited)
        for name, sec, t0 in (
                ("feed.head_queued", queued, t_arrive),
                ("feed.head_running", waited - queued, t_arrive + queued),
                ("feed.ready_behind_head", behind, t_end - behind)):
            if sec > 0.0:
                METRICS.add_wall(name, sec, t0=t0)
        return {"queued_s": queued, "running_s": waited - queued,
                "ready_behind_s": behind, "behind_done": len(done)}

    def _take(entry) -> object:
        """The consumer takes the head unit (docstring: the head wait and
        the unit's counters)."""
        if entry[1].done():
            out = _await(entry)
        else:
            t_arrive = time.perf_counter()
            if active_recorder() is not None:
                with METRICS.span("feed.head_wait", unit=entry[3]) as late:
                    out = _await(entry)
                    late.update(_head_walls(entry, t_arrive, True))
            else:
                out = _await(entry)
                _head_walls(entry, t_arrive, False)
        st = entry[1].stamps
        sums = [("units", 1),
                ("unit_queued_ns", (st.started - st.submitted) * 1e9),
                ("unit_run_ns", (st.finished - st.started) * 1e9),
                ("unit_held_ns", (time.perf_counter() - st.finished) * 1e9)]
        if st.usage is not None:     # taken while a recorder was active
            user, sys_ns = st.usage
            sums += [("unit_cpu_ns", user + sys_ns), ("unit_sys_ns", sys_ns)]
        for name, n in sums:
            METRICS.count(f"feed.{name}", int(n))
        return out

    try:
        for item in it:
            dq.append([item, _submit(item), False, len(dq)])
            if len(dq) >= window:
                break
        n_units = len(dq)
        while dq:
            entry = dq.popleft()
            for item in it:
                dq.append([item, _submit(item), False, n_units])
                n_units += 1
                break
            yield _take(entry)
    finally:
        for entry in dq:
            _abandon(entry[1])


def _close_stream(item) -> None:
    """_iter_windowed cleanup hook: join a fused chunk stream's native
    workers; buffered results (plain arrays/tuples) need nothing."""
    close = getattr(item, "close", None)
    if close is not None:
        close()


def _flatten_span_stream(items) -> Iterator[Tuple[np.ndarray, ...]]:
    """Uniform FeedPipeline input from mixed decode results: buffered
    arrays/tuples pass through as one-span items; fused chunk streams
    flatten into their per-chunk tuples."""
    for item in items:
        if isinstance(item, np.ndarray):
            yield (item,)
        elif isinstance(item, tuple):
            yield item
        else:
            yield from item


class ScanFeed:
    """One whole-file scan's feed (module docstring): the mesh it runs on,
    its tile shape and its quarantine manifest, then ``decoded`` for the
    pool's side and ``run`` (the stats verbs) or ``batches`` (the
    ``tensor_batches`` APIs) for the device's.

    ``specs`` None: the units decode into column dicts and the first one
    names the tile schema.  It is taken on the caller's thread before the
    feed exists, so none of the feed's own waits holds it: its wait is that
    thread's ``feed.head_wait`` (``_iter_windowed``) and, under a
    ``plan.execute``, inside ``feed.first_dispatch_wait``.

    ``gate``: the path whose quarantine circuit the scan answers to —
    refused here while the circuit is open, healed once ``run`` or
    ``batches`` got through the whole plan."""

    def __init__(self, fmt: str, config: HBamConfig, mesh, specs, cap: int,
                 *, block_n: int = 256, balance: bool = False,
                 fixed_shape: bool = False,
                 quarantine: Optional[QuarantineManifest] = None,
                 gate: Optional[str] = None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from hadoop_bam_tpu.parallel.mesh import make_mesh

        if gate is not None:
            check_quarantine_gate(gate, config)
        self.fmt, self.config, self.gate = fmt, config, gate
        self.mesh = make_mesh() if mesh is None else mesh
        self.n_dev = int(np.prod(self.mesh.devices.shape))
        self.sharding = NamedSharding(self.mesh, P("data"))
        self.specs = None if specs is None \
            else [TileSpec.normalize(s) for s in specs]
        self.cap, self.block_n = int(cap), block_n
        self.balance, self.fixed_shape = balance, fixed_shape
        self.quarantine = quarantine
        # a BAM span's decode counts its own records (parallel/pipeline.py)
        self._count_records = fmt != "bam"

    def decoded(self, spans: Sequence, decode: Callable, window: int, *,
                stream: Optional[Iterable] = None,
                ladder: Optional[DemotionLadder] = None,
                chunk_streams: bool = False, empty=None) -> Iterator:
        """The units decoded on the shared pool, in order, at most
        ``window`` in flight: the ``spans``, or ``stream`` — chunks cut from
        them as a compressed file inflates.

        ``decode(span)`` (``decode(span, plane)`` under a demotion
        ``ladder``) runs under the span retry policy with the scan's
        quarantine manifest, and a skipped span stands as ``empty`` (no
        rows of the specs' shapes unless given).  A chunk of a ``stream``
        runs ``decode(chunk)`` exactly once: it gives its text up, so
        nothing retries, quarantines, speculates on or re-runs it.  With
        ``chunk_streams`` a decode may hand back a fused decode's chunk
        stream, whose chunks go on as the native walk lands them; one the
        consumer never reaches is closed."""
        config, quarantine = self.config, self.quarantine
        spans = list(spans)
        if quarantine is not None and quarantine.total_spans is None:
            quarantine.total_spans = len(spans)
        if empty is None:
            empty = tuple(np.empty((0,) + s.shape, s.dtype)
                          for s in self.specs)
        wall = f"{self.fmt}.host_decode_wall"

        def unit(u):
            with (METRICS.timer("pipeline.host_decode") if chunk_streams
                  else contextlib.nullcontext()), \
                    METRICS.wall_timer("pipeline.host_decode_wall"), \
                    METRICS.span(wall):
                if stream is not None:
                    return decode(u)
                out = decode_with_retry(decode, u, config,
                                        quarantine=quarantine, ladder=ladder)
            return empty if out is None else out

        # a stream's chunks are not the idempotent unit the straggler
        # defence may run twice: it is off for them
        out = _iter_windowed(decode_pool(config),
                             spans if stream is None else stream, unit,
                             window,
                             cleanup=_close_stream if chunk_streams else None,
                             config=config if stream is None else None)
        return _flatten_span_stream(out) if chunk_streams else out

    def _feed(self, stream: Iterable, count_bytes: bool = True):
        """(fp, tuples, names): the FeedPipeline and the stream as it feeds
        it, with a column-dict feed's names (None for a tuple feed);
        (None, None, None) for a column-dict feed with no units."""
        specs, names = self.specs, None
        if specs is None:
            stream = iter(stream)
            first = next(stream, None)
            if first is None:
                return None, None, None
            names = list(first)
            specs = [TileSpec(tuple(v.shape[1:]), v.dtype,
                              _COLUMN_PADS.get(k, 0))
                     for k, v in first.items()]
            stream = (tuple(d[k] for k in names)
                      for d in itertools.chain([first], stream))
        fp = FeedPipeline(self.n_dev, self.cap, specs, block_n=self.block_n,
                          fixed_shape=self.fixed_shape, balance=self.balance,
                          config=self.config, count_bytes=count_bytes,
                          fmt=self.fmt)
        return fp, stream, names

    def _put(self, arrays, counts) -> List:
        """A group's host arrays and counts on the mesh; what comes back is
        the ring slot's in-flight handle (``FeedPipeline.stream``), so the
        packer waits on the transfer before it reuses the buffers."""
        import jax

        out = [jax.device_put(a, self.sharding) for a in arrays]
        out.append(jax.device_put(counts, self.sharding))
        if self._count_records:
            METRICS.count("pipeline.records", int(counts.sum()))
        return out

    def run(self, stream: Iterable, consume: Callable, *,
            cut: Optional[Callable] = None) -> None:
        """Eager form: every group of ``stream`` to the mesh and through
        ``consume(args, counts)`` inside ``<fmt>.kernel_wall`` — ``args``
        the group's device arrays with its device counts last (for a
        column-dict feed a dict by the schema's names and ``n_records``),
        ``counts`` the host's.  ``consume`` queues the step (dispatch stays
        asynchronous) and keeps what it returns for the family's drain.

        ``cut(arrays, counts)`` narrows a group's host arrays before they
        cross (coverage's op width); the bytes that cross are then counted
        here rather than by the feed."""
        fp, tuples, names = self._feed(stream, count_bytes=cut is None)
        if fp is not None:
            wall = f"{self.fmt}.kernel_wall"
            keys = None if names is None else (*names, "n_records")

            def dispatch(arrays, counts):
                if cut is not None:
                    arrays = cut(arrays, counts)
                    METRICS.count("pipeline.dispatch_bytes",
                                  sum(int(a.nbytes) for a in arrays)
                                  + int(counts.nbytes))
                handles = self._put(arrays, counts)
                with METRICS.span(wall):
                    consume(handles if keys is None
                            else dict(zip(keys, handles)), counts)
                return handles

            fp.feed(tuples, dispatch)
        if self.gate is not None:
            quarantine_run_ok(self.gate, self.config)

    def batches(self, stream: Iterable,
                names: Optional[Sequence[str]] = None) -> Iterator[Dict]:
        """Lazy form: one dict a group, its device arrays by ``names`` (a
        column-dict feed's own) and its counts as ``n_records``.  A
        group's ring slot is reused once the consumer asks for the next."""
        fp, tuples, keys = self._feed(stream)
        if fp is None:
            return
        keys = (*(names if keys is None else keys), "n_records")
        yield from fp.stream(
            tuples, lambda arrays, counts: dict(
                zip(keys, self._put(arrays, counts))))
        if self.gate is not None:
            quarantine_run_ok(self.gate, self.config)
