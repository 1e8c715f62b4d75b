"""Stage metrics: counters, timers, histograms, spans — context-scoped.

The reference exposed only Hadoop task counters and stderr warnings
(SURVEY.md section 5); here every pipeline stage (plan/fetch/inflate/
walk/host_decode/pack/dispatch/kernel/combine, and the query engine's
resolve/fetch/filter) ticks named counters and timers, records
latency/size distributions, and emits structured spans:

- ``count`` / ``timer``       flat counters + thread-summed work seconds
- ``wall_timer``              wall-clock UNION spans (overlapping pool
                              threads merge; see the docstring below)
- ``observe``                 log-bucketed mergeable histograms
                              (``obs/hist.py``) with p50/p95/p99
- ``span``                    wall_timer + a trace-ring event when
                              tracing is enabled (``obs/trace.py``) +
                              a ``jax.profiler`` annotation when jax is
                              active — Chrome-trace exportable

**Context scoping.**  ``METRICS`` is a PROXY: attribute access resolves
to the contextvar-scoped current ``Metrics`` instance, falling back to
the process-global default — so every historical ``METRICS.count(...)``
call site keeps working unchanged, while ``MetricsContext`` gives a
concurrent engine batch or bench row its own isolated, attributable
numbers.  ``utils/pools.submit`` and the staging packer thread carry
the context across threads (a bare ``ThreadPoolExecutor.submit`` would
silently fall back to the global).
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from collections import defaultdict
from typing import Dict, Iterator, Optional, Tuple

from hadoop_bam_tpu.obs import context as trace_ctx
from hadoop_bam_tpu.obs import flight as _flight
from hadoop_bam_tpu.obs.hist import Histogram
from hadoop_bam_tpu.obs.trace import active_recorder

try:
    import resource
    _RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)
except ImportError:                         # no rusage on this platform
    resource = None
    _RUSAGE_THREAD = None


def _usage(who) -> Tuple[int, int]:
    ru = resource.getrusage(who)
    return int(ru.ru_utime * 1e9), int(ru.ru_stime * 1e9)


def thread_usage() -> Tuple[int, int]:
    """(user ns, system ns) of the calling thread so far:
    ``getrusage(RUSAGE_THREAD)``, and where the platform has none,
    ``time.thread_time_ns`` as user time.  The kernel's user + system
    total is exact; how it splits the two is sampled by its tick."""
    if _RUSAGE_THREAD is None:
        return time.thread_time_ns(), 0
    return _usage(_RUSAGE_THREAD)


def process_usage() -> Tuple[int, int]:
    """The same two of the whole process, every thread that ever ran in
    it — native workers included (``getrusage(RUSAGE_SELF)``)."""
    if resource is None:
        return time.process_time_ns(), 0
    return _usage(resource.RUSAGE_SELF)


# span-args size guard: a pathological path/region/repr string passed as
# a span attr must not bloat the trace ring or the flight recorder —
# values are truncated and the key set is capped before any recording
_SPAN_ARG_MAX_CHARS = 120
_SPAN_ARG_MAX_KEYS = 8


def trim_span_args(args: Dict[str, object]) -> Dict[str, object]:
    """Bound one span's attr payload: at most ``_SPAN_ARG_MAX_KEYS``
    keys (insertion order wins; a ``dropped_args`` count marks the cut),
    scalar values pass through, everything else is stringified and
    truncated to ``_SPAN_ARG_MAX_CHARS`` with the elided length noted."""
    out: Dict[str, object] = {}
    dropped = 0
    for k, v in args.items():
        if len(out) >= _SPAN_ARG_MAX_KEYS:
            dropped += 1
            continue
        if isinstance(v, (int, float, bool)) or v is None:
            out[k] = v
            continue
        s = v if isinstance(v, str) else repr(v)
        if len(s) > _SPAN_ARG_MAX_CHARS:
            s = (s[:_SPAN_ARG_MAX_CHARS]
                 + f"...(+{len(s) - _SPAN_ARG_MAX_CHARS})")
        out[k] = s
    if dropped:
        out["dropped_args"] = dropped
    return out


class Metrics:
    def __init__(self) -> None:
        # re-entrant: an allocation made under the lock can start a
        # garbage collection, and a collected generator's ``finally``
        # (an abandoned _iter_windowed joining its native jobs) counts
        # into this object on the same thread
        self._lock = threading.RLock()
        self.counters: Dict[str, int] = defaultdict(int)
        self.timers: Dict[str, float] = defaultdict(float)
        self.timer_calls: Dict[str, int] = defaultdict(int)
        self.wall_timers: Dict[str, float] = defaultdict(float)
        self.wall_calls: Dict[str, int] = defaultdict(int)
        self.histograms: Dict[str, Histogram] = {}
        self._wall_active: Dict[str, list] = {}
        # bumped by reset(): a wall span that straddles a reset() must
        # not account into (or corrupt) the post-reset state — the span
        # captures the epoch at entry and discards itself on mismatch
        self._epoch = 0

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] += n

    def count_per_device(self, name: str, counts) -> None:
        """``name.<d>`` += counts[d] for every mesh position d: what a
        mesh phase handed each device, so a position that was fed
        nothing shows up as a zero instead of hiding in a total."""
        for d, c in enumerate(counts):
            self.count(f"{name}.{d}", int(c))

    def get(self, name: str) -> int:
        """Read one counter without mutating the defaultdict (a bare
        ``counters[name]`` probe would materialize a zero entry)."""
        with self._lock:
            return self.counters.get(name, 0)

    def observe(self, name: str, value: float, n: int = 1) -> None:
        """Record ``value`` into the named log-bucketed histogram
        (latencies in seconds, sizes in bytes — the name's suffix says
        which: ``*_s`` / ``*_bytes``)."""
        with self._lock:
            h = self.histograms.get(name)
            if h is None:
                h = self.histograms[name] = Histogram()
            h.record(value, n)

    def hist_summary(self, name: str) -> Dict[str, float]:
        """count/mean/p50/p95/p99/max of one histogram ({} when absent)."""
        with self._lock:
            h = self.histograms.get(name)
            return h.summary() if h is not None else {}

    def hist_dict(self, name: str) -> Dict[str, object]:
        """One histogram's full mergeable state ({} when absent) — the
        targeted read the SLO engine's admission-path burn check uses
        instead of serializing the whole instance with ``to_dict``."""
        with self._lock:
            h = self.histograms.get(name)
            return h.to_dict() if h is not None else {}

    def discard_series(self, *names: str) -> None:
        """Remove the named series (counter/timer/wall/histogram entries
        of exactly these names) — the eviction hook for bounded
        per-tenant series in a long-lived server.  Unknown names are
        ignored."""
        with self._lock:
            for n in names:
                self.counters.pop(n, None)
                self.timers.pop(n, None)
                self.timer_calls.pop(n, None)
                self.wall_timers.pop(n, None)
                self.wall_calls.pop(n, None)
                self.histograms.pop(n, None)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Consistent copy of all counters/timers (one lock acquisition) —
        the hook quarantine/failure reports use to embed resilience counts
        (pipeline.bad_spans / transient_retries / corrupt_spans,
        io.read_retries, chaos.injected_faults) without racing the pool.
        Histograms are included as their p-summaries; ``to_dict`` carries
        the full mergeable buckets."""
        with self._lock:
            return {"counters": dict(self.counters),
                    "timers": dict(self.timers),
                    "timer_calls": dict(self.timer_calls),
                    "wall_timers": dict(self.wall_timers),
                    "histograms": {k: h.summary()
                                   for k, h in self.histograms.items()}}

    @contextlib.contextmanager
    def timer(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.timers[name] += dt
                self.timer_calls[name] += 1

    @contextlib.contextmanager
    def wall_timer(self, name: str) -> Iterator[None]:
        """WALL-CLOCK span aggregation, distinct from ``timer``: spans of
        the same name that overlap in time (pool threads decoding
        concurrently) merge into their union, so the aggregate reports
        how long the stage occupied the wall — not thread-summed work
        seconds, which can exceed wall time and make pipeline overlap
        invisible (the bench's stage_timer_note caveat)."""
        t0 = time.perf_counter()
        with self._lock:
            epoch = self._epoch
            st = self._wall_active.setdefault(name, [0, t0])
            if st[0] == 0:
                st[1] = t0
            st[0] += 1
        try:
            yield
        finally:
            t1 = time.perf_counter()
            with self._lock:
                if self._epoch != epoch:
                    return     # reset() raced this span: discard it
                st = self._wall_active.get(name)
                if st is None:
                    return
                st[0] -= 1
                if st[0] == 0:
                    self.wall_timers[name] += t1 - st[1]
                    self.wall_calls[name] += 1

    def add_wall(self, name: str, seconds: float,
                 t0: Optional[float] = None,
                 args: Optional[dict] = None) -> None:
        """Record an externally-measured wall span (the FeedPipeline's
        packer/dispatch accounting measures its own intervals).  When
        tracing is enabled and the caller passes its ``perf_counter``
        start ``t0``, the interval also lands in the trace ring — with
        the active trace id and parent span, so externally-measured
        intervals join the request's causal tree.  Every add_wall also
        feeds the always-on flight recorder."""
        if args:
            args = trim_span_args(args)
        with self._lock:
            self.wall_timers[name] += seconds
            self.wall_calls[name] += 1
        if t0 is not None:
            rec = active_recorder()
            if rec is not None:
                ev_args = dict(args) if args else {}
                ctx = trace_ctx.current_trace()
                if ctx is not None:
                    ev_args["trace"] = ctx.trace_id
                    ev_args["psid"] = ctx.span_id
                rid = trace_ctx.replica_id()
                if rid is not None:
                    ev_args["replica"] = rid
                rec.complete(name, t0, seconds, ev_args or None)
        _flight.recorder().record_span(name, seconds, args or None)

    @contextlib.contextmanager
    def span(self, name: str, **args) -> Iterator[Dict[str, object]]:
        """A STAGE SPAN: ``wall_timer`` aggregation plus, when tracing is
        enabled (``obs.trace.enable_tracing``), one trace-ring event per
        occurrence — name, thread, duration, the keyword ``args``
        (byte counts, record counts; size-guarded by ``trim_span_args``)
        and the active ``TraceContext``'s (trace, sid, psid) causal ids
        — and a ``jax.profiler`` TraceAnnotation when jax is active.
        Every completion ALSO lands in the always-on flight recorder
        ring (one deque append).  Tracing disabled, this is
        ``wall_timer`` plus the flight append (the bench's
        ``obs_overhead_pct`` row pins the whole cost <2%).

        Yields a dict for args known only when the span ends (the rows
        a pack wrote): what the body puts there joins ``args``."""
        rec = active_recorder()
        late: Dict[str, object] = {}
        # child-span bookkeeping only while tracing (the causal ids are
        # for the exported tree; the flight ring needs just the trace id,
        # which it reads from the contextvar itself)
        ids = trace_ctx.begin_span() if rec is not None else None
        ann = rec.annotation(name) if rec is not None else None
        t0 = time.perf_counter()
        try:
            if ann is not None:
                with ann, self.wall_timer(name):
                    yield late
            else:
                with self.wall_timer(name):
                    yield late
        finally:
            dur = time.perf_counter() - t0
            if args or late:
                args = trim_span_args({**args, **late})
            if rec is not None:
                ev_args = dict(args) if args else {}
                if ids is not None:
                    tok, tid, sid, psid = ids
                    ev_args["trace"] = tid
                    ev_args["sid"] = sid
                    ev_args["psid"] = psid
                    try:
                        trace_ctx.end_span(tok)
                    except ValueError:
                        pass   # closed from another context: ids stand
                rid = trace_ctx.replica_id()
                if rid is not None:
                    # fleet processes stamp their replica on every span
                    # so a cross-replica trace attributes work correctly
                    ev_args["replica"] = rid
                rec.complete(name, t0, dur, ev_args or None)
            _flight.recorder().record_span(name, dur, args or None)

    # -- mesh-wide merge (parallel/distributed.merge_metrics) ----------------

    def to_dict(self) -> Dict[str, object]:
        """Full mergeable state (histograms as buckets, not summaries) —
        the allgather payload of ``merge_metrics``."""
        with self._lock:
            return {"counters": dict(self.counters),
                    "timers": dict(self.timers),
                    "timer_calls": dict(self.timer_calls),
                    "wall_timers": dict(self.wall_timers),
                    "wall_calls": dict(self.wall_calls),
                    "histograms": {k: h.to_dict()
                                   for k, h in self.histograms.items()}}

    def merge_dict(self, d: Dict[str, object]) -> None:
        """Merge one host's ``to_dict`` payload into this instance:
        counters/timers SUM (work adds across hosts), histograms merge
        by bucket addition (associative), and wall spans take the MAX
        across hosts — each host's value is already its local union, and
        hosts run concurrently, so the mesh-wide wall is bounded by the
        slowest host, not the sum."""
        with self._lock:
            for k, v in dict(d.get("counters", {})).items():
                self.counters[k] += int(v)
            for k, v in dict(d.get("timers", {})).items():
                self.timers[k] += float(v)
            for k, v in dict(d.get("timer_calls", {})).items():
                self.timer_calls[k] += int(v)
            for k, v in dict(d.get("wall_timers", {})).items():
                self.wall_timers[k] = max(self.wall_timers[k], float(v))
            for k, v in dict(d.get("wall_calls", {})).items():
                self.wall_calls[k] = max(self.wall_calls[k], int(v))
            for k, hd in dict(d.get("histograms", {})).items():
                h = self.histograms.get(k)
                if h is None:
                    h = self.histograms[k] = Histogram()
                h.merge(Histogram.from_dict(hd))

    @classmethod
    def from_dict(cls, d: Dict[str, object]) -> "Metrics":
        m = cls()
        m.merge_dict(d)
        return m

    def render(self) -> str:
        lines = []
        for k in sorted(self.counters):
            lines.append(f"counter {k} = {self.counters[k]}")
        for k in sorted(self.timers):
            calls = self.timer_calls[k]
            tot = self.timers[k]
            lines.append(f"timer   {k} = {tot:.4f}s over {calls} calls "
                         f"({tot / max(calls, 1) * 1e3:.2f} ms/call)")
        for k in sorted(self.wall_timers):
            lines.append(f"wall    {k} = {self.wall_timers[k]:.4f}s over "
                         f"{self.wall_calls[k]} span(s)")
        for k in sorted(self.histograms):
            s = self.histograms[k].summary()
            lines.append(
                f"hist    {k} = n={s['count']} mean={s['mean']:.4g} "
                f"p50={s['p50']:.4g} p95={s['p95']:.4g} "
                f"p99={s['p99']:.4g} max={s['max']:.4g}")
        return "\n".join(lines)

    def reset(self) -> None:
        with self._lock:
            self._epoch += 1
            self.counters.clear()
            self.timers.clear()
            self.timer_calls.clear()
            self.wall_timers.clear()
            self.wall_calls.clear()
            self.histograms.clear()
            self._wall_active.clear()


class NullMetrics(Metrics):
    """Every recording surface a no-op: the bench's ``obs_overhead_pct``
    row runs flagstat under this to measure what the always-on
    instrumentation itself costs (spans, counters, histogram ticks —
    tracing disabled)."""

    def count(self, name: str, n: int = 1) -> None:
        pass

    def observe(self, name: str, value: float, n: int = 1) -> None:
        pass

    def add_wall(self, name: str, seconds: float,
                 t0: Optional[float] = None,
                 args: Optional[dict] = None) -> None:
        pass

    @contextlib.contextmanager
    def timer(self, name: str) -> Iterator[None]:
        yield

    @contextlib.contextmanager
    def wall_timer(self, name: str) -> Iterator[None]:
        yield

    @contextlib.contextmanager
    def span(self, name: str, **args) -> Iterator[Dict[str, object]]:
        yield {}


# ---------------------------------------------------------------------------
# context scoping: METRICS is a proxy over the contextvar-scoped instance
# ---------------------------------------------------------------------------

_BASE = Metrics()
_CURRENT: "contextvars.ContextVar[Optional[Metrics]]" = \
    contextvars.ContextVar("hbam_metrics", default=None)


def current_metrics() -> Metrics:
    """The Metrics instance this context records into: the innermost
    active ``MetricsContext``, else the process-global default."""
    m = _CURRENT.get()
    return m if m is not None else _BASE


def base_metrics() -> Metrics:
    """The process-global default instance (what ``METRICS`` resolves to
    outside any ``MetricsContext``)."""
    return _BASE


class MetricsContext:
    """Run-scoped isolation: everything recorded inside the ``with``
    block — including work handed to the shared decode pool via
    ``utils.pools.submit`` and the staging packer thread — lands in this
    context's own ``Metrics`` instead of the process global, so two
    concurrent engine batches (or bench rows) get separately
    attributable numbers::

        with MetricsContext() as m:
            engine.query_records(batch)
        print(m.hist_summary("query.latency_s"))

    Re-entrant and nestable; pass an existing instance (e.g.
    ``NullMetrics()``) to substitute rather than isolate."""

    def __init__(self, metrics: Optional[Metrics] = None):
        self.metrics = metrics if metrics is not None else Metrics()
        self._token: Optional[contextvars.Token] = None

    def __enter__(self) -> Metrics:
        self._token = _CURRENT.set(self.metrics)
        return self.metrics

    def __exit__(self, *exc) -> None:
        if self._token is not None:
            _CURRENT.reset(self._token)
            self._token = None


class _MetricsProxy:
    """Attribute access forwards to ``current_metrics()`` — the shim
    that context-scopes every historical ``METRICS.x`` call site without
    touching it."""

    __slots__ = ()

    def __getattr__(self, name: str):
        return getattr(current_metrics(), name)

    def __repr__(self) -> str:
        return f"<METRICS proxy -> {current_metrics()!r}>"


METRICS = _MetricsProxy()
