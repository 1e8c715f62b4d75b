"""Observability-layer tests (``pytest -m obs``): the trace ring,
log-bucketed histograms, contextvar-scoped MetricsContext isolation
(including across the shared decode pool), mesh-merge semantics,
exporters, and the Metrics concurrency edges the code previously only
commented about (reset racing an active wall span, nested same-name
spans, histogram merge associativity)."""
import json
import random
import threading
import time

import pytest

from hadoop_bam_tpu.obs import (
    Histogram, TraceRecorder, disable_tracing, enable_tracing,
    prometheus_text,
)
from hadoop_bam_tpu.utils.metrics import (
    METRICS, Metrics, MetricsContext, NullMetrics, base_metrics,
    current_metrics,
)

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _no_tracing_leak():
    """Every test starts and ends with tracing disabled (the default)."""
    disable_tracing()
    yield
    disable_tracing()


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_bucket_error():
    h = Histogram()
    values = [0.001 * (i + 1) for i in range(1000)]   # 1ms..1s uniform
    for v in values:
        h.record(v)
    # log buckets are ~19% wide; allow one bucket of relative error
    for p, expect in ((50, 0.5), (95, 0.95), (99, 0.99)):
        got = h.percentile(p)
        assert expect * 0.75 <= got <= expect * 1.35, (p, got)
    s = h.summary()
    assert s["count"] == 1000
    assert s["max"] == pytest.approx(1.0)
    assert abs(s["mean"] - sum(values) / 1000) < 1e-9


def test_histogram_empty_and_single():
    h = Histogram()
    assert h.percentile(99) == 0.0 and h.summary()["count"] == 0
    h.record(0.25)
    # a single observation: every percentile is clamped to [min, max]
    assert h.percentile(1) == h.percentile(99) == pytest.approx(0.25,
                                                                rel=0.2)


def test_histogram_merge_associative_and_commutative():
    parts = []
    for seed in range(4):
        h = Histogram()
        r = random.Random(seed)
        for _ in range(500):
            h.record(r.lognormvariate(0.0, 3.0))
        parts.append(h)

    def combine(hs):
        out = Histogram()
        for h in hs:
            out.merge(Histogram.from_dict(h.to_dict()))   # detached
        return out.to_dict()

    left = combine([Histogram.from_dict(combine(parts[:2])), parts[2],
                    parts[3]])
    right = combine([parts[0], Histogram.from_dict(combine(parts[1:]))])
    shuffled = combine([parts[2], parts[0], parts[3], parts[1]])
    assert left == right == shuffled


def test_histogram_dict_round_trip():
    h = Histogram()
    for v in (1e-6, 0.5, 3.0, 3.0, 1e4):
        h.record(v)
    back = Histogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert back.to_dict() == h.to_dict()
    assert back.summary() == h.summary()


# ---------------------------------------------------------------------------
# trace ring
# ---------------------------------------------------------------------------

def test_trace_ring_bounds_and_drop_count():
    rec = TraceRecorder(capacity=32)
    for i in range(100):
        rec.complete(f"s{i}", float(i), 0.5)
    evs = rec.events()
    assert len(evs) == 32
    assert rec.dropped == 68
    # oldest surviving first, newest last
    assert evs[0][0] == "s68" and evs[-1][0] == "s99"
    doc = rec.chrome_trace()
    assert doc["otherData"]["dropped_events"] == 68


def test_chrome_trace_document_shape():
    rec = TraceRecorder()
    rec.complete("bam.inflate_wall", 1.0, 0.25, {"nbytes": 4096})
    doc = rec.chrome_trace(process_label="test", process_index=3)
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert any(e["name"] == "process_name" for e in meta)
    assert any(e["name"] == "thread_name" for e in meta)
    (ev,) = spans
    assert ev["pid"] == 3 and ev["dur"] == pytest.approx(0.25e6)
    assert ev["args"] == {"nbytes": 4096}
    assert ev["cat"] == "bam"
    json.dumps(doc)   # must be JSON-serializable as-is


def test_span_disabled_is_wall_timer_only():
    m = Metrics()
    with m.span("x.stage_wall", nbytes=1):
        time.sleep(0.002)
    assert m.wall_timers["x.stage_wall"] > 0
    assert m.wall_calls["x.stage_wall"] == 1


def test_span_enabled_records_ring_events_across_threads():
    rec = enable_tracing(1024)
    m = Metrics()

    def work(name):
        with m.span(name, part=name):
            time.sleep(0.002)

    ts = [threading.Thread(target=work, args=(f"pool.decode_{i}",))
          for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    with m.span("main.stage"):
        pass
    evs = rec.events()
    names = {e[0] for e in evs}
    assert {"pool.decode_0", "pool.decode_1", "pool.decode_2",
            "main.stage"} <= names
    assert len({e[3] for e in evs}) >= 2          # distinct thread ids
    assert any(e[5] == {"part": "pool.decode_1"} for e in evs)


def test_trace_save_is_loadable(tmp_path):
    rec = enable_tracing()
    with METRICS.span("query.resolve_wall"):
        pass
    out = rec.save(str(tmp_path / "t.json"))
    doc = json.load(open(out))
    assert any(e.get("name") == "query.resolve_wall"
               for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# Metrics.span's profiler annotation (satellite: no bare import error in
# hot loops; the annotation is the recorder's, resolved once)
# ---------------------------------------------------------------------------

def test_span_degrades_without_jax_profiler(monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    rec = enable_tracing()
    assert rec.annotation("stage.t") is None
    m = Metrics()
    with m.span("stage.t"):       # must not raise ImportError
        pass
    assert m.wall_calls["stage.t"] == 1
    assert [e[0] for e in rec.events()] == ["stage.t"]


def test_span_with_profiler_annotation_still_times_and_takes_late_args():
    import jax  # noqa: F401 — the recorder annotates only once jax is in

    rec = enable_tracing()
    assert rec.annotation("stage.t2") is not None
    m = Metrics()
    with m.span("stage.t2", rows=1) as late:
        late["bucket"] = 8        # known only when the span ends
    assert m.wall_calls["stage.t2"] == 1
    (ev,) = rec.events()
    assert ev[0] == "stage.t2"
    assert ev[5]["rows"] == 1 and ev[5]["bucket"] == 8


# ---------------------------------------------------------------------------
# concurrency edges (satellite: the commented races, now pinned)
# ---------------------------------------------------------------------------

def test_reset_racing_active_wall_span_discards_cleanly():
    m = Metrics()
    cm = m.wall_timer("race.stage")
    cm.__enter__()
    m.reset()                      # races the open span
    cm.__exit__(None, None, None)  # must neither raise nor account
    assert "race.stage" not in m.wall_timers
    assert m._wall_active == {}
    # and a FRESH span after the reset accounts normally
    with m.wall_timer("race.stage"):
        pass
    assert m.wall_calls["race.stage"] == 1


def test_reset_race_does_not_corrupt_new_epoch_spans():
    m = Metrics()
    old = m.wall_timer("s")
    old.__enter__()
    m.reset()
    new = m.wall_timer("s")        # new-epoch span opens before old exits
    new.__enter__()
    old.__exit__(None, None, None)  # stale exit: discarded, not counted
    new.__exit__(None, None, None)
    assert m.wall_calls["s"] == 1


def test_nested_same_name_wall_spans_union_once():
    m = Metrics()
    t0 = time.perf_counter()
    with m.wall_timer("n.stage"):
        with m.wall_timer("n.stage"):
            time.sleep(0.004)
        time.sleep(0.002)
    outer = time.perf_counter() - t0
    assert m.wall_calls["n.stage"] == 1          # ONE union span
    assert m.wall_timers["n.stage"] == pytest.approx(outer, abs=0.05)
    assert m.wall_timers["n.stage"] >= 0.006 * 0.5


def test_overlapping_thread_spans_union_not_sum():
    m = Metrics()

    def work():
        with m.wall_timer("o.stage"):
            time.sleep(0.02)

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # four ~20ms spans overlapping: the union must be far below the
    # 80ms thread-sum
    assert m.wall_timers["o.stage"] < 0.06


# ---------------------------------------------------------------------------
# MetricsContext isolation + pool propagation
# ---------------------------------------------------------------------------

def test_metrics_context_isolates_and_falls_back():
    base_before = base_metrics().get("ctx.ticks")
    with MetricsContext() as a:
        METRICS.count("ctx.ticks", 2)
        with MetricsContext() as b:               # nested
            METRICS.count("ctx.ticks", 5)
        assert current_metrics() is a
    assert a.get("ctx.ticks") == 2
    assert b.get("ctx.ticks") == 5
    assert base_metrics().get("ctx.ticks") == base_before   # untouched
    assert current_metrics() is base_metrics()


def test_two_threads_with_separate_contexts_do_not_smear():
    out = {}

    def run(name, n):
        with MetricsContext() as m:
            for _ in range(n):
                METRICS.count("smear.test")
            out[name] = m.get("smear.test")

    t1 = threading.Thread(target=run, args=("a", 3))
    t2 = threading.Thread(target=run, args=("b", 7))
    t1.start(); t2.start(); t1.join(); t2.join()
    assert out == {"a": 3, "b": 7}


def test_pool_submit_carries_context_and_records_histograms():
    import concurrent.futures as cf

    from hadoop_bam_tpu.utils import pools

    pool = cf.ThreadPoolExecutor(max_workers=2)
    try:
        with MetricsContext() as m:
            futs = [pools.submit(pool, lambda i=i: METRICS.count(
                "pooled.work", i)) for i in (1, 2, 4)]
            for f in futs:
                f.result()
        assert m.get("pooled.work") == 7          # landed in the context
        assert base_metrics().get("pooled.work") == 0
        assert m.hist_summary("pool.task_wait_s")["count"] == 3
        assert m.hist_summary("pool.task_run_s")["count"] == 3
    finally:
        pool.shutdown()


def test_null_metrics_is_inert():
    with MetricsContext(NullMetrics()) as m:
        METRICS.count("null.tick")
        METRICS.observe("null.h", 1.0)
        with METRICS.span("null.span"):
            pass
        with METRICS.timer("null.t"):
            pass
    assert m.counters == {} and m.histograms == {}
    assert m.wall_timers == {} and m.timers == {}


# ---------------------------------------------------------------------------
# mesh-wide merge semantics
# ---------------------------------------------------------------------------

def _host(seed, wall):
    m = Metrics()
    r = random.Random(seed)
    m.count("pipeline.records", 100 * (seed + 1))
    with m.timer("pipeline.inflate"):
        pass
    m.timers["pipeline.inflate"] = 0.5 * (seed + 1)
    m.add_wall("pipeline.feed_wall", wall)
    for _ in range(200):
        m.observe("query.latency_s", r.lognormvariate(-3, 1))
    return m


def test_merge_dict_sums_counters_maxes_walls_merges_hists():
    hosts = [_host(0, 1.0), _host(1, 3.0), _host(2, 2.0)]
    merged = Metrics()
    for h in hosts:
        merged.merge_dict(h.to_dict())
    assert merged.get("pipeline.records") == 600
    assert merged.timers["pipeline.inflate"] == pytest.approx(3.0)
    # wall = slowest host, not the sum
    assert merged.wall_timers["pipeline.feed_wall"] == pytest.approx(3.0)
    assert merged.hist_summary("query.latency_s")["count"] == 600
    # fold-order invariance (the allgather gives no ordering guarantee):
    # bucket counts are exactly associative; the float `total` sum is
    # order-sensitive only at machine epsilon
    other = Metrics()
    for h in reversed(hosts):
        other.merge_dict(h.to_dict())
    a = other.to_dict()
    b = merged.to_dict()
    assert a["histograms"]["query.latency_s"]["buckets"] \
        == b["histograms"]["query.latency_s"]["buckets"]
    assert a["histograms"]["query.latency_s"]["total"] \
        == pytest.approx(b["histograms"]["query.latency_s"]["total"])
    for key in ("counters", "timers", "wall_timers", "wall_calls"):
        assert a[key] == b[key]


def test_merge_metrics_single_process_returns_detached_copy():
    from hadoop_bam_tpu.parallel.distributed import merge_metrics

    with MetricsContext() as m:
        METRICS.count("merge.tick", 4)
        merged = merge_metrics()
    assert merged.get("merge.tick") == 4
    merged.count("merge.tick")                    # mutating the copy...
    assert m.get("merge.tick") == 4               # ...not the original


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_prometheus_exposition_shape():
    m = _host(1, 2.0)
    text = prometheus_text(m, labels={"host": "h1"})
    assert '# TYPE hbam_pipeline_records_total counter' in text
    assert 'hbam_pipeline_records_total{host="h1"} 200' in text
    assert '# TYPE hbam_pipeline_feed_wall_seconds gauge' in text
    assert '# TYPE hbam_query_latency_s histogram' in text
    # cumulative buckets: the +Inf bucket equals _count
    lines = text.splitlines()
    inf = next(ln for ln in lines
               if ln.startswith("hbam_query_latency_s_bucket")
               and '+Inf' in ln)
    count = next(ln for ln in lines
                 if ln.startswith("hbam_query_latency_s_count"))
    assert inf.rsplit(" ", 1)[1] == count.rsplit(" ", 1)[1] == "200"
    # bucket counts are non-decreasing
    vals = [int(ln.rsplit(" ", 1)[1]) for ln in lines
            if ln.startswith("hbam_query_latency_s_bucket")]
    assert vals == sorted(vals)


def test_metrics_snapshot_file_round_trip(tmp_path):
    from hadoop_bam_tpu.obs import load_metrics_json, save_metrics_json

    m = _host(2, 1.5)
    path = save_metrics_json(m, str(tmp_path / "m.json"))
    back = Metrics.from_dict(load_metrics_json(path))
    assert back.to_dict() == m.to_dict()


# ---------------------------------------------------------------------------
# end to end: hbam query --trace / --metrics-json and `hbam metrics`
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def indexed_bam(tmp_path_factory):
    from fixtures import make_header, make_records

    from hadoop_bam_tpu.formats.bamio import BamWriter
    from hadoop_bam_tpu.split.bai import write_bai

    path = str(tmp_path_factory.mktemp("obs") / "o.bam")
    header = make_header(2)

    def key(r):
        rid = (header.ref_names.index(r.rname) if r.rname != "*"
               else 1 << 30)
        return (rid, r.pos)

    recs = sorted(make_records(header, 600, seed=3), key=key)
    with BamWriter(path, header) as w:
        for r in recs:
            w.write_sam_record(r)
    write_bai(path)
    return path


def test_cli_query_trace_and_metrics_json(indexed_bam, tmp_path, capsys):
    from hadoop_bam_tpu.tools import cli

    # --metrics-json snapshots the process-global metrics: start them
    # from zero, whatever this worker ran before
    base_metrics().reset()
    trace_path = str(tmp_path / "trace.json")
    snap_path = str(tmp_path / "snap.json")
    rc = cli.main(["query", indexed_bam, "chr1:1-5000", "chr2:1-2000",
                   "-c", "--trace", trace_path,
                   "--metrics-json", snap_path])
    assert rc == 0
    capsys.readouterr()

    doc = json.load(open(trace_path))
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    # the acceptance set: resolve -> chunk decode -> mesh filter, plus
    # the staging pack/dispatch underneath
    assert {"query.resolve_wall", "query.decode_wall",
            "query.filter_wall", "query.dispatch_wall",
            "staging.pack"} <= names

    snap = json.load(open(snap_path))
    assert snap["counters"]["query.requests"] == 2
    assert snap["histograms"]["query.latency_s"]["count"] >= 1
    assert snap["histograms"]["query.chunk_fetch_s"]["count"] >= 1

    # the metrics verb renders and exports the snapshot
    assert cli.main(["metrics", snap_path]) == 0
    out = capsys.readouterr().out
    assert "query.latency_s" in out and "counter query.requests = 2" in out
    assert cli.main(["metrics", snap_path, "--format",
                     "prometheus"]) == 0
    out = capsys.readouterr().out
    assert "# TYPE hbam_query_latency_s histogram" in out


def test_query_latency_histogram_records_per_batch(indexed_bam):
    from hadoop_bam_tpu.query import QueryEngine, QueryRequest

    with MetricsContext() as m:
        engine = QueryEngine()
        for region in ("chr1:1-2000", "chr1:2000-9000", "chr2:1-800"):
            engine.query_records([QueryRequest(indexed_bam, region)])
    lat = m.hist_summary("query.latency_s")
    assert lat["count"] == 3
    assert lat["p99"] >= lat["p50"] > 0
