"""Degrade-and-heal resilience tests (``pytest -m resilience``):

- the closed/open/half-open ``CircuitBreaker`` state machine on an
  injected clock (decayed windows, probe budgets, retry-after hints);
- the decode-plane demotion ladder: flagstat and seq-stats under
  injected native plane faults complete byte-identical to the zlib
  oracle, demote mid-run, and heal back through a half-open probe;
- the upgraded quarantine circuit (fast-fail gate + heal on a clean
  probe run);
- serve-tier degradation: per-tenant breakers, shed taxonomy with
  ``retry_after_s`` on the wire, transport disconnect chaos that ends
  one stream without hanging the dispatcher, the health op, and
  prefetch auto-pause under fault pressure;
- chaos fault points past byte sources (pool submission, writer deflate
  workers) and the seed-derived deterministic schedules that make chaos
  runs reproducible from one ``chaos_seed``.
"""
import dataclasses
import io
import json
import os
import threading
import time

import numpy as np
import pytest

from hadoop_bam_tpu import resilience
from hadoop_bam_tpu.config import DEFAULT_CONFIG
from hadoop_bam_tpu.resilience import (
    CLOSED, HALF_OPEN, OPEN, CircuitBreaker, DecayingWindow, chaos,
)
from hadoop_bam_tpu.resilience.chaos import PointFault, fault_points_on
from hadoop_bam_tpu.utils.errors import (
    CircuitBreakerError, CorruptDataError, PlanError, TransientIOError,
)
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.resilient import (
    FaultInjectingByteSource, FaultSpec, SeededFaultSchedule, chaos_on,
    install_chaos_seeded, clear_chaos,
)

from fixtures import make_header, make_records

pytestmark = pytest.mark.resilience


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, d):
        self.t += d


# fast-backoff config shared by the driver-level tests
def _cfg(**kw):
    base = dict(retry_backoff_base_s=0.001, retry_backoff_max_s=0.002)
    base.update(kw)
    return dataclasses.replace(DEFAULT_CONFIG, **base)


@pytest.fixture(scope="module")
def bam(tmp_path_factory):
    """Coordinate-sorted + indexed, so both the scan drivers AND the
    serve tier (region resolution needs the .bai) run against it."""
    from hadoop_bam_tpu.formats.bamio import BamWriter
    from hadoop_bam_tpu.split.bai import write_bai

    path = str(tmp_path_factory.mktemp("resil") / "r.bam")
    header = make_header(2)

    def key(r):
        return (header.ref_names.index(r.rname) if r.rname != "*"
                else 1 << 30, r.pos)

    records = sorted(make_records(header, 3000, seed=11), key=key)
    with BamWriter(path, header) as w:
        for r in records:
            w.write_sam_record(r)
    write_bai(path)
    return path, header, records


def _spans(path, header, n=4):
    from hadoop_bam_tpu.split.planners import plan_bam_spans
    return plan_bam_spans(path, num_spans=n, header=header)


def _flagstat(path, header, spans, config):
    from hadoop_bam_tpu.parallel.pipeline import flagstat_file
    return flagstat_file(path, header=header, spans=spans, config=config)


# ---------------------------------------------------------------------------
# breaker state machine (injected clock, no real time)
# ---------------------------------------------------------------------------

def test_breaker_full_lifecycle():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=3, window_s=10, cooldown_s=5,
                       half_open_probes=1, clock=clk, name="t")
    assert b.state == CLOSED and b.allow()
    b.record_failure()
    b.record_failure()
    assert b.state == CLOSED        # under threshold
    b.record_failure()
    assert b.state == OPEN and not b.allow()
    assert 0 < b.retry_after_s() <= 5.0
    clk.advance(4.99)
    assert not b.allow()            # still cooling down
    clk.advance(0.02)
    assert b.state == HALF_OPEN
    assert b.allow()                # the one probe slot
    assert not b.allow()            # budget spent
    b.record_success()
    assert b.state == CLOSED and b.allow()
    assert b.opened_total == 1 and b.healed_total == 1


def test_breaker_half_open_failure_reopens():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=1, window_s=10, cooldown_s=2,
                       clock=clk)
    b.record_failure()
    assert b.state == OPEN
    clk.advance(2.1)
    assert b.allow()                # half-open probe
    b.record_failure()              # probe failed
    assert b.state == OPEN          # re-armed
    assert not b.allow()
    clk.advance(2.1)
    assert b.allow()
    b.record_success()
    assert b.state == CLOSED


def test_breaker_decayed_window_forgets_old_failures():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=3, window_s=5, cooldown_s=1,
                       clock=clk)
    b.record_failure()
    b.record_failure()
    clk.advance(60)                 # 12 windows: ~e^-12 left
    assert b.failure_rate() < 0.01
    b.record_failure()              # old burst must NOT push this over
    assert b.state == CLOSED

    w = DecayingWindow(window_s=2.0, clock=clk)
    w.add(4.0)
    clk.advance(2.0)
    assert w.value() == pytest.approx(4.0 * np.exp(-1.0), rel=1e-6)


def test_breaker_probe_budget_multiple():
    clk = FakeClock()
    b = CircuitBreaker(failure_threshold=1, cooldown_s=1,
                       half_open_probes=2, clock=clk)
    b.record_failure()
    clk.advance(1.5)
    assert b.allow() and b.allow() and not b.allow()


def test_breaker_trip_writes_flight_dump(tmp_path):
    """THE flight-recorder acceptance pin: a breaker trip auto-dumps a
    redacted snapshot containing the trip transition, the triggering
    request's trace_id, and the prior span completions — and the dump
    directory honors the rotation cap."""
    from hadoop_bam_tpu.obs import flight
    from hadoop_bam_tpu.obs.context import trace_context

    fr = flight.reset()
    fr.configure(dump_dir=str(tmp_path), dump_cap=2)
    try:
        clk = FakeClock()
        br = CircuitBreaker(failure_threshold=3.0, window_s=30.0,
                            cooldown_s=5.0, clock=clk,
                            name="tenant/web")
        with trace_context(op="serve.request", tenant="web") as ctx:
            # the request does some work (span completions land in the
            # always-on ring), then its failures trip the breaker
            for i in range(4):
                with METRICS.span("bam.fetch_wall", chunk=i):
                    pass
                br.record_failure()
        files = sorted(f for f in os.listdir(tmp_path)
                       if f.endswith(".json"))
        assert len(files) == 1          # exactly one trip, one dump
        doc = json.load(open(os.path.join(str(tmp_path), files[0])))
        assert doc["reason"] == "breaker_open:tenant/web"
        # the triggering request's trace id, at dump time and on the
        # recorded transition
        assert doc["trace"] == ctx.trace_id
        trips = [t for t in doc["transitions"]
                 if t["kind"] == "breaker" and t["state"] == "open"]
        assert trips and trips[-1]["name"] == "tenant/web"
        assert trips[-1]["trace"] == ctx.trace_id
        # the prior N span completions, attributed to the same trace
        prior = [s for s in doc["spans"] if s["name"] == "bam.fetch_wall"]
        assert len(prior) >= 3
        assert all(s["trace"] == ctx.trace_id for s in prior)
        # rotation cap: five more incidents leave at most cap files
        for k in range(5):
            CircuitBreaker(failure_threshold=1.0, clock=clk,
                           name=f"tenant/t{k}").record_failure()
        files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
        assert len(files) == 2
        assert fr.dumps_written == 6
    finally:
        flight.reset()


# ---------------------------------------------------------------------------
# demotion ladder: flagstat demotes then heals, byte-identical throughout
# ---------------------------------------------------------------------------

def test_native_faults_demote_to_zlib_then_heal(bam):
    """THE acceptance pin: injected native-plane faults -> flagstat
    completes byte-identical to the zlib oracle, the native domain's
    breaker opens (demotion), and after the cooldown a half-open probe
    heals it — all mid-run, no failed driver calls anywhere."""
    path, header, records = bam
    spans = _spans(path, header, n=5)
    clk = FakeClock()
    resilience.reset(clock=clk)

    oracle = _flagstat(path, header, spans, _cfg(
        inflate_backend="zlib", adaptive_planes=False))
    assert oracle["total"] == len(records)

    cfg = _cfg(inflate_backend="native")
    with fault_points_on("decode.native",
                         [PointFault("corrupt", count=1000)]):
        faulted = _flagstat(path, header, spans, cfg)
    assert faulted == oracle        # byte-identical through the demotion
    key = f"decode/native/{os.path.abspath(path)}"
    states = resilience.registry().states()
    assert states[key]["state"] == OPEN          # demoted: breaker open
    assert states[key]["failures_total"] >= 3

    # while OPEN (chaos cleared, cooldown NOT elapsed): runs stay on
    # zlib — and still match
    demoted = _flagstat(path, header, spans, cfg)
    assert demoted == oracle
    assert resilience.registry().states()[key]["state"] == OPEN

    # cooldown elapses -> half-open probe on native succeeds -> healed
    clk.advance(float(cfg.breaker_cooldown_s) + 0.1)
    healed = _flagstat(path, header, spans, cfg)
    assert healed == oracle
    states = resilience.registry().states()
    assert states[key]["state"] == CLOSED
    assert states[key]["healed_total"] == 1
    assert METRICS.get("resilience.heals") >= 1


def test_pure_data_corruption_charges_no_plane(bam, tmp_path):
    """Both planes fail on genuinely corrupt bytes: the ladder must NOT
    blame the native plane (oracle confirmation) — and the error class
    is CORRUPT either way."""
    from hadoop_bam_tpu.formats import bgzf

    path, header, _ = bam
    raw = open(path, "rb").read()
    data = bytearray(raw)
    spans = _spans(path, header, n=3)
    mid = (spans[1].start[0] + spans[1].end[0]) // 2
    victim = min((b for b in bgzf.scan_blocks(raw) if b.isize),
                 key=lambda b: abs(b.coffset - mid))
    for i in range(victim.cdata_offset + 10, victim.cdata_offset + 40):
        data[i] ^= 0xFF
    bad = str(tmp_path / "bad.bam")
    open(bad, "wb").write(bytes(data))
    with pytest.raises(CorruptDataError):
        _flagstat(bad, header, _spans(bad, header, n=3),
                  _cfg(inflate_backend="native"))
    assert resilience.registry().states() == {}     # nobody charged


def test_adaptive_planes_off_keeps_static_selection(bam):
    """The kill switch: with adaptive_planes=False an injected native
    fault raises instead of demoting (the pre-ISSUE-11 behavior)."""
    path, header, _ = bam
    spans = _spans(path, header, n=2)
    cfg = _cfg(inflate_backend="native", adaptive_planes=False)
    with fault_points_on("decode.native",
                         [PointFault("corrupt", count=1000)]):
        with pytest.raises(CorruptDataError):
            _flagstat(path, header, spans, cfg)
    assert resilience.registry().states() == {}


def test_payload_native_faults_demote_to_zlib_then_heal(bam):
    """The payload family's rung of the same ladder (the flagstat twin
    is above): seq-stats under injected native-plane faults equals the
    zlib oracle, opens the native domain, stays on zlib while it is
    OPEN, and a half-open probe heals it."""
    from hadoop_bam_tpu.parallel.pipeline import seq_stats_file

    path, header, records = bam
    spans = _spans(path, header, n=3)
    clk = FakeClock()
    resilience.reset(clock=clk)

    def run(config):
        out = seq_stats_file(path, header=header, spans=spans,
                             config=config)
        return {k: np.asarray(v).tolist() for k, v in out.items()}

    oracle = run(_cfg(inflate_backend="zlib", adaptive_planes=False))
    assert oracle["n_reads"] == len(records)

    cfg = _cfg(inflate_backend="native", breaker_failure_threshold=1.0)
    with fault_points_on("decode.native",
                         [PointFault("corrupt", count=1000)]):
        faulted = run(cfg)
    assert faulted == oracle
    key = f"decode/native/{os.path.abspath(path)}"
    assert resilience.registry().states()[key]["state"] == OPEN

    assert run(cfg) == oracle                    # OPEN: straight on zlib
    assert resilience.registry().states()[key]["state"] == OPEN
    clk.advance(float(cfg.breaker_cooldown_s) + 0.1)
    assert run(cfg) == oracle                    # the half-open probe
    states = resilience.registry().states()
    assert states[key]["state"] == CLOSED
    assert states[key]["healed_total"] == 1


def test_plan_error_never_demotes(bam, monkeypatch):
    """A PLAN-class failure on the native rung raises through the ladder
    untouched — no retry, no demotion to a zlib rung that would have
    decoded the span, no domain charged: a misconfigured run must not
    silently degrade (pinned since PR 9)."""
    from hadoop_bam_tpu.parallel import pipeline

    path, header, _ = bam
    real = pipeline.decode_span_prefix_host
    calls = []

    def misconfigured(src, span, check_crc, backend, *a, **kw):
        calls.append(backend)
        if backend == "native":
            raise PlanError("the native rung is misconfigured")
        return real(src, span, check_crc, backend, *a, **kw)

    monkeypatch.setattr(pipeline, "decode_span_prefix_host", misconfigured)
    spans = _spans(path, header, n=2)
    demotions = METRICS.get("pipeline.span_demotions")
    with pytest.raises(PlanError, match="misconfigured"):
        _flagstat(path, header, spans,
                  _cfg(inflate_backend="native", use_fused_decode=False))
    assert calls and set(calls) == {"native"}
    assert resilience.registry().states() == {}
    assert METRICS.get("pipeline.span_demotions") == demotions
    # the zlib rung would have decoded it: the same run, started there
    assert _flagstat(path, header, spans, _cfg(
        inflate_backend="zlib"))["total"] == 3000


# ---------------------------------------------------------------------------
# quarantine circuit: no longer one-way
# ---------------------------------------------------------------------------

def test_quarantine_circuit_gates_then_heals(bam, tmp_path):
    path, header, _ = bam
    data = bytearray(open(path, "rb").read())
    clean_bytes = bytes(data)
    spans = _spans(path, header, n=4)
    mid = (spans[1].start[0] + spans[1].end[0]) // 2
    for i in range(mid + 12, mid + 40):
        data[i] ^= 0xFF
    bad = str(tmp_path / "q.bam")
    open(bad, "wb").write(bytes(data))
    bad_spans = _spans(bad, header, n=4)
    clk = FakeClock()
    resilience.reset(clock=clk)

    cfg = _cfg(skip_bad_spans=True, span_retries=0,
               max_bad_span_fraction=0.1)
    # run 1: trips the fraction breaker — which now also OPENS the
    # per-file quarantine circuit (retry-after hint attached)
    with pytest.raises(CircuitBreakerError,
                       match="max_bad_span_fraction") as ei:
        _flagstat(bad, header, bad_spans, cfg)
    assert ei.value.retry_after_s is not None

    # run 2: fast-fails AT THE GATE (no planning, no decode) while OPEN
    t0 = METRICS.get("pipeline.spans")
    with pytest.raises(CircuitBreakerError, match="quarantine circuit"):
        _flagstat(bad, header, bad_spans, cfg)
    assert METRICS.get("pipeline.spans") == t0    # nothing was decoded
    assert METRICS.get("resilience.quarantine_gate_shed") >= 1

    # cooldown -> half-open: the probe run is admitted; still corrupt,
    # so it trips and re-opens
    clk.advance(float(cfg.breaker_cooldown_s) + 0.1)
    with pytest.raises(CircuitBreakerError, match="max_bad_span_fraction"):
        _flagstat(bad, header, bad_spans, cfg)
    br = resilience.quarantine_breaker(bad, config=cfg)
    assert br.state == OPEN and br.opened_total == 2

    # the file is repaired in place; the next cooled-down probe run
    # finishes clean and HEALS the circuit
    open(bad, "wb").write(clean_bytes)
    clk.advance(float(cfg.breaker_cooldown_s) + 0.1)
    out = _flagstat(bad, header, bad_spans, cfg)
    assert "quarantine" not in out
    assert br.state == CLOSED and br.healed_total == 1


# ---------------------------------------------------------------------------
# serve tier: tenant breakers, shed taxonomy, retry-after, health
# ---------------------------------------------------------------------------

def test_tenant_breaker_unit_shed_and_heal():
    from hadoop_bam_tpu.serve.tenancy import TenantQuotas

    clk = FakeClock()
    q = TenantQuotas(DEFAULT_CONFIG, clock=clk)
    for _ in range(3):
        q.record_outcome("noisy", CorruptDataError("bad tile"))
    # PLAN failures never count (the client's own malformed request)
    q.record_outcome("polite", PlanError("bad region"))

    with pytest.raises(TransientIOError) as ei:
        with q.admit("noisy"):
            pass
    assert ei.value.retry_after_s is not None and ei.value.retry_after_s > 0
    assert METRICS.get("resilience.tenant_shed") >= 1
    with q.admit("polite"):          # isolation: other tenants admit
        pass

    clk.advance(float(DEFAULT_CONFIG.breaker_cooldown_s) + 0.1)
    with q.admit("noisy"):           # half-open probe admits
        pass
    q.record_outcome("noisy", None)  # probe succeeded
    assert q.breaker("noisy").state == CLOSED
    assert q.breaker_states()["noisy"]["healed_total"] == 1


def test_serve_loop_tenant_breaker_sheds_with_taxonomy(bam):
    """Repeated corrupt-serving failures for one tenant open its
    breaker; the next request sheds TRANSIENT (with retry_after) while
    another tenant keeps serving — degradation, not an outage."""
    from hadoop_bam_tpu.serve import ServeLoop

    path, header, _ = bam
    cfg = dataclasses.replace(DEFAULT_CONFIG, serve_prefetch=False)
    with ServeLoop(config=cfg) as loop:
        loop.query(path, ["chr1:1-100000"], tenant="good")  # warm meta
        real_chunk = loop.engine._chunk

        def corrupt_chunk(meta, s, e):
            raise CorruptDataError("injected corrupt tile")

        loop.engine._chunk = corrupt_chunk
        try:
            # distinct uncached windows: a warm tile hit would bypass
            # the chunk tier entirely and never see the fault
            for i in range(3):
                with pytest.raises(CorruptDataError):
                    loop.query(
                        path, [f"chr2:{1 + i * 5000}-{4000 + i * 5000}"],
                        tenant="noisy")
            # breaker open: sheds at admission, TRANSIENT taxonomy
            with pytest.raises(TransientIOError) as ei:
                loop.query(path, ["chr2:90000-95000"], tenant="noisy")
            assert ei.value.retry_after_s is not None
        finally:
            loop.engine._chunk = real_chunk
        # isolation + liveness: the other tenant still gets answers
        res = loop.query(path, ["chr1:1-100000"], tenant="good")
        assert res[0].count >= 0
        h = loop.health()
        assert h["status"] == "serving"
        assert h["tenant_breakers"]["noisy"]["state"] == OPEN


class _StubLoop:
    """Minimal ServeLoop stand-in for transport-only tests."""

    def __init__(self, exc=None):
        self.exc = exc

    def submit(self, path, regions, **kw):
        import concurrent.futures as cf
        if self.exc is not None:
            raise self.exc
        fut = cf.Future()
        fut.set_result([])
        return fut

    def health(self):
        return {"status": "serving", "domains": {}, "tenant_breakers": {}}


def test_transport_error_lines_carry_retry_after():
    from hadoop_bam_tpu.serve.transport import handle_stream

    loop = _StubLoop(exc=TransientIOError("shed", retry_after_s=0.25))
    out = io.StringIO()
    handle_stream(loop, io.StringIO(
        '{"id": 7, "path": "x.bam", "region": "chr1:1-10"}\n'), out)
    doc = json.loads(out.getvalue().strip())
    # the PR-14 request-id contract: every response line echoes the
    # request's trace id (16 hex chars)
    trace = doc.pop("trace")
    assert isinstance(trace, str) and len(trace) == 16
    assert doc == {"id": 7, "error": "shed", "kind": "transient",
                   "retry_after_s": 0.25}


def test_transport_health_op_reports_state():
    from hadoop_bam_tpu.serve.transport import handle_stream

    out = io.StringIO()
    handle_stream(_StubLoop(), io.StringIO('{"id": 1, "op": "health"}\n'),
                  out)
    doc = json.loads(out.getvalue().strip())
    assert doc["id"] == 1 and doc["health"]["status"] == "serving"


def test_transport_disconnect_chaos_no_hang_no_crash(bam):
    """An injected mid-stream disconnect ends THAT stream cleanly
    (bounded time, no exception) and the dispatcher keeps serving."""
    from hadoop_bam_tpu.serve import ServeLoop, handle_stream

    path, header, _ = bam
    cfg = dataclasses.replace(DEFAULT_CONFIG, serve_prefetch=False)
    lines = "".join(
        json.dumps({"id": i, "path": path, "region": "chr1:1-100000"})
        + "\n" for i in range(3))
    with ServeLoop(config=cfg) as loop:
        out = io.StringIO()
        t0 = time.monotonic()
        with fault_points_on("serve.transport",
                             [PointFault("disconnect", at_call=1)]):
            n = handle_stream(loop, io.StringIO(lines), out)
        assert time.monotonic() - t0 < 30.0       # never a hang
        assert n == 1                              # stream ended at line 2
        assert METRICS.get("serve.transport_disconnects") >= 1
        # the response that made it out is a real answer
        docs = [json.loads(x) for x in out.getvalue().splitlines()]
        assert docs and "results" in docs[0]
        # dispatcher alive: a fresh stream serves normally
        out2 = io.StringIO()
        assert handle_stream(loop, io.StringIO(lines), out2) == 3
        assert all("results" in json.loads(x)
                   for x in out2.getvalue().splitlines())


def test_health_after_decode_chaos_reports_domains(bam):
    """Under decode chaos the serve path sheds/fails classified, and
    the health surface names the charged fault domains."""
    from hadoop_bam_tpu.serve import ServeLoop

    path, header, _ = bam
    cfg = dataclasses.replace(
        DEFAULT_CONFIG, serve_prefetch=False,
        retry_backoff_base_s=0.001, retry_backoff_max_s=0.002)
    with ServeLoop(config=cfg) as loop:
        # seed a fault domain the way a degraded decode would
        resilience.registry().domain(
            "decode", "native", "somefile").record_failure()
        h = loop.health()
        assert h["fault_pressure"] > 0
        assert "decode/native/somefile" in h["domains"]


def test_prefetch_auto_pauses_under_fault_pressure(bam):
    from hadoop_bam_tpu.serve import ServeLoop

    path, header, _ = bam
    with ServeLoop() as loop:
        d = resilience.registry().domain("decode", "native", "pressure")
        for _ in range(5):
            d.record_failure()
        assert resilience.registry().fault_pressure() >= \
            DEFAULT_CONFIG.serve_prefetch_pause_pressure
        loop.query(path, ["chr1:1-50000"])
        loop.prefetcher.drain()
        st = loop.prefetcher.stats()
        assert st["issued"] == 0 and st["paused_total"] >= 1
        assert METRICS.get("serve.prefetch_paused") >= 1

        resilience.reset()           # pressure decays away -> resumes
        loop.query(path, ["chr1:50001-100000"])
        loop.prefetcher.drain()
        assert loop.prefetcher.stats()["issued"] > 0


# ---------------------------------------------------------------------------
# chaos fault points: pool submission + writer deflate workers
# ---------------------------------------------------------------------------

def test_pool_submit_chaos_observed_and_healed(bam):
    path, header, records = bam
    spans = _spans(path, header, n=4)
    clean = _flagstat(path, header, spans, _cfg())
    with fault_points_on("pool.submit",
                         [PointFault("transient", count=2)]):
        out = _flagstat(path, header, spans, _cfg())
        assert chaos.injected_counts("pool.submit") == {"transient": 2}
    assert out == clean
    assert METRICS.get("pool.submit_retries") >= 2


def test_writer_deflate_transient_faults_recover_byte_identical():
    # four attempts a block: the three injected faults cannot exhaust one
    # block's budget, whichever worker draws them (with three attempts a
    # loaded host let the first block draw all three, and the test failed)
    cfg = _cfg(span_retries=3)
    payload = np.random.default_rng(3).integers(
        0, 255, size=200_000, dtype=np.uint8).tobytes()
    from hadoop_bam_tpu.write.parallel_bgzf import ParallelBGZFWriter

    def run(faults):
        sink = io.BytesIO()
        with fault_points_on("write.deflate", list(faults)):
            with ParallelBGZFWriter(sink, level=6, max_inflight=4,
                                    config=cfg) as w:
                for lo in range(0, len(payload), 37_000):
                    w.write(payload[lo:lo + 37_000])
        return sink.getvalue()

    clean = run([])
    faulted = run([PointFault("transient", count=3)])
    assert faulted == clean          # worker faults healed in place
    assert chaos.injected_counts("write.deflate") == {}  # cleared
    assert METRICS.get("write.deflate_retries") >= 3


def test_writer_deflate_corrupt_fault_fails_fast():
    from hadoop_bam_tpu.write.parallel_bgzf import ParallelBGZFWriter

    payload = b"x" * 200_000
    sink = io.BytesIO()
    with fault_points_on("write.deflate", [PointFault("corrupt",
                                                      count=1000)]):
        with pytest.raises(CorruptDataError):
            with ParallelBGZFWriter(sink, level=6, max_inflight=2,
                                    config=_cfg()) as w:
                w.write(payload)


# ---------------------------------------------------------------------------
# chaos-registry audit: every byte path observes installed faults
# ---------------------------------------------------------------------------

def test_shard_concat_reads_observe_chaos(bam, tmp_path):
    """The write-path shard concat reads parts through the registry:
    installed transient faults are observed AND healed by its retry."""
    from hadoop_bam_tpu.formats.bamio import BamWriter
    from hadoop_bam_tpu.write.api import write_bam_shards_concat

    path, header, records = bam
    part = str(tmp_path / "part0.bam")
    with BamWriter(part, header, write_header=False) as w:
        for r in records[:50]:
            w.write_sam_record(r)
    final = str(tmp_path / "final.bam")
    t0 = METRICS.get("chaos.injected_faults")
    with chaos_on(part, [FaultSpec("transient", at_read=0, count=1)]):
        res = write_bam_shards_concat([part], final, header, config=_cfg())
    assert res.records == 50
    assert METRICS.get("chaos.injected_faults") == t0 + 1
    assert METRICS.get("write.part_read_retries") >= 1


def test_cram_toc_walk_observes_chaos(tmp_path):
    """The query engine's CRAM container-table walk goes through
    as_byte_source: installed faults are observed (classified), not
    silently bypassed via a raw open()."""
    from hadoop_bam_tpu.api.writers import CramShardWriter
    from hadoop_bam_tpu.query.engine import QueryEngine

    header = make_header(2)
    recs = [r for r in make_records(header, 300, seed=9) if r.flag != 4]
    recs.sort(key=lambda r: (header.ref_names.index(r.rname), r.pos))
    path = str(tmp_path / "t.cram")
    with CramShardWriter(path, header) as w:
        for r in recs:
            w.write_sam_record(r)
    engine = QueryEngine()
    meta = engine._file_meta(path)
    t0 = METRICS.get("chaos.injected_faults")
    with chaos_on(path, [FaultSpec("transient", count=1000)]):
        with pytest.raises(TransientIOError):
            engine._cram_container_table(path, ("fresh", 1))
    assert METRICS.get("chaos.injected_faults") > t0
    assert meta is not None


def test_serve_prefetch_background_reads_observe_chaos(bam):
    """Prefetch's background chunk decodes flow through the registry
    (and their faults stay out of the foreground serve path)."""
    from hadoop_bam_tpu.serve import ServeLoop

    path, header, _ = bam
    cfg = dataclasses.replace(
        DEFAULT_CONFIG, retry_backoff_base_s=0.001,
        retry_backoff_max_s=0.002, span_retries=3)
    with ServeLoop(config=cfg) as loop:
        loop.query(path, ["chr1:1-50000"])       # warm meta cleanly
        t0 = METRICS.get("chaos.injected_faults")
        with chaos_on(path, [FaultSpec("transient", count=2)]):
            res = loop.query(path, ["chr1:50001-120000"])
            loop.prefetcher.drain()
        assert res[0].count >= 0                 # foreground unharmed
        assert METRICS.get("chaos.injected_faults") > t0


# ---------------------------------------------------------------------------
# seed-derived deterministic schedules
# ---------------------------------------------------------------------------

def test_seeded_schedule_is_deterministic_and_offset_keyed():
    data = bytes(np.random.default_rng(0).integers(
        0, 255, size=100_000, dtype=np.uint8))

    def fire_set(seed, order):
        src = FaultInjectingByteSource(
            data, schedule=SeededFaultSchedule(seed, transient_rate=0.4))
        fired = set()
        for off in order:
            try:
                src.pread(off, 512)
            except TransientIOError:
                fired.add(off)
        return fired

    offsets = list(range(0, 100_000, 1013))
    a = fire_set(123, offsets)
    b = fire_set(123, list(reversed(offsets)))   # order-independent
    assert a == b and 0 < len(a) < len(offsets)
    assert fire_set(124, offsets) != a           # seed changes timeline


def test_seeded_schedule_once_budget_heals_on_retry():
    sched = SeededFaultSchedule(7, transient_rate=1.0)
    src = FaultInjectingByteSource(b"abcdef" * 100, schedule=sched)
    with pytest.raises(TransientIOError):
        src.pread(0, 64)
    assert src.pread(0, 64) == (b"abcdef" * 100)[:64]   # healed


def test_chaos_seed_reproduces_flagstat_fault_timeline(bam):
    """One ``chaos_seed`` knob reproduces the whole chaos run: same
    injected offsets, same healed result, run after run."""
    path, header, records = bam
    spans = _spans(path, header, n=4)
    cfg = _cfg(span_retries=4)
    clean = _flagstat(path, header, spans, cfg)

    def seeded_run(seed):
        sched = install_chaos_seeded(path, seed, transient_rate=0.5)
        try:
            out = _flagstat(path, header, spans, cfg)
        finally:
            clear_chaos(path)
        return out, frozenset(sched._fired)

    out1, fired1 = seeded_run(42)
    out2, fired2 = seeded_run(42)
    assert out1 == out2 == clean
    assert fired1 == fired2 and len(fired1) > 0
    _, fired3 = seeded_run(43)
    assert fired3 != fired1


def test_seeded_point_faults_deterministic():
    a = chaos.seeded_point_faults(5, "pool.submit",
                                  ["transient", "delay"], 4, 32)
    b = chaos.seeded_point_faults(5, "pool.submit",
                                  ["transient", "delay"], 4, 32)
    assert [(f.kind, f.at_call) for f in a] == \
        [(f.kind, f.at_call) for f in b]
    c = chaos.seeded_point_faults(6, "pool.submit",
                                  ["transient", "delay"], 4, 32)
    assert [(f.kind, f.at_call) for f in c] != \
        [(f.kind, f.at_call) for f in a]


# ---------------------------------------------------------------------------
# soak: serve/write under combined chaos (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_soak_serve_under_combined_chaos(bam):
    """Sustained multi-tenant serving under byte-source + transport +
    pool chaos with tight quotas: every failure is a classified
    taxonomy error (never a hang, never an unclassified crash), the
    loop answers health throughout, and after the chaos clears the
    answers match the clean oracle."""
    from hadoop_bam_tpu.serve import ServeLoop

    path, header, _ = bam
    regions = ["chr1:1-100000", "chr1:100001-300000", "chr1:1-50000",
               "chr2:1-80000"]
    cfg = dataclasses.replace(
        DEFAULT_CONFIG, serve_prefetch=True, span_retries=3,
        retry_backoff_base_s=0.001, retry_backoff_max_s=0.005,
        serve_tenant_max_in_flight=2, serve_tenant_queue_depth=1,
        breaker_cooldown_s=0.2)
    with ServeLoop(config=cfg) as loop:
        oracle = [r.count for r in loop.query(path, regions)]
        sched = install_chaos_seeded(path, 1234, transient_rate=0.25,
                                     slow_rate=0.1, delay_s=0.001)
        errs = []
        done = [0]

        def client(tenant, n):
            rng = np.random.default_rng(hash(tenant) % 2**32)
            for i in range(n):
                try:
                    loop.query(path, [regions[int(rng.integers(
                        0, len(regions)))]], tenant=tenant,
                        deadline_s=20.0)
                    done[0] += 1
                except (TransientIOError, CorruptDataError,
                        CircuitBreakerError) as e:
                    errs.append(e)      # classified: acceptable shed
                except PlanError as e:  # never expected here
                    errs.append(AssertionError(e))

        try:
            with fault_points_on("pool.submit",
                                 chaos.seeded_point_faults(
                                     99, "pool.submit", ["transient"],
                                     6, 200)):
                ts = [threading.Thread(target=client,
                                       args=(f"t{k}", 15))
                      for k in range(3)]
                t0 = time.monotonic()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=240)
                assert all(not t.is_alive() for t in ts)   # no hang
                assert time.monotonic() - t0 < 240
                h = loop.health()
                assert h["status"] == "serving"
        finally:
            clear_chaos(path)
        assert not any(isinstance(e, AssertionError) for e in errs)
        assert done[0] > 0
        assert len(sched._fired) > 0
        # chaos off: the loop answers the oracle again (degrade-and-
        # heal, not degrade-and-stay-broken)
        time.sleep(0.3)              # past breaker_cooldown_s
        assert [r.count for r in loop.query(path, regions)] == oracle
