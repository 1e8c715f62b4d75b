"""Fault injection on the pipeline decode paths (SURVEY.md section 5:
exceed the reference's corruption coverage — corrupt BGZF blocks mid-file,
flipped CRCs, truncated streams) plus the fault-classified resilience
layer: transient retry with injected-clock backoff, corruption fail-fast,
quarantine manifest, circuit breaker, chaos injection — and record serde
round-trips."""
import dataclasses

import numpy as np
import pytest

from hadoop_bam_tpu.config import DEFAULT_CONFIG
from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.formats.bamio import BamWriter
from hadoop_bam_tpu.formats.bam import SAMHeader
from hadoop_bam_tpu.formats.sam import SamRecord
from hadoop_bam_tpu.parallel.pipeline import (
    PayloadGeometry, decode_span_payload_host, decode_span_prefix_host,
    DecodeGeometry, decode_span_host, decode_with_retry,
)
from hadoop_bam_tpu.split.planners import plan_bam_spans
from hadoop_bam_tpu.utils.errors import (
    CircuitBreakerError, CorruptDataError, PlanError, TransientIOError,
    classify_error,
)
from hadoop_bam_tpu.utils.metrics import METRICS, MetricsContext
from hadoop_bam_tpu.utils.resilient import (
    FaultInjectingByteSource, FaultSpec, QuarantineManifest, RetryPolicy,
    RetryingByteSource, chaos_on,
)

from fixtures import make_header, make_records

pytestmark = pytest.mark.faults


class FakeClock:
    """Injectable clock+sleep pair: sleeping advances virtual time only,
    so backoff schedules are asserted exactly and no test ever waits."""

    def __init__(self):
        self.t = 0.0
        self.sleeps = []

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.sleeps.append(d)
        self.t += d


@pytest.fixture(scope="module")
def bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("faults") / "f.bam")
    header = make_header()
    records = make_records(header, 4000, seed=23)
    with BamWriter(path, header) as w:
        for r in records:
            w.write_sam_record(r)
    return path, header, records


def _spans(path, header, n=3):
    return plan_bam_spans(path, num_spans=n, header=header)


def _corrupt_copy(path, tmp_path, mutate):
    data = bytearray(open(path, "rb").read())
    mutate(data)
    out = str(tmp_path / "corrupt.bam")
    open(out, "wb").write(bytes(data))
    return out


def test_corrupt_cdata_midfile_raises(bam, tmp_path):
    """Garbage inside a mid-file block's DEFLATE payload must raise, not
    produce silent garbage records."""
    path, header, records = bam
    blocks = list(bgzf.scan_blocks(open(path, "rb").read()))
    victim = blocks[len(blocks) // 2]

    def mutate(data):
        start = victim.cdata_offset
        for i in range(start + 10, start + 40):
            data[i] ^= 0xFF

    bad = _corrupt_copy(path, tmp_path, mutate)
    spans = _spans(path, header)  # plan from the intact twin
    with pytest.raises(Exception):
        for s in spans:
            decode_span_prefix_host(bad, s)


def test_crc_flip_detected_with_check_crc(bam, tmp_path):
    """A bit flip that still inflates cleanly is caught by the CRC check."""
    path, header, records = bam
    raw = open(path, "rb").read()
    blocks = list(bgzf.scan_blocks(raw))
    victim = blocks[len(blocks) // 2]

    def mutate(data):
        # flip the stored CRC itself: inflate succeeds, CRC mismatches
        crc_off = victim.cdata_offset + victim.cdata_size
        data[crc_off] ^= 0xFF

    bad = _corrupt_copy(path, tmp_path, mutate)
    spans = _spans(bad, header)
    with pytest.raises(bgzf.BGZFError, match="CRC"):
        for s in spans:
            decode_span_prefix_host(bad, s, True)


def test_truncated_file_raises(bam, tmp_path):
    path, header, records = bam
    raw = open(path, "rb").read()
    out = str(tmp_path / "trunc.bam")
    open(out, "wb").write(raw[:len(raw) // 2 + 37])  # mid-block cut
    spans = _spans(path, header)  # plan from the intact file
    with pytest.raises(Exception):
        for s in spans:
            decode_span_prefix_host(out, s)


def test_bad_block_size_chain_raises(bam, tmp_path):
    """Corrupting a record's block_size field breaks the walk chain."""
    path, header, records = bam
    g = DecodeGeometry(bytes_cap=1 << 24, records_cap=1 << 16)
    spans = _spans(path, header, n=1)
    data, offs, n, _ = decode_span_host(path, spans[0], g)
    # rebuild a BGZF file whose inflated payload is a record chain (no BAM
    # header) with one corrupted block_size mid-chain
    base = int(offs[0])
    payload = bytearray(data[base:int(offs[n - 1])].tobytes())
    victim = int(offs[n // 2]) - base
    payload[victim:victim + 4] = (5).to_bytes(4, "little")  # bs < 32
    out = str(tmp_path / "badchain.bam")
    open(out, "wb").write(bgzf.compress_bytes(bytes(payload)))
    from hadoop_bam_tpu.split.spans import FileVirtualSpan
    from hadoop_bam_tpu.formats.virtual_offset import make_voffset
    import os
    whole = FileVirtualSpan(out, make_voffset(0, 0),
                            make_voffset(os.path.getsize(out), 0))
    with pytest.raises(ValueError):
        decode_span_prefix_host(out, whole)
    with pytest.raises(ValueError):
        decode_span_payload_host(out, whole, PayloadGeometry())


def _corrupt_midfile(bam, tmp_path):
    """Corrupt the DEFLATE payload of a mid-file block; returns the bad
    twin's path and the victim block (located on the intact file)."""
    path, header, records = bam
    raw = open(path, "rb").read()
    blocks = list(bgzf.scan_blocks(raw))
    victim = blocks[len(blocks) // 2]

    def mutate(data):
        start = victim.cdata_offset
        for i in range(start + 10, start + 40):
            data[i] ^= 0xFF

    return _corrupt_copy(path, tmp_path, mutate), victim


def test_skip_bad_spans_policy(bam, tmp_path):
    """With skip_bad_spans=True, a corrupt span is quarantined WITHOUT
    retries (corruption never heals) and excluded — the rest of the file
    still counts.  pipeline.bad_spans ticks only on the actual skip."""
    from hadoop_bam_tpu.parallel.pipeline import flagstat_file

    path, header, records = bam
    bad, _victim = _corrupt_midfile(bam, tmp_path)
    spans = _spans(path, header, n=4)  # plan on the intact twin

    # default policy: raise — and bad_spans must NOT tick on a raise
    METRICS.reset()
    with pytest.raises(Exception):
        flagstat_file(bad, header=header, spans=spans)
    assert METRICS.get("pipeline.bad_spans") == 0

    cfg = dataclasses.replace(DEFAULT_CONFIG, skip_bad_spans=True,
                              span_retries=1)
    METRICS.reset()
    stats = flagstat_file(bad, header=header, spans=spans, config=cfg)
    assert 0 < stats["total"] < len(records)
    assert METRICS.get("pipeline.bad_spans") >= 1
    assert METRICS.get("pipeline.corrupt_spans") >= 1
    # corruption is classified: the old blanket re-decode is gone
    assert METRICS.get("pipeline.transient_retries") == 0


def test_quarantine_manifest_names_bad_span(bam, tmp_path):
    """Acceptance: one corrupted mid-file block + skip_bad_spans=True
    completes with a manifest naming exactly the bad span's virtual-offset
    range, with zero retry attempts spent on it."""
    from hadoop_bam_tpu.parallel.pipeline import flagstat_file

    path, header, records = bam
    spans = _spans(path, header, n=4)
    # victim: the block nearest the MIDDLE of span[1]'s compressed range —
    # strictly interior, so exactly one span reads the corrupt bytes (a
    # boundary-straddling victim would legitimately fail two spans)
    raw = open(path, "rb").read()
    blocks = list(bgzf.scan_blocks(raw))
    mid = (spans[1].start[0] + spans[1].end[0]) // 2
    victim = min((b for b in blocks if b.isize),
                 key=lambda b: abs(b.coffset - mid))

    def mutate(data):
        start = victim.cdata_offset
        for i in range(start + 10, start + 40):
            data[i] ^= 0xFF

    bad = _corrupt_copy(path, tmp_path, mutate)
    bad_spans = [spans[1]]

    cfg = dataclasses.replace(DEFAULT_CONFIG, skip_bad_spans=True,
                              span_retries=3)
    q = QuarantineManifest()
    METRICS.reset()
    stats = flagstat_file(bad, header=header, spans=spans, config=cfg,
                          quarantine=q)
    assert len(q) == 1
    entry = q.to_dicts()[0]
    assert entry["span_start"] == bad_spans[0].start_voffset
    assert entry["span_end"] == bad_spans[0].end_voffset
    assert entry["path"] == bad_spans[0].path  # the span is self-describing
    assert entry["error_class"] == "corrupt"
    # ONE oracle re-decode, zero retry-policy re-decodes: corruption is
    # never retried on the same plane, but since ISSUE 11 the demotion
    # ladder confirms the failure on the zlib oracle before quarantining
    # (the data — not the native plane — is what gets blamed here), so
    # attempts counts the native try plus the zlib confirmation
    assert entry["attempts"] == 2
    assert METRICS.get("pipeline.transient_retries") == 0
    # no fault domain was charged: BOTH planes failed, so the ladder
    # correctly classified this as data corruption, not a plane fault
    from hadoop_bam_tpu import resilience
    assert resilience.registry().states() == {}
    # the manifest also rides the result dict (non-empty runs only)
    assert stats["quarantine"] == q.to_dicts()
    assert q.total_spans == len(spans)
    # clean runs keep the exact historical result shape
    clean = flagstat_file(path, header=header, spans=spans, config=cfg)
    assert "quarantine" not in clean


def test_circuit_breaker_aborts_run(bam, tmp_path):
    """With max_bad_span_fraction exceeded the run raises instead of
    silently degrading into a mostly-skipped answer."""
    from hadoop_bam_tpu.parallel.pipeline import flagstat_file

    path, header, records = bam
    bad, _victim = _corrupt_midfile(bam, tmp_path)
    spans = _spans(path, header, n=4)
    cfg = dataclasses.replace(DEFAULT_CONFIG, skip_bad_spans=True,
                              span_retries=0, max_bad_span_fraction=0.1)
    with pytest.raises(CircuitBreakerError, match="max_bad_span_fraction"):
        flagstat_file(bad, header=header, spans=spans, config=cfg)


def test_transient_retry_uses_injected_clock(bam):
    """A transient fault heals on retry: backoff runs on the injected
    policy (exact schedule asserted, virtual time only) and the span is
    NOT quarantined."""
    path, header, records = bam
    spans = _spans(path, header, n=1)
    clock = FakeClock()
    policy = RetryPolicy(retries=3, backoff_base_s=0.25, backoff_max_s=8.0,
                         jitter=0.0, sleep=clock.sleep, clock=clock)
    # first two preads fail transiently; every retry re-opens the decode
    src = FaultInjectingByteSource(
        path, [FaultSpec("transient", at_read=0, count=2)])

    def inner(s):
        return decode_span_prefix_host(src, s)

    cfg = dataclasses.replace(DEFAULT_CONFIG, span_retries=3)
    q = QuarantineManifest(total_spans=1)
    # counted in a context of the test's own: the aborted run of the test
    # before may still have a corrupt span in flight on the pool, which
    # lands in the process's metrics whenever it finishes
    with MetricsContext() as m:
        rows, _ = decode_with_retry(inner, spans[0], cfg, quarantine=q,
                                    policy=policy)
    assert rows.shape[0] == len(records)
    assert clock.sleeps == [0.25, 0.5]     # exponential, no real sleeps
    assert dict(src.injected) == {"transient": 2}
    assert len(q) == 0
    assert m.get("pipeline.transient_retries") == 2
    assert m.get("pipeline.bad_spans") == 0


def test_corrupt_fails_fast_without_retries():
    """Corruption burns zero retries even with a generous budget."""
    attempts = []

    def fn(_span):
        attempts.append(1)
        raise CorruptDataError("synthetic corruption")

    cfg = dataclasses.replace(DEFAULT_CONFIG, span_retries=5)
    with pytest.raises(CorruptDataError):
        decode_with_retry(fn, _dummy_span(), cfg)
    assert len(attempts) == 1


def test_plan_error_never_retried_or_skipped():
    """PLAN-class errors raise through even under skip_bad_spans."""
    attempts = []

    def fn(_span):
        attempts.append(1)
        raise PlanError("span exceeds geometry")

    cfg = dataclasses.replace(DEFAULT_CONFIG, span_retries=5,
                              skip_bad_spans=True)
    q = QuarantineManifest(total_spans=1)
    with pytest.raises(PlanError):
        decode_with_retry(fn, _dummy_span(), cfg, quarantine=q)
    assert len(attempts) == 1 and len(q) == 0


def _dummy_span():
    from hadoop_bam_tpu.split.spans import FileVirtualSpan
    return FileVirtualSpan("/nonexistent.bam", 0, 1 << 16)


def test_transient_exhaustion_quarantines_as_transient():
    """A fault that never heals is quarantined under its own class."""
    def fn(_span):
        raise TransientIOError("network is down")

    clock = FakeClock()
    policy = RetryPolicy(retries=2, backoff_base_s=0.1, jitter=0.0,
                         sleep=clock.sleep, clock=clock)
    cfg = dataclasses.replace(DEFAULT_CONFIG, skip_bad_spans=True)
    q = QuarantineManifest(total_spans=8)
    out = decode_with_retry(fn, _dummy_span(), cfg, quarantine=q,
                            policy=policy)
    assert out is None
    entry = q.to_dicts()[0]
    assert entry["error_class"] == "transient" and entry["attempts"] == 3
    assert clock.sleeps == [0.1, 0.2]


def test_retrying_byte_source_deadline():
    """The per-read deadline bounds backoff: when the next delay would
    overrun it, RetryingByteSource stops and raises TransientIOError."""
    from hadoop_bam_tpu.utils.seekable import BytesByteSource

    clock = FakeClock()
    always_bad = FaultInjectingByteSource(
        BytesByteSource(b"x" * 64),
        [FaultSpec("transient", count=10 ** 6)])
    src = RetryingByteSource(always_bad, RetryPolicy(
        retries=50, backoff_base_s=2.0, backoff_max_s=2.0, jitter=0.0,
        deadline_s=5.0, sleep=clock.sleep, clock=clock))
    with pytest.raises(TransientIOError):
        src.pread(0, 16)
    # 2s + 4s would pass 5s — exactly two sleeps fit under the deadline
    assert clock.sleeps == [2.0, 2.0]

    # and with a healthy budget the wrapped read heals
    clock2 = FakeClock()
    heals = FaultInjectingByteSource(
        BytesByteSource(bytes(range(64))),
        [FaultSpec("transient", at_read=0, count=2)])
    src2 = RetryingByteSource(heals, RetryPolicy(
        retries=4, backoff_base_s=0.5, jitter=0.0, sleep=clock2.sleep,
        clock=clock2))
    assert src2.pread(0, 4) == bytes(range(4))
    assert clock2.sleeps == [0.5, 1.0]


def test_chaos_registry_wraps_path_sources(bam):
    """install_chaos makes every path-opened source chaotic with zero
    driver plumbing: transient faults surface through the whole pipeline
    and heal under the span retry policy."""
    from hadoop_bam_tpu.parallel.pipeline import flagstat_file

    path, header, records = bam
    spans = _spans(path, header, n=3)
    clean = flagstat_file(path, header=header, spans=spans)
    cfg = dataclasses.replace(DEFAULT_CONFIG, span_retries=3,
                              retry_backoff_base_s=0.001,
                              retry_backoff_max_s=0.002)
    faults = [FaultSpec("transient", at_read=0, count=2)]
    METRICS.reset()
    with chaos_on(path, faults):
        stats = flagstat_file(path, header=header, spans=spans, config=cfg)
    assert {k: stats[k] for k in clean} == clean
    assert "quarantine" not in stats
    assert METRICS.get("chaos.injected_faults") >= 1
    # registry fully uninstalls: later reads are clean again
    assert flagstat_file(path, header=header, spans=spans) == clean


def test_chaos_bitflip_is_corrupt_class(bam, tmp_path):
    """A chaos bit flip inside a block body classifies as corruption:
    zero retries, quarantined when skipping is on."""
    path, header, records = bam
    raw = open(path, "rb").read()
    blocks = list(bgzf.scan_blocks(raw))
    victim = blocks[len(blocks) // 2]
    spans = _spans(path, header, n=4)
    cfg = dataclasses.replace(DEFAULT_CONFIG, skip_bad_spans=True,
                              span_retries=2, check_crc=True)
    faults = [FaultSpec("bitflip",
                        offset_range=(victim.cdata_offset,
                                      victim.cdata_offset + 16),
                        count=10 ** 6, xor_mask=0xFF)]
    from hadoop_bam_tpu.parallel.pipeline import flagstat_file
    q = QuarantineManifest()
    METRICS.reset()
    with chaos_on(path, faults):
        stats = flagstat_file(path, header=header, spans=spans, config=cfg,
                              quarantine=q)
    assert 0 < stats["total"] < len(records)
    assert len(q) >= 1
    assert all(e["error_class"] == "corrupt" for e in q.to_dicts())
    assert METRICS.get("pipeline.transient_retries") == 0


def test_classify_error_taxonomy():
    assert classify_error(TransientIOError("x")) == "transient"
    assert classify_error(TimeoutError()) == "transient"
    assert classify_error(ConnectionResetError()) == "transient"
    assert classify_error(OSError(5, "EIO")) == "transient"
    assert classify_error(CorruptDataError("x")) == "corrupt"
    assert classify_error(bgzf.BGZFError("bad magic")) == "corrupt"
    assert classify_error(ValueError("malformed")) == "corrupt"
    import zlib
    assert classify_error(zlib.error("bad code")) == "corrupt"
    assert classify_error(PlanError("bad num_spans")) == "plan"
    # deterministic OSErrors are PLAN: a path typo must raise loudly, not
    # burn retries or quarantine into an empty result
    assert classify_error(FileNotFoundError("gone.bam")) == "plan"
    assert classify_error(PermissionError("denied")) == "plan"
    assert classify_error(RuntimeError("???")) == "corrupt"  # fail-fast
    # taxonomy keeps builtin compatibility
    assert isinstance(TransientIOError("x"), OSError)
    assert isinstance(CorruptDataError("x"), ValueError)
    assert isinstance(PlanError("x"), ValueError)
    assert isinstance(bgzf.BGZFError("x"), CorruptDataError)


def test_quarantine_manifest_merge_and_serde():
    """JSON round-trip plus the multi-host union: dedup by span range,
    canonical order, identical on every host."""
    s1 = _dummy_span()
    from hadoop_bam_tpu.split.spans import FileVirtualSpan
    s2 = FileVirtualSpan("/nonexistent.bam", 1 << 20, 2 << 20)
    a = QuarantineManifest(total_spans=8)
    a.add(s2, CorruptDataError("crc"), "corrupt", 1, host=0)
    b = QuarantineManifest(total_spans=8)
    b.add(s1, TransientIOError("io"), "transient", 3, host=1)
    b.add(s2, CorruptDataError("crc"), "corrupt", 1, host=1)  # dup range
    merged = a.merged_with([b])
    assert len(merged) == 2
    starts = [e["span_start"] for e in merged.to_dicts()]
    assert starts == sorted(starts)
    back = QuarantineManifest.from_json(merged.to_json())
    assert back.to_dicts() == merged.to_dicts()
    # totals SUM across hosts (disjoint plan slices): 2 bad of 16 planned
    assert merged.total_spans == 16 and back.total_spans == 16
    assert merged.bad_fraction() == 0.125

    # single-process distributed merge is the identity
    from hadoop_bam_tpu.parallel.distributed import (
        merge_quarantine_manifests,
    )
    assert merge_quarantine_manifests(a) is a


def test_plan_errors_from_planners(bam):
    path, header, records = bam
    from hadoop_bam_tpu.parallel.distributed import serialize_plan
    with pytest.raises(PlanError):
        plan_bam_spans(path, num_spans=0, header=header)
    spans = _spans(path, header, n=3)
    with pytest.raises(PlanError, match="broadcast buffer"):
        serialize_plan(spans, max_bytes=16)


def test_serde_sam_round_trip(bam):
    path, header, records = bam
    from hadoop_bam_tpu.utils.serde import (
        decode_sam_records, encode_sam_records,
    )
    wire = encode_sam_records(records[:100], header)
    back = decode_sam_records(wire, header)
    assert len(back) == 100
    for a, b in zip(records[:100], back):
        assert a.to_line() == b.to_line()
    # corrupt wire fails loudly
    with pytest.raises(ValueError):
        decode_sam_records(wire[:len(wire) - 3], header)


def test_serde_variant_round_trip():
    from hadoop_bam_tpu.formats.vcf import VCFHeader, VcfRecord
    from hadoop_bam_tpu.utils.serde import decode_variants, encode_variants
    header_text = (
        "##fileformat=VCFv4.2\n"
        "##contig=<ID=c1,length=1000>\n"
        '##INFO=<ID=DP,Number=1,Type=Integer,Description="Depth">\n'
        '##FORMAT=<ID=GT,Number=1,Type=String,Description="Genotype">\n'
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\ts1\n")
    header = VCFHeader.from_text(header_text)
    recs = [VcfRecord.from_line(f"c1\t{10 + i}\t.\tA\tG\t50\tPASS\tDP={i}"
                                f"\tGT\t0/1") for i in range(20)]
    wire = encode_variants(recs, header)
    back = decode_variants(wire, header)
    assert len(back) == 20
    assert back[3].pos == 13 and back[3].alts == recs[3].alts


def test_metrics_counters_tick(bam):
    path, header, records = bam
    from hadoop_bam_tpu.utils.metrics import METRICS, MetricsContext
    METRICS.reset()
    for s in _spans(path, header):
        decode_span_prefix_host(path, s)
    assert METRICS.counters["pipeline.records"] == len(records)
    assert METRICS.counters["pipeline.spans"] >= 3
    assert METRICS.counters["pipeline.blocks"] > 0
    # fused single-pass decode reports its one sweep as
    # pipeline.fused_decode; the two-pass fallback keeps pipeline.inflate
    assert ("pipeline.fused_decode" in METRICS.timers
            or "pipeline.inflate" in METRICS.timers)
