"""Crash-safe jobs tests (hadoop_bam_tpu/jobs/): durable journal
semantics, SIGKILL-and-resume byte identity for the spill sort / cohort
join / sharded write, refuse-to-resume contracts, straggler speculation
and the pool hard-timeout hang fix.

The kill tests are REAL: a subprocess doing the real pipeline work
SIGKILLs itself at a seeded journal offset (after the Nth committed
unit — deterministic, no timing races), and the parent resumes from
the journal and compares bytes against an uninterrupted oracle run.
"""
import dataclasses
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

import numpy as np
import pytest

from hadoop_bam_tpu.config import DEFAULT_CONFIG
from hadoop_bam_tpu.jobs import (
    JobJournal, UnitLatency, config_fingerprint, file_digest,
    journal_path_for, sweep_unrecorded, verify_artifact,
)
from hadoop_bam_tpu.utils.errors import (
    CorruptDataError, PlanError, TransientIOError,
)
from hadoop_bam_tpu.utils.metrics import MetricsContext

from fixtures import make_header, make_records

pytestmark = pytest.mark.resilience

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

# every journal-touching pipeline in these tests runs with fsync off:
# the durability property it buys needs a power failure to test, and
# the tmpfs-backed CI runs only care about the record/replay semantics
NOSYNC = dataclasses.replace(DEFAULT_CONFIG, journal_fsync=False)


def _child_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return env


def _run_child(script_body: str, *args, timeout=180):
    """Run a self-killing child script; return its CompletedProcess."""
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as f:
        f.write(textwrap.dedent(script_body))
        script = f.name
    try:
        return subprocess.run(
            [sys.executable, script, *map(str, args)],
            env=_child_env(), timeout=timeout, capture_output=True,
            text=True)
    finally:
        os.unlink(script)


# ---------------------------------------------------------------------------
# journal core semantics
# ---------------------------------------------------------------------------

def _mini_job(tmp_path, fingerprint="fp", params=None, kind="k"):
    inp = tmp_path / "in.dat"
    inp.write_bytes(b"x" * 1000)
    from hadoop_bam_tpu.jobs import file_identity_digest
    jp = str(tmp_path / "j.hbam-journal")
    return jp, [(str(inp), file_identity_digest(str(inp)))], {
        "kind": kind, "output": str(tmp_path / "out.dat"),
        "fingerprint": fingerprint, "params": params or {"a": 1}}


def test_journal_roundtrip_and_replay(tmp_path):
    jp, inputs, hdr = _mini_job(tmp_path)
    j, st = JobJournal.resume(jp, inputs=inputs, **hdr)
    assert st is None
    j.event("bounds", bhi=[7], blo=[9])
    j.unit_done("round", 0, runs=[["a", "b", 1, "0abc"]], round_total=5)
    j.unit_done("round", 1, runs=[], round_total=3)
    j.job_done(records=8, size=1, crc="00000000")
    j.close()
    st = JobJournal.replay(jp)
    assert st.kind == "k" and st.done["records"] == 8
    assert st.unit("round", 1)["round_total"] == 3
    assert st.last_event("bounds")["bhi"] == [7]
    assert not st.torn_tail
    # second resume sees the prior state and appends a resume event
    j2, st2 = JobJournal.resume(jp, inputs=inputs, **hdr)
    assert st2 is not None and len(st2.units) == 2
    j2.close()
    assert JobJournal.replay(jp).last_event("resume") is not None


def test_journal_torn_tail_tolerated_mid_corruption_refused(tmp_path):
    jp, inputs, hdr = _mini_job(tmp_path)
    j, _ = JobJournal.resume(jp, inputs=inputs, **hdr)
    j.unit_done("round", 0, round_total=1)
    j.unit_done("round", 1, round_total=2)
    j.close()
    raw = open(jp, "rb").read()
    # torn tail: half a final line — expected after SIGKILL, dropped
    open(jp, "wb").write(raw[:-9])
    st = JobJournal.replay(jp)
    assert st.torn_tail and st.unit("round", 0) is not None \
        and st.unit("round", 1) is None
    # mid-file corruption: NOT an honest crash shape — refused
    lines = raw.split(b"\n")
    lines[1] = lines[1].replace(b"round_total", b"round_tXtal")
    open(jp, "wb").write(b"\n".join(lines))
    with pytest.raises(CorruptDataError):
        JobJournal.replay(jp)


def test_resume_after_torn_tail_keeps_journal_replayable(tmp_path):
    """Appending onto a torn final line would weld the new record into
    one unparseable MID-file line — the resume must truncate the torn
    fragment first so resuming a resume stays the same code path."""
    jp, inputs, hdr = _mini_job(tmp_path)
    j, _ = JobJournal.resume(jp, inputs=inputs, **hdr)
    j.unit_done("round", 0, round_total=1)
    j.unit_done("round", 1, round_total=2)
    j.close()
    raw = open(jp, "rb").read()
    open(jp, "wb").write(raw[:-9])             # tear the final unit
    j2, st2 = JobJournal.resume(jp, inputs=inputs, **hdr)
    assert st2.torn_tail and st2.unit("round", 1) is None
    j2.unit_done("round", 1, round_total=2)
    j2.job_done(records=3, size=1, crc="00000000")
    j2.close()
    st3 = JobJournal.replay(jp)                # resume-of-a-resume
    assert not st3.torn_tail
    assert st3.done is not None
    assert st3.unit("round", 1)["round_total"] == 2
    assert any(e.get("name") == "resume" for e in st3.events)


@pytest.mark.parametrize("mutate,what", [
    (lambda h: {**h, "fingerprint": "other"}, "fingerprint"),
    (lambda h: {**h, "kind": "zzz"}, "kind"),
    (lambda h: {**h, "params": {"a": 2}}, "parameters"),
    (lambda h: {**h, "output": "elsewhere"}, "output"),
])
def test_resume_refuses_mismatch(tmp_path, mutate, what):
    jp, inputs, hdr = _mini_job(tmp_path)
    JobJournal.resume(jp, inputs=inputs, **hdr)[0].close()
    with pytest.raises(PlanError, match="refusing to resume"):
        JobJournal.resume(jp, inputs=inputs, **mutate(hdr))


def test_resume_refuses_changed_input_identity(tmp_path):
    jp, inputs, hdr = _mini_job(tmp_path)
    JobJournal.resume(jp, inputs=inputs, **hdr)[0].close()
    p = inputs[0][0]
    time.sleep(0.01)
    with open(p, "ab") as f:       # size + mtime change
        f.write(b"more")
    from hadoop_bam_tpu.jobs import file_identity_digest
    with pytest.raises(PlanError, match="input file identity"):
        JobJournal.resume(jp, inputs=[(p, file_identity_digest(p))],
                          **hdr)


def test_journal_naming_device_backend_refuses_resume(tmp_path):
    """A journal whose header was written under ``inflate_backend=
    "device"`` (the on-mesh decode plane PR 30 deleted) names a plane
    this build does not have: ``hbam resume`` refuses with a PlanError
    listing the valid backends instead of resuming the job on another
    plane — before the job's pipeline is even entered."""
    from hadoop_bam_tpu.jobs.runner import resume_job

    jp, inputs, hdr = _mini_job(
        tmp_path, kind="mesh_sort",
        params={"input": str(tmp_path / "in.dat"),
                "output": str(tmp_path / "out.dat"),
                "exchange": None, "round_records": None})
    j, _ = JobJournal.resume(
        jp, inputs=inputs, **hdr,
        config_values={"write_compress_level": 6,
                       "inflate_backend": "device"})
    j.close()
    before = open(jp, "rb").read()
    with pytest.raises(PlanError, match="unknown inflate backend 'device'"
                       ) as ei:
        resume_job(jp)
    for name in ("auto", "native", "zlib"):
        assert repr(name) in str(ei.value)
    assert open(jp, "rb").read() == before      # nothing appended
    assert not os.path.exists(tmp_path / "out.dat")


def test_artifact_verification_and_sweep(tmp_path):
    a = tmp_path / "art1"
    a.write_bytes(b"payload")
    size, crc = file_digest(str(a))
    assert verify_artifact(str(a), size, crc)
    assert not verify_artifact(str(a), size + 1, crc)
    a.write_bytes(b"pAyload")
    assert not verify_artifact(str(a), size, crc)
    d = tmp_path / "arts"
    d.mkdir()
    keep = d / "keep"
    keep.write_bytes(b"k")
    (d / "stale1").write_bytes(b"s")
    (d / "stale2").write_bytes(b"s")
    assert sweep_unrecorded(str(d), [str(keep)]) == 2
    assert sorted(os.listdir(d)) == ["keep"]


def test_config_fingerprint_tracks_only_named_fields():
    base = config_fingerprint(DEFAULT_CONFIG, ("write_compress_level",))
    changed = config_fingerprint(
        dataclasses.replace(DEFAULT_CONFIG, write_compress_level=1),
        ("write_compress_level",))
    unrelated = config_fingerprint(
        dataclasses.replace(DEFAULT_CONFIG, serve_prefetch=False),
        ("write_compress_level",))
    assert base != changed and base == unrelated


# ---------------------------------------------------------------------------
# straggler defense: decaying latency -> soft deadlines, speculation
# ---------------------------------------------------------------------------

def test_unit_latency_deadline_and_decay():
    ul = UnitLatency(multiplier=2.0, min_s=0.0, min_samples=8,
                     decay_every=16)
    assert ul.soft_deadline_s() is None     # warmup: never speculate
    for _ in range(8):
        ul.observe(1.0)
    d0 = ul.soft_deadline_s()
    assert d0 == pytest.approx(2.0, rel=0.25)
    # regime shift: decay lets the deadline follow RECENT latencies
    for _ in range(200):
        ul.observe(0.01)
    assert ul.soft_deadline_s() < d0 / 10


def test_speculation_first_result_wins(shared_pool):
    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

    lock = threading.Lock()
    seen = set()

    def fn(i):
        with lock:
            first = i not in seen
            seen.add(i)
        if i == 30 and first:
            time.sleep(2.0)        # the straggler's FIRST copy only
            return i
        time.sleep(0.005)
        return i

    cfg = dataclasses.replace(DEFAULT_CONFIG, straggler_min_s=0.05,
                              straggler_multiplier=2.0)
    with MetricsContext() as m:
        out = list(_iter_windowed(shared_pool, range(32), fn, 4,
                                  config=cfg))
    snap = m.snapshot()
    assert out == list(range(32))          # order preserved, no dupes
    assert snap["counters"].get("jobs.speculative_launched", 0) >= 1
    assert snap["counters"].get("jobs.speculative_won", 0) >= 1


def test_small_runs_never_speculate(shared_pool):
    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

    cfg = dataclasses.replace(DEFAULT_CONFIG, straggler_min_s=0.0,
                              straggler_multiplier=0.0)
    with MetricsContext() as m:
        out = list(_iter_windowed(shared_pool, range(8),
                                  lambda i: i, 4, config=cfg))
    assert out == list(range(8))
    assert m.snapshot()["counters"].get("jobs.speculative_launched",
                                        0) == 0


@pytest.fixture()
def shared_pool():
    import concurrent.futures as cf

    pool = cf.ThreadPoolExecutor(max_workers=8)
    yield pool
    pool.shutdown(wait=False, cancel_futures=True)


# ---------------------------------------------------------------------------
# pool hard timeout: the wedged-worker hang fix
# ---------------------------------------------------------------------------

def test_pool_timeout_resubmits_past_wedged_worker(shared_pool):
    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

    release = threading.Event()
    lock = threading.Lock()
    attempts = {}

    def fn(i):
        with lock:
            attempts[i] = attempts.get(i, 0) + 1
            first = attempts[i] == 1
        if i == 5 and first:
            release.wait()                 # wedged worker
            return -1
        return i * 10

    cfg = dataclasses.replace(DEFAULT_CONFIG, pool_task_timeout_s=0.25,
                              speculative_decode=False)
    try:
        with MetricsContext() as m:
            out = list(_iter_windowed(shared_pool, range(8), fn, 4,
                                      config=cfg))
        snap = m.snapshot()
        assert out == [i * 10 for i in range(8)]
        assert snap["counters"].get("pool.task_timeouts", 0) >= 1
        assert snap["counters"].get("jobs.timeout_resubmits", 0) >= 1
    finally:
        release.set()


def test_pool_timeout_exhaustion_is_classified_transient(shared_pool):
    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

    release = threading.Event()

    def fn(i):
        if i == 2:
            release.wait()
            return -1
        return i

    cfg = dataclasses.replace(DEFAULT_CONFIG, pool_task_timeout_s=0.15,
                              span_retries=1, speculative_decode=False)
    try:
        with pytest.raises(TransientIOError, match="pool_task_timeout"):
            list(_iter_windowed(shared_pool, range(4), fn, 2,
                                config=cfg))
    finally:
        release.set()


def test_pool_timeout_does_not_resubmit_deterministic_failures(
        shared_pool):
    """A span whose decode genuinely FAILED (vs timed out) must raise
    immediately — burning the timeout re-submission budget on a
    known-failing span duplicates the failure and mislabels it as a
    wedged worker."""
    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

    calls = {"n": 0}

    def fn(i):
        if i == 1:
            calls["n"] += 1
            raise CorruptDataError("bad bytes")
        return i

    cfg = dataclasses.replace(DEFAULT_CONFIG, pool_task_timeout_s=30.0,
                              speculative_decode=False)
    with MetricsContext() as m:
        with pytest.raises(CorruptDataError):
            list(_iter_windowed(shared_pool, range(4), fn, 2,
                                config=cfg))
    assert calls["n"] == 1                  # ran once, never re-raced
    assert m.snapshot()["counters"].get("jobs.timeout_resubmits",
                                        0) == 0


def test_pool_timeout_is_active_wait_not_submit_age():
    """Queue wait on a backlogged-but-healthy single-worker pool must
    not burn the wedged-worker deadline: the tail items' submit age
    (~1.3s) far exceeds the 1.0s timeout, but each one's ACTIVE wait is
    well under it — a submit-anchored deadline would abandon healthy
    decodes and exhaust the budget on re-submissions that queue behind
    the same backlog."""
    import concurrent.futures as cf

    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

    pool = cf.ThreadPoolExecutor(max_workers=1)

    def fn(i):
        time.sleep(0.7 if i == 0 else 0.3)
        return i

    cfg = dataclasses.replace(DEFAULT_CONFIG, pool_task_timeout_s=1.0,
                              span_retries=0, speculative_decode=False)
    try:
        with MetricsContext() as m:
            out = list(_iter_windowed(pool, range(4), fn, 4,
                                      config=cfg))
        assert out == list(range(4))
        assert m.snapshot()["counters"].get("pool.task_timeouts",
                                            0) == 0
    finally:
        pool.shutdown(wait=False, cancel_futures=True)


def test_chaos_pool_task_delay_wedges_worker_and_timeout_heals():
    """The standing hang: a chaos 'delay' fault at the new pool.task
    point wedges a WORKER mid-task; without pool_task_timeout_s the
    consumer would block for the full delay — with it, the item is
    re-submitted and the run completes promptly."""
    import concurrent.futures as cf

    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed
    from hadoop_bam_tpu.resilience.chaos import (
        PointFault, fault_points_on,
    )
    from hadoop_bam_tpu.utils import pools

    pool = cf.ThreadPoolExecutor(max_workers=4)
    cfg = dataclasses.replace(DEFAULT_CONFIG, pool_task_timeout_s=0.2,
                              speculative_decode=False)
    t0 = time.perf_counter()
    try:
        with fault_points_on("pool.task",
                             [PointFault(kind="delay", at_call=1,
                                         delay_s=5.0)]):
            with MetricsContext() as m:
                out = list(_iter_windowed(pool, range(6), lambda i: i,
                                          2, config=cfg))
        assert out == list(range(6))
        assert m.snapshot()["counters"].get("pool.task_timeouts",
                                            0) >= 1
        assert time.perf_counter() - t0 < 10.0    # not the 30s wedge
    finally:
        pool.shutdown(wait=False, cancel_futures=True)


def test_fully_wedged_pool_still_surfaces_within_grace():
    """When EVERY worker is wedged, re-submissions never dequeue — the
    bounded queued-anchor grace must let the budget exhaust and raise
    instead of holding the anchor (and the consumer) forever."""
    import concurrent.futures as cf

    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

    release = threading.Event()
    pool = cf.ThreadPoolExecutor(max_workers=2)
    cfg = dataclasses.replace(DEFAULT_CONFIG, pool_task_timeout_s=0.1,
                              span_retries=1, speculative_decode=False)
    t0 = time.perf_counter()
    try:
        with pytest.raises(TransientIOError, match="pool_task_timeout"):
            list(_iter_windowed(pool, range(4),
                                lambda i: (release.wait(), i)[1], 4,
                                config=cfg))
        # ~timeout + (retries * grace-bounded queued wait) — bounded,
        # never the forever-hang
        assert time.perf_counter() - t0 < 10.0
    finally:
        release.set()
        pool.shutdown(wait=False, cancel_futures=True)


def test_result_with_timeout_classifies(shared_pool):
    ev = threading.Event()
    from hadoop_bam_tpu.utils.pools import result_with_timeout

    fut = shared_pool.submit(ev.wait)
    try:
        with pytest.raises(TransientIOError):
            result_with_timeout(fut, 0.1, what="probe")
    finally:
        ev.set()


# ---------------------------------------------------------------------------
# ShardedFileWriter: stale temp sweep + journaled shard commits
# ---------------------------------------------------------------------------

def test_sharded_writer_sweeps_stale_temps(tmp_path):
    from hadoop_bam_tpu.write import ShardedFileWriter

    sw = ShardedFileWriter(str(tmp_path / "out.bin"), 3)
    os.makedirs(sw.shard_dir)
    for name in ("part-00000.tmp", "part-00002.tmp"):
        (tmp_path / "out.bin.hbam-shards" / name).write_bytes(b"junk")
    (tmp_path / "out.bin.hbam-shards" / "part-00001").write_bytes(b"ok")
    with MetricsContext() as m:
        assert sw.sweep_stale_temps() == 2
    assert m.snapshot()["counters"]["write.stale_temps_swept"] == 2
    assert os.listdir(sw.shard_dir) == ["part-00001"]
    # prepare() also counts before clearing the directory
    (tmp_path / "out.bin.hbam-shards" / "part-00000.tmp").write_bytes(
        b"junk")
    with MetricsContext() as m:
        sw.prepare()
    assert m.snapshot()["counters"]["write.stale_temps_swept"] == 1
    assert not os.path.isdir(sw.shard_dir)


def test_sharded_writer_journal_skip_and_reverify(tmp_path):
    from hadoop_bam_tpu.write import (
        ShardedFileWriter, write_shards_journaled,
    )

    final = str(tmp_path / "out.bin")
    jp = str(tmp_path / "w.hbam-journal")
    payloads = [bytes([i]) * 64 for i in range(5)]
    jr, st = JobJournal.resume(jp, kind="shard_write", inputs=[],
                               output=final, fingerprint="f", params={})
    sw = ShardedFileWriter(final, 5, journal=jr)
    assert write_shards_journaled(sw, payloads) == 5
    jr.close()
    mtimes = {k: os.stat(sw.shard_path(k)).st_mtime_ns for k in range(5)}
    jr2, st2 = JobJournal.resume(jp, kind="shard_write", inputs=[],
                                 output=final, fingerprint="f",
                                 params={})
    sw2 = ShardedFileWriter(final, 5, journal=jr2, resume_state=st2)
    with MetricsContext() as m:
        assert write_shards_journaled(sw2, payloads) == 0
    assert m.snapshot()["counters"].get("jobs.shards_skipped") == 5
    assert all(os.stat(sw2.shard_path(k)).st_mtime_ns == mtimes[k]
               for k in range(5))          # verified-skip, not rewrite
    # a part the crash corrupted fails verification and rewrites
    open(sw2.shard_path(3), "wb").write(b"garbage")
    assert write_shards_journaled(sw2, payloads) == 1
    assert open(sw2.shard_path(3), "rb").read() == payloads[3]
    jr2.close()


def test_sigkill_mid_sharded_write_resumes_byte_identical(tmp_path):
    """Child SIGKILLs itself after 2 committed shards; the resumed
    parent writes only the remainder and the concatenation matches an
    uninterrupted oracle byte for byte."""
    out = str(tmp_path / "out.bin")
    jp = str(tmp_path / "w.hbam-journal")
    r = _run_child("""
        import os, signal, sys
        from hadoop_bam_tpu.jobs import JobJournal
        from hadoop_bam_tpu.write import (
            ShardedFileWriter, write_shards_journaled,
        )
        out, jp = sys.argv[1:3]
        orig = JobJournal.unit_done
        n = [0]
        def patched(self, kind, key, **kw):
            orig(self, kind, key, **kw)
            n[0] += 1
            if n[0] >= 2:
                os.kill(os.getpid(), signal.SIGKILL)
        JobJournal.unit_done = patched
        payloads = [bytes([i]) * 4096 for i in range(6)]
        jr, st = JobJournal.resume(jp, kind="shard_write", inputs=[],
                                   output=out, fingerprint="f",
                                   params={}, fsync=False)
        sw = ShardedFileWriter(out, 6, journal=jr, resume_state=st)
        # a stale temp from "an even earlier crash"
        os.makedirs(sw.shard_dir, exist_ok=True)
        open(os.path.join(sw.shard_dir, "part-00005.tmp"), "wb").write(
            b"debris")
        write_shards_journaled(sw, payloads)
        raise SystemExit("unreachable: child must have been killed")
    """, out, jp, timeout=60)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr)

    payloads = [bytes([i]) * 4096 for i in range(6)]
    jr, st = JobJournal.resume(jp, kind="shard_write", inputs=[],
                               output=out, fingerprint="f", params={},
                               fsync=False)
    from hadoop_bam_tpu.write import (
        ShardedFileWriter, write_shards_journaled,
    )
    sw = ShardedFileWriter(out, 6, journal=jr, resume_state=st)
    with MetricsContext() as m:
        swept = sw.sweep_stale_temps()
        wrote = write_shards_journaled(sw, payloads)
    snap = m.snapshot()
    assert swept >= 1                      # the crashed run's debris
    assert 0 < wrote <= 4                  # committed shards skipped
    assert snap["counters"].get("jobs.shards_skipped", 0) >= 2
    assert sw.missing_parts() == []
    got = b"".join(open(sw.shard_path(k), "rb").read()
                   for k in range(6))
    assert got == b"".join(payloads)
    jr.close()


# ---------------------------------------------------------------------------
# SIGKILL mid-sort -> hbam resume, byte-identical, fewer spans decoded
# ---------------------------------------------------------------------------

_SORT_CHILD = """
    import os, sys
    os.environ.pop("JAX_PLATFORMS", None)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import signal
    from hadoop_bam_tpu.jobs import JobJournal
    kill_after, src, out, jp, rr = (int(sys.argv[1]), sys.argv[2],
                                    sys.argv[3], sys.argv[4],
                                    int(sys.argv[5]))
    orig = JobJournal.unit_done
    n = [0]
    def patched(self, kind, key, **kw):
        orig(self, kind, key, **kw)
        if kind == "round":
            n[0] += 1
            if n[0] >= kill_after:
                os.kill(os.getpid(), signal.SIGKILL)
    JobJournal.unit_done = patched
    import dataclasses
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.parallel.mesh_sort import sort_bam_mesh
    cfg = dataclasses.replace(DEFAULT_CONFIG, journal_fsync=False)
    sort_bam_mesh(src, out, round_records=rr, journal_path=jp,
                  config=cfg)
    raise SystemExit("unreachable: child must have been killed")
"""


@pytest.fixture(scope="module")
def sort_fixture(tmp_path_factory):
    """A shuffled BAM + its uninterrupted spill-sort oracle bytes."""
    from hadoop_bam_tpu.formats.bamio import BamWriter
    from hadoop_bam_tpu.parallel.mesh_sort import sort_bam_mesh

    d = tmp_path_factory.mktemp("jobs_sort")
    header = make_header()
    recs = list(make_records(header, 700, seed=11))
    random.Random(5).shuffle(recs)
    src = str(d / "in.bam")
    with BamWriter(src, header) as w:
        for rec in recs:
            w.write_sam_record(rec)
    oracle = str(d / "oracle.bam")
    n = sort_bam_mesh(src, oracle, round_records=30)
    return {"src": src, "oracle_bytes": open(oracle, "rb").read(),
            "records": n, "round_records": 30}


@pytest.mark.parametrize("kill_after", [1, 2])
def test_sigkill_mid_mesh_sort_resumes_byte_identical(tmp_path,
                                                      sort_fixture,
                                                      kill_after):
    out = str(tmp_path / "out.bam")
    jp = journal_path_for(out)
    r = _run_child(_SORT_CHILD, kill_after, sort_fixture["src"], out,
                   jp, sort_fixture["round_records"])
    assert r.returncode == -signal.SIGKILL, (r.returncode,
                                             r.stderr[-2000:])
    st = JobJournal.replay(jp)
    assert len([u for (k, _), u in st.units.items()
                if k == "round"]) == kill_after
    assert os.path.isdir(out + ".mesh-spill")   # survived the kill

    from hadoop_bam_tpu.parallel.mesh_sort import sort_bam_mesh

    with MetricsContext() as m:
        n = sort_bam_mesh(sort_fixture["src"], out,
                          round_records=sort_fixture["round_records"],
                          journal_path=jp, config=NOSYNC)
    snap = m.snapshot()
    assert n == sort_fixture["records"]
    assert open(out, "rb").read() == sort_fixture["oracle_bytes"]
    # journal-verified skips: strictly fewer spans re-decoded
    assert snap["counters"].get("jobs.rounds_skipped") == kill_after
    assert snap["counters"].get("jobs.spans_skipped", 0) > 0
    ev = JobJournal.replay(jp).last_event("resume_plan")
    assert ev["rounds_skipped"] == kill_after
    assert ev["spans_skipped"] > 0
    assert not os.path.isdir(out + ".mesh-spill")  # cleaned on success


def test_sort_journal_torn_tail_resumes(tmp_path, sort_fixture):
    """Truncate the journal mid-final-line (what an unflushed page
    loses): the torn unit's round re-runs, output stays identical."""
    out = str(tmp_path / "out.bam")
    jp = journal_path_for(out)
    r = _run_child(_SORT_CHILD, 2, sort_fixture["src"], out, jp,
                   sort_fixture["round_records"])
    assert r.returncode == -signal.SIGKILL
    raw = open(jp, "rb").read()
    open(jp, "wb").write(raw[:-11])        # tear the final unit record
    st = JobJournal.replay(jp)
    assert st.torn_tail

    from hadoop_bam_tpu.parallel.mesh_sort import sort_bam_mesh

    with MetricsContext() as m:
        n = sort_bam_mesh(sort_fixture["src"], out,
                          round_records=sort_fixture["round_records"],
                          journal_path=jp, config=NOSYNC)
    assert n == sort_fixture["records"]
    assert open(out, "rb").read() == sort_fixture["oracle_bytes"]
    assert m.snapshot()["counters"].get("jobs.rounds_skipped") == 1


def test_sort_resume_refuses_config_fingerprint_mismatch(tmp_path,
                                                         sort_fixture):
    out = str(tmp_path / "out.bam")
    jp = journal_path_for(out)
    r = _run_child(_SORT_CHILD, 1, sort_fixture["src"], out, jp,
                   sort_fixture["round_records"])
    assert r.returncode == -signal.SIGKILL

    from hadoop_bam_tpu.parallel.mesh_sort import sort_bam_mesh

    cfg = dataclasses.replace(NOSYNC, write_compress_level=1)
    with pytest.raises(PlanError, match="fingerprint"):
        sort_bam_mesh(sort_fixture["src"], out,
                      round_records=sort_fixture["round_records"],
                      journal_path=jp, config=cfg)
    # and a changed round_records is a params mismatch
    with pytest.raises(PlanError, match="parameters"):
        sort_bam_mesh(sort_fixture["src"], out, round_records=29,
                      journal_path=jp, config=NOSYNC)


def test_completed_sort_job_is_verified_noop(tmp_path, sort_fixture):
    from hadoop_bam_tpu.parallel.mesh_sort import sort_bam_mesh

    out = str(tmp_path / "out.bam")
    jp = journal_path_for(out)
    n1 = sort_bam_mesh(sort_fixture["src"], out,
                       round_records=sort_fixture["round_records"],
                       journal_path=jp, config=NOSYNC)
    mtime = os.stat(out).st_mtime_ns
    with MetricsContext() as m:
        n2 = sort_bam_mesh(sort_fixture["src"], out,
                           round_records=sort_fixture["round_records"],
                           journal_path=jp, config=NOSYNC)
    assert (n1, n2) == (sort_fixture["records"],) * 2
    assert m.snapshot()["counters"].get("jobs.jobs_skipped") == 1
    assert os.stat(out).st_mtime_ns == mtime    # genuinely untouched
    # ...but a vanished output rebuilds from the journal's done record
    os.unlink(out)
    n3 = sort_bam_mesh(sort_fixture["src"], out,
                       round_records=sort_fixture["round_records"],
                       journal_path=jp, config=NOSYNC)
    assert n3 == n1
    assert open(out, "rb").read() == sort_fixture["oracle_bytes"]


def test_hbam_resume_reconstructs_nondefault_config(tmp_path,
                                                    sort_fixture,
                                                    capsys):
    """A job journaled with non-default output-affecting knobs must be
    resumable from the bare CLI: the header's recorded field values
    rebuild the config, instead of DEFAULT_CONFIG's fingerprint
    refusing a journal nothing actually invalidated."""
    from hadoop_bam_tpu.parallel.mesh_sort import sort_bam_mesh
    from hadoop_bam_tpu.tools import cli

    cfg = dataclasses.replace(NOSYNC, write_compress_level=1)
    out = str(tmp_path / "out.bam")
    jp = journal_path_for(out)
    n1 = sort_bam_mesh(sort_fixture["src"], out,
                       round_records=sort_fixture["round_records"],
                       journal_path=jp, config=cfg)
    want = open(out, "rb").read()
    assert want != sort_fixture["oracle_bytes"]    # level 1 != level 6
    os.unlink(out)                                 # force a rebuild
    assert cli.main(["resume", jp]) == 0
    capsys.readouterr()
    assert open(out, "rb").read() == want
    assert n1 == sort_fixture["records"]


def test_hbam_resume_and_jobs_cli(tmp_path, sort_fixture, capsys):
    """The CLI verbs over a real killed job: `hbam jobs` reports it
    resumable, `hbam resume` finishes it byte-identically."""
    from hadoop_bam_tpu.tools import cli

    out = str(tmp_path / "out.bam")
    jp = journal_path_for(out)
    r = _run_child(_SORT_CHILD, 1, sort_fixture["src"], out, jp,
                   sort_fixture["round_records"])
    assert r.returncode == -signal.SIGKILL

    assert cli.main(["jobs", str(tmp_path)]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert any("mesh_sort_spill" in ln and "resumable" in ln
               for ln in lines)

    assert cli.main(["resume", jp]) == 0
    cap = capsys.readouterr().out
    assert open(out, "rb").read() == sort_fixture["oracle_bytes"]
    # the verb reports the skip counters (value is the process-global
    # accumulation, so pin presence, not magnitude)
    assert "jobs.rounds_skipped" in cap

    assert cli.main(["jobs", str(tmp_path)]) == 0
    assert "done" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# SIGKILL mid-cohort-join -> resumed chunks byte-identical
# ---------------------------------------------------------------------------

def _cohort_fixture(tmp_path):
    from test_cohort import _random_sample_lines, _write_sample

    rng = random.Random(17)
    files = []
    for i in range(4):
        p = str(tmp_path / f"s{i}.vcf")
        _write_sample(p, f"s{i}", _random_sample_lines(rng, n_sites=25))
        files.append(p)
    mp = str(tmp_path / "cohort.json")
    with open(mp, "w") as f:
        json.dump({"samples": [{"id": f"s{i}", "path": p}
                               for i, p in enumerate(files)]}, f)
    return mp


def _chunks_of(ds):
    return [{k: v.copy() for k, v in c.items()}
            for c in ds.site_chunks()]


def _assert_chunks_equal(a, b):
    assert len(a) == len(b)
    for ca, cb in zip(a, b):
        for k in ca:
            np.testing.assert_array_equal(ca[k], cb[k])


def test_sigkill_mid_cohort_join_resumes_identical(tmp_path):
    from hadoop_bam_tpu.cohort.dataset import open_cohort

    mp = _cohort_fixture(tmp_path)
    cfg = dataclasses.replace(NOSYNC, cohort_chunk_sites=11)
    oracle = _chunks_of(open_cohort(mp, cfg))
    assert len(oracle) > 4

    jp = str(tmp_path / "cohort.hbam-journal")
    r = _run_child("""
        import os, signal, sys, dataclasses
        os.environ.pop("JAX_PLATFORMS", None)
        import jax
        jax.config.update("jax_platforms", "cpu")
        from hadoop_bam_tpu.jobs import JobJournal
        mp, jp = sys.argv[1:3]
        orig = JobJournal.unit_done
        n = [0]
        def patched(self, kind, key, **kw):
            orig(self, kind, key, **kw)
            n[0] += 1
            if n[0] >= 3:
                os.kill(os.getpid(), signal.SIGKILL)
        JobJournal.unit_done = patched
        from hadoop_bam_tpu.cohort.dataset import open_cohort
        from hadoop_bam_tpu.config import DEFAULT_CONFIG
        cfg = dataclasses.replace(DEFAULT_CONFIG, cohort_chunk_sites=11,
                                  journal_fsync=False)
        for _ in open_cohort(mp, cfg, journal_path=jp).site_chunks():
            pass
        raise SystemExit("unreachable: child must have been killed")
    """, mp, jp, timeout=120)
    assert r.returncode == -signal.SIGKILL, (r.returncode,
                                             r.stderr[-2000:])
    assert len(JobJournal.replay(jp).units) == 3

    with MetricsContext() as m:
        got = _chunks_of(open_cohort(mp, cfg, journal_path=jp))
    snap = m.snapshot()
    _assert_chunks_equal(oracle, got)
    assert snap["counters"].get("jobs.chunks_replayed") == 3
    # finished job: a THIRD pass is pure replay — no join work at all
    with MetricsContext() as m:
        again = _chunks_of(open_cohort(mp, cfg, journal_path=jp))
    snap = m.snapshot()
    _assert_chunks_equal(oracle, again)
    assert snap["counters"].get("jobs.jobs_skipped") == 1
    assert "cohort.join_wall" not in snap.get("wall_timers", {})


def test_concurrent_journaled_joins_refused(tmp_path):
    """Two live journaled iterations of one dataset would be two
    writers on one journal — the second must refuse up front instead of
    corrupting it; a finished iteration releases the guard."""
    from hadoop_bam_tpu.cohort.dataset import open_cohort

    mp = _cohort_fixture(tmp_path)
    cfg = dataclasses.replace(NOSYNC, cohort_chunk_sites=11)
    jp = str(tmp_path / "cohort.hbam-journal")
    ds = open_cohort(mp, cfg, journal_path=jp)
    it = ds.site_chunks()
    next(it)                                   # live mid-iteration
    with pytest.raises(PlanError, match="already in progress"):
        ds.site_chunks()
    for _ in it:                               # exhaust -> releases
        pass
    assert len(_chunks_of(ds)) > 0             # sequential reuse is fine
    # a generator that is created but NEVER STARTED must not take the
    # lock (or open the journal) — the setup is lazy, at first next()
    never_started = ds.site_chunks()
    del never_started
    assert len(_chunks_of(ds)) > 0


def test_cohort_resume_refuses_changed_inputs(tmp_path):
    from hadoop_bam_tpu.cohort.dataset import open_cohort

    mp = _cohort_fixture(tmp_path)
    cfg = dataclasses.replace(NOSYNC, cohort_chunk_sites=11)
    jp = str(tmp_path / "cohort.hbam-journal")
    _chunks_of(open_cohort(mp, cfg, journal_path=jp))
    time.sleep(0.01)
    with open(str(tmp_path / "s1.vcf"), "a") as f:
        f.write("chr21\t99999999\t.\tA\tC\t50\tPASS\t.\tGT:DP\t0/1:9\n")
    with pytest.raises(PlanError, match="input file identity"):
        _chunks_of(open_cohort(mp, cfg, journal_path=jp))
    # and a changed chunk size is an output-affecting fingerprint change
    sub = tmp_path / "x2"
    sub.mkdir()
    jp2 = str(tmp_path / "cohort2.hbam-journal")
    mp2 = _cohort_fixture(sub)
    _chunks_of(open_cohort(mp2, cfg, journal_path=jp2))
    cfg2 = dataclasses.replace(cfg, cohort_chunk_sites=7)
    with pytest.raises(PlanError, match="fingerprint"):
        _chunks_of(open_cohort(mp2, cfg2, journal_path=jp2))


# ---------------------------------------------------------------------------
# multi-host loss detection plumbing (single-process observables)
# ---------------------------------------------------------------------------

def test_collective_heartbeats_and_timeout():
    from hadoop_bam_tpu.parallel.distributed import _run_collective

    with MetricsContext() as m:
        out = _run_collective(lambda: (time.sleep(0.1) or 7),
                              "probe", timeout_s=5.0)
    snap = m.snapshot()
    assert out == 7
    assert snap["counters"].get("distributed.heartbeats", 0) >= 1
    assert "distributed.collective_wait_s" in snap.get("histograms", {})
    ev = threading.Event()
    try:
        with pytest.raises(TransientIOError, match="timed out"):
            _run_collective(ev.wait, "hung", timeout_s=0.2)
    finally:
        ev.set()


def test_collective_timeout_config_knob():
    from hadoop_bam_tpu.parallel.distributed import collective_timeout

    assert collective_timeout(DEFAULT_CONFIG) is None
    cfg = dataclasses.replace(DEFAULT_CONFIG, collective_timeout_s=12.5)
    assert collective_timeout(cfg) == 12.5
    assert collective_timeout(None) is None
    from hadoop_bam_tpu.config import HBamConfig
    assert HBamConfig.from_dict(
        {"hbam.collective-timeout-s": "3.5",
         "hbam.pool-task-timeout-s": "9",
         "hbam.speculative-decode": "false",
         "hbam.journal-fsync": "0",
         "hbam.straggler-multiplier": "6",
         "hbam.straggler-min-s": "0.25"}) == HBamConfig(
        collective_timeout_s=3.5, pool_task_timeout_s=9.0,
        speculative_decode=False, journal_fsync=False,
        straggler_multiplier=6.0, straggler_min_s=0.25)
