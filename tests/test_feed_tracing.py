"""The feed's critical path seen from inside (PERF.md section 3): wait
spans on the dispatch and packer threads, native decode core-seconds,
named step programs and the sort's phase spans.

- ``feed.wait_group`` / ``feed.wait_rows`` / ``feed.wait_slot`` partition
  each feed thread's time with ``pipeline.dispatch_wall`` /
  ``staging.pack`` / ``staging.transfer_wait``;
- with a recorder active the spans carry ``jax.profiler`` annotations, so
  the benchmark's trace reduction finds them on the profiler's clock;
- ``decode.native_busy_ns`` is bounded by workers x wall;
- every step builder jits a program named ``hbam_<step>`` and counts its
  builds under ``steps.built.hbam_<step>``;
- a unit of the decode window carries its own clock: the wait for a head
  that is not done is ``feed.head_wait`` = ``feed.head_queued`` +
  ``feed.head_running``, ``feed.ready_behind_head`` the part a finished
  follower sat through; ``feed.unit_*`` sum the units' stamps;
  ``feed.first_dispatch_wait`` and ``exec.*`` are once a ``plan.execute``.
"""
import os
import sys
import time

import numpy as np
import pytest

from hadoop_bam_tpu.formats.bam import SAMHeader
from hadoop_bam_tpu.formats.bamio import BamWriter
from hadoop_bam_tpu.obs import disable_tracing, enable_tracing
from hadoop_bam_tpu.ops import inflate as inflate_ops
from hadoop_bam_tpu.parallel.staging import FeedPipeline, TileSpec
from hadoop_bam_tpu.utils.metrics import MetricsContext, thread_usage

from fixtures import make_header, make_records

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_fused = pytest.mark.skipif(not inflate_ops.fused_available(),
                                 reason="native fused decode unavailable")


@pytest.fixture(autouse=True)
def _no_tracing_leak():
    disable_tracing()
    yield
    disable_tracing()


@pytest.fixture(scope="module")
def bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("feedtrace") / "f.bam")
    header = make_header()
    with BamWriter(path, header) as w:
        for r in make_records(header, 3000, seed=7):
            w.write_sam_record(r)
    return path


# ---------------------------------------------------------------------------
# A. the three waits, and the partition of each feed thread's time
# ---------------------------------------------------------------------------

N_DEV, CAP, GROUPS = 2, 16, 30
SLOW_S = 0.01       # long against the per-group Python between spans


def _nap(delay_s: float, slept=None) -> None:
    """``time.sleep`` that notes what a real sleep asked for and got."""
    t0 = time.perf_counter()
    time.sleep(delay_s)
    if slept is not None and delay_s:
        slept.append((delay_s, time.perf_counter() - t0))


def _chunks(delay_s: float, slept=None):
    """GROUPS groups' worth of rows, one group a chunk."""
    for _ in range(GROUPS):
        if delay_s:
            _nap(delay_s, slept)
        yield (np.ones((N_DEV * CAP, 4), np.uint8),)


def _feed(stream_delay_s: float, dispatch_delay_s: float, traced: bool,
          slept=None):
    """One balanced feed (the stats drivers' mode) under its own
    MetricsContext; returns that context's wall timers.  ``slept``
    collects (asked, took) of every sleep of the run."""
    if traced:
        enable_tracing()
    fp = FeedPipeline(N_DEV, CAP, (TileSpec((4,), np.uint8),), block_n=4,
                      balance=True)
    with MetricsContext() as m:
        n = fp.feed(_chunks(stream_delay_s, slept),
                    lambda arrays, counts: _nap(dispatch_delay_s, slept))
    assert n == GROUPS
    return m.snapshot()["wall_timers"], m


@pytest.mark.parametrize("traced", [False, True],
                         ids=["untraced", "traced"])
def test_slow_stream_starves_packer_and_dispatch(traced):
    w, _ = _feed(SLOW_S, 0.0, traced)
    assert w["feed.wait_rows"] > 0.5 * GROUPS * SLOW_S
    assert w["feed.wait_group"] > 0.5 * GROUPS * SLOW_S
    # nothing pushes back on the packer when the dispatch side is idle
    assert w.get("feed.wait_slot", 0.0) < w["feed.wait_rows"]


@pytest.mark.parametrize("traced", [False, True],
                         ids=["untraced", "traced"])
def test_slow_dispatch_holds_the_packer_on_a_slot(traced):
    w, _ = _feed(0.0, SLOW_S, traced)
    assert w["feed.wait_slot"] > 0.5 * GROUPS * SLOW_S
    assert w["pipeline.dispatch_wall"] >= GROUPS * SLOW_S
    assert w["feed.wait_slot"] > w.get("feed.wait_rows", 0.0)


@pytest.mark.parametrize("stream_s,dispatch_s",
                         [(SLOW_S, 0.0), (0.0, SLOW_S)],
                         ids=["slow_stream", "slow_dispatch"])
def test_each_feed_thread_is_partitioned(stream_s, dispatch_s):
    slept = []
    w, _ = _feed(stream_s, dispatch_s, traced=False, slept=slept)
    feed = w["pipeline.feed_wall"]
    dispatch_thread = w["feed.wait_group"] + w["pipeline.dispatch_wall"]
    packer = (w.get("feed.wait_rows", 0.0) + w.get("feed.wait_slot", 0.0)
              + w.get("staging.transfer_wait", 0.0) + w["staging.pack"])
    # What a thread's spans leave uncovered is the Python between them
    # and the hand-offs: a group each way through the queue and the ring,
    # each woken by the scheduler that wakes this run's own sleeps.  How
    # late those woke, summed, is the run's measured scheduling error: on
    # a machine that runs six test workers it is what grows, and it is no
    # share of the feed — so it is the slack, in seconds, beside the 5 %.
    late = sum(took - asked for asked, took in slept)
    assert len(slept) == GROUPS and late >= 0.0
    assert dispatch_thread >= 0.95 * feed - late
    # the packer leaves once the last group is handed over, up to two
    # dispatches (the queued group and the one in hand) before the feed
    # ends: that tail is all a slow dispatch side may leave uncovered —
    # as long as those dispatches took, not as long as they asked for
    tail = sum(sorted(took for _asked, took in slept)[-2:]) \
        if dispatch_s else 0.0
    assert packer + tail >= 0.95 * feed - late
    # the spans of one thread lie inside the feed's wall and never overlap
    assert dispatch_thread <= 1.02 * feed and packer <= 1.02 * feed


def test_wait_rows_is_one_wall_a_group_untraced_one_span_a_pull_traced():
    _, m = _feed(0.0, 0.0, traced=False)
    assert 1 <= m.wall_calls["feed.wait_rows"] <= GROUPS + 1
    _, m = _feed(0.0, 0.0, traced=True)
    # GROUPS pulls that return rows + the one that finds the stream's end
    assert m.wall_calls["feed.wait_rows"] == GROUPS + 1


def test_pack_and_dispatch_spans_keep_their_args_in_the_ring():
    rec = enable_tracing()
    _feed(0.0, 0.0, traced=True)
    by_name = {}
    for name, _ts, _dur, _tid, thread, args in rec.events():
        by_name.setdefault(name, []).append((thread, args))
    packs = by_name["staging.pack"]
    assert len(packs) == GROUPS
    assert all(a["rows"] == N_DEV * CAP and a["bucket"] == CAP
               for _t, a in packs)
    assert {t for t, _a in packs} == {"hbam-feed-pack"}
    assert all(a["bytes"] == N_DEV * CAP * 4 + N_DEV * 4
               for _t, a in by_name["pipeline.dispatch_wall"])
    # the waits sit on the thread they describe
    assert {t for t, _a in by_name["feed.wait_rows"]} == {"hbam-feed-pack"}
    assert {t for t, _a in by_name["feed.wait_slot"]} == {"hbam-feed-pack"}
    assert "hbam-feed-pack" not in {t for t, _a in
                                    by_name["feed.wait_group"]}


# ---------------------------------------------------------------------------
# the spans are on the profiler's clock
# ---------------------------------------------------------------------------

def test_feed_and_cli_spans_land_in_the_profiler_trace(bam, tmp_path,
                                                       capsys):
    import jax

    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed
    from hadoop_bam_tpu.tools.cli import main
    from hadoop_bam_tpu.utils.pools import decode_pool

    sys.path.insert(0, ROOT)
    try:
        from benchmark import trace_reduce
    finally:
        sys.path.remove(ROOT)

    assert main(["summarize", bam]) == 0           # compile outside
    enable_tracing()
    trace_dir = str(tmp_path / "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        assert main(["summarize", bam]) == 0
        # a head the consumer certainly waits for (the scan's own span
        # starts may all be done when the packer comes for them)
        assert list(_iter_windowed(decode_pool(), range(2),
                                   lambda i: time.sleep(0.02) or i, 2)) \
            == [0, 1]
    finally:
        jax.profiler.stop_trace()
    capsys.readouterr()
    names = {n for _s, _e, n in
             trace_reduce.host_spans(trace_reduce.load(trace_dir))}
    assert {"feed.wait_group", "feed.wait_rows", "staging.pack",
            "pipeline.dispatch_wall", "cli.main_wall",
            "plan.execute_wall", "feed.head_wait"} <= names


def test_cli_main_wall_covers_the_parser_and_the_plan(bam, capsys):
    from hadoop_bam_tpu.tools.cli import main

    rec = enable_tracing()
    with MetricsContext() as m:
        assert main(["summarize", bam]) == 0
    capsys.readouterr()
    w = m.snapshot()["wall_timers"]
    assert w["cli.main_wall"] >= w["plan.execute_wall"] > 0
    (main_ev,) = [e for e in rec.events() if e[0] == "cli.main_wall"]
    assert main_ev[5]["verb"] == "summarize"
    # one trace id over every span of the scan, the CLI's
    assert {e[5]["trace"] for e in rec.events()} == {main_ev[5]["trace"]}


# ---------------------------------------------------------------------------
# native decode core-seconds
# ---------------------------------------------------------------------------

@needs_fused
def test_native_busy_ns_is_bounded_by_workers_times_wall(bam):
    raw = open(bam, "rb").read()
    table = inflate_ops.block_table(raw)
    data, _ = inflate_ops.inflate_span(raw, table)
    _, after = SAMHeader.from_bam_bytes(data.tobytes())
    workers = 2
    with MetricsContext() as m:
        t0 = time.perf_counter_ns()
        dec = inflate_ops.FusedSpanDecode(raw, table, start=after,
                                          chunk_blocks=1,
                                          n_threads=workers)
        n, _tail = dec.run()
        wall_ns = time.perf_counter_ns() - t0
    assert n == 3000
    busy = m.get("decode.native_busy_ns")
    assert 0 < busy <= workers * wall_ns
    assert m.get("decode.native_jobs") == 1
    # finish() is idempotent: a second call counts nothing
    with MetricsContext() as m2:
        dec.finish()
    assert m2.get("decode.native_jobs") == 0


@needs_fused
def test_native_jobs_equals_the_spans_a_scan_decoded(bam):
    from hadoop_bam_tpu.config import HBamConfig
    from hadoop_bam_tpu.parallel.pipeline import flagstat_file

    with MetricsContext() as m:
        out = flagstat_file(bam, config=HBamConfig(backend="cpu"))
    assert out["total"] == 3000
    assert m.get("decode.native_jobs") == m.get("pipeline.spans") > 0
    assert m.get("decode.native_busy_ns") > 0


# ---------------------------------------------------------------------------
# B. named device programs
# ---------------------------------------------------------------------------

def _mesh(n=4):
    import jax

    from hadoop_bam_tpu.parallel.mesh import make_mesh

    return make_mesh(devices=jax.devices()[:n])


def _step_table():
    """{step: (build(mesh), example args(n_dev), built once per mesh?)} —
    every builder that goes through ``named_step``.  Shapes are the
    smallest each step's layout allows: the test lowers, it never runs."""
    import jax
    import jax.numpy as jnp

    from hadoop_bam_tpu.cohort.gwas import (
        make_cohort_gwas_step, make_gwas_assoc_step, make_gwas_load_step,
    )
    from hadoop_bam_tpu.cohort.serving import make_cohort_slice_step
    from hadoop_bam_tpu.parallel import mesh_sort
    from hadoop_bam_tpu.parallel import pipeline as pl
    from hadoop_bam_tpu.parallel import variant_pipeline as vp
    from hadoop_bam_tpu.prep import markdup
    from hadoop_bam_tpu.query.engine import make_overlap_step
    from hadoop_bam_tpu.serve.tiles import make_tile_filter_step

    S = jax.ShapeDtypeStruct
    u8, i8, i32, u32, f32 = (jnp.uint8, jnp.int8, jnp.int32, jnp.uint32,
                             jnp.float32)
    R, D, T = 64, 4096, 256
    g = pl.PayloadGeometry(max_len=32, tile_records=T, block_n=T)
    vg = vp.VariantGeometry(tile_records=R, n_samples=8)
    row = pl.projection_row_bytes(pl.FLAGSTAT_PROJECTION)

    def spans(n):
        return [S((n, D), u8), S((n, R), i32), S((n,), i32)]

    def bounds(n):
        return [S((n - 1,), u32), S((n - 1,), u32)]

    return {
        "flagstat_step": (pl.make_flagstat_step, spans, True),
        "flagstat_tile_step": (
            pl.make_flagstat_tile_step,
            lambda n: [S((n, R, row), u8), S((n,), i32)], True),
        "unpack_step": (pl.make_unpack_step, spans, True),
        "seq_stats_step": (
            lambda m: pl.make_seq_stats_step(m, g),
            lambda n: [S((n, T, 36), u8), S((n, T, g.seq_stride), u8),
                       S((n, T, g.qual_stride), u8), S((n,), i32)], True),
        "read_stats_step": (
            lambda m: pl.make_read_stats_step(m, g),
            lambda n: [S((n, T, g.seq_stride), u8),
                       S((n, T, g.qual_stride), u8), S((n, T), i32),
                       S((n,), i32)], True),
        "coverage_step": (
            lambda m: pl.make_coverage_step(m, 128, 4),
            lambda n: [S((n, R, pl._CIGAR_ROW_HDR + 16), u8), S((n,), i32),
                       S((), i32), S((), i32)], True),
        "tile_filter_step": (
            make_tile_filter_step,
            lambda n: [S((n, R), i32)] * 3 + [S((n,), i32), S((3,), i32)],
            True),
        # the sort and markdup builders keep no cache: a job builds (and
        # JAX re-traces) its exchange step every time, which is what
        # steps.built.* is there to count
        "sort_step": (
            lambda m: mesh_sort._make_sort_step(m, R),
            lambda n: spans(n) + [S((n,), i32)] + bounds(n), False),
        "bytes_sort_step": (
            lambda m: mesh_sort._make_bytes_sort_step(m, R, 64),
            lambda n: [S((n, R, 64), u8), S((n, R), i32), S((n,), i32),
                       S((n,), i32)] + bounds(n), False),
        "fused_sort_markdup_step": (
            lambda m: markdup._make_fused_sort_markdup_step(m, R, 64, 4),
            lambda n: [S((n, R, 64), u8), S((n, R), i32), S((n,), i32),
                       S((n,), i32), S((n, R), u32)] + bounds(n), False),
        "markdup_exchange_step": (
            lambda m: markdup._make_markdup_exchange_step(m, R),
            lambda n: [S((n, R), u32)] * 6 + [S((n, R), i32),
                                              S((n,), i32)], False),
        "variant_step": (
            lambda m: vp.make_variant_stats_step(m, vg),
            lambda n: [S((n, R), i32), S((n, R), i32), S((n, R), u8),
                       S((n, R, 8), i8), S((n,), i32)], True),
        "query_filter_step": (
            make_overlap_step,
            lambda n: [S((n, R), i32)] * 7 + [S((n,), i32)], True),
        "gwas_step": (
            lambda m: make_cohort_gwas_step(m, vg, True),
            lambda n: [S((n, R, 8), i8), S((n,), i32), S((8,), f32)], True),
        "cohort_slice_step": (
            make_cohort_slice_step,
            lambda n: [S((n, R), i32), S((n, R), i32), S((n, R, 8), i8),
                       S((n,), i32), S((3,), i32)], True),
        # the two programs of `hbam vcf-gwas` hold their matrix on one
        # device: no mesh, no leading device axis but the feed's 1
        "gwas_load_step": (
            lambda m: make_gwas_load_step(8),
            lambda n: [S((1024, 512), i8), S((2, 1024), i32),
                       S((512, 512), f32), S((512,), f32), S((), f32),
                       S((), i32), S((1, R), i32), S((1, R), i32),
                       S((1, R), u8), S((1, R, 8), i8), S((1,), i32),
                       S((), i32)], True),
        "gwas_assoc_step": (
            lambda m: make_gwas_assoc_step(8, 3),
            lambda n: [S((1024, 512), i8), S((3, 512, 128), jnp.bfloat16),
                       S((128,), f32), S((1,), i32)], True),
        "totals_add": (
            lambda m: pl._ADD,
            lambda n: [S((16,), i32), S((16,), i32)], True),
    }


STEP_NAMES = [
    "flagstat_step", "flagstat_tile_step", "unpack_step", "seq_stats_step",
    "read_stats_step", "coverage_step", "tile_filter_step", "sort_step",
    "bytes_sort_step", "fused_sort_markdup_step", "markdup_exchange_step",
    "variant_step", "query_filter_step", "gwas_step", "cohort_slice_step",
    "gwas_load_step", "gwas_assoc_step", "totals_add",
]


def test_the_step_table_names_every_builder_once():
    assert sorted(_step_table()) == sorted(STEP_NAMES)
    assert len(set(STEP_NAMES)) == len(STEP_NAMES) == 18


@pytest.mark.parametrize("name", STEP_NAMES)
def test_step_program_is_named_and_its_build_is_counted(name):
    build, args, built_once = _step_table()[name]
    mesh = _mesh()
    n_dev = mesh.devices.size
    counter = f"steps.built.hbam_{name}"
    step = build(mesh)
    text = step.lower(*args(n_dev)).as_text()
    assert text.startswith(f"module @jit_hbam_{name} "), text[:80]
    with MetricsContext() as m:
        again = build(mesh)
    if built_once:
        # cached per mesh: a second build is the same program, not counted
        assert again is step and m.get(counter) == 0
    else:
        assert again is not step and m.get(counter) == 1


@pytest.mark.parametrize("name,scopes", [
    ("seq_stats_step", ("unpack", "kernel", "psum")),
    ("sort_step", ("local_sort", "exchange", "merge")),
    ("bytes_sort_step", ("local_sort", "exchange", "merge")),
])
def test_multi_phase_steps_carry_their_scopes(name, scopes):
    build, args, _ = _step_table()[name]
    mesh = _mesh()
    text = build(mesh).lower(*args(mesh.devices.size)).as_text(
        debug_info=True)
    for scope in scopes:
        assert f'loc("{scope}/' in text, scope


# ---------------------------------------------------------------------------
# C. the sort's phases, and the count of its step builds
# ---------------------------------------------------------------------------

def test_two_mesh_sort_jobs_build_the_step_twice_and_span_every_phase(
        tmp_path, monkeypatch, capsys):
    import random

    from hadoop_bam_tpu.parallel import mesh as mesh_mod
    from hadoop_bam_tpu.tools.cli import main

    header = make_header()
    recs = make_records(header, 400, seed=12)
    random.Random(12).shuffle(recs)
    src = str(tmp_path / "in.bam")
    with BamWriter(src, header) as w:
        for r in recs:
            w.write_sam_record(r)
    four = _mesh(4)
    monkeypatch.setattr(mesh_mod, "make_mesh", lambda *a, **k: four)

    rec = enable_tracing()
    with MetricsContext() as m:
        for job in range(2):
            assert main(["sort", "--mesh", src,
                         str(tmp_path / f"out{job}.bam")]) == 0
    capsys.readouterr()
    # what the code gives today: no cache over the exchange step, so one
    # build (and one JAX trace) a job — PERF.md section 6
    assert m.get("steps.built.hbam_sort_step") == 2
    w = m.snapshot()["wall_timers"]
    for phase in ("read", "pack", "exchange", "permute", "write"):
        assert w[f"sort.{phase}_wall"] > 0, phase
    # the permute runs inside the writer's pull, on the same thread
    assert w["sort.permute_wall"] <= w["sort.write_wall"]
    by_name = {}
    for name, *_rest, args in rec.events():
        if name.startswith("sort."):
            by_name.setdefault(name, []).append(args)
    assert all(a["round"] == 0 for evs in by_name.values() for a in evs)
    assert [a["records"] for a in by_name["sort.read_wall"]] == [400, 400]
    assert sum(a["records"] for a in by_name["sort.permute_wall"]) == 800
    assert open(tmp_path / "out0.bam", "rb").read() \
        == open(tmp_path / "out1.bam", "rb").read()


def test_serve_counts_its_filter_launches(bam):
    from hadoop_bam_tpu.serve import ServeLoop
    from hadoop_bam_tpu.split.bai import write_bai

    write_bai(bam)
    with MetricsContext() as m:
        with ServeLoop() as loop:
            for _ in range(3):
                loop.submit(bam, ["chr1:1-500000"]).result(timeout=60)
    launches = m.get("serve.filter_launches")
    assert launches >= 3
    # one launch a tile group, counted beside the span that times them
    assert m.wall_calls["serve.filter_wall"] <= launches


# ---------------------------------------------------------------------------
# D. a unit's life through the decode window
# ---------------------------------------------------------------------------

TRACED = pytest.mark.parametrize("traced", [False, True],
                                 ids=["untraced", "traced"])
HEAD_S, FOLLOWER_S = 0.30, 0.02
LATE_S = 0.06       # what a sleep may overrun on a machine of six workers


@pytest.fixture()
def wide_pool():
    import concurrent.futures as cf

    pool = cf.ThreadPoolExecutor(max_workers=8)
    yield pool
    pool.shutdown(wait=False, cancel_futures=True)


def _windowed(pool, items, fn, window, traced, **kw):
    """Drain one ``_iter_windowed`` under its own MetricsContext; returns
    (results, wall timers, the context, the head waits' ring events)."""
    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

    rec = enable_tracing() if traced else None
    with MetricsContext() as m:
        out = list(_iter_windowed(pool, items, fn, window, **kw))
    waits = [e for e in rec.events() if e[0] == "feed.head_wait"] \
        if rec is not None else []
    return out, m.snapshot()["wall_timers"], m, waits


def _slow_head(i, ends=None):
    time.sleep(HEAD_S if i == 0 else FOLLOWER_S)
    if ends is not None:
        ends[i] = time.perf_counter()
    return i


@TRACED
def test_a_slow_head_with_fast_followers_books_their_lead(wide_pool, traced):
    ends = {}
    out, w, m, waits = _windowed(wide_pool, range(4),
                                 lambda i: _slow_head(i, ends), 4, traced)
    assert out == [0, 1, 2, 3]
    # from the first follower's end to the head's, as the sleeps really
    # ended; the consumer wakes a little after the head does
    lead = ends[0] - min(ends[1], ends[2], ends[3])
    assert lead > 0.5 * (HEAD_S - FOLLOWER_S)
    assert lead - 0.005 <= w["feed.ready_behind_head"] <= lead + LATE_S
    assert w["feed.ready_behind_head"] <= w["feed.head_wait"]
    # the followers were done when they were taken: one wait, the head's
    assert m.wall_calls["feed.head_wait"] == 1
    if traced:
        (ev,) = waits
        args = ev[5]
        assert args["unit"] == 0 and args["behind_done"] == 3
        assert args["ready_behind_s"] == pytest.approx(
            w["feed.ready_behind_head"])
        assert args["queued_s"] + args["running_s"] == pytest.approx(ev[2],
                                                                     rel=0.05)


@TRACED
def test_head_queued_plus_running_is_the_head_wait(wide_pool, traced):
    _, w, _, _ = _windowed(wide_pool, range(8), _slow_head, 4, traced)
    parts = w.get("feed.head_queued", 0.0) + w["feed.head_running"]
    assert parts == pytest.approx(w["feed.head_wait"], rel=0.05)
    assert w["feed.head_running"] >= HEAD_S - LATE_S


@pytest.mark.parametrize("workers", [1, 8], ids=["one_thread", "wide"])
def test_head_queued_is_a_head_no_thread_was_free_for(workers):
    """A pool hands its tasks out in order, so the head — the oldest of
    the window — is queued only behind work that is not the window's: here
    another job's task, on the pool when the scan begins."""
    import concurrent.futures as cf

    slept = []
    pool = cf.ThreadPoolExecutor(max_workers=workers)
    try:
        pool.submit(_nap, 0.15, slept)
        out, w, _, _ = _windowed(pool, range(4),
                                 lambda i: time.sleep(FOLLOWER_S) or i, 4,
                                 traced=False)
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
    assert out == [0, 1, 2, 3]
    if workers == 1:
        (_asked, busy_s), = slept    # as long as the other job really took
        assert busy_s - LATE_S <= w["feed.head_queued"] <= busy_s + LATE_S
    else:
        # a free thread picks the head up as fast as it wakes
        assert w.get("feed.head_queued", 0.0) < 0.02
        assert w["feed.head_running"] > 0.0


@TRACED
def test_a_head_that_is_done_on_arrival_records_nothing(wide_pool, traced):
    def items():
        # every later item is late, and so is the stream's end: each
        # head was done long before the consumer came for it
        for i in range(8):
            if i:
                time.sleep(0.02)
            yield i
        time.sleep(0.02)

    out, w, m, waits = _windowed(wide_pool, items(), lambda i: i, 4, traced)
    assert out == list(range(8)) and not waits
    assert not {"feed.head_wait", "feed.head_queued", "feed.head_running",
                "feed.ready_behind_head"} & set(w)
    assert m.get("feed.units") == 8
    # ... and the units were held, finished, while the consumer was away
    assert m.get("feed.unit_held_ns") > 7 * 0.02e9


@TRACED
def test_under_a_feed_the_head_walls_lie_inside_wait_rows(wide_pool, traced):
    from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

    def decode(_i):
        time.sleep(SLOW_S)
        return (np.ones((N_DEV * CAP, 4), np.uint8),)

    rec = enable_tracing() if traced else None
    fp = FeedPipeline(N_DEV, CAP, (TileSpec((4,), np.uint8),), block_n=4,
                      balance=True)
    with MetricsContext() as m:
        # a window of one (the head and the unit submitted as it is
        # taken): nearly every head is waited for
        n = fp.feed(_iter_windowed(wide_pool, range(GROUPS), decode, 1),
                    lambda arrays, counts: None)
    w = m.snapshot()["wall_timers"]
    assert n == GROUPS and m.get("feed.units") == GROUPS
    head = w.get("feed.head_queued", 0.0) + w["feed.head_running"]
    # a head has run since the unit before it was taken, so a wait is a
    # part of SLOW_S: a quarter of the sleeps is a floor, not the figure
    assert 0.25 * GROUPS * SLOW_S < head <= 1.02 * w["feed.wait_rows"]
    assert "feed.first_dispatch_wait" not in w   # no plan.execute around it
    if traced:
        threads = {e[4] for e in rec.events() if e[0] == "feed.head_wait"}
        assert threads == {"hbam-feed-pack"}


def _cpu_time_is_fine_grained():
    """Whether this kernel charges a thread its CPU time as it runs: spin
    20 ms and look.  Linux does; a sandboxed kernel (the chip host's
    ``runsc``) reports it in 10 ms steps, and there only presence and
    order can be asserted of the rusage counters, not amounts."""
    u0, t0 = sum(thread_usage()), time.perf_counter()
    while time.perf_counter() - t0 < 0.02:
        pass
    return 0.012e9 <= sum(thread_usage()) - u0 <= 0.03e9


@TRACED
def test_unit_counters_sum_what_the_units_stamps_say(wide_pool, traced):
    def fn(i):
        c_end, t_end = time.thread_time() + 0.01, time.perf_counter() + 0.01
        # 10 ms on the CPU, and of the wall where CPU time comes in steps
        while time.thread_time() < c_end or time.perf_counter() < t_end:
            pass
        time.sleep(0.01)                        # 10 ms off it
        return i

    out, _, m, _ = _windowed(wide_pool, range(12), fn, 4, traced)
    assert out == list(range(12)) and m.get("feed.units") == 12
    run = m.get("feed.unit_run_ns")
    assert run >= 12 * 0.018e9
    assert m.get("feed.unit_queued_ns") > 0
    assert m.get("feed.unit_held_ns") >= 0
    counters = m.snapshot()["counters"]
    if not traced:
        # the threads' rusage is taken only while a recorder is active
        assert not {"feed.unit_cpu_ns", "feed.unit_sys_ns"} & set(counters)
        return
    cpu = counters["feed.unit_cpu_ns"]
    assert 0 <= counters["feed.unit_sys_ns"] <= cpu
    if _cpu_time_is_fine_grained():
        assert cpu >= 12 * 0.008e9 and run >= cpu * 0.95


@pytest.mark.parametrize("defence", ["speculated", "resubmitted"])
def test_a_twin_or_a_resubmit_is_one_unit_with_the_winners_stamps(
        wide_pool, defence):
    import dataclasses
    import threading

    from hadoop_bam_tpu.config import DEFAULT_CONFIG

    release = threading.Event()
    lock = threading.Lock()
    seen = set()
    n, straggler = 32, 30

    def fn(i):
        with lock:
            first = i not in seen
            seen.add(i)
        if i == straggler and first:
            release.wait(20)        # the first copy never ends in time
            return i
        time.sleep(0.005)
        return i

    if defence == "speculated":
        cfg, wait_s = dataclasses.replace(
            DEFAULT_CONFIG, straggler_min_s=0.05,
            straggler_multiplier=2.0), 0.05
        counter = "jobs.speculative_won"
    else:
        cfg, wait_s = dataclasses.replace(
            DEFAULT_CONFIG, pool_task_timeout_s=0.25,
            speculative_decode=False), 0.25
        counter = "jobs.timeout_resubmits"
    try:
        out, w, m, _ = _windowed(wide_pool, range(n), fn, 4, traced=False,
                                 config=cfg)
    finally:
        release.set()
    assert out == list(range(n)) and m.get(counter) >= 1
    assert m.get("feed.units") == n
    # the loser's stamps have no end yet; the winner ran 5 ms, and began
    # only once the defence had waited: that wait is the head's queue
    assert m.get("feed.unit_run_ns") < n * 0.05e9
    assert w["feed.head_queued"] >= wait_s * 0.9


@pytest.fixture(scope="module")
def small_vcf(tmp_path_factory):
    import test_variant_pipeline as tv

    path = str(tmp_path_factory.mktemp("feedtrace_vcf") / "v.vcf")
    with open(path, "w") as f:
        f.write(tv.HEADER_TEXT)
        for r in tv._make_records(600):
            f.write(r.to_line() + "\n")
    return path


@pytest.fixture(scope="module")
def small_fastqs(tmp_path_factory):
    d = tmp_path_factory.mktemp("feedtrace_fq")
    rng = np.random.default_rng(11)
    paths = []
    for mate in (1, 2):
        path = str(d / f"r{mate}.fastq")
        with open(path, "w") as f:
            for i in range(3000):
                seq = "".join(rng.choice(list("ACGT"), 40))
                f.write(f"@r{i}/{mate}\n{seq}\n+\n{'I' * 40}\n")
        paths.append(path)
    return paths


def _execute(verb, bam, small_vcf, small_fastqs):
    """One ``plan.execute`` a file behind the verb, with tiles small
    enough that a scan dispatches several groups."""
    from hadoop_bam_tpu.parallel.pipeline import (
        DecodeGeometry, PayloadGeometry, fastq_seq_stats_file, flagstat_file,
    )
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        VariantGeometry, variant_stats_file,
    )

    if verb == "summarize":
        assert flagstat_file(
            bam, geometry=DecodeGeometry(tile_records=256))["total"] == 3000
        return 1
    if verb == "vcf-stats":
        out = variant_stats_file(small_vcf, geometry=VariantGeometry(
            tile_records=32, n_samples=5))
        assert out["n_variants"] == 600
        return 1
    for path in small_fastqs:
        out = fastq_seq_stats_file(path, geometry=PayloadGeometry(
            max_len=64, tile_records=256, block_n=256))
        assert out["n_reads"] == 3000
    return len(small_fastqs)


@pytest.mark.parametrize("verb", ["summarize", "vcf-stats", "seq-stats"])
def test_first_dispatch_wait_is_once_an_execute(verb, bam, small_vcf,
                                                small_fastqs):
    _execute(verb, bam, small_vcf, small_fastqs)        # compile outside
    with MetricsContext() as m:
        executes = _execute(verb, bam, small_vcf, small_fastqs)
    w = m.snapshot()["wall_timers"]
    assert m.get("plan.executions") == executes
    assert m.wall_calls["pipeline.dispatch_wall"] > executes
    assert m.wall_calls["feed.first_dispatch_wait"] == executes
    assert 0.0 < w["feed.first_dispatch_wait"] < w["plan.execute_wall"]
    if verb == "vcf-stats":
        # the peek's wait is on the calling thread, outside the feed
        assert w["feed.first_dispatch_wait"] >= w.get("vcf.plan_wall", 0.0)


def test_exec_counters_rise_by_one_executes_worth(bam, small_vcf,
                                                  small_fastqs):
    _execute("summarize", bam, small_vcf, small_fastqs)
    with MetricsContext() as m:
        _execute("summarize", bam, small_vcf, small_fastqs)
    # the process's rusage is taken only while a recorder is active
    assert not [k for k in m.snapshot()["counters"] if k.startswith("exec.")]
    enable_tracing()
    with MetricsContext() as m:
        _execute("summarize", bam, small_vcf, small_fastqs)
        once = dict(m.snapshot()["counters"])
        wall = m.snapshot()["wall_timers"]["plan.execute_wall"]
        _execute("summarize", bam, small_vcf, small_fastqs)
        twice = m.snapshot()["counters"]
    assert once["exec.wall_ns"] == pytest.approx(wall * 1e9, rel=0.02)
    cpu = once["exec.cpu_user_ns"] + once["exec.cpu_sys_ns"]
    assert 0 <= cpu <= (os.cpu_count() or 1) * once["exec.wall_ns"] * 1.5
    assert twice["exec.wall_ns"] > once["exec.wall_ns"]
    for name in ("exec.cpu_user_ns", "exec.cpu_sys_ns"):
        assert twice[name] >= once[name] >= 0, name
    if _cpu_time_is_fine_grained():
        assert cpu > 0
        assert twice["exec.cpu_user_ns"] > once["exec.cpu_user_ns"]


def test_vcf_dispatch_wall_counts_a_dispatch_once(bam, small_vcf,
                                                  small_fastqs):
    _execute("vcf-stats", bam, small_vcf, small_fastqs)
    # one is the span's own wall, the other the clock reads around it: a
    # thread switch between the reads (one 5 ms interval of the
    # interpreter's, seen once under six test workers) lands in one of the
    # two, so a scan that reads apart is taken again, twice at most
    for _attempt in range(3):
        with MetricsContext() as m:
            _execute("vcf-stats", bam, small_vcf, small_fastqs)
        w = m.snapshot()["wall_timers"]
        groups = m.wall_calls["pipeline.dispatch_wall"]
        assert m.wall_calls["vcf.dispatch_wall"] == groups > 1
        want = pytest.approx(w["pipeline.dispatch_wall"], rel=0.05,
                             abs=groups * 1e-4)
        if w["vcf.dispatch_wall"] == want:
            break
    assert w["vcf.dispatch_wall"] == want
