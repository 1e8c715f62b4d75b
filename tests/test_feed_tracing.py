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
  builds under ``steps.built.hbam_<step>``.
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
from hadoop_bam_tpu.utils.metrics import MetricsContext

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

    from hadoop_bam_tpu.tools.cli import main

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
    finally:
        jax.profiler.stop_trace()
    capsys.readouterr()
    names = {n for _s, _e, n in
             trace_reduce.host_spans(trace_reduce.load(trace_dir))}
    assert {"feed.wait_group", "feed.wait_rows", "staging.pack",
            "pipeline.dispatch_wall", "cli.main_wall",
            "plan.execute_wall"} <= names


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
