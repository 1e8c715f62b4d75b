"""Runner ``mesh_job``: whole mesh preprocessing jobs, back to back.

Traffic parameters: ``argv`` (the ``hbam`` verb with ``{input}`` and
``{output}`` placeholders), ``warmup_jobs``, ``expect`` (``sorted_stream``:
the output's inflated record stream must be byte-identical to a stable
(refID, pos) sort of the generator's own arrays, unplaced records last, and
a ``.bai`` must be co-written).  The input is the configuration's unsorted
subset; every job writes a fresh output.  The rate is the records of whole
jobs over the wall from the first job's start to the end of the last job
that started inside ``--seconds``; the answer is checked once per run, on
the first job's output, after the window.
"""
from __future__ import annotations

import os
import time

from benchmark import gen
from benchmark.runners.scan import run_cli


def _job(ctx, out: str) -> None:
    run_cli([a.format(input=ctx.input, output=out)
             for a in ctx.param("argv")])


def setup(ctx) -> None:
    from hadoop_bam_tpu.formats.bam import SAMHeader
    from hadoop_bam_tpu.write import write_bam_records

    n_chunks, chunk = ctx.sizes["chunks"], ctx.sizes["chunk_records"]
    subset = ctx.sizes["subset_chunks"]
    fields = gen.shuffled_fields(ctx.seed, n_chunks, chunk, subset)
    ctx.records = subset * chunk
    ctx.input = os.path.join(ctx.workdir, "unsorted.bam")
    gen.write_unsorted_bam(ctx.input, fields, write_bam_records,
                           SAMHeader.from_sam_text)
    ctx.part_done("generate+write")
    if ctx.param("expect") != "sorted_stream":
        raise ValueError(f"unknown expectation {ctx.param('expect')!r}")
    ctx.want_digest = gen.sorted_stream_digest(fields)
    ctx.part_done("reference")
    for k in range(int(ctx.param("warmup_jobs"))):
        out = os.path.join(ctx.workdir, f"warm-{k}.bam")
        _job(ctx, out)
        _remove(out)
    ctx.part_done("warm-up")


def _remove(out: str) -> None:
    for suffix in ("", ".bai", ".sbi"):
        if os.path.exists(out + suffix):
            os.unlink(out + suffix)


def measure(ctx) -> dict:
    jobs = errors = 0
    ctx.first_out = None
    t0 = t_end = time.perf_counter()
    while t_end - t0 < ctx.seconds:
        out = os.path.join(ctx.workdir, f"job-{jobs}.bam")
        try:
            _job(ctx, out)
            if ctx.first_out is None:
                ctx.first_out = out
            else:
                _remove(out)
        except Exception as e:  # noqa: BLE001 — a failed job is counted
            ctx.say(f"job failed: {type(e).__name__}: {e}")
            errors += 1
        jobs += 1
        t_end = time.perf_counter()
    done = jobs - errors
    wall = t_end - t0
    rate = done * ctx.records / wall
    ctx.say(f"{jobs} jobs attempted, {done} completed in {wall:.3f} s: "
            f"{rate:.1f} records/s ({wall / max(jobs, 1):.3f} s a job of "
            f"{ctx.records} records)")
    return {"correct": done > 0, "attempted": jobs, "failed": errors,
            "end_to_end": {"prep_records_per_s": rate},
            "observations": {"units": {"records": done * ctx.records,
                                       "jobs": done}}}


def verify(ctx) -> bool:
    """After the window: the first job's output against the reference."""
    out = ctx.first_out
    if out is None:
        return False
    got = gen.bam_record_stream_digest(out)
    ok = got == ctx.want_digest and os.path.exists(out + ".bai")
    ctx.say(f"first job's record stream sha256 {got[:16]} "
            f"{'==' if got == ctx.want_digest else '!='} reference "
            f"{ctx.want_digest[:16]}; .bai co-written: "
            f"{os.path.exists(out + '.bai')}")
    return ok
