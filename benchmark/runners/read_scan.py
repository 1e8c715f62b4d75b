"""Runner ``read_scan``: whole scans of a lane's pair of read files through
one ``hbam`` verb, back to back.

Traffic parameters: ``verb`` (``seq-stats``), ``warmup_scans``.  The files are
the configuration's ``_R1_001.fastq.gz`` and ``_R2_001.fastq.gz``, each one
gzip member, made in two child processes by ``benchmark/gen_hiseq_fastq.py``
(NumPy + zlib only) with the reference's sums, and re-read from the start
each scan (host page cache).  A scan is the verb on R1, then on R2, through
``tools.cli.main`` in this process.  The rate is the reads of whole scans
over the wall from the first scan's start to the end of the last scan that
started inside ``--seconds``.  Every scan's two printed answers are compared
with the plain reference (counts exactly, means to the printed tolerances);
after the window ``verify`` scans each file once more through the function
the verb calls and compares the unrounded means, which the reference's
bfloat16 reading has to fail.
"""
from __future__ import annotations

import resource
import time

from benchmark import gen_hiseq_fastq as hiseq
from benchmark.runners.scan import run_cli
from benchmark.runners.variant_job import _config
from benchmark.runners.variant_scan import guard_memory


def setup(ctx) -> None:
    ctx.tol = _config(ctx)["tolerances"]
    ref = ctx.ref = hiseq.Reference()
    pairs = int(ctx.sizes["pairs"])
    ctx.files = hiseq.write_pair(ctx.workdir, ctx.seed, pairs, ref,
                                 workers=ctx.gen_workers)
    ctx.records = 2 * pairs
    if ref.pair().n != ctx.records:
        raise RuntimeError("generator lost reads")
    ctx.part_done("generate+write")
    every = ref.pair()
    ctx.say(f"{pairs} pairs of 2 x {hiseq.READ_LEN}: "
            f"{ref.text_bytes[0] / pairs:.1f} B of text a record, "
            f"{ref.text_bytes[0] / 1e6:.1f} MB a file, gzip "
            f"{ref.gz_bytes[0] / 1e6:.1f} + {ref.gz_bytes[1] / 1e6:.1f} MB "
            f"({ref.gz_bytes[0] / pairs:.1f} / {ref.gz_bytes[1] / pairs:.1f} "
            f"B a record); reference means of R1 {ref.all[0].means()!r} R2 "
            f"{ref.all[1].means()!r}; N share "
            f"{every.hist[15] / every.hist.sum():.5f}; passed the filter "
            f"{ref.pair(passed=True).n / every.n:.4f}")
    guard_memory(ctx)
    for _ in range(int(ctx.param("warmup_scans"))):
        _scan(ctx)
    ctx.part_done("warm-up")


def _scan(ctx):
    """R1, then R2; the first disagreement with the reference, or None."""
    for r, path in enumerate(ctx.files):
        wrong = ctx.ref.wrong(run_cli([ctx.param("verb"), path]), r,
                              ctx.tol["printed"])
        if wrong:
            ctx.say(f"WRONG (R{r + 1}): {wrong}")
            return wrong
    return None


def measure(ctx) -> dict:
    import jax

    scans = bad = errors = 0
    t0 = t_end = time.perf_counter()
    while t_end - t0 < ctx.seconds:
        try:
            bad += _scan(ctx) is not None
        except Exception as e:  # noqa: BLE001 — a failed scan is counted
            ctx.say(f"scan failed: {type(e).__name__}: {e}")
            errors += 1
        scans += 1
        t_end = time.perf_counter()
    done = scans - errors
    wall = t_end - t0
    rate = done * ctx.records / wall
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    ctx.say(f"{scans} scans attempted, {done} completed in {wall:.3f} s: "
            f"{rate:.1f} records/s ({wall / max(scans, 1):.4f} s a scan); "
            f"ru_maxrss {rss:.0f} MB")
    return {"correct": bad == 0 and done > 0, "attempted": scans,
            "failed": errors,
            "end_to_end": {"scan_records_per_s": rate},
            "observations": {
                "units": {"records": done * ctx.records, "scans": done},
                "device_kind": jax.devices()[0].device_kind,
                # the sizes benchmark/kernel_work_reads.py counts from
                "reads": {"records": done * ctx.records,
                          "read_len": hiseq.READ_LEN}}}


def verify(ctx) -> bool:
    """One more scan of each file through the function the verb calls, the
    unrounded means against the float64 reference: inside both limits on
    both files, where the bfloat16 reading is outside at least one."""
    from hadoop_bam_tpu.parallel.pipeline import (
        PayloadGeometry, fastq_seq_stats_file,
    )

    tol, ok = ctx.tol["unrounded"], True
    for r, path in enumerate(ctx.files):
        want = ctx.ref.all[r]
        res = fastq_seq_stats_file(path, geometry=PayloadGeometry())
        exact = (int(res["n_reads"]) == want.n
                 and [int(c) for c in res["base_hist"]] == want.hist.tolist())
        got = (float(res["mean_gc"]), float(res["mean_qual"]))
        bf16 = want.means("bf16")
        broke = hiseq.outside(got, want, tol)
        bf16_broke = hiseq.outside(bf16, want, tol)
        gc, mq = want.means()
        ctx.say(f"verify R{r + 1}: counts exact {exact}; unrounded mean_gc "
                f"{got[0]!r} off {abs(got[0] - gc):.3e} (bfloat16 reading "
                f"{abs(bf16[0] - gc):.3e}, limit {tol['mean_gc']:.1e}); "
                f"mean_qual {got[1]!r} off {abs(got[1] - mq):.3e} (bfloat16 "
                f"reading {abs(bf16[1] - mq):.3e}, limit "
                f"{tol['mean_qual']:.1e}); breaks {broke}, the bfloat16 "
                f"reading breaks {bf16_broke}")
        ok = ok and exact and not broke and bool(bf16_broke)
    return ok
