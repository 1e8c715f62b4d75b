"""Runner ``cram_scan``: whole scans of a reference-compressed CRAM through
one ``hbam`` verb, back to back.

Traffic parameters: ``verb`` (``seq-stats``), ``warmup_scans``,
``scan_deadline_s`` (a set-up scan still running after that many seconds
ends the run with one line and exit 3: a program that decodes rANS Nx16 a
symbol at a time in Python needs minutes a scan).  The files are the
configuration's ``chr20.cram`` and ``chr20.fa`` (+ ``.fai``), made by
``benchmark/gen_cram31.py`` (NumPy + zlib only; the reads are
``benchmark/gen.py``'s, chunk by chunk in child processes) with the
reference's sums.  A scan is ``hbam seq-stats chr20.cram --reference
chr20.fa`` through ``tools.cli.main`` in this process; both files are
re-read from the start each scan (host page cache) and the verb re-opens the
reference each scan.  The rate is the reads of whole scans over the wall
from the first scan's start to the end of the last scan that started inside
``--seconds``.  Every scan's printed answer is compared with the plain
reference (counts exactly, means to the printed tolerances); after the
window ``verify`` scans once more through the function the verb calls and
compares the unrounded means, which the reference's bfloat16 reading has to
fail.
"""
from __future__ import annotations

import resource
import time

from benchmark import gen_cram31 as cram31
from benchmark.runners.scan import run_cli
from benchmark.runners.variant_job import _config
from benchmark.runners.variant_scan import guard_memory
from benchmark.runners.variant_text_scan import guard_deadline


def setup(ctx) -> None:
    ctx.tol = _config(ctx)["tolerances"]
    n_chunks, chunk = ctx.sizes["chunks"], ctx.sizes["chunk_records"]
    ctx.files, ctx.ref = cram31.write_cram(ctx.workdir, ctx.seed, n_chunks,
                                           chunk, workers=ctx.gen_workers)
    ctx.records = n_chunks * chunk
    if ctx.ref.n != ctx.records:
        raise RuntimeError("generator lost reads")
    ctx.part_done("generate+write")
    w = ctx.files
    ctx.say(f"{ctx.records} reads of 2 x {cram31.READ_LEN}: "
            f"{w.cram_bytes / 1e6:.1f} MB of CRAM 3.1 "
            f"({w.cram_bytes / ctx.records:.2f} B a read; by series "
            f"{ {k: round(v / ctx.records, 3) for k, v in sorted(w.series_bytes.items())} }"
            f" B a read by content id); chunk 0's methods "
            f"{w.methods}; reference means {ctx.ref.means()!r}, the "
            f"bfloat16 reading's {ctx.ref.means('bf16')!r}")
    guard_memory(ctx)
    done = guard_deadline(ctx, float(ctx.param("scan_deadline_s")))
    for _ in range(int(ctx.param("warmup_scans"))):
        _scan(ctx)
    done.set()
    ctx.part_done("warm-up")


def _scan(ctx):
    """One scan; the first disagreement with the reference, or None."""
    wrong = ctx.ref.wrong(run_cli([ctx.param("verb"), ctx.files.cram,
                                   "--reference", ctx.files.fasta]),
                          ctx.tol["printed"])
    if wrong:
        ctx.say(f"WRONG: {wrong}")
    return wrong


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
                          "read_len": cram31.READ_LEN}}}


def verify(ctx) -> bool:
    """One more scan through the function the verb calls, the unrounded
    means against the float64 reference: inside both limits, where the
    bfloat16 reading is outside at least one."""
    import dataclasses

    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.parallel.pipeline import (
        PayloadGeometry, cram_seq_stats_file,
    )

    cfg = dataclasses.replace(DEFAULT_CONFIG,
                              cram_reference_source_path=ctx.files.fasta)
    res = cram_seq_stats_file(ctx.files.cram, config=cfg,
                              geometry=PayloadGeometry())
    want, tol = ctx.ref, ctx.tol["unrounded"]
    exact = (int(res["n_reads"]) == want.n
             and [int(c) for c in res["base_hist"]] == want.hist.tolist())
    got = (float(res["mean_gc"]), float(res["mean_qual"]))
    bf16 = want.means("bf16")
    broke, bf16_broke = want.outside(got, tol), want.outside(bf16, tol)
    gc, mq = want.means()
    ctx.say(f"verify: counts exact {exact}; unrounded mean_gc {got[0]!r} "
            f"off {abs(got[0] - gc):.3e} (bfloat16 reading "
            f"{abs(bf16[0] - gc):.3e}, limit {tol['mean_gc']:.1e}); "
            f"mean_qual {got[1]!r} off {abs(got[1] - mq):.3e} (bfloat16 "
            f"reading {abs(bf16[1] - mq):.3e}, limit "
            f"{tol['mean_qual']:.1e}); breaks {broke}, the bfloat16 "
            f"reading breaks {bf16_broke}")
    return exact and not broke and bool(bf16_broke)
