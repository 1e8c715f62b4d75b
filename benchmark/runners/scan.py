"""Runner ``scan``: whole-file scans through one ``hbam`` verb, back to back.

Traffic parameters: ``verb`` (``summarize`` | ``seq-stats``), ``warmup_scans``.
The file is the configuration's coordinate-sorted sample, re-read from the
start each scan (host page cache).  The rate is the records of whole scans
over the wall from the first scan's start to the end of the last scan that
started inside ``--seconds``.  Every scan's answer is compared with the
NumPy reference.
"""
from __future__ import annotations

import contextlib
import io
import os
import time

from benchmark import gen


def run_cli(argv) -> str:
    """One `hbam` verb through its normal entry point, in this process."""
    from hadoop_bam_tpu.tools.cli import main as hbam_main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = hbam_main(list(argv))
    if rc != 0:
        raise RuntimeError(f"hbam {' '.join(argv)} exited {rc}")
    return out.getvalue()


def wrong_summarize(out: str, ref: gen.Reference):
    got = [int(ln.split(" ", 1)[0]) for ln in out.strip().splitlines()]
    want = [ref.flagstat[k] for k in gen.FLAGSTAT_KEYS]
    return None if got == want else f"summarize {got} != reference {want}"


def wrong_seq_stats(out: str, ref: gen.Reference):
    """Exact on reads and the base histogram; means to the smoke's
    tolerances (float32 partial sums on the device)."""
    kv = {ln.split("\t")[0]: ln.split("\t")[1:]
          for ln in out.strip().splitlines()}
    if int(kv["reads"][0]) != ref.n:
        return f"seq-stats reads {kv['reads'][0]} != {ref.n}"
    gc, mq = float(kv["mean_gc"][0]), float(kv["mean_qual"][0])
    if abs(gc - ref.sum_gc / ref.n) >= 2e-5:
        return f"mean_gc {gc} vs {ref.sum_gc / ref.n}"
    if abs(mq - ref.sum_mq / ref.n) >= 2e-3:
        return f"mean_qual {mq} vs {ref.sum_mq / ref.n}"
    hist = [int(kv.get(f"base_{c}", [0])[0]) for c in gen.BASE_NAMES]
    if hist != ref.base_hist.tolist():
        return f"base histogram {hist} != {ref.base_hist.tolist()}"
    return None


VERBS = {"summarize": ("flagstat", wrong_summarize),
         "seq-stats": ("seqstats", wrong_seq_stats)}


def setup(ctx) -> None:
    from hadoop_bam_tpu.formats.bam import SAMHeader
    from hadoop_bam_tpu.write import write_bam_records

    verb = ctx.param("verb")
    need, _ = VERBS[verb]
    ctx.ref = gen.Reference(needs=(need,))
    ctx.bam = os.path.join(ctx.workdir, "sample.bam")
    n_chunks, chunk = ctx.sizes["chunks"], ctx.sizes["chunk_records"]
    res = gen.write_sorted_bam(ctx.bam, ctx.seed, n_chunks, chunk, ctx.ref,
                               write_bam_records, SAMHeader.from_sam_text,
                               workers=ctx.gen_workers)
    ctx.records = n_chunks * chunk
    if res.records != ctx.records:
        raise RuntimeError("writer lost records")
    ctx.part_done("generate+write")
    ctx.say(f"{ctx.records} records, {os.path.getsize(ctx.bam) / 1e6:.1f} MB "
            f"BGZF + {sorted(res.sidecars)}")
    for _ in range(int(ctx.param("warmup_scans"))):
        _scan(ctx, verb)
    ctx.part_done("warm-up")


def _scan(ctx, verb: str):
    wrong = VERBS[verb][1](run_cli([verb, ctx.bam]), ctx.ref)
    if wrong:
        ctx.say(f"WRONG: {wrong}")
    return wrong


def measure(ctx) -> dict:
    verb = ctx.param("verb")
    scans = bad = errors = 0
    t0 = t_end = time.perf_counter()
    while t_end - t0 < ctx.seconds:
        try:
            bad += _scan(ctx, verb) is not None
        except Exception as e:  # noqa: BLE001 — a failed scan is counted
            ctx.say(f"scan failed: {type(e).__name__}: {e}")
            errors += 1
        scans += 1
        t_end = time.perf_counter()
    done = scans - errors
    wall = t_end - t0
    rate = done * ctx.records / wall
    ctx.say(f"{scans} scans attempted, {done} completed in {wall:.3f} s: "
            f"{rate:.1f} records/s ({wall / max(scans, 1):.4f} s a scan)")
    return {"correct": bad == 0 and done > 0, "attempted": scans,
            "failed": errors,
            "end_to_end": {"scan_records_per_s": rate},
            "observations": {"units": {"records": done * ctx.records,
                                       "scans": done}}}
