"""Runner ``variant_scan``: whole-file scans of a cohort BCF through one
``hbam`` verb, back to back.

Traffic parameters: ``verb`` (``vcf-stats``), ``warmup_scans``.  The file is
the configuration's coordinate-sorted BGZF BCF, made in child processes by
``benchmark/gen_kgp3.py`` (NumPy + zlib only), written by this process, and
re-read from the start each scan (host page cache).  The rate is the records
of whole scans over the wall from the first scan's start to the end of the
last scan that started inside ``--seconds``.  Every scan's printed answer is
compared with the plain reference; after the window ``verify`` compares one
more scan's unrounded ``mean_af`` (the verb prints six decimals, too few to
tell a float32 ratio from a bfloat16 one).
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

from benchmark import gen_kgp3
from benchmark.runners.scan import run_cli


def _tolerances(ctx) -> dict:
    """The configuration's stated limits on ``mean_af``
    (``configs/<config>.json``, found by the cell's config name)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", ctx.cell["config"] + ".json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)["mean_af_tolerance"]


def _resident_bytes() -> int:
    with open("/proc/self/statm", encoding="ascii") as fh:
        return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def _available_bytes() -> int:
    with open("/proc/meminfo", encoding="ascii") as fh:
        for line in fh:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) * 1024
    return 0


def guard_memory(ctx) -> None:
    """A run that would run the host out of memory ends itself: from here
    on, if the process grows by more than half of what the host had
    available now, one line says so and the process exits 3.  (A program
    that gathers a whole span's genotypes at once needs ~27 GB for this
    file; the kernel would kill it on a 40 GiB host, and a killed run
    tells the driver nothing.)"""
    base, room = _resident_bytes(), _available_bytes() // 2
    if not room:
        return

    def watch() -> None:
        while True:
            grown = _resident_bytes() - base
            if grown > room:
                print(f"benchmark: the scan grew this process by "
                      f"{grown / 2**30:.1f} GiB, over half of the "
                      f"{2 * room / 2**30:.1f} GiB the host had available: "
                      f"stopping before the host runs out", file=sys.stderr,
                      flush=True)
                os._exit(3)
            time.sleep(0.05)

    threading.Thread(target=watch, name="bench-memory-guard",
                     daemon=True).start()
    ctx.say(f"memory guard: resident {base / 2**30:.2f} GiB, stops the run "
            f"at +{room / 2**30:.1f} GiB")


def setup(ctx) -> None:
    verb = ctx.param("verb")
    ctx.tol = _tolerances(ctx)
    ctx.ref = gen_kgp3.Reference()
    ctx.bcf = os.path.join(ctx.workdir, "cohort.bcf")
    n_chunks, chunk = ctx.sizes["chunks"], ctx.sizes["chunk_records"]
    size = gen_kgp3.write_bcf(ctx.bcf, ctx.seed, n_chunks, chunk, ctx.ref,
                              workers=ctx.gen_workers)
    ctx.records = n_chunks * chunk
    if ctx.ref.n != ctx.records:
        raise RuntimeError("generator lost records")
    ctx.part_done("generate+write")
    ctx.say(f"{ctx.records} records of {gen_kgp3.N_SAMPLES} samples, "
            f"{ctx.ref.record_bytes / ctx.records:.1f} B a record, "
            f"{ctx.ref.record_bytes / 1e6:.1f} MB inflated, "
            f"{size / 1e6:.1f} MB BGZF; reference mean_af "
            f"{ctx.ref.mean_af:.9f} (each ratio in bfloat16: "
            f"{ctx.ref.mean_af_bf16:.9f})")
    guard_memory(ctx)
    for _ in range(int(ctx.param("warmup_scans"))):
        _scan(ctx, verb)
    ctx.part_done("warm-up")


def _scan(ctx, verb: str):
    wrong = ctx.ref.wrong(run_cli([verb, ctx.bcf]), ctx.tol["printed"])
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


def verify(ctx) -> bool:
    """One more scan through the function the verb calls, its ``mean_af``
    unrounded against the reference's float64 mean."""
    from hadoop_bam_tpu.parallel.distributed import distributed_variant_stats

    got = float(distributed_variant_stats(ctx.bcf)["mean_af"])
    off = abs(got - ctx.ref.mean_af)
    ctx.say(f"verify: unrounded mean_af {got!r} vs reference "
            f"{ctx.ref.mean_af!r}: off by {off:.3e} (limit "
            f"{ctx.tol['unrounded']:.1e}; the bfloat16 reading is off by "
            f"{abs(ctx.ref.mean_af_bf16 - ctx.ref.mean_af):.3e})")
    return off <= ctx.tol["unrounded"]
