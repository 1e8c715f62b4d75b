"""Runner ``variant_text_scan``: whole-file scans of a cohort's bgzip'd VCF
text through one ``hbam`` verb, back to back.

``variant_scan`` with the file-making replaced: the file is the
configuration's coordinate-sorted ``.vcf.gz`` as the source ships it, made
in child processes by ``benchmark/gen_kgp3_vcf.py`` (NumPy + zlib only) from
the very field arrays ``gen_kgp3.py`` makes the BCF of, so the reference —
``gen_kgp3.Reference``, folded from the generator's allele arrays — is the
BCF cell's for the same seed.  Measuring, comparing and ``verify`` are
``variant_scan``'s own functions: they read ``ctx.bcf``, which here names
the ``.vcf.gz``.

Traffic parameters: ``verb`` (``vcf-stats``), ``warmup_scans``,
``scan_deadline_s``: a set-up scan still running after that many seconds
ends the run with one line and exit 3, as the memory guard does (a program
that tokenises a 2,504-sample line a sample at a time needs minutes a scan:
a run must not outlive its usefulness).
"""
from __future__ import annotations

import os
import sys
import threading

from benchmark import gen_kgp3, gen_kgp3_vcf
from benchmark.runners import variant_scan
from benchmark.runners.variant_scan import measure, verify  # noqa: F401


def guard_deadline(ctx, seconds: float) -> threading.Event:
    """From here until the returned event is set, ``seconds`` at most."""
    done = threading.Event()

    def watch() -> None:
        if not done.wait(seconds):
            print(f"benchmark: a set-up scan was still running after "
                  f"{seconds:.0f} s (scan_deadline_s): stopping, the cell "
                  f"cannot be measured on this program", file=sys.stderr,
                  flush=True)
            os._exit(3)

    threading.Thread(target=watch, name="bench-scan-deadline",
                     daemon=True).start()
    return done


def setup(ctx) -> None:
    verb = ctx.param("verb")
    ctx.tol = variant_scan._tolerances(ctx)
    ctx.ref = gen_kgp3.Reference()
    ctx.bcf = os.path.join(ctx.workdir, "cohort.vcf.gz")
    n_chunks, chunk = ctx.sizes["chunks"], ctx.sizes["chunk_records"]
    size = gen_kgp3_vcf.write_vcfgz(ctx.bcf, ctx.seed, n_chunks, chunk,
                                    ctx.ref, workers=ctx.gen_workers)
    ctx.records = n_chunks * chunk
    if ctx.ref.n != ctx.records:
        raise RuntimeError("generator lost records")
    ctx.part_done("generate+write")
    ctx.say(f"{ctx.records} lines of {gen_kgp3.N_SAMPLES} samples, "
            f"{ctx.ref.record_bytes / ctx.records:.1f} B a line, "
            f"{ctx.ref.record_bytes / 1e6:.1f} MB of text, "
            f"{size / 1e6:.1f} MB BGZF; reference mean_af "
            f"{ctx.ref.mean_af:.9f} (each ratio in bfloat16: "
            f"{ctx.ref.mean_af_bf16:.9f})")
    variant_scan.guard_memory(ctx)
    done = guard_deadline(ctx, float(ctx.param("scan_deadline_s")))
    for _ in range(int(ctx.param("warmup_scans"))):
        variant_scan._scan(ctx, verb)
    done.set()
    ctx.part_done("warm-up")
