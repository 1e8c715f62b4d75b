"""Runner ``gatk_text_scan``: whole-file scans of a GATK joint call set's
bgzip'd VCF text through one ``hbam`` verb, back to back.

``variant_text_scan`` with the file-making replaced: the file is the
configuration's coordinate-sorted ``.vcf.gz`` as GATK writes it (FORMAT
``GT:AD:DP:GQ:PL``, no-calls, VQSR filters), made in child processes by
``benchmark/gen_kgp30x_gatk.py`` (NumPy + zlib only), whose ``Reference`` is
folded from the generator's allele arrays and FILTER draws.  Measuring,
comparing and ``verify`` are ``variant_scan``'s own functions: they read
``ctx.bcf``, which here names the ``.vcf.gz``.

Traffic parameters: ``verb`` (``vcf-stats``), ``warmup_scans``,
``scan_deadline_s``: a set-up scan still running after that many seconds
ends the run with one line and exit 3 (``variant_text_scan.guard_deadline``:
a program that parses a keyed 3,202-sample line a cell at a time in Python
needs minutes a scan).
"""
from __future__ import annotations

import os

from benchmark import gen_kgp30x_gatk
from benchmark.runners import variant_scan
from benchmark.runners.variant_scan import measure, verify  # noqa: F401
from benchmark.runners.variant_text_scan import guard_deadline


def setup(ctx) -> None:
    verb = ctx.param("verb")
    ctx.tol = variant_scan._tolerances(ctx)
    ctx.ref = gen_kgp30x_gatk.Reference()
    ctx.bcf = os.path.join(ctx.workdir, "cohort.vcf.gz")
    n_chunks, chunk = ctx.sizes["chunks"], ctx.sizes["chunk_records"]
    size = gen_kgp30x_gatk.write_vcfgz(ctx.bcf, ctx.seed, n_chunks, chunk,
                                       ctx.ref, workers=ctx.gen_workers)
    ctx.records = n_chunks * chunk
    if ctx.ref.n != ctx.records:
        raise RuntimeError("generator lost records")
    ctx.part_done("generate+write")
    ctx.say(f"{ctx.records} lines of {gen_kgp30x_gatk.N_SAMPLES} samples, "
            f"{ctx.ref.record_bytes / ctx.records:.1f} B a line, "
            f"{ctx.ref.record_bytes / 1e6:.1f} MB of text, "
            f"{size / 1e6:.1f} MB BGZF; {ctx.ref.n_pass} PASS, "
            f"{ctx.ref.nocall_cells} no-call cells; reference mean_af "
            f"{ctx.ref.mean_af:.9f} (each ratio in bfloat16: "
            f"{ctx.ref.mean_af_bf16:.9f})")
    variant_scan.guard_memory(ctx)
    done = guard_deadline(ctx, float(ctx.param("scan_deadline_s")))
    for _ in range(int(ctx.param("warmup_scans"))):
        variant_scan._scan(ctx, verb)
    done.set()
    ctx.part_done("warm-up")
