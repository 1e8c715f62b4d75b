"""Runner ``variant_job``: whole ``hbam vcf-gwas`` jobs over a cohort BCF and
its trait file, back to back.

Traffic parameters: ``verb`` (``vcf-gwas``), ``warmup_jobs``.  The BCF is the
file ``variant_scan`` scans, made by the same generator (``benchmark/
gen_kgp3.py``) through ``benchmark/gen_kgp3_gwas.py``, which also keeps the
int8 dosage, draws the seeded traits, and computes the job's answers in
float64 — and a second time with Z and Y~ rounded to bfloat16, the reading
the comparison has to refuse.  Both files are re-read from the start each
job (host page cache).  A job is a scan of the file's records, so the rate is
``scan_records_per_s``: the sites of whole jobs over the wall from the first
job's start to the end of the last that started inside ``--seconds``.  Every
job's printed answer is compared with the reference's; after the window
``verify`` runs one more job through the function the verb calls and compares
A's eigenvalues, the projector ``Q Q^T`` and the whole ``[M, P]`` chi2 table,
block by block, with the configuration's tolerances.
"""
from __future__ import annotations

import json
import os
import time

from benchmark import gen_kgp3_gwas as gwas_ref
from benchmark.runners.scan import run_cli
from benchmark.runners.variant_scan import guard_memory


def _config(ctx) -> dict:
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", ctx.cell["config"] + ".json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def setup(ctx) -> None:
    # a program from before the verb ends here, at once and with a
    # non-zero exit, before any data is made
    from hadoop_bam_tpu.cohort.gwas import variant_gwas_file  # noqa: F401

    ctx.tol = _config(ctx)["tolerances"]
    ref = ctx.ref = gwas_ref.Reference(ctx.sizes["traits"])
    ctx.bcf = os.path.join(ctx.workdir, "cohort.bcf")
    ctx.tsv = os.path.join(ctx.workdir, "traits.tsv")
    n_chunks, chunk = ctx.sizes["chunks"], ctx.sizes["chunk_records"]
    size = gwas_ref.write_bcf(ctx.bcf, ctx.seed, n_chunks, chunk, ref,
                              workers=ctx.gen_workers)
    ref.write_traits(ctx.tsv, ctx.seed)
    ctx.records = n_chunks * chunk
    if ref.n != ctx.records:
        raise RuntimeError("generator lost records")
    ctx.part_done("generate+write")
    ctx.say(f"{ref.n} sites x {ref.shape.n_samples} samples x "
            f"{ref.n_traits} traits, {ref.scan.record_bytes / 1e6:.1f} MB "
            f"inflated, {size / 1e6:.1f} MB BGZF; GRM sites {ref.n_grm}; "
            f"reference eigenvalues 1-8 "
            f"{[round(float(x), 4) for x in ref.f64.eigenvalues[:8]]}, "
            f"gap (l4 - l5) / l4 = {ref.f64.gap:.3f}")
    # one pass over the float64 table for both
    ctx.bf16 = ref.readings(None, None, None, "bf16",
                            summary_rel=ctx.tol["chi2_rel"])
    s = ref.summary(ctx.tol["chi2_rel"])
    ctx.part_done("reference")
    ctx.say(f"reference: tested {s['tested']} sites, {s['borderline']} "
            f"with v / |g|^2 within a factor 2 of the floor; the bfloat16 "
            f"reading lies {gwas_ref.describe(ctx.bf16)} from it and breaks "
            f"{ref.outside(ctx.bf16, ctx.tol)}")
    guard_memory(ctx)
    for _ in range(int(ctx.param("warmup_jobs"))):
        _job(ctx)
    ctx.part_done("warm-up")


def _job(ctx):
    wrong = ctx.ref.wrong(
        run_cli([ctx.param("verb"), ctx.bcf, "--pheno", ctx.tsv]), ctx.tol)
    if wrong:
        ctx.say(f"WRONG: {wrong}")
    return wrong


def measure(ctx) -> dict:
    import jax

    jobs = bad = errors = 0
    t0 = t_end = time.perf_counter()
    while t_end - t0 < ctx.seconds:
        try:
            bad += _job(ctx) is not None
        except Exception as e:  # noqa: BLE001 — a failed job is counted
            ctx.say(f"job failed: {type(e).__name__}: {e}")
            errors += 1
        jobs += 1
        t_end = time.perf_counter()
    done = jobs - errors
    wall = t_end - t0
    rate = done * ctx.records / wall
    ctx.say(f"{jobs} jobs attempted, {done} completed in {wall:.3f} s: "
            f"{rate:.1f} records/s ({wall / max(jobs, 1):.4f} s a job)")
    ref = ctx.ref
    return {"correct": bad == 0 and done > 0, "attempted": jobs,
            "failed": errors,
            "end_to_end": {"scan_records_per_s": rate},
            "observations": {
                "units": {"records": done * ctx.records, "jobs": done},
                "device_kind": jax.devices()[0].device_kind,
                # the sizes benchmark/kernel_work.py counts a job's work from
                "gwas": {"jobs": done, "sites": ref.n,
                         "grm_sites": ref.n_grm,
                         "samples": ref.shape.n_samples,
                         "traits": ref.n_traits,
                         "covariates": 1 + gwas_ref.AXES}}}


def verify(ctx) -> bool:
    """One more job through the function the verb calls, its whole answer
    against the float64 reference: inside every tolerance, where the
    bfloat16 reading is outside at least one."""
    from hadoop_bam_tpu.cohort.gwas import variant_gwas_file

    res = variant_gwas_file(ctx.bcf, ctx.tsv, return_table=True)
    ref = ctx.ref
    exact = (res["n_sites"] == ref.n and res["n_grm_sites"] == ref.n_grm
             and bool((res["pos"] == ref.pos).all()))
    got = ref.readings(res["eigenvalues"], res["q"] @ res["q"].T,
                       lambda lo, hi: res["chi2"][lo:hi])
    broke, bf16_broke = ref.outside(got, ctx.tol), \
        ref.outside(ctx.bf16, ctx.tol)
    limits = {k: v for k, v in ctx.tol.items() if k != "why"}
    ctx.say(f"verify: sites {res['n_sites']} GRM sites "
            f"{res['n_grm_sites']} positions in file order: {exact}; the "
            f"job's reading {gwas_ref.describe(got)} breaks {broke}; the "
            f"bfloat16 reading {gwas_ref.describe(ctx.bf16)} breaks "
            f"{bf16_broke}; limits {limits}")
    return exact and not broke and bool(bf16_broke)
