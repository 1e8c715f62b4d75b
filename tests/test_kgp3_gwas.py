"""The ``kgp3-chr20-gwas-x1`` deployment on the CPU: the seeded 1000 Genomes
phase-3 chr20-shaped BCF and its trait file (tests/kgp3_gwas_reference.py)
through ``hbam vcf-gwas`` against the plain float64 reference, at the
published width of 2,504 samples — and small cohorts for the paths the
source never takes.

The chip compares the same things at the configured size
(benchmark/runners/variant_job.py); here the sizes are small and the timings
mean nothing.
"""
from __future__ import annotations

import contextlib
import io
import json
import os

import numpy as np
import pytest

import kgp3_gwas_reference as R
import kgp3_reference as K

from hadoop_bam_tpu.utils import native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "kgp3-chr20-gwas-x1.json"), encoding="utf-8") as _fh:
    CONFIG = json.load(_fh)
TOL = CONFIG["tolerances"]
# Q Q^T's entries are ~5 / S: 60 times larger in a 40-sample cohort than in
# the deployment's, and so is their float32 error (1.0e-7 against the
# bfloat16 reading's 1.3e-4 there)
SMALL_TOL = dict(TOL, projector_abs=1e-5)
SEED = 3_000_000_019


def run_cli(argv):
    from hadoop_bam_tpu.tools.cli import main as hbam_main

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = hbam_main(list(argv))
    return rc, out.getvalue(), err.getvalue()


def make(tmp, shape, n_traits, n_chunks, chunk_records, seed=41, mutate=None):
    bcf, tsv = str(tmp / "cohort.bcf"), str(tmp / "traits.tsv")
    ref = R.Reference(n_traits, shape)
    R.write_bcf(bcf, seed, n_chunks, chunk_records, ref, mutate=mutate)
    ref.write_traits(tsv, seed)
    return bcf, tsv, ref


def readings(ref, res):
    return ref.readings(res["eigenvalues"], res["q"] @ res["q"].T,
                        lambda lo, hi: res["chi2"][lo:hi])


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The configuration's ``tiny`` sizes: 2 x 2,048 sites, 2,504 samples
    still, 8 traits — and one job's whole answer."""
    from hadoop_bam_tpu.cohort.gwas import variant_gwas_file

    t = CONFIG["tiny"]
    bcf, tsv, ref = make(tmp_path_factory.mktemp("gwas"), K.KGP3,
                         t["traits"], t["chunks"], t["chunk_records"], SEED)
    return bcf, tsv, ref, variant_gwas_file(bcf, tsv, return_table=True)


SMALL = K.Shape((9, 7, 8, 6, 10), type_shares=(0.8, 0.15, 0.05),
                multi_share=0.2)


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """40 samples x 2,100 sites x 5 traits: several tile groups at small
    tile sizes, a fast job."""
    return make(tmp_path_factory.mktemp("gwas_small"), SMALL, 5, 3, 700)


# -- (a) the deployment at the published width --------------------------------

def test_the_configuration_is_the_scans_shape_plus_the_job():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kgp3-chr20-x1.json"), encoding="utf-8") as fh:
        scan = json.load(fh)
    assert {k: CONFIG["shape"][k] for k in scan["shape"]} == scan["shape"]
    assert {k: CONFIG["sizes"][k] for k in scan["sizes"]} == scan["sizes"]
    assert CONFIG["sizes"]["traits"] == 256 and CONFIG["tiny"]["traits"] == 8
    assert set(scan["assumed"]) < set(CONFIG["assumed"])
    from hadoop_bam_tpu.cohort import gwas
    from hadoop_bam_tpu.ops import gwas_pallas

    assert CONFIG["assumed"]["covariate_axes"] == gwas.GWAS_AXES == R.AXES
    assert gwas.GWAS_MAF_PERCENT / 100 == R.MAF
    assert gwas_pallas.V_FLOOR == R.V_FLOOR
    assert gwas_pallas.CHI2_GENOME_WIDE == R.CHI2_GENOME_WIDE


def test_the_benchmarks_copy_of_the_reference_is_verbatim():
    with open(os.path.join(ROOT, "tests", "kgp3_gwas_reference.py"),
              "rb") as a, open(os.path.join(
                  ROOT, "benchmark", "gen_kgp3_gwas.py"), "rb") as b:
        assert a.read() == b.read()


def test_the_generator_writes_the_scans_file(tiny, tmp_path):
    """``kgp3_gwas_reference.write_bcf`` composes the scan's generator: the
    same bytes, and the scan's own answers ride along."""
    bcf, _tsv, ref, _res = tiny
    t = CONFIG["tiny"]
    scan_ref = K.Reference()
    K.write_bcf(str(tmp_path / "scan.bcf"), SEED, t["chunks"],
                t["chunk_records"], scan_ref)
    with open(bcf, "rb") as a, open(tmp_path / "scan.bcf", "rb") as b:
        assert a.read() == b.read()
    assert ref.scan.mean_af == scan_ref.mean_af and ref.n == scan_ref.n
    assert ref.g.dtype == np.int8 and ref.g.shape == (4096, 2504)
    # four axes carry the five super-populations; the rest is bulk
    assert ref.f64.gap > 0.2


def test_tiny_job_equals_the_reference(tiny):
    _bcf, _tsv, ref, res = tiny
    assert (res["n_sites"], res["n_grm_sites"]) == (4096, ref.n_grm)
    assert 0 < ref.n_grm < 4096 and res["tested"] == 4096
    assert np.array_equal(res["pos"], ref.pos)
    got = readings(ref, res)
    assert ref.outside(got, TOL) == [], got
    assert got["nan_differs"] == 0
    # the reading one precision below is refused
    assert ref.outside(ref.readings(None, None, None, "bf16"), TOL)
    # the summaries are the table's
    chi2 = res["chi2"].astype(np.float64)
    assert np.allclose(res["mean_chi2"], np.nanmean(chi2, axis=0), rtol=1e-5)
    assert np.array_equal(res["max_chi2"], np.nanmax(res["chi2"], axis=0))
    assert np.array_equal(res["max_pos"],
                          ref.pos[np.nanargmax(chi2, axis=0)])
    assert np.array_equal(res["genome_wide"],
                          (chi2 > R.CHI2_GENOME_WIDE).sum(axis=0))


def test_tiny_file_through_the_verb_equals_the_reference(tiny):
    from hadoop_bam_tpu.cohort.gwas import EIGH_BLOCK
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    bcf, tsv, ref, _res = tiny
    with MetricsContext() as m:
        rc, out, _err = run_cli(["vcf-gwas", bcf, "--pheno", tsv])
    assert rc == 0 and ref.wrong(out, TOL) is None, out[:300]
    # 2,504 samples: the four pairs came from the subspace iteration,
    # inside its cap
    assert (m.get("gwas.eigh_topk_jobs"), m.get("gwas.eigh_full_jobs")) \
        == (1, 0)
    assert 0 < m.get("gwas.eigh_products") < 2504 // (2 * EIGH_BLOCK)
    lines = out.strip().splitlines()
    assert lines[0] == "sites\t4096" and len(lines) == 3 + R.AXES + 1 + 8
    # the comparison refuses what it should
    assert "sites" in ref.wrong(out.replace("sites\t4096", "sites\t4095", 1),
                                TOL)
    row = lines[-1].split("\t")
    off = "\t".join(row[:2] + [f"{float(row[2]) * 1.002:.9g}"] + row[3:])
    assert "mean_chi2" in ref.wrong(out.replace(lines[-1], off), TOL)
    moved = "\t".join(row[:4] + [str(int(ref.pos[0]))] + row[5:])
    assert "max_pos" in ref.wrong(out.replace(lines[-1], moved), TOL)
    more = "\t".join(row[:5] + [str(int(row[5]) + 1)])
    assert "genome_wide" in ref.wrong(out.replace(lines[-1], more), TOL)


def test_the_resident_matrix_is_the_files_dosage_byte_for_byte(tiny):
    """File order, the last group partial, pad rows and columns -1."""
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        _variant_gwas_load, DEFAULT_CONFIG,
    )

    bcf, _tsv, ref, _res = tiny
    _header, st = _variant_gwas_load(bcf, None, DEFAULT_CONFIG, None, None,
                                     None, 2)
    g = np.asarray(st.resident)
    assert st.rows == 4096 and g.dtype == np.int8
    assert g.shape[0] >= 4096 and g.shape[1] == 2560
    assert np.array_equal(g[:4096, :2504], ref.g)
    assert (g[4096:] == -1).all() and (g[:, 2504:] == -1).all()
    assert np.array_equal(np.asarray(st.sites)[1, :4096], ref.pos)
    assert int(st.n_grm) == ref.n_grm


def test_the_plan_reserves_rows_from_what_it_can_observe(tiny, small):
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        _estimate_variant_sites,
    )

    for path, n in ((tiny[0], 4096), (small[0], 2100)):
        est = _estimate_variant_sites(open_vcf(path))
        assert abs(est - n) < 0.01 * n, (path, est)


# -- (b) small cohorts: plans, tiles, the paths the source never takes -------

@pytest.mark.parametrize("n_spans", [1, 3, 7])
@pytest.mark.parametrize("tile_records", [256, 1000])
def test_span_plans_and_tile_sizes_give_the_same_answer(small, n_spans,
                                                        tile_records):
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.cohort.gwas import variant_gwas_file
    from hadoop_bam_tpu.parallel.variant_pipeline import VariantGeometry

    bcf, tsv, ref = small
    res = variant_gwas_file(
        bcf, tsv, return_table=True,
        geometry=VariantGeometry(tile_records=tile_records, n_samples=40),
        spans=open_vcf(bcf).spans(num_spans=n_spans))
    assert (res["n_sites"], res["n_grm_sites"]) == (2100, ref.n_grm)
    assert np.array_equal(res["pos"], ref.pos)
    assert ref.outside(readings(ref, res), SMALL_TOL) == []


def test_missing_multiallelic_and_collinear_sites_take_their_paths(tmp_path):
    """A site with a missing call is outside the GRM's set and untested; a
    multi-allelic SNP counts with its non-REF dosage; a site whose dosage
    lies in the covariates' span (every sample heterozygous: the
    intercept's) is untested."""
    from hadoop_bam_tpu.cohort.gwas import variant_gwas_file

    shape = SMALL._replace(missing=0.002)

    def mutate(f, c):
        if c == 0:
            f["a0"][5], f["a1"][5] = 0, 1           # g = 1 for everyone
            f["ploidy"][5] = 2

    bcf, tsv, ref = make(tmp_path, shape, 3, 2, 600, mutate=mutate)
    missing = (ref.g < 0).any(axis=1)
    multi = (ref.g == 2).any(axis=1) & ref.in_c
    assert 50 < missing.sum() < 1100 and multi.any()
    assert not ref.in_c[missing].any() and not ref.in_c[5]
    res = variant_gwas_file(bcf, tsv, return_table=True)
    assert res["n_grm_sites"] == ref.n_grm
    nan = np.isnan(res["chi2"])
    assert nan[missing].all() and nan[5].all()
    assert not nan[~missing & (np.arange(ref.n) != 5)].any()
    assert res["tested"] == ref.n - missing.sum() - 1 \
        == ref.summary()["tested"]
    assert ref.outside(readings(ref, res), SMALL_TOL) == []


def test_a_text_vcf_is_loaded_like_the_bcf(small, tmp_path):
    """The feed decides by the container; a plain-gzip VCF, which cannot be
    sized without inflating it, is refused."""
    import gzip

    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.cohort.gwas import variant_gwas_file
    from hadoop_bam_tpu.utils.errors import PlanError

    bcf, tsv, ref = small
    vcf = str(tmp_path / "cohort.vcf")
    ds = open_vcf(bcf)
    with open(vcf, "w", encoding="ascii") as fh:
        fh.write(K.header_text(SMALL))
        for rec in ds.records():
            fh.write(rec.to_line() + "\n")
    res = variant_gwas_file(vcf, tsv, return_table=True)
    assert res["n_grm_sites"] == ref.n_grm
    assert ref.outside(readings(ref, res), SMALL_TOL) == []
    with open(vcf, "rb") as src, gzip.open(vcf + ".gz", "wb") as dst:
        dst.write(src.read())
    with pytest.raises(PlanError, match="plain-gzip"):
        variant_gwas_file(vcf + ".gz", tsv)


def test_a_small_cohort_gets_its_covariates_from_the_full_solve(small):
    """40 samples: LAPACK's ``eigh`` is cheaper than any block product;
    the shape decides, and the counters say so."""
    from hadoop_bam_tpu.cohort.gwas import variant_gwas_file
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    bcf, tsv, ref = small
    with MetricsContext() as m:
        res = variant_gwas_file(bcf, tsv, return_table=True)
    assert (m.get("gwas.eigh_topk_jobs"), m.get("gwas.eigh_full_jobs"),
            m.get("gwas.eigh_products")) == (0, 1, 0)
    assert res["eigenvalues"].shape == (R.AXES,)
    assert ref.outside(readings(ref, res), SMALL_TOL) == []


# -- (c) the trait file --------------------------------------------------------

def _edit(tsv, tmp_path, fn):
    with open(tsv, encoding="ascii") as fh:
        lines = fh.read().splitlines()
    out = str(tmp_path / "edited.tsv")
    with open(out, "w", encoding="ascii") as fh:
        fh.write("\n".join(fn(lines)) + "\n")
    return out


BAD_TSV = {
    "a missing value": (lambda ls: ls[:3] + [ls[3].rsplit("\t", 1)[0]
                                             + "\t"] + ls[4:],
                        "missing or not a number"),
    "NA for a value": (lambda ls: ls[:3] + [ls[3].rsplit("\t", 1)[0]
                                            + "\tNA"] + ls[4:],
                       "missing or not a number"),
    "a non-finite value": (lambda ls: ls[:3] + [ls[3].rsplit("\t", 1)[0]
                                                + "\tinf"] + ls[4:],
                           "not finite"),
    "a value short": (lambda ls: ls[:3] + [ls[3].rsplit("\t", 1)[0]]
                      + ls[4:], "values for"),
    "an unknown sample": (lambda ls: ls + ["HG99999" + ls[1][7:]],
                          "not in the call set"),
    "an absent sample": (lambda ls: ls[:-1], "no row for 1"),
    "a sample twice": (lambda ls: ls + [ls[1]], "twice"),
    "no header": (lambda ls: ls[1:], "header line"),
}


@pytest.mark.parametrize("what", BAD_TSV)
def test_a_bad_trait_file_is_a_plan_error(small, tmp_path, what):
    from hadoop_bam_tpu.cohort.gwas import variant_gwas_file
    from hadoop_bam_tpu.utils.errors import PlanError

    bcf, tsv, _ref = small
    edit, message = BAD_TSV[what]
    bad = _edit(tsv, tmp_path, edit)
    with pytest.raises(PlanError, match=message):
        variant_gwas_file(bcf, bad)
    rc, _out, err = run_cli(["vcf-gwas", bcf, "--pheno", bad])
    assert rc == 1 and message in err


def test_trait_rows_are_matched_by_name(small, tmp_path):
    from hadoop_bam_tpu.cohort.gwas import read_traits_tsv

    _bcf, tsv, ref = small
    names, y = read_traits_tsv(tsv, K.sample_names(SMALL))
    assert names == R.trait_names(5) and np.array_equal(y, ref.y)
    # the file's rows are not in the call set's order
    with open(tsv, encoding="ascii") as fh:
        rows = [ln.split("\t")[0] for ln in fh.read().splitlines()[1:]]
    assert rows != K.sample_names(SMALL) \
        and sorted(rows) == sorted(K.sample_names(SMALL))


# -- (d) what the job reports from inside --------------------------------------

def test_two_jobs_count_their_work_and_build_their_steps_once(tmp_path):
    from hadoop_bam_tpu.utils.metrics import base_metrics

    # a sample count no other test uses: the steps are built here
    shape = K.Shape((5, 6, 4, 5, 3))
    bcf, tsv, ref = make(tmp_path, shape, 2, 2, 500)
    base_metrics().reset()
    for _ in range(2):
        rc, out, _err = run_cli(["vcf-gwas", bcf, "--pheno", tsv])
        assert rc == 0 and ref.wrong(out, SMALL_TOL) is None
    snap = base_metrics().snapshot()
    c = snap["counters"]
    assert c["gwas.jobs"] == 2 and c["plan.executions"] == 2
    assert c["gwas.sites"] == c["pipeline.records"] == 2 * ref.n
    assert c["gwas.assoc_sites_resident"] == c["gwas.assoc_sites"] \
        == 2 * ref.n
    assert c["gwas.grm_sites"] == 2 * ref.n_grm and c["gwas.traits"] == 4
    assert c["gwas.eigh_full_jobs"] == 2 and "gwas.eigh_topk_jobs" not in c
    # the file crosses once a job: pass 2 decodes nothing
    assert c["vcf.inflated_bytes"] == 2 * ref.scan.record_bytes
    assert c["steps.built.hbam_gwas_load_step"] == 1
    assert c["steps.built.hbam_gwas_assoc_step"] == 1
    # int8, as allocated: rows reserved x the lane-padded sample stride
    assert c["gwas.resident_bytes"] % (2 * 128) == 0
    walls = snap["wall_timers"]
    for span in ("gwas.pheno_wall", "gwas.load_wall", "gwas.grm_wall",
                 "gwas.grm_readback_wall", "gwas.grm_finish_wall",
                 "gwas.eigh_wall", "gwas.assoc_wall", "vcf.plan_wall",
                 "vcf.dispatch_wall", "pipeline.feed_wall"):
        assert walls[span] > 0, span
    assert walls["pipeline.feed_wall"] <= walls["gwas.load_wall"] \
        <= walls["plan.execute_wall"]
    # the GRM's one readback and its finish lie inside its wall
    assert walls["gwas.grm_readback_wall"] + walls["gwas.grm_finish_wall"] \
        <= walls["gwas.grm_wall"]
    # A is written into one buffer kept from the first job to the second
    # (the first job may reuse one an earlier test left at this S)
    assert c.get("gwas.grm_buffer_reused", 0) \
        + c.get("gwas.grm_buffer_minted", 0) == 2
    assert c["gwas.grm_buffer_reused"] >= 1
    if native.load() is not None:
        assert c["gwas.grm_native_jobs"] == 2 and "gwas.grm_numpy_jobs" \
            not in c
    else:
        assert c["gwas.grm_numpy_jobs"] == 2


def test_the_load_step_names_its_phases_and_donates_its_state():
    import jax
    import jax.numpy as jnp

    from hadoop_bam_tpu.cohort.gwas import make_gwas_load_step

    S = jax.ShapeDtypeStruct
    step = make_gwas_load_step(30)
    args = (S((1024, 512), jnp.int8), S((2, 1024), jnp.int32),
            S((512, 512), jnp.float32), S((512,), jnp.float32),
            S((), jnp.float32), S((), jnp.int32),
            S((1, 64), jnp.int32), S((1, 64), jnp.int32),
            S((1, 64), jnp.uint8), S((1, 64, 32), jnp.int8),
            S((1,), jnp.int32), S((), jnp.int32))
    lowered = step.lower(*args)
    assert lowered.as_text().startswith("module @jit_hbam_gwas_load_step ")
    text = lowered.as_text(debug_info=True)
    for scope in ("resident_update", "af", "grm"):
        assert f"{scope}/" in text or f"/{scope}" in text, scope
    assert "dynamic_update_slice" in text
    # the resident state goes in donated and comes out in place
    assert text.count("tf.aliasing_output") == 6


# -- (e) the two kernels against their plain-XLA twins ------------------------

def _ragged_tile(rng, rows, cols, n_rows, n_cols):
    tile = rng.integers(0, 3, (rows, cols)).astype(np.int8)
    tile[n_rows:] = -1
    tile[:, n_cols:] = -1
    return tile


@pytest.mark.parametrize("rows,n_rows,n_samples", [
    (512, 512, 1024), (1024, 700, 1000), (1536, 1025, 513)])
def test_the_grm_kernel_equals_its_twin(rows, n_rows, n_samples):
    import jax

    from hadoop_bam_tpu.ops import gwas_pallas as gp

    rng = np.random.default_rng(rows)
    sp = gp.round_up(n_samples, gp.GRM_BLOCK)
    tile = _ragged_tile(rng, rows, sp, n_rows, n_samples)
    p = np.clip(tile[:, :n_samples].clip(0).sum(1) / (2 * n_samples),
                0.01, 0.99)
    m = np.where(np.arange(rows) < n_rows, 2 * p, 0).astype(np.float32)
    w = np.where(np.arange(rows) < n_rows, 1 / (2 * p * (1 - p)), 0) \
        .astype(np.float32)
    w[::7] = m[::7] = 0                       # sites outside the set
    acc = rng.standard_normal((sp, sp)).astype(np.float32)
    twin = np.asarray(jax.jit(gp.grm_accumulate)(acc, tile, m, w))
    kernel = np.asarray(jax.jit(
        lambda *a: gp.grm_accumulate(*a, force_pallas=True))(acc, tile, m,
                                                             w))
    block = np.arange(sp) // gp.GRM_BLOCK
    upper = block[:, None] <= block[None, :]
    assert np.allclose(kernel[upper], twin[upper], rtol=0,
                       atol=2e-6 * np.abs(twin).max())
    # below the block diagonal nothing is accumulated
    assert np.array_equal(kernel[~upper], acc[~upper])
    # and both are T^T G in float64
    g = tile.astype(np.float64)
    want = acc + ((g - m[:, None]) * w[:, None]).T @ g
    assert np.allclose(twin, want, rtol=0, atol=2e-6 * np.abs(want).max())


@pytest.mark.parametrize("with_table", [False, True])
@pytest.mark.parametrize("cap,n_sites,n_samples,n_traits", [
    (1024, 1000, 100, 3), (2048, 1025, 250, 130), (512, 512, 128, 123)])
def test_the_assoc_kernel_equals_its_twin(cap, n_sites, n_samples,
                                          n_traits, with_table):
    import jax

    from hadoop_bam_tpu.ops import gwas_pallas as gp

    rng = np.random.default_rng(cap + n_traits)
    sp = gp.round_up(n_samples, gp.GRM_BLOCK)
    g = _ragged_tile(rng, cap, sp, n_sites, n_samples)
    g[3, 5] = -1                               # a missing call
    g[9, :n_samples] = 1                       # in the intercept's span
    n_cov = 5
    np_ = gp.round_up(n_traits + n_cov, gp.LANE)
    q = np.linalg.qr(np.concatenate(
        [np.ones((n_samples, 1)), rng.standard_normal((n_samples, 4))],
        axis=1))[0]
    w = np.zeros((sp, np_), np.float32)
    w[:n_samples, :n_traits] = rng.standard_normal((n_samples, n_traits))
    w[:n_samples, n_traits:n_traits + n_cov] = q
    isig = np.zeros(np_, np.float32)
    isig[:n_traits] = rng.uniform(0.5, 2.0, n_traits)
    args = (g, np.stack(gp.split_bf16(w)), isig,
            np.array([n_sites], np.int32))
    kw = dict(n_traits=n_traits, n_cov=n_cov, n_samples=n_samples,
              with_table=with_table)
    twin = jax.jit(lambda *a: gp.assoc_scan(*a, **kw))(*args)
    kernel = jax.jit(lambda *a: gp.assoc_scan(*a, force_pallas=True,
                                              **kw))(*args)
    assert set(kernel) == set(twin) \
        == {"sum", "max", "max_row", "tested", "hits"} \
        | ({"chi2"} if with_table else set())
    assert int(twin["tested"]) == n_sites - 2
    for k in twin:
        assert np.allclose(np.asarray(kernel[k]), np.asarray(twin[k]),
                           rtol=1e-5, atol=1e-6, equal_nan=True), k
    if with_table:
        chi2 = np.asarray(twin["chi2"], np.float64)
        assert chi2.shape == (cap, n_traits)
        assert np.isnan(chi2[[3, 9]]).all() and np.isnan(chi2[n_sites:]).all()
        gf = g[:n_sites, :n_samples].astype(np.float64)
        r = gf @ w[:n_samples].astype(np.float64)
        v = (gf * gf).sum(1) - (r[:, n_traits:n_traits + n_cov] ** 2).sum(1)
        want = r[:, :n_traits] ** 2 * isig[:n_traits] / v[:, None]
        ok = ~np.isnan(chi2[:n_sites, 0])
        assert np.allclose(chi2[:n_sites][ok], want[ok], rtol=2e-5,
                           atol=1e-6)
        assert np.array_equal(np.asarray(twin["max_row"]),
                              np.nanargmax(chi2, axis=0))


def test_split_bf16_loses_nothing_float32_keeps():
    import jax.numpy as jnp

    from hadoop_bam_tpu.ops.gwas_pallas import split_bf16

    x = np.random.default_rng(2).standard_normal(4096).astype(np.float32)
    x[:3] = (0.0, 1.0, 1 / 3)
    for parts in (split_bf16(x), split_bf16(jnp.asarray(x))):
        assert all(p.dtype == jnp.bfloat16 for p in parts)
        total = sum(np.asarray(p.astype(jnp.float32), np.float64)
                    for p in parts)
        assert np.array_equal(total.astype(np.float32), x)
    assert all(isinstance(p, np.ndarray) for p in split_bf16(x))
