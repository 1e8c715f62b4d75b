"""GWAS-style mesh drivers over the joined [variants, samples] tensor.

One ``shard_map`` step per tile group computes, per variant row:

- **allele frequency** ``af = alt_allele_sum / (2 * n_called)`` —
  diploid ALT frequency over called samples (NaN when nothing called);
- **call rate** ``n_called / n_samples``;
- **HWE chi-square**: observed diploid genotype counts (hom-ref / het /
  hom-alt among called samples with dosage <= 2) against
  Hardy-Weinberg expectation at the observed allele frequency, 1 d.f.
  (NaN when no classed genotypes);
- **score-test association** against a phenotype vector ``y`` [SPEC:
  the standard 1-d.f. score test of H0: beta_g = 0 in
  ``y = mu + beta_g * g``]::

      U  = sum_i (y_i - ybar)(g_i - gbar)      over called, phenotyped i
      Vg = sum_i (g_i - gbar)^2
      Vy = sum_i (y_i - ybar)^2 / n            (MLE variance under H0)
      chi2 = U^2 / (Vy * Vg)                   (NaN when Vy*Vg == 0)

Every formula has a NumPy twin in tests/test_cohort.py pinned to
float32 tolerance — the drivers are reductions along the SAMPLE axis,
so rows shard cleanly over the mesh's data axis with no collective at
all; only the phenotype is replicated.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.errors import PlanError
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.stepcache import named_step

# columns of the per-variant stats tensor the step returns, in order
GWAS_COLUMNS = ("af", "call_rate", "hwe_chi2", "score_chi2")


def make_cohort_gwas_step(mesh, geometry, with_pheno: bool,
                          axis: str = "data"):
    """Jitted sharded step: one joined tile group -> per-variant stats
    ``[n_dev, cap, 4]`` float32 (NaN where a stat is undefined).  The
    phenotype rides as a replicated runtime argument, so one compiled
    step serves every batch and every phenotype."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from hadoop_bam_tpu.parallel.mesh import shard_map
    from hadoop_bam_tpu.parallel.pipeline import _STEP_CACHE

    key = ("cohort_gwas", tuple(mesh.devices.flat), mesh.axis_names,
           axis, geometry, bool(with_pheno))
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]

    S = int(geometry.n_samples)
    nan = jnp.float32(jnp.nan)

    def per_device(dosage, count, pheno):
        dosage, count = dosage[0], count[0]
        cap = dosage.shape[0]
        valid = jnp.arange(cap, dtype=jnp.int32) < count
        samp = jnp.arange(dosage.shape[1], dtype=jnp.int32) < S
        d = dosage.astype(jnp.int32)
        called = (d >= 0) & samp[None, :]
        cf = called.astype(jnp.float32)
        n_called = called.sum(axis=1)                       # [cap] i32
        ncf = n_called.astype(jnp.float32)
        alt = jnp.where(called, d, 0).sum(axis=1).astype(jnp.float32)
        has = n_called > 0
        af = jnp.where(has, alt / (2.0 * jnp.maximum(ncf, 1.0)), nan)
        call_rate = ncf / jnp.float32(max(S, 1))

        # HWE: diploid-classed genotypes only (dosage 0/1/2); polyploid
        # dosage > 2 counts as called but is excluded from the table
        n0 = ((d == 0) & called).sum(axis=1).astype(jnp.float32)
        n1 = ((d == 1) & called).sum(axis=1).astype(jnp.float32)
        n2 = ((d == 2) & called).sum(axis=1).astype(jnp.float32)
        m = n0 + n1 + n2
        msafe = jnp.maximum(m, 1.0)
        p = (2.0 * n2 + n1) / (2.0 * msafe)
        e0 = (1.0 - p) ** 2 * m
        e1 = 2.0 * p * (1.0 - p) * m
        e2 = p ** 2 * m

        def term(obs, exp):
            return jnp.where(exp > 0, (obs - exp) ** 2
                             / jnp.maximum(exp, 1e-12), 0.0)

        hwe = jnp.where(m > 0, term(n0, e0) + term(n1, e1) + term(n2, e2),
                        nan)

        if with_pheno:
            yok = jnp.isfinite(pheno) & samp
            use = called & yok[None, :]
            uf = use.astype(jnp.float32)
            n = uf.sum(axis=1)
            nsafe = jnp.maximum(n, 1.0)
            y = jnp.where(yok, pheno, 0.0)[None, :]
            g = jnp.where(use, d, 0).astype(jnp.float32)
            sy = (y * uf).sum(axis=1)
            sg = g.sum(axis=1)
            sgy = (g * y).sum(axis=1)
            sgg = (g * g).sum(axis=1)
            syy = (y * y * uf).sum(axis=1)
            u_stat = sgy - sy * sg / nsafe
            vg = sgg - sg * sg / nsafe
            vy = (syy - sy * sy / nsafe) / nsafe
            denom = vy * vg
            score = jnp.where((n > 1) & (denom > 1e-12),
                              u_stat * u_stat / jnp.maximum(denom, 1e-12),
                              nan)
        else:
            score = jnp.full((cap,), nan, jnp.float32)

        stats = jnp.stack([af, call_rate, hwe, score], axis=1)
        # padding rows report NaN across the board, never a fake 0 stat
        return jnp.where(valid[:, None], stats, nan)[None]

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(P(axis), P(axis), P()),
                   out_specs=P(axis))
    step = named_step("gwas_step", fn)
    _STEP_CACHE[key] = step
    return step


def cohort_gwas(source, phenotype=None, mesh=None,
                config: HBamConfig = DEFAULT_CONFIG,
                geometry=None) -> Dict[str, np.ndarray]:
    """Drive the joined cohort through the GWAS step: returns
    per-variant arrays ``chrom``/``pos``/``n_allele`` plus the
    ``GWAS_COLUMNS`` float32 stats (and ``n_variants``,
    ``sample_ids``, ``quarantined``).

    ``phenotype`` is one float per manifest sample (NaN = missing
    phenotype; that sample drops out of the score test only).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hadoop_bam_tpu.cohort.dataset import CohortDataset
    from hadoop_bam_tpu.parallel.mesh import make_mesh

    ds = source if isinstance(source, CohortDataset) \
        else CohortDataset(source, config)
    if mesh is None:
        mesh = make_mesh()
    if geometry is None:
        geometry = ds.geometry

    pheno_dev = None
    if phenotype is not None:
        y = np.asarray(phenotype, dtype=np.float32)
        if y.shape != (ds.n_samples,):
            raise PlanError(
                f"phenotype must be one value per manifest sample "
                f"({ds.n_samples}), got shape {tuple(y.shape)}")
        ypad = np.full(geometry.samples_pad, np.nan, np.float32)
        ypad[:ds.n_samples] = y
        pheno_dev = jax.device_put(ypad, NamedSharding(mesh, P()))
    else:
        pheno_dev = jax.device_put(
            np.full(geometry.samples_pad, np.nan, np.float32),
            NamedSharding(mesh, P()))

    step = make_cohort_gwas_step(mesh, geometry, phenotype is not None)
    chroms, poss, nalls, stats_parts = [], [], [], []
    for out in ds.tensor_batches(mesh, geometry):
        with METRICS.span("cohort.kernel_wall"):
            stats = step(out["dosage"], out["n_records"], pheno_dev)
        counts = np.asarray(out["n_records"])
        host = np.asarray(stats)
        hchrom = np.asarray(out["chrom"])
        hpos = np.asarray(out["pos"])
        hnall = np.asarray(out["n_allele"])
        for dev in range(counts.shape[0]):
            c = int(counts[dev])
            if c:
                chroms.append(hchrom[dev, :c])
                poss.append(hpos[dev, :c])
                nalls.append(hnall[dev, :c])
                stats_parts.append(host[dev, :c])
    if stats_parts:
        stats_all = np.concatenate(stats_parts, axis=0)
        chrom = np.concatenate(chroms)
        pos = np.concatenate(poss)
        nall = np.concatenate(nalls)
    else:
        stats_all = np.empty((0, len(GWAS_COLUMNS)), np.float32)
        chrom = np.empty(0, np.int32)
        pos = np.empty(0, np.int32)
        nall = np.empty(0, np.int16)
    out = {
        "n_variants": int(stats_all.shape[0]),
        "chrom": chrom, "pos": pos, "n_allele": nall,
        "sample_ids": list(ds.sample_ids),
        "quarantined": dict(ds.manifest.quarantined),
    }
    for j, name in enumerate(GWAS_COLUMNS):
        out[name] = stats_all[:, j]
    return out


# ---------------------------------------------------------------------------
# the structure-adjusted, many-trait form over a device-resident matrix
# (``hbam vcf-gwas``; the driver is parallel/variant_pipeline.py's)
# ---------------------------------------------------------------------------
#
# Pass 1 keeps every tile group of the file in one int8 matrix on the
# chip and accumulates GCTA's genetic relationship matrix from it (Yang
# et al., AJHG 88:76, 2011):  A = Z^T Z / |C|,  z_js = (g_js - 2 p_j) /
# sqrt(2 p_j (1 - p_j)) over the sites C = {SNP, no missing call, MAF >=
# GWAS_MAF_PERCENT %}.  The GWAS_AXES leading eigenvectors of A are the
# covariates (EIGENSTRAT, Price et al., Nat Genet 38:904, 2006):
# X = [1, v_1..v_k], Q = qr(X).Q.  Pass 2 reads the matrix, not the file:
# Y~ = Y - Q Q^T Y, sigma2_p = |y~_p|^2 / S, and for every site and trait
#
#     u = g_j . y~_p     v_j = |g_j|^2 - |Q^T g_j|^2
#     chi2 = u^2 / (v_j sigma2_p)      (the score test above, adjusted)
#
# NaN where v_j <= 1e-6 |g_j|^2 (the dosage lies in the covariates' span)
# or a call is missing.  Traits share their missingness (none), which is
# what lets one matrix product serve them all (as PLINK 2's --glm batches
# quantitative traits).

GWAS_AXES = 4              # covariate axes beside the intercept
GWAS_MAF_PERCENT = 1       # the GRM's site filter, as a whole percentage
# covariates(): the subspace iteration's block, the residual it stops on
# (relative to the leading eigenvalue) and the fewest block products worth
# starting it for (``_leading_eigenpairs`` says where each comes from)
EIGH_BLOCK = 16
EIGH_RESIDUAL = 1e-13
EIGH_MIN_PRODUCTS = 12
# grm_buffer(): the A buffer kept between leases
_GRM_LOCK = threading.Lock()
_grm_kept: Optional[np.ndarray] = None


def read_traits_tsv(path: str, samples):
    """(trait names, Y [S, P] float64 in the header's sample order) of a
    trait file: header ``sample`` + P names, one row a sample, matched by
    name.  A missing or non-finite value, an unknown, repeated or absent
    sample is a ``PlanError``: equal missingness is the verb's contract."""
    with open(path, encoding="utf-8") as fh:
        lines = fh.read().splitlines()
    head = lines[0].split("\t") if lines else []
    if len(head) < 2 or head[0] != "sample":
        raise PlanError(f"{path}: a trait file starts with a header line "
                        f"'sample<TAB>name...'")
    names = head[1:]
    index = {s: i for i, s in enumerate(samples)}
    y = np.full((len(samples), len(names)), np.nan)
    seen = np.zeros(len(samples), bool)
    for n, line in enumerate(lines[1:], 2):
        if not line:
            continue
        parts = line.split("\t")
        i = index.get(parts[0])
        if i is None:
            raise PlanError(f"{path}:{n}: sample {parts[0]!r} is not in "
                            f"the call set")
        if seen[i]:
            raise PlanError(f"{path}:{n}: sample {parts[0]!r} twice")
        if len(parts) != len(head):
            raise PlanError(f"{path}:{n}: {len(parts) - 1} values for "
                            f"{len(names)} traits")
        try:
            y[i] = np.array(parts[1:], dtype=np.float64)
        except ValueError:
            raise PlanError(f"{path}:{n}: a value of sample {parts[0]!r} "
                            f"is missing or not a number") from None
        seen[i] = True
    if not seen.all():
        absent = [s for s, ok in zip(samples, seen) if not ok]
        raise PlanError(f"{path}: no row for {len(absent)} of the call "
                        f"set's samples (first: {absent[0]!r})")
    if not np.isfinite(y).all():
        i, p = np.argwhere(~np.isfinite(y))[0]
        raise PlanError(f"{path}: trait {names[p]!r} of sample "
                        f"{samples[i]!r} is not finite")
    return names, y


def make_gwas_load_step(n_samples: int):
    """Jitted pass-1 step, built once a process for a sample count: one
    tile group -> written into the donated resident matrix at ``row0``
    (``resident_update``), reduced to allele frequencies (``af``) and
    added into the donated GRM accumulators (``grm``:
    ops/gwas_pallas.py::grm_accumulate says what they hold).

    ``step(resident [cap, Sp] i8, sites [2, cap] i32, acc [Sp, Sp] f32,
    r [Sp] f32, c [] f32, n_grm [] i32, chrom, pos [1, bucket] i32, flags
    [1, bucket] u8, dosage [1, bucket, S_pad] i8, count [1] i32, row0 []
    i32)`` returns the first six, updated."""
    import jax
    import jax.numpy as jnp

    from hadoop_bam_tpu.ops.gwas_pallas import (
        GRM_BLOCK, grm_accumulate, round_up,
    )
    from hadoop_bam_tpu.parallel.pipeline import _STEP_CACHE
    from hadoop_bam_tpu.parallel.variant_pipeline import FLAG_SNP

    key = ("gwas_load", int(n_samples))
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]

    def load(resident, sites, acc, r, c, n_grm, chrom, pos, flags, dosage,
             count, row0):
        d, fl, cnt = dosage[0], flags[0], count[0]
        bucket, s_pad = d.shape
        kp, sp = round_up(bucket, GRM_BLOCK), resident.shape[1]
        with jax.named_scope("resident_update"):
            dp = jnp.pad(d, ((0, kp - bucket), (0, sp - s_pad)),
                         constant_values=-1)
            resident = jax.lax.dynamic_update_slice(
                resident, dp[:bucket], (row0, 0))
            sites = jax.lax.dynamic_update_slice(
                sites, jnp.concatenate([chrom, pos]), (0, row0))
        with jax.named_scope("af"):
            gi = dp.astype(jnp.int32)
            called = gi >= 0
            n_called = called.sum(axis=1)
            alt = jnp.where(called, gi, 0).sum(axis=1)
            snp = (jnp.pad(fl, (0, kp - bucket)) & FLAG_SNP) != 0
            # 0.01 <= alt / (2 n) <= 0.99, in integers: exact at the edge
            in_c = ((jnp.arange(kp, dtype=jnp.int32) < cnt) & snp
                    & (n_called == n_samples)
                    & (100 * alt >= 2 * GWAS_MAF_PERCENT * n_called)
                    & (100 * alt <= 2 * (100 - GWAS_MAF_PERCENT)
                       * n_called))
            p = alt.astype(jnp.float32) \
                / (2 * jnp.maximum(n_called, 1)).astype(jnp.float32)
            m = jnp.where(in_c, 2.0 * p, 0.0)
            w = jnp.where(in_c, 1.0 / (2.0 * p * (1.0 - p)), 0.0)
        with jax.named_scope("grm"):
            acc = grm_accumulate(acc, dp, m, w)
            wm = w * m
            r = r + jnp.dot(wm, dp.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
            c = c + (wm * m).sum()
            n_grm = n_grm + in_c.astype(jnp.int32).sum()
        return resident, sites, acc, r, c, n_grm

    step = named_step("gwas_load_step", load,
                      donate_argnums=(0, 1, 2, 3, 4, 5))
    _STEP_CACHE[key] = step
    return step


def make_gwas_assoc_step(n_samples: int, n_traits: int,
                         with_table: bool = False):
    """Jitted pass-2 step over the resident matrix
    (ops/gwas_pallas.py::assoc_scan): ``step(resident, w3 [3, Sp, Np]
    bf16, isig [Np] f32, n_sites [1] i32)`` -> the per-trait summaries and, when
    ``with_table``, the ``[cap, P]`` chi2 table."""
    from hadoop_bam_tpu.ops.gwas_pallas import assoc_scan
    from hadoop_bam_tpu.parallel.pipeline import _STEP_CACHE

    key = ("gwas_assoc", int(n_samples), int(n_traits), bool(with_table))
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]

    def assoc(resident, w3, isig, n_sites):
        return assoc_scan(resident, w3, isig, n_sites, n_traits=n_traits,
                          n_cov=1 + GWAS_AXES, n_samples=n_samples,
                          with_table=with_table)

    step = named_step("gwas_assoc_step", assoc)
    _STEP_CACHE[key] = step
    return step


@contextlib.contextmanager
def grm_buffer(n_samples: int):
    """Lease the [S, S] float64 buffer kept for a job's A across jobs (50 MB
    at S = 2,504, above the allocator's mmap threshold: a fresh one is
    faulted in page by page, which can cost more than the pass that fills
    it).  A job holds it until ``covariates()`` has
    returned; a new one is minted where S changed or the kept one is out on
    lease, so two jobs in one process never share one.  A job that fails
    inside the lease gives nothing back."""
    global _grm_kept
    s = int(n_samples)
    with _GRM_LOCK:
        buf, _grm_kept = _grm_kept, None
    if buf is not None and buf.shape == (s, s):
        METRICS.count("gwas.grm_buffer_reused")
    else:
        buf = np.empty((s, s))
        METRICS.count("gwas.grm_buffer_minted")
    yield buf
    with _GRM_LOCK:
        _grm_kept = buf


def grm_from_accumulators(acc, r, c: float, n_grm: int, n_samples: int,
                          out: Optional[np.ndarray] = None) -> np.ndarray:
    """A [S, S] float64 from what pass 1 accumulated (host arrays), written
    into ``out`` where given: one native pass (``utils/native.py::
    grm_finish``) where ``native.load()`` gives a library, else the NumPy
    body; bitwise the same either way."""
    if out is None:
        out = np.empty((int(n_samples),) * 2)
    if native.load() is not None:
        METRICS.count("gwas.grm_native_jobs")
        return native.grm_finish(acc, r, c, n_grm, n_samples, out)
    METRICS.count("gwas.grm_numpy_jobs")
    return _grm_from_accumulators_numpy(acc, r, c, n_grm, n_samples, out)


def _grm_from_accumulators_numpy(acc, r, c: float, n_grm: int,
                                 n_samples: int,
                                 out: Optional[np.ndarray] = None
                                 ) -> np.ndarray:
    """``(T^T G - (r - c) 1^T) / |C|`` read from the upper triangle and
    mirrored: the statement of ``hbam_grm_finish``, its oracle and the path
    of a host without the native library."""
    s = int(n_samples)
    upper = np.triu(np.asarray(acc, np.float64)[:s, :s]
                    - (np.asarray(r, np.float64)[:s] - float(c))[:, None])
    return np.divide(upper + np.triu(upper, 1).T, max(int(n_grm), 1),
                     out=out)


def _leading_eigenpairs(a: np.ndarray):
    """``(theta [GWAS_AXES] descending, V [S, GWAS_AXES], block products
    spent)``: the leading eigenpairs of the symmetric float64 ``a`` by
    block subspace iteration with a Rayleigh-Ritz step; ``theta`` and
    ``V`` are None where this method is not the cheaper one for this
    matrix.

    ``X`` is ``[S, EIGH_BLOCK]``, orthonormal, from a fixed-seed generator
    (two jobs on one file return the same bits).  A round is one product
    ``Y = A X``, from which come the Ritz pairs of ``H = X^T Y``, their
    residuals ``Y z_i - theta_i X z_i`` and the next block ``qr(Y)``; it
    stops when ``max_i |A v_i - theta_i v_i|_2 <= EIGH_RESIDUAL theta_1``
    over the leading GWAS_AXES pairs, never on a count.  1e-13 is 10-30
    times the floor float64 leaves such a residual at (3e-15 to 1e-14 at
    S = 2,504) and puts the span within ``1e-13 theta_1 / (theta_4 -
    theta_5)`` of the full solve's: 1.5e-13 on a kgp3 cohort, whose
    readings against the reference (1e-8: the float32 accumulation of A)
    it cannot move.

    A round contracts the error by ``lambda_(EIGH_BLOCK + 1) / lambda_4``:
    0.022 on a kgp3 chromosome (10 products, the last residual 5e-15),
    0.21 on a 4,096-site file (20-21 products).  On that chromosome's A
    every block from 4 to 32 took 10 or 11 products, 10-23 ms on the chip
    host up to 16 columns and 60-72 ms from 24 (PERF.md, PR 33); 16 is the
    widest under that step, and the wider block contracts faster where
    the tail decays.  The products are capped by the shape at ``S // (2
    EIGH_BLOCK)``, which costs a fifth to two fifths of LAPACK's full
    ``eigh`` of the same matrix (two hosts, S = 128 to 2,504; 78 products
    at 2,504).  Past the cap the gap after the fourth eigenvalue is too
    small for this method (a ratio over 0.68) and the caller pays the full
    solve; where the cap is under EIGH_MIN_PRODUCTS (S < 384: not even the
    easiest spectrum would pass it, and the full solve is under 20 ms)
    nothing is tried."""
    s = a.shape[0]
    cap = s // (2 * EIGH_BLOCK)
    if cap < EIGH_MIN_PRODUCTS:
        return None, None, 0
    x = np.linalg.qr(np.random.default_rng(0)
                     .standard_normal((s, EIGH_BLOCK)))[0]
    for products in range(1, cap + 1):
        y = a @ x
        h = x.T @ y
        theta, z = np.linalg.eigh((h + h.T) / 2)
        theta, z = theta[::-1][:GWAS_AXES], z[:, ::-1][:, :GWAS_AXES]
        v = x @ z
        r = y @ z - v * theta
        if np.linalg.norm(r, axis=0).max() <= EIGH_RESIDUAL * abs(theta[0]):
            return theta, v, products
        x = np.linalg.qr(y)[0]
    return None, None, cap


def covariates(a: np.ndarray):
    """(the GWAS_AXES leading eigenvalues descending, Q [S, 1 +
    GWAS_AXES]) of the GRM: the intercept and the leading eigenvectors,
    orthonormalised.  Only those pairs are computed where the iteration
    converges (``_leading_eigenpairs``); a small cohort, or one whose
    fourth axis does not stand clear of the rest, gets them from LAPACK's
    full float64 ``eigh`` of the same matrix."""
    w, v, products = _leading_eigenpairs(a)
    METRICS.count("gwas.eigh_products", products)
    if w is None:
        w, v = np.linalg.eigh(a)
        w, v = w[::-1][:GWAS_AXES], v[:, ::-1][:, :GWAS_AXES]
        METRICS.count("gwas.eigh_full_jobs")
    else:
        METRICS.count("gwas.eigh_topk_jobs")
    x = np.concatenate([np.ones((a.shape[0], 1)), v], axis=1)
    return w, np.linalg.qr(x)[0]


def variant_gwas_file(path: str, traits: str, return_table: bool = False,
                      mesh=None, config: HBamConfig = DEFAULT_CONFIG,
                      geometry=None, spans=None) -> Dict[str, object]:
    """Structure-adjusted association of every site of a cohort VCF/BCF
    against every trait of the TSV ``traits``: one read of the file into a
    device-resident int8 matrix, the GRM and its leading eigenvectors as
    covariates, then the score test from the matrix (the comment above
    has the formulas).  Returns ``n_sites``, ``n_grm_sites``,
    ``eigenvalues`` (the GWAS_AXES leading, descending), ``q`` [S, 5],
    ``traits`` (names) and per trait ``tested`` (one count: traits share
    their missingness), ``mean_chi2``, ``max_chi2``, ``max_pos``,
    ``genome_wide`` (chi2 over 29.72, p < 5e-8); with ``return_table``
    also ``pos`` [M] and ``chi2`` [M, P] float32 in file order.  A thin
    plan builder over the one executor."""
    from hadoop_bam_tpu.plan import builders
    from hadoop_bam_tpu.plan import executor as plan_executor

    plan = builders.variant_gwas_plan(path, traits, config)
    return plan_executor.execute(plan, config=config, mesh=mesh,
                                 geometry=geometry, spans=spans,
                                 return_table=return_table)


def format_gwas(res: Dict[str, object]):
    """The lines ``hbam vcf-gwas`` prints."""
    lines = [f"sites\t{res['n_sites']}",
             f"grm_sites\t{res['n_grm_sites']}",
             f"traits\t{len(res['traits'])}"]
    lines += [f"eigenvalue_{k + 1}\t{res['eigenvalues'][k]:.9g}"
              for k in range(GWAS_AXES)]
    lines.append("trait\ttested\tmean_chi2\tmax_chi2\tmax_pos\tgenome_wide")
    for t, name in enumerate(res["traits"]):
        lines.append(f"{name}\t{res['tested']}\t{res['mean_chi2'][t]:.9g}\t"
                     f"{res['max_chi2'][t]:.9g}\t{res['max_pos'][t]}\t"
                     f"{res['genome_wide'][t]}")
    return lines
