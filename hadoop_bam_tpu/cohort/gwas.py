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

from typing import Dict, Optional

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
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
