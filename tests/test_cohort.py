"""Cohort variant plane (hadoop_bam_tpu/cohort/).

The load-bearing pins:

- **Oracle join identity**: the streaming k-way merge + harmonize +
  FeedPipeline tiling is VALUE-IDENTICAL to an independent serial
  per-site Python oracle (dict-of-sites, written from the harmonization
  spec, sharing no code with the join) across randomized
  k / missingness / multi-allelic / duplicate / swap fixtures and
  mixed containers (text VCF, BGZF VCF, BCF).
- **Harmonization edge cases**: multi-allelic split/merge, REF/ALT
  swap, allele reorder, duplicate positions within one input,
  inconsistent REF shapes -> sentinel.
- **Sentinel propagation**: rows beyond each shard's n_records carry
  -1 dosage / NaN qual through ``tensor_batches``.
- **GWAS parity**: the shard_map drivers match NumPy reference
  implementations of af / call rate / HWE chi2 / score chi2 to float32
  tolerance.
- **Per-input fault domains**: a corrupt sample under chaos
  quarantines (sentinel column + manifest entry + fed breaker) without
  failing the build; the fraction circuit and the quarantine=off path
  raise.
- **Cohort-slice serving**: warm slices are answered entirely from
  device-resident tiles (zero host decode in an isolated
  MetricsContext), wire round-trip included.
"""
import dataclasses
import json
import math
import os
import random

import numpy as np
import pytest

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.cohort import (
    CohortDataset, CohortManifest, as_manifest, cohort_gwas, load_manifest,
)
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.errors import CorruptDataError, PlanError

pytestmark = pytest.mark.cohort

_HDR = ("##fileformat=VCFv4.2\n"
        "##contig=<ID=chr20,length=64444167>\n"
        "##contig=<ID=chr21,length=46709983>\n"
        '##FILTER=<ID=q10,Description="low">\n'
        '##FORMAT=<ID=GT,Number=1,Type=String,Description="Genotype">\n'
        '##FORMAT=<ID=DP,Number=1,Type=Integer,Description="Depth">\n')


def _write_sample(path, sample_id, lines):
    """One single-sample VCF in the container the extension names."""
    text = (_HDR + "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\t"
            f"FORMAT\t{sample_id}\n" + "".join(l + "\n" for l in lines))
    if path.endswith(".vcf"):
        with open(path, "w") as f:
            f.write(text)
        return path
    from hadoop_bam_tpu.api.writers import open_vcf_writer
    from hadoop_bam_tpu.formats.vcf import VCFHeader, VcfRecord

    header = VCFHeader.from_text(_HDR + "#CHROM\tPOS\tID\tREF\tALT\tQUAL\t"
                                 f"FILTER\tINFO\tFORMAT\t{sample_id}\n")
    with open_vcf_writer(path, header) as w:
        for l in lines:
            w.write_record(VcfRecord.from_line(l))
    return path


def _manifest(tmp_path, files, ids=None):
    man = {"samples": [
        {"id": ids[i] if ids else f"s{i}", "path": str(p)}
        for i, p in enumerate(files)]}
    mp = tmp_path / "cohort.json"
    mp.write_text(json.dumps(man))
    return str(mp)


# ---------------------------------------------------------------------------
# the independent serial per-site oracle
# ---------------------------------------------------------------------------

def _oracle_join(paths, config=DEFAULT_CONFIG):
    """Dict-of-sites reference join: read every record of every sample,
    bucket by (contig, pos), harmonize per the spec (README "Cohort
    analysis"), emit sorted columns.  Shares no code with
    cohort/join.py or cohort/harmonize.py."""
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf

    datasets = [open_vcf(p, config) for p in paths]
    contigs = []
    for ds in datasets:
        for c in ds.header.contigs:
            if c not in contigs:
                contigs.append(c)
    cidx = {c: i for i, c in enumerate(contigs)}
    k = len(paths)
    sites = {}                       # (ci, pos) -> {si: [rec, ...]}
    for si, ds in enumerate(datasets):
        for rec in ds.records():
            sites.setdefault((cidx[rec.chrom], rec.pos), {}) \
                .setdefault(si, []).append(rec)
    rows = []
    for key in sorted(sites):
        per = sites[key]
        chosen = {si: recs[0] for si, recs in per.items()}  # dup: first
        order = sorted(chosen)
        refs = [chosen[si].ref for si in order]
        ref = max(set(refs), key=lambda r: (refs.count(r), -refs.index(r)))
        alts = []
        for si in order:
            r = chosen[si]
            if r.ref == ref:
                for a in r.alts:
                    if a != ref and a not in alts:
                        alts.append(a)
        canon = {ref: 0, **{a: j + 1 for j, a in enumerate(alts)}}
        dosage = np.full(k, -1, np.int8)
        qual = np.full(k, np.nan, np.float32)
        for si in order:
            r = chosen[si]
            if r.qual is not None:
                qual[si] = np.float32(r.qual)
            if not r.fmt or r.fmt[0] != "GT" or not r.genotypes:
                continue
            gt = r.genotypes[0].split(":", 1)[0]
            if not gt:
                continue
            if r.ref != ref and r.ref not in canon:
                continue             # incompatible shape: sentinel
            local = (r.ref,) + tuple(r.alts)
            dose, ok = 0, True
            for a in gt.replace("|", "/").split("/"):
                if not a.isdigit() or int(a) >= len(local):
                    ok = False
                    break
                c = canon.get(local[int(a)])
                if c is None:
                    ok = False
                    break
                dose += 1 if c != 0 else 0
            if ok:
                dosage[si] = min(dose, 127)
        rows.append((key[0], key[1], 1 + len(alts), dosage, qual))
    return contigs, rows


def _collect_batches(ds, mesh=None):
    """Drain tensor_batches into trimmed host columns (the join's
    public value surface)."""
    chrom, pos, nall, dosage, qual = [], [], [], [], []
    for out in ds.tensor_batches(mesh=mesh):
        counts = np.asarray(out["n_records"])
        h = {kk: np.asarray(out[kk]) for kk in
             ("chrom", "pos", "n_allele", "dosage", "qual")}
        for dev in range(counts.shape[0]):
            c = int(counts[dev])
            if c:
                chrom.append(h["chrom"][dev, :c])
                pos.append(h["pos"][dev, :c])
                nall.append(h["n_allele"][dev, :c])
                dosage.append(h["dosage"][dev, :c])
                qual.append(h["qual"][dev, :c])
    if not chrom:
        return None
    return {
        "chrom": np.concatenate(chrom), "pos": np.concatenate(pos),
        "n_allele": np.concatenate(nall),
        "dosage": np.concatenate(dosage), "qual": np.concatenate(qual),
    }


def _assert_join_matches_oracle(paths, config=DEFAULT_CONFIG):
    contigs, rows = _oracle_join(paths, config)
    ds = CohortDataset(list(paths), config)
    assert ds.contigs == contigs
    got = _collect_batches(ds)
    k = len(paths)
    if got is None:
        assert rows == []
        return ds
    assert got["chrom"].tolist() == [r[0] for r in rows]
    assert got["pos"].tolist() == [r[1] for r in rows]
    assert got["n_allele"].tolist() == [r[2] for r in rows]
    want_d = np.stack([r[3] for r in rows])
    want_q = np.stack([r[4] for r in rows])
    np.testing.assert_array_equal(got["dosage"][:, :k], want_d)
    np.testing.assert_array_equal(np.isnan(got["qual"][:, :k]),
                                  np.isnan(want_q))
    np.testing.assert_allclose(
        np.nan_to_num(got["qual"][:, :k]), np.nan_to_num(want_q),
        rtol=1e-6)
    return ds


# ---------------------------------------------------------------------------
# randomized oracle identity
# ---------------------------------------------------------------------------

def _random_sample_lines(rng, n_sites=40):
    """One sample's sorted lines over a shared position grid with
    missingness, multi-allelic records, swaps, duplicates, polyploid
    and missing genotypes."""
    lines = []
    for chrom in ("chr20", "chr21"):
        pos = 0
        for _ in range(n_sites):
            pos += rng.randint(1, 25)
            if rng.random() < 0.35:
                continue                      # this sample skips the site
            ref = rng.choice("ACGT")
            n_alt = rng.choice([1, 1, 1, 2, 3])
            alts = rng.sample([c for c in "ACGT" if c != ref], n_alt)
            if rng.random() < 0.1:            # REF/ALT swap shape
                ref, alts[0] = alts[0], ref
            gt = rng.choice(["0/0", "0/1", "1/1", "./.", "1|0", ".",
                             "0/1/1", "2/1" if n_alt >= 2 else "0/1"])
            qual = rng.choice([".", str(rng.randint(1, 99)),
                               f"{rng.random() * 50:.2f}"])
            dp = rng.randint(1, 40)
            dup = 2 if rng.random() < 0.06 else 1
            for _d in range(dup):
                lines.append(f"{chrom}\t{pos}\t.\t{ref}\t"
                             f"{','.join(alts)}\t{qual}\tPASS\t.\t"
                             f"GT:DP\t{gt}:{dp}")
    return lines


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_join_matches_oracle_randomized(tmp_path, seed):
    rng = random.Random(seed)
    k = rng.randint(2, 6)
    exts = [".vcf", ".vcf.gz", ".bcf"]
    paths = []
    for s in range(k):
        ext = exts[s % len(exts)]
        paths.append(_write_sample(str(tmp_path / f"s{s}{ext}"), f"s{s}",
                                   _random_sample_lines(rng)))
    _assert_join_matches_oracle(paths)


def test_join_across_mixed_containers_small(tmp_path):
    """A tiny hand-checked cohort across all three containers."""
    p0 = _write_sample(str(tmp_path / "a.vcf"), "a", [
        "chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t0/1",
        "chr21\t5\t.\tC\tT\t7\tPASS\t.\tGT\t1/1",
    ])
    p1 = _write_sample(str(tmp_path / "b.vcf.gz"), "b", [
        "chr20\t100\t.\tA\tT\t11\tPASS\t.\tGT\t1/1",
    ])
    p2 = _write_sample(str(tmp_path / "c.bcf"), "c", [
        "chr20\t100\t.\tA\tG\t22\tPASS\t.\tGT\t1/1",
        "chr21\t5\t.\tC\tT\t9\tPASS\t.\tGT\t0/1",
    ])
    ds = _assert_join_matches_oracle([p0, p1, p2])
    got = _collect_batches(ds)
    # chr20:100 joins A->[G, T]: multi-allelic union in sample order
    assert got["n_allele"].tolist() == [3, 2]
    np.testing.assert_array_equal(got["dosage"][0, :3], [1, 2, 2])


# ---------------------------------------------------------------------------
# harmonization edge cases (explicit)
# ---------------------------------------------------------------------------

def _join_two(tmp_path, lines_a, lines_b, config=DEFAULT_CONFIG):
    pa = _write_sample(str(tmp_path / "ha.vcf"), "ha", lines_a)
    pb = _write_sample(str(tmp_path / "hb.vcf"), "hb", lines_b)
    ds = CohortDataset([pa, pb], config)
    return ds, _collect_batches(ds)


def test_harmonize_ref_alt_swap(tmp_path):
    """One caller normalized the other way: its hom-ref is dosage 2
    against the canonical orientation."""
    ds, got = _join_two(
        tmp_path,
        ["chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t0/1"],
        ["chr20\t100\t.\tG\tA\t30\tPASS\t.\tGT\t0/0"])
    assert got["n_allele"].tolist() == [2]
    # sample b's REF G maps to canonical ALT G: 0/0 -> two G alleles ->
    # dosage 2
    np.testing.assert_array_equal(got["dosage"][0, :2], [1, 2])


def test_harmonize_multiallelic_split_and_reorder(tmp_path):
    """Split multi-allelics merge into one allele set; ALT order
    differences map by string, not by index."""
    ds, got = _join_two(
        tmp_path,
        ["chr20\t100\t.\tA\tG,T\t30\tPASS\t.\tGT\t1/2"],
        ["chr20\t100\t.\tA\tT,G\t30\tPASS\t.\tGT\t1/1"])
    assert got["n_allele"].tolist() == [3]      # A -> [G, T]
    # b's "1" is T (its own ALT order) -> canonical non-ref: dosage 2
    np.testing.assert_array_equal(got["dosage"][0, :2], [2, 2])


def test_harmonize_duplicate_positions_first_wins(tmp_path):
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    with MetricsContext() as m:
        ds, got = _join_two(
            tmp_path,
            ["chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t1/1",
             "chr20\t100\t.\tA\tG\t99\tPASS\t.\tGT\t0/0"],  # dup: ignored
            ["chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t0/1"])
    np.testing.assert_array_equal(got["dosage"][0, :2], [2, 1])
    assert got["qual"][0, 0] == np.float32(30)
    assert m.snapshot()["counters"].get("cohort.duplicate_sites") == 1


def test_harmonize_inconsistent_ref_goes_sentinel(tmp_path):
    """An indel REF overlapping a SNP site cannot map: that sample's
    call is missing, and no fabricated allele appears."""
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    with MetricsContext() as m:
        ds, got = _join_two(
            tmp_path,
            ["chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t0/1"],
            ["chr20\t100\t.\tAT\tA\t30\tPASS\t.\tGT\t1/1"])
    assert got["n_allele"].tolist() == [2]      # A -> [G] only
    np.testing.assert_array_equal(got["dosage"][0, :2], [1, -1])
    assert m.snapshot()["counters"].get("cohort.harmonize_dropped") == 1


def test_harmonize_missing_and_polyploid(tmp_path):
    ds, got = _join_two(
        tmp_path,
        ["chr20\t100\t.\tA\tG\t.\tPASS\t.\tGT\t./.",
         "chr20\t200\t.\tC\tT\t5\tPASS\t.\tGT\t0/1/1"],   # triploid
        ["chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t1/1"])
    np.testing.assert_array_equal(got["dosage"][0, :2], [-1, 2])
    assert np.isnan(got["qual"][0, 0])          # '.' QUAL -> NaN
    np.testing.assert_array_equal(got["dosage"][1, :2], [2, -1])


def test_abandoned_join_restarts_from_file_start(tmp_path):
    """An abandoned iteration (early tensor_batches break, a tripped
    circuit) must not make the NEXT join silently resume mid-file
    (reviewed: VcfDataset.records() only auto-resets after full
    exhaustion)."""
    rng = random.Random(31)
    paths = [_write_sample(str(tmp_path / f"r{s}.vcf"), f"r{s}",
                           _random_sample_lines(rng, n_sites=30))
             for s in range(2)]
    cfg = dataclasses.replace(DEFAULT_CONFIG, cohort_chunk_sites=4)
    ds = CohortDataset(paths, cfg)
    full = _collect_batches(CohortDataset(paths, cfg))
    # abandon a site_chunks iteration mid-stream...
    it = ds.site_chunks()
    next(it)
    it.close()
    # ...then both the host surface and the GWAS driver still cover
    # the whole cohort
    got = _collect_batches(ds)
    np.testing.assert_array_equal(got["pos"], full["pos"])
    assert ds.gwas()["n_variants"] == full["pos"].shape[0]


def test_sentinel_propagation_through_tensor_batches(tmp_path):
    """Rows past each shard's n_records carry -1 dosage / NaN qual —
    the PR-4 sentinel convention, on every shard including empty
    ones."""
    p = _write_sample(str(tmp_path / "one.vcf"), "one", [
        "chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t0/1"])
    ds = CohortDataset([p])
    outs = list(ds.tensor_batches())
    assert len(outs) == 1
    out = outs[0]
    counts = np.asarray(out["n_records"])
    dosage = np.asarray(out["dosage"])
    qual = np.asarray(out["qual"])
    assert counts.sum() == 1
    for dev in range(counts.shape[0]):
        c = int(counts[dev])
        assert (dosage[dev, c:] == -1).all()
        assert np.isnan(qual[dev, c:]).all()


# ---------------------------------------------------------------------------
# GWAS drivers vs NumPy references
# ---------------------------------------------------------------------------

def _np_gwas_reference(dosage, n_samples, pheno=None):
    """Independent float64 NumPy implementations of the driver
    formulas (cohort/gwas.py docstring)."""
    d = dosage[:, :n_samples].astype(np.int64)
    called = d >= 0
    n_called = called.sum(axis=1)
    alt = np.where(called, d, 0).sum(axis=1)
    with np.errstate(invalid="ignore", divide="ignore"):
        af = np.where(n_called > 0, alt / (2.0 * np.maximum(n_called, 1)),
                      np.nan)
        call_rate = n_called / n_samples
        n0 = ((d == 0) & called).sum(axis=1).astype(float)
        n1 = ((d == 1) & called).sum(axis=1).astype(float)
        n2 = ((d == 2) & called).sum(axis=1).astype(float)
        m = n0 + n1 + n2
        p = np.where(m > 0, (2 * n2 + n1) / (2 * np.maximum(m, 1)), 0.0)
        hwe = np.full(d.shape[0], np.nan)
        for i in range(d.shape[0]):
            if m[i] <= 0:
                continue
            chi = 0.0
            for obs, exp in (
                    (n0[i], (1 - p[i]) ** 2 * m[i]),
                    (n1[i], 2 * p[i] * (1 - p[i]) * m[i]),
                    (n2[i], p[i] ** 2 * m[i])):
                if exp > 0:
                    chi += (obs - exp) ** 2 / exp
            hwe[i] = chi
        score = np.full(d.shape[0], np.nan)
        if pheno is not None:
            y = np.asarray(pheno, float)
            for i in range(d.shape[0]):
                use = called[i] & np.isfinite(y)
                n = use.sum()
                if n <= 1:
                    continue
                yi, gi = y[use], d[i, use].astype(float)
                u = ((yi - yi.mean()) * (gi - gi.mean())).sum()
                vg = ((gi - gi.mean()) ** 2).sum()
                vy = ((yi - yi.mean()) ** 2).sum() / n
                if vy * vg > 1e-12:
                    score[i] = u * u / (vy * vg)
    return {"af": af, "call_rate": call_rate, "hwe_chi2": hwe,
            "score_chi2": score}


def test_gwas_matches_numpy_reference(tmp_path):
    rng = random.Random(11)
    k = 5
    paths = [_write_sample(str(tmp_path / f"g{s}.vcf"), f"g{s}",
                           _random_sample_lines(rng, n_sites=30))
             for s in range(k)]
    ds = CohortDataset(paths)
    pheno = np.asarray([0.2, 1.5, float("nan"), -0.7, 0.9], np.float32)
    res = ds.gwas(phenotype=pheno)
    got = _collect_batches(CohortDataset(paths))
    ref = _np_gwas_reference(got["dosage"], k, pheno)
    assert res["n_variants"] == got["dosage"].shape[0] > 0
    for col in ("af", "call_rate", "hwe_chi2", "score_chi2"):
        np.testing.assert_allclose(res[col], ref[col], rtol=2e-4,
                                   atol=2e-4, equal_nan=True,
                                   err_msg=col)


# -- the structure-adjusted, many-trait form (hbam vcf-gwas): its host half;
#    the job itself is tests/test_kgp3_gwas.py's ------------------------------

def _grm_inputs(seed, n_sites, n_samples):
    rng = np.random.default_rng(seed)
    p = rng.uniform(0.05, 0.95, n_sites)
    g = rng.binomial(2, p[:, None], (n_sites, n_samples)).astype(np.int8)
    g[:, 0] = 0
    g[:, 1] = 2                         # no monomorphic site
    return g


needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native library unavailable")
# grm_from_accumulators' two paths: the native pass, and the NumPy body a
# host without the library runs
GRM_PATHS = [pytest.param("native", marks=needs_native), "numpy"]


@pytest.mark.parametrize("path", GRM_PATHS)
@pytest.mark.parametrize("n_sites,n_samples,pad", [
    (40, 12, 4), (300, 31, 1), (64, 50, 14)])
def test_grm_from_accumulators_is_gctas_a(n_sites, n_samples, pad, path,
                                          monkeypatch):
    """What pass 1 accumulates — T^T G on and above the diagonal, the
    vector r, the scalar c — mirrors back into A = Z^T Z / |C|, by either
    path, which counts itself once a call."""
    from hadoop_bam_tpu.cohort.gwas import grm_from_accumulators
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    if path == "numpy":
        monkeypatch.setattr(native, "load", lambda: None)
    g = _grm_inputs(n_sites, n_sites, n_samples).astype(np.float64)
    p = g.sum(axis=1) / (2 * n_samples)
    z = (g - 2 * p[:, None]) / np.sqrt(2 * p * (1 - p))[:, None]
    want = z.T @ z / n_sites
    m, w = 2 * p, 1 / (2 * p * (1 - p))
    sp = n_samples + pad
    gp = np.full((n_sites, sp), -1.0)
    gp[:, :n_samples] = g
    t = (gp - m[:, None]) * w[:, None]
    acc = np.triu(t.T @ gp) + np.tril(np.full((sp, sp), 7.0), -1)
    r, c = (w * m) @ gp, float((w * m * m).sum())
    with MetricsContext() as mc:
        got = grm_from_accumulators(acc.astype(np.float32),
                                    r.astype(np.float32), c, n_sites,
                                    n_samples)
    counters = mc.snapshot()["counters"]
    assert counters == {f"gwas.grm_{path}_jobs": 1}
    assert got.shape == (n_samples, n_samples)
    assert np.array_equal(got, got.T)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def _accumulators(seed, s, sp):
    """Float32 accumulators at the magnitudes of a kgp3 job's, with a zero
    of either sign left at row 0 (r_0 = c): the mirror must make both +0."""
    rng = np.random.default_rng(seed)
    acc = (rng.standard_normal((sp, sp)) * 5e4).astype(np.float32)
    r = (rng.standard_normal(sp) * 5e4).astype(np.float32)
    c = float(r[0]) if sp else 0.0
    if s >= 3:
        acc[0, :3] = [-0.0, 0.0, -0.0]
    return acc, r, c


@needs_native
@pytest.mark.parametrize("s,sp,n_grm", [
    (2504, 2560, 52_429),       # the kgp3 cohort, lane-padded
    (131, 256, 17),             # not a multiple of the 64-row tile
    (64, 64, 3), (65, 128, 1), (1, 128, 9), (0, 128, 4),
    (7, 7, 0)])                 # no site in C: divided by 1, as NumPy
def test_native_grm_finish_is_the_numpy_body_bit_for_bit(s, sp, n_grm):
    from hadoop_bam_tpu.cohort.gwas import _grm_from_accumulators_numpy

    acc, r, c = _accumulators(s + sp, s, sp)
    want = _grm_from_accumulators_numpy(acc, r, c, n_grm, s)
    out = np.full((s, s), np.nan)
    got = native.grm_finish(acc, r, c, n_grm, s, out)
    assert got is out and np.array_equal(got, want)
    # signed zeros too: the same bits, element for element
    assert np.array_equal(got.view(np.uint64), want.view(np.uint64))


@needs_native
def test_native_grm_finish_refuses_shapes_it_cannot_write():
    acc, r, c = _accumulators(3, 40, 64)
    for s, out in ((65, np.empty((65, 65))),            # S > Sp
                   (40, np.empty((40, 40), np.float32)),
                   (40, np.empty((40, 41))),
                   (40, np.empty((40, 80))[:, ::2])):
        with pytest.raises(ValueError):
            native.grm_finish(acc, r, c, 5, s, out)


def _grm_counters(mc):
    c = mc.snapshot()["counters"]
    return c.get("gwas.grm_buffer_reused", 0), \
        c.get("gwas.grm_buffer_minted", 0)


@pytest.mark.parametrize("path", GRM_PATHS)
def test_the_grm_buffer_is_kept_across_jobs_and_minted_per_s(path,
                                                              monkeypatch):
    from hadoop_bam_tpu.cohort import gwas
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    if path == "numpy":
        monkeypatch.setattr(native, "load", lambda: None)
    monkeypatch.setattr(gwas, "_grm_kept", None)
    acc, r, c = _accumulators(11, 40, 64)
    want = gwas._grm_from_accumulators_numpy(acc, r, c, 6, 40)
    with MetricsContext() as mc:
        with gwas.grm_buffer(40) as first:
            a = gwas.grm_from_accumulators(acc, r, c, 6, 40, out=first)
            assert a is first and np.array_equal(a, want)
        with gwas.grm_buffer(40) as second:      # the next job, same S
            a = gwas.grm_from_accumulators(acc, r, c, 6, 40, out=second)
            assert a is first and np.array_equal(a, want)
        assert _grm_counters(mc) == (1, 1)
        with gwas.grm_buffer(30) as other:       # another S: a new one
            assert other.shape == (30, 30)
        assert _grm_counters(mc) == (1, 2)
        with gwas.grm_buffer(40) as again:       # and the S=30 one is kept
            assert again is not first and again.shape == (40, 40)
        assert _grm_counters(mc) == (1, 3)


def test_a_grm_on_lease_is_not_overwritten_by_a_second_job(monkeypatch):
    from hadoop_bam_tpu.cohort import gwas
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    monkeypatch.setattr(gwas, "_grm_kept", None)
    acc, r, c = _accumulators(12, 40, 64)
    acc2, r2, c2 = _accumulators(13, 40, 64)
    with MetricsContext() as mc:
        with gwas.grm_buffer(40) as held:
            a = gwas.grm_from_accumulators(acc, r, c, 6, 40, out=held)
            kept = a.copy()
            # a second job while the first still holds its A
            with gwas.grm_buffer(40) as mine:
                assert mine is not held
                gwas.grm_from_accumulators(acc2, r2, c2, 9, 40, out=mine)
            assert np.array_equal(a, kept)
        assert _grm_counters(mc) == (0, 2)
        # a job that fails inside its lease gives nothing back
        with pytest.raises(PlanError):
            with gwas.grm_buffer(40) as lost:
                raise PlanError("no site passes the GRM's filter")
        with gwas.grm_buffer(40) as next_one:
            assert next_one is not lost
        assert _grm_counters(mc) == (1, 3)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_covariates_are_the_intercept_and_the_leading_axes(seed):
    from hadoop_bam_tpu.cohort.gwas import GWAS_AXES, covariates

    rng = np.random.default_rng(seed)
    s = 60
    pop = rng.integers(0, 5, s)
    x = np.eye(5)[pop] * 3 + rng.standard_normal((s, 5)) * 0.1
    x -= x.mean(axis=0)
    a = x @ x.T / 5 + np.eye(s) * 0.01
    w, q = covariates(a)
    assert q.shape == (s, 1 + GWAS_AXES) and (np.diff(w) <= 0).all()
    np.testing.assert_allclose(q.T @ q, np.eye(1 + GWAS_AXES), atol=1e-12)
    # the intercept and the top eigenvectors lie in Q's span
    wv, v = np.linalg.eigh(a)
    for col in [np.ones(s) / np.sqrt(s)] + [v[:, -k]
                                            for k in range(1, GWAS_AXES + 1)]:
        np.testing.assert_allclose(q @ (q.T @ col), col, atol=1e-9)
    np.testing.assert_allclose(w[:GWAS_AXES], wv[::-1][:GWAS_AXES])


def _grm_like(s, lead, seed):
    """A symmetric [s, s] float64 shaped like a cohort's GRM: five unequal
    groups make four axes with the eigenvalues ``lead``, over a Wishart
    tail topping at 1.9; rounded through float32, as the accumulators
    are."""
    rng = np.random.default_rng(seed)
    pop = np.searchsorted(np.cumsum([0.26, 0.14, 0.20, 0.19]),
                          np.arange(s) / s, side="right")
    u = np.eye(5)[pop][:, :4] + 0.01 * rng.standard_normal((s, 4))
    u = np.linalg.qr(u - u.mean(axis=0))[0]
    z = rng.standard_normal((s, s // 2))
    tail = z @ z.T
    a = (u * np.asarray(lead, np.float64)) @ u.T \
        + tail * (1.9 / np.linalg.eigvalsh(tail)[-1])
    a = a.astype(np.float32).astype(np.float64)
    return (a + a.T) / 2


def _with_spectrum(s, spectrum, seed):
    """A symmetric [s, s] float64 with exactly this spectrum, in a seeded
    orthogonal basis."""
    v = np.linalg.qr(np.random.default_rng(seed)
                     .standard_normal((s, s)))[0]
    a = (v * np.asarray(spectrum, np.float64)) @ v.T
    return (a + a.T) / 2


SOLVER_CASES = {
    # name: (matrix, the path it takes)
    "a GRM-shaped matrix": (
        lambda: _grm_like(640, (60, 45, 30, 20), 11), "topk"),
    "the cell's near-degenerate pair": (
        lambda: _grm_like(512, (103, 89, 88, 72), 12), "topk"),
    "an exactly repeated leading eigenvalue": (
        lambda: _with_spectrum(448, [100, 100, 80, 60]
                               + list(np.linspace(2, 0.1, 444)), 13),
        "topk"),
    "no gap after the fourth": (
        lambda: _with_spectrum(416, np.linspace(2, 1, 416), 14), "full"),
    "a small cohort": (
        lambda: _grm_like(60, (60, 45, 30, 20), 15), "full"),
}


@pytest.mark.parametrize("case", SOLVER_CASES)
def test_covariates_solver_equals_the_full_solve(case):
    """Every case against ``np.linalg.eigh`` of the same matrix; which
    path delivered is what the counters say; two calls give the same
    bits."""
    from hadoop_bam_tpu.cohort.gwas import (
        EIGH_BLOCK, EIGH_MIN_PRODUCTS, GWAS_AXES, covariates,
    )
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    build, path = SOLVER_CASES[case]
    a = build()
    s = a.shape[0]
    with MetricsContext() as m:
        w, q = covariates(a)
    w2, q2 = covariates(a)
    assert np.array_equal(q, q2) and np.array_equal(w, w2)
    assert w.shape == (GWAS_AXES,) and q.shape == (s, 1 + GWAS_AXES)
    wf, vf = np.linalg.eigh(a)
    np.testing.assert_allclose(w, wf[::-1][:GWAS_AXES], rtol=1e-12)
    assert np.abs(q.T @ q - np.eye(1 + GWAS_AXES)).max() < 1e-12
    qf = np.linalg.qr(np.concatenate(
        [np.ones((s, 1)), vf[:, ::-1][:, :GWAS_AXES]], axis=1))[0]
    assert np.abs(q @ q.T - qf @ qf.T).max() < 1e-12
    cap = s // (2 * EIGH_BLOCK)
    products = m.get("gwas.eigh_products")
    if path == "topk":
        assert (m.get("gwas.eigh_topk_jobs"),
                m.get("gwas.eigh_full_jobs")) == (1, 0)
        assert EIGH_MIN_PRODUCTS <= cap and 0 < products < cap
    else:
        assert (m.get("gwas.eigh_topk_jobs"),
                m.get("gwas.eigh_full_jobs")) == (0, 1)
        # the shape rule tries nothing; the fallback paid the cap once
        assert products == (cap if cap >= EIGH_MIN_PRODUCTS else 0)
        assert np.array_equal(q, qf) and np.array_equal(
            w, wf[::-1][:GWAS_AXES])


@pytest.mark.parametrize("body,message", [
    ("sample\tA\tB\ns1\t1\t2\ns2\t3\t4\n", None),
    ("sample\tA\tB\ns2\t3\t4\n\ns1\t1\t2\n", None),
    ("sample\tA\tB\ns1\t1\t\ns2\t3\t4\n", "missing or not a number"),
    ("sample\tA\tB\ns1\t1\tnan\ns2\t3\t4\n", "not finite"),
    ("sample\tA\tB\ns1\t1\t2\ns3\t3\t4\n", "not in the call set"),
    ("sample\tA\tB\ns1\t1\t2\n", "no row for 1"),
    ("sample\tA\tB\ns1\t1\t2\ns1\t3\t4\n", "twice"),
    ("sample\tA\tB\ns1\t1\ns2\t3\t4\n", "1 values for 2"),
    ("id\tA\tB\ns1\t1\t2\ns2\t3\t4\n", "header line"),
    ("", "header line"),
])
def test_read_traits_tsv(tmp_path, body, message):
    from hadoop_bam_tpu.cohort.gwas import read_traits_tsv

    path = tmp_path / "traits.tsv"
    path.write_text(body)
    if message is None:
        names, y = read_traits_tsv(str(path), ["s1", "s2"])
        assert names == ["A", "B"]
        assert np.array_equal(y, [[1.0, 2.0], [3.0, 4.0]])
    else:
        with pytest.raises(PlanError, match=message):
            read_traits_tsv(str(path), ["s1", "s2"])


def test_gwas_without_phenotype_and_bad_phenotype(tmp_path):
    p = _write_sample(str(tmp_path / "p.vcf"), "p", [
        "chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t0/1"])
    ds = CohortDataset([p])
    res = ds.gwas()
    assert np.isnan(res["score_chi2"]).all()
    with pytest.raises(PlanError):
        ds.gwas(phenotype=np.zeros(3, np.float32))


# ---------------------------------------------------------------------------
# manifests
# ---------------------------------------------------------------------------

def test_manifest_forms_and_plan_errors(tmp_path):
    p = _write_sample(str(tmp_path / "m.vcf"), "m", [])
    mp = tmp_path / "man.json"
    # relative paths resolve against the manifest's directory
    mp.write_text(json.dumps({"samples": [{"id": "m", "path": "m.vcf"}]}))
    man = load_manifest(str(mp))
    assert man.samples[0].path == str(tmp_path / "m.vcf")
    assert man.sample_ids == ["m"]
    # bare path list form + default ids
    assert as_manifest([p]).sample_ids == ["m"]
    # malformed shapes are PLAN class
    with pytest.raises(PlanError):
        CohortManifest.from_doc({"nope": []})
    with pytest.raises(PlanError):
        CohortManifest.from_doc([])
    with pytest.raises(PlanError):
        CohortManifest.from_doc([{"path": p}, {"path": p}])  # dup id
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(PlanError):
        load_manifest(str(bad))
    with pytest.raises(FileNotFoundError):
        load_manifest(str(tmp_path / "absent.json"))


def test_manifest_identity_tracks_inputs(tmp_path):
    p = _write_sample(str(tmp_path / "i.vcf"), "i", [
        "chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t0/1"])
    mp = _manifest(tmp_path, [p])
    i0 = load_manifest(mp).identity()
    assert i0 == load_manifest(mp).identity()
    os.utime(p, ns=(1, 1))       # touch an input: identity changes
    assert load_manifest(mp).identity() != i0
    assert i0[0] == os.path.abspath(mp)   # anchor = manifest abspath


# ---------------------------------------------------------------------------
# per-input-file fault domains
# ---------------------------------------------------------------------------

def test_corrupt_input_under_chaos_quarantines(tmp_path):
    """A byte-flipped sample stream quarantines: sentinel column,
    manifest entry, fed fault domain — the build completes."""
    from hadoop_bam_tpu import resilience
    from hadoop_bam_tpu.utils.metrics import MetricsContext
    from hadoop_bam_tpu.utils.resilient import clear_chaos, \
        install_chaos_seeded

    good = _write_sample(str(tmp_path / "ok.vcf"), "ok", [
        "chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t0/1",
        "chr20\t200\t.\tC\tT\t30\tPASS\t.\tGT\t1/1"])
    bad = _write_sample(str(tmp_path / "bad.bcf"), "bad", [
        "chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t1/1",
        "chr20\t200\t.\tC\tT\t30\tPASS\t.\tGT\t0/1"])
    ds = CohortDataset([good, bad])      # headers read CLEAN, then...
    install_chaos_seeded(bad, seed=99, bitflip_rate=1.0)
    try:
        with MetricsContext() as m:
            got = _collect_batches(ds)
    finally:
        clear_chaos(bad)
    # the good sample's column is intact; the bad one is all sentinel
    assert got["pos"].tolist() == [100, 200]
    np.testing.assert_array_equal(got["dosage"][:, 0], [1, 2])
    np.testing.assert_array_equal(got["dosage"][:, 1], [-1, -1])
    assert list(ds.manifest.quarantined) == ["bad"]
    assert m.snapshot()["counters"]["cohort.samples_quarantined"] == 1
    # the input's fault domain breaker was fed
    states = resilience.registry().states()
    assert any(k.startswith("cohort/input/") for k in states)


def test_out_of_order_input_quarantines_and_strict_raises(tmp_path):
    good = _write_sample(str(tmp_path / "g.vcf"), "g", [
        "chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t0/1"])
    unsorted = _write_sample(str(tmp_path / "u.vcf"), "u", [
        "chr20\t500\t.\tA\tG\t30\tPASS\t.\tGT\t1/1",
        "chr20\t100\t.\tC\tT\t30\tPASS\t.\tGT\t0/1"])
    ds = CohortDataset([good, unsorted])
    got = _collect_batches(ds)
    assert "u" in ds.manifest.quarantined
    # records BEFORE the fault still joined (degrade, don't discard)
    assert 500 in got["pos"].tolist()
    # quarantine off: the same data fault raises
    cfg = dataclasses.replace(DEFAULT_CONFIG,
                              cohort_quarantine_inputs=False)
    with pytest.raises(CorruptDataError):
        _collect_batches(CohortDataset([good, unsorted], cfg))


def test_quarantine_fraction_circuit(tmp_path):
    """Losing more than cohort_max_quarantine_fraction of the columns
    fails the build — mostly-sentinel output is not a result."""
    u1 = _write_sample(str(tmp_path / "u1.vcf"), "u1", [
        "chr20\t500\t.\tA\tG\t30\tPASS\t.\tGT\t1/1",
        "chr20\t100\t.\tC\tT\t30\tPASS\t.\tGT\t0/1"])
    u2 = _write_sample(str(tmp_path / "u2.vcf"), "u2", [
        "chr21\t500\t.\tA\tG\t30\tPASS\t.\tGT\t1/1",
        "chr21\t100\t.\tC\tT\t30\tPASS\t.\tGT\t0/1"])
    cfg = dataclasses.replace(DEFAULT_CONFIG,
                              cohort_max_quarantine_fraction=0.5)
    with pytest.raises(CorruptDataError, match="quarantined"):
        _collect_batches(CohortDataset([u1, u2], cfg))


def test_corrupt_header_quarantines_at_build(tmp_path):
    """Corruption that already breaks the HEADER read is still data,
    not configuration: the sample quarantines before the join starts
    and its column is all sentinel."""
    good = _write_sample(str(tmp_path / "hok.vcf"), "hok", [
        "chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t0/1"])
    broken = _write_sample(str(tmp_path / "hbad.bcf"), "hbad", [
        "chr20\t100\t.\tA\tG\t30\tPASS\t.\tGT\t1/1"])
    raw = bytearray(open(broken, "rb").read())
    raw[20:60] = os.urandom(40)              # garble the header block
    with open(broken, "wb") as f:
        f.write(raw)
    cfg = dataclasses.replace(DEFAULT_CONFIG,
                              cohort_max_quarantine_fraction=0.6)
    ds = CohortDataset([good, broken], cfg)
    assert "hbad" in ds.manifest.quarantined
    got = _collect_batches(ds)
    np.testing.assert_array_equal(got["dosage"][:, :2], [[1, -1]])
    # the default 0.5 fraction circuit counts header casualties too
    with pytest.raises(CorruptDataError):
        CohortDataset([broken], dataclasses.replace(
            DEFAULT_CONFIG, cohort_max_quarantine_fraction=0.4))
    # quarantine off: the corruption raises
    with pytest.raises(Exception):
        CohortDataset([good, broken], dataclasses.replace(
            DEFAULT_CONFIG, cohort_quarantine_inputs=False))


def test_missing_input_is_plan_never_quarantined(tmp_path):
    with pytest.raises(FileNotFoundError):
        CohortDataset([str(tmp_path / "nope.vcf")])


# ---------------------------------------------------------------------------
# cohort-slice serving
# ---------------------------------------------------------------------------

def _serve_fixture(tmp_path, k=3, n_sites=25):
    rng = random.Random(21)
    paths = []
    for s in range(k):
        lines = []
        pos = 0
        for _ in range(n_sites):
            pos += rng.randint(1, 20)
            if rng.random() < 0.2:
                continue
            lines.append(f"chr20\t{pos}\t.\tA\tG\t30\tPASS\t.\tGT\t"
                         f"{rng.choice(['0/0', '0/1', '1/1', './.'])}")
        paths.append(_write_sample(str(tmp_path / f"v{s}.vcf"), f"v{s}",
                                   lines))
    return _manifest(tmp_path, [str(p) for p in paths]), paths


def test_cohort_slice_serving_warm_bypass(tmp_path):
    """Cold builds the joined tiles; every warm slice is answered from
    the device tier — zero host decode in an isolated context — and
    counts match the host oracle."""
    from hadoop_bam_tpu.serve import ServeLoop
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    man, paths = _serve_fixture(tmp_path)
    contigs, rows = _oracle_join([str(p) for p in paths])
    lo, hi = 1, 150
    want = sum(1 for r in rows if r[0] == 0 and lo <= r[1] <= hi)
    with ServeLoop() as loop:
        cold = loop.query(man, [f"chr20:{lo}-{hi}"], cohort=True)[0]
        assert cold.count == want
        assert cold.tile_misses >= 1 and cold.tile_hits == 0
        assert cold.extra["n_samples"] == 3
        with MetricsContext() as m:
            warm = loop.query(man, [f"chr20:{lo}-{hi}"], cohort=True,
                              want_records=True)[0]
        snap = m.snapshot()
        assert warm.count == want
        assert warm.tile_hits >= 1 and warm.tile_misses == 0
        # THE bypass proof: repeat slices do no host decode / join work
        assert snap["wall_timers"].get("cohort.join_wall", 0.0) == 0.0
        assert snap["wall_timers"].get("pipeline.host_decode_wall",
                                       0.0) == 0.0
        # records mode: wire-shaped per-variant dicts, sorted, af in range
        assert len(warm.records) == want
        assert all(r["chrom"] == "chr20" and lo <= r["pos"] <= hi
                   for r in warm.records)
        assert all(r["af"] is None or 0.0 <= r["af"] <= 1.0
                   for r in warm.records)
        # a different slice over the same cohort is ALSO warm (tiles
        # hold the whole joined tensor, keyed by manifest identity)
        with MetricsContext() as m2:
            other = loop.query(man, ["chr20:151-100000"], cohort=True)[0]
        assert m2.snapshot()["wall_timers"].get("cohort.join_wall",
                                                0.0) == 0.0
        want2 = sum(1 for r in rows if r[0] == 0 and 151 <= r[1] <= 100000)
        assert other.count == want2


def test_cohort_slice_input_rewrite_invalidates(tmp_path):
    """Rewriting one sample file changes the manifest identity: the
    next slice re-joins instead of serving stale tiles."""
    from hadoop_bam_tpu.serve import ServeLoop

    man, paths = _serve_fixture(tmp_path, k=2, n_sites=8)
    with ServeLoop() as loop:
        before = loop.query(man, ["chr20"], cohort=True)[0]
        # rewrite sample 0 with an extra site at pos 1
        _write_sample(str(paths[0]), "v0", [
            "chr20\t1\t.\tA\tG\t30\tPASS\t.\tGT\t1/1"])
        after = loop.query(man, ["chr20"], cohort=True)[0]
        assert after.tile_misses >= 1        # re-built, not stale
        assert after.count != before.count or after.n_candidates \
            != before.n_candidates


def test_cohort_slice_serves_through_header_corrupt_sample(tmp_path):
    """The serve path shares the CLI/API quarantine policy: a sample
    whose HEADER bytes are corrupt quarantines inside the serve build
    instead of failing the request (reviewed: the old separate
    header-read path raised out of serve())."""
    from hadoop_bam_tpu.serve import ServeLoop

    good = _write_sample(str(tmp_path / "sg.vcf"), "sg", [
        "chr20\t10\t.\tA\tG\t30\tPASS\t.\tGT\t0/1"])
    broken = _write_sample(str(tmp_path / "sb.bcf"), "sb", [
        "chr20\t10\t.\tA\tG\t30\tPASS\t.\tGT\t1/1"])
    raw = bytearray(open(broken, "rb").read())
    raw[20:60] = os.urandom(40)
    with open(broken, "wb") as f:
        f.write(raw)
    man = _manifest(tmp_path, [good, broken], ids=["sg", "sb"])
    cfg = dataclasses.replace(DEFAULT_CONFIG,
                              cohort_max_quarantine_fraction=0.6)
    with ServeLoop(config=cfg) as loop:
        res = loop.query(man, ["chr20:1-100"], cohort=True)[0]
        assert res.count == 1
        assert res.extra["n_samples"] == 2
        assert res.extra["quarantined"] == ["sb"]


def test_cohort_slice_bad_contig_and_quarantine_on_wire(tmp_path):
    import io

    from hadoop_bam_tpu.serve import ServeLoop
    from hadoop_bam_tpu.serve.transport import handle_stream

    good = _write_sample(str(tmp_path / "w.vcf"), "w", [
        "chr20\t10\t.\tA\tG\t30\tPASS\t.\tGT\t0/1"])
    unsorted = _write_sample(str(tmp_path / "x.vcf"), "x", [
        "chr20\t500\t.\tA\tG\t30\tPASS\t.\tGT\t1/1",
        "chr20\t100\t.\tC\tT\t30\tPASS\t.\tGT\t0/1"])
    man = _manifest(tmp_path, [good, unsorted], ids=["w", "x"])
    with ServeLoop() as loop:
        with pytest.raises(PlanError):
            loop.query(man, ["chrBOGUS:1-2"], cohort=True)
        reqs = (json.dumps({"id": 1, "cohort": True, "path": man,
                            "regions": ["chr20:1-1000"]}) + "\n"
                + json.dumps({"id": 2, "cohort": True, "path": man,
                              "regions": ["chrBOGUS:1-2"]}) + "\n")
        out = io.StringIO()
        handle_stream(loop, io.StringIO(reqs), out)
        docs = {d["id"]: d for d in
                (json.loads(l) for l in out.getvalue().splitlines())}
        r1 = docs[1]["results"][0]
        # w's chr20:10 + x's chr20:500 (x's out-of-order 100 is where
        # its stream faulted and quarantined)
        assert r1["count"] == 2
        assert r1["n_samples"] == 2
        # the quarantined sample surfaces on the wire
        assert r1["quarantined"] == ["x"]
        assert docs[2]["kind"] == "plan"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_cohort_stats_and_tsv(tmp_path, capsys):
    from hadoop_bam_tpu.tools.cli import main

    man, _paths = _serve_fixture(tmp_path, k=2, n_sites=10)
    pheno = tmp_path / "pheno.txt"
    pheno.write_text("1.0\n0.0\n")
    tsv = tmp_path / "stats.tsv"
    assert main(["cohort", man, "--pheno", str(pheno),
                 "--tsv", str(tsv)]) == 0
    out = capsys.readouterr().out
    assert "samples\t2" in out
    assert "variants\t" in out and "mean_af\t" in out
    header = tsv.read_text().splitlines()[0].split("\t")
    assert header == ["chrom", "pos", "n_allele", "af", "call_rate",
                      "hwe_chi2", "score_chi2"]
    # --region slices the report
    assert main(["cohort", man, "--region", "chr20:1-3"]) == 0
    out2 = capsys.readouterr().out
    assert "variants\t0" in out2
