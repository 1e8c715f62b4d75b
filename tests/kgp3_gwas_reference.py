"""The plain reference of the ``kgp3-chr20-gwas-x1`` deployment: the seeded
1000 Genomes phase-3 chr20-shaped BCF of ``kgp3_reference`` (its generator,
unedited), a seeded trait file, and the answers ``hbam vcf-gwas`` must give.

NumPy and the standard library only, float64, no blocking tricks beyond
computing in row blocks so that nothing matrix-times-traits sized is held
whole; nothing here imports the program under test.
``benchmark/gen_kgp3_gwas.py`` is a verbatim copy
(``tests/test_kgp3_gwas.py`` holds the two together), which is why the
generator is imported under either of its two names.

The job (ISSUE 32; the constants are the verb's, recorded in
``benchmark/configs/kgp3-chr20-gwas-x1.json``).  ``g_js`` is the verb's int8
dosage of site j, sample s: the count of non-REF alleles, -1 where a call is
missing.  S samples, M sites, P traits.

1. ``p_j = sum_s g_js / (2 n_called_j)``.  The GRM's site set C: SNPs (the
   verb's rule: REF one base, every ALT one base of ACGTN; a multi-allelic
   SNP counts with its non-REF dosage), no missing call, ``0.01 <= p_j <=
   0.99``.  ``z_js = (g_js - 2 p_j) / sqrt(2 p_j (1 - p_j))``,
   ``A = Z^T Z / |C|`` — GCTA's A_jk (Yang et al., AJHG 88:76, 2011).
2. The ``AXES`` leading eigenvectors of A (EIGENSTRAT, Price et al., Nat
   Genet 38:904, 2006), ``X = [1, v_1..v_4]``, ``Q = qr(X).Q``.
3. ``Y~ = Y - Q Q^T Y``, ``sigma2_p = |y~_p|^2 / S``; for every site and
   trait ``u = g_j . y~_p``, ``v_j = |g_j|^2 - |Q^T g_j|^2``, ``chi2 = u^2 /
   (v_j sigma2_p)``, NaN where ``v_j <= V_FLOOR |g_j|^2`` or a call is missing.

``reading="bf16"`` is the same job with Z and Y~ rounded to bfloat16: the
reading one precision below the verb's, which the comparison has to refuse.

Traits (assumed, seeded): ``y_p = b_p[superpop(s)] + sum over 8 causal common
sites of beta_pc g_cs + N(0, 1)``, ``b ~ N(0, 0.5^2)``, ``beta ~ N(0,
0.15^2)``; common = no missing call and ``0.05 <= p_j <= 0.95``.  Written as
the TSV the verb reads (header ``sample`` + P names, one row a sample, rows
in a seeded order: the verb matches samples by name).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

try:                               # beside tests/kgp3_reference.py ...
    import kgp3_reference as K
except ImportError:                # ... or beside benchmark/gen_kgp3.py
    from benchmark import gen_kgp3 as K

AXES = 4                           # covariate axes beside the intercept
MAF = 0.01                         # the GRM's site filter
COMMON = 0.05                      # a causal site's
CHI2_GENOME_WIDE = 29.72           # p < 5e-8 at 1 d.f.
V_FLOOR = 1e-6                     # chi2 is NaN where v_j <= V_FLOOR |g_j|^2
CAUSAL = 8
B_SD, BETA_SD = 0.5, 0.15
BLOCK = 4096                       # sites a block of the [M, P] pass


def trait_names(n_traits: int):
    return [f"T{p:03d}" for p in range(n_traits)]


def dosage(f: dict) -> np.ndarray:
    """[n, S] int8: non-REF alleles of a called genotype, -1 where an
    allele is missing (the verb's dosage; a haploid call counts one)."""
    a0, a1, two = f["a0"], f["a1"], f["ploidy"] == 2
    called = (a0 >= 0) & (~two | (a1 >= 0))
    alt = (a0 > 0).astype(np.int8) + ((a1 > 0) & two)
    return np.where(called, alt, -1).astype(np.int8)


def is_snp(f: dict) -> np.ndarray:
    """The verb's SNP rule (``kgp3_reference.Reference.add`` counts it)."""
    alen, alleles, n_alt = f["alen"], f["alleles"], f["n_alt"]
    snp = alen[:, 0] == 1
    for k in (1, 2, 3):
        base_ok = np.isin(alleles[:, k, 0], np.frombuffer(b"ACGTN", np.uint8))
        snp &= ~(n_alt >= k) | ((alen[:, k] == 1) & base_ok)
    return snp


def frequencies(g: np.ndarray):
    """(p_j float64 [n], every call present [n] bool)."""
    called = g >= 0
    n_called = called.sum(axis=1, dtype=np.int64)
    alt = np.where(called, g, 0).sum(axis=1, dtype=np.int64)
    p = alt / (2.0 * np.maximum(n_called, 1))
    return p, n_called == g.shape[1]


def grm_sites(g: np.ndarray, snp: np.ndarray) -> np.ndarray:
    p, complete = frequencies(g)
    return snp & complete & (p >= MAF) & (p <= 1.0 - MAF)


def standardise(g: np.ndarray) -> np.ndarray:
    """Z float64 of complete sites ``g``: (g - 2p) / sqrt(2p(1 - p))."""
    p, _ = frequencies(g)
    return (g - 2.0 * p[:, None]) / np.sqrt(2.0 * p * (1.0 - p))[:, None]


def fold_chunk(f: dict, shape: K.Shape, level: int = 6):
    """One chunk's field arrays folded: ``kgp3_reference.chunk_job``'s BGZF
    bytes and share of the scan's answers, and this job's: the dosage, the
    positions, the GRM's sites and their Z^T Z in both readings."""
    data, starts = K.assemble(f, shape)
    part = K.Reference(shape.n_samples)
    part.add(f, int(starts[-1]))
    g = dosage(f)
    in_c = grm_sites(g, is_snp(f))
    z = standardise(g[in_c])
    zb = K._round_bf16(z).astype(np.float64)
    return (K.bgzf(data, level), part, g, f["pos"].astype(np.int64), in_c,
            z.T @ z, zb.T @ zb)


def chunk_job(job):
    """One chunk, as a child process makes it."""
    seed, c, n_chunks, chunk_records, shape, level = job
    return fold_chunk(K.gen_fields(seed, c, n_chunks, chunk_records, shape),
                      shape, level)


class Covariates:
    """One reading's A, its spectrum, Q and the residualised traits."""

    def __init__(self, ztz: np.ndarray, n_c: int, y: np.ndarray,
                 round_y: bool):
        self.a = ztz / max(n_c, 1)
        w, v = np.linalg.eigh(self.a)
        self.eigenvalues = w[::-1].copy()              # descending
        x = np.concatenate([np.ones((y.shape[0], 1)), v[:, ::-1][:, :AXES]],
                           axis=1)
        self.q = np.linalg.qr(x)[0]                     # [S, 1 + AXES]
        yt = y - self.q @ (self.q.T @ y)
        self.yt = K._round_bf16(yt).astype(np.float64) if round_y else yt
        self.sigma2 = (self.yt * self.yt).sum(axis=0) / y.shape[0]

    @property
    def projector(self) -> np.ndarray:
        return self.q @ self.q.T

    @property
    def gap(self) -> float:
        w = self.eigenvalues
        return float((w[AXES - 1] - w[AXES]) / w[AXES - 1])


class _Summary:
    """``Reference.summary`` folded a block of the float64 table at a
    time."""

    def __init__(self, n_traits: int, rel: float):
        self.rel, self.tested, self.borderline = rel, 0, 0
        self.total = np.zeros(n_traits)
        self.best = np.full(n_traits, -np.inf)
        self.best_at = np.zeros(n_traits, np.int64)
        self.hits_lo = np.zeros(n_traits, np.int64)
        self.hits_hi = np.zeros(n_traits, np.int64)

    def add(self, lo: int, chi2, ratio, missing) -> None:
        ok = ~np.isnan(chi2[:, 0])
        self.tested += int(ok.sum())
        self.borderline += int((~missing & (ratio > V_FLOOR / 2)
                                & (ratio < V_FLOOR * 2)).sum())
        if not ok.any():
            return
        c = np.where(ok[:, None], chi2, -np.inf)
        self.total += np.where(ok[:, None], chi2, 0.0).sum(axis=0)
        at = c.argmax(axis=0)
        top = c[at, np.arange(c.shape[1])]
        better = top > self.best
        self.best_at = np.where(better, lo + at, self.best_at)
        self.best = np.where(better, top, self.best)
        self.hits_hi += (c > CHI2_GENOME_WIDE * (1 - self.rel)).sum(axis=0)
        self.hits_lo += (c > CHI2_GENOME_WIDE * (1 + self.rel)).sum(axis=0)

    def done(self) -> dict:
        return {"rel": self.rel, "tested": self.tested,
                "borderline": self.borderline,
                "mean": self.total / max(self.tested, 1), "max": self.best,
                "max_at": self.best_at, "hits_lo": self.hits_lo,
                "hits_hi": self.hits_hi}


class Reference:
    """The file's dosage matrix (int8, file order), the traits, and both
    readings of the job.  ``write_bcf`` fills it; ``finish`` draws the traits
    and takes the eigen-decompositions; the ``[M, P]`` table is computed a
    block at a time on demand."""

    def __init__(self, n_traits: int, shape: K.Shape = K.KGP3):
        self.shape, self.n_traits = shape, int(n_traits)
        self.scan = K.Reference(shape.n_samples)
        s = shape.n_samples
        self._g, self._pos, self._in_c = [], [], []
        self._ztz = np.zeros((s, s))
        self._ztz_bf16 = np.zeros((s, s))
        self.g = self.pos = self.in_c = self.y = None
        self.f64 = self.bf16 = None
        self._summary = self._site_of = None

    # -- filled by write_bcf --------------------------------------------------
    def add_chunk(self, part, g, pos, in_c, ztz, ztz_bf16) -> None:
        self.scan.merge(part)
        self._g.append(g)
        self._pos.append(pos)
        self._in_c.append(in_c)
        self._ztz += ztz
        self._ztz_bf16 += ztz_bf16

    def finish(self, seed: int) -> None:
        self.g = np.concatenate(self._g)
        self.pos = np.concatenate(self._pos)
        self.in_c = np.concatenate(self._in_c)
        self._g = self._pos = self._in_c = None
        self.y = self._draw_traits(seed)
        n_c = int(self.in_c.sum())
        self.f64 = Covariates(self._ztz, n_c, self.y, round_y=False)
        self.bf16 = Covariates(self._ztz_bf16, n_c, self.y, round_y=True)

    @property
    def n(self) -> int:
        return int(self.g.shape[0])

    @property
    def n_grm(self) -> int:
        return int(self.in_c.sum())

    # -- the traits -----------------------------------------------------------
    def _draw_traits(self, seed: int) -> np.ndarray:
        rng = np.random.default_rng([seed, 0x6A5])
        s, n_pop = self.shape.n_samples, len(self.shape.pops)
        p, complete = frequencies(self.g)
        common = np.flatnonzero(complete & (p >= COMMON) & (p <= 1 - COMMON))
        if common.size == 0:
            common = np.flatnonzero(complete)
        pop = np.repeat(np.arange(n_pop), self.shape.pops)
        b = rng.normal(0.0, B_SD, (self.n_traits, n_pop))
        beta = rng.normal(0.0, BETA_SD, (self.n_traits, CAUSAL))
        self.causal = common[rng.integers(0, common.size,
                                          (self.n_traits, CAUSAL))]
        y = b[:, pop].T + rng.standard_normal((s, self.n_traits))
        for t in range(self.n_traits):
            y[:, t] += beta[t] @ self.g[self.causal[t]].astype(np.float64)
        # the traits are what the file says: round as it is written
        return np.array([[float(f"{v:.9g}") for v in row] for row in y])

    def write_traits(self, path: str, seed: int) -> None:
        """The TSV the verb reads, rows in a seeded order."""
        names = K.sample_names(self.shape)
        order = np.random.default_rng([seed, 0x75F]).permutation(len(names))
        with open(path, "w", encoding="ascii") as fh:
            fh.write("\t".join(["sample"] + trait_names(self.n_traits))
                     + "\n")
            for i in order:
                fh.write("\t".join([names[i]] + [f"{v:.9g}"
                                                 for v in self.y[i]]) + "\n")

    # -- the [M, P] table, a block at a time ----------------------------------
    def chi2_block(self, lo: int, hi: int, reading: str = "f64"):
        """(chi2 [hi - lo, P] float64 with the NaN rule applied,
        v_j / |g_j|^2 [hi - lo], a call is missing [hi - lo])."""
        cov = self.f64 if reading == "f64" else self.bf16
        g = self.g[lo:hi].astype(np.float64)
        missing = (self.g[lo:hi] < 0).any(axis=1)
        gg = (g * g).sum(axis=1)
        qg = g @ cov.q
        v = gg - (qg * qg).sum(axis=1)
        u = g @ cov.yt
        with np.errstate(divide="ignore", invalid="ignore"):
            chi2 = u * u / (v[:, None] * cov.sigma2[None, :])
            ratio = v / gg
        untested = missing | ~(v > V_FLOOR * gg)
        chi2[untested] = np.nan
        return chi2, ratio, missing

    def summary(self, rel: float = 0.0) -> dict:
        """What the verb prints, over the float64 reading: tested sites,
        and per trait the mean and the max chi2 with its site.  ``hits``
        counts ``chi2 > CHI2_GENOME_WIDE`` twice, with the threshold moved
        by ``-rel`` and ``+rel``: a value that close to it may fall either
        side in float32.  ``borderline`` counts sites whose ``v / |g|^2``
        lies within a factor 2 of ``V_FLOOR`` (none expected)."""
        if self._summary is None or self._summary["rel"] != rel:
            fold = _Summary(self.n_traits, rel)
            for lo in range(0, self.n, BLOCK):
                fold.add(lo, *self.chi2_block(lo, min(lo + BLOCK, self.n)))
            self._summary = fold.done()
        return self._summary

    def chi2_at(self, site: int, trait: int) -> float:
        return float(self.chi2_block(site, site + 1)[0][0, trait])

    # -- comparisons ----------------------------------------------------------
    def wrong(self, printed: str, tol: dict) -> Optional[str]:
        """``None`` when a job's printed answer is the reference's, else
        what differs.  Exact: sites, GRM sites, the traits' names, tested
        sites (but for borderline ones).  Within ``tol``: the eigenvalues
        (``eigenvalue_rel``) and each trait's mean and max chi2
        (``chi2_rel``); the printed site of the max must read, in the
        reference, within ``chi2_rel`` of the reference's max; the count over
        the genome-wide threshold must lie between the reference's counts
        with the threshold moved by ``chi2_rel`` either way."""
        s = self.summary(tol["chi2_rel"])
        lines = printed.strip().splitlines()
        kv = dict(ln.split("\t", 1) for ln in lines if ln.count("\t") == 1)
        for key, want in (("sites", self.n), ("grm_sites", self.n_grm),
                          ("traits", self.n_traits)):
            if int(kv.get(key, -1)) != want:
                return f"{key} {kv.get(key)} != reference {want}"
        for k in range(AXES):
            got, want = float(kv[f"eigenvalue_{k + 1}"]), \
                self.f64.eigenvalues[k]
            if abs(got - want) > tol["eigenvalue_rel"] * abs(want):
                return f"eigenvalue_{k + 1} {got!r} vs reference {want!r}"
        rows = [ln.split("\t") for ln in lines if ln.count("\t") == 5]
        if rows[0] != ["trait", "tested", "mean_chi2", "max_chi2", "max_pos",
                       "genome_wide"]:
            return f"trait table header {rows[0]}"
        rows = rows[1:]
        if [r[0] for r in rows] != trait_names(self.n_traits):
            return "trait names differ"
        if self._site_of is None:
            self._site_of = {int(p): i for i, p in enumerate(self.pos)}
        site_of = self._site_of
        for t, (_, tested, mean, top, pos, hits) in enumerate(rows):
            if not s["tested"] <= int(tested) <= s["tested"] + s["borderline"]:
                return f"{rows[t][0]} tested {tested} != {s['tested']}"
            for name, got, want in (("mean_chi2", float(mean), s["mean"][t]),
                                    ("max_chi2", float(top), s["max"][t])):
                if abs(got - want) > tol["chi2_rel"] * (1.0 + abs(want)):
                    return (f"{rows[t][0]} {name} {got!r} vs reference "
                            f"{want!r}")
            site = site_of.get(int(pos))
            if site is None or self.chi2_at(site, t) \
                    < s["max"][t] - tol["chi2_rel"] * (1.0 + s["max"][t]):
                return (f"{rows[t][0]} max_pos {pos} is not where the "
                        f"reference's max is ({self.pos[s['max_at'][t]]})")
            if not s["hits_lo"][t] <= int(hits) <= s["hits_hi"][t]:
                return (f"{rows[t][0]} genome_wide {hits} outside "
                        f"[{s['hits_lo'][t]}, {s['hits_hi'][t]}]")
        return None

    def readings(self, eigenvalues, projector, chi2_rows,
                 reading: str = "f64",
                 summary_rel: Optional[float] = None) -> dict:
        """How far a result lies from the float64 reading, as the three
        numbers the tolerances bound.  ``chi2_rows(lo, hi)`` returns the
        result's ``[hi - lo, P]`` rows; ``reading="bf16"`` measures the
        reference's own bfloat16 reading instead (the arguments are then
        ignored).  ``nan_differs`` counts entries NaN on one side only,
        sites with a borderline ``v / |g|^2`` left out.  ``summary_rel``
        folds ``summary(summary_rel)`` from the same pass over the table."""
        fold = None if summary_rel is None \
            else _Summary(self.n_traits, summary_rel)
        if reading == "bf16":
            eigenvalues = self.bf16.eigenvalues
            projector = self.bf16.projector

            def chi2_rows(lo, hi):
                return self.chi2_block(lo, hi, "bf16")[0]
        want = self.f64.eigenvalues[:AXES]
        out = {"eigenvalue_rel": float(np.max(
                   np.abs(np.asarray(eigenvalues)[:AXES] - want)
                   / np.abs(want))),
               "projector_abs": float(np.max(np.abs(
                   np.asarray(projector) - self.f64.projector))),
               "chi2_rel": 0.0, "nan_differs": 0}
        for lo in range(0, self.n, BLOCK):
            hi = min(lo + BLOCK, self.n)
            ref, ratio, missing = self.chi2_block(lo, hi)
            if fold is not None:
                fold.add(lo, ref, ratio, missing)
            got = np.asarray(chi2_rows(lo, hi), np.float64)
            sure = missing | (ratio <= V_FLOOR / 2) | (ratio >= V_FLOOR * 2)
            both = ~np.isnan(ref) & ~np.isnan(got)
            out["nan_differs"] += int(((np.isnan(ref) != np.isnan(got))
                                       & sure[:, None]).sum())
            if both.any():
                out["chi2_rel"] = max(out["chi2_rel"], float(np.max(
                    np.abs(got[both] - ref[both]) / (1.0 + ref[both]))))
        if fold is not None:
            self._summary = fold.done()
        return out

    @staticmethod
    def outside(readings: dict, tol: dict):
        """The tolerances a reading breaks (empty: inside every one)."""
        bad = [k for k in ("eigenvalue_rel", "projector_abs", "chi2_rel")
               if not readings[k] <= tol[k]]      # NaN is outside
        if readings["nan_differs"]:
            bad.append("nan_differs")
        return bad


def write_bcf(path: str, seed: int, n_chunks: int, chunk_records: int,
              ref: Reference, workers: int = 1, level: int = 6,
              mutate=None) -> int:
    """``kgp3_reference.write_bcf`` with this job's share of every chunk
    folded into ``ref``: the same file, byte for byte.  ``mutate(f, c)``
    (tests only, one process) edits a chunk's field arrays first."""
    shape = ref.shape
    jobs = [(seed, c, n_chunks, chunk_records, shape, level)
            for c in range(n_chunks)]
    if mutate is not None:
        def make(job):
            f = K.gen_fields(*job[:5])
            mutate(f, job[1])
            return fold_chunk(f, shape, level)
    else:
        make = chunk_job
    pool = None
    if workers > 1:
        import multiprocessing

        pool = multiprocessing.get_context("spawn").Pool(
            min(workers, n_chunks))
    try:
        with open(path, "wb") as fh:
            fh.write(K.bgzf(K.header_bytes(shape), level))
            for blob, *part in (pool.imap(chunk_job, jobs) if pool
                                else map(make, jobs)):
                ref.add_chunk(*part)
                fh.write(blob)
            fh.write(K.BGZF_EOF)
            size = fh.tell()
    finally:
        if pool is not None:
            pool.terminate()
            pool.join()
    ref.finish(seed)
    return size


def describe(r: dict) -> str:
    return ", ".join(f"{k} {r[k]:.3e}" if isinstance(r[k], float)
                     else f"{k} {r[k]}" for k in r)
