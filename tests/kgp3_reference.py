"""The plain reference of the ``kgp3-chr20-x1`` deployment: a seeded 1000
Genomes phase-3 chr20-shaped BCF and the answers ``hbam vcf-stats`` must give.

NumPy and the standard library only: nothing here imports the program under
test.  ``gen_fields`` draws one chunk's field arrays from ``(seed, chunk)``,
``assemble`` turns them into BCF2.2 record bytes by itself ([SPEC] hts-specs
VCFv4.3 section 6: l_shared, l_indiv, the 24 fixed bytes, typed values, the
per-sample block), ``bgzf`` wraps bytes into BGZF members with plain zlib, and
``Reference`` folds the generator's *own* allele arrays into the verb's answers
in int64/float64.  Chunks are independent draws confined to their own slice of
the contig, so they can be made in child processes and concatenate into one
coordinate-sorted file.  ``benchmark/gen_kgp3.py`` is a verbatim copy
(``tests/test_kgp3_vcfstats.py`` holds the two together).

Shapes (the source's, never cut; what is set from memory of the source is
listed under ``assumed`` in ``benchmark/configs/kgp3-chr20-x1.json``): 2,504
samples in 26 populations of 5 super-populations, contig ``20`` of GRCh37,
FORMAT ``GT`` only, diploid, phased, none missing, FILTER ``PASS``, the
source's twelve INFO keys with typed values, SNPs, indels and symbolic
structural ALTs, bi- and multi-allelic, the paper's frequency spectrum, and
haplotypes with linkage: every sample haplotype copies founder haplotypes of
its super-population and switches founder at a fixed rate a site, and
neighbouring common sites often carry the same founder pattern, so the file
deflates as a call set does and not as noise.  Scale (chunks, records a chunk)
comes from the configuration file.

Departures from the source, noted as the contract asks:

- ``mean_af`` is the verb's: non-REF alleles / (2 x called samples) per
  variant, averaged over variants with a call.  At a multi-allelic site the
  source's ``INFO/AF`` is per ALT; the verb's is their sum.  A haploid call
  still counts 2 in the denominator (the device step's documented rule).
- structural records carry the same INFO keys less ``AA`` (the source adds
  ``SVTYPE``, ``END``, ``CS``, ... there) and ``rlen`` 1.
"""
from __future__ import annotations

import struct
import zlib
from typing import NamedTuple, Optional, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# the deployment's shape (1000 Genomes phase 3, release 20130502, chr20)
# ---------------------------------------------------------------------------

SUPERPOPS = (
    ("AFR", (("YRI", 108), ("LWK", 99), ("GWD", 113), ("MSL", 85),
             ("ESN", 99), ("ASW", 61), ("ACB", 96))),
    ("AMR", (("MXL", 64), ("PUR", 104), ("CLM", 94), ("PEL", 85))),
    ("EAS", (("CHB", 103), ("JPT", 104), ("CHS", 105), ("CDX", 93),
             ("KHV", 99))),
    ("EUR", (("CEU", 99), ("TSI", 107), ("FIN", 99), ("GBR", 91),
             ("IBS", 107))),
    ("SAS", (("GIH", 103), ("PJL", 96), ("BEB", 86), ("STU", 102),
             ("ITU", 102))),
)
# the HapMap-era populations keep Coriell's NA prefix, the rest are HG
_NA_POPS = frozenset(("YRI", "LWK", "ASW", "MXL", "CHB", "JPT", "CEU",
                      "TSI", "GIH"))
CONTIG, CONTIG_LEN = "20", 63_025_520
CHR20_SITES = 1_812_841            # the source file's records (assumed)
FIRST_POS = 60_343                 # its first site (assumed)
GRCH37 = (("1", 249250621), ("2", 243199373), ("3", 198022430),
          ("4", 191154276), ("5", 180915260), ("6", 171115067),
          ("7", 159138663), ("8", 146364022), ("9", 141213431),
          ("10", 135534747), ("11", 135006516), ("12", 133851895),
          ("13", 115169878), ("14", 107349540), ("15", 102531392),
          ("16", 90354753), ("17", 81195210), ("18", 78077248),
          ("19", 59128983), ("20", 63025520), ("21", 48129895),
          ("22", 51304566), ("X", 155270560), ("Y", 59373566),
          ("MT", 16569))
CHROM_IDX = [c for c, _ in GRCH37].index(CONTIG)
SYMBOLIC_ALTS = ("<CN0>", "<INS:ME:ALU>", "<CN2>", "<INS:ME:L1>", "<INV>",
                 "<INS:ME:SVA>")
INFO_DEFS = (
    ("AC", "A", "Integer", "Total number of alternate alleles in called "
     "genotypes"),
    ("AF", "A", "Float", "Estimated allele frequency in the range (0,1)"),
    ("AN", "1", "Integer", "Total number of alleles in called genotypes"),
    ("NS", "1", "Integer", "Number of samples with data"),
    ("DP", "1", "Integer", "Total read depth"),
    ("EAS_AF", "A", "Float", "Allele frequency in the EAS populations"),
    ("AMR_AF", "A", "Float", "Allele frequency in the AMR populations"),
    ("AFR_AF", "A", "Float", "Allele frequency in the AFR populations"),
    ("EUR_AF", "A", "Float", "Allele frequency in the EUR populations"),
    ("SAS_AF", "A", "Float", "Allele frequency in the SAS populations"),
    ("AA", "1", "String", "Ancestral allele"),
    ("VT", ".", "String", "Variant type: SNP, INDEL or SV"),
)
INFO_KEYS = tuple(d[0] for d in INFO_DEFS)
# BCF dictionary of strings [SPEC 6.2.1]: PASS, then IDs as they appear
STRINGS = ("PASS", "GT") + INFO_KEYS
_POP_AF_ORDER = ("EAS", "AMR", "AFR", "EUR", "SAS")   # the INFO keys' order

# the paper's spectrum: of ~84 M autosomal variants ~64 M under 0.5 %,
# ~12 M from 0.5 to 5 %, ~8 M over 5 %
SPECTRUM = (64 / 84, 12 / 84, 8 / 84)
# what is drawn so that the file realises it: a low-frequency site drawn
# near 0.5 % falls under it in half the cohorts the copying makes
CLASS_DRAW = (0.69, 0.235, 0.075)
LOW_LO = 0.006                     # lowest frequency drawn for that class
RARE_MAX_AC = 25                   # 0.5 % of 5,008 haplotypes
TYPE_SHARES = (0.958, 0.0413, 0.0007)      # SNP, indel, SV (assumed)
MULTI_SHARE = 0.005                # sites with 2 or 3 ALTs (assumed)
FOUNDERS = 48                      # founder haplotypes a super-population
SWITCH_RATE = 0.002                # founder switches a haplotype a site
TAG_SHARE = 0.5                    # common sites repeating a neighbour
FST = 0.08                         # spread of a site's frequency over pops
RARE_POP_WEIGHTS = (0.40, 0.12, 0.17, 0.14, 0.17)    # SUPERPOPS' order


class Shape(NamedTuple):
    """What a file is made of.  ``KGP3`` is the deployment; the tests use
    small cohorts with the genotype forms the source never has."""
    pops: Tuple[int, ...]          # samples a super-population
    missing: float = 0.0           # share of genotypes ./. or half-missing
    haploid: float = 0.0           # share of genotypes with one allele
    unphased: float = 0.0          # share of genotypes written a/b
    haploid_records: float = 0.0   # share of records with ploidy 1
    type_shares: Tuple[float, float, float] = TYPE_SHARES
    multi_share: float = MULTI_SHARE

    @property
    def n_samples(self) -> int:
        return int(sum(self.pops))


KGP3 = Shape(tuple(sum(n for _, n in pops) for _, pops in SUPERPOPS))
N_SAMPLES = KGP3.n_samples         # 2,504


def sample_names(shape: Shape = KGP3):
    """Names in the source's style (HG00096, NA18486, ...), grouped by
    population; a cohort of another size gets plain HG names."""
    if shape.pops == KGP3.pops:
        names, hg, na = [], 96, 6984
        for _, pops in SUPERPOPS:
            for pop, n in pops:
                for _ in range(n):
                    if pop in _NA_POPS:
                        names.append(f"NA{na:05d}")
                        na += 1
                    else:
                        names.append(f"HG{hg:05d}")
                        hg += 1
        return names
    return [f"HG{96 + i:05d}" for i in range(shape.n_samples)]


def header_text(shape: Shape = KGP3) -> str:
    lines = ["##fileformat=VCFv4.1",
             '##FILTER=<ID=PASS,Description="All filters passed">',
             "##fileDate=20150218",
             "##reference=ftp://ftp.1000genomes.ebi.ac.uk//vol1/ftp/"
             "technical/reference/phase2_reference_assembly_sequence/"
             "hs37d5.fa.gz",
             "##source=1000GenomesPhase3Pipeline"]
    lines += [f"##contig=<ID={c},assembly=b37,length={ln}>"
              for c, ln in GRCH37]
    lines += [f'##ALT=<ID={a[1:-1]},Description="{a[1:-1]}">'
              for a in SYMBOLIC_ALTS]
    lines.append('##FORMAT=<ID=GT,Number=1,Type=String,'
                 'Description="Genotype">')
    lines += [f'##INFO=<ID={k},Number={num},Type={typ},Description="{d}">'
              for k, num, typ, d in INFO_DEFS]
    lines.append("\t".join(["#CHROM", "POS", "ID", "REF", "ALT", "QUAL",
                            "FILTER", "INFO", "FORMAT"]
                           + sample_names(shape)))
    return "\n".join(lines) + "\n"


def header_bytes(shape: Shape = KGP3) -> bytes:
    """[SPEC 6.1] magic, l_text, the NUL-terminated header text."""
    text = header_text(shape).encode() + b"\x00"
    return b"BCF\x02\x02" + struct.pack("<I", len(text)) + text


# ---------------------------------------------------------------------------
# field arrays of one chunk
# ---------------------------------------------------------------------------

_BASES = np.frombuffer(b"ACGT", np.uint8)
_ALLELE_W = 40                     # widest allele the generator writes


def _hap_pops(shape: Shape):
    """Per haplotype: its super-population; and each one's haplotype
    range (samples are grouped by population, two haplotypes a sample)."""
    sizes = 2 * np.asarray(shape.pops, np.int64)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    return np.repeat(np.arange(len(sizes)), sizes), bounds


def _copy_paths(rng, n: int, n_hap: int) -> np.ndarray:
    """[n, n_hap] founder index copied at each site: a haplotype keeps
    its founder and switches to a fresh one at SWITCH_RATE a site."""
    k = rng.poisson(n * SWITCH_RATE, n_hap)
    hap = np.repeat(np.arange(n_hap, dtype=np.int64), k)
    cut = np.sort(hap * n + rng.integers(1, max(n, 2), hap.size)) \
        - hap * n
    # segment starts a haplotype: 0, then its cuts; lengths to the next
    starts = np.concatenate([np.zeros(n_hap, np.int64), cut])
    owner = np.concatenate([np.arange(n_hap, dtype=np.int64), hap])
    order = np.lexsort((starts, owner))
    starts, owner = starts[order], owner[order]
    ends = np.empty_like(starts)
    ends[:-1] = starts[1:]
    ends[-1] = n
    ends[np.flatnonzero(np.diff(owner))] = n    # a haplotype's last
    ids = rng.integers(0, FOUNDERS, starts.size).astype(np.uint8)
    return np.repeat(ids, ends - starts).reshape(n_hap, n).T


def _draw_alleles(rng, n: int, n_alt: np.ndarray, shape: Shape
                  ) -> np.ndarray:
    """[n, n_hap] int8 allele index a haplotype (0 REF, 1.. the ALTs)."""
    hap_pop, bounds = _hap_pops(shape)
    n_hap, n_pop = hap_pop.size, len(shape.pops)
    g = np.zeros((n, n_hap), np.int8)
    cls = rng.choice(3, n, p=CLASS_DRAW)

    # common and low-frequency sites: founders carry the allele, samples
    # copy founders; a tagging site repeats its neighbour's founders
    f_rows = np.flatnonzero(cls > 0)
    if f_rows.size:
        nf = f_rows.size
        low = cls[f_rows] == 1
        p = np.where(low,
                     np.exp(rng.uniform(np.log(LOW_LO), np.log(0.05), nf)),
                     np.exp(rng.uniform(np.log(0.05), np.log(0.95), nf)))
        a = (p * (1 - FST) / FST)[:, None].repeat(n_pop, 1)
        b = ((1 - p) * (1 - FST) / FST)[:, None].repeat(n_pop, 1)
        p_pop = rng.beta(a, b)                              # [nf, n_pop]
        founders = (rng.random((nf, n_pop * FOUNDERS), dtype=np.float32)
                    < np.repeat(p_pop, FOUNDERS, axis=1)).astype(np.int8)
        tag = rng.random(nf) < TAG_SHARE
        tag[0] = False
        src = np.maximum.accumulate(np.where(tag, 0, np.arange(nf)))
        founders = founders[src]
        paths = _copy_paths(rng, n, n_hap)[f_rows]          # [nf, n_hap]
        col = (hap_pop * FOUNDERS)[None, :] + paths
        g[f_rows] = np.take_along_axis(founders, col, axis=1)

    # rare sites: AC ~ 1/k copies on haplotypes of one super-population
    r_rows = np.flatnonzero(cls == 0)
    if r_rows.size:
        w = 1.0 / np.arange(1, RARE_MAX_AC + 1)
        ac = rng.choice(RARE_MAX_AC, r_rows.size, p=w / w.sum()) + 1
        pop = rng.choice(n_pop, r_rows.size, p=_pop_weights(shape))
        rows = np.repeat(r_rows, ac)
        lo, hi = bounds[np.repeat(pop, ac)], bounds[np.repeat(pop, ac) + 1]
        hap = lo + (rng.random(rows.size) * (hi - lo)).astype(np.int64)
        g[rows, hap] = 1

    # no monomorphic site: one copy where none was realised
    empty = np.flatnonzero(~g.any(axis=1))
    g[empty, rng.integers(0, n_hap, empty.size)] = 1
    # multi-allelic sites: part of the ALT copies are the 2nd or 3rd ALT
    for k in (2, 3):
        rows = np.flatnonzero(n_alt >= k)
        if rows.size:
            sub = g[rows]
            sub[(sub > 0) & (rng.random(sub.shape) < 0.3)] = k
            g[rows] = sub
    return g


def _pop_weights(shape: Shape) -> np.ndarray:
    w = np.asarray(RARE_POP_WEIGHTS[:len(shape.pops)], np.float64)
    w = np.where(np.asarray(shape.pops) > 0, w, 0.0)
    return w / w.sum()


def _draw_sites(rng, n: int, shape: Shape):
    """Variant type, alleles (bytes + lengths), IDs of ``n`` sites."""
    vtype = rng.choice(3, n, p=shape.type_shares)    # 0 SNP 1 indel 2 SV
    n_alt = np.ones(n, np.int64)
    multi = rng.random(n) < shape.multi_share
    n_alt[multi] = 2 + (rng.random(int(multi.sum())) < 0.15)
    alleles = np.zeros((n, 4, _ALLELE_W), np.uint8)
    alen = np.zeros((n, 4), np.int64)
    ref = rng.integers(0, 4, n)
    alleles[:, 0, 0] = _BASES[ref]
    alen[:, 0] = 1
    # SNP ALTs: the other three bases in a seeded order
    rot = rng.integers(1, 4, n)
    step = rng.integers(0, 2, n) * 2 - 1
    for k in range(1, 4):
        base = _BASES[(ref + 1 + (rot - 1 + (k - 1) * step) % 3) % 4]
        on = (vtype == 0) & (n_alt >= k)
        alleles[on, k, 0] = base[on]
        alen[on, k] = 1
    # indels: the anchor base, then up to 30 deleted (REF grows, every
    # ALT is a prefix of it) or inserted (the ALTs grow) bases
    ind = np.flatnonzero(vtype == 1)
    if ind.size:
        tail = _BASES[rng.integers(0, 4, (ind.size, _ALLELE_W - 1))]
        deletion = rng.random(ind.size) < 0.55
        size = np.maximum(np.minimum(rng.geometric(0.35, ind.size), 30),
                          n_alt[ind])
        alleles[ind, :, 0] = alleles[ind, 0, 0][:, None]
        alleles[ind, :, 1:] = tail[:, None, :]
        alen[ind, 0] = np.where(deletion, 1 + size, 1)
        for k in range(1, 4):
            on = n_alt[ind] >= k
            alen[ind, k] = np.where(
                on, np.where(deletion, k, 2 + size - k), 0)
    # structural: symbolic ALTs
    sv = np.flatnonzero(vtype == 2)
    for k in range(1, 4):
        on = sv[n_alt[sv] >= k]
        pick = (rng.integers(0, len(SYMBOLIC_ALTS), on.size) + k) \
            % len(SYMBOLIC_ALTS) if k > 1 else \
            rng.integers(0, len(SYMBOLIC_ALTS), on.size)
        for j, name in enumerate(SYMBOLIC_ALTS):
            rows = on[pick == j]
            raw = np.frombuffer(name.encode(), np.uint8)
            alleles[rows, k, :raw.size] = raw
            alen[rows, k] = raw.size
    # IDs: rs numbers on most small variants, esv numbers on structural
    ids = np.zeros((n, 12), np.uint8)
    idlen = np.ones(n, np.int64)
    ids[:, 0] = ord(".")
    named = (rng.random(n) < 0.9) | (vtype == 2)
    number = rng.integers(1_000_000, 600_000_000, n)
    for i in np.flatnonzero(named):
        s = (f"esv{number[i] % 9_000_000 + 1_000_000}" if vtype[i] == 2
             else f"rs{number[i]}").encode()
        ids[i, :len(s)] = np.frombuffer(s, np.uint8)
        idlen[i] = len(s)
    return vtype, n_alt, alleles, alen, ids, idlen


def gen_fields(seed: int, chunk: int, n_chunks: int, n: int,
               shape: Shape = KGP3) -> dict:
    """Field arrays of one coordinate-sorted chunk of ``n`` sites, confined
    to the chunk's own slice of the region so chunks concatenate into one
    sorted file.  The region keeps the source's density of sites."""
    rng = np.random.default_rng([seed, chunk])
    span = CONTIG_LEN * (n * n_chunks) // CHR20_SITES
    lo = FIRST_POS + span * chunk // n_chunks
    hi = FIRST_POS + span * (chunk + 1) // n_chunks
    pos = lo + np.sort(rng.choice(hi - lo, n, replace=False))  # 1-based
    vtype, n_alt, alleles, alen, ids, idlen = _draw_sites(rng, n, shape)
    g = _draw_alleles(rng, n, n_alt, shape)
    n_hap = g.shape[1]
    f = {"pos": pos.astype(np.int64), "vtype": vtype, "n_alt": n_alt,
         "alleles": alleles, "alen": alen, "ids": ids, "idlen": idlen,
         "dp": np.clip(rng.normal(18500, 5500, n), 2000, 60000
                       ).astype(np.int64),
         "aa_case": rng.integers(0, 3, n)}
    # genotype forms the source never has (tests only)
    S = n_hap // 2
    a0, a1 = g[:, 0::2].copy(), g[:, 1::2].copy()
    ploidy = np.full((n, S), 2, np.int8)
    phased = np.ones((n, S), bool)
    if shape.missing:
        u = rng.random((n, S))
        a0[u < shape.missing * 0.75] = -1
        a1[(u < shape.missing * 0.75)
           | (u > 1 - shape.missing * 0.25)] = -1
    if shape.haploid:
        ploidy[rng.random((n, S)) < shape.haploid] = 1
    if shape.haploid_records:
        ploidy[rng.random(n) < shape.haploid_records] = 1
    if shape.unphased:
        phased = rng.random((n, S)) >= shape.unphased
    f.update(a0=a0, a1=a1, ploidy=ploidy, phased=phased)
    return f


# ---------------------------------------------------------------------------
# record bytes [SPEC 6.3]
# ---------------------------------------------------------------------------

T_INT8, T_INT16, T_INT32, T_FLOAT, T_CHAR = 1, 2, 3, 5, 7
INT8_EOV = 0x81


def _typed_ints(vals: np.ndarray, cnt: np.ndarray):
    """[n, k] left-aligned ints, ``cnt`` of them valid a row -> typed int
    vectors in the smallest type that holds a row's values."""
    n, k = vals.shape
    valid = np.arange(k)[None, :] < cnt[:, None]
    top = np.where(valid, np.abs(vals), 0).max(axis=1)
    typ = np.where(top <= 127, T_INT8, np.where(top <= 32767, T_INT16,
                                                T_INT32))
    out = np.zeros((n, 1 + 4 * k), np.uint8)
    out[:, 0] = (cnt << 4) | typ
    width = np.zeros(n, np.int64)
    for t, w, dt in ((T_INT8, 1, "i1"), (T_INT16, 2, "<i2"),
                     (T_INT32, 4, "<i4")):
        rows = np.flatnonzero(typ == t)
        out[rows, 1:1 + w * k] = np.ascontiguousarray(
            vals[rows].astype(dt)).view(np.uint8).reshape(rows.size, w * k)
        width[rows] = w
    return out, 1 + width * cnt


def _typed_floats(vals: np.ndarray, cnt: np.ndarray):
    n, k = vals.shape
    out = np.zeros((n, 1 + 4 * k), np.uint8)
    out[:, 0] = (cnt << 4) | T_FLOAT
    out[:, 1:] = np.ascontiguousarray(vals.astype("<f4")).view(
        np.uint8).reshape(n, 4 * k)
    return out, 1 + 4 * cnt


def _typed_str(mat: np.ndarray, ln: np.ndarray):
    """Typed char vectors; 15 or more characters carry their count as a
    typed int8 scalar after the descriptor."""
    n, w = mat.shape
    out = np.zeros((n, w + 3), np.uint8)
    short = ln < 15
    out[short, 0] = (ln[short] << 4) | T_CHAR
    out[short, 1:1 + w] = mat[short]
    out[~short, 0] = (15 << 4) | T_CHAR
    out[~short, 1] = (1 << 4) | T_INT8
    out[~short, 2] = ln[~short]
    out[~short, 3:] = mat[~short]
    return out, np.where(short, 1, 3) + ln


def _key(name: str, n: int, on: Optional[np.ndarray] = None):
    """A dictionary index as a typed int8 scalar, on the rows ``on``."""
    out = np.empty((n, 2), np.uint8)
    out[:, 0] = (1 << 4) | T_INT8
    out[:, 1] = STRINGS.index(name)
    ln = np.full(n, 2, np.int64)
    return out, ln if on is None else np.where(on, 2, 0)


def _const_str(values, pick: np.ndarray):
    w = max(len(v) for v in values)
    table = np.zeros((len(values), w), np.uint8)
    for i, v in enumerate(values):
        table[i, :len(v)] = np.frombuffer(v, np.uint8)
    return _typed_str(table[pick],
                      np.asarray([len(v) for v in values])[pick])


def allele_counts(f: dict):
    """Per site, from the allele arrays: copies of each ALT [n, 3], called
    alleles AN, samples with data NS."""
    a0, a1, two = f["a0"], f["a1"], f["ploidy"] == 2
    ac = np.stack([(a0 == k).sum(axis=1, dtype=np.int64)
                   + ((a1 == k) & two).sum(axis=1, dtype=np.int64)
                   for k in (1, 2, 3)], axis=1)
    an = (a0 >= 0).sum(axis=1, dtype=np.int64) \
        + ((a1 >= 0) & two).sum(axis=1, dtype=np.int64)
    ns = ((a0 >= 0) | ((a1 >= 0) & two)).sum(axis=1, dtype=np.int64)
    return ac, an, ns


def assemble(f: dict, shape: Shape = KGP3):
    """(uint8 bytes of the chunk's records, int64 start offset of each
    with the total appended).  Every field is a padded byte matrix and a
    length a record; one ragged concatenation lays the records out."""
    n = f["pos"].size
    S = f["a0"].shape[1]
    n_alt, vtype = f["n_alt"], f["vtype"]
    ac, an, ns = allele_counts(f)
    bounds = np.cumsum([0] + list(shape.pops))
    two = f["ploidy"] == 2

    fields = []
    fields.append(_typed_str(f["ids"], f["idlen"]))
    for k in range(4):
        mat, ln = _typed_str(f["alleles"][:, k], f["alen"][:, k])
        fields.append((mat, np.where(f["alen"][:, k] > 0, ln, 0)))
    fields.append((np.tile(np.array([(1 << 4) | T_INT8, 0], np.uint8),
                           (n, 1)), np.full(n, 2, np.int64)))   # PASS
    af = ac / np.maximum(an, 1)[:, None]
    info = {"AC": _typed_ints(ac, n_alt), "AF": _typed_floats(af, n_alt),
            "AN": _typed_ints(an[:, None], np.ones(n, np.int64)),
            "NS": _typed_ints(ns[:, None], np.ones(n, np.int64)),
            "DP": _typed_ints(f["dp"][:, None], np.ones(n, np.int64))}
    for name in _POP_AF_ORDER:
        p = [s for s, _ in SUPERPOPS].index(name)
        sub = np.zeros((n, 3))
        if p < len(shape.pops) and shape.pops[p]:
            sl = slice(bounds[p], bounds[p + 1])
            a0, a1, t = f["a0"][:, sl], f["a1"][:, sl], two[:, sl]
            sub_an = (a0 >= 0).sum(axis=1) + ((a1 >= 0) & t).sum(axis=1)
            sub = np.stack([(a0 == k).sum(axis=1)
                            + ((a1 == k) & t).sum(axis=1)
                            for k in (1, 2, 3)], axis=1) \
                / np.maximum(sub_an, 1)[:, None]
        info[name + "_AF"] = _typed_floats(np.round(sub, 4), n_alt)
    # ancestral allele: the REF base in one of three spellings, '?' for
    # an indel; structural records carry none
    ref0 = f["alleles"][:, 0, 0]
    aa = np.zeros((n, 4), np.uint8)
    aa[:, 0] = np.where(vtype == 1, ord("?"),
                        np.where(f["aa_case"] == 0, ref0,
                                 np.where(f["aa_case"] == 1, ref0 | 0x20,
                                          ord("."))))
    aa[:, 1:] = ord("|")
    info["AA"] = _typed_str(aa, np.full(n, 4, np.int64))
    info["VT"] = _const_str((b"SNP", b"INDEL", b"SV"), vtype)
    has_aa = vtype != 2
    for name in INFO_KEYS:
        on = has_aa if name == "AA" else None
        mat, ln = info[name]
        fields.append(_key(name, n, on))
        fields.append((mat, ln if on is None else np.where(on, ln, 0)))
    n_info = np.where(has_aa, len(INFO_KEYS), len(INFO_KEYS) - 1)

    # the per-sample block: GT key, one int8 vector of the record's ploidy
    width = np.where((f["ploidy"] == 2).any(axis=1), 2, 1)
    l_shared = 24 + sum(ln for _, ln in fields)
    l_indiv = 3 + S * width
    total = 8 + l_shared + l_indiv
    starts = np.concatenate([[0], np.cumsum(total)]).astype(np.int64)
    out = np.zeros(int(starts[-1]), np.uint8)

    fixed = np.zeros((n, 32), np.uint8)
    fixed[:, 0:4] = l_shared.astype("<u4").view(np.uint8).reshape(n, 4)
    fixed[:, 4:8] = l_indiv.astype("<u4").view(np.uint8).reshape(n, 4)
    fixed[:, 8:12] = np.full(n, CHROM_IDX, "<i4").view(np.uint8
                                                       ).reshape(n, 4)
    fixed[:, 12:16] = (f["pos"] - 1).astype("<i4").view(np.uint8
                                                        ).reshape(n, 4)
    rlen = np.where(vtype == 2, 1, f["alen"][:, 0])
    fixed[:, 16:20] = rlen.astype("<i4").view(np.uint8).reshape(n, 4)
    fixed[:, 20:24] = np.full(n, 100.0, "<f4").view(np.uint8
                                                    ).reshape(n, 4)
    fixed[:, 24:26] = n_info.astype("<u2").view(np.uint8).reshape(n, 2)
    fixed[:, 26:28] = (1 + n_alt).astype("<u2").view(np.uint8
                                                     ).reshape(n, 2)
    fixed[:, 28:32] = np.full(n, S | (1 << 24), "<u4").view(
        np.uint8).reshape(n, 4)
    at = starts[:-1].copy()
    for mat, ln in [(fixed, np.full(n, 32, np.int64))] + fields:
        j = np.arange(mat.shape[1])[None, :]
        keep = j < ln[:, None]
        out[(at[:, None] + j)[keep]] = mat[keep]
        at += ln
    gt_head = np.array([(1 << 4) | T_INT8, STRINGS.index("GT"), 0],
                       np.uint8)
    # GT [SPEC 6.3.3]: (allele + 1) << 1 | phased, 0 for a missing
    # allele, END_OF_VECTOR where a sample has fewer alleles than the
    # record's width; the first allele never carries the phase bit
    b0 = ((f["a0"].astype(np.int16) + 1) << 1).astype(np.uint8)
    b1 = (((f["a1"].astype(np.int16) + 1) << 1)
          | f["phased"]).astype(np.uint8)
    b1[f["ploidy"] == 1] = INT8_EOV
    gt2 = np.stack([b0, b1], axis=2).reshape(n, 2 * S)
    for i in range(n):
        p = int(at[i])
        gt_head[2] = (int(width[i]) << 4) | T_INT8
        out[p:p + 3] = gt_head
        if width[i] == 2:
            out[p + 3:p + 3 + 2 * S] = gt2[i]
        else:
            out[p + 3:p + 3 + S] = b0[i]
    return out, starts


def bgzf(data, level: int = 6) -> bytes:
    """``data`` as BGZF members [SPEC SAMv1 4.1] of at most 0xff00 payload
    bytes, by plain zlib (no end-of-file marker: see ``BGZF_EOF``)."""
    view = memoryview(data).cast("B")
    out = []
    for lo in range(0, len(view), 0xFF00):
        raw = view[lo:lo + 0xFF00]
        c = zlib.compressobj(level, zlib.DEFLATED, -15)
        body = c.compress(raw) + c.flush()
        out.append(b"\x1f\x8b\x08\x04\x00\x00\x00\x00\x00\xff\x06\x00BC"
                   b"\x02\x00" + struct.pack("<H", len(body) + 25) + body
                   + struct.pack("<II", zlib.crc32(raw), len(raw)))
    return b"".join(out)


BGZF_EOF = bytes.fromhex("1f8b08040000000000ff0600424302001b0003000000"
                         "000000000000")


# ---------------------------------------------------------------------------
# the answers
# ---------------------------------------------------------------------------

def _round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest, ties to even)."""
    u = np.ascontiguousarray(x.astype(np.float32)).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


class Reference:
    """What ``hbam vcf-stats`` must print, in int64/float64 over the
    generator's allele arrays: variants, SNPs (the verb's rule: REF one
    base and every ALT one base of ACGTN), PASS, called genotypes a sample
    (a genotype is called when it has an allele and none is missing) and
    the mean over variants with a call of non-REF alleles / (2 x called).
    ``sum_af_bf16`` is the same mean with each variant's ratio rounded to
    bfloat16: the reading one precision below the verb's, which the
    comparison has to refuse."""

    def __init__(self, n_samples: int = N_SAMPLES):
        self.n = self.snps = self.n_pass = self.n_af = 0
        self.sum_af = self.sum_af_bf16 = 0.0
        self.called = np.zeros(n_samples, np.int64)
        self.record_bytes = 0

    def add(self, f: dict, record_bytes: int = 0) -> None:
        n = f["pos"].size
        alen, alleles, n_alt = f["alen"], f["alleles"], f["n_alt"]
        snp = alen[:, 0] == 1
        for k in (1, 2, 3):
            on = n_alt >= k
            base_ok = np.isin(alleles[:, k, 0],
                              np.frombuffer(b"ACGTN", np.uint8))
            snp &= ~on | ((alen[:, k] == 1) & base_ok)
        a0, a1, two = f["a0"], f["a1"], f["ploidy"] == 2
        called = (a0 >= 0) & (~two | (a1 >= 0))
        alt = (a0 > 0).astype(np.int8) + ((a1 > 0) & two)
        n_called = called.sum(axis=1, dtype=np.int64)
        alt_sum = (alt * called).sum(axis=1, dtype=np.int64)
        has = n_called > 0
        af = alt_sum[has] / (2.0 * n_called[has])
        self.n += n
        self.snps += int(snp.sum())
        self.n_pass += n                       # FILTER is PASS throughout
        self.n_af += int(has.sum())
        self.sum_af += float(af.sum(dtype=np.float64))
        self.sum_af_bf16 += float(_round_bf16(af).sum(dtype=np.float64))
        self.called += called.sum(axis=0, dtype=np.int64)
        self.record_bytes += int(record_bytes)

    def merge(self, other: "Reference") -> None:
        for k in ("n", "snps", "n_pass", "n_af", "sum_af", "sum_af_bf16",
                  "record_bytes"):
            setattr(self, k, getattr(self, k) + getattr(other, k))
        self.called += other.called

    @property
    def mean_af(self) -> float:
        return self.sum_af / max(self.n_af, 1)

    @property
    def mean_af_bf16(self) -> float:
        return self.sum_af_bf16 / max(self.n_af, 1)

    def callrates(self):
        """Per sample, as the verb prints them (4 decimals)."""
        return [f"{c / max(self.n, 1):.4f}" for c in self.called]

    def wrong(self, printed: str, mean_af_tol: float):
        """``None`` when a scan's printed answer is the reference's, else
        what differs: counts and every call rate exactly as printed,
        ``mean_af`` within ``mean_af_tol``."""
        kv = dict(ln.split("\t", 1) for ln in printed.strip().splitlines())
        for key, want in (("variants", self.n), ("snps", self.snps),
                          ("pass", self.n_pass)):
            if int(kv.get(key, -1)) != want:
                return f"{key} {kv.get(key)} != reference {want}"
        got = [kv.get(f"callrate_{i}") for i in range(self.called.size)]
        want_cr = self.callrates()
        if got != want_cr:
            bad = [i for i, (g, w) in enumerate(zip(got, want_cr))
                   if g != w]
            return (f"{len(bad)} call rates differ, first callrate_"
                    f"{bad[0]} {got[bad[0]]} != reference "
                    f"{want_cr[bad[0]]}")
        if abs(float(kv["mean_af"]) - self.mean_af) > mean_af_tol:
            return (f"mean_af {kv['mean_af']} vs reference "
                    f"{self.mean_af:.9f} (tolerance {mean_af_tol})")
        return None


# ---------------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------------

def chunk_job(job):
    """One chunk, as a child process makes it: its records as BGZF bytes
    and its share of the reference."""
    seed, c, n_chunks, chunk_records, shape, level = job
    f = gen_fields(seed, c, n_chunks, chunk_records, shape)
    data, starts = assemble(f, shape)
    part = Reference(shape.n_samples)
    part.add(f, int(starts[-1]))
    return bgzf(data, level), part


def write_bcf(path: str, seed: int, n_chunks: int, chunk_records: int,
              ref: Reference, shape: Shape = KGP3, workers: int = 1,
              level: int = 6) -> int:
    """The coordinate-sorted BGZF BCF: the header in members of its own,
    every chunk's members, the end-of-file marker.  ``workers`` > 1 makes
    the chunks in spawned NumPy-only processes, in order.  Folds every
    chunk into ``ref`` and returns the file's size."""
    jobs = [(seed, c, n_chunks, chunk_records, shape, level)
            for c in range(n_chunks)]
    pool = None
    if workers > 1:
        import multiprocessing

        pool = multiprocessing.get_context("spawn").Pool(
            min(workers, n_chunks))
    try:
        with open(path, "wb") as fh:
            fh.write(bgzf(header_bytes(shape), level))
            for blob, part in (pool.imap(chunk_job, jobs) if pool
                               else map(chunk_job, jobs)):
                ref.merge(part)
                fh.write(blob)
            fh.write(BGZF_EOF)
            size = fh.tell()
    finally:
        if pool is not None:
            pool.terminate()
            pool.join()     # every worker has ended before set-up goes on
    return size
