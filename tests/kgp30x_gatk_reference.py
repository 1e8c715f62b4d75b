"""The plain reference of the ``kgp30x-chr20-gatk-vcfgz-x1`` deployment: a
seeded chr20 of the 1000 Genomes 30x call set on GRCh38 (NYGC) as GATK
writes it — HaplotypeCaller, GenotypeGVCFs, VQSR; one bgzip'd VCFv4.2 text
file, FORMAT ``GT:AD:DP:GQ:PL`` on every record, unphased, with no-call
cells and VQSR tranche names beside ``PASS`` in FILTER — and the answers
``hbam vcf-stats`` must give on it.

NumPy, zlib and the standard library only; nothing here imports the program
under test.  The sites and alleles are ``kgp3_reference.gen_fields``' (the
phase-3 generator, unedited) drawn for ``SHAPE``: 3,202 samples in five
super-populations, every genotype unphased, ``NOCALL_SHARE`` of them
missing, GATK's type and multi-allelic shares.  ``gatk_fields`` then makes
them what a joint caller writes: a no-call is whole (``./.``), the alleles
of a call are in ascending order, the last ALT of a share of the
multi-allelic sites is the ``*`` of a spanning deletion, and every cell
gets a depth, allele depths, likelihoods and a genotype quality from a
depth model (``assumed`` in ``benchmark/configs/kgp30x-chr20-gatk-vcfgz-x1
.json``).  A cell's keys after GT are a function of (ALTs, genotype, depth,
reads of the other allele), so they are rendered once a process into a
table (``cell_table``) and a chunk's sample block is one gather from it:
no Python a cell.  The fixed columns and GATK's seventeen INFO keys are
formatted a line at a time.  ``kgp3_reference.bgzf`` wraps the text into
BGZF members of 0xff00 payload bytes at zlib level 6, as ``bgzip`` does.

The answers are ``Reference``'s: ``kgp3_reference.Reference`` folded from
the allele arrays (never from parsing the text back), with ``pass`` counted
from the FILTER drawn and the no-call cells counted beside.
``benchmark/gen_kgp30x_gatk.py`` is a verbatim copy
(``tests/test_kgp30x_gatk.py`` holds the two together), which is why the
phase-3 generator is imported under either of its two names.
"""
from __future__ import annotations

import numpy as np

try:                               # beside tests/kgp3_reference.py ...
    import kgp3_reference as K
except ImportError:                # ... or beside benchmark/gen_kgp3.py
    from benchmark import gen_kgp3 as K

# ---------------------------------------------------------------------------
# the deployment's shape (1000 Genomes 30x, NYGC, GRCh38, chr20)
# ---------------------------------------------------------------------------

# the 2,504 phase-3 samples and 698 relatives, by population (assumed)
SUPERPOPS = (
    ("AFR", (("ACB", 116), ("ASW", 74), ("ESN", 149), ("GWD", 178),
             ("LWK", 99), ("MSL", 99), ("YRI", 178))),
    ("AMR", (("CLM", 132), ("MXL", 97), ("PEL", 122), ("PUR", 139))),
    ("EAS", (("CDX", 93), ("CHB", 103), ("CHS", 163), ("JPT", 104),
             ("KHV", 122))),
    ("EUR", (("CEU", 179), ("FIN", 99), ("GBR", 91), ("IBS", 157),
             ("TSI", 107))),
    ("SAS", (("BEB", 131), ("GIH", 103), ("ITU", 107), ("PJL", 146),
             ("STU", 114))),
)
NOCALL_SHARE = 0.0115              # cells ./. (assumed)
TYPE_SHARES = (0.86, 0.14, 0.0)    # SNP, indel, SV: none in this set
MULTI_SHARE = 0.06                 # sites with 2 or 3 ALTs (assumed)
STAR_SHARE = 0.35                  # of those, last ALT '*' (assumed)
SHAPE = K.Shape(tuple(sum(n for _, n in pops) for _, pops in SUPERPOPS),
                missing=NOCALL_SHARE, unphased=1.0, type_shares=TYPE_SHARES,
                multi_share=MULTI_SHARE)
N_SAMPLES = SHAPE.n_samples        # 3,202

CONTIG, CONTIG_LEN = "chr20", 64_444_167
CHR20_SITES = 2_600_000            # the source file's records (assumed)
FIRST_POS = 60_061                 # its first site (assumed)
GRCH38 = (("chr1", 248956422), ("chr2", 242193529), ("chr3", 198295559),
          ("chr4", 190214555), ("chr5", 181538259), ("chr6", 170805979),
          ("chr7", 159345973), ("chr8", 145138636), ("chr9", 138394717),
          ("chr10", 133797422), ("chr11", 135086622),
          ("chr12", 133275309), ("chr13", 114364328),
          ("chr14", 107043718), ("chr15", 101991189), ("chr16", 90338345),
          ("chr17", 83257441), ("chr18", 80373285), ("chr19", 58617616),
          ("chr20", 64444167), ("chr21", 46709983), ("chr22", 50818468),
          ("chrX", 156040895), ("chrY", 57227415), ("chrM", 16569))
# the analysis set's other sequences: unlocalized, unplaced, ALT, EBV,
# decoy, HLA (counts as hs38DH has them; names and lengths assumed)
N_RANDOM, N_UNPLACED, N_ALT, N_DECOY, N_HLA = 42, 127, 261, 2385, 525
FILTERS = (("VQSRTrancheSNP99.80to100.00", 0.05),
           ("VQSRTrancheSNP99.80to100.00+", 0.02),
           ("VQSRTrancheINDEL99.00to100.00", 0.09),
           ("VQSRTrancheINDEL99.00to100.00+", 0.03))
INFO_KEYS = ("AC", "AF", "AN", "BaseQRankSum", "DP", "ExcessHet", "FS",
             "InbreedingCoeff", "MLEAC", "MLEAF", "MQ", "MQRankSum", "QD",
             "ReadPosRankSum", "SOR", "VQSLOD", "culprit")
CULPRITS = ("FS", "MQ", "MQRankSum", "QD", "ReadPosRankSum", "SOR")

# the depth model (assumed): a sample's mean depth, a site's factor, a
# cell's reads; a homozygous call carries one read of another allele at
# HOM_ERR; a no-call is written bare (``./.``) at BARE_NOCALL, else with
# its keys (``./.:0,0:0:.:0,0,0``)
DEPTH_MEAN, DEPTH_SD, DEPTH_LO, DEPTH_HI = 34.0, 4.0, 24.0, 48.0
SITE_DEPTH_SHAPE = 20.0
D_MAX = 99
HOM_ERR = 0.04
BARE_NOCALL = 0.5
ERR = 0.001                        # per-read error of the likelihoods


def sample_names(shape: K.Shape = SHAPE):
    """Names in the source's style (HG00096, NA18486, ...), grouped by
    population; a cohort of another size gets plain HG names."""
    if shape.pops != SHAPE.pops:
        return [f"HG{96 + i:05d}" for i in range(shape.n_samples)]
    names, hg, na = [], 96, 6984
    for _, pops in SUPERPOPS:
        for pop, n in pops:
            for _ in range(n):
                if pop in K._NA_POPS:
                    names.append(f"NA{na:05d}")
                    na += 1
                else:
                    names.append(f"HG{hg:05d}")
                    hg += 1
    return names


def contigs():
    """(name, length) of the 3,366 sequences of GRCh38's analysis set with
    decoys and HLA, in its order."""
    rng = np.random.default_rng(38)
    out = list(GRCH38)
    primary = [c for c, _ in GRCH38[:24]]
    for kind, n, lo, hi in (("random", N_RANDOM, 2_000, 400_000),
                            ("unplaced", N_UNPLACED, 1_000, 200_000),
                            ("alt", N_ALT, 10_000, 2_000_000)):
        sizes = rng.integers(lo, hi, n)
        for i in range(n):
            if kind == "unplaced":
                out.append((f"chrUn_KI27{300 + i:04d}v1", int(sizes[i])))
            else:
                c = primary[i % len(primary)]
                num = (700 if kind == "random" else 800) + i
                out.append((f"{c}_KI27{num:04d}v1_{kind}", int(sizes[i])))
    out.append(("chrEBV", 171_823))
    sizes = rng.integers(1_000, 200_000, N_DECOY)
    out += [(f"chrUn_JTFH0100{i + 1:04d}v1_decoy", int(sizes[i]))
            for i in range(N_DECOY)]
    genes = ("A", "B", "C", "DQA1", "DQB1", "DRB1")
    sizes = rng.integers(2_500, 16_000, N_HLA)
    out += [(f"HLA-{genes[i % 6]}*{i // 6 % 90 + 1:02d}:{i % 97 + 1:02d}"
             f":01", int(sizes[i])) for i in range(N_HLA)]
    return out


_INFO_DEFS = (
    ("AC", "A", "Integer", "Allele count in genotypes, for each ALT allele, "
     "in the same order as listed"),
    ("AF", "A", "Float", "Allele Frequency, for each ALT allele, in the "
     "same order as listed"),
    ("AN", "1", "Integer", "Total number of alleles in called genotypes"),
    ("BaseQRankSum", "1", "Float", "Z-score from Wilcoxon rank sum test of "
     "Alt Vs. Ref base qualities"),
    ("DP", "1", "Integer", "Approximate read depth; some reads may have "
     "been filtered"),
    ("ExcessHet", "1", "Float", "Phred-scaled p-value for exact test of "
     "excess heterozygosity"),
    ("FS", "1", "Float", "Phred-scaled p-value using Fisher's exact test to "
     "detect strand bias"),
    ("InbreedingCoeff", "1", "Float", "Inbreeding coefficient as estimated "
     "from the genotype likelihoods"),
    ("MLEAC", "A", "Integer", "Maximum likelihood expectation (MLE) for the "
     "allele counts"),
    ("MLEAF", "A", "Float", "Maximum likelihood expectation (MLE) for the "
     "allele frequency"),
    ("MQ", "1", "Float", "RMS Mapping Quality"),
    ("MQRankSum", "1", "Float", "Z-score From Wilcoxon rank sum test of Alt "
     "vs. Ref read mapping qualities"),
    ("QD", "1", "Float", "Variant Confidence/Quality by Depth"),
    ("ReadPosRankSum", "1", "Float", "Z-score from Wilcoxon rank sum test "
     "of Alt vs. Ref read position bias"),
    ("SOR", "1", "Float", "Symmetric Odds Ratio of 2x2 contingency table to "
     "detect strand bias"),
    ("VQSLOD", "1", "Float", "Log odds of being a true variant versus being "
     "false under the trained gaussian mixture model"),
    ("culprit", "1", "String", "The annotation which was the worst "
     "performing in the Gaussian mixture model"),
)


def header_text(shape: K.Shape = SHAPE) -> str:
    lines = ["##fileformat=VCFv4.2",
             '##FILTER=<ID=PASS,Description="All filters passed">',
             '##FILTER=<ID=LowQual,Description="Low quality">']
    lines += [f'##FILTER=<ID={name},Description="Truth sensitivity tranche '
              f'level for {"SNP" if "SNP" in name else "INDEL"} model">'
              for name, _ in FILTERS]
    lines += ['##FORMAT=<ID=AD,Number=R,Type=Integer,Description="Allelic '
              'depths for the ref and alt alleles in the order listed">',
              '##FORMAT=<ID=DP,Number=1,Type=Integer,Description="Approximate'
              ' read depth (reads with MQ=255 or with bad mates are '
              'filtered)">',
              '##FORMAT=<ID=GQ,Number=1,Type=Integer,Description="Genotype '
              'Quality">',
              '##FORMAT=<ID=GT,Number=1,Type=String,Description="Genotype">',
              '##FORMAT=<ID=PL,Number=G,Type=Integer,Description="Normalized,'
              ' Phred-scaled likelihoods for genotypes as defined in the VCF '
              'specification">',
              '##GATKCommandLine=<ID=ApplyVQSR,CommandLine="ApplyVQSR '
              '--truth-sensitivity-filter-level 99.8 --mode SNP",'
              'Version="4.1.0.0">']
    lines += [f'##INFO=<ID={k},Number={num},Type={typ},Description="{d}">'
              for k, num, typ, d in _INFO_DEFS]
    lines += [f"##contig=<ID={c},length={ln}>" for c, ln in contigs()]
    lines += ["##reference=file:///GRCh38_full_analysis_set_plus_decoy_hla.fa",
              "##source=ApplyVQSR",
              "\t".join(["#CHROM", "POS", "ID", "REF", "ALT", "QUAL",
                         "FILTER", "INFO", "FORMAT"] + sample_names(shape))]
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# the cells' keys after GT
# ---------------------------------------------------------------------------

_TRI = (D_MAX + 1) * (D_MAX + 2) // 2      # (depth, other reads) pairs
_TABLES: dict = {}


def genotypes(k: int):
    """The diploid genotypes of a site of ``k`` ALTs in the VCF's PL order
    (a <= b, ordered by b then a)."""
    return [(a, b) for b in range(k + 1) for a in range(b + 1)]


def cell_table(k: int):
    """Every ``:AD:DP:GQ:PL`` tail and its tab a cell of a site of ``k``
    ALTs can have: ([E, W] u8 padded, [E] lengths).  Entry ``g * _TRI +
    d (d + 1) / 2 + x`` is genotype ``g`` at depth ``d`` with ``x`` reads
    of its other allele (the ALT of a het, a read error of a homozygote),
    ``d - x`` of its first; PL from those reads at ERR a read, GQ the
    second-smallest PL capped at 99.  The last two are a no-call's: bare,
    then with its keys."""
    if k in _TABLES:
        return _TABLES[k]
    gts = genotypes(k)
    d = np.repeat(np.arange(D_MAX + 1), np.arange(1, D_MAX + 2))
    x = np.arange(_TRI) - d * (d + 1) // 2
    ad = np.zeros((len(gts), _TRI, k + 1), np.int64)
    for g, (a, b) in enumerate(gts):
        other = b if a != b else int(a == 0)
        ad[g, :, a] += d - x
        ad[g, :, other] += x
    q = np.full((k + 1, k + 1), ERR / k)
    np.fill_diagonal(q, 1 - ERR)
    logp = np.log10(np.stack([(q[a] + q[b]) / 2 for a, b in gts], axis=1))
    ll = ad @ logp                                       # [G, TRI, G]
    pl = np.rint(-10 * (ll - ll.max(axis=2, keepdims=True))).astype(np.int64)
    gq = np.minimum(np.sort(pl, axis=2)[:, :, 1], 99)
    texts = [":%s:%d:%d:%s\t" % (",".join(map(str, ad[g, t])), d[t],
                                  gq[g, t], ",".join(map(str, pl[g, t])))
             for g in range(len(gts)) for t in range(_TRI)]
    texts += ["\t", ":%s:0:.:%s\t" % (",".join(["0"] * (k + 1)),
                                       ",".join(["0"] * len(gts)))]
    raw = np.frombuffer("".join(texts).encode(), np.uint8)
    lens = np.array([len(t) for t in texts], np.int64)
    mat = np.zeros((lens.size, int(lens.max())), np.uint8)
    row = np.repeat(np.arange(lens.size), lens)
    mat[row, np.arange(raw.size) - np.repeat(np.cumsum(lens) - lens, lens)] \
        = raw
    _TABLES[k] = (mat, lens)
    return _TABLES[k]


# ---------------------------------------------------------------------------
# field arrays of one chunk
# ---------------------------------------------------------------------------

def _sample_depth(seed: int, n_samples: int) -> np.ndarray:
    """A sample's mean depth: one draw a run, the same in every chunk."""
    rng = np.random.default_rng([seed, 1 << 32])
    return np.clip(rng.normal(DEPTH_MEAN, DEPTH_SD, n_samples), DEPTH_LO,
                   DEPTH_HI)


def gatk_fields(seed: int, chunk: int, n_chunks: int, n: int,
                shape: K.Shape = SHAPE) -> dict:
    """``kgp3_reference.gen_fields``' chunk, as a joint caller writes it:
    whole no-calls, ascending alleles, '*' ALTs, GRCh38 positions, and a
    cell's depth ``d`` / other-allele reads ``x`` / no-call spelling
    ``bare``, the site's FILTER and INFO draws."""
    f = K.gen_fields(seed, chunk, n_chunks, n, shape)
    rng = np.random.default_rng([seed, chunk, 30])
    a0, a1 = f["a0"], f["a1"]
    miss = (a0 < 0) | (a1 < 0)
    f["a0"] = np.where(miss, -1, np.minimum(a0, a1)).astype(np.int8)
    f["a1"] = np.where(miss, -1, np.maximum(a0, a1)).astype(np.int8)
    # the last ALT of a share of the multi-allelic sites: '*'
    star = np.flatnonzero((f["n_alt"] >= 2) & (rng.random(n) < STAR_SHARE))
    last = f["n_alt"][star]
    f["alleles"][star, last, :] = 0
    f["alleles"][star, last, 0] = ord("*")
    f["alen"][star, last] = 1
    # the phase-3 generator's positions at this set's density on GRCh38
    scale = (CONTIG_LEN / CHR20_SITES) / (K.CONTIG_LEN / K.CHR20_SITES)
    f["pos"] = FIRST_POS + np.floor(
        (f["pos"] - K.FIRST_POS) * scale).astype(np.int64)
    # depth: a sample's mean x a site's factor, Poisson a cell (as its
    # normal approximation, which draws ~5x faster at these means)
    S = f["a0"].shape[1]
    site = rng.gamma(SITE_DEPTH_SHAPE, 1 / SITE_DEPTH_SHAPE, n)
    lam = (site[:, None] * _sample_depth(seed, S)[None, :]).astype(np.float32)
    d = np.clip(np.rint(lam + np.sqrt(lam) * rng.standard_normal(
        lam.shape, dtype=np.float32)), 1, D_MAX).astype(np.int64)
    miss = f["a0"] < 0
    het = f["a0"] != f["a1"]
    x = (rng.random(d.shape, dtype=np.float32) < HOM_ERR).astype(np.int64)
    x[het] = rng.binomial(d[het], 0.5)
    d[miss] = x[miss] = 0
    f["d"], f["x"] = d, x
    f["bare"] = np.zeros(d.shape, bool)
    f["bare"][miss] = rng.random(int(miss.sum())) < BARE_NOCALL
    # FILTER: PASS or a VQSR tranche of the site's model
    u = rng.random(n)
    snp = f["vtype"] == 0
    filt = np.zeros(n, np.int64)                 # 0 PASS, 1 + FILTERS index
    for model in (True, False):
        lo = 0.0
        for i, (name, share) in enumerate(FILTERS):
            if ("SNP" in name) == model:
                filt[(snp == model) & (u >= lo) & (u < lo + share)] = 1 + i
                lo += share
    f["filter"] = filt
    f["pass"] = filt == 0
    f["info_draw"] = rng.random((n, 8))
    return f


# ---------------------------------------------------------------------------
# the text
# ---------------------------------------------------------------------------

def _g(x: float) -> str:
    """A double as GATK writes one: 3 decimals, or 3 significant digits
    under 0.001."""
    return f"{x:.3e}" if 0 < x < 1e-3 else f"{x:.3f}"


def fixed_columns(f: dict):
    """Per record, the line up to and with the tab after FORMAT."""
    n = f["pos"].size
    n_alt, vtype = f["n_alt"], f["vtype"]
    ac, an, _ns = K.allele_counts(f)
    af = ac / np.maximum(an, 1)[:, None]
    dp = f["d"].sum(axis=1)
    has_het = (f["a0"] != f["a1"]).any(axis=1)
    r = f["info_draw"]
    qual = np.maximum(ac.sum(axis=1), 1) * 30.0 * np.exp(r[:, 0] - 0.5) \
        * np.where(f["pass"], 1.0, 0.2)
    vqslod = np.where(f["pass"], 8.0, -6.0) + 8 * (r[:, 7] - 0.5)
    names = ("PASS",) + tuple(name for name, _ in FILTERS)
    out = []
    for i in range(n):
        k = int(n_alt[i])
        alleles = [bytes(f["alleles"][i, a, :f["alen"][i, a]]).decode()
                   for a in range(1 + k)]
        acs = ",".join(str(int(v)) for v in ac[i, :k])
        afs = ",".join(_g(v) for v in af[i, :k])
        vals = {"AC": acs, "AF": afs, "AN": str(int(an[i])),
                "DP": str(int(dp[i])),
                "ExcessHet": f"{3.0103 + 40 * r[i, 1] ** 8:.4f}",
                "FS": f"{-2 * np.log(1 - r[i, 2] * 0.999):.3f}",
                "InbreedingCoeff": f"{(r[i, 3] - 0.5) * 0.08:.4f}",
                "MLEAC": acs, "MLEAF": afs,
                "MQ": f"{60 - 6 * r[i, 4] ** 4:.2f}",
                "QD": f"{2 + 33 * r[i, 5]:.2f}",
                "SOR": f"{0.2 + 2 * r[i, 6] ** 2:.3f}",
                "VQSLOD": f"{vqslod[i]:.2f}",
                "culprit": CULPRITS[int(r[i, 7] * 1e6) % len(CULPRITS)]}
        if has_het[i]:
            vals["BaseQRankSum"] = f"{(r[i, 1] - 0.5) * 3:.3f}"
            vals["MQRankSum"] = f"{(r[i, 2] - 0.5) * 0.8:.3f}"
            vals["ReadPosRankSum"] = f"{(r[i, 3] - 0.5) * 2.5:.3f}"
        info = ";".join(f"{key}={vals[key]}" for key in INFO_KEYS
                        if key in vals)
        out.append("\t".join([
            CONTIG, str(int(f["pos"][i])), ".", alleles[0],
            ",".join(alleles[1:]), f"{qual[i]:.2f}",
            names[int(f["filter"][i])], info, "GT:AD:DP:GQ:PL", ""]).encode())
    return out


_LINES_A_GATHER = 128


def sample_blocks(f: dict):
    """Per record, its sample cells and the newline: ``a/b`` (``.`` for a
    missing allele) and the cell's tail from ``cell_table``, gathered a
    slab of lines of one ALT count at once."""
    n = f["pos"].size
    blocks = [None] * n
    for k in (1, 2, 3):
        rows_k = np.flatnonzero(f["n_alt"] == k)
        if not rows_k.size:
            continue
        table, lens = cell_table(k)
        n_gt = len(genotypes(k))
        for lo in range(0, rows_k.size, _LINES_A_GATHER):
            rows = rows_k[lo:lo + _LINES_A_GATHER]
            a0 = f["a0"][rows].astype(np.int64)
            a1 = f["a1"][rows].astype(np.int64)
            d, x = f["d"][rows], f["x"][rows]
            miss = (a0 < 0) | (a1 < 0)
            idx = np.where(miss, n_gt * _TRI + ~f["bare"][rows],
                           (a1 * (a1 + 1) // 2 + a0) * _TRI
                           + d * (d + 1) // 2 + x)
            cell = np.empty(idx.shape + (3 + table.shape[1],), np.uint8)
            cell[:, :, 0] = np.where(a0 < 0, ord("."), a0 + ord("0"))
            cell[:, :, 1] = ord("/")
            cell[:, :, 2] = np.where(a1 < 0, ord("."), a1 + ord("0"))
            cell[:, :, 3:] = table[idx]
            width = 3 + lens[idx]
            flat = cell[np.arange(cell.shape[2]) < width[:, :, None]]
            ends = np.cumsum(width.sum(axis=1))
            flat[ends - 1] = ord("\n")
            for r, b in zip(rows.tolist(), np.split(flat, ends[:-1])):
                blocks[r] = b
    return blocks


def assemble(f: dict) -> np.ndarray:
    """uint8 text of the chunk's record lines."""
    pieces = []
    for head, block in zip(fixed_columns(f), sample_blocks(f)):
        pieces += [np.frombuffer(head, np.uint8), block]
    return np.concatenate(pieces)


# ---------------------------------------------------------------------------
# the answers
# ---------------------------------------------------------------------------

class Reference(K.Reference):
    """``kgp3_reference.Reference`` with ``pass`` counted from the FILTER
    drawn, and the cells written as no-calls (``nocall_cells``): what the
    verb must print, and what the tokeniser's counters must read."""

    def __init__(self, n_samples: int = N_SAMPLES):
        super().__init__(n_samples)
        self.nocall_cells = 0

    def add(self, f: dict, record_bytes: int = 0) -> None:
        super().add(f, record_bytes)
        self.n_pass += int(f["pass"].sum()) - f["pos"].size
        self.nocall_cells += int(((f["a0"] < 0) | (f["a1"] < 0)).sum())

    def merge(self, other: "Reference") -> None:
        super().merge(other)
        self.nocall_cells += other.nocall_cells


# ---------------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------------

def chunk_job(job):
    """One chunk, as a child process makes it: its lines as BGZF bytes and
    its share of the reference (``record_bytes`` counts text bytes)."""
    seed, c, n_chunks, chunk_records, shape, level = job
    f = gatk_fields(seed, c, n_chunks, chunk_records, shape)
    text = assemble(f)
    part = Reference(shape.n_samples)
    part.add(f, int(text.size))
    return K.bgzf(text, level), part


def write_vcfgz(path: str, seed: int, n_chunks: int, chunk_records: int,
                ref: Reference, shape: K.Shape = SHAPE, workers: int = 1,
                level: int = 6) -> int:
    """The coordinate-sorted bgzip'd VCF: the header's members, every
    chunk's members, the end-of-file marker (no ``.tbi``).  ``workers`` > 1
    makes the chunks in spawned NumPy-only processes, in order.  Folds every
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
            fh.write(K.bgzf(header_text(shape).encode(), level))
            for blob, part in (pool.imap(chunk_job, jobs) if pool
                               else map(chunk_job, jobs)):
                ref.merge(part)
                fh.write(blob)
            fh.write(K.BGZF_EOF)
            size = fh.tell()
    finally:
        if pool is not None:
            pool.terminate()
            pool.join()     # every worker has ended before set-up goes on
    return size
