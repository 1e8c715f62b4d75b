"""The plain reference of the ``kgp3-chr20-vcfgz-x1`` deployment: the seeded
1000 Genomes phase-3 chr20-shaped call set of ``kgp3_reference`` (its
generator, unedited) written as the source ships it — bgzip'd VCFv4.1 text —
and the answers ``hbam vcf-stats`` must give on it.

NumPy, zlib and the standard library only; nothing here imports the program
under test.  ``gen_fields``' arrays are formatted as VCF lines by this file
itself ([SPEC] hts-specs VCFv4.1 section 1.4: eight fixed columns, FORMAT,
one column a sample): the fixed columns and the twelve INFO keys as
``key=value;...`` text a record, the ``[n, 2504, 4]`` genotype block
(``a|b`` and a tab a sample) written as bytes with no Python a genotype.  The
header is ``kgp3_reference.header_text``: the source's meta lines and the
2,504 sample names.  ``kgp3_reference.bgzf`` wraps the text into BGZF members
of 0xff00 payload bytes at zlib level 6, as ``bgzip`` does.  The answers are
``kgp3_reference.Reference``'s, folded from the generator's own allele arrays
and never from parsing the text back, so the BCF and the ``.vcf.gz`` of one
seed have ONE reference and must print the same lines.
``benchmark/gen_kgp3_vcf.py`` is a verbatim copy (``tests/test_kgp3_vcfgz.py``
holds the two together), which is why the generator is imported under either
of its two names.

What is set from memory of the source (``assumed`` in
``benchmark/configs/kgp3-chr20-vcfgz-x1.json``): ``AF`` with six significant
digits (``AF=0.000199681``), the population AFs rounded to four decimals and
trimmed (``EAS_AF=0``, ``SAS_AF=0.001``), comma lists at multi-allelic sites,
QUAL ``100``, no ``.tbi`` beside the file.

The genotype forms the source never has (``Shape.missing`` / ``haploid`` /
``unphased`` / ``haploid_records``: tests only) are written a line at a time
in Python: ``.`` for a missing allele, one allele for a haploid call, ``/``
for an unphased one.
"""
from __future__ import annotations

import numpy as np

try:                               # beside tests/kgp3_reference.py ...
    import kgp3_reference as K
except ImportError:                # ... or beside benchmark/gen_kgp3.py
    from benchmark import gen_kgp3 as K

GT_BYTES = 4                       # ``a|b`` and the tab (or the newline)


def _g(x: float) -> str:
    return f"{x:.6g}"


def fixed_columns(f: dict, shape: K.Shape = K.KGP3):
    """Per record, the line up to and with the tab after FORMAT: CHROM POS
    ID REF ALT QUAL FILTER INFO ``GT``."""
    n = f["pos"].size
    n_alt, vtype = f["n_alt"], f["vtype"]
    ac, an, ns = K.allele_counts(f)
    bounds = np.cumsum([0] + list(shape.pops))
    two = f["ploidy"] == 2
    af = ac / np.maximum(an, 1)[:, None]
    pop_af = {}
    for name in K._POP_AF_ORDER:
        p = [s for s, _ in K.SUPERPOPS].index(name)
        sub = np.zeros((n, 3))
        if p < len(shape.pops) and shape.pops[p]:
            sl = slice(bounds[p], bounds[p + 1])
            a0, a1, t = f["a0"][:, sl], f["a1"][:, sl], two[:, sl]
            sub_an = (a0 >= 0).sum(axis=1) + ((a1 >= 0) & t).sum(axis=1)
            sub = np.stack([(a0 == k).sum(axis=1)
                            + ((a1 == k) & t).sum(axis=1)
                            for k in (1, 2, 3)], axis=1) \
                / np.maximum(sub_an, 1)[:, None]
        pop_af[name] = np.round(sub, 4)
    ref0 = f["alleles"][:, 0, 0]
    out = []
    for i in range(n):
        k = int(n_alt[i])
        alleles = [bytes(f["alleles"][i, a, :f["alen"][i, a]]).decode()
                   for a in range(1 + k)]
        info = [f"AC={','.join(str(int(x)) for x in ac[i, :k])}",
                f"AF={','.join(_g(x) for x in af[i, :k])}",
                f"AN={int(an[i])}", f"NS={int(ns[i])}",
                f"DP={int(f['dp'][i])}"]
        info += [f"{name}_AF={','.join(_g(x) for x in pop_af[name][i, :k])}"
                 for name in K._POP_AF_ORDER]
        if vtype[i] != 2:          # structural records carry no AA
            aa = "?" if vtype[i] == 1 else \
                (chr(ref0[i]), chr(ref0[i] | 0x20), ".")[f["aa_case"][i]]
            info.append(f"AA={aa}|||")
        info.append(f"VT={('SNP', 'INDEL', 'SV')[vtype[i]]}")
        out.append("\t".join([
            K.CONTIG, str(int(f["pos"][i])),
            bytes(f["ids"][i, :f["idlen"][i]]).decode(), alleles[0],
            ",".join(alleles[1:]), "100", "PASS", ";".join(info),
            "GT", ""]).encode())
    return out


def genotype_block(f: dict) -> np.ndarray:
    """[n, S, 4] bytes: ``a|b`` and a tab a sample, a newline after the
    last — every genotype of a diploid, phased, complete record."""
    n, S = f["a0"].shape
    block = np.empty((n, S, GT_BYTES), np.uint8)
    block[:, :, 0] = f["a0"] + ord("0")
    block[:, :, 1] = np.where(f["phased"], ord("|"), ord("/"))
    block[:, :, 2] = f["a1"] + ord("0")
    block[:, :, 3] = ord("\t")
    block[:, -1, 3] = ord("\n")
    return block


def _odd_genotypes(f: dict, i: int) -> bytes:
    """One record's sample columns where a genotype is missing, haploid
    or half-missing (tests only)."""
    cells = []
    for a0, a1, p, ph in zip(f["a0"][i], f["a1"][i], f["ploidy"][i],
                             f["phased"][i]):
        first = "." if a0 < 0 else str(int(a0))
        if p == 1:
            cells.append(first)
        else:
            cells.append(first + ("|" if ph else "/")
                         + ("." if a1 < 0 else str(int(a1))))
    return ("\t".join(cells) + "\n").encode()


def assemble(f: dict, shape: K.Shape = K.KGP3) -> np.ndarray:
    """uint8 text of the chunk's record lines."""
    n, S = f["a0"].shape
    head = fixed_columns(f, shape)
    regular = ((f["ploidy"] == 2) & (f["a0"] >= 0) & (f["a1"] >= 0)
               ).all(axis=1)
    odd = {int(i): _odd_genotypes(f, int(i))
           for i in np.flatnonzero(~regular)}
    block = genotype_block(f).reshape(n, S * GT_BYTES)
    size = sum(len(h) for h in head) + int(regular.sum()) * S * GT_BYTES \
        + sum(len(v) for v in odd.values())
    out = np.empty(size, np.uint8)
    p = 0
    for i in range(n):
        h = head[i]
        out[p:p + len(h)] = np.frombuffer(h, np.uint8)
        p += len(h)
        tail = block[i] if regular[i] else np.frombuffer(odd[i], np.uint8)
        out[p:p + tail.size] = tail
        p += tail.size
    assert p == size
    return out


def chunk_job(job):
    """One chunk, as a child process makes it: its lines as BGZF bytes and
    its share of the reference (``record_bytes`` counts text bytes)."""
    seed, c, n_chunks, chunk_records, shape, level = job
    f = K.gen_fields(seed, c, n_chunks, chunk_records, shape)
    text = assemble(f, shape)
    part = K.Reference(shape.n_samples)
    part.add(f, int(text.size))
    return K.bgzf(text, level), part


def write_vcfgz(path: str, seed: int, n_chunks: int, chunk_records: int,
                ref: K.Reference, shape: K.Shape = K.KGP3, workers: int = 1,
                level: int = 6) -> int:
    """The coordinate-sorted bgzip'd VCF: the header's members, every
    chunk's members, the end-of-file marker (no ``.tbi``).  ``workers`` > 1
    makes the chunks in spawned NumPy-only processes, in order.  Folds every
    chunk into ``ref`` — the same sites as ``kgp3_reference.write_bcf`` makes
    of the seed — and returns the file's size."""
    jobs = [(seed, c, n_chunks, chunk_records, shape, level)
            for c in range(n_chunks)]
    pool = None
    if workers > 1:
        import multiprocessing

        pool = multiprocessing.get_context("spawn").Pool(
            min(workers, n_chunks))
    try:
        with open(path, "wb") as fh:
            fh.write(K.bgzf(K.header_text(shape).encode(), level))
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
