"""The ``kgp30x-chr20-gatk-vcfgz-x1`` deployment on the CPU: a seeded chr20
of the 1000 Genomes 30x call set as GATK writes it
(tests/kgp30x_gatk_reference.py: FORMAT ``GT:AD:DP:GQ:PL``, unphased,
no-calls, VQSR filters, 3,202 samples) through ``hbam vcf-stats`` against
the plain reference, and the native tokeniser's keyed walk against the
scalar parse, line for line.

The chip compares the same things at the configured size
(benchmark/runners/gatk_text_scan.py); here the sizes are small and the
timings mean nothing.
"""
from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import zlib

import numpy as np
import pytest

import kgp30x_gatk_reference as G
from test_kgp3_vcfstats import run_cli

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "kgp30x-chr20-gatk-vcfstats"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "kgp30x-chr20-gatk-vcfgz-x1.json"),
          encoding="utf-8") as _fh:
    CONFIG = json.load(_fh)
TOL = CONFIG["mean_af_tolerance"]
SEED = 3_000_000_019
# a small cohort of the deployment's genotype forms, for the line fuzz
SMALL = G.SHAPE._replace(pops=(9, 8, 8, 8, 7))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The configuration's ``tiny`` file: 2 x 256 lines, 3,202 samples."""
    path = str(tmp_path_factory.mktemp("kgp30x") / "tiny.vcf.gz")
    ref = G.Reference()
    size = G.write_vcfgz(path, SEED, CONFIG["tiny"]["chunks"],
                         CONFIG["tiny"]["chunk_records"], ref)
    assert size == os.path.getsize(path)
    return path, ref


def _inflate(path: str) -> bytes:
    with open(path, "rb") as fh:
        raw = fh.read()
    out, p = bytearray(), 0
    while p < len(raw):
        size = int.from_bytes(raw[p + 16:p + 18], "little") + 1
        out += zlib.decompress(raw[p + 18:p + size - 8], -15)
        p += size
    return bytes(out)


# -- the shape ---------------------------------------------------------------

def test_the_configuration_is_the_generators():
    shape = CONFIG["shape"]
    assert G.N_SAMPLES == shape["samples"] == 3202
    assert sum(len(p) for _, p in G.SUPERPOPS) == shape["populations"] == 26
    assert dict(zip((s for s, _ in G.SUPERPOPS), G.SHAPE.pops)) \
        == shape["super_populations"]
    assert (G.CONTIG, G.CONTIG_LEN) == (shape["contig"],
                                        shape["contig_length"])
    assert dict(G.GRCH38)["chr20"] == G.CONTIG_LEN
    assert G.SHAPE.unphased == 1.0 and G.SHAPE.missing > 0
    assert list(CONFIG["reduced"]) == ["records"]
    sizes = CONFIG["sizes"]
    assert sizes["chunks"] * sizes["chunk_records"] == 1 << 17
    assert CONFIG["source_records"] == G.CHR20_SITES \
        == CONFIG["assumed"]["chr20_sites"]
    assert CONFIG["assumed"]["first_site"] == G.FIRST_POS
    assert len(G.contigs()) == 3366
    assert len(set(c for c, _ in G.contigs())) == 3366
    names = G.sample_names()
    assert len(names) == len(set(names)) == 3202


def test_the_file_is_what_gatk_writes(tiny):
    """Plain zlib reads the members back to VCFv4.2 text: GATK's header
    (3,366 contigs, 3,202 names), then one line a site, sorted, FORMAT
    ``GT:AD:DP:GQ:PL``, every call unphased with its alleles ascending,
    AD one value an allele, PL one a genotype, no-calls bare or keyed,
    VQSR tranches beside PASS, and the '*' ALT."""
    path, ref = tiny
    text = _inflate(path)
    head = G.header_text().encode()
    assert text.startswith(head) and head.startswith(b"##fileformat=VCFv4.2")
    assert head.count(b"\n##contig=<ID=") == 3366
    lines = text[len(head):].split(b"\n")
    assert lines.pop() == b"" and len(lines) == ref.n == 512
    assert len(text) - len(head) == ref.record_bytes
    pos, filters, bare, keyed_nocall, stars = [], set(), 0, 0, 0
    for ln in lines:
        parts = ln.split(b"\t")
        assert len(parts) == 9 + 3202 and parts[0] == b"chr20"
        assert parts[8] == b"GT:AD:DP:GQ:PL"
        float(parts[5])
        filters.add(parts[6])
        alts = parts[4].split(b",")
        stars += b"*" in alts
        k = len(alts)
        keys = [kv.split(b"=")[0].decode() for kv in parts[7].split(b";")]
        assert keys == [key for key in G.INFO_KEYS if key in keys]
        assert set(G.INFO_KEYS) - set(keys) <= {
            "BaseQRankSum", "MQRankSum", "ReadPosRankSum"}
        for cell in parts[9::37]:
            f = cell.split(b":")
            if f[0] == b"./.":
                bare += len(f) == 1
                keyed_nocall += len(f) == 5
                continue
            a, b = f[0].split(b"/")
            assert int(a) <= int(b) <= k
            assert len(f[1].split(b",")) == k + 1
            assert len(f[4].split(b",")) == (k + 1) * (k + 2) // 2
            assert sum(map(int, f[1].split(b","))) == int(f[2])
        pos.append(int(parts[1]))
    assert pos == sorted(pos) and G.FIRST_POS <= pos[0]
    assert b"PASS" in filters and filters - {b"PASS"} \
        and filters <= {b"PASS"} | {n.encode() for n, _ in G.FILTERS}
    assert bare and keyed_nocall and stars
    # ~83 KB a line, deflating ~11x as a call set's keyed text does
    assert abs(ref.record_bytes / ref.n - CONFIG["shape"]["mean_line_bytes"]) \
        < 0.02 * CONFIG["shape"]["mean_line_bytes"]
    assert 8 < len(text) / os.path.getsize(path) < 16


def test_same_seed_same_bytes_and_the_benchmarks_copy_is_verbatim():
    import hashlib

    def digest(seed):
        blob, part = G.chunk_job((seed, 1, 2, 16, G.SHAPE, 6))
        return hashlib.sha256(blob).hexdigest(), part.mean_af, \
            part.nocall_cells

    assert digest(2_147_483_999) == digest(2_147_483_999)
    assert digest(2_147_483_999) != digest(2_147_484_000)
    with open(os.path.join(ROOT, "tests", "kgp30x_gatk_reference.py"),
              "rb") as a, \
            open(os.path.join(ROOT, "benchmark", "gen_kgp30x_gatk.py"),
                 "rb") as b:
        assert a.read() == b.read()


# -- hbam vcf-stats on the file -----------------------------------------------

def test_vcf_stats_prints_the_references_answer(tiny):
    """Exact counts of variants, SNPs ('*' is no base) and PASS (FILTER
    exactly ``PASS``), every call rate (a no-call is not called)."""
    path, ref = tiny
    out = run_cli(["vcf-stats", path])
    assert ref.wrong(out, TOL["printed"]) is None
    kv = dict(ln.split("\t") for ln in out.strip().splitlines())
    assert int(kv["variants"]) == 512
    assert 0 < ref.n_pass < ref.n and 0 < ref.snps < ref.n
    rates = [kv[f"callrate_{i}"] for i in range(3202)]
    assert rates == ref.callrates() and len(set(rates)) > 1
    assert ref.wrong(out.replace(f"pass\t{ref.n_pass}",
                                 f"pass\t{ref.n}"), TOL["printed"])


def test_unrounded_mean_af_within_the_limit_and_bfloat16_refused(tiny):
    from hadoop_bam_tpu.parallel.distributed import distributed_variant_stats

    path, ref = tiny
    stats = distributed_variant_stats(path)
    assert abs(stats["mean_af"] - ref.mean_af) <= TOL["unrounded"]
    assert abs(ref.mean_af_bf16 - ref.mean_af) > TOL["unrounded"]
    assert stats["n_af"] == ref.n_af == ref.n


def test_the_scan_counts_keyed_records_and_no_calls_exactly(tiny):
    """Every line of the file read by the keyed walk, none by the scalar
    parse, and the no-call cells the generator wrote."""
    from hadoop_bam_tpu.utils.metrics import base_metrics

    path, ref = tiny
    base_metrics().reset()
    run_cli(["vcf-stats", path])
    c = base_metrics().snapshot()["counters"]
    assert c["pipeline.records"] == ref.n
    assert c["vcf.text_keyed_records"] == c["vcf.text_bulk_records"] == ref.n
    assert c["vcf.text_scalar_records"] == 0
    assert c["vcf.text_nocall_cells"] == ref.nocall_cells > 0
    assert c["vcf.inflated_bytes"] \
        == ref.record_bytes + len(G.header_text().encode())


# -- the keyed walk against the scalar parse ----------------------------------

def _gatk_lines(rng, n_lines: int, shape) -> list:
    """The generator's lines of ``shape``, each irregular kind mixed in:
    half-missing calls (``./1``, ``1/.``), a multi-digit allele, a haploid
    call, too few and too many cells, a trailing empty cell, a ``\\r``
    line end, GT-only lines and sites-only lines among the keyed ones."""
    f = G.gatk_fields(rng.randrange(1 << 31), 0, 1, n_lines, shape)
    half = np.random.default_rng(rng.randrange(1 << 31)).random(
        f["a0"].shape) < 0.01
    f["a0"] = np.where(half & (f["a0"] >= 0), -1, f["a0"]).astype(np.int8)
    lines = G.assemble(f).tobytes().decode().split("\n")[:-1]
    for i, ln in enumerate(lines):
        parts = ln.split("\t")
        cells, at = parts[9:], rng.randrange(shape.n_samples)
        kind = rng.choice(["keyed"] * 8 + [
            "multi-digit", "haploid", "short", "long", "empty", "cr",
            "cr-bare", "gt-only", "gt-only-cr", "sites-only", "one-half"])
        if kind == "multi-digit":
            cells[at] = "10/1" + cells[at][3:]
        elif kind == "haploid":
            cells[at] = "1" + cells[at][3:]
        elif kind == "short":
            cells = cells[:at]
        elif kind == "long":
            cells.append(cells[at])
        elif kind == "empty":
            cells[-1] = ""
        elif kind == "cr":
            cells[-1] += "\r"
        elif kind == "cr-bare":
            cells[-1] = "./.\r"
        elif kind.startswith("gt-only"):
            parts[8] = "GT"
            cells = [c.split(":")[0].replace(".", "0") for c in cells]
            if kind == "gt-only-cr":
                cells[-1] += "\r"
        elif kind == "one-half":
            cells[at] = "1/." + cells[at][3:]
        lines[i] = "\t".join(parts[:9] + cells if kind != "sites-only"
                             else parts[:8])
    return lines


def _header(shape):
    from hadoop_bam_tpu.formats.vcf import VCFHeader

    return VCFHeader.from_text(G.header_text(shape))


@pytest.mark.parametrize("twin", [False, True], ids=["native", "numpy"])
@pytest.mark.parametrize("shape,n_lines", [(SMALL, 400), (G.SHAPE, 60)],
                         ids=["40-samples", "3202-samples"])
def test_keyed_walk_matches_the_scalar_parse(shape, n_lines, twin,
                                             monkeypatch):
    """GATK lines with every irregular kind mixed in, keyed and GT-only
    lines in one span, with a trailing newline and without: the bulk
    tokeniser and its fallback give the scalar parse's arrays, the keyed
    records are the keyed lines whose every GT reads, and the no-calls
    counted are theirs."""
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        VariantGeometry, _pack_variant_tiles_from_text_scalar,
        pack_variant_tiles_from_text,
    )
    from hadoop_bam_tpu.utils import native
    from hadoop_bam_tpu.utils.metrics import base_metrics

    if twin:
        monkeypatch.setattr(native, "load", lambda: None)
    elif native.load() is None:
        pytest.skip("no native library on this host")
    lines = _gatk_lines(random.Random(46 + n_lines), n_lines, shape)
    header = _header(shape)
    geom = VariantGeometry(n_samples=shape.n_samples)
    text = ("\n".join(lines) + "\n").encode()
    want = _pack_variant_tiles_from_text_scalar(text, header, geom)
    assert want["chrom"].shape[0] == n_lines
    assert (want["dosage"] == -1).any() and (want["dosage"] == 2).any()
    # what the keyed walk must take: FORMAT GT:..., exactly S cells, and
    # every GT one it reads
    ok_gt = {f"{a}/{b}" for a in "0123." for b in "0123."} | {"."}
    keyed = [ln for ln in lines
             if ln.split("\t")[8:9] == ["GT:AD:DP:GQ:PL"]
             and len(ln.split("\t")) == 9 + shape.n_samples
             and all(c.split(":")[0] in ok_gt for c in ln.split("\t")[9:])]
    nocalls = sum("." in c.split(":")[0]
                  for ln in keyed for c in ln.split("\t")[9:])
    for cut in (0, 1):          # with the trailing newline, then without
        base_metrics().reset()
        got = pack_variant_tiles_from_text(memoryview(text)[:len(text) - cut],
                                           header, geom)
        for k in want:
            assert want[k].dtype == got[k].dtype, k
            assert np.array_equal(want[k], got[k]), k
        c = base_metrics().snapshot()["counters"]
        assert c["vcf.text_bulk_records"] + c["vcf.text_scalar_records"] \
            == n_lines
        assert c.get("vcf.text_keyed_records", 0) \
            == (0 if twin else len(keyed))
        assert c.get("vcf.text_nocall_cells", 0) == (0 if twin else nocalls)
    if not twin:                # both paths are really taken
        assert len(keyed) > n_lines // 3
        assert c["vcf.text_scalar_records"] > n_lines // 4


def test_a_keyed_line_sent_to_the_scalar_parse_leaves_the_counts():
    """A keyed line the walk reads but whose ALT is wider than the fixed
    fields' gather goes to the scalar parse: it is then no keyed record,
    and its no-calls are not counted."""
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        VariantGeometry, _pack_variant_tiles_from_text_scalar,
        pack_variant_tiles_from_text,
    )
    from hadoop_bam_tpu.utils import native
    from hadoop_bam_tpu.utils.metrics import base_metrics

    if native.load() is None:
        pytest.skip("no native library on this host")
    f = G.gatk_fields(7, 0, 1, 30, SMALL)
    lines = G.assemble(f).tobytes().decode().split("\n")[:-1]
    parts = lines[3].split("\t")
    parts[4] = "ACGTACGTACGTACGTACGT"
    lines[3] = "\t".join(parts)
    text = ("\n".join(lines) + "\n").encode()
    header, geom = _header(SMALL), VariantGeometry(n_samples=SMALL.n_samples)
    base_metrics().reset()
    got = pack_variant_tiles_from_text(text, header, geom)
    want = _pack_variant_tiles_from_text_scalar(text, header, geom)
    for k in want:
        assert np.array_equal(want[k], got[k]), k
    c = base_metrics().snapshot()["counters"]
    assert c["vcf.text_scalar_records"] == 1
    assert c["vcf.text_keyed_records"] == c["vcf.text_bulk_records"] == 29
    nocall = (f["a0"] < 0) | (f["a1"] < 0)
    assert c["vcf.text_nocall_cells"] == int(nocall.sum() - nocall[3].sum())


# -- the cell, rehearsed ------------------------------------------------------

@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_rehearsal_of_the_cell(trace, tmp_path):
    """``benchmark/run.py --tiny`` on the CPU: the contract's last line,
    correct, with the cell's end-to-end metrics or, traced, its per-layer
    ones — the keyed share at 100 and the no-calls read."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000046019", "--seconds", "1",
         "--trace", trace, "--tiny"], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["device"]["platform"] == "cpu"
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    table = bench["per_layer"] if trace == "1" else bench["end_to_end"]
    allowed = {m["name"] for m in table
               if "workloads" not in m or CELL in m["workloads"]}
    assert set(doc["metrics"]) == allowed
    m = {k: v["value"] for k, v in doc["metrics"].items()}
    if trace == "1":
        assert m["gatk.keyed_share"] == m["gatk.bulk_gt_share"] == 100.0
        assert 30 < m["gatk.nocall_cells_per_rec"] < 45
        assert 80_000 < m["gatk.text_bytes_per_rec"] < 90_000
    else:
        assert m["scan_records_per_s"] > 0 and m["setup_s"] > 0
