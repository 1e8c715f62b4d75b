"""The ``kgp3-chr20-vcfgz-x1`` deployment on the CPU: the seeded 1000 Genomes
phase-3 chr20-shaped call set as bgzip'd VCF text (tests/kgp3_vcf_reference.py)
through ``hbam vcf-stats`` against the plain reference and against the BCF of
the same seed, at the published width of 2,504 samples.

The chip compares the same things at the configured size
(benchmark/runners/variant_text_scan.py); here the sizes are small and the
timings mean nothing — the CPU-time limits below have ten times of room.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
import tracemalloc

import numpy as np
import pytest

import kgp3_reference as K
import kgp3_vcf_reference as V
from test_kgp3_vcfstats import SMALL, run_cli

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "kgp3-chr20-vcfgz-x1.json"), encoding="utf-8") as _fh:
    CONFIG = json.load(_fh)
with open(os.path.join(ROOT, "benchmark", "configs", "kgp3-chr20-x1.json"),
          encoding="utf-8") as _fh:
    BCF_CONFIG = json.load(_fh)
TOL = CONFIG["mean_af_tolerance"]
SEED = 3_000_000_019


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The configuration's ``tiny`` file, and the BCF of the same seed."""
    d = tmp_path_factory.mktemp("kgp3vcf")
    sizes = CONFIG["tiny"]
    ref, ref_bcf = K.Reference(), K.Reference()
    vcfgz, bcf = str(d / "tiny.vcf.gz"), str(d / "tiny.bcf")
    size = V.write_vcfgz(vcfgz, SEED, sizes["chunks"],
                         sizes["chunk_records"], ref)
    assert size == os.path.getsize(vcfgz)
    K.write_bcf(bcf, SEED, sizes["chunks"], sizes["chunk_records"], ref_bcf)
    return vcfgz, bcf, ref, ref_bcf


# -- the shape ---------------------------------------------------------------

def test_the_configuration_keeps_every_shape_of_the_bcf_one():
    for key, value in BCF_CONFIG["shape"].items():
        assert CONFIG["shape"][key] == value, key
    assert CONFIG["shape"]["fileformat"] == "VCFv4.1"
    assert CONFIG["shape"]["text_bytes_per_genotype"] == V.GT_BYTES == 4
    assert CONFIG["sizes"] == BCF_CONFIG["sizes"]
    assert list(CONFIG["reduced"]) == ["records"]
    assert CONFIG["guarantees"][:3] == BCF_CONFIG["guarantees"]
    assert len(CONFIG["guarantees"]) == 4
    assert CONFIG["mean_af_tolerance"] == BCF_CONFIG["mean_af_tolerance"]
    for key, value in BCF_CONFIG["assumed"].items():
        if key != "compression":
            assert CONFIG["assumed"][key] == value, key


def test_the_file_is_the_sources_text(tiny):
    """Plain zlib reads the members back to VCFv4.1 text: the header's meta
    lines and 2,504 names, then one line a site, sorted, ``GT`` only, every
    genotype ``a|b``."""
    import zlib

    vcfgz, _, ref, _ = tiny
    with open(vcfgz, "rb") as fh:
        raw = fh.read()
    assert raw.endswith(K.BGZF_EOF)
    out, p, payloads = bytearray(), 0, []
    while p < len(raw):
        size = int.from_bytes(raw[p + 16:p + 18], "little") + 1
        payloads.append(zlib.decompress(raw[p + 18:p + size - 8], -15))
        out += payloads[-1]
        p += size
    assert max(len(b) for b in payloads) == 0xFF00
    head = K.header_text().encode()
    assert bytes(out[:len(head)]) == head
    assert head.startswith(b"##fileformat=VCFv4.1\n")
    lines = bytes(out[len(head):]).split(b"\n")
    assert lines.pop() == b"" and len(lines) == ref.n == 4096
    assert len(out) - len(head) == ref.record_bytes
    mean = ref.record_bytes / ref.n
    assert abs(mean - CONFIG["shape"]["mean_line_bytes"]) \
        < 0.02 * CONFIG["shape"]["mean_line_bytes"]
    pos = []
    for ln in lines[::97]:
        parts = ln.split(b"\t")
        assert len(parts) == 9 + 2504 and parts[0] == b"20"
        assert parts[5:7] == [b"100", b"PASS"] and parts[8] == b"GT"
        keys = [kv.split(b"=")[0].decode() for kv in parts[7].split(b";")]
        want = list(K.INFO_KEYS)
        if b"VT=SV" in parts[7]:
            want.remove("AA")
        assert keys == want
        assert all(len(g) == 3 and g[1:2] == b"|" for g in parts[9:])
        pos.append(int(parts[1]))
    assert pos == sorted(pos)
    # it deflates as a call set's text does
    assert 30 < len(out) / len(raw) < 80


def test_lines_parse_back_to_the_generators_alleles():
    """The generator's own text, read back by the program's record parser:
    the INFO values and every genotype of a small odd cohort."""
    from hadoop_bam_tpu.formats.vcf import VcfRecord

    shape = SMALL["missing-haploid-unphased"]
    f = K.gen_fields(11, 0, 1, 150, shape)
    text = V.assemble(f, shape).tobytes().decode()
    ac, an, ns = K.allele_counts(f)
    lines = text.splitlines()
    assert len(lines) == 150
    for i, ln in enumerate(lines):
        rec = VcfRecord.from_line(ln)
        k = int(f["n_alt"][i])
        assert (rec.chrom, rec.pos) == ("20", int(f["pos"][i]))
        assert len(rec.alts) == k and rec.filters == ("PASS",)
        assert rec.info["AC"] == ",".join(str(int(x)) for x in ac[i, :k])
        assert rec.info["AN"] == str(int(an[i]))
        assert rec.info["NS"] == str(int(ns[i]))
        assert rec.info["VT"] == ("SNP", "INDEL", "SV")[f["vtype"][i]]
        want = []
        for a0, a1, p, ph in zip(f["a0"][i], f["a1"][i], f["ploidy"][i],
                                 f["phased"][i]):
            g = "." if a0 < 0 else str(a0)
            if p == 2:
                g += ("|" if ph else "/") + ("." if a1 < 0 else str(a1))
            want.append(g)
        assert list(rec.genotypes) == want


# -- (a) three doors, one call set --------------------------------------------

def test_vcfgz_and_bcf_of_one_seed_print_the_same_answer(tiny):
    vcfgz, bcf, ref, ref_bcf = tiny
    out = run_cli(["vcf-stats", vcfgz])
    assert out == run_cli(["vcf-stats", bcf])
    assert ref.wrong(out, TOL["printed"]) is None
    # one reference: folded from the allele arrays, not from either file
    assert (ref.n, ref.snps, ref.n_pass, ref.n_af) \
        == (ref_bcf.n, ref_bcf.snps, ref_bcf.n_pass, ref_bcf.n_af)
    assert ref.mean_af == ref_bcf.mean_af
    assert np.array_equal(ref.called, ref_bcf.called)
    kv = dict(ln.split("\t") for ln in out.strip().splitlines())
    assert int(kv["variants"]) == 4096
    assert [kv[f"callrate_{i}"] for i in range(2504)] == ["1.0000"] * 2504
    lost = out.replace("variants\t4096", "variants\t4095")
    assert "variants" in ref.wrong(lost, TOL["printed"])


def test_unrounded_mean_af_within_the_limit_and_bfloat16_refused(tiny):
    from hadoop_bam_tpu.parallel.distributed import distributed_variant_stats

    vcfgz, _, ref, _ = tiny
    stats = distributed_variant_stats(vcfgz)
    assert abs(stats["mean_af"] - ref.mean_af) <= TOL["unrounded"]
    assert abs(ref.mean_af_bf16 - ref.mean_af) > TOL["unrounded"]
    assert stats["n_af"] == ref.n_af == ref.n


@pytest.mark.parametrize("name", SMALL)
def test_small_cohorts_with_odd_genotypes_equal_the_reference(name,
                                                              tmp_path):
    """Missing, haploid and unphased calls: lines the bulk pass hands to
    the scalar parse — the same answer as the BCF's, and the reference's."""
    shape = SMALL[name]
    vcfgz, bcf = str(tmp_path / "small.vcf.gz"), str(tmp_path / "small.bcf")
    ref = K.Reference(shape.n_samples)
    V.write_vcfgz(vcfgz, 41, 3, 700, ref, shape=shape)
    K.write_bcf(bcf, 41, 3, 700, K.Reference(shape.n_samples), shape=shape)
    out = run_cli(["vcf-stats", vcfgz])
    assert ref.wrong(out, TOL["printed"]) is None, out[:200]
    assert out == run_cli(["vcf-stats", bcf])


def test_same_seed_same_bytes_and_the_benchmarks_copy_is_verbatim():
    import hashlib

    def digest(seed):
        blob, part = V.chunk_job((seed, 1, 2, 128, K.KGP3, 6))
        return hashlib.sha256(blob).hexdigest(), part.mean_af

    assert digest(2_147_483_999) == digest(2_147_483_999)
    assert digest(2_147_483_999) != digest(2_147_484_000)
    with open(os.path.join(ROOT, "tests", "kgp3_vcf_reference.py"),
              "rb") as a, \
            open(os.path.join(ROOT, "benchmark", "gen_kgp3_vcf.py"),
                 "rb") as b:
        assert a.read() == b.read()


# -- (b) what the scan reports from inside -----------------------------------

def test_the_text_scan_counts_its_records_bytes_and_text_alive(tiny):
    from hadoop_bam_tpu.utils.metrics import base_metrics

    vcfgz, _, ref, _ = tiny
    base_metrics().reset()
    run_cli(["vcf-stats", vcfgz])
    snap = base_metrics().snapshot()
    c, walls = snap["counters"], snap["wall_timers"]
    assert c["pipeline.records"] == ref.n == 4096
    # every line of the file once: the records' and the header's
    assert c["vcf.inflated_bytes"] \
        == ref.record_bytes + len(K.header_text().encode())
    assert c["vcf.decode_busy_ns"] > 0
    assert c["vcf.text_bulk_records"] == ref.n
    assert c["vcf.text_scalar_records"] == 0
    assert c["vcf.text_native_records"] == ref.n
    assert "vcf.text_numpy_records" not in c
    assert c["vcf.native_read_spans"] >= 1
    assert "vcf.python_read_spans" not in c
    assert 0 < c["vcf.text_peak_bytes"] <= c["vcf.inflated_bytes"]
    assert 0 < walls["vcf.gt_dosage_wall"] <= walls["vcf.tokenize_wall"]
    assert walls["vcf.inflate_wall"] > 0
    assert "vcf.dosage_pack_wall" not in walls
    assert c["pipeline.dispatch_bytes"] >= 2513 * 4096


def test_text_alive_is_bounded_by_the_window_not_the_file(tiny):
    """Many spans: what is alive at once is at most a span's text a pool
    thread, whatever the file holds."""
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        variant_span_count, variant_stats_file,
    )
    from hadoop_bam_tpu.utils.metrics import base_metrics
    from hadoop_bam_tpu.utils.pools import decode_pool_size

    vcfgz, _, ref, _ = tiny
    cfg = dataclasses.replace(DEFAULT_CONFIG, split_size=32 << 10)
    ds = open_vcf(vcfgz, cfg)
    spans = ds.spans(num_spans=variant_span_count(ds, 8, cfg))
    widest = max(len(ds.read_span_text(s)) for s in spans)
    threads = decode_pool_size(cfg)
    assert len(spans) > 4 * threads
    base_metrics().reset()
    stats = variant_stats_file(vcfgz, config=cfg)
    assert stats["n_variants"] == ref.n
    c = base_metrics().snapshot()["counters"]
    assert c["vcf.native_read_spans"] == len(spans)
    assert 0 < c["vcf.text_peak_bytes"] <= threads * widest
    assert threads * widest < c["vcf.inflated_bytes"] / 2


def test_a_bgzf_vcf_gets_spans_by_its_inflated_size(tiny):
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.parallel.pipeline import pipeline_span_count
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        _bgzf_inflate_ratio, variant_span_count,
    )

    vcfgz, _, _, _ = tiny
    ratio = _bgzf_inflate_ratio(vcfgz)
    assert 30 < ratio < 80                       # ~50x, as a call set's text
    cfg = dataclasses.replace(DEFAULT_CONFIG, split_size=64 << 10)
    ds = open_vcf(vcfgz, cfg)
    got = variant_span_count(ds, 1, cfg)
    assert got == int(np.ceil(os.path.getsize(vcfgz) * ratio / 4
                              / (64 << 10)))
    assert got > 8 * pipeline_span_count(vcfgz, 1, cfg)
    # a span is then about four grains of TEXT
    spans = ds.spans(num_spans=got)
    text = sum(len(ds.read_span_text(s)) for s in spans)
    assert 2 * (64 << 10) < text / len(spans) < 8 * (64 << 10)


# -- (c) the tokeniser's work follows the bytes -------------------------------

def _wide_text(n_lines: int):
    f = K.gen_fields(5, 0, 1, n_lines)
    return V.assemble(f).tobytes(), f


@pytest.mark.parametrize("twin,cpu_limit", [(False, 0.1), (True, 1.2)],
                         ids=["native", "numpy"])
def test_a_wide_span_is_tokenised_within_stated_cpu_and_memory(
        twin, cpu_limit, monkeypatch):
    """2,000 lines of 2,504 samples (20 MB of text).  A ``[lines, S]`` int64
    array is 40 MB, the four ``[lines, 10 + S]`` ones of the grid tokeniser
    160 MB, and its per-sample loop took ~1 ms a line (2.1 s here).  The
    limits: 32 MiB of peak allocation beside the text (the native pass
    allocates 5.8 MiB — the dosage rows and the bounds — its NumPy twin
    11.4), and of this thread's CPU 0.1 s for the native pass (it needs
    ~10 ms) and 1.2 s for the twin (~120 ms): ten times of room each."""
    from hadoop_bam_tpu.formats.vcf import VCFHeader
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        VariantGeometry, pack_variant_tiles_from_text,
    )
    from hadoop_bam_tpu.utils import native

    if twin:
        monkeypatch.setattr(native, "load", lambda: None)
    elif native.load() is None:
        pytest.skip("no native library on this host")
    text, f = _wide_text(2000)
    header = VCFHeader.from_text(K.header_text())
    geometry = VariantGeometry(n_samples=K.N_SAMPLES)
    pack_variant_tiles_from_text(text[:1 << 20], header, geometry)   # warm
    tracemalloc.start()
    t0 = time.thread_time()
    cols = pack_variant_tiles_from_text(text, header, geometry)
    cpu = time.thread_time() - t0
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    want = (f["a0"] > 0).astype(np.int8) + (f["a1"] > 0)
    assert np.array_equal(cols["dosage"][:, :K.N_SAMPLES], want)
    assert np.array_equal(cols["pos"], f["pos"])
    assert (cols["chrom"] == K.CHROM_IDX).all()
    assert peak < 32 << 20, f"{peak / 2**20:.1f} MiB allocated"
    assert cpu < cpu_limit, f"{cpu:.3f} s of CPU"
