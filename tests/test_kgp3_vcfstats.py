"""The ``kgp3-chr20-x1`` deployment on the CPU: the seeded 1000 Genomes
phase-3 chr20-shaped BCF (tests/kgp3_reference.py) through ``hbam vcf-stats``
against the plain reference, at the published width of 2,504 samples.

The chip compares the same things at the configured size
(benchmark/runners/variant_scan.py); here the sizes are small and the
timings mean nothing.
"""
from __future__ import annotations

import contextlib
import hashlib
import io
import json
import os

import numpy as np
import pytest

import kgp3_reference as K

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "kgp3-chr20-x1.json"),
          encoding="utf-8") as _fh:
    CONFIG = json.load(_fh)
TOL = CONFIG["mean_af_tolerance"]


def run_cli(argv) -> str:
    from hadoop_bam_tpu.tools.cli import main as hbam_main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = hbam_main(list(argv))
    assert rc == 0
    return out.getvalue()


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The configuration's ``tiny`` file: 2 x 2,048 records, 2,504
    samples still."""
    path = str(tmp_path_factory.mktemp("kgp3") / "tiny.bcf")
    ref = K.Reference()
    size = K.write_bcf(path, 3_000_000_019, CONFIG["tiny"]["chunks"],
                       CONFIG["tiny"]["chunk_records"], ref)
    assert size == os.path.getsize(path)
    return path, ref


# -- the shape ---------------------------------------------------------------

def test_header_is_the_sources_shape():
    from hadoop_bam_tpu.formats.bcf import decode_header

    assert K.N_SAMPLES == 2504 == CONFIG["shape"]["samples"]
    assert sum(len(p) for _, p in K.SUPERPOPS) == 26
    assert dict(zip((s for s, _ in K.SUPERPOPS), K.KGP3.pops)) \
        == CONFIG["shape"]["super_populations"]
    names = K.sample_names()
    assert len(set(names)) == 2504
    assert all(len(n) == 7 and n[:2] in ("HG", "NA") for n in names)
    header, _ = decode_header(K.header_bytes())
    assert header.n_samples == 2504 and header.samples == names
    assert tuple(header.string_dictionary()) == K.STRINGS
    assert header.contig_index(K.CONTIG) == K.CHROM_IDX == 19
    assert list(K.INFO_KEYS) == CONFIG["shape"]["info_keys"]
    sizes = CONFIG["sizes"]
    assert sizes["chunks"] * sizes["chunk_records"] == 1 << 18


def test_spectrum_and_types_are_the_papers():
    f = K.gen_fields(5, 3, 32, 4096)
    ac, an, ns = K.allele_counts(f)
    assert (an == 5008).all() and (ns == 2504).all() and (ac.sum(1) > 0).all()
    af = ac.sum(axis=1) / 5008
    got = ((af < 0.005).mean(), ((af >= 0.005) & (af < 0.05)).mean(),
           (af >= 0.05).mean())
    assert np.allclose(got, K.SPECTRUM, atol=0.03), got
    assert (f["vtype"] == 0).mean() > 0.93 and (f["vtype"] == 1).any()
    assert (np.diff(f["pos"]) > 0).all()
    # linkage: neighbouring common sites repeat founder patterns, so the
    # records deflate far better than independent draws would
    data, starts = K.assemble(f)
    assert abs(starts[-1] / 4096 - CONFIG["shape"]["mean_record_bytes"]) \
        < 0.01 * CONFIG["shape"]["mean_record_bytes"]
    assert len(K.bgzf(data[:1 << 22])) < (1 << 22) / 20


def test_records_decode_with_the_record_codec():
    """The generator's own BCF bytes, read back by the program's
    record-at-a-time codec: every typed INFO value and genotype."""
    from hadoop_bam_tpu.formats.bcf import BCFRecordCodec, decode_header

    shape = K.Shape((3, 2, 2, 2, 3), type_shares=(0.5, 0.3, 0.2),
                    multi_share=0.3)
    f = K.gen_fields(11, 0, 1, 200, shape)
    data, starts = K.assemble(f, shape)
    header, _ = decode_header(K.header_bytes(shape))
    codec = BCFRecordCodec(header)
    buf, p = data.tobytes(), 0
    ac, an, _ = K.allele_counts(f)
    kinds = set()
    for i in range(200):
        assert p == starts[i]
        rec, p = codec.decode(buf, p)
        k = int(f["n_alt"][i])
        assert (rec.chrom, rec.pos) == ("20", int(f["pos"][i]))
        assert len(rec.alts) == k and rec.filters == ("PASS",)
        assert rec.ref == bytes(
            f["alleles"][i, 0, :f["alen"][i, 0]]).decode()
        assert rec.info["AC"] == ",".join(str(int(x)) for x in ac[i, :k])
        assert rec.info["AN"] == str(int(an[i])) and rec.info["NS"] == "12"
        assert rec.info["VT"] == ("SNP", "INDEL", "SV")[f["vtype"][i]]
        assert ("AA" in rec.info) == (f["vtype"][i] != 2)
        want = [f"{a}|{b}" for a, b in zip(f["a0"][i], f["a1"][i])]
        assert list(rec.genotypes) == want
        kinds.add((int(f["vtype"][i]), k > 1))
        if f["vtype"][i] == 2:
            assert all(a in K.SYMBOLIC_ALTS for a in rec.alts)
    assert p == len(buf) and len(kinds) >= 5


# -- (a) the verb at the published width -------------------------------------

def test_tiny_file_through_the_verb_equals_the_reference(tiny):
    path, ref = tiny
    out = run_cli(["vcf-stats", path])
    assert ref.wrong(out, TOL["printed"]) is None
    kv = dict(ln.split("\t") for ln in out.strip().splitlines())
    assert int(kv["variants"]) == 4096 == ref.n
    assert [kv[f"callrate_{i}"] for i in range(2504)] == ["1.0000"] * 2504
    # the comparison refuses what it should: a lost record, a sample
    # read as padding, a mean off by more than the printed tolerance
    lost = out.replace("variants\t4096", "variants\t4095")
    assert "variants" in ref.wrong(lost, TOL["printed"])
    pad = out.replace("callrate_7\t1.0000", "callrate_7\t0.9442")
    assert "callrate_7" in ref.wrong(pad, TOL["printed"])
    off = out.replace(f"mean_af\t{kv['mean_af']}",
                      f"mean_af\t{float(kv['mean_af']) + 3e-6:.6f}")
    assert "mean_af" in ref.wrong(off, TOL["printed"])


def test_unrounded_mean_af_is_within_the_stated_tolerance(tiny):
    from hadoop_bam_tpu.parallel.distributed import distributed_variant_stats

    path, ref = tiny
    stats = distributed_variant_stats(path)
    assert abs(stats["mean_af"] - ref.mean_af) <= TOL["unrounded"]
    assert stats["n_af"] == ref.n_af == ref.n


def test_round_bf16_is_bfloat16():
    import jax.numpy as jnp

    x = np.random.default_rng(3).random(4096, dtype=np.float32)
    x[:4] = (0.0, 1.0, 1 / 5008, 2504 / 5008)
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                      .astype(jnp.float32))
    assert np.array_equal(K._round_bf16(x), want)


# -- (b) written and scanned in one process ----------------------------------

def test_written_then_scanned_three_times_every_scan_exact(tmp_path):
    """The wrong answer of issue 28: a process that wrote the file and
    then scanned it counted 19,888 of 20,000 records on its first scan
    (and sample call rates under 1 later).  Several tile groups, three
    scans, each exact and all identical."""
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        VariantGeometry, variant_stats_file,
    )

    path = str(tmp_path / "cohort.bcf")
    ref = K.Reference()
    K.write_bcf(path, 20, 2, 3500, ref)
    # 8 devices x 256 records a group: 3 full groups and a partial one
    geometry = VariantGeometry(tile_records=256, n_samples=K.N_SAMPLES)
    got = [variant_stats_file(path, geometry=geometry) for _ in range(3)]
    for s in got:
        assert (s["n_variants"], s["n_snp"], s["n_pass"]) \
            == (7000, ref.snps, 7000)
        assert np.array_equal(s["sample_callrate"] * 7000, ref.called)
        assert abs(s["mean_af"] - ref.mean_af) <= TOL["unrounded"]
    assert all(s["mean_af"] == got[0]["mean_af"] for s in got)


def test_a_cpu_dispatch_keeps_its_own_counts():
    """The cause: on the CPU backend ``device_put`` may alias host
    memory, and the feed handed each dispatch the ring slot's own counts,
    which the packer rewrites for a later group while a late step still
    reads them.  What a dispatch was given must not change afterwards."""
    from hadoop_bam_tpu.parallel.staging import FeedPipeline, TileSpec

    fp = FeedPipeline(1, 8, [TileSpec((), np.int32, 0)], block_n=1,
                      balance=True)
    kept = []

    def dispatch(arrays, counts):
        kept.append((counts, counts.copy(), arrays[0], arrays[0].copy()))
        return ()

    rows = np.arange(8 * 5 + 3, dtype=np.int32)
    assert fp.feed(iter([(rows[:20],), (rows[20:],)]), dispatch) == 6
    assert [int(c.sum()) for c, _, _, _ in kept] == [8] * 5 + [3]
    for counts, counts_then, tile, tile_then in kept:
        assert np.array_equal(counts, counts_then)
        assert np.array_equal(tile, tile_then)


# -- (c) the genotype forms the source never has -----------------------------

SMALL = {
    "missing-haploid-unphased": K.Shape(
        (5, 4, 3, 0, 6), missing=0.1, haploid=0.1, unphased=0.3,
        haploid_records=0.05, type_shares=(0.6, 0.3, 0.1), multi_share=0.3),
    "all-missing-heavy": K.Shape(
        (2, 2, 2, 2, 2), missing=0.6, unphased=1.0,
        type_shares=(0.9, 0.05, 0.05), multi_share=0.1),
    "haploid-records": K.Shape(
        (7, 0, 0, 6, 0), haploid_records=0.5, haploid=0.3,
        type_shares=(0.3, 0.3, 0.4), multi_share=0.5),
    "one-population-phased": K.Shape((40,), multi_share=0.2),
}


@pytest.mark.parametrize("name", SMALL)
def test_small_cohorts_with_odd_genotypes_equal_the_reference(name,
                                                              tmp_path):
    shape = SMALL[name]
    path = str(tmp_path / "small.bcf")
    ref = K.Reference(shape.n_samples)
    K.write_bcf(path, 41, 3, 700, ref, shape=shape)
    assert ref.n == 2100 and 0 < ref.snps < 2100
    if shape.missing:
        assert ref.called.min() < ref.n
    out = run_cli(["vcf-stats", path])
    assert ref.wrong(out, TOL["printed"]) is None, out[:200]


# -- (d) both decoders at the published width --------------------------------

def test_columnar_and_record_scan_agree_on_a_2504_wide_span():
    from hadoop_bam_tpu.formats.bcf import decode_header, scan_variant_columns
    from hadoop_bam_tpu.formats.bcf_columns import decode_bcf_columns

    f = K.gen_fields(9, 1, 4, 300)
    data, starts = K.assemble(f)
    header, _ = decode_header(K.header_bytes())
    buf = data.tobytes()
    fast = decode_bcf_columns(buf, header, 2504, starts=starts[:-1])
    slow = scan_variant_columns(buf, header, 2504)
    assert fast is not None
    for k in slow:
        assert np.array_equal(fast[k], slow[k]), k
    # and both are the generator's alleles
    want = (f["a0"] > 0).astype(np.int8) + (f["a1"] > 0)
    assert np.array_equal(fast["dosage"], want)
    assert np.array_equal(fast["pos"], f["pos"])
    assert (fast["chrom"] == K.CHROM_IDX).all()


def test_the_gt_gather_is_slabbed_not_span_wide(monkeypatch):
    """The NumPy twin of the native GT kernel (what a host without the
    library runs) reduces a span's GT values a bounded slab at a time:
    the same columns whatever the slab, down to one record — and the
    kernel's."""
    from hadoop_bam_tpu.formats import bcf_columns
    from hadoop_bam_tpu.formats.bcf import decode_header
    from hadoop_bam_tpu.utils import native

    shape = SMALL["missing-haploid-unphased"]
    f = K.gen_fields(4, 0, 1, 120, shape)
    buf = K.assemble(f, shape)[0].tobytes()
    header, _ = decode_header(K.header_bytes(shape))
    kernel = bcf_columns.decode_bcf_columns(buf, header, 24)
    monkeypatch.setattr(native, "load", lambda: None)
    whole = bcf_columns.decode_bcf_columns(buf, header, 24)
    monkeypatch.setattr(bcf_columns, "_GT_SLAB_VALUES", 1)
    one = bcf_columns.decode_bcf_columns(buf, header, 24)
    for k in whole:
        assert np.array_equal(whole[k], one[k], equal_nan=True), k
        assert np.array_equal(whole[k], kernel[k], equal_nan=True), k


# -- (e) the generator is a function of the seed; the benchmark's copy -------

def test_same_seed_same_bytes_and_the_benchmarks_copy_is_verbatim():
    def digest(seed):
        blob, part = K.chunk_job((seed, 1, 2, 256, K.KGP3, 6))
        return hashlib.sha256(blob).hexdigest(), part.mean_af

    assert digest(2_147_483_999) == digest(2_147_483_999)
    assert digest(2_147_483_999) != digest(2_147_484_000)
    with open(os.path.join(ROOT, "tests", "kgp3_reference.py"), "rb") as a, \
            open(os.path.join(ROOT, "benchmark", "gen_kgp3.py"), "rb") as b:
        assert a.read() == b.read()


def test_chunks_concatenate_into_one_sorted_file(tiny):
    """The file is the header's members, each chunk's, the EOF marker;
    plain zlib reads it back to the sorted record stream."""
    import zlib

    path, ref = tiny
    with open(path, "rb") as fh:
        raw = fh.read()
    assert raw.endswith(K.BGZF_EOF)
    out, p = bytearray(), 0
    while p < len(raw):
        size = int.from_bytes(raw[p + 16:p + 18], "little") + 1
        out += zlib.decompress(raw[p + 18:p + size - 8], -15)
        p += size
    head = K.header_bytes()
    assert bytes(out[:len(head)]) == head
    assert len(out) - len(head) == ref.record_bytes
    pos, q = [], len(head)
    while q < len(out):
        l_shared, l_indiv = np.frombuffer(out, "<u4", 2, q)
        pos.append(int(np.frombuffer(out, "<i4", 1, q + 12)[0]))
        q += 8 + int(l_shared) + int(l_indiv)
    assert len(pos) == ref.n and pos == sorted(pos)


# -- (f) what the scan reports from inside ------------------------------------

def test_the_scan_counts_its_records_and_bytes(tiny):
    from hadoop_bam_tpu.utils.metrics import base_metrics

    path, ref = tiny
    base_metrics().reset()
    run_cli(["vcf-stats", path])
    snap = base_metrics().snapshot()
    c = snap["counters"]
    assert c["pipeline.records"] == ref.n == 4096
    width = c["vcf.inflated_bytes"] / c["pipeline.records"]
    assert c["vcf.inflated_bytes"] == ref.record_bytes
    assert abs(width - CONFIG["shape"]["mean_record_bytes"]) \
        < 0.02 * CONFIG["shape"]["mean_record_bytes"]
    assert c["vcf.decode_busy_ns"] > 0
    assert "vcf.columnar_declined_spans" not in c
    # every record's dosage row came from the native GT kernel
    assert c["vcf.gt_native_records"] == ref.n
    assert "vcf.gt_numpy_records" not in c
    walls = snap["wall_timers"]
    assert 0 < walls["vcf.gt_dosage_wall"] <= walls["vcf.tokenize_wall"]
    # a group ships 2,504 dosages + chrom + pos + flags a record
    assert c["pipeline.dispatch_bytes"] >= 2513 * 4096


def test_a_cohort_wide_bcf_gets_spans_by_its_inflated_size(tiny, tmp_path):
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.parallel.pipeline import pipeline_span_count
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        _bgzf_inflate_ratio, variant_span_count,
    )
    import dataclasses

    path, ref = tiny
    ratio = _bgzf_inflate_ratio(path)
    assert 20 < ratio < 60                       # ~35x, as a call set
    # grain 64 KiB: the 0.6 MB file is 10 spans by compressed bytes,
    # ratio / 4 times as many by inflated bytes
    cfg = dataclasses.replace(DEFAULT_CONFIG, split_size=64 << 10)
    ds = open_vcf(path, cfg)
    by_bytes = pipeline_span_count(path, 1, cfg)
    assert variant_span_count(ds, 1, cfg) \
        == int(np.ceil(os.path.getsize(path) * ratio / 4 / (64 << 10)))
    assert variant_span_count(ds, 1, cfg) > 5 * by_bytes
    # a raw BCF and the default grain on a small file: unchanged
    assert variant_span_count(open_vcf(path), 8) == 8
    assert _bgzf_inflate_ratio(str(tmp_path / "missing.bcf")) == 1.0


def test_the_step_names_its_phases():
    import jax

    from hadoop_bam_tpu.parallel.mesh import make_mesh
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        VariantGeometry, make_variant_stats_step,
    )

    geometry = VariantGeometry(tile_records=64, n_samples=16)
    step = make_variant_stats_step(make_mesh(), geometry)
    n = len(jax.devices())
    args = (np.zeros((n, 64), np.int32), np.zeros((n, 64), np.int32),
            np.zeros((n, 64), np.uint8), np.zeros((n, 64, 16), np.int8),
            np.zeros(n, np.int32))
    text = step.lower(*args).as_text(debug_info=True)
    assert "hbam_variant_step" in text
    for scope in ("unpack", "reduce", "psum"):
        assert f"{scope}/" in text or f"/{scope}" in text, scope


def test_a_finalizer_that_counts_cannot_deadlock_the_metrics():
    """Found by this file's tests running before another file's in one
    process (PR 28): ``wall_timer`` allocates under the metrics lock, the
    allocation started a garbage collection, the collector closed an
    abandoned ``_iter_windowed`` generator, whose ``finally`` joined a
    native job and counted ``decode.native_busy_ns`` — into the lock its
    own thread held."""
    import gc
    import threading

    from hadoop_bam_tpu.utils.metrics import Metrics

    m = Metrics()

    class Counts:
        def __del__(self):
            m.count("finalized")

    def collect_under_the_lock():
        cycle = [Counts()]
        cycle.append(cycle)
        del cycle
        with m._lock:
            gc.collect()

    t = threading.Thread(target=collect_under_the_lock, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    assert m.snapshot()["counters"]["finalized"] == 1
