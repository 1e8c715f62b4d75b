"""The scan feed's contract, family by family (``parallel/scan.py``).

Every whole-file verb and every ``tensor_batches`` API runs one loop,
``ScanFeed``.  Each case below runs one family through it on a small file,
with a trace recorder active (as the benchmark's traced run is), and pins:

- the answer: it equals the family's own oracle;
- the names: the run emits every wall and counter the benchmark's readers
  of the family's prefix read (``benchmark/layer_metrics/<prefix>.*.json``
  — the structural contract a cell's per-layer metrics stand on), and the
  loop's own walls and counters, which every family shares;
- ``pipeline.records``: exactly the records the scan read, counted once —
  by a BAM span's decode, at dispatch for every other family.
"""
import dataclasses
import glob
import gzip
import json
import os
import random

import numpy as np
import pytest

from hadoop_bam_tpu.config import DEFAULT_CONFIG
from hadoop_bam_tpu.formats.bamio import BamWriter
from hadoop_bam_tpu.obs import disable_tracing, enable_tracing
from hadoop_bam_tpu.ops import inflate as inflate_ops
from hadoop_bam_tpu.utils.metrics import MetricsContext

from fixtures import make_header, make_records

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = os.path.join(ROOT, "benchmark", "layer_metrics")

# names a reader reads that no scan's structure promises: a head wait
# exists only where the window's head was not done on arrival (timing),
# and ``cli.main_wall`` is the CLI's own span around a verb
# (tests/test_feed_tracing.py pins it)
_NOT_STRUCTURAL = {"feed.head_running", "feed.ready_behind_head",
                   "cli.main_wall"}
# a gzip'd FASTQ's stream: a plain file is read, not inflated
_FASTQ_STREAM = {"fastq.inflate_wall", "fastq.inflate_busy_ns",
                 "fastq.inflated_bytes", "fastq.inflated_bytes_parallel",
                 "fastq.stream_peak_text_bytes"}
N_BAM, N_READS, N_SITES = 3000, 6000, 2000
SPLIT = 65536        # a small pipeline grain: the plans have many units


def _reader_names(prefix):
    """(all_of, any_of): the host names the readers of ``prefix`` read —
    every numerator of a ratio that is not a share of its own denominator
    and every span of a self-time, all of them; one at least of each
    share's denominator and of each span list.  The device trace's
    readers (``device.*``, ``roofline*``) read programs, not these."""
    all_of, any_of = set(), []
    for path in sorted(glob.glob(os.path.join(READERS, prefix + ".*.json"))):
        with open(path) as fh:
            m = json.load(fh)
        p = m.get("params", {})
        if m["reducer"] == "host.span_share_of_window":
            any_of.append(set(p["spans"]))
        elif m["reducer"] == "host.counter_ratio":
            num, den = set(p["numerator"]), set(p["denominator"])
            if not num <= den:
                all_of |= num
            any_of.append(den)
        elif m["reducer"] == "selftime.span_self_share_of_window":
            all_of |= {p["span"], *p["children"]}
    assert all_of or any_of, prefix
    return all_of, any_of


def _loop_names(fmt, eager, executed):
    """What the scan feed itself emits for a family of prefix ``fmt``."""
    names = {"pipeline.records", "pipeline.dispatch_bytes",
             "pipeline.host_decode_wall", f"{fmt}.host_decode_wall",
             "pipeline.feed_wall", f"{fmt}.feed_wall",
             "pipeline.dispatch_wall", f"{fmt}.dispatch_wall",
             "feed.wait_rows", "feed.wait_group", "feed.wait_slot",
             "staging.pack", "staging.transfer_wait", "feed.units",
             "feed.unit_run_ns", "feed.unit_cpu_ns"}
    if eager:
        names.add(f"{fmt}.kernel_wall")
    if executed:
        names |= {"plan.execute_wall", "feed.first_dispatch_wait",
                  "exec.wall_ns", "exec.cpu_user_ns", "exec.cpu_sys_ns"}
    return names


@pytest.fixture(autouse=True)
def _traced():
    disable_tracing()
    enable_tracing()
    yield
    disable_tracing()


@pytest.fixture(scope="module")
def mesh():
    """Two devices and small tiles: every scan packs several groups, so
    a ring slot is reused and waits on its transfer."""
    import jax

    from hadoop_bam_tpu.parallel.mesh import make_mesh

    return make_mesh(devices=jax.devices()[:2])


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    from hadoop_bam_tpu.api.writers import QseqShardWriter, open_vcf_writer
    from hadoop_bam_tpu.formats.fastq import SequencedFragment
    from hadoop_bam_tpu.formats.vcf import VCFHeader

    import test_variant_pipeline as tv

    d = tmp_path_factory.mktemp("scan_feed")
    out = {}
    header = make_header()
    recs = make_records(header, N_BAM, seed=43)
    out["bam"] = (str(d / "s.bam"), header, recs)
    with BamWriter(out["bam"][0], header) as w:
        for r in recs:
            w.write_sam_record(r)

    rng = random.Random(43)
    reads = []
    for _ in range(N_READS):
        n = rng.randint(60, 150)
        reads.append(("".join(rng.choice("ACGTN") for _ in range(n)),
                      "".join(chr(33 + rng.randint(2, 40))
                              for _ in range(n))))
    text = "".join(f"@r{i}\n{s}\n+\n{q}\n"
                   for i, (s, q) in enumerate(reads)).encode()
    out["reads"] = reads
    out["fastq"] = str(d / "r.fastq")
    out["fastq.gz"] = str(d / "r.fastq.gz")
    with open(out["fastq"], "wb") as fh:
        fh.write(text)
    with open(out["fastq.gz"], "wb") as fh:
        fh.write(gzip.compress(text, 4))

    frags = [SequencedFragment.from_name(
        f"M:1:F:1:{i}:{i}:{i} 1:N:0:AAA", s, q)
        for i, (s, q) in enumerate(reads[:N_BAM])]
    out["qseq"] = str(d / "r.qseq")
    with QseqShardWriter(out["qseq"]) as w:
        for f in frags:
            w.write_record(f)

    vh = VCFHeader.from_text(tv.HEADER_TEXT)
    sites = tv._make_records(N_SITES, seed=43)
    out["sites"] = (vh, sites)
    for ext in ("bcf", "vcf.gz"):
        out[ext] = str(d / f"v.{ext}")
        with open_vcf_writer(out[ext], vh) as w:
            for r in sites:
                w.write_record(r)
    return out


@pytest.fixture(scope="module")
def cram(tmp_path_factory):
    import cram31_reference as C

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "na12878-chr20-cram31-x1.json")) as fh:
        tiny = json.load(fh)["tiny"]
    return C.write_cram(str(tmp_path_factory.mktemp("scan_feed_cram")),
                        3000000043, tiny["chunks"], tiny["chunk_records"])


def _geometry(**kw):
    from hadoop_bam_tpu.parallel.pipeline import PayloadGeometry

    return PayloadGeometry(tile_records=256, block_n=256, **kw)


def _means(seqs, quals, max_len=160):
    gc = np.mean([sum(c in "GC" for c in s[:max_len]) / len(s[:max_len])
                  for s in seqs])
    mq = np.mean([np.mean([ord(c) - 33 for c in q[:max_len]])
                  for q in quals])
    return gc, mq


def _same_payload_stats(got, seqs, quals):
    gc, mq = _means(seqs, quals)
    assert got["n_reads"] == len(seqs)
    assert abs(got["mean_gc"] - gc) < 1e-6
    assert abs(got["mean_qual"] - mq) < 1e-4
    assert int(np.asarray(got["base_hist"]).sum()) == \
        sum(min(len(s), 160) for s in seqs)


# ---------------------------------------------------------------------------
# the families: each returns (records scanned, reader prefix or None,
# scan fmt, eager?, under a plan.execute?, names absent by design)
# ---------------------------------------------------------------------------

def _bam_flagstat(files, mesh, cram, monkeypatch):
    from hadoop_bam_tpu.api.dataset import _flagstat_records
    from hadoop_bam_tpu.parallel.pipeline import DecodeGeometry, flagstat_file

    path, header, recs = files["bam"]
    got = flagstat_file(path, mesh=mesh, header=header,
                        geometry=DecodeGeometry(tile_records=256))
    assert got == _flagstat_records(recs)
    # the native fused sweep is what counts its core-seconds
    absent = () if inflate_ops.fused_available() \
        else ("decode.native_busy_ns",)
    return len(recs), "scan", "bam", True, True, absent


def _bam_seq_stats(files, mesh, cram, monkeypatch):
    from hadoop_bam_tpu.parallel.pipeline import seq_stats_file

    path, header, recs = files["bam"]
    got = seq_stats_file(path, mesh=mesh, header=header,
                         geometry=_geometry())
    _same_payload_stats(got, [r.seq for r in recs], [r.qual for r in recs])
    return len(recs), None, "bam", True, True, ()


def _bam_tensor_batches(files, mesh, cram, monkeypatch):
    from hadoop_bam_tpu.api import open_bam
    from hadoop_bam_tpu.ops.unpack_bam import unpack_fixed_fields_tile

    path, _header, recs = files["bam"]
    flags, lens = [], []
    for batch in open_bam(path).tensor_batches(mesh=mesh,
                                               geometry=_geometry(),
                                               num_spans=5):
        assert set(batch) == {"prefix", "seq_packed", "qual", "n_records"}
        counts = np.asarray(batch["n_records"])
        prefix = np.asarray(batch["prefix"])
        for dev, c in enumerate(counts):
            cols = unpack_fixed_fields_tile(prefix[dev][:c])
            flags += np.asarray(cols["flag"]).tolist()
            lens += np.asarray(cols["l_seq"]).tolist()
    # the serial placement: file order, shard after shard
    assert flags == [r.flag for r in recs]
    assert lens == [len(r.seq) for r in recs]
    return len(recs), None, "bam", False, False, ()


def _fastq(which):
    def run(files, mesh, cram, monkeypatch):
        from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file
        from hadoop_bam_tpu.split import read_planners

        # the parallel inflaters take a host of two CPUs or more
        monkeypatch.setattr(read_planners.os, "cpu_count", lambda: 8)
        got = fastq_seq_stats_file(
            files[which], mesh=mesh, geometry=_geometry(),
            config=dataclasses.replace(DEFAULT_CONFIG, split_size=SPLIT))
        reads = files["reads"]
        _same_payload_stats(got, [s for s, _ in reads],
                            [q for _, q in reads])
        absent = _FASTQ_STREAM if which == "fastq" else ()
        return len(reads), "fastq", "fastq", True, True, absent
    return run


def _qseq(files, mesh, cram, monkeypatch):
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file

    got = fastq_seq_stats_file(
        files["qseq"], mesh=mesh, geometry=_geometry(),
        config=dataclasses.replace(DEFAULT_CONFIG, split_size=SPLIT))
    reads = files["reads"][:N_BAM]
    _same_payload_stats(got, [s for s, _ in reads], [q for _, q in reads])
    return len(reads), None, "qseq", True, True, ()


def _cram(files, mesh, cram, monkeypatch):
    from hadoop_bam_tpu.parallel.pipeline import cram_seq_stats_file

    written, sums = cram
    got = cram_seq_stats_file(
        written.cram, mesh=mesh, geometry=_geometry(),
        config=dataclasses.replace(
            DEFAULT_CONFIG, cram_reference_source_path=written.fasta,
            split_size=SPLIT))
    assert got["n_reads"] == sums.n
    assert np.asarray(got["base_hist"]).tolist() == sums.hist.tolist()
    gc, mq = sums.means()
    assert abs(got["mean_gc"] - gc) < 1e-6
    assert abs(got["mean_qual"] - mq) < 1e-4
    return sums.n, "cram", "cram", True, True, ()


def _variant(ext, prefix):
    def run(files, mesh, cram, monkeypatch):
        from hadoop_bam_tpu.formats.vcf import VariantBatch
        from hadoop_bam_tpu.parallel.variant_pipeline import (
            VariantGeometry, variant_stats_file,
        )

        header, sites = files["sites"]
        got = variant_stats_file(
            files[ext], mesh=mesh,
            geometry=VariantGeometry(tile_records=64,
                                     n_samples=header.n_samples),
            config=dataclasses.replace(DEFAULT_CONFIG, split_size=SPLIT))
        d = VariantBatch(sites, header).dosage_matrix().astype(np.int64)
        called = d >= 0
        has = called.sum(1) > 0
        af = np.where(called, d, 0).sum(1) / (2.0 * np.maximum(
            called.sum(1), 1))
        assert got["n_variants"] == got["n_snp"] == len(sites)
        assert got["n_pass"] == sum(r.filters == ("PASS",) for r in sites)
        assert got["n_af"] == int(has.sum())
        assert abs(got["mean_af"] - af[has].mean()) < 1e-6
        np.testing.assert_allclose(got["sample_callrate"],
                                   called.mean(axis=0), atol=1e-9)
        return len(sites), prefix, "vcf", True, True, ()
    return run


def _coverage(files, mesh, cram, monkeypatch):
    from hadoop_bam_tpu.parallel.pipeline import coverage_file

    from test_cigar import _oracle_depth

    path, header, recs = files["bam"]
    window = 200000
    got = coverage_file(path, f"chr1:1-{window}", mesh=mesh, header=header,
                        tile_records=256)
    want = _oracle_depth(recs, header, "chr1", 0, window)
    assert want.sum() > 0
    assert got.tolist() == want.tolist()
    # no .bai: the whole file is read, every record decoded once
    return len(recs), None, "bam", True, False, ()


def _fastq_tensor_batches(files, mesh, cram, monkeypatch):
    from hadoop_bam_tpu.api.read_datasets import open_fastq
    from hadoop_bam_tpu.ops.seq_pallas import unpack_bases

    reads = files["reads"]
    code = {1: "A", 2: "C", 4: "G", 8: "T", 15: "N"}
    got = []
    for batch in open_fastq(files["fastq"]).tensor_batches(
            mesh=mesh, geometry=_geometry(), num_spans=7):
        assert set(batch) == {"seq_packed", "qual", "lengths", "n_records"}
        counts = np.asarray(batch["n_records"])
        seq = np.asarray(batch["seq_packed"])
        lens = np.asarray(batch["lengths"])
        for dev, c in enumerate(counts):
            bases = np.asarray(unpack_bases(seq[dev][:c]))
            got += ["".join(code[int(b)] for b in row[:n])
                    for row, n in zip(bases, lens[dev][:c])]
    assert got == [s[:160] for s, _ in reads]
    return len(reads), None, "fastq", False, False, ()


FAMILIES = {
    "bam-flagstat": _bam_flagstat,
    "bam-seq-stats": _bam_seq_stats,
    "bam-tensor-batches": _bam_tensor_batches,
    "fastq-plain": _fastq("fastq"),
    "fastq-gzip": _fastq("fastq.gz"),
    "qseq": _qseq,
    "cram-seq-stats": _cram,
    "bcf-vcf-stats": _variant("bcf", "vcf"),
    "vcf-text-vcf-stats": _variant("vcf.gz", "vcfgz"),
    "coverage": _coverage,
    "fastq-tensor-batches": _fastq_tensor_batches,
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_scan_family_answers_and_emits_what_its_readers_read(
        family, files, mesh, cram, monkeypatch):
    with MetricsContext() as m:
        n, prefix, fmt, eager, executed, absent = FAMILIES[family](
            files, mesh, cram, monkeypatch)
    snap = m.snapshot()
    names = set(snap["counters"]) | set(snap["wall_timers"])
    assert m.get("pipeline.records") == n
    missing = _loop_names(fmt, eager, executed) - names
    assert not missing, missing
    if prefix is not None:
        skip = _NOT_STRUCTURAL | set(absent)
        all_of, any_of = _reader_names(prefix)
        missing = all_of - skip - names
        assert not missing, missing
        for group in any_of:
            if group - skip:
                assert group & names, group
    # absent by design means absent: a name the case excuses is not there
    assert not set(absent) & names, set(absent) & names
