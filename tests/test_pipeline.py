"""Mesh pipeline tests on the virtual 8-device CPU mesh."""
import os

import numpy as np
import pytest

from hadoop_bam_tpu.formats.bamio import BamWriter
from hadoop_bam_tpu.parallel.distributed import assign_spans, broadcast_plan
from hadoop_bam_tpu.parallel.mesh import make_mesh
from hadoop_bam_tpu.parallel.pipeline import (
    DecodeGeometry, decode_span_host, flagstat_file, iter_span_groups,
    make_unpack_step, stack_span_group,
)
from hadoop_bam_tpu.split.planners import plan_bam_spans

from fixtures import make_header, make_records


@pytest.fixture(scope="module")
def bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("pipe") / "p.bam")
    header = make_header()
    records = make_records(header, 5000, seed=11)
    with BamWriter(path, header, track_voffsets=True) as w:
        for r in records:
            w.write_sam_record(r)
        voffs = list(w.record_voffsets())
    return path, header, records, voffs


GEOM = DecodeGeometry(bytes_cap=1 << 21, records_cap=1 << 14)


def test_decode_span_host_union(bam):
    """Union-exactly-once for the pipeline's own span decoder."""
    path, header, records, voffs = bam
    spans = plan_bam_spans(path, num_spans=7, header=header)
    got = []
    for s in spans:
        d, o, n, v = decode_span_host(path, s, GEOM)
        got.extend(int(x) for x in v)
        assert n == v.size
    assert got == voffs


def test_record_chain_spanning_many_blocks(tmp_path):
    """A record whose bytes span >=64 BGZF blocks decodes correctly — the
    span decoder's tail-extension path (one concatenate, not a per-block
    re-copy) must fetch the whole chain."""
    from hadoop_bam_tpu.api.dataset import open_bam
    from hadoop_bam_tpu.formats import bgzf
    from hadoop_bam_tpu.formats.bamio import read_bam
    from hadoop_bam_tpu.parallel.pipeline import _decode_span_core
    from hadoop_bam_tpu.split.spans import FileVirtualSpan

    header = make_header()
    # header-only BAM, EOF stripped, then the record re-blocked tiny
    base = str(tmp_path / "hdr.bam")
    with BamWriter(base, header) as w:
        pass
    hdr_bytes = open(base, "rb").read()[:-len(bgzf.EOF_BLOCK)]

    recs = make_records(header, 2, seed=3)
    tmp = str(tmp_path / "tmp.bam")
    with BamWriter(tmp, header) as w:
        w.write_sam_record(recs[0])
        long = recs[1]
        long.seq = "ACGT" * 30000          # 120k bases -> ~180 KB record
        long.qual = "I" * len(long.seq)
        long.cigar = f"{len(long.seq)}M"
        w.write_sam_record(long)
    _, tmp_batch = read_bam(tmp)
    wire = [tmp_batch.record_bytes(0), tmp_batch.record_bytes(1)]

    payload = b"".join(wire)
    chunk = 1024                            # ~180 blocks for the chain
    blocks = b"".join(bgzf.deflate_block(payload[i:i + chunk])
                      for i in range(0, len(payload), chunk))
    path = str(tmp_path / "chain.bam")
    with open(path, "wb") as f:
        f.write(hdr_bytes + blocks + bgzf.EOF_BLOCK)

    first_c = len(hdr_bytes)
    # span owns only the first block: both records start in it, the second
    # extends across the whole chain
    span = FileVirtualSpan(path, (first_c << 16),
                           ((first_c + bgzf.parse_block_header(
                               open(path, "rb").read()[first_c:], 0
                           ).block_size) << 16))
    data, offs, voffs, _ = _decode_span_core(path, span)
    assert offs.size == 2
    got = [bytes(data[int(offs[0]):int(offs[1])]),
           bytes(data[int(offs[1]):int(offs[1]) + len(wire[1])])]
    assert got == wire

    # and the dataset surface decodes it end to end
    ds = open_bam(path)
    batches = list(ds.batches())
    total = sum(len(b) for b in batches)
    assert total == 2
    last = batches[-1]
    assert last.read_name(len(last) - 1) == long.qname
    assert last.seq_string(len(last) - 1) == long.seq


def test_mesh_has_8_devices():
    mesh = make_mesh()
    assert int(np.prod(mesh.devices.shape)) == 8


def test_flagstat_file_on_mesh(bam):
    path, header, records, voffs = bam
    mesh = make_mesh()
    stats = flagstat_file(path, mesh=mesh, geometry=GEOM, header=header)
    flags = np.asarray([r.flag for r in records])
    assert stats["total"] == len(records)
    assert stats["mapped"] == int(np.sum((flags & 0x4) == 0))
    assert stats["paired"] == int(np.sum((flags & 0x1) != 0))
    assert stats["secondary"] == int(np.sum((flags & 0x100) != 0))


def test_unpack_step_sharded(bam):
    path, header, records, voffs = bam
    mesh = make_mesh()
    spans = plan_bam_spans(path, num_spans=8, header=header)
    group = list(iter_span_groups(spans, 8))[0]
    batch = stack_span_group(path, group, 8, GEOM)
    step = make_unpack_step(mesh)
    cols = step(batch.data, batch.offsets, batch.n_records)
    assert cols["pos"].shape == (8, GEOM.records_cap)
    # device 0's first records match host decode of span 0
    d, o, n, v = decode_span_host(path, group[0], GEOM)
    from hadoop_bam_tpu.formats.bam import BamBatch
    hb = BamBatch(d, o[:n].astype(np.int64))
    np.testing.assert_array_equal(np.asarray(cols["pos"])[0, :n], hb.pos)
    valid = np.asarray(cols["valid"])
    assert valid[0, :n].all() and not valid[0, n:].any()


def test_decode_span_prefix_host_matches_span_mode(bam):
    """Prefix-tile rows must equal the 36-byte record prefixes from the
    full-span decode, for both native and NumPy-fallback packers."""
    path, header, records, voffs = bam
    from hadoop_bam_tpu.parallel.pipeline import decode_span_prefix_host
    spans = plan_bam_spans(path, num_spans=5, header=header)
    got_voffs = []
    for s in spans:
        d, o, n, v = decode_span_host(path, s, GEOM)
        rows, pv = decode_span_prefix_host(path, s)
        assert rows.shape == (n, 36)
        got_voffs.extend(int(x) for x in pv)
        idx = o[:n].astype(np.int64)[:, None] + np.arange(36)[None, :]
        np.testing.assert_array_equal(rows, d[idx])
    assert got_voffs == voffs


def test_projection_pack_and_unpack(bam):
    """Projected rows decode to the same columns as the full-field path."""
    path, header, records, voffs = bam
    from hadoop_bam_tpu.ops.unpack_bam import (
        FLAGSTAT_PROJECTION, projection_ranges, projection_row_bytes,
        unpack_projected_tile,
    )
    from hadoop_bam_tpu.parallel.pipeline import decode_span_prefix_host
    assert projection_ranges(tuple(
        ["block_size", "refid", "pos", "l_read_name", "mapq", "bin",
         "n_cigar", "flag", "l_seq", "mate_refid", "mate_pos", "tlen"])) \
        == [(0, 36)]
    spans = plan_bam_spans(path, num_spans=3, header=header)
    rows, _ = decode_span_prefix_host(
        path, spans[0], projection=FLAGSTAT_PROJECTION, want_voffs=False)
    assert rows.shape[1] == projection_row_bytes(FLAGSTAT_PROJECTION) == 11
    cols = unpack_projected_tile(rows, FLAGSTAT_PROJECTION)
    full, _ = decode_span_prefix_host(path, spans[0])
    from hadoop_bam_tpu.ops.unpack_bam import unpack_fixed_fields_tile
    ref = unpack_fixed_fields_tile(full)
    for name in FLAGSTAT_PROJECTION:
        np.testing.assert_array_equal(np.asarray(cols[name]),
                                      np.asarray(ref[name]))


def test_native_walk_packed_matches_fallback(bam):
    path, header, records, voffs = bam
    from hadoop_bam_tpu.utils import native
    if not native.available():
        pytest.skip("native library unavailable")
    from hadoop_bam_tpu.ops import inflate as inflate_ops
    from hadoop_bam_tpu.formats.bam import SAMHeader
    raw = open(path, "rb").read()
    data, _ = inflate_ops.inflate_span(raw)
    _, after = SAMHeader.from_bam_bytes(data.tobytes())
    offs, tail = inflate_ops.walk_records(data, start=after)
    rows, offs2, tail2 = native.walk_bam_packed(
        data, after, offs.size + 16, [(18, 2), (4, 4)], 6)
    np.testing.assert_array_equal(offs, offs2)
    assert tail == tail2
    # spot-check packing: bytes 18-19 (flag) then 4-7 (refid)
    i = len(records) // 2
    rec_off = int(offs[i])
    np.testing.assert_array_equal(rows[i, :2], data[rec_off + 18:rec_off + 20])
    np.testing.assert_array_equal(rows[i, 2:6], data[rec_off + 4:rec_off + 8])


def test_broadcast_and_assign(bam):
    path, header, *_ = bam
    spans = plan_bam_spans(path, num_spans=6, header=header)
    assert broadcast_plan(spans) == spans
    # partition over 3 fake hosts: disjoint cover
    parts = [assign_spans(spans, index=i, count=3) for i in range(3)]
    flat = [s for p in parts for s in p]
    assert sorted(flat, key=lambda s: s.start_voffset) == spans
    assert all(len(p) >= 1 for p in parts)


def test_two_host_simulation(bam):
    """Simulate the multi-host protocol single-process: host 0 plans,
    every 'host' decodes only its assigned spans, and the per-host stats
    sum to the whole-file answer (psum-over-DCN equivalence)."""
    path, header, records, voffs = bam
    from hadoop_bam_tpu.ops.flagstat import FLAGSTAT_FIELDS
    from hadoop_bam_tpu.parallel.pipeline import flagstat_file

    spans = plan_bam_spans(path, num_spans=6, header=header)
    whole = flagstat_file(path, header=header, spans=spans)
    merged = {k: 0 for k in FLAGSTAT_FIELDS}
    for host in range(2):
        part = assign_spans(spans, index=host, count=2)
        assert part, "each host must get work"
        stats = flagstat_file(path, header=header, spans=part)
        for k in FLAGSTAT_FIELDS:
            merged[k] += stats[k]
    assert merged == whole
    assert whole["total"] == len(records)


_DIST_STATS_CHILD = """\
import json, os, sys
import numpy as np
idx, port, bam_src, vcf_src, fq_src = (int(sys.argv[1]), sys.argv[2],
                                       sys.argv[3], sys.argv[4],
                                       sys.argv[5])
# 2 virtual CPU devices per process via XLA_FLAGS: works on every jax
# (the jax_num_cpu_devices config option only exists on newer releases)
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(f"localhost:{port}", num_processes=2,
                           process_id=idx)
from hadoop_bam_tpu.parallel.distributed import (
    distributed_coverage, distributed_fastq_seq_stats, distributed_flagstat,
    distributed_seq_stats, distributed_variant_stats,
)
print("FLAGSTAT", json.dumps(distributed_flagstat(bam_src)), flush=True)
cov = distributed_coverage(bam_src, "chr1:1-16384")
print("COV", json.dumps([int(x) for x in cov]), flush=True)
s = distributed_seq_stats(bam_src)
s["base_hist"] = [int(v) for v in s["base_hist"]]
print("SEQ", json.dumps(s), flush=True)
v = distributed_variant_stats(vcf_src)
v["sample_callrate"] = [round(float(x), 9) for x in v["sample_callrate"]]
print("VAR", json.dumps(v), flush=True)
f = distributed_fastq_seq_stats(fq_src)
f["base_hist"] = [int(x) for x in f["base_hist"]]
print("FQ", json.dumps(f), flush=True)
"""


def test_distributed_stats_two_process(bam, tmp_path):
    """REAL 2-process jax.distributed stats drivers (gloo CPU
    collectives): host 0 plans + broadcasts, each process reduces only
    its share over its local devices, one allgather combines — both
    processes must report whole-file answers matching single-process."""
    import json

    from _multihost import run_two_process
    from hadoop_bam_tpu.api.writers import open_vcf_writer
    from hadoop_bam_tpu.formats.vcf import VCFHeader, VcfRecord
    from hadoop_bam_tpu.parallel.pipeline import seq_stats_file
    from hadoop_bam_tpu.parallel.variant_pipeline import variant_stats_file

    path, header, records, _ = bam
    whole = flagstat_file(path, header=header)
    whole_seq = seq_stats_file(path, header=header)

    vh = VCFHeader.from_text(
        "##fileformat=VCFv4.2\n##contig=<ID=chr1,length=248956422>\n"
        '##FORMAT=<ID=GT,Number=1,Type=String,Description="G">\n'
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\ts0\n")
    vcf_path = str(tmp_path / "dist.vcf.gz")
    with open_vcf_writer(vcf_path, vh) as w:
        for i in range(500):
            w.write_record(VcfRecord.from_line(
                f"chr1\t{100 + i * 7}\t.\tA\tC\t30\tPASS\t.\tGT\t"
                f"{'0/1' if i % 3 else './.'}"))
    whole_var = variant_stats_file(vcf_path)

    import random as _random
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file
    rng = _random.Random(9)
    fq_path = str(tmp_path / "dist.fastq")
    with open(fq_path, "w") as f:
        for i in range(3000):
            seq = "".join(rng.choice("ACGT") for _ in range(100))
            qual = "".join(chr(33 + rng.randint(2, 40)) for _ in range(100))
            f.write(f"@r{i}\n{seq}\n+\n{qual}\n")
    whole_fq = fastq_seq_stats_file(fq_path)

    from hadoop_bam_tpu.parallel.pipeline import coverage_file
    whole_cov = [int(x) for x in coverage_file(path, "chr1:1-16384",
                                               header=header)]

    got = {"FLAGSTAT": [], "SEQ": [], "VAR": [], "FQ": [], "COV": []}
    for rc, so, se in run_two_process(tmp_path, _DIST_STATS_CHILD,
                                      [path, vcf_path, fq_path]):
        assert rc == 0, f"child failed:\n{so}\n{se[-2000:]}"
        for key in got:
            line = next(ln for ln in so.splitlines()
                        if ln.startswith(key + " "))
            got[key].append(json.loads(line[len(key) + 1:]))
    assert got["FLAGSTAT"][0] == got["FLAGSTAT"][1] == whole
    # per-base depths sum exactly across hosts: integer equality
    assert got["COV"][0] == got["COV"][1] == whole_cov
    assert sum(whole_cov) > 0
    for g in got["SEQ"]:
        assert g["n_reads"] == whole_seq["n_reads"]
        # f32 partial sums regroup across hosts: tolerance is f32-scale
        assert abs(g["mean_gc"] - whole_seq["mean_gc"]) < 1e-4
        assert abs(g["mean_qual"] - whole_seq["mean_qual"]) < 1e-4
        assert g["base_hist"] == [int(v) for v in whole_seq["base_hist"]]
    for g in got["VAR"]:
        assert g["n_variants"] == whole_var["n_variants"] == 500
        assert g["n_snp"] == whole_var["n_snp"]
        assert g["n_pass"] == whole_var["n_pass"]
        assert abs(g["mean_af"] - whole_var["mean_af"]) < 1e-4
    for g in got["FQ"]:
        assert g["n_reads"] == whole_fq["n_reads"] == 3000
        assert abs(g["mean_gc"] - whole_fq["mean_gc"]) < 1e-4
        assert abs(g["mean_qual"] - whole_fq["mean_qual"]) < 1e-4
        assert g["base_hist"] == [int(v) for v in whole_fq["base_hist"]]
    assert whole["total"] == len(records)


def test_bucketed_final_tile_matches_full_cap(tmp_path):
    """The small-input dispatch ladder (_bucket_cap): a file far smaller
    than tile_records dispatches a shrunk final tile, and every stats
    answer is identical to the full-cap geometry's."""
    import random as _random

    from hadoop_bam_tpu.parallel.pipeline import (
        PayloadGeometry, _bucket_cap, fastq_seq_stats_file,
    )

    # ladder arithmetic: block_n-aligned, monotone, capped
    assert _bucket_cap(100, 1 << 16, 256) == 4096
    assert _bucket_cap(5000, 1 << 16, 256) == 16384
    assert _bucket_cap(40000, 1 << 16, 256) == 1 << 16
    assert _bucket_cap(100, 1536, 256) == 256       # cap//16 rounded up
    assert _bucket_cap(100, 256, 256) == 256        # no smaller bucket
    for cap, bn in ((1 << 16, 256), (1536, 256), (32768, 8192)):
        for c in (1, 200, cap // 4, cap):
            b = _bucket_cap(c, cap, bn)
            assert b % bn == 0 and c <= b <= cap

    rng = _random.Random(21)
    fq = str(tmp_path / "small.fastq")
    with open(fq, "w") as f:
        for i in range(700):
            seq = "".join(rng.choice("ACGT") for _ in range(80))
            qual = "".join(chr(33 + rng.randint(2, 40)) for _ in range(80))
            f.write(f"@r{i}\n{seq}\n+\n{qual}\n")

    big = PayloadGeometry(tile_records=4096, block_n=256)
    small = PayloadGeometry(tile_records=256, block_n=256)
    got = fastq_seq_stats_file(fq, geometry=big)        # shrink path
    want = fastq_seq_stats_file(fq, geometry=small)     # full tiles only
    assert got["n_reads"] == want["n_reads"] == 700
    assert abs(got["mean_gc"] - want["mean_gc"]) < 1e-5
    assert abs(got["mean_qual"] - want["mean_qual"]) < 1e-5
    assert [int(v) for v in got["base_hist"]] == \
        [int(v) for v in want["base_hist"]]


def test_bucketed_tensor_batches_shapes(tmp_path):
    """tensor_batches: full batches keep tile_records rows; the final
    batch may shrink to a bucket, and totals are unchanged."""
    import numpy as np

    from hadoop_bam_tpu.api.read_datasets import open_fastq
    from hadoop_bam_tpu.parallel.pipeline import PayloadGeometry

    fq = str(tmp_path / "shapes.fastq")
    with open(fq, "w") as f:
        for i in range(600):
            f.write(f"@r{i}\nACGTACGTAC\n+\nIIIIIIIIII\n")
    geom = PayloadGeometry(tile_records=4096, block_n=256)
    batches = list(open_fastq(fq).tensor_batches(geometry=geom))
    total = sum(int(np.asarray(b["n_records"]).sum()) for b in batches)
    assert total == 600
    # the lone batch shrank to the smallest bucket that holds 600 rows
    assert batches[-1]["qual"].shape[1] <= 1024


def test_fixed_shape_geometry_pads_final_batch(tmp_path):
    """PayloadGeometry(fixed_shape=True): the final batch PADS to
    tile_records instead of shrinking — the opt-out for consumers that
    preallocate by tile_records.  Totals are unchanged."""
    import numpy as np

    from hadoop_bam_tpu.api.dataset import open_bam
    from hadoop_bam_tpu.api.read_datasets import open_fastq
    from hadoop_bam_tpu.parallel.pipeline import PayloadGeometry

    fq = str(tmp_path / "fixed.fastq")
    with open(fq, "w") as f:
        for i in range(600):
            f.write(f"@r{i}\nACGTACGTAC\n+\nIIIIIIIIII\n")
    geom = PayloadGeometry(tile_records=4096, block_n=256,
                           fixed_shape=True)
    batches = list(open_fastq(fq).tensor_batches(geometry=geom))
    assert all(b["qual"].shape[1] == 4096 for b in batches)
    assert sum(int(np.asarray(b["n_records"]).sum())
               for b in batches) == 600

    # the BAM payload feed honors it too
    bam = str(tmp_path / "fixed.bam")
    header = make_header()
    with BamWriter(bam, header) as w:
        for r in make_records(header, 500, seed=3):
            w.write_sam_record(r)
    batches = list(open_bam(bam).tensor_batches(geometry=geom))
    assert all(b["prefix"].shape[1] == 4096 for b in batches)
    assert sum(int(np.asarray(b["n_records"]).sum())
               for b in batches) == 500


def test_assign_spans_empty_plan():
    """A .bai-pruned region with zero aligned reads yields an empty
    plan; every host must receive an empty assignment (not IndexError)
    so distributed coverage of read-free tiles returns zeros."""
    assert assign_spans([], index=0, count=2) == []
    assert assign_spans([], index=1, count=2) == []
    assert assign_spans([], index=0, count=1) == []


# ---------------------------------------------------------------------------
# which code inflates: config.resolve_inflate_backend, the one place
# ---------------------------------------------------------------------------

def test_inflate_backend_knob_and_selector():
    from hadoop_bam_tpu.config import (
        INFLATE_BACKENDS, HBamConfig, resolve_inflate_backend,
    )
    from hadoop_bam_tpu.utils.errors import PlanError

    assert INFLATE_BACKENDS == ("auto", "native", "zlib")
    cfg = HBamConfig.from_dict({"hbam.inflate-backend": "zlib"})
    assert cfg.inflate_backend == "zlib"
    assert resolve_inflate_backend(cfg) == "zlib"
    assert resolve_inflate_backend(
        HBamConfig(inflate_backend="native")) == "native"
    assert resolve_inflate_backend(HBamConfig()) == "native"     # "auto"
    assert resolve_inflate_backend(None) == "native"
    for name in ("warp", "device"):
        with pytest.raises(PlanError, match="expected one of") as ei:
            resolve_inflate_backend(HBamConfig(inflate_backend=name))
        assert all(repr(b) in str(ei.value) for b in INFLATE_BACKENDS)


def test_auto_backend_resolves_without_jax(monkeypatch):
    """"auto" is a pure function of the config: no probe, no compile, no
    JAX call — whatever the backend calls itself.  (Every process on the
    chip used to jit-compile and time a device resolve step here.)"""
    import jax

    from hadoop_bam_tpu import config as hconfig

    touched = []

    def refuse(*a, **kw):
        touched.append(a)
        raise RuntimeError("resolve_inflate_backend touched JAX")

    # a backend that does not call itself "cpu", were anything to ask
    monkeypatch.setattr(jax, "default_backend",
                        lambda: touched.append("backend") or "tpu")
    for name in ("jit", "devices", "device_put"):
        monkeypatch.setattr(jax, name, refuse)
    monkeypatch.setattr(jax.numpy, "asarray", refuse)
    assert hconfig.resolve_inflate_backend(hconfig.HBamConfig()) == "native"
    assert touched == []
    assert not hasattr(hconfig, "plane_probe_report")
    assert not hasattr(hconfig, "_PLANE_CACHE")


def test_flagstat_zlib_backend_honored(tmp_path):
    """inflate_backend='zlib' rides the host path with the fused native
    plane disabled — same totals, portable plane."""
    import dataclasses

    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.formats.bamio import write_bam
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    header = make_header()
    path = str(tmp_path / "z.bam")
    write_bam(path, header, make_records(header, 500, seed=2))
    with MetricsContext() as m:
        host = flagstat_file(path)
    assert m.get("decode.native_jobs") > 0        # the fused sweep ran
    cfg = dataclasses.replace(DEFAULT_CONFIG, inflate_backend="zlib")
    with MetricsContext() as m:
        assert flagstat_file(path, config=cfg) == host
    assert m.get("decode.native_jobs") == 0
