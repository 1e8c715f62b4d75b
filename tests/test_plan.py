"""Plan/execute layer tests (hadoop_bam_tpu/plan/).

The load-bearing pins:

- **Byte/value identity per rewired driver**: every driver that became
  a thin plan builder (flagstat, seq_stats, variant_stats, query-engine
  chunk decode, cohort tensor_batches) produces output identical to the
  pre-refactor direct path — the inline mesh-feed impls it now wraps.
- **Plane selection in ONE function**: ``select_plane`` is the single
  predicate table; the gate matrix (intervals x skip_bad_spans x
  backend x native-missing x family) is pinned combination by
  combination, including the rejection reasons ``hbam explain``
  prints.
- **Digest stability**: the IR serialization is canonical — same plan,
  same digest across processes; any field change moves it; the format
  matches ``jobs.journal.plan_digest`` (24 hex chars) so the two can
  share journal headers.
"""
import dataclasses
import json
import re

import numpy as np
import pytest

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.formats.bamio import BamWriter
from hadoop_bam_tpu.plan import builders
from hadoop_bam_tpu.plan.executor import select_plane
from hadoop_bam_tpu.plan.ir import (
    PlanIR, SinkIR, SourceIR, SpansIR, op_node,
)
from tests.fixtures import make_header, make_records

pytestmark = pytest.mark.plan


@pytest.fixture(scope="module")
def bam(tmp_path_factory):
    d = tmp_path_factory.mktemp("plan")
    header = make_header()
    recs = make_records(header, 500, seed=11)
    path = str(d / "plan.bam")
    with BamWriter(path, header) as w:
        for r in recs:
            w.write_sam_record(r)
    return path, header, recs


# ---------------------------------------------------------------------------
# IR digest
# ---------------------------------------------------------------------------

def test_digest_stable_and_plan_digest_compatible(bam):
    path, _, _ = bam
    a = builders.flagstat_plan(path)
    b = builders.flagstat_plan(path)
    assert a == b
    assert a.digest() == b.digest()
    # the jobs.journal.plan_digest format: 24 lowercase hex chars
    assert re.fullmatch(r"[0-9a-f]{24}", a.digest())
    # any field change moves the digest
    other = builders.flagstat_plan(path + ".other")
    assert other.digest() != a.digest()
    cfg = dataclasses.replace(DEFAULT_CONFIG, bam_intervals="chr1")
    assert builders.flagstat_plan(path, cfg).digest() != a.digest()
    # and the doc round-trips through canonical JSON
    doc = json.loads(json.dumps(a.to_doc(), sort_keys=True))
    assert doc["source"]["fmt"] == "bam"
    assert doc["sink"]["kind"] == "flagstat"
    assert [o["op"] for o in doc["ops"]] == ["project", "flagstat_reduce"]


# Digests of the scan and query plans as the parent of PR 30 computed
# them (commit a0c6db5, default config): journals written before the
# on-mesh decode plane was deleted must keep resuming.
_PARENT_DIGESTS = {
    "flagstat": "49b6e793209ed79f83785074",
    "seq_stats": "20ec2ff440d832936a30b064",
    "variant_stats_bcf": "97f470ead550d1b20e311557",
    "variant_stats_vcf": "8b8f7023e089e56eef57d23f",
    "query_chunk": "bff6ff5d4b22c71639959132",
    "query_region": "baad55ad16475495a68f77c0",
    "serve_tile": "5e0a3d81d12536fb73c91066",
}
_GWAS_DIGEST = "84825dee93d292ee5b065ae0"


@pytest.mark.parametrize("backend", ["auto", "native", "zlib"])
def test_plan_digests_unchanged(backend):
    cfg = dataclasses.replace(DEFAULT_CONFIG, inflate_backend=backend)
    bam_path = "/data/na12878.bam"
    got = {
        "flagstat": builders.flagstat_plan(bam_path, cfg),
        "seq_stats": builders.seq_stats_plan(bam_path, cfg),
        "variant_stats_bcf": builders.variant_stats_plan(
            "/data/kgp3.bcf", cfg),
        "variant_stats_vcf": builders.variant_stats_plan(
            "/data/kgp3.vcf.gz", cfg),
        "query_chunk": builders.query_chunk_plan(
            bam_path, "bam", 65536, 1 << 24),
        "query_region": builders.query_region_plan(
            bam_path, "bam", "chr20:1000-2000",
            [(65536, 1 << 20), (1 << 21, 1 << 22)]),
        "serve_tile": builders.serve_tile_plan(
            bam_path, "bam", 65536, 1 << 24),
    }
    assert {k: p.digest() for k, p in got.items()} == _PARENT_DIGESTS
    # the plan PR 32 added: pinned from its first commit on, and moved by
    # the trait file (part of the job's identity)
    gwas = builders.variant_gwas_plan("/data/kgp3.bcf", "/data/traits.tsv",
                                      cfg)
    assert gwas.digest() == _GWAS_DIGEST
    assert gwas.sink.kind == "variant_gwas"
    assert [o.op for o in gwas.ops] == [
        "variant_pack", "resident_load", "grm_accumulate", "covariates",
        "assoc_scan"]
    assert builders.variant_gwas_plan(
        "/data/kgp3.bcf", "/data/other.tsv", cfg).digest() != _GWAS_DIGEST


def test_pinned_spans_and_param_normalization():
    s = SpansIR.pin([("f.bam", 7, 99)])
    assert s.mode == "pinned" and s.pinned == (("f.bam", 7, 99),)
    assert "pinned" in s.summary()
    # list and tuple params digest identically
    assert op_node("x", cols=["a", "b"]) == op_node("x", cols=("a", "b"))
    with pytest.raises(TypeError):
        op_node("x", bad=object())
    plan = PlanIR(SourceIR("f.bam", "bam", role="chunk"), s,
                  (op_node("chunk_decode"),), SinkIR.of("chunk_columns"))
    assert plan.to_doc()["spans"]["pinned"][0][1:] == [7, 99]


# ---------------------------------------------------------------------------
# plane selection: the gate matrix
# ---------------------------------------------------------------------------

def _cfg(**kw):
    return dataclasses.replace(HBamConfig(), **kw)


def _rejected(decision):
    return dict(decision.rejected)


def test_select_native_clean_path():
    from hadoop_bam_tpu.ops.inflate import fused_available
    d = select_plane(_cfg(inflate_backend="native"))
    assert d.plane == "native"
    assert d.use_fused == fused_available()
    assert d.stream_fused == fused_available()
    assert set(_rejected(d)) == (set() if fused_available() else {"fused"})


def test_select_zlib_pins_portable_plane():
    d = select_plane(_cfg(inflate_backend="zlib"))
    assert d.plane == "zlib"
    assert not d.use_fused and not d.stream_fused
    rej = _rejected(d)
    assert "native" in rej and "fused" in rej


def test_select_fused_stream_rejected_by_intervals():
    d = select_plane(_cfg(inflate_backend="native"), intervals=[()])
    assert d.plane == "native"
    # the sweep itself stays eligible; only chunk streaming is gated
    from hadoop_bam_tpu.ops.inflate import fused_available
    assert d.use_fused == fused_available() and not d.stream_fused
    if fused_available():
        assert "whole span's offsets" in _rejected(d)["fused-stream"]


def test_select_fused_stream_rejected_by_skip_bad_spans():
    d = select_plane(_cfg(inflate_backend="native", skip_bad_spans=True))
    assert d.plane == "native"
    from hadoop_bam_tpu.ops.inflate import fused_available
    assert d.use_fused == fused_available() and not d.stream_fused
    if fused_available():
        assert "quarantine" in _rejected(d)["fused-stream"]


@pytest.mark.parametrize("family", ["payload", "variant", "serve"])
def test_select_families_share_the_gate_matrix(family, monkeypatch):
    """Every family decides through the SAME gates as flagstat:
    intervals and skip_bad_spans gate chunk streaming, zlib pins the
    portable plane with the sweep off — reason strings included (the
    `hbam explain` surface and the `hbam serve` health report)."""
    from hadoop_bam_tpu.ops import inflate as inflate_ops
    from hadoop_bam_tpu.plan.executor import plane_report
    monkeypatch.setattr(inflate_ops, "fused_available", lambda: True)

    def report(**kw):
        rep = plane_report(_cfg(**kw))
        assert rep[family] == rep["flagstat"]
        return rep[family]

    d = select_plane(_cfg(), intervals=[()])
    assert report(bam_intervals="chr1") == d.to_doc()
    assert d.plane == "native" and d.use_fused and not d.stream_fused
    assert "whole span's offsets" in _rejected(d)["fused-stream"]

    d = select_plane(_cfg(skip_bad_spans=True))
    assert report(skip_bad_spans=True) == d.to_doc()
    assert d.plane == "native" and d.use_fused and not d.stream_fused
    assert "quarantine" in _rejected(d)["fused-stream"]

    d = select_plane(_cfg(inflate_backend="zlib"), intervals=[()])
    assert report(inflate_backend="zlib", bam_intervals="chr1") == d.to_doc()
    assert d.plane == "zlib" and not d.use_fused and not d.stream_fused
    assert set(_rejected(d)) == {"fused", "native"}

    d = select_plane(_cfg())
    assert report() == d.to_doc()
    assert d.plane == "native" and d.use_fused and d.stream_fused
    assert _rejected(d) == {}


def test_inflate_backend_device_is_refused(bam, capsys):
    """The deleted on-mesh decode plane left no name behind: a config
    that asks for it is a PlanError listing the three valid values, at
    selection and at execution, and the CLI's parser rejects the flag
    value."""
    from hadoop_bam_tpu.config import INFLATE_BACKENDS
    from hadoop_bam_tpu.parallel.pipeline import flagstat_file
    from hadoop_bam_tpu.parallel.variant_pipeline import variant_stats_file
    from hadoop_bam_tpu.tools.cli import main
    from hadoop_bam_tpu.utils.errors import PLAN, PlanError, classify_error

    assert INFLATE_BACKENDS == ("auto", "native", "zlib")
    path, header, _ = bam
    cfg = _cfg(inflate_backend="device")
    for run in (lambda: select_plane(cfg),
                lambda: flagstat_file(path, config=cfg, header=header),
                lambda: variant_stats_file(path + ".bcf", config=cfg)):
        with pytest.raises(PlanError) as ei:
            run()
        assert classify_error(ei.value) == PLAN
        for name in INFLATE_BACKENDS:
            assert repr(name) in str(ei.value)
    with pytest.raises(SystemExit) as se:
        main(["explain", "flagstat", path, "--inflate-backend", "device"])
    assert se.value.code == 2
    assert "invalid choice: 'device'" in capsys.readouterr().err


def test_select_native_missing_disables_fused(monkeypatch):
    from hadoop_bam_tpu.ops import inflate as inflate_ops
    monkeypatch.setattr(inflate_ops, "fused_available", lambda: False)
    d = select_plane(_cfg(inflate_backend="native"))
    assert d.plane == "native"
    assert not d.use_fused and not d.stream_fused
    assert "unavailable" in _rejected(d)["fused"]


def test_select_fused_off_by_config():
    d = select_plane(_cfg(inflate_backend="native",
                          use_fused_decode=False))
    assert not d.use_fused and not d.stream_fused
    assert "use_fused_decode" in _rejected(d)["fused"]


def test_plane_report_families():
    from hadoop_bam_tpu.plan.executor import plane_report
    rep = plane_report(_cfg(inflate_backend="native"))
    assert set(rep) == {"flagstat", "payload", "variant", "serve"}
    for fam in rep.values():
        assert fam["plane"] == "native"
        assert set(fam) == {"plane", "use_fused", "stream_fused",
                            "rejected"}
        assert isinstance(fam["rejected"], dict)
    # under the zlib backend every family routes zlib, the sweep off
    z = plane_report(_cfg(inflate_backend="zlib"))
    assert all(f["plane"] == "zlib" and not f["use_fused"]
               for f in z.values())


# ---------------------------------------------------------------------------
# byte/value identity: plan path vs the pre-refactor direct path
# ---------------------------------------------------------------------------

def test_flagstat_plan_path_identical(bam):
    from hadoop_bam_tpu.parallel.pipeline import (
        _flagstat_impl, flagstat_file,
    )
    path, header, _ = bam
    via_plan = flagstat_file(path, header=header)
    inline = _flagstat_impl(path, header=header)
    assert via_plan == inline
    assert via_plan["total"] == 500


def test_seq_stats_plan_path_identical(bam):
    from hadoop_bam_tpu.parallel.pipeline import (
        _seq_stats_impl, seq_stats_file,
    )
    path, header, _ = bam
    via_plan = seq_stats_file(path, header=header)
    inline = _seq_stats_impl(path, header=header)
    assert via_plan["n_reads"] == inline["n_reads"] > 0
    assert via_plan["mean_gc"] == inline["mean_gc"]
    assert via_plan["mean_qual"] == inline["mean_qual"]
    assert np.array_equal(via_plan["base_hist"], inline["base_hist"])


def test_variant_stats_plan_path_identical(tmp_path):
    from hadoop_bam_tpu.formats.vcf import VCFHeader, VcfRecord
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        _variant_stats_impl, variant_stats_file,
    )
    hdr = ("##fileformat=VCFv4.2\n"
           "##contig=<ID=c1,length=100000>\n"
           '##FORMAT=<ID=GT,Number=1,Type=String,Description="G">\n'
           "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\ts0\n")
    path = str(tmp_path / "v.vcf")
    with open(path, "w") as f:
        f.write(hdr)
        for i in range(300):
            gt = ("0/1", "1/1", "0/0", "./.")[i % 4]
            f.write(f"c1\t{100 + i}\t.\tA\tG\t30\tPASS\t.\tGT\t{gt}\n")
    via_plan = variant_stats_file(path)
    inline = _variant_stats_impl(path)
    for k in ("n_variants", "n_snp", "n_pass", "mean_af", "n_af"):
        assert via_plan[k] == inline[k]
    assert via_plan["n_variants"] == 300
    assert np.array_equal(via_plan["sample_callrate"],
                          inline["sample_callrate"])


def test_query_chunk_plan_path_identical(bam, tmp_path):
    from hadoop_bam_tpu.parallel.pipeline import decode_with_retry
    from hadoop_bam_tpu.query.engine import QueryEngine
    from hadoop_bam_tpu.split.spans import FileVirtualSpan
    from hadoop_bam_tpu.tools.cli import main
    path, header, _ = bam
    assert main(["index", "--flavor", "bai", path]) == 0
    engine = QueryEngine(config=DEFAULT_CONFIG)
    meta = engine._file_meta(path)
    _iv, ranges = engine._resolve(meta, "chr1")
    chunks = engine._coalesce(ranges, meta.kind)
    assert chunks
    s, e = chunks[0]
    via_plan, cost = engine._compute_chunk(meta, s, e)
    direct = decode_with_retry(
        lambda sp: engine._decode_chunk(meta, sp),
        FileVirtualSpan(meta.path, s, e), engine.config)
    assert cost == int(direct["nbytes"])
    assert via_plan["n"] == direct["n"] > 0
    for k in ("rid", "pos1", "end1"):
        assert np.array_equal(via_plan[k], direct[k])
    assert np.array_equal(via_plan["batch"].data, direct["batch"].data)


def test_cohort_plan_path_identical(tmp_path):
    """tensor_batches (plan path, executor-wired feed) vs an inline
    replica of the pre-refactor wiring (the first chunk's schema, a
    FeedPipeline, device_put)."""
    import itertools

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hadoop_bam_tpu.cohort import CohortDataset
    from hadoop_bam_tpu.parallel.mesh import make_mesh
    from hadoop_bam_tpu.parallel.staging import FeedPipeline, TileSpec

    hdr = ("##fileformat=VCFv4.2\n"
           "##contig=<ID=c1,length=100000>\n"
           '##FORMAT=<ID=GT,Number=1,Type=String,Description="G">\n')

    def write_sample(name, offset):
        p = str(tmp_path / name)
        with open(p, "w") as f:
            f.write(hdr + "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\t"
                          f"INFO\tFORMAT\t{name}\n")
            for i in range(60):
                gt = ("0/1", "1/1", "0/0")[(i + offset) % 3]
                f.write(f"c1\t{50 + 3 * i}\t.\tA\tT\t9\tPASS\t.\t"
                        f"GT\t{gt}\n")
        return p

    paths = [write_sample(f"s{i}.vcf", i) for i in range(3)]

    ds = CohortDataset(paths)
    got = list(ds.tensor_batches())

    ds2 = CohortDataset(paths)
    mesh = make_mesh()
    n_dev = int(np.prod(mesh.devices.shape))
    sharding = NamedSharding(mesh, P("data"))
    chunks = ds2.site_chunks()
    first = next(chunks)
    keys = list(first)
    pads = {"dosage": -1, "qual": np.nan}
    fp = FeedPipeline(n_dev, ds2.geometry.tile_records,
                      [TileSpec(first[k].shape[1:], first[k].dtype,
                                pads.get(k, 0)) for k in keys],
                      config=ds2.config, fixed_shape=True, fmt="cohort")
    tuples = (tuple(d[k] for k in keys)
              for d in itertools.chain([first], chunks))

    def emit(arrays, counts):
        out = {k: jax.device_put(a, sharding)
               for k, a in zip(keys, arrays)}
        out["n_records"] = jax.device_put(counts, sharding)
        return out

    want = list(fp.stream(tuples, emit))
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert set(g) == set(w)
        for k in g:
            ga, wa = np.asarray(g[k]), np.asarray(w[k])
            assert np.array_equal(ga, wa, equal_nan=(ga.dtype.kind
                                                     == "f"))


def test_cohort_tensor_batches_stays_lazy(tmp_path):
    """Building the batch iterator must start no join and open no
    journal (the executor runner is a generator)."""
    from hadoop_bam_tpu.cohort import CohortDataset
    hdr = ("##fileformat=VCFv4.2\n"
           "##contig=<ID=c1,length=1000>\n"
           '##FORMAT=<ID=GT,Number=1,Type=String,Description="G">\n')
    p = str(tmp_path / "s.vcf")
    with open(p, "w") as f:
        f.write(hdr + "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\t"
                      "FORMAT\ts\n")
        f.write("c1\t10\t.\tA\tT\t9\tPASS\t.\tGT\t0/1\n")
    jp = str(tmp_path / "j.hbam-journal")
    ds = CohortDataset([p], journal_path=jp)
    it = ds.tensor_batches()          # built, never iterated
    import os
    assert not os.path.exists(jp)
    assert not ds._journal_live
    del it
    assert len(list(ds.tensor_batches())) >= 1   # still usable after


# ---------------------------------------------------------------------------
# journal seam + executor surface
# ---------------------------------------------------------------------------

def test_plan_journal_params_carries_digest(bam):
    from hadoop_bam_tpu.jobs.runner import plan_journal_params
    path, _, _ = bam
    plan = builders.flagstat_plan(path)
    params = plan_journal_params(plan, {"input": path})
    assert params["plan_digest"] == plan.digest()
    assert params["input"] == path


def test_execute_counts_and_rejects_unknown_sink(bam):
    from hadoop_bam_tpu.plan.executor import execute
    from hadoop_bam_tpu.utils.errors import PlanError
    from hadoop_bam_tpu.utils.metrics import METRICS, MetricsContext
    path, header, _ = bam
    bad = PlanIR(SourceIR(path, "bam"), SpansIR.auto(),
                 (op_node("nope"),), SinkIR.of("nope"))
    with pytest.raises(PlanError):
        execute(bad)
    with MetricsContext():
        from hadoop_bam_tpu.parallel.pipeline import flagstat_file
        flagstat_file(path, header=header)
        snap = METRICS.snapshot()
    assert snap["counters"]["plan.executions"] == 1


def test_explain_cli_text_and_json(bam, capsys):
    from hadoop_bam_tpu.tools.cli import main
    path, _, _ = bam
    assert main(["explain", "flagstat", path]) == 0
    out = capsys.readouterr().out
    assert "plane   " in out and "sink    flagstat" in out

    assert "probe" not in out

    assert main(["explain", "flagstat", path, "--json",
                 "--inflate-backend", "zlib",
                 "--skip-bad-spans"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {"plan", "digest", "decision"}
    assert doc["digest"] == builders.flagstat_plan(path).digest()
    assert doc["decision"]["plane"] == "zlib"
    assert "portable" in doc["decision"]["rejected"]["native"]
    assert "zlib" in doc["decision"]["rejected"]["fused"]


def test_explain_cli_query_pins_chunks(bam, capsys):
    from hadoop_bam_tpu.tools.cli import main
    path, _, _ = bam
    main(["index", "--flavor", "bai", path])
    capsys.readouterr()               # drain the index verb's output
    assert main(["explain", "query", path, "--region", "chr1",
                 "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["plan"]["source"]["role"] == "chunk"
    assert len(doc["plan"]["spans"]["pinned"]) >= 1
    ops = [o["op"] for o in doc["plan"]["ops"]]
    assert ops == ["chunk_decode", "overlap_filter"]


def test_explain_cli_cohort(tmp_path, capsys):
    from hadoop_bam_tpu.tools.cli import main
    hdr = ("##fileformat=VCFv4.2\n"
           "##contig=<ID=c1,length=1000>\n"
           '##FORMAT=<ID=GT,Number=1,Type=String,Description="G">\n')
    p = tmp_path / "s.vcf"
    p.write_text(hdr + "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\t"
                       "FORMAT\ts\nc1\t10\t.\tA\tT\t9\tPASS\t.\tGT\t"
                       "0/1\n")
    man = tmp_path / "cohort.json"
    man.write_text(json.dumps(
        {"samples": [{"id": "s", "path": str(p)}]}))
    assert main(["explain", "cohort", str(man), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["plan"]["source"]["role"] == "join"
    assert doc["plan"]["sink"]["kind"] == "tensor_batches"
    assert doc["plan"]["ops"][0]["op"] == "kway_join"
    assert doc["plan"]["ops"][0]["params"]["samples"] == 1


def test_explain_cli_vcf_gwas(capsys):
    from hadoop_bam_tpu.tools.cli import main
    assert main(["explain", "vcf-gwas", "/data/kgp3.bcf", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["plan"]["sink"]["kind"] == "variant_gwas"
    assert doc["plan"]["source"]["fmt"] == "bcf"
    ops = {o["op"]: o.get("params", {}) for o in doc["plan"]["ops"]}
    assert ops["covariates"] == {"axes": 4}
    assert ops["grm_accumulate"] == {"maf_percent": 1}
    assert ops["assoc_scan"]["traits"] == "/data/kgp3.bcf.traits.tsv"
    assert main(["explain", "vcf-gwas", "/data/kgp3.bcf"]) == 0
    assert "sink    variant_gwas" in capsys.readouterr().out
