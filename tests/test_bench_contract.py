"""bench.py emission contract: the FINAL stdout line must stay under
FINAL_LINE_BUDGET so the driver's 2000-char tail always parses it
(VERDICT r5 next-round #1 — the r5 line grew to 2.2 KB and parsed as
null)."""
import importlib.util
import json
import os

import pytest


@pytest.fixture()
def bench():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")
    spec = importlib.util.spec_from_file_location("_bench_under_test",
                                                  os.path.abspath(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fill_state(bench, n_notes=6):
    rows = [
        ("bam_decode_records_per_sec_per_chip", 907987.4, "records/s", 2.87),
        ("bgzf_inflate_gbps", 0.305, "GB/s", 3.9),
        ("split_guess_p50_ms_per_boundary", 5.1, "ms", 1.6),
        ("faulted_flagstat_records_per_sec", 650123.9, "records/s", 0.93),
        ("cram_tensor_records_per_sec", 432087.1, "records/s", 6.7),
        ("vcf_variants_per_sec", 507001.2, "variants/s", 1.5),
        ("bcf_variants_per_sec", 612345.7, "variants/s", 1.21),
        ("region_query_queries_per_sec", 41.7, "queries/s", 2.4),
        ("region_serve_queries_per_sec", 200.3, "queries/s", 9.5),
        ("faulted_serve_queries_per_sec", 151.2, "queries/s", 0.81),
        ("obs_overhead_pct", 1.3, "%", None),
        ("plan_overhead_pct", 0.6, "%", None),
        ("cohort_join_variants_per_sec", 48211.5, "variants/s", None),
        ("fastq_reads_per_sec", 188001.0, "reads/s", 2.37),
        ("bam_write_records_per_sec", 301222.5, "records/s", 2.1),
        ("coverage_records_per_sec", 375000.2, "records/s", 1.25),
        ("sort_records_per_sec_mesh", 47368.1, "records/s", 6.6),
        ("resume_overhead_pct", 1.4, "%", None),
        ("sort_write_mb_per_sec", 38.52, "MB/s", 0.97),
        ("mkdup_mb_per_sec", 31.04, "MB/s", None),
        ("seq_pallas_kernel_bases_per_sec", 1.9e9, "bases/s", 12.2),
        ("cigar_pileup_kernel_records_per_sec", 8.1e6, "records/s", None),
        ("mesh_sort_device_sort_keys_per_sec", 5.4e7, "keys/s", None),
    ]
    comps = []
    for m, v, u, vs in rows:
        row = {"metric": m, "value": v, "unit": u,
               "note": "x" * 120}          # progress lines carry detail
        if vs is not None:
            row["vs_baseline"] = vs
        if m == "vcf_variants_per_sec":
            # per-stage wall spans ride the FULL row only; the compact
            # line keeps just the numeric value
            row["vcf_stage_seconds"] = {
                "inflate_wall": 0.21, "tokenize_wall": 0.33,
                "gt_dosage_wall": 0.12, "dispatch_wall": 0.18}
        if m == "region_query_queries_per_sec":
            row.update(cold_queries_per_sec=17.1, cache_hit_rate=0.93,
                       regions=250, records_matched=2_551_000,
                       latency_p50_ms=19.2, latency_p99_ms=88.4)
        if m == "region_serve_queries_per_sec":
            # the r11 serving row: tile-cache bypass + prefetch
            # usefulness + client saturation ride the FULL row only
            row.update(cold_queries_per_sec=23.6, tile_hit_rate=1.0,
                       zipf_first_pass_hit_rate=0.9356,
                       prefetch_hit_rate=0.28, prefetch_issued=168,
                       latency_p50_ms=4.6, latency_p99_ms=9.3,
                       cold_p50_ms=44.2, warm_host_decode_share=0.0,
                       clients_qps=[[1, 196.0], [8, 188.9]],
                       regions=250, distinct_windows=51,
                       # the r19 fleet arm: 1->2 endpoint q/s, the
                       # cross-replica tile hit rate from the fleet
                       # counters, and the client-observed SIGKILL
                       # failover p99 — full row only
                       fleet_replicas=2,
                       fleet_qps=[[1, 41.2], [2, 66.9]],
                       cross_replica_tile_hit_rate=0.44,
                       fleet_kill_p99_ms=61.3,
                       fleet_failed_requests=0)
        if m == "faulted_serve_queries_per_sec":
            # the r14 degrade-and-heal row: shed accounting, degraded vs
            # clean p50, ladder heal time and the reproducibility seed —
            # full row only; the compact line keeps the number
            row.update(shed_rate=0.175, served=66, shed=14,
                       degraded_p50_ms=6.1, warm_chaos_p50_ms=5.2,
                       clean_p50_ms=4.8, ladder_heal_s=0.41,
                       chaos_seed=1234)
        if m == "sort_write_mb_per_sec":
            # the write-path row: parallel vs serial arm, deflate wall
            # share, byte identity — full row only; the contract pins
            # row SHAPE (the speedup is host-dependent on the 1-core
            # bench machine), never a ratio
            row.update(serial_mb_per_sec=39.7, write_deflate_share=0.41,
                       records=100000, output_bytes=9_100_000,
                       byte_identical_to_serial=True)
        if m == "mkdup_mb_per_sec":
            # the r22 fused preprocessing row: fused vs staged arms,
            # per-stage wall shares, oracle byte identity — full row
            # only; the compact line keeps the fused MB/s
            row.update(vs_staged=1.12, staged_mb_per_sec=27.7,
                       stage_wall_shares={"sort": 0.58, "markdup": 0.07,
                                          "write": 0.31},
                       records=100000, duplicates_marked=1834,
                       output_bytes=9_100_000,
                       byte_identical_to_oracle=True)
        if m == "obs_overhead_pct":
            row.update(instrumented_s=0.1301, null_s=0.1284)
        if m == "plan_overhead_pct":
            # the r18 plan-layer row: both arm walls + the value-identity
            # pin ride the FULL row only; the compact line keeps the
            # overhead number
            row.update(plan_s=0.1310, inline_s=0.1302,
                       identical_to_inline=True)
        if m == "resume_overhead_pct":
            # the r16 crash-safe jobs row: journal-on vs journal-off
            # walls, and the SIGKILL-resume arm's journal-verified
            # skipped-work fraction + byte identity — full row only;
            # the compact line keeps the overhead number
            row.update(journaled_wall_s=2.113, plain_wall_s=2.084,
                       round_records=3125, records=100000,
                       byte_identical_to_plain=True,
                       resume_records=100000, resume_wall_s=1.61,
                       resume_rounds_skipped=1,
                       resume_fraction_skipped=0.25,
                       resume_byte_identical=True)
        if m == "cohort_join_variants_per_sec":
            # the r15 cohort-plane row: k-way join+pack rate, per-stage
            # wall shares, warm vs cold cohort-slice serving — full row
            # only; the compact line keeps the number
            row.update(samples=64, variants=91234,
                       stage_wall_shares={"join": 0.41, "feed": 0.22,
                                          "dispatch": 0.09},
                       cold_slice_p50_ms=310.2, warm_slice_p50_ms=3.1,
                       warm_host_decode_share=0.0)
        comps.append(row)
    comps.append({"metric": "broken_row", "error": "RuntimeError: boom"})
    comps.append({"metric": "late_row", "skipped": "deadline"})
    bench._STATE.update({
        "platform": "cpu",
        "headline": comps[0],
        "components": comps,
        "notes": [f"note {i}: " + "y" * 90 for i in range(n_notes)],
        "scaling": {
            "host_cores": 1,
            "note": "z" * 200,
            "devices": [
                {"n_devices": n, "jax_devices": n, "file_records": 100000,
                 "flagstat_records_per_sec": 862000.0 / n,
                 "flagstat_stage_seconds_per_run": {"pipeline.inflate": 0.2},
                 "flagstat_wall_seconds_per_run":
                     {"pipeline.feed_wall": 0.31,
                      "pipeline.dispatch_wall": 0.24,
                      "pipeline.host_decode_wall": 0.28},
                 "flagstat_overlap_efficiency": 0.774,
                 "flagstat_dispatch_bytes": 3301400,
                 "seq_stats_records_per_sec": 250000.0 / n,
                 "seq_stats_overlap_efficiency": 0.61,
                 "seq_stats_dispatch_bytes": 76600000,
                 "coverage_records_per_sec": 400000.0 / n}
                for n in (1, 8, 2, 4)],
        },
    })


def test_final_line_fits_budget_and_parses(bench):
    _fill_state(bench)
    line = json.dumps(bench._compact_snapshot(bench._snapshot("ok")))
    assert len(line) <= bench.FINAL_LINE_BUDGET
    out = json.loads(line)
    # driver contract keys
    assert out["metric"] == "bam_decode_records_per_sec_per_chip"
    assert out["value"] == 907987.4
    assert out["unit"] == "records/s"
    assert out["vs_baseline"] == 2.87
    # compressed matrix: name -> value, errors/skips as strings
    assert out["components"]["bcf_variants_per_sec"] == 612345.7
    assert out["components"]["sort_write_mb_per_sec"] == 38.52
    assert out["components"]["broken_row"] == "error"
    assert out["components"]["late_row"] == "skipped"
    # r9: the obs overhead row rides the compact matrix, and the warm
    # region-query [p50_ms, p99_ms] pair rides as the latency component
    assert out["components"]["obs_overhead_pct"] == 1.3
    assert out["latency"] == [19.2, 88.4]
    # scaling compressed to [n_dev, flagstat rec/s] pairs, sorted
    assert out["scaling"][0] == [1, 862000.0]
    assert [r[0] for r in out["scaling"]] == [1, 2, 4, 8]


def test_final_line_budget_survives_pathological_notes(bench):
    _fill_state(bench, n_notes=40)
    line = json.dumps(bench._compact_snapshot(bench._snapshot("timeout")))
    assert len(line) <= bench.FINAL_LINE_BUDGET
    assert json.loads(line)["status"] == "timeout"


def test_full_snapshot_keeps_detail_on_progress_lines(bench):
    _fill_state(bench)
    full = bench._snapshot("partial")
    assert any("note" in c for c in full["components"])
    assert "flagstat_stage_seconds_per_run" in \
        full["scaling"]["devices"][0]
    by_metric = {c.get("metric"): c for c in full["components"]}
    # r9: VCF per-stage walls + region-query cache detail stay on the
    # progress lines (the compact line keeps only the numeric values)
    assert set(by_metric["vcf_variants_per_sec"]["vcf_stage_seconds"]) \
        == {"inflate_wall", "tokenize_wall", "gt_dosage_wall",
            "dispatch_wall"}
    rq = by_metric["region_query_queries_per_sec"]
    assert 0.0 <= rq["cache_hit_rate"] <= 1.0
    assert rq["regions"] >= 200
    # r9: warm-pass latency percentiles from the query.latency_s
    # histogram ride the full region-query row
    assert rq["latency_p99_ms"] >= rq["latency_p50_ms"] > 0
    # r11: the serving row pins the tile-cache bypass (hit rate, ~zero
    # warm host-decode share), prefetch usefulness, and the 1->8 client
    # saturation pairs — full row only, compact line keeps the number
    rs = by_metric["region_serve_queries_per_sec"]
    assert 0.0 <= rs["tile_hit_rate"] <= 1.0
    assert 0.0 <= rs["prefetch_hit_rate"] <= 1.0
    assert rs["warm_host_decode_share"] < 0.1
    assert rs["cold_p50_ms"] > rs["latency_p50_ms"] > 0
    assert [c for c, _q in rs["clients_qps"]] == [1, 8]
    assert all(q > 0 for _c, q in rs["clients_qps"])
    # r19: the fleet arm pins the 1->2 endpoint q/s pairs, a bounded
    # cross-replica tile hit rate, the client-observed kill-failover
    # p99 and ZERO failed requests through the SIGKILL — shape only
    # (rates are host-dependent), compact line keeps the number
    assert rs["fleet_replicas"] == 2
    assert [n for n, _q in rs["fleet_qps"]] == [1, 2]
    assert all(q > 0 for _n, q in rs["fleet_qps"])
    assert 0.0 <= rs["cross_replica_tile_hit_rate"] <= 1.0
    assert rs["fleet_kill_p99_ms"] > 0
    assert rs["fleet_failed_requests"] == 0
    ov = by_metric["obs_overhead_pct"]
    assert ov["instrumented_s"] > 0 and ov["null_s"] > 0
    # the write-path row pins the arm comparison fields and byte
    # identity — shape only, no ratio (host-dependent on 1 core)
    # r14: the degrade-and-heal serving row pins shed accounting (rate
    # consistent with the counts), the degraded-vs-clean p50 pair, the
    # ladder heal time and the chaos seed — shape only, no host ratio
    fs = by_metric["faulted_serve_queries_per_sec"]
    assert 0.0 <= fs["shed_rate"] <= 1.0
    assert fs["shed_rate"] == pytest.approx(
        fs["shed"] / (fs["served"] + fs["shed"]), abs=1e-3)
    assert fs["degraded_p50_ms"] > 0 and fs["clean_p50_ms"] > 0
    assert fs["warm_chaos_p50_ms"] > 0
    assert fs["ladder_heal_s"] > 0
    assert isinstance(fs["chaos_seed"], int)
    # r15: the cohort-plane row pins the join's per-stage wall shares,
    # the cold-vs-warm slice pair and the warm host-decode bypass —
    # shape only (the rate is host-dependent), compact line keeps the
    # number
    cj = by_metric["cohort_join_variants_per_sec"]
    assert cj["samples"] > 1 and cj["variants"] > 0
    assert set(cj["stage_wall_shares"]) == {"join", "feed", "dispatch"}
    assert all(0.0 <= v <= 1.0 for v in cj["stage_wall_shares"].values())
    assert cj["cold_slice_p50_ms"] > cj["warm_slice_p50_ms"] > 0
    assert cj["warm_host_decode_share"] < 0.1
    sw = by_metric["sort_write_mb_per_sec"]
    assert sw["serial_mb_per_sec"] > 0
    assert 0.0 <= sw["write_deflate_share"] <= 1.0
    assert sw["byte_identical_to_serial"] is True
    assert sw["records"] > 0 and sw["output_bytes"] > 0
    # r22: the fused preprocessing row pins the fused-vs-staged arm
    # pair, per-stage wall shares over the three prep spans, and byte
    # identity against the serial markdup oracle — shape only (the
    # ratio is host-dependent), compact line keeps the fused MB/s
    mk = by_metric["mkdup_mb_per_sec"]
    assert mk["staged_mb_per_sec"] > 0
    assert set(mk["stage_wall_shares"]) == {"sort", "markdup", "write"}
    assert all(0.0 <= v <= 1.0
               for v in mk["stage_wall_shares"].values())
    assert mk["byte_identical_to_oracle"] is True
    assert mk["records"] > 0 and mk["output_bytes"] > 0
    assert mk["duplicates_marked"] >= 0
    line = json.dumps(bench._compact_snapshot(full))
    assert len(line) <= bench.FINAL_LINE_BUDGET
    out = json.loads(line)
    assert out["components"]["region_query_queries_per_sec"] == 41.7


def test_latency_component_dropped_before_components(bench):
    """Budget pressure sheds notes, then latency, then scaling —
    components (the driver-parsed matrix) go last."""
    _fill_state(bench, n_notes=0)
    full = bench._snapshot("ok")
    out = bench._compact_snapshot(full)
    assert "latency" in out
    # a region-query row without the percentile fields (old artifacts,
    # error rows) must simply omit the component, not crash
    for c in full["components"]:
        c.pop("latency_p50_ms", None)
    out2 = bench._compact_snapshot(full)
    assert "latency" not in out2
    assert len(json.dumps(out2)) <= bench.FINAL_LINE_BUDGET


def test_scaling_rows_pin_feed_overlap_fields(bench):
    """The r8 feed-pipeline fields ride the full scaling rows (and the
    compact final line still fits the budget with them aboard): per
    driver, ``*_overlap_efficiency`` (device-busy wall / feed wall from
    Metrics.wall_timer spans) and ``*_dispatch_bytes``."""
    _fill_state(bench)
    full = bench._snapshot("ok")
    for row in full["scaling"]["devices"]:
        for prefix in ("flagstat", "seq_stats"):
            assert f"{prefix}_overlap_efficiency" in row
            assert 0.0 <= row[f"{prefix}_overlap_efficiency"] <= 1.0
            assert row[f"{prefix}_dispatch_bytes"] > 0
        assert "pipeline.feed_wall" in row["flagstat_wall_seconds_per_run"]
    line = json.dumps(bench._compact_snapshot(full))
    assert len(line) <= bench.FINAL_LINE_BUDGET


def test_resume_row_shape_pinned(bench):
    """The r16 crash-safe jobs row: the full row carries both arms
    (journal-on/off walls, the resume arm's fraction-of-work-skipped
    and byte identity); the compact final line keeps only the overhead
    number and still fits the budget."""
    _fill_state(bench)
    full = bench._snapshot("ok")
    row = next(c for c in full["components"]
               if c["metric"] == "resume_overhead_pct")
    assert row["unit"] == "%"
    assert row["journaled_wall_s"] > 0 and row["plain_wall_s"] > 0
    assert row["byte_identical_to_plain"] is True
    assert row["resume_byte_identical"] is True
    assert 0.0 < row["resume_fraction_skipped"] < 1.0
    assert row["resume_rounds_skipped"] >= 1
    out = bench._compact_snapshot(full)
    assert out["components"]["resume_overhead_pct"] == 1.4
    assert len(json.dumps(out)) <= bench.FINAL_LINE_BUDGET


def test_plan_overhead_row_shape_pinned(bench):
    """The r18 plan/execute-layer row: the full row carries both arm
    walls and the identity pin (flagstat via the executor must be
    value-identical to the inline mesh-feed impl); the compact final
    line keeps only the overhead number and still fits the budget."""
    _fill_state(bench)
    full = bench._snapshot("ok")
    row = next(c for c in full["components"]
               if c["metric"] == "plan_overhead_pct")
    assert row["unit"] == "%"
    assert row["plan_s"] > 0 and row["inline_s"] > 0
    assert row["identical_to_inline"] is True
    out = bench._compact_snapshot(full)
    assert out["components"]["plan_overhead_pct"] == 0.6
    assert len(json.dumps(out)) <= bench.FINAL_LINE_BUDGET


def test_stale_sidecars_healed_fresh_kept(bench, tmp_path):
    """The stale-sidecar auto-heal (the recurring 'truncated BGZF
    header' scaling failure): sidecars OLDER than their fixture are
    removed, fresh ones are kept, and the purge flavor removes
    everything."""
    bam = tmp_path / "f.bam"
    bam.write_bytes(b"x" * 10)
    stale = tmp_path / "f.bam.bai"
    stale.write_bytes(b"old")
    os.utime(stale, ns=(1, 1))                 # older than the fixture
    fresh = tmp_path / "f.bam.sbi"
    fresh.write_bytes(b"new")
    os.utime(fresh, ns=(2**62, 2**62))         # newer than the fixture
    removed = bench._heal_stale_sidecars(str(bam))
    assert removed == ["f.bam.bai"]
    assert not stale.exists() and fresh.exists()
    # idempotent + missing fixture is a no-op
    assert bench._heal_stale_sidecars(str(bam)) == []
    assert bench._heal_stale_sidecars(str(tmp_path / "absent.bam")) == []
    assert bench._purge_sidecars(str(bam)) == ["f.bam.sbi"]
    assert not fresh.exists()


def test_snapshot_mutation_not_duplicated_by_compact(bench):
    """_compact_snapshot must consume an existing snapshot dict —
    _snapshot appends a note when the headline is missing, and the old
    double-call duplicated it in the final artifact."""
    _fill_state(bench)
    bench._STATE["headline"] = None
    full = bench._snapshot("ok")
    out = bench._compact_snapshot(full)
    assert out["status"] == "partial"          # downgraded, not "ok"
    note = "headline measurement failed; see components"
    assert bench._STATE["notes"].count(note) == 1


def test_exit_code_says_whether_the_json_can_be_trusted(bench):
    """JSON always comes out; the exit code is 0 only when the headline
    was measured and no row (scaling rows included) errored."""
    _fill_state(bench)
    assert bench._exit_code() == 1                 # broken_row errored
    bench._STATE["components"] = [
        c for c in bench._STATE["components"] if "error" not in c]
    assert bench._exit_code() == 0                 # skipped rows are fine
    bench._STATE["scaling"]["devices"][1] = {"n_devices": 8,
                                             "error": "timeout"}
    assert bench._exit_code() == 1
    bench._STATE["scaling"] = {"error": "scaling fixture: boom"}
    assert bench._exit_code() == 1
    bench._STATE["scaling"] = None
    assert bench._exit_code() == 0
    bench._STATE["headline"] = None
    assert bench._exit_code() == 1
