"""Tests of the benchmark's own code (never of the chip).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They live under ``benchmark/`` because a benchmark PR may add files only
there; tier-1 (``tests/``) does not collect them.  None touches libtpu.
"""
from __future__ import annotations

import importlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import gen, loadgen, trace_reduce  # noqa: E402
from benchmark.reducers import device as device_reducers  # noqa: E402
from benchmark.reducers import host as host_reducers  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as fh:
        return json.load(fh)


BENCH = _json("BENCHMARK.json")
# cells that are built but not yet proved live beside the benchmark in the
# same shape; they are held to the same rules and rehearsed the same way
CANDIDATES = _json("benchmark", "candidates.json")
DOCS = {"BENCHMARK.json": BENCH, "benchmark/candidates.json": CANDIDATES}
CELLS = [(path, w["name"]) for path, doc in DOCS.items()
         for w in doc["workloads"]]


@pytest.mark.parametrize("BENCH", DOCS.values(), ids=list(DOCS))
def test_every_cell_resolves_by_name(BENCH):
    configs = {c["name"]: c for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for cell in BENCH["workloads"]:
        for key in ("name", "config", "traffic"):
            assert NAME.match(cell[key]), cell[key]
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        cfg = configs[cell["config"]]
        assert cfg["file"].startswith("benchmark/")
        doc = _json(cfg["file"])
        assert doc["chips"] == cell["chips"]
        assert set(cfg["reduced"]) == set(doc["reduced"])
        assert doc["guarantees"] and doc["assumed"] and doc["tiny"]
        traffic = _json("benchmark", "traffic", cell["traffic"] + ".json")
        runner = importlib.import_module(
            f"benchmark.runners.{traffic['runner']}")
        assert callable(runner.setup) and callable(runner.measure)
        mine = [m for m in BENCH["end_to_end"]
                if "workloads" not in m or cell["name"] in m["workloads"]]
        assert len(mine) >= 2, f"{cell['name']} reports only setup_s"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        spec = _json("benchmark", "layer_metrics", m["name"] + ".json")
        mod, fn = spec["reducer"].split(".")
        assert callable(getattr(importlib.import_module(
            f"benchmark.reducers.{mod}"), fn))
        # the arrow: every cell that reports it reports what it moves
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for cell in cells:
        assert any(cell in m.get("workloads", cells)
                   for m in BENCH["per_layer"])


def test_generator_copy_gives_the_smokes_bytes():
    import chip_smoke

    for chunk, tail in ((0, False), (3, True)):
        mine = gen.gen_fields(20, chunk, 4, 2048, tail)
        theirs = chip_smoke.gen_fields(20, chunk, 4, 2048, tail)
        assert all(np.array_equal(mine[k], theirs[k]) for k in theirs)
        a, ao = gen.assemble(mine, 0, 2048)
        b, bo = chip_smoke.assemble(theirs, 0, 2048)
        assert np.array_equal(a, b) and np.array_equal(ao, bo)


def test_reference_region_count_is_the_plain_overlap_count():
    ref = gen.Reference(needs=("regions",))
    fields = list(gen.chunk_fields(5, 4, 2048))
    for f in fields:
        ref.add(f)
    for lo, hi in ((1, 10_000_000), (16_000_000, 16_400_000),
                   (40_000_000, 40_002_000)):
        want = 0
        for f in fields:
            rl = np.where(f["cig"] == 5, gen.READ_LEN,
                          gen.REF_LEN[f["cig"]])
            pos1, end1 = f["pos"] + 1, f["pos"] + np.maximum(rl, 1)
            want += int(((f["refid"] == 0) & (pos1 <= hi)
                         & (end1 >= lo)).sum())
        assert ref.region_count(lo, hi) == want


def test_schedule_is_a_function_of_the_seed_alone():
    params = _json("benchmark", "traffic",
                   "serve-review-warm.json")["params"]
    a = loadgen.schedule(params, 3_000_000_019, 30.0, 512)
    b = loadgen.schedule(params, 3_000_000_019, 30.0, 512)
    c = loadgen.schedule(params, 7, 30.0, 512)
    assert a == b and a != c
    assert len(a) == round(params["rate_per_s"] * 30.0)
    assert all(x[0] <= y[0] for x, y in zip(a, a[1:]))
    # another seed: the same sites, widths, tenants and gaps, reordered
    for col in (1, 2, 3):
        assert sorted(r[col] for r in a) == sorted(r[col] for r in c)
    # ... and the same arrivals: exponential gaps, mean 1 / rate
    assert [r[0] for r in a] == [r[0] for r in c]
    gaps = np.diff([0.0] + [r[0] for r in a])
    assert abs(gaps.mean() * params["rate_per_s"] - 1.0) < 0.01
    assert abs(np.median(gaps) * params["rate_per_s"] - np.log(2)) < 0.01
    assert abs(a[-1][0] - 30.0) < 1.0


def test_trace_reduce_on_synthetic_intervals():
    ms = 1_000_000
    ops = [(0, 10 * ms, "fusion.1"), (5 * ms, 20 * ms, "all-to-all.2"),
           (40 * ms, 50 * ms, "all-to-all"), (90 * ms, 120 * ms, "copy")]
    ops = trace_reduce.clip(ops, 0, 100 * ms)
    assert trace_reduce.union(ops) == [(0, 20 * ms), (40 * ms, 50 * ms),
                                       (90 * ms, 100 * ms)]
    busy = trace_reduce.busy_seconds(ops)
    assert busy == pytest.approx(0.040)
    assert trace_reduce.idle_share(busy, 0.100) == pytest.approx(0.6)
    assert trace_reduce.ops_prefix_seconds(ops, "all-to-all") \
        == pytest.approx(0.025)
    assert trace_reduce.gaps(ops, 0, 100 * ms) == [(20 * ms, 40 * ms),
                                                  (50 * ms, 90 * ms)]
    spans = [(0, 100 * ms, "plan.execute_wall"),
             (45 * ms, 95 * ms, "write.deflate_wall")]
    named = dict(trace_reduce.idle_gaps_by_span(ops, spans, 0, 100 * ms))
    assert named == {"write.deflate_wall": pytest.approx(0.040),
                     "plan.execute_wall": pytest.approx(0.020)}
    assert trace_reduce.top_ops(ops, 1) == [["all-to-all.2", 0.015]]
    assert trace_reduce.op_name(
        "%all-to-all.1 = (s32[4]{0}) all-to-all(...)") == "all-to-all.1"

    obs = {"trace": {"ops": {0: ops, 1: []}, "busy_s": 0.020,
                     "window_s": 0.100},
           "units": {"jobs": 2, "records": 1000}}
    assert device_reducers.trace_idle_share({}, obs) == pytest.approx(80.0)
    assert device_reducers.trace_idle_share({"device": 0}, obs) \
        == pytest.approx(60.0)
    assert device_reducers.trace_ops_prefix(
        {"prefix": "all-to-all", "device": 0, "per": "jobs",
         "scale": 1000.0}, obs) == pytest.approx(12.5)
    assert device_reducers.trace_busy_over_counter(
        {"per": "records", "scale": 1e9}, obs) == pytest.approx(20_000.0)
    assert device_reducers.trace_idle_share({}, {"trace": None}) is None


def test_host_reducers():
    obs = {"window_s": 10.0, "lateness_s": [0.001] * 99 + [0.5],
           "span_durations": {"serve.filter_wall": [0.001, 0.002, 0.003]},
           "snapshot": {
               "counters": {"a": 30, "b": 10},
               "wall_timers": {"x": 0.0, "y": 2.5},
               "histograms": {"h": {"count": 4, "p50": 0.25}}}}
    assert host_reducers.counter_ratio(
        {"numerator": ["a"], "denominator": ["a", "b"], "scale": 100.0},
        obs) == 75.0
    assert host_reducers.counter_ratio(
        {"numerator": ["a"], "denominator": ["zz"]}, obs) is None
    assert host_reducers.span_share_of_window({"spans": ["x", "y"]},
                                              obs) == 25.0
    assert host_reducers.span_share_of_window({"spans": ["x"]}, obs) is None
    assert host_reducers.hist_quantile(
        {"histogram": "h", "quantile": "p50", "scale": 1000.0}, obs) == 250.0
    assert host_reducers.span_quantile(
        {"span": "serve.filter_wall", "quantile": 0.5, "scale": 1000.0},
        obs) == 2.0
    assert host_reducers.generator_lateness(
        {"quantile": 0.99, "scale": 1000.0}, obs) == 1.0
    assert host_reducers.generator_lateness({"quantile": 0.99}, {}) is None


def _run(bench: str, cell: str, cache_dir, *extra: str, devices: int = 1):
    # the persistent cache stays on (a run tells a program compiled inside
    # the window from one loaded there by the cache's own events), in a
    # directory of the test's
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--bench", bench, "--workload", cell, "--seed", "3000000019",
         "--seconds", "1",
         *extra], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=300)


@pytest.mark.parametrize("bench,cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_cpu_rehearsal_ends_in_the_contracts_line(bench, cell, trace,
                                                       tmp_path):
    BENCH = DOCS[bench]
    chips = next(w["chips"] for w in BENCH["workloads"]
                 if w["name"] == cell)
    p = _run(bench, cell, tmp_path, "--trace", trace, "--tiny",
             devices=chips)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(doc) - {"breakdown"} == RESULT_KEYS
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["device"]["platform"] == "cpu"
    assert doc["device"]["count"] == chips
    table = BENCH["per_layer"] if trace == "1" else BENCH["end_to_end"]
    allowed = {m["name"]: m["unit"] for m in table
               if "workloads" not in m or cell in m["workloads"]}
    assert doc["metrics"], "a run reports at least one metric"
    for name, m in doc["metrics"].items():
        assert allowed[name] == m["unit"]
        assert isinstance(m["value"], float) and m["value"] > 0
    if trace == "0":
        assert set(doc["metrics"]) == set(allowed)
    else:
        assert {"busy_s", "window_s"} <= set(doc["device"])
        assert len(doc["breakdown"]["device_ops"]) <= 10
        assert len(doc["breakdown"]["idle_gaps"]) <= 10


def test_refuses_a_cpu_that_was_not_asked_for_as_a_rehearsal(tmp_path):
    cell = BENCH["workloads"][0]["name"]
    p = _run("BENCHMARK.json", cell, tmp_path, "--trace", "0")   # no --tiny
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    assert "refusing" in p.stderr
