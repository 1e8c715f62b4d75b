"""hbam-lint suite tests: seeded-violation corpus, baseline round-trip,
and the repo-lints-clean CI gate (``pytest -m lint``).

Each analyzer gets at least one intentionally-bad snippet proving it
fires, plus a clean twin proving the approved idiom passes — the lint
suite is itself under test, so a silent analyzer regression (an analyzer
that stops finding anything) fails here, not in review.
"""
import json

import pytest

from hadoop_bam_tpu.analysis.core import (
    Baseline, Finding, Project, run_analyzers,
)

pytestmark = pytest.mark.lint


def lint_sources(sources, only=None):
    return run_analyzers(Project.from_sources(sources), only=only)


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# trace safety (TS1xx)
# ---------------------------------------------------------------------------

def test_ts_seeded_violations_fire():
    findings = lint_sources({"hadoop_bam_tpu/ops/bad.py": '''
import jax
import numpy as np

@jax.jit
def f(x, n):
    if x > 0:                  # TS102
        x = x + 1
    for i in range(n):         # TS103
        x = x + i
    y = np.asarray(x)          # TS104
    return x.item()            # TS101
'''}, only=["trace_safety"])
    assert rules_of(findings) == {"TS101", "TS102", "TS103", "TS104"}
    assert all(f.path == "hadoop_bam_tpu/ops/bad.py" for f in findings)
    assert all(f.severity == "error" for f in findings)


def test_ts_reaches_through_shard_map_and_calls():
    findings = lint_sources({"hadoop_bam_tpu/parallel/bad.py": '''
from hadoop_bam_tpu.parallel.mesh import shard_map

def make_step(mesh):
    def per_device(tile, count):
        return helper(tile)
    return shard_map(per_device, mesh=mesh, in_specs=(), out_specs=())

def helper(t):
    return t.tolist()          # TS101, two hops from the shard_map root
'''}, only=["trace_safety"])
    assert rules_of(findings) == {"TS101"}
    assert "helper" in findings[0].message


def test_ts_static_argnames_and_shape_are_not_tracers():
    findings = lint_sources({"hadoop_bam_tpu/ops/good.py": '''
import functools
import jax
import jax.numpy as jnp

@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def f(x, block_n, interpret):
    n = x.shape[0]
    if interpret:              # static arg: allowed
        block_n = 2 * block_n
    for i in range(n // block_n):   # shape-derived: allowed
        x = x + i
    return jnp.sum(x)
'''}, only=["trace_safety"])
    assert findings == []


def test_ts_unreached_host_helper_not_flagged():
    findings = lint_sources({"hadoop_bam_tpu/ops/oracle.py": '''
import numpy as np

def host_oracle(x):            # never traced: host NumPy is fine here
    out = np.asarray(x)
    return out.item()
'''}, only=["trace_safety"])
    assert findings == []


def test_ts_findings_pinned_across_engine_extraction():
    """TS1xx now runs on the shared interprocedural engine
    (``analysis/callgraph.py``); this pins rule, path, line, message,
    severity AND fingerprint so the extraction stays observably
    identical (fingerprints feed the baseline contract)."""
    findings = lint_sources({
        "hadoop_bam_tpu/ops/bad.py": '''
import jax
import numpy as np

@jax.jit
def f(x, n):
    if x > 0:
        x = x + 1
    for i in range(n):
        x = x + i
    y = np.asarray(x)
    return x.item()
''',
        "hadoop_bam_tpu/parallel/bad.py": '''
from hadoop_bam_tpu.parallel.mesh import shard_map

def make_step(mesh):
    def per_device(tile, count):
        return helper(tile)
    return shard_map(per_device, mesh=mesh, in_specs=(), out_specs=())

def helper(t):
    return t.tolist()
''',
    }, only=["trace_safety"])
    got = [(f.rule, f.path, f.line, f.message, f.severity, f.fingerprint)
           for f in findings]
    assert got == [
        ("TS102", "hadoop_bam_tpu/ops/bad.py", 7,
         "data-dependent Python branch on a traced value; use jnp.where "
         "/ lax.cond (in traced function 'f')", "error",
         "9b285a92eb74ecba"),
        ("TS103", "hadoop_bam_tpu/ops/bad.py", 9,
         "Python loop over a traced value; use lax control flow or "
         "vectorize (in traced function 'f')", "error",
         "c1b5129827abde42"),
        ("TS104", "hadoop_bam_tpu/ops/bad.py", 11,
         "host NumPy call 'np.asarray' on a traced value; use jnp "
         "(in traced function 'f')", "error", "3e9860b427381ca6"),
        ("TS101", "hadoop_bam_tpu/ops/bad.py", 12,
         ".item() forces a host sync on a traced value (in traced "
         "function 'f')", "error", "cc4fe5181e8ea137"),
        ("TS101", "hadoop_bam_tpu/parallel/bad.py", 10,
         ".tolist() forces a host sync on a traced value (in traced "
         "function 'helper')", "error", "045e954f117b94e5"),
    ]


# ---------------------------------------------------------------------------
# collective lockstep (CL2xx)
# ---------------------------------------------------------------------------

_CL_BAD = '''
import jax
import numpy as np
from jax.experimental import multihost_utils

def bad_rank_nested(x):
    pid = jax.process_index()
    if pid == 0:
        multihost_utils.process_allgather(x)      # CL201

def bad_divergent_order(x, flag):
    if flag:
        multihost_utils.broadcast_one_to_all(x)   # CL202: A then B
        multihost_utils.process_allgather(x)
    else:
        multihost_utils.process_allgather(x)      # CL202: B then A
        multihost_utils.broadcast_one_to_all(x)
'''

_CL_GOOD = '''
import jax
import numpy as np
from jax.experimental import multihost_utils

def good(plan, x):
    pid = jax.process_index()
    if jax.process_count() == 1:       # uniform test: fine
        return plan
    payload = plan if pid == 0 else None      # data diverges, not control
    out = multihost_utils.broadcast_one_to_all(x)   # unconditional
    if pid == 0:
        print("planner host")          # no collective under the rank test
    return out
'''


def test_cl_seeded_violations_fire():
    findings = lint_sources(
        {"hadoop_bam_tpu/parallel/bad.py": _CL_BAD}, only=["lockstep"])
    assert rules_of(findings) == {"CL201", "CL202"}
    by_rule = {f.rule: f for f in findings}
    assert "bad_rank_nested" in by_rule["CL201"].message
    assert "bad_divergent_order" in by_rule["CL202"].message


def test_cl_uniform_and_data_conditionals_pass():
    findings = lint_sources(
        {"hadoop_bam_tpu/parallel/good.py": _CL_GOOD}, only=["lockstep"])
    assert findings == []


def test_cl_symmetric_branches_pass():
    findings = lint_sources({"hadoop_bam_tpu/parallel/sym.py": '''
from jax.experimental import multihost_utils

def symmetric(x, big):
    if big:
        y = multihost_utils.process_allgather(2 * x)
    else:
        y = multihost_utils.process_allgather(x)
    return y
'''}, only=["lockstep"])
    assert findings == []


# ---------------------------------------------------------------------------
# error taxonomy (ET3xx)
# ---------------------------------------------------------------------------

def test_et_seeded_violation_fires_only_at_boundaries():
    bad = '''
def f(n):
    if n < 0:
        raise ValueError("bad n")          # ET301 at a boundary module
'''
    findings = lint_sources(
        {"hadoop_bam_tpu/split/planners.py": bad}, only=["taxonomy"])
    assert rules_of(findings) == {"ET301"}
    # same code OUTSIDE the policy boundaries is not taxonomy-scoped
    findings = lint_sources(
        {"hadoop_bam_tpu/utils/other.py": bad}, only=["taxonomy"])
    assert findings == []


def test_et_scope_covers_write_and_serve_boundaries():
    """ISSUE 11 scope extension: bare builtins raised in the write-path
    and serve-tier boundary modules reach clients as the WRONG wire
    taxonomy (transport.error_kind) or poison the parallel writer —
    ET301 now fires there too."""
    bad = '''
def merge(parts, missing):
    if missing:
        raise RuntimeError("shards missing at merge time")
'''
    for mod in ("hadoop_bam_tpu/write/sharded.py",
                "hadoop_bam_tpu/write/parallel_bgzf.py",
                "hadoop_bam_tpu/serve/transport.py",
                "hadoop_bam_tpu/serve/loop.py"):
        findings = lint_sources({mod: bad}, only=["taxonomy"])
        assert rules_of(findings) == {"ET301"}, mod
    # non-boundary serve-adjacent code stays out of scope
    findings = lint_sources(
        {"hadoop_bam_tpu/serve/__init__.py": bad}, only=["taxonomy"])
    assert findings == []


def test_et_write_serve_clean_twin_passes():
    """The classified version of the same boundary code is clean."""
    good = '''
from hadoop_bam_tpu.utils.errors import PlanError, TransientIOError

def merge(parts, missing):
    if missing:
        raise TransientIOError("shards missing — shared-fs lag, retry")

def parse(doc):
    if not isinstance(doc, dict):
        raise PlanError("request must be a JSON object")
'''
    for mod in ("hadoop_bam_tpu/write/sharded.py",
                "hadoop_bam_tpu/serve/transport.py"):
        assert lint_sources({mod: good}, only=["taxonomy"]) == []


def test_et_scope_covers_cohort_boundaries():
    """ISSUE 12 scope extension: the cohort plane's boundary modules —
    a bare builtin there makes the per-input fault guard quarantine a
    configuration error (or fail a build on data the policy should
    have quarantined)."""
    bad = '''
def join(manifest):
    if not manifest:
        raise ValueError("empty manifest")
'''
    for mod in ("hadoop_bam_tpu/cohort/manifest.py",
                "hadoop_bam_tpu/cohort/join.py",
                "hadoop_bam_tpu/cohort/serving.py"):
        findings = lint_sources({mod: bad}, only=["taxonomy"])
        assert rules_of(findings) == {"ET301"}, mod
    # non-boundary cohort code (the pure harmonizer, the device
    # drivers) stays out of scope
    for mod in ("hadoop_bam_tpu/cohort/harmonize.py",
                "hadoop_bam_tpu/cohort/gwas.py",
                "hadoop_bam_tpu/cohort/dataset.py"):
        assert lint_sources({mod: bad}, only=["taxonomy"]) == [], mod


def test_et_cohort_clean_twin_passes():
    """The classified version of the same cohort boundary code is
    clean: PlanError for configuration, CorruptDataError for bytes."""
    good = '''
from hadoop_bam_tpu.utils.errors import CorruptDataError, PlanError

def load(doc):
    if not isinstance(doc, dict):
        raise PlanError("cohort manifest must be a JSON object")

def stream(records):
    for last, key in records:
        if key < last:
            raise CorruptDataError("records out of (contig, pos) order")
'''
    for mod in ("hadoop_bam_tpu/cohort/manifest.py",
                "hadoop_bam_tpu/cohort/join.py"):
        assert lint_sources({mod: good}, only=["taxonomy"]) == [], mod


def test_et_scope_covers_fleet_boundaries():
    """ISSUE 16 scope extension: the fleet modules are policy
    boundaries twice over — the error class decides whether a peer
    answer feeds that peer's circuit breaker (PLAN never does) AND what
    ``error_kind`` the peer sees on the wire.  A bare builtin raised
    there misroutes both."""
    bad = '''
def answer(resp):
    if "cols" not in resp:
        raise ValueError("peer answered without columns")
'''
    for mod in ("hadoop_bam_tpu/serve/fleet.py",
                "hadoop_bam_tpu/serve/membership.py"):
        findings = lint_sources({mod: bad}, only=["taxonomy"])
        assert rules_of(findings) == {"ET301"}, mod


def test_et_fleet_clean_twin_passes():
    """The classified version of the same fleet boundary code is
    clean: CorruptDataError for bad peer bytes, PlanError for a
    misconfigured roster, TransientIOError for a dead peer."""
    good = '''
from hadoop_bam_tpu.utils.errors import (
    CorruptDataError, PlanError, TransientIOError,
)

def answer(resp):
    if "cols" not in resp:
        raise CorruptDataError("peer answered without columns")

def roster(spec):
    if not spec:
        raise PlanError("a fleet needs a non-empty peer roster")

def dial(ok):
    if not ok:
        raise TransientIOError("peer closed the connection; retry")
'''
    for mod in ("hadoop_bam_tpu/serve/fleet.py",
                "hadoop_bam_tpu/serve/membership.py"):
        assert lint_sources({mod: good}, only=["taxonomy"]) == [], mod


def test_et_classified_raises_pass():
    findings = lint_sources({"hadoop_bam_tpu/formats/bgzf.py": '''
from hadoop_bam_tpu.utils.errors import CorruptDataError, PlanError

class BGZFError(CorruptDataError):
    pass

def f(buf, n):
    if n < 0:
        raise PlanError("bad span parameters")
    if not buf:
        raise BGZFError("truncated block")
    raise KeyboardInterrupt                    # re-raise style: not scoped
'''}, only=["taxonomy"])
    assert findings == []


# ---------------------------------------------------------------------------
# layout contracts (LC4xx)
# ---------------------------------------------------------------------------

def test_lc_unknown_struct_format_fires():
    findings = lint_sources({"hadoop_bam_tpu/formats/bad.py": '''
import struct

def parse(buf):
    return struct.unpack_from("<QQi", buf, 0)     # LC401: unregistered
'''}, only=["layout"])
    assert rules_of(findings) == {"LC401"}
    assert "<QQi" in findings[0].message


def test_lc_offset_contract_violations_fire():
    findings = lint_sources({"hadoop_bam_tpu/split/bam_guesser.py": '''
class BAMSplitGuesser:
    def _chain_ok(self, data, p, n):
        return data[p:p + 4]

    def _record_ok(self, data, p, n):
        ok = data[p + 13]                    # inside mapq: fine
        bad_span = data[p + 17:p + 19]       # LC403: crosses n_cigar/flag
        bad_byte = data[p + 36]              # LC403: past the prefix
        return ok
'''}, only=["layout"])
    lc403 = [f for f in findings if f.rule == "LC403"]
    assert len(lc403) == 2
    assert {f.line for f in lc403} == {8, 9}


def test_lc_exact_field_reads_pass():
    findings = lint_sources({"hadoop_bam_tpu/split/bam_guesser.py": '''
class BAMSplitGuesser:
    def _record_ok(self, data, p, n):
        bs = int.from_bytes(data[p:p + 4], "little", signed=True)
        refid = int.from_bytes(data[p + 4:p + 8], "little", signed=True)
        n_cigar = int.from_bytes(data[p + 16:p + 18], "little")
        whole = data[p:p + 36]               # full contiguous field run
        return bs, refid, n_cigar, whole

    def _chain_ok(self, data, p, n):
        return data[p:p + 4]
'''}, only=["layout"])
    assert [f for f in findings if f.severity == "error"] == []


def test_lc_runtime_mirror_drift_fires():
    findings = lint_sources({"hadoop_bam_tpu/ops/unpack_bam.py": '''
FIXED_FIELDS = {
    "block_size": (0, 4, True),
    "refid": (4, 4, True),
    "pos": (9, 4, True),
}
'''}, only=["layout"])
    assert "LC404" in rules_of(findings)
    (f,) = [f for f in findings if f.rule == "LC404"]
    assert "pos" in f.message


def test_lc_spec_table_self_check():
    from hadoop_bam_tpu.analysis.layout_specs import (
        SPECS, Field, LayoutSpec, spec_self_check,
    )
    for spec in SPECS.values():
        assert spec_self_check(spec) == (), spec.name
    broken = LayoutSpec(
        name="broken", doc="", fmt="<II",
        fields=(Field("a", 0, 4, "u32"), Field("b", 6, 2, "u16")))
    problems = spec_self_check(broken)
    assert any("gap or overlap" in p for p in problems)
    assert any("calcsize" in p for p in problems)


# ---------------------------------------------------------------------------
# feed-path allocation discipline (PF5xx)
# ---------------------------------------------------------------------------

_PF_BAD = '''
import numpy as np

def driver(stream, n_dev, cap, w):
    group = []

    def dispatch():
        out = np.zeros((n_dev, cap, w), dtype=np.uint8)    # PF501: emit fn
        return out

    def emit_group():
        return np.empty((n_dev, cap), dtype=np.int8)       # PF501: emit fn

    for tile in stream:
        pad = np.full((n_dev, cap, w), -1, np.int8)        # PF501: loop
        group.append(pad)
    return dispatch(), emit_group()
'''

_PF_CLEAN = '''
import numpy as np

def stack_span_group(source, n_dev, cap):
    # top-level body, not a loop, not an emit helper: one-shot staging
    data = np.zeros((n_dev, cap), dtype=np.uint8)
    return data

def dispatch(counts, n_dev):
    cvec = np.zeros((n_dev,), dtype=np.int32)   # 1-D count vector: noise
    return cvec

def per_tile(stream, cap, w):
    for t in stream:
        tile = np.zeros((cap, w), np.uint8)     # no device leading dim
        yield tile
'''


def test_pf_seeded_violations_fire():
    findings = lint_sources(
        {"hadoop_bam_tpu/parallel/bad_feed.py": _PF_BAD},
        only=["feedpath"])
    assert rules_of(findings) == {"PF501"}
    assert len(findings) == 3
    assert all(f.severity == "error" for f in findings)
    assert "staging ring" in findings[0].message


def test_pf_clean_idioms_and_staging_ring_pass():
    findings = lint_sources(
        {"hadoop_bam_tpu/parallel/clean_feed.py": _PF_CLEAN},
        only=["feedpath"])
    assert findings == []
    # the staging ring module itself is the allowed owner of group
    # buffers — allocations there are exempt even inside loops
    findings = lint_sources({"hadoop_bam_tpu/parallel/staging.py": '''
import numpy as np

def ring(n_dev, cap, slots):
    out = []
    for _ in range(slots):
        out.append(np.full((n_dev, cap), 0, np.uint8))
    return out
'''}, only=["feedpath"])
    assert findings == []


def test_pf_outside_parallel_not_scoped():
    findings = lint_sources(
        {"hadoop_bam_tpu/ops/elsewhere.py": _PF_BAD}, only=["feedpath"])
    assert findings == []


# ---------------------------------------------------------------------------
# query-cache key identity (QE5xx)
# ---------------------------------------------------------------------------

_QE_BAD = '''
def lookup(cache, path, lo, hi):
    hit = cache.get((path, lo, hi))            # QE501: raw-path key
    if hit is None:
        hit = decode(path, lo, hi)
        cache.put((path, lo, hi), hit, 128)    # QE501 again
    return hit

def decode(path, lo, hi):
    return path
'''

_QE_CLEAN = '''
from hadoop_bam_tpu.query.cache import file_identity

def lookup(cache, path, lo, hi):
    ident = file_identity(path)
    hit = cache.get((ident, lo, hi))                 # identity name: ok
    if hit is None:
        hit = decode(path, lo, hi)
        cache.put((file_identity(path), lo, hi), hit, 128)  # call: ok
    stats = cache.get("toc")                         # no path at all: ok
    return hit, stats

def decode(path, lo, hi):
    return path
'''


def test_qe_seeded_violations_fire():
    findings = lint_sources(
        {"hadoop_bam_tpu/query/bad_keys.py": _QE_BAD},
        only=["querycache"])
    assert rules_of(findings) == {"QE501"}
    assert len(findings) == 2
    assert all(f.severity == "error" for f in findings)
    assert "file_identity" in findings[0].message


def test_qe_identity_keys_pass():
    findings = lint_sources(
        {"hadoop_bam_tpu/query/good_keys.py": _QE_CLEAN},
        only=["querycache"])
    assert findings == []


def test_qe_outside_query_not_scoped():
    findings = lint_sources(
        {"hadoop_bam_tpu/split/elsewhere.py": _QE_BAD},
        only=["querycache"])
    assert findings == []


# ---------------------------------------------------------------------------
# observability discipline (OB6xx)
# ---------------------------------------------------------------------------

_OB_RAW_CLOCK = '''
import time

def decode_stage(spans):
    t0 = time.perf_counter()     # OB601: interval never reaches Metrics
    out = [s * 2 for s in spans]
    dt = time.perf_counter() - t0
    print("stage took", dt)
    return out
'''

_OB_CLOCK_FEEDS_METRICS = '''
import time
from hadoop_bam_tpu.utils.metrics import METRICS

def dispatch(arrays, do):
    t0 = time.perf_counter()
    out = do(arrays)
    METRICS.add_wall("pipeline.dispatch_wall", time.perf_counter() - t0)
    return out
'''

_OB_POOLED_TIMER = '''
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

def driver(pool, spans, work):
    def decode(span):
        with METRICS.timer("fmt.host_decode"):   # OB602: pool tasks
            return work(span)                    # overlap; thread-sum
    return list(_iter_windowed(pool, spans, decode, 8))
'''

_OB_POOLED_TIMER_WITH_WALL = '''
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

def driver(pool, spans, work):
    def decode(span):
        with METRICS.timer("fmt.host_decode"), \\
                METRICS.wall_timer("fmt.host_decode_wall"):
            return work(span)
    return list(_iter_windowed(pool, spans, decode, 8))
'''

_OB_POOLED_SPAN = '''
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.parallel.pipeline import _iter_windowed

def driver(pool, spans, work):
    def decode(span):
        with METRICS.span("fmt.host_decode_wall"):
            return work(span)
    return list(_iter_windowed(pool, spans, decode, 8))
'''


def test_ob_raw_clock_seeded_violation_fires():
    findings = lint_sources(
        {"hadoop_bam_tpu/parallel/bad_clock.py": _OB_RAW_CLOCK},
        only=["obs"])
    assert rules_of(findings) == {"OB601"}
    assert len(findings) == 2        # both perf_counter calls
    assert all(f.severity == "error" for f in findings)
    assert "Metrics" in findings[0].message


def test_ob_clock_feeding_metrics_passes():
    findings = lint_sources(
        {"hadoop_bam_tpu/parallel/ok_clock.py": _OB_CLOCK_FEEDS_METRICS},
        only=["obs"])
    assert findings == []


def test_ob_timer_in_pooled_decode_fires():
    findings = lint_sources(
        {"hadoop_bam_tpu/query/bad_timer.py": _OB_POOLED_TIMER},
        only=["obs"])
    assert rules_of(findings) == {"OB602"}
    assert "wall_timer" in findings[0].message


def test_ob_pooled_timer_with_wall_or_span_passes():
    for src in (_OB_POOLED_TIMER_WITH_WALL, _OB_POOLED_SPAN):
        findings = lint_sources(
            {"hadoop_bam_tpu/query/ok_timer.py": src}, only=["obs"])
        assert findings == []


def test_ob_outside_hot_paths_not_scoped():
    findings = lint_sources(
        {"hadoop_bam_tpu/formats/elsewhere.py": _OB_RAW_CLOCK},
        only=["obs"])
    assert findings == []


# ---------------------------------------------------------------------------
# OB603: entry points must mint/propagate a TraceContext
# ---------------------------------------------------------------------------

_OB_UNTRACED_ENTRY = '''
def handle_stream(loop, rfile, wfile):
    for line in rfile:                    # OB603: starts work with no
        fut = loop.submit(line)           # TraceContext minted
        fut.result()
'''

_OB_TRACED_ENTRY = '''
from hadoop_bam_tpu.obs.context import trace_context

def handle_stream(loop, rfile, wfile):
    for line in rfile:
        with trace_context(op="serve.request"):
            fut = loop.submit(line)
            fut.result()
'''

_OB_CLI_MAIN_MINTS = '''
from hadoop_bam_tpu.obs.context import trace_context

def cmd_sort(args):
    return run_sort(args.input)

def main(argv=None):
    args = parse(argv)
    with trace_context(op=f"cli.{args.verb}"):
        return args.fn(args)
'''

_OB_CLI_NO_MAIN_MINT = '''
def cmd_sort(args):
    return run_sort(args.input)

def main(argv=None):
    args = parse(argv)
    return args.fn(args)
'''


def test_ob603_untraced_entry_point_fires():
    findings = lint_sources(
        {"hadoop_bam_tpu/serve/bad_entry.py": _OB_UNTRACED_ENTRY},
        only=["obs"])
    assert rules_of(findings) == {"OB603"}
    assert "TraceContext" in findings[0].message


def test_ob603_traced_entry_point_passes():
    findings = lint_sources(
        {"hadoop_bam_tpu/serve/good_entry.py": _OB_TRACED_ENTRY},
        only=["obs"])
    assert findings == []


def test_ob603_cli_verbs_covered_by_main_mint():
    # the CLI-frontend idiom: one trace_context in main() covers every
    # cmd_* verb it dispatches to
    findings = lint_sources(
        {"hadoop_bam_tpu/tools/cli.py": _OB_CLI_MAIN_MINTS},
        only=["obs"])
    assert findings == []
    # ...but a main() that does NOT mint leaves the verbs flagged
    findings = lint_sources(
        {"hadoop_bam_tpu/tools/cli.py": _OB_CLI_NO_MAIN_MINT},
        only=["obs"])
    assert rules_of(findings) == {"OB603"}


def test_ob603_jobs_entry_and_scope():
    # run_job_level in jobs/ is an entry point...
    findings = lint_sources({"hadoop_bam_tpu/jobs/bad_runner.py": '''
def run_job_level(journal_path, kind, run):
    return run()
'''}, only=["obs"])
    assert rules_of(findings) == {"OB603"}
    # ...the same code outside the entry scope is not in scope
    findings = lint_sources({"hadoop_bam_tpu/split/elsewhere.py": '''
def run_job_level(journal_path, kind, run):
    return run()
'''}, only=["obs"])
    assert findings == []


def test_ob603_entry_point_with_no_work_passes():
    # an entry-point NAME that starts no work (pure accessor) is fine
    findings = lint_sources({"hadoop_bam_tpu/serve/idle.py": '''
def submit(self):
    return self._queue
'''}, only=["obs"])
    assert findings == []


# ---------------------------------------------------------------------------
# decode-path copy discipline (DP7xx)
# ---------------------------------------------------------------------------

_DP_BAD = '''
import numpy as np

def walk_fallback(data, start):
    buf = data.tobytes()                     # DP701: whole-span copy
    arr = np.frombuffer(buf, np.uint8).copy()  # DP702: copy of a view
    return buf, arr

class Decoder:
    def pack(self):
        return self.data.tobytes()           # DP701: attribute receiver
'''

_DP_CLEAN = '''
import numpy as np

def walk_fallback(data, start, s, e):
    head = data[s:e].tobytes()               # bounded slice: blessed
    crc_src = data[int(s):int(e)].tobytes()  # ditto
    view = np.frombuffer(head, np.uint8)     # zero-copy view: blessed
    whole = data.tobytes                     # bare reference, no call
    return head, crc_src, view, whole

FULL = None
SNAPSHOT = np.frombuffer(b"x", np.uint8)
'''


def test_dp_seeded_violations_fire():
    findings = lint_sources(
        {"hadoop_bam_tpu/ops/inflate.py": _DP_BAD}, only=["decodepath"])
    assert rules_of(findings) == {"DP701", "DP702"}
    assert sum(f.rule == "DP701" for f in findings) == 2
    assert all(f.severity == "error" for f in findings)


def test_dp_clean_idioms_pass():
    findings = lint_sources(
        {"hadoop_bam_tpu/parallel/pipeline.py": _DP_CLEAN},
        only=["decodepath"])
    assert findings == []


def test_dp_outside_decode_path_not_scoped():
    # same bad source in a module off the inflated-span hot path: silent
    findings = lint_sources(
        {"hadoop_bam_tpu/formats/bam.py": _DP_BAD,
         "hadoop_bam_tpu/parallel/mesh_sort.py": _DP_BAD},
        only=["decodepath"])
    assert findings == []


def test_dp_module_level_code_not_scoped():
    # the rule fires only inside function bodies: module-level fixture
    # materializations (test corpora, constants) stay out of scope
    findings = lint_sources(
        {"hadoop_bam_tpu/ops/inflate.py": '''
import numpy as np
GOLDEN = np.zeros(4, np.uint8).tobytes()
'''}, only=["decodepath"])
    assert findings == []


# ---------------------------------------------------------------------------
# serving-tier cache bounds (SV8xx)
# ---------------------------------------------------------------------------

_SV_BAD = '''
from collections import OrderedDict

_STEP_CACHE = {}                     # SV801: module dict, insert only

def get_step(key, build):
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = build()
    return _STEP_CACHE[key]

class TileServer:
    def __init__(self):
        self.tile_cache = OrderedDict()   # SV801: never evicted
        self.client_log = []              # SV802: append-only registry

    def serve(self, key, tiles, who):
        self.tile_cache[key] = tiles
        self.client_log.append(who)
        return self.tile_cache[key]
'''

_SV_CLEAN = '''
import collections
from collections import OrderedDict

_STEP_CACHE = {}
_CAP = 8

def get_step(key, build):
    if key not in _STEP_CACHE:
        while len(_STEP_CACHE) >= _CAP:
            _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
        _STEP_CACHE[key] = build()
    return _STEP_CACHE[key]

class TileServer:
    def __init__(self, budget):
        self.tile_cache = OrderedDict()            # LRU: popitem below
        self.recent_clients = collections.deque(maxlen=16)  # bounded
        self._bytes, self.budget = 0, budget

    def serve(self, key, tiles, nbytes, who):
        self.tile_cache[key] = tiles
        self.recent_clients.append(who)
        self._bytes += nbytes
        while self._bytes > self.budget and len(self.tile_cache) > 1:
            _k, v = self.tile_cache.popitem(last=False)
            self._bytes -= v.nbytes
        return self.tile_cache[key]

def working_state(items):
    # locals are out of scope: they die with the call
    batch_cache = {}
    for k, v in items:
        batch_cache[k] = v
    return batch_cache
'''


def test_sv_seeded_violations_fire():
    findings = lint_sources(
        {"hadoop_bam_tpu/serve/bad_caches.py": _SV_BAD},
        only=["servebounds"])
    assert rules_of(findings) == {"SV801", "SV802"}
    assert sum(f.rule == "SV801" for f in findings) == 2
    assert sum(f.rule == "SV802" for f in findings) == 1
    assert all(f.severity == "error" for f in findings)
    assert any("popitem" in f.message or "LRU" in f.message
               for f in findings)


def test_sv_bounded_idioms_pass():
    findings = lint_sources(
        {"hadoop_bam_tpu/query/good_caches.py": _SV_CLEAN},
        only=["servebounds"])
    assert findings == []


def test_sv_reassignment_reset_counts_as_bound():
    # draining by rebinding (self.pending = still_pending) is a bound
    findings = lint_sources({"hadoop_bam_tpu/serve/drained.py": '''
class Builder:
    def __init__(self):
        self.pending_tiles = []

    def add(self, t):
        self.pending_tiles.append(t)

    def reap(self):
        done = [t for t in self.pending_tiles if t.ready()]
        self.pending_tiles = [t for t in self.pending_tiles
                              if not t.ready()]
        return done
'''}, only=["servebounds"])
    assert findings == []


def test_sv_outside_query_and_serve_not_scoped():
    findings = lint_sources(
        {"hadoop_bam_tpu/formats/elsewhere.py": _SV_BAD,
         "hadoop_bam_tpu/parallel/elsewhere.py": _SV_BAD},
        only=["servebounds"])
    assert findings == []


def test_sv_non_cacheish_names_not_flagged():
    # plain working-state containers (no cache-ish name) stay out of
    # scope even when append-only — the rule targets lookup structures
    findings = lint_sources({"hadoop_bam_tpu/serve/state.py": '''
class Loop:
    def __init__(self):
        self.results = {}
        self.errors = []

    def run(self, k, v, e):
        self.results[k] = v
        self.errors.append(e)
'''}, only=["servebounds"])
    assert findings == []


# ---------------------------------------------------------------------------
# write-path discipline (WR10x)
# ---------------------------------------------------------------------------

_WR_BAD = '''
import os
from hadoop_bam_tpu.formats.bgzf import deflate_block

def publish(final_path, blocks):
    with open(final_path, "wb") as f:      # WR101: no temp, no replace
        for b in blocks:
            f.write(b)

def compress_all(payloads):
    out = []
    for p in payloads:
        out.append(deflate_block(p, 6))    # WR102: serial deflate loop
    return out
'''

_WR_CLEAN = '''
import os
from hadoop_bam_tpu.formats.bgzf import deflate_block

def publish(final_path, blocks):
    tmp_path = final_path + ".tmp"
    with open(tmp_path, "wb") as f:        # temp name + atomic replace
        for b in blocks:
            f.write(b)
    os.replace(tmp_path, final_path)

def _deflate_task(payload):
    return deflate_block(payload, 6)       # single block, pool-submitted

class Writer:
    def _commit_loop(self, q, sink):
        while True:
            fut = q.get()
            if fut is None:
                return
            sink.write(fut.result())
'''


def test_wr_seeded_violations_fire():
    findings = lint_sources(
        {"hadoop_bam_tpu/write/bad_writer.py": _WR_BAD},
        only=["writepath"])
    assert rules_of(findings) == {"WR101", "WR102"}
    assert all(f.severity == "error" for f in findings)
    assert any("os.replace" in f.message for f in findings)
    assert any("ParallelBGZFWriter" in f.message for f in findings)


def test_wr_clean_idioms_pass():
    findings = lint_sources(
        {"hadoop_bam_tpu/write/good_writer.py": _WR_CLEAN},
        only=["writepath"])
    assert findings == []


def test_wr_replace_in_function_exempts_open():
    # a function that opens the final path but renames it into place is
    # the approved idiom even when the variable name is not tmp-ish
    findings = lint_sources({"hadoop_bam_tpu/write/renamer.py": '''
import os

def publish(final_path, data):
    staging = final_path + ".new"
    with open(staging, "wb") as f:
        f.write(data)
    os.replace(staging, final_path)
'''}, only=["writepath"])
    assert findings == []


def test_wr_outside_write_not_scoped():
    findings = lint_sources(
        {"hadoop_bam_tpu/utils/elsewhere.py": _WR_BAD,
         "hadoop_bam_tpu/formats/elsewhere.py": _WR_BAD},
        only=["writepath"])
    assert findings == []


def test_wr_read_mode_open_not_flagged():
    findings = lint_sources({"hadoop_bam_tpu/write/reader.py": '''
def load(final_path):
    with open(final_path, "rb") as f:
        return f.read()
'''}, only=["writepath"])
    assert findings == []


# ---------------------------------------------------------------------------
# crash-safe job discipline (JS1xx)
# ---------------------------------------------------------------------------

_JS_BAD = '''
import os
import tempfile                            # JS102: tempfile import

def publish_bucket(payload, final_path):
    staging = final_path + ".new"
    with open(staging, "wb") as f:
        f.write(payload)
    os.replace(staging, final_path)        # JS101: unjournaled rename

def spill_round(payload, out_dir):
    # JS102: pid-derived temp name — resume can never sweep/verify it
    path = os.path.join(out_dir, f"run-{os.getpid()}.tmp")
    with open(path, "wb") as f:
        f.write(payload)
    return path
'''

_JS_CLEAN = '''
import os

def _publish(tmp_path, path):
    os.replace(tmp_path, path)             # blessed publication helper

def open_shard(part, payload):
    tmp_part = part + ".tmp"               # deterministic job-scoped
    with open(tmp_part, "wb") as f:
        f.write(payload)
    os.replace(tmp_part, part)

def commit_round(journal, t, path, payload):
    with open(path + ".tmp", "wb") as f:
        f.write(payload)
    os.rename(path + ".tmp", path)         # journaled alongside:
    journal.unit_done("round", t, path=path)
'''


def test_js_seeded_violations_fire():
    findings = lint_sources(
        {"hadoop_bam_tpu/write/bad_jobs.py": _JS_BAD},
        only=["jobsafety"])
    assert rules_of(findings) == {"JS101", "JS102"}
    assert all(f.severity == "error" for f in findings)
    assert sum(f.rule == "JS102" for f in findings) == 2  # import + pid
    assert any("journal" in f.message for f in findings)


def test_js_clean_idioms_pass():
    findings = lint_sources(
        {"hadoop_bam_tpu/write/good_jobs.py": _JS_CLEAN,
         "hadoop_bam_tpu/parallel/mesh_sort.py": _JS_CLEAN},
        only=["jobsafety"])
    assert findings == []


def test_js_scope_is_write_and_mesh_sort_only():
    findings = lint_sources(
        {"hadoop_bam_tpu/parallel/mesh_sort.py": _JS_BAD,
         "hadoop_bam_tpu/parallel/pipeline.py": _JS_BAD,
         "hadoop_bam_tpu/utils/elsewhere.py": _JS_BAD,
         "hadoop_bam_tpu/query/engine.py": _JS_BAD},
        only=["jobsafety"])
    assert {f.path for f in findings} == \
        {"hadoop_bam_tpu/parallel/mesh_sort.py"}


def test_js_rename_args_checked_for_nondeterminism():
    findings = lint_sources({"hadoop_bam_tpu/write/renamer.py": '''
import os
import time

def open_shard(part, payload):
    tmp = part + "." + str(time.time_ns()) + ".tmp"   # JS102 even in a
    with open(tmp, "wb") as f:                        # blessed helper
        f.write(payload)
    os.replace(tmp, part)
'''}, only=["jobsafety"])
    assert rules_of(findings) == {"JS102"}


# ---------------------------------------------------------------------------
# baseline round-trip / suppression
# ---------------------------------------------------------------------------

_BAD_FOR_BASELINE = {"hadoop_bam_tpu/split/planners.py": '''
def f(n):
    raise ValueError("legacy")
'''}


def test_baseline_round_trip_suppresses(tmp_path):
    findings = lint_sources(_BAD_FOR_BASELINE)
    assert findings
    path = str(tmp_path / "baseline.json")
    Baseline.from_findings(findings).save(path)
    loaded = Baseline.load(path)
    unsup, sup, stale = loaded.apply(findings)
    assert unsup == [] and len(sup) == len(findings) and stale == []
    # the stored entries keep human-readable context
    doc = json.loads(open(path).read())
    assert doc["findings"][0]["rule"] == "ET301"


def test_baseline_is_line_insensitive_but_not_content_insensitive():
    f1 = Finding("ET301", "error", "a/b.py", 10, "bare 'ValueError' ...")
    f2 = Finding("ET301", "error", "a/b.py", 99, "bare 'ValueError' ...")
    f3 = Finding("ET301", "error", "a/c.py", 10, "bare 'ValueError' ...")
    bl = Baseline.from_findings([f1])
    assert bl.suppresses(f2)          # same finding, shifted line
    assert not bl.suppresses(f3)      # moved to a new file: surfaces


def test_baseline_stale_entries_reported():
    findings = lint_sources(_BAD_FOR_BASELINE)
    bl = Baseline.from_findings(findings)
    unsup, sup, stale = bl.apply([])      # violation since fixed
    assert unsup == [] and sup == [] and len(stale) == len(findings)


def test_missing_baseline_file_is_empty(tmp_path):
    bl = Baseline.load(str(tmp_path / "nope.json"))
    assert len(bl) == 0


# ---------------------------------------------------------------------------
# plane-routing discipline (PL101)
# ---------------------------------------------------------------------------

_PL_BAD = '''
def gate(config, intervals):
    if config.use_fused_decode:                      # PL101: solo knob
        pass
    b = "x" if getattr(config, "inflate_backend", "auto") == "native" \
        else "y"                                     # PL101: getattr form
    return (not config.skip_bad_spans) and intervals is None \
        and config.use_fused_decode                  # PL101: combo gate
'''

_PL_GOOD = '''
from hadoop_bam_tpu.plan.executor import select_plane


def run(config, intervals, quarantine):
    decision = select_plane(config, intervals=intervals)
    if decision.stream_fused:          # consuming the decision: fine
        pass
    if config.skip_bad_spans:          # solo read: failure policy,
        return None                    # not plane routing
    backend = config.inflate_backend   # assignment, not a gate
    import dataclasses
    cfg = dataclasses.replace(config, use_fused_decode=False)  # kwarg
    return decision.plane, backend, cfg
'''


def test_pl_seeded_violations_fire():
    findings = lint_sources(
        {"hadoop_bam_tpu/parallel/bad.py": _PL_BAD}, only=["planroute"])
    assert rules_of(findings) == {"PL101"}
    assert all(f.severity == "error" for f in findings)
    knobs = {k for f in findings
             for k in ("use_fused_decode", "inflate_backend",
                       "skip_bad_spans") if f"'{k}'" in f.message}
    # the solo knobs fire, and skip_bad_spans fires in the combo gate
    assert knobs == {"use_fused_decode", "inflate_backend",
                     "skip_bad_spans"}


def test_pl_clean_twin_and_policy_reads_pass():
    findings = lint_sources(
        {"hadoop_bam_tpu/parallel/good.py": _PL_GOOD},
        only=["planroute"])
    assert findings == []


def test_pl_scope_excludes_plan_and_config():
    # the same gate inside plan/ (its one home) and config.py (knob
    # definitions + the auto resolver) is silent; in a driver package
    # it fires
    src = ("def f(c, intervals):\n"
           "    return c.use_fused_decode and intervals is None\n")
    assert lint_sources({"hadoop_bam_tpu/plan/executor.py": src},
                        only=["planroute"]) == []
    assert lint_sources({"hadoop_bam_tpu/config.py": src},
                        only=["planroute"]) == []
    assert rules_of(lint_sources({"hadoop_bam_tpu/query/gate.py": src},
                                 only=["planroute"])) == {"PL101"}


# ---------------------------------------------------------------------------
# thread-topology races & lock discipline (TH1xx/LK2xx)
# ---------------------------------------------------------------------------

_TH101_BAD = '''
import threading


class Fleet:
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def _loop(self):
        while True:
            self._count += 1           # TH101: heartbeat side, no lock

    def bump(self):
        self._count += 1               # TH101: client side, no lock
'''

_TH101_GOOD = '''
import threading


class Fleet:
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def _loop(self):
        while True:
            with self._lock:
                self._count += 1

    def bump(self):
        with self._lock:
            self._count += 1
'''


def test_th101_seeded_cross_thread_writes_fire():
    findings = lint_sources(
        {"hadoop_bam_tpu/serve/bad.py": _TH101_BAD},
        only=["threadsafety"])
    assert rules_of(findings) == {"TH101"}
    assert len(findings) == 2          # both unguarded write sites
    assert all("Fleet.self._count" in f.message for f in findings)
    assert all(f.severity == "error" for f in findings)


def test_th101_clean_twin_locked_writes_pass():
    assert lint_sources({"hadoop_bam_tpu/serve/good.py": _TH101_GOOD},
                        only=["threadsafety"]) == []


def test_th101_helper_called_only_under_lock_is_guarded():
    # the entry-guard fixpoint: every call site of _record holds the
    # lock, so its write is guarded even with no lexical `with` inside
    findings = lint_sources({"hadoop_bam_tpu/serve/entry.py": '''
import threading


class Meter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
        self._t = threading.Thread(target=self._loop, daemon=True)

    def _record(self):
        self._n += 1

    def _loop(self):
        while True:
            with self._lock:
                self._record()

    def add(self):
        with self._lock:
            self._record()
'''}, only=["threadsafety"])
    assert findings == []


def test_th101_scope_excludes_formats():
    # the identical race outside serve/parallel/write/jobs/resilience/
    # utils/pools.py is not this analyzer's business
    assert lint_sources({"hadoop_bam_tpu/formats/bad.py": _TH101_BAD},
                        only=["threadsafety"]) == []


def test_th_no_thread_roots_means_no_findings():
    # single-threaded scope: nothing is cross-thread, whole analyzer
    # stands down (the 'client' root alone can never conflict)
    assert lint_sources({"hadoop_bam_tpu/serve/calm.py": '''
N = 0


def bump():
    global N
    N += 1


def reset():
    global N
    N = 0
'''}, only=["threadsafety"]) == []


_TH102_BAD = '''
import threading


class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self._seen = {}
        self._t = threading.Thread(target=self._sweep, daemon=True)

    def _sweep(self):
        with self._lock:
            self._seen.clear()

    def put(self, k, v):
        if k not in self._seen:        # TH102: the decision is unlocked
            with self._lock:
                self._seen[k] = v

    def drain(self):
        if not self._seen:             # TH102: emptiness probe, unlocked
            with self._lock:
                self._seen.update({})
'''

_TH102_GOOD = '''
import threading


class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self._seen = {}
        self._t = threading.Thread(target=self._sweep, daemon=True)

    def _sweep(self):
        with self._lock:
            self._seen.clear()

    def put(self, k, v):
        with self._lock:
            if k not in self._seen:
                self._seen[k] = v

    def drain(self):
        with self._lock:
            if not self._seen:
                self._seen.update({})
'''


def test_th102_check_then_act_fires():
    # note every WRITE here is lock-guarded — TH101 stays silent; the
    # defect is purely the unlocked decision (classic TOCTOU)
    findings = lint_sources({"hadoop_bam_tpu/serve/bad.py": _TH102_BAD},
                            only=["threadsafety"])
    assert rules_of(findings) == {"TH102"}
    assert len(findings) == 2
    assert all("Cache.self._seen" in f.message for f in findings)


def test_th102_clean_twin_atomic_check_passes():
    assert lint_sources({"hadoop_bam_tpu/serve/good.py": _TH102_GOOD},
                        only=["threadsafety"]) == []


_LK201_BAD = '''
import threading


class Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
        self._t = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        with self._a:
            with self._b:
                pass

    def poke(self):
        with self._b:
            with self._a:               # LK201: opposite nesting order
                pass
'''

_LK201_GOOD = '''
import threading


class Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
        self._t = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        with self._a:
            with self._b:
                pass

    def poke(self):
        with self._a:
            with self._b:               # same global order: fine
                pass
'''


def test_lk201_lock_order_cycle_fires():
    findings = lint_sources({"hadoop_bam_tpu/serve/bad.py": _LK201_BAD},
                            only=["threadsafety"])
    assert rules_of(findings) == {"LK201"}
    [f] = findings
    assert "Pair.self._a -> Pair.self._b -> Pair.self._a" in f.message


def test_lk201_clean_twin_single_order_passes():
    assert lint_sources({"hadoop_bam_tpu/serve/good.py": _LK201_GOOD},
                        only=["threadsafety"]) == []


def test_th101_parallel_bgzf_prefix_pattern_regression():
    """Both directions of the in-PR fix: the PRE-fix shape of
    write/parallel_bgzf.py (committer thread and close() racing on
    _err with no lock) must keep firing, and the shipped module (now
    serialized through _mu) must stay clean."""
    findings = lint_sources({"hadoop_bam_tpu/write/bad.py": '''
import threading


class Writer:
    def __init__(self):
        self._err = None
        self._t = threading.Thread(target=self._commit_loop, daemon=True)

    def _commit_loop(self):
        try:
            self._commit()
        except Exception as e:
            if self._err is None:
                self._err = e

    def _commit(self):
        pass

    def close(self):
        err, self._err = self._err, None
        if err is not None:
            raise err
'''}, only=["threadsafety"])
    assert rules_of(findings) == {"TH101"}
    assert len(findings) == 2
    assert all("Writer.self._err" in f.message for f in findings)

    repo = run_analyzers(Project.load(), only=["threadsafety"])
    assert repo == []


# ---------------------------------------------------------------------------
# the CI gate: the repo itself lints clean
# ---------------------------------------------------------------------------

def test_repo_lints_clean():
    """``python -m hadoop_bam_tpu lint`` exits 0: zero unsuppressed
    findings against the checked-in baseline.  New violations anywhere in
    the package fail HERE — this test is the tier-1 lint gate."""
    from hadoop_bam_tpu.analysis.core import lint_main
    assert lint_main([]) == 0


def test_lint_cli_exit_codes(tmp_path, capsys):
    """The lint frontend exits 1 on unsuppressed findings and 0 once they
    are baselined (exercises --root / --baseline / --update-baseline)."""
    from hadoop_bam_tpu.analysis.core import lint_main

    pkg = tmp_path / "hadoop_bam_tpu" / "split"
    pkg.mkdir(parents=True)
    (pkg / "planners.py").write_text(
        "def f(n):\n    raise ValueError('x')\n")
    root = str(tmp_path / "hadoop_bam_tpu")
    bl = str(tmp_path / "bl.json")
    assert lint_main(["--root", root, "--baseline", bl]) == 1
    assert lint_main(["--root", root, "--baseline", bl,
                      "--update-baseline"]) == 0
    assert lint_main(["--root", root, "--baseline", bl]) == 0
    out = capsys.readouterr().out
    assert "ET301" in out and "1 suppressed" in out


# ---------------------------------------------------------------------------
# output formats & the findings cache
# ---------------------------------------------------------------------------

def _seed_bad_tree(tmp_path):
    """One-module tree with a single ET301 finding at line 2."""
    pkg = tmp_path / "hadoop_bam_tpu" / "split"
    pkg.mkdir(parents=True)
    (pkg / "planners.py").write_text(
        "def f(n):\n    raise ValueError('x')\n")
    return str(tmp_path / "hadoop_bam_tpu"), str(tmp_path / "bl.json")


def test_lint_format_json(tmp_path, capsys):
    from hadoop_bam_tpu.analysis.core import lint_main
    root, bl = _seed_bad_tree(tmp_path)
    rc = lint_main(["--root", root, "--baseline", bl,
                    "--format", "json", "--no-cache"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["tool"] == "hbam-lint"
    [f] = doc["findings"]
    assert f["rule"] == "ET301"
    assert f["path"].endswith("planners.py")
    assert f["line"] == 2
    assert f["severity"] == "error"
    assert len(f["fingerprint"]) == 16
    assert doc["summary"]["unsuppressed"] == 1


def test_lint_format_sarif(tmp_path, capsys):
    from hadoop_bam_tpu.analysis.core import lint_main
    root, bl = _seed_bad_tree(tmp_path)
    rc = lint_main(["--root", root, "--baseline", bl,
                    "--format", "sarif", "--no-cache"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "hbam-lint"
    assert run["tool"]["driver"]["rules"] == [{"id": "ET301"}]
    [res] = run["results"]
    assert res["ruleId"] == "ET301"
    assert res["level"] == "error"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("planners.py")
    assert loc["region"]["startLine"] == 2
    assert "hbamLint/v1" in res["partialFingerprints"]


def test_lint_format_json_suppressed_exit_zero(tmp_path, capsys):
    from hadoop_bam_tpu.analysis.core import lint_main
    root, bl = _seed_bad_tree(tmp_path)
    assert lint_main(["--root", root, "--baseline", bl,
                      "--update-baseline", "--no-cache"]) == 0
    capsys.readouterr()
    assert lint_main(["--root", root, "--baseline", bl,
                      "--format", "json", "--no-cache"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["findings"] == []
    assert doc["summary"]["suppressed"] == 1


def test_lint_cache_replay_and_invalidation(tmp_path, capsys,
                                            monkeypatch):
    from hadoop_bam_tpu.analysis.core import lint_main
    cache = tmp_path / "cache.json"
    monkeypatch.setenv("HBAM_LINT_CACHE", str(cache))
    root, bl = _seed_bad_tree(tmp_path)

    assert lint_main(["--root", root, "--baseline", bl]) == 1
    out_cold = capsys.readouterr().out
    assert cache.exists()

    # warm replay: byte-identical report and exit code off the digest
    assert lint_main(["--root", root, "--baseline", bl]) == 1
    assert capsys.readouterr().out == out_cold

    # any tree drift invalidates: fixing the file flips the exit code
    fixed = tmp_path / "hadoop_bam_tpu" / "split" / "planners.py"
    fixed.write_text("def f(n):\n    return n\n")
    assert lint_main(["--root", root, "--baseline", bl]) == 0
    capsys.readouterr()

    # --no-cache neither reads nor writes the cache file
    stamp = cache.stat().st_mtime_ns
    assert lint_main(["--root", root, "--baseline", bl,
                      "--no-cache"]) == 0
    assert cache.stat().st_mtime_ns == stamp


# ---------------------------------------------------------------------------
# ISSUE 20: prep/ joins the ET3xx / JS1xx / TH1xx scopes
# ---------------------------------------------------------------------------

_PREP_ET_BAD = '''
def signature(rec):
    if len(rec) < 36:
        raise ValueError("record shorter than fixed header")  # ET301
'''

_PREP_ET_GOOD = '''
from hadoop_bam_tpu.utils.errors import CorruptDataError


def signature(rec):
    if len(rec) < 36:
        raise CorruptDataError("record shorter than fixed header")
'''


def test_et_scope_covers_prep_boundaries():
    """ISSUE 20 scope extension: the fused preprocessing plane's
    modules classify faults for retry/quarantine policy — a bare
    ValueError from the signature walk would retry corrupt bytes."""
    for mod in ("hadoop_bam_tpu/prep/oracle.py",
                "hadoop_bam_tpu/prep/markdup.py",
                "hadoop_bam_tpu/prep/pipeline.py"):
        findings = lint_sources({mod: _PREP_ET_BAD}, only=["taxonomy"])
        assert rules_of(findings) == {"ET301"}, mod
        assert lint_sources({mod: _PREP_ET_GOOD},
                            only=["taxonomy"]) == [], mod
    # prep's package __init__ is not a policy boundary
    assert lint_sources({"hadoop_bam_tpu/prep/__init__.py":
                         _PREP_ET_BAD}, only=["taxonomy"]) == []


_PREP_JS_BAD = '''
import os


def publish_bitmap(spill_dir, bits):
    tmp = os.path.join(spill_dir, "dupbits." + str(os.getpid()))
    with open(tmp, "wb") as f:                # JS102: pid-derived name
        f.write(bits)
    os.replace(tmp, os.path.join(spill_dir, "dupbits.u8"))  # JS101
'''

_PREP_JS_GOOD = '''
import os


def publish_bitmap(jr, spill_dir, bits, size, crc):
    tmp = os.path.join(spill_dir, "dupbits.u8.tmp")
    with open(tmp, "wb") as f:
        f.write(bits)
    final = os.path.join(spill_dir, "dupbits.u8")
    os.replace(tmp, final)
    jr.unit_done("markdup", 0, path=final, size=size, crc=crc)
'''


def test_js_scope_covers_prep_pipeline():
    """ISSUE 20: the fused pipeline publishes spill runs, column
    sidecars and the duplicate bitmap — JS1xx polices it like the
    write path (deterministic temp names, journaled publication)."""
    findings = lint_sources(
        {"hadoop_bam_tpu/prep/pipeline.py": _PREP_JS_BAD},
        only=["jobsafety"])
    assert rules_of(findings) == {"JS101", "JS102"}
    # the journaled-commit twin is the blessed shape
    assert lint_sources({"hadoop_bam_tpu/prep/pipeline.py":
                         _PREP_JS_GOOD}, only=["jobsafety"]) == []
    # the same bad code outside the crash-safe scope is not JS-scoped
    assert lint_sources({"hadoop_bam_tpu/tools/other.py": _PREP_JS_BAD},
                        only=["jobsafety"]) == []


_PREP_TH_BAD = '''
import threading


class StepCache:
    def __init__(self):
        self._lock = threading.Lock()
        self._steps = {}
        self._t = threading.Thread(target=self._warm, daemon=True)
        self._t.start()

    def _warm(self):
        self._steps["warm"] = 1        # TH101: warmer side, no lock

    def get(self, key):
        self._steps[key] = object()    # TH101: caller side, no lock
'''

_PREP_TH_GOOD = '''
import threading


class StepCache:
    def __init__(self):
        self._lock = threading.Lock()
        self._steps = {}
        self._t = threading.Thread(target=self._warm, daemon=True)
        self._t.start()

    def _warm(self):
        with self._lock:
            self._steps["warm"] = 1

    def get(self, key):
        with self._lock:
            self._steps[key] = object()
'''


def test_th_scope_covers_prep():
    """ISSUE 20: a warmed compile-step cache in prep/ shared with a
    background thread gets the same TH1xx policing as serve/."""
    findings = lint_sources(
        {"hadoop_bam_tpu/prep/steps.py": _PREP_TH_BAD},
        only=["threadsafety"])
    assert rules_of(findings) == {"TH101"}
    assert lint_sources({"hadoop_bam_tpu/prep/steps.py":
                         _PREP_TH_GOOD}, only=["threadsafety"]) == []


def test_prep_repo_modules_lint_clean():
    """The shipped prep/ modules themselves pass their new scopes —
    and the committed baseline stays EMPTY (no grandfathered debt)."""
    import json as _json
    import os as _os

    root = _os.path.join(_os.path.dirname(__file__), _os.pardir)
    sources = {}
    for name in ("oracle.py", "markdup.py", "pipeline.py",
                 "__init__.py"):
        rel = f"hadoop_bam_tpu/prep/{name}"
        with open(_os.path.join(root, rel)) as f:
            sources[rel] = f.read()
    findings = run_analyzers(
        Project.from_sources(sources),
        only=["taxonomy", "jobsafety", "threadsafety"])
    assert findings == []
    with open(_os.path.join(root, "hadoop_bam_tpu", "analysis",
                            "baseline.json")) as f:
        assert _json.load(f)["findings"] == []
