"""ET3xx — error taxonomy: classified errors at the policy boundaries.

PR 1's resilience layer (``utils/errors.py``) keys every retry /
quarantine / fail-fast decision on the error *class*:
``TransientIOError`` retries with backoff, ``CorruptDataError`` fails
fast (re-decoding corrupt bytes never heals), ``PlanError`` always
raises (a misconfigured run must not be skipped as if the data were
bad).  ``classify_error`` has builtin fallbacks, but a bare
``ValueError`` at a decode boundary classifies as CORRUPT even when the
real cause is a bad parameter — and a bare ``OSError`` classifies as
TRANSIENT even when it is deterministic.  At the policy boundaries the
class must be explicit.

Rule:

- ET301 bare builtin raise (``ValueError`` / ``OSError`` / ``IOError``
  / ``RuntimeError`` / ``Exception``) at a bgzf / bamio / inflate /
  planner policy boundary; raise a ``utils.errors`` taxonomy class (or
  a subclass like ``BGZFError``) instead.
"""
from __future__ import annotations

import ast
from typing import List

from hadoop_bam_tpu.analysis.astutil import last_segment
from hadoop_bam_tpu.analysis.core import Finding, Project, register

# the policy boundaries decode_with_retry / RetryingByteSource /
# broadcast_plan classify across (ISSUE 3 tentpole scope), extended in
# ISSUE 11 to the write-path and serve-tier boundary modules — a bare
# builtin raised there reaches clients as the WRONG wire taxonomy kind
# (transport.error_kind) or poisons the parallel writer with a class
# the retry policy misreads — and in ISSUE 12 to the cohort plane's
# boundary modules, where the class decides whether a faulting sample
# input QUARANTINES (data) or fails the build (configuration).
# ISSUE 16 adds the fleet modules, where the class also decides
# whether a peer answer feeds that peer's circuit breaker (PLAN never
# does) and what error_kind a peer sees on the wire
SCOPE = (
    "hadoop_bam_tpu/formats/bgzf.py",
    "hadoop_bam_tpu/formats/bamio.py",
    "hadoop_bam_tpu/ops/inflate.py",
    "hadoop_bam_tpu/split/planners.py",
    "hadoop_bam_tpu/split/vcf_planners.py",
    "hadoop_bam_tpu/split/read_planners.py",
    "hadoop_bam_tpu/split/cram_planner.py",
    "hadoop_bam_tpu/write/parallel_bgzf.py",
    "hadoop_bam_tpu/write/sharded.py",
    "hadoop_bam_tpu/write/api.py",
    "hadoop_bam_tpu/write/indexing.py",
    "hadoop_bam_tpu/serve/transport.py",
    "hadoop_bam_tpu/serve/loop.py",
    "hadoop_bam_tpu/serve/tenancy.py",
    "hadoop_bam_tpu/serve/prefetch.py",
    "hadoop_bam_tpu/serve/tiles.py",
    "hadoop_bam_tpu/serve/fleet.py",
    "hadoop_bam_tpu/serve/membership.py",
    "hadoop_bam_tpu/cohort/manifest.py",
    "hadoop_bam_tpu/cohort/join.py",
    "hadoop_bam_tpu/cohort/serving.py",
    # ISSUE 20: the fused preprocessing plane — oracle, device kernels,
    # and pipeline all classify faults for retry/quarantine policy
    "hadoop_bam_tpu/prep/oracle.py",
    "hadoop_bam_tpu/prep/markdup.py",
    "hadoop_bam_tpu/prep/pipeline.py",
)

_BARE = {
    "ValueError": "CorruptDataError (bad bytes) or PlanError (bad "
                  "parameters)",
    "OSError": "TransientIOError (environment) or PlanError "
               "(deterministic, e.g. missing path)",
    "IOError": "TransientIOError or PlanError",
    "RuntimeError": "PlanError (misconfiguration) or CorruptDataError",
    "Exception": "an explicit utils.errors taxonomy class",
}


@register("taxonomy")
def analyze(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for m in project.select(SCOPE):
        for node in ast.walk(m.tree):
            if not isinstance(node, ast.Raise) or node.exc is None:
                continue
            exc = node.exc
            name = None
            if isinstance(exc, ast.Call):
                name = last_segment(exc.func)
            elif isinstance(exc, (ast.Name, ast.Attribute)):
                name = last_segment(exc)
            if name in _BARE:
                findings.append(Finding(
                    rule="ET301", severity="error", path=m.path,
                    line=node.lineno,
                    message=f"bare '{name}' raised at a policy boundary — "
                            f"decode_with_retry cannot classify it as "
                            f"intended; use {_BARE[name]}"))
    return findings
