"""hbam-lint: repo-native static analysis (``python -m hadoop_bam_tpu lint``).

AST analyzers over correctness regimes generic linters cannot see:

- ``trace_safety`` (TS1xx) — host Python inside JAX-traced code
- ``lockstep``     (CL2xx) — collectives off the uniform control path
- ``taxonomy``     (ET3xx) — unclassified raises at policy boundaries
- ``layout``       (LC4xx) — hand-coded offsets vs the declared
  binary-layout contract table (``analysis/layout_specs.py``)
- ``feedpath``     (PF5xx) — fresh per-group device-tile allocations in
  the feed paths (group buffers belong to ``parallel/staging.py``'s
  rings; the memset tax scales with device count)
- ``decodepath``   (DP7xx) — full-buffer ``.tobytes()`` /
  ``np.frombuffer(...).copy()`` materializations of inflated spans on
  the decode hot path (every extra sweep is a DRAM pass the fused
  decode exists to remove)
- ``jobsafety``    (JS1xx) — crash-safe job discipline in ``write/`` +
  the mesh sort: publication renames outside the blessed/journaled
  commit helpers, non-idempotent (random/pid/time-derived) temp names
  that resume can neither verify nor sweep
- ``threadsafety`` (TH1xx/LK2xx) — thread-topology races and lock
  discipline on the shared interprocedural engine
  (``analysis/callgraph.py``): unguarded cross-thread writes,
  check-then-act outside a guard, lock-order cycles

Findings carry file:line, rule id and severity; ``analysis/baseline.json``
suppresses accepted legacy findings so CI fails only on regressions.
``analysis/lintcache.py`` short-circuits a full re-run when nothing in
the tree (or the analyzers) changed; ``--format json|sarif`` emits
machine-readable findings for CI annotation.
"""
from hadoop_bam_tpu.analysis.core import (  # noqa: F401
    Baseline, Finding, Project, analyzers, lint_main, run_analyzers,
)
