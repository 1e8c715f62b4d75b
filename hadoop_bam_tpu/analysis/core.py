"""hbam-lint core: findings, project model, baseline, runner, CLI.

The codebase spans three correctness regimes that generic linters cannot
see — JAX-traced code where host Python silently serializes the mesh,
multi-host collective code where a rank-conditional branch is a deadlock,
and dozens of hand-written binary-layout offsets whose only prior contract
was a comment.  Each regime gets a repo-native AST analyzer
(``analysis/trace_safety.py``, ``analysis/lockstep.py``,
``analysis/taxonomy.py``, ``analysis/layout.py``); this module is the
shared machinery: the ``Finding`` record, the parsed-``Project`` model the
analyzers consume, the checked-in ``baseline.json`` that suppresses
accepted legacy findings so CI fails only on regressions, and the
``python -m hadoop_bam_tpu lint`` frontend.

Baseline matching is deliberately line-insensitive: a finding's
fingerprint hashes (rule, path, message), so unrelated edits that shift
line numbers do not un-suppress legacy findings, while moving or copying
a violation to a new file (or changing what it says) does surface it.
"""
from __future__ import annotations

import ast
import dataclasses
import hashlib
import json
import os
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

SEVERITIES = ("error", "warning")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One analyzer hit: file:line, rule id, severity, human message."""
    rule: str              # e.g. "TS101"
    severity: str          # "error" | "warning"
    path: str              # repo-relative, forward slashes
    line: int              # 1-based
    message: str

    @property
    def fingerprint(self) -> str:
        """Line-insensitive identity used for baseline suppression."""
        key = f"{self.rule}\x00{self.path}\x00{self.message}"
        return hashlib.sha256(key.encode()).hexdigest()[:16]

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}] "
                f"{self.severity}: {self.message}")

    def to_dict(self) -> Dict[str, object]:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "severity": self.severity, "message": self.message,
                "fingerprint": self.fingerprint}


@dataclasses.dataclass(frozen=True)
class Module:
    """One parsed source file of the project under analysis."""
    path: str              # repo-relative, forward slashes
    source: str
    tree: ast.Module

    @property
    def package_parts(self) -> Tuple[str, ...]:
        """('hadoop_bam_tpu', 'ops', 'inflate') for the module path."""
        parts = self.path.replace("\\", "/").split("/")
        if parts[-1].endswith(".py"):
            parts[-1] = parts[-1][:-3]
        if parts[-1] == "__init__":
            parts = parts[:-1]
        return tuple(parts)

    @property
    def dotted(self) -> str:
        return ".".join(self.package_parts)


class Project:
    """The set of parsed modules the analyzers run over."""

    def __init__(self, modules: Sequence[Module]):
        self.modules = list(modules)
        self.by_path = {m.path: m for m in self.modules}
        self.by_dotted = {m.dotted: m for m in self.modules}

    @classmethod
    def from_sources(cls, sources: Dict[str, str]) -> "Project":
        """Build a project from {relative_path: source}; the seeded-violation
        fixture corpus in tests goes through here."""
        mods = []
        for path, src in sorted(sources.items()):
            mods.append(Module(path=path.replace("\\", "/"), source=src,
                               tree=ast.parse(src, filename=path)))
        return cls(mods)

    @classmethod
    def load(cls, root: Optional[str] = None,
             package: str = "hadoop_bam_tpu") -> "Project":
        """Parse every .py file of the installed package (or of ``root``).

        Module paths are ALWAYS rooted at ``package`` regardless of the
        on-disk directory name, so the analyzers' path-prefix scopes
        cannot silently miss everything when ``--root`` points at a
        checkout named differently; pointing ``--root`` at a repo that
        *contains* the package descends into it."""
        if root is None:
            import hadoop_bam_tpu
            root = os.path.dirname(os.path.abspath(hadoop_bam_tpu.__file__))
        root = os.path.abspath(root)
        if os.path.basename(root) != package \
                and os.path.isdir(os.path.join(root, package)):
            root = os.path.join(root, package)
        sources: Dict[str, str] = {}
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in sorted(dirnames)
                           if d != "__pycache__" and not d.startswith(".")]
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fn)
                rel = os.path.join(package, os.path.relpath(full, root))
                with open(full, "r", encoding="utf-8") as f:
                    sources[rel.replace(os.sep, "/")] = f.read()
        return cls.from_sources(sources)

    def select(self, prefixes: Sequence[str]) -> List[Module]:
        """Modules whose path starts with any of the given prefixes (the
        per-analyzer scoping hook).  Prefixes match path segments, e.g.
        'hadoop_bam_tpu/ops'."""
        out = []
        for m in self.modules:
            for p in prefixes:
                p = p.rstrip("/")
                if m.path == p or m.path.startswith(p + "/") \
                        or m.path == p + ".py":
                    out.append(m)
                    break
        return out


# ---------------------------------------------------------------------------
# analyzer registry
# ---------------------------------------------------------------------------

Analyzer = Callable[[Project], List[Finding]]
_REGISTRY: Dict[str, Analyzer] = {}


def register(name: str) -> Callable[[Analyzer], Analyzer]:
    def deco(fn: Analyzer) -> Analyzer:
        _REGISTRY[name] = fn
        return fn
    return deco


def analyzers() -> Dict[str, Analyzer]:
    """Name -> analyzer map (importing the analyzer modules on demand)."""
    # import for registration side effects
    from hadoop_bam_tpu.analysis import (  # noqa: F401
        decodepath, feedpath, jobsafety, layout, lockstep, obsrules,
        planroute, querycache, servebounds, taxonomy, threadsafety,
        trace_safety, writepath,
    )
    return dict(_REGISTRY)


def run_analyzers(project: Project,
                  only: Optional[Sequence[str]] = None) -> List[Finding]:
    findings: List[Finding] = []
    for name, fn in sorted(analyzers().items()):
        if only and name not in only:
            continue
        findings.extend(fn(project))
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return findings


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")


class Baseline:
    """Checked-in suppression list: accepted legacy findings by fingerprint.

    The stored entries keep rule/path/line/message for human review, but
    only the fingerprint participates in matching, so line drift never
    un-suppresses and never silently suppresses a *new* finding."""

    def __init__(self, entries: Sequence[Dict[str, object]] = ()):
        self.entries = [dict(e) for e in entries]
        self._fps = {str(e["fingerprint"]) for e in self.entries}

    @classmethod
    def from_findings(cls, findings: Iterable[Finding]) -> "Baseline":
        return cls([f.to_dict() for f in findings])

    @classmethod
    def load(cls, path: str = DEFAULT_BASELINE) -> "Baseline":
        if not os.path.exists(path):
            return cls()
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        return cls(doc.get("findings", []))

    def save(self, path: str = DEFAULT_BASELINE) -> None:
        doc = {
            "comment": "hbam-lint accepted-legacy findings; matching is by "
                       "fingerprint (line-insensitive). Regenerate with "
                       "`python -m hadoop_bam_tpu lint --update-baseline`.",
            "findings": sorted(
                self.entries,
                key=lambda e: (e.get("path", ""), e.get("rule", ""),
                               e.get("fingerprint", ""))),
        }
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")

    def __len__(self) -> int:
        return len(self.entries)

    def suppresses(self, finding: Finding) -> bool:
        return finding.fingerprint in self._fps

    def apply(self, findings: Sequence[Finding]
              ) -> Tuple[List[Finding], List[Finding], List[Dict]]:
        """(unsuppressed, suppressed, stale_baseline_entries).  Stale
        entries — baselined findings the analyzers no longer report —
        signal the baseline can be burned down further."""
        unsup = [f for f in findings if not self.suppresses(f)]
        sup = [f for f in findings if self.suppresses(f)]
        live = {f.fingerprint for f in findings}
        stale = [e for e in self.entries
                 if str(e.get("fingerprint")) not in live]
        return unsup, sup, stale


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def lint_main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m hadoop_bam_tpu lint`` / ``hbam lint`` entry point.

    Exit 0 when every finding is baseline-suppressed; 1 when unsuppressed
    findings remain (the CI contract)."""
    import argparse

    p = argparse.ArgumentParser(
        prog="hadoop_bam_tpu lint",
        description="repo-native static analysis: trace safety (TS1xx), "
                    "collective lockstep (CL2xx), error taxonomy (ET3xx), "
                    "binary-layout contracts (LC4xx), feed-path "
                    "allocation discipline (PF5xx), query-cache key "
                    "identity (QE5xx), observability discipline (OB6xx), "
                    "decode-path copy discipline (DP7xx), serving-tier "
                    "cache bounds (SV8xx), write-path atomicity/"
                    "parallelism (WR10x), plane-routing discipline "
                    "(PL101), thread-topology races and lock ordering "
                    "(TH1xx/LK2xx)")
    p.add_argument("--root", default=None,
                   help="package directory to analyze (default: the "
                        "installed hadoop_bam_tpu package)")
    p.add_argument("--only", action="append", default=None,
                   metavar="ANALYZER",
                   help="run one analyzer (trace_safety, lockstep, "
                        "taxonomy, layout, feedpath, querycache, obs, "
                        "decodepath, servebounds, writepath, "
                        "threadsafety); repeatable")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline file (default: analysis/baseline.json)")
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding, ignoring the baseline")
    p.add_argument("--update-baseline", action="store_true",
                   help="accept all current findings into the baseline "
                        "file and exit 0")
    p.add_argument("--show-suppressed", action="store_true",
                   help="also print baseline-suppressed findings")
    p.add_argument("--format", choices=("text", "json", "sarif"),
                   default="text", dest="fmt",
                   help="output format: human text (default, "
                        "byte-stable), a JSON findings document, or "
                        "SARIF 2.1.0 for CI annotation")
    p.add_argument("--no-cache", action="store_true",
                   help="always re-parse and re-analyze, ignoring the "
                        "findings cache (.hbam-lint-cache.json)")
    args = p.parse_args(argv)

    known = sorted(analyzers())
    for name in args.only or ():
        if name not in known:
            # fail CLOSED: a typo'd --only must not run zero analyzers
            # and report a green lint
            p.error(f"unknown analyzer {name!r}; choose from {known}")

    # findings cache: sound only as a whole-run short-circuit (several
    # analyzers are interprocedural), so a stat-digest of the entire
    # tree + the analyzer sources gates replay; any drift -> full run
    from hadoop_bam_tpu.analysis import lintcache
    findings: Optional[List[Finding]] = None
    n_mod = 0
    digest = None if args.no_cache \
        else lintcache.compute_digest(args.root, only=args.only)
    if digest is not None:
        cached = lintcache.load(lintcache.default_cache_path(), digest)
        if cached is not None:
            findings, n_mod = cached
    if findings is None:
        project = Project.load(root=args.root)
        if not project.modules:
            p.error(f"no Python modules found under --root {args.root!r}")
        n_mod = len(project.modules)
        findings = run_analyzers(project, only=args.only)
        if digest is not None:
            lintcache.store(lintcache.default_cache_path(), digest,
                            findings, n_mod)

    if args.update_baseline:
        Baseline.from_findings(findings).save(args.baseline)
        print(f"wrote {args.baseline} ({len(findings)} finding(s))")
        return 0

    if args.no_baseline:
        unsup, sup, stale = list(findings), [], []
    else:
        unsup, sup, stale = Baseline.load(args.baseline).apply(findings)

    if args.fmt == "json":
        doc = {"tool": "hbam-lint", "version": 1,
               "findings": [f.to_dict() for f in unsup],
               "suppressed": [f.to_dict() for f in sup]
               if args.show_suppressed else [],
               "summary": {"modules": n_mod, "findings": len(findings),
                           "suppressed": len(sup),
                           "unsuppressed": len(unsup)}}
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 1 if unsup else 0
    if args.fmt == "sarif":
        print(json.dumps(_sarif_doc(unsup), indent=2, sort_keys=True))
        return 1 if unsup else 0

    for f in unsup:
        print(f.render())
    if args.show_suppressed:
        for f in sup:
            print(f"{f.render()}  [baseline-suppressed]")
    for e in stale:
        print(f"note: stale baseline entry {e.get('fingerprint')} "
              f"({e.get('rule')} {e.get('path')}) — no longer reported; "
              f"run --update-baseline to burn it down")
    print(f"hbam-lint: {n_mod} modules, {len(findings)} finding(s), "
          f"{len(sup)} suppressed, {len(unsup)} unsuppressed")
    return 1 if unsup else 0


def _sarif_doc(findings: Sequence[Finding]) -> Dict[str, object]:
    """Minimal SARIF 2.1.0 document for CI annotation surfaces."""
    rules = sorted({f.rule for f in findings})
    return {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "hbam-lint",
                "rules": [{"id": r} for r in rules],
            }},
            "results": [{
                "ruleId": f.rule,
                "level": "error" if f.severity == "error" else "warning",
                "message": {"text": f.message},
                "locations": [{"physicalLocation": {
                    "artifactLocation": {"uri": f.path},
                    "region": {"startLine": f.line},
                }}],
                "partialFingerprints": {"hbamLint/v1": f.fingerprint},
            } for f in findings],
        }],
    }
