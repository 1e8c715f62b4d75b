"""DP7xx — decode-path copy discipline: no full-buffer materializations
of inflated spans on the hot path.

The fused decode rework (round 10) exists because every extra sweep over
an inflated span is DRAM traffic the host CPU pays per record batch.  A
``data.tobytes()`` on a whole span silently duplicates megabytes per span
per walk (the founding case lived in ``ops/inflate.py``'s ``walk_records``
fallback), and ``np.frombuffer(...).copy()`` re-copies a buffer that was
already zero-copy.  Both patterns read as innocent one-liners and creep
back easily; this analyzer keeps them out of the modules on the inflated-
span hot path:

- DP701: a ``.tobytes()`` call whose receiver is a whole buffer (a bare
  name or attribute — NOT a sliced/indexed subscript) inside a function
  in a decode-path module.  Slices like ``data[s:e].tobytes()`` are the
  blessed idiom (bounded copies of exactly the bytes needed) and are not
  flagged.
- DP702: ``np.frombuffer(...).copy()`` in the same scope — the copy
  defeats the zero-copy view ``frombuffer`` exists to provide; if a
  mutable buffer is required, allocate once and decompress into it.

Module-level constants and test fixtures are out of scope: the rule only
fires inside function bodies of the listed hot-path modules.
"""
from __future__ import annotations

import ast
from typing import List

from hadoop_bam_tpu.analysis.astutil import last_segment
from hadoop_bam_tpu.analysis.core import Finding, Module, Project, register

# the modules every inflated byte flows through on the BAM-family hot
# path: inflate dispatch + fused decode, the tile unpack layer, and the
# span pipeline + staging feed
SCOPE = (
    "hadoop_bam_tpu/ops/inflate.py",
    "hadoop_bam_tpu/ops/unpack_bam.py",
    "hadoop_bam_tpu/parallel/pipeline.py",
    "hadoop_bam_tpu/parallel/staging.py",
)


def _is_full_buffer_tobytes(node: ast.AST) -> bool:
    """``X.tobytes()`` with X a bare name/attribute (whole buffer) —
    sliced receivers (``X[a:b].tobytes()``) are the blessed idiom."""
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "tobytes"
            and not node.args and not node.keywords
            and isinstance(node.func.value, (ast.Name, ast.Attribute)))


def _is_frombuffer_copy(node: ast.AST) -> bool:
    """``np.frombuffer(...).copy()`` — any-args frombuffer, immediate
    copy."""
    if not (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "copy"):
        return False
    inner = node.func.value
    return (isinstance(inner, ast.Call)
            and last_segment(inner.func) == "frombuffer")


def _scan_function(m: Module, fn: ast.AST, findings: List[Finding]) -> None:
    for node in ast.walk(fn):
        if _is_full_buffer_tobytes(node):
            recv = ast.unparse(node.func.value)
            findings.append(Finding(
                rule="DP701", severity="error", path=m.path,
                line=node.lineno,
                message=f"full-buffer '{recv}.tobytes()' materializes a "
                        f"whole inflated span on the decode hot path — "
                        f"walk/pack over the array's own buffer (a "
                        f"memoryview reaches every consumer), or slice "
                        f"exactly the bytes needed"))
        elif _is_frombuffer_copy(node):
            findings.append(Finding(
                rule="DP702", severity="error", path=m.path,
                line=node.lineno,
                message="'np.frombuffer(...).copy()' re-copies a buffer "
                        "frombuffer just mapped zero-copy — decompress "
                        "into a preallocated array instead of copying "
                        "the view"))


def _outermost_functions(tree: ast.Module):
    """Top-level functions and methods — NOT nested defs, whose bodies
    the enclosing scan already covers (scanning both would double-report
    every finding inside a closure)."""
    stack = list(ast.iter_child_nodes(tree))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node
        else:
            stack.extend(ast.iter_child_nodes(node))


@register("decodepath")
def analyze(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for m in project.select(SCOPE):
        for fn in _outermost_functions(m.tree):
            _scan_function(m, fn, findings)
    return findings
