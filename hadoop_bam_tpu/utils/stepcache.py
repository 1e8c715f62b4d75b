"""How every device step is built: named, jitted, counted — and, where a
process may cycle through meshes, cached with a bound.

``named_step`` is the one ``jax.jit`` call of the step builders: it gives
the program a stable name on the device plane and counts the build.

Both the query engine's overlap predicate and the serve tier's tile
filter key one compiled step per (mesh, axis) — a process cycling
through many meshes must not grow those module caches forever (the
SV801 discipline), and the logic (lock, double-check, FIFO evict) is
identical.  One implementation, shared.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, Hashable

from hadoop_bam_tpu.utils.metrics import METRICS


def named_step(name: str, fn: Callable, donate_argnums=()) -> Callable:
    """``jax.jit(fn)`` as the program ``hbam_<name>``: the XLA module is
    then ``jit_hbam_<name>``, which the profiler's device plane carries,
    so a trace reduction finds the step after a refactor renumbers XLA's
    fusions.  The identity is the function's NAME, not only a scope
    inside it: the persistent compile cache's key leaves debug metadata
    out but not the module name.  ``fn`` is renamed in place, so pass a
    function built for this step (a ``shard_map`` result, a local def),
    never a shared one.  Counts ``steps.built.hbam_<name>``: a count that
    grows with the jobs run is a step re-traced every job.
    ``donate_argnums`` names the arguments the step updates in place
    (device-resident state a driver threads through its dispatches)."""
    import jax

    fn.__name__ = fn.__qualname__ = f"hbam_{name}"
    METRICS.count(f"steps.built.{fn.__name__}")
    return jax.jit(fn, donate_argnums=donate_argnums)


class BoundedStepCache:
    """``get_or_build(key, build)``: returns the cached value or builds,
    inserts (evicting oldest-inserted past ``cap``), and returns it.
    ``build`` runs OUTSIDE the lock — jit construction is slow and must
    not serialize unrelated lookups; two racing builders of the same key
    both build, first insert wins for future callers."""

    def __init__(self, cap: int = 8):
        self.cap = max(1, int(cap))
        self._lock = threading.Lock()
        self._entries: Dict[Hashable, object] = {}

    def get_or_build(self, key: Hashable, build: Callable[[], object]):
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                return hit
        value = build()
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                return existing
            while len(self._entries) >= self.cap:
                self._entries.pop(next(iter(self._entries)))
            self._entries[key] = value
        return value

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)
