"""From a profiler trace (xplane) to device busy time, idle share, op sums
and the breakdown — the reduction every PR's numbers go through.

Reads ``*.xplane.pb`` with ``jax.profiler.ProfileData`` and nothing else.
All arithmetic is on plain ``(start_ns, end_ns, name)`` tuples so that it can
be checked on synthetic intervals (``tests/test_benchmark.py``).

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per executed HLO op.  On the CPU (the explicit tiny rehearsal only) the ops
of the XLA:CPU client's worker threads stand in so that the same code path
runs; a rehearsal's numbers are stamped ``cpu`` and never reported as a
device's.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_ANNOTATION = "benchmark.window"
_CPU_OP_LINES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen")
# the TPU runtime's own threads: millions of task events a window, none of
# them a span of the program
_RUNTIME_LINES = ("pjrt-tpu-tasks", "tfrt-", "futex-", "EventFDAsyncWorker")
_HLO_NAME = re.compile(r"%([\w.\-]+) = ")


def load(log_dir: str):
    """The newest xplane under ``log_dir`` as ``ProfileData``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no xplane under {log_dir}")
    return ProfileData.from_file(files[-1])


def op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO line (``%fusion.3 = ...``):
    keep the instruction's own name."""
    m = _HLO_NAME.match(name)
    return m.group(1) if m else name


def _events(line):
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns),
             op_name(e.name))
            for e in line.events if e.duration_ns > 0]


def device_ops(profile, platform: str) -> dict:
    """{device index: [(start_ns, end_ns, op name)]} of executed ops."""
    out: dict = {}
    for plane in profile.planes:
        if platform == "tpu" and plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out.setdefault(idx, []).extend(_events(line))
        elif platform == "cpu" and plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith(_CPU_OP_LINES):
                    out.setdefault(0, []).extend(
                        ev for ev in _events(line)
                        if "::" not in ev[2])
    return out


def host_spans(profile) -> list:
    """[(start_ns, end_ns, name)] of host-plane annotations whose name
    looks like one of the program's spans (``layer.stage``) or is the
    benchmark's own window marker."""
    out = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith(_CPU_OP_LINES + _RUNTIME_LINES):
                continue
            out.extend(ev for ev in _events(line)
                       if "." in ev[2] and "(" not in ev[2]
                       and "::" not in ev[2] and " " not in ev[2]
                       and "/" not in ev[2])
    return out


def window_of(spans, fallback_ops: dict):
    """(start_ns, end_ns) of the traced window: the benchmark's own
    annotation around the measured work, else the span of all ops."""
    for s, e, name in spans:
        if name == WINDOW_ANNOTATION:
            return s, e
    flat = [ev for evs in fallback_ops.values() for ev in evs]
    if not flat:
        return None
    return min(e[0] for e in flat), max(e[1] for e in flat)


def clip(events, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def union(events) -> list:
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out: list = []
    for s, e in sorted((s, e) for s, e, *_ in events):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(events) -> float:
    return sum(e - s for s, e in union(events)) / 1e9


def mean_busy_seconds(ops_by_device: dict) -> float:
    """Busy seconds averaged over the devices that appear in the trace."""
    if not ops_by_device:
        return 0.0
    return sum(busy_seconds(evs) for evs in ops_by_device.values()) \
        / len(ops_by_device)


def idle_share(busy_s: float, window_s: float) -> float:
    return 1.0 - busy_s / window_s


def ops_prefix_seconds(events, prefix: str) -> float:
    """Summed device seconds of ops whose name starts with ``prefix``."""
    return sum(e - s for s, e, n in events if n.startswith(prefix)) / 1e9


def top_ops(events, k: int = 10) -> list:
    by: dict = {}
    for s, e, n in events:
        by[n] = by.get(n, 0) + (e - s)
    return [[n, t / 1e9] for n, t in
            sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def gaps(events, lo: int, hi: int) -> list:
    """Idle [(start, end)] inside [lo, hi] between the merged intervals."""
    out, at = [], lo
    for s, e in union(events):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def idle_gaps_by_span(events, spans, lo: int, hi: int,
                      k: int = 10, longest: int = 4000) -> list:
    """The idle time of one device, named by what the host was doing:
    each of the ``longest`` gaps goes to the innermost (latest-begun)
    program span open at its midpoint (``no_span_open`` when none is);
    the rest are summed under one name."""
    import numpy as np

    gs = sorted(gaps(events, lo, hi), key=lambda g: g[0] - g[1])
    spans = [sp for sp in spans if sp[2] != WINDOW_ANNOTATION]
    begin = np.array([sp[0] for sp in spans], np.int64)
    end = np.array([sp[1] for sp in spans], np.int64)
    by: dict = {}
    for s, e in gs[:longest]:
        mid = (s + e) // 2
        open_ = np.flatnonzero((begin <= mid) & (mid < end))
        name = spans[open_[np.argmax(begin[open_])]][2] if open_.size \
            else "no_span_open"
        by[name] = by.get(name, 0) + (e - s)
    rest = sum(e - s for s, e in gs[longest:])
    if rest:
        by[f"gaps_after_the_{longest}_longest"] = rest
    return [[n, t / 1e9] for n, t in
            sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def reduce(log_dir: str, platform: str) -> dict:
    """Everything the per-layer reducers and the last line read from one
    trace: per-device op lists clipped to the window, busy and window
    seconds, and the breakdown."""
    profile = load(log_dir)
    ops = device_ops(profile, platform)
    spans = host_spans(profile)
    win = window_of(spans, ops)
    if win is None:
        return {"ops": {}, "busy_s": 0.0, "window_s": 0.0,
                "inventory": _inventory(profile)}
    lo, hi = win
    ops = {d: clip(evs, lo, hi) for d, evs in ops.items()}
    first = ops.get(min(ops)) if ops else []
    return {
        "ops": ops,
        "busy_s": mean_busy_seconds(ops),
        "window_s": (hi - lo) / 1e9,
        "breakdown": {
            "device_ops": top_ops(first),
            "idle_gaps": idle_gaps_by_span(first, clip(spans, lo, hi),
                                           lo, hi)},
        "inventory": _inventory(profile),
    }


def _inventory(profile) -> list:
    """[plane, line, events] (lines of one name summed; the runtime's own
    threads are named, not counted): printed on an earlier line of a
    traced run so a reader can see what the reduction had to work with."""
    by: dict = {}
    for plane in profile.planes:
        for line in plane.lines:
            key = (plane.name, line.name.split("/")[0])
            n = -1 if line.name.startswith(_RUNTIME_LINES) \
                else sum(1 for _ in line.events)
            by[key] = by.get(key, 0) + n
    return [[p, ln, n] for (p, ln), n in by.items()][:40]
