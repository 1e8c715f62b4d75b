"""Per-layer reducers over the profiler trace (``trace_reduce.reduce``)."""
from __future__ import annotations

from benchmark import trace_reduce


def _ops(params: dict, obs: dict):
    """The op list the metric reads: one device's, or ``None``."""
    ops = (obs.get("trace") or {}).get("ops") or {}
    return ops.get(int(params["device"])) if "device" in params else None


def trace_idle_share(params: dict, obs: dict):
    """100 x (1 - busy / traced window); busy is one device's union of op
    intervals (``device``) or the mean over the devices."""
    tr = obs.get("trace") or {}
    if not tr.get("window_s") or not tr.get("ops"):
        return None
    busy = trace_reduce.busy_seconds(_ops(params, obs) or []) \
        if "device" in params else tr["busy_s"]
    return 100.0 * trace_reduce.idle_share(busy, tr["window_s"])


def trace_busy_over_counter(params: dict, obs: dict):
    """Mean device busy seconds / a unit of work the runner counted in the
    window (``per``), x scale."""
    tr = obs.get("trace") or {}
    n = obs.get("units", {}).get(params["per"])
    if not n or not tr.get("ops"):
        return None
    return params.get("scale", 1.0) * tr["busy_s"] / n


def trace_ops_prefix(params: dict, obs: dict):
    """Device seconds of ops whose name starts with ``prefix`` on one
    device / a unit of work, x scale."""
    ops = _ops(params, obs)
    n = obs.get("units", {}).get(params["per"])
    if ops is None or not n:
        return None
    return params.get("scale", 1.0) \
        * trace_reduce.ops_prefix_seconds(ops, params["prefix"]) / n
