"""Per-layer reducer for a kernel's share of its roofline.

    share = 100 x max(ops / peak_ops, bytes / hbm_bytes_per_s)
                / device seconds of the named ops

The bound is the least time the chip could take for the work the result
needs (``benchmark/kernel_work.py``, counted from the sizes the runner
reports under ``observations["gwas"]``); the peaks are ``benchmark/peaks.json``
by device kind; the time is the trace's (``XLA Ops`` events whose name starts
with ``prefix``, device 0).  Same contract as the other reducers: the metric
file's ``params`` and the run's observations in, a number or ``None`` (nothing
to read: no such op in the trace, no sizes, a device without peaks) out."""
from __future__ import annotations

import json
import os

from benchmark import kernel_work, trace_reduce

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def bound_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def kernel_share(params: dict, obs: dict):
    sizes = obs.get(params["sizes"])
    events = ((obs.get("trace") or {}).get("ops") or {}).get(0)
    if not sizes or not events:
        return None
    with open(_PEAKS, encoding="utf-8") as fh:
        peaks = json.load(fh)["devices"].get(obs.get("device_kind"))
    seconds = trace_reduce.ops_prefix_seconds(events, params["prefix"])
    if not peaks or not seconds:
        return None
    ops, nbytes = kernel_work.KERNELS[params["kernel"]](sizes)
    return 100.0 * int(sizes["jobs"]) * bound_seconds(ops, nbytes, peaks) \
        / seconds
