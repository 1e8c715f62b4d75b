"""Per-layer reducer for the read kernels' share of their roofline: the twin
of ``reducers/roofline.py`` over ``benchmark/kernel_work_reads.py``, whose
functions count the work of the whole window (the runner reports the records
it reduced), not of one job.

    share = 100 x max(ops / peak_ops, bytes / hbm_bytes_per_s)
                / device seconds of the named ops

Same contract as the other reducers: the metric file's ``params`` and the
run's observations in, a number or ``None`` (nothing to read: no such op in
the trace, no sizes, a device without peaks) out."""
from __future__ import annotations

import json

from benchmark import kernel_work_reads, trace_reduce
from benchmark.reducers.roofline import _PEAKS, bound_seconds


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
    ops, nbytes = kernel_work_reads.KERNELS[params["kernel"]](sizes)
    return 100.0 * bound_seconds(ops, nbytes, peaks) / seconds
