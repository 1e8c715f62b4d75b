"""Tests of the read kernel's roofline reader and of the work it counts
(never of the chip).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import kernel_work_reads  # noqa: E402
from benchmark.reducers import roofline, roofline_reads  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "peaks.json"),
          encoding="utf-8") as _fh:
    V5E = json.load(_fh)["devices"]["TPU v5 lite"]

PARAMS = {"kernel": "seq_stats", "prefix": "hbam_seq_stats_kernel",
          "sizes": "reads"}


def test_kernel_work_on_hand_counted_sizes():
    # 3 reads of 5 bases: 3 packed bytes + 5 qualities + a 4-byte length a
    # read, 4 operations a base
    assert kernel_work_reads.seq_stats({"records": 3, "read_len": 5}) \
        == (60, 3 * (3 + 5 + 4))
    # the cell's record: 51 + 101 + 4 bytes, whatever strides a program pads
    assert kernel_work_reads.seq_stats({"records": 1, "read_len": 101}) \
        == (404, 156)
    assert set(kernel_work_reads.KERNELS) == {"seq_stats"}


def test_memory_binds_at_the_cells_sizes():
    ops, nbytes = kernel_work_reads.seq_stats({"records": 1 << 22,
                                               "read_len": 101})
    assert nbytes / V5E["hbm_bytes_per_s"] \
        > 50 * ops / V5E["bf16_flops_per_s"]
    assert roofline.bound_seconds(ops, nbytes, V5E) \
        == nbytes / V5E["hbm_bytes_per_s"] \
        == pytest.approx(0.19e-9 * (1 << 22), rel=0.01)


def _obs(seconds_of_ops, records: int = 1 << 22, kind="TPU v5 lite"):
    ops = [(10 + 10**10 * i, 10 + 10**10 * i + round(s * 1e9), name)
           for i, (name, s) in enumerate(seconds_of_ops)]
    return {"reads": {"records": records, "read_len": 101},
            "device_kind": kind, "trace": {"ops": {0: ops}}}


def test_a_synthetic_op_of_exactly_the_bounds_length_reads_100():
    bound = roofline.bound_seconds(*kernel_work_reads.seq_stats(
        {"records": 1 << 22, "read_len": 101}), V5E)
    # the window's kernel time split over three launches; other ops ignored
    obs = _obs([("hbam_seq_stats_kernel", bound / 2),
                ("fusion.3", 1.0),
                ("hbam_seq_stats_kernel.1", bound / 4),
                ("hbam_seq_stats_kernel.2", bound / 4)])
    assert roofline_reads.kernel_share(PARAMS, obs) \
        == pytest.approx(100.0, rel=1e-5)
    # the kernel as the builder's run of PR 28 timed its BAM twin, 7.4 ns
    # a record: 0.19 of 7.4 ns
    obs = _obs([("hbam_seq_stats_kernel", 7.4e-9 * (1 << 22))])
    assert roofline_reads.kernel_share(PARAMS, obs) \
        == pytest.approx(2.57, rel=0.01)


@pytest.mark.parametrize("obs", [
    _obs([("fusion.3", 1.0)]),                              # no such op
    _obs([("hbam_seq_stats_kernel", 1.0)], kind="cpu"),     # no peaks
    {"device_kind": "TPU v5 lite",
     "trace": {"ops": {0: [(0, 10, "hbam_seq_stats_kernel")]}}},  # no sizes
    dict(_obs([("hbam_seq_stats_kernel", 1.0)]), trace=None),
], ids=["no_op", "no_peaks", "no_sizes", "no_trace"])
def test_nothing_to_read_is_none_and_never_raises(obs):
    assert roofline_reads.kernel_share(PARAMS, obs) is None
