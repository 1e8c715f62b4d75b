"""Tests of the roofline reader and of the work it counts (never of the chip).

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

from benchmark import kernel_work  # noqa: E402
from benchmark.reducers import roofline  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "peaks.json"),
          encoding="utf-8") as _fh:
    V5E = json.load(_fh)["devices"]["TPU v5 lite"]


def test_kernel_work_on_hand_counted_sizes():
    # 3 sites of the set, 4 samples: 10 entries of the symmetric half, 3
    # multiply-adds each, 2 operations a multiply-add; 12 int8 in, 16
    # float32 out
    assert kernel_work.grm({"grm_sites": 3, "samples": 4}) == (60, 12 + 64)
    # 5 sites x 4 samples against 1 covariate + 2 traits: 60 multiply-adds;
    # 20 int8 in, 12 float32 of the small operand, 5 float32 out
    assert kernel_work.assoc({"sites": 5, "samples": 4, "covariates": 1,
                              "traits": 2}) == (120, 20 + 48 + 20)
    assert set(kernel_work.KERNELS) == {"grm", "assoc"}


def test_which_bound_binds_at_the_cells_sizes():
    sizes = {"jobs": 1, "sites": 262144, "grm_sites": 54000,
             "samples": 2504, "traits": 256, "covariates": 5}
    for kernel in ("grm", "assoc"):
        ops, nbytes = kernel_work.KERNELS[kernel](sizes)
        assert ops / V5E["bf16_flops_per_s"] \
            > nbytes / V5E["hbm_bytes_per_s"], kernel     # compute binds
    ops, nbytes = kernel_work.assoc(sizes)
    assert roofline.bound_seconds(ops, nbytes, V5E) \
        == pytest.approx(1.74e-3, rel=0.01)
    # few traits: the int8 matrix's bytes bind pass 2 (the ridge is ~115)
    ops, nbytes = kernel_work.assoc(dict(sizes, traits=64))
    assert nbytes / V5E["hbm_bytes_per_s"] > ops / V5E["bf16_flops_per_s"]
    assert roofline.bound_seconds(ops, nbytes, V5E) \
        == nbytes / V5E["hbm_bytes_per_s"]


def _obs(kernel: str, seconds_of_ops, jobs: int = 2, kind="TPU v5 lite"):
    sizes = {"jobs": jobs, "sites": 262144, "grm_sites": 54000,
             "samples": 2504, "traits": 256, "covariates": 5}
    ops = [(10 + 10**10 * i, 10 + 10**10 * i + round(s * 1e9), name)
           for i, (name, s) in enumerate(seconds_of_ops)]
    return {"gwas": sizes, "device_kind": kind,
            "trace": {"ops": {0: ops}}}, sizes


@pytest.mark.parametrize("kernel,prefix", [("grm", "hbam_grm_kernel"),
                                           ("assoc", "hbam_assoc_kernel")])
def test_a_synthetic_op_of_exactly_the_bounds_length_reads_100(kernel,
                                                               prefix):
    params = {"kernel": kernel, "prefix": prefix, "sizes": "gwas"}
    _, sizes = _obs(kernel, [])
    bound = roofline.bound_seconds(*kernel_work.KERNELS[kernel](sizes), V5E)
    # two jobs, the kernel's time split over three events; other ops ignored
    events = [(prefix + ".1", bound), (prefix + ".1", bound / 2),
              (prefix, bound / 2), ("fusion.7", 1.0)]
    obs, _ = _obs(kernel, events)
    assert roofline.kernel_share(params, obs) == pytest.approx(100.0,
                                                               rel=1e-5)
    # twice the time: half the share
    obs, _ = _obs(kernel, [(prefix, 4 * bound)])
    assert roofline.kernel_share(params, obs) == pytest.approx(50.0,
                                                               rel=1e-5)


def test_nothing_to_read_is_none_not_an_error():
    params = {"kernel": "grm", "prefix": "hbam_grm_kernel", "sizes": "gwas"}
    obs, _ = _obs("grm", [("fusion.1", 0.01)])         # no such op
    assert roofline.kernel_share(params, obs) is None
    obs, _ = _obs("grm", [("hbam_grm_kernel", 0.01)], kind="cpu")
    assert roofline.kernel_share(params, obs) is None   # no peaks
    assert roofline.kernel_share(params, {"trace": None}) is None
    assert roofline.kernel_share(params, {}) is None    # a parent's program
